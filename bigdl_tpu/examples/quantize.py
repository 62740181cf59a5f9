"""int8 quantization CLI (reference example/mkldnn int8 conversion +
the whitepaper's quantized-inference recipe, docs/docs/whitepaper.md
179-196: local min/max windows, <0.1% accuracy drop, ~4x model-size
reduction).

    bigdl-tpu-quantize --model trained.bigdl --output quantized.bigdl
    bigdl-tpu-quantize --model trained.bigdl --evaluate <folder>/val

Loads a bigdl-format model, swaps Linear/SpatialConvolution layers for
int8 versions (``Quantizer.quantize``), optionally compares fp32 vs
int8 accuracy on an image folder, reports the parameter-bytes
reduction, and saves the quantized model.
"""

from __future__ import annotations

import argparse
import logging


def _param_bytes(model) -> int:
    import jax
    import numpy as np
    from bigdl_tpu.core.module import partition
    params, rest = partition(model)
    # int8 layers keep their weights in buffers (rest), so count both
    return sum(np.asarray(leaf).nbytes
               for tree in (params, rest)
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "dtype"))


def main(argv=None):
    p = argparse.ArgumentParser(
        description="Quantize a trained model to int8 inference form")
    p.add_argument("--model", required=True, help="bigdl-format model file")
    p.add_argument("--output", default=None,
                   help="where to save the quantized model")
    p.add_argument("--evaluate", default=None, metavar="FOLDER",
                   help="image folder: report fp32 vs int8 accuracy")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("-b", "--batch-size", type=int, default=32)
    p.add_argument("--workers", type=int, default=8,
                   help="decode threads for --evaluate")
    p.add_argument("-q", "--quiet", action="store_true")
    args = p.parse_args(argv)
    logging.basicConfig(
        level=logging.WARNING if args.quiet else logging.INFO)

    from bigdl_tpu.nn.quantized import Quantizer
    from bigdl_tpu.utils.serializer import load_module, save_module

    model = load_module(args.model).eval_mode()
    quantized = Quantizer.quantize(model)
    before, after = _param_bytes(model), _param_bytes(quantized)
    print(f"parameter bytes: {before} -> {after} "
          f"({before / max(after, 1):.2f}x reduction)")

    results = {"bytes_fp32": before, "bytes_int8": after}
    if args.evaluate:
        from bigdl_tpu.examples.imagenet import eval_pipeline
        from bigdl_tpu.examples.loadmodel import check_class_count
        from bigdl_tpu.optim.predictor import Evaluator
        from bigdl_tpu.optim.validation import Top1Accuracy
        data, classes, _ = eval_pipeline(
            args.evaluate, args.image_size, args.batch_size,
            workers=args.workers)
        check_class_count(model, classes, args.image_size)
        for tag, m in (("fp32", model), ("int8", quantized)):
            (res, _meth), = Evaluator(m, args.batch_size).evaluate(
                data, [Top1Accuracy()])
            results[f"top1_{tag}"] = res.result()[0]
            print(f"{tag} Top1Accuracy: {res.result()[0]:.4f}")
    if args.output:
        save_module(quantized, args.output)
        print(f"saved int8 model to {args.output}")
    return results


def cli():
    """Console entry, the owner of its process: turns the compile cache
    on, and discards main()'s return value so the generated script
    exits 0 (sys.exit(<object>) would exit 1)."""
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()


if __name__ == "__main__":
    cli()

"""Model-import validator CLI (reference example/loadmodel: load an
AlexNet/Inception model from Caffe/Torch/BigDL format and validate or
predict with it).

    bigdl-tpu-loadmodel --format bigdl  --model m.bigdl  --predict img.jpg
    bigdl-tpu-loadmodel --format caffe  --prototxt d.prototxt \
        --model w.caffemodel --evaluate <folder>/val
    bigdl-tpu-loadmodel --format torch  --model m.t7 --predict img.jpg

``--evaluate`` expects a class-per-subdirectory image folder and prints
Top-1/Top-5 accuracy; ``--predict`` prints the top-5 (index, score)
pairs per image.  Indices are 1-based like every label in the
framework.
"""

from __future__ import annotations

import argparse
import logging


def load_model(fmt: str, model_path: str, prototxt: str = None):
    """Load a module from any supported interop format."""
    if fmt == "bigdl":
        from bigdl_tpu.utils.serializer import load_module
        return load_module(model_path)
    if fmt == "caffe":
        if not prototxt:
            raise SystemExit("--format caffe requires --prototxt")
        from bigdl_tpu.interop.caffe import load_caffe
        return load_caffe(prototxt, model_path)
    if fmt == "torch":
        from bigdl_tpu.interop.torch_file import load_torch_module
        return load_torch_module(model_path)
    raise SystemExit(f"unknown --format {fmt!r}")


def _prep_images(paths, size):
    """Decode + eval-augment via the single shared _Augment path."""
    import numpy as np
    from bigdl_tpu.examples.imagenet import (_Augment, _decode_rgb,
                                             _short_side)
    aug = _Augment(train=False, size=size)
    ms = _short_side(size)
    return np.stack([aug.apply_one(_decode_rgb(p, ms)) for p in paths])


def check_class_count(model, folder_classes: int, size: int) -> None:
    """Warn when the evaluate folder's class-directory count disagrees
    with the model's output width: labels are assigned by sorted
    directory order, so a subset/superset folder silently renumbers
    classes and scores garbage (see _list_image_folder's docstring)."""
    import numpy as np
    try:
        probe = np.zeros((1, size, size, 3), np.float32)
        width = int(np.asarray(model.forward(probe)).shape[-1])
    except Exception:
        return  # non-image or shape-incompatible model: nothing to check
    if width != folder_classes:
        logging.warning(
            "evaluate folder has %d class directories but the model "
            "outputs %d classes — labels follow sorted directory order, "
            "so accuracy is only meaningful if the folder holds ALL "
            "model classes", folder_classes, width)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="Load a Caffe/Torch/BigDL model; predict or evaluate")
    p.add_argument("--format", required=True,
                   choices=["bigdl", "caffe", "torch"])
    p.add_argument("--model", required=True, help="weights/model file")
    p.add_argument("--prototxt", default=None,
                   help="network definition (caffe format)")
    p.add_argument("--predict", nargs="+", default=None, metavar="IMAGE",
                   help="image files to classify")
    p.add_argument("--evaluate", default=None, metavar="FOLDER",
                   help="class-per-subdirectory folder to score")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("-b", "--batch-size", type=int, default=32)
    p.add_argument("--workers", type=int, default=8,
                   help="decode threads for --evaluate")
    p.add_argument("-q", "--quiet", action="store_true")
    args = p.parse_args(argv)
    logging.basicConfig(
        level=logging.WARNING if args.quiet else logging.INFO)
    if not args.predict and not args.evaluate:
        p.error("provide --predict IMAGE... or --evaluate FOLDER")

    model = load_model(args.format, args.model, args.prototxt)
    model.eval_mode()

    results = {}
    if args.predict:
        import numpy as np
        for start in range(0, len(args.predict), args.batch_size):
            chunk = args.predict[start:start + args.batch_size]
            out = np.asarray(model.forward(
                _prep_images(chunk, args.image_size)))
            if out.ndim == 1:
                out = out[None]
            for path, row in zip(chunk, out):
                top = np.argsort(row)[::-1][:5]
                pairs = [(int(i) + 1, float(row[i])) for i in top]
                results[path] = pairs
                print(path, " ".join(f"{c}:{s:.4f}" for c, s in pairs))
    if args.evaluate:
        from bigdl_tpu.examples.imagenet import eval_pipeline
        from bigdl_tpu.optim.predictor import Evaluator
        from bigdl_tpu.optim.validation import Loss, Top1Accuracy, \
            Top5Accuracy
        import bigdl_tpu.nn as nn
        data, classes, _ = eval_pipeline(
            args.evaluate, args.image_size, args.batch_size,
            workers=args.workers)
        check_class_count(model, classes, args.image_size)
        methods = [Top1Accuracy(), Loss(nn.CrossEntropyCriterion())]
        if classes >= 5:
            methods.insert(1, Top5Accuracy())
        for res, meth in Evaluator(model, args.batch_size).evaluate(
                data, methods):
            results[meth.fmt] = res.result()[0]
            print(f"{meth.fmt}: {res.result()[0]:.4f}")
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

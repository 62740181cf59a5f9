"""MNIST autoencoder training main.

Reference: models/autoencoder/Train.scala (GreyImgToBatch →
``toAutoencoderBatch`` makes the TARGET the input image itself;
MSECriterion; Adagrad lr 0.01, weight decay 5e-4, Trigger.maxEpoch).

    bigdl-tpu-autoencoder -f /data/mnist -b 150 -e 10
    bigdl-tpu-autoencoder --synthetic 1024 -e 3
"""

from __future__ import annotations

import numpy as np

from bigdl_tpu.examples.common import apply_common, base_parser, setup


def to_reconstruction_samples(samples):
    """toAutoencoderBatch semantics: label := the image, flattened and
    min-max squashed to [0,1] so the sigmoid decoder can reach it."""
    from bigdl_tpu.dataset import Sample

    out = []
    for s in samples:
        f = np.asarray(s.feature, np.float32)
        flat = f.reshape(-1)
        lo, hi = float(flat.min()), float(flat.max())
        target = (flat - lo) / max(hi - lo, 1e-6)
        out.append(Sample(f, target))
    return out


def synthetic_split(n: int, batch_size: int):
    """Synthetic train/validation split: draw n + n_val samples in ONE
    generation (synthetic_mnist prototypes depend on both seed and
    count, so train and val must come from the same draw) keeping the
    full requested n — and at least one batch — for training."""
    from bigdl_tpu.dataset.mnist import synthetic_mnist

    n_val = max(n // 10, batch_size)
    samples = synthetic_mnist(n + n_val, seed=0)
    return samples[:n], samples[n:]


def main(argv=None):
    p = base_parser("Train the MNIST autoencoder")
    p.add_argument("--bottleneck", type=int, default=32,
                   help="encoder output width (reference classNum)")
    # the reference recipe's Adagrad lr (models/autoencoder/Train.scala)
    p.set_defaults(learning_rate=0.01)
    args = p.parse_args(argv)
    train_summary, val_summary = setup(args, "autoencoder")

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.mnist import mnist_samples
    from bigdl_tpu.models import Autoencoder
    from bigdl_tpu.optim import Loss, Optimizer, Trigger
    from bigdl_tpu.optim.methods import Adagrad

    if args.synthetic:
        train_s, test_s = synthetic_split(args.synthetic, args.batch_size)
    else:
        train_s = mnist_samples(args.folder, train=True)
        test_s = mnist_samples(args.folder, train=False)
    train = to_reconstruction_samples(train_s)
    test = to_reconstruction_samples(test_s)

    # clamp so a small smoke run still yields at least one full batch
    # (SampleToMiniBatch drops ragged tails)
    batch = min(args.batch_size, len(train))
    data = DataSet.array(train).transform(SampleToMiniBatch(batch))
    if args.cache_device:
        data = data.cache_on_device()
    model = Autoencoder(class_num=args.bottleneck)
    opt = (Optimizer(model, data, nn.MSECriterion())
           .set_optim_method(Adagrad(learning_rate=args.learning_rate,
                                     weight_decay=5e-4))
           .set_end_when(Trigger.max_epoch(args.max_epoch))
           .set_validation(Trigger.every_epoch(), test,
                           [Loss(nn.MSECriterion())],
                           batch_size=min(batch, len(test))))
    apply_common(opt, args, train_summary, val_summary)
    opt.optimize()
    print(f"Final reconstruction loss: {opt.state['loss']:.5f}")
    return model


def cli():
    """Console entry, the owner of its process: turns the compile cache
    on, and discards main()'s return value so the generated script
    exits 0 (sys.exit(<object>) would exit 1)."""
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()


if __name__ == "__main__":
    cli()

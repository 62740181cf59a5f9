"""LeNet-5 / MNIST training main (reference models/lenet/Train.scala:35-105
and the scopt flags in models/lenet/Utils.scala).

    bigdl-tpu-lenet -f /data/mnist -b 128 -e 5 --checkpoint /tmp/ckpt
    bigdl-tpu-lenet --synthetic 2048 -e 2        # no dataset files needed
"""

from __future__ import annotations

from bigdl_tpu.examples.common import apply_common, base_parser, setup


def main(argv=None):
    args = base_parser("Train LeNet-5 on MNIST").parse_args(argv)
    train_summary, val_summary = setup(args, "lenet")

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.mnist import mnist_samples, synthetic_mnist
    from bigdl_tpu.models import LeNet5
    from bigdl_tpu.optim import (
        Loss, Optimizer, SGD, Top1Accuracy, Trigger,
    )

    if args.synthetic:
        # hold out a split of ONE generation: synthetic_mnist's class
        # prototypes are seed-dependent, so a differently-seeded test
        # set would be a different task (validation stuck near chance)
        n_test = max(args.synthetic // 4, args.batch_size)
        samples = synthetic_mnist(args.synthetic + n_test, seed=0)
        train, test = samples[:args.synthetic], samples[args.synthetic:]
    else:
        train = mnist_samples(args.folder, train=True)
        test = mnist_samples(args.folder, train=False)

    data = DataSet.array(train).transform(SampleToMiniBatch(args.batch_size))
    if args.cache_device:
        data = data.cache_on_device()
    model = LeNet5(class_num=10)
    opt = (Optimizer(model, data, nn.ClassNLLCriterion())
           .set_optim_method(SGD(args.learning_rate))
           .set_end_when(Trigger.max_epoch(args.max_epoch))
           .set_validation(Trigger.every_epoch(), test,
                           [Top1Accuracy(), Loss(nn.ClassNLLCriterion())],
                           batch_size=args.batch_size))
    apply_common(opt, args, train_summary, val_summary)
    opt.optimize()
    print(f"Final validation score: {opt.state['score']:.4f}")
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

"""ResNet / CIFAR-10 training main (reference models/resnet/Train.scala
and the parameter table in models/resnet/README.md:63-78).

    bigdl-tpu-resnet-cifar -f /data/cifar10 --depth 20 -b 128 -e 10
    bigdl-tpu-resnet-cifar --synthetic 2048 -e 2
"""

from __future__ import annotations

from bigdl_tpu.examples.common import apply_common, base_parser, setup


def main(argv=None):
    p = base_parser("Train ResNet on CIFAR-10")
    p.add_argument("--depth", type=int, default=20,
                   help="6n+2 CIFAR ResNet depth (20/32/44/56/110)")
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.set_defaults(learning_rate=0.1)
    args = p.parse_args(argv)
    train_summary, val_summary = setup(args, "resnet-cifar")

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.cifar import cifar10_samples, synthetic_cifar10
    from bigdl_tpu.models import resnet_cifar
    from bigdl_tpu.optim import (
        Loss, MultiStep, Optimizer, SGD, Top1Accuracy, Trigger,
    )

    if args.synthetic:
        train, test = (synthetic_cifar10(args.synthetic, seed=0),
                       synthetic_cifar10(max(args.synthetic // 4, args.batch_size),
                                         seed=1))
    else:
        train = cifar10_samples(args.folder, train=True)
        test = cifar10_samples(args.folder, train=False)

    data = DataSet.array(train).transform(SampleToMiniBatch(args.batch_size))
    if args.cache_device:
        data = data.cache_on_device()
    model = resnet_cifar(depth=args.depth, class_num=10)
    # reference recipe: SGD momentum 0.9, lr/10 at epochs 80 and 120
    iters_per_epoch = max(len(train) // args.batch_size, 1)
    method = SGD(args.learning_rate, momentum=args.momentum, dampening=0.0,
                 weight_decay=args.weight_decay,
                 learning_rate_schedule=MultiStep(
                     [80 * iters_per_epoch, 120 * iters_per_epoch], 0.1))
    opt = (Optimizer(model, data, nn.CrossEntropyCriterion())
           .set_optim_method(method)
           .set_end_when(Trigger.max_epoch(args.max_epoch))
           .set_validation(Trigger.every_epoch(), test,
                           [Top1Accuracy(),
                            Loss(nn.CrossEntropyCriterion())],
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

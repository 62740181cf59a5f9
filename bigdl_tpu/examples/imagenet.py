"""ImageNet-style training main for the large vision models
(reference models/resnet/TrainImageNet.scala + models/inception/
Train.scala; README recipe at models/resnet/README.md:85-150).

    bigdl-tpu-imagenet -f /data/imagenet --model resnet50 -b 256 --bf16
    bigdl-tpu-imagenet --synthetic 512 --model inception-v1 -e 1

Data layout: ``<folder>/train/<class>/*.jpg`` and
``<folder>/val/<class>/*.jpg`` (class-per-subdirectory).  The input
pipeline is the reference's: aspect-preserving short-side-256 scale →
random-crop-224 + HFlip + channel-normalize for training,
center-crop-224 for validation — all host-side so the jitted step gets
ready NHWC arrays.
"""

from __future__ import annotations

import os

from bigdl_tpu.examples.common import apply_common, base_parser, setup

# ImageNet RGB mean/std on the [0, 255] scale (reference
# models/resnet/ImageNet dataset constants)
MEAN = (123.68, 116.779, 103.939)
STD = (58.395, 57.12, 57.375)


MODELS = {"resnet50": "resnet50",
          "inception-v1": "Inception_v1",
          "inception-v2": "Inception_v2",
          "vgg16": "Vgg_16"}


def _build_model(name: str, class_num: int):
    from bigdl_tpu import models
    return getattr(models, MODELS[name])(class_num)


def _short_side(size: int) -> int:
    """Short-side resize target for a given crop size (256 for 224
    crops, scaled proportionally) — shared by the augment recipe AND
    the native decoder's minimum decode size so they cannot drift."""
    return max(size * 256 // 224, size)


class _Augment:
    """Sample-level wrapper over the vision FeatureTransformers:
    aspect-preserving short-side scale (256 for 224-px crops, scaled
    with the crop size) followed by random/center crop."""

    def __init__(self, train: bool, size: int = 224):
        from bigdl_tpu.transform.vision import (
            AspectScale, CenterCrop, ChannelNormalize, HFlip, RandomCrop,
            RandomTransformer,
        )
        # short-side resize preserving aspect ratio, then crop — the
        # standard recipe (reference RandomAlterAspect/RandomCropper for
        # train, Resize(short=256)+CenterCrop(224) for eval); a square
        # Resize(r, r) would distort non-square images.  The long side
        # is uncapped: a max_size cap could shrink the short side below
        # the crop and crash batching on extreme panoramas.
        r = _short_side(size)
        scale = AspectScale(r, max_size=None)
        if train:
            self.stages = [scale, RandomCrop(size, size),
                           RandomTransformer(HFlip(), 0.5),
                           ChannelNormalize(*MEAN, *STD)]
        else:
            self.stages = [scale, CenterCrop(size, size),
                           ChannelNormalize(*MEAN, *STD)]

    def apply_one(self, image):
        """HWC array → augmented HWC array (single copy of the stage
        loop, shared by the sequential and ParallelMap paths)."""
        from bigdl_tpu.transform.vision import ImageFeature
        feat = ImageFeature(image)
        for t in self.stages:
            feat = t(feat)
        return feat.image

    def __call__(self, it):
        from bigdl_tpu.dataset.dataset import Sample
        for s in it:
            yield Sample(self.apply_one(s.feature), s.label)


IMAGE_EXTS = (".jpg", ".jpeg", ".png", ".bmp", ".gif", ".webp", ".ppm")


def _list_image_folder(path: str, class_to_label=None):
    """Lazy ImageNet listing: (file path, 1-based label) pairs — images
    decode inside the pipeline, never all-at-once in host RAM.  Only
    image-extension files are listed (a stray README/.DS_Store must not
    abort a run mid-epoch).  Pass the training split's ``class_to_label``
    for the val split so labels share one mapping even when a class is
    missing from val."""
    classes = sorted(d for d in os.listdir(path)
                     if os.path.isdir(os.path.join(path, d)))
    if class_to_label is None:
        class_to_label = {cls: ci + 1 for ci, cls in enumerate(classes)}
    items = []
    for cls in classes:
        if cls not in class_to_label:
            raise SystemExit(
                f"class directory {cls!r} in {path} has no corresponding "
                f"training class (train classes: {sorted(class_to_label)})")
        cdir = os.path.join(path, cls)
        items.extend((os.path.join(cdir, fn), class_to_label[cls])
                     for fn in sorted(os.listdir(cdir))
                     if fn.lower().endswith(IMAGE_EXTS))
    return items, len(class_to_label), class_to_label


def _decode_rgb(path, min_short: int = 0):
    """path → HWC float32 RGB array (single decode expression shared by
    every pipeline so color handling cannot diverge).

    JPEGs go through the native libjpeg decoder when it built
    (bigdl_tpu.native.jpeg_decode_scaled): with ``min_short`` > 0 it
    DCT-downscales during decode so a 4000px photo headed for a 256px
    short side never materializes at full resolution — the AspectScale
    stage downstream then only closes the last <=2x gap.  Everything
    else (PNG/BMP/..., no native lib, corrupt data) falls back to PIL."""
    import numpy as np
    if path.lower().endswith((".jpg", ".jpeg")):
        from bigdl_tpu.native import jpeg_available, jpeg_decode_scaled
        arr = None
        if jpeg_available():   # cached; don't double-read on PIL hosts
            try:
                with open(path, "rb") as f:
                    data = f.read()
                arr = jpeg_decode_scaled(data, min_short)
            except OSError:
                arr = None
        if arr is not None:
            return arr.astype(np.float32)
    from PIL import Image
    return np.asarray(Image.open(path).convert("RGB"), np.float32)


class _DecodeAugment:
    """Per-item decode + augment for ParallelMap: PIL decode and numpy
    resampling release the GIL, so worker threads genuinely overlap
    (≙ the reference's MTImageFeatureToBatch per-thread pipelines).

    Each worker thread gets its OWN _Augment: RandomCrop and
    RandomTransformer hold legacy np.random.RandomState instances,
    which are not thread-safe — sharing one across workers could
    corrupt the Mersenne state or correlate the augmentation streams.
    Fresh RandomState() instances seed from OS entropy, so per-thread
    streams are independent."""

    def __init__(self, train: bool, size: int):
        import threading
        self._train, self._size = train, size
        # the augment's short-side target: decode no smaller than this
        self._min_short = _short_side(size)
        self._local = threading.local()

    def _aug(self) -> _Augment:
        aug = getattr(self._local, "aug", None)
        if aug is None:
            aug = self._local.aug = _Augment(train=self._train,
                                             size=self._size)
        return aug

    def __call__(self, item):
        from bigdl_tpu.dataset.dataset import Sample
        path, label = item
        return Sample(
            self._aug().apply_one(_decode_rgb(path, self._min_short)),
            label)


def train_pipeline(folder: str, size: int, batch_size: int,
                   workers: int = 8):
    """Class-per-subdirectory folder → (DataSet, n_classes, class_map)
    through the threaded TRAIN augment path (random crop/flip) +
    double-buffered prefetch — the pipeline the training main builds."""
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.prefetch import ParallelMap, Prefetch
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch
    items, classes, cmap = _list_image_folder(folder)
    data = (DataSet.array(items)
            .transform(ParallelMap(_DecodeAugment(train=True, size=size),
                                   workers=workers))
            .transform(SampleToMiniBatch(batch_size))
            .transform(Prefetch(2)))
    return data, classes, cmap


def eval_pipeline(folder: str, size: int, batch_size: int,
                  workers: int = 8, class_map=None):
    """Class-per-subdirectory folder → (DataSet, n_classes, class_map)
    through the threaded eval augment path — the one evaluation pipeline
    shared by the imagenet, loadmodel, and quantize CLIs."""
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.prefetch import ParallelMap
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch
    items, classes, cmap = _list_image_folder(folder, class_map)
    data = (DataSet.array(items, shuffle=False)
            .transform(ParallelMap(_DecodeAugment(train=False, size=size),
                                   workers=workers))
            .transform(SampleToMiniBatch(batch_size)))
    return data, classes, cmap


def _synthetic(n: int, size: int, classes: int, seed: int):
    """Per-class prototypes generated lazily from the label's own seed,
    so the full --classes head is honored without a classes-sized
    prototype tensor in RAM."""
    import numpy as np
    from bigdl_tpu.dataset.dataset import Sample
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, classes, size=n)
    out = []
    for l in labels:
        proto = np.random.default_rng(10_000 + int(l)).normal(
            size=(size, size, 3))
        out.append(Sample((proto + 0.25 * rng.normal(
            size=(size, size, 3))).astype(np.float32), int(l) + 1))
    return out, classes


def main(argv=None):
    p = base_parser("Train ResNet-50 / Inception-v1 / VGG16 on ImageNet")
    p.add_argument("--model", default="resnet50", choices=sorted(MODELS))
    p.add_argument("--classes", type=int, default=1000)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--warmup-epochs", type=int, default=0)
    p.add_argument("--workers", type=int, default=8,
                   help="decode/augment threads (folder input)")
    p.set_defaults(batch_size=256, learning_rate=0.1, max_epoch=90)
    args = p.parse_args(argv)
    train_summary, val_summary = setup(args, f"imagenet-{args.model}")

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.optim import (
        Loss, Optimizer, Poly, SGD, SequentialSchedule, Top1Accuracy,
        Top5Accuracy, Trigger, Warmup,
    )

    size = args.image_size
    val_data = None
    if args.synthetic:
        classes = args.classes
        train, _ = _synthetic(args.synthetic, size, classes, seed=0)
        val, _ = _synthetic(max(args.synthetic // 8, args.batch_size),
                            size, classes, seed=1)
        n_train = len(train)
        train_data = (DataSet.array(train)
                      .transform(SampleToMiniBatch(args.batch_size)))
        if args.cache_device:
            train_data = train_data.cache_on_device()
        val_data = (DataSet.array(val, shuffle=False)
                    .transform(SampleToMiniBatch(args.batch_size)))
    else:
        if args.cache_device:
            raise SystemExit(
                "--cache-device would freeze the random crops/flips of "
                "epoch 1 and replay them forever; it is only valid with "
                "--synthetic data")
        from bigdl_tpu.dataset.prefetch import Prefetch
        train_data, classes, class_map = train_pipeline(
            os.path.join(args.folder, "train"), size, args.batch_size,
            workers=args.workers)
        n_train = train_data.size()
        val_dir = os.path.join(args.folder, "val")
        if os.path.isdir(val_dir):
            val_data, _, _ = eval_pipeline(
                val_dir, size, args.batch_size, workers=args.workers,
                class_map=class_map)
            val_data = val_data.transform(Prefetch(2))

    model = _build_model(args.model, classes)
    iters_per_epoch = max(n_train // args.batch_size, 1)
    total_iters = args.max_epoch * iters_per_epoch
    base_lr = args.learning_rate
    if args.warmup_epochs > 0:
        # Linear ramp from a small starting lr up to the requested
        # --learning-rate (the peak), then Poly decay from the peak over
        # the remaining budget — the reference's large-batch recipe
        # (models/resnet/TrainImageNet.scala warmup: delta =
        # (maxLr - lr) / warmupIters inside SGD.SequentialSchedule).
        # SequentialSchedule hands each stage's final lr to the next
        # stage, so Poly decays exactly from the peak.
        warm_iters = args.warmup_epochs * iters_per_epoch
        if warm_iters >= total_iters:
            p.error(f"--warmup-epochs ({args.warmup_epochs}) must be "
                    f"smaller than --max-epoch ({args.max_epoch})")
        start_lr = args.learning_rate / warm_iters
        base_lr = start_lr
        schedule = (SequentialSchedule(iters_per_epoch)
                    .add(Warmup((args.learning_rate - start_lr)
                                / warm_iters), warm_iters)
                    .add(Poly(0.5, total_iters - warm_iters),
                         total_iters - warm_iters))
    else:
        schedule = Poly(0.5, total_iters)
    method = SGD(base_lr, momentum=args.momentum,
                 dampening=0.0, weight_decay=args.weight_decay,
                 nesterov=True, learning_rate_schedule=schedule)
    opt = (Optimizer(model, train_data, nn.CrossEntropyCriterion())
           .set_optim_method(method)
           .set_end_when(Trigger.max_epoch(args.max_epoch)))
    if val_data is not None:
        methods = [Top1Accuracy(), Loss(nn.CrossEntropyCriterion())]
        if classes >= 5:
            methods.insert(1, Top5Accuracy())
        opt.set_validation(Trigger.every_epoch(), val_data, methods)
    apply_common(opt, args, train_summary, val_summary)
    opt.optimize()
    if val_data is not None:
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

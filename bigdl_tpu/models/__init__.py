from bigdl_tpu.models.lenet import LeNet5, lenet5_graph
from bigdl_tpu.models.resnet import (
    ResNet, resnet_cifar, resnet50, BasicBlock, Bottleneck,
)
from bigdl_tpu.models.inception import Inception_v1, Inception_v2
from bigdl_tpu.models.vgg import VggForCifar10, Vgg_16, Vgg_19
from bigdl_tpu.models.rnn_lm import PTBModel, SimpleRNN
from bigdl_tpu.models.autoencoder import Autoencoder, autoencoder
from bigdl_tpu.models.maskrcnn import (
    MaskRCNN, MaskRCNNParams, ResNetFPNBackbone,
)
from bigdl_tpu.models.ssd import SSDVGG16, ssd_vgg16_300
from bigdl_tpu.models.transformer_lm import TransformerLM, transformer_lm
from bigdl_tpu.models.hybrid_decoder import (
    HybridDecoder, afmoe, falcon_h1, lfm2_moe, mimo_v2, phi4_flash,
    sarvam_mla,
)
from bigdl_tpu.models.ncf import NeuralCF
from bigdl_tpu.models.dlrm import WideAndDeep, wide_and_deep

# ---------------------------------------------------------------------------
# Zoo registry: name → builder, for CLI entry points (serving demo, tools)
# that take a model by name.  Only models constructible with no required
# arguments are listed; kwargs pass through to the builder.
# ---------------------------------------------------------------------------

def _transformer_lm_tiny(**kwargs):
    """Small decoder-only LM for the serving demos: big enough to show
    continuous batching winning, small enough to compile in seconds on
    the CPU backend."""
    cfg = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
               filter_size=128, max_len=128)
    cfg.update(kwargs)
    return transformer_lm(**cfg)


_ZOO = {
    "lenet5": LeNet5,
    "lenet5_graph": lenet5_graph,
    "autoencoder": autoencoder,
    "resnet_cifar": resnet_cifar,
    "vgg_cifar10": VggForCifar10,
    "transformer_lm_tiny": _transformer_lm_tiny,
    "wide_and_deep": wide_and_deep,
}

# per-sample (unbatched) input shape each zoo model expects, used by the
# serving CLI to parse stdin rows and warm up bucket shapes
_ZOO_SAMPLE_SHAPES = {
    "lenet5": (784,),
    "lenet5_graph": (784,),
    "autoencoder": (784,),
    "resnet_cifar": (32, 32, 3),
    "vgg_cifar10": (32, 32, 3),
    # (user, item) 1-based id pair — the scoring row RecommenderScorer
    # ships as the router "prompt"
    "wide_and_deep": (2,),
}


def zoo(name: str, **kwargs):
    """Build a zoo model by name (e.g. ``zoo('lenet5', class_num=10)``)."""
    try:
        builder = _ZOO[name]
    except KeyError:
        raise ValueError(
            f"unknown zoo model {name!r}; available: {sorted(_ZOO)}") \
            from None
    return builder(**kwargs)


def zoo_sample_shape(name: str):
    """Per-sample input shape for a zoo model (serving CLI contract)."""
    if name not in _ZOO_SAMPLE_SHAPES:
        raise ValueError(f"no registered sample shape for {name!r}; "
                         f"available: {sorted(_ZOO_SAMPLE_SHAPES)}")
    return _ZOO_SAMPLE_SHAPES[name]

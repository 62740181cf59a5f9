"""Configuration kind ``resnet``: bottleneck ResNet trained through
``Optimizer.optimize()``."""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from harness import flops, weights

REFERENCE = "resnet50"
RESNET_STAGES = flops.RESNET_STAGES


def param_spec(cfg: Dict[str, Any]) -> List[Tuple[str, Tuple[int, ...]]]:
    """Leaves of ``bigdl_tpu.models.resnet.ResNet(Bottleneck, layers)``
    in flattening order, from the configuration's sizes."""
    def bn(prefix, c):
        return [(f"{prefix}.weight", (c,)), (f"{prefix}.bias", (c,)),
                (f"{prefix}.running_mean", (c,)), (f"{prefix}.running_var", (c,))]
    spec = [(".stem_conv.weight", (7, 7, 3, 64))] + bn(".stem_bn", 64)
    nin, b = 64, 0
    for (planes, stride), n in zip(RESNET_STAGES, cfg["layers"]):
        for i in range(n):
            s = stride if i == 0 else 1
            p = f".blocks[{b}]"
            spec += [(p + ".conv1.weight", (1, 1, nin, planes))] + bn(p + ".bn1", planes)
            spec += [(p + ".conv2.weight", (3, 3, planes, planes))] + bn(p + ".bn2", planes)
            spec += [(p + ".conv3.weight", (1, 1, planes, planes * 4))] + bn(p + ".bn3", planes * 4)
            if s != 1 or nin != planes * 4:
                spec += [(p + ".down_conv.weight", (1, 1, nin, planes * 4))] \
                    + bn(p + ".down_bn", planes * 4)
            nin = planes * 4
            b += 1
    spec += [(".head.weight", (cfg["num_classes"], nin)),
             (".head.bias", (cfg["num_classes"],))]
    return spec


def build_train(cfg: Dict[str, Any], job: Dict[str, Any], seed: int,
                devices) -> Dict[str, Any]:
    """The program's model with seeded weights, its criterion and one
    seeded batch on the device."""
    import jax
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.resnet import Bottleneck, ResNet

    abstract = jax.eval_shape(
        lambda: ResNet(Bottleneck, list(cfg["layers"]), cfg["num_classes"]))
    weights.reset_program_rng(seed)
    spec = param_spec(cfg)
    weights.check_spec(spec, abstract)
    leaves = weights.make(spec, seed, jnp.float32)
    model = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract), leaves)
    x, y = batch(cfg, job, seed)
    return {"model": model, "criterion": nn.CrossEntropyCriterion(),
            "x": x, "y": y, "spec": spec,
            "flops_per_step": flops.resnet_train_flops_per_step(cfg, job["batch"]),
            "samples_per_step": job["batch"]}


def batch(cfg: Dict[str, Any], job: Dict[str, Any], seed: int):
    """One batch of images (standard normal, NHWC float32) and 1-based
    labels, made on the device."""
    import jax
    import jax.numpy as jnp
    b, s = job["batch"], cfg["image_size"]

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        return (jax.random.normal(kx, (b, s, s, 3), jnp.float32),
                jax.random.randint(ky, (b,), 1, cfg["num_classes"] + 1))
    return make(weights.seed_key(seed, 2))


train_param_spec = param_spec


def reference_train(ref, params, x, y, cfg, job, steps, precision):
    """Losses of the first ``steps`` steps, and the trained leaves
    after them, by name."""
    return ref.train(params, x, y, cfg["layers"], job["optimizer"],
                     steps, precision)


def program_state_names(spec):
    """The leaves the program trains: buffers left out."""
    return [p for p, _ in spec
            if not p.endswith(("running_mean", "running_var"))]

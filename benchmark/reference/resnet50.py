"""Bottleneck ResNet (He et al. 2015, arXiv:1512.03385, Table 1) in
plain float32: forward, softmax cross-entropy, gradients, SGD with
momentum.  NHWC images, HWIO kernels; batch normalisation uses the
batch's own mean and biased variance (training mode), eps 1e-5.  The
stride of a down-sampling bottleneck sits on its 3x3 convolution."""
from __future__ import annotations

from typing import Any, Dict, Sequence

from reference.precision import quantizer

STAGES = ((64, 1), (128, 2), (256, 2), (512, 2))
EPS = 1e-5


def forward(params: Dict[str, Any], x, layers: Sequence[int], q):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def conv(x, w, stride, pad):
        return jax.lax.conv_general_dilated(
            q(x), q(w), (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)

    def bn(x, prefix):
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
        return ((x - mean) * jax.lax.rsqrt(var + EPS)
                * params[prefix + ".weight"] + params[prefix + ".bias"])

    def block(x, p, stride, has_down):
        y = jax.nn.relu(bn(conv(x, params[p + ".conv1.weight"], 1, 0), p + ".bn1"))
        y = jax.nn.relu(bn(conv(y, params[p + ".conv2.weight"], stride, 1), p + ".bn2"))
        y = bn(conv(y, params[p + ".conv3.weight"], 1, 0), p + ".bn3")
        sc = x
        if has_down:
            sc = bn(conv(x, params[p + ".down_conv.weight"], stride, 0),
                    p + ".down_bn")
        return jax.nn.relu(y + sc)

    y = jax.nn.relu(bn(conv(x, params[".stem_conv.weight"], 2, 3), ".stem_bn"))
    y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    nin, b = 64, 0
    for (planes, stride), n in zip(STAGES, layers):
        for i in range(n):
            s = stride if i == 0 else 1
            has_down = s != 1 or nin != planes * 4
            # recomputed in the backward pass, so that a float32 batch
            # at the benchmark's size fits beside nothing else
            y = jax.checkpoint(
                lambda y_, p=f".blocks[{b}]", s=s, d=has_down: block(y_, p, s, d))(y)
            nin = planes * 4
            b += 1
    y = jnp.mean(y, axis=(1, 2))
    return jnp.matmul(q(y), q(params[".head.weight"]).T, precision=hi) \
        + params[".head.bias"]


def loss_fn(params, x, y, layers, q):
    import jax
    import jax.numpy as jnp
    logits = forward(params, x, layers, q)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, (y - 1)[:, None], axis=1)[:, 0]
    return -jnp.mean(picked)


def train(params: Dict[str, Any], x, y, layers: Sequence[int],
          optimizer: Dict[str, Any], steps: int, precision: str = "float32"):
    """``steps`` steps of SGD with momentum (``v = m v + g``,
    ``p -= lr v``) on the one batch ``(x, y)``: the loss of each step,
    and the trained leaves after the last.  Running
    statistics are buffers, not trained, and do not enter a
    training-mode loss: they are left out."""
    import jax
    q = quantizer(precision)
    trainable = {k: v for k, v in params.items()
                 if not k.endswith(("running_mean", "running_var"))}
    lr, mom = optimizer["lr"], optimizer["momentum"]

    @jax.jit
    def step(p, v, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y, tuple(layers), q)
        v = jax.tree_util.tree_map(lambda v_, g_: mom * v_ + g_, v, g)
        p = jax.tree_util.tree_map(lambda p_, v_: p_ - lr * v_, p, v)
        return p, v, loss

    vel = jax.tree_util.tree_map(lambda a: a * 0.0, trainable)
    losses = []
    for _ in range(steps):
        trainable, vel, loss = step(trainable, vel, x, y)
        losses.append(float(loss))
    return losses, trainable

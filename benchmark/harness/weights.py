"""Weights and data from ``--seed``, made on the device in one call.

A specification is an ordered list of ``(path, shape)``; what a leaf is
initialised to follows from its path and rank.  The distributions are
the benchmark's own, chosen so that the loss at seeded weights is
sensitive to the arithmetic (logits of order one, no zero-initialised
residual branch, but one that starts small enough for rounding errors to
stay small beside the gradient): a check against the reference then
tells one precision from the next.
"""
from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Tuple

Spec = Sequence[Tuple[str, Tuple[int, ...]]]


def seed_key(seed: int, stream: int = 0):
    """A key from any whole-number seed (the driver's exceed 2**31)."""
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def reset_program_rng(seed: int) -> None:
    """Building the program's model under ``jax.eval_shape`` (shapes
    only, nothing allocated) leaves a tracer in the program's global key
    stream; reseed it so that later draws get a real key."""
    from bigdl_tpu.utils import set_seed
    set_seed(int(seed) & 0x7FFFFFFF)


def _leaf(key, path: str, shape: Tuple[int, ...]):
    import jax
    import jax.numpy as jnp
    name = path.rsplit(".", 1)[-1]
    if name == "running_mean":
        return jnp.zeros(shape, jnp.float32)
    if name == "running_var":
        return jnp.ones(shape, jnp.float32)
    if len(shape) == 1:
        noise = jax.random.normal(key, shape, jnp.float32)
        if name == "bias":
            return 0.02 * noise
        # a norm's gain.  The last norm of a residual branch starts at a
        # tenth: at one, fifty layers amplify any rounding until the
        # gradient shares nothing with the reference's, in bfloat16 and
        # int8 alike (PERF.md, Findings of PR 24), and no comparison can
        # tell the two apart
        gain = 0.1 if name == "weight" and ".bn3." in path else 1.0
        return gain * (1.0 + 0.1 * noise)
    if len(shape) == 4:                                # HWIO convolution
        fan_in = shape[0] * shape[1] * shape[2]
        return math.sqrt(2.0 / fan_in) * jax.random.normal(
            key, shape, jnp.float32)
    if len(shape) == 2:
        fan_in = shape[1]
        if path.endswith("embedding.weight"):
            # the tied head scores a token against the residual stream,
            # which starts as that token's own embedding times sqrt(H):
            # at unit scale every position would predict its own input by
            # a wide margin, whatever the precision.  At 0.3 the blocks'
            # outputs dominate and the best token depends on the context.
            return 0.3 * fan_in ** -0.5 * jax.random.normal(
                key, shape, jnp.float32)
        return fan_in ** -0.5 * jax.random.normal(key, shape, jnp.float32)
    raise ValueError(f"no initialisation rule for {path} of shape {shape}")


def make(spec: Spec, seed: int, dtype, shardings: Optional[List[Any]] = None):
    """All leaves of ``spec`` from ``seed``, as ``dtype``, in one jitted
    call.  Buffers of running statistics stay float32."""
    import jax
    import jax.numpy as jnp

    def build(key):
        out = []
        for i, (path, shape) in enumerate(spec):
            leaf = _leaf(jax.random.fold_in(key, i), path, tuple(shape))
            name = path.rsplit(".", 1)[-1]
            if name not in ("running_mean", "running_var"):
                leaf = leaf.astype(dtype)
            out.append(leaf)
        return out

    fn = jax.jit(build, out_shardings=shardings) if shardings is not None \
        else jax.jit(build)
    return fn(seed_key(seed, 1))


def row_shardings(spec: Spec, devices: Sequence[Any]) -> List[Any]:
    """Spread each leaf's first axis over ``devices`` where it divides,
    so that no one device has to hold all of a sharded job's weights."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devices), ("w",))
    n = len(devices)
    return [NamedSharding(mesh, P("w") if shape and shape[0] % n == 0
                          and len(shape) > 1 else P())
            for _, shape in spec]


def check_spec(spec: Spec, abstract_model) -> None:
    """The program's model must have exactly the leaves the
    configuration implies, in the same order."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(abstract_model)[0]
    theirs = [(jax.tree_util.keystr(p), tuple(l.shape)) for p, l in flat]
    ours = [(p, tuple(s)) for p, s in spec]
    if theirs != ours:
        diff = [(a, b) for a, b in zip(theirs, ours) if a != b][:3]
        raise RuntimeError(
            f"the program's model has {len(theirs)} leaves, the "
            f"configuration implies {len(ours)}; first differences: {diff}")

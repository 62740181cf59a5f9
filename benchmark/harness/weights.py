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
from typing import Any, Dict, List, Optional, Sequence, Tuple

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


def _rule(path: str) -> str:
    """What of a leaf's path its initialisation depends on: two leaves
    with the same rule and shape are made by the same program."""
    name = path.rsplit(".", 1)[-1]
    if name in ("running_mean", "running_var", "bias"):
        return name
    if name == "weight" and ".bn3." in path:
        return "last_norm_gain"
    return "embedding" if path.endswith("embedding.weight") else "weight"


def _leaf(key, rule: str, shape: Tuple[int, ...]):
    import jax
    import jax.numpy as jnp
    if rule == "running_mean":
        return jnp.zeros(shape, jnp.float32)
    if rule == "running_var":
        return jnp.ones(shape, jnp.float32)
    noise = jax.random.normal(key, shape, jnp.float32)
    if len(shape) == 1:
        if rule == "bias":
            return 0.02 * noise
        # a norm's gain.  The last norm of a residual branch starts at a
        # tenth: at one, fifty layers amplify any rounding until the
        # gradient shares nothing with the reference's, in bfloat16 and
        # int8 alike (PERF.md, Findings of PR 24), and no comparison can
        # tell the two apart
        gain = 0.1 if rule == "last_norm_gain" else 1.0
        return gain * (1.0 + 0.1 * noise)
    if len(shape) == 4:                                # HWIO convolution
        fan_in = shape[0] * shape[1] * shape[2]
        return math.sqrt(2.0 / fan_in) * noise
    if len(shape) in (2, 3):    # [out, in], or a stack of them [E, out, in]
        fan_in = shape[-1]
        if rule == "embedding":
            # the tied head scores a token against the residual stream,
            # which starts as that token's own embedding times sqrt(H):
            # at unit scale every position would predict its own input by
            # a wide margin, whatever the precision.  At 0.3 the blocks'
            # outputs dominate and the best token depends on the context.
            return 0.3 * fan_in ** -0.5 * noise
        return fan_in ** -0.5 * noise
    raise ValueError(f"no initialisation rule for a {rule} of shape {shape}")


_BUILDERS: Dict[Any, Any] = {}


def make(spec: Spec, seed: int, dtype, shardings: Optional[List[Any]] = None,
         only: Optional[Sequence[int]] = None):
    """Leaves of ``spec`` from ``seed``, as ``dtype``, in one jitted call:
    all of them, or those whose indices ``only`` names (``shardings``
    then go with those).  A leaf's key is folded from its index in the
    whole ``spec``, so a leaf made alone is bit for bit the leaf of the
    one call; the indices are arguments of the program, so blocks of the
    same shapes (a model's layers) share one.  Buffers of running
    statistics stay float32."""
    import jax
    import jax.numpy as jnp
    idx = list(range(len(spec))) if only is None else [int(i) for i in only]
    leaves = tuple((_rule(spec[i][0]), tuple(spec[i][1])) for i in idx)
    sig = (leaves, jnp.dtype(dtype).name,
           None if shardings is None else tuple(shardings))
    if sig not in _BUILDERS:
        def build(key, indices):
            out = []
            for n, (rule, shape) in enumerate(leaves):
                leaf = _leaf(jax.random.fold_in(key, indices[n]), rule, shape)
                if rule not in ("running_mean", "running_var"):
                    leaf = leaf.astype(dtype)
                out.append(leaf)
            return out
        _BUILDERS[sig] = (jax.jit(build, out_shardings=shardings)
                          if shardings is not None else jax.jit(build))
    return _BUILDERS[sig](seed_key(seed, 1), jnp.asarray(idx, jnp.uint32))


def blocks_float32(spec: Spec, blocks, seed: int, dtype):
    """One block of a model at a time, as the check walks it: yields
    ``(name, {path: leaf})`` for each ``(name, indices)`` of ``blocks``,
    the leaves made in ``dtype`` (what is served) and cast to float32 one
    by one, each freed as it is cast (waiting for each cast: the host runs
    ahead of the device, and a leaf it has let go lives until its cast has
    run).  The peak is one block in ``dtype`` while it is made, then that
    block in float32 and its largest leaf in ``dtype``, never the model;
    whoever iterates drops a block before asking for the next."""
    import jax.numpy as jnp
    for name, idx in blocks:
        leaves = make(spec, seed, dtype, only=idx)
        params = {}
        for i in reversed(idx):
            params[spec[i][0]] = leaves.pop().astype(
                jnp.float32).block_until_ready()
        yield name, params
        del params


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

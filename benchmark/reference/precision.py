"""The arithmetic a reference computes in.

``float32`` is the reference proper.  The lower precisions are the
*controls*: the same mathematics with the inputs of every matrix product
rounded the way a lower-precision path would round them, used to show
that the comparison deciding ``correct`` fails when it should.
"""
from __future__ import annotations


def quantizer(precision: str):
    """A function that rounds a matmul operand to ``precision`` and
    returns it as float32."""
    import jax.numpy as jnp
    if precision == "float32":
        return lambda a: a
    if precision == "bfloat16":
        return lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    import jax

    def both_ways(rounded):
        """Round the operand on the way forward and its gradient on the
        way back, as a training path in that precision rounds both."""
        @jax.custom_vjp
        def q(a):
            return rounded(a)
        q.defvjp(lambda a: (rounded(a), None), lambda _, g: (rounded(g),))
        return q

    if precision == "float8":
        # per-tensor scaled e4m3, the usual fp8 recipe: 3 bits of mantissa
        def q(a):
            scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
            return (a / scale).astype(jnp.float8_e4m3fn).astype(
                jnp.float32) * scale
        return both_ways(q)
    if precision == "int8":
        # per-tensor symmetric int8, the repo's own quantised path's grid
        def q8(a):
            scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 127.0
            return jnp.clip(jnp.rint(a / scale), -127, 127) * scale
        return both_ways(q8)
    raise ValueError(f"unknown precision {precision!r}")

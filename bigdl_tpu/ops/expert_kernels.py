"""The products of a layer of gated experts over rows laid out by expert.

A serving pool's pass sends each held expert a few tens of rows: one
batched product over every held stack multiplies every row by every
expert (sixteen times the wanted work at 64 held and 4 a token), and
XLA's ``ragged_dot`` has a floor of milliseconds at 64 groups.  Here the
rows come **in tiles of ``ROW_TILE``, each tile inside one expert** (the
caller pads an expert's rows to whole tiles), and two Pallas TPU programs
do an expert layer's three products:

* :func:`gate_up` — ``silu(rows W_gate) * (rows W_up)``, both stacks'
  blocks fetched side by side, the activation written in the experts'
  dtype;
* :func:`down` — that activation times ``W_down``, float32 out.

Both have a grid (column block, row tile) with the tile's expert as
scalar prefetch: a tile's rows ``[ROW_TILE, in]`` meet its expert's block
``[in, columns]``, whole in ``in``.  The row tiles are the inner axis, so
while consecutive tiles stay in one expert the block is not fetched again:
**each chosen expert's stack is read once a call**, an expert nobody
chose is never read, and the cost follows the tiles.  Tiles past
``used`` are not computed and fetch nothing (they name the last used
tile's blocks, which are there already).  A stack is taken as it lies,
``[experts, in, out]``.

**Why a tile is 32 rows.**  A step of the grid costs what its blocks cost
to fetch or, where they are there already, about 1.2 us of its own (the
MXU is handed the block's columns whatever the rows), and the pipeline
asks for a block one step ahead: an expert whose rows fill two tiles pays
the second tile's step *and then* waits for the next expert's block, where
one tile an expert hides everything behind the fetches.  At a pool's joint
pass (384 tokens, 4 of 64 experts each: 24 rows an expert) tiles of 16 made
two steps an expert and a layer's products 2.10 ms; at 8 rows an expert
(one tile) they were 1.63 ms against 1.47 for the stacks' bytes at the HBM
peak (PERF.md section 6, PR 46).  Tiles of 32 hold 24 rows in one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ROW_TILE", "gate_up", "down"]

ROW_TILE = 32       # two of a bfloat16 operand's sublane tiles (see above)
_BLOCK_BYTES = 8 << 20      # a call's stack blocks, each fetched twice over


def _columns(fan_in: int, fan_out: int, itemsize: int, stacks: int) -> int:
    """Columns of a stack's block: the most (a divisor of ``fan_out``
    that fills whole lanes) whose ``stacks`` blocks of ``[fan_in,
    columns]``, each held twice (the next one is fetched while this one
    multiplies), stay inside ``_BLOCK_BYTES``."""
    columns = fan_out
    while columns % 256 == 0 and \
            2 * stacks * fan_in * columns * itemsize > _BLOCK_BYTES:
        columns //= 2
    return columns


def _maps():
    """Index maps of a grid ``(column block j, row tile i)`` whose scalar
    prefetch is ``(group [tiles], used [1])``: a tile past ``used`` names
    the last used one."""
    def tile(i, used):
        return jnp.minimum(i, jnp.maximum(used[0] - 1, 0))

    def rows(j, i, group, used):
        return tile(i, used), 0

    def stack(j, i, group, used):
        return group[tile(i, used)], 0, j

    def out(j, i, group, used):
        return tile(i, used), j
    return rows, stack, out


def _call(kernel, rows, stacks, group, used, out_dtype, interpret):
    m, fan_in = rows.shape
    fan_out = stacks[0].shape[2]
    tiles = m // ROW_TILE
    columns = _columns(fan_in, fan_out, stacks[0].dtype.itemsize,
                       len(stacks))
    at_rows, at_stack, at_out = _maps()
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, fan_out), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(fan_out // columns, tiles),
            in_specs=[pl.BlockSpec((ROW_TILE, fan_in), at_rows)] + [
                pl.BlockSpec((None, fan_in, columns), at_stack)
                for _ in stacks],
            out_specs=pl.BlockSpec((ROW_TILE, columns), at_out)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(group, used, rows, *stacks)


def _check(rows, stacks, group, used):
    m, fan_in = rows.shape
    if m % ROW_TILE:
        raise ValueError(f"{m} rows are not whole tiles of {ROW_TILE}")
    for stack in stacks:
        if stack.ndim != 3 or stack.shape[1] != fan_in \
                or stack.shape != stacks[0].shape:
            raise ValueError(f"stacks {[s.shape for s in stacks]} for rows "
                             f"of {fan_in}: [experts, in, out] each")
    if group.shape != (m // ROW_TILE,) or used.shape != (1,):
        raise ValueError("one group a tile, and the tiles used as [1]")


@functools.partial(jax.jit, static_argnames=("interpret",))
def gate_up(rows, w_gate, w_up, group, used, *, interpret: bool = False):
    """``rows [M, in]`` (``M`` whole tiles of ``ROW_TILE``), ``w_gate``
    and ``w_up [experts, in, out]``, ``group [M / ROW_TILE]`` int32 (each
    tile's expert) and ``used [1]`` int32 (the tiles that hold rows, the
    first ones) -> ``silu(rows W_gate) * (rows W_up)`` as ``[M, out]`` in
    the rows' dtype.  Tiles past ``used`` are not computed: what they
    hold is undefined."""
    _check(rows, (w_gate, w_up), group, used)

    def kernel(group_ref, used_ref, x_ref, gate_ref, up_ref, out_ref):
        @pl.when(pl.program_id(1) < used_ref[0])
        def _():
            x = x_ref[...]
            g = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
            u = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
            out_ref[...] = (jax.nn.silu(g) * u).astype(out_ref.dtype)

    return _call(kernel, rows, (w_gate, w_up), group, used, rows.dtype,
                 interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def down(rows, w_down, group, used, *, interpret: bool = False):
    """``rows [M, in]`` times each tile's expert's ``w_down [experts, in,
    out]`` -> float32 ``[M, out]``; ``group`` and ``used`` as
    :func:`gate_up` takes them."""
    _check(rows, (w_down,), group, used)

    def kernel(group_ref, used_ref, x_ref, down_ref, out_ref):
        @pl.when(pl.program_id(1) < used_ref[0])
        def _():
            out_ref[...] = jnp.dot(x_ref[...], down_ref[...],
                                   preferred_element_type=jnp.float32)

    return _call(kernel, rows, (w_down,), group, used, jnp.float32,
                 interpret)

"""Writing one new position a row into a pooled key/value cache.

The serving pool's decode step writes, for every layer, row ``b``'s new
keys and values at a place of its own.  As XLA operations that is one
``dynamic_update_slice`` a row and leaf (anything batched becomes a
``scatter`` over the whole leaf: PERF.md, PR 27), a few KB each, and at
32 slots the time of some 700 such operations is their fixed cost, not
their bytes.  :func:`write_cache_rows` does a layer's keys and values in
one Pallas TPU program:

* grid over the pool's rows, the rows' places as scalar prefetch;
* each leaf aliased from input to output, so what is not written is not
  moved;
* of each row only the tile of positions that holds its place is fetched,
  the one position replaced (a select, never arithmetic: the values are
  copied, not rounded) and the tile written back.

**A leaf is taken as it lies on the chip.**  A TPU stores a
``[S, heads, L, width]`` leaf width-minor where the width fills the 128
lanes (``sublanes``: the tile is ``8`` float32 or ``16`` bfloat16
positions by the whole width), and positions-minor where it does not and
the positions do (``lanes``: OPT's 64, MiMo's keys of 192; the tile is
the whole width by 128 positions, and the kernel is handed the leaf with
its last two axes swapped, which is a change of name and not of bytes).
A custom call fixes its operands' layout, so a leaf handed over the other
way is copied whole in and out of every step;
``tests/test_tpu_compile.py`` holds the compiled decode step to "no copy
of a cache leaf".  :func:`cache_row_tiles` says which way a leaf goes, or
that it does not tile (the caller then keeps its ``dynamic_update_slice``
loop).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops import attention_kernels

__all__ = ["cache_row_tiles", "cache_row_writer", "write_cache_rows"]

_LANES = 128
_WRITE_VMEM = 8 * 2 ** 20    # every block of a call, in and out, twice over


def _sublanes(dtype) -> int:
    """Positions of a width-minor tile: 8 float32, 16 bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def cache_row_tiles(shape, dtype) -> Optional[str]:
    """How :func:`write_cache_rows` takes a leaf ``[S, heads, L, width]``:
    ``"sublanes"`` (width-minor, a tile of 8 or 16 positions),
    ``"lanes"`` (positions-minor, a tile of 128 positions) or None where
    the leaf does not tile."""
    _, _, length, width = shape
    sub = _sublanes(dtype)
    if width % _LANES == 0 and length % sub == 0:
        return "sublanes"
    if length % _LANES == 0 and width % sub == 0:
        return "lanes"
    return None


def _block_bytes(shape, dtype, tiles) -> int:
    _, heads, _, width = shape
    places = _LANES if tiles == "lanes" else _sublanes(dtype)
    return heads * places * width * jnp.dtype(dtype).itemsize


def cache_row_writer(k_shape, v_shape, dtype, *,
                     force: Optional[str] = None
                     ) -> Optional[Tuple[str, str]]:
    """How the per-row decode step writes a layer's cache leaves of these
    shapes: the tiles :func:`write_cache_rows` takes of the keys and of
    the values (one program a layer), or None for one
    ``dynamic_update_slice`` a row and leaf.  The kernel is chosen on a
    TPU where both leaves tile and their blocks fit its share of VMEM
    (``force`` ∈ {"kernel", "loop", None} overrides, as in
    :func:`attention_kernels.decode_key_block`).  The serving pool asks
    this too, to count the programs that write its cache."""
    if force == "loop" or (force is None
                           and not attention_kernels._on_tpu()):
        return None
    shapes = (k_shape, v_shape)
    tiles = tuple(cache_row_tiles(s, dtype) for s in shapes)
    if None not in tiles and 4 * sum(
            _block_bytes(s, dtype, t)
            for s, t in zip(shapes, tiles)) <= _WRITE_VMEM:
        return tiles
    if force == "kernel":
        raise ValueError(f"leaves {tuple(k_shape)} / {tuple(v_shape)} do "
                         f"not tile for the row-write kernel")
    return None


def _write_rows_kernel(place_ref, k_ref, v_ref, nk_ref, nv_ref, ok_ref,
                       ov_ref, *, tiles: Tuple[str, str]):
    """One row of the pool: the tile of each leaf that holds the row's
    place, with that one position replaced by the row's new keys or
    values.  Selects only; a bfloat16 tile passes through float32 and
    back, which changes no bit."""
    place = place_ref[pl.program_id(0)]
    for leaf_ref, new_ref, out_ref, how in (
            (k_ref, nk_ref, ok_ref, tiles[0]),
            (v_ref, nv_ref, ov_ref, tiles[1])):
        heads, rows, cols = leaf_ref.shape
        new = new_ref[...].astype(jnp.float32)             # [heads, width]
        # a tile is [sub, width] or [width, 128]: the place is a row of
        # the one and a column of the other
        axis = 0 if how == "sublanes" else 1
        mine = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), axis) \
            == place % leaf_ref.shape[1 + axis]
        if how == "lanes":
            # a head's new row lies along the lanes and its place in the
            # tile is a column: turned by a select against the diagonal
            # and a maximum over the lanes, which moves the value as it is
            diag = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0) \
                == jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
        for h in range(heads):
            val = new[h:h + 1, :]                          # [1, width]
            if how == "lanes":
                val = jnp.max(jnp.where(diag, val, -jnp.inf), axis=1,
                              keepdims=True)               # [width, 1]
            out_ref[h] = jnp.where(
                mine, val,
                leaf_ref[h].astype(jnp.float32)).astype(out_ref.dtype)


def write_cache_rows(leaf_k, leaf_v, new_k, new_v, place, *,
                     tiles: Optional[Tuple[str, str]] = None,
                     interpret: bool = False):
    """``new_k [S, heads, 1, d]`` into ``leaf_k [S, heads, L, d]`` and
    ``new_v [S, heads, 1, dv]`` into ``leaf_v [S, heads, L, dv]``, row
    ``b`` at ``place[b]``, in one program; returns the two leaves.  Bit
    for bit what one ``dynamic_update_slice`` a row and leaf writes.
    ``tiles`` is :func:`cache_row_writer`'s answer (asked here when not
    given); leaves that do not tile are an error."""
    if tiles is None:
        tiles = cache_row_writer(leaf_k.shape, leaf_v.shape, leaf_k.dtype,
                                 force="kernel")
    for leaf, new in ((leaf_k, new_k), (leaf_v, new_v)):
        s, heads, _, width = leaf.shape
        if new.shape != (s, heads, 1, width) or leaf.dtype != leaf_k.dtype:
            raise ValueError(f"one new position a row of one dtype: leaf "
                             f"{leaf.shape} {leaf.dtype}, new {new.shape}")
    return _write_cache_rows(leaf_k, leaf_v, new_k, new_v, place,
                             tiles=tuple(tiles), interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _write_cache_rows(leaf_k, leaf_v, new_k, new_v, place, *, tiles,
                      interpret):
    """:func:`write_cache_rows` on checked arguments.  A function of its
    own under ``jit`` so that the layers of a model, which call it on the
    same shapes, share one trace and one lowering of the kernel."""
    dtype = leaf_k.dtype
    sub = _sublanes(dtype)
    place = place.astype(jnp.int32)

    def spec(leaf, how):
        _, heads, _, width = leaf.shape
        if how == "sublanes":
            return leaf, pl.BlockSpec(
                (None, heads, sub, width),
                lambda b, place: (b, 0, place[b] // sub, 0))
        return jnp.swapaxes(leaf, 2, 3), pl.BlockSpec(
            (None, heads, width, _LANES),
            lambda b, place: (b, 0, 0, place[b] // _LANES))

    def row(new):
        _, heads, _, width = new.shape
        return new[:, :, 0, :].astype(dtype), pl.BlockSpec(
            (None, heads, width), lambda b, place: (b, 0, 0))

    (k, k_spec), (v, v_spec) = spec(leaf_k, tiles[0]), spec(leaf_v, tiles[1])
    (nk, nk_spec), (nv, nv_spec) = row(new_k), row(new_v)
    out_k, out_v = pl.pallas_call(
        functools.partial(_write_rows_kernel, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(leaf_k.shape[0],),
            in_specs=[k_spec, v_spec, nk_spec, nv_spec],
            out_specs=[k_spec, v_spec]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, dtype),
                   jax.ShapeDtypeStruct(v.shape, dtype)],
        # operands count the prefetched places: the leaves are 1 and 2
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
        **attention_kernels._dimsem("parallel"),
    )(place, k, v, nk, nv)
    return tuple(out if how == "sublanes" else jnp.swapaxes(out, 2, 3)
                 for out, how in ((out_k, tiles[0]), (out_v, tiles[1])))

"""Differential attention (arXiv:2410.05258, its ``multihead_flashdiff_2``
form): two softmaxes over one set of values, subtracted with a learned
weight, a norm a head, a constant.

``num_heads`` query heads and ``num_kv_heads`` key/value heads of
``head_dim``, adjacent heads paired: differential query head ``j`` is
``(q_{2j}, q_{2j+1})``, differential key/value head ``g`` is ``(k_{2g},
k_{2g+1})`` with the ``2 * head_dim``-wide value ``V_g = [v_{2g} |
v_{2g+1}]``, and head ``j`` reads ``g = j // (num_heads // num_kv_heads)``.
With ``P_a = softmax(q_{2j+a} k_{2g+a}^T / sqrt(head_dim) + mask)``::

    o_j = P_0 V_g - lambda * P_1 V_g
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 depth)            depth: the layer's index
    out = W_o [ RMSNorm(o_j) * (1 - lambda_init) ]_j + b_o

The four ``lambda`` vectors are ``head_dim`` wide, one set a layer; the
norm runs over a head's ``2 * head_dim`` with a learned gain; the
projections carry biases; **no position is encoded** (the mask alone
carries order).

**How it is laid here.**  The paired key heads of a differential head lie
side by side: ``K_g = [k_{2g} | k_{2g+1}]``, like ``V_g``, so a cache row
holds ``num_kv_heads / 2`` heads of ``2 * head_dim`` (128 at the published
sizes: lane-full, where 64 is not; the same bytes a place).  Each query
half is zero-filled to the pair's width on its own side, ``[q_{2j} | 0]``
and ``[0 | q_{2j+1}]``, and then ``q . K_g`` is exactly ``q_{2j+a} .
k_{2g+a}``: the two softmaxes are plain grouped-query attention of
``num_heads`` query heads over ``num_kv_heads / 2`` key/value heads at
scale ``1 / sqrt(head_dim)``, through the same functions a grouped-query
layer takes (``grouped_attention``, ``ops.ragged_decode_attention``,
``ops.write_cache_rows``), and the subtraction, norm and constant follow
on its result.

Three kinds of layer: a **window** layer (``window`` given; its cache a
ring), a **full** layer, and a **cross** layer (``cross=True``) that has a
query and an output projection only and attends keys and values it is
handed (``shared``: another layer's, under the same causal mask).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Module, Parameter
from bigdl_tpu.nn.attention import (_write_rows, _write_window,
                                    cache_positions, grouped_attention)
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.ops import attention_kernels

__all__ = ["DifferentialAttention", "lambda_init"]


def lambda_init(depth: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


class PairNorm(Module):
    """``x / rms(x) * gain`` over a differential head's width, float32."""

    def __init__(self, width: int, eps: float):
        super().__init__()
        self.eps = eps
        self.weight = Parameter(jnp.ones(width))

    def forward(self, x):
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.eps) \
            * self.weight.astype(jnp.float32)


def _biased(x, layer: Linear):
    """``x [B, T, in] @ W.T + b``, float32 out of the product."""
    return jnp.einsum("bti,oi->bto", x, layer.weight,
                      preferred_element_type=jnp.float32) \
        + layer.bias.astype(jnp.float32)


class DifferentialAttention(Module):
    def __init__(self, hidden_size: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, depth: int, window: Optional[int] = None,
                 cross: bool = False, eps: float = 1e-5):
        super().__init__()
        if num_heads % 2 or num_kv_heads % 2 or num_heads % num_kv_heads:
            raise ValueError("differential attention pairs adjacent heads: "
                             "even counts, num_heads a multiple of "
                             "num_kv_heads")
        if cross and window is not None:
            raise ValueError("a cross layer has the mask of the row it "
                             "reads, not a window of its own")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = head_dim
        self.pair_heads, self.pair_dim = num_kv_heads // 2, 2 * head_dim
        self.window = None if window is None else int(window)
        self.cross = bool(cross)
        self.lambda_init = lambda_init(depth)
        self.scale = head_dim ** -0.5
        kv = 0 if cross else 2 * num_kv_heads * head_dim
        # [q ; k ; v] in one projection (a cross layer: q alone)
        self.q_layer = Linear(hidden_size, num_heads * head_dim + kv,
                              with_bias=True)
        self.output_layer = Linear(num_heads * head_dim, hidden_size,
                                   with_bias=True)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, Parameter(jnp.zeros(head_dim)))
        self.norm = PairNorm(self.pair_dim, eps)

    # ---- what the pool asks ------------------------------------------------

    def cache_length(self, max_len: int, ring_margin: int = 1) -> int:
        """As :meth:`GroupedQueryAttention.cache_length`."""
        if self.window is None:
            return max_len
        return 1 + min(max_len, self.window + max(int(ring_margin), 1) - 1)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32,
                   ring_margin: int = 1):
        """``{"k", "v"}``, each ``[batch, num_kv_heads / 2, places, 2 *
        head_dim]``; a cross layer keeps nothing."""
        if self.cross:
            return {}
        shape = (batch, self.pair_heads,
                 self.cache_length(max_len, ring_margin), self.pair_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def cache_kind(self, max_len: int):
        """As :meth:`GroupedQueryAttention.cache_kind`."""
        return ("full", max_len) if self.window is None \
            else ("ring", self.window)

    def chunk_key_block(self, cache) -> None:
        """A prefill chunk attends its slot's row whole."""
        return None

    def decode_key_block(self, cache) -> Optional[int]:
        """As :meth:`GroupedQueryAttention.decode_key_block`, of the row
        this layer's step attends (a cross layer: the row it is
        handed)."""
        if self.window is not None:
            return None
        return attention_kernels.decode_key_block(
            cache["k"].shape, cache["v"].shape, cache["k"].dtype)

    # ---- the pieces ----------------------------------------------------------

    def _project(self, x):
        """``(q [B, Hq, T, 2d]`` zero-filled, ``k, v [B, Hkv/2, T, 2d]``
        or None for a cross layer), in ``x``'s dtype."""
        B, T, _ = x.shape
        d, hq = self.head_dim, self.num_heads
        with jax.named_scope("diffattn/project"):
            y = _biased(x, self.q_layer)
            q = y[..., :hq * d].reshape(B, T, hq // 2, 2, 1, d)
            zero = jnp.zeros_like(q)
            # head 2j: [q | 0]; head 2j + 1: [0 | q]
            side = jnp.arange(2).reshape(2, 1, 1) == jnp.arange(2).reshape(
                1, 2, 1)
            q = jnp.where(side, q, zero).reshape(B, T, hq, 2 * d)
            q = q.transpose(0, 2, 1, 3).astype(x.dtype)
            if self.cross:
                return q, None, None
            k, v = self._paired(y[..., hq * d:], x.dtype)
        return q, k, v

    def _paired(self, kv, dtype):
        """``[k | v]`` as projected, ``[B, T, 2 Hkv d]`` -> keys and
        values ``[B, Hkv/2, T, 2d]``: adjacent heads side by side."""
        B, T, _ = kv.shape
        kv = kv.reshape(B, T, 2, self.pair_heads, self.pair_dim)
        return tuple(kv[:, :, n].transpose(0, 2, 1, 3).astype(dtype)
                     for n in (0, 1))

    def _lambda(self):
        f32 = jnp.float32
        return jnp.exp(jnp.sum(self.lambda_q1.astype(f32)
                               * self.lambda_k1.astype(f32))) \
            - jnp.exp(jnp.sum(self.lambda_q2.astype(f32)
                              * self.lambda_k2.astype(f32))) \
            + self.lambda_init

    def _combine(self, ctx, dtype):
        """``ctx [B, Hq, T, 2d]`` float32, the two softmaxes' contexts of
        every differential head -> ``y [B, T, hidden]`` float32."""
        B, hq, T, w = ctx.shape
        with jax.named_scope("diffattn/combine"):
            pairs = ctx.reshape(B, hq // 2, 2, T, w)
            o = pairs[:, :, 0] - self._lambda() * pairs[:, :, 1]
            o = self.norm(o) * (1.0 - self.lambda_init)
            o = o.astype(dtype).transpose(0, 2, 1, 3).reshape(B, T, -1)
            return _biased(o, self.output_layer)

    def _write(self, k, v, index, cache, slot, active):
        """The new keys and values into ``cache`` -> ``(kv, keys, vals,
        last)``: the cache as written, the rows to attend, and the last
        position they hold."""
        ring = self.window is not None
        L = cache["k"].shape[2]
        if jnp.ndim(index) == 1:
            if k.shape[2] != 1:
                raise ValueError("a position per row takes T == 1")
            place = index
            if ring:
                place = jnp.mod(index, L - 1)
                if active is not None:
                    place = jnp.where(active, place, L - 1)
            kv = _write_rows(cache, k, v, place)
            return kv, kv["k"], kv["v"], index
        if slot is None and cache["k"].shape[0] != k.shape[0]:
            raise ValueError("a cache of other rows than x takes a slot")
        row = 0 if slot is None else slot
        kv, rows = {}, {}
        with jax.named_scope("cache/write"):
            for n, new in (("k", k), ("v", v)):
                kv[n], rows[n] = _write_window(cache[n], new, row, index,
                                               ring)
        return kv, rows["k"], rows["v"], index + (k.shape[2] - 1)

    def _attend(self, q, keys, vals, index, q_pos, last, pad, slot, active,
                cached: bool):
        """The two softmaxes of every head as one grouped product ->
        ``[B, Hq, T, 2d]`` float32."""
        ring = self.window is not None
        per_row = jnp.ndim(index) == 1
        with jax.named_scope("diffattn/attend"):
            if not cached:
                return grouped_attention(q, keys, vals, q_pos, q_pos,
                                         self.window, pad, scale=self.scale)
            L = keys.shape[2]
            block = self.decode_key_block({"k": keys, "v": vals}) \
                if per_row else None
            if block is not None:
                # the pool's step over full rows: live key blocks only
                lengths = index + 1
                if active is not None:
                    lengths = jnp.where(active, lengths, 0)
                return attention_kernels.ragged_decode_attention(
                    q.astype(jnp.float32), keys, vals, lengths, pad,
                    scale=self.scale, block_k=block,
                    interpret=not attention_kernels._on_tpu())
            k_pos = cache_positions(L, last, ring)
            if pad is not None:
                # pad is by position; a ring place holds position k_pos
                if slot is not None:
                    pad = jax.lax.dynamic_slice(
                        pad, (slot, 0), (1, pad.shape[1]))
                if ring:
                    pad = jnp.take_along_axis(
                        pad, jnp.broadcast_to(jnp.maximum(k_pos, 0),
                                              (pad.shape[0], L)), axis=1)
            return grouped_attention(q, keys, vals, q_pos, k_pos,
                                     self.window, pad, scale=self.scale)

    # ---- the entries ---------------------------------------------------------

    def forward(self, x, index=0, cache=None, pad=None, slot=None,
                active=None, shared=None):
        """``x [B, T, H]`` (normed) at positions ``index .. index+T-1``
        -> ``(y [B, T, H] float32, kv)``; ``index``, ``cache``, ``pad``,
        ``slot`` and ``active`` as :meth:`GroupedQueryAttention.forward`
        has them.  ``kv`` is the compact ``{"k", "v"}`` ``[B, Hkv/2, T,
        2d]`` without a cache, the updated cache with one.

        A cross layer is handed ``shared``, the ``kv`` that the layer it
        reads returned **in this pass** (compact, or that layer's cache
        as just written), and ``cache`` only to say whether there is one
        (anything but None); it returns ``shared`` as ``kv``."""
        T = x.shape[1]
        per_row = jnp.ndim(index) == 1
        index = jnp.asarray(index, jnp.int32)
        q_pos = (index[:, None] if per_row else index[None, None]) \
            + jnp.arange(T, dtype=jnp.int32)[None, :]       # [1|B, T]
        q, k, v = self._project(x)
        if self.cross:
            if slot is not None:
                raise ValueError("a cross layer attends every row it is "
                                 "handed: a chunk's rows into one slot "
                                 "stop where the caches stop")
            kv, keys, vals = shared, shared["k"], shared["v"]
            last = index if per_row else index + (T - 1)
        elif cache is None:
            kv, keys, vals, last = {"k": k, "v": v}, k, v, None
        else:
            kv, keys, vals, last = self._write(k, v, index, cache, slot,
                                               active)
        ctx = self._attend(q, keys, vals, index, q_pos, last, pad, slot,
                           active, cache is not None)
        return self._combine(ctx, x.dtype), kv

    def write(self, x, index=0, cache=None, slot=None):
        """What :meth:`forward` writes and nothing else: the keys and
        values of ``x`` (normed) into ``cache`` at ``index ..`` (compact,
        without one).  No query, no attention: the last thing a chunk's
        rows do in a model whose later layers keep nothing."""
        rows = self.num_heads * self.head_dim
        with jax.named_scope("diffattn/project"):
            w, b = self.q_layer.weight[rows:], self.q_layer.bias[rows:]
            k, v = self._paired(
                jnp.einsum("bti,oi->bto", x, w,
                           preferred_element_type=jnp.float32)
                + b.astype(jnp.float32), x.dtype)
        if cache is None:
            return {"k": k, "v": v}
        return self._write(k, v, jnp.asarray(index, jnp.int32), cache, slot,
                           None)[0]

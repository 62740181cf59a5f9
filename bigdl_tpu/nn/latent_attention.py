"""Latent attention: one compressed cache row a position, shared by all
heads (multi-head latent attention, ``model_type`` ``sarvam_mla`` and the
DeepSeek-V2 family).

``x_t`` is the block's normed input, ``H`` the heads, each query head
``[q^N (nope_dim) ; q^R (rope_dim)]``:

* ``q_t = W_q x_t`` (no low-rank query);
* ``[c_t (latent_dim) ; k^R_t (rope_dim)] = W_kva x_t``, ``c_t <-
  RMSNorm(c_t)`` with a learned gain; ``k^R_t`` is not normed;
* ``q^R_{t,h}`` and ``k^R_t`` (one for all heads) are rotated at position
  ``t`` (:func:`rotary_pairs`, YaRN frequencies);
* **expanded** (a whole sequence): ``[k^N_{s,h} ; v_{s,h}] = W_kvb,h c_s``,
  ``score = (q^N . k^N + q^R . k^R) * scale``, causal,
  ``o = softmax(score) v``;
* **absorbed** (against a cache row): with ``W_kvb,h = [W^K_h ; W^V_h]``,
  ``q~_{t,h} = W^K_h^T q^N_{t,h}`` (``latent_dim`` wide),
  ``score = (q~ . c_s + q^R . k^R_s) * scale``, ``ctx = softmax(score) c``,
  ``o = W^V_h ctx``: the same numbers, and the row is never expanded;
* ``out = W_o [o_1 .. o_H]``.

**What is cached a place is ``c_s`` (normed) and ``k^R_s`` (rotated)**: a
row of one head with two leaves, ``"v"`` the latent (it is the value and
most of the key) and ``"k"`` the rotary part of the key.  They are
written and read by position like any full row, so every program of the
serving pool takes them as it takes keys and values.

**A prefill chunk** attends the live key blocks of its slot's row,
absorbed: on a TPU, where the leaves tile, through one kernel a layer
(``ops.attention_kernels.latent_chunk_attention``; the predicate is
``latent_chunk_takes``), on every other backend and shape through
:func:`latent_rows_attention`, a loop over slices.  One meaning, and the
choice follows the backend and the shapes.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Module, Parameter
from bigdl_tpu.nn.attention import _write_rows, cache_positions, \
    grouped_attention, rotary_pairs, yarn_frequencies, yarn_mscale
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.ops import attention_kernels
from bigdl_tpu.ops.attention_kernels import _NEG_INF

__all__ = ["LatentAttention", "latent_rows_attention"]

# places of a row that a prefill chunk attends at a time
CHUNK_KEY_BLOCK = 512


def _project(a, weight):
    """``a [B, T, in] @ weight.T`` with float32 out of the product."""
    return jnp.einsum("bti,oi->bto", a, weight,
                      preferred_element_type=jnp.float32)


class LatentNorm(Module):
    """``c / rms(c) * gain`` in float32, over the compressed row."""

    def __init__(self, width: int, eps: float):
        super().__init__()
        self.eps = eps
        self.weight = Parameter(jnp.ones(width))

    def forward(self, c):
        c = c.astype(jnp.float32)
        ms = jnp.mean(jnp.square(c), axis=-1, keepdims=True)
        return c * jax.lax.rsqrt(ms + self.eps) \
            * self.weight.astype(jnp.float32)


def latent_rows_attention(q_latent, q_rotary, latent, rotary, row, q_pos,
                          pad, scale: float, block: int):
    """Queries ``q_latent [B, H, T, r]`` / ``q_rotary [B, H, T, dr]`` at
    positions ``q_pos [1, T]`` over rows ``row .. row+B`` of the pooled
    leaves ``latent [S, 1, L, r]`` and ``rotary [S, 1, L, dr]``, in the
    absorbed form, **over the live places only**: key blocks of ``block``
    places up to the last query's, in a loop whose length is traced
    (one compiled program whatever the position), an online softmax in
    float32 across them.  ``pad [B, L]`` flags padding by position.
    Returns the context in the latent space, ``[B, H, T, r]`` float32."""
    B, H, T, r = q_latent.shape
    dr = q_rotary.shape[-1]
    blocks = jnp.max(q_pos) // block + 1

    def step(j, carry):
        m, den, acc = carry
        start = j * block
        c = jax.lax.dynamic_slice(latent, (row, 0, start, 0),
                                  (B, 1, block, r))[:, 0]
        kr = jax.lax.dynamic_slice(rotary, (row, 0, start, 0),
                                   (B, 1, block, dr))[:, 0]
        s = jnp.einsum("bhtr,bkr->bhtk", q_latent, c,
                       preferred_element_type=jnp.float32) \
            + jnp.einsum("bhtd,bkd->bhtk", q_rotary, kr,
                         preferred_element_type=jnp.float32)
        k_pos = start + jnp.arange(block, dtype=jnp.int32)
        ok = k_pos[None, None, :] <= q_pos[:, :, None]        # [1, T, block]
        if pad is not None:
            ok = ok & ~jax.lax.dynamic_slice(
                pad, (0, start), (pad.shape[0], block))[:, None, :]
        s = jnp.where(ok[:, None], s * scale, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        den = alpha * den + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhtk,bkr->bhtr", p.astype(c.dtype), c,
            preferred_element_type=jnp.float32)
        return m_new, den, acc

    init = (jnp.full((B, H, T), _NEG_INF, jnp.float32),
            jnp.zeros((B, H, T), jnp.float32),
            jnp.zeros((B, H, T, r), jnp.float32))
    _, den, acc = jax.lax.fori_loop(0, blocks, step, init)
    return acc / den[..., None]


class LatentAttention(Module):
    """Causal self-attention over a latent row (module docstring).

    ``rope_scaling`` holds the YaRN keys of the public ``config.json``
    (``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``, ``mscale``, ``mscale_all_dim``): the frequencies are
    :func:`yarn_frequencies`', cos and sin are scaled by
    ``m(mscale) / m(mscale_all_dim)`` and the scores by ``(nope_dim +
    rope_dim) ** -0.5 * m(mscale_all_dim) ** 2`` with ``m`` =
    :func:`yarn_mscale`.  Without it the rotation is plain.

    :meth:`forward` is the one entry, as
    :meth:`GroupedQueryAttention.forward`: a whole sequence (expanded), a
    prefill chunk against the slot's row (absorbed, live key blocks
    only; on a TPU through ``ops.latent_chunk_attention``, elsewhere
    :func:`latent_rows_attention`), and one token a row (absorbed; on a
    TPU through
    ``ops.latent_decode_attention``, which reads live blocks only, each
    once) share their projections."""

    window = None        # a latent row is a full row: every position kept

    def __init__(self, hidden_size: int, num_heads: int, nope_dim: int,
                 rope_dim: int, v_head_dim: int, latent_dim: int,
                 rope_theta: float = 10000.0,
                 rope_scaling: Optional[Dict[str, Any]] = None,
                 eps: float = 1e-6):
        super().__init__()
        if rope_dim % 2:
            raise ValueError(f"rope_dim {rope_dim} must be even")
        self.num_heads = num_heads
        self.nope_dim, self.rope_dim = nope_dim, rope_dim
        self.v_head_dim, self.latent_dim = v_head_dim, latent_dim
        self.rope_theta = float(rope_theta)
        ys = dict(rope_scaling or {})
        self.yarn = None if not ys else (
            float(ys["factor"]),
            int(ys["original_max_position_embeddings"]),
            float(ys.get("beta_fast", 32)), float(ys.get("beta_slow", 1)))
        factor = self.yarn[0] if self.yarn else 1.0
        all_dim = yarn_mscale(factor, float(ys.get("mscale_all_dim", 0)))
        self.rotary_magnitude = yarn_mscale(
            factor, float(ys.get("mscale", 1))) / all_dim if self.yarn \
            else 1.0
        self.scale = (nope_dim + rope_dim) ** -0.5 * all_dim ** 2
        self.q_layer = Linear(hidden_size, num_heads * (nope_dim + rope_dim),
                              with_bias=False)
        self.kv_a_layer = Linear(hidden_size, latent_dim + rope_dim,
                                 with_bias=False)
        self.kv_norm = LatentNorm(latent_dim, eps)
        self.kv_b_layer = Linear(latent_dim,
                                 num_heads * (nope_dim + v_head_dim),
                                 with_bias=False)
        self.output_layer = Linear(num_heads * v_head_dim, hidden_size,
                                   with_bias=False)

    # ---- what the pool asks ------------------------------------------------

    def cache_length(self, max_len: int, ring_margin: int = 1) -> int:
        return max_len

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32,
                   ring_margin: int = 1):
        """``"k"``: the rotated rotary keys ``[batch, 1, max_len,
        rope_dim]``; ``"v"``: the normed latent ``[batch, 1, max_len,
        latent_dim]``."""
        length = self.cache_length(max_len, ring_margin)
        return {"k": jnp.zeros((batch, 1, length, self.rope_dim), dtype),
                "v": jnp.zeros((batch, 1, length, self.latent_dim), dtype)}

    def cache_kind(self, max_len: int):
        """What the layer declares to a slot pool: a latent row."""
        return ("latent", max_len)

    def decode_key_block(self, cache) -> Optional[int]:
        """Places of a row that the per-row decode step attends at a time
        (``ops.latent_decode_attention``), or None where it attends every
        place of every row: rows that do not tile, and every backend but
        a TPU."""
        return attention_kernels.decode_key_block(
            cache["k"].shape, cache["v"].shape, cache["k"].dtype)

    def chunk_key_block(self, cache) -> int:
        """Places of its slot's row that a prefill chunk attends at a
        time, on every backend (live blocks only, up to the chunk's last
        position): :data:`CHUNK_KEY_BLOCK`, or what it shares with a row
        it does not divide."""
        return math.gcd(cache["v"].shape[2], CHUNK_KEY_BLOCK)

    # ---- the three entries -------------------------------------------------

    def _frequencies(self):
        if self.yarn is None:
            half = self.rope_dim // 2
            return jnp.exp(jnp.arange(half, dtype=jnp.float32)
                           * (-math.log(self.rope_theta) / half))
        return yarn_frequencies(self.rope_dim, self.rope_theta, *self.yarn)

    def _kv_b(self):
        """``W^K [H, nope_dim, r]`` and ``W^V [H, v_head_dim, r]``."""
        w = self.kv_b_layer.weight.reshape(
            self.num_heads, self.nope_dim + self.v_head_dim, self.latent_dim)
        return w[:, :self.nope_dim], w[:, self.nope_dim:]

    def forward(self, x, index=0, cache=None, pad=None, slot=None,
                active=None):
        """``x [B, T, H]`` (normed) at positions ``index .. index+T-1``
        -> ``(y [B, T, H] float32, kv)``; ``index``, ``cache``, ``pad``,
        ``slot`` and ``active`` as :meth:`GroupedQueryAttention.forward`
        has them.  ``kv`` is the compact ``{"k": [B, 1, T, rope_dim],
        "v": [B, 1, T, latent_dim]}`` without a cache, the updated cache
        with one."""
        B, T, _ = x.shape
        H, dn, dr = self.num_heads, self.nope_dim, self.rope_dim
        r = self.latent_dim
        per_row = jnp.ndim(index) == 1
        index = jnp.asarray(index, jnp.int32)
        q_pos = (index[:, None] if per_row else index[None, None]) \
            + jnp.arange(T, dtype=jnp.int32)[None, :]       # [1|B, T]
        with jax.named_scope("mla/project"):
            q = _project(x, self.q_layer.weight).reshape(
                B, T, H, dn + dr).transpose(0, 2, 1, 3)
            kv = _project(x, self.kv_a_layer.weight)
            c = self.kv_norm(kv[..., :r])[:, None]           # [B, 1, T, r]
            freq = self._frequencies()
            q_r = rotary_pairs(q[..., dn:], q_pos[:, None, :], freq,
                               self.rotary_magnitude)
            k_r = rotary_pairs(kv[..., r:][:, None], q_pos[:, None, :],
                               freq, self.rotary_magnitude)
            q_n, q_r, c, k_r = (a.astype(x.dtype)
                                for a in (q[..., :dn], q_r, c, k_r))
        w_k, w_v = self._kv_b()
        if cache is None:
            with jax.named_scope("mla/expand"):
                e = _project(c[:, 0], self.kv_b_layer.weight).reshape(
                    B, T, H, dn + self.v_head_dim).transpose(0, 2, 1, 3)
                keys = jnp.concatenate(
                    [e[..., :dn], jnp.broadcast_to(
                        k_r.astype(jnp.float32), (B, H, T, dr))],
                    axis=-1).astype(x.dtype)
                vals = e[..., dn:].astype(x.dtype)
            with jax.named_scope("mla/attend"):
                o = grouped_attention(
                    jnp.concatenate([q_n, q_r], axis=-1), keys, vals,
                    q_pos, q_pos, None, pad, scale=self.scale)
            kv = {"k": k_r, "v": c}
        else:
            with jax.named_scope("mla/absorb"):
                # the heads lead both operands (the CPU backend has no
                # bfloat16 product for a batch axis in the middle)
                q_c = jnp.einsum("hbtn,hnr->hbtr", q_n.transpose(1, 0, 2, 3),
                                 w_k, preferred_element_type=jnp.float32
                                 ).transpose(1, 0, 2, 3)
            if per_row:
                if T != 1:
                    raise ValueError("a position per row takes T == 1")
                kv = _write_rows(cache, k_r, c, index)
                ctx = self._attend_step(q_c, q_r, kv, index, pad, active)
            else:
                if slot is None and cache["k"].shape[0] != B:
                    raise ValueError("a cache of other rows than x "
                                     "takes a slot")
                row = 0 if slot is None else slot
                with jax.named_scope("cache/write"):
                    kv = {n: jax.lax.dynamic_update_slice(
                        cache[n], new.astype(cache[n].dtype),
                        (row, 0, index, 0))
                        for n, new in (("k", k_r), ("v", c))}
                if pad is not None and slot is not None:
                    pad = jax.lax.dynamic_slice(
                        pad, (slot, 0), (1, pad.shape[1]))
                block = self.chunk_key_block(kv)
                q_c = q_c.astype(x.dtype)
                with jax.named_scope("mla/attend"):
                    if attention_kernels.latent_chunk_takes(
                            q_c.shape, kv["v"].shape, kv["k"].shape,
                            kv["v"].dtype, block):
                        ctx = attention_kernels.latent_chunk_attention(
                            q_c, q_r, kv["v"], kv["k"], row, index, pad,
                            scale=self.scale, block=block,
                            interpret=not attention_kernels._on_tpu())
                    else:
                        ctx = latent_rows_attention(
                            q_c, q_r, kv["v"], kv["k"], row, q_pos, pad,
                            self.scale, block)
            with jax.named_scope("mla/absorb"):
                o = jnp.einsum("bhtr,hvr->bhtv", ctx.astype(x.dtype), w_v,
                               preferred_element_type=jnp.float32)
        with jax.named_scope("mla/out"):
            o = o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(B, T, -1)
            y = _project(o, self.output_layer.weight)
        return y, kv

    def _attend_step(self, q_c, q_r, kv, index, pad, active):
        """The per-row step's context in the latent space, ``[B, H, 1,
        r]`` float32: live key blocks only on a TPU, the masked product
        over every place elsewhere."""
        block = self.decode_key_block(kv)
        with jax.named_scope("mla/attend"):
            if block is not None:
                lengths = index + 1
                if active is not None:
                    lengths = jnp.where(active, lengths, 0)
                # float32 in, so float32 out (the kernel rounds its
                # queries to the row's dtype itself)
                return attention_kernels.latent_decode_attention(
                    q_c, q_r.astype(jnp.float32), kv["k"], kv["v"], lengths,
                    pad, scale=self.scale, block_k=block,
                    interpret=not attention_kernels._on_tpu())
            dtype = kv["v"].dtype
            L = kv["v"].shape[2]
            return grouped_attention(
                jnp.concatenate([q_c.astype(dtype), q_r.astype(dtype)],
                                axis=-1),
                jnp.concatenate([kv["v"], kv["k"]], axis=-1), kv["v"],
                index[:, None], cache_positions(L, index, False), None, pad,
                scale=self.scale)

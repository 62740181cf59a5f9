"""Multi-head attention + Transformer stack.

Reference: nn/Attention.scala (multi-head attention as a graph of
MM/SoftMax/Dropout layers), nn/FeedForwardNetwork.scala,
nn/TransformerOperation.scala (position encoding, padding/causal bias,
shiftRight3D), nn/Transformer.scala (LanguageModel + Translation
topologies, pre-norm blocks, shared embedding/softmax weights),
nn/SequenceBeamSearch.scala.

TPU-first redesign: attention scores never materialize at [B,H,T,T] on
the hot path — :func:`bigdl_tpu.ops.dot_product_attention` dispatches to
a Pallas flash kernel (blockwise online softmax) on TPU.  Decode uses a
fixed-size KV cache updated with ``lax.dynamic_update_slice`` so the
beam-search loop stays jittable (static shapes, no concat-growing
tensors like the reference's JoinTable cache, Attention.scala joinK/V).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.core.module import Module, ModuleList, Parameter, \
    next_rng_key
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.normalization import LayerNormalization
from bigdl_tpu.ops import attention_kernels, cache_kernels, \
    dot_product_attention
from bigdl_tpu.ops.attention_kernels import _NEG_INF

__all__ = [
    "Attention", "FeedForwardNetwork", "TransformerEncoderLayer",
    "TransformerDecoderLayer", "Transformer", "SequenceBeamSearch",
    "position_encoding", "padding_bias", "causal_bias",
    "incremental_bias", "chunk_incremental_bias", "shift_right_3d",
    "GroupedQueryAttention", "grouped_attention", "rotary_half",
    "rotary_pairs", "yarn_frequencies", "yarn_mscale", "cache_positions",
]


# ---------------------------------------------------------------------------
# TransformerOperation equivalents (reference nn/TransformerOperation.scala)
# ---------------------------------------------------------------------------

def position_encoding(length: int, hidden_size: int,
                      min_timescale: float = 1.0,
                      max_timescale: float = 1.0e4,
                      dtype=jnp.float32):
    """Sinusoidal position encoding [length, hidden_size]
    (reference TransformerOperation.getPositionEncode:118)."""
    position = jnp.arange(length, dtype=jnp.float32)
    num_timescales = hidden_size // 2
    log_inc = math.log(max_timescale / min_timescale) / max(
        num_timescales - 1, 1)
    inv_timescales = min_timescale * jnp.exp(
        jnp.arange(num_timescales, dtype=jnp.float32) * -log_inc)
    scaled = position[:, None] * inv_timescales[None, :]
    signal = jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=1)
    if signal.shape[1] < hidden_size:  # odd hidden size
        signal = jnp.pad(signal, ((0, 0), (0, hidden_size - signal.shape[1])))
    return signal.astype(dtype)


def padding_bias(tokens, padding_value: float = 0.0):
    """[B, 1, 1, T] additive bias: -1e9 at padding positions
    (reference TransformerOperation.getPaddingBias:74)."""
    pad = (tokens == padding_value).astype(jnp.float32) * _NEG_INF
    return pad[:, None, None, :]


def causal_bias(length: int, dtype=jnp.float32):
    """[1, 1, T, T] lower-triangle attention bias (reference
    TransformerOperation.attentionBiasLowerTriangle:156)."""
    mask = jnp.tril(jnp.ones((length, length), bool))
    return jnp.where(mask, 0.0, _NEG_INF).astype(dtype)[None, None]


def incremental_bias(max_len: int, index, pad=None, dtype=jnp.float32):
    """Additive attention bias over a fixed-size KV cache for one decode
    step at position ``index``: slots beyond ``index`` (not yet written)
    are masked, and so are per-batch padding slots when ``pad``
    ([B, max_len] bool) is given.  ``index`` is a scalar (every row at
    the same position) or ``[B]`` (row ``b`` at ``index[b]``).  Returns
    [1,1,1,max_len] (scalar index, no pad) or [B,1,1,max_len].  Shared
    by every incremental decoder so the cache-masking logic has one
    home."""
    if jnp.ndim(index) == 1:
        index = index[:, None]
    invalid = jnp.arange(max_len) > index      # [max_len] or [B, max_len]
    if pad is not None:
        invalid = invalid | pad                # [B, max_len]
    bias = jnp.where(invalid, _NEG_INF, 0.0).astype(dtype)
    if bias.ndim == 2:
        return bias[:, None, None, :]
    return bias[None, None, None, :]


def chunk_incremental_bias(max_len: int, index, width: int, pad,
                           dtype=jnp.float32):
    """Additive attention bias for a ``width``-token chunk written at
    positions ``[index, index+width)`` of a fixed-size KV cache: query
    ``i`` (global position ``index+i``) may attend cache slots
    ``j <= index+i`` that are not padding (``pad``: [B, max_len] bool,
    including the chunk's own freshly written flags).  The ``width==1``
    row is exactly :func:`incremental_bias` — decode is the degenerate
    chunk.  Returns [B, 1, width, max_len]."""
    qpos = index + jnp.arange(width)[:, None]
    invalid = jnp.arange(max_len)[None, :] > qpos          # [W, max_len]
    invalid = invalid[None, :, :] | pad[:, None, :]        # [B, W, max_len]
    return jnp.where(invalid, _NEG_INF, 0.0).astype(dtype)[:, None, :, :]


def shift_right_3d(x):
    """Shift the time axis right by one, zero-filling position 0
    (reference TransformerOperation.shiftRight3D:94 — decoder input
    shifting)."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

class Attention(Module):
    """Multi-head (self/cross) attention (reference nn/Attention.scala).

    ``forward(x, y=None, bias=None, cache=None, cache_index=None)``:

    * x: queries [B, Tq, H]; y: keys/values source (defaults to x —
      self-attention, like the reference feeding inputX=inputY).
    * bias: additive attention bias broadcastable to [B, h, Tq, Tk]
      (padding mask and/or causal mask).
    * cache: optional dict {"k": [B, h, Tmax, d], "v": ...} for
      incremental decoding; cache_index is the current step.  Returns
      (output, new_cache) when a cache is passed, else output.
    """

    def __init__(self, hidden_size: int, num_heads: int,
                 attention_dropout: float = 0.0):
        super().__init__()
        if hidden_size % num_heads:
            raise ValueError("hidden_size must be divisible by num_heads")
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.attention_dropout = attention_dropout
        self.q_layer = Linear(hidden_size, hidden_size, with_bias=False)
        self.k_layer = Linear(hidden_size, hidden_size, with_bias=False)
        self.v_layer = Linear(hidden_size, hidden_size, with_bias=False)
        self.output_layer = Linear(hidden_size, hidden_size, with_bias=False)

    def _split_heads(self, x):
        b, t, _ = x.shape
        d = self.hidden_size // self.num_heads
        return x.reshape(b, t, self.num_heads, d).transpose(0, 2, 1, 3)

    def _combine_heads(self, x):
        b, h, t, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)

    def forward(self, x, y=None, bias=None, cache=None, cache_index=None,
                causal=False):
        """``causal=True`` applies the lower-triangular mask inside the
        attention kernel instead of via an additive bias — on TPU the
        flash path then skips above-diagonal blocks entirely and never
        materializes/streams a [B, H, Tq, Tk] bias."""
        self_attention = y is None
        y = x if self_attention else y
        q = self._split_heads(self.q_layer(x))
        d = self.hidden_size // self.num_heads
        # reference scales q by 1/sqrt(depth) before the MM
        # (Attention.scala createModule); we fold it into the kernel scale.

        new_cache = None
        if cache is not None:
            if self_attention:
                if causal:
                    # The kernel mask is end-aligned (k = tk - tq): with
                    # a decode cache tq=1 vs tk=max_len it would admit
                    # every slot, including uninitialized future ones —
                    # silently wrong logits.  Decode callers must pass
                    # the position mask as an additive bias (the
                    # TransformerLM decode_step path does).
                    raise ValueError(
                        "causal=True is unsupported with a decode cache: "
                        "the kernel mask cannot know the cache fill; "
                        "pass the decode position mask as `bias` instead")
                k_step = self._split_heads(self.k_layer(y))
                v_step = self._split_heads(self.v_layer(y))
                k = jax.lax.dynamic_update_slice(
                    cache["k"], k_step.astype(cache["k"].dtype),
                    (0, 0, cache_index, 0))
                v = jax.lax.dynamic_update_slice(
                    cache["v"], v_step.astype(cache["v"].dtype),
                    (0, 0, cache_index, 0))
                new_cache = {"k": k, "v": v}
            else:
                # cross-attention: cache holds the projected encoder K/V
                k, v = cache["k"], cache["v"]
                new_cache = cache
        else:
            k = self._split_heads(self.k_layer(y))
            v = self._split_heads(self.v_layer(y))

        if self.training and self.attention_dropout > 0.0:
            # dropout on the softmax weights forces the materialized path
            # (reference dropLayer after softMaxLayer)
            logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                                preferred_element_type=jnp.float32)
            logits = logits / math.sqrt(d)
            if bias is not None:
                logits = logits + bias.astype(jnp.float32)
            if causal:
                tq, tk = logits.shape[-2], logits.shape[-1]
                mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
                logits = jnp.where(mask, logits, _NEG_INF)
            w = jax.nn.softmax(logits, axis=-1)
            keep = jax.random.bernoulli(
                next_rng_key(), 1.0 - self.attention_dropout, w.shape)
            w = jnp.where(keep, w / (1.0 - self.attention_dropout), 0.0)
            ctxt = jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)
        else:
            ctxt = dot_product_attention(q, k, v, bias, causal=causal)
        out = self.output_layer(self._combine_heads(ctxt))
        if cache is not None:
            return out, new_cache
        return out

    def init_cache(self, batch: int, max_length: int, dtype=jnp.float32):
        d = self.hidden_size // self.num_heads
        shape = (batch, self.num_heads, max_length, d)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# Grouped-query attention over a cache whose layers differ in length
# ---------------------------------------------------------------------------

# float32 scores of one product, [B, heads, Tq, Tk], above which the
# product goes one key/value head (and, if still over, one block of
# queries) at a time: a whole 6,144-token sequence against itself would
# hold 9 GiB of scores at 64 heads
SCORE_BYTES = 512 << 20


def rotary_half(x, positions, theta: float, rotary_dim: int):
    """Rotate the first ``rotary_dim`` dims of ``x [..., T, d]`` by its
    ``positions [..., T]``, pairing dim ``j`` with ``j + rotary_dim/2``
    (the half-split rotary embedding: the pair turns by
    ``position * theta**(-2j/rotary_dim)``); the dims past
    ``rotary_dim`` pass unchanged (``partial_rotary_factor``).
    Computed in float32."""
    half = rotary_dim // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-math.log(theta) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv     # [..., T, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:rotary_dim]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                           xf[..., rotary_dim:]], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1`` (1
    where nothing is stretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(rotary_dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0):
    """The ``rotary_dim / 2`` rotary frequencies of a YaRN-stretched
    context (``rope_scaling`` of type ``deepseek_yarn``), float32.

    Pair ``j`` turns by ``f_j = theta**(-2j/rotary_dim)`` a position
    unstretched and by ``f_j / factor`` interpolated; it takes the one
    below ``lo``, the other above ``hi`` and a linear blend between,
    ``lo`` and ``hi`` being the pairs that make ``beta_fast`` and
    ``beta_slow`` turns over ``original_max`` positions (rounded down
    and up to whole pairs, kept inside the width): fast pairs keep their
    resolution, slow ones are stretched to the longer context."""
    half = rotary_dim // 2

    def pair_of(turns):
        return rotary_dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(pair_of(beta_fast)), 0)
    hi = min(math.ceil(pair_of(beta_slow)), rotary_dim - 1)
    j = jnp.arange(half, dtype=jnp.float32)
    plain = jnp.exp(j * (-math.log(theta) / half))
    ramp = jnp.clip((j - lo) / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotary_pairs(x, positions, frequencies, magnitude: float = 1.0):
    """Rotate ``x [..., T, d]`` by its ``positions [..., T]``, pairing dim
    ``2j`` with ``2j + 1``: the pair turns by ``position *
    frequencies[j]`` (``[d / 2]``) and is scaled by ``magnitude`` (YaRN's
    correction of cos and sin).  Computed in float32."""
    ang = positions.astype(jnp.float32)[..., None] * frequencies
    cos, sin = jnp.cos(ang) * magnitude, jnp.sin(ang) * magnitude
    xf = x.astype(jnp.float32)
    pairs = xf.reshape(xf.shape[:-1] + (xf.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(xf.shape).astype(x.dtype)


def cache_positions(length: int, last, ring: bool):
    """The position each of a cache row's ``length`` places holds once
    position ``last`` (a scalar, or ``[B]``) has been written.  A
    ``full`` row holds position ``j`` at place ``j``.  A ``ring`` row is
    ``length - 1`` ring places and one spare: position ``p`` lies at
    place ``p % (length - 1)``, so place ``j`` holds the newest position
    congruent to it, ``last - (last - j) % (length - 1)`` (negative:
    nothing written yet), and the spare place holds nothing, ever — it
    is where a row that only rides along writes.  Returns
    ``[1|B, length]``."""
    j = jnp.arange(length, dtype=jnp.int32)
    last = jnp.reshape(jnp.asarray(last, jnp.int32), (-1, 1))
    if not ring:
        return jnp.broadcast_to(j, (last.shape[0], length))
    return jnp.where(j == length - 1, -1,
                     last - jnp.mod(last - j, length - 1))


def grouped_attention(q, k, v, q_pos, k_pos, window: Optional[int] = None,
                      pad=None, sink=None, scale: Optional[float] = None):
    """Causal attention of ``q [B, Hq, Tq, d]`` over ``k [B, Hkv, Tk, d]``
    and ``v [B, Hkv, Tk, dv]`` with ``Hq`` a multiple of ``Hkv``: query
    head ``h`` reads key/value head ``h // (Hq // Hkv)``.  The queries
    are grouped onto their key/value head inside the product; keys and
    values are never expanded to ``Hq`` heads, and their widths may
    differ.

    ``q_pos [1|B, Tq]`` and ``k_pos [1|B, Tk]`` give the position of
    every query and of what every key place holds (negative: nothing);
    a query attends ``0 <= q_pos - k_pos`` and, with ``window``,
    ``q_pos - k_pos < window``.  ``pad [1|B, Tk]`` masks padding keys.
    ``sink [Hq]`` adds ``exp(sink[h])`` to every softmax denominator of
    head ``h``: a place that takes weight and gives no value.  The scores
    are scaled by ``scale`` (default ``d ** -0.5``).  Scores and softmax
    are float32, and so is the result ``[B, Hq, Tq, dv]``."""
    B, Hq, Tq, d = q.shape
    Hkv, Tk, dv = k.shape[1], k.shape[2], v.shape[3]
    G = Hq // Hkv
    dist = q_pos[:, :, None] - k_pos[:, None, :]             # [1|B, Tq, Tk]
    ok = (dist >= 0) & (k_pos[:, None, :] >= 0)
    if window is not None:
        ok = ok & (dist < window)
    if pad is not None:
        ok = ok & ~pad[:, None, :]
    bias = jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32)
    scale = jnp.float32(1.0 / math.sqrt(d) if scale is None else scale)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(Hkv, G)

    def heads(qh, kh, vh, bias_t, sink_g):
        # qh [..., G, t, d]; kh [..., Tk, d]; vh [..., Tk, dv];
        # bias_t [..., 1, t, Tk]; sink_g [..., G]
        s = jnp.einsum("...gtd,...kd->...gtk", qh, kh,
                       preferred_element_type=jnp.float32) * scale + bias_t
        m = jnp.max(s, axis=-1, keepdims=True)
        if sink_g is not None:
            sk = sink_g[..., None, None]
            m = jnp.maximum(m, sk)
        # the row maxima are made before they are used: fused into the
        # subtraction, the TPU compiler turns max-then-broadcast into a
        # reduce-window as wide as the row (a 256-token chunk over a
        # 6,144-place row took 27 ms a layer in that one fusion, of the
        # chunk program's 73)
        m = jax.lax.optimization_barrier(m)
        e = jnp.exp(s - m)
        den = jnp.sum(e, axis=-1, keepdims=True)
        if sink_g is not None:
            den = den + jnp.exp(sk - m)
        # normalised after the product with the values: one pass fewer
        # over the scores
        ctx = jnp.einsum("...gtk,...kd->...gtd", e.astype(vh.dtype), vh,
                         preferred_element_type=jnp.float32)
        return ctx / den

    qg = q.reshape(B, Hkv, G, Tq, d)
    if B * Hq * Tq * Tk * 4 <= SCORE_BYTES:
        return heads(qg, k, v, bias[:, None, None], sink).reshape(
            B, Hq, Tq, dv)
    # one key/value head at a time, the queries in blocks of tb
    tb = Tq
    while B * G * tb * Tk * 4 > SCORE_BYTES and tb % 2 == 0:
        tb //= 2
    nb = Tq // tb
    qb = qg.reshape(B, Hkv, G, nb, tb, d).transpose(1, 3, 0, 2, 4, 5)
    bias_b = bias.reshape(bias.shape[0], nb, tb, Tk).transpose(1, 0, 2, 3)
    kt, vt = k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3)
    sinks = jnp.zeros((Hkv, 0)) if sink is None else sink

    def one_head(args):
        qh, kh, vh, sg = args                    # qh [nb, B, G, tb, d]
        sg = None if sink is None else sg
        return jax.lax.map(lambda a: heads(a[0], kh, vh, a[1][:, None], sg),
                           (qh, bias_b))
    out = jax.lax.map(one_head, (qb, kt, vt, sinks))  # [Hkv,nb,B,G,tb,dv]
    return out.transpose(2, 0, 3, 1, 4, 5).reshape(B, Hq, Tq, dv)


def _write_rows(cache, k, v, place):
    """``k [B, h, 1, d]`` and ``v [B, h, 1, dv]`` into ``cache``'s leaves
    ``[B, h, L, d]`` and ``[B, h, L, dv]``, row ``b`` at ``place[b]``;
    returns the written ``{"k", "v"}``.  On a TPU, leaves that tile are
    written by one program a layer (``ops.write_cache_rows``, which takes
    each leaf as it lies); everywhere else by one
    ``dynamic_update_slice`` a row and leaf (a static loop), so a donated
    leaf is updated in place in whatever layout it has (see
    ``TransformerLM._decode_step_rows``).  Either way nothing is read
    back whole: a read-modify-write of the row's old value made the TPU
    compiler keep the leaf slots-minor and copy it in and out on every
    step."""
    tiles = cache_kernels.cache_row_writer(
        cache["k"].shape, cache["v"].shape, cache["k"].dtype)
    with jax.named_scope("cache/write"):
        if tiles is not None:
            leaves = cache_kernels.write_cache_rows(
                cache["k"], cache["v"], k, v, place, tiles=tiles,
                interpret=not attention_kernels._on_tpu())
            return dict(zip(("k", "v"), leaves))
        kv = {}
        for n, new in (("k", k), ("v", v)):
            leaf, new = cache[n], new.astype(cache[n].dtype)
            for b in range(new.shape[0]):
                leaf = jax.lax.dynamic_update_slice(leaf, new[b:b + 1],
                                                    (b, 0, place[b], 0))
            kv[n] = leaf
        return kv


def _write_window(leaf, new, row, start, ring: bool):
    """``new [B, h, W, d]`` (``W`` consecutive positions from ``start``)
    into rows ``row .. row+B`` of ``leaf [S, h, L, d]``; returns the leaf
    and those rows as they now are.

    A ``full`` leaf takes one window at ``start``, and the rows are
    sliced back out.  A ring takes position ``p`` at place
    ``p % (L - 1)`` (the last place is the spare) and the window may
    wrap, so the rows are read whole, the chunk's places replaced, and
    the rows written back whole: a ring row is short (the window and a
    chunk), and a window read back out of the leaf at a traced place
    made the TPU compiler relayout the whole leaf on every call."""
    new = new.astype(leaf.dtype)
    B, h, W, d = new.shape
    L = leaf.shape[2]
    if not ring:
        leaf = jax.lax.dynamic_update_slice(leaf, new, (row, 0, start, 0))
        return leaf, jax.lax.dynamic_slice(leaf, (row, 0, 0, 0),
                                           (B, h, L, d))
    R = L - 1
    if W > R:
        raise ValueError(f"a chunk of {W} positions does not fit a ring "
                         f"of {R}")
    rows = jax.lax.dynamic_slice(leaf, (row, 0, 0, 0), (B, h, L, d))
    # place j takes chunk offset (j - start) mod R where that is < W
    place = jnp.arange(L)
    off = jnp.mod(place - start, R)
    mine = ((off < W) & (place < R))[None, None, :, None]
    rows = jnp.where(mine, jnp.take(new, jnp.minimum(off, W - 1), axis=2),
                     rows)
    return jax.lax.dynamic_update_slice(leaf, rows, (row, 0, 0, 0)), rows


class AttentionSink(Module):
    """One learned logit a query head: a place in every softmax that
    takes weight and gives no value (``add_swa_attention_sink_bias``)."""

    def __init__(self, num_heads: int):
        super().__init__()
        self.bias = Parameter(jnp.zeros(num_heads))


class HeadNorm(Module):
    """``x / rms(x) * gain`` over a head's width, in float32: one learned
    gain a dim, shared by the heads it is applied to."""

    def __init__(self, head_dim: int, eps: float = 1e-5):
        super().__init__()
        self.eps = eps
        self.weight = Parameter(jnp.ones(head_dim))

    def forward(self, x):
        x = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.eps) \
            * self.weight.astype(jnp.float32)


class GroupedQueryAttention(Module):
    """Causal self-attention with fewer key/value heads than query
    heads, keys ``head_dim`` wide and values ``v_head_dim``, for the two
    layer kinds of a hybrid pattern:

    * a **window** layer (``window`` given) attends the last ``window``
      positions, may carry a learned ``sink`` logit per head, and its
      cache may be a ring;
    * a **full** layer attends everything before it.

    With ``rotary_dim`` the first ``rotary_dim`` dims of every query and
    key head are rotated by position (:func:`rotary_half`, base
    ``rope_theta``: a layer kind has its own).  The keys are scaled by
    ``key_scale`` as they leave their projection and the context by
    ``value_scale`` before the output projection.  With ``qk_norm`` every
    query head and every key head goes through an RMS norm over its
    ``head_dim`` (:class:`HeadNorm`: ``q_norm`` and ``k_norm``, a learned
    gain each, eps ``norm_eps``) as it leaves its projection, **before**
    it is rotated (named scope ``attn/qk_norm``); the cache then holds
    normed, rotated keys.  With ``gate`` the attended context is
    multiplied by ``sigmoid(W_a x)`` before the output projection
    (``gate_layer``, as wide as the heads' output, on the very input that
    made the queries; float32; named scope ``attn/gate``): ``y = W_o (ctx
    * sigmoid(W_a x))``.  No bias, scores over ``sqrt(head_dim)``.
    :meth:`forward` is the one entry, so the norm and the gate are on
    every path: a full forward, a prefill that returns compact keys and
    values, a prefill chunk and a decode step differ only in whether a
    cache is passed and what ``index`` is (no ``write``: a chunk's rows
    walk such a layer whole; what they read, :meth:`chunk_key_block`)."""

    def __init__(self, hidden_size: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, v_head_dim: Optional[int] = None,
                 window: Optional[int] = None, rope_theta: float = 10000.0,
                 rotary_dim: int = 0, sink: bool = False,
                 value_scale: float = 1.0, key_scale: float = 1.0,
                 qk_norm: bool = False, norm_eps: float = 1e-5,
                 gate: bool = False):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if rotary_dim % 2 or rotary_dim > head_dim:
            raise ValueError(f"rotary_dim {rotary_dim} must be even and at "
                             f"most head_dim {head_dim}")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = head_dim
        self.v_head_dim = head_dim if v_head_dim is None else v_head_dim
        self.window = None if window is None else int(window)
        self.rope_theta, self.rotary_dim = float(rope_theta), int(rotary_dim)
        self.value_scale = float(value_scale)
        self.key_scale = float(key_scale)
        self.q_layer = Linear(hidden_size, num_heads * head_dim,
                              with_bias=False)
        self.k_layer = Linear(hidden_size, num_kv_heads * head_dim,
                              with_bias=False)
        self.v_layer = Linear(hidden_size, num_kv_heads * self.v_head_dim,
                              with_bias=False)
        self.output_layer = Linear(num_heads * self.v_head_dim, hidden_size,
                                   with_bias=False)
        if sink:
            self.sink = AttentionSink(num_heads)
        self.has_sink = bool(sink)
        self.has_qk_norm = bool(qk_norm)
        if qk_norm:
            self.q_norm = HeadNorm(head_dim, norm_eps)
            self.k_norm = HeadNorm(head_dim, norm_eps)
        self.has_gate = bool(gate)
        if gate:
            self.gate_layer = Linear(hidden_size,
                                     num_heads * self.v_head_dim,
                                     with_bias=False)

    def cache_length(self, max_len: int, ring_margin: int = 1) -> int:
        """Places of one cache row.  A ``full`` row holds ``max_len``
        positions.  A ring holds the window and what a prefill chunk of
        up to ``ring_margin`` positions needs beside it (the chunk is
        written before it is attended, and must not overwrite keys its
        first query still needs): ``window + ring_margin - 1`` ring
        places, ``max_len`` at most, and the spare place
        (:func:`cache_positions`)."""
        if self.window is None:
            return max_len
        return 1 + min(max_len, self.window + max(int(ring_margin), 1) - 1)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32,
                   ring_margin: int = 1):
        length = self.cache_length(max_len, ring_margin)
        return {"k": jnp.zeros((batch, self.num_kv_heads, length,
                                self.head_dim), dtype),
                "v": jnp.zeros((batch, self.num_kv_heads, length,
                                self.v_head_dim), dtype)}

    def cache_kind(self, max_len: int):
        """What the layer declares to a slot pool: a ring of its window,
        or a full row of ``max_len`` positions."""
        return ("full", max_len) if self.window is None \
            else ("ring", self.window)

    def decode_key_block(self, cache) -> Optional[int]:
        """Places of a row that the per-row decode step (``index [B]``)
        over ``cache`` attends at a time, through
        ``ops.ragged_decode_attention``; None where it attends every
        place of every row through :func:`grouped_attention`: a window
        layer (its ring is position-mapped and may carry a sink; it is
        short where the window is a few hundred positions, and at a
        window of 2,048 beside a chunk of 256 it is 2,304 places that
        every step reads of every slot, live or not: the serving pool
        counts them, ``ring_positions_live`` and ``ring_positions_read``),
        rows that do not tile, and every backend but a TPU
        (``ops.decode_key_block``)."""
        if self.window is not None or self.has_sink:
            return None
        return attention_kernels.decode_key_block(
            cache["k"].shape, cache["v"].shape, cache["k"].dtype)

    def _heads(self, x, layer, n, d):
        # float32 out of the product: rotated, then rounded once
        y = jnp.einsum("bti,oi->bto", x, layer.weight,
                       preferred_element_type=jnp.float32)
        b, t, _ = y.shape
        return y.reshape(b, t, n, d).transpose(0, 2, 1, 3)

    def forward(self, x, index=0, cache=None, pad=None, slot=None,
                active=None):
        """``x [B, T, H]`` (normed) at positions ``index .. index+T-1``
        -> ``(y [B, T, H] float32, kv)``.

        * ``cache=None``: the sequence attends itself; ``kv`` is its
          compact ``{"k": [B, Hkv, T, d], "v": [B, Hkv, T, dv]}`` (keys
          rotated), what a bucketed prefill scatters into slots.
          ``pad [B, T]``.
        * ``cache`` given, scalar ``index``: the new keys and values are
          written at ``index ..`` of every row (of row ``slot`` alone
          when given: the pool's chunked prefill, ``B == 1``) and the
          queries attend the row: in a full layer with no sink the key
          blocks up to the chunk's last position and no place beyond,
          out of the leaves as they lie (``ops.chunk_attention``,
          :meth:`chunk_key_block`); a window layer its ring, whole.
          ``pad [B|S, max_len]`` by position.
        * ``cache`` given, ``index [B]``, ``T == 1``: the pool's decode
          step, row ``b`` written and masked at ``index[b]``.  A row
          whose ``active[b]`` is false only rides along: in a ring it
          writes the spare place (in a ``full`` row the caller gives it
          an ``index`` that is rewritten before it is read).

        ``kv`` is then the updated cache."""
        B, T, _ = x.shape
        ring = self.window is not None
        per_row = jnp.ndim(index) == 1
        q = self._heads(x, self.q_layer, self.num_heads, self.head_dim)
        k = self._heads(x, self.k_layer, self.num_kv_heads,
                        self.head_dim)
        v = self._heads(x, self.v_layer, self.num_kv_heads,
                        self.v_head_dim)
        if self.has_qk_norm:
            with jax.named_scope("attn/qk_norm"):
                q, k = self.q_norm.forward(q), self.k_norm.forward(k)
        if self.key_scale != 1.0:
            k = k * self.key_scale
        index = jnp.asarray(index, jnp.int32)
        q_pos = (index[:, None] if per_row else index[None, None]) \
            + jnp.arange(T, dtype=jnp.int32)[None, :]       # [1|B, T]
        if self.rotary_dim:
            q = rotary_half(q, q_pos[:, None, :], self.rope_theta,
                            self.rotary_dim)
            k = rotary_half(k, q_pos[:, None, :], self.rope_theta,
                            self.rotary_dim)
        q, k, v = (a.astype(x.dtype) for a in (q, k, v))
        block = chunk = None
        if cache is None:
            kv = {"k": k, "v": v}
            keys, vals, k_pos = k, v, q_pos
        else:
            L = cache["k"].shape[2]
            if per_row:
                if T != 1:
                    raise ValueError("a position per row takes T == 1")
                place = index
                if ring:
                    place = jnp.mod(index, L - 1)
                    if active is not None:
                        place = jnp.where(active, place, L - 1)
                kv = _write_rows(cache, k, v, place)
                keys, vals = kv["k"], kv["v"]
                last = index
                block = self.decode_key_block(kv)
            else:
                if slot is None and cache["k"].shape[0] != B:
                    raise ValueError("a cache of other rows than x "
                                     "takes a slot")
                row = 0 if slot is None else slot
                chunk = self.chunk_key_block(cache)
                kv, rows = {}, {}
                for n, new in (("k", k), ("v", v)):
                    kv[n], rows[n] = _write_window(cache[n], new, row,
                                                   index, ring)
                keys, vals = rows["k"], rows["v"]
                last = index + (T - 1)
            k_pos = cache_positions(L, last, ring)
            if pad is not None and chunk is None:
                # pad is by position; a ring place holds position k_pos
                if slot is not None:
                    pad = jax.lax.dynamic_slice(
                        pad, (slot, 0), (1, pad.shape[1]))
                if ring:
                    pad = jnp.take_along_axis(
                        pad, jnp.broadcast_to(
                            jnp.maximum(k_pos, 0),
                            (pad.shape[0], L)), axis=1)
        if block is not None:
            # the pool's step over full rows: live key blocks only (the
            # queries go in float32, as do a chunk's: see below)
            lengths = index + 1
            if active is not None:
                lengths = jnp.where(active, lengths, 0)
            ctx = attention_kernels.ragged_decode_attention(
                q.astype(jnp.float32), keys, vals, lengths, pad,
                block_k=block, interpret=not attention_kernels._on_tpu())
        elif chunk is not None:
            # a chunk over full rows: the key blocks up to its last
            # position, out of the leaves as they lie (the rows sliced
            # back out above are read by nothing, and so not made).  The
            # queries go in float32 (they hold x's precision) so that the
            # context comes back in float32, as grouped_attention's does
            if pad is None:
                pad = jnp.zeros((cache["k"].shape[0], L), bool)
            ctx = attention_kernels.chunk_attention(
                q.astype(jnp.float32), kv["k"], kv["v"], row, index, pad)
        else:
            ctx = grouped_attention(
                q, keys, vals, q_pos, k_pos, self.window, pad,
                self.sink.bias if self.has_sink else None)
        ctx = ctx * self.value_scale
        if self.has_gate:
            with jax.named_scope("attn/gate"):
                a = self._heads(x, self.gate_layer, self.num_heads,
                                self.v_head_dim)
                ctx = ctx * jax.nn.sigmoid(a)
        ctx = ctx.astype(x.dtype).transpose(0, 2, 1, 3).reshape(B, T, -1)
        y = jnp.einsum("bti,oi->bto", ctx, self.output_layer.weight,
                       preferred_element_type=jnp.float32)
        return y, kv

    # (behind forward: the lines of the calls above are in the lowered
    # text of every kernel they reach)
    def chunk_key_block(self, cache) -> Optional[int]:
        """Places of its rows that a prefill chunk (scalar ``index``)
        over ``cache`` attends at a time, through ``ops.chunk_attention``:
        the key blocks up to the chunk's last position and no place
        beyond, on every backend, so that no array of scores as long as
        the row exists; None where it attends its rows whole through
        :func:`grouped_attention`: a window layer (its ring is
        position-mapped and short) and a layer with a sink, the rule
        :meth:`decode_key_block` has."""
        if self.window is not None or self.has_sink:
            return None
        return attention_kernels.chunk_key_block(cache["k"].shape)


class FeedForwardNetwork(Module):
    """Position-wise FFN: Linear→ReLU→Dropout→Linear
    (reference nn/FeedForwardNetwork.scala)."""

    def __init__(self, hidden_size: int, filter_size: int,
                 relu_dropout: float = 0.0):
        super().__init__()
        self.relu_dropout = relu_dropout
        self.filter_layer = Linear(hidden_size, filter_size, with_bias=True)
        self.output_layer = Linear(filter_size, hidden_size, with_bias=True)

    def forward(self, x):
        h = jax.nn.relu(self.filter_layer(x))
        if self.training and self.relu_dropout > 0.0:
            keep = jax.random.bernoulli(
                next_rng_key(), 1.0 - self.relu_dropout, h.shape)
            h = jnp.where(keep, h / (1.0 - self.relu_dropout), 0.0)
        return self.output_layer(h)


def _residual_dropout(x, p, training):
    if training and p > 0.0:
        keep = jax.random.bernoulli(next_rng_key(), 1.0 - p, x.shape)
        return jnp.where(keep, x / (1.0 - p), 0.0)
    return x


class TransformerEncoderLayer(Module):
    """Pre-norm encoder block: LN→self-attn→dropout→residual;
    LN→FFN→dropout→residual (reference Transformer.scala block(),
    encode branch)."""

    def __init__(self, hidden_size, num_heads, filter_size,
                 attention_dropout=0.0, ffn_dropout=0.0):
        super().__init__()
        self.ffn_dropout = ffn_dropout
        self.attn_norm = LayerNormalization(hidden_size)
        self.attn = Attention(hidden_size, num_heads, attention_dropout)
        self.ffn_norm = LayerNormalization(hidden_size)
        self.ffn = FeedForwardNetwork(hidden_size, filter_size, ffn_dropout)

    def forward(self, x, bias=None):
        y = self.attn(self.attn_norm(x), None, bias)
        x = x + _residual_dropout(y, self.ffn_dropout, self.training)
        y = self.ffn(self.ffn_norm(x))
        return x + _residual_dropout(y, self.ffn_dropout, self.training)


class TransformerDecoderLayer(Module):
    """Pre-norm decoder block: self-attn (causal) [+ cross-attn] + FFN
    (reference Transformer.scala block(), decode branch)."""

    def __init__(self, hidden_size, num_heads, filter_size,
                 attention_dropout=0.0, ffn_dropout=0.0,
                 with_cross_attention=True):
        super().__init__()
        self.ffn_dropout = ffn_dropout
        self.with_cross_attention = with_cross_attention
        self.self_norm = LayerNormalization(hidden_size)
        self.self_attn = Attention(hidden_size, num_heads, attention_dropout)
        if with_cross_attention:
            self.cross_norm = LayerNormalization(hidden_size)
            self.cross_attn = Attention(hidden_size, num_heads,
                                        attention_dropout)
        self.ffn_norm = LayerNormalization(hidden_size)
        self.ffn = FeedForwardNetwork(hidden_size, filter_size, ffn_dropout)

    def forward(self, x, self_bias=None, enc_out=None, enc_bias=None,
                cache=None, cache_index=None, self_causal=False):
        new_cache = None
        if cache is not None:
            if self_causal and self_bias is None:
                # the intent cannot be honored on the cache path (see
                # Attention.forward): decode callers carry causality in
                # the position bias
                raise ValueError(
                    "self_causal with a decode cache needs the decode "
                    "position mask passed as self_bias; the kernel-side "
                    "causal mask only applies to full-sequence forwards")
            y, self_cache = self.self_attn(
                self.self_norm(x), None, self_bias,
                cache=cache["self"], cache_index=cache_index)
            new_cache = dict(cache)
            new_cache["self"] = self_cache
        else:
            y = self.self_attn(self.self_norm(x), None, self_bias,
                               causal=self_causal)
        x = x + _residual_dropout(y, self.ffn_dropout, self.training)
        if self.with_cross_attention and enc_out is not None:
            if cache is not None and "cross" in cache:
                y, _ = self.cross_attn(self.cross_norm(x), enc_out, enc_bias,
                                       cache=cache["cross"])
            else:
                y = self.cross_attn(self.cross_norm(x), enc_out, enc_bias)
            x = x + _residual_dropout(y, self.ffn_dropout, self.training)
        y = self.ffn(self.ffn_norm(x))
        x = x + _residual_dropout(y, self.ffn_dropout, self.training)
        if cache is not None:
            return x, new_cache
        return x


class Transformer(Module):
    """Full transformer (reference nn/Transformer.scala:53).

    transformer_type:
      * "lm" — decoder-only language model: ``forward(tokens[B,T])`` →
        logits [B,T,vocab] when with_share_weights_linear (shared
        embedding/softmax matrix, reference shareWeights) else hidden
        [B,T,H].
      * "translation" — encoder-decoder: ``forward(src[B,Ts],
        tgt[B,Tt])`` → decoder hidden/logits.

    Token ids are 1-based with ``padding_value`` (default 0) as padding,
    matching the reference's LookupTable(paddingValue, maskZero=true).
    """

    def __init__(self, vocab_size: int, hidden_size: int, num_heads: int,
                 filter_size: int, num_hidden_layers: int,
                 embedding_dropout: float = 0.0,
                 attention_dropout: float = 0.0,
                 ffn_dropout: float = 0.0,
                 padding_value: float = 0.0,
                 with_share_weights_linear: bool = False,
                 transformer_type: str = "lm"):
        super().__init__()
        if transformer_type not in ("lm", "translation"):
            raise ValueError(transformer_type)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.embedding_dropout = embedding_dropout
        self.padding_value = padding_value
        self.with_share_weights_linear = with_share_weights_linear
        self.transformer_type = transformer_type
        from bigdl_tpu.utils.rng import next_key
        self.embedding = Parameter(
            jax.random.normal(next_key(), (vocab_size, hidden_size))
            * (hidden_size ** -0.5))
        if transformer_type == "translation":
            self.encoder_layers = ModuleList([
                TransformerEncoderLayer(hidden_size, num_heads, filter_size,
                                        attention_dropout, ffn_dropout)
                for _ in range(num_hidden_layers)])
            self.encoder_norm = LayerNormalization(hidden_size)
        self.decoder_layers = ModuleList([
            TransformerDecoderLayer(
                hidden_size, num_heads, filter_size, attention_dropout,
                ffn_dropout,
                with_cross_attention=(transformer_type == "translation"))
            for _ in range(num_hidden_layers)])
        self.decoder_norm = LayerNormalization(hidden_size)

    # -- embedding ---------------------------------------------------------

    def embed(self, tokens):
        """LookupTable(padding→0) * sqrt(H) (reference buildLM embedding)."""
        idx = jnp.clip(tokens.astype(jnp.int32) - 1, 0, self.vocab_size - 1)
        emb = self.embedding[idx] * math.sqrt(self.hidden_size)
        mask = (tokens != self.padding_value)
        return emb * mask[..., None].astype(emb.dtype)

    def logits(self, hidden):
        """Project to vocab with the shared embedding matrix
        (reference linearSharedWeigths/shareWeights)."""
        return jnp.einsum("bth,vh->btv", hidden, self.embedding)

    # -- topologies --------------------------------------------------------

    def _decoder_input(self, emb):
        t = emb.shape[1]
        x = shift_right_3d(emb) + position_encoding(
            t, self.hidden_size, dtype=emb.dtype)
        return _residual_dropout(x, self.embedding_dropout, self.training)

    def encode(self, src):
        emb = self.embed(src)
        bias = padding_bias(src, self.padding_value)
        x = emb + position_encoding(emb.shape[1], self.hidden_size,
                                    dtype=emb.dtype)
        x = _residual_dropout(x, self.embedding_dropout, self.training)
        for layer in self.encoder_layers:
            x = layer(x, bias)
        return self.encoder_norm(x), bias

    def decode(self, tgt, enc_out=None, enc_bias=None):
        emb = self.embed(tgt)
        x = self._decoder_input(emb)
        self_bias = causal_bias(x.shape[1], x.dtype)
        for layer in self.decoder_layers:
            x = layer(x, self_bias, enc_out, enc_bias)
        x = self.decoder_norm(x)
        if self.with_share_weights_linear:
            return self.logits(x)
        return x

    def forward(self, *inputs):
        if self.transformer_type == "lm":
            (tokens,) = inputs
            return self.decode(tokens)
        src, tgt = inputs
        enc_out, enc_bias = self.encode(src)
        return self.decode(tgt, enc_out, enc_bias)

    # -- incremental decoding (used by SequenceBeamSearch) -----------------

    def init_decode_cache(self, batch: int, max_length: int,
                          dtype=jnp.float32, enc_out=None):
        """Fixed-size decode cache; when ``enc_out`` (encoder output) is
        given, each layer's cross-attention K/V is projected ONCE and
        cached (the reference re-projects per step via joinK/joinV)."""
        cache = []
        for layer in self.decoder_layers:
            entry = {"self": layer.self_attn.init_cache(
                batch, max_length, dtype)}
            if enc_out is not None and layer.with_cross_attention:
                ca = layer.cross_attn
                entry["cross"] = {
                    "k": ca._split_heads(ca.k_layer(enc_out)).astype(dtype),
                    "v": ca._split_heads(ca.v_layer(enc_out)).astype(dtype),
                }
            cache.append(entry)
        return cache

    def decode_step(self, token, step, cache, enc_out=None, enc_bias=None):
        """One decode step: token [B, 1] at position ``step`` (0-based
        traced int), fixed-size cache.  Returns (out [B, vocab] when
        with_share_weights_linear else hidden [B, H], new_cache) —
        consistent with decode()/forward(); wire an external head in
        your logits_fn when weights aren't shared.  ≙ reference
        Transformer.symbols (Transformer.scala) but with static
        shapes."""
        emb = self.embed(token)  # [B, 1, H]
        max_len = cache[0]["self"]["k"].shape[2]
        pos = position_encoding(max_len, self.hidden_size, dtype=emb.dtype)
        x = emb + jax.lax.dynamic_slice_in_dim(pos, step, 1, axis=0)[None]
        self_bias = incremental_bias(max_len, step)
        new_cache = []
        for layer, layer_cache in zip(self.decoder_layers, cache):
            x, lc = layer(x, self_bias, enc_out, enc_bias,
                          cache=layer_cache, cache_index=step)
            new_cache.append(lc)
        x = self.decoder_norm(x)
        if self.with_share_weights_linear:
            return self.logits(x)[:, 0, :], new_cache
        return x[:, 0, :], new_cache


# ---------------------------------------------------------------------------
# Beam search (reference nn/SequenceBeamSearch.scala)
# ---------------------------------------------------------------------------

class SequenceBeamSearch(Module):
    """Length-normalized beam search over a ``symbols_to_logits`` step
    function (reference nn/SequenceBeamSearch.scala:37).

    The search state is a fixed-shape pytree advanced by a jitted step;
    the loop runs ``lax.while_loop`` with the reference's early-stop
    condition (best alive score can no longer beat worst finished score
    under length normalization ``((5+len)/6)^alpha``,
    SequenceBeamSearch.scala lengthNormalization:89).
    """

    def __init__(self, vocab_size: int, beam_size: int, alpha: float,
                 max_decode_length: int, eos_id: int,
                 padding_value: float = 0.0):
        super().__init__()
        self.vocab_size = vocab_size
        self.beam_size = beam_size
        self.alpha = alpha
        self.max_decode_length = max_decode_length
        self.eos_id = eos_id
        self.padding_value = padding_value
        self._logits_fn = None

    def set_logit_fn(self, fn):
        """fn(flat_ids[B*beam, 1], step, cache) -> (logits[B*beam, V],
        cache)  (reference setLogitFn:309)."""
        self._logits_fn = fn
        return self

    def _length_norm(self, length):
        return ((5.0 + length) / 6.0) ** self.alpha

    def search(self, batch_size: int, initial_cache):
        """Run the search; returns (seq [B, beam, T+1], scores [B, beam])."""
        assert self._logits_fn is not None, "call set_logit_fn first"
        beam, vocab = self.beam_size, self.vocab_size
        tmax = self.max_decode_length

        def flatten(x):  # [B, beam, ...] -> [B*beam, ...]
            return x.reshape((batch_size * beam,) + x.shape[2:])

        def unflatten(x):
            return x.reshape((batch_size, beam) + x.shape[1:])

        neg = jnp.float32(_NEG_INF)
        alive_seq = jnp.zeros((batch_size, beam, tmax + 1), jnp.int32)
        alive_log_probs = jnp.tile(
            jnp.array([[0.0] + [float(_NEG_INF)] * (beam - 1)], jnp.float32),
            (batch_size, 1))
        finished_seq = jnp.zeros_like(alive_seq)
        finished_scores = jnp.full((batch_size, beam), neg)
        finished_flags = jnp.zeros((batch_size, beam), bool)
        # replicate the cache across beams
        cache = jax.tree_util.tree_map(
            lambda x: flatten(jnp.broadcast_to(
                x[:, None], (batch_size, beam) + x.shape[1:])),
            initial_cache)

        state = (jnp.int32(0), alive_seq, alive_log_probs, finished_seq,
                 finished_scores, finished_flags, cache)

        def cond(state):
            i, _, alive_lp, _, fin_scores, fin_flags, _ = state
            max_alive = alive_lp[:, 0] / self._length_norm(tmax)
            worst_fin = jnp.min(
                jnp.where(fin_flags, fin_scores, neg), axis=1)
            worst_fin = jnp.where(jnp.any(fin_flags, 1), worst_fin, neg)
            bound_met = jnp.all(worst_fin >= max_alive)
            return jnp.logical_and(i < tmax, jnp.logical_not(bound_met))

        def body(state):
            i, alive_seq, alive_lp, fin_seq, fin_scores, fin_flags, cache \
                = state
            ids = jax.lax.dynamic_slice_in_dim(alive_seq, i, 1, axis=2)
            logits, cache = self._logits_fn(flatten(ids), i, cache)
            log_probs = jax.nn.log_softmax(logits.astype(jnp.float32))
            log_probs = unflatten(log_probs) + alive_lp[:, :, None]
            flat_lp = log_probs.reshape(batch_size, beam * vocab)
            # 2*beam candidates so EOS-heavy rows keep enough alive beams
            top_lp, top_idx = jax.lax.top_k(flat_lp, 2 * beam)
            beam_idx = top_idx // vocab
            token_id = top_idx % vocab
            cand_seq = jnp.take_along_axis(
                alive_seq, beam_idx[:, :, None], axis=1)
            cand_seq = jax.lax.dynamic_update_slice_in_dim(
                cand_seq, token_id[:, :, None].astype(jnp.int32), i + 1,
                axis=2)
            is_eos = token_id == self.eos_id
            # new alive = best beam non-EOS candidates
            alive_cand_lp = jnp.where(is_eos, neg, top_lp)
            new_alive_lp, alive_sel = jax.lax.top_k(alive_cand_lp, beam)
            new_alive_seq = jnp.take_along_axis(
                cand_seq, alive_sel[:, :, None], axis=1)
            sel_beam = jnp.take_along_axis(beam_idx, alive_sel, axis=1)
            cache = jax.tree_util.tree_map(
                lambda x: flatten(jnp.take_along_axis(
                    unflatten(x),
                    sel_beam.reshape(sel_beam.shape + (1,) * (x.ndim - 1)),
                    axis=1)),
                cache)
            # finished pool = old finished + EOS candidates, keep top beam
            cand_scores = jnp.where(
                is_eos, top_lp / self._length_norm(i + 1), neg)
            pool_scores = jnp.concatenate([fin_scores, cand_scores], 1)
            pool_flags = jnp.concatenate(
                [fin_flags, is_eos], 1)
            pool_seq = jnp.concatenate([fin_seq, cand_seq], 1)
            new_fin_scores, fin_sel = jax.lax.top_k(pool_scores, beam)
            new_fin_seq = jnp.take_along_axis(
                pool_seq, fin_sel[:, :, None], axis=1)
            new_fin_flags = jnp.take_along_axis(pool_flags, fin_sel, axis=1)
            return (i + 1, new_alive_seq, new_alive_lp, new_fin_seq,
                    new_fin_scores, new_fin_flags, cache)

        (i, alive_seq, alive_lp, fin_seq, fin_scores, fin_flags, _) = \
            jax.lax.while_loop(cond, body, state)
        # rows with no finished hypothesis fall back to alive beams
        any_fin = jnp.any(fin_flags, axis=1, keepdims=True)
        seq = jnp.where(any_fin[:, :, None], fin_seq, alive_seq)
        scores = jnp.where(any_fin, fin_scores,
                           alive_lp / self._length_norm(tmax))
        return seq[:, :, 1:], scores

    def forward(self, batch_size, initial_cache):
        return self.search(int(batch_size), initial_cache)

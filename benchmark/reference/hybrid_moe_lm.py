"""The language model of ``mimo_v2`` (MiMo-V2.5,
https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json) in plain
float32.  Pre-norm, sequential; one layer is

    h = x + Attn_i(RMSNorm(x))            eps ``layernorm_epsilon``
    y = h + FFN_i(RMSNorm(h))

``Attn``: ``num_attention_heads`` query heads of ``head_dim``; keys of
``head_dim`` and values of ``v_head_dim`` on fewer heads (query head ``h``
reads key/value head ``h // group``); no bias, no q/k norm; the first
``int(partial_rotary_factor * head_dim)`` dims of every query and key head
rotated by position, dim ``j`` paired with ``j + half``; scores over
``sqrt(head_dim)``; the context times ``attention_value_scale`` before the
output projection.  ``hybrid_layer_pattern[i]`` 0 is a **full** layer
(``num_key_value_heads``, base ``rope_theta``, causal), 1 a **window** layer
(``swa_num_key_value_heads``, base ``swa_rope_theta``, mask
``0 <= i - j < sliding_window``, and a learned sink per head:
``P_ij = exp(a_ij) / (exp(s_h) + sum_j' exp(a_ij'))``).

``FFN``: where ``moe_layer_freq[i]`` is 0, ``W_d(silu(W_g n) * W_u n)`` of
width ``intermediate_size``; else ``s = sigmoid(n W_r)`` over
``n_routed_experts``, the ``num_experts_per_tok`` largest of ``s + b``
chosen (``b`` the ``noaux_tc`` selection bias; one group), ``w_e = s_e`` over
the sum of the chosen (``norm_topk_prob``), each expert
``W_d(silu(W_g n) * W_u n)`` of width ``moe_intermediate_size``; no shared
expert, no scaling factor.  Final RMSNorm, an untied head, the embedding not
scaled.

No cache, no grouping of tokens: every held expert runs over every token
and is weighted by the routing (zero where it was not chosen), one expert
at a time.  Given a held share (``experts_held`` of ``n_routed_experts``,
from ``experts_offset``) and a sliced vocabulary it leaves out what the
absent experts would add, as the program on one chip of the deployment does.

Departures and readings, each also under ``assumed`` in the configuration:
the half-split rotary pairing; ``attention_chunk_size`` read as a block size
equal to the window (no further mask); token ids 1-based (``TOKEN_BASE``),
column ``j`` of the head scoring token ``j + 1``; the expert stacks lie
``[experts, in, out]``; the MTP layers and the vision and audio towers are
left out.

Weights come as a dict ``path -> array`` from ``harness.weights``.  This
file imports nothing of the program.
"""
from __future__ import annotations

import math
from typing import Any, Dict

from reference.precision import quantizer

NEG = -1e9
TOKEN_BASE = 1      # column j of the head's logits scores token id j + 1

# scores of one request's attention, [heads, Tq, T] float32, are held to
# this by taking the queries Q_BLOCK at a time
SCORES_BYTES = 1 << 30
Q_BLOCK = 512


def rms_norm(x, gain, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def rotary(x, theta: float, rot: int):
    """``x [B, heads, T, d]`` with its first ``rot`` dims rotated by
    position ``t``: the pair ``(x[j], x[j + rot/2])`` turns by
    ``t * theta**(-2j/rot)``; the other dims pass."""
    import jax.numpy as jnp
    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def attention(qh, kh, vh, q, window, sink, in_blocks: bool):
    """``qh [B, Hq, T, d]`` over ``kh [B, Hkv, T, d]``, ``vh [B, Hkv, T,
    dv]``: causal, within ``window`` when given, ``sink [Hq]`` in every
    denominator when given.  The queries of a group share their key/value
    head inside the product (the same numbers as repeating it).
    ``in_blocks``: where the scores of the whole sequence pass SCORES_BYTES
    the queries go Q_BLOCK at a time, each block against every key: the
    same rows of the same softmax."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, Hq, T, d = qh.shape
    Hkv, dv = kh.shape[1], vh.shape[3]
    G = Hq // Hkv
    qg = qh.reshape(B, Hkv, G, T, d)
    cols = jnp.arange(T)

    def rows(q_rows, first):
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q(q_rows), q(kh),
                       precision=hi) / math.sqrt(d)
        dist = (first + jnp.arange(q_rows.shape[3]))[:, None] - cols[None, :]
        mask = dist >= 0
        if window is not None:
            mask = mask & (dist < window)
        s = jnp.where(mask, s, NEG)
        if sink is None:
            w = jax.nn.softmax(s, axis=-1)
        else:
            sk = sink.reshape(Hkv, G)[None, :, :, None, None]
            m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sk)
            e = jnp.exp(s - m)
            w = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sk - m))
        return jnp.einsum("bhgqk,bhkd->bhgqd", q(w), q(vh), precision=hi)

    if not in_blocks or B * Hq * T * T * 4 <= SCORES_BYTES or T % Q_BLOCK:
        out = rows(qg, 0)
    else:
        n = T // Q_BLOCK
        blocks = qg.reshape(B, Hkv, G, n, Q_BLOCK, d) \
            .transpose(3, 0, 1, 2, 4, 5)
        out = jax.lax.map(lambda a: rows(a[0], a[1]),
                          (blocks, jnp.arange(n) * Q_BLOCK))
        out = out.transpose(1, 2, 3, 0, 4, 5)
    return out.reshape(B, Hq, T, dv)


def gated(x, wg, wu, wd, q):
    """``W_d(silu(W_g x) * W_u x)`` for ``x [T, H]`` and weights lying
    ``[in, out]``."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    a = jax.nn.silu(jnp.einsum("th,hf->tf", q(x), q(wg), precision=hi)) \
        * jnp.einsum("th,hf->tf", q(x), q(wu), precision=hi)
    return jnp.einsum("tf,fh->th", q(a), q(wd), precision=hi)


def routing(n, w: Dict[str, Any], cfg: Dict[str, Any], q):
    """``[T, E]``: each token's weight on every expert (zero where the
    expert was not chosen)."""
    import jax
    import jax.numpy as jnp
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.einsum(
        "th,eh->te", q(n), q(w[".ffn.router.weight"]),
        precision=jax.lax.Precision.HIGHEST))
    ranked = s + w[".ffn.router.bias"]
    kth = jnp.sort(ranked, axis=-1)[:, -k][:, None]
    chosen = jnp.where(ranked >= kth, s, 0.0)
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return chosen


def experts(n, w: Dict[str, Any], cfg: Dict[str, Any], q):
    """The held experts' part of the expert layer for ``n [T, H]``."""
    import jax
    import jax.numpy as jnp
    first = cfg.get("experts_offset", 0)
    held = w[".ffn.w_gate"].shape[0]
    weights = routing(n, w, cfg, q)[:, first:first + held].T    # [held, T]

    def one(y, e):
        wg, wu, wd, we = e
        return y + we[:, None] * gated(n, wg, wu, wd, q), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        w[".ffn.w_gate"], w[".ffn.w_up"], w[".ffn.w_down"], weights))
    return y


def layer_kind(cfg: Dict[str, Any], i: int):
    """``(window: bool, sparse: bool)`` of layer ``i``."""
    return bool(cfg["hybrid_layer_pattern"][i]), bool(cfg["moe_layer_freq"][i])


def _block(x, w: Dict[str, Any], cfg: Dict[str, Any], window: bool,
           sparse: bool, q, in_blocks: bool = False):
    """One layer on ``x [B, T, H]``; ``w`` holds its leaves by their names
    inside it (``.attn_norm.weight``)."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, T, H = x.shape
    d, dv = cfg["head_dim"], cfg["v_head_dim"]
    eps = cfg.get("layernorm_epsilon", 1e-5)
    kv = cfg["swa_num_key_value_heads" if window else "num_key_value_heads"]
    theta = cfg["swa_rope_theta" if window else "rope_theta"]
    rot = 2 * (int(cfg["partial_rotary_factor"] * d) // 2)

    n = rms_norm(x, w[".attn_norm.weight"], eps)

    def heads(name, count, width):
        y = jnp.einsum("bti,oi->bto", q(n), q(w[name]), precision=hi)
        return y.reshape(B, T, count, width).transpose(0, 2, 1, 3)

    qh = rotary(heads(".attn.q_layer.weight", cfg["num_attention_heads"], d),
                theta, rot)
    kh = rotary(heads(".attn.k_layer.weight", kv, d), theta, rot)
    vh = heads(".attn.v_layer.weight", kv, dv)
    sink = w[".attn.sink.bias"] if window and cfg.get(
        "add_swa_attention_sink_bias") else None
    ctx = attention(qh, kh, vh, q, cfg["sliding_window"] if window else None,
                    sink, in_blocks)
    ctx = (cfg.get("attention_value_scale") or 1.0) \
        * ctx.transpose(0, 2, 1, 3).reshape(B, T, -1)
    h = x + jnp.einsum("bti,oi->bto", q(ctx),
                       q(w[".attn.output_layer.weight"]), precision=hi)
    n = rms_norm(h, w[".ffn_norm.weight"], eps)
    if sparse:
        f = jax.vmap(lambda rows: experts(rows, w, cfg, q))(n)
    else:
        f = jax.vmap(lambda rows: gated(
            rows, w[".ffn.gate.weight"].T, w[".ffn.up.weight"].T,
            w[".ffn.down.weight"].T, q))(n)
    return h + f


def _inside(params: Dict[str, Any], p: str) -> Dict[str, Any]:
    """The leaves under the path ``p``, by their names inside it."""
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p + ".")}


def logits_of(params: Dict[str, Any], cfg: Dict[str, Any], x, q):
    """Final norm and untied head of ``x [..., H]``."""
    import jax
    import jax.numpy as jnp
    h = rms_norm(x, params[".final_norm.weight"],
                 cfg.get("layernorm_epsilon", 1e-5))
    return jnp.einsum("...h,vh->...v", q(h), q(params[".lm_head.weight"]),
                      precision=jax.lax.Precision.HIGHEST)


def forward(params: Dict[str, Any], cfg: Dict[str, Any], tokens,
            precision: str = "float32"):
    """Logits ``[B, T, vocab]`` of 1-based ``tokens [B, T]``: the whole
    model at once (the tests' sizes)."""
    q = quantizer(precision)
    x = params[".embedding.weight"][tokens - TOKEN_BASE]
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, _inside(params, f".blocks[{i}]"), cfg,
                   *layer_kind(cfg, i), q)
    return logits_of(params, cfg, x, q)


# ---- the model a block at a time, as the serving check walks it -------------
# (``harness.kinds.hybrid_moe_lm.param_blocks`` names the blocks: the
# embedding, each layer, the final norm with the head).  Each step takes only
# its own block's leaves, by their full paths, and the name of the precision
# its matrix products run in; layers of one kind share one compiled program.

_STEPS: Dict[Any, Any] = {}
_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "swa_num_key_value_heads", "head_dim", "v_head_dim",
         "partial_rotary_factor", "rope_theta", "swa_rope_theta",
         "sliding_window", "attention_value_scale",
         "add_swa_attention_sink_bias", "num_experts_per_tok",
         "norm_topk_prob", "experts_offset", "layernorm_epsilon")


def _step(name: str, cfg: Dict[str, Any], precision: str, build, **jit_kw):
    import jax
    key = (name, precision) + tuple(cfg.get(k) for k in _KEYS)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(build(quantizer(precision)), **jit_kw)
    return _STEPS[key]


def embed(params: Dict[str, Any], cfg: Dict[str, Any], tokens):
    """``[B, T]`` 1-based token ids to the residual stream ``[B, T, H]``
    (the embedding is not scaled and no position is added)."""
    return _step("embed", cfg, "float32", lambda q: lambda emb, t:
                 emb[t - TOKEN_BASE])(params[".embedding.weight"], tokens)


def block(params: Dict[str, Any], cfg: Dict[str, Any], i: int, x,
          precision: str = "float32"):
    """Layer ``i`` on ``x [B, T, H]``; ``x`` is given up to the result."""
    window, sparse = layer_kind(cfg, i)
    return _step(f"block.{window}.{sparse}", cfg, precision,
                 lambda q: lambda w, x_: _block(x_, w, cfg, window, sparse, q,
                                                in_blocks=True),
                 donate_argnums=1)(_inside(params, f".blocks[{i}]"), x)


def head(params: Dict[str, Any], cfg: Dict[str, Any], rows,
         precision: str = "float32"):
    """Logits ``[..., vocab]`` of the residual stream's ``rows``."""
    return _step("head", cfg, precision, lambda q: lambda w, r:
                 logits_of(w, cfg, r, q))(params, rows)

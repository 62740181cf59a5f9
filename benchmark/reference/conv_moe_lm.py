"""The language model of ``lfm2_moe`` (LFM2-24B-A2B,
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json) in
plain float32.  Pre-norm, sequential; layer ``i`` of ``layer_types`` is

    h = x + Mix_i(RMSNorm(x))             eps ``norm_eps``, a learned gain
    y = h + FF_i(RMSNorm(h))

``Mix`` of a ``"conv"`` layer, the **gated short convolution**:

    [B ; C ; u] = W_in n                  three thirds of 3 x hidden, in that order
    g_t = B_t * u_t
    c_t = w_0 * g_{t-2} + w_1 * g_{t-1} + w_2 * g_t
                                          depthwise, causal, ``conv_L_cache`` = 3
                                          taps a channel, g zero before the
                                          sequence, no bias, no activation
    out_t = W_out (C_t * c_t)

computed as **three shifted products**: the sequence of ``g`` shifted by
two, by one and not at all, each times its tap.

``Mix`` of a ``"full_attention"`` layer: ``num_attention_heads`` query
heads and ``num_key_value_heads`` key/value heads of ``hidden_size /
num_attention_heads`` (query head ``j`` reads key head ``j // group``); no
bias; every query head and every key head through an RMS norm over its
width with a learned gain (one for the queries, one for the keys; eps
``norm_eps``), **then** rotated over the whole head, dim ``j`` paired with
``j + d/2``, base ``rope_parameters.rope_theta``; a causal softmax over
``sqrt(d)``.

``FF``: in the first ``num_dense_layers`` layers ``W_d(silu(W_g n) * W_u
n)`` of width ``intermediate_size``; in the others ``s = sigmoid(W_r n)``
over ``num_experts``, the ``num_experts_per_tok`` largest of ``s + b``
chosen (``b`` the selection bias, never in the weights), ``w_e = s_e /
(sum of the chosen s + 1e-6)`` times ``routed_scaling_factor``, and the
sum of ``w_e E_e(n)`` over a token's chosen experts, ``E_e`` the gated
layer of width ``moe_intermediate_size``.  No shared expert, no capacity,
no drop.  One more RMSNorm after the last layer; the logits score with
the embedding table itself; the embedding is not scaled.

No cache, no chunk, no sort.  :func:`forward` (the tests' sizes) sends
**each token through its own chosen experts by a gather** of their
matrices.  The serving check walks a layer at a time over sequences of
thousands of positions, where a gather of four matrices a token cannot be
held: there (:func:`block`) the held experts go ``EXPERT_GROUP`` at a time
over every token, each weighted by the routing (zero where it was not
chosen): the same sum.  Given a held share (``experts_held`` of
``num_experts``, from ``experts_offset``) both leave out what the absent
experts would add.

Readings of the public modeling file as remembered (no network to read it
again), each also under ``assumed`` in the configuration: sigmoid scores;
the ``1e-6``; the order ``[B ; C ; u]``; as many taps as ``conv_L_cache``;
the norm of query and key heads, before rotation; half-split rotation over
the whole head; the tied head and the last norm; token ids 1-based
(``TOKEN_BASE``), column ``j`` of the logits scoring token ``j + 1``; the
expert stacks lie ``[experts, in, out]`` and the taps ``[taps, hidden]``.

**What the harness's seeding means for these leaves** (:func:`seeded`, by
the configuration's ``seeding``; the kind applies the same function to the
served model).  ``harness.weights`` makes a matrix a normal of
``fan_in ** -0.5`` by its last axis: the taps ``[3, hidden]`` would be
``hidden ** -0.5`` small and the mixer's output a forty-fifth of the
stream.  ``seeding.taps_std`` (``3 ** -0.5``: a convolution that keeps its
input's size) is what they are read at.  The query and key projections
come at ``hidden ** -0.5``, which gives every query and key head an rms of
one: the norm over a head would be the identity but for its gain, and a
program without it would pass.  ``seeding.qk_scale`` (4, a power of two:
the rounded weights are the same numbers, shifted) multiplies both
projections; with the norm nothing moves, without it the scores are
sixteen times too large.  ``seeding.routed_down`` multiplies the experts'
``w_down`` (the configuration says why).  The selection bias comes as the
harness seeds a bias, ``0.02 x normal``, and is read as it comes.

Weights come as a dict ``path -> array`` from ``harness.weights``.  This
file imports nothing of the program.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

from reference.precision import quantizer

NEG = -1e9
TOKEN_BASE = 1      # column j of the logits scores token id j + 1
ROUTING_EPS = 1e-6

# scores of one request's attention, [heads, Tq, T] float32, are held to
# this by taking the queries Q_BLOCK at a time
SCORES_BYTES = 1 << 30
Q_BLOCK = 512
# held experts that run over every token at a time in the blocked walk
EXPERT_GROUP = 8


def rms_norm(x, gain, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def head_width(cfg: Dict[str, Any]) -> int:
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def rope_theta(cfg: Dict[str, Any]) -> float:
    return float((cfg.get("rope_parameters") or {}).get(
        "rope_theta", cfg.get("rope_theta", 1e6)))


def rotary(x, theta: float):
    """``x [B, heads, T, d]`` rotated by position ``t`` over the whole
    head: the pair ``(x[j], x[j + d/2])`` turns by ``t *
    theta**(-2j/d)``."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(qh, kh, vh, q, in_blocks: bool):
    """``qh [B, Hq, T, d]`` over ``kh``, ``vh [B, Hkv, T, d]``: a causal,
    masked softmax.  The queries of a group share their key/value head
    inside the product (the same numbers as repeating it).  ``in_blocks``:
    where the scores of the whole sequence pass SCORES_BYTES the queries go
    Q_BLOCK at a time, each block against every key: the same rows of the
    same softmax."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, Hq, T, d = qh.shape
    Hkv = kh.shape[1]
    G = Hq // Hkv
    qg = qh.reshape(B, Hkv, G, T, d)
    cols = jnp.arange(T)

    def rows(q_rows, first):
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q(q_rows), q(kh),
                       precision=hi) / math.sqrt(d)
        at = first + jnp.arange(q_rows.shape[3])
        s = jnp.where(at[:, None] >= cols[None, :], s, NEG)
        return jnp.einsum("bhgqk,bhkd->bhgqd", q(jax.nn.softmax(s, axis=-1)),
                          q(vh), precision=hi)

    if not in_blocks or B * Hq * T * T * 4 <= SCORES_BYTES or T % Q_BLOCK:
        out = rows(qg, 0)
    else:
        n = T // Q_BLOCK
        blocks = qg.reshape(B, Hkv, G, n, Q_BLOCK, d) \
            .transpose(3, 0, 1, 2, 4, 5)
        out = jax.lax.map(lambda a: rows(a[0], a[1]),
                          (blocks, jnp.arange(n) * Q_BLOCK))
        out = out.transpose(1, 2, 3, 0, 4, 5)
    return out.reshape(B, Hq, T, d)


def _linear(x, w, q):
    """``x [..., in]`` times ``w [out, in]``."""
    import jax
    import jax.numpy as jnp
    return jnp.einsum("...i,oi->...o", q(x), q(w),
                      precision=jax.lax.Precision.HIGHEST)


def short_conv(n, w: Dict[str, Any], q):
    """The gated short convolution of ``n [B, T, H]``; ``w`` holds the
    mixer's leaves (``.taps``, ``.in_proj.weight``, ``.out_proj.weight``).
    Three shifted products: ``g`` moved back by two, by one and not at
    all, zeros moving in at the sequence's start."""
    import jax.numpy as jnp
    h = n.shape[-1]
    p = _linear(n, w[".in_proj.weight"], q)
    b, c, u = p[..., :h], p[..., h:2 * h], p[..., 2 * h:]
    g = b * u
    taps = w[".taps"]
    width, t = taps.shape[0], g.shape[1]
    conv = sum(taps[j] * jnp.pad(
        g, ((0, 0), (width - 1 - j, 0), (0, 0)))[:, :t]
        for j in range(width))
    return _linear(c * conv, w[".out_proj.weight"], q)


def self_attention(n, w: Dict[str, Any], cfg: Dict[str, Any], q,
                   in_blocks: bool, qk_norm: bool = True):
    """The attention layer's mixer on ``n [B, T, H]``; ``w`` holds the
    layer's ``.attn`` leaves.  ``qk_norm`` false is a control: the norm of
    the query and key heads left out."""
    B, T, _ = n.shape
    d, eps = head_width(cfg), cfg.get("norm_eps", 1e-5)

    def heads(name, count):
        return _linear(n, w[name], q).reshape(B, T, count, d) \
            .transpose(0, 2, 1, 3)
    qh = heads(".q_layer.weight", cfg["num_attention_heads"])
    kh = heads(".k_layer.weight", cfg["num_key_value_heads"])
    vh = heads(".v_layer.weight", cfg["num_key_value_heads"])
    if qk_norm:
        qh = rms_norm(qh, w[".q_norm.weight"], eps)
        kh = rms_norm(kh, w[".k_norm.weight"], eps)
    theta = rope_theta(cfg)
    ctx = attention(rotary(qh, theta), rotary(kh, theta), vh, q, in_blocks)
    return _linear(ctx.transpose(0, 2, 1, 3).reshape(B, T, -1),
                   w[".output_layer.weight"], q)


def gated(x, wg, wu, wd, q):
    """``W_d(silu(W_g x) * W_u x)`` for ``x [T, H]`` and weights lying
    ``[in, out]``."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    a = jax.nn.silu(jnp.einsum("th,hf->tf", q(x), q(wg), precision=hi)) \
        * jnp.einsum("th,hf->tf", q(x), q(wu), precision=hi)
    return jnp.einsum("tf,fh->th", q(a), q(wd), precision=hi)


def route(n, w: Dict[str, Any], cfg: Dict[str, Any], q,
          bias_in_weights: bool = False) -> Tuple[Any, Any]:
    """``n [T, H] -> (experts [T, k], weights [T, k])``: the chosen
    experts of every token and their weights.  ``bias_in_weights`` is a
    control: the selection bias added into the weights too."""
    import jax
    import jax.numpy as jnp
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.einsum(
        "th,eh->te", q(n), q(w[".ffn.router.weight"]),
        precision=jax.lax.Precision.HIGHEST))
    ranked = s + w[".ffn.router.bias"]
    _, idx = jax.lax.top_k(ranked, k)
    vals = jnp.take_along_axis(ranked if bias_in_weights else s, idx, axis=-1)
    vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + ROUTING_EPS)
    return idx, vals * float(cfg.get("routed_scaling_factor") or 1.0)


def experts_by_gather(n, w: Dict[str, Any], cfg: Dict[str, Any], q, **how):
    """The expert layer for ``n [T, H]``: each token through its own
    chosen experts, their matrices gathered for it (the tests' sizes)."""
    import jax
    import jax.numpy as jnp
    first = cfg.get("experts_offset", 0)
    held = w[".ffn.w_gate"].shape[0]
    idx, vals = route(n, w, cfg, q, **how)
    local = idx - first
    here = (local >= 0) & (local < held)
    local = jnp.clip(local, 0, held - 1)

    def token(x, experts, weights):
        outs = jax.vmap(lambda e: gated(
            x[None], w[".ffn.w_gate"][e], w[".ffn.w_up"][e],
            w[".ffn.w_down"][e], q)[0])(experts)
        return jnp.sum(weights[:, None] * outs, axis=0)
    return jax.vmap(token)(n, local, jnp.where(here, vals, 0.0))


def experts_in_groups(n, w: Dict[str, Any], cfg: Dict[str, Any], q, **how):
    """The same sum for ``n [T, H]`` with thousands of rows: the held
    experts ``EXPERT_GROUP`` at a time over every token, each weighted by
    the routing (zero where the token did not choose it)."""
    import jax
    import jax.numpy as jnp
    first = cfg.get("experts_offset", 0)
    held = w[".ffn.w_gate"].shape[0]
    idx, vals = route(n, w, cfg, q, **how)
    hot = (idx - first)[..., None] == jnp.arange(held)        # [T, k, held]
    weights = jnp.sum(jnp.where(hot, vals[..., None], 0.0), axis=1).T
    group = math.gcd(EXPERT_GROUP, held)

    def some(y, e):
        wg, wu, wd, we = e
        outs = jax.vmap(lambda a, b, c: gated(n, a, b, c, q))(wg, wu, wd)
        return y + jnp.sum(we[:, :, None] * outs, axis=0), None
    y, _ = jax.lax.scan(some, jnp.zeros_like(n), tuple(
        a.reshape((held // group, group) + a.shape[1:]) for a in (
            w[".ffn.w_gate"], w[".ffn.w_up"], w[".ffn.w_down"], weights)))
    return y


def layer_kind(cfg: Dict[str, Any], i: int) -> Tuple[str, bool]:
    """``("conv" | "attn", sparse)`` of layer ``i``."""
    kind = {"conv": "conv", "full_attention": "attn"}[cfg["layer_types"][i]]
    return kind, i >= cfg.get("num_dense_layers", 0)


def _block(x, w: Dict[str, Any], cfg: Dict[str, Any], kind: str,
           sparse: bool, q, in_blocks: bool = False, qk_norm: bool = True,
           bias_in_weights: bool = False):
    """One layer on ``x [B, T, H]``; ``w`` holds its leaves by their names
    inside it (``.attn_norm.weight``)."""
    import jax
    eps = cfg.get("norm_eps", 1e-5)
    n = rms_norm(x, w[".attn_norm.weight"], eps)
    if kind == "conv":
        h = x + short_conv(n, _inside(w, ".ssm"), q)
    else:
        h = x + self_attention(n, _inside(w, ".attn"), cfg, q, in_blocks,
                               qk_norm)
    n = rms_norm(h, w[".ffn_norm.weight"], eps)
    if sparse:
        experts = experts_in_groups if in_blocks else experts_by_gather
        f = jax.vmap(lambda rows: experts(
            rows, w, cfg, q, bias_in_weights=bias_in_weights))(n)
    else:
        f = jax.vmap(lambda rows: gated(
            rows, w[".ffn.gate.weight"].T, w[".ffn.up.weight"].T,
            w[".ffn.down.weight"].T, q))(n)
    return h + f


def _inside(params: Dict[str, Any], p: str) -> Dict[str, Any]:
    """The leaves under the path ``p``, by their names inside it."""
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p + ".")}


def logits_of(params: Dict[str, Any], cfg: Dict[str, Any], x, q):
    """The last norm and the tied head of ``x [..., H]``."""
    import jax
    import jax.numpy as jnp
    h = rms_norm(x, params[".final_norm.weight"], cfg.get("norm_eps", 1e-5))
    return jnp.einsum("...h,vh->...v", q(h), q(params[".embedding.weight"]),
                      precision=jax.lax.Precision.HIGHEST)


# ---- seeding ----------------------------------------------------------------

def seeded(w: Dict[str, Any], cfg: Dict[str, Any], dtype) -> Dict[str, Any]:
    """One layer's leaves (by their names inside it: ``.ssm.taps``) as the
    configuration's ``seeding`` reads what ``harness.weights`` seeded (this
    file's docstring), rounded to ``dtype`` (what is served) and returned
    in the dtype they came in.  Leaves it has no rule for pass
    unchanged."""
    import jax.numpy as jnp
    rule = cfg.get("seeding") or {}
    out = dict(w)

    def served(new, old):
        return new.astype(dtype).astype(old.dtype)
    if ".ssm.taps" in w and rule.get("taps_std"):
        taps = w[".ssm.taps"]
        # made a normal of hidden ** -0.5 (the matrix rule, by the last
        # axis)
        out[".ssm.taps"] = served(
            taps.astype(jnp.float32)
            * (float(rule["taps_std"]) * taps.shape[-1] ** 0.5), taps)
    for name in (".attn.q_layer.weight", ".attn.k_layer.weight"):
        if name in w and rule.get("qk_scale", 1.0) != 1.0:
            out[name] = served(w[name].astype(jnp.float32)
                               * float(rule["qk_scale"]), w[name])
    if ".ffn.w_down" in w and rule.get("routed_down", 1.0) != 1.0:
        down = w[".ffn.w_down"]
        out[".ffn.w_down"] = served(
            down.astype(jnp.float32) * float(rule["routed_down"]), down)
    return out


# ---- the whole model at once (the tests' sizes) ------------------------------

def forward(params: Dict[str, Any], cfg: Dict[str, Any], tokens,
            precision: str = "float32", **how):
    """Logits ``[B, T, vocab]`` of 1-based ``tokens [B, T]``, each token
    through its own experts by a gather.  ``params`` are read as they come
    (a test that seeds through :func:`seeded` applies it itself); ``how``
    are the controls' arguments of :func:`_block`."""
    q = quantizer(precision)
    x = params[".embedding.weight"][tokens - TOKEN_BASE]
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, _inside(params, f".blocks[{i}]"), cfg,
                   *layer_kind(cfg, i), q, **how)
    return logits_of(params, cfg, x, q)


# ---- the model a block at a time, as the serving check walks it -------------
# (``harness.kinds.conv_moe_lm.param_blocks`` names the blocks: the
# embedding, each layer, the last norm with the tied head).  Each step takes
# only its own block's leaves, by their full paths, and the name of the
# precision its matrix products run in; layers of one type share one
# compiled program: a convolution before a dense layer, a convolution before
# the experts, attention before the experts.

_STEPS: Dict[Any, Any] = {}
_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "num_experts_per_tok", "num_experts", "experts_offset",
         "routed_scaling_factor", "norm_eps", "conv_L_cache")


def _step(name: str, cfg: Dict[str, Any], precision: str, build, **jit_kw):
    import jax
    key = (name, precision, rope_theta(cfg)) \
        + tuple(cfg.get(k) for k in _KEYS)
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
    """Layer ``i`` on ``x [B, T, H]``; ``x`` is given up to the result.
    ``params`` are the harness's seeded leaves, read as the configuration's
    ``seeding`` says (:func:`seeded`)."""
    import jax.numpy as jnp
    kind, sparse = layer_kind(cfg, i)
    w = seeded(_inside(params, f".blocks[{i}]"), cfg,
               jnp.dtype(cfg["serving"]["weights_dtype"]))
    return _step(f"block.{kind}.{sparse}", cfg, precision,
                 lambda q: lambda w_, x_: _block(x_, w_, cfg, kind, sparse, q,
                                                 in_blocks=True),
                 donate_argnums=1)(w, x)


def head(params: Dict[str, Any], cfg: Dict[str, Any], rows,
         precision: str = "float32"):
    """Logits ``[..., vocab]`` of the residual stream's ``rows``."""
    return _step("head", cfg, precision, lambda q: lambda w, r:
                 logits_of(w, cfg, r, q))(params, rows)

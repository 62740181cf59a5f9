"""The language model of ``afmoe`` (Trinity-Mini,
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json) in
plain float32.  Sequential; every sub-layer is normed **on both sides**
(RMS norms with a learned gain, eps ``rms_norm_eps``); layer ``i`` of
``layer_types`` is

    h = x + N_post_attn(Attn_i(N_in(x)))
    y = h + N_post_mlp(FF_i(N_pre_mlp(h)))

``Attn`` on the normed ``n``: ``num_attention_heads`` query heads and
``num_key_value_heads`` key/value heads of ``head_dim`` (query head ``j``
reads key head ``j // group``), no bias, and a **gate** as wide as the
heads' output:

    q = W_q n,  k = W_k n,  v = W_v n,  a = W_a n
    q <- RMSNorm_q(q),  k <- RMSNorm_k(k)     over each head's width, a
                                              learned gain each, before any
                                              rotation
    a ``sliding_attention`` layer rotates q and k by position over the whole
    head (dim ``j`` paired with ``j + d/2``, base ``rope_theta``, no
    scaling) and a query at ``t`` attends ``max(0, t - window + 1) .. t``;
    a ``full_attention`` layer **rotates nothing** and attends ``0 .. t``
    scores over ``sqrt(d)``, a masked softmax, no sink
    Attn(n) = W_o (ctx * sigmoid(a))

A window is a **mask over the whole sequence**: no ring, no cache.

``FF``: in the first ``num_dense_layers`` layers ``W_d(silu(W_g n) * W_u
n)`` of width ``intermediate_size``; in the others ``s = sigmoid(W_r n)``
over ``num_experts``, the ``num_experts_per_tok`` largest of ``s + b``
chosen (``b`` the selection bias, never in the weights), ``w_e = s_e /
(sum of the chosen s + 1e-20)``, and

    FF(n) = route_scale * sum_chosen w_e E_e(n) + E_shared(n)

every expert and the shared one a gated layer of ``moe_intermediate_size``.
No capacity, no drop.  The embedding is ``E[t] * sqrt(hidden_size)``
(``mup_enabled``); one more RMSNorm after the last layer; an untied head.

No cache, no chunk, no sort.  :func:`forward` (the tests' sizes) sends
**each token through its own chosen experts by a gather** of their
matrices.  The serving check walks a layer at a time over sequences of
thousands of positions, where a gather of eight matrices a token cannot be
held: there (:func:`block`) the held experts go ``EXPERT_GROUP`` at a time
over every token, each weighted by the routing (zero where it was not
chosen): the same sum.  Given a held share (``experts_held`` of
``num_experts``, from ``experts_offset``) both leave out what the absent
experts would add.

**Readings of the public modeling file as remembered** (``modeling_afmoe.py``
of ``transformers``; no network to read it again), each also under
``assumed`` in the configuration, none compared with the published
weights: the gate on the attention's output, ``sigmoid`` of a projection
of the layer's input, before ``o_proj``; rotation in the window layers
only; the norm of query and key heads before it; half-split rotation over
the whole head; four norms a block; the embedding times ``sqrt(hidden)``
under ``mup_enabled``; sigmoid scores, the selection bias in the choice
alone, ``1e-20`` under the normalising sum, ``route_scale`` on the routed
sum and not on the shared expert; token ids 1-based (``TOKEN_BASE``),
column ``j`` of the logits scoring token ``j + 1`` (the repo's
convention); the expert stacks lie ``[experts, in, out]``.

**What the harness's seeding means for these leaves** (:func:`seeded`, by
the configuration's ``seeding``; the kind applies the same function to the
served model).  ``harness.weights`` makes a matrix a normal of ``fan_in **
-0.5``: the query and key projections then give every head an rms of one,
the norm over a head is the identity but for its gain, and a program
without it would pass: ``seeding.qk_scale`` (a power of two: the rounded
weights are the same numbers, shifted) multiplies both.
``seeding.routed_down`` multiplies the experts' ``w_down`` (the
configuration says why).  The gate's projection is read as it comes: its
``a`` has an rms of one, ``sigmoid(a)`` lies within 0.27 to 0.73 for most
dims, and a gate left out is seen all the same (the configuration has the
reading).  The selection bias comes as the harness seeds a bias, ``0.02 x
normal``, and is read as it comes.

Weights come as a dict ``path -> array`` from ``harness.weights``.  This
file imports nothing of the program.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

from reference.precision import quantizer

NEG = -1e9
TOKEN_BASE = 1      # column j of the logits scores token id j + 1
ROUTING_EPS = 1e-20

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


def attention(qh, kh, vh, q, in_blocks: bool, window: Optional[int]):
    """``qh [B, Hq, T, d]`` over ``kh``, ``vh [B, Hkv, T, d]``: a causal
    softmax, masked to the last ``window`` positions where one is given.
    The queries of a group share their key/value head inside the product
    (the same numbers as repeating it).  ``in_blocks``: where the scores of
    the whole sequence pass SCORES_BYTES the queries go Q_BLOCK at a time,
    each block against every key: the same rows of the same softmax."""
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
        dist = (first + jnp.arange(q_rows.shape[3]))[:, None] - cols[None, :]
        ok = dist >= 0
        if window is not None:
            ok = ok & (dist < window)
        s = jnp.where(ok, s, NEG)
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


def self_attention(n, w: Dict[str, Any], cfg: Dict[str, Any], q,
                   in_blocks: bool, window: Optional[int]):
    """The attention on ``n [B, T, H]``; ``w`` holds the layer's ``.attn``
    leaves.  ``window`` None: a full layer, which rotates nothing."""
    import jax
    B, T, _ = n.shape
    d, eps = head_width(cfg), cfg.get("rms_norm_eps", 1e-5)

    def heads(name, count):
        return _linear(n, w[name], q).reshape(B, T, count, d) \
            .transpose(0, 2, 1, 3)
    qh = rms_norm(heads(".q_layer.weight", cfg["num_attention_heads"]),
                  w[".q_norm.weight"], eps)
    kh = rms_norm(heads(".k_layer.weight", cfg["num_key_value_heads"]),
                  w[".k_norm.weight"], eps)
    vh = heads(".v_layer.weight", cfg["num_key_value_heads"])
    if window is not None:
        theta = float(cfg.get("rope_theta", 10000.0))
        qh, kh = rotary(qh, theta), rotary(kh, theta)
    ctx = attention(qh, kh, vh, q, in_blocks, window)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, -1)
    gate = jax.nn.sigmoid(_linear(n, w[".gate_layer.weight"], q))
    return _linear(ctx * gate, w[".output_layer.weight"], q)


def gated(x, wg, wu, wd, q):
    """``W_d(silu(W_g x) * W_u x)`` for ``x [T, H]`` and weights lying
    ``[in, out]``."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    a = jax.nn.silu(jnp.einsum("th,hf->tf", q(x), q(wg), precision=hi)) \
        * jnp.einsum("th,hf->tf", q(x), q(wu), precision=hi)
    return jnp.einsum("tf,fh->th", q(a), q(wd), precision=hi)


def route(n, w: Dict[str, Any], cfg: Dict[str, Any], q) -> Tuple[Any, Any]:
    """``n [T, H] -> (experts [T, k], weights [T, k])``: the chosen
    experts of every token and their weights, the scale in them."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(jnp.einsum(
        "th,eh->te", q(n), q(w[".ffn.router.weight"]),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + w[".ffn.router.bias"],
                           cfg["num_experts_per_tok"])
    vals = jnp.take_along_axis(s, idx, axis=-1)
    vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + ROUTING_EPS)
    return idx, vals * float(cfg.get("route_scale") or 1.0)


def experts_by_gather(n, w: Dict[str, Any], cfg: Dict[str, Any], q):
    """The routed sum for ``n [T, H]``: each token through its own chosen
    experts, their matrices gathered for it (the tests' sizes)."""
    import jax
    import jax.numpy as jnp
    first = cfg.get("experts_offset", 0)
    held = w[".ffn.w_gate"].shape[0]
    idx, vals = route(n, w, cfg, q)
    local = idx - first
    here = (local >= 0) & (local < held)
    local = jnp.clip(local, 0, held - 1)

    def token(x, experts, weights):
        outs = jax.vmap(lambda e: gated(
            x[None], w[".ffn.w_gate"][e], w[".ffn.w_up"][e],
            w[".ffn.w_down"][e], q)[0])(experts)
        return jnp.sum(weights[:, None] * outs, axis=0)
    return jax.vmap(token)(n, local, jnp.where(here, vals, 0.0))


def experts_in_groups(n, w: Dict[str, Any], cfg: Dict[str, Any], q):
    """The same sum for ``n [T, H]`` with thousands of rows: the held
    experts ``EXPERT_GROUP`` at a time over every token, each weighted by
    the routing (zero where the token did not choose it)."""
    import jax
    import jax.numpy as jnp
    first = cfg.get("experts_offset", 0)
    held = w[".ffn.w_gate"].shape[0]
    idx, vals = route(n, w, cfg, q)
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


def layer_kind(cfg: Dict[str, Any], i: int) -> Tuple[Optional[int], bool]:
    """``(window or None, sparse)`` of layer ``i``."""
    window = {"sliding_attention": cfg["sliding_window"],
              "full_attention": None}[cfg["layer_types"][i]]
    return window, i >= cfg.get("num_dense_layers", 0)


def _block(x, w: Dict[str, Any], cfg: Dict[str, Any],
           window: Optional[int], sparse: bool, q, in_blocks: bool = False):
    """One layer on ``x [B, T, H]``; ``w`` holds its leaves by their names
    inside it (``.attn_norm.weight``)."""
    import jax
    eps = cfg.get("rms_norm_eps", 1e-5)
    a = self_attention(rms_norm(x, w[".attn_norm.weight"], eps),
                       _inside(w, ".attn"), cfg, q, in_blocks, window)
    h = x + rms_norm(a, w[".attn_post_norm.weight"], eps)
    n = rms_norm(h, w[".ffn_norm.weight"], eps)
    if sparse:
        experts = experts_in_groups if in_blocks else experts_by_gather

        def feed(rows):
            f = experts(rows, w, cfg, q)
            if ".ffn.shared.gate.weight" in w:
                f = f + gated(rows, w[".ffn.shared.gate.weight"].T,
                              w[".ffn.shared.up.weight"].T,
                              w[".ffn.shared.down.weight"].T, q)
            return f
    else:
        def feed(rows):
            return gated(rows, w[".ffn.gate.weight"].T,
                         w[".ffn.up.weight"].T, w[".ffn.down.weight"].T, q)
    return h + rms_norm(jax.vmap(feed)(n), w[".ffn_post_norm.weight"], eps)


def _inside(params: Dict[str, Any], p: str) -> Dict[str, Any]:
    """The leaves under the path ``p``, by their names inside it."""
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p + ".")}


def embedded(params: Dict[str, Any], cfg: Dict[str, Any], tokens):
    """The residual stream of 1-based ``tokens``: the table's rows times
    ``sqrt(hidden_size)`` under ``mup_enabled``."""
    x = params[".embedding.weight"][tokens - TOKEN_BASE]
    return x * cfg["hidden_size"] ** 0.5 if cfg.get("mup_enabled") else x


def logits_of(params: Dict[str, Any], cfg: Dict[str, Any], x, q):
    """The last norm and the untied head of ``x [..., H]``."""
    h = rms_norm(x, params[".final_norm.weight"],
                 cfg.get("rms_norm_eps", 1e-5))
    return _linear(h, params[".lm_head.weight"], q)


# ---- seeding ----------------------------------------------------------------

def seeded(w: Dict[str, Any], cfg: Dict[str, Any], dtype) -> Dict[str, Any]:
    """One layer's leaves (by their names inside it: ``.ffn.w_down``) as
    the configuration's ``seeding`` reads what ``harness.weights`` seeded
    (this file's docstring), rounded to ``dtype`` (what is served) and
    returned in the dtype they came in.  Leaves it has no rule for pass
    unchanged."""
    import jax.numpy as jnp
    rule = cfg.get("seeding") or {}
    out = dict(w)
    for factor, names in (
            ("qk_scale", (".attn.q_layer.weight", ".attn.k_layer.weight")),
            ("routed_down", (".ffn.w_down",))):
        for name in names:
            if name in w and rule.get(factor, 1.0) != 1.0:
                out[name] = (w[name].astype(jnp.float32)
                             * float(rule[factor])) \
                    .astype(dtype).astype(w[name].dtype)
    return out


# ---- the whole model at once (the tests' sizes) ------------------------------

def forward(params: Dict[str, Any], cfg: Dict[str, Any], tokens,
            precision: str = "float32"):
    """Logits ``[B, T, vocab]`` of 1-based ``tokens [B, T]``, each token
    through its own experts by a gather.  ``params`` are read as they come
    (a test that seeds through :func:`seeded` applies it itself)."""
    q = quantizer(precision)
    x = embedded(params, cfg, tokens)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, _inside(params, f".blocks[{i}]"), cfg,
                   *layer_kind(cfg, i), q)
    return logits_of(params, cfg, x, q)


# ---- the model a block at a time, as the serving check walks it -------------
# (``harness.kinds.gated_window_moe_lm.param_blocks`` names the blocks: the
# embedding, each layer, the last norm with the head).  Each step takes only
# its own block's leaves, by their full paths, and the name of the precision
# its matrix products run in; layers of one kind share one compiled program:
# a window over a dense layer, a window over the experts, a full layer over
# the experts.

_STEPS: Dict[Any, Any] = {}
_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "num_experts_per_tok", "num_experts", "experts_offset",
         "route_scale", "rms_norm_eps", "rope_theta", "mup_enabled")


def _step(name: str, cfg: Dict[str, Any], precision: str, build, **jit_kw):
    import jax
    key = (name, precision) + tuple(cfg.get(k) for k in _KEYS)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(build(quantizer(precision)), **jit_kw)
    return _STEPS[key]


def embed(params: Dict[str, Any], cfg: Dict[str, Any], tokens):
    """``[B, T]`` 1-based token ids to the residual stream ``[B, T, H]``
    (no position is added)."""
    return _step("embed", cfg, "float32", lambda q: lambda w, t:
                 embedded(w, cfg, t))(params, tokens)


def block(params: Dict[str, Any], cfg: Dict[str, Any], i: int, x,
          precision: str = "float32"):
    """Layer ``i`` on ``x [B, T, H]``; ``x`` is given up to the result.
    ``params`` are the harness's seeded leaves, read as the configuration's
    ``seeding`` says (:func:`seeded`)."""
    import jax.numpy as jnp
    window, sparse = layer_kind(cfg, i)
    w = seeded(_inside(params, f".blocks[{i}]"), cfg,
               jnp.dtype(cfg["serving"]["weights_dtype"]))
    return _step(f"block.{window}.{sparse}", cfg, precision,
                 lambda q: lambda w_, x_: _block(x_, w_, cfg, window, sparse,
                                                 q, in_blocks=True),
                 donate_argnums=1)(w, x)


def head(params: Dict[str, Any], cfg: Dict[str, Any], rows,
         precision: str = "float32"):
    """Logits ``[..., vocab]`` of the residual stream's ``rows``."""
    return _step("head", cfg, precision, lambda q: lambda w, r:
                 logits_of(w, cfg, r, q))(params, rows)

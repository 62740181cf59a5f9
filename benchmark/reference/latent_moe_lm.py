"""The language model of ``sarvam_mla`` (sarvam-105b,
https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json) in plain
float32.  Pre-norm, sequential; one layer is

    h = x + Attn(RMSNorm(x))              eps ``rms_norm_eps``
    y = h + FFN_i(RMSNorm(h))

``Attn`` is latent attention in its **expanded** form only (no cache, no
absorbed projection).  With ``n`` the normed input, ``H =
num_attention_heads``:

    q_t               = W_q n_t                      per head [q^N (qk_nope_head_dim) ; q^R (qk_rope_head_dim)]
    [c_t ; k^R_t]     = W_kva n_t                    kv_lora_rank + qk_rope_head_dim
    c_t               = RMSNorm(c_t) * gain          (``use_qk_norm``: the norm on the compressed row)
    q^R, k^R          rotated at position t          pairs (2j, 2j+1), YaRN frequencies
    [k^N_sh ; v_sh]   = W_kvb,h c_s                  qk_nope_head_dim + v_head_dim a head
    score_tsh         = (q^N_th . k^N_sh + q^R_th . k^R_s) * scale     causal
    o_th              = sum_s softmax_s(score) v_sh
    out_t             = W_o [o_t1 .. o_tH]

``k^R`` is one vector a position for all heads.  YaRN (``rope_scaling``
of type ``deepseek_yarn``): pair ``j`` turns by ``f_j = theta**(-2j/d)``
below pair ``lo``, by ``f_j / factor`` above pair ``hi`` and by a linear
blend between, ``lo`` / ``hi`` the pairs making ``beta_fast`` /
``beta_slow`` turns in ``original_max_position_embeddings`` positions
(rounded down / up); cos and sin times ``m(mscale) / m(mscale_all_dim)``
and ``scale = q_head_dim**-0.5 * m(mscale_all_dim)**2`` with ``m(s) = 0.1
s ln(factor) + 1``.

``FFN``: layer 0 (``first_k_dense_replace`` 1) is ``W_d(silu(W_g n) * W_u
n)`` of width ``intermediate_size``; every other layer

    s = sigmoid(n W_r)                  over ``num_experts`` (published width)
    chosen: the ``num_experts_per_tok`` largest of s + b     (b the selection bias; one group)
    w_e = s_e / sum_chosen s
    y = routed_scaling_factor * sum_chosen w_e E_e(n) + E_shared(n)

every expert ``W_d(silu(W_g n) * W_u n)`` of width ``moe_intermediate_size``
and the shared expert of ``num_shared_experts`` times that.  Final RMSNorm,
an untied head, the embedding not scaled.

Given a held share (the stacks' leading axis, from ``experts_offset``) and
a sliced vocabulary it leaves out what the absent experts would add, as the
program on one chip of the deployment does; the shared expert is whole.
Every held expert runs over every token, one at a time, weighted by the
routing (zero where it was not chosen).  ``routed`` takes the share's first
expert as an argument, so a test can add the shares' parts up to the uncut
layer's.

Departures and readings, each also under ``assumed`` in the configuration:
``use_qk_norm`` as above; the rotary pairing; sigmoid scores, one group,
weights normalised over the chosen; token ids 1-based (``TOKEN_BASE``);
stacks ``[experts, in, out]``.

Weights come as a dict ``path -> array`` from ``harness.weights``, read
through the configuration's ``seeding`` where it has one
(:func:`seeded_experts`).  This file imports nothing of the program.
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
Q_BLOCK = 256


def rms_norm(x, gain, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def _m(factor: float, s: float) -> float:
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(cfg: Dict[str, Any]):
    """``(frequencies [d/2], magnitude of cos and sin, softmax scale)``."""
    import jax.numpy as jnp
    d, theta = cfg["qk_rope_head_dim"], float(cfg.get("rope_theta", 10000.0))
    q_dim = cfg["qk_nope_head_dim"] + d
    j = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * j / d)
    ys = cfg.get("rope_scaling")
    if not ys:
        return f, 1.0, q_dim ** -0.5
    factor, orig = float(ys["factor"]), ys["original_max_position_embeddings"]

    def pair(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(pair(ys.get("beta_fast", 32))), 0)
    hi = min(math.ceil(pair(ys.get("beta_slow", 1))), d - 1)
    ramp = jnp.clip((j - lo) / max(hi - lo, 0.001), 0.0, 1.0)
    all_dim = _m(factor, ys.get("mscale_all_dim", 0))
    return (f / factor * ramp + f * (1.0 - ramp),
            _m(factor, ys.get("mscale", 1)) / all_dim,
            q_dim ** -0.5 * all_dim ** 2)


def rotate(x, freq, magnitude):
    """``x [..., T, d]`` with the pair ``(x[2j], x[2j+1])`` turned by
    ``t * freq[j]`` at position ``t``."""
    import jax.numpy as jnp
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang) * magnitude, jnp.sin(ang) * magnitude
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(qn, qr, kn, kr, v, scale, q, in_blocks: bool):
    """``qn [B, H, T, dn]``, ``qr [B, H, T, dr]`` over ``kn [B, H, T,
    dn]``, ``kr [B, T, dr]`` (one for all heads) and ``v [B, H, T, dv]``,
    causal.  ``in_blocks``: where the scores of the whole sequence pass
    SCORES_BYTES the queries go Q_BLOCK at a time, each block against
    every key: the same rows of the same softmax."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, H, T, _ = qn.shape
    cols = jnp.arange(T)

    def rows(qn_rows, qr_rows, first):
        s = (jnp.einsum("bhqd,bhkd->bhqk", q(qn_rows), q(kn), precision=hi)
             + jnp.einsum("bhqd,bkd->bhqk", q(qr_rows), q(kr), precision=hi)
             ) * scale
        dist = (first + jnp.arange(qn_rows.shape[2]))[:, None] - cols[None, :]
        w = jax.nn.softmax(jnp.where(dist >= 0, s, NEG), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", q(w), q(v), precision=hi)

    if not in_blocks or B * H * T * T * 4 <= SCORES_BYTES or T % Q_BLOCK:
        return rows(qn, qr, 0)
    n = T // Q_BLOCK

    def split(a):
        return a.reshape(B, H, n, Q_BLOCK, a.shape[-1]).transpose(
            2, 0, 1, 3, 4)
    out = jax.lax.map(lambda a: rows(a[0], a[1], a[2]),
                      (split(qn), split(qr), jnp.arange(n) * Q_BLOCK))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, H, T, v.shape[-1])


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
    expert was not chosen), over the router's whole width."""
    import jax
    import jax.numpy as jnp
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.einsum(
        "th,eh->te", q(n), q(w[".ffn.router.weight"]),
        precision=jax.lax.Precision.HIGHEST))
    ranked = s + w[".ffn.router.bias"]
    kth = jnp.sort(ranked, axis=-1)[:, -k][:, None]
    chosen = jnp.where(ranked >= kth, s, 0.0)
    return chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def routed(n, w: Dict[str, Any], cfg: Dict[str, Any], q, first: int):
    """The held experts' part of the routed sum for ``n [T, H]``, scaled:
    the stacks in ``w`` are experts ``first ..`` of the router's."""
    import jax
    import jax.numpy as jnp
    held = w[".ffn.w_gate"].shape[0]
    weights = routing(n, w, cfg, q)[:, first:first + held].T    # [held, T]

    def one(y, e):
        wg, wu, wd, we = e
        return y + we[:, None] * gated(n, wg, wu, wd, q), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        w[".ffn.w_gate"], w[".ffn.w_up"], w[".ffn.w_down"], weights))
    return y * float(cfg.get("routed_scaling_factor") or 1.0)


def shared(n, w: Dict[str, Any], q):
    """The shared expert for ``n [T, H]`` (leaves ``[out, in]``)."""
    return gated(n, w[".ffn.shared.gate.weight"].T,
                 w[".ffn.shared.up.weight"].T,
                 w[".ffn.shared.down.weight"].T, q)


def seeded_experts(w: Dict[str, Any], cfg: Dict[str, Any]):
    """An expert layer's leaves (by their names inside it) as the
    configuration's ``seeding`` reads what ``harness.weights`` seeded,
    rounded to the served dtype and returned in the dtype they came in.

    ``seeding.routed_down``: the held experts' ``w_down`` times this.
    ``harness.weights`` seeds every matrix at ``fan_in ** -0.5``, which
    makes one routed expert's output as large as the shared expert's; with
    ``routed_scaling_factor`` 2.5 on weights of about an eighth, **one
    expert chosen here and not there** (the 8th and 9th of 128 scores lie
    0.01 apart, bfloat16 activations move a score by 0.001: one token in
    thirty a layer has a held expert on that edge) then moves a token's
    hidden state by a tenth, the next layers' routers flip in turn, and
    the served token's reference logit read 2.47 below the best on a
    sound run (my chip run, PR 40): no fault of a latent row can be told
    from that.  At a quarter sound runs read 0.36-0.51 and rows kept in
    float8 0.54; at a sixteenth, where a flip moves a token by less than
    bfloat16 rounding does, 0.08-0.10 against 0.31-0.35 (PERF.md section
    2).  The program serves the same numbers
    (``harness.kinds.latent_moe_lm.build_serve``)."""
    import jax.numpy as jnp
    rule = cfg.get("seeding") or {}
    factor = rule.get("routed_down", 1.0)
    if factor == 1.0 or ".ffn.w_down" not in w:
        return w
    old = w[".ffn.w_down"]
    dtype = jnp.dtype(cfg["serving"]["weights_dtype"])
    return dict(w, **{".ffn.w_down": (old.astype(jnp.float32) * factor)
                      .astype(dtype).astype(old.dtype)})


def is_sparse(cfg: Dict[str, Any], i: int) -> bool:
    return i >= cfg.get("first_k_dense_replace", 1)


def attend(x, w: Dict[str, Any], cfg: Dict[str, Any], q,
           in_blocks: bool = False):
    """``x + Attn(RMSNorm(x))`` for ``x [B, T, H]``."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, T, _ = x.shape
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg.get("rms_norm_eps", 1e-6)
    freq, magnitude, scale = yarn(cfg)

    n = rms_norm(x, w[".attn_norm.weight"], eps)

    def product(a, name):
        return jnp.einsum("bti,oi->bto", q(a), q(w[name]), precision=hi)

    qh = product(n, ".attn.q_layer.weight").reshape(
        B, T, H, dn + dr).transpose(0, 2, 1, 3)
    kva = product(n, ".attn.kv_a_layer.weight")
    c = rms_norm(kva[..., :r], w[".attn.kv_norm.weight"], eps)
    kr = rotate(kva[..., r:], freq, magnitude)                 # [B, T, dr]
    qr = rotate(qh[..., dn:], freq, magnitude)
    e = product(c, ".attn.kv_b_layer.weight").reshape(
        B, T, H, dn + dv).transpose(0, 2, 1, 3)
    ctx = attention(qh[..., :dn], qr, e[..., :dn], kr, e[..., dn:], scale, q,
                    in_blocks)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, H * dv)
    return x + product(ctx, ".attn.output_layer.weight")


def _block(x, w: Dict[str, Any], cfg: Dict[str, Any], sparse: bool, q,
           in_blocks: bool = False):
    """One layer on ``x [B, T, H]``; ``w`` holds its leaves by their names
    inside it (``.attn_norm.weight``)."""
    import jax
    w = seeded_experts(w, cfg)
    h = attend(x, w, cfg, q, in_blocks)
    n = rms_norm(h, w[".ffn_norm.weight"], cfg.get("rms_norm_eps", 1e-6))
    if sparse:
        first = cfg.get("experts_offset", 0)
        f = jax.vmap(lambda rows: routed(rows, w, cfg, q, first)
                     + shared(rows, w, q))(n)
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
                 cfg.get("rms_norm_eps", 1e-6))
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
                   is_sparse(cfg, i), q)
    return logits_of(params, cfg, x, q)


# ---- the model a block at a time, as the serving check walks it -------------
# (``harness.kinds.latent_moe_lm.param_blocks`` names the blocks: the
# embedding, each layer, the final norm with the head).  Each step takes only
# its own block's leaves, by their full paths, and the name of the precision
# its matrix products run in; layers of one kind share one compiled program.

_STEPS: Dict[Any, Any] = {}
_KEYS = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rope_theta",
         "num_experts_per_tok", "routed_scaling_factor", "experts_offset",
         "rms_norm_eps", "first_k_dense_replace")
_GROUPS = ("rope_scaling", "seeding", "serving")


def _step(name: str, cfg: Dict[str, Any], precision: str, build, **jit_kw):
    import jax
    key = (name, precision) + tuple(cfg.get(k) for k in _KEYS) \
        + tuple(repr(sorted((cfg.get(g) or {}).items())) for g in _GROUPS)
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
    sparse = is_sparse(cfg, i)
    return _step(f"block.{sparse}", cfg, precision,
                 lambda q: lambda w, x_: _block(x_, w, cfg, sparse, q,
                                                in_blocks=True),
                 donate_argnums=1)(_inside(params, f".blocks[{i}]"), x)


def head(params: Dict[str, Any], cfg: Dict[str, Any], rows,
         precision: str = "float32"):
    """Logits ``[..., vocab]`` of the residual stream's ``rows``."""
    return _step("head", cfg, precision, lambda q: lambda w, r:
                 logits_of(w, cfg, r, q))(params, rows)

"""The language model of ``falcon_h1`` (Falcon-H1-34B-Instruct,
https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json;
the public ``modeling_falcon_h1.py`` is the description) in plain float32.
Every layer is the same: attention and a Mamba-2 mixer **in parallel** on
one normed input,

    x0 = E[token] * embedding_multiplier
    n = RMSNorm(x)                                   eps ``rms_norm_eps``
    m = SSM(n * ssm_in_multiplier) * ssm_out_multiplier
    a = Attn(n * attention_in_multiplier) * attention_out_multiplier
    h = x + m + a
    y = h + MLP(RMSNorm(h))
    logits = W_head RMSNorm(x_last) * lm_head_multiplier      (untied head)

``Attn``: ``num_attention_heads`` query heads of ``head_dim`` (their total
need not be the hidden size), ``k = (W_k n) * key_multiplier`` and ``v`` on
``num_key_value_heads`` heads (query head ``h`` reads key/value head ``h //
group``), every dim of every query and key head rotated by position at base
``rope_theta``, dim ``j`` paired with ``j + head_dim / 2``; causal softmax
over ``sqrt(head_dim)``; no bias.

``MLP``: ``W_down(W_up n * silu(W_gate n * mlp_multipliers[0])) *
mlp_multipliers[1]``, width ``intermediate_size``, no bias.

``SSM`` (``mamba_n_heads`` heads of ``mamba_d_head``, ``mamba_n_groups``
groups, state ``mamba_d_state``, ``mamba_d_conv`` taps):

    [z | xBC | dt] = (W_in u) * mu         mu: the five ``ssm_multipliers``
                                           over the segments z, x, B, C, dt
    xBC = silu(conv(xBC))                  depthwise, causal, with a bias; tap k
                                           multiplies position t - (taps - 1) + k
    x [heads, d_head], B [groups, state], C [groups, state] = split(xBC)
    D_t = softplus(dt + dt_bias),  A = -exp(A_log)            one each a head
    S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t,   y_t = S_t C_t + D x_t
                                           head h reads group h // (heads / groups)
    g = RMSNorm_grouped(y * silu(z))       gate first, then a norm over each
                                           group's channels, one gain a channel
    out = W_out g

**The recurrence is a plain scan over positions**: no chunking, no
products between positions, no cache.  The program computes prefill in
chunks through matrix products and decode a token at a time over a
pooled state; this file shares none of that.

Departures and readings, each also under ``assumed`` in the configuration:
token ids 1-based (``TOKEN_BASE``), column ``j`` of the head scoring token
``j + 1``; the convolution's weight lies ``[channels, taps]``; the order of
the projection's segments, the head-to-group map, the rotary pairing and
where each multiplier multiplies are the public modeling file's.

Weights come as a dict ``path -> array`` from ``harness.weights``.  This
file imports nothing of the program.

**What the harness's seeding means for a mixer's leaves**
(:func:`seeded_mixer`, by the configuration's ``seeding``; the kind applies
the same function to the leaves the program serves).
``harness/weights.py`` has no rule for a recurrence: it seeds ``A_log`` and
``dt_bias`` as gains (1 +- 0.1), a decay of ``exp(dt A)`` of about 0.03 a
token, and the input projection at ``fan_in ** -0.5``, which the
multipliers then shrink until ``B``, ``C`` and ``x`` are a few hundredths
and the state's part of ``y`` a thousandth of ``D x`` beside it.  Seeded
so, a state carried, reset or rounded wrongly moves no logit.  Read
through this function the same seeded numbers give the decay and the step
sizes of Mamba-2's published initialisation (``A`` uniform in 1-16, ``dt``
log-uniform in 0.001-0.1: memories of one to a thousand tokens) and an
input projection whose segments leave their multipliers at a stated
standard deviation, so that the state's part of ``y`` is of the order of
``D x``.
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
HEAD_BYTES = 1 << 30    # the head's rows in float32, a block of them at a time


def rms_norm(x, gain, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def rotary(x, theta: float):
    """``x [B, heads, T, d]`` rotated by position ``t``: the pair ``(x[j],
    x[j + d/2])`` turns by ``t * theta**(-2j/d)``."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(qh, kh, vh, q, in_blocks: bool):
    """Causal attention of ``qh [B, Hq, T, d]`` over ``kh``, ``vh [B, Hkv,
    T, d]``; the queries of a group share their key/value head inside the
    product.  ``in_blocks``: where the scores of the whole sequence pass
    SCORES_BYTES the queries go Q_BLOCK at a time, each block against
    every key: the same rows of the same softmax."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, Hq, T, d = qh.shape
    Hkv = kh.shape[1]
    qg = qh.reshape(B, Hkv, Hq // Hkv, T, d)
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
        blocks = qg.reshape(B, Hkv, Hq // Hkv, n, Q_BLOCK, d) \
            .transpose(3, 0, 1, 2, 4, 5)
        out = jax.lax.map(lambda a: rows(a[0], a[1]),
                          (blocks, jnp.arange(n) * Q_BLOCK))
        out = out.transpose(1, 2, 3, 0, 4, 5)
    return out.reshape(B, Hq, T, d)


def recurrence(x, dt, a, b, c):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) b_t``, ``y_t = S_t c_t``
    from ``S = 0``, one position after another: ``x [T, heads, P]``, ``dt
    [T, heads]``, ``a [heads]``, ``b`` and ``c [T, heads, N]`` -> ``y [T,
    heads, P]``.  ``S`` is ``[heads, P, N]``."""
    import jax
    import jax.numpy as jnp

    def one(s, at):
        x_t, dt_t, b_t, c_t = at
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)
    zero = jnp.zeros(x.shape[1:] + (b.shape[-1],), jnp.float32)
    return jax.lax.scan(one, zero, (x, dt, b, c))[1]


def mixer(u, w: Dict[str, Any], cfg: Dict[str, Any], q):
    """The Mamba-2 mixer on ``u [B, T, H]``; ``w`` holds its leaves by
    their names inside it (``.in_proj.weight``)."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, T, _ = u.shape
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, n, taps = (cfg["mamba_n_groups"], cfg["mamba_d_state"],
                       cfg["mamba_d_conv"])
    inner, gn = heads * p, groups * n
    mu = jnp.concatenate([jnp.full((size,), m, jnp.float32) for size, m in zip(
        (inner, inner, gn, gn, heads), cfg["ssm_multipliers"])])
    proj = jnp.einsum("bti,oi->bto", q(u), q(w[".in_proj.weight"]),
                      precision=hi) * mu
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * gn],
                  proj[..., 2 * inner + 2 * gn:])
    before = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(w[".conv.weight"][:, k] * before[:, k:k + T]
               for k in range(taps)) + w[".conv.bias"]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :inner].reshape(B, T, heads, p)
    per = heads // groups
    b = jnp.repeat(xbc[..., inner:inner + gn].reshape(B, T, groups, n),
                   per, axis=2)
    c = jnp.repeat(xbc[..., inner + gn:].reshape(B, T, groups, n),
                   per, axis=2)
    dt = jax.nn.softplus(dt + w[".dt_bias"])
    a = -jnp.exp(w[".A_log"])
    y = jax.vmap(lambda x_, dt_, b_, c_: recurrence(x_, dt_, a, b_, c_))(
        x, dt, b, c)
    y = y + w[".D"][:, None] * x
    g = (y.reshape(B, T, inner) * jax.nn.silu(z)).reshape(B, T, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + cfg.get("rms_norm_eps", 1e-5))
    g = g.reshape(B, T, inner) * w[".norm.weight"]
    return jnp.einsum("bti,oi->bto", q(g), q(w[".out_proj.weight"]),
                      precision=hi)


def seeded_mixer(w: Dict[str, Any], cfg: Dict[str, Any], dtype):
    """A mixer's leaves (by their names inside it) as the configuration's
    ``seeding`` reads what ``harness.weights`` seeded, rounded to ``dtype``
    (what is served) and returned in the dtype they came in.

    ``A_log`` and ``dt_bias`` come as gains ``1 + 0.1 n`` (``n`` standard
    normal): ``u = Phi(n)`` is uniform, ``A = lo + (hi - lo) u`` for
    ``seeding.A = [lo, hi]``, and ``dt = lo (hi / lo) ** u`` for
    ``seeding.dt``, with ``dt_bias`` the number whose softplus is ``dt``.
    The rows of ``in_proj.weight`` come at ``fan_in ** -0.5``: each of the
    segments z, x, B, C, dt is divided by ``ssm_in_multiplier`` and by its
    own multiplier and multiplied by ``seeding.projection_std``, which is
    then the standard deviation of what the segment gives on a normed
    input."""
    import jax
    import jax.numpy as jnp
    rule = cfg["seeding"]
    f32 = jnp.float32

    def uniform(gain):
        return jax.scipy.special.ndtr((gain.astype(f32) - 1.0) / 0.1)

    def served(new, old):
        return new.astype(dtype).astype(old.dtype)
    lo, hi = rule["A"]
    a_log = jnp.log(lo + (hi - lo) * uniform(w[".A_log"]))
    lo, hi = rule["dt"]
    dt = lo * (hi / lo) ** uniform(w[".dt_bias"])
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    heads = cfg["mamba_n_heads"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    inner = heads * cfg["mamba_d_head"]
    scale = jnp.concatenate([
        jnp.full((size,), rule["projection_std"]
                 / (cfg.get("ssm_in_multiplier", 1.0) * m), f32)
        for size, m in zip((inner, inner, gn, gn, heads),
                           cfg["ssm_multipliers"])])
    proj, out = w[".in_proj.weight"], w[".out_proj.weight"]
    return dict(w, **{
        ".A_log": served(a_log, w[".A_log"]),
        ".dt_bias": served(dt_bias, w[".dt_bias"]),
        ".in_proj.weight": served(proj.astype(f32) * scale[:, None], proj),
        ".out_proj.weight": served(out.astype(f32) * rule["output_std"],
                                   out)})


def _inside(params: Dict[str, Any], p: str) -> Dict[str, Any]:
    """The leaves under the path ``p``, by their names inside it."""
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p + ".")}


def _block(x, w: Dict[str, Any], cfg: Dict[str, Any], q,
           in_blocks: bool = False):
    """One layer on ``x [B, T, H]``; ``w`` holds its leaves by their names
    inside it (``.attn_norm.weight``)."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, T, _ = x.shape
    d, eps = cfg["head_dim"], cfg.get("rms_norm_eps", 1e-5)
    n = rms_norm(x, w[".attn_norm.weight"], eps)

    u = n * cfg.get("attention_in_multiplier", 1.0)

    def heads(name, count):
        y = jnp.einsum("bti,oi->bto", q(u), q(w[name]), precision=hi)
        return y.reshape(B, T, count, d).transpose(0, 2, 1, 3)

    theta = float(cfg["rope_theta"])
    qh = rotary(heads(".attn.q_layer.weight", cfg["num_attention_heads"]),
                theta)
    kh = rotary(heads(".attn.k_layer.weight", cfg["num_key_value_heads"])
                * cfg.get("key_multiplier", 1.0), theta)
    vh = heads(".attn.v_layer.weight", cfg["num_key_value_heads"])
    ctx = attention(qh, kh, vh, q, in_blocks)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, -1)
    a = jnp.einsum("bti,oi->bto", q(ctx), q(w[".attn.output_layer.weight"]),
                   precision=hi) * cfg.get("attention_out_multiplier", 1.0)
    m = mixer(n * cfg.get("ssm_in_multiplier", 1.0), _inside(w, ".ssm"), cfg,
              q) * cfg.get("ssm_out_multiplier", 1.0)
    h = x + m + a
    n = rms_norm(h, w[".ffn_norm.weight"], eps)
    gate_m, down_m = cfg.get("mlp_multipliers") or (1.0, 1.0)
    up = jnp.einsum("bti,oi->bto", q(n), q(w[".ffn.up.weight"]), precision=hi)
    gate = jnp.einsum("bti,oi->bto", q(n), q(w[".ffn.gate.weight"]),
                      precision=hi) * gate_m
    f = jnp.einsum("bti,oi->bto", q(up * jax.nn.silu(gate)),
                   q(w[".ffn.down.weight"]), precision=hi) * down_m
    return h + f


def logits_of(params: Dict[str, Any], cfg: Dict[str, Any], x, q,
              in_blocks: bool = False):
    """Final norm and untied head of ``x [..., H]``, scaled.
    ``in_blocks``: where the head passes HEAD_BYTES its rows go a power
    of two of equal blocks at a time, one after another (the same columns
    of the same product: at ``highest`` a float32 product splits each
    operand into bfloat16 parts, and a head of 5 GB would stand on the
    device several times over)."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    h = q(rms_norm(x, params[".final_norm.weight"],
                   cfg.get("rms_norm_eps", 1e-5)))
    w = params[".lm_head.weight"]
    n = 1
    while in_blocks and w.size * 4 > n * HEAD_BYTES and w.shape[0] % (2 * n) == 0:
        n *= 2
    if n == 1:
        out = jnp.einsum("...h,vh->...v", h, q(w), precision=hi)
    else:
        parts = jax.lax.map(
            lambda rows: jnp.einsum("...h,vh->...v", h, q(rows), precision=hi),
            w.reshape((n, w.shape[0] // n) + w.shape[1:]))
        out = jnp.moveaxis(parts, 0, -2).reshape(h.shape[:-1] + w.shape[:1])
    return out * cfg.get("lm_head_multiplier", 1.0)


def _embed(emb, cfg: Dict[str, Any], tokens):
    return emb[tokens - TOKEN_BASE] * cfg.get("embedding_multiplier", 1.0)


def forward(params: Dict[str, Any], cfg: Dict[str, Any], tokens,
            precision: str = "float32"):
    """Logits ``[B, T, vocab]`` of 1-based ``tokens [B, T]``: the whole
    model at once (the tests' sizes)."""
    q = quantizer(precision)
    x = _embed(params[".embedding.weight"], cfg, tokens)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, _inside(params, f".blocks[{i}]"), cfg, q)
    return logits_of(params, cfg, x, q)


# ---- the model a block at a time, as the serving check walks it -------------
# (``harness.kinds.hybrid_ssm_lm.param_blocks`` names the blocks: the
# embedding, each layer, the final norm with the head).  Each step takes only
# its own block's leaves, by their full paths, and the name of the precision
# its matrix products run in; the layers share one compiled program.

_STEPS: Dict[Any, Any] = {}
_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "rope_theta", "rms_norm_eps", "mamba_n_heads",
         "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
         "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
         "attention_in_multiplier", "attention_out_multiplier",
         "ssm_in_multiplier", "ssm_out_multiplier")


def _step(name: str, cfg: Dict[str, Any], precision: str, build, **jit_kw):
    import jax
    key = (name, precision) + tuple(cfg.get(k) for k in _KEYS) + tuple(
        tuple(cfg.get(k) or ()) for k in ("ssm_multipliers",
                                          "mlp_multipliers"))
    if key not in _STEPS:
        _STEPS[key] = jax.jit(build(quantizer(precision)), **jit_kw)
    return _STEPS[key]


def embed(params: Dict[str, Any], cfg: Dict[str, Any], tokens):
    """``[B, T]`` 1-based token ids to the residual stream ``[B, T, H]``
    (the embedding scaled; no position is added)."""
    return _step("embed", cfg, "float32", lambda q: lambda emb, t:
                 _embed(emb, cfg, t))(params[".embedding.weight"], tokens)


def block(params: Dict[str, Any], cfg: Dict[str, Any], i: int, x,
          precision: str = "float32"):
    """Layer ``i`` on ``x [B, T, H]``; ``x`` is given up to the result.
    ``params`` are the harness's seeded leaves: the mixer's are read as
    the configuration's ``seeding`` says (:func:`seeded_mixer`)."""
    import jax.numpy as jnp
    w = _inside(params, f".blocks[{i}]")
    w.update({".ssm" + k: v for k, v in seeded_mixer(
        _inside(w, ".ssm"), cfg,
        jnp.dtype(cfg["serving"]["weights_dtype"])).items()})
    return _step("block", cfg, precision,
                 lambda q: lambda w_, x_: _block(x_, w_, cfg, q,
                                                 in_blocks=True),
                 donate_argnums=1)(w, x)


def head(params: Dict[str, Any], cfg: Dict[str, Any], rows,
         precision: str = "float32"):
    """Logits ``[..., vocab]`` of the residual stream's ``rows``."""
    return _step("head", cfg, precision, lambda q: lambda w, r:
                 logits_of(w, cfg, r, q, in_blocks=True))(params, rows)

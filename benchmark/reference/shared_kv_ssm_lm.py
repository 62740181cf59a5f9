"""The language model of ``phi4flash`` (Phi-4-mini-flash-reasoning,
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json;
the architecture is SambaY, arXiv:2507.06607, with differential attention,
arXiv:2410.05258, and Mamba-1 mixers, arXiv:2312.00752) in plain float32.

``L = num_hidden_layers`` layers, indices from 0.  Every layer is pre-norm
and sequential,

    h = x + Mix_i(LN(x)),   y = h + MLP(LN(h))        LN: gain and bias, eps 1e-5
    MLP(n) = W_down( silu(W_gate n) * W_up n )        no bias
    x0 = E[token]            no scale, **no position encoded anywhere**
    logits = E LN(x_last)    the head is the embedding table (tied)

and ``Mix_i`` by index (``mb_per_layer`` 2, ``sliding_window`` on the odd
layers of the first half):

    i < L/2, even      Mamba-1
    i < L/2, odd       differential attention over the last ``sliding_window`` positions
    i = L/2            Mamba-1, which also hands on its scan output ``m``
    i = L/2 + 1        differential attention over everything; its keys and values
                       are the ones the cross layers read
    i > L/2 + 1, even  gated memory unit:  W_2( silu(W_1 n_t) * m_t )
    i > L/2 + 1, odd   differential **cross** attention: its own query and output
                       projection, layer ``L/2 + 1``'s keys and values, the same mask

**Mamba-1** (``inner = mamba_expand * hidden``, ``N = mamba_d_state``, ``R =
mamba_dt_rank``)::

    [x | z] = W_in n
    x = silu(conv(x))              depthwise, causal, ``mamba_d_conv`` taps, a bias;
                                   tap k multiplies position t - (taps - 1) + k
    [d | B | C] = W_x x            R | N | N
    D_t = softplus(W_dt d + b_dt),  A = -exp(A_log)                   [inner, N]
    S_t[c, n] = exp(D_t[c] A[c, n]) S_{t-1}[c, n] + D_t[c] x_t[c] B_t[n]
    y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]
    m_t = y_t   (layer L/2: before the gate, ``D x`` in it)
    out = W_out( y_t * silu(z_t) )

**Differential attention.**  ``[q | k | v] = W_qkv n + b``: ``H_q`` query
heads and ``H_kv`` key/value heads of ``d = hidden / H_q``.  Adjacent heads
pair up: differential query head ``j`` is ``(q_{2j}, q_{2j+1})``,
differential key/value head ``g`` is ``(k_{2g}, k_{2g+1})`` with the
``2d``-wide value ``V_g = [v_{2g} | v_{2g+1}]``; head ``j`` reads ``g = j //
(H_q / H_kv)``.  With ``P_a = softmax(q_{2j+a} k_{2g+a}^T / sqrt(d) + mask)``::

    o_j = P_0 V_g - lambda P_1 V_g
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 i)                      i: the layer's index
    out = W_o [ RMSNorm_2d(o_j) * (1 - lambda_init) ]_j + b_o      learned gain, eps 1e-5

**The recurrence is a plain scan over positions and attention a masked
softmax**: no cache, no chunk, no ring, no zero-filled queries.  The program
lays paired heads side by side and runs both softmaxes as one grouped
product, scans prefill in chunks from a carried state, and decodes a token
at a time over pooled caches of which one row is read by eight layers; this
file shares none of that.

Weights come as a dict ``path -> array`` from ``harness.weights``.  This
file imports nothing of the program.

**What the harness's seeding means for these leaves** (:func:`seeded`, by
the configuration's ``seeding``; the kind applies the same function to the
leaves the program serves).  ``harness/weights.py`` seeds every vector leaf
as a gain ``1 + 0.1 n``: read so, ``lq . lk`` is about ``d`` and ``lambda``
the difference of two ``exp(d)``.  The four ``lambda`` vectors are read as
``gain - 1`` (a normal of 0.1, the published initialisation).  ``A_log`` is
set so that ``A`` runs ``seeding.A`` = 1..N over the states of every
channel, and ``dt_proj.bias`` so that the step size is log-uniform in
``seeding.dt`` (Mamba's published initialisation: memories of ten to a
thousand tokens).  ``x_proj``'s rows for ``B`` and ``C`` come at ``fan_in **
-0.5`` on an input of rms about 0.6, which leaves the state's part of ``y``
an eighth of ``D x`` beside it (rms 0.08 against 0.62 at a width of 512): they
are multiplied by ``seeding.bc_scale`` (4: the state's part grows with its
square, to 1.2), so that a state carried, reset or rounded wrongly moves the
logits.
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


# ---- sizes and the layout -----------------------------------------------------

def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    h = cfg["hidden_size"]
    rank = cfg.get("mamba_dt_rank", "auto")
    return {"inner": cfg.get("mamba_expand", 2) * h,
            "state": cfg.get("mamba_d_state", 16),
            "taps": cfg.get("mamba_d_conv", 4),
            "rank": -(-h // 16) if rank == "auto" else int(rank),
            "head": h // cfg["num_attention_heads"]}


def layer_kind(cfg: Dict[str, Any], i: int) -> str:
    """``"mamba"``, ``"window"``, ``"full"``, ``"memory"`` or ``"cross"``."""
    half = cfg["num_hidden_layers"] // 2
    if i < half:
        return "mamba" if i % 2 == 0 else "window"
    if i <= half + 1:
        return "mamba" if i == half else "full"
    return "memory" if i % 2 == 0 else "cross"


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


# ---- the pieces -----------------------------------------------------------------

def layer_norm(x, gain, bias, eps):
    import jax
    import jax.numpy as jnp
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain + bias


def _linear(x, w, q, b=None):
    import jax
    import jax.numpy as jnp
    y = jnp.einsum("...i,oi->...o", q(x), q(w),
                   precision=jax.lax.Precision.HIGHEST)
    return y if b is None else y + b


def recurrence(x, dt, a, b, c):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) b_t``, ``y_t = S_t c_t``
    from ``S = 0``, one position after another: ``x``, ``dt [T, inner]``,
    ``a [inner, N]``, ``b`` and ``c [T, N]`` -> ``y [T, inner]``.  ``S`` is
    ``[inner, N]``."""
    import jax
    import jax.numpy as jnp

    def one(s, at):
        x_t, dt_t, b_t, c_t = at
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=-1)
    return jax.lax.scan(one, jnp.zeros(a.shape, jnp.float32),
                        (x, dt, b, c))[1]


def mamba(u, w: Dict[str, Any], cfg: Dict[str, Any], q):
    """The Mamba-1 mixer on ``u [B, T, H]`` -> ``(out [B, T, H], y [B, T,
    inner])``; ``w`` holds its leaves by their names inside it
    (``.in_proj.weight``)."""
    import jax
    import jax.numpy as jnp
    z_ = sizes(cfg)
    inner, n, taps, rank = z_["inner"], z_["state"], z_["taps"], z_["rank"]
    T = u.shape[1]
    proj = _linear(u, w[".in_proj.weight"], q)
    x, z = proj[..., :inner], proj[..., inner:]
    before = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(w[".conv.weight"][:, k] * before[:, k:k + T]
                        for k in range(taps)) + w[".conv.bias"])
    sel = _linear(x, w[".x_proj.weight"], q)
    dt = jax.nn.softplus(_linear(sel[..., :rank], w[".dt_proj.weight"], q,
                                 w[".dt_proj.bias"]))
    b, c = sel[..., rank:rank + n], sel[..., rank + n:]
    a = -jnp.exp(w[".A_log"])
    y = jax.vmap(lambda x_, dt_, b_, c_: recurrence(x_, dt_, a, b_, c_))(
        x, dt, b, c) + w[".D"] * x
    return _linear(y * jax.nn.silu(z), w[".out_proj.weight"], q), y


def _softmaxes(qh, kh, vh, window, q, in_blocks: bool):
    """``softmax(q_h k_{h // r}^T / sqrt(d) + mask) V_{h // (2 r)}`` for
    every query head ``h``: ``qh [B, Hq, T, d]``, ``kh [B, Hkv, T, d]``,
    ``vh [B, Hkv / 2, T, 2d]`` (the paired values), ``r = Hq / Hkv`` ->
    ``[B, Hq, T, 2d]``.  The mask is causal, and over the last ``window``
    positions (a number, possibly traced; ``T`` or more: everything).
    ``in_blocks``: where the scores of the whole sequence pass SCORES_BYTES
    the queries go Q_BLOCK at a time, each block against every key: the
    same rows of the same softmax."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, Hq, T, d = qh.shape
    Hkv = kh.shape[1]
    r = Hq // Hkv
    # query head h is member a = h % 2 of differential head j = h // 2,
    # which reads differential key/value head g = j // r: key head 2g + a
    # and the paired value g
    heads = jnp.arange(Hq)
    k_of = 2 * ((heads // 2) // r) + heads % 2
    v_of = (heads // 2) // r
    keys, vals = q(kh)[:, k_of], q(vh)[:, v_of]  # [B, Hq, T, .]
    cols = jnp.arange(T)

    def rows(q_rows, first):
        s = jnp.einsum("bhqd,bhkd->bhqk", q(q_rows), keys,
                       precision=hi) / math.sqrt(d)
        at = first + jnp.arange(q_rows.shape[2])
        dist = at[:, None] - cols[None, :]
        s = jnp.where((dist >= 0) & (dist < window), s, NEG)
        return jnp.einsum("bhqk,bhkd->bhqd", q(jax.nn.softmax(s, axis=-1)),
                          vals, precision=hi)

    if not in_blocks or B * Hq * T * T * 4 <= SCORES_BYTES or T % Q_BLOCK:
        return rows(qh, 0)
    n = T // Q_BLOCK
    blocks = qh.reshape(B, Hq, n, Q_BLOCK, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(lambda a: rows(a[0], a[1]),
                      (blocks, jnp.arange(n) * Q_BLOCK))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, Hq, T, -1)


def differential(n, w: Dict[str, Any], cfg: Dict[str, Any], q, lam_init,
                 window, kv=None, in_blocks: bool = False):
    """Differential attention on ``n [B, T, H]`` (normed) -> ``(out, k, v)``
    with ``k``, ``v [B, T, Hkv * d]`` its keys and values as projected.  A
    cross layer is handed ``kv = (k, v)`` of the layer it reads and projects
    a query alone.  ``w`` holds the leaves by their names inside the
    attention (``.q_layer.weight``: ``[q | k | v]`` in one)."""
    import jax
    import jax.numpy as jnp
    B, T, _ = n.shape
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = sizes(cfg)["head"], cfg.get("layer_norm_eps", 1e-5)
    proj = _linear(n, w[".q_layer.weight"], q, w[".q_layer.bias"])
    if kv is None:
        k = proj[..., hq * d:(hq + hkv) * d]
        v = proj[..., (hq + hkv) * d:]
    else:
        k, v = kv
    qh = proj[..., :hq * d].reshape(B, T, hq, d).transpose(0, 2, 1, 3)
    kh = k.reshape(B, T, hkv, d).transpose(0, 2, 1, 3)
    vh = v.reshape(B, T, hkv // 2, 2 * d).transpose(0, 2, 1, 3)
    ctx = _softmaxes(qh, kh, vh, window, q, in_blocks)       # [B, Hq, T, 2d]
    lam = jnp.exp(jnp.sum(w[".lambda_q1"] * w[".lambda_k1"])) \
        - jnp.exp(jnp.sum(w[".lambda_q2"] * w[".lambda_k2"])) + lam_init
    pairs = ctx.reshape(B, hq // 2, 2, T, 2 * d)
    o = pairs[:, :, 0] - lam * pairs[:, :, 1]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + eps) * w[".norm.weight"] * (1.0 - lam_init)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, hq * d)
    return _linear(o, w[".output_layer.weight"], q,
                   w[".output_layer.bias"]), k, v


def memory_unit(n, m, w: Dict[str, Any], q):
    import jax
    return _linear(jax.nn.silu(_linear(n, w[".in_proj.weight"], q)) * m,
                   w[".out_proj.weight"], q)


def _feed_forward(h, w: Dict[str, Any], cfg: Dict[str, Any], q):
    import jax
    n = layer_norm(h, w[".ffn_norm.weight"], w[".ffn_norm.bias"],
                   cfg.get("layer_norm_eps", 1e-5))
    return h + _linear(jax.nn.silu(_linear(n, w[".ffn.gate.weight"], q))
                       * _linear(n, w[".ffn.up.weight"], q),
                       w[".ffn.down.weight"], q)


def _inside(params: Dict[str, Any], p: str) -> Dict[str, Any]:
    """The leaves under the path ``p``, by their names inside it."""
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p + ".")}


def _pre_norm(x, w, cfg):
    return layer_norm(x, w[".attn_norm.weight"], w[".attn_norm.bias"],
                      cfg.get("layer_norm_eps", 1e-5))


# ---- seeding --------------------------------------------------------------------

def seeded(w: Dict[str, Any], cfg: Dict[str, Any], dtype) -> Dict[str, Any]:
    """One layer's leaves (by their names inside it: ``.ssm.A_log``,
    ``.attn.lambda_q1``) as the configuration's ``seeding`` reads what
    ``harness.weights`` seeded (this file's docstring), rounded to
    ``dtype`` (what is served) and returned in the dtype they came in.
    Leaves it has no rule for pass unchanged."""
    import jax
    import jax.numpy as jnp
    rule, f32 = cfg["seeding"], jnp.float32
    out = dict(w)

    def served(new, old):
        return new.astype(dtype).astype(old.dtype)
    for name in (".attn.lambda_q1", ".attn.lambda_k1", ".attn.lambda_q2",
                 ".attn.lambda_k2"):
        if name in w:
            out[name] = served(w[name].astype(f32) - 1.0, w[name])
    if ".ssm.A_log" in w:
        z_ = sizes(cfg)
        n, rank = z_["state"], z_["rank"]
        lo, hi = rule["A"]
        a = lo + (hi - lo) * jnp.arange(n, dtype=f32) / max(n - 1, 1)
        out[".ssm.A_log"] = served(
            jnp.broadcast_to(jnp.log(a), w[".ssm.A_log"].shape),
            w[".ssm.A_log"])
        # the bias came as 0.02 n: u = Phi(n) is uniform
        lo, hi = rule["dt"]
        bias = w[".ssm.dt_proj.bias"]
        u = jax.scipy.special.ndtr(bias.astype(f32) / 0.02)
        dt = lo * (hi / lo) ** u
        out[".ssm.dt_proj.bias"] = served(dt + jnp.log(-jnp.expm1(-dt)),
                                          bias)
        proj = w[".ssm.x_proj.weight"]
        scale = jnp.concatenate([jnp.ones((rank,), f32),
                                 jnp.full((2 * n,), rule["bc_scale"], f32)])
        out[".ssm.x_proj.weight"] = served(proj.astype(f32) * scale[:, None],
                                           proj)
    return out


# ---- the whole model at once (the tests' sizes) ----------------------------------

def forward(params: Dict[str, Any], cfg: Dict[str, Any], tokens,
            precision: str = "float32", memory_after_gate: bool = False,
            cross_window=None):
    """Logits ``[B, T, vocab]`` of 1-based ``tokens [B, T]``.  The two last
    arguments are the controls' (what a wrong reading of the architecture
    would compute): ``m`` taken after the gate, and the cross layers
    attending only the last ``cross_window`` positions of the row."""
    import jax
    import jax.numpy as jnp
    q = quantizer(precision)
    x = params[".embedding.weight"][tokens - TOKEN_BASE]
    T = tokens.shape[1]
    m = row = None
    for i in range(cfg["num_hidden_layers"]):
        w, kind = _inside(params, f".blocks[{i}]"), layer_kind(cfg, i)
        n = _pre_norm(x, w, cfg)
        if kind == "mamba":
            out, y = mamba(n, _inside(w, ".ssm"), cfg, q)
            if i == cfg["num_hidden_layers"] // 2:
                m = y
                if memory_after_gate:
                    inner = sizes(cfg)["inner"]
                    z = _linear(n, w[".ssm.in_proj.weight"], q)[..., inner:]
                    m = y * jax.nn.silu(z)
        elif kind == "memory":
            out = memory_unit(n, m, _inside(w, ".unit"), q)
        else:
            window = cfg["sliding_window"] if kind == "window" else T
            if kind == "cross" and cross_window is not None:
                window = cross_window
            out, k, v = differential(
                n, _inside(w, ".attn"), cfg, q, lambda_init(i), window,
                kv=row if kind == "cross" else None)
            if kind == "full":
                row = (k, v)
        x = _feed_forward(x + out, w, cfg, q)
    n = layer_norm(x, params[".final_norm.weight"],
                   params[".final_norm.bias"], cfg.get("layer_norm_eps", 1e-5))
    return _linear(n, params[".embedding.weight"], q)


# ---- the model a block at a time, as the serving check walks it -------------
# (``harness.kinds.shared_kv_ssm_lm.param_blocks`` names the blocks: the
# embedding, each layer, the final norm with the tied head).  What the check
# hands from block to block is one array a request: **the residual stream and,
# beside it along the last axis, what later layers read** — ``m`` (``inner``
# wide, written by layer L/2) and layer L/2 + 1's keys and values (``2 Hkv d``
# wide): ``[B, T, H + inner + 2 Hkv d]``.  Each step takes only its own
# block's leaves; the layers of one kind share one compiled program (the
# layer's index enters as numbers: ``lambda_init``, the window, whether this
# layer writes ``m`` or the row).

_STEPS: Dict[Any, Any] = {}
_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "intermediate_size", "layer_norm_eps", "mamba_d_state",
         "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "vocab_size")


def _step(name: str, cfg: Dict[str, Any], precision: str, build, **jit_kw):
    import jax
    key = (name, precision) + tuple(cfg.get(k) for k in _KEYS)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(build(quantizer(precision)), **jit_kw)
    return _STEPS[key]


def _widths(cfg):
    h, inner = cfg["hidden_size"], sizes(cfg)["inner"]
    kv = cfg["num_key_value_heads"] * sizes(cfg)["head"]
    return h, inner, kv


def _layer(kind: str, cfg: Dict[str, Any], q):
    """One layer of ``kind`` on the carried array: ``(w, carried, lam_init,
    window, writes) -> carried``."""
    import jax.numpy as jnp
    h, inner, kvw = _widths(cfg)

    def run(w, carried, lam_init, window, writes):
        x = carried[..., :h]
        m = carried[..., h:h + inner]
        k = carried[..., h + inner:h + inner + kvw]
        v = carried[..., h + inner + kvw:]
        n = _pre_norm(x, w, cfg)
        if kind == "mamba":
            out, y = mamba(n, _inside(w, ".ssm"), cfg, q)
            m = jnp.where(writes, y, m)
        elif kind == "memory":
            out = memory_unit(n, m, _inside(w, ".unit"), q)
        elif kind == "cross":
            out, _, _ = differential(n, _inside(w, ".attn"), cfg, q,
                                     lam_init, window, kv=(k, v),
                                     in_blocks=True)
        else:
            out, k_new, v_new = differential(n, _inside(w, ".attn"), cfg, q,
                                             lam_init, window,
                                             in_blocks=True)
            k, v = jnp.where(writes, k_new, k), jnp.where(writes, v_new, v)
        x = _feed_forward(x + out, w, cfg, q)
        return jnp.concatenate([x, m, k, v], axis=-1)
    return run


def embed(params: Dict[str, Any], cfg: Dict[str, Any], tokens):
    """``[B, T]`` 1-based token ids to what the blocks carry: the residual
    stream ``[B, T, H]`` (no scale, no position) and zeros beside it for
    ``m`` and the shared keys and values."""
    import jax.numpy as jnp
    h, inner, kvw = _widths(cfg)

    def build(q):
        def run(emb, t):
            x = emb[t - TOKEN_BASE]
            return jnp.concatenate(
                [x, jnp.zeros(x.shape[:-1] + (inner + 2 * kvw,), x.dtype)],
                axis=-1)
        return run
    return _step("embed", cfg, "float32", build)(
        params[".embedding.weight"], tokens)


def block(params: Dict[str, Any], cfg: Dict[str, Any], i: int, x,
          precision: str = "float32"):
    """Layer ``i`` on the carried array; ``x`` is given up to the result.
    ``params`` are the harness's seeded leaves, read as the configuration's
    ``seeding`` says (:func:`seeded`)."""
    import jax.numpy as jnp
    kind, half = layer_kind(cfg, i), cfg["num_hidden_layers"] // 2
    w = seeded(_inside(params, f".blocks[{i}]"), cfg,
               jnp.dtype(cfg["serving"]["weights_dtype"]))
    program = "self" if kind in ("window", "full") else kind
    window = cfg["sliding_window"] if kind == "window" else x.shape[1]
    return _step(program, cfg, precision,
                 lambda q: _layer("full" if program == "self" else kind, cfg,
                                  q),
                 donate_argnums=1)(
        w, x, jnp.float32(lambda_init(i)), jnp.int32(window),
        jnp.asarray(i in (half, half + 1)))


def head(params: Dict[str, Any], cfg: Dict[str, Any], rows,
         precision: str = "float32"):
    """Logits ``[..., vocab]`` of the carried array's ``rows`` (their
    residual stream): the last LayerNorm and the embedding table as the
    head, its rows a power of two of equal blocks at a time where the table
    passes HEAD_BYTES."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    h = cfg["hidden_size"]

    def build(q):
        def run(p, r):
            n = q(layer_norm(r[..., :h], p[".final_norm.weight"],
                             p[".final_norm.bias"],
                             cfg.get("layer_norm_eps", 1e-5)))
            w = p[".embedding.weight"]
            parts = 1
            while w.size * 4 > parts * HEAD_BYTES \
                    and w.shape[0] % (2 * parts) == 0:
                parts *= 2
            if parts == 1:
                return jnp.einsum("...h,vh->...v", n, q(w), precision=hi)
            out = jax.lax.map(
                lambda rows_: jnp.einsum("...h,vh->...v", n, q(rows_),
                                         precision=hi),
                w.reshape((parts, w.shape[0] // parts) + w.shape[1:]))
            return jnp.moveaxis(out, 0, -2).reshape(n.shape[:-1]
                                                    + w.shape[:1])
        return run
    return _step("head", cfg, precision, build)(params, rows)

"""Pre-LayerNorm decoder-only transformer in plain float32: the block of
OPT (Zhang et al. 2022, arXiv:2205.01068) as the repo's ``TransformerLM``
runs it.  Departures from OPT, also listed in the configuration under
``assumed``: sinusoidal positions added to the scaled embedding instead
of learned ones; token ids are 1-based with one extra, never-trained row
in the tied embedding/head; attention projections carry no bias;
LayerNorm eps 1e-6.

Weights come as a dict ``path -> array`` from ``harness.weights``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

from reference.precision import quantizer

LN_EPS = 1e-6
NEG = -1e9


def positions(length: int, hidden: int):
    import jax.numpy as jnp
    half = hidden // 2
    inc = math.log(1.0e4) / max(half - 1, 1)
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32) * -inc)
    scaled = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=1)


def layer_norm(x, w, b):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * w + b


def block(x, params: Dict[str, Any], p: str, heads: int, q):
    """One decoder block on ``x [B, T, H]`` with a causal mask."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, T, H = x.shape
    d = H // heads

    def lin(a, w):
        return jnp.einsum("bti,oi->bto", q(a), q(params[w]), precision=hi)

    def split(a):
        return a.reshape(B, T, heads, d).transpose(0, 2, 1, 3)

    xn = layer_norm(x, params[p + ".self_norm.weight"], params[p + ".self_norm.bias"])
    qh = split(lin(xn, p + ".self_attn.q_layer.weight"))
    kh = split(lin(xn, p + ".self_attn.k_layer.weight"))
    vh = split(lin(xn, p + ".self_attn.v_layer.weight"))
    s = jnp.einsum("bhqd,bhkd->bhqk", q(qh), q(kh), precision=hi) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask, s, NEG)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", q(w), q(vh), precision=hi)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, H)
    x = x + lin(ctx, p + ".self_attn.output_layer.weight")
    y = layer_norm(x, params[p + ".ffn_norm.weight"], params[p + ".ffn_norm.bias"])
    y = jax.nn.relu(lin(y, p + ".ffn.filter_layer.weight")
                    + params[p + ".ffn.filter_layer.bias"])
    y = lin(y, p + ".ffn.output_layer.weight") + params[p + ".ffn.output_layer.bias"]
    return x + y


def hidden_states(params: Dict[str, Any], tokens, cfg: Dict[str, Any], q,
                  prefix: str = "", remat: bool = False):
    """Final-norm hidden states ``[B, T, H]`` for 1-based ``tokens``."""
    import jax
    H = cfg["hidden_size"]
    emb = params[prefix + ".embedding.weight"]
    x = emb[tokens - 1] * math.sqrt(H) + positions(tokens.shape[1], H)
    for i in range(cfg["num_hidden_layers"]):
        f = (lambda x_, p=f"{prefix}.blocks[{i}]":
             block(x_, params, p, cfg["num_attention_heads"], q))
        x = jax.checkpoint(f)(x) if remat else f(x)
    return layer_norm(x, params[prefix + ".final_norm.weight"],
                      params[prefix + ".final_norm.bias"])


def logits_of(params, hidden, q, prefix: str = ""):
    import jax
    import jax.numpy as jnp
    return jnp.einsum("...h,vh->...v", q(hidden),
                      q(params[prefix + ".embedding.weight"]),
                      precision=jax.lax.Precision.HIGHEST)


def loss_fn(params, x, y, cfg, q, prefix):
    """Mean cross-entropy of next-token targets ``y [B*T]`` (1-based)."""
    import jax
    import jax.numpy as jnp
    h = hidden_states(params, x, cfg, q, prefix, remat=True)
    logits = logits_of(params, h, q, prefix).reshape(-1, cfg["vocab_size"] + 1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, (y - 1)[:, None], axis=1))


def train_losses(params: Dict[str, Any], x, y, cfg: Dict[str, Any],
                 optimizer: Dict[str, Any], steps: int,
                 precision: str = "float32", prefix: str = "") -> List[float]:
    """The loss of each of the first ``steps`` steps of Adam (bias
    corrected, no weight decay) on the one batch ``(x, y)``.  Sharded
    weights stay sharded: the compiler partitions the plain program."""
    import jax
    import jax.numpy as jnp
    q = quantizer(precision)
    lr = optimizer["lr"]
    b1, b2, eps = (optimizer.get("beta1", 0.9), optimizer.get("beta2", 0.999),
                   optimizer.get("epsilon", 1e-8))
    frozen = tuple(sorted(cfg.items(), key=lambda kv: kv[0]))

    def step(p, m, v, t, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y, dict(frozen), q, prefix)
        t = t + 1
        m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree_util.tree_map(
            lambda p_, m_, v_: p_ - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
            p, m, v)
        return p, m, v, t, loss

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    t = jnp.zeros((), jnp.float32)
    out = []
    for _ in range(steps):
        params, m, v, t, loss = step(params, m, v, t, x, y)
        out.append(float(loss))
    return out


_FWD_CACHE: Dict[Any, Any] = {}


def _served_forward(frozen, served_pad: int, precision: str):
    """Jitted: logits of ``served_pad`` consecutive positions from
    ``start`` on, in ``precision``; built once per shape."""
    import jax
    key = (frozen, served_pad, precision)
    if key not in _FWD_CACHE:
        q = quantizer(precision)

        def fwd(params, seq, start):
            h = hidden_states(params, seq, dict(frozen), q)
            # only the positions that predicted a served token need logits
            h = jax.lax.dynamic_slice_in_dim(h[0], start, served_pad, axis=0)
            lg = logits_of(params, h, q)
            return lg.at[:, -1].set(NEG)      # the never-trained extra row

        _FWD_CACHE[key] = jax.jit(fwd)
    return _FWD_CACHE[key]


def served_gaps(params: Dict[str, Any], cfg: Dict[str, Any],
                prompt: Sequence[int], served: Sequence[int],
                pad_to: int, served_pad: int, control: str = "") -> Dict[str, float]:
    """For one finished request, the widest gap by which a served token's
    logit lies below the reference's best at its position.  The
    reference runs once over the prompt followed by the served tokens
    (padded to ``pad_to``; under a causal mask padding changes nothing
    before it).  With ``control`` (a lower precision) it also reads the
    gap of the token that precision puts first at each position."""
    import jax.numpy as jnp
    import numpy as np
    n_p, n_s = len(prompt), len(served)
    seq = np.ones((1, pad_to), np.int32)
    seq[0, :n_p] = prompt
    seq[0, n_p:n_p + n_s - 1] = served[:-1]
    frozen = tuple(sorted((k, v) for k, v in cfg.items()
                          if isinstance(v, (int, float, str, bool))))
    served_pad = min(max(served_pad, n_s), pad_to)
    start = min(n_p - 1, pad_to - served_pad)
    off = (n_p - 1) - start
    seq_d = jnp.asarray(seq)
    ref = _served_forward(frozen, served_pad, "float32")(
        params, seq_d, start)[off:off + n_s]
    best = jnp.max(ref, axis=-1)
    tok = jnp.asarray(np.asarray(served, np.int32) - 1)
    gap = best - jnp.take_along_axis(ref, tok[:, None], axis=1)[:, 0]
    out = {"gap_max": float(jnp.max(gap)), "positions": n_s}
    if control:
        low = _served_forward(frozen, served_pad, control)(
            params, seq_d, start)[off:off + n_s]
        pick = jnp.argmax(low, axis=-1)
        cgap = best - jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]
        out["control_gap_max"] = float(jnp.max(cgap))
        out["control_gap_median"] = float(jnp.median(cgap))
        out["gap_median"] = float(jnp.median(gap))
    return out

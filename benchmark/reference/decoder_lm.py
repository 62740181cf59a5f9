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
from typing import Any, Dict, List

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


# scores of one request's attention, [heads, T, T] float32, above which
# the queries go in blocks of Q_BLOCK: max_len squared would not fit
SCORES_BYTES = 1 << 30
Q_BLOCK = 512


def attention(qh, kh, vh, q, in_blocks: bool = False):
    """Causal attention of ``[B, heads, T, d]`` queries over keys and
    values of the same length.  ``in_blocks``, where the scores of the
    whole sequence pass SCORES_BYTES the queries go Q_BLOCK at a time,
    each block against every key: the same rows of the same softmax."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, heads, T, d = qh.shape
    cols = jnp.arange(T)

    def rows(q_rows, first):
        s = jnp.einsum("bhqd,bhkd->bhqk", q(q_rows), q(kh), precision=hi) \
            / math.sqrt(d)
        mask = (first + jnp.arange(q_rows.shape[2]))[:, None] >= cols[None, :]
        w = jax.nn.softmax(jnp.where(mask, s, NEG), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", q(w), q(vh), precision=hi)

    if not in_blocks or B * heads * T * T * 4 <= SCORES_BYTES or T % Q_BLOCK:
        return rows(qh, 0)
    blocks = qh.reshape(B, heads, T // Q_BLOCK, Q_BLOCK, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(lambda a: rows(a[0], a[1]),
                      (blocks, jnp.arange(T // Q_BLOCK) * Q_BLOCK))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, heads, T, d)


def _block(x, w: Dict[str, Any], heads: int, q, in_blocks: bool = False):
    """One decoder block on ``x [B, T, H]`` with a causal mask; ``w`` holds
    the block's leaves by their names inside it (``.self_norm.weight``)."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    B, T, H = x.shape
    d = H // heads

    def lin(a, name):
        return jnp.einsum("bti,oi->bto", q(a), q(w[name]), precision=hi)

    def split(a):
        return a.reshape(B, T, heads, d).transpose(0, 2, 1, 3)

    xn = layer_norm(x, w[".self_norm.weight"], w[".self_norm.bias"])
    ctx = attention(split(lin(xn, ".self_attn.q_layer.weight")),
                    split(lin(xn, ".self_attn.k_layer.weight")),
                    split(lin(xn, ".self_attn.v_layer.weight")), q, in_blocks)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, H)
    x = x + lin(ctx, ".self_attn.output_layer.weight")
    y = layer_norm(x, w[".ffn_norm.weight"], w[".ffn_norm.bias"])
    y = jax.nn.relu(lin(y, ".ffn.filter_layer.weight")
                    + w[".ffn.filter_layer.bias"])
    y = lin(y, ".ffn.output_layer.weight") + w[".ffn.output_layer.bias"]
    return x + y


def _inside(params: Dict[str, Any], p: str) -> Dict[str, Any]:
    """The leaves under the path ``p``, by their names inside it."""
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p + ".")}


def _embed(emb, tokens, hidden: int):
    return emb[tokens - 1] * math.sqrt(hidden) + positions(tokens.shape[1], hidden)


def hidden_states(params: Dict[str, Any], tokens, cfg: Dict[str, Any], q,
                  prefix: str = "", remat: bool = False):
    """Final-norm hidden states ``[B, T, H]`` for 1-based ``tokens``."""
    import jax
    x = _embed(params[prefix + ".embedding.weight"], tokens, cfg["hidden_size"])
    for i in range(cfg["num_hidden_layers"]):
        f = (lambda x_, w=_inside(params, f"{prefix}.blocks[{i}]"):
             _block(x_, w, cfg["num_attention_heads"], q))
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


# ---- the model a block at a time, as the serving check walks it -------------
# (``harness.kinds.decoder_lm.param_blocks`` names the blocks: the embedding,
# each layer, the final norm with the tied head).  Each step takes only its
# own block's leaves, by their full paths, and the name of the precision its
# matrix products run in; layers share one compiled program.

TOKEN_BASE = 1      # column j of the head's logits scores token id j + 1

_STEPS: Dict[Any, Any] = {}


def _step(name: str, cfg: Dict[str, Any], precision: str, build, **jit_kw):
    import jax
    key = (name, cfg["hidden_size"], cfg["num_attention_heads"], precision)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(build(quantizer(precision)), **jit_kw)
    return _STEPS[key]


def embed(params: Dict[str, Any], cfg: Dict[str, Any], tokens):
    """``[B, T]`` 1-based token ids to the residual stream ``[B, T, H]``."""
    return _step("embed", cfg, "float32", lambda q: lambda emb, t: _embed(
        emb, t, cfg["hidden_size"]))(params[".embedding.weight"], tokens)


def block(params: Dict[str, Any], cfg: Dict[str, Any], i: int, x,
          precision: str = "float32"):
    """Layer ``i`` on ``x [B, T, H]``; ``x`` is given up to the result."""
    return _step("block", cfg, precision, lambda q: lambda w, x_: _block(
        x_, w, cfg["num_attention_heads"], q, in_blocks=True), donate_argnums=1)(
            _inside(params, f".blocks[{i}]"), x)


def head(params: Dict[str, Any], cfg: Dict[str, Any], rows,
         precision: str = "float32"):
    """Logits ``[..., vocab + 1]`` of the residual stream's ``rows``: the
    final norm and the tied head, the never-trained extra row at NEG."""
    def build(q):
        def f(w, r):
            h = layer_norm(r, w[".final_norm.weight"], w[".final_norm.bias"])
            return logits_of(w, h, q).at[..., -1].set(NEG)
        return f
    return _step("head", cfg, precision, build)(params, rows)

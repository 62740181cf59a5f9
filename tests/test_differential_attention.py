"""Differential attention (``nn.differential_attention``) on the CPU at a
small size against ``benchmark/reference/shared_kv_ssm_lm.py``'s paired
form (two softmaxes over 64-wide-style heads, subtracted; loaded by path,
nothing of the program): a window layer, a full layer and a cross layer
over a whole sequence; chunks and steps through a ring and a row equal the
whole sequence; the side-by-side layout of paired heads equals the paired
form; the step through the chip's kernels (the ragged decode kernel and
the row writer, interpreted) equals the XLA product."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import shared_kv_ssm_lm as ref                 # noqa: E402

from bigdl_tpu.nn.differential_attention import (             # noqa: E402
    DifferentialAttention, lambda_init)
from bigdl_tpu.ops import attention_kernels, cache_kernels    # noqa: E402

HIDDEN, HQ, HKV, D, WINDOW, T, MAX_LEN = 32, 8, 4, 4, 6, 21, 32
CFG = dict(hidden_size=HIDDEN, num_attention_heads=HQ,
           num_key_value_heads=HKV, layer_norm_eps=1e-5)
TOL = 2e-5


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def build(depth, window=None, cross=False, seed=0, **sizes):
    """The layer on seeded leaves: biases and lambda vectors a normal of
    0.1 (so that lambda is neither 0 nor huge), gains 1 +- 0.1."""
    sizes = dict(dict(hidden_size=HIDDEN, num_heads=HQ, num_kv_heads=HKV,
                      head_dim=D), **sizes)
    m = DifferentialAttention(depth=depth, window=window, cross=cross,
                              **sizes).eval_mode()
    flat, tree = jax.tree_util.tree_flatten_with_path(m)
    key, leaves = jax.random.key(seed), []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        if leaf.ndim == 1:
            small = "lambda" in name or name.endswith("bias")
            leaf = (0.3 if "lambda" in name else 0.1) * noise if small \
                else 1 + 0.1 * noise
        else:
            leaf = noise * leaf.shape[-1] ** -0.5
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(tree, leaves)


def leaves_of(m):
    flat = jax.tree_util.tree_flatten_with_path(m)[0]
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat}


def x_of(t=T, batch=2, seed=1, hidden=HIDDEN):
    return jax.random.normal(jax.random.key(seed), (batch, t, hidden))


def close(a, b, tol=TOL):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b)))) <= tol


def reference(m, x, depth, window, kv=None, cfg=CFG):
    return ref.differential(x, leaves_of(m), cfg, lambda a: a,
                            lambda_init(depth), window, kv=kv)


def test_lambda_init_follows_the_layers_index():
    assert lambda_init(0) == pytest.approx(0.2)
    assert lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    m = build(depth=3)
    lam = float(m._lambda())
    w = leaves_of(m)
    want = np.exp(float(jnp.sum(w[".lambda_q1"] * w[".lambda_k1"]))) \
        - np.exp(float(jnp.sum(w[".lambda_q2"] * w[".lambda_k2"]))) \
        + lambda_init(3)
    assert lam == pytest.approx(want, rel=1e-5)
    assert abs(lam - lambda_init(3)) > 1e-3          # the vectors count


@pytest.mark.parametrize("kind", ["window", "full"])
def test_whole_sequence_equals_the_paired_reference(kind):
    window = WINDOW if kind == "window" else None
    m, x = build(depth=5, window=window), x_of()
    y, kv = m.forward(x)
    want, k, v = reference(m, x, 5, WINDOW if window else T)
    assert close(y, want)
    # the cache's layout: paired heads side by side, [B, Hkv/2, T, 2d]
    assert kv["k"].shape == kv["v"].shape == (2, HKV // 2, T, 2 * D)
    assert close(kv["k"].transpose(0, 2, 1, 3).reshape(2, T, -1), k)
    assert close(kv["v"].transpose(0, 2, 1, 3).reshape(2, T, -1), v)


def test_a_cross_layer_attends_the_row_it_is_handed():
    full, cross = build(depth=5), build(depth=7, cross=True, seed=3)
    x, x2 = x_of(), x_of(seed=2)
    _, kv = full.forward(x)
    y, same = cross.forward(x2, shared=kv)
    _, k, v = reference(full, x, 5, T)
    want, _, _ = reference(cross, x2, 7, T, kv=(k, v))
    assert close(y, want) and same is kv
    w = leaves_of(cross)
    assert w[".q_layer.weight"].shape == (HQ * D, HIDDEN)   # a query alone
    assert cross.init_cache(2, MAX_LEN) == {}


def test_a_wrong_sign_of_lambda_or_a_wrong_pairing_is_seen():
    """What the comparison above would catch: the subtraction as an
    addition, and head j reading g = j instead of j // 2."""
    m, x = build(depth=5), x_of()
    want, _, _ = reference(m, x, 5, T)
    y, _ = m.forward(x)
    flipped = m._lambda
    m2 = build(depth=5)
    object.__setattr__(m2, "_lambda", lambda: -flipped())
    assert not close(m2.forward(x)[0], want, 1e-3)
    assert close(y, want)


@pytest.mark.parametrize("kind", ["window", "full"])
def test_chunks_then_steps_through_the_cache_equal_the_whole_sequence(kind):
    """A ring of the window and a chunk's margin, or a full row: two
    chunks (the second wraps the ring), then a position a row."""
    window = WINDOW if kind == "window" else None
    m, x = build(depth=9, window=window), x_of()
    want, _ = m.forward(x)
    cache = m.init_cache(2, MAX_LEN, ring_margin=8)
    assert cache["k"].shape[2] == (WINDOW + 8 if window else MAX_LEN)
    pad = jnp.zeros((2, MAX_LEN), bool)
    outs = []
    for s, w in ((0, 8), (8, 7)):
        y, cache = m.forward(x[:, s:s + w], s, cache, pad)
        outs.append(y)
    for t in range(15, T):
        y, cache = m.forward(x[:, t:t + 1], jnp.full((2,), t, jnp.int32),
                             cache, pad, active=jnp.asarray([True, True]))
        outs.append(y)
    assert close(jnp.concatenate(outs, 1), want)


def test_write_alone_writes_what_forward_writes():
    m, x = build(depth=9), x_of()
    cache = m.init_cache(3, MAX_LEN)
    pad = jnp.zeros((3, MAX_LEN), bool)
    _, want = m.forward(x[:1, :8], 4, cache, pad, slot=1)
    got = m.write(x[:1, :8], 4, cache, slot=1)
    for n in ("k", "v"):
        # a product of fewer columns: the same numbers to rounding
        assert close(got[n], want[n], 1e-5)
        assert float(jnp.max(jnp.abs(got[n][1, :, 4:12]))) > 0.1
        np.testing.assert_array_equal(np.asarray(got[n][0]), 0)
    compact = m.write(x[:, :8])
    _, kv = m.forward(x[:, :8])
    assert close(compact["k"], kv["k"]) and close(compact["v"], kv["v"])


def test_a_cross_layers_step_reads_the_shared_cache_after_its_write():
    """Decode: the full layer writes this step's place, the cross layer
    attends the row as just written (its own position included)."""
    full, cross = build(depth=5), build(depth=7, cross=True, seed=3)
    x, x2 = x_of(), x_of(seed=2)
    _, kv = full.forward(x)
    want, _ = cross.forward(x2, shared=kv)
    cache = full.init_cache(2, MAX_LEN)
    pad = jnp.zeros((2, MAX_LEN), bool)
    _, cache = full.forward(x[:, :10], 0, cache, pad)
    for t in range(10, T):
        index = jnp.full((2,), t, jnp.int32)
        _, cache = full.forward(x[:, t:t + 1], index, cache, pad)
        y, _ = cross.forward(x2[:, t:t + 1], index, {}, pad, shared=cache)
        assert close(y[:, 0], want[:, t]), t
    with pytest.raises(ValueError, match="stop where the caches stop"):
        cross.forward(x2[:1, :4], 0, {}, pad, slot=0, shared=cache)


def test_the_step_through_the_chips_kernels_equals_the_xla_product(
        monkeypatch):
    """At the published head counts (40 query heads of 64 over 20
    key/value heads: a cache of 10 heads of 128), bfloat16 rows of 256
    places: the step's write through ``ops.write_cache_rows`` and its
    attention through ``ops.ragged_decode_attention`` (both interpreted
    here; a TPU process takes them) against the per-row loop and the
    masked product, for a full layer and for a cross layer on its row; an
    idle row rides along."""
    sizes = dict(hidden_size=64, num_heads=40, num_kv_heads=20, head_dim=64)
    full = build(depth=17, **sizes)
    cross = build(depth=19, cross=True, seed=3, **sizes)
    bf = jnp.bfloat16
    x = x_of(t=12, batch=3, hidden=64).astype(bf)
    full, cross = (jax.tree_util.tree_map(lambda a: a.astype(bf), m)
                   for m in (full, cross))
    pad = jnp.zeros((3, 256), bool)
    cache = full.init_cache(3, 256, bf)
    assert cache["k"].shape == (3, 10, 256, 128)
    _, cache = full.forward(x[:, :11], 0, cache, pad)
    index = jnp.asarray([11, 11, 3], jnp.int32)
    active = jnp.asarray([True, True, False])

    def step():
        y, kv = full.forward(x[:, 11:12], index, cache, pad, active=active)
        y2, _ = cross.forward(x[:, 11:12], index, {}, pad, active=active,
                              shared=kv)
        return y, y2, kv
    want, want2, want_kv = step()
    assert full.decode_key_block(cache) is None
    monkeypatch.setattr(
        attention_kernels, "decode_key_block",
        functools.partial(attention_kernels.decode_key_block, force="ragged"))
    monkeypatch.setattr(
        cache_kernels, "cache_row_writer",
        functools.partial(cache_kernels.cache_row_writer, force="kernel"))
    assert full.decode_key_block(cache) == 256
    got, got2, got_kv = step()
    live = np.asarray(active)
    assert close(got[live], want[live], 2e-2)
    assert close(got2[live], want2[live], 2e-2)
    for n in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(got_kv[n][:2].astype(jnp.float32)),
            np.asarray(want_kv[n][:2].astype(jnp.float32)))

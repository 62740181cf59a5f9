"""The products ``nn.HeldExperts`` can take over its held stacks (every
token through every held expert; the pairs laid out by expert in tiles,
``ops.expert_kernels``, interpreted on the CPU) give one result and one
routing count, whatever the routing looks like; what each multiplied
(``rows_computed``); which one a call takes; the shares of a layer add up
to the reference's layer (``benchmark/reference/conv_moe_lm.py``, loaded
by path); and ``route_top_k``'s ``eps``."""

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

from reference import conv_moe_lm as ref                      # noqa: E402

from bigdl_tpu.nn.moe import ROUTING, HeldExperts, route_top_k  # noqa: E402
from bigdl_tpu.ops import expert_kernels                      # noqa: E402

HIDDEN, WIDTH, EXPERTS, TOP_K = 32, 24, 64, 4
PRODUCTS = [name for name in ("every_stack", "tiled", "grouped")
            if hasattr(HeldExperts, "_" + name)]
TOKENS = [1, 7, 128, 384]
TOL = 2e-5


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def layer_of(held=None, seed=3, bias=0.05, experts=EXPERTS, top_k=TOP_K):
    layer = HeldExperts(HIDDEN, WIDTH, experts, top_k, held=held,
                        normalize_eps=1e-6)
    flat, tree = jax.tree_util.tree_flatten_with_path(layer)
    key = jax.random.key(seed)
    return jax.tree_util.tree_unflatten(tree, [
        bias * jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        if leaf.ndim == 1 else jax.random.normal(
            jax.random.fold_in(key, i), leaf.shape) * leaf.shape[-1] ** -0.5
        for i, (p, leaf) in enumerate(flat)])


def through(monkeypatch, product, layer, x, valid=None):
    monkeypatch.setattr(
        HeldExperts, "product_of",
        classmethod(lambda cls, held, experts, tokens: product))
    y, counts = layer.forward(x, valid)
    return np.asarray(y), np.asarray(counts).tolist()


def inputs(tokens, seed=0):
    return jax.random.normal(jax.random.key(seed), (tokens, HIDDEN))


@pytest.mark.parametrize("tokens", TOKENS)
@pytest.mark.parametrize("held", [None, (16, 8)], ids=["all", "a-share"])
def test_every_product_gives_one_result_and_one_count(monkeypatch, held,
                                                      tokens):
    layer, x = layer_of(held), inputs(tokens)
    valid = jnp.arange(tokens) % 5 != 3 if tokens > 1 else None
    got = {p: through(monkeypatch, p, layer, x, valid) for p in PRODUCTS}
    y0, c0 = got["every_stack"]
    assert len(c0) == ROUTING and c0[0] == 1
    n_valid = tokens if valid is None else int(valid.sum())
    assert c0[1] == n_valid * TOP_K
    for name, (y, c) in got.items():
        np.testing.assert_allclose(y, y0, atol=TOL, err_msg=name)
        assert c[:4] == c0[:4], name
        if valid is not None:
            assert float(np.abs(y[~np.asarray(valid)]).max()) == 0.0
    # what each multiplied: held x tokens on the dense path, the pairs and
    # the tiles' padding on a grouped one
    count = EXPERTS if held is None else held[1]
    assert got["every_stack"][1][4] == count * tokens
    pairs, chosen, tiled = c0[2], c0[3], got["tiled"][1][4]
    assert pairs <= tiled <= pairs + chosen * (expert_kernels.ROW_TILE - 1)
    assert tiled % expert_kernels.ROW_TILE == 0
    if "grouped" in got:
        assert got["grouped"][1][4] == pairs


@pytest.mark.parametrize("product", PRODUCTS)
def test_an_expert_nobody_chose_and_all_tokens_on_one(monkeypatch, product):
    """A selection bias that keeps expert 5 from every token, then one
    that sends every token to experts 0-3: the products agree with the
    reference's gather, and an expert without rows costs no row."""
    x = inputs(40, seed=2)
    for bias, want_chosen in ((-10.0, EXPERTS - 1), (None, TOP_K)):
        layer = layer_of()
        b = layer.router.bias
        layer.router.bias = b.at[5].set(bias) if bias is not None \
            else b.at[:TOP_K].add(10.0)
        y, counts = through(monkeypatch, product, layer, x)
        w = {".ffn" + k: v for k, v in _leaves(layer).items()}
        cfg = dict(num_experts_per_tok=TOP_K, num_experts=EXPERTS)
        want = ref.experts_by_gather(x, w, cfg, lambda a: a)
        np.testing.assert_allclose(y, want, atol=TOL)
        assert counts[2] == 40 * TOP_K
        assert counts[3] <= want_chosen
        if bias is None:
            assert counts[3] == TOP_K
            if product == "tiled":      # 40 rows an expert: 64 with padding
                assert counts[4] == TOP_K * 64


def _leaves(module):
    flat = jax.tree_util.tree_flatten_with_path(module)[0]
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat}


@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("tokens", [7, 128])
def test_eight_shares_add_up_to_the_layer_the_reference_computes(
        monkeypatch, product, tokens):
    """Eight chips, each holding 8 of the 64 experts: their parts add up
    to the all-held layer, which is the reference's layer; every pair is
    computed on exactly one of them."""
    whole, x = layer_of(), inputs(tokens, seed=4)
    total, held_pairs = np.zeros((tokens, HIDDEN), np.float32), 0
    for i in range(8):
        share = HeldExperts(HIDDEN, WIDTH, EXPERTS, TOP_K, held=(8 * i, 8),
                            normalize_eps=1e-6)
        share.router = whole.router
        for name in ("w_gate", "w_up", "w_down"):
            setattr(share, name, jax.lax.slice_in_dim(
                getattr(whole, name), 8 * i, 8 * i + 8))
        y, counts = through(monkeypatch, product, share, x)
        total += y
        held_pairs += counts[2]
        assert counts[:2] == [1, tokens * TOP_K]
    assert held_pairs == tokens * TOP_K
    y, counts = through(monkeypatch, product, whole, x)
    np.testing.assert_allclose(total, y, atol=TOL)
    assert counts[2] == tokens * TOP_K
    w = {".ffn" + k: v for k, v in _leaves(whole).items()}
    cfg = dict(num_experts_per_tok=TOP_K, num_experts=EXPERTS)
    np.testing.assert_allclose(
        y, ref.experts_by_gather(x, w, cfg, lambda a: a), atol=TOL)
    np.testing.assert_allclose(
        y, ref.experts_in_groups(x, w, cfg, lambda a: a), atol=TOL)


def test_which_product_a_call_takes_follows_from_static_shapes():
    """This model's calls (64 held, 4 a token) and the standing expert
    cells' (16 held of 256 and of 128, 8 a token)."""
    of = HeldExperts.product_of
    # every expert held: the pairs here are all there are
    assert [of(64, 64, t) for t in (1, 16, 128, 256, 257, 384, 4096)] \
        == ["tiled"] * 7
    # a share stands for a deployment that keeps every held expert busy:
    # the standing cells' calls keep the product they had
    for experts in (256, 128):
        assert [of(16, experts, t) for t in (32, 112, 256, 288, 368, 512)] \
            == ["every_stack"] * 6
        assert of(16, experts, 513) == "tiled"
    for layer in (layer_of(), layer_of((16, 8))):
        for tokens in (1, 128, 384, 600):
            name = of(layer.count, EXPERTS, tokens)
            assert layer._product(tokens) == getattr(layer, "_" + name)


def test_the_pool_names_one_counter_for_each_count():
    from bigdl_tpu.serving.generation import MOE_COUNTERS
    assert len(MOE_COUNTERS) == ROUTING
    assert MOE_COUNTERS[-1] == "moe_rows_computed"


def test_the_counts_add_up_over_calls_under_jit():
    layer, x = layer_of(), inputs(24)
    step = jax.jit(lambda x: layer.forward(x)[1])
    total = step(x) + step(x[:8].repeat(3, axis=0))
    assert total.shape == (ROUTING,) and total.dtype == jnp.int32
    assert total.tolist()[:3] == [2, 2 * 24 * TOP_K, 2 * 24 * TOP_K]


# ---- the routing's normalising sum -------------------------------------------

def test_route_top_k_puts_eps_under_the_normalising_sum():
    s = jnp.asarray([[0.9, 0.1, 0.5, 0.3], [1e-7, 2e-7, 0.0, 0.0]])
    idx, plain = route_top_k(s, 2)
    idx2, with_eps = route_top_k(s, 2, eps=1e-6)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx2))
    np.testing.assert_allclose(with_eps[0], np.asarray([0.9, 0.5]) / (1.4 + 1e-6),
                               rtol=1e-6)
    np.testing.assert_allclose(plain[0], np.asarray([0.9, 0.5]) / 1.4,
                               rtol=1e-6)
    # scores near nothing: the eps keeps the weights small, not a half each
    assert float(with_eps[1].sum()) < 0.3 and float(plain[1].sum()) > 0.99
    # no normalising, no eps
    _, raw = route_top_k(s, 2, normalize=False, eps=1e-6)
    np.testing.assert_allclose(raw[0], [0.9, 0.5], rtol=1e-6)
    # the bias chooses and never weighs
    idx3, w3 = route_top_k(s, 2, bias=jnp.asarray([0.0, 1.0, 0.0, 0.0]),
                           eps=1e-6)
    assert sorted(np.asarray(idx3[0]).tolist()) == [0, 1]
    np.testing.assert_allclose(sorted(np.asarray(w3[0]).tolist()),
                               [0.1 / (1.0 + 1e-6), 0.9 / (1.0 + 1e-6)],
                               rtol=1e-6)


def test_held_experts_routes_with_its_eps():
    x = inputs(9)
    plain, eps = layer_of(), layer_of()
    plain.normalize_eps = 0.0
    (i0, w0), (i1, w1) = plain.route(x), eps.route(x)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    s = np.asarray(w0) * 0 + np.asarray(w1)
    total = 1.0 / (1.0 + 1e-6 / np.asarray(jnp.sum(jnp.take_along_axis(
        jax.nn.sigmoid(x @ eps.router.weight.T), i1, axis=-1), -1)))
    np.testing.assert_allclose(s.sum(-1), total, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w0).sum(-1), 1.0, rtol=1e-6)


# ---- the kernels themselves ----------------------------------------------------

def _tiles(seed=0, tiles=9, used=6, experts=5, fan_in=32, fan_out=48):
    key = jax.random.key(seed)
    k = [jax.random.fold_in(key, i) for i in range(5)]
    tile = expert_kernels.ROW_TILE
    rows = jax.random.normal(k[0], (tiles * tile, fan_in))
    stacks = [jax.random.normal(k[i], (experts, fan_in, fan_out))
              * fan_in ** -0.5 for i in (1, 2)]
    down = jax.random.normal(k[3], (experts, fan_out, fan_in)) \
        * fan_out ** -0.5
    group = jnp.asarray([0, 0, 2, 3, 3, 4, 4, 4, 4][:tiles], jnp.int32)
    return rows, stacks, down, group, jnp.asarray([used], jnp.int32)


def test_gate_up_and_down_multiply_each_tile_by_its_experts_blocks():
    rows, (wg, wu), wd, group, used = _tiles()
    tile, n = expert_kernels.ROW_TILE, int(used[0])
    act = expert_kernels.gate_up(rows, wg, wu, group, used, interpret=True)
    out = expert_kernels.down(act, wd, group, used, interpret=True)
    assert act.shape == (rows.shape[0], 48) and act.dtype == rows.dtype
    assert out.shape == rows.shape and out.dtype == jnp.float32
    for i in range(n):
        x, e = rows[i * tile:(i + 1) * tile], int(group[i])
        want = jax.nn.silu(x @ wg[e]) * (x @ wu[e])
        np.testing.assert_allclose(act[i * tile:(i + 1) * tile], want,
                                   atol=TOL)
        np.testing.assert_allclose(out[i * tile:(i + 1) * tile],
                                   want @ wd[e], atol=TOL)


def test_a_bfloat16_call_gives_bfloat16_activations_and_float32_out():
    rows, (wg, wu), wd, group, used = _tiles(seed=1)
    b16 = jnp.bfloat16
    act = expert_kernels.gate_up(rows.astype(b16), wg.astype(b16),
                                 wu.astype(b16), group, used, interpret=True)
    out = expert_kernels.down(act, wd.astype(b16), group, used,
                              interpret=True)
    assert act.dtype == b16 and out.dtype == jnp.float32
    tile = expert_kernels.ROW_TILE
    x, e = rows[:tile], int(group[0])
    want = (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
    assert float(jnp.max(jnp.abs(out[:tile] - want))) < 0.15


def test_column_blocks_follow_the_stack_blocks_bytes():
    cols = expert_kernels._columns
    # this model's stacks in bfloat16: gate and up side by side, then down
    assert cols(2048, 1536, 2, 2) == 384 and cols(1536, 2048, 2, 1) == 1024
    # the standing cells' (4096 x 2048)
    assert cols(4096, 2048, 2, 2) == 256 and cols(2048, 4096, 2, 1) == 1024
    # a small stack is one block
    assert cols(32, 48, 4, 2) == 48


@pytest.mark.parametrize("what", ["rows", "stack", "group"])
def test_the_kernels_refuse_what_they_cannot_tile(what):
    rows, (wg, wu), wd, group, used = _tiles()
    with pytest.raises(ValueError):
        if what == "rows":
            expert_kernels.down(rows[:-3], wd.transpose(0, 2, 1), group,
                                used, interpret=True)
        elif what == "stack":
            expert_kernels.gate_up(rows, wg, wu.transpose(0, 2, 1), group,
                                   used, interpret=True)
        else:
            expert_kernels.gate_up(rows, wg, wu, group[:-1], used,
                                   interpret=True)

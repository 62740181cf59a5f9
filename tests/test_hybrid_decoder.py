"""The pattern-driven decoder of window and full grouped-query layers with
a leading dense layer and a held share of sigmoid-routed experts
(``models.mimo_v2``), at a small size on the CPU, on seeded weights,
against the plain reference in ``benchmark/reference/hybrid_moe_lm.py``
(loaded by path: it is the one copy, and imports nothing of the program):
the full forward, prefill then decode through caches whose layers differ
in length, heads and widths, the slot pool's programs, the ring against a
full-length cache, the share test, and the engine end to end."""

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

import joint_pass                                             # noqa: E402
from reference import hybrid_moe_lm as ref                   # noqa: E402

from bigdl_tpu.models import mimo_v2, transformer_lm          # noqa: E402
from bigdl_tpu.nn import attention as att                     # noqa: E402
from bigdl_tpu.nn.moe import HeldExperts, MoE, route_top_k    # noqa: E402
from bigdl_tpu.serving.generation import (                    # noqa: E402
    GenerationScheduler, SlotPool)

WINDOW, CHUNK, MAX_LEN, VOCAB = 8, 4, 64, 50
# layer 0 full and dense; 1, 2 window; 3 full; 4 window: both kinds, the
# leading dense layer, and sequences longer than the window and the ring
CFG = dict(vocab_size=VOCAB, hidden_size=64, num_hidden_layers=5,
           hybrid_layer_pattern=[0, 1, 1, 0, 1, 1],
           moe_layer_freq=[0, 1, 1, 1, 1, 1],
           num_attention_heads=8, num_key_value_heads=2,
           swa_num_key_value_heads=4, head_dim=24, v_head_dim=16,
           partial_rotary_factor=0.334, rope_theta=1e7, swa_rope_theta=1e4,
           sliding_window=WINDOW, attention_value_scale=0.707,
           add_swa_attention_sink_bias=True, intermediate_size=96,
           moe_intermediate_size=48, n_routed_experts=8,
           num_experts_per_tok=2, norm_topk_prob=True,
           layernorm_epsilon=1e-5, experts_held=8, experts_offset=0)
N_SPARSE = 4
# shorter than the window; longer than twice the window plus a chunk (the
# ring wraps, more than once); ending mid-chunk
PROMPTS = {"short": 5, "wraps": 2 * WINDOW + CHUNK + 3, "mid-chunk": 14}


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def params_of(model):
    flat = jax.tree_util.tree_flatten_with_path(model)[0]
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat}


def build(**over):
    """The model with a sink and a selection bias that are not zero (the
    program initialises both at zero; the benchmark seeds them)."""
    cfg = dict(CFG, **over)
    m = mimo_v2(cfg, MAX_LEN).eval_mode()
    key = jax.random.key(7)
    for i, blk in enumerate(m.blocks):
        if blk.attn.has_sink:
            blk.attn.sink.bias = 0.5 * jax.random.normal(
                jax.random.fold_in(key, i), (cfg["num_attention_heads"],))
        if blk.sparse:
            blk.ffn.router.bias = 0.05 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), (cfg["n_routed_experts"],))
    return m, cfg


@pytest.fixture(scope="module")
def model():
    with jax.default_matmul_precision("highest"):
        return build()


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(
        1, VOCAB + 1, (2, 40)), jnp.int32)


@pytest.fixture(scope="module")
def ref_logits(model, tokens):
    m, cfg = model
    with jax.default_matmul_precision("highest"):
        return ref.forward(params_of(m), cfg, tokens)


def close(a, b, tol=1e-4):
    """Both sides are float32 at ``highest``: what is left is the order
    of the sums (the grouped product against one expert at a time, the
    grouped heads against repeated ones), a few float32 roundings on
    logits of order one."""
    return float(jnp.max(jnp.abs(a - b))) <= tol


# ---- the model against the reference ----------------------------------------

def test_full_forward_equals_the_reference(model, tokens, ref_logits):
    m, cfg = model
    kinds = [(blk.attn.window is not None, blk.sparse) for blk in m.blocks]
    assert kinds == [ref.layer_kind(cfg, i) for i in range(5)]
    assert kinds == [(False, False), (True, True), (True, True),
                     (False, True), (True, True)]
    assert ref_logits.shape == (2, 40, VOCAB)
    assert close(m.forward(tokens), ref_logits)


def test_a_held_share_equals_the_reference_given_the_same_share(tokens):
    m, cfg = build(experts_held=3, experts_offset=2)
    assert m.blocks[1].ffn.w_gate.shape == (3, 64, 48)
    assert m.blocks[1].ffn.router.weight.shape == (8, 64)
    assert close(m.forward(tokens), ref.forward(params_of(m), cfg, tokens))


@pytest.mark.parametrize("n_prompt", list(PROMPTS.values()),
                         ids=list(PROMPTS))
@pytest.mark.parametrize("per_row", [False, True],
                         ids=["scalar-index", "index-per-row"])
def test_chunked_prefill_then_decode_equals_the_reference(
        model, tokens, ref_logits, n_prompt, per_row):
    """The prompt through ``prefill_chunk`` in chunks (the last one
    short), then decode steps, each step's logits against the full
    forward's column: the window layers' rings hold the window and a
    chunk, and wrap."""
    m, _ = model
    caches = m.init_cache(2, ring_margin=CHUNK)
    shapes = [c["self"]["k"].shape[1:] + c["self"]["v"].shape[3:]
              for c in caches["layers"]]
    ring, full = (4, WINDOW + CHUNK, 24, 16), (2, MAX_LEN, 24, 16)
    assert shapes == [full, ring, ring, full, ring]
    for s in range(0, n_prompt - 1, CHUNK):
        caches, _ = m.prefill_chunk(
            tokens[:, s:min(s + CHUNK, n_prompt - 1)], s, caches)
    for t in range(n_prompt - 1, tokens.shape[1]):
        index = jnp.full((2,), t, jnp.int32) if per_row else jnp.int32(t)
        logits, caches, _ = m.decode_step(tokens[:, t:t + 1], index, caches)
        assert close(logits, ref_logits[:, t]), t


@pytest.mark.parametrize("scenario", joint_pass.SCENARIOS)
def test_the_joint_pass_equals_the_chunk_program_then_the_step(
        model, scenario):
    """``decode_step_with_chunk`` (one walk of the blocks, each layer's
    feed-forward once over the decode rows and the chunk's) against
    ``prefill_chunk`` followed by ``decode_step`` on the same caches:
    ``joint_pass.py`` has the four passes and the comparison."""
    m, _ = model
    joint_pass.assert_joint_pass_equals_chunk_then_step(
        m, CHUNK, VOCAB, scenario)


def _pool_prefill(pool, prompt, slot):
    """A prompt into ``slot`` as the scheduler sends it: no longer than
    the chunk through ``prefill_kv`` and the scatter, longer through the
    pooled chunk program (the last chunk suffix-aligned)."""
    n_prompt, end = len(prompt), len(prompt) - 1
    if n_prompt <= CHUNK + 1:
        pool.prefill_into([prompt], [slot], 1 << (n_prompt - 1).bit_length())
        return
    pos = 0
    while pos < end:
        w = CHUNK if end - pos >= CHUNK else 1 << (end - pos - 1).bit_length()
        s = pos if end - pos >= CHUNK else max(end - w, 0)
        pool.chunk_prefill_into(prompt[s:s + w], slot, s)
        pos = s + w


@pytest.mark.parametrize("n_prompt", list(PROMPTS.values()),
                         ids=list(PROMPTS))
def test_pool_prefill_then_pooled_decode_equals_the_reference(
        model, tokens, ref_logits, n_prompt):
    """The slot pool's own programs, then pooled decode steps,
    teacher-forced, whose logits are the full forward's columns."""
    m, _ = model
    pool = SlotPool(m, slots=3, ring_margin=CHUNK)
    slot, row = 1, np.asarray(tokens[0])
    _pool_prefill(pool, row[:n_prompt], slot)
    active = jnp.asarray([False, True, False])
    caches = pool.caches
    for t in range(n_prompt - 1, len(row)):
        tok = jnp.asarray([[0], [row[t]], [0]], jnp.int32)
        logits, caches, _ = pool.model.decode_step(
            tok, jnp.asarray([0, t, 0], jnp.int32), caches, active=active)
        assert close(logits[slot], ref_logits[0, t]), t


@pytest.mark.parametrize("n_prompt", [6, 21, 33])
def test_a_bucketed_prefill_longer_than_the_ring_keeps_the_newest_real_keys(
        model, tokens, ref_logits, n_prompt):
    """A pool that is never handed a chunk (rings of the window alone)
    takes a whole prompt through ``prefill_kv`` and the scatter: each
    ring place gets the newest real position congruent to it, whatever
    the bucket's trailing padding holds."""
    m, _ = model
    pool = SlotPool(m, slots=2)                 # ring_margin 1: R == WINDOW
    assert pool.caches["layers"][1]["self"]["k"].shape[2] == WINDOW + 1
    row = np.asarray(tokens[1])
    pool.prefill_into([row[:n_prompt]], [0], 1 << (n_prompt - 1).bit_length())
    caches = pool.caches
    for t in range(n_prompt - 1, min(n_prompt + 9, len(row))):
        logits, caches, _ = pool.model.decode_step(
            jnp.asarray([[row[t]], [0]], jnp.int32),
            jnp.asarray([t, 0], jnp.int32), caches,
            active=jnp.asarray([True, False]))
        assert close(logits[0], ref_logits[1, t]), t


def test_the_ring_gives_the_logits_of_a_full_length_cache(model, tokens):
    """The same prompt and decode steps through rings of the window and a
    chunk, and through rings as long as ``max_len`` (every position keeps
    a place of its own: a full-length cache): the same logits, to the
    last bit — a ring drops only what the window's mask already hides."""
    m, _ = model
    out = {}
    for margin in (CHUNK, MAX_LEN):
        caches = m.init_cache(2, ring_margin=margin)
        for s in range(0, 28, CHUNK):
            caches, _ = m.prefill_chunk(tokens[:, s:s + CHUNK], s, caches)
        rows = []
        for t in range(28, 40):
            logits, caches, _ = m.decode_step(
                tokens[:, t:t + 1], jnp.full((2,), t, jnp.int32), caches)
            rows.append(logits)
        out[margin] = jnp.stack(rows)
    assert m.init_cache(1, ring_margin=MAX_LEN)["layers"][1]["self"][
        "k"].shape[2] == MAX_LEN + 1
    np.testing.assert_allclose(out[CHUNK], out[MAX_LEN], atol=1e-5)
    np.testing.assert_array_equal(jnp.argmax(out[CHUNK], -1),
                                  jnp.argmax(out[MAX_LEN], -1))


def test_prefill_kv_returns_the_keys_the_chunks_write(model, tokens):
    m, _ = model
    layers, pad, routing = m.prefill_kv(tokens[:, :7])
    caches = m.init_cache(2, ring_margin=CHUNK)
    for s in (0, 4):
        caches, _ = m.prefill_chunk(tokens[:, s:min(s + CHUNK, 7)], s, caches)
    assert not bool(pad.any())
    assert routing.tolist()[:2] == [N_SPARSE, N_SPARSE * 2 * 7 * 2]
    for kv, cache in zip(layers, caches["layers"]):
        assert kv["k"].shape[2:] == (7, 24) and kv["v"].shape[2:] == (7, 16)
        for n in ("k", "v"):
            np.testing.assert_allclose(
                kv[n], cache["self"][n][:, :, :7], atol=1e-5)


# ---- the expert layer -------------------------------------------------------

# tokens a call, on either side of where a held share changes its product
# over the held stacks: every token through every held expert, or (a longer
# call, and every call of a layer that holds all its experts) the pairs laid
# out by expert in tiles (tests/test_expert_products.py forces each)
PRODUCTS = {"every-stack": 11, "grouped": HeldExperts.DENSE_TOKENS + 9}


def _layer(held=None, seed=3):
    layer = HeldExperts(64, 48, 8, 2, held=held)
    layer.router.bias = 0.05 * jax.random.normal(jax.random.key(seed), (8,))
    return layer


@pytest.mark.parametrize("tokens", list(PRODUCTS.values()),
                         ids=list(PRODUCTS))
@pytest.mark.parametrize("shares", [8, 4, 2, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares, tokens):
    """``shares`` chips, each holding ``8 / shares`` of the eight experts:
    the parts of the result that the shares give add up to what the
    reference gives for the whole layer, and every token-to-expert pair
    is computed on exactly one of them."""
    whole = _layer()
    x = jax.random.normal(jax.random.key(3), (tokens, 64))
    n = 8 // shares
    total, held_pairs = jnp.zeros((tokens, 64)), 0
    for i in range(shares):
        share = HeldExperts(64, 48, 8, 2, held=(i * n, n))
        share.router = whole.router
        for name in ("w_gate", "w_up", "w_down"):
            setattr(share, name, jax.lax.slice_in_dim(
                getattr(whole, name), i * n, (i + 1) * n))
        y, counts = share.forward(x)
        total = total + y
        assert counts.tolist()[:2] == [1, tokens * 2]
        held_pairs += int(counts[2])
    assert held_pairs == tokens * 2
    w = {".ffn" + k: v for k, v in params_of(whole).items()}
    want = ref.experts(x, w, dict(CFG, experts_offset=0), lambda a: a)
    np.testing.assert_allclose(total, want, atol=1e-5)


@pytest.mark.parametrize("tokens", list(PRODUCTS.values()),
                         ids=list(PRODUCTS))
def test_the_counts_follow_the_routing_and_idle_rows_get_nothing(tokens):
    layer = _layer(held=(2, 3))
    x = jax.random.normal(jax.random.key(4), (tokens, 64))
    experts, _ = layer.route(x)
    held = (experts >= 2) & (experts < 5)
    y, counts = layer.forward(x)
    assert counts.tolist()[:4] == [
        1, 2 * tokens, int(held.sum()),
        len(set(np.asarray(experts)[np.asarray(held)]))]
    name = HeldExperts.product_of(3, 8, tokens)
    assert name == ("every_stack" if tokens <= HeldExperts.DENSE_TOKENS
                    else "tiled")
    assert counts.tolist()[4] == 3 * tokens if name == "every_stack" \
        else counts.tolist()[4] >= int(held.sum())
    valid = jnp.arange(tokens) < 4
    y2, counts2 = layer.forward(x, valid)
    assert counts2.tolist()[1:3] == [8, int(held[:4].sum())]
    np.testing.assert_allclose(y2[:4], y[:4], atol=1e-6)
    assert float(jnp.abs(y2[4:]).max()) == 0.0


@pytest.mark.parametrize("normalize", [True, False])
def test_the_selection_bias_moves_the_choice_and_never_the_weights(normalize):
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(5), (2, 6, 8)))
    idx, w = route_top_k(scores, 2, normalize)
    assert idx.dtype == jnp.int32 and idx.shape == (2, 6, 2)
    if normalize:
        np.testing.assert_allclose(jnp.sum(w, -1), 1.0, atol=1e-6)
    # a bias that lifts expert 7 above everything: it is always chosen,
    # and its weight is still made of its own score
    bias = jnp.zeros(8).at[7].set(10.0)
    idx_b, w_b = route_top_k(scores, 2, normalize, bias)
    assert bool(jnp.all(idx_b[..., 0] == 7))
    assert not np.array_equal(np.asarray(idx_b), np.asarray(idx))
    chosen = jnp.take_along_axis(scores, idx_b, axis=-1)
    want = chosen / jnp.sum(chosen, -1, keepdims=True) if normalize else chosen
    np.testing.assert_allclose(w_b, want, atol=1e-6)
    # a bias equal on every expert moves nothing
    idx_c, w_c = route_top_k(scores, 2, normalize, jnp.full(8, 0.3))
    np.testing.assert_array_equal(idx_c, idx)
    np.testing.assert_allclose(w_c, w, atol=1e-7)


def test_moe_keeps_stacked_leaves_and_routes_through_the_one_function():
    from bigdl_tpu.nn.attention import FeedForwardNetwork
    experts = [FeedForwardNetwork(16, 32) for _ in range(8)]
    moe = MoE(16, experts, top_k=2)
    # stacked once, when the layer is built: a leading expert axis on
    # every leaf, no list of modules left to stack again on a call
    assert isinstance(moe.experts, FeedForwardNetwork)
    assert moe.experts.filter_layer.weight.shape == (8, 32, 16)
    np.testing.assert_array_equal(moe.experts.filter_layer.weight[3],
                                  experts[3].filter_layer.weight)
    assert not hasattr(moe, "_stacked_experts")
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(5), (2, 6, 8)))
    kth = jax.lax.top_k(probs, 2)[0][..., -1:]
    old = jnp.where(probs >= kth, probs, 0.0)
    old = old / jnp.sum(old, axis=-1, keepdims=True)
    np.testing.assert_allclose(
        moe._route(jnp.zeros((2, 6, 16)), probs), old, atol=1e-6)


# ---- attention --------------------------------------------------------------

@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
def test_grouped_attention_equals_attention_over_repeated_heads(
        monkeypatch, sink):
    key = jax.random.split(jax.random.key(6), 4)
    q = jax.random.normal(key[0], (2, 8, 16, 24))
    k = jax.random.normal(key[1], (2, 2, 16, 24))
    v = jax.random.normal(key[2], (2, 2, 16, 16))
    s_h = jax.random.normal(key[3], (8,)) if sink else None
    pos = jnp.arange(16)[None]
    got = att.grouped_attention(q, k, v, pos, pos, window=5, sink=s_h)
    assert got.shape == (2, 8, 16, 16)
    kr, vr = jnp.repeat(k, 4, axis=1), jnp.repeat(v, 4, axis=1)
    dist = pos[0][:, None] - pos[0][None, :]
    bias = jnp.where((dist >= 0) & (dist < 5), 0.0, -1e9)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kr) / np.sqrt(24.0) + bias
    if sink:
        col = jnp.broadcast_to(s_h[None, :, None, None], s.shape[:3] + (1,))
        w = jax.nn.softmax(jnp.concatenate([s, col], -1), -1)[..., :-1]
        assert float(jnp.max(jnp.sum(w, -1))) < 1.0
    else:
        w = jax.nn.softmax(s, -1)
    want = jnp.einsum("bhqk,bhkd->bhqd", w, vr)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # one key/value head and one block of queries at a time: the same rows
    monkeypatch.setattr(att, "SCORE_BYTES", 2 * 4 * 4 * 16 * 4)
    np.testing.assert_allclose(
        att.grouped_attention(q, k, v, pos, pos, window=5, sink=s_h), want,
        atol=1e-5)


def test_a_ring_place_holds_the_newest_position_congruent_to_it():
    got = np.asarray(att.cache_positions(6, jnp.asarray([3, 7]), ring=True))
    # five ring places and the spare; after position 7: 5, 6, 7, 3, 4
    assert got.tolist() == [[0, 1, 2, 3, -1, -1], [5, 6, 7, 3, 4, -1]]
    assert np.asarray(att.cache_positions(4, 2, ring=False)).tolist() \
        == [[0, 1, 2, 3]]


def test_rotary_turns_the_first_dims_in_half_split_pairs():
    x = jnp.ones((1, 1, 3, 6))
    out = att.rotary_half(x, jnp.arange(3)[None, None], 100.0, 4)
    ang = np.arange(3)[:, None] * np.asarray([1.0, 0.1])
    want = np.concatenate([np.cos(ang) - np.sin(ang),
                           np.cos(ang) + np.sin(ang), np.ones((3, 2))], -1)
    np.testing.assert_allclose(out[0, 0], want, atol=1e-6)
    np.testing.assert_allclose(
        ref.rotary(x, 100.0, 4)[0, 0], want, atol=1e-6)


@pytest.mark.parametrize("window", [None, 6], ids=["full", "window"])
def test_grouped_query_attention_is_one_entry_for_both_layer_kinds(window):
    """``GroupedQueryAttention.forward`` without a cache is the sequence
    attending itself; with one, a chunk written and attended gives the
    same rows.  A window layer declares a ring, a full one a row."""
    layer = att.GroupedQueryAttention(
        32, 4, 2, 12, 8, window=window, rope_theta=1e4, rotary_dim=4,
        sink=window is not None, value_scale=0.5)
    x = jax.random.normal(jax.random.key(8), (2, 10, 32))
    whole, kv = layer.forward(x)
    assert kv["k"].shape == (2, 2, 10, 12) and kv["v"].shape == (2, 2, 10, 8)
    cache = layer.init_cache(2, 32, ring_margin=5)
    assert cache["k"].shape[2] == (6 + 5 if window else 32)
    assert cache["v"].shape[3] == 8
    got = []
    for s in (0, 5):
        y, cache = layer.forward(x[:, s:s + 5], s, cache)
        got.append(y)
    np.testing.assert_allclose(jnp.concatenate(got, 1), whole, atol=1e-5)


def test_the_builders_refuse_what_they_do_not_build():
    with pytest.raises(ValueError, match="multiple"):
        att.GroupedQueryAttention(32, 4, 3, 8)
    with pytest.raises(ValueError, match="rotary_dim"):
        att.GroupedQueryAttention(32, 4, 2, 8, rotary_dim=10)
    with pytest.raises(ValueError, match="shared experts"):
        mimo_v2(dict(CFG, n_shared_experts=1), MAX_LEN)
    with pytest.raises(ValueError, match="sigmoid"):
        mimo_v2(dict(CFG, scoring_func="softmax"), MAX_LEN)
    with pytest.raises(ValueError, match="ring"):
        m, _ = build()
        m.prefill_chunk(jnp.ones((1, 6), jnp.int32), 0, m.init_cache(1))


# ---- the slot pool ----------------------------------------------------------

def _tiny_lm():
    return transformer_lm(30, hidden_size=32, num_layers=2, num_heads=2,
                          filter_size=64, max_len=32)


def test_the_pool_shares_the_models_buffers(model):
    m, _ = model
    pool = SlotPool(m, slots=2, ring_margin=CHUNK)
    mine, theirs = (jax.tree_util.tree_leaves(t) for t in (m, pool.model))
    assert len(mine) == len(theirs) and all(
        a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()
        for a, b in zip(mine, theirs))
    assert pool.model is not m
    lm = _tiny_lm()                                          # in train mode
    opt = SlotPool(lm, slots=2)
    assert lm.training and not opt.model.training
    assert all(a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()
               for a, b in zip(jax.tree_util.tree_leaves(lm),
                               jax.tree_util.tree_leaves(opt.model)))


def test_the_pool_allocates_each_layers_cache_as_declared(model):
    m, _ = model
    pool = SlotPool(m, slots=3, ring_margin=CHUNK)
    ring, full = ("ring", WINDOW), ("full", MAX_LEN)
    assert pool.cache_layers == (full, ring, ring, full, ring)
    by_kind = pool.cache_nbytes_by_kind()
    per_head = 3 * (24 + 16) * 4             # slots, k + v widths, float32
    assert by_kind == {"ring": 3 * (WINDOW + CHUNK) * 4 * per_head,
                       "full": 2 * MAX_LEN * 2 * per_head, "state": 0,
                       "latent": 0}
    opt = SlotPool(_tiny_lm(), slots=2)
    assert opt.cache_layers == (("full", 32),) * 2 and not opt.has_ring
    assert opt.cache_nbytes_by_kind()["ring"] == 0
    assert opt.expert_layers == 0 and pool.expert_layers == N_SPARSE
    assert set(opt.caches) == set(pool.caches) == {"layers", "pad"}


def test_transformer_lm_through_the_generalised_pool_is_what_it_was():
    """``TransformerLM`` is the pool's case "every layer full, one shape":
    the decode step with idle lanes told to the model (``active``) is bit
    for bit the step the pool ran before, which sent idle lanes to
    ``max_len - 1`` itself; the chunk program returns the caches the model
    does; and no routing rides the read-back."""
    lm = _tiny_lm().eval_mode()
    pool = SlotPool(lm, slots=3)
    prompt = np.asarray([3, 9, 4, 7, 12, 5, 8], np.int32)
    pool.prefill_into([prompt], [1], 8)
    tok = jnp.asarray([[0], [prompt[-1]], [0]], jnp.int32)
    index = jnp.asarray([0, 6, 0], jnp.int32)
    active = jnp.asarray([False, True, False])
    new, new_caches = pool.model.decode_step(tok, index, pool.caches,
                                             active=active)
    old, old_caches = pool.model.decode_step(
        tok, jnp.where(active, index, 31), pool.caches)
    np.testing.assert_array_equal(new, old)
    for a, b in zip(jax.tree_util.tree_leaves(new_caches),
                    jax.tree_util.tree_leaves(old_caches)):
        np.testing.assert_array_equal(a, b)
    handle = pool.decode_dispatch()
    out = pool.read_emit(handle)
    assert out.shape == (3,) and handle.routing.shape == (0,)
    assert int(out[1]) == int(jnp.argmax(
        lm._mask_untrained_logit(old)[1])) + 1
    want = np.asarray(lm.generate(jnp.asarray(prompt)[None], 6))[0]
    got = [int(out[1])] + [int(pool.decode()[1]) for _ in range(5)]
    assert got == want[len(prompt):].tolist()


def test_a_decode_step_leaves_a_prefilling_slots_ring_untouched(model, tokens):
    """Slot 0 decodes while slot 1 is in chunked prefill past the window:
    every place of slot 1's rings, and every position of its full rows
    that prefill has written, is bit for bit what it was.  (An idle lane
    writes the ring's spare place and the full row's last position.)"""
    m, _ = model
    pool = SlotPool(m, slots=2, ring_margin=CHUNK)
    row = np.asarray(tokens[1])
    pool.prefill_into([row[:4]], [0], 4)
    for s in range(0, 20, CHUNK):            # past the window, and wrapped
        pool.chunk_prefill_into(row[s:s + CHUNK], 1, s)
    before = jax.tree_util.tree_map(np.asarray, pool.caches["layers"])
    for _ in range(3):
        pool.decode()
    after = jax.tree_util.tree_map(np.asarray, pool.caches["layers"])
    for i, (kind, _) in enumerate(pool.cache_layers):
        keep = slice(0, WINDOW + CHUNK - 1) if kind == "ring" \
            else slice(0, MAX_LEN - 1)
        for n in ("k", "v"):
            assert np.array_equal(before[i]["self"][n][1][:, keep],
                                  after[i]["self"][n][1][:, keep]), (i, n)
    # and slot 0 did decode: its own rows moved
    assert not np.array_equal(before[1]["self"]["k"][0],
                              after[1]["self"]["k"][0])


def test_the_copy_and_extract_programs_take_layers_of_any_shape(model, tokens):
    """``_kv_extract`` then ``_kv_copy`` move a span of one slot's keys
    and values into another, layer by layer at each layer's own heads and
    widths, by position in a full row and by place in a ring; the
    scheduler keeps the prefix cache off a model with rings, because a
    ring stops holding a prefix once the window has moved on."""
    m, _ = model
    pool = SlotPool(m, slots=2, ring_margin=CHUNK)
    row = np.asarray(tokens[0])
    for s in (0, 4):
        pool.chunk_prefill_into(row[s:s + CHUNK], 0, s)
    layers, pad = pool.kv_extract(0, 4, 4)
    assert [kv["k"].shape for kv in layers] == [
        (2, 4, 24), (4, 4, 24), (4, 4, 24), (2, 4, 24), (4, 4, 24)]
    assert layers[0]["v"].shape == (2, 4, 16) and pad.shape == (4,)
    pool.caches = pool._kv_copy_jit(pool.caches, np.int32(1), layers, pad,
                                    np.int32(4))
    for cache in pool.caches["layers"]:
        for n in ("k", "v"):
            np.testing.assert_array_equal(cache["self"][n][1][:, 4:8],
                                          cache["self"][n][0][:, 4:8])
    with pytest.raises(ValueError, match="ring"):
        GenerationScheduler(m, slots=2, prefill_chunk=CHUNK,
                            prefix_cache_bytes=1 << 20, start=False)


# ---- the engine -------------------------------------------------------------

def test_the_engine_end_to_end_on_mixed_lengths(model):
    """``GenerationScheduler`` over the pool: prompts shorter than a
    chunk, longer than the window and far past it, more of them than
    slots; every row is what ``generate()`` gives alone, and the routing
    counters came back with the tokens, as differences between two
    snapshots."""
    m, _ = model
    engine = GenerationScheduler(m, slots=3, prefill_chunk=CHUNK,
                                 prefill_batch=2)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, VOCAB + 1, n).astype(np.int32)
               for n in (3, 5, 21, 30, 9, 2, 40, 17)]
    try:
        first = engine.stats()
        futs = [engine.submit_async(p, 12) for p in prompts[:4]]
        rows = [f.result(timeout=300) for f in futs]
        mid = engine.stats()
        futs = [engine.submit_async(p, 12) for p in prompts[4:]]
        rows += [f.result(timeout=300) for f in futs]
        last = engine.stats()
    finally:
        engine.shutdown()
    for p, row in zip(prompts, rows):
        want = np.asarray(m.generate(jnp.asarray(p)[None], 12, chunk=CHUNK))
        assert np.array_equal(row, want[0]), len(p)
    assert engine.pool.trace_counts["decode"] == 1
    assert first["moe_layer_calls"] == 0
    for a, b in ((first, mid), (mid, last)):
        calls = b["moe_layer_calls"] - a["moe_layer_calls"]
        pairs = b["moe_pairs_total"] - a["moe_pairs_total"]
        held = b["moe_pairs_held"] - a["moe_pairs_held"]
        active = b["moe_active_experts"] - a["moe_active_experts"]
        steps = b["decode_steps"] - a["decode_steps"]
        programs = steps + b["prefill_calls"] - a["prefill_calls"]
        # every program with the model in it runs the four expert layers
        # (a step around the second snapshot may read back after it)
        assert calls % N_SPARSE == 0
        assert abs(calls - N_SPARSE * programs) <= N_SPARSE * 2
        # all eight experts are held: every routed pair lands on one
        assert held == pairs > 0 and pairs % (2 * N_SPARSE) == 0
        assert 0 < active <= 8 * calls
    assert last["cache_bytes_window"] + last["cache_bytes_full"] \
        == sum(engine.pool.cache_nbytes_by_kind().values())
    assert last["cache_bytes_window"] > 0


def test_a_chunk_beside_decoding_slots_rides_their_step(model):
    """Mixed arrivals through the engine: B's and C's prompts arrive while
    A decodes, so every one of their chunks is carried by a decode step
    (one joint program a pass, never the chunk program by itself); the
    rows are ``generate()``'s, the counters say what went where, and the
    gap after a joint pass is a ``prefill`` gap."""
    m, _ = model
    rng = np.random.default_rng(5)
    a, b, c = (rng.integers(1, VOCAB + 1, n).astype(np.int32)
               for n in (3, 21, 14))
    engine = GenerationScheduler(m, slots=3, prefill_chunk=CHUNK,
                                 start=False)
    log = joint_pass.logged_pool_calls(engine.pool)
    engine.start()
    try:
        rows = joint_pass.serve_beside_a_decoding_slot(engine, a, [b, c],
                                                       new_first=40)
        engine.shutdown()
        stats = engine.stats()
    finally:
        engine.shutdown()
    for p, row, new in zip((a, b, c), rows, (40, 6, 6)):
        want = np.asarray(m.generate(jnp.asarray(p)[None], new, chunk=CHUNK))
        assert np.array_equal(row, want[0]), len(p)
    # B: 20 positions, five chunks of 4; C: 13, three and one of 1
    assert "alone" not in log and log.count("step+chunk") == 9
    assert (stats["chunks_joint"], stats["chunks_alone"]) == (9, 0)
    assert stats["prefill_calls"] == 9 + 1      # and A's bucketed prefill
    assert stats["step_gaps"]["prefill"] == 9
    assert stats["step_gaps"]["plain"] == stats["decode_steps"] - 1 - 9
    counts = engine.pool.trace_counts
    # the pool's first chunk compiled every chunk program, the lone one
    # at the full width among them, though nothing ran it
    assert counts["decode_with_chunk"] == {1: 1, 2: 1, CHUNK: 1}
    assert counts["chunk_prefill"] == {CHUNK: 1}
    assert counts["decode"] == 1


def test_chunks_beside_an_idle_pool_leave_nothing_to_compile_beside_a_busy_one(
        model):
    """The benchmark's warm-up sends every chunk width beside an idle
    pool; a chunk that then meets decoding slots inside the window must
    trace nothing.  Beside an idle pool the full width goes through the
    lone chunk program and a narrower one through the joint program with
    every row idle; both count as sent alone."""
    import copy
    m, _ = model
    rng = np.random.default_rng(6)
    engine = GenerationScheduler(m, slots=3, prefill_chunk=CHUNK)
    try:
        # one request at a time: widths 4; 4, 1; 4, 2; 4, 4 (3 padded up)
        for n in (CHUNK + 1, CHUNK + 2, CHUNK + 3, CHUNK + 4):
            p = rng.integers(1, VOCAB + 1, n).astype(np.int32)
            row = engine.submit_async(p, 2).result(timeout=300)
            want = np.asarray(m.generate(jnp.asarray(p)[None], 2,
                                         chunk=CHUNK))
            assert np.array_equal(row, want[0]), n
        warm = engine.stats()
        traced = copy.deepcopy(engine.pool.trace_counts)
        assert (warm["chunks_joint"], warm["chunks_alone"]) == (0, 7)
        assert traced["decode_with_chunk"] == {1: 1, 2: 1, CHUNK: 1}
        assert traced["chunk_prefill"] == {CHUNK: 1}
        a, b, c = (rng.integers(1, VOCAB + 1, n).astype(np.int32)
                   for n in (CHUNK + 1, CHUNK + 3, 2 * CHUNK + 2))
        rows = joint_pass.serve_beside_a_decoding_slot(engine, a, [b, c])
        engine.shutdown()
        stats = engine.stats()
    finally:
        engine.shutdown()
    for p, row, new in zip((a, b, c), rows, (30, 6, 6)):
        want = np.asarray(m.generate(jnp.asarray(p)[None], new, chunk=CHUNK))
        assert np.array_equal(row, want[0]), len(p)
    assert engine.pool.trace_counts == traced
    # A's one chunk met an idle pool; B's two and C's three rode a step
    assert stats["chunks_joint"] - warm["chunks_joint"] == 5
    assert stats["chunks_alone"] - warm["chunks_alone"] == 1


def test_a_joint_pool_keeps_the_upper_four_chunk_widths(model):
    """A joint program costs a start more than the lone program it
    replaces, so a pool keeps four widths (the full one down to its
    eighth) and a shorter remainder rides the narrowest, moved back over
    the prompt.  The rows are still ``generate()``'s."""
    m, _ = model
    assert SlotPool(m, slots=2, ring_margin=32).chunk_widths == (4, 8, 16, 32)
    lm = _tiny_lm().eval_mode()
    assert SlotPool(lm, slots=2, ring_margin=32).chunk_widths \
        == (4, 8, 16, 32)
    engine = GenerationScheduler(m, slots=2, prefill_chunk=16)
    rng = np.random.default_rng(8)
    # 16 + 1, 16 + 3 and 16 + 6 positions: remainders under and over 2
    prompts = [rng.integers(1, VOCAB + 1, n).astype(np.int32)
               for n in (18, 20, 23)]
    try:
        rows = [engine.submit_async(p, 5).result(timeout=300)
                for p in prompts]
    finally:
        engine.shutdown()
    for p, row in zip(prompts, rows):
        want = np.asarray(m.generate(jnp.asarray(p)[None], 5, chunk=16))
        assert np.array_equal(row, want[0]), len(p)
    assert set(engine.pool.trace_counts["decode_with_chunk"]) == {2, 4, 8, 16}


def test_a_dense_models_engine_counts_no_expert_layers():
    engine = GenerationScheduler(_tiny_lm().eval_mode(), slots=2,
                                 prefill_chunk=8)
    try:
        engine.submit_async(np.asarray([3, 4, 5], np.int32), 4).result(120)
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert stats["moe_layer_calls"] == 0 and stats["moe_pairs_total"] == 0
    assert stats["cache_bytes_window"] == 0 and stats["cache_bytes_full"] > 0


def test_a_killed_engine_gives_the_device_back():
    """``kill()`` then ``shutdown()``: the engine thread has exited when
    ``shutdown`` returns, and the pool holds no cache, feed or model any
    more — whoever still holds the engine does not keep its weights on
    the device.  A drained engine keeps its pool."""
    lm = _tiny_lm().eval_mode()
    engine = GenerationScheduler(lm, slots=2, prefill_chunk=8)
    engine.submit_async(np.asarray([3, 4, 5], np.int32), 4).result(120)
    engine.kill()
    engine.shutdown(drain=False, timeout=60.0)
    assert not engine.alive
    assert engine.pool.caches is None and engine.pool.model is None
    assert engine.stats()["requests_done"] == 1
    kept = GenerationScheduler(lm, slots=2, prefill_chunk=8)
    kept.submit_async(np.asarray([3, 4, 5], np.int32), 4).result(120)
    kept.shutdown()
    assert kept.pool.caches is not None and kept.pool.model is not None


@pytest.mark.parametrize("ragged", [False, True], ids=["whole", "ragged"])
def test_the_pool_counts_what_the_step_reads_of_its_full_rows(
        ragged, monkeypatch):
    """``stats()["decode_positions_read"]`` over ``["decode_positions_live"]``
    for a pool of window and full layers.  One request alone, 120 prompt
    tokens and 12 new, in rows of 384: dispatch ``i`` attends ``120 + i``
    positions.  Where the full layers' step goes through the ragged decode
    kernel (what a TPU process chooses: forced here, interpreted) the pool
    counts each active slot's length rounded up to the key block of 128,
    full rows only (the rings are read whole either way and are not
    positions of a row); where it reads whole rows, ``slots x max_len``
    every step.  The tokens are the same either way."""
    import functools
    import time
    from bigdl_tpu.ops import attention_kernels
    if ragged:
        monkeypatch.setattr(
            attention_kernels, "decode_key_block",
            functools.partial(attention_kernels.decode_key_block,
                              force="ragged"))
    max_len, slots, new = 384, 2, 12
    m = mimo_v2(CFG, max_len).eval_mode()
    assert m.decode_key_block(m.init_cache(slots)) == (128 if ragged else None)
    prompt = np.arange(1, 121, dtype=np.int32) % VOCAB + 1
    engine = GenerationScheduler(m, slots=slots, prefill_chunk=24)
    try:
        assert engine.pool.key_block == (128 if ragged else None)
        row = engine.submit_async(prompt, new).result(timeout=300)
        deadline = time.time() + 10
        while time.time() < deadline and engine.pool.n_active():
            time.sleep(0.01)
        time.sleep(0.05)
        st = engine.stats()
    finally:
        engine.shutdown()
    n = st["decode_dispatches"]
    assert n in (new, new + 1)          # the pipeline is one step deep
    lengths = [120 + i for i in range(n)]
    assert st["decode_positions_live"] == sum(lengths)
    if ragged:
        assert st["decode_positions_read"] == 128 * 9 + 256 * (n - 9)
    else:
        assert st["decode_positions_read"] == n * slots * max_len
    want = np.asarray(m.generate(jnp.asarray(prompt)[None], new, chunk=24))
    assert np.array_equal(row, want[0])


@pytest.mark.parametrize("family", ["mimo_v2", "falcon_h1", "lfm2_moe",
                                    "afmoe"])
def test_a_chunk_over_grouped_query_rows_reads_up_to_its_own_position(
        family):
    """A pool of each family whose full rows are grouped-query ones, at
    its tests' tiny configuration and rows of 96 places: its full layers
    answer the chunk's key block (32: what 256 shares with the row; 256 at
    a cell's row), the model and the pool repeat it, and
    ``chunk_positions_read`` follows the chunk's position: a prompt of 41
    tokens goes out as five chunks of 8, of which the last ends in the
    second block.  (That the tokens are those of a request generated
    alone is held by every engine test of this file and of the families'
    own, which take this path too.)"""
    import math
    from bigdl_tpu import models
    from bigdl_tpu.ops.attention_kernels import CHUNK_KEY_BLOCK
    from tests import test_afmoe, test_lfm2_moe, test_state_space
    tiny = {"mimo_v2": sys.modules[__name__], "falcon_h1": test_state_space,
            "lfm2_moe": test_lfm2_moe, "afmoe": test_afmoe}[family]
    max_len, chunk = 96, 8
    block = math.gcd(max_len, CHUNK_KEY_BLOCK)
    m = getattr(models, family)(tiny.CFG, max_len).eval_mode()
    prompt = np.arange(41, dtype=np.int32) % tiny.VOCAB + 1
    chunks = [(s, chunk) for s in range(0, 40, chunk)]
    engine = GenerationScheduler(m, slots=2, prefill_chunk=chunk)
    try:
        assert engine.pool.chunk_key_block == block \
            == m.chunk_key_block(engine.pool.caches)
        row = engine.submit(prompt, 1)
        engine.shutdown()
        st = engine.stats()
    finally:
        engine.shutdown()
    assert len(row) == len(prompt) + 1
    assert st["chunks_joint"] + st["chunks_alone"] == len(chunks)
    assert st["chunk_positions_live"] == sum(s + w for s, w in chunks)
    assert st["chunk_positions_read"] == 4 * block + 2 * block \
        == sum(block * -(-(s + w) // block) for s, w in chunks)

"""``lfm2_moe`` (``HybridDecoder`` with gated short-convolution layers that
keep a two-row tail and no keys, grouped-query attention whose query and
key heads are normed before rotation, two leading dense layers, then every
expert of a layer held here; RMS norms, a tied head) at a small size on
the CPU, on seeded weights, against the plain reference in
``benchmark/reference/conv_moe_lm.py`` (loaded by path: it is the one copy
and imports nothing of the program): the whole-sequence pass, chunks then
steps through a slot pool, the joint pass, the reset at admission, the
factory's refusals, and the engine end to end."""

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
from reference import conv_moe_lm as ref                      # noqa: E402

from bigdl_tpu.models import lfm2_moe                         # noqa: E402
from bigdl_tpu.nn.attention import GroupedQueryAttention      # noqa: E402
from bigdl_tpu.serving.generation import (                    # noqa: E402
    GenerationScheduler, SlotPool)

CHUNK, MAX_LEN, VOCAB, LAYERS, SERVED = 8, 64, 50, 6, 10
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv", "full_attention", "conv"]
CFG = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=LAYERS,
           layer_types=TYPES, num_attention_heads=4, num_key_value_heads=2,
           intermediate_size=48, num_dense_layers=2,
           moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
           conv_L_cache=3, conv_bias=False, norm_eps=1e-5,
           norm_topk_prob=True, use_expert_bias=True,
           routed_scaling_factor=1,
           rope_parameters={"rope_theta": 1e6, "rope_type": "default"})
CONVS = [i for i, t in enumerate(TYPES[:LAYERS]) if t == "conv"]
N_CONV, N_ATTN, N_EXPERT = len(CONVS), LAYERS - len(CONVS), LAYERS - 2
PROMPTS = {"one": 1, "three": 3, "chunk-1": CHUNK - 1, "chunk": CHUNK,
           "chunk+1": CHUNK + 1, "3.5-chunks": 3 * CHUNK + CHUNK // 2}
TOL = 5e-5


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def params_of(model):
    flat = jax.tree_util.tree_flatten_with_path(model)[0]
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat}


def build(seed=11, **over):
    """The model on seeded leaves: matrices and stacks a normal of their
    last axis' ``** -0.5``, the taps of ``3 ** -0.5``, gains ``1 +- 0.1``,
    the selection bias ``0.05 x normal``."""
    cfg = dict(CFG, **over)
    m = lfm2_moe(cfg, MAX_LEN).eval_mode()
    flat, tree = jax.tree_util.tree_flatten_with_path(m)
    key, leaves = jax.random.key(seed), []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        if name.endswith(".taps"):
            leaf = noise * 3 ** -0.5
        elif leaf.ndim == 1:
            leaf = 0.05 * noise if name.endswith("bias") else 1 + 0.1 * noise
        else:
            leaf = noise * leaf.shape[-1] ** -0.5
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(tree, leaves), cfg


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


def close(a, b, tol=TOL):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b)))) <= tol


# ---- the model ------------------------------------------------------------------

def test_whole_sequence_logits_equal_the_reference(model, tokens, ref_logits):
    m, _ = model
    got = m.forward(tokens)
    assert got.shape == (2, 40, VOCAB)
    assert close(got, ref_logits)
    assert float(jnp.std(ref_logits)) > 0.1


def test_the_reference_walked_in_blocks_equals_its_gather(model, tokens,
                                                          ref_logits):
    """The reference's two ways through the experts (each token through
    its own by a gather; the held experts a group at a time over every
    token) are one sum, and the blocked walk is what the serving check
    runs."""
    m, cfg = model
    params = params_of(m)
    cfg = dict(cfg, serving={"weights_dtype": "float32"})
    x = ref.embed(params, cfg, tokens)
    for i in range(LAYERS):
        x = ref.block(params, cfg, i, x)
    assert close(ref.head(params, cfg, x), ref_logits)


def test_the_layers_by_index_at_the_served_depth():
    """Ten layers: eight tails, two rows; two leading dense layers; every
    layer keeps something, so a chunk's rows walk the whole depth."""
    m = jax.eval_shape(lambda: lfm2_moe(
        dict(CFG, num_hidden_layers=SERVED), 1024))
    decl = m.cache_layers()
    state = {"ssm": ("state", None)}
    assert decl == (state, state, ("full", 1024), state, state, state,
                    ("full", 1024), state, state, state)
    assert (m.chunk_layers, m.chunk_writes, m.tied) == (SERVED, False, True)
    assert [blk.sparse for blk in m.blocks] == [False, False] + [True] * 8
    assert m.expert_layers() == 8
    assert not hasattr(m, "lm_head")
    names = [type(b).__name__ for b in m.blocks]
    assert names[:4] == ["MixerBlock", "MixerBlock", "HybridBlock",
                         "MixerBlock"]
    attn = m.blocks[2].attn
    assert attn.has_qk_norm and attn.rotary_dim == attn.head_dim == 8
    assert attn.q_norm.weight.shape == attn.k_norm.weight.shape == (8,)
    ffn = m.blocks[2].ffn
    assert (ffn.first, ffn.count, ffn.normalize_eps) == (0, 8, 1e-6)
    assert list(m.blocks[0].ssm.init_state(3)) == ["conv"]


@pytest.mark.parametrize("dense", [0, 1, 3])
def test_any_number_of_leading_dense_layers(tokens, dense):
    m, cfg = build(num_dense_layers=dense, num_hidden_layers=6)
    assert [blk.sparse for blk in m.blocks] == [i >= dense for i in range(6)]
    assert close(m.forward(tokens), ref.forward(params_of(m), cfg, tokens))


@pytest.mark.parametrize("control", ["qk_norm", "bias_in_weights"])
def test_a_wrong_reading_of_the_architecture_is_seen(
        model, tokens, ref_logits, control):
    """The reference with the head norm left out, or with the selection
    bias added into the weights: the program's logits are not those."""
    m, cfg = model
    arg = {"qk_norm": False} if control == "qk_norm" \
        else {"bias_in_weights": True}
    wrong = ref.forward(params_of(m), cfg, tokens, **arg)
    assert float(jnp.max(jnp.abs(wrong - ref_logits))) > 100 * TOL


def test_a_routed_scaling_factor_is_passed_on_as_the_scale(tokens):
    m, cfg = build(routed_scaling_factor=2.5, num_hidden_layers=4)
    assert m.blocks[2].ffn.scale == 2.5
    assert close(m.forward(tokens), ref.forward(params_of(m), cfg, tokens))


REFUSED = [("conv_bias", True), ("use_expert_bias", False),
           ("norm_topk_prob", False), ("tie_word_embeddings", False),
           ("attention_bias", True), ("rope_scaling", {"type": "yarn"}),
           ("num_shared_experts", 1),
           ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
           ("layer_types", ["conv", "sliding_attention"] * 6)]


@pytest.mark.parametrize("key,value", REFUSED,
                         ids=[f"{k}={v}"[:40] for k, v in REFUSED])
def test_what_is_not_built_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=f"lfm2_moe: .*{key}"):
        lfm2_moe(dict(CFG, **{key: value}), MAX_LEN)


def test_a_decoder_refuses_a_block_with_no_earlier_layer_to_read():
    """What only the combination of blocks can get wrong: a cross block
    names an earlier layer that shares its row, a memory block comes
    after a mixer that hands its scan output on."""
    from bigdl_tpu.models import HybridDecoder
    from bigdl_tpu.models.hybrid_decoder import (
        CrossBlock, GatedFFN, GatedMemoryUnit, HybridBlock, MemoryBlock,
        MixerBlock)
    from bigdl_tpu.nn.differential_attention import DifferentialAttention
    from bigdl_tpu.nn.short_conv import GatedShortConv

    def ffn():
        return GatedFFN(16, 16)

    def row(**kw):
        return HybridBlock(16, DifferentialAttention(16, 4, 2, 4, 0), ffn(),
                           1e-5, **kw)

    def cross(reads):
        return CrossBlock(16, DifferentialAttention(16, 4, 2, 4, 1,
                                                    cross=True),
                          ffn(), 1e-5, reads=reads)

    def mixer(**kw):
        return MixerBlock(16, GatedShortConv(16), ffn(), 1e-5, **kw)

    def memory():
        return MemoryBlock(16, GatedMemoryUnit(16, 16), ffn(), 1e-5)

    for blocks, says in (
            ([row(), cross(0)], "reads layer 0"),       # row 0 is not shared
            ([cross(1), row(shares_row=True)], "reads layer 1"),   # later
            ([mixer(), memory()], "layer 1 reads a scan output"),
            ([memory(), mixer(hands_on=True)], "layer 0 reads a scan")):
        with pytest.raises(ValueError, match=says):
            HybridDecoder(20, 16, blocks, max_len=16)
    m = HybridDecoder(20, 16, [row(shares_row=True), cross(0)], max_len=16)
    assert (m.chunk_layers, m.chunk_writes) == (0, True)


# ---- the attention layer's head norm ------------------------------------------

def _normed_attention(seed=2):
    attn = GroupedQueryAttention(32, 4, 2, 8, rope_theta=1e6, rotary_dim=8,
                                 qk_norm=True)
    flat, tree = jax.tree_util.tree_flatten_with_path(attn)
    key = jax.random.key(seed)
    return jax.tree_util.tree_unflatten(tree, [
        1 + 0.3 * jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        if leaf.ndim == 1 else jax.random.normal(
            jax.random.fold_in(key, i), leaf.shape) * leaf.shape[-1] ** -0.5
        for i, (p, leaf) in enumerate(flat)])


def test_head_norm_whole_sequence_equals_the_reference():
    attn = _normed_attention()
    x = jax.random.normal(jax.random.key(0), (2, 21, 32))
    got, kv = attn.forward(x)
    w = {k: v for k, v in params_of(attn).items()}
    cfg = dict(CFG, hidden_size=32)
    want = ref.self_attention(x, w, cfg, lambda a: a, in_blocks=False)
    assert close(got, want)
    assert not close(got, ref.self_attention(
        x, w, cfg, lambda a: a, in_blocks=False, qk_norm=False), 100 * TOL)
    # the compact keys are normed and rotated: position 0 is not turned,
    # so its key has the norm's size, gain and all
    k0 = np.asarray(kv["k"][:, :, 0])
    rms = np.sqrt(np.mean(np.square(k0 / np.asarray(attn.k_norm.weight)),
                          axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-3)


@pytest.mark.parametrize("cuts", [(8,), (5, 13)])
def test_head_norm_chunks_then_row_steps_equal_the_whole_sequence(cuts):
    attn = _normed_attention()
    x = jax.random.normal(jax.random.key(1), (1, 21, 32))
    whole, _ = attn.forward(x)
    cache = attn.init_cache(3, 32)
    pad = jnp.zeros((3, 32), bool)
    outs, start = [], 0
    for stop in cuts:
        out, cache = attn.forward(x[:, start:stop], start, cache, pad, slot=1)
        outs.append(out)
        start = stop
    active = jnp.asarray([False, True, False])
    for t in range(start, 21):
        xs = jnp.zeros((3, 1, 32)).at[1].set(x[0, t:t + 1])
        index = jnp.where(active, t, 31).astype(jnp.int32)
        out, cache = attn.forward(xs, index, cache, pad, active=active)
        outs.append(out[1:2])
    assert close(jnp.concatenate(outs, axis=1), whole)


# ---- the joint pass and the slot pool -------------------------------------------

@pytest.mark.parametrize("scenario", joint_pass.SCENARIOS)
def test_the_joint_pass_equals_the_chunk_program_then_the_step(
        model, scenario):
    m, _ = model
    joint_pass.assert_joint_pass_equals_chunk_then_step(
        m, CHUNK, VOCAB, scenario, tol=TOL)


def _pool_prefill(pool, prompt, slot, chunks_only=False):
    joint_pass.pool_prefill(pool, prompt, slot, CHUNK, chunks_only)


def _decode_check(pool, slot, row, n_prompt, want, steps=None, tol=TOL):
    return joint_pass.decode_check(
        pool, slot, row, n_prompt, want,
        lambda got, ref_row: close(got, ref_row, tol), steps)


@pytest.mark.parametrize("n_prompt", list(PROMPTS.values()), ids=list(PROMPTS))
@pytest.mark.parametrize("chunks_only", [False, True],
                         ids=["as-scheduled", "chunk-program"])
def test_pool_prefill_then_pooled_decode_equals_the_reference(
        model, tokens, ref_logits, n_prompt, chunks_only):
    m, _ = model
    pool = SlotPool(m, slots=3, prefill_batch=1, ring_margin=CHUNK)
    slot, row = 1, np.asarray(tokens[0])
    _pool_prefill(pool, row[:n_prompt], slot, chunks_only)
    _decode_check(pool, slot, row, n_prompt, ref_logits[0])


@pytest.mark.parametrize("second", ["one", "three", "chunk+1", "3.5-chunks"])
def test_a_slots_second_occupant_starts_its_tails_from_zeros(
        model, tokens, ref_logits, second):
    m, _ = model
    pool = SlotPool(m, slots=2, prefill_batch=1, ring_margin=CHUNK)
    first, row = np.asarray(tokens[1]), np.asarray(tokens[0])
    _pool_prefill(pool, first[:20], 0)
    pool.caches = _decode_check(pool, 0, first, 20, ref_logits[1], steps=6)
    for i in CONVS:
        held = pool.caches["layers"][i]["ssm"]["conv"][0]
        assert float(jnp.max(jnp.abs(held))) > 1e-3   # something to forget
    n = PROMPTS[second]
    _pool_prefill(pool, row[:n], 0)
    _decode_check(pool, 0, row, n, ref_logits[0], steps=12)


def test_a_tail_left_by_the_last_occupant_would_be_seen(
        model, tokens, ref_logits):
    """The control of the test above: the same second occupant on tails
    that were not reset leaves the reference."""
    m, _ = model
    pool = SlotPool(m, slots=1, prefill_batch=1, ring_margin=CHUNK)
    first, row = np.asarray(tokens[1]), np.asarray(tokens[0])
    _pool_prefill(pool, first[:20], 0)
    caches = pool.caches
    # a one-token prompt's first step at position 0, told it is not fresh
    # by being sent at position 1 of a row whose position 0 is padding
    caches = dict(caches, pad=caches["pad"].at[0, 0].set(True))
    logits, _, _ = pool.model.decode_step(
        jnp.asarray([[int(row[0])]], jnp.int32), jnp.asarray([1], jnp.int32),
        caches, active=jnp.asarray([True]))
    assert not close(logits[0], ref_logits[0, 0], 100 * TOL)


def test_the_pool_declares_counts_and_sizes_what_each_layer_keeps(model):
    m, cfg = model
    pool = SlotPool(m, slots=3, dtype=jnp.bfloat16, ring_margin=CHUNK)
    assert pool.state_layers == N_CONV and pool.has_state
    assert not pool.has_ring and pool.full_row_readers == 1
    assert pool.chunk_layers == LAYERS and pool.expert_layers == N_EXPERT
    layers = pool.caches["layers"]
    assert [sorted(layer) for layer in layers] == [
        ["ssm"] if i in CONVS else ["self"] for i in range(LAYERS)]
    # a state whose only leaf is the tail, in the pool's dtype
    tail = layers[0]["ssm"]
    assert list(tail) == ["conv"]
    assert (tail["conv"].shape, tail["conv"].dtype) == ((3, 2 * 32),
                                                        jnp.bfloat16)
    assert layers[2]["self"]["k"].shape == (3, 2, MAX_LEN, 8)
    by_kind = pool.cache_nbytes_by_kind()
    assert by_kind["state"] == N_CONV * 3 * 2 * 32 * 2
    assert by_kind["full"] == N_ATTN * 3 * MAX_LEN * (2 * 2 * 8 * 2)
    assert by_kind["ring"] == by_kind["latent"] == 0
    assert sum(by_kind.values()) + pool.caches["pad"].size \
        == pool.cache_nbytes()
    # rows a slot and leaf where a layer has them (no row-write kernel on
    # a CPU), the flags' select, and one writer a tail
    assert pool.cache_write_programs == 1 + N_ATTN * 2 * 3 + N_CONV
    with pytest.raises(ValueError, match="no positions"):
        pool.kv_extract(0, 0, 4)
    with pytest.raises(ValueError, match="prefix cache"):
        GenerationScheduler(m, slots=2, prefill_chunk=CHUNK,
                            prefix_cache_bytes=1 << 20, start=False)


# ---- the engine end to end ---------------------------------------------------

def test_engine_serves_mixed_lengths_greedily(model, tokens):
    """Requests of every prefill route through ``GenerationScheduler`` (two
    slots, so slots are reused and prefills ride decode steps): every
    emitted token is the reference's best at its position given what came
    before; one row is the model's own ``generate()``; the counters of the
    tails and of the experts' product in ``stats()``."""
    m, cfg = model
    engine = GenerationScheduler(m, slots=2, prefill_chunk=CHUNK,
                                 prefill_batch=1)
    lengths, new = [1, 3, 7, 8, 9, 12, 20, 28, 33], 6
    row = np.asarray(tokens[0])
    try:
        futs = [engine.submit_async(row[:n], new) for n in lengths]
        rows = [np.asarray(fut.result(180)) for fut in futs]
        stats = engine.stats()
    finally:
        engine.shutdown()
    batch = np.ones((len(rows), 40), np.int32)
    for i, r in enumerate(rows):
        batch[i, :len(r)] = r
    best = np.asarray(jnp.argmax(
        ref.forward(params_of(m), cfg, jnp.asarray(batch)), -1)) + 1
    for i, n in enumerate(lengths):
        np.testing.assert_array_equal(rows[i][:n], row[:n])
        np.testing.assert_array_equal(rows[i][n:], best[i, n - 1:n - 1 + new])
    np.testing.assert_array_equal(
        rows[5], np.asarray(m.generate(tokens[:1, :12], new, chunk=CHUNK))[0])
    assert engine.pool.trace_counts["decode"] == 1
    assert stats["state_resets"] == len(lengths)
    assert stats["ssm_scan_positions_real"] \
        == N_CONV * sum(n - 1 for n in lengths)
    assert stats["ssm_layer_calls"] == N_CONV * (
        stats["decode_dispatches"] + stats["prefill_calls"])
    assert stats["chunk_layer_positions"] \
        == LAYERS * stats["prefill_positions"]
    assert stats["cache_bytes_state"] == N_CONV * 2 * 2 * 32 * 4
    # every expert is held: every routed pair lands here, and the product
    # multiplied at least the rows the pairs asked for
    assert stats["moe_pairs_held"] == stats["moe_pairs_total"] > 0
    assert stats["moe_rows_computed"] >= stats["moe_pairs_held"]
    assert stats["moe_layer_calls"] > 0
    assert stats["moe_layer_calls"] % N_EXPERT == 0

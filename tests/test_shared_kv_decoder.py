"""``phi4_flash`` (the decoder-hybrid-decoder walk of ``HybridDecoder``:
Mamba-1 layers that keep a state and no row, differential attention over
rings and over one full row that the cross layers read too, gated memory
units that keep nothing) at a small size on the CPU, on seeded weights,
against the plain reference in ``benchmark/reference/shared_kv_ssm_lm.py``
(loaded by path: it is the one copy and imports nothing of the program):
the whole-sequence pass, chunks then steps through a slot pool, the joint
pass, where a chunk's rows stop, the reset at admission, and the engine
end to end."""

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
from reference import shared_kv_ssm_lm as ref                 # noqa: E402

from bigdl_tpu.models import phi4_flash, transformer_lm       # noqa: E402
from bigdl_tpu.serving.generation import (                    # noqa: E402
    GenerationScheduler, SlotPool)

CHUNK, MAX_LEN, VOCAB, LAYERS = 8, 64, 50, 8
HALF = LAYERS // 2                  # the mixer that hands on; HALF + 1: the row
CFG = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=LAYERS,
           num_attention_heads=8, num_key_value_heads=4, intermediate_size=48,
           sliding_window=6, mb_per_layer=2, layer_norm_eps=1e-5,
           tie_word_embeddings=True, hidden_act="silu", mamba_d_state=8,
           mamba_d_conv=4, mamba_expand=2, mamba_dt_rank="auto")
INNER, N = 64, 8
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
    """The model on seeded leaves, the recurrence's set to remember (step
    sizes about 0.05, ``A`` 1..8 as built) and the lambda vectors a normal
    of 0.3."""
    cfg = dict(CFG, **over)
    m = phi4_flash(cfg, MAX_LEN).eval_mode()
    flat, tree = jax.tree_util.tree_flatten_with_path(m)
    key, leaves = jax.random.key(seed), []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        if name.endswith("dt_proj.bias"):
            leaf = -3.0 + 0.4 * noise
        elif name.endswith("A_log"):
            pass
        elif "lambda" in name:
            leaf = 0.3 * noise
        elif leaf.ndim == 1:
            leaf = 0.1 * noise if name.endswith("bias") else 1 + 0.1 * noise
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


def test_the_layers_by_index_at_the_published_depth():
    """32 layers: 9 states, 8 rings, one full row, 7 layers that read it
    and keep nothing, 7 that keep nothing at all; a chunk's rows walk 17
    layers whole and then write layer 17's keys and values."""
    cfg = dict(CFG, num_hidden_layers=32, sliding_window=512)
    m = jax.eval_shape(lambda: phi4_flash(cfg, 1024))
    decl = m.cache_layers()
    state = {"ssm": ("state", None)}
    assert decl[:16] == (state, ("ring", 512)) * 8
    assert decl[16:18] == (state, ("full", 1024))
    assert decl[18:] == ({}, {"reads": ("shared", 17)}) * 7
    assert (m.chunk_layers, m.chunk_writes, m.tied) == (17, True, True)
    names = [type(b).__name__ for b in m.blocks]
    assert names[16:20] == ["MixerBlock", "HybridBlock", "MemoryBlock",
                            "CrossBlock"]
    assert m.blocks[16].hands_on and not m.blocks[14].hands_on
    assert m.blocks[17].shares_row and not m.blocks[15].shares_row
    assert m.blocks[31].attn.lambda_init == pytest.approx(
        0.8 - 0.6 * np.exp(-0.3 * 31))
    assert not hasattr(m, "lm_head")


def test_the_other_factories_walk_every_layer_with_a_chunk():
    lm = jax.eval_shape(lambda: __import__(
        "bigdl_tpu.models", fromlist=["falcon_h1"]).falcon_h1(
        dict(vocab_size=30, hidden_size=32, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=8,
             intermediate_size=48, rope_theta=1e4, mamba_n_heads=4,
             mamba_d_head=8, mamba_d_ssm=32, mamba_n_groups=2,
             mamba_d_state=16, mamba_d_conv=4), 32))
    assert (lm.chunk_layers, lm.chunk_writes, lm.tied) == (2, False, False)


@pytest.mark.parametrize("control", ["memory_after_gate", "cross_window"])
def test_a_wrong_reading_of_the_architecture_is_seen(
        model, tokens, ref_logits, control):
    """The reference with ``m`` taken after the gate, or with the cross
    layers attending a window of the row: the program's logits are not
    those."""
    m, cfg = model
    arg = {"memory_after_gate": True} if control == "memory_after_gate" \
        else {"cross_window": 6}
    wrong = ref.forward(params_of(m), cfg, tokens, **arg)
    assert float(jnp.max(jnp.abs(wrong - ref_logits))) > 100 * TOL


REFUSED = [("mlp_bias", True), ("lm_head_bias", True), ("embd_pdrop", 0.1),
           ("resid_pdrop", 0.1), ("attention_dropout", 0.1),
           ("rope_scaling", {"type": "longrope"}),
           ("tie_word_embeddings", False), ("hidden_act", "gelu"),
           ("mb_per_layer", 4), ("num_hidden_layers", 6),
           ("num_hidden_layers", 4)]


@pytest.mark.parametrize("key,value", REFUSED,
                         ids=[f"{k}={v}" for k, v in REFUSED])
def test_what_is_not_built_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=f"phi4_flash: .*{key}"):
        phi4_flash(dict(CFG, **{key: value}), MAX_LEN)


def test_a_layer_must_read_an_earlier_layer_of_the_right_kind():
    """The factory's own blocks, put in an order it never makes: the
    cross layers before the row they name, the units before the mixer
    that hands its scan output on."""
    from bigdl_tpu.models import HybridDecoder
    blocks = list(phi4_flash(CFG, MAX_LEN).blocks)
    args = dict(vocab_size=VOCAB, hidden_size=32, max_len=MAX_LEN)
    for order, says in (
            (blocks[:HALF + 1] + blocks[HALF + 2:], f"reads layer {HALF + 1}"),
            (blocks[HALF + 1:], "reads a scan output")):
        with pytest.raises(ValueError, match=says):
            HybridDecoder(blocks=order, **args)
    HybridDecoder(blocks=blocks, **args)


# ---- where a chunk's rows stop ----------------------------------------------------

def _scaled(m, pick):
    """``m`` with every leaf whose path ``pick`` accepts doubled and
    shifted."""
    flat, tree = jax.tree_util.tree_flatten_with_path(m)
    return jax.tree_util.tree_unflatten(tree, [
        leaf * 2.0 + 0.3 if pick(jax.tree_util.keystr(p)) else leaf
        for p, leaf in flat])


def _beyond_the_caches(name: str) -> bool:
    """Leaves of layers HALF + 2 .., the last norm, and of layer HALF + 1
    its query rows, lambda vectors, head norm, output projection and
    feed-forward."""
    row = f".blocks[{HALF + 1}]."
    if name.startswith(row):
        rest = name[len(row):]
        return rest.startswith(("ffn", "attn.lambda", "attn.norm",
                                "attn.output_layer"))
    layer = int(name.split("[")[1].split("]")[0]) if "[" in name else -1
    return layer > HALF + 1 or name.startswith(".final_norm")


@pytest.mark.parametrize("entry", ["prefill_chunk", "prefill_kv",
                                   "decode_step_with_chunk"])
def test_nothing_beyond_the_caches_moves_what_a_chunk_writes(
        model, tokens, entry):
    """Changing any weight of the layers after the row's, or that layer's
    query, output or feed-forward, leaves what a chunk writes unchanged:
    its rows stop where the caches stop."""
    m, _ = model
    other = _scaled(m, _beyond_the_caches)
    # layer HALF + 1's query rows: the first Hq * d of the one projection
    flat, tree = jax.tree_util.tree_flatten_with_path(other)
    rows = CFG["num_attention_heads"] * 4
    other = jax.tree_util.tree_unflatten(tree, [
        leaf.at[:rows].multiply(3.0) if jax.tree_util.keystr(p).startswith(
            f".blocks[{HALF + 1}].attn.q_layer") else leaf
        for p, leaf in flat])
    assert not close(other.forward(tokens), m.forward(tokens), 1e-2)
    toks = tokens[:1, :CHUNK]

    def written(mod):
        if entry == "prefill_kv":
            return mod.prefill_kv(tokens[:, :12])[0]
        caches = mod.init_cache(3, ring_margin=CHUNK)
        if entry == "prefill_chunk":
            caches, _ = mod.prefill_chunk(toks, 0, caches, slot=1)
            return mod.prefill_chunk(tokens[:1, CHUNK:2 * CHUNK], CHUNK,
                                     caches, slot=1)[0]["layers"]
        # the joint pass with every row idle: only the chunk writes
        return mod.decode_step_with_chunk(
            jnp.zeros((3, 1), jnp.int32), jnp.zeros((3,), jnp.int32), caches,
            jnp.zeros((3,), bool), toks, 0, 1)[1]["layers"]
    a, b = written(m), written(other)
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert all(layer == {} for layer in a[HALF + 2:])
    assert set(a[HALF + 1] if entry == "prefill_kv"
               else a[HALF + 1]["self"]) == {"k", "v"}


@pytest.mark.parametrize("scenario", joint_pass.SCENARIOS)
def test_the_joint_pass_equals_the_chunk_program_then_the_step(
        model, scenario):
    m, _ = model
    joint_pass.assert_joint_pass_equals_chunk_then_step(
        m, CHUNK, VOCAB, scenario, tol=TOL)


# ---- the slot pool ------------------------------------------------------------

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
def test_a_slots_second_occupant_starts_its_states_from_zeros(
        model, tokens, ref_logits, second):
    m, _ = model
    pool = SlotPool(m, slots=2, prefill_batch=1, ring_margin=CHUNK)
    first, row = np.asarray(tokens[1]), np.asarray(tokens[0])
    _pool_prefill(pool, first[:20], 0)
    pool.caches = _decode_check(pool, 0, first, 20, ref_logits[1], steps=6)
    for i in range(0, HALF + 1, 2):
        held = pool.caches["layers"][i]["ssm"]["ssm"][0]
        assert float(jnp.max(jnp.abs(held))) > 1e-3   # something to forget
    n = PROMPTS[second]
    _pool_prefill(pool, row[:n], 0)
    _decode_check(pool, 0, row, n, ref_logits[0], steps=12)


def test_a_state_left_by_the_last_occupant_would_be_seen(
        model, tokens, ref_logits):
    """The control of the test above: the same second occupant on states
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
    assert pool.state_layers == HALF // 2 + 1 and pool.has_state
    assert pool.has_ring and pool.full_row_readers == 1 + (HALF - 2) // 2
    assert pool.chunk_layers == HALF + 1
    layers = pool.caches["layers"]
    assert [sorted(layer) for layer in layers] == \
        [["ssm"], ["self"]] * (HALF // 2) + [["ssm"], ["self"]] \
        + [[], []] * ((HALF - 2) // 2)
    state = layers[0]["ssm"]
    assert state["ssm"].shape == (3, N, INNER)      # channels along the lanes
    assert state["ssm"].dtype == jnp.float32        # whatever the pool's
    assert (state["conv"].shape, state["conv"].dtype) == ((3, 3, INNER),
                                                          jnp.bfloat16)
    # paired heads side by side: Hkv / 2 heads of 2 d
    assert layers[1]["self"]["k"].shape == (3, 2, 6 + CHUNK, 8)
    assert layers[HALF + 1]["self"]["v"].shape == (3, 2, MAX_LEN, 8)
    by_kind = pool.cache_nbytes_by_kind()
    place = 2 * 2 * 8 * 2
    assert by_kind["state"] == pool.state_layers * 3 * (N * INNER * 4
                                                        + 3 * INNER * 2)
    assert by_kind["ring"] == (HALF // 2) * 3 * (6 + CHUNK) * place
    assert by_kind["full"] == 3 * MAX_LEN * place        # one row, not five
    assert by_kind["latent"] == 0
    assert sum(by_kind.values()) + pool.caches["pad"].size \
        == pool.cache_nbytes()
    # rows a slot and leaf where a layer has them (no row-write kernel on
    # a CPU), the flags' select, and two writers a state
    assert pool.cache_write_programs == 1 + (HALF // 2 + 1) * 2 * 3 \
        + pool.state_layers * 2
    with pytest.raises(ValueError, match="no positions"):
        pool.kv_extract(0, 0, 4)
    with pytest.raises(ValueError, match="prefix cache"):
        GenerationScheduler(m, slots=2, prefill_chunk=CHUNK,
                            prefix_cache_bytes=1 << 20, start=False)


def test_a_model_whose_layers_all_keep_rows_has_one_reader_a_row():
    opt = SlotPool(transformer_lm(vocab_size=30, hidden_size=16, num_layers=2,
                                  num_heads=2, filter_size=32, max_len=32),
                   slots=2)
    assert opt.full_row_readers == 1 and opt.chunk_layers == 2


# ---- the engine end to end ---------------------------------------------------

def test_engine_serves_mixed_lengths_greedily(model, tokens):
    """Requests of every prefill route through ``GenerationScheduler`` (two
    slots, so slots are reused and prefills ride decode steps): every
    emitted token is the reference's best at its position given what came
    before; one row is the model's own ``generate()``; the counters of the
    shared row and of the chunk's depth in ``stats()``."""
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
    states = HALF // 2 + 1
    assert stats["ssm_scan_positions_real"] \
        == states * sum(n - 1 for n in lengths)
    assert stats["ssm_layer_calls"] == states * (
        stats["decode_dispatches"] + stats["prefill_calls"])
    assert stats["full_row_readers"] == 1 + (HALF - 2) // 2
    assert stats["prefill_positions"] > 0
    assert stats["chunk_layer_positions"] \
        == (HALF + 1) * stats["prefill_positions"]
    assert stats["cache_bytes_state"] > 0 and stats["cache_bytes_window"] > 0

"""``afmoe`` (``HybridDecoder`` with gated grouped-query attention, three
rotated window layers over rings to every full layer that rotates nothing,
a norm on both sides of every mixer and feed-forward, a leading dense
layer, then every expert of a layer held here beside a shared one; an
embedding times ``sqrt(hidden)``, an untied head) at a small size on the
CPU, on seeded weights, against the plain reference in
``benchmark/reference/gated_window_moe_lm.py`` (loaded by path: it is the
one copy and imports nothing of the program): the whole-sequence pass,
chunks then steps through a slot pool whose rings wrap, the joint pass, a
slot's second occupant, the gate on every path, what a layer kind sees of
positions, the factory's refusals, the pool's ring counters, and the
engine end to end.

Tolerances: everything here runs in float32 at ``highest`` on the same
leaves, so the program and the reference differ by the order of their sums
alone; ``TOL`` 5e-5 on logits of order one is what the five other
families' tests hold (a wrong mask, a missing norm or a position off by
one moves a logit by 1e-2 and more)."""

import json
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
from reference import gated_window_moe_lm as ref              # noqa: E402

from bigdl_tpu.models import afmoe                            # noqa: E402
from bigdl_tpu.nn.attention import GroupedQueryAttention      # noqa: E402
from bigdl_tpu.serving.generation import (                    # noqa: E402
    GenerationScheduler, SlotPool)

WINDOW, CHUNK, MAX_LEN, VOCAB, LAYERS = 12, 8, 64, 50, 5
TYPES = ["sliding_attention", "sliding_attention", "sliding_attention",
         "full_attention"] * 2
# heads of 16 on a stream of 32: the heads' output (and the gate) is 64
# wide, twice the stream, as the published 4,096 is of 2,048
CFG = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=LAYERS,
           layer_types=TYPES, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, sliding_window=WINDOW, rope_theta=10000,
           rope_scaling=None, intermediate_size=48, num_dense_layers=1,
           moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
           num_shared_experts=1, route_scale=2.826, route_norm=True,
           score_func="sigmoid", rms_norm_eps=1e-5, mup_enabled=True,
           tie_word_embeddings=False, n_group=1, num_expert_groups=1,
           topk_group=1, num_limited_groups=1, hidden_act="silu")
RINGS = [i for i, t in enumerate(TYPES[:LAYERS]) if t == "sliding_attention"]
PLACES = WINDOW + CHUNK          # the window, the chunk's margin, the spare
# a prompt shorter than the window, one that fills it, and prompts whose
# rings wrap (19 ring places: a position past 18 lands on an earlier one)
PROMPTS = {"one": 1, "three": 3, "under-the-window": WINDOW - 2,
           "chunk+1": CHUNK + 1, "window+1": WINDOW + 1,
           "3.5-chunks": 3 * CHUNK + CHUNK // 2, "wraps-twice": 45}
TOL = 5e-5


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def params_of(model):
    flat = jax.tree_util.tree_flatten_with_path(model)[0]
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat}


def seeded(module, seed):
    """``module`` on seeded leaves: matrices and stacks a normal of their
    last axis' ``** -0.5`` (the gate's projection four times that, so that
    it opens and shuts), gains ``1 +- 0.1``, the selection bias ``0.05 x
    normal``."""
    flat, tree = jax.tree_util.tree_flatten_with_path(module)
    key, leaves = jax.random.key(seed), []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        if leaf.ndim == 1:
            leaf = 0.05 * noise if name.endswith("bias") else 1 + 0.1 * noise
        else:
            leaf = noise * leaf.shape[-1] ** -0.5 \
                * (4.0 if "gate_layer" in name else 1.0)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(tree, leaves)


def with_leaf(module, path, value):
    """``module`` with the leaf at ``path`` (its ``keystr``) replaced."""
    flat, tree = jax.tree_util.tree_flatten_with_path(module)
    assert path in [jax.tree_util.keystr(p) for p, _ in flat]
    return jax.tree_util.tree_unflatten(tree, [
        value if jax.tree_util.keystr(p) == path else leaf
        for p, leaf in flat])


def build(seed=11, **over):
    cfg = dict(CFG, **over)
    return seeded(afmoe(cfg, MAX_LEN).eval_mode(), seed), cfg


@pytest.fixture(scope="module")
def model():
    with jax.default_matmul_precision("highest"):
        return build()


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(
        1, VOCAB + 1, (2, 56)), jnp.int32)


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
    assert got.shape == (2, 56, VOCAB)
    assert close(got, ref_logits)
    assert float(jnp.std(ref_logits)) > 0.1


def test_the_reference_walked_in_blocks_equals_its_gather(
        model, tokens, ref_logits, monkeypatch):
    """The reference's two ways (each token through its own experts by a
    gather and the whole scores; the held experts a group at a time over
    every token and the queries in blocks) are one sum, and the blocked
    walk is what the serving check runs."""
    monkeypatch.setattr(ref, "SCORES_BYTES", 0)
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    monkeypatch.setattr(ref, "EXPERT_GROUP", 2)
    ref._STEPS.clear()
    m, cfg = model
    params = params_of(m)
    cfg = dict(cfg, serving={"weights_dtype": "float32"})
    x = ref.embed(params, cfg, tokens)
    for i in range(LAYERS):
        x = ref.block(params, cfg, i, x)
    assert close(ref.head(params, cfg, x), ref_logits)
    # one program a layer kind: a window over a dense layer, a window over
    # the experts, a full layer over the experts
    assert sorted(k[0] for k in ref._STEPS) == [
        f"block.{WINDOW}.False", f"block.{WINDOW}.True", "block.None.True",
        "embed", "head"]
    ref._STEPS.clear()


def test_the_layers_by_index_at_the_served_depth():
    """Five layers: four rings and one full row; one leading dense layer;
    every block has its four norms, every attention its gate and its head
    norms; the window layers rotate the whole head and the full layer
    nothing."""
    m = jax.eval_shape(lambda: afmoe(CFG, 1024))
    ring = ("ring", WINDOW)
    assert m.cache_layers() == (ring, ring, ring, ("full", 1024), ring)
    assert (m.chunk_layers, m.chunk_writes, m.tied) == (LAYERS, False, False)
    assert [blk.sparse for blk in m.blocks] == [False] + [True] * 4
    assert m.expert_layers() == 4
    assert m.embedding_multiplier == 32 ** 0.5
    assert m.lm_head.weight.shape == (VOCAB, 32)
    for i, blk in enumerate(m.blocks):
        assert type(blk).__name__ == "HybridBlock"
        assert blk.attn_post_norm.weight.shape == (32,)
        assert blk.ffn_post_norm.weight.shape == (32,)
        attn = blk.attn
        assert attn.has_gate and attn.has_qk_norm and not attn.has_sink
        assert attn.gate_layer.weight.shape == (4 * 16, 32)
        assert attn.q_norm.weight.shape == attn.k_norm.weight.shape == (16,)
        assert (attn.window, attn.rotary_dim) == (
            (WINDOW, 16) if i in RINGS else (None, 0))
    ffn = m.blocks[1].ffn
    assert (ffn.first, ffn.count, ffn.scale, ffn.normalize_eps) == (
        0, 8, 2.826, 1e-20)
    assert ffn.has_shared and ffn.shared.gate.weight.shape == (16, 32)
    caches = jax.eval_shape(lambda: m.init_cache(3, ring_margin=CHUNK))
    assert caches["layers"][0]["self"]["k"].shape == (3, 2, PLACES, 16)
    assert caches["layers"][3]["self"]["k"].shape == (3, 2, 1024, 16)


def test_the_published_cut_counts_4241_million_parameters():
    """Nothing is allocated: the model at the benchmark's configuration
    (Trinity-Mini's widths, five layers, one of them dense)."""
    with open(os.path.join(BENCH, "configs", "trinity-mini.json")) as f:
        cfg = json.load(f)
    m = jax.eval_shape(lambda: afmoe(cfg, cfg["serving"]["max_len"]))
    leaves = jax.tree_util.tree_leaves(m)
    assert round(sum(int(np.prod(l.shape)) for l in leaves) / 1e5) == 42415
    assert m.blocks[0].attn.gate_layer.weight.shape == (4096, 2048)
    assert m.blocks[1].ffn.w_gate.shape == (128, 2048, 1024)
    assert m.cache_layers()[3] == ("full", 14336)


@pytest.mark.parametrize("over", [
    dict(num_dense_layers=0), dict(num_dense_layers=2, num_hidden_layers=6),
    dict(num_shared_experts=0), dict(mup_enabled=False),
    dict(num_hidden_layers=8, route_scale=1.0)],
    ids=["no-dense", "two-dense", "no-shared", "no-mup",
         "two-periods-scale-1"])
def test_the_factory_follows_the_configurations_keys(tokens, over):
    m, cfg = build(**over)
    dense = cfg["num_dense_layers"]
    assert [blk.sparse for blk in m.blocks] == [
        i >= dense for i in range(cfg["num_hidden_layers"])]
    assert m.embedding_multiplier == (32 ** 0.5 if cfg["mup_enabled"] else 1)
    assert close(m.forward(tokens), ref.forward(params_of(m), cfg, tokens))


REFUSED = [("score_func", "softmax"), ("n_group", 2),
           ("num_expert_groups", 4), ("topk_group", 2),
           ("num_limited_groups", 2), ("route_norm", False),
           ("rope_scaling", {"type": "yarn", "factor": 4}),
           ("num_shared_experts", 2), ("tie_word_embeddings", True),
           ("hidden_act", "gelu"),
           ("layer_types", ["sliding_attention", "conv"] * 4)]


@pytest.mark.parametrize("key,value", REFUSED,
                         ids=[f"{k}={v}"[:40] for k, v in REFUSED])
def test_what_is_not_built_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=f"afmoe: .*{key}"):
        afmoe(dict(CFG, **{key: value}), MAX_LEN)


def test_the_further_norms_are_this_familys_alone():
    """Every block of this family has a norm behind its mixer and behind
    its feed-forward; no block of the five other families has a leaf of
    either (``family_params.json``, which ``test_model_families`` holds
    each factory to), and a block that is handed none adds its sub-layers'
    outputs as they come."""
    from bigdl_tpu.models.hybrid_decoder import GatedFFN, HybridBlock
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "family_params.json")) as f:
        committed = json.load(f)
    assert sorted(committed) == ["afmoe", "falcon_h1", "lfm2_moe", "mimo_v2",
                                 "phi4_flash", "sarvam_mla"]
    for family, leaves in committed.items():
        further = [p for p, _ in leaves if "post_norm" in p]
        gates = [p for p, _ in leaves if "gate_layer" in p]
        if family == "afmoe":
            assert len(further) == 2 * LAYERS and len(gates) == LAYERS
        else:
            assert not further and not gates, family
    x = jax.random.normal(jax.random.key(0), (1, 6, 32))
    plain = seeded(HybridBlock(
        32, GroupedQueryAttention(32, 4, 2, 16), GatedFFN(32, 48), 1e-5), 3)
    assert [n for n in params_of(plain) if "post_norm" in n] == []
    y, _, _ = plain.forward(x)
    a, _ = plain.attn.forward(plain.attn_norm(x))
    h = x + a
    assert close(y, h + plain.ffn.forward(plain.ffn_norm(h)))


# ---- the gate, and what a layer kind sees of positions ---------------------------

def _attention(window, gate=True, seed=2):
    return seeded(GroupedQueryAttention(
        32, 4, 2, 16, window=window, rotary_dim=16 if window else 0,
        qk_norm=True, gate=gate), seed)


def _on_every_path(attn, x, cuts=(5, 13)):
    """``attn`` on ``x [1, T, H]`` four ways: the whole sequence, the
    compact prefill's own output, and chunks then per-row steps through a
    pool's cache (row 1 of 3, rings with a chunk's margin)."""
    T = x.shape[1]
    whole, _ = attn.forward(x)
    cache = attn.init_cache(3, 32, ring_margin=8)
    pad = jnp.zeros((3, 32), bool)
    outs, start = [], 0
    for stop in cuts:
        out, cache = attn.forward(x[:, start:stop], start, cache, pad, slot=1)
        outs.append(out)
        start = stop
    active = jnp.asarray([False, True, False])
    for t in range(start, T):
        xs = jnp.zeros((3, 1, 32)).at[1].set(x[0, t:t + 1])
        index = jnp.where(active, t, 31).astype(jnp.int32)
        out, cache = attn.forward(xs, index, cache, pad, active=active)
        outs.append(out[1:2])
    return whole, jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("window", [None, 6], ids=["full", "window"])
def test_the_gate_is_on_every_path(window):
    """Input whose first feature is one, and a gate projection that reads
    that feature alone: at ``-1000`` the gate is shut and the attention
    gives nothing on any path (whole sequence, chunk, per-row step); at
    ``+1000`` it is open and every path gives what the layer without a
    gate gives on the same leaves; as seeded, the paths agree with each
    other and with neither."""
    x = jax.random.normal(jax.random.key(0), (1, 21, 32)).at[..., 0].set(1.0)
    attn = _attention(window)
    column = jnp.zeros_like(attn.gate_layer.weight).at[:, 0].set(1000.0)
    shut = with_leaf(attn, ".gate_layer.weight", -column)
    opened = with_leaf(attn, ".gate_layer.weight", column)
    plain = _attention(window, gate=False)
    for name, leaf in params_of(plain).items():
        plain = with_leaf(plain, name, params_of(attn)[name])
    want, _ = plain.forward(x)
    assert float(jnp.std(want)) > 0.05
    for layer, target in ((shut, jnp.zeros_like(want)), (opened, want)):
        whole, pooled = _on_every_path(layer, x)
        assert close(whole, target) and close(pooled, target)
    whole, pooled = _on_every_path(attn, x)
    assert close(whole, pooled)
    assert not close(whole, want, 100 * TOL)
    assert float(jnp.max(jnp.abs(whole))) > 100 * TOL


@pytest.mark.parametrize("window", [None, 32], ids=["full", "window"])
def test_a_full_layer_sees_no_position_and_a_window_layer_does(window):
    """The positions before the last one put in another order.  A full
    layer rotates nothing, so its last query attends a set: its output
    there does not move.  A window layer (wide enough here to mask
    nothing) rotates queries and keys by position, and it moves.  (A
    uniform offset of all positions moves neither: rotation is relative.)
    """
    attn = _attention(window)
    x = jax.random.normal(jax.random.key(4), (1, 17, 32))
    order = np.concatenate([np.random.default_rng(1).permutation(16), [16]])
    there, _ = attn.forward(x)
    moved, _ = attn.forward(x[:, order])
    same = close(there[:, -1], moved[:, -1])
    assert same == (window is None)
    if window is not None:
        assert not close(there[:, -1], moved[:, -1], 100 * TOL)
    shifted, _ = attn.forward(x, index=7)
    assert close(there, shifted, 4 * TOL)


# ---- the joint pass and the slot pool -------------------------------------------

@pytest.mark.parametrize("scenario", joint_pass.ROW_SCENARIOS)
def test_the_joint_pass_equals_the_chunk_program_then_the_step(
        model, scenario):
    m, _ = model
    joint_pass.assert_joint_pass_equals_chunk_then_step(
        m, CHUNK, VOCAB, scenario, tol=TOL)


@pytest.fixture(scope="module")
def pool(model):
    """One pool of three slots for the tests below (its chunk programs
    compile once): each test's request takes a slot that the test before
    left as it was, so every one of them is also a slot's next occupant."""
    with jax.default_matmul_precision("highest"):
        return SlotPool(model[0], slots=3, prefill_batch=1,
                        ring_margin=CHUNK)


def _pool_prefill(pool, prompt, slot, chunks_only=False):
    joint_pass.pool_prefill(pool, prompt, slot, CHUNK, chunks_only)


def _decode_check(pool, slot, row, n_prompt, want, steps=None, tol=TOL):
    return joint_pass.decode_check(
        pool, slot, row, n_prompt, want,
        lambda got, ref_row: close(got, ref_row, tol), steps)


# the prompts as the scheduler sends them, or through the chunk program
# whatever their length
ROUTES = [(n, False) for n in PROMPTS if n not in ("three", "window+1")] + [
    (n, True) for n in ("three", "window+1", "wraps-twice")]


@pytest.mark.parametrize("prompt,chunks_only", ROUTES, ids=[
    f"{'chunk-program' if c else 'as-scheduled'}-{n}" for n, c in ROUTES])
def test_pool_prefill_then_pooled_decode_equals_the_reference(
        pool, tokens, ref_logits, prompt, chunks_only):
    """Prefill as the scheduler sends it, then 14 teacher-forced steps: the
    slot's rings (19 places and the spare) have wrapped by then for every
    prompt of a chunk and more, twice for the longest; the shortest never
    fill their window; the chunk's margin is used by every chunk past the
    window; and the reference has no ring at all (a window is a mask over
    the whole sequence)."""
    assert pool.caches["layers"][0]["self"]["k"].shape[2] == PLACES
    slot, row, n_prompt = 1, np.asarray(tokens[0]), PROMPTS[prompt]
    _pool_prefill(pool, row[:n_prompt], slot, chunks_only)
    pool.caches = _decode_check(pool, slot, row, n_prompt, ref_logits[0],
                                steps=14)


@pytest.mark.parametrize("second", ["one", "under-the-window",
                                    "3.5-chunks"])
def test_a_slot_reused_by_a_shorter_request_sees_nothing_of_the_last(
        pool, tokens, ref_logits, second):
    """A long request fills slot 0's rings past a wrap and its full row to
    position 44; the next occupant is shorter: what the rings and the row
    still hold beyond its positions is masked by position, not by
    content."""
    first, row = np.asarray(tokens[1]), np.asarray(tokens[0])
    _pool_prefill(pool, first[:40], 0)
    pool.caches = _decode_check(pool, 0, first, 40, ref_logits[1], steps=5)
    for i in range(LAYERS):
        held = pool.caches["layers"][i]["self"]["k"][0]
        assert float(jnp.min(jnp.max(jnp.abs(held[:, :PLACES - 1]),
                                     axis=(0, 2)))) > 1e-3
    n = PROMPTS[second]
    _pool_prefill(pool, row[:n], 0)
    pool.caches = _decode_check(pool, 0, row, n, ref_logits[0], steps=8)


def test_the_pool_declares_and_sizes_what_each_layer_keeps(model):
    m, _ = model
    pool = SlotPool(m, slots=3, dtype=jnp.bfloat16, ring_margin=CHUNK)
    assert pool.has_ring and not pool.has_state
    assert pool.full_row_readers == 1 and pool.chunk_layers == LAYERS
    assert pool.expert_layers == 4
    assert pool.rings == ((WINDOW, PLACES, len(RINGS)),)
    by_kind = pool.cache_nbytes_by_kind()
    place = 2 * 2 * 16 * 2           # keys and values, 2 heads of 16, bf16
    assert by_kind["ring"] == len(RINGS) * 3 * PLACES * place
    assert by_kind["full"] == 3 * MAX_LEN * place
    assert by_kind["state"] == by_kind["latent"] == 0
    # rows a slot and leaf (no row-write kernel on a CPU) and the flags
    assert pool.cache_write_programs == 1 + LAYERS * 2 * 3
    with pytest.raises(ValueError, match="prefix cache"):
        GenerationScheduler(m, slots=2, prefill_chunk=CHUNK,
                            prefix_cache_bytes=1 << 20, start=False)


def test_the_pool_counts_what_the_step_reads_of_its_rings(model):
    """A hand-made pool: three slots of which two decode, one under its
    window and one past it.  ``ring_positions_live`` of a dispatch is each
    active slot's positions up to the window, summed over the four ring
    layers; ``ring_positions_read`` every slot's ring whole in each of
    them, active or not.  A step still unread stands one position behind
    on the mirrors and is counted as the device has it."""
    m, _ = model
    pool = SlotPool(m, slots=3, prefill_batch=1, ring_margin=CHUNK)
    pool.index[:] = [4, 0, 30]
    pool.tok[:] = [5, 0, 7]
    pool.active[:] = [True, False, True]
    first = pool.decode_dispatch()
    assert first.rings == (len(RINGS) * (5 + WINDOW),
                           len(RINGS) * 3 * PLACES)
    assert first.positions[0] == 5 + 31
    second = pool.decode_dispatch()
    assert second.rings == (len(RINGS) * (6 + WINDOW),
                            len(RINGS) * 3 * PLACES)
    pool.read_emit(first)
    pool.read_emit(second)
    # a pool without window layers counts none
    from tests import test_lfm2_moe
    other = SlotPool(test_lfm2_moe.build()[0], slots=2, prefill_batch=1,
                     ring_margin=CHUNK)
    other.active[:] = [True, False]
    assert other.rings == () and other.decode_dispatch().rings == (0, 0)


# ---- the engine end to end ---------------------------------------------------

def test_engine_serves_mixed_lengths_greedily(model, tokens):
    """Requests of every prefill route through ``GenerationScheduler`` (two
    slots, so slots are reused and prefills ride decode steps), prompts
    under the window and past a wrap of the rings: every emitted token is
    the reference's best at its position given what came before; one row
    is the model's own ``generate()``; the ring counters in ``stats()``."""
    m, cfg = model
    engine = GenerationScheduler(m, slots=2, prefill_chunk=CHUNK,
                                 prefill_batch=1)
    lengths, new = [1, 3, 9, 12, 28, 45], 6
    row = np.asarray(tokens[0])
    try:
        futs = [engine.submit_async(row[:n], new) for n in lengths]
        rows = [np.asarray(fut.result(180)) for fut in futs]
        stats = engine.stats()
    finally:
        engine.shutdown()
    batch = np.ones((len(rows), 56), np.int32)
    for i, r in enumerate(rows):
        batch[i, :len(r)] = r
    best = np.asarray(jnp.argmax(
        ref.forward(params_of(m), cfg, jnp.asarray(batch)), -1)) + 1
    for i, n in enumerate(lengths):
        np.testing.assert_array_equal(rows[i][:n], row[:n])
        np.testing.assert_array_equal(rows[i][n:], best[i, n - 1:n - 1 + new])
    np.testing.assert_array_equal(
        rows[lengths.index(12)],
        np.asarray(m.generate(tokens[:1, :12], new, chunk=CHUNK))[0])
    assert engine.pool.trace_counts["decode"] == 1
    n = stats["decode_dispatches"]
    assert stats["ring_positions_read"] == n * len(RINGS) * 2 * PLACES
    live = stats["ring_positions_live"]
    assert 0 < live <= len(RINGS) * min(
        stats["decode_positions_live"], n * 2 * WINDOW)
    assert live < stats["decode_positions_live"] * len(RINGS)
    assert stats["cache_bytes_window"] \
        == len(RINGS) * 2 * PLACES * (2 * 2 * 16 * 4)
    assert stats["moe_pairs_held"] == stats["moe_pairs_total"] > 0
    assert stats["moe_layer_calls"] % 4 == 0

"""The parallel block of ``falcon_h1`` (grouped-query attention beside a
Mamba-2 mixer on one normed input) at a small size on the CPU, on seeded
weights, against the plain reference in
``benchmark/reference/hybrid_ssm_lm.py`` (loaded by path: it is the one
copy, computes the recurrence one position after another, and imports
nothing of the program): the whole-sequence pass, the chunked scan from a
carried state, prefill then decode through a slot pool that keeps a state
beside keys and values, the reset at admission, idle lanes, the decode
step's update, and the engine end to end.

**The leaves of the recurrence are set here to remember** (``dt`` about
0.02 and ``A`` about -1: a decay of 0.98 a token, a memory of some fifty
tokens): a state that forgot within a few tokens would hide one carried or reset
wrongly before anything compared it."""

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
from reference import hybrid_ssm_lm as ref                    # noqa: E402

from bigdl_tpu.models import falcon_h1, transformer_lm        # noqa: E402
from bigdl_tpu.ops import ssm_kernels                         # noqa: E402
from bigdl_tpu.serving.generation import (                    # noqa: E402
    GenerationScheduler, SlotPool)

CHUNK, MAX_LEN, VOCAB, LAYERS = 8, 64, 50, 2
CFG = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=LAYERS,
           num_attention_heads=4, num_key_value_heads=2, head_dim=8,
           intermediate_size=48, rope_theta=1e4, rms_norm_eps=1e-5,
           mamba_n_heads=4, mamba_d_head=8, mamba_d_ssm=32, mamba_n_groups=2,
           mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=4,
           mamba_conv_bias=True, mamba_rms_norm=True,
           mamba_norm_before_gate=False,
           ssm_multipliers=[0.7, 0.9, 0.6, 1.2, 0.8],
           mlp_multipliers=[0.5, 0.7], embedding_multiplier=2.0,
           lm_head_multiplier=0.5, key_multiplier=0.6,
           attention_in_multiplier=1.1, attention_out_multiplier=0.6,
           ssm_in_multiplier=0.9, ssm_out_multiplier=0.8)
# by the scheduler's rule a prompt no longer than the chunk goes through
# the bucketed prefill and a longer one through chunks, the last padded:
# one token; three; a chunk less one; a chunk; a chunk and one (one whole
# chunk); three chunks and a half (the last chunk a bucket of 4, padded)
PROMPTS = {"one": 1, "three": 3, "chunk-1": CHUNK - 1, "chunk": CHUNK,
           "chunk+1": CHUNK + 1, "3.5-chunks": 3 * CHUNK + CHUNK // 2}


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def params_of(model):
    flat = jax.tree_util.tree_flatten_with_path(model)[0]
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat}


def build(**over):
    """The model on seeded leaves, the recurrence's set to remember."""
    cfg = dict(CFG, **over)
    m = falcon_h1(cfg, MAX_LEN).eval_mode()
    flat, tree = jax.tree_util.tree_flatten_with_path(m)
    key, leaves = jax.random.key(11), []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        if name.endswith("dt_bias"):
            leaf = -4.0 + 0.3 * noise
        elif name.endswith("A_log"):
            leaf = 0.3 * noise
        elif leaf.ndim == 1:
            leaf = (0.1 * noise if name.endswith("bias") else 1 + 0.1 * noise)
        else:
            leaf = noise * leaf.shape[-1] ** -0.5
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(tree, leaves).eval_mode(), cfg


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


TOL = 1e-4


def close(a, b, tol=TOL):
    """Both sides are float32 at ``highest``: what is left is the order
    of the sums (the scan's products within a sub-chunk against one
    position after another, grouped heads against repeated ones), a few
    float32 roundings on logits of order one.  A state kept in bfloat16
    reads fifty times this (``test_a_bfloat16_state_fails_the_tolerance``)."""
    return float(jnp.max(jnp.abs(a - b))) <= tol


# ---- the model against the reference ----------------------------------------

def test_whole_sequence_logits_equal_the_reference(model, tokens, ref_logits):
    m, _ = model
    assert ref_logits.shape == (2, 40, VOCAB)
    assert float(jnp.max(jnp.abs(ref_logits))) > 0.5    # logits of order one
    assert close(m.forward(tokens), ref_logits)


def test_the_recurrence_remembers(model, tokens, ref_logits):
    """The leaves set here carry a token's mark for tens of positions:
    changing the first token moves the logits thirty positions on by more
    than the tolerance through the state alone (attention left out)."""
    m, cfg = model
    muted = dict(cfg, attention_out_multiplier=0.0)
    p = params_of(m)
    other = tokens.at[:, 0].set(tokens[:, 0] % VOCAB + 1)
    a = ref.forward(p, muted, tokens)[:, 30]
    b = ref.forward(p, muted, other)[:, 30]
    assert float(jnp.max(jnp.abs(a - b))) > 10 * TOL


MULTIPLIERS = [(k, None) for k in (
    "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
    "attention_in_multiplier", "attention_out_multiplier",
    "ssm_in_multiplier", "ssm_out_multiplier")] \
    + [("ssm_multipliers", i) for i in range(5)] \
    + [("mlp_multipliers", i) for i in range(2)]


@pytest.mark.parametrize("name,part", MULTIPLIERS, ids=[
    k if i is None else f"{k}[{i}]" for k, i in MULTIPLIERS])
def test_each_multiplier_multiplies_where_the_reference_says(
        tokens, ref_logits, name, part):
    """One multiplier changed, in the program's configuration and in the
    reference's alike: the logits move, and the two still agree."""
    if part is None:
        over = {name: CFG[name] * 1.7}
    else:
        vals = list(CFG[name])
        vals[part] *= 1.7
        over = {name: vals}
    m, cfg = build(**over)
    logits = m.forward(tokens)
    assert close(logits, ref.forward(params_of(m), cfg, tokens))
    assert not close(logits, ref_logits, 20 * TOL)


REFUSED = [("attention_bias", True), ("mlp_bias", True),
           ("projectors_bias", True), ("mamba_proj_bias", True),
           ("rope_scaling", {"type": "linear", "factor": 2.0}),
           ("tie_word_embeddings", True), ("attn_layer_indices", [0]),
           ("mamba_rms_norm", False), ("mamba_norm_before_gate", True),
           ("mamba_conv_bias", False), ("hidden_act", "gelu")]


@pytest.mark.parametrize("key,value", REFUSED, ids=[k for k, _ in REFUSED])
def test_what_is_not_built_is_refused(key, value):
    with pytest.raises(ValueError, match="falcon_h1"):
        falcon_h1(dict(CFG, **{key: value}), MAX_LEN)


def test_the_mixers_three_entries_have_one_meaning(model):
    """``nn.Mamba2Mixer`` alone, on one input: the whole sequence, chunks
    from a carried state (the last padded at its end), and one token a
    row give the same outputs, which are the reference's mixer's, and
    leave the same state."""
    from bigdl_tpu.nn import Mamba2Mixer
    m, cfg = model
    mixer = m.blocks[0].ssm
    assert isinstance(mixer, Mamba2Mixer)
    u = jnp.asarray(np.random.default_rng(3).normal(size=(2, 21, 32)),
                    jnp.float32)
    w = {k[len(".blocks[0].ssm"):]: v for k, v in params_of(m).items()
         if k.startswith(".blocks[0].ssm.")}
    want = ref.mixer(u, w, cfg, lambda a: a)
    whole, end = mixer.forward(u)
    assert close(whole, want, 1e-5)
    state, outs = mixer.init_state(2), []
    for lo in range(0, 21, 8):
        part = u[:, lo:lo + 8]
        real = part.shape[1]
        part = jnp.pad(part, ((0, 0), (0, 8 - real), (0, 0)))
        y, state = mixer.forward(part, state, jnp.arange(8)[None] < jnp.full(
            (2, 1), real))
        outs.append(y[:, :real])
    assert close(jnp.concatenate(outs, axis=1), want, 1e-5)
    stepped, rows = mixer.init_state(2), []
    for t in range(21):
        y, stepped = mixer.step(u[:, t:t + 1], stepped)
        rows.append(y)
    assert close(jnp.concatenate(rows, axis=1), want, 1e-5)
    for other in (state, stepped):
        for name in ("ssm", "conv"):
            np.testing.assert_allclose(np.asarray(other[name]),
                                       np.asarray(end[name]),
                                       rtol=1e-5, atol=1e-6)


# ---- the chunked scan against the plain recurrence --------------------------

def _scan_inputs(t, seed=0, heads=4, p=8, groups=2, n=16):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(1, t, heads, p)), jnp.float32)
    dt = jnp.asarray(r.uniform(0.003, 0.03, size=(1, t, heads)), jnp.float32)
    a = -jnp.asarray(r.uniform(0.5, 2.0, size=(heads,)), jnp.float32)
    b = jnp.asarray(r.normal(size=(1, t, groups, n)), jnp.float32)
    c = jnp.asarray(r.normal(size=(1, t, groups, n)), jnp.float32)
    return x, dt, a, b, c


def _plain(x, dt, a, b, c):
    per = x.shape[2] // b.shape[2]
    return ref.recurrence(x[0], dt[0], a, jnp.repeat(b[0], per, axis=1),
                          jnp.repeat(c[0], per, axis=1))[None]


@pytest.mark.parametrize("first,second,chunk", [
    (13, 19, 8), (8, 8, 8), (5, 27, 4), (1, 31, 16), (21, 11, 128)])
def test_chunked_scan_from_a_carried_state_equals_the_plain_recurrence(
        first, second, chunk):
    """Two calls, the second from the state the first left (not zero, and
    with a memory of hundreds of positions), against one plain scan over
    all the positions; the state left at the end against one call's."""
    x, dt, a, b, c = _scan_inputs(first + second)
    want = _plain(x, dt, a, b, c)
    zero = jnp.zeros((1, 4, 16, 8), jnp.float32)
    cut = lambda arr, lo, hi: arr[:, lo:hi]                   # noqa: E731
    y1, s1 = ssm_kernels.ssm_chunk_scan(
        cut(x, 0, first), cut(dt, 0, first), a, cut(b, 0, first),
        cut(c, 0, first), zero, chunk)
    assert float(jnp.max(jnp.abs(s1))) > 0.01
    y2, s2 = ssm_kernels.ssm_chunk_scan(
        cut(x, first, None), cut(dt, first, None), a, cut(b, first, None),
        cut(c, first, None), s1, chunk)
    got = jnp.concatenate([y1, y2], axis=1)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale
    _, whole = ssm_kernels.ssm_chunk_scan(x, dt, a, b, c, zero, chunk)
    assert float(jnp.max(jnp.abs(s2 - whole))) \
        <= 1e-5 * float(jnp.max(jnp.abs(whole)))


def test_a_padded_position_advances_no_state():
    """``dt`` 0 at the trailing positions: the state after them is the
    state after the last real one, bit for bit what a call without them
    leaves when they fall in a sub-chunk of their own."""
    x, dt, a, b, c = _scan_inputs(16)
    zero = jnp.zeros((1, 4, 16, 8), jnp.float32)
    _, real = ssm_kernels.ssm_chunk_scan(
        x[:, :8], dt[:, :8], a, b[:, :8], c[:, :8], zero, 8)
    _, padded = ssm_kernels.ssm_chunk_scan(
        x, dt.at[:, 8:].set(0.0), a, b, c, zero, 8)
    np.testing.assert_array_equal(np.asarray(real), np.asarray(padded))
    _, inside = ssm_kernels.ssm_chunk_scan(
        x[:, :12], dt[:, :12].at[:, 8:].set(0.0), a, b[:, :12], c[:, :12],
        zero, 16)
    np.testing.assert_allclose(np.asarray(inside), np.asarray(real),
                               rtol=1e-5, atol=1e-6)


# ---- the decode step's update against the chunked scan ---------------------

@pytest.mark.parametrize("heads,groups", [(16, 2), (32, 2), (8, 1)])
def test_state_step_equals_a_scan_of_one_position(heads, groups):
    """The new state and ``y`` of one step against the chunked scan over
    that one position from the same state, and a row with decay 1 and no
    input keeps its state bit for bit (how an idle lane is left alone)."""
    rows, n, p = 3, 16, 128
    r = np.random.default_rng(1)
    state = jnp.asarray(r.normal(size=(rows, heads, n, p)), jnp.float32)
    dt = jnp.asarray(r.uniform(0.01, 0.5, size=(rows, heads)), jnp.float32)
    a = -jnp.asarray(r.uniform(1.0, 4.0, size=(heads,)), jnp.float32)
    x = jnp.asarray(r.normal(size=(rows, heads, p)), jnp.float32)
    b = jnp.asarray(r.normal(size=(rows, groups, n)), jnp.float32)
    c = jnp.asarray(r.normal(size=(rows, groups, n)), jnp.float32)
    dt = dt.at[1].set(0.0)
    got_s, got_y = ssm_kernels.ssm_state_step(
        state, jnp.exp(dt * a), dt[..., None] * x, b, c)
    want_y, want_s = ssm_kernels.ssm_chunk_scan(
        x[:, None], dt[:, None], a, b[:, None], c[:, None], state)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y[:, 0]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got_s[1]), np.asarray(state[1]))


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
    """The slot pool's own programs (the bucketed prefill and its scatter,
    or chunks from a carried state with the last one padded), then pooled
    decode steps, against the reference's one forward pass."""
    m, _ = model
    pool = SlotPool(m, slots=3, prefill_batch=1, ring_margin=CHUNK)
    assert pool.has_state and not pool.has_ring and pool.state_layers == 2
    slot, row = 1, np.asarray(tokens[0])
    _pool_prefill(pool, row[:n_prompt], slot, chunks_only)
    _decode_check(pool, slot, row, n_prompt, ref_logits[0])


@pytest.mark.parametrize("second", ["one", "three", "chunk+1", "3.5-chunks"])
def test_a_slots_second_occupant_equals_a_fresh_pools_first(
        model, tokens, ref_logits, second):
    """A slot that held a sequence takes another: whichever program is the
    newcomer's first (the first chunk, the scatter, or a one-token
    prompt's first step) starts the state from zeros, so its logits are
    the reference's for it alone, as a fresh pool's are."""
    m, _ = model
    pool = SlotPool(m, slots=2, prefill_batch=1, ring_margin=CHUNK)
    first, row = np.asarray(tokens[1]), np.asarray(tokens[0])
    _pool_prefill(pool, first[:20], 0)
    pool.caches = _decode_check(pool, 0, first, 20, ref_logits[1], steps=6)
    held = pool.caches["layers"][0]["ssm"]["ssm"][0]
    assert float(jnp.max(jnp.abs(held))) > 0.01     # something to forget
    n = PROMPTS[second]
    _pool_prefill(pool, row[:n], 0)
    _decode_check(pool, 0, row, n, ref_logits[0], steps=12)


def test_a_slot_prefilled_between_others_decode_steps_equals_it_alone(
        model, tokens, ref_logits):
    """Slot 1's chunks go in between pooled decode steps of slot 0, in
    which slot 1 rides as an idle lane: an idle lane keeps its state (and
    its convolution's inputs) as they were, so slot 1 decodes what it
    decodes alone; slot 0 is not disturbed either."""
    m, _ = model
    pool = SlotPool(m, slots=2, prefill_batch=1, ring_margin=CHUNK)
    row0, row1 = np.asarray(tokens[0]), np.asarray(tokens[1])
    _pool_prefill(pool, row0[:5], 0)
    n1, end, pos, t0 = 3 * CHUNK + 3, 3 * CHUNK + 2, 0, 4
    active = jnp.asarray([True, False])
    while pos < end:
        w = CHUNK if end - pos >= CHUNK else 1 << (end - pos - 1).bit_length()
        toks = np.zeros(w, np.int32)
        toks[:min(w, end - pos)] = row1[pos:min(pos + w, end)]
        pool.chunk_prefill_into(toks, 1, pos)
        pos += w
        for _ in range(2):      # slot 0 decodes; slot 1 rides along
            before = pool.caches["layers"][1]["ssm"]
            logits, pool.caches, _ = pool.model.decode_step(
                jnp.asarray([[row0[t0]], [0]], jnp.int32),
                jnp.asarray([t0, 0], jnp.int32), pool.caches, active=active)
            assert close(logits[0], ref_logits[0, t0]), t0
            after = pool.caches["layers"][1]["ssm"]
            for name in ("ssm", "conv"):
                np.testing.assert_array_equal(np.asarray(before[name][1]),
                                              np.asarray(after[name][1]))
            t0 += 1
    _decode_check(pool, 1, row1, n1, ref_logits[1])


def test_a_bfloat16_state_fails_the_tolerance(model, tokens, ref_logits):
    """The same path with the recurrence's state rounded to bfloat16
    after every program, as a pool that kept it in bfloat16 would: the
    logits leave the reference's by well over the tolerance every other
    test holds them to."""
    m, _ = model
    pool = SlotPool(m, slots=1, prefill_batch=1, ring_margin=CHUNK)
    row, n = np.asarray(tokens[0]), 3 * CHUNK + 4

    def rounded(caches):
        layers = [dict(layer, ssm=dict(
            layer["ssm"], ssm=layer["ssm"]["ssm"].astype(
                jnp.bfloat16).astype(jnp.float32)))
            for layer in caches["layers"]]
        return dict(caches, layers=layers)
    _pool_prefill(pool, row[:n], 0)
    caches, worst = rounded(pool.caches), 0.0
    for t in range(n - 1, len(row)):
        logits, caches, _ = pool.model.decode_step(
            jnp.asarray([[row[t]]], jnp.int32), jnp.asarray([t], jnp.int32),
            caches, active=jnp.asarray([True]))
        caches = rounded(caches)
        worst = max(worst, float(jnp.max(jnp.abs(logits[0] - ref_logits[0, t]))))
    assert worst > 5 * TOL, worst


def test_the_pool_declares_and_counts_a_state_beside_the_rows(model):
    m, cfg = model
    pool = SlotPool(m, slots=3, dtype=jnp.bfloat16, ring_margin=CHUNK)
    layer = {"self": ("full", MAX_LEN), "ssm": ("state", None)}
    assert pool.cache_layers == (layer,) * LAYERS
    state = pool.caches["layers"][0]["ssm"]
    assert state["ssm"].shape == (3, 4, 16, 8)          # [rows, heads, N, P]
    assert state["ssm"].dtype == jnp.float32            # whatever the pool's
    assert state["conv"].shape == (3, 3, 32 + 2 * 2 * 16)
    assert state["conv"].dtype == jnp.bfloat16
    by_kind = pool.cache_nbytes_by_kind()
    assert by_kind["ring"] == 0
    assert by_kind["state"] == LAYERS * 3 * (4 * 16 * 8 * 4 + 3 * 96 * 2)
    assert by_kind["full"] == LAYERS * 3 * 2 * 2 * MAX_LEN * 8 * 2
    assert sum(by_kind.values()) + pool.caches["pad"].size \
        == pool.cache_nbytes()
    # the rows a slot and leaf (no row-write kernel on a CPU), the flags'
    # select, and two writers a state
    assert pool.cache_write_programs == 1 + LAYERS * (2 * 3 + 2)
    opt = SlotPool(transformer_lm(vocab_size=30, hidden_size=16, num_layers=2,
                                  num_heads=2, filter_size=32, max_len=32),
                   slots=2)
    assert not opt.has_state and opt.state_layers == 0
    assert opt.cache_nbytes_by_kind()["state"] == 0


def test_keys_by_position_are_not_offered_beside_a_state(model):
    m, _ = model
    pool = SlotPool(m, slots=2, ring_margin=CHUNK)
    with pytest.raises(ValueError, match="no positions"):
        pool.kv_extract(0, 0, 4)
    with pytest.raises(ValueError, match="no positions"):
        pool.kv_copy_into(0, [])
    with pytest.raises(ValueError, match="prefix cache.*state"):
        GenerationScheduler(m, slots=2, prefill_chunk=CHUNK,
                            prefix_cache_bytes=1 << 20, start=False)


# ---- the engine end to end ---------------------------------------------------

def test_engine_serves_mixed_lengths_greedily(model, tokens):
    """Requests of every prefill route through ``GenerationScheduler``
    (two slots, so slots are reused and prefills ride between decode
    steps): every emitted token is the reference's best at its position
    given what came before (one forward pass of the reference over all
    the rows: it is causal), and one row is the model's own
    ``generate()``; one decode program, chunk programs keyed by width
    alone, the counters of the state layers in ``stats()``."""
    m, cfg = model
    engine = GenerationScheduler(m, slots=2, prefill_chunk=CHUNK,
                                 prefill_batch=1)
    lengths, new = [1, 3, 7, 8, 9, 12, 20, 28, 33], 6
    row = np.asarray(tokens[0])
    try:
        futs = [engine.submit_async(row[:n], new) for n in lengths]
        rows = [np.asarray(fut.result(120)) for fut in futs]
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
    counts = engine.pool.trace_counts
    assert counts["decode"] == 1
    assert set(counts["chunk_prefill"]) <= {1, 2, 4, 8}
    assert all(v == 1 for v in counts["chunk_prefill"].values())
    assert not counts["kv_copy"] and not counts["kv_extract"]
    assert stats["state_resets"] == len(lengths)
    real = sum(n - 1 for n in lengths)
    assert stats["ssm_scan_positions_real"] == LAYERS * real
    assert stats["ssm_scan_positions"] > stats["ssm_scan_positions_real"]
    # 9 -> one chunk; 12 -> 8 + 4 (3 real); 20 -> 8 + 8 + 4 (3); 28 -> 3 x 8
    # + 4 (3); 33 -> 4 x 8; the bucketed 3, 7, 8 -> 2, 6 (of 7), 7
    assert stats["ssm_scan_positions"] == LAYERS * (
        8 + 12 + 20 + 28 + 32 + 3 + 7 + 7)
    assert stats["ssm_layer_calls"] == LAYERS * (
        stats["decode_dispatches"] + stats["prefill_calls"])
    assert stats["cache_bytes_state"] \
        == engine.pool.cache_nbytes_by_kind()["state"] > 0
    assert stats["cache_bytes_window"] == 0


def test_a_padded_last_chunk_rides_the_step_of_its_own_slots_first_token(
        model, tokens):
    """B's prompt of three chunks and a half arrives while A decodes: its
    four chunks ride decode steps, the last padded at its end, and in
    that pass B's slot decodes its first token from the state the chunk
    left; C's prompt, a chunk and one, follows through the slot A or B
    leaves.  The rows are ``generate()``'s."""
    m, _ = model
    row = np.asarray(tokens[1])
    a, b, c = row[:5], row[:3 * CHUNK + CHUNK // 2], row[:CHUNK + 1]
    engine = GenerationScheduler(m, slots=2, prefill_chunk=CHUNK,
                                 prefill_batch=1, start=False)
    log = joint_pass.logged_pool_calls(engine.pool)
    engine.start()
    try:
        rows = joint_pass.serve_beside_a_decoding_slot(
            engine, a, [b, c], new_first=12, new_later=8)
        engine.shutdown()
        stats = engine.stats()
    finally:
        engine.shutdown()
    for p, got, new in zip((a, b, c), rows, (12, 8, 8)):
        want = np.asarray(m.generate(jnp.asarray(p)[None], new, chunk=CHUNK))
        np.testing.assert_array_equal(got, want[0])
    # B's 27 positions: 8, 8, 8 and 4 (3 real); C's 8: one chunk; A's 4
    # through the bucket of 8
    assert stats["chunks_joint"] + stats["chunks_alone"] == 5
    assert stats["chunks_joint"] == log.count("step+chunk") >= 4
    assert stats["ssm_scan_positions"] == LAYERS * (28 + 8 + 7)
    assert stats["ssm_scan_positions_real"] == LAYERS * (27 + 8 + 4)
    assert stats["ssm_layer_calls"] == LAYERS * (
        stats["decode_dispatches"] + stats["prefill_calls"])


def test_a_short_remainder_rides_the_narrowest_width_padded(model, tokens):
    """A pool with the joint program keeps four chunk widths: with chunks
    of 16, a remainder of one or three positions rides the width of 2 or
    4, padded at its end, and the state holds every real position once
    (the rows are ``generate()``'s); the scans' padding is counted."""
    m, _ = model
    engine = GenerationScheduler(m, slots=2, prefill_chunk=16,
                                 prefill_batch=1)
    assert engine.pool.chunk_widths == (2, 4, 8, 16)
    row = np.asarray(tokens[0])
    try:
        rows = [engine.submit_async(row[:n], 5).result(timeout=300)
                for n in (18, 20)]
        stats = engine.stats()
    finally:
        engine.shutdown()
    for n, got in zip((18, 20), rows):
        want = np.asarray(m.generate(tokens[:1, :n], 5, chunk=16))
        np.testing.assert_array_equal(got, want[0])
    # 17 positions: 16 + 2 (1 real); 19: 16 + 4 (3 real)
    assert stats["ssm_scan_positions"] == LAYERS * (18 + 20)
    assert stats["ssm_scan_positions_real"] == LAYERS * (17 + 19)


def test_a_model_without_state_counts_none():
    lm = transformer_lm(vocab_size=30, hidden_size=16, num_layers=1,
                        num_heads=2, filter_size=32, max_len=32).eval_mode()
    engine = GenerationScheduler(lm, slots=2, prefill_chunk=4)
    try:
        engine.submit_async(np.arange(1, 11, dtype=np.int32), 3).result(60)
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert stats["cache_bytes_state"] == 0
    assert [stats[k] for k in ("ssm_layer_calls", "ssm_scan_positions",
                               "ssm_scan_positions_real", "state_resets")] \
        == [0, 0, 0, 0]

"""Latent attention (one compressed row a place shared by every head, a
rotary key kept apart, absorbed at decode) and the expert layer with a
shared expert and a scaling factor (``models.sarvam_mla``), at a small
size on the CPU, in float32, on seeded weights, against the plain
reference in ``benchmark/reference/latent_moe_lm.py`` (loaded by path: it
is the one copy, it computes the expanded form only and imports nothing of
the program): the whole forward, the absorbed step against the expanded
form, chunks then steps through both of the pool's prefill routes, YaRN's
frequencies against hand-worked values, a slot's second occupant, the
pool's declaration and bytes, what the builder refuses, the share test,
and the engine end to end; and the chunk's kernel
(``ops.latent_chunk_attention``, what a TPU process chooses for a chunk:
interpreted here) against the loop over slices on the same leaves, and
through the model."""

import functools
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import joint_pass                                             # noqa: E402
from reference import latent_moe_lm as ref                    # noqa: E402

from bigdl_tpu.models import mimo_v2, sarvam_mla              # noqa: E402
from bigdl_tpu.models.hybrid_decoder import GatedFFN          # noqa: E402
from bigdl_tpu.nn import attention as att                     # noqa: E402
from bigdl_tpu.nn.latent_attention import (                    # noqa: E402
    LatentAttention, latent_rows_attention)
from bigdl_tpu.nn.moe import HeldExperts                      # noqa: E402
from bigdl_tpu.ops import attention_kernels                   # noqa: E402
from bigdl_tpu.serving.generation import (                    # noqa: E402
    GenerationScheduler, SlotPool)

CHUNK, MAX_LEN, VOCAB, LAYERS = 4, 64, 50, 3
YARN = dict(type="deepseek_yarn", factor=40, beta_fast=32, beta_slow=1,
            mscale=1, mscale_all_dim=1, original_max_position_embeddings=16)
# layer 0 dense, 1 and 2 expert layers with a shared expert; a rotary part
# of four pairs of which YaRN keeps the first and stretches the rest
CFG = dict(vocab_size=VOCAB, hidden_size=64, num_hidden_layers=LAYERS,
           num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
           q_head_dim=24, v_head_dim=16, kv_lora_rank=32, head_dim=40,
           intermediate_size=96, moe_intermediate_size=24, num_experts=8,
           num_experts_per_tok=2, num_shared_experts=1,
           routed_scaling_factor=2.5, first_k_dense_replace=1,
           moe_router_enable_expert_bias=True, use_qk_norm=True,
           rms_norm_eps=1e-6, rope_theta=10000, rope_scaling=YARN,
           tie_word_embeddings=False, experts_held=8, experts_offset=0)
# the bucketed route (no longer than a chunk and one), the chunked route
# ending on a chunk's edge and ending mid-chunk
PROMPTS = {"bucketed": 5, "chunks": 13, "mid-chunk": 18}


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def params_of(model):
    flat = jax.tree_util.tree_flatten_with_path(model)[0]
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat}


def build(max_len=MAX_LEN, **over):
    """The model with a latent gain and a selection bias that are not
    what the program initialises them to (one and zero; the benchmark
    seeds them)."""
    cfg = dict(CFG, **over)
    m = sarvam_mla(cfg, max_len).eval_mode()
    key = jax.random.key(11)
    for i, blk in enumerate(m.blocks):
        blk.attn.kv_norm.weight = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), (cfg["kv_lora_rank"],))
        if blk.sparse:
            blk.ffn.router.bias = 0.05 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), (cfg["num_experts"],))
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


@pytest.fixture
def chunk_kernel(monkeypatch):
    """What a TPU process chooses for a chunk whose leaves tile, asked
    for here at the one place ``LatentAttention.forward`` asks, so the
    kernel runs interpreted (a single token at a scalar position tiles
    nothing and keeps the loop); the list gathers the queries' shapes the
    kernel took."""
    asked = []

    def takes(q_shape, *args, _takes=attention_kernels.latent_chunk_takes):
        try:
            _takes(q_shape, *args, force="kernel")
        except ValueError:
            return False
        asked.append(tuple(q_shape))
        return True

    monkeypatch.setattr(attention_kernels, "latent_chunk_takes", takes)
    return asked


@pytest.fixture(scope="module")
def tiling_model():
    """Rows the chunk kernel takes: a latent of 128 (whole lane tiles),
    a rotary key of 8, 384 places."""
    with jax.default_matmul_precision("highest"):
        return build(384, kv_lora_rank=128, head_dim=136)


def close(a, b, tol=1e-4):
    """Both sides are float32 at ``highest``: what is left is the order
    of the sums (absorbed against expanded, blocks of keys against a
    whole row), a few float32 roundings on logits of order one."""
    return float(jnp.max(jnp.abs(a - b))) <= tol


# ---- the model against the reference ----------------------------------------

def test_whole_forward_equals_the_reference(model, tokens, ref_logits):
    m, cfg = model
    assert [type(blk.attn) for blk in m.blocks] == [LatentAttention] * LAYERS
    assert [blk.sparse for blk in m.blocks] == [False, True, True]
    assert [blk.sparse for blk in m.blocks] \
        == [ref.is_sparse(cfg, i) for i in range(LAYERS)]
    assert ref_logits.shape == (2, 40, VOCAB)
    assert float(jnp.max(jnp.abs(ref_logits))) > 0.5
    assert close(m.forward(tokens), ref_logits)


def test_a_held_share_equals_the_reference_given_the_same_share(tokens):
    m, cfg = build(experts_held=3, experts_offset=2)
    ffn = m.blocks[1].ffn
    assert ffn.w_gate.shape == (3, 64, 24)
    assert ffn.router.weight.shape == (8, 64)
    assert ffn.shared.gate.weight.shape == (24, 64)
    assert close(m.forward(tokens), ref.forward(params_of(m), cfg, tokens))


def test_the_absorbed_step_equals_the_expanded_form(model):
    """One layer alone: a sequence through the expanded form (no cache),
    then the same sequence a token at a time through the absorbed step
    against the row the steps wrote; the row holds the normed latent and
    the rotated rotary key, one head of each."""
    m, _ = model
    attn = m.blocks[1].attn
    x = jax.random.normal(jax.random.key(5), (2, 12, 64))
    whole, compact = attn.forward(x)
    assert compact["k"].shape == (2, 1, 12, 8)
    assert compact["v"].shape == (2, 1, 12, 32)
    cache = attn.init_cache(2, MAX_LEN)
    assert {n: leaf.shape for n, leaf in cache.items()} \
        == {"k": (2, 1, MAX_LEN, 8), "v": (2, 1, MAX_LEN, 32)}
    for t in range(12):
        y, cache = attn.forward(x[:, t:t + 1], jnp.full((2,), t, jnp.int32),
                                cache)
        assert close(y[:, 0], whole[:, t], 1e-5), t
    for n in ("k", "v"):
        np.testing.assert_allclose(cache[n][:, :, :12], compact[n],
                                   atol=1e-6)
    # the cached latent is normed: unit mean square before the gain
    c = cache["v"][:, 0, :12] / attn.kv_norm.weight
    np.testing.assert_allclose(jnp.mean(jnp.square(c), axis=-1), 1.0,
                               atol=1e-3)


@pytest.mark.parametrize("n_prompt", list(PROMPTS.values()),
                         ids=list(PROMPTS))
@pytest.mark.parametrize("per_row", [False, True],
                         ids=["scalar-index", "index-per-row"])
def test_chunk_after_chunk_then_steps_equal_one_whole_forward(
        model, tokens, ref_logits, n_prompt, per_row):
    m, _ = model
    caches = m.init_cache(2)
    assert [sorted(c["self"]) for c in caches["layers"]] == [["k", "v"]] * 3
    for s in range(0, n_prompt - 1, CHUNK):
        caches, _ = m.prefill_chunk(
            tokens[:, s:min(s + CHUNK, n_prompt - 1)], s, caches)
    for t in range(n_prompt - 1, tokens.shape[1]):
        index = jnp.full((2,), t, jnp.int32) if per_row else jnp.int32(t)
        logits, caches, _ = m.decode_step(tokens[:, t:t + 1], index, caches)
        assert close(logits, ref_logits[:, t]), t


def test_a_chunk_attends_key_blocks_past_the_first(monkeypatch):
    """The chunk's attention walks the row in blocks of keys up to the
    last query's place: with a block of 8 places a chunk at position 40
    crosses six of them, and the logits are still the whole forward's."""
    from bigdl_tpu.nn import latent_attention
    monkeypatch.setattr(latent_attention, "CHUNK_KEY_BLOCK", 8)
    m, cfg = build()
    toks = jnp.asarray(np.random.default_rng(2).integers(
        1, VOCAB + 1, (1, 48)), jnp.int32)
    want = ref.forward(params_of(m), cfg, toks)
    caches = m.init_cache(1)
    for s in range(0, 44, CHUNK):
        caches, _ = m.prefill_chunk(toks[:, s:s + CHUNK], s, caches)
    for t in range(44, 48):
        logits, caches, _ = m.decode_step(toks[:, t:t + 1], jnp.int32(t),
                                          caches)
        assert close(logits, want[:, t]), t


def test_a_chunk_attends_key_blocks_past_the_first_through_the_kernel(
        monkeypatch, tiling_model, chunk_kernel):
    """The same through the chunk kernel: blocks of 128 places, chunks of
    32 tokens up to position 288, the last of which crosses three blocks
    (and skips none of the row's three); the steps that follow read the
    rows the chunks wrote."""
    from bigdl_tpu.nn import latent_attention
    monkeypatch.setattr(latent_attention, "CHUNK_KEY_BLOCK", 128)
    m, cfg = tiling_model
    toks = jnp.asarray(np.random.default_rng(2).integers(
        1, VOCAB + 1, (1, 292)), jnp.int32)
    want = ref.forward(params_of(m), cfg, toks)
    caches = m.init_cache(1)
    for s in range(0, 288, 32):
        caches, _ = m.prefill_chunk(toks[:, s:s + 32], s, caches)
    assert chunk_kernel == [(1, 4, 32, 128)] * (9 * LAYERS)
    for t in range(288, 292):
        logits, caches, _ = m.decode_step(toks[:, t:t + 1], jnp.int32(t),
                                          caches)
        assert close(logits, want[:, t]), t


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


@pytest.mark.parametrize("scenario", joint_pass.SCENARIOS)
def test_the_joint_pass_equals_the_chunk_program_then_the_step_through_the_kernel(
        monkeypatch, tiling_model, chunk_kernel, scenario):
    """The same where the chunk attends through the kernel, in the joint
    walk and in the chunk program alike (blocks of 128: the chunk's
    places lie in the first and the steps past it skip their work)."""
    from bigdl_tpu.nn import latent_attention
    monkeypatch.setattr(latent_attention, "CHUNK_KEY_BLOCK", 128)
    m, _ = tiling_model
    joint_pass.assert_joint_pass_equals_chunk_then_step(
        m, CHUNK, VOCAB, scenario)
    assert chunk_kernel and set(chunk_kernel) == {(1, 4, CHUNK, 128)}


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


def _teacher_forced(pool, row, slot, first, last, want):
    active = jnp.arange(pool.slots) == slot
    caches = pool.caches
    for t in range(first, last):
        tok = jnp.where(active, int(row[t]), 0).astype(jnp.int32)[:, None]
        logits, caches, _ = pool.model.decode_step(
            tok, jnp.where(active, t, 0).astype(jnp.int32), caches,
            active=active)
        assert close(logits[slot], want[t]), t
    return caches


@pytest.mark.parametrize("n_prompt", list(PROMPTS.values()),
                         ids=list(PROMPTS))
def test_both_pool_routes_then_pooled_steps_equal_the_reference(
        model, tokens, ref_logits, n_prompt):
    m, _ = model
    pool = SlotPool(m, slots=3)
    row = np.asarray(tokens[0])
    _pool_prefill(pool, row[:n_prompt], 1)
    _teacher_forced(pool, row, 1, n_prompt - 1, len(row), ref_logits[0])


def test_a_slots_second_occupant_reads_nothing_of_the_first(
        model, tokens, ref_logits):
    """A long sequence fills a slot's row; a shorter one then takes the
    slot, by each route: what the first left beyond the second's places
    is never read."""
    m, _ = model
    pool = SlotPool(m, slots=2)
    first, second = np.asarray(tokens[0]), np.asarray(tokens[1])
    _pool_prefill(pool, first[:30], 0)
    pool.caches = _teacher_forced(pool, first, 0, 29, 40, ref_logits[0])
    for n_prompt in PROMPTS.values():
        _pool_prefill(pool, second[:n_prompt], 0)
        _teacher_forced(pool, second, 0, n_prompt - 1, 28, ref_logits[1])


# ---- the chunk's kernel against the loop --------------------------------------

# (heads, width, places, row, index, places flagged from 0, dtype): a block
# is 128 places; the row is one of three
CHUNK_CASES = {
    "position-0": (8, 256, 1024, 1, 0, 0, jnp.float32),
    "inside-the-first-block": (8, 256, 1024, 2, 37, 0, jnp.float32),
    "on-a-blocks-boundary-in-slot-0": (8, 256, 1024, 0, 256, 0, jnp.float32),
    "ending-on-the-rows-last-place": (8, 256, 1024, 2, 768, 0, jnp.float32),
    "padding-before-the-first-token": (8, 256, 1024, 1, 128, 140,
                                       jnp.float32),
    "width-32-ending-on-the-last-place": (64, 32, 512, 1, 480, 0,
                                          jnp.float32),
    "width-32-padded-in-the-first-block": (64, 32, 512, 2, 100, 107,
                                           jnp.float32),
    "bfloat16-rows": (8, 256, 1024, 1, 700, 0, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_the_chunk_kernel_equals_the_loop_over_slices(case):
    """``ops.latent_chunk_attention`` (interpreted) against
    ``latent_rows_attention`` on the same leaves: the same mathematics in
    another order of summation, so float32 to a few roundings (and
    bfloat16 rows to half a bfloat16 step of the result, which the kernel
    rounds to the queries' dtype).  Every place past the chunk's last
    block holds NaN: neither form reads it.  A query all of whose places
    are flagged (padding before its row's first token) averages the
    blocks read, in both."""
    h, w, t, row, index, flagged, dtype = CHUNK_CASES[case]
    r, dr, block = 128, 8 if dtype == jnp.float32 else 16, 128
    keys = jax.random.split(jax.random.key(len(case)), 4)
    ql = jax.random.normal(keys[0], (1, h, w, r), dtype)
    qr = jax.random.normal(keys[1], (1, h, w, dr), dtype)
    live = -(-(index + w) // block) * block
    dead = (jnp.arange(t) >= live)[None, None, :, None]
    latent = jnp.where(dead, jnp.nan, jax.random.normal(
        keys[2], (3, 1, t, r))).astype(dtype)
    rotary = jnp.where(dead, jnp.nan, jax.random.normal(
        keys[3], (3, 1, t, dr))).astype(dtype)
    pad = (jnp.arange(t) < flagged)[None] if flagged else None
    assert attention_kernels.latent_chunk_takes(
        ql.shape, latent.shape, rotary.shape, dtype, block, force="kernel")
    assert not attention_kernels.latent_chunk_takes(
        ql.shape, latent.shape, rotary.shape, dtype, block)   # not a TPU
    got = attention_kernels.latent_chunk_attention(
        ql, qr, latent, rotary, row, jnp.int32(index), pad, scale=0.11,
        block=block, interpret=True)
    want = latent_rows_attention(
        ql, qr, latent, rotary, row,
        (index + jnp.arange(w, dtype=jnp.int32))[None], pad, 0.11, block)
    assert got.shape == (1, h, w, r) and got.dtype == dtype
    assert bool(jnp.all(jnp.isfinite(want)))
    assert close(got.astype(jnp.float32), want,
                 2e-6 if dtype == jnp.float32 else 8e-3)


@pytest.mark.parametrize("why,q,latent,rotary,dtype,block", [
    ("a latent that is no whole lane tile", (1, 4, 32, 32), (3, 1, 384, 32),
     (3, 1, 384, 8), jnp.float32, 128),
    ("a block that is no whole lane tile", (1, 4, 32, 128), (3, 1, 384, 128),
     (3, 1, 384, 8), jnp.float32, 64),
    ("a row the block does not divide", (1, 4, 32, 128), (3, 1, 320, 128),
     (3, 1, 320, 8), jnp.float32, 128),
    ("query rows that fill no sublane tile", (1, 2, 2, 128), (3, 1, 384, 128),
     (3, 1, 384, 8), jnp.float32, 128),
    ("a width that does not divide the rows of a product", (1, 8, 96, 128),
     (3, 1, 384, 128), (3, 1, 384, 8), jnp.float32, 128),
    ("a rotary part that lies width-minor", (1, 4, 32, 128),
     (3, 1, 384, 128), (3, 1, 384, 128), jnp.float32, 128),
    ("a tile past the kernel's VMEM (the compiler refuses it on a v5e)",
     (1, 64, 256, 512), (3, 1, 1024, 512), (3, 1, 1024, 64), jnp.float32,
     512),
])
def test_the_chunk_kernel_is_refused_where_the_leaves_do_not_tile(
        why, q, latent, rotary, dtype, block):
    with pytest.raises(ValueError, match="do not tile"):
        attention_kernels.latent_chunk_takes(q, latent, rotary, dtype, block,
                                             force="kernel")
    assert not attention_kernels.latent_chunk_takes(
        q, latent, rotary, dtype, block, force="loop")


# ---- rotary ------------------------------------------------------------------

def test_yarn_frequencies_are_the_hand_worked_ones():
    """sarvam-105b's ``rope_scaling``: 64 dims at base 10,000, factor 40
    over 4,096 original positions.  The pairs that make 32 and 1 turns in
    4,096 positions are 10.47 and 22.51: pairs 0-10 keep their frequency,
    pairs 23-31 take a fortieth, the ones between a linear blend."""
    f = np.asarray(att.yarn_frequencies(64, 10000.0, 40.0, 4096, 32.0, 1.0))
    assert f.shape == (32,)
    plain = [10000.0 ** (-j / 32) for j in range(32)]
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-5)
    np.testing.assert_allclose(f[23:], [p / 40 for p in plain[23:]],
                               rtol=1e-5)
    # by hand: pair 0 turns a radian a position; pair 10 0.056234; pair 16
    # is 6/13 of the way: 0.01 * (7/13 + 6/13/40) = 0.0055; pair 31
    # 1.3335e-4 / 40
    np.testing.assert_allclose(
        f[[0, 10, 16, 31]], [1.0, 0.056234, 0.0055, 3.33376e-6], rtol=1e-4)
    m = 0.1 * math.log(40) + 1
    assert att.yarn_mscale(40.0, 1.0) == pytest.approx(m) \
        == pytest.approx(1.36889, rel=1e-5)
    assert att.yarn_mscale(1.0, 1.0) == 1.0 == att.yarn_mscale(40.0, 0.0)
    layer = LatentAttention(64, 4, 128, 64, 128, 32, rope_scaling=dict(
        YARN, original_max_position_embeddings=4096))
    assert layer.scale == pytest.approx(192 ** -0.5 * m * m) \
        == pytest.approx(0.135234, rel=1e-5)
    assert layer.rotary_magnitude == pytest.approx(1.0)
    assert LatentAttention(64, 4, 128, 64, 128, 32).scale \
        == pytest.approx(192 ** -0.5)


def test_rotary_pairs_turn_neighbouring_dims():
    x = jnp.arange(8, dtype=jnp.float32).reshape(1, 1, 8) + 1.0
    freq = jnp.asarray([1.0, 0.5, 0.25, 0.125])
    out = np.asarray(att.rotary_pairs(x, jnp.asarray([[2]]), freq, 1.5))[0, 0]
    for j in range(4):
        a, b, ang = 2 * j + 1.0, 2 * j + 2.0, 2 * float(freq[j])
        np.testing.assert_allclose(
            out[2 * j:2 * j + 2],
            [1.5 * (a * math.cos(ang) - b * math.sin(ang)),
             1.5 * (b * math.cos(ang) + a * math.sin(ang))], rtol=1e-5)
    at_zero = att.rotary_pairs(x, jnp.asarray([[0]]), freq)
    np.testing.assert_allclose(at_zero, x)


def test_the_rotary_key_is_one_for_all_heads_and_the_score_needs_it(model):
    """Leaving the rotary part out of the score, or the norm off the
    compressed row, is another model: both change a layer's output by
    more than any rounding."""
    m, _ = model
    attn = m.blocks[0].attn
    x = jax.random.normal(jax.random.key(6), (1, 10, 64))
    whole, _ = attn.forward(x)
    cache = attn.init_cache(1, MAX_LEN)
    for t in range(10):
        _, cache = attn.forward(x[:, t:t + 1], jnp.full((1,), t, jnp.int32),
                                cache)
    no_rotary = dict(cache, k=jnp.zeros_like(cache["k"]))
    y, _ = attn.forward(x[:, 9:10], jnp.int32(9), no_rotary)
    assert float(jnp.max(jnp.abs(y[:, 0] - whole[:, 9]))) > 1e-2


# ---- the builder -------------------------------------------------------------

@pytest.mark.parametrize("key,value,says", [
    ("q_lora_rank", 1536, "q_lora_rank"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("attention_bias", True, "attention_bias"),
    ("rope_scaling", dict(YARN, type="linear"), "deepseek_yarn"),
    ("first_k_dense_replace", 3, "leading dense"),
    ("n_group", 8, "one group"),
    ("moe_router_enable_expert_bias", False, "selection bias"),
    ("use_qk_norm", False, "use_qk_norm"),
    ("hidden_act", "gelu", "silu"),
    ("q_head_dim", 32, "q_head_dim"),
])
def test_the_builder_refuses_what_it_does_not_build(key, value, says):
    with pytest.raises(ValueError, match=says):
        sarvam_mla(dict(CFG, **{key: value}), MAX_LEN)


def test_mimo_v2_keeps_refusing_both_for_its_own_config():
    from tests.test_hybrid_decoder import CFG as MIMO
    with pytest.raises(ValueError, match="shared experts"):
        mimo_v2(dict(MIMO, n_shared_experts=1), MAX_LEN)
    with pytest.raises(ValueError, match="routed_scaling_factor"):
        mimo_v2(dict(MIMO, routed_scaling_factor=2.5), MAX_LEN)


def test_a_latent_layer_needs_its_arguments():
    """A block over a latent layer declares a latent row to the pool, and
    the layer refuses a rotary part that has no pairs."""
    from bigdl_tpu.models import HybridDecoder
    from bigdl_tpu.models.hybrid_decoder import HybridBlock
    m = HybridDecoder(30, 32, [HybridBlock(
        32, LatentAttention(32, 4, 8, 4, 8, 16), GatedFFN(32, 64), 1e-6)],
        max_len=16)
    assert m.cache_layers() == (("latent", 16),)
    with pytest.raises(ValueError, match="rope_dim"):
        LatentAttention(32, 4, 8, 3, 8, 16)


# ---- the expert layer --------------------------------------------------------

def _layer(held=None, shared=True, scale=2.5):
    layer = HeldExperts(64, 24, 8, 2, held=held, scale=scale,
                        shared=GatedFFN(64, 24) if shared else None)
    layer.router.bias = 0.05 * jax.random.normal(jax.random.key(9), (8,))
    return layer


def _loop_over_experts(layer, x):
    """``scale * sum_chosen w_e E_e(x) + E_shared(x)``, an expert and a
    token at a time."""
    experts, weights = layer.route(x)
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for e, w in zip(np.asarray(experts[t]), np.asarray(weights[t])):
            g = x[t] @ layer.w_gate[e]
            a = jax.nn.silu(g) * (x[t] @ layer.w_up[e])
            out[t] += layer.scale * float(w) * np.asarray(a @ layer.w_down[e])
        s = layer.shared
        a = jax.nn.silu(x[t] @ s.gate.weight.T) * (x[t] @ s.up.weight.T)
        out[t] += np.asarray(a @ s.down.weight.T)
    return out


@pytest.mark.parametrize("tokens", [6, HeldExperts.DENSE_TOKENS + 8],
                         ids=["every-stack", "grouped"])
def test_shared_and_scale_equal_a_loop_over_experts(tokens):
    layer = _layer()
    x = jax.random.normal(jax.random.key(3), (tokens, 64))
    y, counts = layer.forward(x)
    np.testing.assert_allclose(y[:6], _loop_over_experts(layer, x[:6]),
                               atol=2e-5)
    # the shared expert is no pair: the counts are the routed experts'
    chosen = len(set(np.asarray(layer.route(x)[0]).ravel().tolist()))
    assert counts.tolist()[:4] == [1, 2 * tokens, 2 * tokens, chosen]
    # an idle row gets nothing, not even the shared expert
    valid = jnp.arange(tokens) < 4
    y2, counts2 = layer.forward(x, valid)
    assert counts2.tolist()[1] == 8
    np.testing.assert_allclose(y2[:4], y[:4], atol=1e-6)
    assert float(jnp.abs(y2[4:]).max()) == 0.0


@pytest.mark.parametrize("shares", [8, 4, 1])
def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        shares):
    """The share test of the deployment (eight chips a layer at the
    published size, ``shares`` here): every chip computes its held
    experts' part of the routed sum, scaled, and the shared expert whole;
    the routed parts of all the shares and the shared expert counted
    **once** add up to what the reference gives for the uncut layer."""
    whole = _layer()
    x = jax.random.normal(jax.random.key(4), (10, 64))
    n = 8 // shares
    w = {".ffn" + k: v for k, v in params_of(whole).items()}
    cfg = dict(CFG, experts_offset=0)
    shared = ref.shared(x, w, lambda a: a)
    want = ref.routed(x, w, cfg, lambda a: a, 0) + shared
    np.testing.assert_allclose(whole.forward(x)[0], want, atol=2e-5)
    total, held_pairs = jnp.zeros((10, 64)), 0
    for i in range(shares):
        share = HeldExperts(64, 24, 8, 2, held=(i * n, n), scale=2.5,
                            shared=whole.shared)
        share.router = whole.router
        for name in ("w_gate", "w_up", "w_down"):
            setattr(share, name, jax.lax.slice_in_dim(
                getattr(whole, name), i * n, (i + 1) * n))
        y, counts = share.forward(x)
        ws = dict(w, **{".ffn." + name: getattr(share, name)
                        for name in ("w_gate", "w_up", "w_down")})
        # a chip's result is its routed part and the shared expert whole,
        # which is what the reference is given for the same share
        np.testing.assert_allclose(
            y, ref.routed(x, ws, cfg, lambda a: a, i * n) + shared,
            atol=2e-5)
        total = total + (y - shared)
        held_pairs += int(counts[2])
    assert held_pairs == 10 * 2
    np.testing.assert_allclose(total + shared, want, atol=5e-5)


# ---- the slot pool and the engine -------------------------------------------

def test_the_pool_declares_latent_rows_and_counts_their_bytes(model):
    m, _ = model
    pool = SlotPool(m, slots=3)
    assert pool.cache_layers == (("latent", MAX_LEN),) * LAYERS
    assert not pool.has_ring and not pool.has_state
    assert pool.expert_layers == 2
    by_kind = pool.cache_nbytes_by_kind()
    # slots, places, the latent and the rotary key, float32: one head
    assert by_kind == {"latent": LAYERS * 3 * MAX_LEN * (32 + 8) * 4,
                       "full": 0, "ring": 0, "state": 0}
    assert pool.cache_nbytes() == by_kind["latent"] + 3 * MAX_LEN
    # off a TPU the step writes a row and leaf at a time and reads whole
    # rows; the kernels' paths are steered in test_decode_attention.py,
    # test_cache_write.py and test_tpu_compile.py
    assert pool.key_block is None
    assert pool.cache_write_programs == 1 + LAYERS * 2 * 3


def test_the_copy_and_extract_programs_take_latent_rows(model, tokens):
    """A latent row is written and read by position like a full row, so a
    span of one slot's row moves into another's and the prefix cache is
    allowed."""
    m, _ = model
    pool = SlotPool(m, slots=2)
    row = np.asarray(tokens[0])
    for s in (0, 4):
        pool.chunk_prefill_into(row[s:s + CHUNK], 0, s)
    layers, pad = pool.kv_extract(0, 4, 4)
    assert [(kv["k"].shape, kv["v"].shape) for kv in layers] \
        == [((1, 4, 8), (1, 4, 32))] * LAYERS
    pool.caches = pool._kv_copy_jit(pool.caches, np.int32(1), layers, pad,
                                    np.int32(4))
    for cache in pool.caches["layers"]:
        for n in ("k", "v"):
            np.testing.assert_array_equal(cache["self"][n][1][:, 4:8],
                                          cache["self"][n][0][:, 4:8])
    engine = GenerationScheduler(m, slots=2, prefill_chunk=CHUNK,
                                 prefix_cache_bytes=1 << 20, start=False)
    assert engine.stats()["prefix_cache"] is not None


def test_the_engine_end_to_end_on_mixed_lengths(model):
    m, _ = model
    engine = GenerationScheduler(m, slots=3, prefill_chunk=CHUNK,
                                 prefill_batch=2)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, VOCAB + 1, n).astype(np.int32)
               for n in (3, 5, 21, 30, 9, 2, 40, 17)]
    try:
        futs = [engine.submit_async(p, 12) for p in prompts]
        rows = [f.result(timeout=300) for f in futs]
        stats = engine.stats()
    finally:
        engine.shutdown()
    for p, row in zip(prompts, rows):
        want = np.asarray(m.generate(jnp.asarray(p)[None], 12, chunk=CHUNK))
        assert np.array_equal(row, want[0]), len(p)
    counts = engine.pool.trace_counts
    assert counts["decode"] == 1
    # chunk programs are keyed by width alone
    assert set(counts["chunk_prefill"].values()) == {1}
    assert set(counts["chunk_prefill"]) <= {1, 2, 4}
    assert stats["cache_bytes_latent"] \
        == sum(engine.pool.cache_nbytes_by_kind().values()) > 0
    assert stats["cache_bytes_full"] == stats["cache_bytes_window"] == 0
    assert stats["moe_layer_calls"] % 2 == 0 and stats["moe_pairs_held"] > 0
    assert engine.pool.cache_write_programs == 1 + LAYERS * 2 * 3


def test_with_a_prefix_cache_a_prompts_last_chunk_goes_out_alone(model):
    """Latent rows may be cached by prefix.  B's chunks ride A's decode
    steps, but its last goes out alone, before its rows are extracted for
    the cache; C, the same prompt again, then copies them and prefills
    nothing.  All three rows are ``generate()``'s."""
    m, _ = model
    rng = np.random.default_rng(9)
    a = rng.integers(1, VOCAB + 1, 3).astype(np.int32)
    b = rng.integers(1, VOCAB + 1, 3 * CHUNK + 1).astype(np.int32)
    engine = GenerationScheduler(m, slots=3, prefill_chunk=CHUNK,
                                 prefix_cache_bytes=1 << 20,
                                 prefix_granularity=CHUNK, start=False)
    log = joint_pass.logged_pool_calls(engine.pool)
    engine.start()
    try:
        rows = joint_pass.serve_beside_a_decoding_slot(engine, a, [b],
                                                       new_first=40)
        rows.append(engine.submit_async(b, 6).result(timeout=300))
        engine.shutdown()
        stats = engine.stats()
    finally:
        engine.shutdown()
    for p, row, new in zip((a, b, b), rows, (40, 6, 6)):
        want = np.asarray(m.generate(jnp.asarray(p)[None], new, chunk=CHUNK))
        assert np.array_equal(row, want[0]), len(p)
    # B: 12 positions, three chunks, the last alone; C: three copies
    assert [e for e in log if e != "step"][:3] == ["step+chunk"] * 2 \
        + ["alone"]
    assert (stats["chunks_joint"], stats["chunks_alone"]) == (2, 1)
    assert stats["prefix_chunks_copied"] == 3
    assert engine.pool.trace_counts["kv_extract"] == {CHUNK: 1}


def test_the_pool_counts_what_the_kernels_step_reads_and_writes(monkeypatch):
    """What a TPU process chooses, forced here and interpreted: the step
    attends through ``ops.latent_decode_attention`` (live key blocks
    only) and writes each layer's row through ``ops.write_cache_rows``
    (one program a layer).  One request alone, 120 prompt tokens and 10
    new, in rows of 384: dispatch ``i`` attends ``120 + i`` places and
    reads them rounded up to the block of 128; the tokens are what
    ``generate()`` gives."""
    from bigdl_tpu.ops import attention_kernels, cache_kernels
    monkeypatch.setattr(
        attention_kernels, "decode_key_block",
        functools.partial(attention_kernels.decode_key_block, force="ragged"))
    monkeypatch.setattr(
        cache_kernels, "cache_row_writer",
        functools.partial(cache_kernels.cache_row_writer, force="kernel"))
    max_len, slots, new = 384, 2, 10
    # widths the kernels tile: a latent of 128 and a rotary key of 8
    m, _ = build(max_len, kv_lora_rank=128, head_dim=136)
    caches = m.init_cache(slots)
    assert m.decode_key_block(caches) == 128
    assert m.cache_write_programs(caches) == 1 + LAYERS
    prompt = np.arange(1, 121, dtype=np.int32) % VOCAB + 1
    engine = GenerationScheduler(m, slots=slots, prefill_chunk=24)
    try:
        assert engine.pool.key_block == 128
        row = engine.submit_async(prompt, new).result(timeout=600)
        deadline = time.time() + 10
        while time.time() < deadline and engine.pool.n_active():
            time.sleep(0.01)
        time.sleep(0.05)
        st = engine.stats()
    finally:
        engine.shutdown()
    n = st["decode_dispatches"]
    assert n in (new, new + 1)          # the pipeline is one step deep
    assert st["decode_positions_live"] == sum(120 + i for i in range(n))
    assert st["decode_positions_read"] == 128 * 9 + 256 * (n - 9)
    assert engine.pool.cache_write_programs == 1 + LAYERS
    want = np.asarray(m.generate(jnp.asarray(prompt)[None], new, chunk=24))
    assert np.array_equal(row, want[0])


@pytest.mark.parametrize("rows", ["latent", "grouped-query"])
def test_chunk_position_counters_follow_a_known_schedule(rows):
    """A prompt of 300 tokens in chunks of 64 goes out as chunks at 0,
    64, 128 and 192 and a suffix-aligned one at 235 (as
    ``tests/test_generation.py`` has it for ``TransformerLM``).  A latent
    pool's chunk reads the live key blocks of its slot's row, 128 places
    here (what 512 and a row of 384 share), on every backend, and the
    pool counts so; so does a pool of grouped-query rows, whose full
    layers read blocks of what 256 and the row share (the whole row of 64
    here: ``tests/test_hybrid_decoder.py`` has rows of several blocks)."""
    if rows == "latent":
        m, _ = build(384)
        chunk, prompt = 64, np.arange(300, dtype=np.int32) % VOCAB + 1
        chunks = [(0, 64), (64, 64), (128, 64), (192, 64), (235, 64)]
        block = 128
    else:
        from tests.test_hybrid_decoder import CFG as MIMO
        m = mimo_v2(MIMO, MAX_LEN).eval_mode()
        chunk, prompt = CHUNK, np.arange(14, dtype=np.int32) % VOCAB + 1
        chunks = [(0, 4), (4, 4), (8, 4), (12, 1)]
        block = MAX_LEN
    engine = GenerationScheduler(m, slots=2, prefill_chunk=chunk)
    try:
        assert engine.pool.chunk_key_block == block \
            == m.chunk_key_block(engine.pool.caches)
        row = engine.submit(prompt, 3)
        engine.shutdown()
        st = engine.stats()
    finally:
        engine.shutdown()
    want = np.asarray(m.generate(jnp.asarray(prompt)[None], 3, chunk=chunk))
    assert np.array_equal(row, want[0])
    assert st["chunks_joint"] + st["chunks_alone"] == len(chunks)
    assert st["chunk_positions_live"] == sum(s + w for s, w in chunks)
    assert st["chunk_positions_read"] == sum(
        block * -(-(s + w) // block) for s, w in chunks)

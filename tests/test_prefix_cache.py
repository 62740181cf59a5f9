"""Prefix KV-cache reuse + chunked prefill (serving/prefix_cache.py,
serving/generation.py): LRU byte budgeting, hit/miss/eviction
semantics, chunked-prefill equivalence across chunk boundaries, the
decode-must-not-disturb-inactive-rows pin, and the cadence/TTFT
reservoirs.

The load-bearing assertion throughout: greedy rows stay BIT-IDENTICAL
to solo ``model.generate()`` — cache hit or miss, chunked or bucketed
prefill, across evictions — and cache disabled reproduces the PR-10
engine's rows exactly.
"""

import numpy as np
import pytest

from bigdl_tpu.models import transformer_lm
from bigdl_tpu.serving.generation import GenerationScheduler, SlotPool
from bigdl_tpu.serving.prefix_cache import PrefixKVCache
from bigdl_tpu.utils import set_seed


@pytest.fixture(scope="module")
def lm():
    set_seed(0)
    return transformer_lm(vocab_size=50, hidden_size=32, num_layers=2,
                          num_heads=4, filter_size=64,
                          max_len=64).eval_mode()


_SOLO_CACHE = {}


def solo(model, prompt, max_new, eos_id=None):
    import jax.numpy as jnp
    key = (id(model), prompt.tobytes(), int(max_new), eos_id)
    if key not in _SOLO_CACHE:
        _SOLO_CACHE[key] = np.asarray(model.generate(
            jnp.asarray(prompt, jnp.int32)[None], int(max_new),
            eos_id=eos_id))[0]
    return _SOLO_CACHE[key]


# ---------------------------------------------------------------------------
# PrefixKVCache unit semantics
# ---------------------------------------------------------------------------

def _fake_chunk_arrays(g=8, h=2, d=4):
    return ([{"k": np.zeros((h, g, d), np.float32),
              "v": np.zeros((h, g, d), np.float32)}],
            np.zeros((g,), bool))


def test_prefix_cache_match_insert_and_lru_eviction():
    layers, pad = _fake_chunk_arrays()
    nbytes = 2 * layers[0]["k"].nbytes + pad.size   # one entry's cost
    cache = PrefixKVCache(byte_budget=2 * nbytes, granularity=8)
    toks = np.arange(1, 25, dtype=np.int32)         # 3 granules
    assert cache.match(toks) == []                  # miss counted
    assert cache.missing_boundaries(toks) == [1, 2, 3]
    cache.insert(toks, 1, *_fake_chunk_arrays())
    cache.insert(toks, 2, *_fake_chunk_arrays())
    chain = cache.match(toks)
    assert [c.index for c in chain] == [0, 8]
    # a DIFFERENT second granule shares granule 1 only
    other = toks.copy()
    other[10] += 1
    assert len(cache.match(other)) == 1
    # third insert exceeds the 2-entry budget: LRU (granule-1 entry was
    # most recently touched by the matches) evicts the granule-2 entry
    cache.insert(toks, 3, *_fake_chunk_arrays())
    st = cache.stats()
    assert st["evictions"] == 1
    assert st["resident_bytes"] <= cache.byte_budget
    assert len(cache.match(toks)) >= 1              # granule 1 survived
    # prompts shorter than one granule are neither hit nor miss
    before = cache.stats()["lookups"]
    assert cache.match(np.arange(1, 5, dtype=np.int32)) == []
    assert cache.stats()["lookups"] == before


def test_prefix_cache_validation():
    with pytest.raises(ValueError, match="byte_budget"):
        PrefixKVCache(0, 8)
    with pytest.raises(ValueError, match="power of two"):
        PrefixKVCache(1 << 20, 12)
    # an entry larger than the whole budget is refused, not thrashed
    cache = PrefixKVCache(8, 8)
    layers, pad = _fake_chunk_arrays()
    assert cache.insert(np.arange(1, 9, dtype=np.int32), 1,
                        layers, pad) is None
    assert len(cache) == 0


# ---------------------------------------------------------------------------
# chunked prefill: equivalence across chunk boundaries
# ---------------------------------------------------------------------------

def test_chunked_prefill_equivalence_straddling_boundaries(lm):
    """Prompts whose prefill region lands exactly on, one short of, and
    past chunk boundaries (including the suffix-aligned overlapping
    final chunk) must stay bit-identical to solo generate()."""
    chunk = 8
    eng = GenerationScheduler(lm, slots=2, prefill_chunk=chunk,
                              prefill_chunk_budget=1)
    rng = np.random.default_rng(3)
    try:
        for tp in (chunk - 1, chunk, chunk + 1, 2 * chunk,
                   2 * chunk + 3, 6 * chunk + 5):
            prompt = rng.integers(1, 51, tp).astype(np.int32)
            row = eng.submit(prompt, 4, timeout=120)
            np.testing.assert_array_equal(row, solo(lm, prompt, 4),
                                          err_msg=f"Tp={tp}")
        counts = eng.pool.trace_counts
        assert counts["chunk_prefill"], "chunk path never exercised"
        assert all(n == 1 for n in counts["chunk_prefill"].values()), \
            counts
    finally:
        eng.shutdown()


@pytest.mark.parametrize("sharers", [0, 6])
def test_prefix_hit_longer_than_suffix_bucket(lm, sharers):
    """A cached prefix longer than the remaining suffix's bucket: the
    copy path must seed positions beyond where the suffix prefill
    writes, and the row stays bit-identical — also for six sharers in
    flight together over two slots, each of which hits."""
    rng = np.random.default_rng(4)
    prefix = rng.integers(1, 51, 32).astype(np.int32)
    eng = GenerationScheduler(lm, slots=2, prefill_chunk=16,
                              prefix_cache_bytes=1 << 24,
                              prefix_granularity=8)
    try:
        p1 = np.concatenate([prefix, rng.integers(1, 51, 2)
                             .astype(np.int32)])
        p2 = np.concatenate([prefix, rng.integers(1, 51, 3)
                             .astype(np.int32)])
        np.testing.assert_array_equal(eng.submit(p1, 4, timeout=120),
                                      solo(lm, p1, 4))
        np.testing.assert_array_equal(eng.submit(p2, 4, timeout=120),
                                      solo(lm, p2, 4))
        st = eng.stats()
        # p2 hit the full 32-token prefix: 4 chunks of 8 copied, and
        # its suffix bucket (<= 4) is far shorter than the hit
        assert st["prefix_cache"]["hits"] == 1
        assert st["prefix_chunks_copied"] == 4
        more = [np.concatenate([prefix, rng.integers(1, 51, 2 + i)
                                .astype(np.int32)]) for i in range(sharers)]
        futs = [eng.submit_async(p, 4) for p in more]
        for p, f in zip(more, futs):
            np.testing.assert_array_equal(f.result(120), solo(lm, p, 4))
        assert eng.stats()["prefix_cache"]["hits"] == 1 + sharers
    finally:
        eng.shutdown()


def test_cache_disabled_byte_identical_to_baseline_engine(lm):
    """prefix_cache_bytes=None must reproduce the PR-10 behavior: same
    rows, no cache programs ever traced, bucketed prefill only (these
    prompts fit one chunk)."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 51, int(rng.integers(2, 20)))
               .astype(np.int32) for _ in range(8)]
    eng = GenerationScheduler(lm, slots=3)     # defaults: cache off
    try:
        rows = [f.result(120) for f in
                [eng.submit_async(p, 5) for p in prompts]]
        counts = eng.pool.trace_counts
    finally:
        eng.shutdown()
    for p, row in zip(prompts, rows):
        np.testing.assert_array_equal(row, solo(lm, p, 5))
    assert counts["kv_copy"] == {} and counts["kv_extract"] == {}
    assert counts["chunk_prefill"] == {}
    assert counts["prefill"], "bucketed prefill path was not used"
    # the cache-enabled engine emits the same bytes
    eng2 = GenerationScheduler(lm, slots=3, prefix_cache_bytes=1 << 24,
                               prefix_granularity=8)
    try:
        rows2 = [f.result(120) for f in
                 [eng2.submit_async(p, 5) for p in prompts]]
    finally:
        eng2.shutdown()
    for a, b in zip(rows, rows2):
        np.testing.assert_array_equal(a, b)


def test_eviction_under_byte_pressure_mid_stream(lm):
    """A byte budget that cannot hold every prefix forces evictions
    while requests are decoding; rows stay correct and the cache stays
    within budget (matched chains keep their arrays alive by
    reference, so eviction cannot corrupt an admitted request)."""
    rng = np.random.default_rng(6)
    pool = SlotPool(lm, slots=1)
    one_chunk = sum(
        2 * c["self"]["k"][0, :, :8, :].nbytes
        for c in pool.caches["layers"]) + 8
    eng = GenerationScheduler(lm, slots=3,
                              prefill_chunk=8,
                              prefix_cache_bytes=3 * one_chunk,
                              prefix_granularity=8)
    prompts = [rng.integers(1, 51, int(rng.integers(17, 40)))
               .astype(np.int32) for _ in range(10)]
    try:
        rows = [f.result(180) for f in
                [eng.submit_async(p, 4) for p in prompts]]
        st = eng.stats()["prefix_cache"]
    finally:
        eng.shutdown()
    for p, row in zip(prompts, rows):
        np.testing.assert_array_equal(row, solo(lm, p, 4))
    assert st["evictions"] > 0
    assert st["resident_bytes"] <= st["byte_budget"]


# ---------------------------------------------------------------------------
# the slot-isolation pin behind chunked prefill
# ---------------------------------------------------------------------------

def test_decode_does_not_disturb_inactive_rows(lm):
    """Pooled decode steps must not write into an INACTIVE slot's
    freshly prefilled region — every lane burns a write (S is
    shape-stable), so inactive lanes are steered to the always-masked,
    always-rewritten-before-read position max_len-1.  A stale index
    would silently clobber a co-scheduled chunked prefill (this is a
    byte-level pin; greedy-row tests can miss an ulp-scale poisoning
    that does not flip an argmax)."""
    rng = np.random.default_rng(7)
    pool = SlotPool(lm, slots=2)
    pool.chunk_prefill_into(rng.integers(1, 51, 8).astype(np.int32),
                            0, 0)
    k_before = [np.asarray(c["self"]["k"])[0, :, :8, :].copy()
                for c in pool.caches["layers"]]
    pad_before = np.asarray(pool.caches["pad"])[0, :8].copy()
    pool.activate(1, 5, 20)
    for _ in range(3):
        pool.decode()
    for i, c in enumerate(pool.caches["layers"]):
        np.testing.assert_array_equal(
            np.asarray(c["self"]["k"])[0, :, :8, :], k_before[i])
    np.testing.assert_array_equal(np.asarray(pool.caches["pad"])[0, :8],
                                  pad_before)


# ---------------------------------------------------------------------------
# observability: reservoirs, stats, telemetry families, trace counts
# ---------------------------------------------------------------------------

def test_ttft_and_inter_token_reservoir_quantiles_in_stats(lm):
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, 51, int(rng.integers(2, 16)))
               .astype(np.int32) for _ in range(5)]
    eng = GenerationScheduler(lm, slots=2)
    try:
        [f.result(120) for f in
         [eng.submit_async(p, 6) for p in prompts]]
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["queue_to_first_token_s_p50"] > 0
    assert st["queue_to_first_token_s_p99"] >= \
        st["queue_to_first_token_s_p50"]
    assert st["inter_token_s_p50"] > 0
    assert st["inter_token_s_p99"] >= st["inter_token_s_p50"]
    assert st["prefix_cache"] is None      # off by default


def test_prefix_and_cadence_families_recorded_when_enabled(lm):
    from bigdl_tpu import telemetry
    telemetry.enable()
    telemetry.reset()
    try:
        rng = np.random.default_rng(9)
        prefix = rng.integers(1, 51, 16).astype(np.int32)
        eng = GenerationScheduler(lm, slots=2, prefill_chunk=8,
                                  prefix_cache_bytes=1 << 24,
                                  prefix_granularity=8)
        try:
            for _ in range(3):
                tail = rng.integers(1, 51, 3).astype(np.int32)
                eng.submit(np.concatenate([prefix, tail]), 4,
                           timeout=120)
        finally:
            eng.shutdown()
        text = telemetry.prometheus_text()
        assert 'generation_prefix_cache_events_total{result="miss"}' \
            in text
        assert 'generation_prefix_cache_events_total{result="hit"}' \
            in text
        assert "generation_prefix_cache_bytes_reused_total" in text
        assert "generation_prefix_cache_resident_bytes" in text
        assert "generation_inter_token_seconds_count" in text
    finally:
        telemetry.reset()
        telemetry.disable()


def test_cache_and_seed_programs_compile_once(lm):
    """The new programs keep the O(1) budget: chunk prefill once per
    width, kv copy/extract once per granularity, the membership seed
    once total — across many requests joining and leaving."""
    rng = np.random.default_rng(10)
    eng = GenerationScheduler(lm, slots=2, prefill_chunk=8,
                              prefix_cache_bytes=1 << 24,
                              prefix_granularity=8)
    prompts = [rng.integers(1, 51, int(rng.integers(10, 40)))
               .astype(np.int32) for _ in range(8)]
    try:
        [f.result(180) for f in
         [eng.submit_async(p, 4) for p in prompts]]
        [f.result(180) for f in
         [eng.submit_async(p, 4) for p in prompts]]
        counts = {k: (dict(v) if isinstance(v, dict) else v)
                  for k, v in eng.pool.trace_counts.items()}
    finally:
        eng.shutdown()
    assert counts["decode"] == 1
    assert counts["seed"] == 1
    assert counts["kv_copy"] == {8: 1}
    assert counts["kv_extract"] == {8: 1}
    assert counts["chunk_prefill"] and \
        all(n == 1 for n in counts["chunk_prefill"].values()), counts

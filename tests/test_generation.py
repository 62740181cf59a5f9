"""Continuous batching for generation (serving/generation.py): greedy
equivalence with solo ``generate()`` across join/leave orderings, O(1)
compile counts, slot-pool cache donation, streaming, admission, drain,
and the ModelServer generation backend.

The load-bearing assertion (ISSUE 10 acceptance): every request's
emitted tokens are BIT-IDENTICAL to a solo ``model.generate()`` call at
fixed seed, regardless of which requests share the pool or the order
they join and leave.
"""

import threading
import time

import numpy as np
import pytest

import joint_pass
from bigdl_tpu.models import transformer_lm
from bigdl_tpu.serving import (
    GenerationScheduler, ModelServer, QueueFullError, ServerClosedError,
)
from bigdl_tpu.serving.generation import SlotPool
from bigdl_tpu.utils import set_seed


@pytest.fixture(scope="module")
def lm():
    set_seed(0)
    return transformer_lm(vocab_size=50, hidden_size=32, num_layers=2,
                          num_heads=4, filter_size=64,
                          max_len=64).eval_mode()


_SOLO_CACHE = {}


def solo(model, prompt, max_new, eos_id=None):
    """Reference row from model.generate, memoized (eager generate
    re-traces per shape, the expensive part of these tests)."""
    import jax.numpy as jnp
    key = (id(model), prompt.tobytes(), int(max_new), eos_id)
    if key not in _SOLO_CACHE:
        _SOLO_CACHE[key] = np.asarray(model.generate(
            jnp.asarray(prompt, jnp.int32)[None], int(max_new),
            eos_id=eos_id))[0]
    return _SOLO_CACHE[key]


def _requests(rng, n, max_len=64, pmax=20, nmax=10):
    prompts = [rng.integers(1, 51, rng.integers(1, pmax)).astype(np.int32)
               for _ in range(n)]
    max_news = [int(rng.integers(2, nmax)) for _ in range(n)]
    return prompts, max_news


# ---------------------------------------------------------------------------
# the acceptance property: bit-identical greedy rows, any pool sharing
# ---------------------------------------------------------------------------

def test_greedy_equivalence_mixed_lengths(lm):
    rng = np.random.default_rng(0)
    prompts, max_news = _requests(rng, 10)
    eng = GenerationScheduler(lm, slots=4, prefill_batch=2)
    try:
        futs = [eng.submit_async(p, m)
                for p, m in zip(prompts, max_news)]
        rows = [f.result(timeout=120) for f in futs]
    finally:
        eng.shutdown()
    for p, m, row in zip(prompts, max_news, rows):
        np.testing.assert_array_equal(row, solo(lm, p, m))


def test_greedy_equivalence_randomized_arrivals(lm):
    """Property-style: the SAME request set under different randomized
    arrival schedules (submission order + staggering) must emit the
    same bit-identical rows — join/leave ordering cannot leak between
    co-resident slots."""
    rng = np.random.default_rng(1)
    prompts, max_news = _requests(rng, 8)
    want = [solo(lm, p, m) for p, m in zip(prompts, max_news)]
    for schedule_seed in (0, 1, 2):
        srng = np.random.default_rng(schedule_seed)
        order = srng.permutation(len(prompts))
        eng = GenerationScheduler(lm, slots=3, prefill_batch=2)
        try:
            futs = {}
            for i in order:
                futs[i] = eng.submit_async(prompts[i], max_news[i])
                if srng.random() < 0.5:
                    # stagger: some requests join mid-decode of others
                    time.sleep(float(srng.random()) * 0.05)
            for i, f in futs.items():
                np.testing.assert_array_equal(
                    f.result(timeout=120), want[i],
                    err_msg=f"schedule {schedule_seed}, request {i}")
        finally:
            eng.shutdown()


def test_eos_leaves_slot_without_disturbing_neighbors(lm):
    """A request hitting EOS leaves mid-flight; its row matches solo
    generate (EOS emitted, zeros after) and co-resident requests are
    unaffected."""
    rng = np.random.default_rng(2)
    prompts, _ = _requests(rng, 4)
    # pick row 0's first greedily-generated token as the "EOS" so it
    # fires on the very first decode step for that request
    eos = int(solo(lm, prompts[0], 6)[len(prompts[0])])
    want = [solo(lm, p, 6, eos_id=eos) for p in prompts]
    eng = GenerationScheduler(lm, slots=4, eos_id=eos)
    try:
        futs = [eng.submit_async(p, 6) for p in prompts]
        rows = [f.result(timeout=120) for f in futs]
    finally:
        eng.shutdown()
    for row, w in zip(rows, want):
        np.testing.assert_array_equal(row, w)
    # row 0 really stopped at EOS: everything after it is 0-padding
    i0 = len(prompts[0])
    assert rows[0][i0] == eos and not rows[0][i0 + 1:].any()


# ---------------------------------------------------------------------------
# compiled-program budget + donation
# ---------------------------------------------------------------------------

def test_decode_compile_count_is_o1_in_requests(lm):
    """The pooled decode step compiles ONCE per (S, dtype) and prefill
    once per prompt bucket, across many requests joining and leaving in
    arbitrary order (the hlo-recompile determinism idea, applied to the
    engine)."""
    rng = np.random.default_rng(3)
    prompts, max_news = _requests(rng, 14, pmax=33)
    eng = GenerationScheduler(lm, slots=4, prefill_batch=2)
    try:
        futs = [eng.submit_async(p, m)
                for p, m in zip(prompts, max_news)]
        [f.result(timeout=120) for f in futs]
        counts = dict(eng.pool.trace_counts)
    finally:
        eng.shutdown()
    assert counts["decode"] == 1, counts
    assert counts["prefill"], "no prefill bucket was traced"
    assert all(n == 1 for n in counts["prefill"].values()), counts
    assert all(n == 1 for n in counts["scatter"].values()), counts
    # buckets are powers of two over the prompt lengths seen
    for b in counts["prefill"]:
        assert b & (b - 1) == 0, f"non-power-of-two bucket {b}"


def test_slot_pool_cache_donation_hlo_alias(lm):
    """The compiled decode step's input_output_alias must cover at
    least the full slot-pool cache bytes — donation really elides the
    per-iteration copy of S x layers x max_len K/V (the existing
    hlo-donation machinery, pointed at the serving program)."""
    from bigdl_tpu.analysis.hlo_lint import donated_alias_bytes
    pool = SlotPool(lm, slots=4)
    need = pool.cache_nbytes()
    got, n = donated_alias_bytes(pool.decode_hlo_text())
    assert n > 0
    assert got >= need, (got, need)


# ---------------------------------------------------------------------------
# the batched step under the pool: a position per row
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache_dtype", ["float32", "bfloat16"])
def test_per_row_decode_step_equals_batch1_scalar_calls(lm, cache_dtype):
    """``decode_step`` with ``index [S]`` is S batch-1 steps with a
    scalar index: the same logits, the same key, value and padding flag
    written at ``index[b]`` of row ``b``, and nothing else of the cache
    touched.  Row 1 feeds a padding token; row 3 is an inactive lane
    parked at ``max_len - 1``, as ``SlotPool._decode`` parks it."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cache_dtype)
    tol = 1e-5 if cache_dtype == "float32" else 2e-2
    rng = np.random.default_rng(11)
    index = np.array([3, 17, 40, lm.max_len - 1], np.int32)
    tokens = np.array([[7], [0], [12], [5]], np.int32)
    # a cache with history: random K/V and some padding flags below
    # each row's position, so the step attends over something
    caches = jax.tree_util.tree_map(
        lambda a: jnp.asarray(
            rng.normal(size=a.shape) if a.dtype != bool
            else rng.random(a.shape) < 0.2, a.dtype),
        lm.init_cache(len(index), dtype))
    logits, new = jax.jit(lm.decode_step)(
        jnp.asarray(tokens), jnp.asarray(index), caches)
    assert logits.shape == (len(index), 51)

    def leaves(tree):
        return [np.asarray(a, np.float32)
                for a in jax.tree_util.tree_leaves(tree)]

    for b, at in enumerate(index):
        row = jax.tree_util.tree_map(lambda a: a[b:b + 1], caches)
        want_logits, want = lm.decode_step(
            jnp.asarray(tokens[b:b + 1]), int(at), row)
        np.testing.assert_allclose(logits[b], want_logits[0],
                                   rtol=tol, atol=tol)
        got = jax.tree_util.tree_map(lambda a: a[b:b + 1], new)
        for g, w, before in zip(leaves(got), leaves(want), leaves(row)):
            np.testing.assert_allclose(g, w, rtol=tol, atol=tol)
            # in place: only position index[b] of the row moved (the
            # position axis is the last of the flags [1, T] and the
            # second to last of a K/V leaf [1, h, T, d])
            axis = 1 if g.ndim == 2 else 2
            np.testing.assert_array_equal(np.delete(g, at, axis),
                                          np.delete(before, at, axis))
            if g.ndim == 4:
                assert (np.take(g, at, axis)
                        != np.take(before, at, axis)).all()
    pad = np.asarray(new["pad"])
    assert pad[1, 17] and not pad[0, 3] and not pad[2, 40]


def test_quantized_model_through_slot_pool_matches_its_generate(lm):
    """A model whose ``Linear``s are int8 rides the batched step: the
    per-row path calls the projections as modules, so the pool's greedy
    rows equal the quantized model's own ``generate()``."""
    from bigdl_tpu.nn.quantized import QuantizedLinear, quantize
    qlm = quantize(lm)
    assert isinstance(qlm.blocks[0].self_attn.k_layer, QuantizedLinear)
    rng = np.random.default_rng(12)
    prompts, max_news = _requests(rng, 5)
    eng = GenerationScheduler(qlm, slots=3, prefill_batch=2)
    try:
        rows = [f.result(timeout=120) for f in
                [eng.submit_async(p, m)
                 for p, m in zip(prompts, max_news)]]
    finally:
        eng.shutdown()
    for p, m, row in zip(prompts, max_news, rows):
        np.testing.assert_array_equal(row, solo(qlm, p, m))


# ---------------------------------------------------------------------------
# streaming, stats, validation, admission
# ---------------------------------------------------------------------------

def test_on_token_streams_in_decode_order(lm):
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, 51, 5).astype(np.int32)
    got = []
    eng = GenerationScheduler(lm, slots=2)
    try:
        fut = eng.submit_async(prompt, 6, on_token=got.append)
        row = fut.result(timeout=120)
    finally:
        eng.shutdown()
    want = solo(lm, prompt, 6)
    np.testing.assert_array_equal(row, want)
    assert got == [int(t) for t in want[len(prompt):len(prompt) + 6]]


def test_stats_and_queue_to_first_token(lm):
    rng = np.random.default_rng(5)
    prompts, max_news = _requests(rng, 5)
    eng = GenerationScheduler(lm, slots=2)
    try:
        futs = [eng.submit_async(p, m)
                for p, m in zip(prompts, max_news)]
        [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert stats["requests_done"] == 5
    assert stats["tokens_emitted"] == sum(max_news)
    assert stats["decode_steps"] >= max(max_news)
    assert 0 < stats["slot_occupancy_mean"] <= 2.0
    assert stats["queue_to_first_token_s_mean"] > 0
    assert stats["tokens_per_second"] > 0
    assert stats["prefill_calls"] >= 1


def test_validation_errors(lm):
    eng = GenerationScheduler(lm, slots=2)
    try:
        with pytest.raises(ValueError, match="exceeds"):
            eng.submit_async(np.arange(1, 60, dtype=np.int32), 30)
        with pytest.raises(ValueError, match="empty"):
            eng.submit_async(np.zeros((0,), np.int32), 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit_async(np.ones((3,), np.int32), 0)
    finally:
        eng.shutdown()


def test_generation_admission_reject_policy(lm):
    """The bounded generation queue honors the one-shot admission
    policies: reject fails fast once capacity is hit."""
    eng = GenerationScheduler(lm, slots=1, queue_capacity=1,
                              admission="reject", start=False)
    # not started: nothing drains the queue, so capacity is decisive
    eng.submit_async(np.ones((2,), np.int32), 2)
    with pytest.raises(QueueFullError):
        eng.submit_async(np.ones((2,), np.int32), 2)
    eng.start()
    eng.shutdown(drain=True)


def test_cancelled_future_frees_no_slot(lm):
    rng = np.random.default_rng(6)
    prompts, max_news = _requests(rng, 3)
    eng = GenerationScheduler(lm, slots=1, start=False)
    futs = [eng.submit_async(p, m) for p, m in zip(prompts, max_news)]
    assert futs[1].cancel()     # still queued -> cancellable
    eng.start()
    eng.shutdown(drain=True)
    np.testing.assert_array_equal(futs[0].result(timeout=60),
                                  solo(lm, prompts[0], max_news[0]))
    np.testing.assert_array_equal(futs[2].result(timeout=60),
                                  solo(lm, prompts[2], max_news[2]))
    assert futs[1].cancelled()


def test_engine_survives_decode_failure(lm):
    """A failing pooled decode fails the RESIDENT futures with the
    error and keeps the engine thread alive for later arrivals — the
    BatchScheduler invariant, kept for the multi-step plane (a dead
    engine thread would strand RUNNING futures forever)."""
    rng = np.random.default_rng(11)
    p1 = rng.integers(1, 51, 4).astype(np.int32)
    p2 = rng.integers(1, 51, 4).astype(np.int32)
    eng = GenerationScheduler(lm, slots=2)
    try:
        calls = {"n": 0}
        orig = eng.pool.decode_dispatch

        def boom(chunk=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("device on fire")
            return orig(chunk)

        # engine is idle (blocked on the queue) here, so the patch
        # lands before any decode of p1 can start
        eng.pool.decode_dispatch = boom
        f1 = eng.submit_async(p1, 4)
        with pytest.raises(RuntimeError, match="device on fire"):
            f1.result(timeout=60)
        assert eng.alive
        f2 = eng.submit_async(p2, 4)
        np.testing.assert_array_equal(f2.result(timeout=60),
                                      solo(lm, p2, 4))
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# ModelServer generation backend
# ---------------------------------------------------------------------------

def test_model_server_generation_backend(lm):
    rng = np.random.default_rng(7)
    prompts, max_news = _requests(rng, 6)
    server = ModelServer(generator=lm, slots=3)
    try:
        rows = server.submit_generate_many(prompts, max_news,
                                           timeout=120)
        for p, m, row in zip(prompts, max_news, rows):
            np.testing.assert_array_equal(row, solo(lm, p, m))
        one = server.submit_generate(prompts[0], max_news[0],
                                     timeout=120)
        np.testing.assert_array_equal(one,
                                      solo(lm, prompts[0], max_news[0]))
        # a numpy integer budget (rng.integers) broadcasts like an int
        np_rows = server.submit_generate_many(prompts[:2], np.int64(3),
                                              timeout=120)
        np.testing.assert_array_equal(np_rows[1], solo(lm, prompts[1], 3))
        # a short per-prompt budget list is an error, not silent drops
        with pytest.raises(ValueError, match="per prompt"):
            server.submit_generate_many(prompts[:3], [2, 2])
        # generation-only server: one-shot submission is a clear error
        with pytest.raises(RuntimeError, match="one-shot"):
            server.submit(np.ones((4,), np.float32))
        assert server.generation_stats()["requests_done"] == 9
    finally:
        server.shutdown()
    with pytest.raises(ServerClosedError):
        server.submit_generate_async(prompts[0], 2)


def test_model_server_requires_some_backend():
    with pytest.raises(TypeError, match="backend"):
        ModelServer()


def test_model_server_both_backends(lm):
    """A server may carry the one-shot batcher AND the generation
    engine; each request class routes to its own scheduler."""
    import bigdl_tpu.nn as nn
    set_seed(3)
    clf = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3),
                        nn.LogSoftMax())
    server = ModelServer(clf, max_batch=4, batch_timeout_ms=5.0,
                         generator=lm, slots=2)
    try:
        y = server.submit(np.ones((4,), np.float32), timeout=60)
        assert y.shape == (3,)
        prompt = np.asarray([3, 1, 4], np.int32)
        row = server.submit_generate(prompt, 3, timeout=120)
        np.testing.assert_array_equal(row, solo(lm, prompt, 3))
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# telemetry wiring
# ---------------------------------------------------------------------------

def test_generation_families_recorded_when_enabled(lm):
    from bigdl_tpu import telemetry
    telemetry.enable()
    telemetry.reset()
    try:
        rng = np.random.default_rng(8)
        prompts, max_news = _requests(rng, 4)
        eng = GenerationScheduler(lm, slots=2)
        try:
            futs = [eng.submit_async(p, m)
                    for p, m in zip(prompts, max_news)]
            [f.result(timeout=120) for f in futs]
        finally:
            eng.shutdown()
        text = telemetry.prometheus_text()
        assert 'generation_phase_seconds_count{phase="decode"}' in text
        assert 'generation_phase_seconds_count{phase="prefill"}' in text
        assert "generation_slot_occupancy" in text
        assert "generation_queue_to_first_token_seconds_count" in text
        assert "generation_tokens_per_second" in text
        # spans: prefill batches + one retroactive span per request
        names = {s.name for s in telemetry.finished_spans()}
        assert "serving/prefill" in names
        assert "serving/generate" in names
    finally:
        telemetry.reset()
        telemetry.disable()


def test_generation_telemetry_off_by_default(lm):
    """With telemetry disabled the engine must not create families."""
    from bigdl_tpu import telemetry
    telemetry.disable()
    telemetry.get_registry().clear()
    rng = np.random.default_rng(9)
    eng = GenerationScheduler(lm, slots=2)
    try:
        eng.submit(rng.integers(1, 51, 4).astype(np.int32), 3,
                   timeout=120)
    finally:
        eng.shutdown()
    assert "generation_" not in telemetry.prometheus_text()


# ---------------------------------------------------------------------------
# the engine's own measurement: phase seconds, step gaps, counters, spans
# ---------------------------------------------------------------------------

def _timed_engine(lm, **kw):
    """An engine whose thread's wall time is taken from outside it."""
    eng = GenerationScheduler(lm, start=False, **kw)
    wall = {}
    run = eng._run

    def timed_run():
        wall["t0"] = time.perf_counter()
        try:
            run()
        finally:
            wall["t1"] = time.perf_counter()

    eng._run = timed_run
    eng.start()
    return eng, wall


def test_engine_phase_seconds_sum_to_the_threads_wall_time(lm):
    rng = np.random.default_rng(21)
    prompts, max_news = _requests(rng, 6, pmax=30)
    eng, wall = _timed_engine(lm, slots=2, prefill_chunk=8)
    try:
        futs = [eng.submit_async(p, m) for p, m in zip(prompts, max_news)]
        [f.result(timeout=120) for f in futs]
        # a future resolves inside the thread's last pass: under load the
        # thread reaches its queue some milliseconds later, and a sleep
        # begun before that is not all idle
        deadline = time.perf_counter() + 60
        while eng._phase_key != "idle" and time.perf_counter() < deadline:
            time.sleep(0.001)
        assert eng._phase_key == "idle"
        time.sleep(0.05)            # blocked on the empty queue: idle
        eng.submit(prompts[0], 3, timeout=120)
    finally:
        eng.shutdown()
    stats = eng.stats()
    phases = stats["engine_phase_seconds"]
    assert set(phases) == {"admit", "prefill_dispatch", "decode_dispatch",
                           "readback_wait", "emit", "other", "idle"}
    assert all(v >= 0.0 for v in phases.values())
    assert phases["idle"] >= 0.05
    for key in ("admit", "prefill_dispatch", "decode_dispatch", "emit",
                "other"):
        assert phases[key] > 0.0, key
    # the marks tile the time between the first and the last of them: no
    # phase holds the thread's start before the first and its end after
    # the last, a few statements each whatever the thread's life
    unheld = wall["t1"] - wall["t0"] - sum(phases.values())
    assert -1e-6 <= unheld <= 0.25
    # a pass per decode step at least, and the lame-duck drains
    assert stats["iterations"] >= stats["decode_steps"]
    assert stats["decode_dispatches"] == stats["decode_steps"]


def test_decode_seconds_is_the_sum_of_step_gaps_within_wall_time(lm):
    """Under the one-deep pipeline a step's dispatch-to-read-back interval
    covers up to two device steps and overlaps its neighbour's: summed,
    they exceeded wall time.  Step gaps (read-back to read-back) tile it."""
    rng = np.random.default_rng(22)
    eng = GenerationScheduler(lm, slots=2)
    read = eng.pool.read_emit_masked

    def slow_read(handle):
        time.sleep(0.02)            # the device, busy with the step
        return read(handle)

    eng.pool.read_emit_masked = slow_read
    try:
        t0 = time.perf_counter()
        eng.submit(rng.integers(1, 51, 5).astype(np.int32), 12,
                   timeout=120)
        wall = time.perf_counter() - t0
        stats = eng.stats()
    finally:
        eng.shutdown()
    gaps = stats["step_gap_seconds"]
    assert stats["decode_seconds"] == pytest.approx(
        gaps["plain"] + gaps["prefill"], rel=1e-12)
    n = stats["step_gaps"]["plain"] + stats["step_gaps"]["prefill"]
    assert n >= 11                  # 12 tokens: 12 steps, 11 gaps
    assert stats["decode_seconds"] >= n * 0.02
    assert stats["decode_seconds"] <= wall
    assert stats["tokens_per_second"] == pytest.approx(
        stats["tokens_emitted"] / stats["decode_seconds"])
    # the sleeping read-back is where the thread waited
    assert stats["engine_phase_seconds"]["readback_wait"] >= n * 0.02


def test_step_gap_is_flagged_prefill_when_a_chunk_preceded_its_step(lm):
    """Scripted arrivals: B's long prompt arrives at A's fifth token and
    is prefilled in chunks between A's decode steps (on this pool, which
    has the joint program, by them: a dispatch that carries a chunk is a
    prefill and a dispatch).  The expectation comes from the pool's own
    call log, not from a chunking rule."""
    rng = np.random.default_rng(23)
    eng = GenerationScheduler(lm, slots=2, prefill_chunk=8, start=False)
    pool = eng.pool
    log = []

    def logged(name, fn):
        def call(*a, **k):
            log.append(name)
            return fn(*a, **k)
        return call

    dispatch = pool.decode_dispatch

    def decode_dispatch(chunk=None):
        log.extend(["dispatch"] if chunk is None else ["prefill", "dispatch"])
        return dispatch(chunk)

    pool.chunk_prefill_into = logged("prefill", pool.chunk_prefill_into)
    pool.prefill_into = logged("prefill", pool.prefill_into)
    pool.decode_dispatch = decode_dispatch
    pool.read_emit_masked = logged("read", pool.read_emit_masked)
    eng.start()
    b_prompt = rng.integers(1, 51, 20).astype(np.int32)
    seen, futs = [], []

    def on_a(_tok):
        seen.append(_tok)
        if len(seen) == 5:          # on the engine thread: only enqueues
            futs.append(eng.submit_async(b_prompt, 6))

    try:
        a = eng.submit_async(rng.integers(1, 51, 4).astype(np.int32), 30,
                             on_token=on_a)
        a.result(timeout=120)
        futs[0].result(timeout=120)
        eng.shutdown()
        stats = eng.stats()
    finally:
        eng.shutdown()
    # A outlives B, so the pool never empties between the first dispatch
    # and the last read-back: one chain of steps, a gap for every step
    # but the first, flagged by what was dispatched before ITS dispatch
    flags, since = [], False
    for name in log:
        if name == "prefill":
            since = True
        elif name == "dispatch":
            flags.append(since)
            since = False
    assert log.count("read") == len(flags) == stats["decode_steps"]
    assert stats["step_gaps"] == {"prefill": sum(flags[1:]),
                                  "plain": len(flags) - 1 - sum(flags[1:])}
    # B's 19 prefill positions at 8 a chunk: three chunks, three gaps
    assert stats["step_gaps"]["prefill"] == 3
    assert stats["step_gaps"]["plain"] >= 25
    assert stats["pipeline_drains"] == 1        # the pool emptied at the end
    assert all(v > 0.0 for v in stats["step_gap_seconds"].values())


def test_a_pool_with_the_joint_entry_carries_a_chunk_on_its_step(lm):
    """B's prompt arrives while A decodes and each of its chunks rides a
    decode step as one program (``decode_step_with_chunk``); the tokens
    are ``generate()``'s and the counters say what went where."""
    rng = np.random.default_rng(29)
    eng = GenerationScheduler(lm, slots=2, prefill_chunk=8, start=False)
    pool = eng.pool
    assert pool.chunk_widths == (1, 2, 4, 8)
    log = joint_pass.logged_pool_calls(pool)
    eng.start()
    a_prompt = rng.integers(1, 51, 4).astype(np.int32)
    b_prompt = rng.integers(1, 51, 20).astype(np.int32)
    try:
        a, b = joint_pass.serve_beside_a_decoding_slot(
            eng, a_prompt, [b_prompt], timeout=120)
        eng.shutdown()
        stats = eng.stats()
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(a, solo(lm, a_prompt, 30))
    np.testing.assert_array_equal(b, solo(lm, b_prompt, 6))
    # B's 19 positions: two chunks of 8 and a suffix-aligned one of 4
    assert "alone" not in log and log.count("step+chunk") == 3
    assert (stats["chunks_joint"], stats["chunks_alone"]) == (3, 0)
    assert stats["step_gaps"]["prefill"] == 3
    # the pool's first chunk compiled every chunk program, the lone one
    # at the full width among them, though nothing ran it
    counts = pool.trace_counts
    assert counts["decode_with_chunk"] == {1: 1, 2: 1, 4: 1, 8: 1}
    assert counts["chunk_prefill"] == {8: 1}
    assert counts["decode"] == 1


def test_a_pool_refuses_a_model_that_lacks_the_joint_entry(lm):
    """``decode_step_with_chunk`` is part of what a pool requires of a
    model: one without it is refused by name, as one without any other
    entry of the incremental API is."""
    class NoJointEntry:
        def __getattr__(self, name):
            if name == "decode_step_with_chunk":
                raise AttributeError(name)
            return getattr(lm, name)

    with pytest.raises(TypeError, match="lacks 'decode_step_with_chunk'"):
        SlotPool(NoJointEntry(), slots=2)


def test_prefill_counters_cover_every_prompt_once(lm):
    """Prefix cache off: the prefill programs cover positions [0, Tp-1)
    of every prompt exactly once (the last prompt token is fed to the
    first decode step); what they compute is that plus padding, dead
    batch lanes and suffix-aligned overlap."""
    rng = np.random.default_rng(24)
    lens = [1, 3, 7, 8, 9, 17, 20, 31]      # bucketed and chunked paths
    prompts = [rng.integers(1, 51, n).astype(np.int32) for n in lens]
    eng = GenerationScheduler(lm, slots=3, prefill_chunk=8, prefill_batch=2)
    try:
        futs = [eng.submit_async(p, 3) for p in prompts]
        [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert stats["requests_done"] == len(lens)
    assert stats["prefill_prompt_tokens"] == sum(n - 1 for n in lens)
    assert stats["prefill_positions"] >= stats["prefill_prompt_tokens"]
    assert stats["prefill_calls"] >= 4
    assert stats["admitted"] == len(lens)
    # 8 requests into 3 slots: the later ones waited for a slot
    assert stats["queue_wait_seconds"] > 0.0
    assert stats["queue_wait_seconds"] <= \
        stats["queue_to_first_token_s_mean"] * len(lens)


def test_engine_pass_spans_nest_under_the_iteration(lm):
    from bigdl_tpu import telemetry
    telemetry.enable()
    telemetry.reset()
    try:
        rng = np.random.default_rng(25)
        server = ModelServer(
            generator=GenerationScheduler(lm, slots=2, prefill_chunk=8))
        caller = threading.get_ident()
        try:
            futs = [server.submit_generate_async(
                rng.integers(1, 51, n).astype(np.int32), 4)
                for n in (5, 20)]
            [f.result(timeout=120) for f in futs]
        finally:
            server.shutdown()
        spans = telemetry.finished_spans()
    finally:
        telemetry.reset()
        telemetry.disable()
    by_id = {s.span_id: s for s in spans}
    names = {s.name for s in spans}
    assert {"serving/iteration", "serving/idle", "serving/admit",
            "serving/prefill", "serving/decode_dispatch",
            "serving/readback", "serving/emit", "serving/submit"} <= names
    engine_threads = {s.thread for s in spans
                      if s.name == "serving/iteration"}
    assert len(engine_threads) == 1 and caller not in engine_threads
    for s in spans:
        if s.name in ("serving/admit", "serving/prefill",
                      "serving/decode_dispatch", "serving/readback",
                      "serving/emit"):
            parent = by_id[s.parent_id]
            assert parent.name == "serving/iteration", s.name
            assert parent.t_start <= s.t_start and s.t_end <= parent.t_end
        elif s.name in ("serving/idle", "serving/iteration"):
            assert s.parent_id is None
            assert s.thread in engine_threads
        elif s.name == "serving/submit":
            assert s.thread == caller
    disp = [s for s in spans if s.name == "serving/decode_dispatch"]
    assert all(set(s.args) == {"seq", "n_active", "after_prefill",
                               "drained"} for s in disp)
    # the 20-token prompt was chunked while the other request decoded
    assert any(s.args["after_prefill"] for s in disp)
    emits = [s for s in spans if s.name == "serving/emit"]
    assert sum(s.args["emitted"] for s in emits) == 8
    assert sum(s.args["finished"] for s in emits) == 2


def test_engine_spans_reach_the_profiler_with_telemetry_off(lm, monkeypatch):
    """The ring stays empty and nothing is allocated into it, while every
    pass still opens its profiler annotations."""
    from bigdl_tpu import telemetry
    from bigdl_tpu.telemetry import tracing
    seen = []

    class Counting:
        def __init__(self, name, **args):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    telemetry.disable()
    telemetry.reset()
    monkeypatch.setattr(tracing, "_annotation", Counting)
    eng = GenerationScheduler(lm, slots=2)
    try:
        eng.submit(np.arange(1, 6, dtype=np.int32), 4, timeout=120)
    finally:
        eng.shutdown()      # the lame-duck step is read before it ends
    stats = eng.stats()
    assert telemetry.finished_spans() == []
    assert seen.count("serving/iteration") == stats["iterations"]
    assert seen.count("serving/decode_dispatch") == stats["decode_steps"]
    assert seen.count("serving/readback") == stats["decode_steps"]
    assert seen.count("serving/emit") == stats["decode_steps"]


# ---------------------------------------------------------------------------
# the ragged decode kernel through the pool (interpreted), and its counters
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm384():
    """Rows of three key blocks of 128, heads of 8: the smallest the
    ragged decode kernel tiles."""
    set_seed(3)
    return transformer_lm(vocab_size=50, hidden_size=32, num_layers=2,
                          num_heads=4, filter_size=64,
                          max_len=384).eval_mode()


def _force_ragged(monkeypatch):
    """What a TPU process chooses by itself, asked for on the CPU (the
    kernel then runs interpreted): ``decode_key_block`` is the one place
    both the model's decode step and the pool's counters ask."""
    import functools
    from bigdl_tpu.ops import attention_kernels
    monkeypatch.setattr(
        attention_kernels, "decode_key_block",
        functools.partial(attention_kernels.decode_key_block,
                          force="ragged"))


def test_ragged_decode_kernel_through_the_pool_emits_generates_tokens(
        lm384, monkeypatch):
    """Requests of mixed lengths, one of which crosses a key block while
    it decodes and one of which starts from a single token, share a pool
    whose decode step attends through the kernel: each emits what solo
    ``generate()`` (the XLA product over the whole row) emits."""
    _force_ragged(monkeypatch)
    rng = np.random.default_rng(5)
    lengths, max_news = [5, 124, 1, 200, 130, 40], [6, 8, 5, 4, 7, 6]
    prompts = [rng.integers(1, 51, n).astype(np.int32) for n in lengths]
    eng = GenerationScheduler(lm384, slots=3, prefill_batch=2)
    try:
        assert eng.pool.key_block == 128
        futs = [eng.submit_async(p, m) for p, m in zip(prompts, max_news)]
        rows = [f.result(timeout=300) for f in futs]
        st = eng.stats()
    finally:
        eng.shutdown()
    for p, m, row in zip(prompts, max_news, rows):
        np.testing.assert_array_equal(row, solo(lm384, p, m))
    assert 0 < st["decode_positions_live"] <= st["decode_positions_read"]
    # no step read a slot's whole row, let alone every slot's
    assert st["decode_positions_read"] \
        < st["decode_dispatches"] * 3 * 384


@pytest.mark.parametrize("ragged", [False, True], ids=["xla", "ragged"])
def test_decode_position_counters_follow_a_known_schedule(
        lm384, monkeypatch, ragged):
    """One request alone, 120 prompt tokens and 12 new: dispatch ``i``
    attends ``120 + i`` positions (the last prompt token is fed by the
    first step).  With the kernel the program reads each length rounded
    up to the key block of 128, so one block until the length passes 128
    and two after; the XLA product reads both slots' whole rows every
    step.  The engine may dispatch one step more than it emits (the
    pipeline is one deep): the counters count dispatches."""
    if ragged:
        _force_ragged(monkeypatch)
    prompt = np.arange(1, 121, dtype=np.int32) % 50 + 1
    eng = GenerationScheduler(lm384, slots=2)
    try:
        before = eng.stats()
        eng.submit(prompt, 12)
        # the engine folds its counters at the next pass at the latest
        deadline = time.time() + 10
        while time.time() < deadline:
            st = eng.stats()
            if st["decode_dispatches"] >= 12 and eng.pool.n_active() == 0:
                break
            time.sleep(0.01)
        time.sleep(0.05)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert before["decode_positions_live"] == 0 \
        and before["decode_positions_read"] == 0
    n = st["decode_dispatches"]
    assert n in (12, 13)
    lengths = [120 + i for i in range(n)]
    assert st["decode_positions_live"] == sum(lengths)
    if ragged:
        assert st["decode_positions_read"] == sum(
            128 * -(-length // 128) for length in lengths)
        assert st["decode_positions_read"] == 128 * 9 + 256 * (n - 9)
    else:
        assert st["decode_positions_read"] == n * 2 * 384
    assert st["decode_positions_live"] <= st["decode_positions_read"]


def test_chunk_kernel_through_the_pool_emits_generates_tokens(
        lm384, monkeypatch):
    """Prompts of several chunks, prefilled beside decoding slots and
    into an idle pool, whose chunks attend through the chunk kernel (and
    the rows through the ragged decode kernel), both interpreted: each
    request emits what solo ``generate()`` emits.  What a TPU process
    chooses by itself is asked for here, at the one place the model's
    two chunk entries ask."""
    from bigdl_tpu.ops import attention_kernels
    _force_ragged(monkeypatch)
    calls = []

    def chunk_attention(*args, _kernel=attention_kernels.chunk_attention):
        calls.append(args[0].shape)
        return _kernel(*args, force="ragged")

    monkeypatch.setattr(attention_kernels, "chunk_attention",
                        chunk_attention)
    rng = np.random.default_rng(11)
    lengths, max_news = [150, 300, 70, 201], [5, 4, 6, 4]
    prompts = [rng.integers(1, 51, n).astype(np.int32) for n in lengths]
    eng = GenerationScheduler(lm384, slots=2, prefill_chunk=64)
    try:
        futs = [eng.submit_async(p, m) for p, m in zip(prompts, max_news)]
        rows = [f.result(timeout=600) for f in futs]
        st = eng.stats()
    finally:
        eng.shutdown()
    for p, m, row in zip(prompts, max_news, rows):
        np.testing.assert_array_equal(row, solo(lm384, p, m))
    assert st["chunks_joint"] > 0 and st["chunks_alone"] > 0
    assert (1, 4, 64, 8) in calls
    assert st["chunk_positions_read"] < \
        (st["chunks_joint"] + st["chunks_alone"]) * 384


@pytest.mark.parametrize("how", ["alone", "beside-a-step", "whole-rows"])
def test_chunk_position_counters_follow_a_known_schedule(lm384, how):
    """A prompt of 300 tokens in chunks of 64: its 299 prefill positions
    go out as chunks at 0, 64, 128 and 192 and a suffix-aligned one at
    235.  ``chunk_positions_live`` is what each chunk's last query may
    attend (its start and its width), ``chunk_positions_read`` that
    rounded up to the model's chunk key block (128: what 256 and a row of
    384 share), lone chunks and chunks that ride a decode step alike; a
    pool whose model does not say reads every chunk's whole row."""
    prompt = np.arange(300, dtype=np.int32) % 50 + 1
    eng = GenerationScheduler(lm384, slots=2, prefill_chunk=64, start=False)
    assert eng.pool.chunk_key_block == 128
    if how == "whole-rows":
        eng.pool.chunk_key_block = None
    eng.start()
    try:
        before = eng.stats()
        if how == "beside-a-step":
            _, row = joint_pass.serve_beside_a_decoding_slot(
                eng, prompt[:4], [prompt], new_later=3, timeout=120)
        else:
            row = eng.submit(prompt, 3)
        eng.shutdown()
        st = eng.stats()
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(row, solo(lm384, prompt, 3))
    assert before["chunk_positions_live"] == 0 \
        and before["chunk_positions_read"] == 0
    chunks = [(0, 64), (64, 64), (128, 64), (192, 64), (235, 64)]
    assert st["chunks_joint"] + st["chunks_alone"] == len(chunks)
    assert (st["chunks_joint"] > 0) == (how == "beside-a-step")
    assert st["chunk_positions_live"] == sum(s + w for s, w in chunks)
    assert st["chunk_positions_read"] == (
        len(chunks) * 384 if how == "whole-rows"
        else sum(128 * -(-(s + w) // 128) for s, w in chunks))


@pytest.mark.parametrize("ragged", [False, True], ids=["xla", "ragged"])
def test_decode_row_counters_count_the_rows_a_call_starts(
        lm384, monkeypatch, ragged):
    """Two of a pool's four slots decode: a full layer's call of the
    ragged kernel starts two rows and fetches the second's first key block
    behind a step of the first (``decode_rows_live`` 2,
    ``decode_rows_prefetched`` 1 a dispatch); a row alone has no live row
    before it; a step that reads whole rows counts none.  First the
    pool's handle, then the engine's sums against its own pass records."""
    if ragged:
        _force_ragged(monkeypatch)
    pool = SlotPool(lm384, slots=4)
    pool.activate(1, 7, 130)
    assert pool.decode_dispatch().rows == ((1, 0) if ragged else (0, 0))
    pool.activate(3, 9, 5)
    assert pool.decode_dispatch().rows == ((2, 1) if ragged else (0, 0))
    rng = np.random.default_rng(9)
    eng = GenerationScheduler(lm384, slots=4)
    try:
        assert eng.stats()["decode_rows_live"] == 0
        futs = [eng.submit_async(rng.integers(1, 51, n).astype(np.int32), 16)
                for n in (40, 130)]
        for f in futs:
            f.result(timeout=300)
    finally:
        eng.shutdown()
    st = eng.stats()
    active = st["pass_log"].records()["n_active"]
    assert active.max() == 2 and len(active) == st["decode_dispatches"]
    if ragged:
        assert st["decode_rows_live"] == active.sum()
        assert st["decode_rows_prefetched"] \
            == np.maximum(active - 1, 0).sum() > 0
    else:
        assert st["decode_rows_live"] == st["decode_rows_prefetched"] == 0

"""The engine's pass log (``GenerationScheduler.stats()["pass_log"]``): one
record a decode step's read-back, in which the engine thread's seven phases
tile the step gap, the prefill programs that the device ran in the gap are
named, and the old sums (``step_gaps``, ``step_gap_seconds``,
``chunks_joint``, ``chunks_alone``, ``tokens_emitted``) are the records
added up.  On the CPU backend, with a tiny ``TransformerLM`` and a tiny
``HybridDecoder`` (every chunk rides a step) and a ``TransformerLM``
behind a prefix cache (a prompt's last chunk goes out alone)."""

import gc
import http.client
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import joint_pass                                             # noqa: E402
from harness import pass_log as reader                        # noqa: E402

from bigdl_tpu.models import mimo_v2, transformer_lm          # noqa: E402
from bigdl_tpu.serving import ModelServer                     # noqa: E402
from bigdl_tpu.serving.generation import (                    # noqa: E402
    PASS_RECORD, GenerationScheduler, PassLog, _ENGINE_PHASES, _PassRing)
from bigdl_tpu.telemetry.debugz import Debugz, DebugzServer   # noqa: E402

VOCAB, CHUNK = 40, 4
HYBRID = dict(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=3,
              hybrid_layer_pattern=[0, 1, 1], moe_layer_freq=[0, 1, 1],
              num_attention_heads=4, num_key_value_heads=1,
              swa_num_key_value_heads=2, head_dim=16, v_head_dim=8,
              partial_rotary_factor=0.5, rope_theta=1e6, swa_rope_theta=1e4,
              sliding_window=8, attention_value_scale=0.707,
              add_swa_attention_sink_bias=True, intermediate_size=48,
              moe_intermediate_size=16, n_routed_experts=4,
              num_experts_per_tok=2, norm_topk_prob=True,
              layernorm_epsilon=1e-5)


def _lm():
    return transformer_lm(VOCAB, hidden_size=32, num_layers=2, num_heads=2,
                          filter_size=64, max_len=64)


def _hybrid():
    return mimo_v2(HYBRID, 64).eval_mode()


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB + 1, n).astype(np.int32) for n in lens]


def _logged_chunks(pool):
    """Wraps the pool's two dispatch entries; returns what they were handed
    of chunks, in order: ``("alone" | "joint", width, index, slot)``."""
    sent = []
    alone, step = pool.chunk_prefill_into, pool.decode_dispatch

    def chunk_prefill_into(toks, slot, index):
        sent.append(("alone", len(toks), index, slot))
        return alone(toks, slot, index)

    def decode_dispatch(chunk=None):
        if chunk is not None:
            toks, slot, index = chunk
            sent.append(("joint", len(toks), index, slot))
        return step(chunk)

    pool.chunk_prefill_into = chunk_prefill_into
    pool.decode_dispatch = decode_dispatch
    return sent


@pytest.fixture(scope="module", params=["lm", "prefix-cache-lm", "hybrid"])
def served(request):
    """A short request decodes; two longer prompts arrive at its fifth token
    and prefill in chunks beside it.  ``(stats, records, chunks sent)`` after
    a drained shutdown.  ``prefix-cache-lm`` keeps a prefix cache: each
    prompt's last chunk goes out alone (its keys are extracted right after
    it), the others ride a step."""
    model = _hybrid() if request.param == "hybrid" else _lm()
    cache = dict(prefix_cache_bytes=1 << 20, prefix_granularity=CHUNK) \
        if request.param == "prefix-cache-lm" else {}
    engine = GenerationScheduler(model, slots=3, prefill_chunk=CHUNK,
                                 start=False, **cache)
    sent = _logged_chunks(engine.pool)
    engine.start()
    try:
        first, *later = _prompts(5, (3, 21, 14))
        joint_pass.serve_beside_a_decoding_slot(engine, first, later,
                                                new_first=40)
    finally:
        engine.shutdown()
    stats = engine.stats()
    assert stats["chunks_alone"] == (2 if cache else 0)
    return stats, stats["pass_log"].records(), sent


def test_the_phases_of_a_record_tile_its_gap(served):
    _stats, rec, _sent = served
    timed = rec[np.isfinite(rec["gap_s"])]
    assert len(timed) >= 40
    phases = sum(timed[k] for k in _ENGINE_PHASES)
    assert np.abs(phases - timed["gap_s"]).max() <= 1e-6
    # and where no gap is (the first step, one after a pause) they tile the
    # time since the previous record
    assert np.allclose(sum(rec[k] for k in _ENGINE_PHASES)[1:],
                       np.diff(rec["t"]), atol=1e-6)
    assert np.isnan(rec["gap_s"][0])


def test_the_sums_are_the_records_added_up(served):
    stats, rec, _sent = served
    assert len(rec) == stats["decode_steps"] == stats["pass_log"]["seq"]
    assert list(rec["seq"]) == list(range(1, len(rec) + 1))
    timed = rec[np.isfinite(rec["gap_s"])]
    plain = reader.group_of(timed) == "plain"
    for kind, m in (("plain", plain), ("prefill", ~plain)):
        assert m.sum() == stats["step_gaps"][kind] > 0
        assert timed["gap_s"][m].sum() == pytest.approx(
            stats["step_gap_seconds"][kind], rel=1e-9)
    assert rec["joint"].sum() == stats["chunks_joint"]
    assert rec["chunks_alone"].sum() == stats["chunks_alone"]
    assert rec["emitted"].sum() == stats["tokens_emitted"]
    assert rec["positions_live"].sum() == stats["decode_positions_live"]
    assert rec["positions_read"].sum() == stats["decode_positions_read"]
    assert (rec["n_active"] >= rec["emitted"]).all()


def test_a_record_names_the_chunk_its_gap_ran(served):
    """The widths and first positions ``_chunk_prefill_step`` chose, on the
    record of the step dispatched after them: ``joint`` where the chunk
    rode the step, ``chunks_alone`` where it went out before it (behind a
    prefix cache, the two prompts' last chunks)."""
    stats, rec, sent = served
    assert sent and {w for _, w, _, _ in sent} <= {1, 2, CHUNK}
    alone = [c for c in sent if c[0] == "alone"]
    assert len(alone) == stats["chunks_alone"]
    assert len(sent) - len(alone) == stats["chunks_joint"] > 0
    chunked = rec[(rec["joint"] > 0) | (rec["chunks_alone"] > 0)]
    # at most one chunk a pass beside a decoding slot (the budget)
    assert (chunked["joint"] + chunked["chunks_alone"] == 1).all()
    got = [("joint" if r["joint"] else "alone", r["chunk_width"],
            r["chunk_index"], r["chunk_slot"]) for r in chunked]
    assert got == sent
    # 20 positions in chunks of 4 from 0; 13 in three and a suffix-aligned
    # remainder (TransformerLM) or a one-wide one (the rings)
    assert [i for _, w, i, s in sent if s == sent[0][3]] == [0, 4, 8, 12, 16]
    rest = rec[(rec["joint"] == 0) & (rec["chunks_alone"] == 0)]
    assert (rest["chunk_width"] == 0).all()
    assert (rest["chunk_index"] == -1).all()
    assert (rest["chunk_slot"] == -1).all()
    # the first request's bucketed prefill, before the first step
    assert rec["bucketed"][0] == 1 and rec["bucketed"].sum() == 1


def test_a_trace_is_booked_to_the_pass_that_waited_for_it(served):
    _stats, rec, _sent = served
    assert rec["compiles"][0] >= 2          # the prefill and the step
    assert rec["compiles"].sum() >= 4       # and the chunk programs
    assert (rec["compiles"][-20:] == 0).all()


def test_the_ring_wraps_and_counts_what_it_dropped():
    ring = _PassRing(capacity=8)
    row = np.zeros((), PASS_RECORD)
    for i in range(1, 21):
        row["seq"], row["t"] = i, float(i)
        # the engine hands over running totals: a record's share is the
        # difference from the record before, also across the wrap
        row["emit"], row["gc_s"], row["compiles"] = 0.5 * i, 0.25 * i, 3 * i
        ring.append(row.item())
        if i == 3:
            assert list(ring.records()["emit"]) == [0.5, 0.5, 0.5]
    assert ring.dropped() == 12
    got = ring.records()
    assert list(got["seq"]) == list(range(13, 21))
    assert (got["emit"] == 0.5).all() and (got["gc_s"] == 0.25).all()
    assert (got["compiles"] == 3).all() and (got["idle"] == 0.0).all()
    assert list(ring.records(15.0, 18.0)["seq"]) == [15, 16, 17]
    assert list(ring.records(t1=14.5)["seq"]) == [13, 14]
    log = PassLog(ring, 20)
    assert json.loads(json.dumps(log, sort_keys=True)) \
        == {"capacity": 8, "dropped": 12, "seq": 20}
    assert _PassRing().capacity == 16384
    got = ring.records()
    got["seq"] = 0                          # a copy, not the ring
    assert ring.records()["seq"][0] == 13


def test_the_log_outlives_a_killed_engine_and_its_pool():
    engine = GenerationScheduler(_lm(), slots=2, prefill_chunk=CHUNK)
    t0 = time.perf_counter()
    (prompt,) = _prompts(7, (9,))
    engine.submit_async(prompt, 12).result(timeout=300)
    t1 = time.perf_counter()
    stats = engine.stats()
    engine.kill()
    engine.shutdown(drain=False, timeout=60)
    assert not engine.alive and engine.pool.caches is None
    del engine
    gc.collect()
    rec = stats["pass_log"].records(t0, t1)
    assert len(rec) == stats["pass_log"]["seq"] >= 12
    assert rec["emitted"].sum() == 12
    assert len(stats["pass_log"].records(t1, None)) == 0


def test_stats_stay_json_through_the_status_page():
    server = ModelServer(generator=_lm(), slots=2)
    page = DebugzServer(Debugz(statusz_fn=lambda: {
        "generation": server.generation_stats()}))
    page.start()
    try:
        (prompt,) = _prompts(8, (5,))
        server.submit_generate_async(prompt, 6).result(timeout=300)
        conn = http.client.HTTPConnection("127.0.0.1", page.port, timeout=30)
        conn.request("GET", "/statusz")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        conn.close()
        plain = json.loads(json.dumps(server.generation_stats(),
                                      sort_keys=True))
    finally:
        page.stop()
        server.shutdown()
    assert resp.status == 200
    for stats in (body["generation"], plain):
        assert stats["pass_log"] == {"seq": stats["decode_steps"],
                                     "capacity": 16384, "dropped": 0}
        assert "cache_write_programs" not in stats


def test_a_collection_shows_in_the_pass_it_stopped_and_the_hook_goes():
    """``gc.collect()`` from a token's callback runs after that step's
    read-back returned: inside the next gap."""
    before = list(gc.callbacks)
    engine = GenerationScheduler(_lm(), slots=2, prefill_chunk=CHUNK)
    assert len(gc.callbacks) == len(before) + 1
    seen = []

    def on_token(_tok):
        seen.append(_tok)
        if len(seen) == 6:
            gc.collect()

    was = gc.isenabled()
    gc.disable()            # no collection but the forced one
    try:
        (prompt,) = _prompts(9, (5,))
        engine.submit_async(prompt, 12, on_token=on_token).result(timeout=300)
    finally:
        engine.shutdown()       # the last step's read-back comes after
        if was:
            gc.enable()
    rec = engine.stats()["pass_log"].records()
    assert gc.callbacks == before
    assert len(rec) >= 12
    hit = np.flatnonzero(rec["gc_s"] > 0)
    assert list(hit) == [6]                 # the record after the sixth token
    assert rec["gc_s"][6] <= rec["emit"][6]


def test_a_sleeping_callback_makes_one_slow_pass_whose_emit_holds_it():
    """The callback of the 20th token sleeps after that step's read-back
    returned: the next record's ``emit`` holds the time."""
    engine = GenerationScheduler(_lm(), slots=2, prefill_chunk=CHUNK)
    seen = []

    def on_token(_tok):
        seen.append(_tok)
        if len(seen) == 20:
            time.sleep(0.1)

    t_open = time.perf_counter()
    try:
        (prompt,) = _prompts(10, (5,))
        engine.submit_async(prompt, 40, on_token=on_token).result(timeout=300)
    finally:
        engine.shutdown()
    rec = engine.stats()["pass_log"].records(t_open, None)
    after = int(np.searchsorted(np.cumsum(rec["emitted"]), 20)) + 1
    assert rec["emit"][after] >= 0.1 and rec["emit"].argmax() == after
    assert rec["gap_s"][after] >= rec["emit"][after]
    timed = rec[np.isfinite(rec["gap_s"])]
    (row,) = [r for r in reader.slow_rows(timed, t_open)
              if r["t"] == pytest.approx(rec["t"][after] - t_open)]
    assert row["group"] == "plain" and row["emit"] == rec["emit"][after]
    assert row["gap_s"] == pytest.approx(
        sum(row[k] for k in _ENGINE_PHASES), abs=1e-6)
    _slow, median = reader.slow_passes(timed)
    assert reader.stall_share(timed, 1.0) >= 100.0 * (0.1 - median[0])


def test_a_snapshots_seq_delimits_the_records_its_sums_hold():
    """``stats()`` from another thread while the engine runs: between any
    two snapshots the step gaps' counts and seconds grew by the records
    whose ``seq`` lies between theirs."""
    engine = GenerationScheduler(_lm(), slots=3, prefill_chunk=CHUNK)
    snaps, stop = [], threading.Event()

    def poll():
        while not stop.is_set():
            snaps.append(engine.stats())
            time.sleep(0.001)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    try:
        futs = [engine.submit_async(p, 30)
                for p in _prompts(11, (3, 17, 9, 26))]
        [f.result(timeout=300) for f in futs]
    finally:
        stop.set()
        poller.join(30)
        engine.shutdown()
    assert not poller.is_alive()
    snaps.append(engine.stats())
    rec = snaps[-1]["pass_log"].records()
    assert len({s["pass_log"]["seq"] for s in snaps}) >= 3
    for a, b in zip(snaps, snaps[1:]):
        lo, hi = a["pass_log"]["seq"], b["pass_log"]["seq"]
        part = rec[(rec["seq"] > lo) & (rec["seq"] <= hi)
                   & np.isfinite(rec["gap_s"])]
        plain = reader.group_of(part) == "plain"
        for kind, m in (("plain", plain), ("prefill", ~plain)):
            assert m.sum() == b["step_gaps"][kind] - a["step_gaps"][kind]
            assert part["gap_s"][m].sum() == pytest.approx(
                b["step_gap_seconds"][kind] - a["step_gap_seconds"][kind],
                abs=1e-9)


def test_the_two_spans_carry_the_records_seq():
    """``serving/decode_dispatch`` and ``serving/readback`` say which
    dispatch they are of, the read-back's first: the end of
    ``serving/readback#seq=n#`` is record n's ``t``."""
    from bigdl_tpu import telemetry
    telemetry.enable()
    telemetry.reset()
    try:
        engine = GenerationScheduler(_lm(), slots=2, prefill_chunk=CHUNK)
        try:
            (prompt,) = _prompts(12, (5,))
            engine.submit_async(prompt, 8).result(timeout=300)
        finally:
            engine.shutdown()
        spans = telemetry.finished_spans()
    finally:
        telemetry.reset()
        telemetry.disable()
    rec = engine.stats()["pass_log"].records()
    back = {s.args["seq"]: s for s in spans if s.name == "serving/readback"}
    sent = [s.args["seq"] for s in spans
            if s.name == "serving/decode_dispatch"]
    assert sent == list(rec["seq"]) == sorted(back)
    assert all(list(s.args) == ["seq"] for s in back.values())
    for r in rec:
        span = back[int(r["seq"])]
        assert span.t_start <= r["t"] <= span.t_end
        assert span.t_end - r["t"] < 0.05


def test_the_readers_take_the_engines_records(served):
    """The benchmark's readers on a real log: the window is the records by
    ``t``, and the check lines up the records and the sums."""
    stats, rec, _sent = served
    zero = {"pass_log": {"seq": 0}, "step_gaps": {"plain": 0, "prefill": 0},
            "step_gap_seconds": {"plain": 0.0, "prefill": 0.0},
            "chunks_joint": 0}
    obs = {"stats0": zero, "stats1": stats, "t_open": float(rec["t"][0]),
           "t_close": float(rec["t"][-1]) + 1.0}
    win = reader.window(obs)
    assert len(win) == len(rec) - 1         # the first has no gap
    assert reader.token_gap_p95_ms(win) >= 1e3 * np.median(win["gap_s"])
    # the full-width chunk passes, the slow ones (over three medians of
    # their group: here a pass that compiled) left to ``stall_share``
    full = reader.full_chunk_passes(win, CHUNK)
    slow, _median = reader.slow_passes(win)
    assert (full["chunk_width"] == CHUNK).all() and len(full) <= 8
    assert len(full) + slow.sum() >= 5
    assert not np.isin(full["seq"], win["seq"][slow]).any()
    out = reader.check(obs)
    assert out["records"] == len(win)
    assert out["phase_sum_error_max_s"] <= 1e-6
    for key in ("gaps_plain", "gaps_prefill", "joint"):
        assert out[key][0] == out[key][1]
    for key in ("gap_seconds_plain", "gap_seconds_prefill"):
        assert out[key][0] == pytest.approx(out[key][1], rel=1e-9)

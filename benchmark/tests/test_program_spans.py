"""The readers of what the serving engine measures of itself
(``harness/program_spans.py`` and the seven ``layer_metrics`` that use
it): the counter readers on synthetic ``stats0``/``stats1``, and the idle
attribution on a small trace of the engine recorded on a v5e chip.

The recording is this file run as a script on the chip, the way
``record_trace.py`` records ``probe_1chip.xplane.pb``:

    chiprun -- python3 benchmark/tests/test_program_spans.py

A one-layer ``TransformerLM`` behind ``GenerationScheduler`` (2 slots,
16-token chunks), warmed up, then traced for three requests: the first two
overlap, an ``on_token`` callback sleeps 3 ms per token (host work the
device waits for, under ``serving/emit``), and a 30 ms pause before the
third leaves the engine blocked on its queue (``serving/idle``).  The
kept file leaves out the ``/host:metadata`` plane (the programs' HLO
protos, which no reader opens).
"""
import os
import sys

if __name__ == "__main__":      # on the chip: no conftest sets the path
    _bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_bench, os.path.dirname(_bench)]

import pytest

from harness import manifest, program_spans as ps
from harness import trace as tr

PATH = os.path.join(os.path.dirname(__file__), "data",
                    "engine_1chip.xplane.pb")


# ---- counters ---------------------------------------------------------------

def _stats(scale):
    return {
        "iterations": 100 * scale, "decode_dispatches": 90 * scale,
        "pipeline_drains": 9 * scale, "prefill_calls": 50 * scale,
        "admitted": 4 * scale, "queue_wait_seconds": 10.0 * scale,
        "prefill_positions": 3200 * scale,
        "prefill_prompt_tokens": 3000 * scale,
        "engine_phase_seconds": {
            "admit": 0.01 * scale, "prefill_dispatch": 0.02 * scale,
            "decode_dispatch": 0.03 * scale, "readback_wait": 5.0 * scale,
            "emit": 0.04 * scale, "other": 0.05 * scale, "idle": 1.0 * scale},
        "step_gaps": {"plain": 40 * scale, "prefill": 5 * scale},
        "step_gap_seconds": {"plain": 2.0 * scale, "prefill": 0.3 * scale},
    }


def _obs(drop=()):
    s0, s1 = _stats(1), _stats(3)
    for key in drop:
        s1.pop(key)
    return {"stats0": s0, "stats1": s1, "seconds": 50.0, "t_open": 0.0,
            "t_close": 50.0, "tokens": {"prompt": 5990.0},
            "requests": [{"stamps": [1.0, 2.0], "prefill_calls": 7},
                         {"stamps": [60.0], "prefill_calls": 9},
                         {"stamps": [], "prefill_calls": 3}]}


READERS = ["engine_host_ms", "pipeline_drain_share", "queue_wait_mean_ms",
           "engine_iter_plain_ms", "engine_iter_prefill_ms",
           "prefill_positions_per_s"]


@pytest.mark.parametrize("name, expected", [
    ("engine_host_ms", 1e3 * 0.30 / 200),       # 2 x 0.15 s over 200 passes
    ("pipeline_drain_share", 10.0),
    ("queue_wait_mean_ms", 2500.0),
    ("engine_iter_plain_ms", 50.0),
    ("engine_iter_prefill_ms", 60.0),
    ("prefill_positions_per_s", 120.0),
])
def test_counter_readers(name, expected, capsys):
    reader = manifest.load_reader(name)
    assert reader.read(_obs()) == pytest.approx(expected)
    assert reader.DEVICE is False
    if name == "prefill_positions_per_s":
        line = capsys.readouterr().out
        assert '"calls": 100.0' in line and '"mirrored_calls": 7' in line
        assert '"positions": 6400.0' in line
        assert '"mirrored_prompt_tokens": 5990.0' in line


@pytest.mark.parametrize("name, key", [
    ("engine_host_ms", "engine_phase_seconds"),
    ("engine_host_ms", "iterations"),
    ("pipeline_drain_share", "pipeline_drains"),
    ("queue_wait_mean_ms", "admitted"),
    ("engine_iter_plain_ms", "step_gaps"),
    ("engine_iter_prefill_ms", "step_gap_seconds"),
    ("prefill_positions_per_s", "prefill_prompt_tokens"),
])
def test_a_missing_counter_reads_as_left_out(name, key):
    """The parent's ``stats()`` has none of the keys: no crash, no value."""
    assert manifest.load_reader(name).read(_obs(drop=[key])) is None


@pytest.mark.parametrize("name", READERS + ["idle_attributed_share"])
def test_nothing_observed_reads_as_left_out(name):
    assert manifest.load_reader(name).read({}) is None
    old = {"stats0": {"decode_steps": 1}, "stats1": {"decode_steps": 2},
           "seconds": 50.0, "requests": [], "trace": None}
    assert manifest.load_reader(name).read(old) is None


def test_a_mean_needs_ten_gaps():
    obs = _obs()
    obs["stats1"]["step_gaps"]["prefill"] = 5 + 9
    assert manifest.load_reader("engine_iter_prefill_ms").read(obs) is None
    obs["stats1"]["step_gaps"]["prefill"] = 5 + 10
    assert manifest.load_reader("engine_iter_prefill_ms").read(obs) == \
        pytest.approx(60.0)


def test_every_reader_is_in_the_manifest():
    entries = {m["name"]: m for m in manifest.manifest()["per_layer"]}
    for name in READERS + ["idle_attributed_share"]:
        reader, m = manifest.load_reader(name), entries[name]
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == \
            (m["layer"], m["source"], m["moves"])


# ---- spans ------------------------------------------------------------------

def test_innermost_segments():
    spans = [(0.0, 10.0, "it"), (1.0, 3.0, "a"), (3.0, 4.0, "b"),
             (6.0, 9.0, "c"), (7.0, 8.0, "d"), (12.0, 13.0, "idle")]
    assert ps.innermost(spans) == [
        (0.0, 1.0, "it"), (1.0, 3.0, "a"), (3.0, 4.0, "b"), (4.0, 6.0, "it"),
        (6.0, 7.0, "c"), (7.0, 8.0, "d"), (8.0, 9.0, "c"), (9.0, 10.0, "it"),
        (12.0, 13.0, "idle")]


def test_idle_goes_to_the_innermost_span_over_the_gaps_middle():
    spans = [(0.0, 10.0, "serving/iteration"), (1.0, 3.0, "serving/emit"),
             (6.0, 9.0, "serving/readback"), (12.0, 13.0, "serving/idle")]
    gaps = [(1.5, 2.5), (4.0, 5.0), (8.5, 9.1), (10.5, 11.0), (12.0, 12.5)]
    table = ps.idle_by_span(gaps, spans)
    assert table == pytest.approx({
        "serving/emit": 1.0, "serving/iteration": 1.0,
        "serving/readback": 0.6, "unannotated": 0.5, "serving/idle": 0.5})
    assert ps.named_gaps(gaps, spans)[:2] == [
        (1.0, "serving/emit"), (1.0, "serving/iteration")]
    # host work: emit and the iteration's own time, 2.0 of 3.6 s
    assert ps.attributed_share(table) == pytest.approx(100 * 2.0 / 3.6)
    assert ps.attributed_share({}) is None


@pytest.fixture(scope="module")
def recorded():
    t = tr.Trace(PATH, window="bench.wait_window")
    return t, ps.engine_spans(PATH)


def test_engine_spans_of_the_recording(recorded):
    _, spans = recorded
    names = {n for _, _, n in spans}
    assert {"serving/iteration", "serving/idle", "serving/admit",
            "serving/prefill", "serving/decode_dispatch", "serving/readback",
            "serving/emit"} <= names
    assert "serving/submit" not in names        # the caller's thread
    # arguments ride the annotation's name and are cut off it
    assert all("#" not in n for n in names)
    # one thread: children lie inside their iteration (a pass that was
    # open when the profiler started or stopped has its children in the
    # trace and not itself)
    its = [(s, e) for s, e, n in spans if n == "serving/iteration"]
    first, last = min(s for s, _ in its), max(e for _, e in its)
    for s, e, n in spans:
        if (n in ps.WORK or n == "serving/readback") and first < s < last:
            assert any(a <= s and e <= b for a, b in its), (n, s, e)


def test_idle_table_of_the_recording(recorded):
    t, _ = recorded
    table = ps.idle_table({"trace": t}, path=PATH)
    gaps = ps.device_gaps(t)
    assert sum(table.values()) == pytest.approx(tr.measure(gaps), rel=1e-9)
    assert sum(table.values()) == pytest.approx(
        t.window_s - t.busy_s(), rel=1e-6)
    # the sleeping callback: the device waits under serving/emit for most
    # of the window; the 30 ms pause lies under serving/idle
    assert max(table, key=table.get) == "serving/emit"
    assert table["serving/idle"] == pytest.approx(0.030, rel=0.25)
    share = ps.attributed_share(table)
    assert 50.0 < share < 100.0
    assert share == pytest.approx(
        100.0 * sum(v for k, v in table.items()
                    if k in ps.WORK or k == ps.ITERATION)
        / (t.window_s - t.busy_s()), rel=1e-6)


def test_reader_on_the_recording(recorded, capsys, monkeypatch):
    t, _ = recorded
    monkeypatch.setattr(ps, "newest_xplane", lambda root=None: PATH)
    reader = manifest.load_reader("idle_attributed_share")
    assert reader.DEVICE is True
    value = reader.read({"trace": t})
    assert value == pytest.approx(
        ps.attributed_share(ps.idle_table({"trace": t}, path=PATH)))
    out = capsys.readouterr().out
    assert "[idle_by_engine_span]" in out
    # the five longest gaps, longest first: the 30 ms pause leads
    longest = [l for l in out.splitlines()
               if l.startswith("[idle_longest_gaps]")][0]
    assert '"longest": [[30.' in longest and '"serving/idle"]' in longest
    assert longest.count("serving/") == 5


def test_a_trace_without_engine_spans_reads_as_left_out(monkeypatch):
    """The parent commit annotates nothing: the probe trace stands in."""
    probe = os.path.join(os.path.dirname(PATH), "probe_1chip.xplane.pb")
    monkeypatch.setattr(ps, "newest_xplane", lambda root=None: probe)
    t = tr.Trace(probe)
    assert ps.engine_spans(probe) == []
    assert manifest.load_reader("idle_attributed_share").read(
        {"trace": t}) is None


def test_newest_xplane(tmp_path):
    assert ps.newest_xplane(str(tmp_path)) is None
    for i, cell in enumerate(("a", "b")):
        d = tmp_path / cell / "plugins" / "profile" / "run"
        d.mkdir(parents=True)
        f = d / "host.xplane.pb"
        f.write_bytes(b"")
        os.utime(f, (1000 + i, 1000 + i))
    assert ps.newest_xplane(str(tmp_path)).endswith(
        os.path.join("b", "plugins", "profile", "run", "host.xplane.pb"))


# ---- the recording ----------------------------------------------------------

def record() -> int:
    import glob
    import shutil
    import time

    import jax
    import numpy as np
    from bigdl_tpu.models import transformer_lm
    from bigdl_tpu.serving import ModelServer
    from bigdl_tpu.serving.generation import GenerationScheduler
    from bigdl_tpu.utils import set_seed

    if jax.devices()[0].platform != "tpu":
        print("record: needs a TPU", file=sys.stderr)
        return 1
    out = os.path.join("chiprun_out", "engine_probe")
    shutil.rmtree(out, ignore_errors=True)
    set_seed(0)
    lm = transformer_lm(vocab_size=255, hidden_size=128, num_layers=1,
                        num_heads=2, filter_size=256, max_len=128).eval_mode()
    engine = GenerationScheduler(lm, slots=2, prefill_chunk=16)
    server = ModelServer(generator=engine)
    rng = np.random.default_rng(0)

    def prompt(n):
        return rng.integers(1, 256, n).astype(np.int32)

    def slow(_tok):
        time.sleep(0.003)

    # the programs the traced requests need: the 16-token chunk (every
    # prompt is a whole number of them, to keep the trace's table of
    # operation names short), decode, seed
    for n in (33, 17):
        server.submit_generate_async(prompt(n), 3).result(timeout=600)
    time.sleep(0.05)
    # a file small enough to commit: no Python call events, no HLO protos
    # (run.py traces with the defaults; the annotations are the same)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.wait_window"):
        a = server.submit_generate_async(prompt(33), 4, on_token=slow)
        b = server.submit_generate_async(prompt(17), 3, on_token=slow)
        a.result(timeout=60)
        b.result(timeout=60)
        time.sleep(0.03)
        server.submit_generate_async(prompt(17), 2).result(timeout=60)
    jax.profiler.stop_trace()
    stats = engine.stats()
    server.shutdown()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    print("file", path, os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:8]:
                print("     ", repr(ev.name[:90]), ev.start_ns, ev.duration_ns)
    t = tr.Trace(path, window="bench.wait_window")
    table = ps.idle_table({"trace": t}, path=path)
    print("window_s", t.window_s, "busy_s", t.busy_s())
    print("idle_by_engine_span", table)
    print("idle_attributed_share", ps.attributed_share(table))
    print("stats", {k: v for k, v in stats.items() if k != "prefix_cache"})
    kept = os.path.join("chiprun_out", "engine_1chip.xplane.pb")
    with open(path, "rb") as f, open(kept, "wb") as g:
        g.write(_without_plane(f.read(), b"/host:metadata"))
    print("kept", kept, os.path.getsize(kept))
    again = ps.idle_table({"trace": tr.Trace(kept, window="bench.wait_window")},
                          path=kept)
    assert again == table, (again, table)
    return 0


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _without_plane(space: bytes, name: bytes) -> bytes:
    """The serialized ``XSpace`` less the planes called ``name`` (its
    field 1, each an ``XPlane`` whose field 2 is the name).  The programs'
    HLO protos live on ``/host:metadata``, four fifths of the file; no
    reader opens that plane."""
    out, i = bytearray(), 0
    while i < len(space):
        start = i
        key, i = _varint(space, i)
        if key & 7 != 2:
            raise ValueError("an XSpace holds only length-delimited fields")
        size, i = _varint(space, i)
        body = space[i:i + size]
        i += size
        if key >> 3 == 1 and b"\x12" + bytes([len(name)]) + name in body[:64]:
            continue
        out += space[start:i]
    return bytes(out)


if __name__ == "__main__":
    sys.exit(record())

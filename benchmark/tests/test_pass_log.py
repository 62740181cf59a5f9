"""The readers of the engine's pass log (``harness/pass_log.py`` and the four
``layer_metrics`` that use it) on a synthetic log with known answers, and
the join to a device trace on synthetic read-back ends."""
import json

import numpy as np
import pytest

from harness import manifest, pass_log as pl

FIELDS = [("seq", "i8"), ("t", "f8"), ("gap_s", "f8"), ("joint", "i1"),
          ("chunks_alone", "i4"), ("bucketed", "i4"), ("chunk_width", "i4"),
          ("chunk_index", "i4"), ("chunk_slot", "i4"), ("n_active", "i4"),
          ("emitted", "i4"), ("positions_live", "i8"),
          ("positions_read", "i8"), ("drained", "i1")] \
    + [(k, "f8") for k in pl.PHASES] + [("gc_s", "f8"), ("compiles", "i4")]
WIDTH = 64


class Log(dict):
    """What ``stats()["pass_log"]`` is to a reader."""

    def __init__(self, rows, seq):
        super().__init__(seq=seq, capacity=16384, dropped=0)
        self.rows = rows

    def records(self, t0=None, t1=None):
        keep = np.ones(len(self.rows), bool)
        if t0 is not None:
            keep &= self.rows["t"] >= t0
        if t1 is not None:
            keep &= self.rows["t"] < t1
        return self.rows[keep].copy()


def _rows():
    """A window of 1,000 s from t = 100: 700 plain passes of 10 ms (4
    tokens each), 270 full-width chunk passes whose gap grows with the
    chunk's place (20 ms at 0, 21 at 64, 23 at 128; 90 each, 4 tokens),
    30 remainders of 32 at 15 ms, one plain pass of 0.5 s whose time
    sits in ``emit`` and one full-width chunk pass of 0.3 s (a late
    chunk); before the window a pass with no gap and one outside it."""
    gaps = [(0.010, 0, 0, -1)] * 700
    for index, gap in ((0, 0.020), (64, 0.021), (128, 0.023)):
        gaps += [(gap, 1, WIDTH, index)] * 90
    gaps += [(0.015, 1, 32, 192)] * 30
    gaps += [(0.5, 0, 0, -1), (0.3, 1, WIDTH, 128)]
    order = np.random.default_rng(0).permutation(len(gaps))
    rows = np.zeros(len(gaps) + 2, FIELDS)
    rows["gap_s"][0] = np.nan            # a pause before it
    rows["t"][0], rows["emitted"][0] = 100.0, 1
    rows["gap_s"][1], rows["t"][1], rows["emitted"][1] = 9.0, 99.0, 4
    for n, i in enumerate(order, start=2):
        gap, chunked, width, index = gaps[i]
        r = rows[n]
        r["gap_s"], r["emitted"], r["n_active"] = gap, 4, 4
        r["positions_live"], r["positions_read"] = 1000, 1024
        r["chunk_slot"] = 2 if chunked else -1
        r["chunks_alone"], r["chunk_width"], r["chunk_index"] = \
            chunked, width, index
        r["readback_wait"], r["other"] = 0.008, gap - 0.008
        if gap == 0.5:
            r["readback_wait"], r["other"], r["emit"] = 0.008, 0.002, 0.49
            r["gc_s"] = 0.25
    rows["t"][2:] = 100.0 + np.cumsum(rows["gap_s"][2:])
    rows["seq"] = np.arange(1, len(rows) + 1)
    return rows


def _obs(rows=None, **over):
    rows = _rows() if rows is None else rows
    timed = rows[np.isfinite(rows["gap_s"])][1:]
    plain = (timed["chunks_alone"] == 0)
    stats1 = {
        "pass_log": Log(rows, int(rows["seq"][-1])),
        "step_gaps": {"plain": int(plain.sum()), "prefill": int((~plain).sum())},
        "step_gap_seconds": {"plain": float(timed["gap_s"][plain].sum()),
                             "prefill": float(timed["gap_s"][~plain].sum())},
        "chunks_joint": 0}
    stats0 = {"pass_log": Log(rows, 2),
              "step_gaps": {"plain": 0, "prefill": 0},
              "step_gap_seconds": {"plain": 0.0, "prefill": 0.0},
              "chunks_joint": 0}
    obs = {"stats0": stats0, "stats1": stats1, "t_open": 100.0,
           "t_close": 1100.0, "cfg": {"serving": {"prefill_chunk": WIDTH}},
           "gaps": [(float(g), 0.0) for g in np.repeat(
               timed["gap_s"], timed["emitted"])]}
    obs.update(over)
    return obs


@pytest.mark.parametrize("name, expected", [
    # 4,008 token gaps: 2,800 of 10 ms, 120 of 15, 360 each of 20, 21 and
    # 23, 4 of 300 and of 500: the 95th percentile lies among the 23s
    ("engine_token_gap_p95_ms", 23.0),
    # the chunk pass of 0.3 s is a slow pass: in neither number (its mean
    # would read 1.0 ms more, the late third's 3.0 ms)
    ("chunk_full_pass_ms", (20.0 + 21.0 + 23.0) / 3),
    ("chunk_position_cost_ms", 3.0),
    # 0.5 s over the plain passes' median of 10 ms and 0.3 s over the
    # chunk passes' of 21, in a window of 1,000 s
    ("stall_share", 100.0 * (0.49 + 0.279) / 1000.0),
])
def test_the_readers_on_a_log_with_known_answers(name, expected, capsys):
    reader = manifest.load_reader(name)
    assert reader.read(_obs()) == pytest.approx(expected, rel=1e-9)
    assert reader.DEVICE is False and reader.SOURCE == "program_span"
    entry = [m for m in manifest.manifest()["per_layer"]
             if m["name"] == name][0]
    assert (reader.LAYER, reader.MOVES) == (entry["layer"], entry["moves"])
    assert entry["source"] == reader.SOURCE and entry["better"] == "lower"
    capsys.readouterr()


@pytest.mark.parametrize("name", [
    "engine_token_gap_p95_ms", "chunk_full_pass_ms",
    "chunk_position_cost_ms", "stall_share"])
def test_a_program_without_the_log_gives_none(name):
    reader = manifest.load_reader(name)
    obs = _obs()
    for stats in (obs["stats0"], obs["stats1"]):
        stats.pop("pass_log")
    assert reader.read(obs) is None
    assert reader.read({"t_open": 0.0, "t_close": 1.0}) is None


def test_too_few_chunk_passes_give_none():
    rows = _rows()
    keep = np.ones(len(rows), bool)
    full = np.flatnonzero(rows["chunk_width"] == WIDTH)
    keep[full[80:]] = False                 # 80 left: under 30 a third
    obs = _obs(rows[keep])
    assert manifest.load_reader("chunk_position_cost_ms").read(obs) is None
    assert manifest.load_reader("chunk_full_pass_ms").read(obs) is not None
    keep[full[5:]] = False
    assert manifest.load_reader("chunk_full_pass_ms").read(
        _obs(rows[keep])) is None


def test_position_cost_reads_medians():
    """One pass of 0.06 s (under three medians: not a slow pass) among
    the late third moves its mean by 0.4 ms and its median not at all."""
    rows = _rows()
    late = np.flatnonzero((rows["chunk_index"] == 128)
                          & (rows["gap_s"] == 0.023))
    rows["gap_s"][late[0]] = 0.060
    obs = _obs(rows)
    assert manifest.load_reader("chunk_position_cost_ms").read(obs) \
        == pytest.approx(3.0)
    assert manifest.load_reader("chunk_full_pass_ms").read(obs) \
        == pytest.approx((64.0 * 90 + 37.0) / 270)


@pytest.mark.parametrize("first", ["stall_share", "chunk_full_pass_ms"])
def test_the_tables_come_once_a_run_whichever_reader_is_first(first, capsys):
    obs = _obs()
    manifest.load_reader(first).read(obs)
    assert capsys.readouterr().out.count("[pass_log] ") == 1
    manifest.load_reader("engine_token_gap_p95_ms").read(obs)
    assert capsys.readouterr().out == ""


def test_the_printed_tables(capsys):
    manifest.load_reader("engine_token_gap_p95_ms").read(_obs())
    lines = dict(line[1:].split("] ", 1)
                 for line in capsys.readouterr().out.splitlines())
    table = {(r["group"], r["chunk_width"]): r
             for r in json.loads(lines["pass_log"])["rows"]}
    assert set(table) == {("plain", 0), ("alone", WIDTH), ("alone", 32)}
    assert table["plain", 0]["count"] == 701
    assert table["plain", 0]["p50_ms"] == pytest.approx(10.0)
    assert table["alone", WIDTH]["count"] == 271
    assert table["alone", WIDTH]["token_gap_share"] == pytest.approx(
        100.0 * 1084 / 4008)
    assert table["alone", WIDTH]["p95_ms"] == pytest.approx(23.0)
    assert table["alone", WIDTH]["mean_ms"] == pytest.approx(
        (64.0 * 90 + 300.0) / 271)
    # beyond the percentile (23 ms) lie the two slow passes' tokens alone
    assert table["plain", 0]["beyond_p95_share"] == pytest.approx(50.0)
    assert table["alone", WIDTH]["beyond_p95_share"] == pytest.approx(50.0)
    assert table["alone", 32]["positions_read_mean"] == 1024.0
    check = json.loads(lines["pass_log_check"])
    assert check["engine_token_gap_p95_ms"] == pytest.approx(23.0)
    assert check["difference_pct"] == pytest.approx(0.0, abs=1e-9)
    assert check["token_gaps"] == check["stamp_gaps"] == 4008
    assert check["records"] == 1002
    assert check["gaps_plain"] == [701, 701.0]
    assert check["gap_seconds_prefill"][0] == pytest.approx(
        check["gap_seconds_prefill"][1])
    assert check["phase_sum_error_max_s"] <= 1e-9
    slow = json.loads(lines["slow_passes"])
    assert slow["passes"] == 2 and slow["seconds"] == pytest.approx(0.8)
    row, chunked = sorted(slow["rows"], key=lambda r: -r["gap_s"])
    assert row["emit"] == pytest.approx(0.49) and row["gc_s"] == 0.25
    assert row["group"] == "plain" and row["n_active"] == 4
    assert (row["positions_live"], row["positions_read"]) == (1000, 1024)
    assert (chunked["group"], chunked["chunk_width"], chunked["chunk_index"],
            chunked["chunk_slot"]) == ("alone", WIDTH, 128, 2)
    assert row["next_gap_s"] in (0.010, 0.015, 0.020, 0.021, 0.023)
    assert 0.0 < row["t"] < 1000.0 and "device_busy_s" not in row
    assert "pass_log_trace" not in lines
    early, late = json.loads(lines["chunk_position"])["thirds"]
    assert (early["third"], early["passes"], early["chunk_index_max"],
            early["p50_ms"]) == ("early", 90, 0, pytest.approx(20.0))
    assert (late["chunk_index_min"], late["p50_ms"]) \
        == (128, pytest.approx(23.0))
    assert all(r["drained_share"] == 0.0 for r in table.values())


class _Trace:
    """The part of ``harness.trace.Trace`` the join reads: one device busy
    but for 30 ms in every 100."""
    lo, hi = 50.0, 54.0
    ops = {"/device:TPU:0": [(50.0 + 0.1 * i, 50.07 + 0.1 * i, "op")
                             for i in range(40)]}

    def devices(self):
        return sorted(self.ops)


def test_the_join_to_the_device_trace(monkeypatch):
    """The trace's clock is ``perf_counter`` less 50 s here: the end of
    ``serving/readback#seq=n#`` is record n's ``t``."""
    rows = np.zeros(40, FIELDS)
    rows["seq"] = np.arange(1, 41)
    rows["gap_s"] = 0.1
    rows["t"] = 100.0 + 0.1 * np.arange(1, 41)
    ends = {int(r["seq"]): r["t"] - 50.0 + 1e-6 * (i % 3)
            for i, r in enumerate(rows)}
    ends[999] = 1.0                        # a read-back with no record
    offset, spread, matched = pl.clock_offset(rows, ends)
    assert offset == pytest.approx(-50.0 + 1e-6) and matched == 40
    assert spread == pytest.approx(1e-6)
    assert pl.clock_offset(rows, {}) is None
    monkeypatch.setattr(pl, "readback_ends", lambda path: ends)
    busy, joined = pl.device_busy({"trace": _Trace()}, rows, path="x")
    assert joined[2] == 40
    assert busy(100.5, 0.1) == pytest.approx([0.07, 0.1], abs=1e-5)
    assert busy(100.55, 0.1) == pytest.approx([0.07, 0.1], abs=1e-5)
    # began before the trace did: the part inside it
    assert busy(100.05, 0.1) == pytest.approx([0.05, 0.05], abs=1e-5)
    assert busy(104.5, 0.1) is None and busy(99.0, 0.1) is None
    slow = pl.slow_rows(np.concatenate([rows, rows[-1:]]), 100.0, busy)
    assert slow == []
    # a program whose read-backs carry no ``seq``: no join, and no raise
    monkeypatch.setattr(pl, "readback_ends", lambda path: {})
    assert pl.device_busy({"trace": _Trace()}, rows, path="x") is None
    assert pl.device_busy({}, rows) is None

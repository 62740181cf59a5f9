"""The serving engine's pass log as the per-layer readers take it.

``GenerationScheduler.stats()["pass_log"]`` gives one record a decode
step's read-back (``bigdl_tpu/serving/generation.py``, ``PASS_RECORD``):
when it returned (``t``), the step gap it closed (``gap_s``), which prefill
programs the device ran in that gap (``joint``, ``chunks_alone``,
``bucketed``, and the chunk's ``chunk_width``, ``chunk_index``,
``chunk_slot``), the step (``n_active``, ``emitted``, ...), the seconds the
engine thread spent in each of its seven phases during the gap, and
``gc_s`` and ``compiles``.  The readers take the records with
``t_open <= t < t_close`` through :func:`read`, which also prints the
run's tables once, whichever reader comes first; everything here returns
None where the program keeps no such log (a commit from before it).

A record's *group* is ``joint`` (the step carried a chunk), ``alone`` (a
lone chunk program or a bucketed prefill went out before it) or ``plain``.
In a traced run the end of the annotation ``serving/readback#seq=n#`` is
record n's ``t``: that gives the offset between ``perf_counter`` and the
profiler's clock, and with it the device's busy seconds inside a gap.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from harness import program_spans as ps, result
from harness import trace as tr

PHASES = ("admit", "prefill_dispatch", "decode_dispatch", "readback_wait",
          "emit", "other", "idle")
GROUPS = ("plain", "joint", "alone")
SLOW = 3.0          # a slow pass: its gap over this many medians of its group
THIRD_LEAST = 30    # passes a third, under which a place's cost is not read
READBACK = "serving/readback"


def log_of(obs: Dict[str, Any]):
    log = (obs.get("stats1") or {}).get("pass_log")
    return log if hasattr(log, "records") else None


def window(obs: Dict[str, Any]) -> Optional[np.ndarray]:
    """The records of the window that closed a step gap; None without a
    log."""
    log = log_of(obs)
    if log is None:
        return None
    rec = log.records(obs["t_open"], obs["t_close"])
    return rec[np.isfinite(rec["gap_s"])]


def read(obs: Dict[str, Any], value) -> Optional[float]:
    """What each reader of the log does: ``value`` of the window's
    records, after the run's tables (:func:`report`) the first time any
    of them asks."""
    rec = window(obs)
    if rec is None or not len(rec):
        return None
    if not obs.get("pass_log_reported"):
        obs["pass_log_reported"] = True
        report(obs, rec)
    return value(rec)


def group_of(rec: np.ndarray) -> np.ndarray:
    alone = (rec["chunks_alone"] > 0) | (rec["bucketed"] > 0)
    return np.where(rec["joint"] > 0, "joint",
                    np.where(alone, "alone", "plain"))


def token_gaps(rec: np.ndarray) -> np.ndarray:
    """Every emitted token's gap: a record's ``gap_s`` once a token."""
    return np.repeat(rec["gap_s"], rec["emitted"])


def token_gap_p95_ms(rec: np.ndarray) -> Optional[float]:
    gaps = token_gaps(rec)
    return 1e3 * float(np.percentile(gaps, 95)) if len(gaps) else None


def slow_passes(rec: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``(mask, median)``: which records' gaps are over ``SLOW`` medians
    of their group, and each record's group's median."""
    group = group_of(rec)
    median = np.zeros(len(rec))
    for g in GROUPS:
        if (group == g).any():
            median[group == g] = np.median(rec["gap_s"][group == g])
    return rec["gap_s"] > SLOW * median, median


def full_chunk_passes(rec: np.ndarray, width: int) -> np.ndarray:
    """The passes that carried exactly one chunk, of the full width, and
    no bucketed prefill; the slow passes (:func:`slow_passes`) left out:
    what stops the process for a tenth of a second is no cost of a chunk,
    and ``stall_share`` counts those seconds."""
    one = rec["joint"] + rec["chunks_alone"] == 1
    slow, _median = slow_passes(rec)
    return rec[one & (rec["bucketed"] == 0) & (rec["chunk_width"] == width)
               & ~slow]


def chunk_full_pass_ms(rec: np.ndarray, width: int) -> Optional[float]:
    full = full_chunk_passes(rec, width)
    return 1e3 * float(full["gap_s"].mean()) if len(full) >= 10 else None


def thirds_by_place(rec: np.ndarray, width: int) \
        -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Of the full-width chunk passes, the third whose chunk lies earliest
    in its row and the third whose chunk lies latest; None under
    ``THIRD_LEAST`` passes a third."""
    full = full_chunk_passes(rec, width)
    third = len(full) // 3
    if third < THIRD_LEAST:
        return None
    by_place = full[np.argsort(full["chunk_index"], kind="stable")]
    return by_place[:third], by_place[-third:]


def chunk_position_cost_ms(rec: np.ndarray, width: int) -> Optional[float]:
    """Median gap of the third of the full-width chunk passes whose chunk
    lies latest in its row, less that of the third whose chunk lies
    earliest.  Medians, because a third is a hundred passes or so and one
    pass of 0.06 s among them moves its mean by half a millisecond."""
    thirds = thirds_by_place(rec, width)
    if thirds is None:
        return None
    early, late = thirds
    return 1e3 * float(np.median(late["gap_s"]) - np.median(early["gap_s"]))


def stall_share(rec: np.ndarray, seconds: float) -> Optional[float]:
    """Seconds the slow passes took over their group's median, as a share
    (%) of the window."""
    if not len(rec) or seconds <= 0:
        return None
    slow, median = slow_passes(rec)
    return 100.0 * float((rec["gap_s"] - median)[slow].sum()) / seconds


# ---- the tables -------------------------------------------------------------

def table(rec: np.ndarray) -> List[Dict[str, Any]]:
    """By group and the chunk's width (0: a plain pass, or one after
    bucketed prefills alone): count, share of the token gaps, p50, p95 and
    mean of ``gap_s`` (ms), the share of the token gaps beyond the window's
    95th percentile, the share that drained the pipeline first, and the
    mean of what a pass's step read of a full layer (``positions_read``:
    a slow pass's own stands beside it in ``[slow_passes]``)."""
    group = group_of(rec)
    p95 = np.percentile(token_gaps(rec), 95) if rec["emitted"].sum() else 0.0
    beyond = rec["emitted"] * (rec["gap_s"] > p95)
    rows = []
    keys = sorted({(g, int(w)) for g, w in zip(group, rec["chunk_width"])},
                  key=lambda k: (GROUPS.index(k[0]), -k[1]))
    for g, w in keys:
        m = (group == g) & (rec["chunk_width"] == w)
        gaps = 1e3 * rec["gap_s"][m]
        rows.append({
            "group": g, "chunk_width": w, "count": int(m.sum()),
            "token_gap_share": _share(rec["emitted"][m].sum(),
                                      rec["emitted"].sum()),
            "p50_ms": float(np.median(gaps)),
            "p95_ms": float(np.percentile(gaps, 95)),
            "mean_ms": float(gaps.mean()),
            "beyond_p95_share": _share(beyond[m].sum(), beyond.sum()),
            "drained_share": _share(rec["drained"][m].sum(), m.sum()),
            "positions_read_mean": float(rec["positions_read"][m].mean())})
    return rows


def place_rows(rec: np.ndarray, width: int) -> List[Dict[str, Any]]:
    """The two thirds ``chunk_position_cost_ms`` compares: where their
    chunks lie and their passes' median."""
    thirds = thirds_by_place(rec, width)
    if thirds is None:
        return []
    return [{"third": name, "passes": len(part),
             "chunk_index_min": int(part["chunk_index"].min()),
             "chunk_index_max": int(part["chunk_index"].max()),
             "p50_ms": 1e3 * float(np.median(part["gap_s"]))}
            for name, part in zip(("early", "late"), thirds)]


def _share(part, whole) -> float:
    return 100.0 * float(part) / float(whole) if whole else 0.0


def slow_rows(rec: np.ndarray, t_open: float, busy=None) \
        -> List[Dict[str, Any]]:
    """One row a slow pass; ``busy(t_end, gap_s)`` gives the device's busy
    seconds inside a gap and how much of the gap the trace holds (None
    outside the traced seconds).  ``next_gap_s`` is the gap of the record
    after: with a step already dispatched behind the one awaited, a host
    that stood still finds the next read-back waiting (a gap of a
    millisecond or two), a device that stood still does not.  The chunk's
    slot and index say whose prompt it was (the request whose trace has
    that ``slot`` on ``request/queue``); the step's positions, whether it
    was long for its work (its group's mean: ``[pass_log]``)."""
    slow, _median = slow_passes(rec)
    group = group_of(rec)
    rows = []
    for i in np.flatnonzero(slow):
        r = rec[i]
        row = {"t": float(r["t"] - t_open), "gap_s": float(r["gap_s"]),
               "next_gap_s": float(rec["gap_s"][i + 1])
               if i + 1 < len(rec) else None,
               "group": str(group[i]), "chunk_width": int(r["chunk_width"]),
               "chunk_index": int(r["chunk_index"]),
               "chunk_slot": int(r["chunk_slot"]),
               **{k: float(r[k]) for k in PHASES},
               "gc_s": float(r["gc_s"]), "compiles": int(r["compiles"]),
               "n_active": int(r["n_active"]),
               "positions_live": int(r["positions_live"]),
               "positions_read": int(r["positions_read"])}
        if busy is not None:
            row["device_busy_s"] = busy(float(r["t"]), float(r["gap_s"]))
        rows.append(row)
    return rows


# ---- the join to the device trace ---------------------------------------------

def readback_ends(path: str) -> Dict[int, float]:
    """End of every ``serving/readback#seq=n#`` annotation, in seconds on
    the trace's clock, by ``n`` (the profile gives an annotation's
    arguments as the event's ``stats``)."""
    import jax
    out: Dict[int, float] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.partition("#")[0] != READBACK:
                    continue
                seq = dict(ev.stats).get("seq")
                if seq is not None:
                    out[int(seq)] = (ev.start_ns + ev.duration_ns) * 1e-9
    return out


def clock_offset(rec: np.ndarray, ends: Dict[int, float]) \
        -> Optional[Tuple[float, float, int]]:
    """``(offset, spread, matched)``: the trace's clock less
    ``perf_counter`` as the median over the records whose read-back the
    trace holds, and the widest departure from it."""
    diffs = np.asarray([ends[int(r["seq"])] - r["t"] for r in rec
                        if int(r["seq"]) in ends])
    if not len(diffs):
        return None
    offset = float(np.median(diffs))
    return offset, float(np.abs(diffs - offset).max()), len(diffs)


def device_busy(obs: Dict[str, Any], rec: np.ndarray,
                path: Optional[str] = None):
    """``busy(t_end, gap_s)`` on the first device of this run's trace, and
    the join it rests on; None without a trace or without ``seq`` on the
    read-back annotations."""
    t = obs.get("trace")
    if t is None or not t.devices():
        return None
    path = ps.newest_xplane() if path is None else path
    joined = clock_offset(rec, readback_ends(path)) if path else None
    if joined is None:
        return None
    offset = joined[0]
    ops = tr.union((s, e) for s, e, _ in t.ops[t.devices()[0]])

    def busy(t_end: float, gap_s: float) -> Optional[List[float]]:
        """``[busy seconds, traced seconds]`` of the gap's part inside
        the trace; None where none of it is."""
        lo = max(t_end - gap_s + offset, t.lo)
        hi = min(t_end + offset, t.hi)
        if hi <= lo:
            return None
        return [tr.measure(tr.clip(ops, lo, hi)), hi - lo]
    return busy, joined


# ---- what one run says ----------------------------------------------------------

def check(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The old sums against the new records: over the records between the
    two snapshots (by ``seq``), the count and the seconds of each kind of
    step gap beside ``stats()``'s differences, and the widest departure of
    a record's seven phases from its gap."""
    log, log0 = log_of(obs), (obs.get("stats0") or {}).get("pass_log")
    if log is None or log0 is None:
        return None
    rec = log.records()
    rec = rec[(rec["seq"] > log0["seq"]) & (rec["seq"] <= log["seq"])
              & np.isfinite(rec["gap_s"])]
    plain = group_of(rec) == "plain"
    out: Dict[str, Any] = {"records": len(rec), "dropped": log["dropped"]}
    for kind, m in (("plain", plain), ("prefill", ~plain)):
        out["gaps_" + kind] = [int(m.sum()), ps.delta(obs, "step_gaps", kind)]
        out["gap_seconds_" + kind] = [float(rec["gap_s"][m].sum()),
                                      ps.delta(obs, "step_gap_seconds", kind)]
    out["joint"] = [int(rec["joint"].sum()), ps.delta(obs, "chunks_joint")]
    out["phase_sum_error_max_s"] = float(np.abs(
        sum(rec[k] for k in PHASES) - rec["gap_s"]).max()) if len(rec) else 0.0
    return out


def report(obs: Dict[str, Any], rec: np.ndarray) -> None:
    """Print ``[pass_log]`` (the table), ``[chunk_position]`` (the thirds
    ``chunk_position_cost_ms`` compares), ``[pass_log_check]`` (the
    engine's p95 beside the one from the benchmark's stamps; the records
    beside the sums), in a traced run ``[pass_log_trace]`` (the join), and
    ``[slow_passes]``."""
    result.say("pass_log", rows=table(rec))
    places = place_rows(rec, obs["cfg"]["serving"]["prefill_chunk"])
    if places:
        result.say("chunk_position", thirds=places)
    mine = token_gap_p95_ms(rec)
    stamps = [g for g, _ in obs.get("gaps") or []]
    theirs = 1e3 * float(np.percentile(stamps, 95)) if stamps else None
    result.say("pass_log_check", engine_token_gap_p95_ms=mine,
               serve_itl_p95_ms_from_stamps=theirs,
               difference_pct=(None if not theirs or mine is None
                               else 100.0 * (mine - theirs) / theirs),
               token_gaps=int(rec["emitted"].sum()), stamp_gaps=len(stamps),
               **(check(obs) or {}))
    seen = device_busy(obs, rec)
    busy = None
    if seen is not None:
        busy, (offset, spread, matched) = seen
        result.say("pass_log_trace", clock_offset_s=offset,
                   offset_spread_s=spread, readbacks_matched=matched)
    rows = slow_rows(rec, obs["t_open"], busy)
    result.say("slow_passes", passes=len(rows), t_open=obs["t_open"],
               seconds=float(sum(r["gap_s"] for r in rows)), rows=rows[:40])

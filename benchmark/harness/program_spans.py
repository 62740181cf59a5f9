"""What the serving engine measures of itself, as the per-layer readers
take it: the always-on counters of ``GenerationScheduler.stats()`` as
differences across the window, and the engine thread's ``serving/*``
profiler annotations out of this run's trace.

Everything here returns ``None`` where the program has no such counter or
span (a commit from before they existed): the metric is then left out of
the result line.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from harness import manifest
from harness import trace as tr

Span = Tuple[float, float, str]

# engine-thread spans in which the host works; under the other two it waits
# (for the device, or for a request), so an idle gap there is not the host's
WORK = ("serving/admit", "serving/prefill", "serving/decode_dispatch",
        "serving/emit")
WAIT = ("serving/readback", "serving/idle")
ITERATION = "serving/iteration"     # its self time is host work too
HOST_PHASES = ("admit", "prefill_dispatch", "decode_dispatch", "emit", "other")


# ---- counters ---------------------------------------------------------------

def delta(obs: Dict[str, Any], key: str, part: Optional[str] = None) \
        -> Optional[float]:
    """``stats1[key] - stats0[key]`` (``[key][part]`` for a nested
    counter); None when either snapshot lacks it."""
    a, b = obs.get("stats0"), obs.get("stats1")
    if not a or not b:
        return None
    try:
        x, y = a[key], b[key]
        if part is not None:
            x, y = x[part], y[part]
        return float(y) - float(x)
    except (KeyError, TypeError, ValueError):
        return None


def ratio(num: Optional[float], den: Optional[float], scale: float = 1.0,
          least: float = 1.0) -> Optional[float]:
    """``scale * num / den``; None when a part is missing or the
    denominator is under ``least`` (too few events for a mean)."""
    if num is None or den is None or den < least:
        return None
    return scale * num / den


def host_seconds(obs: Dict[str, Any]) -> Optional[float]:
    """Seconds the engine thread spent working (not waiting) in the
    window."""
    parts = [delta(obs, "engine_phase_seconds", k) for k in HOST_PHASES]
    return None if any(p is None for p in parts) else sum(parts)


def step_gap_mean_ms(obs: Dict[str, Any], kind: str) -> Optional[float]:
    """Mean step gap of one kind (``plain`` | ``prefill``), from ten up."""
    return ratio(delta(obs, "step_gap_seconds", kind),
                 delta(obs, "step_gaps", kind), 1e3, least=10)


# ---- spans ------------------------------------------------------------------

def newest_xplane(root: Optional[str] = None) -> Optional[str]:
    """This run's trace: the newest ``.xplane.pb`` under ``.bench_trace/``
    (one process runs one cell)."""
    root = os.path.join(manifest.ROOT, ".bench_trace") if root is None else root
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def engine_spans(path: str) -> List[Span]:
    """``serving/*`` annotations of the engine's thread(s), in seconds on
    the trace's clock: the host lines that hold a ``serving/iteration`` or
    ``serving/idle`` event.  A TraceMe with arguments is named
    ``name#k=v,...#``; the name is what comes before."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out: List[Span] = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                    ev.name.partition("#")[0])
                   for ev in line.events if ev.name.startswith("serving/")]
            if any(n in (ITERATION, "serving/idle") for _, _, n in evs):
                out += evs
    return out


def innermost(spans: Iterable[Span]) -> List[Span]:
    """Properly nested spans as non-overlapping segments, each named by
    the innermost span open there (a parent's segments are its self
    time)."""
    out: List[Span] = []
    stack: List[Tuple[float, str]] = []      # (end, name) of the open spans
    cur = 0.0

    def close_until(t: float) -> None:
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                out.append((cur, end, name))
                cur = end

    for s, e, name in sorted(spans, key=lambda t: (t[0], -t[1])):
        close_until(s)
        if stack and s > cur:
            out.append((cur, s, stack[-1][1]))
        cur = max(cur, s) if stack else s
        stack.append((e, name))
    close_until(float("inf"))
    return out


def device_gaps(t: "tr.Trace") -> List[tr.Interval]:
    """Idle intervals of the first device inside the trace's window."""
    devs = t.devices()
    if not devs:
        return []
    busy = tr.union(tr.clip(((s, e) for s, e, _ in t.ops[devs[0]]), t.lo, t.hi))
    return tr.subtract([(t.lo, t.hi)], busy)


def named_gaps(gaps: Sequence[tr.Interval], spans: Sequence[Span]) \
        -> List[Tuple[float, str]]:
    """``(seconds, name)`` of every gap: the innermost engine span over its
    middle, ``unannotated`` where the engine thread had no span open."""
    segs = innermost(spans)
    starts = [s for s, _, _ in segs]
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        i = bisect.bisect_right(starts, mid) - 1
        out.append((e - s, segs[i][2] if i >= 0 and mid <= segs[i][1]
                    else "unannotated"))
    return out


def idle_by_span(gaps: Sequence[tr.Interval], spans: Sequence[Span]) \
        -> Dict[str, float]:
    """Idle seconds by engine span."""
    out: Dict[str, float] = {}
    for seconds, name in named_gaps(gaps, spans):
        out[name] = out.get(name, 0.0) + seconds
    return out


def attributed_share(table: Dict[str, float]) -> Optional[float]:
    """Share (%) of the idle seconds that lie under host work."""
    total = sum(table.values())
    if total <= 0.0:
        return None
    return 100.0 * sum(v for k, v in table.items()
                       if k in WORK or k == ITERATION) / total


def observed(obs: Dict[str, Any], path: Optional[str] = None) \
        -> Optional[Tuple[List[tr.Interval], List[Span]]]:
    """The first device's idle gaps in the traced window and the engine's
    spans; None without a device trace or without engine spans in it."""
    t = obs.get("trace")
    if t is None or not t.devices():
        return None
    path = newest_xplane() if path is None else path
    if path is None:
        return None
    spans = engine_spans(path)
    if not spans:
        return None
    return device_gaps(t), spans


def idle_table(obs: Dict[str, Any], path: Optional[str] = None) \
        -> Optional[Dict[str, float]]:
    """The first device's idle seconds in the traced window by engine
    span."""
    seen = observed(obs, path)
    return None if seen is None else idle_by_span(*seen)

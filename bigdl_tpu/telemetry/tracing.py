"""Span tracing: where a step's wall time actually goes.

``span("optimizer/step")`` is a context manager recording one timed
interval.  Spans nest via a thread-local stack (a span opened inside
another becomes its child), finished spans land in a bounded ring
buffer, and the whole buffer exports to Chrome trace-event JSON —
loadable in Perfetto / ``chrome://tracing`` — so the data-wait /
compiled-step / validation / checkpoint-commit breakdown of a training
run is one file away instead of unanswerable.

Clock: ``time.perf_counter()``, the same clock the serving scheduler
and optimizer already stamp with, so :func:`record_span` can adopt
timestamps measured elsewhere (e.g. a request's ``t_enqueue``)
retroactively.  Trace timestamps are exported relative to the module's
load instant; ``wall_time_of`` converts to epoch seconds when needed.

Cross-thread propagation: a worker thread adopts a parent with::

    token = tracing.current_span()          # in the submitting thread
    with tracing.propagate(token):          # in the worker
        with tracing.span("serving/execute"):
            ...

Two sinks, one call.  ``span`` always enters a
``jax.profiler.TraceAnnotation`` (a TraceMe: one atomic flag read while
no profile is being taken), so every span site lands in any
``jax.profiler`` session an operator or the benchmark runs, on the
clock of the device's ``XLA Ops`` line.  The ring buffer above records
only while ``telemetry.enabled()``.  :func:`record_span` is
retroactive and therefore ring-only: the profiler cannot be handed an
interval that has already passed.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional

__all__ = ["span", "record_span", "current_span", "propagate",
           "finished_spans", "dropped_spans", "reset_spans",
           "set_ring_capacity", "chrome_trace", "write_chrome_trace",
           "merge_chrome_traces", "wall_time_of"]

# The clock contract (enforced tree-wide by graftlint's
# clock-discipline pass, docs/static_analysis.md):
#
#   * DURATIONS and span endpoints live on ``time.perf_counter()`` —
#     monotonic, NTP-immune, the only clock two in-process stamps may
#     be subtracted on;
#   * TIMESTAMPS (event records, checkpoint manifests, cross-process
#     staleness checks) live on ``time.time()`` — epoch-meaningful,
#     comparable across processes, never subtracted from a
#     perf_counter value.
#
# ``(_EPOCH_PERF, _EPOCH_WALL)`` is the one sanctioned bridge between
# the two: a paired reading captured once at import, so
# :func:`wall_time_of` can render a perf_counter stamp as approximate
# epoch seconds for humans.  Code must cross the bridge through that
# function, not by mixing clocks ad hoc — PR 3's review round found
# optimizer spans stranded ~an epoch off the trace timeline from
# exactly such a mix.
_EPOCH_PERF = time.perf_counter()
_EPOCH_WALL = time.time()

_DEFAULT_CAPACITY = 16384

_ids = itertools.count(1)
_tls = threading.local()


class _NoAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` where jax cannot
    be imported: ``telemetry`` has to work without it."""

    __slots__ = ()

    def __init__(self, name, **args):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# resolved on the first span(): importing jax here would make
# ``import bigdl_tpu.telemetry`` pay for (and depend on) it
_annotation = None


def _trace_annotation():
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except Exception:   # noqa: BLE001 - no jax: the ring still works
            TraceAnnotation = _NoAnnotation
        _annotation = TraceAnnotation
    return _annotation

_buf_lock = threading.Lock()
_buffer: deque = deque(maxlen=_DEFAULT_CAPACITY)
_dropped = 0


class SpanRecord:
    """One finished span.  Plain object, not a dataclass: this is
    allocated on every traced interval."""

    __slots__ = ("name", "t_start", "t_end", "span_id", "parent_id",
                 "thread", "args")

    def __init__(self, name, t_start, t_end, span_id, parent_id,
                 thread, args):
        self.name = name
        self.t_start = t_start
        self.t_end = t_end
        self.span_id = span_id
        self.parent_id = parent_id
        self.thread = thread
        self.args = args

    @property
    def duration_s(self) -> float:
        return self.t_end - self.t_start


def _stack() -> List[int]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _record(rec: SpanRecord) -> None:
    global _dropped
    with _buf_lock:
        if len(_buffer) == _buffer.maxlen:
            _dropped += 1
        _buffer.append(rec)


def current_span() -> Optional[int]:
    """The innermost open span id on THIS thread (a propagation token
    for worker threads), or None."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


@contextmanager
def propagate(parent_id: Optional[int]) -> Iterator[None]:
    """Adopt ``parent_id`` as this thread's span parent for the block —
    the cross-thread half of parent/child propagation."""
    st = _stack()
    if parent_id is None:
        yield
        return
    st.append(parent_id)
    try:
        yield
    finally:
        st.pop()


@contextmanager
def span(name: str, **args) -> Iterator[Optional[int]]:
    """Record one timed interval: always as a profiler annotation (seen
    only by a running ``jax.profiler`` session), and into the ring when
    telemetry is enabled.  Yields the ring's span id (None when
    telemetry is disabled).  ``args`` become the annotation's metadata
    and the Chrome-trace args."""
    from bigdl_tpu import telemetry
    with (_annotation or _trace_annotation())(name, **args):
        if not telemetry.enabled():
            yield None
            return
        st = _stack()
        parent = st[-1] if st else None
        sid = next(_ids)
        st.append(sid)
        t0 = time.perf_counter()
        try:
            yield sid
        finally:
            t1 = time.perf_counter()
            st.pop()
            _record(SpanRecord(name, t0, t1, sid, parent,
                               threading.get_ident(), args or None))


def record_span(name: str, t_start: float, t_end: float,
                parent_id: Optional[int] = None, **args) -> Optional[int]:
    """Record a span from timestamps measured elsewhere (both on the
    ``time.perf_counter`` clock).  Used where the interval's endpoints
    are only known after the fact — e.g. the optimizer's async loss
    drain learns a window's completion time in a worker thread, and a
    serving request's queue wait starts at its ``t_enqueue``.  Ring
    only: a profiler session takes no interval after the fact."""
    from bigdl_tpu import telemetry
    if not telemetry.enabled():
        return None
    if parent_id is None:
        parent_id = current_span()
    sid = next(_ids)
    _record(SpanRecord(name, t_start, t_end, sid, parent_id,
                       threading.get_ident(), args or None))
    return sid


# ---- reading / export ------------------------------------------------------

def finished_spans() -> List[SpanRecord]:
    with _buf_lock:
        return list(_buffer)


def dropped_spans() -> int:
    with _buf_lock:
        return _dropped


def reset_spans() -> None:
    global _dropped
    with _buf_lock:
        _buffer.clear()
        _dropped = 0


def set_ring_capacity(n: int) -> None:
    """Resize the finished-span ring (keeps the newest spans)."""
    global _buffer
    if n < 1:
        raise ValueError("ring capacity must be >= 1")
    with _buf_lock:
        _buffer = deque(_buffer, maxlen=n)


def wall_time_of(t_perf: float) -> float:
    """perf_counter timestamp -> epoch seconds (approximate: anchored
    at module import)."""
    return _EPOCH_WALL + (t_perf - _EPOCH_PERF)


def chrome_trace() -> Dict:
    """The ring buffer as a Chrome trace-event object: complete ("X")
    events with microsecond ts/dur, pid/tid, and span/parent ids in
    args — ``json.dump`` it and load in Perfetto."""
    events = []
    for rec in finished_spans():
        args = {"span_id": rec.span_id}
        if rec.parent_id is not None:
            args["parent_id"] = rec.parent_id
        if rec.args:
            args.update(rec.args)
        events.append({
            "ph": "X",
            "name": rec.name,
            "cat": "bigdl_tpu",
            "ts": (rec.t_start - _EPOCH_PERF) * 1e6,
            "dur": max(rec.t_end - rec.t_start, 0.0) * 1e6,
            "pid": os.getpid(),
            "tid": rec.thread,
            "args": args,
        })
    # epoch_wall anchors this file's ts=0 on the shared wall clock, so
    # merge_chrome_traces can re-base per-process timelines onto one
    # axis (each process's perf_counter starts at an arbitrary zero)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"dropped_spans": dropped_spans(),
                          "epoch_wall": _EPOCH_WALL}}


def write_chrome_trace(path: str) -> str:
    """Serialize :func:`chrome_trace` to ``path`` (JSON)."""
    with open(path, "w", encoding="utf-8") as f:
        json.dump(chrome_trace(), f)
    return path


def merge_chrome_traces(paths) -> Dict:
    """Merge per-process Chrome trace files into ONE Perfetto-loadable
    timeline.  Each file's ``ts`` values are relative to its own
    process's perf_counter zero; the ``otherData.epoch_wall`` anchor
    (written by :func:`chrome_trace`) says where that zero sits on the
    shared wall clock, so every file is shifted onto the earliest
    anchor's axis.  A file with no anchor (pre-anchor export) merges
    unshifted.  Distinct pids keep their own tracks; drop counters
    sum."""
    loaded = []
    dropped = 0
    for p in paths:
        with open(p, "r", encoding="utf-8") as f:
            data = json.load(f)
        other = data.get("otherData") or {}
        loaded.append((data, other.get("epoch_wall")))
        try:
            dropped += int(other.get("dropped_spans", 0) or 0)
        except (TypeError, ValueError):
            pass
    anchors = [a for _, a in loaded if a is not None]
    base = min(anchors) if anchors else None
    events: List[Dict] = []
    for data, anchor in loaded:
        shift_us = (0.0 if anchor is None or base is None
                    else (float(anchor) - base) * 1e6)
        for ev in data.get("traceEvents", []):
            ev = dict(ev)
            if "ts" in ev:
                ev["ts"] = float(ev["ts"]) + shift_us
            events.append(ev)
    events.sort(key=lambda e: e.get("ts", 0.0))
    out = {"traceEvents": events, "displayTimeUnit": "ms",
           "otherData": {"dropped_spans": dropped,
                         "merged_files": len(loaded)}}
    if base is not None:
        out["otherData"]["epoch_wall"] = base
    return out

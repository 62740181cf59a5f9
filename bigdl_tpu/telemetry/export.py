"""Exposition: Prometheus text, JSON snapshots, TensorBoard bridge,
and a periodic background exporter.

* :func:`prometheus_text` — the text exposition format (0.0.4) a
  Prometheus scrape expects; served by ``examples/serve.py /metrics``.
* :func:`json_snapshot` — one JSON-able dict of every metric (plus a
  span-buffer summary), so a slow run can be attributed to data-wait
  vs compute without a TPU profile.
* :func:`publish_summary` — writes the snapshot through a
  ``visualization.Summary`` (see ``TelemetrySummary``) so telemetry
  lands in the same TensorBoard run as train/validation/serving
  scalars.
* :class:`PeriodicExporter` — a daemon thread exporting every
  ``interval_s`` with a clean ``stop()`` (final export included, so a
  short run's tail is never lost).
"""

from __future__ import annotations

import json
import threading
import time
from typing import Callable, Dict, Optional

from bigdl_tpu.telemetry import events as _events
from bigdl_tpu.telemetry import tracing
from bigdl_tpu.telemetry.metrics import (
    Counter, Gauge, Histogram, TelemetryRegistry, get_registry,
)

__all__ = ["prometheus_text", "json_snapshot", "publish_summary",
           "PeriodicExporter"]


def _fmt_value(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _esc(s: str) -> str:
    return (str(s).replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r'\"'))


def _labelstr(names, values, extra: str = "") -> str:
    parts = [f'{n}="{_esc(v)}"' for n, v in zip(names, values)]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def prometheus_text(registry: Optional[TelemetryRegistry] = None) -> str:
    """Render every metric in the Prometheus text exposition format.
    Collectors (e.g. the serving bridge) run first, so reservoir
    quantiles are fresh as of this scrape."""
    registry = registry or get_registry()
    registry.run_collectors()
    lines = []
    for m in registry.metrics():
        if m.help:
            lines.append(f"# HELP {m.name} {_esc(m.help)}")
        lines.append(f"# TYPE {m.name} {m.kind}")
        if isinstance(m, Histogram):
            for labels, snap in m.samples():
                cum = 0
                for le, n in zip(snap["buckets"], snap["counts"]):
                    cum += n
                    ls = _labelstr(m.labelnames, labels,
                                   f'le="{_fmt_value(le)}"')
                    lines.append(f"{m.name}_bucket{ls} {cum}")
                ls = _labelstr(m.labelnames, labels)
                lines.append(f"{m.name}_sum{ls} {_fmt_value(snap['sum'])}")
                lines.append(f"{m.name}_count{ls} {snap['count']}")
        else:
            for labels, v in m.samples():
                ls = _labelstr(m.labelnames, labels)
                lines.append(f"{m.name}{ls} {_fmt_value(v)}")
    return "\n".join(lines) + "\n"


def json_snapshot(registry: Optional[TelemetryRegistry] = None) -> Dict:
    """One coherent JSON-able dict: every metric (collectors included)
    plus summaries of the span ring buffer and the flight recorder
    (the run's retry/fault/checkpoint event history)."""
    registry = registry or get_registry()
    spans = tracing.finished_spans()
    by_name: Dict[str, Dict] = {}
    for s in spans:
        agg = by_name.setdefault(s.name, {"count": 0, "total_s": 0.0})
        agg["count"] += 1
        agg["total_s"] += s.duration_s
    ev = _events.events_summary(50)
    return {
        "time": time.time(),
        "metrics": registry.snapshot(),
        "spans": {"buffered": len(spans),
                  "dropped": tracing.dropped_spans(),
                  "by_name": by_name},
        "events": {"buffered": ev["buffered"], "dropped": ev["dropped"],
                   "by_kind": ev["counts"], "recent": ev["recent"]},
    }


def publish_summary(summary, step: int,
                    registry: Optional[TelemetryRegistry] = None) -> None:
    """Write the current metric values through a ``visualization``
    Summary (``TelemetrySummary`` puts them under a ``telemetry`` tag
    directory in the same TensorBoard run as train/val/serving).
    Counters/gauges become scalars tagged ``telemetry/<name>`` (label
    values joined into the tag); histograms become TB histograms
    weighted by bucket counts."""
    import numpy as np
    registry = registry or get_registry()
    registry.run_collectors()
    for m in registry.metrics():
        if isinstance(m, Histogram):
            for labels, snap in m.samples():
                if not snap["count"]:
                    continue
                tag = "/".join(("telemetry", m.name) + labels)
                # bucket representative = upper bound (finite), lower
                # neighbor for the +Inf bucket
                values, weights = [], []
                prev = 0.0
                for le, n in zip(snap["buckets"], snap["counts"]):
                    if n:
                        values.append(prev if le == float("inf") else le)
                        weights.append(n)
                    if le != float("inf"):
                        prev = le
                summary.add_histogram(tag, np.asarray(values, np.float64),
                                      step, weights=weights)
        else:
            for labels, v in m.samples():
                tag = "/".join(("telemetry", m.name) + labels)
                summary.add_scalar(tag, float(v), step)


class PeriodicExporter:
    """Background exporter thread.

    >>> exp = PeriodicExporter(interval_s=30, path="telemetry.json")
    >>> exp.start()
    ...
    >>> exp.stop()          # joins the thread; writes one final export

    Exactly one of ``path`` (JSON snapshot written atomically-enough
    via truncate+rename-free rewrite) or ``fn`` (called with the
    snapshot dict) must be given.  ``prometheus=True`` with ``path``
    writes text exposition instead of JSON (node-exporter textfile
    style)."""

    def __init__(self, interval_s: float,
                 path: Optional[str] = None,
                 fn: Optional[Callable[[Dict], None]] = None,
                 prometheus: bool = False,
                 registry: Optional[TelemetryRegistry] = None):
        if (path is None) == (fn is None):
            raise ValueError("give exactly one of path= or fn=")
        self.interval_s = float(interval_s)
        self.path = path
        self.fn = fn
        self.prometheus = prometheus
        self.registry = registry or get_registry()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.exports = 0
        self.errors = 0

    def _export_once(self) -> None:
        try:
            if self.path is not None:
                if self.prometheus:
                    data = prometheus_text(self.registry)
                else:
                    data = json.dumps(json_snapshot(self.registry))
                with open(self.path, "w", encoding="utf-8") as f:
                    f.write(data)
            else:
                self.fn(json_snapshot(self.registry))
            self.exports += 1
        except Exception:
            # an unwritable disk must not kill the exporter (next
            # interval may succeed); errors are counted, not raised
            self.errors += 1

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self._export_once()
        self._export_once()  # final export on clean shutdown

    def start(self) -> "PeriodicExporter":
        if self._thread is not None:
            raise RuntimeError("exporter already started")
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="bigdl-telemetry-export")
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        """Signal the thread, wait for its final export, join."""
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout)
        self._thread = None

    def __enter__(self) -> "PeriodicExporter":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

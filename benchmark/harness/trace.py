"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per HLO operation that ran (name = the HLO instruction text),
``XLA Modules`` one per program execution (``jit_<fn>(<id>)``) and
``Async XLA Ops`` the spans of asynchronous operations (collectives,
prefetch copies).  Host threads live on ``/host:CPU``; the benchmark's
own ``jax.profiler.TraceAnnotation`` spans (``bench.*``) are among their
events.  Times are nanoseconds on one clock for all planes (the device
and host clocks were seen about a millisecond apart).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
CONTAINERS = ("while", "conditional", "call")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def start(logdir: str) -> None:
    """Start the profiler with its Python tracer off.  That tracer (on by
    default) records every Python call of every thread; under it the
    serving engine's dispatches grew from 7 to 38 ms of host as the trace
    filled and the device waited for them (PERF.md, Findings of PRs 27 and
    28): a traced window then measured the tracer.  The device planes and
    the ``TraceAnnotation`` spans the readers use are the host tracer's
    and stay."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def hlo_category(text: str) -> str:
    """The HLO category of one ``XLA Ops`` event name: its opcode, with
    fusions split as convolution / output / loop / custom fusion and
    custom calls named by their target."""
    body = _LAYOUT.sub("", text)
    name, _, rest = body.partition(" = ")
    name = name.lstrip("%")
    rest = rest.lstrip()
    if rest.startswith("("):               # tuple type: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.partition(" ")[2]
    op = rest.partition("(")[0].strip()
    if not op:                              # not HLO text: keep the name
        return re.sub(r"[.\d]+$", "", name) or "unknown"
    if op == "fusion":
        if "convolution" in name:
            return "convolution_fusion"
        kind = re.search(r"kind=k(\w+)", body)
        return (kind.group(1).lower() if kind else "other") + "_fusion"
    if op == "custom-call":
        target = re.search(r'custom_call_target="([^"]+)"', body)
        return target.group(1) if target else op
    return op


def is_collective(category: str, text: str) -> bool:
    base = category.removesuffix("-start").removesuffix("-done")
    return base in COLLECTIVES or any(
        ("%" + c) in text.partition(" = ")[0] for c in COLLECTIVES)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of union ``a`` that no interval of union ``b`` covers."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


class Trace:
    """Events of one trace, in seconds, clipped to ``[lo, hi]`` when a
    host span named ``window`` is given."""

    def __init__(self, path: str, window: Optional[str] = None):
        import jax
        data = jax.profiler.ProfileData.from_file(path)
        self.ops: Dict[str, List[Tuple[float, float, str]]] = {}
        self.async_ops: Dict[str, List[Tuple[float, float, str]]] = {}
        self.modules: Dict[str, List[Tuple[float, float, str]]] = {}
        self.host: List[Tuple[float, float, str]] = []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    dest = {"XLA Ops": self.ops, "Async XLA Ops": self.async_ops,
                            "XLA Modules": self.modules}.get(line.name)
                    if dest is None:
                        continue
                    dest[plane.name] = [
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                        for ev in line.events]
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("bench."):
                            self.host.append(
                                (ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9,
                                 ev.name))
        self.lo, self.hi = None, None
        if window is not None:
            spans = [(s, e) for s, e, n in self.host if n == window]
            if spans:
                self.lo = min(s for s, _ in spans)
                self.hi = max(e for _, e in spans)
        if self.lo is None:
            every = [iv for evs in self.ops.values() for iv in evs]
            every += self.host
            self.lo = min((s for s, _, _ in every), default=0.0)
            self.hi = max((e for _, e, _ in every), default=0.0)

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def devices(self) -> List[str]:
        return sorted(self.ops)

    def _busy(self, dev: str) -> List[Interval]:
        return union(clip(((s, e) for s, e, _ in self.ops[dev]),
                          self.lo, self.hi))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(measure(self._busy(d)) for d in devs) / len(devs)

    def category_seconds(self) -> Dict[str, float]:
        """Device seconds by HLO category, averaged over the devices.
        Control-flow operations (a scan's ``while``) are left out: their
        events span the operations of their bodies, which are listed
        too."""
        out: Dict[str, float] = {}
        devs = self.devices()
        for d in devs:
            for s, e, name in self.ops[d]:
                c = clip([(s, e)], self.lo, self.hi)
                if c:
                    cat = hlo_category(name)
                    if cat in CONTAINERS:
                        continue
                    out[cat] = out.get(cat, 0.0) + measure(c) / len(devs)
        return out

    def module_seconds(self, prefix: str) -> Tuple[float, int]:
        """Device seconds and executions of the programs whose name
        starts with ``prefix`` (``jit__decode``), on the first device."""
        devs = sorted(self.modules)
        if not devs:
            return 0.0, 0
        total, n = 0.0, 0
        for s, e, name in self.modules[devs[0]]:
            c = clip([(s, e)], self.lo, self.hi)
            if c and name.startswith(prefix) and s >= self.lo and e <= self.hi:
                total += e - s
                n += 1
        return total, n

    def ops_seconds(self, pred) -> float:
        """Device seconds of the operations whose (category, text)
        satisfy ``pred``, averaged over the devices."""
        devs = self.devices()
        total = 0.0
        for d in devs:
            for s, e, name in self.ops[d]:
                if pred(hlo_category(name), name):
                    total += measure(clip([(s, e)], self.lo, self.hi))
        return total / len(devs) if devs else 0.0

    def collective_exposed_s(self) -> float:
        """Seconds in collectives during which no other operation ran
        on that device, averaged over the devices."""
        devs = self.devices()
        total = 0.0
        for d in devs:
            coll, comp = [], []
            for s, e, name in self.ops[d]:
                cat = hlo_category(name)
                if cat in CONTAINERS:
                    continue        # spans its body's operations
                (coll if is_collective(cat, name) else comp).append((s, e))
            for s, e, name in self.async_ops.get(d, []):
                if any(c in name.partition(" = ")[0] or (" " + c) in name
                       for c in COLLECTIVES):
                    coll.append((s, e))
            exposed = subtract(union(clip(coll, self.lo, self.hi)),
                               union(clip(comp, self.lo, self.hi)))
            total += measure(exposed)
        return total / len(devs) if devs else 0.0

    def idle_gaps(self) -> Dict[str, float]:
        """Idle seconds of the first device by what the host was doing:
        each gap between operations goes to the ``bench.*`` span that
        covers its middle, or to ``unannotated``."""
        devs = self.devices()
        if not devs:
            return {}
        busy = self._busy(devs[0])
        gaps = subtract([(self.lo, self.hi)], busy)
        spans = sorted(self.host, key=lambda t: t[1] - t[0])   # innermost first
        out: Dict[str, float] = {}
        for s, e in gaps:
            mid = 0.5 * (s + e)
            name = next((n for hs, he, n in spans if hs <= mid <= he),
                        "unannotated")
            out[name] = out.get(name, 0.0) + (e - s)
        return out


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]

"""Least time of the Mamba-1 scans in the traced prefill chunks, over the
device time they took.

A chunk's scan is a loop over its positions that carries one slot's state:
a ``while`` on the XLA Ops line whose text names the carried state
(``f32[1, state, inner]``), whose event spans the operations of its body.
Those loops are what this reads, **each counted as the trace shows it**:
its positions are those of the per-position inputs its text names
(``f32[turns, unrolled, 1, inner]`` as the compiler lays a loop that takes
several positions a turn, or ``f32[positions, 1, inner]``: the product of
what stands before ``1, inner``; a chunk of any width the pool sends, a
last chunk's padding included), so a trace with few chunks or narrow ones
is counted for what it held and not for what the window did.

The least time of one scan is the larger of its operations over the bf16
peak and its bytes over the HBM peak, as the configuration's kind counts
them (``selective_scan_cost``).  The program runs the scan a position at a
time on the vector unit (a decay a channel and state leaves no product
between positions), so the share says how far a ``jax.numpy`` scan is
from what a kernel that kept the state in VMEM could reach, and ``PERF.md``
says what share of a chunk's time it is.
"""
import math
import re

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_itl_p95_ms"
DEVICE = True   # True: only a chip run can give it


def read(obs):
    t = obs.get("trace")
    if t is None or not t.devices():
        return None
    from harness import manifest
    from harness import trace as tr
    kind = manifest.load_kind(obs["kind"])
    if not hasattr(kind, "selective_scan_cost"):
        return None
    cfg = obs["cfg"]
    _, n, inner = kind.selective_state_shape(cfg)
    carried = "f32[1,%d,%d]" % (n, inner)
    per_position = re.compile(r"f32\[((?:\d+,)+)1,%d\]" % inner)
    peaks = obs["peaks"]
    least, secs = 0.0, 0.0
    for s, e, text in t.ops[t.devices()[0]]:
        if tr.hlo_category(text) != "while" or carried not in text \
                or s < t.lo or e > t.hi:
            continue
        widths = [math.prod(int(d) for d in dims.split(",") if d)
                  for dims in per_position.findall(text)]
        if not widths:
            continue
        cost = kind.selective_scan_cost(cfg, max(widths))
        least += max(cost["bytes"] / peaks["hbm_bytes_per_s"],
                     cost["flops"] / peaks["bf16_flops_per_s"])
        secs += e - s
    return 100.0 * least / secs if secs > 0.0 else None

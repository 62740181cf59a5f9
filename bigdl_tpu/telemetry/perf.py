"""Perf attribution: where a step's wall time goes.

:func:`attribute_windows` decomposes the optimizer's
completion-timestamp stream (``Optimizer.window_records``, written by
the loss-drain worker) into four measured phases plus an explicit
*unattributed residual*:

* ``data_wait``       — host blocked pulling batches from the input
  pipeline (decode, augment, a stalled loader);
* ``host_staging``    — host→device transfer + window stacking + rng
  build between fetch and dispatch;
* ``device_compute``  — host blocked on the device completing the
  window (the pure-transfer pin in ``consume_window``; only the
  NON-overlapped device time can show up in wall time, which is
  exactly what attribution of wall time wants);
* ``readback``        — device→host loss transfer + float conversion.

``residual`` is wall minus the measured phases, clamped non-negative —
the honest "we don't know" number.  When host and device genuinely
overlap (async drain), the phases can over-sum the
completion-to-completion wall; the excess is reported as ``overlap``
rather than silently rescaled, so the published invariant is exact::

    sum(phases) + residual - overlap == wall

:func:`attribution_report` pairs the decomposition with the analytic
cost model (``utils/xla_cost.cost_breakdown``: compiled FLOPs + bytes
accessed) to state MFU vs the public spec
(overall and device-only), plus a compute-bound vs HBM-bound
verdict from bytes/step against the device's HBM bandwidth.

This module never imports jax.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Dict, List, Optional

logger = logging.getLogger("bigdl_tpu.telemetry")

__all__ = [
    "PHASES", "attribute_windows", "attribution_report",
    "roofline_verdict", "device_peak_flops", "device_hbm_bytes_per_s",
    "device_ici_bytes_per_s", "device_dcn_bytes_per_s",
    "optimizer_perf_status",
]

# The measured phases, in pipeline order.  ``residual`` is not a phase:
# it is defined as what the phases do NOT cover.
PHASES = ("data_wait", "host_staging", "device_compute", "readback")

# Record keys as written by Optimizer's consume_window.
_PHASE_KEYS = {
    "data_wait": "data_wait_s",
    "host_staging": "host_staging_s",
    "device_compute": "device_compute_s",
    "readback": "readback_s",
}

# ---------------------------------------------------------------------------
# Device capability tables (public numbers, per chip)
# ---------------------------------------------------------------------------

# Dense bf16 peak FLOP/s by device_kind substring (Google Cloud TPU
# documentation, per chip), declared once.
_PEAK_BF16_FLOPS = (
    ("v6", 918e12), ("v5p", 459e12), ("v5e", 197e12), ("v5 lite", 197e12),
    ("v5litepod", 197e12), ("v4", 275e12), ("v3", 123e12), ("v2", 46e12),
)

# HBM bandwidth (bytes/s) by device_kind substring — the denominator of
# the HBM-bound verdict (docs/performance.md measured v5e conv fusions
# at ~94% of the 819 GB/s figure, so these are usable rooflines).
_HBM_BYTES_PER_S = (
    ("v6", 1640e9), ("v5p", 2765e9), ("v5e", 819e9), ("v5 lite", 819e9),
    ("v5litepod", 819e9), ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9),
)

# Aggregate per-chip ICI bandwidth (bytes/s) by device_kind substring —
# the denominator of the comm-bound verdict: the floor a step's
# inter-chip payload (collective_bytes_total / collective_hlo_bytes)
# puts under its time.  Public interconnect figures, converted from the
# advertised per-chip link Gb/s; treat as rooflines, not guarantees
# (real ring/torus schedules land below them).
_ICI_BYTES_PER_S = (
    ("v6", 448e9), ("v5p", 600e9), ("v5e", 200e9), ("v5 lite", 200e9),
    ("v5litepod", 200e9), ("v4", 300e9), ("v3", 82e9), ("v2", 62e9),
)

# Per-chip DCN bandwidth (bytes/s) by device_kind substring — the slow
# tier BETWEEN slices (data-center network), the denominator of the
# ``dcn_bound`` verdict over the cross-slice payload
# (``xla_cost.cross_group_hlo_bytes`` /
# ``grad_allreduce_bytes(hierarchical=True)["dcn_bytes_per_step"]``).
# Order-of-magnitude figures from published multislice host NIC specs
# amortized per chip — one to two decades below ICI, which is exactly
# why parallel/hierarchy.py exists.  Override with
# ``BIGDL_TPU_DCN_BYTES_PER_S`` (e.g. to pin the table slow in a smoke
# test, or to enter a measured fleet number).
_DCN_BYTES_PER_S = (
    ("v6", 25e9), ("v5p", 25e9), ("v5e", 12.5e9), ("v5 lite", 12.5e9),
    ("v5litepod", 12.5e9), ("v4", 12.5e9), ("v3", 6e9), ("v2", 6e9),
)


def _lookup(table, device_kind: Optional[str]) -> Optional[float]:
    """Table value for ``device_kind``.  None for a device that is not
    a TPU (the CPU the tests run on); a TPU kind the table does not
    know raises — a utilization must never quietly disappear because
    the chip is new."""
    kind = (device_kind or "").lower()
    for key, value in table:
        if key in kind:
            return value
    if "tpu" in kind:
        raise ValueError(
            f"unknown TPU device_kind {device_kind!r}: add its published "
            f"peaks to the tables in bigdl_tpu/telemetry/perf.py")
    return None


def device_peak_flops(device_kind: Optional[str]) -> Optional[float]:
    """Public dense bf16 peak FLOP/s for a ``device_kind`` string; None
    off-TPU, ValueError for an unknown TPU kind."""
    return _lookup(_PEAK_BF16_FLOPS, device_kind)


def device_hbm_bytes_per_s(device_kind: Optional[str]) -> Optional[float]:
    """Public HBM bandwidth (bytes/s) for a ``device_kind`` string; None
    off-TPU, ValueError for an unknown TPU kind."""
    return _lookup(_HBM_BYTES_PER_S, device_kind)


def device_ici_bytes_per_s(device_kind: Optional[str]) -> Optional[float]:
    """Aggregate per-chip ICI bandwidth (bytes/s) for a ``device_kind``
    string; None off-TPU, ValueError for an unknown TPU kind."""
    return _lookup(_ICI_BYTES_PER_S, device_kind)


def device_dcn_bytes_per_s(device_kind: Optional[str]) -> Optional[float]:
    """Per-chip DCN (inter-slice) bandwidth in bytes/s for a
    ``device_kind`` string; None off-TPU, ValueError for an unknown TPU
    kind.  The
    ``BIGDL_TPU_DCN_BYTES_PER_S`` env var overrides the table
    unconditionally (measured fleet numbers beat public specs; smoke
    tests pin it slow to force a ``dcn_bound`` verdict)."""
    env = os.environ.get("BIGDL_TPU_DCN_BYTES_PER_S")
    if env:
        try:
            return float(env)
        except ValueError:
            logger.warning(
                "BIGDL_TPU_DCN_BYTES_PER_S=%r is not a number; "
                "ignoring the override and using the spec table "
                "(pass plain bytes/s, e.g. 12.5e9)", env)
    return _lookup(_DCN_BYTES_PER_S, device_kind)


# ---------------------------------------------------------------------------
# Step-time attribution
# ---------------------------------------------------------------------------

def attribute_windows(records: List[Dict[str, Any]],
                      skip_first: int = 1) -> Optional[Dict[str, Any]]:
    """Aggregate the optimizer's per-window phase records into one
    per-step attribution table.

    ``records`` is ``Optimizer.window_records`` — one dict per flushed
    readback window with ``iterations``, ``wall_s``
    (completion-to-completion), and the four measured phase durations.
    The first ``skip_first`` windows bear compile and are excluded when
    enough windows exist; with nothing left the full list is used and
    ``includes_compile_window`` is set so the reader knows the numbers
    carry one-time costs.

    Returns None for an empty stream; otherwise a dict whose exact
    invariant is ``sum(phases_s.values()) + residual_s - overlap_s ==
    wall_step_s`` (see module docstring for why ``overlap`` exists
    instead of rescaling)."""
    if not records:
        return None
    records = list(records)  # accept any sequence (deque included)
    steady = records[skip_first:] if len(records) > skip_first else None
    includes_compile = steady is None
    if steady is None:
        steady = list(records)
    iters = sum(int(r.get("iterations", 1)) for r in steady)
    iters = max(iters, 1)
    wall = sum(float(r.get("wall_s", 0.0)) for r in steady)
    phase_totals = {
        name: sum(max(float(r.get(key, 0.0)), 0.0) for r in steady)
        for name, key in _PHASE_KEYS.items()
    }
    measured = sum(phase_totals.values())
    residual = max(wall - measured, 0.0)
    overlap = max(measured - wall, 0.0)
    wall_step = wall / iters
    phases_s = {k: v / iters for k, v in phase_totals.items()}
    denom = max(wall, 1e-12)
    fractions = {k: v / denom for k, v in phase_totals.items()}
    fractions["residual"] = residual / denom
    # the residual competes for "dominant": when unattributed time
    # dwarfs every measured phase, naming a sliver phase would steer
    # the operator at exactly the wrong target (the runbook's "attack
    # the loop, not the kernels" case)
    dominant = max(fractions, key=fractions.get)
    return {
        "windows": len(steady),
        "iterations": iters,
        "wall_step_s": wall_step,
        "phases_s": phases_s,
        "residual_s": residual / iters,
        "overlap_s": overlap / iters,
        "fractions": fractions,
        "unattributed_fraction": residual / denom,
        "dominant_phase": dominant,
        "includes_compile_window": includes_compile,
    }


def roofline_verdict(flops_per_step: Optional[float],
                     bytes_per_step: Optional[float],
                     peak_flops: Optional[float],
                     hbm_bytes_per_s: Optional[float],
                     comm_bytes_per_step: Optional[float] = None,
                     ici_bytes_per_s: Optional[float] = None,
                     dcn_bytes_per_step: Optional[float] = None,
                     dcn_bytes_per_s: Optional[float] = None) \
        -> Optional[Dict[str, Any]]:
    """Compute-bound vs HBM-bound vs comm-bound vs dcn-bound from the
    analytic cost model: the step's minimum time on the MXU
    (flops/peak) against its minimum time on the memory system
    (bytes/bandwidth), on the interconnect when a comm budget is known
    (``collective_hlo_bytes`` / ``collective_bytes_total`` over ICI
    bandwidth), and — on a two-tier mesh — on the SLOW network tier
    (the cross-slice payload from ``cross_group_hlo_bytes`` or the
    hierarchical ``grad_allreduce_bytes`` floor, over DCN bandwidth).
    The largest floor is the binding resource; ``attainable_step_s``
    is the best step time this program can reach on this device no
    matter how well scheduled.  A ``dcn_bound`` verdict says: compress
    the cross-slice hop or grow the slice — more ICI won't help.
    Returns None when no floor is computable; ``verdict`` is None with
    fewer than two floors (nothing to compare)."""
    t_compute = (flops_per_step / peak_flops
                 if flops_per_step and peak_flops else None)
    t_hbm = (bytes_per_step / hbm_bytes_per_s
             if bytes_per_step and hbm_bytes_per_s else None)
    t_comm = (comm_bytes_per_step / ici_bytes_per_s
              if comm_bytes_per_step and ici_bytes_per_s else None)
    t_dcn = (dcn_bytes_per_step / dcn_bytes_per_s
             if dcn_bytes_per_step and dcn_bytes_per_s else None)
    floors = {"compute_bound": t_compute, "hbm_bound": t_hbm,
              "comm_bound": t_comm, "dcn_bound": t_dcn}
    known = {k: v for k, v in floors.items() if v is not None}
    if not known:
        return None
    verdict = (max(known, key=known.get) if len(known) > 1 else None)
    out: Dict[str, Any] = {
        "verdict": verdict,
        "min_compute_s": t_compute,
        "min_hbm_s": t_hbm,
        "attainable_step_s": max(known.values()),
    }
    if t_comm is not None:
        out["min_comm_s"] = t_comm
    if t_dcn is not None:
        out["min_dcn_s"] = t_dcn
    if flops_per_step and bytes_per_step:
        out["arithmetic_intensity_flops_per_byte"] = (
            flops_per_step / bytes_per_step)
    if peak_flops and hbm_bytes_per_s:
        out["machine_balance_flops_per_byte"] = (
            peak_flops / hbm_bytes_per_s)
    return out


def attribution_report(records: List[Dict[str, Any]],
                       flops_per_step: Optional[float] = None,
                       bytes_per_step: Optional[float] = None,
                       peak_spec_flops: Optional[float] = None,
                       hbm_bytes_per_s: Optional[float] = None,
                       device_kind: Optional[str] = None,
                       skip_first: int = 1,
                       comm_bytes_per_step: Optional[float] = None,
                       ici_bytes_per_s: Optional[float] = None,
                       dcn_bytes_per_step: Optional[float] = None,
                       dcn_bytes_per_s: Optional[float] = None) \
        -> Optional[Dict[str, Any]]:
    """The full perf-attribution table: phase decomposition + MFU
    accounting + roofline verdict, as one JSON-able dict.

    MFU is stated two ways: ``vs_spec`` uses the
    wall step time (the headline — what a user experiences), while
    ``device_vs_spec`` uses only the measured
    device-compute phase (what the chip achieves while actually busy);
    the gap between the two is precisely what the host phases
    cost.  ``peak_*`` default from the :func:`device_peak_flops` /
    :func:`device_hbm_bytes_per_s` tables when ``device_kind`` is
    given.  Publishes no gauge (the
    ``step_unattributed_fraction`` gauge stays per-window, written
    only by the drain worker — one writer, one semantic; the run
    aggregate lives in this report)."""
    report = attribute_windows(records, skip_first=skip_first)
    if report is None:
        return None
    if peak_spec_flops is None:
        peak_spec_flops = device_peak_flops(device_kind)
    if hbm_bytes_per_s is None:
        hbm_bytes_per_s = device_hbm_bytes_per_s(device_kind)
    if ici_bytes_per_s is None:
        ici_bytes_per_s = device_ici_bytes_per_s(device_kind)
    if dcn_bytes_per_s is None:
        dcn_bytes_per_s = device_dcn_bytes_per_s(device_kind)
    if device_kind:
        report["device_kind"] = device_kind
    if flops_per_step:
        report["flops_per_step"] = float(flops_per_step)
    if bytes_per_step:
        report["bytes_per_step"] = float(bytes_per_step)
    if comm_bytes_per_step:
        # comm is a named contributor hiding inside device_compute (the
        # collectives execute on-device) and, when the host can't keep
        # up with the ICI, inside the residual — state how much of the
        # measured device phase the comm floor alone explains
        comm: Dict[str, Any] = {
            "bytes_per_step": float(comm_bytes_per_step)}
        if ici_bytes_per_s:
            t_comm = comm_bytes_per_step / ici_bytes_per_s
            comm["min_comm_s"] = t_comm
            dev_s = report["phases_s"]["device_compute"]
            if dev_s > 0:
                comm["fraction_of_device_compute"] = min(
                    t_comm / dev_s, 1.0)
        report["comm"] = comm
    if dcn_bytes_per_step:
        # the slow-tier slice of the comm budget, stated on its own:
        # the dcn hop has its own (much lower) bandwidth floor, and on
        # a multi-slice step it is usually the one that binds
        dcn: Dict[str, Any] = {
            "bytes_per_step": float(dcn_bytes_per_step)}
        if dcn_bytes_per_s:
            dcn["min_dcn_s"] = dcn_bytes_per_step / dcn_bytes_per_s
        report["dcn"] = dcn
    wall_step = report["wall_step_s"]
    device_step = report["phases_s"]["device_compute"]
    mfu: Dict[str, float] = {}
    if flops_per_step and peak_spec_flops and wall_step > 0:
        mfu["vs_spec"] = flops_per_step / wall_step / peak_spec_flops
    if flops_per_step and peak_spec_flops and device_step > 0:
        mfu["device_vs_spec"] = (
            flops_per_step / device_step / peak_spec_flops)
    if mfu:
        report["mfu"] = mfu
    roof = roofline_verdict(
        flops_per_step, bytes_per_step,
        peak_spec_flops, hbm_bytes_per_s,
        comm_bytes_per_step=comm_bytes_per_step,
        ici_bytes_per_s=ici_bytes_per_s,
        dcn_bytes_per_step=dcn_bytes_per_step,
        dcn_bytes_per_s=dcn_bytes_per_s)
    if roof is not None:
        report["roofline"] = roof
    return report


def optimizer_perf_status(opt) -> Optional[Dict[str, Any]]:
    """The trainer's ``perf`` contribution to ``GET /statusz``: the
    cumulative attribution over this run's readback windows plus the
    latest window raw, so an operator can see where time is going
    mid-run without waiting for the artifact."""
    records = getattr(opt, "window_records", None)
    if not records:
        return None
    report = attribute_windows(records)
    last = records[-1]
    return {
        "attribution": report,
        "last_window": {
            "iterations": last.get("iterations"),
            "wall_s": last.get("wall_s"),
            **{key: last.get(key) for key in _PHASE_KEYS.values()},
        },
        "flops_per_step": getattr(opt, "compiled_flops_per_iteration",
                                  None),
    }

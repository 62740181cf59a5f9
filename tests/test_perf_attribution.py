"""Perf-attribution layer (telemetry.perf): step-time decomposition
(phases + residual summing to wall), MFU/roofline accounting, the
xla_cost cost_breakdown satellite, and the
optimizer's window-record capture end-to-end — including the
stalled-pipeline chaos run attributing the gap to data-wait.
"""

import numpy as np
import pytest

from bigdl_tpu import nn, telemetry
from bigdl_tpu.telemetry import families, perf
from bigdl_tpu.utils.xla_cost import (
    compiled_bytes, compiled_flops, cost_breakdown,
)


@pytest.fixture(autouse=True)
def _telemetry_clean():
    """Leave the process in the repo-wide default (disabled, zeroed)."""
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.disable()


def _rec(iters=1, wall=1.0, fetch=0.1, stage=0.2, block=0.5, rb=0.1,
         sync=True):
    return {"iterations": iters, "wall_s": wall, "data_wait_s": fetch,
            "host_staging_s": stage, "device_compute_s": block,
            "readback_s": rb, "t_ready": 0.0, "sync": sync}


# --------------------------------------------------------------------------
# attribution math on synthetic streams with known phase durations
# --------------------------------------------------------------------------

class TestAttributionMath:
    def test_decomposition_sums_to_wall(self):
        # compile window (skipped) + 4 steady windows of known phases
        recs = [_rec(wall=9.0)] + [_rec() for _ in range(4)]
        rep = perf.attribute_windows(recs)
        assert rep["windows"] == 4 and rep["iterations"] == 4
        assert not rep["includes_compile_window"]
        assert rep["wall_step_s"] == pytest.approx(1.0)
        # phases land exactly where the synthetic stream put them
        assert rep["phases_s"]["data_wait"] == pytest.approx(0.1)
        assert rep["phases_s"]["host_staging"] == pytest.approx(0.2)
        assert rep["phases_s"]["device_compute"] == pytest.approx(0.5)
        assert rep["phases_s"]["readback"] == pytest.approx(0.1)
        # the residual is explicit, non-negative, and closes the sum
        assert rep["residual_s"] == pytest.approx(0.1)
        assert rep["residual_s"] >= 0.0
        total = (sum(rep["phases_s"].values()) + rep["residual_s"]
                 - rep["overlap_s"])
        assert total == pytest.approx(rep["wall_step_s"], rel=1e-9)
        assert rep["dominant_phase"] == "device_compute"
        assert rep["unattributed_fraction"] == pytest.approx(0.1)

    def test_multi_iteration_windows_amortize(self):
        # 2 windows x 5 iterations: per-step values divide by 10
        recs = [_rec()] + [_rec(iters=5, wall=5.0, fetch=1.0, stage=0.5,
                                block=3.0, rb=0.25) for _ in range(2)]
        rep = perf.attribute_windows(recs)
        assert rep["iterations"] == 10
        assert rep["wall_step_s"] == pytest.approx(1.0)
        assert rep["phases_s"]["data_wait"] == pytest.approx(0.2)
        assert rep["phases_s"]["device_compute"] == pytest.approx(0.6)
        assert rep["residual_s"] == pytest.approx(0.05)

    def test_overlap_is_reported_not_rescaled(self):
        # async drain: measured phases over-sum the completion-to-
        # completion wall — residual clamps at 0, the excess is named
        recs = [_rec()] + [_rec(wall=1.0, fetch=0.5, stage=0.5,
                                block=0.4, rb=0.1, sync=False)]
        rep = perf.attribute_windows(recs)
        assert rep["residual_s"] == 0.0
        assert rep["overlap_s"] == pytest.approx(0.5)
        total = (sum(rep["phases_s"].values()) + rep["residual_s"]
                 - rep["overlap_s"])
        assert total == pytest.approx(rep["wall_step_s"], rel=1e-9)

    def test_empty_and_compile_only_streams(self):
        assert perf.attribute_windows([]) is None
        assert perf.attribute_windows(None) is None
        # one window: nothing steady to skip into — used whole, flagged
        rep = perf.attribute_windows([_rec()])
        assert rep["includes_compile_window"]
        assert rep["windows"] == 1

    def test_negative_clock_skew_clamped(self):
        recs = [_rec()] + [_rec(fetch=-0.5)]
        rep = perf.attribute_windows(recs)
        assert rep["phases_s"]["data_wait"] == 0.0
        assert rep["residual_s"] >= 0.0

    def test_fractions_sum_to_one_minus_overlap(self):
        recs = [_rec()] + [_rec() for _ in range(3)]
        rep = perf.attribute_windows(recs)
        assert sum(rep["fractions"].values()) == pytest.approx(1.0)

    def test_dominant_residual_when_unattributed_dwarfs_phases(self):
        # the pre-fix XLA:CPU regime: phases are slivers, residual is
        # the story — the diagnosis must say so, not name a sliver
        recs = [_rec()] + [_rec(wall=1.0, fetch=0.01, stage=0.02,
                                block=0.03, rb=0.01) for _ in range(2)]
        rep = perf.attribute_windows(recs)
        assert rep["dominant_phase"] == "residual"
        assert rep["unattributed_fraction"] == pytest.approx(0.93)

    def test_accepts_deque_input(self):
        from collections import deque
        recs = deque([_rec(), _rec(), _rec()], maxlen=8)
        rep = perf.attribute_windows(recs)
        assert rep["windows"] == 2  # compile window skipped


class TestRoofline:
    def test_hbm_bound_verdict(self):
        # 1 TFLOP over 10 GB on a 100 TF/s / 100 GB/s device:
        # compute floor 0.01 s, memory floor 0.1 s -> HBM bound
        v = perf.roofline_verdict(1e12, 10e9, 100e12, 100e9)
        assert v["verdict"] == "hbm_bound"
        assert v["min_compute_s"] == pytest.approx(0.01)
        assert v["min_hbm_s"] == pytest.approx(0.1)
        assert v["attainable_step_s"] == pytest.approx(0.1)
        assert v["arithmetic_intensity_flops_per_byte"] == pytest.approx(100)
        assert v["machine_balance_flops_per_byte"] == pytest.approx(1000)

    def test_compute_bound_verdict(self):
        # compute floor 10 s dwarfs the 0.01 s memory floor
        v = perf.roofline_verdict(1e15, 1e9, 100e12, 100e9)
        assert v["verdict"] == "compute_bound"
        assert v["attainable_step_s"] == pytest.approx(10.0)

    def test_partial_inputs(self):
        assert perf.roofline_verdict(None, None, 1e12, 1e9) is None
        v = perf.roofline_verdict(1e12, None, 100e12, 100e9)
        assert v["verdict"] is None  # one floor only: no comparison
        assert v["attainable_step_s"] == pytest.approx(0.01)

    def test_device_capability_tables(self):
        assert perf.device_peak_flops("TPU v5 lite") == pytest.approx(
            197e12)
        assert perf.device_peak_flops("TPU v4") == pytest.approx(275e12)
        assert perf.device_peak_flops("cpu") is None
        assert perf.device_peak_flops(None) is None
        assert perf.device_hbm_bytes_per_s("TPU v5 lite") == \
            pytest.approx(819e9)
        assert perf.device_hbm_bytes_per_s("weird-chip") is None

    @pytest.mark.parametrize("fn,v5e", [
        (perf.device_peak_flops, 197e12),
        (perf.device_hbm_bytes_per_s, 819e9),
        (perf.device_ici_bytes_per_s, 200e9),
        (perf.device_dcn_bytes_per_s, 12.5e9),
    ])
    def test_unknown_tpu_kind_is_an_error(self, fn, v5e, monkeypatch):
        # the v5e reports itself as "TPU v5 lite"; the CPU the tests run
        # on has no peak; a TPU the table does not know must raise so a
        # utilization never silently disappears on a new chip
        monkeypatch.delenv("BIGDL_TPU_DCN_BYTES_PER_S", raising=False)
        assert fn("TPU v5 lite") == pytest.approx(v5e)
        assert fn("cpu") is None
        with pytest.raises(ValueError, match="unknown TPU device_kind"):
            fn("TPU v9 hyper")

    def test_attribution_report_unknown_tpu_raises(self):
        with pytest.raises(ValueError, match="unknown TPU device_kind"):
            perf.attribution_report([_rec(), _rec()], flops_per_step=1e12,
                                    device_kind="TPU v9 hyper")


class TestAttributionReport:
    def test_mfu_overall_vs_device(self):
        # wall 1.0 s/step with 0.5 s device-compute; 50 TFLOP/step on a
        # 100 TF/s spec part: overall MFU 0.5, device-busy MFU 1.0
        recs = [_rec()] + [_rec() for _ in range(2)]
        rep = perf.attribution_report(
            recs, flops_per_step=50e12, bytes_per_step=100e9,
            peak_spec_flops=100e12,
            hbm_bytes_per_s=100e9)
        assert rep["mfu"]["vs_spec"] == pytest.approx(0.5)
        assert rep["mfu"]["device_vs_spec"] == pytest.approx(1.0)
        assert set(rep["mfu"]) == {"vs_spec", "device_vs_spec"}
        # memory floor 1.0 s vs compute floor 0.5 s (vs the spec
        # peak): HBM bound
        assert rep["roofline"]["verdict"] == "hbm_bound"
        assert rep["flops_per_step"] == 50e12

    def test_peaks_default_from_device_kind(self):
        recs = [_rec(), _rec()]
        rep = perf.attribution_report(
            recs, flops_per_step=197e12, bytes_per_step=819e9,
            device_kind="TPU v5 lite")
        assert rep["mfu"]["vs_spec"] == pytest.approx(1.0)
        # bytes floor == compute floor here is 1s vs 1s -> compute wins
        # the tie (strictly-greater test), so just assert a verdict
        assert rep["roofline"]["verdict"] in ("hbm_bound",
                                              "compute_bound")
        assert rep["device_kind"] == "TPU v5 lite"

    def test_report_publishes_no_gauge(self):
        telemetry.enable()
        telemetry.reset()
        before = telemetry.prometheus_text()
        recs = [_rec(), _rec()]
        rep = perf.attribution_report(
            recs, flops_per_step=40e12, peak_spec_flops=80e12)
        assert rep["mfu"]["vs_spec"] == pytest.approx(0.5)
        # the residual gauge has exactly ONE writer (the drain worker,
        # per window) — a report must not overwrite it with the run
        # aggregate, or a scrape's value depends on who ran last
        assert families.step_unattributed_fraction().value() == 0.0
        assert telemetry.prometheus_text() == before

    def test_report_without_cost_model(self):
        rep = perf.attribution_report([_rec(), _rec()])
        assert "mfu" not in rep and "roofline" not in rep
        assert rep["residual_s"] >= 0.0


# --------------------------------------------------------------------------
# xla_cost.cost_breakdown: missing-key vs legitimate-zero, one pass
# --------------------------------------------------------------------------

class _FakeCompiled:
    def __init__(self, analysis, wrap_list=False, raise_=False):
        self.analysis = analysis
        self.wrap_list = wrap_list
        self.raise_ = raise_
        self.calls = 0

    def cost_analysis(self):
        self.calls += 1
        if self.raise_:
            raise RuntimeError("no analysis on this backend")
        return [self.analysis] if self.wrap_list else self.analysis


class TestCostBreakdown:
    def test_all_present(self):
        c = _FakeCompiled({"flops": 100.0, "bytes accessed": 50.0,
                           "transcendentals": 7.0})
        # comm_bytes: None — the fake has no HLO text to read
        assert cost_breakdown(c) == {"flops": 100.0, "bytes": 50.0,
                                     "transcendentals": 7.0,
                                     "comm_bytes": None}

    def test_zero_is_legitimate_not_missing(self):
        c = _FakeCompiled({"flops": 0.0, "bytes accessed": 0,
                           "transcendentals": 0.0})
        out = cost_breakdown(c)
        assert out["flops"] == 0.0 and out["flops"] is not None
        assert out["bytes"] == 0.0
        assert out["transcendentals"] == 0.0

    def test_missing_keys_are_none(self):
        c = _FakeCompiled({"flops": 10.0})
        out = cost_breakdown(c)
        assert out["flops"] == 10.0
        assert out["bytes"] is None
        assert out["transcendentals"] is None

    def test_negative_sentinel_and_non_numeric_are_none(self):
        c = _FakeCompiled({"flops": -1.0, "bytes accessed": "n/a",
                           "transcendentals": 3.0})
        out = cost_breakdown(c)
        assert out["flops"] is None
        assert out["bytes"] is None
        assert out["transcendentals"] == 3.0

    def test_list_wrapped_and_raising_analyses(self):
        c = _FakeCompiled({"flops": 5.0, "bytes accessed": 6.0,
                           "transcendentals": 0.0}, wrap_list=True)
        assert cost_breakdown(c)["bytes"] == 6.0
        bad = _FakeCompiled({}, raise_=True)
        assert cost_breakdown(bad) == {"flops": None, "bytes": None,
                                       "transcendentals": None,
                                       "comm_bytes": None}

    def test_single_pass(self):
        c = _FakeCompiled({"flops": 1.0, "bytes accessed": 2.0,
                           "transcendentals": 3.0})
        cost_breakdown(c)
        assert c.calls == 1

    def test_existing_helpers_agree(self):
        c = _FakeCompiled({"flops": 9.0, "bytes accessed": 0.0})
        assert compiled_flops(c) == 9.0
        assert compiled_bytes(c) == 0.0  # zero, not None (PR-4 fix)


# --------------------------------------------------------------------------
# end-to-end: the optimizer's window records drive real attribution
# --------------------------------------------------------------------------

def _mini_dataset(n=32, feature=6, classes=4, seed=0):
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.dataset import Sample
    rng = np.random.default_rng(seed)
    samples = [Sample(rng.normal(size=(feature,)).astype(np.float32),
                      int(rng.integers(1, classes + 1)))
               for _ in range(n)]
    return DataSet.array(samples).transform(SampleToMiniBatch(16))


def _mini_model():
    return nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 4),
                         nn.LogSoftMax())


class TestOptimizerCaptureE2E:
    def test_window_records_statusz_and_families(self):
        from bigdl_tpu.optim import Optimizer, Trigger
        telemetry.enable()
        telemetry.reset()
        opt = (Optimizer(_mini_model(), _mini_dataset(),
                         nn.ClassNLLCriterion())
               .set_end_when(Trigger.max_epoch(4)))
        opt.optimize()

        recs = opt.window_records
        assert recs, "optimizer recorded no windows"
        for r in recs:
            assert r["iterations"] >= 1 and r["wall_s"] >= 0.0
            for key in ("data_wait_s", "host_staging_s",
                        "device_compute_s", "readback_s"):
                assert r[key] >= 0.0
        # the real stream obeys the published invariant
        rep = perf.attribute_windows(recs)
        total = (sum(rep["phases_s"].values()) + rep["residual_s"]
                 - rep["overlap_s"])
        assert total == pytest.approx(rep["wall_step_s"], rel=1e-6)
        assert rep["residual_s"] >= 0.0

        # /statusz surfaces the same attribution live
        st = opt.statusz()
        assert st["perf"] is not None
        assert st["perf"]["attribution"]["wall_step_s"] == \
            pytest.approx(rep["wall_step_s"])
        assert set(st["perf"]["last_window"]) >= {
            "iterations", "wall_s", "data_wait_s", "host_staging_s",
            "device_compute_s", "readback_s"}

        # preregistered families got real observations
        h = families.step_phase_seconds()
        for phase in perf.PHASES:
            snap = h.labels(phase).snapshot()
            assert snap["count"] == len(recs), phase
        # residual gauge was set from the final window
        assert 0.0 <= families.step_unattributed_fraction().value() <= 1.0

    def test_window_records_are_bounded(self, monkeypatch):
        # a multi-million-iteration run must not grow host memory one
        # dict per window forever: the record stream is a deque capped
        # by BIGDL_TPU_WINDOW_RECORDS_CAP
        from bigdl_tpu.optim import Optimizer, Trigger
        monkeypatch.setenv("BIGDL_TPU_WINDOW_RECORDS_CAP", "3")
        opt = (Optimizer(_mini_model(), _mini_dataset(),
                         nn.ClassNLLCriterion())
               .set_end_when(Trigger.max_epoch(6)))
        opt.optimize()
        assert len(opt.window_records) == 3  # 6 windows flushed, 3 kept
        assert perf.attribute_windows(opt.window_records) is not None

    def test_off_by_default_records_still_exist(self):
        # telemetry disabled: the phase stream (plain floats, no
        # metrics) still exists so harnesses can attribute without
        # flipping the global switch
        from bigdl_tpu.optim import Optimizer, Trigger
        assert not telemetry.enabled()
        opt = (Optimizer(_mini_model(), _mini_dataset(),
                         nn.ClassNLLCriterion())
               .set_end_when(Trigger.max_epoch(2)))
        opt.optimize()
        assert opt.window_records
        assert families.step_phase_seconds().labels(
            "data_wait").snapshot()["count"] == 0

    def test_stalled_pipeline_attributes_to_data_wait(self):
        # chaos delays every batch fetch; the attribution must point at
        # data_wait — the question ROADMAP item 1 wants answered per
        # phase, demonstrated end-to-end
        from bigdl_tpu.optim import Optimizer, Trigger
        from bigdl_tpu.utils import chaos
        telemetry.enable()
        telemetry.reset()
        chaos.reset()
        chaos.install(stall_pipeline_s=0.05)
        try:
            opt = (Optimizer(_mini_model(), _mini_dataset(),
                             nn.ClassNLLCriterion())
                   .set_end_when(Trigger.max_epoch(4)))
            opt.optimize()
        finally:
            chaos.reset()
        rep = perf.attribute_windows(opt.window_records)
        assert rep["dominant_phase"] == "data_wait", rep
        assert rep["fractions"]["data_wait"] > 0.3, rep
        assert rep["residual_s"] >= 0.0

    def test_statusz_perf_none_before_any_window(self):
        from bigdl_tpu.optim import Optimizer, Trigger
        opt = (Optimizer(_mini_model(), _mini_dataset(),
                         nn.ClassNLLCriterion())
               .set_end_when(Trigger.max_epoch(1)))
        st = opt.statusz()  # before optimize(): no records yet
        assert st["perf"] is None

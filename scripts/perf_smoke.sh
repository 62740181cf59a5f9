#!/usr/bin/env bash
# Perf-attribution smoke (CPU only), locking the acceptance behavior of
# the perf layer (docs/performance.md "Attributing an MFU gap"):
#
#   1. a short Optimizer.optimize() loop emits a step-time attribution
#      table whose measured phases + residual sum to the measured wall
#      step time (exact invariant, overlap-aware) with a non-negative
#      residual, and the step_phase_seconds/step_unattributed_fraction
#      families carry real observations;
#   2. the metric families pass scripts/metrics_lint.py (fatal form).
#
# Standalone: exits non-zero on any failed assertion.
set -o pipefail
cd "$(dirname "$0")/.."

# ---- 1. attribution table from a real optimize loop ---------------------
env JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import numpy as np

from bigdl_tpu import nn, telemetry
from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
from bigdl_tpu.dataset.dataset import Sample
from bigdl_tpu.optim import Optimizer, Trigger
from bigdl_tpu.telemetry import families, perf
from bigdl_tpu.utils import set_seed

telemetry.enable()
telemetry.reset()
set_seed(7)

rng = np.random.default_rng(0)
samples = [Sample(rng.normal(size=(6,)).astype(np.float32),
                  int(rng.integers(1, 5))) for _ in range(32)]
model = nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 4),
                      nn.LogSoftMax())
data = DataSet.array(samples).transform(SampleToMiniBatch(16))
opt = (Optimizer(model, data, nn.ClassNLLCriterion())
       .set_end_when(Trigger.max_epoch(5)))
opt.optimize()

assert opt.window_records, "no window records captured"
rep = perf.attribution_report(opt.window_records)
assert rep is not None, "no attribution table"
# the acceptance invariant: phases + residual sum to measured wall
total = sum(rep["phases_s"].values()) + rep["residual_s"] - rep["overlap_s"]
assert abs(total - rep["wall_step_s"]) <= 1e-9 * max(rep["wall_step_s"], 1.0), \
    f"phases do not sum to wall: {rep}"
assert rep["residual_s"] >= 0.0, rep
assert set(rep["phases_s"]) == set(perf.PHASES), rep
assert 0.0 <= rep["unattributed_fraction"] <= 1.0, rep

for phase in perf.PHASES:
    snap = families.step_phase_seconds().labels(phase).snapshot()
    assert snap["count"] == len(opt.window_records), (phase, snap)

st = opt.statusz()
assert st["perf"] and st["perf"]["attribution"], "statusz perf missing"
print("perf_smoke[1]: attribution OK "
      f"(wall {rep['wall_step_s'] * 1e3:.2f} ms/step, dominant "
      f"{rep['dominant_phase']}, residual {rep['residual_s'] * 1e3:.2f} ms, "
      f"{rep['windows']} windows)")
PY

# ---- 2. new families pass the fatal metrics lint ------------------------
python scripts/metrics_lint.py || exit 1

echo "perf_smoke: OK (attribution invariant, lint)"

#!/usr/bin/env bash
# Tier-1 verify — THE line builders and CI must both run (ROADMAP.md).
# Any edit here must be mirrored into ROADMAP.md "Tier-1 verify" and
# vice versa; the whole point of this wrapper is that there is exactly
# one encoding of the command.
set -o pipefail
cd "$(dirname "$0")/.."
rm -f /tmp/_t1.log
timeout -k 10 1500 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
  -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
dots=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
  | tr -cd . | wc -c)
echo DOTS_PASSED=$dots
# delta vs the recorded baseline so a regression is visible at a glance;
# update scripts/tier1_baseline.txt when a PR legitimately moves the count
base_file="$(dirname "$0")/tier1_baseline.txt"
if [ -f "$base_file" ]; then
  base=$(tr -cd 0-9 < "$base_file")
  echo "DOTS_DELTA=$((dots - base)) (baseline $base)"
fi
# telemetry catalog lint (metric families AND span inventory, both
# directions): non-fatal here (ride-along visibility); the standalone
# `python scripts/metrics_lint.py` form is fatal
python "$(dirname "$0")/metrics_lint.py" --warn-only || true
# graftlint static-analysis suite (trace safety, lock discipline +
# lock order, thread lifecycle, collective accounting, clock
# discipline): AST passes only here — warn-only ride-along; run
# `scripts/lint.sh` standalone for the fatal form incl. the
# compiled-HLO invariant passes
bash "$(dirname "$0")/lint.sh" --warn-only --ast-only \
  | tail -n 2 || true
# parallelism-conformance budget matrix (composition x collective-byte
# gate vs scripts/parallel_budget.json): warn-only ride-along — the
# probe compiles are cached under /tmp keyed by source hash, so an
# unchanged tree pays one file-hash pass, not the full re-lower; run
# `scripts/lint.sh --budget` standalone for the fatal form
env JAX_PLATFORMS=cpu python -m bigdl_tpu.analysis \
  --warn-only --budget-only | tail -n 1 || true
# health-watchdog smoke (chaos mini-train, /statusz, flight recorder):
# warn-only ride-along; run scripts/health_smoke.sh standalone for the
# fatal form.  mktemp, not a fixed /tmp name: parallel runs must not
# clobber each other's log
smoke_log=$(mktemp /tmp/health_smoke.XXXXXX.log)
if bash "$(dirname "$0")/health_smoke.sh" >"$smoke_log" 2>&1; then
  tail -n 1 "$smoke_log"
else
  echo "health_smoke: FAILED (non-fatal ride-along; see $smoke_log)"
fi
# data-pipeline smoke (seeded order equality + snapshot/restore):
# warn-only ride-along; run scripts/data_smoke.sh standalone for the
# fatal form
data_log=$(mktemp /tmp/data_smoke.XXXXXX.log)
if bash "$(dirname "$0")/data_smoke.sh" >"$data_log" 2>&1; then
  tail -n 1 "$data_log"
else
  echo "data_smoke: FAILED (non-fatal ride-along; see $data_log)"
fi
# mesh-observability smoke (collective bytes vs HLO cross-check, fleet
# /statusz + straggler, forced-OOM forensics): warn-only ride-along;
# run scripts/fleet_smoke.sh standalone for the fatal form
fleet_log=$(mktemp /tmp/fleet_smoke.XXXXXX.log)
if bash "$(dirname "$0")/fleet_smoke.sh" >"$fleet_log" 2>&1; then
  tail -n 1 "$fleet_log"
else
  echo "fleet_smoke: FAILED (non-fatal ride-along; see $fleet_log)"
fi
# hierarchical-sync / wire-compression smoke (HLO cross-slice bytes
# halve under bf16, int8 codec round-trip bound, hier+bf16 loss
# equivalence, pinned-slow dcn table -> dcn_bound): warn-only
# ride-along; run scripts/comm_smoke.sh standalone for the fatal form
comm_log=$(mktemp /tmp/comm_smoke.XXXXXX.log)
if bash "$(dirname "$0")/comm_smoke.sh" >"$comm_log" 2>&1; then
  tail -n 1 "$comm_log"
else
  echo "comm_smoke: FAILED (non-fatal ride-along; see $comm_log)"
fi
# elastic-resume smoke (chaos reshard 8 -> 2x4 / 4x2 with loss
# trajectories equal to the uninterrupted oracle, reshard
# flight-recorder event, fenced writer race): warn-only ride-along;
# run scripts/reshard_smoke.sh standalone for the fatal form
reshard_log=$(mktemp /tmp/reshard_smoke.XXXXXX.log)
if bash "$(dirname "$0")/reshard_smoke.sh" >"$reshard_log" 2>&1; then
  tail -n 1 "$reshard_log"
else
  echo "reshard_smoke: FAILED (non-fatal ride-along; see $reshard_log)"
fi
# serving-fabric smoke (3-replica router: session affinity, drain/
# deploy zero-drop, typed shedding under 2x overload within SLO,
# single-flight prefill dedup, disaggregated prefill bit-identity):
# warn-only ride-along; run scripts/router_smoke.sh standalone for the
# fatal form
router_log=$(mktemp /tmp/router_smoke.XXXXXX.log)
if bash "$(dirname "$0")/router_smoke.sh" >"$router_log" 2>&1; then
  tail -n 1 "$router_log"
else
  echo "router_smoke: FAILED (non-fatal ride-along; see $router_log)"
fi
# self-driving-fleet smoke (chaos kill -> controller replaces, spike
# -> scale-up, new checkpoint generation -> rolling zero-drop
# hot-deploy with bit-identical greedy rows, idle -> scale-down; no
# operator step anywhere): warn-only ride-along; run
# scripts/controller_smoke.sh standalone for the fatal form
controller_log=$(mktemp /tmp/controller_smoke.XXXXXX.log)
if bash "$(dirname "$0")/controller_smoke.sh" >"$controller_log" 2>&1; then
  tail -n 1 "$controller_log"
else
  echo "controller_smoke: FAILED (non-fatal ride-along; see $controller_log)"
fi
# request-reliability smoke (chaos hard-kill mid-decode -> failover
# with bit-identical stitched stream; flaky submits -> breaker opens
# -> half-open recovery): warn-only ride-along; run
# scripts/reliability_smoke.sh standalone for the fatal form
reliability_log=$(mktemp /tmp/reliability_smoke.XXXXXX.log)
if bash "$(dirname "$0")/reliability_smoke.sh" >"$reliability_log" 2>&1; then
  tail -n 1 "$reliability_log"
else
  echo "reliability_smoke: FAILED (non-fatal ride-along; see $reliability_log)"
fi
# sharded-embedding smoke (hybrid train loss == single-device baseline,
# compiled step provably sparse — a2a present, no dense table
# all-reduce — streaming HitRatio/NDCG resumes to the one-shot
# numbers, one scored request through the router with a shard-affinity
# key): warn-only ride-along; run scripts/embedding_smoke.sh
# standalone for the fatal form
embedding_log=$(mktemp /tmp/embedding_smoke.XXXXXX.log)
if bash "$(dirname "$0")/embedding_smoke.sh" >"$embedding_log" 2>&1; then
  tail -n 1 "$embedding_log"
else
  echo "embedding_smoke: FAILED (non-fatal ride-along; see $embedding_log)"
fi
# declarative-planner smoke (PartitionPlan dp2xtp2xpp2 losses == dp
# baseline, compiled 3D step moves bytes on all three axes with the
# dp sync within 2x the analytic floor, plan-stamped checkpoint
# resumed under a different plan): warn-only ride-along; run
# scripts/plan_smoke.sh standalone for the fatal form
plan_log=$(mktemp /tmp/plan_smoke.XXXXXX.log)
if bash "$(dirname "$0")/plan_smoke.sh" >"$plan_log" 2>&1; then
  tail -n 1 "$plan_log"
else
  echo "plan_smoke: FAILED (non-fatal ride-along; see $plan_log)"
fi
# request-tracing smoke (chaos hard-kill mid-decode -> ONE assembled
# trace across both replicas with exactly-once decode-span accounting,
# tail-retained with reason failover, TTFT exemplar resolving through
# /tracez?trace=<id>): warn-only ride-along; run
# scripts/trace_smoke.sh standalone for the fatal form
trace_log=$(mktemp /tmp/trace_smoke.XXXXXX.log)
if bash "$(dirname "$0")/trace_smoke.sh" >"$trace_log" 2>&1; then
  tail -n 1 "$trace_log"
else
  echo "trace_smoke: FAILED (non-fatal ride-along; see $trace_log)"
fi
exit $rc

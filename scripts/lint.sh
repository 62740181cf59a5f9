#!/usr/bin/env bash
# graftlint — the fatal static-analysis gate (docs/static_analysis.md).
#
#   scripts/lint.sh                 # fatal: AST + compiled-HLO passes
#   scripts/lint.sh --budget        # + parallelism-conformance budgets
#   scripts/lint.sh --warn-only     # CI ride-along: report, exit 0
#   scripts/lint.sh --ast-only      # skip the HLO compiles (fast)
#   scripts/lint.sh --budget-only   # ONLY the budget matrix (cached)
#
# Writes the machine report (every finding, suppressed ones included)
# to ${TMPDIR:-/tmp}/graftlint_report.json, never into the checkout.
# With --budget the budget verdicts (matrix per probe, parity ratios,
# reshard findings) land in the same report.
#
# The deliberately-broken negative legs run in
# tests/test_static_analysis.py; run them by hand with:
#   BIGDL_TPU_UNPIN_DCN_WIRE=1 python -m bigdl_tpu.analysis \
#     --hlo-only --select hlo-narrow-wire   # must FAIL
#   BIGDL_TPU_BUDGET_MISSPEC=1 python -m bigdl_tpu.analysis \
#     --budget-only --select hlo-reshard    # must FAIL
set -o pipefail
cd "$(dirname "$0")/.."

warn=""
hlo="--hlo"
budget=""
for arg in "$@"; do
  case "$arg" in
    --warn-only)   warn="--warn-only" ;;
    --ast-only)    hlo="" ;;
    --budget)      budget="--budget" ;;
    --budget-only) hlo=""; budget="--budget-only" ;;
    *) echo "lint.sh: unknown arg $arg" >&2; exit 2 ;;
  esac
done

report="${TMPDIR:-/tmp}/graftlint_report.json"

env JAX_PLATFORMS=cpu python -m bigdl_tpu.analysis \
  $hlo $budget $warn --json "$report"
rc=$?
echo "lint.sh: report written to $report"
exit $rc

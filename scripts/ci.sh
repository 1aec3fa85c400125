#!/usr/bin/env bash
# Tier-1 CI gate: the suite must COLLECT (zero import errors) and PASS
# within a time budget.
#
# Usage: scripts/ci.sh [--obs-smoke|--chaos-smoke] [extra pytest args]
#   --obs-smoke   run ONLY the observability smoke: a 3-step instrumented
#                 simulation that must emit a schema-valid metrics JSONL
#                 and pass the physics monitors (exit != 0 on violation)
#   --chaos-smoke run ONLY the chaos smoke: a seeded fault matrix (NaN
#                 poisoning, corrupt checkpoint, preemption, save-thread
#                 failure) on a tiny mesh; each class must recover with a
#                 final state bitwise equal to the fault-free run
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
BUDGET="${CI_TIME_BUDGET_S:-2400}"

if [[ "${1:-}" == "--obs-smoke" ]]; then
    exec timeout 600 python scripts/obs_smoke.py
fi

if [[ "${1:-}" == "--chaos-smoke" ]]; then
    exec timeout 600 python scripts/chaos_smoke.py
fi

# collection gate: any import error fails fast and loudly
timeout 300 python -m pytest -q --collect-only >/dev/null

# kernel-layer smoke: compile + run the horizontal-RHS benchmark on a tiny
# mesh (ref + fused + Pallas-interpret lateral-flux kernel) so import/shape
# regressions in the kernel layer fail fast
timeout 600 python -m benchmarks.bench_horizontal_rhs --dry-run >/dev/null

# observability smoke: instrumented 3-step run + JSONL schema validation
timeout 600 python scripts/obs_smoke.py >/dev/null

# the tier-1 command from ROADMAP.md, under the time budget
exec timeout "$BUDGET" python -m pytest -x -q "$@"

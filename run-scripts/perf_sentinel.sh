#!/usr/bin/env bash
# Perf-contract sentinel (tools/perf_sentinel.py): diff the
# DETERMINISTIC counters of the bench-shaped workloads against
# PERF_CONTRACT.json — fusion breaking (dispatch count up), the wire
# codec silently disabling (bytes_on_wire up), plan-build/optimism
# regressions, all caught without trusting a single wall clock.
#
#   run-scripts/perf_sentinel.sh          # check (exit 1 on regression)
#   run-scripts/perf_sentinel.sh snapshot # re-seed the contract
#
# Runs with the counter-relevant THRILL_TPU_* knobs CLEARED so the
# contract always compares default arming (running the module by hand
# with knobs set is the way to SEE a knob's counter cost — the check
# then fails on those counters, by design).
set -euo pipefail
cd "$(dirname "$0")/.."

mode="--check"
if [ "${1:-}" = "snapshot" ]; then
    mode="--snapshot"
    shift
fi

# scrub counter-relevant knobs: the contract is for DEFAULT arming
for v in THRILL_TPU_FUSE THRILL_TPU_OVERLAP THRILL_TPU_XCHG_CHUNKS \
         THRILL_TPU_XCHG_CAP_CACHE THRILL_TPU_XCHG_NARROW \
         THRILL_TPU_WIRE_COMPRESS THRILL_TPU_PLANNER \
         THRILL_TPU_PLAN_STORE THRILL_TPU_EXCHANGE \
         THRILL_TPU_LOCATION_DETECT THRILL_TPU_DUP_DETECT \
         THRILL_TPU_LOOP_REPLAY THRILL_TPU_FAULTS; do
    unset "$v" || true
done

exec env JAX_PLATFORMS=cpu \
    python -m thrill_tpu.tools.perf_sentinel "$mode" \
    "${1:-PERF_CONTRACT.json}"

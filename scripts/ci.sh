#!/usr/bin/env bash
# One-command tier-1 verification (ROADMAP.md "Tier-1 verify").
# Usage: scripts/ci.sh [--bench-smoke] [--incremental-smoke] [--compact-smoke] [--shard-smoke] [--ingress-smoke] [--pipeline-smoke] [--destm-wave-smoke] [--failover-smoke] [extra pytest args]
#
# --bench-smoke additionally runs benchmarks/engine_bench.py --smoke after
# the test suite: it executes every engine through the preserved legacy
# commit scans, the PR2 rebuild pipeline AND the PR3 incremental
# RoundState loop, asserting the store fingerprints / commit positions
# agree bitwise, so perf refactors of the commit machinery cannot
# silently diverge.
#
# --incremental-smoke runs benchmarks/engine_bench.py --incremental-smoke:
# incremental == rebuild store fingerprints and traces across all three
# engines (the RoundState equivalence gate).
#
# --compact-smoke runs benchmarks/engine_bench.py --compact-smoke:
# the PR4 gather-compacted cascade == the masked incremental loop ==
# rebuild, on store fingerprints and traces, across all three engines,
# plus run_live_compact == run_live at the primitive level (the
# compacted-execution equivalence gate).
#
# --shard-smoke runs benchmarks/engine_bench.py --shard-smoke under an
# 8-device host-platform mesh (XLA_FLAGS): the PR5 sharded store ==
# the dense store bitwise across engines and both code paths, including
# the per-shard write-back running one-shard-per-device via shard_map
# (the shard-decomposition equivalence gate).
#
# --ingress-smoke runs benchmarks/engine_bench.py --ingress-smoke: two
# PR6 IngressPool replicas fed the same arrival journal, drained under
# different budget schedules, agree bitwise through PotSession —
# fingerprints + replay logs — and a full journal replay reproduces the
# formed batch stream exactly (the deterministic-ingress gate).
#
# --pipeline-smoke runs benchmarks/engine_bench.py --pipeline-smoke: one
# PR7 arrival journal replayed through a serial session and pipelined
# sessions (pipeline_depth in {1, 2}, engines pcc + occ) under
# different drain budgets agrees bitwise — fingerprints, replay logs
# AND every pre-existing ExecTrace field (speculation cost may only
# appear in the new spec_* observables) — plus the blocked OCC wave
# solve is decision-identical with fewer while_loop trips (the
# cross-batch speculation equivalence gate).
#
# --destm-wave-smoke runs benchmarks/engine_bench.py --destm-wave-smoke:
# the PR10 wave-speculative DeSTM retry walk == the serial token walk
# bitwise — store fingerprints and every trace field except the wave
# observables (retry_waves / waves_per_round) — across K x contention x
# lane count, with retry_waves <= retry events everywhere and a strict
# wave-count reduction on the blind write-write best case (the
# wave-retry equivalence gate).
#
# --failover-smoke runs the FULL PR9 fault-injection matrix
# (REPRO_FAILOVER_FULL=1 tests/test_failover.py): replicas killed at
# deterministic (batch, phase) fault points — including real subprocess
# SIGKILLs and torn mid-snapshot tmp dirs — across engines {pcc, occ} x
# shards {1, 8} x pipeline_depth {0, 2} x two drain-budget schedules,
# each restored from its latest complete snapshot + the arrival-journal
# suffix and required to reconverge bitwise with the uninterrupted
# replica (the crash-consistent failover gate).  The victim/recovery
# subprocesses share the persistent XLA compile cache
# (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache/) so the matrix
# is not compile-bound.
#
# Stages do NOT short-circuit each other: every requested stage runs and
# the script exits non-zero if ANY stage failed (the last failing stage's
# exit code is propagated).
set -uo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

BENCH_SMOKE=0
INCREMENTAL_SMOKE=0
COMPACT_SMOKE=0
SHARD_SMOKE=0
INGRESS_SMOKE=0
PIPELINE_SMOKE=0
DESTM_WAVE_SMOKE=0
FAILOVER_SMOKE=0
PYTEST_ARGS=()
for arg in "$@"; do
  case "$arg" in
    --bench-smoke) BENCH_SMOKE=1 ;;
    --incremental-smoke) INCREMENTAL_SMOKE=1 ;;
    --compact-smoke) COMPACT_SMOKE=1 ;;
    --shard-smoke) SHARD_SMOKE=1 ;;
    --ingress-smoke) INGRESS_SMOKE=1 ;;
    --pipeline-smoke) PIPELINE_SMOKE=1 ;;
    --destm-wave-smoke) DESTM_WAVE_SMOKE=1 ;;
    --failover-smoke) FAILOVER_SMOKE=1 ;;
    *) PYTEST_ARGS+=("$arg") ;;
  esac
done

FAIL=0
run_stage() {
  local name="$1"
  shift
  echo "== ci stage: $name"
  "$@"
  local rc=$?
  if [[ $rc -ne 0 ]]; then
    echo "== ci stage FAILED: $name (exit $rc)" >&2
    FAIL=$rc
  fi
}

run_stage tier-1 python -m pytest -x -q "${PYTEST_ARGS[@]+"${PYTEST_ARGS[@]}"}"

if [[ "$BENCH_SMOKE" == "1" ]]; then
  run_stage bench-smoke python benchmarks/engine_bench.py --smoke
fi

if [[ "$INCREMENTAL_SMOKE" == "1" ]]; then
  run_stage incremental-smoke python benchmarks/engine_bench.py --incremental-smoke
fi

if [[ "$COMPACT_SMOKE" == "1" ]]; then
  run_stage compact-smoke python benchmarks/engine_bench.py --compact-smoke
fi

if [[ "$SHARD_SMOKE" == "1" ]]; then
  # run the equivalence suite on a real multi-device mesh: 8 host-platform
  # CPU devices, so the shard_map per-device write-back path is exercised
  run_stage shard-smoke env \
    XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
    python benchmarks/engine_bench.py --shard-smoke
fi

if [[ "$INGRESS_SMOKE" == "1" ]]; then
  run_stage ingress-smoke python benchmarks/engine_bench.py --ingress-smoke
fi

if [[ "$PIPELINE_SMOKE" == "1" ]]; then
  run_stage pipeline-smoke python benchmarks/engine_bench.py --pipeline-smoke
fi

if [[ "$DESTM_WAVE_SMOKE" == "1" ]]; then
  run_stage destm-wave-smoke python benchmarks/engine_bench.py --destm-wave-smoke
fi

if [[ "$FAILOVER_SMOKE" == "1" ]]; then
  run_stage failover-smoke env \
    REPRO_FAILOVER_FULL=1 \
    JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0 \
    python -m pytest -x -q tests/test_failover.py
fi

exit "$FAIL"

#!/usr/bin/env bash
# One-entry-point CI gate: tier-1 test suite + offload-engine smoke benchmark
# + the multi-tenant service check.
#
#   bash scripts/ci.sh           # full tier-1 + offload/planner/service smoke
#
# The smoke benchmark (benchmarks.run --smoke) runs a budgeted autotuning grid,
# proves the descriptor schedule cache (hit/miss telemetry), executes one 3D
# planned collective end-to-end per CollType — asserting the repeat dispatch
# hits the plan cache and that telemetry exposes cache_size + per-coll
# latency — reports the tuned-vs-fixed axis split, runs a 2-step DP
# trainer on a 2x2 CPU mesh with use_offload_engine=True, asserting the
# step-2 dispatch is a plan-cache hit and that loss/grads/params are bitwise
# equal to the raw shard_map baseline (plus planner-first remesh adoption),
# drives the multi-tenant broker, asserting coalesced dispatches are
# bitwise equal to direct engine dispatch with a coalesce factor > 1, and
# proves the plan-optimizer pass pipeline: fused plans bitwise-equal to
# unfused, fewer SCAN/EXSCAN communication rounds on multi-axis meshes, and
# a profiler-sourced per-schedule device latency in the engine telemetry,
# plus the chunked-streaming check: every chunked lowering bitwise-equal to
# the unchunked schedule and the tuned chunked plan beating C=1 wall-clock
# past the payload threshold.
# The service check (repro.testing.service_check) then exercises the broker
# in driver mode on a real 2x2 mesh: 4 concurrent tenant streams, bitwise
# equality, backpressure isolation, and registry split-winner inheritance.
# The pallas check (repro.testing.pallas_check) proves the fused-Pallas
# "NIC" kernel lowering backend on a 1x8 host mesh in interpret mode:
# SCAN/EXSCAN/BARRIER and both FUSED_SCAN_TOTAL forms bitwise-equal to
# the op-per-round lower_spmd reference, and non-zero-identity operators
# cleanly rejected by the capability gate (the engine's fallback path).
# The observability check (repro.testing.obs_check) proves the tracing
# layer: a traced 2x2 dispatch is bitwise-identical to the untraced one
# and yields >= 1 phase span plus the declared round spans per comm phase,
# with host+device trace merge and Prometheus rendering.
# The health check (repro.testing.health_check) proves the health stack on
# a 2x2 mesh: a synthetic 10ms delay planted on one link is attributed to
# exactly that (axis, src, dst) by the per-link straggler detector, a
# deadline-miss SLO burn-rate alert fires, every probed/driver dispatch
# stays bitwise-identical to the sim baseline, and the flight-recorder
# dump is valid JSON.
# The chaos check (repro.testing.chaos_check) proves the reliability
# stack on a 2x2 mesh: all five CollTypes bitwise-correct through seeded
# 5% message drop+corrupt chaos purely via retries, a poisoned queued
# payload quarantined by group bisection while clean neighbors complete,
# and the circuit breaker tripping into the raw-lax reference under 100%
# loss then recovering through a half-open probe, with /healthz tracking
# both transitions. benchmarks.obs_overhead then measures the
# flight-recorder cost on the smoke dispatch path, and
# benchmarks.reliability_overhead measures the reliable-dispatch happy
# path (checksums + retry bookkeeping) against the raw broker path.
# Finally, benchmarks.check_regression diffs the freshly-written BENCH
# artifacts against the committed baselines (snapshotted BEFORE the
# smoke run overwrites them): lost grid rows, lost bitwise/coalesce
# proofs, > 2x latency drift, flight-recorder overhead past 2%, or
# reliability overhead past 2% fail CI. Regressions in the
# offload/planner/service subsystems fail CI even when no unit test
# covers them yet.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "=== tier-1: pytest ==="
python -m pytest -x -q

echo
echo "=== offload-engine + planner + service smoke benchmark ==="
SMOKE_OUT="$(mktemp -t repro_smoke.XXXXXX.csv)"
trap 'rm -f "$SMOKE_OUT"' EXIT
# snapshot the committed BENCH baselines before --report-json rewrites them
BASE_DIR="$(mktemp -d -t repro_bench_base.XXXXXX)"
trap 'rm -f "$SMOKE_OUT"; rm -rf "$BASE_DIR"' EXIT
cp benchmarks/BENCH_fusion.json "$BASE_DIR/BENCH_fusion.json"
cp benchmarks/BENCH_service.json "$BASE_DIR/BENCH_service.json"
cp benchmarks/BENCH_obs.json "$BASE_DIR/BENCH_obs.json"
cp benchmarks/BENCH_reliability.json "$BASE_DIR/BENCH_reliability.json"
python -m benchmarks.run --smoke --report-json | tee "$SMOKE_OUT"
# the offloaded trainer gate runs in a process of its own: it fixes the host
# device count before importing jax
TRAIN_OUT="$(python -m repro.testing.train_offload_check 2 2 --steps 2)"
grep -q "^trainer_offload_summary,bitwise_equal,1,step2_cache_hit,1," <<<"$TRAIN_OUT" \
  || { echo "CI FAIL: offloaded trainer smoke missing or not bitwise"; exit 1; }
grep -q "^planned_smoke_summary," "$SMOKE_OUT" \
  || { echo "CI FAIL: planned 3D smoke section missing"; exit 1; }
grep -q "^service_smoke_summary,bitwise_equal,1,coalesce_gt1,1," "$SMOKE_OUT" \
  || { echo "CI FAIL: service smoke missing, not bitwise, or not coalescing"; exit 1; }
grep -q "^fusion_summary,bitwise_equal,1,rounds_reduced,1,device_latency,1," "$SMOKE_OUT" \
  || { echo "CI FAIL: plan-optimizer smoke missing, fused plan regressed the bitwise check, or rounds/device-latency not reported"; exit 1; }
grep -Eq "^chunking_check,.*,bitwise,1,win,1$" "$SMOKE_OUT" \
  || { echo "CI FAIL: chunked streaming check missing, not bitwise, or the tuned chunked plan no longer beats C=1 wall-clock"; exit 1; }
echo "fusion speedup: $(grep '^fusion_summary,' "$SMOKE_OUT")"
echo "chunked streaming: $(grep '^chunking_check,' "$SMOKE_OUT")"

echo
echo "=== multi-tenant service check (driver mode, 2x2 mesh) ==="
SVC_OUT="$(mktemp -t repro_service.XXXXXX.log)"
trap 'rm -f "$SMOKE_OUT" "$SVC_OUT"' EXIT
python -m repro.testing.service_check 2 2 | tee "$SVC_OUT"
grep -q "^service_check_summary,bitwise_equal,1,coalesce_gt1,1," "$SVC_OUT" \
  || { echo "CI FAIL: service check not bitwise or not coalescing"; exit 1; }
grep -q "^ALL-OK$" "$SVC_OUT" \
  || { echo "CI FAIL: service check did not pass"; exit 1; }

echo
echo "=== pallas lowering-backend check (fused kernel vs lower_spmd, 1x8) ==="
PAL_OUT="$(mktemp -t repro_pallas.XXXXXX.log)"
trap 'rm -f "$SMOKE_OUT" "$SVC_OUT" "$PAL_OUT"; rm -rf "$BASE_DIR"' EXIT
python -m repro.testing.pallas_check 8 | tee "$PAL_OUT"
grep -q "^pallas_check,scan:sum,p,8,bitwise,1$" "$PAL_OUT" \
  || { echo "CI FAIL: fused pallas kernel not bitwise-equal to lower_spmd"; exit 1; }
grep -q "^ALL-OK$" "$PAL_OUT" \
  || { echo "CI FAIL: pallas lowering-backend check did not pass"; exit 1; }

echo
echo "=== observability check (traced dispatch: spans + metrics + merge) ==="
OBS_OUT="$(mktemp -t repro_obs.XXXXXX.log)"
trap 'rm -f "$SMOKE_OUT" "$SVC_OUT" "$PAL_OUT" "$OBS_OUT"; rm -rf "$BASE_DIR"' EXIT
python -m repro.testing.obs_check 2 2 | tee "$OBS_OUT"
grep -q "^obs_check_summary,bitwise_equal,1," "$OBS_OUT" \
  || { echo "CI FAIL: traced dispatch not bitwise-identical"; exit 1; }
grep -q "^ALL-OK$" "$OBS_OUT" \
  || { echo "CI FAIL: observability check did not pass"; exit 1; }

echo
echo "=== health check (link attribution + SLO alerting, 2x2 mesh) ==="
HLT_OUT="$(mktemp -t repro_health.XXXXXX.log)"
trap 'rm -f "$SMOKE_OUT" "$SVC_OUT" "$PAL_OUT" "$OBS_OUT" "$HLT_OUT"; rm -rf "$BASE_DIR"' EXIT
python -m repro.testing.health_check 2 2 | tee "$HLT_OUT"
grep -q "^health_check_summary,bitwise_equal,1,.*attribution_ok,1,slo_alert,1,dump_valid,1," "$HLT_OUT" \
  || { echo "CI FAIL: health check lost bitwise equality, link attribution, SLO alerting, or the flight-recorder dump"; exit 1; }
grep -q "^ALL-OK$" "$HLT_OUT" \
  || { echo "CI FAIL: health check did not pass"; exit 1; }

echo
echo "=== chaos check (retries, bisection quarantine, breaker, 2x2 mesh) ==="
CHS_OUT="$(mktemp -t repro_chaos.XXXXXX.log)"
trap 'rm -f "$SMOKE_OUT" "$SVC_OUT" "$PAL_OUT" "$OBS_OUT" "$HLT_OUT" "$CHS_OUT"; rm -rf "$BASE_DIR"' EXIT
python -m repro.testing.chaos_check 2 2 | tee "$CHS_OUT"
grep -Eq "^chaos_check_summary,bitwise_equal,1,faults,[1-9][0-9]*,retries,[1-9][0-9]*,quarantine_ok,1,breaker_ok,1,healthz_ok,1$" "$CHS_OUT" \
  || { echo "CI FAIL: chaos check lost bitwise recovery, injected no faults, or lost quarantine/breaker/healthz behavior"; exit 1; }
grep -q "^ALL-OK$" "$CHS_OUT" \
  || { echo "CI FAIL: chaos check did not pass"; exit 1; }

echo
echo "=== flight-recorder overhead benchmark ==="
python -m benchmarks.obs_overhead

echo
echo "=== reliability overhead benchmark ==="
python -m benchmarks.reliability_overhead

echo
echo "=== benchmark regression gate (fresh BENCH vs committed baseline) ==="
REG_OUT="$(mktemp -t repro_reg.XXXXXX.log)"
trap 'rm -f "$SMOKE_OUT" "$SVC_OUT" "$PAL_OUT" "$OBS_OUT" "$HLT_OUT" "$CHS_OUT" "$REG_OUT"; rm -rf "$BASE_DIR"' EXIT
python -m benchmarks.check_regression \
  --baseline-fusion "$BASE_DIR/BENCH_fusion.json" \
  --fusion benchmarks/BENCH_fusion.json \
  --baseline-service "$BASE_DIR/BENCH_service.json" \
  --service benchmarks/BENCH_service.json \
  --baseline-obs "$BASE_DIR/BENCH_obs.json" \
  --obs benchmarks/BENCH_obs.json \
  --baseline-reliability "$BASE_DIR/BENCH_reliability.json" \
  --reliability benchmarks/BENCH_reliability.json \
  --require-per-round | tee "$REG_OUT"
grep -q "^ALL-OK$" "$REG_OUT" \
  || { echo "CI FAIL: benchmark regression gate did not pass"; exit 1; }

echo
echo "CI OK"

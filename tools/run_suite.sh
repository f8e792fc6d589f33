#!/bin/bash
# Full test suite in TWO pytest slices, with crash-retry, plus the
# lint / selfcheck / fault-matrix passes.  (The driver's tier-1 gate is
# the plain pytest command in /root/TESTS_LAST_RUN.json; this runner is
# the fuller local pass.)
#
# XLA:CPU maps every compiled kernel separately: one EC program is
# 3,000-8,000 memory maps, and a long-running test process that has
# compiled a dozen of them reaches the kernel's vm.max_map_count
# (65,530) — the next compile or cache load then dies with SIGABRT or
# SIGSEGV.  tests/conftest.py drops jax's executable caches at a
# module's teardown once a process is past 40,000 maps (PR 23), and the
# suite shares one read-write persistent cache (.jax_cache/), so what
# is needed again is a load.  The retry below stays as a belt.
#
# Crash exits (>=128) and slice timeouts (124) are retried once, then
# the slice finishes file-per-process; test FAILURES (rc 1) are never
# retried.
set -u
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"

run_slice() {
  local name="$1"; shift
  local attempt rc f
  for attempt in 1 2; do
    # slice-level hang guard: a test blocking on a silent daemon must
    # never stall the suite for hours
    timeout 3600 python -m pytest "$@" -x -q && return 0
    rc=$?
    if [ "$rc" -ne 124 ] && [ "$rc" -lt 128 ]; then
      echo "slice $name failed rc=$rc (test failure, not retried)"
      return "$rc"
    fi
    echo "slice $name crashed/timed out rc=$rc (attempt $attempt) —" \
         "retrying"
  done
  # every file is known to pass in a fresh process, so finish the
  # slice file-per-process (slower: every file pays its own compiles).
  # Per-file timeout: one hanging test (e.g. a readline on a silent
  # daemon) must never stall the whole suite for hours.  3000 s: the
  # pallas interpret-mode file needs >900 s with four fused engines.
  echo "slice $name: falling back to file-per-process"
  for f in "$@"; do
    timeout 3000 python -m pytest "$f" -x -q || { rc=$?;
      echo "slice $name: $f failed rc=$rc"; return "$rc"; }
  done
  return 0
}

run_slice A tests/test_[a-f]*.py || exit $?
run_slice B tests/test_[g-z]*.py || exit $?

# Trace-export schema pass (doc/tracing.md): a synthetic cross-thread
# workload is exported as Chrome trace-event JSON and validated against
# the fields Perfetto actually enforces (ph/ts/dur/pid/tid, flow-arrow
# pairing and slice binding) plus corr-id flow connectivity — schema
# drift fails the suite instead of silently rendering an empty
# timeline.
echo "trace-export schema pass"
timeout 300 python tools/trace_export.py --selfcheck \
  || { echo "trace-export selfcheck failed"; exit 1; }

# Static-analysis pass (doc/static_analysis.md): graftlint runs all
# ten passes — input-contract asserts, span/label cardinality, jit
# hygiene, host-sync leaks in kernel builders, guarded-by lock
# discipline, lock-order deadlock topology + callback-under-lock,
# async-blocking on the event loop, supervision-coverage of every jit
# dispatch, x64/msat staging discipline, and the env-knob/metric
# registry cross-check — and fails on any finding not baselined with a
# justification.  Subsumes the old standalone lint_asserts/lint_spans
# scripts (still available as shims).  Stdlib only, no jax import: the
# 300 s budget is pure headroom.  The FULL run is the gate; --changed
# (the <1 s pre-push subset) and --format sarif (the CI diff-annotation
# artifact) are exercised after it so their plumbing can't rot.
echo "graftlint static-analysis pass"
timeout 300 python tools/graftlint.py \
  || { echo "graftlint failed"; exit 1; }
timeout 120 python tools/graftlint.py --changed \
  || { echo "graftlint --changed failed"; exit 1; }
SARIF_OUT=$(mktemp -t graftlint.XXXXXX.sarif)
timeout 300 python tools/graftlint.py --format sarif > "$SARIF_OUT" \
  || { echo "graftlint sarif failed"; exit 1; }
SARIF_OUT="$SARIF_OUT" python - <<'PYEOF' || { echo "graftlint sarif schema check failed"; exit 1; }
import json, os
doc = json.load(open(os.environ["SARIF_OUT"]))
assert doc["version"] == "2.1.0" and doc["runs"][0]["tool"]["driver"]["name"] == "graftlint"
PYEOF
rm -f "$SARIF_OUT"

# Perf-smoke pass (doc/perf.md): the attribution model is driven with
# a synthetic workload whose dispatch stage is deliberately inflated
# and must name exactly that stage as the bottleneck, reproduce the
# hand-computed speedup-if-removed projection, and reconcile the
# flight-ring sums against the clntpu_replay_* counters within the
# stated epsilon.  Jax-free, seconds of budget.
echo "perf-smoke pass (tools/perf_report.py --selfcheck)"
timeout 300 python tools/perf_report.py --selfcheck \
  || { echo "perf selfcheck failed"; exit 1; }

# Incident-smoke pass (doc/incidents.md): the black-box recorder is
# driven with a jax-free fault-shaped mini workload — correlated flight
# records and trace spans, quarantine then breaker-open triggers — and
# must produce exactly ONE bundle, escalated to the breaker-open
# trigger with the quarantine history and the suppressed duplicate
# recorded, that passes the full bundle validation (manifest schema,
# Chrome-trace export, flight-ring <-> clntpu_dispatches_total
# reconciliation) and renders.  The LIVE-daemon incident acceptance
# (dispatch:verify:raise:1 -> one breaker-open bundle) rides the
# health-smoke pass below.
echo "incident-smoke pass (tools/incident_report.py --selfcheck)"
timeout 300 python tools/incident_report.py --selfcheck \
  || { echo "incident selfcheck failed"; exit 1; }

# Fault-matrix pass (doc/resilience.md): re-run the resilience suite
# with deterministic faults armed at every named device seam — dispatch
# raises for verify/route, the mesh reshard and the sign kernel fail
# half the time — plus generous dispatch deadlines so the deadline
# plumbing is live without firing spuriously.  The workload tests in
# tests/test_zz_resilience.py assert OUTPUT correctness, so this pass
# proves the breakers/quarantine/host-fallback paths complete every
# replay/route/sign workload bit-identically under sustained failure.
echo "fault-matrix pass (LIGHTNING_TPU_FAULT armed)"
LIGHTNING_TPU_FAULT="dispatch:verify:raise:0.25,dispatch:route:raise:0.5,dispatch:mcf:raise:0.5,mesh:mesh:raise:0.5,sign:sign:raise:0.5,readback:verify:raise:0.125" \
LIGHTNING_TPU_DEADLINE_VERIFY_S=120 \
LIGHTNING_TPU_DEADLINE_ROUTE_S=120 \
LIGHTNING_TPU_DEADLINE_INGEST_S=240 \
  timeout 1800 python -m pytest tests/test_zz_resilience.py -x -q \
  || { echo "fault-matrix pass failed"; exit 1; }

# Health-smoke pass (doc/health.md): a live daemon surface with the
# fast-tick health engine — a dispatch:verify fault armed via the PR-4
# grammar must trip the verify breaker, flip gethealth (and REST
# GET /health and tools/dashboard.py --once) to degraded with
# breaker_open named and clntpu_slo_breach_total incremented, then
# recover to healthy after disarm.  The black-box recorder rides the
# same drive (doc/incidents.md): the fault phase must freeze exactly
# one breaker-open bundle with the verify family and failing
# dispatches inside, validated + rendered by tools/incident_report.py, and
# recovery must add none.  Pins the same jax config as the soak-lite
# pass so the warmed verify programs are reused.
echo "health-smoke pass (tools/health_smoke.py)"
timeout 1200 python tools/health_smoke.py \
  || { echo "health-smoke failed"; exit 1; }

# Overload soak-lite pass (doc/overload.md): a bounded (~20 s storm)
# gossip storm + concurrent getroute/sign load against a live daemon
# surface on the CPU stub, asserting the overload SLOs — bounded
# queues, zero unmetered drops, priority shedding with no own-class
# shed, TRY_AGAIN admission control actually firing, getroute p99,
# and byte-identical unthrottled replay of the non-shed subset.  The
# full-scale storm is tests/test_zz_overload.py's slow-marked soak.
# loadgen pins the suite's jax config (8-device CPU, cache read-only)
# so the warmed verify/sign/route programs are reused, not recompiled.
echo "overload soak-lite pass (tools/loadgen.py --selfcheck)"
timeout 1200 python tools/loadgen.py --selfcheck \
  || { echo "loadgen selfcheck failed"; exit 1; }

# Crash-matrix lite pass (doc/recovery.md): kill a real child daemon
# at three seams — the store append mid-record (torn tail), the db
# commit inside the hook-replica window, and the append seam again
# with payload bitrot injected on the dead store — then restart and
# assert byte-for-byte convergence to the durable-prefix oracle plus
# the quarantine/fixup/marker accounting.  Children run with
# LIGHTNING_TPU_VERIFY_DEVICE=off (host-oracle dispatcher, no device
# programs, no jax cache writes) so this pass is safe alongside the
# read-only compile cache and costs seconds, not compiles.  The full
# five-seam matrix is `python tools/crashmatrix.py --selfcheck`.
echo "crash-matrix lite pass (tools/crashmatrix.py --lite)"
timeout 600 python tools/crashmatrix.py --lite \
  || { echo "crash-matrix lite failed"; exit 1; }

# Journey-smoke pass (doc/journeys.md): per-item provenance through
# the REAL batched pipeline — a signed channel_update driven through
# Gossipd → ingest → verify → store → gossmap fold must leave a
# journey that reaches the planes-patch hop with monotonic timestamps
# and a dispatch_id resolving into the verify flight ring, per-item
# queue-waits must reconcile against the batch-level stage counter, a
# shed message's journey must terminate at the shed hop, and the
# getjourney RPC surface must validate.  Runs with
# LIGHTNING_TPU_VERIFY_DEVICE=off (host pipeline, no device programs)
# so it is jax-cache-safe and costs seconds.
echo "journey-smoke pass (tools/journey_smoke.py)"
timeout 300 python tools/journey_smoke.py \
  || { echo "journey-smoke failed"; exit 1; }
echo "suite green (2 slices + graftlint + perf smoke + incident smoke + fault matrix + health smoke + soak-lite + crash-matrix lite + journey smoke)"

"""Well-known instrument families whose hot-path owners are heavyweight
imports.

`routing/device.py` and `daemon/hsmd.py` pull in the full jax/crypto
stack at import time; declaring their metric families HERE (the obs
package imports nothing heavy) lets lightweight consumers — the
`tools/obs_snapshot.py` capture CLI, exposition-only processes — make
the series present-at-zero without paying those imports.  The registry
re-registers same-name families to the same object, so owner modules
import these instruments rather than re-declaring them.
"""
from __future__ import annotations

from . import registry as _r
from .registry import SIZE_BUCKETS

from . import REGISTRY

RATIO_BUCKETS = _r.RATIO_BUCKETS
DURATION_BUCKETS = _r.DURATION_BUCKETS

# -- routing/device.py: the batched route solver (doc/routing.md) ----------
ROUTE_FLUSH_SECONDS = REGISTRY.histogram(
    "clntpu_route_flush_seconds",
    "End-to-end wall time of one route flush (plane refresh + solve + "
    "reconstruct, device and host paths together)",
    buckets=DURATION_BUCKETS)
ROUTE_BATCH_QUERIES = REGISTRY.histogram(
    "clntpu_route_batch_queries",
    "Route queries coalesced per flush", buckets=SIZE_BUCKETS)
ROUTE_OCCUPANCY = REGISTRY.histogram(
    "clntpu_route_batch_occupancy_ratio",
    "Real queries / padded device lanes per dispatch",
    buckets=RATIO_BUCKETS)
ROUTE_QUERIES = REGISTRY.counter(
    "clntpu_route_queries_total",
    "Route queries solved, by execution path and outcome",
    labelnames=("path", "outcome"))
ROUTE_FALLBACK = REGISTRY.counter(
    "clntpu_route_fallback_total",
    "Queries diverted from the device solver to host dijkstra, by reason",
    labelnames=("reason",))
ROUTE_QUEUE = REGISTRY.gauge(
    "clntpu_route_queue_queries",
    "Route queries currently queued awaiting a flush")
ROUTE_QUEUE_WAIT = REGISTRY.histogram(
    "clntpu_route_queue_wait_seconds",
    "Time a route query waited in the queue, from its enqueue to the "
    "start of the flush that took it (one observation per query)",
    buckets=DURATION_BUCKETS)
ROUTE_SEGMIN_STEPS = REGISTRY.gauge(
    "clntpu_route_segmin_steps",
    "Doubling steps of the route program's segmented minimum "
    "(2^steps >= the graph's largest out-degree): a static size of the "
    "program, as the last flush or warm-up used it")
ROUTE_SEGMIN_BLOCK_PASSES = REGISTRY.gauge(
    "clntpu_route_segmin_block_passes",
    "Doubling passes the route program's segmented minimum runs over "
    "the winners of aligned 32-row blocks (max(steps - 5, 0)): whether "
    "its second level engages, as the last flush or warm-up used it")
ROUTE_PROGRAM_BYTES = REGISTRY.gauge(
    "clntpu_route_program_bytes",
    "Device bytes the compiler reports for the route program at the live "
    "shapes (memory_analysis(): part=temp, its temporaries; "
    "part=arguments, its operands), as the last flush or warm-up used it",
    labelnames=("part",))
ROUTE_PATH_HOPS = REGISTRY.histogram(
    "clntpu_route_path_hops",
    "Hops of a route answered ok from the device path (one observation "
    "per query; the host solver's answers are not counted)",
    buckets=tuple(float(h) for h in range(1, 21)))
# owner: daemon/jsonrpc.py's getroute command.  ANSWERED queries only
# (ok or no-route) — TRY_AGAIN admission rejections are excluded, so
# this is the same population tools/loadgen.py's post-hoc p99 and the
# health engine's route_p99 SLO judge (clntpu_rpc_latency_seconds
# counts every call, and under storm the fast 429s would drag the
# tail estimate down exactly when it matters).
ROUTE_ANSWER_SECONDS = REGISTRY.histogram(
    "clntpu_route_answer_seconds",
    "getroute RPC latency for answered queries (ok or no-route; "
    "TRY_AGAIN rejections excluded)",
    buckets=DURATION_BUCKETS)

# -- routing/mcf_device.py: the batched min-cost-flow payment engine -------
# (doc/routing.md §MCF/MPP; the askrene-parity MPP solver's dispatch
# family — declared here so jax-free consumers see the series at zero.)
MCF_FLUSH_SECONDS = REGISTRY.histogram(
    "clntpu_mcf_flush_seconds",
    "End-to-end wall time of one mcf flush (lane prep + batched solve + "
    "flow decomposition, device and host paths together)",
    buckets=DURATION_BUCKETS)
MCF_BATCH_QUERIES = REGISTRY.histogram(
    "clntpu_mcf_batch_queries",
    "getroutes/xpay queries coalesced per mcf flush", buckets=SIZE_BUCKETS)
MCF_OCCUPANCY = REGISTRY.histogram(
    "clntpu_mcf_batch_occupancy_ratio",
    "Real mcf queries / padded device lanes per dispatch",
    buckets=RATIO_BUCKETS)
MCF_QUERIES = REGISTRY.counter(
    "clntpu_mcf_queries_total",
    "Min-cost-flow queries solved, by execution path and outcome",
    labelnames=("path", "outcome"))
MCF_FALLBACK = REGISTRY.counter(
    "clntpu_mcf_fallback_total",
    "Queries diverted from the device mcf solver to the host oracle, "
    "by reason",
    labelnames=("reason",))
MCF_QUEUE = REGISTRY.gauge(
    "clntpu_mcf_queue_queries",
    "Min-cost-flow queries currently queued awaiting a flush")
MCF_PARTS = REGISTRY.histogram(
    "clntpu_mcf_parts_per_query",
    "Route parts per successfully solved mcf query (MPP split width)",
    buckets=SIZE_BUCKETS)

# -- daemon/hsmd.py: the batched-sign paths --------------------------------
SIGN_BATCH_SIGS = REGISTRY.histogram(
    "clntpu_sign_batch_sigs",
    "Signatures per hsmd batched-sign call, by operation",
    labelnames=("op",), buckets=SIZE_BUCKETS)
SIGN_CALLS = REGISTRY.counter(
    "clntpu_sign_total",
    "hsmd batched-sign calls, by operation and host/device path",
    labelnames=("op", "path"))

# -- resilience/: the device-path supervision layer (doc/resilience.md) ----
BREAKER_STATE = REGISTRY.gauge(
    "clntpu_breaker_state",
    "Circuit-breaker state per dispatch family "
    "(0 = closed, 1 = open, 2 = half-open)",
    labelnames=("family",))
BREAKER_TRANSITIONS = REGISTRY.counter(
    "clntpu_breaker_transitions_total",
    "Circuit-breaker state transitions, by family and target state",
    labelnames=("family", "to"))
BREAKER_FAILURES = REGISTRY.counter(
    "clntpu_breaker_failures_total",
    "Device dispatch failures recorded against a breaker",
    labelnames=("family",))
BREAKER_SHORT_CIRCUITS = REGISTRY.counter(
    "clntpu_breaker_short_circuits_total",
    "Dispatches diverted to the host fallback because the breaker was "
    "open (or a half-open probe was already in flight)",
    labelnames=("family",))
QUARANTINE = REGISTRY.counter(
    "clntpu_quarantine_total",
    "Rows diverted off a failing device dispatch (bisect-isolated or "
    "readback-lost) and re-checked host-side, by family and reason",
    labelnames=("family", "reason"))
FAULT_INJECTED = REGISTRY.counter(
    "clntpu_fault_injected_total",
    "Faults fired by the LIGHTNING_TPU_FAULT injection harness",
    labelnames=("seam", "family", "action"))
DEADLINE_EXCEEDED = REGISTRY.counter(
    "clntpu_deadline_exceeded_total",
    "Dispatch deadlines blown (a hung/slow worker surfaced instead of a "
    "silent stall), by family and seam",
    labelnames=("family", "seam"))
LOOP_RESTARTS = REGISTRY.counter(
    "clntpu_loop_restarts_total",
    "Supervised flush/producer loop restarts after an escaped exception",
    labelnames=("loop",))
INGEST_FLUSH_ERRORS = REGISTRY.counter(
    "clntpu_ingest_flush_errors_total",
    "GossipIngest flush-loop iterations that raised (the loop restarts "
    "with backoff instead of dying silently)")

# -- resilience/overload.py: overload control (doc/overload.md) ------------
SHED = REGISTRY.counter(
    "clntpu_shed_total",
    "Messages/queries shed by the overload controller, by family, "
    "priority class, and reason (every shed is also recorded in the "
    "shed ring — never silently dropped)",
    labelnames=("family", "priority", "reason"))
OVERLOAD_STATE = REGISTRY.gauge(
    "clntpu_overload_state",
    "Degradation-ladder state per dispatch family "
    "(0 = normal, 1 = elevated, 2 = saturated)",
    labelnames=("family",))
OVERLOAD_TRANSITIONS = REGISTRY.counter(
    "clntpu_overload_transitions_total",
    "Degradation-ladder transitions, by family and target state",
    labelnames=("family", "to"))
BACKPRESSURE_WAITS = REGISTRY.counter(
    "clntpu_backpressure_waits_total",
    "Transport read pauses taken because the family was saturated "
    "(one per paused message, bounded per wait)",
    labelnames=("family",))
BACKPRESSURE_WAIT_SECONDS = REGISTRY.histogram(
    "clntpu_backpressure_wait_seconds",
    "Seconds a saturated family paused one transport read",
    labelnames=("family",), buckets=DURATION_BUCKETS)
INGEST_BACKLOG = REGISTRY.gauge(
    "clntpu_ingest_backlog_sigs",
    "Total unverified ingest backlog: queued signatures plus the "
    "in-flight flush batch (the queue gauge counts only queued)")

# -- gossip/verify.py: streaming-replay pipeline stages --------------------
# (doc/replay_pipeline.md owns the timing vocabulary; declared here so
# jax-free consumers — tools/obs_snapshot.py capture, the attribution
# model in obs/attribution.py, perf_report --selfcheck — see the series
# present-at-zero and can drive them synthetically without the crypto
# stack.)  "prep" is host bucket build (slice + pack + pad), "stall" is
# the slice of prep VISIBLE on the dispatch thread's critical path,
# "dispatch" is upload + program enqueue, "readback" is the single
# end-of-replay block on the device booleans.
REPLAY_PREP = REGISTRY.counter(
    "clntpu_replay_prep_seconds_total",
    "Host bucket-prep busy time (slice + pack + pad), all buckets")
REPLAY_STALL = REGISTRY.counter(
    "clntpu_replay_prep_stall_seconds_total",
    "Prep time visible on the dispatch critical path (queue-empty waits; "
    "== prep time when the pipeline is serial/depth 0)")
REPLAY_DISPATCH = REGISTRY.counter(
    "clntpu_replay_dispatch_seconds_total",
    "Dispatch-thread time spent uploading + enqueueing bucket programs")
REPLAY_READBACK = REGISTRY.counter(
    "clntpu_replay_readback_seconds_total",
    "Time blocked on the single end-of-replay device readback")
REPLAY_OVERLAP = REGISTRY.histogram(
    "clntpu_replay_overlap_ratio",
    "Per-replay fraction of host prep hidden behind device compute "
    "(1 - stall/prep; serial pipelines observe 0)",
    buckets=RATIO_BUCKETS)
REPLAY_QDEPTH = REGISTRY.histogram(
    "clntpu_replay_queue_depth",
    "Prepared-bucket queue depth sampled at each dispatch",
    buckets=_r.log2_buckets(1.0, 16.0))
REPLAY_BUCKETS = REGISTRY.counter(
    "clntpu_replay_buckets_total",
    "Fused bucket dispatches, by device path",
    labelnames=("path",))
REPLAY_SIGS = REGISTRY.counter(
    "clntpu_replay_sigs_total",
    "Signatures carried by the replay's bucket dispatches (real lanes; "
    "moves at every dispatch, as the stage counters above do)")

# -- obs/attribution.py: the perf observatory (doc/perf.md) ----------------
TRANSFER_BYTES = REGISTRY.counter(
    "clntpu_transfer_bytes_total",
    "Host<->device bytes staged for batched dispatches, by family and "
    "direction (h2d = operand upload, d2h = result readback; "
    "operand-size accounting, not a PCIe counter)",
    labelnames=("family", "direction"))
RETRACE = REGISTRY.counter(
    "clntpu_retrace_total",
    "Program-shape compile first-sights AFTER warmup() completed — "
    "every increment is an anomaly (a live dispatch paid a compile the "
    "warmup contract promises it never does), by program",
    labelnames=("program",))
DEVICE_MEMORY = REGISTRY.gauge(
    "clntpu_device_memory_bytes",
    "Live device-memory statistics where the backend exposes "
    "memory_stats() (TPU does; CPU reports nothing), by device and stat",
    labelnames=("device", "stat"))

# -- obs/health.py: the always-on health engine (doc/health.md) ------------
HEALTH_STATE = REGISTRY.gauge(
    "clntpu_health_state",
    "Rolled-up daemon health from the continuous SLO evaluator "
    "(0 = healthy, 1 = degraded, 2 = unhealthy)")
SLO_BREACH = REGISTRY.counter(
    "clntpu_slo_breach_total",
    "SLO breach ENTRIES recorded by the health engine (one increment "
    "per transition into breach, not per breached tick), by SLO name",
    labelnames=("slo",))

# -- obs/incident.py: the black-box flight recorder (doc/incidents.md) -----
INCIDENTS = REGISTRY.counter(
    "clntpu_incidents_total",
    "Incident bundles frozen to disk by the black-box recorder, by the "
    "trigger class that names the bundle (escalations re-count under "
    "the new class)",
    labelnames=("trigger",))
INCIDENT_TRIGGERS = REGISTRY.counter(
    "clntpu_incident_triggers_total",
    "Incident triggers observed, by class and what the episode "
    "debouncer did with them (capture = opened a bundle, escalate = "
    "re-froze the open bundle under a higher-severity class, absorb = "
    "suppressed inside the cooldown window)",
    labelnames=("trigger", "action"))
INCIDENT_BYTES = REGISTRY.gauge(
    "clntpu_incident_store_bytes",
    "Total bytes of incident bundles on disk (bounded by "
    "LIGHTNING_TPU_INCIDENT_MAX_BYTES with oldest-first rotation)")

# -- daemon/recovery.py + gossip/store.py: crash-consistent restart --------
# (doc/recovery.md owns the semantics: the clean-shutdown marker, the
# torn-tail truncation rules, and the boot reconciliation sweep.)
RECOVERY_BOOTS = REGISTRY.counter(
    "clntpu_recovery_boots_total",
    "Daemon boots by what the clean-shutdown marker said about the "
    "previous run (first_boot = no marker, clean = orderly shutdown, "
    "crash = the marker still said running)",
    labelnames=("state",))
RECOVERY_STORE_ROWS = REGISTRY.counter(
    "clntpu_recovery_store_rows_total",
    "Store records handled by the recovery scan, by action "
    "(requalified = crc-bad but host re-check passed, dropped = "
    "crc-bad and failed the re-check, flagged deleted)",
    labelnames=("action",))
RECOVERY_STORE_TRUNCATED_BYTES = REGISTRY.counter(
    "clntpu_recovery_store_truncated_bytes_total",
    "Torn-tail bytes truncated off the gossip store at recovery "
    "(a crash mid-append leaves at most one partial record at EOF)")
RECOVERY_DB_FIXUPS = REGISTRY.counter(
    "clntpu_recovery_db_fixups_total",
    "Rows fixed by the boot db reconciliation sweep, by kind "
    "(payment_failed = pending payment older than the crash marked "
    "retryable-failed, retransmit_reset / inflight_reset = journal "
    "blob invalid against channel state, replica_dropped = hook "
    "replica was ahead by one and its tail record was dropped)",
    labelnames=("kind",))
RECOVERY_INCIDENTS_FOUND = REGISTRY.counter(
    "clntpu_recovery_incidents_found_total",
    "Incident bundles from the previous (crashed) run discovered and "
    "logged during boot recovery")
RECOVERY_SECONDS = REGISTRY.histogram(
    "clntpu_recovery_seconds",
    "Wall time of the whole boot recovery phase (marker check + store "
    "scan + optional verify replay + db reconciliation)",
    buckets=DURATION_BUCKETS)

# -- obs/flight.py: the dispatch flight recorder (doc/tracing.md) ----------
DISPATCHES = REGISTRY.counter(
    "clntpu_dispatches_total",
    "Flight-recorded dispatches, by family and outcome (the aggregate "
    "view of the listdispatches ring)",
    labelnames=("family", "outcome"))
SLOW_DISPATCH = REGISTRY.counter(
    "clntpu_slow_dispatch_total",
    "Dispatches flagged by the slow-dispatch watchdog (over "
    "LIGHTNING_TPU_SLOW_DISPATCH_S, or the rolling per-family p99)",
    labelnames=("family",))

# -- obs/journey.py: per-item journeys (doc/journeys.md) -------------------
JOURNEY_SAMPLED = REGISTRY.counter(
    "clntpu_journey_sampled_total",
    "Entities admitted to the journey table by the deterministic "
    "sampler (one per entity, not per hop), by entity kind",
    labelnames=("kind",))
JOURNEY_TABLE = REGISTRY.gauge(
    "clntpu_journey_table_size",
    "Journeys currently held in the bounded per-entity table "
    "(LRU-rotated at LIGHTNING_TPU_JOURNEY_MAX)")
JOURNEY_HOP_WAIT = REGISTRY.histogram(
    "clntpu_journey_hop_wait_seconds",
    "Per-ITEM queue-induced wait at each journey hop (time the item "
    "sat queued before its batch dispatched — the batching tax, split "
    "from service time per doc/journeys.md)",
    labelnames=("hop",), buckets=DURATION_BUCKETS)
JOURNEY_HOP_SERVICE = REGISTRY.histogram(
    "clntpu_journey_hop_service_seconds",
    "Per-ITEM service time at each journey hop (the batch execution "
    "the item shared, split from queue wait per doc/journeys.md)",
    labelnames=("hop",), buckets=DURATION_BUCKETS)
JOURNEY_BATCH_WAIT = REGISTRY.counter(
    "clntpu_journey_batch_wait_seconds_total",
    "Batch-side queue-wait accounting by pipeline stage: "
    "Σ(flush_start − enqueue) over EVERY item of every batch, sampled "
    "or not — the reconciliation target the summed per-item journey "
    "waits must match within ε when sampling is 1",
    labelnames=("stage",))

"""Structured observability: spans, engine/connector telemetry, exporters.

The reference Scotty's only observability was a benchmark-side throughput
logger plus a log-scraping AnalyzeTool (PAPER.md / SURVEY.md §5). This
package replaces that split with a first-class subsystem:

* :class:`Observability` — one :class:`~scotty_tpu.utils.metrics.MetricsRegistry`
  plus one :class:`~scotty_tpu.obs.spans.SpanRecorder`, attachable to
  operators (``TpuWindowOperator(obs=...)``), fused pipelines
  (``pipeline.set_observability(obs)``), connectors
  (``KeyedScottyWindowOperator(obs=...)``) and the bench harness
  (``run_benchmark(..., obs=...)``).
* exporters — JSONL time series, Prometheus text exposition, Chrome-trace
  span dumps (:mod:`.exporters`).
* ``python -m scotty_tpu.obs report <file>`` — summarize any export
  (:mod:`.report`).
* the operational layer (ISSUE 4): an always-on :class:`.flight.
  FlightRecorder` ring of recent engine events sampled at the existing
  drain points, atomic crash bundles + ``python -m scotty_tpu.obs
  postmortem`` triage (:mod:`.flight`, :mod:`.postmortem`), and a live
  ``/metrics``·``/vars``·``/healthz`` endpoint
  (``Observability.serve()``, :mod:`.server`).

Host-side hooks record at batch/interval boundaries; the engine itself
never prints (tier-1 enforces it). What happens INSIDE a fused interval is
covered by the in-jit :mod:`.device` layer: a :class:`.device.DeviceMetrics`
pytree of int64 counters/bucket histograms rides the carried state of every
fused pipeline and the operator's ingest paths, and is folded into the
registry (``device_*`` names) at the existing drain points — zero extra
host syncs. ``python -m scotty_tpu.obs diff <baseline> <candidate>``
(:mod:`.diff`) turns any two metric/bench exports into a CI-enforceable
regression gate.

Stable metric-name contract (documented in README.md / docs/API.md):

========================  ====================================================
``ingest_tuples``         counter: tuples accepted (operator or connector)
``ingest_batch_size``     histogram: tuples per host batch
``ingest_dense_batches``  counter: in-order batches given the dense kernel
``ingest_dense_ts32_batches``    counter: dense batches in int32 offsets
``ingest_dense_ts32_fallbacks``  counter: dense-rung batches whose span
                                 fails the int32 offset test (general kernel)
``late_tuples``           counter: tuples arriving below the stream's max ts
``dropped_tuples``        counter: tuples older than watermark - lateness
``watermarks``            counter: watermark advances
``watermark_lag_ms``      gauge: max event time seen - watermark ts (>= 0)
``watermark_dispatch_ms`` histogram: host time of one watermark dispatch
``interval_step_ms``      histogram: host time of one fused interval step
``sync_ms``               histogram: host time of a pipeline drain/sync
``slice_occupancy``       gauge: live slices / capacity (at sync points)
``slice_headroom``        gauge: capacity - live slices (at sync points)
``queue_depth``           gauge: asyncio source queue depth
``windows_emitted``       counter: non-empty windows delivered
``overflows``             counter: buffer-overflow events detected
``silent_intervals``      counter: session-pipeline intervals with no tuples
``emit_latency_ms``       histogram: sampled dispatch→results-on-host time
========================  ====================================================

Resilience contract (ISSUE 3 — counters emitted by the
:mod:`scotty_tpu.resilience` subsystem and the policy hooks in engine/
connectors; spans ``resilience_checkpoint`` / ``resilience_restore`` /
``resilience_backoff`` / ``resilience_grow`` ride the same recorder):

==============================  ==============================================
``resilience_shed_tuples``      counter: tuples dropped by the SHED policy
                                (also counted as ``device_dropped_tuples``)
``resilience_grow_events``      counter: GROW capacity doublings
``resilience_checkpoints``      counter: automatic supervisor checkpoints
``resilience_restarts``         counter: supervisor restarts after a failure
``resilience_source_retries``   counter: retrying-source reconnect attempts
``resilience_poison_records``   counter: records routed to dead-letter
``resilience_stall_events``     counter: no-progress watchdog detections
==============================  ==============================================

Operations contract (ISSUE 4 — the flight recorder / live endpoint
layer; :mod:`.flight`, :mod:`.server`, :mod:`.postmortem`):

==========================  ==================================================
``flight_dropped_events``   counter: flight-ring events lost to wraparound
                            (folded at every drain-point sample — never
                            silent; gated by the default ``obs diff``)
``health_checks``           counter: ``/healthz`` verdicts computed
``health_unhealthy``        counter: verdicts that came back unhealthy
                            (gated by the default ``obs diff``)
==========================  ==================================================

Emission-latency contract (ISSUE 14 — :mod:`.latency`: stage-stamped
window lineage, sampled 1-in-N with an exact small-stream mode, every
stamp host-side at existing drain points on the injectable
``resilience.Clock``; ``python -m scotty_tpu.obs latency <export>``
prints the critical-path attribution):

=============================  ===========================================
``latency_stage_<stage>_ms``   histogram: one stage's share of a sampled
                               chain (stages: arrival, ring_enqueue,
                               ring_dequeue, shaper_flush, dispatch,
                               eligibility, drain, emit, sink)
``latency_first_emit_ms``      histogram: watermark-eligibility → first
                               delivered window (ROADMAP item 4's bench
                               dimension)
``latency_eligibility_ms``     histogram: eligibility → last delivery
                               (the Karimov-style whole-emission lag)
``latency_end_to_end_ms``      histogram: first stamp → last stamp
                               (stage durations sum to exactly this)
``latency_shard_<s>_emit_ms``  histogram: mesh per-shard emit-fetch time
                               folded at the psum drain
``latency_lineages``           counter: sampled chains finalized
``latency_stamp_dropped``      counter: chains evicted unfinalized /
                               late stamps (gated by ``obs diff``)
=============================  ===========================================

Workload sensor-plane contract (ISSUE 16 — :mod:`.workload`,
:mod:`.drift`, :mod:`.costmodel`: the measurement half of ROADMAP
item 4's self-tuning engine. The fingerprint is sampled only at the
existing drain points — ``flight_sync`` calls the monitor before it
even looks at the flight ring — and every feature doubles as a
``workload_<feature>`` gauge; ``python -m scotty_tpu.obs drift |
costmodel | trend`` are the offline faces):

=============================  ===========================================
``workload_<feature>``         gauge: one fingerprint feature per audit
                               window (arrival_rate_per_s, burst_factor,
                               late_share, late_age_p50_ms, ooo_fraction,
                               fill_ratio, key_top_share, key_entropy,
                               pallas_fallback_share)
``workload_audits``            counter: fingerprint audit windows folded
``workload_drift_events``      counter: confirmed drift excursions
                               (APPEARING gates the default ``obs diff``)
``costmodel_residual_pct``     gauge: live |measured - predicted|
                               interval-step residual in percent (gated
                               past the model's stated bound)
=============================  ===========================================

Actuation-plane contract (ISSUE 18 — :mod:`scotty_tpu.autotune`: the
other half of ROADMAP item 4. Retune commits, itemized recompiles and
the overload degradation ladder; all four names APPEARING gates the
default ``obs diff`` — a certified number that retuned or shed
mid-measure must not pass as clean):

=============================  ===========================================
``autotune_retunes``           counter: committed live retunes
``autotune_retraces``          counter: retunes that compiled a
                               genuinely-new geometry (a warm
                               GeometryCache bucket costs zero)
``degrade_active_rung``        gauge: the ladder's current rung (0 =
                               none, 1 = late shed, 2 = sampled
                               admission, 3 = backpressure)
``degrade_shed_tuples``        counter: tuples the ladder refused
                               (exact: offered = admitted + shed)
=============================  ===========================================

Per-tenant SLO accounting contract (ISSUE 19 — :mod:`.slo` +
:mod:`.attribution`: per-query freshness, exact per-tenant resource
ledgers, and declared objectives judged by error-budget burn rates.
All host-side at the existing drain points; ``slo_budget_exhausted``
APPEARING and burn growth gate the default ``obs diff``;
``python -m scotty_tpu.obs slo <export>`` is the offline face):

===============================  =========================================
``slo_evaluations``              counter: SLO policy drain-point ticks
``slo_burn_events``              counter: (tenant, objective) pairs that
                                 STARTED burning (edge-triggered; gated)
``slo_budget_exhausted``         counter: pairs whose slow-window budget
                                 fully burned (APPEARING gates)
``slo_burning_tenants``          gauge: tenants currently latched burning
``slo_worst_fast_burn``          gauge: worst fast-window burn rate
``slo_freshness_worst_ms``       gauge: worst per-query staleness across
                                 active slots (clock now - newest
                                 delivered window end)
``slo_emission_lag_worst_ms``    gauge: worst per-query event-time lag
                                 (watermark - newest window end)
``slo_tenant_<family>_<tenant>``  gauge: one tenant's ledger cell, top-k
                                 capped (families: windows, rejected,
                                 shed, ...); the remainder folds into
                                 ``slo_tenant_<family>_other``
===============================  =========================================
"""

from __future__ import annotations

import contextlib
from typing import Optional

from ..utils.metrics import MetricsRegistry
from .device import (
    DEVICE_DROPPED_TUPLES,
    DEVICE_INGEST_TUPLES,
    DEVICE_LATE_TUPLES,
    DEVICE_SILENT_INTERVALS,
    DEVICE_SLICES_TOUCHED,
    DEVICE_TRIGGERS_FIRED,
    DEVICE_WINDOWS_NONEMPTY,
    DeviceMetrics,
    init_device_metrics,
)
from .exporters import JsonlExporter, prometheus_text, write_chrome_trace
from .flight import FLIGHT_DROPPED_EVENTS, FlightRecorder, write_postmortem
from .server import HEALTH_CHECKS, HEALTH_UNHEALTHY, HealthPolicy
from .spans import Span, SpanRecorder, program_span

# stable metric names (the contract above)
INGEST_TUPLES = "ingest_tuples"
INGEST_BATCH_SIZE = "ingest_batch_size"
INGEST_DENSE_BATCHES = "ingest_dense_batches"
INGEST_DENSE_TS32_BATCHES = "ingest_dense_ts32_batches"
INGEST_DENSE_TS32_FALLBACKS = "ingest_dense_ts32_fallbacks"
LATE_TUPLES = "late_tuples"
DROPPED_TUPLES = "dropped_tuples"
WATERMARKS = "watermarks"
WATERMARK_LAG_MS = "watermark_lag_ms"
WATERMARK_DISPATCH_MS = "watermark_dispatch_ms"
INTERVAL_STEP_MS = "interval_step_ms"
SYNC_MS = "sync_ms"
SLICE_OCCUPANCY = "slice_occupancy"
SLICE_HEADROOM = "slice_headroom"
QUEUE_DEPTH = "queue_depth"
WINDOWS_EMITTED = "windows_emitted"
OVERFLOWS = "overflows"
SILENT_INTERVALS = "silent_intervals"
EMIT_LATENCY_MS = "emit_latency_ms"

# speculative generic-context batching contract (ISSUE 11 —
# engine/context.py SpeculativePlanner; host counters moved per chunk
# run by TpuWindowOperator._feed_contexts): tuples through the
# vectorized chunk path, tuples the safety proof sent back to the
# per-tuple scan, and how many fallback runs fired — a silent
# regression to the scan shows up as the gated fallback counters
# appearing/growing even when wall time still looks plausible
CTX_SPECULATIVE_TUPLES = "ctx_speculative_tuples"
CTX_SPECULATIVE_FALLBACK_TUPLES = "ctx_speculative_fallback_tuples"
CTX_SPECULATIVE_FALLBACKS = "ctx_speculative_fallbacks"

# Pallas hot-path kernels + micro-batched streamed emission (ISSUE 15
# — scotty_tpu.pallas; host-side counts at the existing call sites,
# zero device syncs): dispatches of jitted programs containing a
# Pallas kernel, dispatches routed to the XLA twin instead (span/shape
# budget misses — gated by obs diff so a silent degrade to the slow
# twin cannot pass as clean), and micro-batched flush programs (the
# per-interval trigger/query dispatch of run_streamed)
PALLAS_KERNEL_DISPATCHES = "pallas_kernel_dispatches"
PALLAS_FALLBACKS = "pallas_fallbacks"
MICROBATCH_FLUSHES = "microbatch_flushes"

# sliding-count lateness relaxation (ISSUE 11 — count_pipeline.py):
# rows carried by the sub-period (max_lateness < wm_period) stratified
# late model; gated so a config silently flipping into (or out of) the
# relaxed retention model cannot pass as clean
COUNT_LATENESS_RELAXED_ROWS = "count_lateness_relaxed_rows"

# shaper contract (ISSUE 5 — scotty_tpu.shaper; counters/gauges folded
# at the existing drain points, documented in README/docs/API.md)
SHAPER_REORDERED_TUPLES = "shaper_reordered_tuples"
SHAPER_FLUSHES = "shaper_flushes"
SHAPER_HELD_TUPLES = "shaper_held_tuples"
SHAPER_LATE_ROUTED = "shaper_late_routed"
SHAPER_SLACK_OVERFLOWS = "shaper_slack_overflows"
SHAPER_FILL_RATIO = "shaper_fill_ratio"

# dynamic-query serving contract (ISSUE 6 — scotty_tpu.serving; counters
# moved by QueryService's control plane, gauges refreshed on every
# register/cancel; per-tenant rollups are serving_tenant_active_<tenant>)
SERVING_REGISTERED = "serving_registered"
SERVING_CANCELLED = "serving_cancelled"
SERVING_REJECTED = "serving_rejected"
SERVING_RETRACES = "serving_retraces"
SERVING_CACHE_HITS = "serving_cache_hits"
SERVING_CACHE_MISSES = "serving_cache_misses"
SERVING_CACHE_EVICTIONS = "serving_cache_evictions"
SERVING_ACTIVE_QUERIES = "serving_active_queries"

# ingest-ring contract (ISSUE 7 — scotty_tpu.ingest; the bounded host
# staging ring between sources and the device boundary. Counters are
# folded at pump/drain points; all are exact integers, so the soak
# harness's tuple-conservation audit can demand
# offered == delivered + shed + occupancy to the tuple)
INGEST_RING_OFFERED = "ingest_ring_offered"
INGEST_RING_DELIVERED = "ingest_ring_delivered"
INGEST_RING_SHED = "ingest_ring_shed"
INGEST_RING_BLOCKS = "ingest_ring_blocks"
INGEST_RING_FULL_EVENTS = "ingest_ring_full_events"
INGEST_RING_OCCUPANCY = "ingest_ring_occupancy"
INGEST_RING_HIGHWATER = "ingest_ring_highwater"

# soak contract (ISSUE 7 — scotty_tpu.soak; the endurance harness's own
# bookkeeping. soak_invariant_failures appearing gates the default
# ``obs diff``: a soak that failed an audit must never pass as clean)
SOAK_AUDITS = "soak_audits"
SOAK_INVARIANT_FAILURES = "soak_invariant_failures"
SOAK_RECORDS_SEEN = "soak_records_seen"

# delivery contract (ISSUE 8 — scotty_tpu.delivery + supervisor lineage:
# the exactly-once output layer. delivery_duplicates_suppressed and
# ckpt_integrity_failures APPEARING gate the default ``obs diff`` — a
# run that started replaying duplicates into its suppression horizon, or
# whose checkpoints started failing digest verification, must be flagged
# even when the defense absorbed it)
DELIVERY_EMITTED = "delivery_emitted"
DELIVERY_DUPLICATES_SUPPRESSED = "delivery_duplicates_suppressed"
DELIVERY_EPOCHS_COMMITTED = "delivery_epochs_committed"
CKPT_INTEGRITY_FAILURES = "ckpt_integrity_failures"
CKPT_LINEAGE_FALLBACKS = "ckpt_lineage_fallbacks"

# mesh-sharded keyed engine contract (scotty_tpu.mesh — counters/gauges)
MESH_REBALANCES = "mesh_rebalances"
MESH_HOT_KEYS = "mesh_hot_keys"
MESH_KEYS_MOVED = "mesh_keys_moved"
MESH_SHARD_IMBALANCE = "mesh_shard_imbalance"

# mesh-serving contract (ISSUE 13 — scotty_tpu.mesh_serving: the
# multi-tenant serving layer fused into the mesh step, plus elastic
# reshard at checkpoint boundaries. mesh_reshards and
# mesh_reshard_retraces APPEARING gate the default ``obs diff`` on mesh
# cells — a steady-state serving run must neither silently reshard nor
# recompile. serving_tenant_other is the top-k gauge rollup's remainder
# bucket (the per-tenant gauge cardinality cap))
MESH_RESHARDS = "mesh_reshards"
MESH_RESHARD_RETRACES = "mesh_reshard_retraces"
SERVING_TENANT_OTHER = "serving_tenant_other"

# emission-latency attribution contract (ISSUE 14 — scotty_tpu.obs.
# latency: stage-stamped window lineage from ingest to delivered
# emission. Stage histograms are latency_stage_<stage>_ms (stages:
# arrival, ring_enqueue, ring_dequeue, shaper_flush, dispatch,
# eligibility, drain, emit, sink); per-shard mesh emit folds are
# latency_shard_<s>_emit_ms. latency_stamp_dropped APPEARING gates the
# default ``obs diff`` — a tracer that lost stamps is losing the very
# attribution it exists to provide. Defined ONCE in .latency (the
# module that observes under them) and re-exported here so METRIC_HELP
# and the diff gate can never drift from the recording side.
from .latency import (  # noqa: E402  (contract re-export)
    LATENCY_ELIGIBILITY_MS,
    LATENCY_END_TO_END_MS,
    LATENCY_FIRST_EMIT_MS,
    LATENCY_LINEAGES,
    LATENCY_OPEN_DECLINED,
    LATENCY_STAMP_DROPPED,
)

# workload sensor-plane contract (ISSUE 16 — scotty_tpu.obs.workload /
# .drift / .costmodel: fingerprint gauges, drift events and the live
# cost-model residual. Same single-definition discipline as the latency
# contract above: each name lives in the module that records under it
# and is re-exported here so METRIC_HELP and the diff gate cannot drift
# from the recording side. workload_drift_events APPEARING gates the
# default ``obs diff`` — a certified number whose workload moved must
# not pass as clean; costmodel_residual_pct past the model's stated
# bound gates the same way.
from .costmodel import (  # noqa: E402  (contract re-export)
    COSTMODEL_RESIDUAL_PCT,
    RESIDUAL_BOUND_PCT,
    CostModel,
)
from .drift import (  # noqa: E402  (contract re-export)
    WORKLOAD_DRIFT_EVENTS,
    DriftDetector,
)
from .workload import (  # noqa: E402  (contract re-export)
    FINGERPRINT_SCHEMA,
    WORKLOAD_AUDITS,
    WorkloadFingerprint,
    WorkloadMonitor,
    feature_gauge,
)

# per-tenant SLO accounting contract (ISSUE 19 — scotty_tpu.obs.slo /
# .attribution: per-query freshness, exact per-tenant ledgers and
# error-budget burn gating. Same single-definition discipline: each
# name lives in the module that records under it and is re-exported
# here so METRIC_HELP and the diff gate cannot drift from the
# recording side. slo_budget_exhausted APPEARING gates the default
# ``obs diff`` — a run that burned a tenant's whole error budget must
# never pass as clean.
from .attribution import (  # noqa: E402  (contract re-export)
    ATTRIBUTION_FAMILIES,
    SLO_EMISSION_LAG_WORST_MS,
    SLO_FRESHNESS_WORST_MS,
    FreshnessTracker,
    TenantAttribution,
    apportion,
    attribution_metric,
)
from .slo import (  # noqa: E402  (contract re-export)
    SLO_BUDGET_EXHAUSTED,
    SLO_BURN_EVENTS,
    SLO_BURNING_TENANTS,
    SLO_EVALUATIONS,
    SLO_WORST_FAST_BURN,
    ErrorBudget,
    SloPolicy,
)

# resilience contract (scotty_tpu.resilience — counters)
RESILIENCE_SHED_TUPLES = "resilience_shed_tuples"
RESILIENCE_GROW_EVENTS = "resilience_grow_events"
RESILIENCE_CHECKPOINTS = "resilience_checkpoints"
RESILIENCE_RESTARTS = "resilience_restarts"
RESILIENCE_SOURCE_RETRIES = "resilience_source_retries"
RESILIENCE_POISON_RECORDS = "resilience_poison_records"
RESILIENCE_STALL_EVENTS = "resilience_stall_events"
# resilience spans
RESILIENCE_CHECKPOINT_SPAN = "resilience_checkpoint"
RESILIENCE_RESTORE_SPAN = "resilience_restore"
RESILIENCE_BACKOFF_SPAN = "resilience_backoff"
RESILIENCE_GROW_SPAN = "resilience_grow"

# actuation-plane contract (ISSUE 18 — scotty_tpu.autotune: retune
# commits, itemized retraces, degradation rungs). Defined HERE like the
# resilience names — the autotune package records via ``from .. import
# obs`` and the diff gate / METRIC_HELP must share one spelling.
AUTOTUNE_RETUNES = "autotune_retunes"
AUTOTUNE_RETRACES = "autotune_retraces"
DEGRADE_ACTIVE_RUNG = "degrade_active_rung"
DEGRADE_SHED_TUPLES = "degrade_shed_tuples"
# actuation spans
AUTOTUNE_RETUNE_SPAN = "autotune_retune"

#: Prometheus HELP text for the contract metrics (``/metrics`` serves it;
#: :func:`.exporters.prometheus_text` escapes it per the exposition format)
METRIC_HELP = {
    INGEST_TUPLES: "tuples accepted (operator or connector boundary)",
    INGEST_BATCH_SIZE: "tuples per host batch",
    INGEST_DENSE_BATCHES: "in-order batches ingested by the scatter-free "
                          "dense kernel",
    INGEST_DENSE_TS32_BATCHES: "dense-kernel batches whose timestamp "
                               "arithmetic ran in int32 offsets",
    INGEST_DENSE_TS32_FALLBACKS: "in-order batches within a dense rung "
                                 "whose span fails the int32 offset test "
                                 "(general kernel)",
    LATE_TUPLES: "tuples arriving below the stream's max event time",
    DROPPED_TUPLES: "tuples older than watermark - allowed lateness",
    WATERMARKS: "watermark advances",
    WATERMARK_LAG_MS: "max event time seen - watermark ts (floored at 0)",
    WATERMARK_DISPATCH_MS: "host wall time of one watermark dispatch",
    INTERVAL_STEP_MS: "host wall time of one fused interval step",
    SYNC_MS: "host wall time of a pipeline drain/sync",
    SLICE_OCCUPANCY: "live slices / capacity (recorded at sync points)",
    SLICE_HEADROOM: "capacity - live slices",
    QUEUE_DEPTH: "asyncio source queue depth",
    WINDOWS_EMITTED: "non-empty windows delivered",
    OVERFLOWS: "buffer-overflow events detected",
    SILENT_INTERVALS: "session-pipeline intervals with no tuples",
    EMIT_LATENCY_MS: "sampled dispatch->results-on-host time",
    SHAPER_REORDERED_TUPLES:
        "tuples the shaper's sort actually moved (arrived below the "
        "running max event time)",
    SHAPER_FLUSHES: "shaper accumulator blocks flushed",
    SHAPER_HELD_TUPLES: "tuples currently held in the shaper accumulator",
    SHAPER_LATE_ROUTED:
        "tuples the device sort-and-split routed to the late residue",
    SHAPER_SLACK_OVERFLOWS:
        "shaped batches whose late residue exceeded late_capacity",
    SHAPER_FILL_RATIO: "flushed shaper block size / batch_size",
    PALLAS_KERNEL_DISPATCHES:
        "host dispatches of jitted programs containing a Pallas kernel",
    PALLAS_FALLBACKS:
        "Pallas-flagged dispatches routed to the XLA twin instead "
        "(bucket-span/shape budget misses; gated)",
    MICROBATCH_FLUSHES:
        "micro-batched trigger/query flush programs dispatched "
        "(run_streamed)",
    SERVING_REGISTERED: "queries registered with the serving layer",
    SERVING_CANCELLED: "queries cancelled (slots recycled)",
    SERVING_REJECTED: "query registrations refused by admission control",
    SERVING_RETRACES:
        "serving-step recompiles forced by slot-grid bucket changes",
    SERVING_CACHE_HITS:
        "registers answered from a warm executable (current or cached "
        "bucket)",
    SERVING_CACHE_MISSES: "bucket changes that found no cached executable",
    SERVING_CACHE_EVICTIONS: "compile-cache entries evicted (LRU)",
    SERVING_ACTIVE_QUERIES: "currently active queries across all tenants",
    INGEST_RING_OFFERED: "records offered to the ingest ring",
    INGEST_RING_DELIVERED:
        "records the ring's consumer delivered downstream (device ingest "
        "or operator replay)",
    INGEST_RING_SHED:
        "records shed at the ring boundary (policy='shed' while full)",
    INGEST_RING_BLOCKS: "staging blocks committed to the ring",
    INGEST_RING_FULL_EVENTS:
        "times a producer found the ring full (backpressure engaged)",
    INGEST_RING_OCCUPANCY: "records currently staged in the ring",
    INGEST_RING_HIGHWATER: "ring occupancy high-water (records)",
    SOAK_AUDITS: "soak invariant audits performed",
    SOAK_INVARIANT_FAILURES: "soak audits that found a violated invariant",
    SOAK_RECORDS_SEEN:
        "records the soak loop pulled from its source (offer attempts; "
        "the left-hand side of the conservation identity)",
    RESILIENCE_SHED_TUPLES: "tuples dropped by the SHED overflow policy",
    RESILIENCE_GROW_EVENTS: "GROW capacity doublings",
    RESILIENCE_CHECKPOINTS: "automatic supervisor checkpoints",
    RESILIENCE_RESTARTS: "supervisor restarts after a failure",
    RESILIENCE_SOURCE_RETRIES: "retrying-source reconnect attempts",
    RESILIENCE_POISON_RECORDS: "records routed to dead-letter",
    RESILIENCE_STALL_EVENTS: "no-progress watchdog detections",
    DELIVERY_EMITTED:
        "sink emissions delivered downstream (post-suppression)",
    DELIVERY_DUPLICATES_SUPPRESSED:
        "replayed emissions suppressed by the exactly-once sink "
        "(seq <= delivered high-water after a supervised restore)",
    DELIVERY_EPOCHS_COMMITTED:
        "delivery epochs closed by a checkpoint commit",
    MESH_REBALANCES:
        "hot-key rebalances applied at checkpoint boundaries",
    MESH_HOT_KEYS: "hot keys detected against the shard-mean load",
    MESH_KEYS_MOVED: "keys migrated between shards by rebalances",
    MESH_SHARD_IMBALANCE:
        "hottest-shard load / mean shard load (gauge, drain-point read)",
    MESH_RESHARDS:
        "elastic shard-count changes applied at checkpoint boundaries",
    MESH_RESHARD_RETRACES:
        "serving-step compiles attributable to a reshard's new mesh "
        "(itemized apart from steady-state serving_retraces)",
    SERVING_TENANT_OTHER:
        "active queries of tenants outside the top-k gauge rollup",
    CKPT_INTEGRITY_FAILURES:
        "checkpoint generations that failed digest verification",
    CKPT_LINEAGE_FALLBACKS:
        "restores that fell back to an older lineage generation",
    FLIGHT_DROPPED_EVENTS:
        "flight-recorder ring events lost to wraparound",
    HEALTH_CHECKS: "/healthz verdicts computed",
    HEALTH_UNHEALTHY: "/healthz verdicts that came back unhealthy",
    LATENCY_FIRST_EMIT_MS:
        "watermark-eligibility -> first delivered window of a sampled "
        "emission chain",
    LATENCY_ELIGIBILITY_MS:
        "watermark-eligibility -> last delivery of the chain (the "
        "Karimov-style whole-emission lag)",
    LATENCY_END_TO_END_MS:
        "first stage stamp -> last stage stamp of a sampled chain "
        "(stage durations telescope to exactly this)",
    LATENCY_LINEAGES: "sampled emission chains finalized",
    LATENCY_STAMP_DROPPED:
        "latency stamps/finalizes that lost their chain "
        "(gated by the default obs diff)",
    LATENCY_OPEN_DECLINED:
        "latency lineages declined at max_open in-flight chains "
        "(sampling backpressure — coverage, not loss)",
    WORKLOAD_AUDITS: "workload fingerprint audit windows folded",
    WORKLOAD_DRIFT_EVENTS:
        "confirmed workload-drift excursions (per-feature, latched; "
        "gated by the default obs diff)",
    COSTMODEL_RESIDUAL_PCT:
        "live |measured - predicted| interval-step residual, percent of "
        "the prediction (gated past the model's stated bound)",
    "workload_arrival_rate_per_s":
        "fingerprint: windowed ingest rate (tuples/s)",
    "workload_burst_factor":
        "fingerprint: max/mean windowed rate over recent audit windows",
    "workload_late_share": "fingerprint: late tuples / ingested tuples",
    "workload_late_age_p50_ms":
        "fingerprint: median lateness age from the device late-age strata",
    "workload_ooo_fraction":
        "fingerprint: shaper-reordered tuples / ingested tuples",
    "workload_fill_ratio":
        "fingerprint: windowed mean flushed block size / batch_size",
    "workload_key_top_share":
        "fingerprint: top-k logical-key load share (keyed/mesh)",
    "workload_key_entropy":
        "fingerprint: normalized key-load entropy (1 = uniform)",
    "workload_pallas_fallback_share":
        "fingerprint: pallas fallbacks / (dispatches + fallbacks)",
    AUTOTUNE_RETUNES:
        "committed live retunes (checkpoint-boundary geometry changes; "
        "APPEARING gates the default obs diff)",
    AUTOTUNE_RETRACES:
        "retunes that compiled a genuinely-new geometry (warm "
        "GeometryCache buckets cost zero; gated by the default obs diff)",
    DEGRADE_ACTIVE_RUNG:
        "degradation-ladder rung in force (0 none, 1 late shed, "
        "2 sampled admission, 3 backpressure; gated by the obs diff)",
    DEGRADE_SHED_TUPLES:
        "tuples the degradation ladder refused (exact conservation: "
        "offered = admitted + shed; gated by the default obs diff)",
    SLO_EVALUATIONS: "SLO policy drain-point evaluation ticks",
    SLO_BURN_EVENTS:
        "(tenant, objective) error budgets that STARTED burning at >= "
        "the alert threshold on both sliding windows (edge-triggered; "
        "gated by the default obs diff)",
    SLO_BUDGET_EXHAUSTED:
        "(tenant, objective) pairs whose slow-window error budget fully "
        "burned (APPEARING gates the default obs diff)",
    SLO_BURNING_TENANTS: "tenants with at least one latched burning "
        "objective",
    SLO_WORST_FAST_BURN:
        "worst fast-window burn rate across every (tenant, objective) "
        "budget (gated by the default obs diff)",
    SLO_FRESHNESS_WORST_MS:
        "worst per-query staleness across active slots (clock now - "
        "newest delivered window end, ms)",
    SLO_EMISSION_LAG_WORST_MS:
        "worst per-query event-time emission lag (watermark - newest "
        "delivered window end, ms)",
}


class Observability:
    """One registry + span recorder, shared by every layer of a run.

    Every span also opens a ``jax.profiler.TraceAnnotation`` named
    ``scotty.<name>``, so the same phases appear inside captured device
    traces (:func:`scotty_tpu.utils.profiling.trace`); without a profiler
    session the annotation is inert.

    ``flight`` attaches a :class:`.flight.FlightRecorder`: spans then
    also land open/close events in the ring, registry activity is sampled
    into it at the drain points (:meth:`flight_sample` — zero extra
    device syncs), and fatal paths flight-record before raising.
    ``postmortem_dir`` arms :meth:`record_failure` to dump an atomic
    crash bundle (``postmortem-<n>.json``) on those paths.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 spans: Optional[SpanRecorder] = None,
                 flight: Optional[FlightRecorder] = None,
                 postmortem_dir: Optional[str] = None,
                 latency=None, workload=None, slo=None,
                 attribution=None):
        self.registry = registry or MetricsRegistry()
        self.spans = spans or SpanRecorder()
        self.flight = flight
        self.postmortem_dir = postmortem_dir
        #: emission-latency tracer (ISSUE 14): None by default — every
        #: stamping seam pays one attribute check, exactly the flight
        #: discipline. Attach with :meth:`attach_latency`.
        self.latency = latency.bind(self) if latency is not None else None
        #: workload fingerprint monitor (ISSUE 16): None by default —
        #: same discipline; sampled inside :meth:`flight_sync` (the hook
        #: every drain point already calls). Attach with
        #: :meth:`attach_workload`.
        self.workload = workload.bind(self) if workload is not None \
            else None
        #: per-tenant SLO plane (ISSUE 19): None by default — same
        #: one-attribute-check discipline. The policy evaluates inside
        #: :meth:`flight_sync`; the attribution ledger is fed by the
        #: serving layers. Attach with :meth:`attach_slo` /
        #: :meth:`attach_attribution`.
        self.slo = slo.bind(self) if slo is not None else None
        self.attribution = attribution.bind(self) \
            if attribution is not None else None
        self._flight_prev: dict = {}
        #: crash-site seam (ISSUE 8): when set, called as
        #: ``flight_hook(kind, name, value)`` BEFORE every flight event
        #: records — each flight-event emit point is thereby an
        #: enumerable crash site (the hook may raise). None in
        #: production: the emission path pays one attribute check.
        self.flight_hook = None

    # -- recording --------------------------------------------------------
    def span(self, name: str, **args):
        if self.flight is None:
            return self.spans.span(name, **args)
        return self._flight_span(name, args)

    @contextlib.contextmanager
    def _flight_span(self, name: str, args: dict):
        from . import flight as _flight

        self.flight.record(_flight.SPAN_OPEN, name)
        try:
            with self.spans.span(name, **args) as ann:
                yield ann
        finally:
            self.flight.record(_flight.SPAN_CLOSE, name)

    def counter(self, name: str):
        return self.registry.counter(name)

    def gauge(self, name: str):
        return self.registry.gauge(name)

    def histogram(self, name: str):
        return self.registry.histogram(name)

    # -- flight recorder (ISSUE 4) ----------------------------------------
    def flight_event(self, kind: str, name: str, value: float = 0.0
                     ) -> None:
        """Record one flight event (no-op without an attached recorder) —
        the single call every wiring site uses, so a bare ``Observability``
        stays exactly as cheap as before. An installed ``flight_hook``
        sees the event FIRST (and may raise — the crash-point fuzzer's
        site enumeration rides exactly this seam)."""
        if self.flight_hook is not None:
            self.flight_hook(kind, name, value)
        if self.flight is not None:
            self.flight.record(kind, name, value)

    def flight_sample(self) -> None:
        """Sample registry activity into the flight ring: one ``counter``
        event per counter that moved since the last sample (value =
        delta) and one ``gauge`` event per gauge that changed. Called at
        the existing sync()/drain points only — the ring sees engine
        state exactly where a device round trip already happens, adding
        zero syncs. Also folds the ring's wraparound drop count into the
        registry (``flight_dropped_events``) so it is never silent."""
        fl = self.flight
        if fl is None:
            return
        from . import flight as _flight

        with self.registry._lock:
            counters = {n: c.value
                        for n, c in self.registry.counters.items()}
            gauges = {n: g.value for n, g in self.registry.gauges.items()}
        prev = self._flight_prev
        for n, v in counters.items():
            if n == FLIGHT_DROPPED_EVENTS:
                continue               # the fold below, not a feedback loop
            last = prev.get(n, 0.0)
            if v != last:
                fl.record(_flight.COUNTER, n, v - last)
                prev[n] = v
        for n, v in gauges.items():
            key = "gauge:" + n
            if prev.get(key) != v:
                fl.record(_flight.GAUGE, n, v)
                prev[key] = v
        dropped = fl.dropped
        last_d = prev.get("flight:dropped", 0)
        if dropped > last_d:
            self.registry.counter(FLIGHT_DROPPED_EVENTS).inc(
                dropped - last_d)
            prev["flight:dropped"] = dropped

    def flight_sync(self, watermark: Optional[float] = None) -> None:
        """The drain-point hook the engine calls from ``sync()`` /
        ``check_overflow()``: samples the workload monitor (when one is
        attached — the fingerprint's zero-new-syncs guarantee lives
        here), records the watermark advance (when known) and samples
        the registry into the flight ring. The workload sample happens
        BEFORE the recorder check: a monitor works without a flight
        ring, and when both ride, the ring's registry sample sees the
        audit's fresh gauges."""
        if self.workload is not None:
            self.workload.sample()
        if self.slo is not None:
            # the SLO tick rides the same drain point, AFTER the
            # workload sample and BEFORE the ring sample — so the
            # sampled counter deltas already include this tick's
            # verdicts. Host-side dict work only: zero new syncs.
            self.slo.evaluate()
        if self.flight is None:
            return
        from . import flight as _flight

        if watermark is not None:
            self.flight.record(_flight.WATERMARK, "watermark",
                               float(watermark))
        self.flight_sample()

    # -- emission-latency attribution (ISSUE 14) --------------------------
    def attach_latency(self, tracer=None, **kwargs):
        """Attach (and return) a :class:`.latency.LatencyTracer` —
        construction kwargs (``clock=``, ``sample_every=``, …) pass
        through when no tracer is given; detach with
        ``obs.latency = None``."""
        from .latency import LatencyTracer

        if tracer is None:
            tracer = LatencyTracer(**kwargs)
        self.latency = tracer.bind(self)
        return tracer

    # -- workload sensor plane (ISSUE 16) ---------------------------------
    def attach_workload(self, monitor=None, **kwargs):
        """Attach (and return) a :class:`.workload.WorkloadMonitor` —
        construction kwargs (``clock=``, ``audit_interval_s=``, …) pass
        through when no monitor is given; detach with
        ``obs.workload = None``. The monitor samples at every
        :meth:`flight_sync` (i.e. at the existing drain points only)."""
        from .workload import WorkloadMonitor

        if monitor is None:
            monitor = WorkloadMonitor(**kwargs)
        self.workload = monitor.bind(self)
        return monitor

    # -- per-tenant SLO accounting plane (ISSUE 19) -----------------------
    def attach_slo(self, policy=None, **kwargs):
        """Attach (and return) a :class:`.slo.SloPolicy` — construction
        kwargs (``freshness_ms=``, ``delivered_share=``, ``clock=``, …)
        pass through when no policy is given; detach with
        ``obs.slo = None``. The policy evaluates one tick at every
        :meth:`flight_sync` (i.e. at the existing drain points only)."""
        from .slo import SloPolicy

        if policy is None:
            policy = SloPolicy(**kwargs)
        self.slo = policy.bind(self)
        return policy

    def attach_attribution(self, attribution=None, **kwargs):
        """Attach (and return) a :class:`.attribution.TenantAttribution`
        ledger — construction kwargs (``clock=``, ``top_k=``, …) pass
        through when none is given; detach with
        ``obs.attribution = None``. Serving layers feed it through
        their ``_attr`` / ``account_emissions`` seams."""
        from .attribution import TenantAttribution

        if attribution is None:
            attribution = TenantAttribution(**kwargs)
        self.attribution = attribution.bind(self)
        return attribution

    def record_failure(self, exc: BaseException, kind: str = "overflow",
                       config=None, checkpoint: Optional[str] = None):
        """Flight-record a fatal event and, when ``postmortem_dir`` is
        set, dump an atomic postmortem bundle. Returns the bundle path
        (or None). NEVER raises — this runs on crash paths where a
        secondary failure would mask the real one."""
        try:
            if self.flight is not None:
                self.flight.record(kind, type(exc).__name__)
                self.flight_sample()
            if self.postmortem_dir:
                from .flight import write_postmortem as _write

                return _write(self.postmortem_dir, exception=exc,
                              obs=self, config=config,
                              checkpoint=checkpoint)
        # scotty: allow(silent-drop) — crash-path side channel: this
        # runs while the REAL failure is propagating; a secondary
        # postmortem-write error must never mask it
        except Exception:       # noqa: BLE001
            pass
        return None

    # -- live endpoint ----------------------------------------------------
    def serve(self, port: int = 0, host: str = "127.0.0.1",
              health: Optional[HealthPolicy] = None):
        """Start the daemon-thread HTTP endpoint (``/metrics``, ``/vars``,
        ``/healthz`` — :mod:`.server`) over this Observability; returns
        the :class:`.server.ObsServer` (read ``.port`` back, ``close()``
        when done)."""
        from .server import serve as _serve

        return _serve(self, port=port, host=host, health=health)

    # -- export -----------------------------------------------------------
    def snapshot(self) -> dict:
        return self.registry.snapshot()

    def export(self) -> dict:
        """The structured artifact section: metrics snapshot + span
        summary (what ``BenchResult.to_dict()`` embeds as ``metrics``),
        plus the workload fingerprint when a monitor rode the run — so
        every recorded cell carries the workload it was certified
        under."""
        out = {"metrics": self.snapshot(), "spans": self.spans.summary()}
        if self.workload is not None:
            out["fingerprint"] = self.workload.fingerprint().to_dict()
        if self.attribution is not None:
            out["attribution"] = self.attribution.export()
        if self.slo is not None:
            out["slo"] = self.slo.export()
        return out

    def write_jsonl(self, path, label: Optional[str] = None) -> dict:
        """Append one snapshot row to a JSONL time-series file."""
        with JsonlExporter(path) as ex:
            return ex.write(self.registry, label=label)

    def write_chrome_trace(self, path: str) -> None:
        self.spans.dump_chrome_trace(path)

    def prometheus(self, prefix: str = "scotty_") -> str:
        return prometheus_text(self.registry, prefix=prefix,
                               help_texts=METRIC_HELP)


__all__ = [
    "Observability", "MetricsRegistry", "SpanRecorder", "Span",
    "program_span", "JsonlExporter", "prometheus_text", "write_chrome_trace",
    "FlightRecorder", "write_postmortem", "HealthPolicy",
    "FLIGHT_DROPPED_EVENTS", "HEALTH_CHECKS", "HEALTH_UNHEALTHY",
    "METRIC_HELP",
    "DeviceMetrics", "init_device_metrics",
    "DEVICE_INGEST_TUPLES", "DEVICE_LATE_TUPLES", "DEVICE_DROPPED_TUPLES",
    "DEVICE_TRIGGERS_FIRED", "DEVICE_WINDOWS_NONEMPTY",
    "DEVICE_SLICES_TOUCHED", "DEVICE_SILENT_INTERVALS",
    "INGEST_TUPLES", "INGEST_BATCH_SIZE", "INGEST_DENSE_BATCHES",
    "INGEST_DENSE_TS32_BATCHES", "INGEST_DENSE_TS32_FALLBACKS",
    "LATE_TUPLES", "DROPPED_TUPLES",
    "WATERMARKS", "WATERMARK_LAG_MS", "WATERMARK_DISPATCH_MS",
    "INTERVAL_STEP_MS", "SYNC_MS", "SLICE_OCCUPANCY", "SLICE_HEADROOM",
    "QUEUE_DEPTH", "WINDOWS_EMITTED", "OVERFLOWS", "SILENT_INTERVALS",
    "EMIT_LATENCY_MS",
    "SHAPER_REORDERED_TUPLES", "SHAPER_FLUSHES", "SHAPER_HELD_TUPLES",
    "SHAPER_LATE_ROUTED", "SHAPER_SLACK_OVERFLOWS", "SHAPER_FILL_RATIO",
    "INGEST_RING_OFFERED", "INGEST_RING_DELIVERED", "INGEST_RING_SHED",
    "INGEST_RING_BLOCKS", "INGEST_RING_FULL_EVENTS",
    "INGEST_RING_OCCUPANCY", "INGEST_RING_HIGHWATER",
    "SOAK_AUDITS", "SOAK_INVARIANT_FAILURES", "SOAK_RECORDS_SEEN",
    "SERVING_REGISTERED", "SERVING_CANCELLED", "SERVING_REJECTED",
    "SERVING_RETRACES", "SERVING_CACHE_HITS", "SERVING_CACHE_MISSES",
    "SERVING_CACHE_EVICTIONS", "SERVING_ACTIVE_QUERIES",
    "MESH_RESHARDS", "MESH_RESHARD_RETRACES", "SERVING_TENANT_OTHER",
    "LATENCY_FIRST_EMIT_MS", "LATENCY_ELIGIBILITY_MS",
    "LATENCY_END_TO_END_MS", "LATENCY_LINEAGES", "LATENCY_STAMP_DROPPED",
    "LATENCY_OPEN_DECLINED",
    "WorkloadMonitor", "WorkloadFingerprint", "DriftDetector", "CostModel",
    "FINGERPRINT_SCHEMA", "WORKLOAD_AUDITS", "WORKLOAD_DRIFT_EVENTS",
    "COSTMODEL_RESIDUAL_PCT", "RESIDUAL_BOUND_PCT", "feature_gauge",
    "RESILIENCE_SHED_TUPLES", "RESILIENCE_GROW_EVENTS",
    "RESILIENCE_CHECKPOINTS", "RESILIENCE_RESTARTS",
    "DELIVERY_EMITTED", "DELIVERY_DUPLICATES_SUPPRESSED",
    "DELIVERY_EPOCHS_COMMITTED", "CKPT_INTEGRITY_FAILURES",
    "CKPT_LINEAGE_FALLBACKS",
    "RESILIENCE_SOURCE_RETRIES", "RESILIENCE_POISON_RECORDS",
    "RESILIENCE_STALL_EVENTS", "RESILIENCE_CHECKPOINT_SPAN",
    "RESILIENCE_RESTORE_SPAN", "RESILIENCE_BACKOFF_SPAN",
    "RESILIENCE_GROW_SPAN",
    "AUTOTUNE_RETUNES", "AUTOTUNE_RETRACES", "AUTOTUNE_RETUNE_SPAN",
    "DEGRADE_ACTIVE_RUNG", "DEGRADE_SHED_TUPLES",
    "SloPolicy", "ErrorBudget", "TenantAttribution", "FreshnessTracker",
    "apportion", "attribution_metric", "ATTRIBUTION_FAMILIES",
    "SLO_EVALUATIONS", "SLO_BURN_EVENTS", "SLO_BUDGET_EXHAUSTED",
    "SLO_BURNING_TENANTS", "SLO_WORST_FAST_BURN",
    "SLO_FRESHNESS_WORST_MS", "SLO_EMISSION_LAG_WORST_MS",
]

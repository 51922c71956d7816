"""Config-driven benchmark runner — BenchmarkRunner.java:20-202 parity.

``python -m scotty_tpu.bench [config.json ...]`` iterates every
windowConfiguration × configuration(engine) × aggFunction cell of each JSON
config, runs it, prints a table, and writes ``result_<name>.json`` next to
``--out-dir`` (default ./bench_results), the analogue of the reference's
``result_<name>.txt`` files (BenchmarkRunner.java:62-69).

Engines:

* ``TpuEngine`` (reference config name ``Slicing`` accepted): the fused
  slicing pipeline — AlignedStreamPipeline when the spec allows, otherwise
  the batch-at-a-time TpuWindowOperator path (out-of-order streams, count
  measure, bands).
* ``Buckets`` (reference name ``Flink`` accepted): the no-sharing
  window-bucket baseline (buckets.py) anchoring the ≥10× claim. Offered load
  comes from ``bucketsThroughput`` (the reference likewise ran its Flink
  baseline at a fraction of Scotty's rate —
  random_tumbling_benchmark_flink.json's 1,600 vs 2,000,000 tuples/s).
* ``Simulator``: the host reference-semantics operator (tiny loads only).
"""

from __future__ import annotations

import contextlib
import json
import os
import time

from typing import List, Optional

import numpy as np

from .. import obs as _obs
from ..obs import latency as _lat
from ..utils import stdout_echo as _stdout
from .harness import (
    BenchmarkConfig,
    BenchResult,
    finalize_observability,
    first_emit_stats,
    latency_stats,
    make_aggregation,
    parse_window_spec,
    run_benchmark,
)


def _round_throughput(throughput: int, grid: int) -> int:
    """Largest rate ≤ throughput that is an integer per-slice count."""
    per = max(1, throughput * grid // 1000)
    return per * 1000 // grid


#: drained emit-latency sampling discipline, shared by every cell type:
#: up to LATENCY_SAMPLES_MAX samples within LATENCY_BUDGET_S seconds,
#: never fewer than LATENCY_SAMPLES_MIN
LATENCY_SAMPLES_MAX = 100
LATENCY_BUDGET_S = 45.0
LATENCY_SAMPLES_MIN = 5

#: every optional result attribute a cell may pin onto its row —
#: run_config copies the ones present, and ``obs diff`` imports this
#: list as part of its known-threshold-key universe (a threshold file
#: gating a row field must not be rejected as a typo)
CELL_EXTRA_FIELDS = (
    "link_mbps_raw", "link_mbps_achieved",
    "link_saturation", "n_lat_samples",
    "first_emit_p50_ms", "first_emit_p99_ms",
    "first_emit_samples",
    "latency_stages_ms",
    "latency_conservation_ok",
    "latency_worst_chain_gap_ms",
    "latency_chains", "latency_owner_stage",
    "latency_overhead_pct_median",
    "first_emit_microbatch_p50_ms",
    "first_emit_microbatch_p99_ms",
    "first_emit_microbatch_samples",
    "microbatch_arms",
    "microbatch_conservation_ok",
    "microbatch_worst_chain_gap_ms",
    "microbatch_tps",
    "microbatch_oracle_match",
    "microbatch_oracle_windows",
    "microbatch_flushes",
    "flags_off_ab_pct_median",
    "p50_emit_ms", "emit_ms_device",
    "p99_emit_ms_trimmed", "n_stall_samples",
    "n_trimmed_samples", "stall_flagged",
    "tail_unattributed", "shaper_back_ms",
    "shaper_late_routed", "shaper_reordered",
    "serving_retraces_after_warmup",
    "serving_registered", "serving_cancelled",
    "serving_rejected", "serving_cache_hits",
    "churn_ops", "throughput_static",
    "throughput_delta_pct", "oracle_match",
    "scan_match", "oracle_windows",
    "tuples_per_sec_inorder",
    "inprogram_tps", "generator_share",
    "legacy_anchor_tps",
    "generator_share_legacy",
    "legacy_anchor_note",
    "ring_fed_vs_inprogram",
    "context_mode", "ctx_speculative_tuples",
    "ctx_fallback_tuples", "ctx_fallback_runs",
    "ctx_fallback_rate",
    "churn_schedule", "churn_seed",
    "ring_occupancy_p50", "ring_occupancy_p90",
    "ring_occupancy_p99",
    "host_staged_p50", "host_staged_p90",
    "host_staged_p99",
    "ring_full_events", "ring_shed",
    "ring_blocks", "baseline_per_record_tps",
    "speedup_vs_per_record", "platform",
    "tpu_floor_note", "soak_passed",
    "soak_seen", "soak_audits_n",
    "soak_findings", "soak_last_terms",
    "soak_healthz_unhealthy", "soak_report",
    "delivery_mode", "delivery_snapshot",
    "delivery_overhead_pct_median",
    "n_keys", "n_shards", "host_cores",
    "tuples_per_sec_1shard", "scaling_ratio",
    "per_shard_occupancy", "shard_placement", "rebalance_match",
    "reshard_retraces", "reshard_timeline",
    "reshard_wall_s", "delivery_tags_unique",
    "workload_phases", "drift_events",
    "drift_fired", "drift_transitions",
    "drift_detect_lags", "drift_all_detected",
    "drift_false_positives",
    "workload_overhead_pct_median",
    "served_health_ok", "served_drift_events",
    "autotune_phases", "autotune_decisions",
    "autotune_retunes", "autotune_retraces",
    "autotune_schedule",
    "adaptive_admitted", "static_admitted",
    "autotune_beats_all_statics",
    "stable_retunes", "stable_decisions",
    "autotune_overhead_pct_median",
    "degrade_transitions",
    "degrade_shed_tuples",
    "slo_tenants", "slo_hot_tenant",
    "slo_violation_detected",
    "slo_violating_tenant",
    "slo_violating_objective",
    "slo_owning_stage",
    "slo_false_positives",
    "slo_burn_events_total",
    "slo_conservation_ok",
    "attribution_overhead_pct_median",
    "sla_ms", "sla_met",
)


def measure_rtt_floor(n: int = 12) -> float:
    """Drained device→host round-trip floor (ms): device_get of a tiny
    freshly-computed scalar on an idle queue. Every emit-latency sample in
    this harness pays at least this, so artifacts report it alongside."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    h = f(jnp.int32(0))
    jax.device_get(h)
    best = float("inf")
    for _ in range(n):
        # a FRESH array each time — re-fetching the same jax.Array hits
        # its cached host copy and measures nothing (r3 review)
        h = f(h)
        t0 = time.perf_counter()
        jax.device_get(h)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _run_pipeline_cell(pipeline, cfg: BenchmarkConfig, window_spec: str,
                       agg_name: str, mode: str,
                       latency_samples: int = LATENCY_SAMPLES_MAX,
                       latency_budget_s: float = LATENCY_BUDGET_S,
                       obs: Optional[_obs.Observability] = None) -> BenchResult:
    """bench.py's measurement discipline for any fused pipeline object:
    pre-roll past the widest window span, time a steady-state region, then
    sample emit latency with a drained queue (up to ``latency_samples``
    samples within ``latency_budget_s``, at least 5).

    With ``obs`` attached, the pipeline's driver hooks record per-interval
    step latency + ingest counters, the harness phases record spans, and
    the structured export lands in the result's ``metrics`` section."""
    import jax

    from ..core.windows import SessionWindow

    _span = obs.span if obs is not None else (
        lambda name: contextlib.nullcontext())

    max_span = max(int(w.gap) if isinstance(w, SessionWindow)
                   else w.clear_delay() for w in pipeline.windows)
    warmup = -(-max_span // pipeline.wm_period_ms) + 2
    timed = max(1, cfg.runtime_s,
                getattr(pipeline, "min_timed_intervals", 0))
    if mode == "buckets":
        # the no-sharing baseline is deliberately O(#triggers × ring) per
        # interval — a few deterministic intervals measure it fine
        timed = min(timed, 3)
        latency_samples = min(latency_samples, 3)
    # the sparsest window must trigger at least once inside the timed
    # region (a 60 s-slide window fires every 60 intervals — a 10-interval
    # run would report windows_emitted=0)
    def _trigger_horizon(w):
        from ..core.windows import FixedBandWindow, SlidingWindow

        if isinstance(w, SessionWindow):
            return 0                    # emission cadence is gap-driven;
                                        # min_timed_intervals covers it
        if isinstance(w, FixedBandWindow):
            return int(w.start + w.size)      # its single trigger point
        if isinstance(w, SlidingWindow):
            # the warmup phase (prefill or a full run) always advances past
            # the widest window span before the timed region, so the first
            # sliding trigger has already fired: one slide per further
            # trigger is the exact post-warmup horizon (r3 review —
            # max(size, slide) here only inflated cell wall time)
            return int(w.slide)
        return int(w.size)

    max_period = max(_trigger_horizon(w) for w in pipeline.windows)
    timed = max(timed, -(-max_period // pipeline.wm_period_ms) + 1)

    with _span("warmup"):
        pipeline.reset()
        if hasattr(pipeline, "prefill"):
            pipeline.prefill(warmup)   # ring fill without the query cost
        else:
            pipeline.run(warmup, collect=False)
        pipeline.sync()

    if obs is not None:
        # attach AFTER warmup: warmup tuples must not pollute the counters,
        # and the rate denominator restarts so *_per_s reflects the
        # measured region, not compile/warmup wall time
        if obs.latency is None:
            # emission-latency lineage (ISSUE 14): every metrics-bearing
            # cell traces sampled chains through the driver seams in the
            # timed region, and the drained phase below force-samples
            # its first-emit probes on the same tracer
            obs.attach_latency()
        pipeline.set_observability(obs)
        obs.registry.reset_clock()
    timed_from = getattr(pipeline, "_interval", warmup)
    t0 = time.perf_counter()
    with _span("timed"):
        outs = pipeline.run(timed, collect=True)
        pipeline.sync()
    wall = time.perf_counter() - t0

    cnts = jax.device_get([o[2] for o in outs])
    emitted = int(sum(int((c > 0).sum()) for c in cnts))

    # Emit-latency samples measure DELIVERY of final window values: wide
    # sketch partials lower to one float per window ON DEVICE
    # (DeviceAggregateSpec.lower_device) so the fetched payload is [T]-
    # sized — on bandwidth-limited links, fetching raw [T, width] sketch
    # registers would measure the link, not the engine (docs/DESIGN.md).
    specs = [a.device_spec() for a in pipeline.aggregations]
    if any(s.lower_device is not None for s in specs):
        emit_payload = jax.jit(lambda cnt, results: (cnt, tuple(
            (s.lower_device(r, cnt) if s.lower_device is not None else r)
            for s, r in zip(specs, results))))
        # warm the lowering jit on the last timed output so the first
        # sample doesn't time its compile (r3 review)
        jax.device_get(emit_payload(outs[-1][2], outs[-1][3]))
    else:
        # dense aggs: [T, w<=2] payloads are already small — a jitted
        # identity would only add a dispatch per sample
        emit_payload = lambda cnt, results: (cnt, results)  # noqa: E731
    if obs is not None:
        # the timed region is over: freeze the rate denominator and detach
        # the per-interval hooks so the drained latency phase (up to 45 s
        # of syncs) neither dilutes *_per_s nor inflates the counters
        obs.registry.stop_clock()
        pipeline.set_observability(None)
    lats = []
    fe_lats = []
    tracer = obs.latency if obs is not None else None
    t_lat = time.perf_counter()
    with _span("latency"):
        for _ in range(latency_samples):
            pipeline.sync()
            t1 = time.perf_counter()
            # first-emit probe (ISSUE 14): a force-sampled chain around
            # exactly this drained sample — dispatch at run(1),
            # eligibility the moment the watermark-advancing dispatch
            # returns, emit when the window payload is host-delivered;
            # first_emit = eligibility -> emit, the Karimov-style
            # number the whole-sample wall time (lats) only bounds
            lid = tracer.open(force=True) if tracer is not None else None
            out = pipeline.run(1)[0]
            if lid is not None:
                tracer.stamp(lid, _lat.STAGE_ELIGIBILITY)
            jax.device_get(emit_payload(out[2], out[3]))
            lats.append((time.perf_counter() - t1) * 1e3)
            if lid is not None:
                tracer.stamp(lid, _lat.STAGE_EMIT)
                fin = tracer.finalize(lid)
                if fin is not None and fin["first_emit_ms"] is not None:
                    fe_lats.append(fin["first_emit_ms"])
            if (len(lats) >= LATENCY_SAMPLES_MIN
                    and time.perf_counter() - t_lat > latency_budget_s):
                break
    pipeline.check_overflow()

    if hasattr(pipeline, "tuples_in_range"):
        # silence-aware accounting (session pipelines: silent intervals
        # carry no tuples)
        n_tuples = pipeline.tuples_in_range(timed_from, timed_from + timed)
    else:
        n_tuples = timed * pipeline.tuples_per_interval
    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=0.0,                    # filled by latency_stats below
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    res.n_lat_samples = len(lats)
    # stall-robust stats (VERDICT r4 weak #5): raw p99 stays the primary
    # field, but trimmed p99 + stall count ride alongside so a transport
    # stall can never masquerade as an engine latency
    for k, v in latency_stats(lats).items():
        setattr(res, k, v)
    first_emit_stats(res, fe_lats)
    finalize_observability(res, obs, lats, emitted)
    # emit latency free of the round trip (VERDICT r3 item 9): the fused
    # step computes an interval's window results within the same device
    # program that ingests it, so the steady-state per-interval time IS
    # the interval-attributable emit latency (the sampled p50/p99 above
    # measure dispatch→fetched delivery instead, round trip included)
    res.emit_ms_device = wall / timed * 1e3
    return res


def run_cell(cfg: BenchmarkConfig, window_spec: str, agg_name: str,
             engine: str,
             collect_metrics: bool = True,
             make_obs: Optional[callable] = None) -> BenchResult:
    """One (windowConfiguration × engine × aggFunction) cell. Unless
    ``collect_metrics=False``, a fresh per-cell
    :class:`scotty_tpu.obs.Observability` rides the run and its export is
    embedded in the result (``metrics`` section). ``make_obs`` overrides
    how that per-cell Observability is built (the runner's
    ``--flight-capacity``/``--serve-port`` wiring passes a factory that
    attaches a FlightRecorder and publishes the live instance to the
    shared endpoint)."""
    windows = parse_window_spec(window_spec, seed=cfg.seed)
    engine = {"Slicing": "TpuEngine", "Flink": "Buckets"}.get(engine, engine)
    if not collect_metrics:
        obs = None
    else:
        obs = make_obs() if make_obs is not None else _obs.Observability()
    if cfg.legacy_generator and (engine != "TpuEngine"
                                 or cfg.session_config):
        # the anchor cell must never silently substitute a different
        # execution mode — the whole point is a workload-identical
        # cross-round comparison on the aligned pipeline
        raise NotImplementedError(
            "legacyGenerator anchor cells run only on the TpuEngine "
            "aligned pipeline (no sessionConfig, no alternate engines)")

    if engine == "TpuEngine":
        if not cfg.session_config:
            from ..engine import EngineConfig
            from ..engine.pipeline import AlignedStreamPipeline, StreamPipeline

            econf = EngineConfig(capacity=cfg.capacity, annex_capacity=8,
                                 min_trigger_pad=32,
                                 overflow_policy=cfg.overflow_policy)
            try:
                tp = _round_throughput(
                    cfg.throughput,
                    AlignedStreamPipeline.slice_grid(
                        windows, cfg.watermark_period_ms))
                p = AlignedStreamPipeline(
                    windows, [make_aggregation(agg_name)], config=econf,
                    throughput=tp, wm_period_ms=cfg.watermark_period_ms,
                    max_lateness=cfg.max_lateness, seed=cfg.seed,
                    gc_every=32, out_of_order_pct=cfg.out_of_order_pct,
                    legacy_generator=cfg.legacy_generator,
                    collect_device_metrics=collect_metrics)
                return _run_pipeline_cell(p, cfg, window_spec, agg_name,
                                          "aligned", obs=obs)
            except NotImplementedError:
                if cfg.legacy_generator:
                    # no silent fallback for the anchor cell (see the
                    # guard above; this covers aligned-spec rejections
                    # like sketch aggs or an unaligned window mix)
                    raise
            try:
                # count-measure workloads (count tumbling, optionally mixed
                # with time grids, in- or out-of-order): the fused record-
                # ring pipeline — closed-form count bound, no per-watermark
                # probe (VERDICT r4 item 1)
                from ..engine.count_pipeline import CountStreamPipeline

                p = CountStreamPipeline(
                    windows, [make_aggregation(agg_name)], config=econf,
                    throughput=cfg.throughput,
                    wm_period_ms=cfg.watermark_period_ms,
                    max_lateness=cfg.max_lateness, seed=cfg.seed,
                    out_of_order_pct=cfg.out_of_order_pct,
                    collect_device_metrics=collect_metrics)
                return _run_pipeline_cell(p, cfg, window_spec, agg_name,
                                          "count-fused", obs=obs)
            except NotImplementedError:
                pass
            try:
                # fused fallback for specs the aligned pipeline rejects
                # (fixed-band windows, sketch lifts on bands…): still one
                # XLA dispatch per watermark interval, via the general
                # scatter ingest (+ per-sub-batch late lanes when OOO)
                p = StreamPipeline(
                    windows, [make_aggregation(agg_name)], config=econf,
                    throughput=cfg.throughput,
                    wm_period_ms=cfg.watermark_period_ms,
                    max_lateness=cfg.max_lateness, seed=cfg.seed,
                    out_of_order_pct=cfg.out_of_order_pct,
                    collect_device_metrics=collect_metrics)
                return _run_pipeline_cell(p, cfg, window_spec, agg_name,
                                          "fused", obs=obs)
            except NotImplementedError:
                pass
        # count-measure / session specs: batch-at-a-time device operator
        # via the classic harness (device-generated streams with split
        # late sub-batches). Anything the fused pipelines reject pays
        # per-batch dispatch overhead, so the pipelines above are always
        # preferred.
        return run_benchmark(cfg, window_spec, agg_name, engine="TpuEngine",
                             obs=obs, collect_metrics=collect_metrics)

    if engine == "Buckets":
        from .buckets import BucketWindowPipeline
        from ..engine.pipeline import AlignedStreamPipeline

        tp = getattr(cfg, "buckets_throughput", None) or max(
            1000, cfg.throughput // 200)
        tp = _round_throughput(
            tp, AlignedStreamPipeline.slice_grid(windows,
                                                 cfg.watermark_period_ms))
        p = BucketWindowPipeline(
            windows, [make_aggregation(agg_name)], throughput=tp,
            wm_period_ms=cfg.watermark_period_ms, seed=cfg.seed,
            max_lateness=cfg.max_lateness)
        return _run_pipeline_cell(p, cfg, window_spec, agg_name, "buckets",
                                  obs=obs)

    if engine == "Hybrid":
        # resolve the backend the way HybridWindowOperator would, then use
        # the matching measurement loop: device-realizable workloads take
        # a fused pipeline (one dispatch per watermark interval) or the
        # async TpuEngine path; everything else runs on the host
        from ..hybrid import HybridWindowOperator

        probe = HybridWindowOperator()
        for w in windows:
            probe.add_window_assigner(w)
        probe.add_aggregation(make_aggregation(agg_name))
        if probe._device_realizable():
            if cfg.out_of_order_pct == 0 and cfg.session_config:
                from ..engine import EngineConfig
                from ..engine.session_pipeline import SessionStreamPipeline

                try:
                    p = SessionStreamPipeline(
                        windows, [make_aggregation(agg_name)],
                        config=EngineConfig(
                            capacity=cfg.capacity, annex_capacity=8,
                            min_trigger_pad=32,
                            overflow_policy=cfg.overflow_policy),
                        throughput=cfg.throughput,
                        wm_period_ms=cfg.watermark_period_ms,
                        max_lateness=cfg.max_lateness, seed=cfg.seed,
                        session_config=cfg.session_config,
                        collect_device_metrics=collect_metrics)
                    return _run_pipeline_cell(p, cfg, window_spec,
                                              agg_name, "session", obs=obs)
                except NotImplementedError:
                    pass
            return run_benchmark(cfg, window_spec, agg_name,
                                 engine="TpuEngine", obs=obs,
                                 collect_metrics=collect_metrics)
        return run_benchmark(cfg, window_spec, agg_name, engine="Hybrid",
                             obs=obs, collect_metrics=collect_metrics)

    if engine == "Simulator":
        return run_benchmark(cfg, window_spec, agg_name, engine="Simulator",
                             obs=obs, collect_metrics=collect_metrics)

    if engine == "Keyed":
        return run_keyed_cell(cfg, window_spec, agg_name, obs=obs)

    if engine == "MeshKeyed":
        return run_mesh_keyed_cell(cfg, window_spec, agg_name, obs=obs)

    if engine == "HostFed":
        return run_host_fed_cell(cfg, window_spec, agg_name, obs=obs)

    if engine == "KeyedHostFed":
        return run_keyed_host_fed_cell(cfg, window_spec, agg_name, obs=obs)

    if engine == "ShapedOOO":
        return run_shaped_ooo_cell(cfg, window_spec, agg_name, obs=obs)

    if engine == "ContextChaos":
        return run_context_chaos_cell(cfg, window_spec, agg_name, obs=obs)

    if engine == "CountFused":
        return run_count_fused_cell(cfg, window_spec, agg_name, obs=obs)

    if engine == "RingFed":
        return run_ring_fed_cell(cfg, window_spec, agg_name, obs=obs)

    if engine == "LatencyHeadline":
        return run_latency_headline_cell(cfg, window_spec, agg_name,
                                         obs=obs)

    if engine == "RingFedMesh":
        return run_ring_fed_mesh_cell(cfg, window_spec, agg_name, obs=obs)

    if engine == "IngestExternal":
        return run_ingest_external_cell(cfg, window_spec, agg_name,
                                        obs=obs)

    if engine == "Soak":
        return run_soak_cell(cfg, window_spec, agg_name, obs=obs)

    if engine == "QueryChurn":
        return run_query_churn_cell(cfg, window_spec, agg_name, obs=obs)

    if engine == "QueryChurnMesh":
        return run_query_churn_mesh_cell(cfg, window_spec, agg_name,
                                         obs=obs)

    if engine == "WorkloadDrift":
        return run_workload_drift_cell(cfg, window_spec, agg_name,
                                       obs=obs)

    if engine == "AutotuneShift":
        return run_autotune_shift_cell(cfg, window_spec, agg_name,
                                       obs=obs)

    if engine == "SloChurn":
        return run_slo_churn_cell(cfg, window_spec, agg_name, obs=obs)

    raise ValueError(f"unknown engine {engine!r}")


def _churn_schedule(cfg: BenchmarkConfig, pool, n_intervals: int,
                    n_initial: int):
    """The seeded register/cancel schedule: ``schedule[i]`` is interval
    i's command list (the :func:`scotty_tpu.serving.replay_schedule`
    format), deterministically generated from ``cfg.seed`` — the serving
    run AND the oracle replay both consume THIS structure, so the two
    runs cannot drift. Registers ramp toward ``churn_max_active`` then
    alternate with cancels; >= ``cfg.churn_ops`` operations total."""
    rng = np.random.default_rng(cfg.seed + 0x5e41)
    ops_per_interval = -(-cfg.churn_ops // n_intervals)
    schedule = [[] for _ in range(n_intervals)]
    live: list = []
    next_id = 0
    n_ops = 0
    for i in range(n_intervals):
        for _ in range(ops_per_interval):
            headroom = n_initial + len(live) < cfg.churn_max_active
            if live and (not headroom or rng.random() < 0.45):
                rid = live.pop(int(rng.integers(len(live))))
                schedule[i].append(("cancel", rid))
            else:
                w = pool[int(rng.integers(len(pool)))]
                tenant = f"tenant{next_id % max(1, cfg.churn_tenants)}"
                schedule[i].append(("register", next_id, w, tenant))
                live.append(next_id)
                next_id += 1
            n_ops += 1
    return schedule, n_ops, next_id


def _churn_pool(windows, g: int, P: int, max_size: int):
    """Churnable window geometries: slides/sizes multiples of the slice
    grid, slides >= P/8 so the per-slot trigger-lane bucket stays fixed
    for the whole run (steady-state churn must not rebucket)."""
    from ..core.windows import SlidingWindow, TumblingWindow, WindowMeasure

    T = WindowMeasure.Time
    slides = [s for s in (P, P // 2, P // 4, P // 8)
              if s >= g and s % g == 0] or [max(g, P)]
    pool = []
    for sl in slides:
        for m in (1, 2, 4):
            if sl * m <= max_size:
                pool.append(SlidingWindow(T, sl * m, sl))
        if sl <= max_size:
            pool.append(TumblingWindow(T, sl))
    return pool


def _churn_rows(by_slot: dict, slot: int):
    """One slot's emissions as exact-comparable tuples (f32 value bits)."""
    return [(s, e, c, tuple(np.float32(v).tobytes() for v in vals))
            for (s, e, c, vals) in by_slot.get(slot, ())]


def run_query_churn_cell(cfg: BenchmarkConfig, window_spec: str,
                         agg_name: str,
                         obs: Optional[_obs.Observability] = None
                         ) -> BenchResult:
    """Query-churn cell (ISSUE 6): a seeded schedule registers/cancels
    >= ``churnOps`` windows MID-STREAM against a
    :class:`scotty_tpu.serving.QueryService`, recording the jit-trace
    count after warmup (the zero-steady-state-retrace acceptance), the
    throughput delta vs the static-set equivalent pipeline, and — unless
    ``churnOracle`` is off — a bit-exact comparison of every active
    query's emissions against an always-active superset oracle replaying
    the same schedule (per-trigger-row results are independent and the
    engine state is query-set independent, so equality must be exact)."""
    import jax

    from ..engine import EngineConfig
    from ..engine.pipeline import AlignedStreamPipeline
    from ..serving import QueryAdmission, QueryService, replay_schedule
    from ..serving.cache import pad_pow2

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    P = cfg.watermark_period_ms
    g = AlignedStreamPipeline.slice_grid(windows, P)
    tp = _round_throughput(cfg.throughput, g)
    max_size = max([4 * P] + [int(w.size) for w in windows])
    pool = _churn_pool(windows, g, P, max_size)
    lanes = max(P // int(getattr(w, "slide", w.size)) + 2
                for w in pool + windows)
    econf = EngineConfig(capacity=cfg.capacity, annex_capacity=8,
                         min_trigger_pad=32,
                         overflow_policy=cfg.overflow_policy)

    n_timed = max(4, cfg.runtime_s)
    schedule, n_ops, n_regs = _churn_schedule(cfg, pool, n_timed,
                                              len(windows))
    warmup = max_size // P + 2

    def build_service(max_queries: int, min_slots: int) -> QueryService:
        return QueryService(
            [make_aggregation(agg_name)], slice_grid=g,
            max_window_size=max_size, throughput=tp, wm_period_ms=P,
            max_lateness=cfg.max_lateness, seed=cfg.seed, config=econf,
            admission=QueryAdmission(max_queries=max_queries),
            windows=windows, min_slots=min_slots,
            min_trigger_lanes=pad_pow2(lanes, 8))

    svc = build_service(cfg.churn_max_active,
                        pad_pow2(cfg.churn_max_active, 8))
    svc.run(warmup, collect=False)
    svc.sync()
    svc.mark_warm()
    if obs is not None:
        svc.set_observability(obs)
        obs.registry.reset_clock()

    handles: dict = {}
    slot_maps = []                  # per timed interval: live reg -> slot
    outs = []
    t0 = time.perf_counter()
    for cmds in schedule:
        replay_schedule(svc, cmds, handles)
        slot_maps.append({rid: h.slot for rid, h in handles.items()})
        outs.extend(svc.run(1, collect=True))
    svc.sync()
    wall = time.perf_counter() - t0
    svc.check_overflow()
    retraces = svc.retraces_since_warm
    n_tuples = n_timed * svc.pipeline.tuples_per_interval
    if obs is not None:
        obs.registry.stop_clock()
        svc.set_observability(None)

    # drained emit-latency samples on the live churned query set
    lats = []
    t_lat = time.perf_counter()
    for _ in range(LATENCY_SAMPLES_MAX):
        svc.sync()
        t1 = time.perf_counter()
        out = svc.run(1)[0]
        jax.device_get((out[2], out[3]))
        lats.append((time.perf_counter() - t1) * 1e3)
        if (len(lats) >= LATENCY_SAMPLES_MIN
                and time.perf_counter() - t_lat > LATENCY_BUDGET_S):
            break
    svc.check_overflow()
    emitted = 0
    by_slot_per_interval = [svc.results_by_slot(o) for o in outs]
    for bs in by_slot_per_interval:
        emitted += sum(len(rows) for rows in bs.values())

    # static-set equivalent: the same engine geometry with the seed
    # window set baked in at build time — the <= 5% penalty comparator
    ps = AlignedStreamPipeline(
        windows, [make_aggregation(agg_name)], config=econf, throughput=tp,
        wm_period_ms=P, max_lateness=cfg.max_lateness, seed=cfg.seed)
    ps.run(warmup, collect=False)
    ps.sync()
    t0 = time.perf_counter()
    ps.run(n_timed, collect=False)
    ps.sync()
    static_wall = time.perf_counter() - t0
    ps.check_overflow()
    static_tps = n_timed * ps.tuples_per_interval / static_wall

    oracle_match = None
    if cfg.churn_oracle:
        # superset oracle: every scheduled registration active from the
        # start; the serving run's results for a query active at interval
        # i must BIT-MATCH the oracle's rows for that query at interval i
        oracle = build_service(n_regs + len(windows) + 1,
                               pad_pow2(n_regs + len(windows), 8))
        ohandles: dict = {}
        for cmds in schedule:
            for cmd in cmds:
                if cmd[0] == "register":
                    _, rid, w, tenant = cmd
                    ohandles[rid] = oracle.register(w, tenant=tenant)
        oracle.run(warmup, collect=False)
        oracle.sync()
        oouts = oracle.run(n_timed, collect=True)
        oracle.sync()
        oracle.check_overflow()
        oracle_match = True
        for i, (bs, omap) in enumerate(zip(by_slot_per_interval,
                                           slot_maps)):
            obs_rows = oracle.results_by_slot(oouts[i])
            for rid, slot in omap.items():
                if _churn_rows(bs, slot) != _churn_rows(
                        obs_rows, ohandles[rid].slot):
                    oracle_match = False
                    break
            if not oracle_match:
                break

    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=float(np.percentile(lats, 99)) if lats else 0.0,
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    res.n_lat_samples = len(lats)
    res.p50_emit_ms = float(np.percentile(lats, 50)) if lats else 0.0
    res.emit_ms_device = wall / n_timed * 1e3
    stats = svc.stats()
    res.serving_retraces_after_warmup = int(retraces)
    res.serving_registered = int(stats.get("serving_registered", 0))
    res.serving_cancelled = int(stats.get("serving_cancelled", 0))
    res.serving_rejected = int(stats.get("serving_rejected", 0))
    res.serving_cache_hits = int(stats.get("serving_cache_hits", 0))
    res.churn_ops = int(n_ops)
    res.throughput_static = static_tps
    res.throughput_delta_pct = (1.0 - res.tuples_per_sec
                                / max(static_tps, 1e-9)) * 100.0
    if oracle_match is not None:
        res.oracle_match = bool(oracle_match)
    # the full schedule, compactly: [interval, "r", reg_id, str(window),
    # tenant] / [interval, "c", reg_id] — with the seed this is the
    # complete reproduction recipe
    res.churn_schedule = [
        ([i, "r", cmd[1], str(cmd[2]), cmd[3]] if cmd[0] == "register"
         else [i, "c", cmd[1]])
        for i, cmds in enumerate(schedule) for cmd in cmds]
    res.churn_seed = int(cfg.seed)
    finalize_observability(res, obs, lats, emitted, n_tuples=n_tuples)
    return res


def run_query_churn_mesh_cell(cfg: BenchmarkConfig, window_spec: str,
                              agg_name: str,
                              obs: Optional[_obs.Observability] = None
                              ) -> BenchResult:
    """Mesh-serving churn cell (ISSUE 13): the seeded churn schedule
    registers/cancels >= ``churnOps`` windows MID-STREAM against a
    :class:`scotty_tpu.mesh_serving.MeshQueryService` — ``nKeys``
    logical keys over ``nShards`` device shards — while
    ``meshReshardSchedule`` drives live checkpoint-boundary reshards
    under a Supervisor with an exactly-once TransactionalSink tagging
    every per-query global emission ``(epoch, seq)``.

    Recorded contract:

    * ``serving_retraces_after_warmup`` — trace-counter-reconciled
      steady-state retraces (the zero-retrace acceptance), with the
      compiles a reshard's genuinely-new mesh forces itemized apart as
      ``reshard_retraces``;
    * ``reshard_timeline`` — each live reshard's from/to/interval/wall;
    * ``oracle_match`` — unless ``churnOracle`` is off, every live
      query's emissions (psum-folded global AND sampled per-key rows)
      bit-compared against an always-active superset service replaying
      the SAME reshard schedule (equal shard-count phases make the psum
      reduction trees identical, so equality is exact);
    * ``delivery_tags_unique`` — no ``(epoch, seq)`` tag delivered
      twice across the whole churned, resharded run;
    * aggregate throughput over the churn loop, reshard wall time
      excluded and reported separately (``platform``/``host_cores``
      recorded — the >=6x mesh scaling number stays a TPU-box cert per
      the PR 5/7/10 discipline).
    """
    import os as _os
    import tempfile

    import jax

    from ..delivery import EXACTLY_ONCE, TransactionalSink
    from ..engine import EngineConfig
    from ..engine.pipeline import AlignedStreamPipeline
    from ..mesh_serving import MeshQueryService
    from ..resilience import ManualClock, Supervisor
    from ..serving import QueryAdmission, replay_schedule
    from ..serving.cache import pad_pow2

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    P = cfg.watermark_period_ms
    g = AlignedStreamPipeline.slice_grid(windows, P)
    max_size = max([4 * P] + [int(w.size) for w in windows])
    pool = _churn_pool(windows, g, P, max_size)
    lanes = max(P // int(getattr(w, "slide", w.size)) + 2
                for w in pool + windows)
    n_shards = cfg.n_shards or len(jax.devices())
    K = int(cfg.n_keys)
    econf = EngineConfig(capacity=cfg.capacity, annex_capacity=8,
                         min_trigger_pad=32)
    n_timed = max(4, cfg.runtime_s)
    schedule, n_ops, n_regs = _churn_schedule(cfg, pool, n_timed,
                                              len(windows))
    warmup = max_size // P + 2
    reshard_at = {int(i): int(m) for i, m in cfg.mesh_reshard_schedule}
    for m in reshard_at.values():
        if K % m:
            raise ValueError(
                f"meshReshardSchedule: nKeys {K} is not a multiple of "
                f"shard count {m}")

    def build(max_queries: int, min_slots: int) -> MeshQueryService:
        return MeshQueryService(
            [make_aggregation(agg_name)], slice_grid=g,
            max_window_size=max_size, n_keys=K, n_shards=n_shards,
            throughput=cfg.throughput, wm_period_ms=P,
            max_lateness=cfg.max_lateness, seed=cfg.seed, config=econf,
            admission=QueryAdmission(max_queries=max_queries),
            windows=windows, min_slots=min_slots,
            min_trigger_lanes=pad_pow2(lanes, 4))

    sample_keys = sorted({0, K // 3, K - 1})

    svc = build(cfg.churn_max_active,
                pad_pow2(cfg.churn_max_active, 8))
    svc.run(warmup, collect=False)
    svc.sync()
    svc.mark_warm()
    if obs is not None:
        svc.set_observability(obs)
        obs.registry.reset_clock()
        # served-cell sensor plane (ISSUE 18 satellite): the workload_*
        # fingerprint gauges and the drift counter that the /healthz
        # workload_drift check reads ride the served mesh cell exactly
        # like the single-device connector loops do — audit cadence is
        # wall-time-paced, so keep it short against ms-scale intervals
        from ..obs.drift import DriftDetector
        from ..obs.workload import WorkloadMonitor
        monitor = WorkloadMonitor(audit_interval_s=0.05)
        monitor.attach_detector(DriftDetector())
        obs.attach_workload(monitor)
    # TemporaryDirectory, not mkdtemp: at 64 K keys each committed
    # bundle is 100s of MB, and the live + oracle reshards commit
    # several — cleanup() runs on the success path below and the
    # finalizer reclaims the error path, so repeated bench runs cannot
    # fill /tmp with checkpoint bundles
    tmpdir = tempfile.TemporaryDirectory(prefix="mesh_churn_ck_")
    tmp = tmpdir.name
    sup = Supervisor(_os.path.join(tmp, "ck"), clock=ManualClock(),
                     seed=cfg.seed, obs=obs)
    tags: list = []
    sink = TransactionalSink(mode=EXACTLY_ONCE, obs=obs,
                             deliver=lambda it, e, s: tags.append((e, s)))
    sup.sink = sink

    handles: dict = {}
    per_interval = []          # (slot_map, global rows, sampled key rows)
    reshard_wall_s = 0.0
    t0 = time.perf_counter()
    for i, cmds in enumerate(schedule):
        if i in reshard_at and svc.n_shards != reshard_at[i]:
            row = svc.reshard(reshard_at[i], sup, pos=svc.interval)
            reshard_wall_s += row["wall_ms"] / 1e3
        replay_schedule(svc, cmds, handles)
        out = svc.run(1)[0]
        g_rows = svc.global_rows_by_slot(out)
        k_rows = {k: svc.key_rows_by_slot(out, k) for k in sample_keys}
        slot_map = {rid: h.slot for rid, h in handles.items()}
        per_interval.append((slot_map, g_rows, k_rows))
        for rid in sorted(slot_map):
            sink.emit((i, rid,
                       tuple(map(tuple, g_rows.get(slot_map[rid], ())))))
        if obs is not None:
            # the served loop's drain point: monitor sampled first,
            # then the flight ring — same contract as run_supervised_mesh
            obs.flight_sync(watermark=float((i + 1) * P))
    svc.sync()
    wall = time.perf_counter() - t0 - reshard_wall_s
    svc.check_overflow()
    retraces = svc.retraces_since_warm
    n_tuples = n_timed * svc.pipeline.tuples_per_interval
    health_verdict = None
    if obs is not None:
        # probe the served health verdict while the registry is still
        # live — the same verdict /healthz would have served
        from ..obs.server import HealthPolicy
        health_verdict = HealthPolicy().verdict(obs)
        obs.registry.stop_clock()
        svc.set_observability(None)

    # drained emit-latency samples on the live churned query set
    lats = []
    t_lat = time.perf_counter()
    for _ in range(LATENCY_SAMPLES_MAX):
        svc.sync()
        t1 = time.perf_counter()
        out = svc.run(1)[0]
        svc.pipeline.lowered_global(out)
        lats.append((time.perf_counter() - t1) * 1e3)
        if (len(lats) >= LATENCY_SAMPLES_MIN
                and time.perf_counter() - t_lat > LATENCY_BUDGET_S):
            break
    svc.check_overflow()
    emitted = sum(sum(len(rows) for rows in gr.values())
                  for (_sm, gr, _kr) in per_interval)

    oracle_match = None
    if cfg.churn_oracle:
        # superset oracle: every scheduled registration active from the
        # start, replaying the SAME reshard schedule (equal shard-count
        # phases => identical psum trees => exact equality demanded)
        oracle = build(n_regs + len(windows) + 1,
                       pad_pow2(n_regs + len(windows), 8))
        ohandles: dict = {}
        for cmds in schedule:
            for cmd in cmds:
                if cmd[0] == "register":
                    _, rid, w, tenant = cmd
                    ohandles[rid] = oracle.register(w, tenant=tenant)
        oracle.run(warmup, collect=False)
        oracle.sync()
        osup = Supervisor(_os.path.join(tmp, "ock"), clock=ManualClock(),
                          seed=cfg.seed)
        oracle_match = True
        for i in range(n_timed):
            if i in reshard_at and oracle.n_shards != reshard_at[i]:
                oracle.reshard(reshard_at[i], osup, pos=oracle.interval)
            out = oracle.run(1)[0]
            og = oracle.global_rows_by_slot(out)
            okr = {k: oracle.key_rows_by_slot(out, k)
                   for k in sample_keys}
            slot_map, g_rows, k_rows = per_interval[i]
            for rid, slot in slot_map.items():
                oslot = ohandles[rid].slot
                if g_rows.get(slot) != og.get(oslot):
                    oracle_match = False
                    break
                for k in sample_keys:
                    if k_rows[k].get(slot) != okr[k].get(oslot):
                        oracle_match = False
                        break
                if not oracle_match:
                    break
            if not oracle_match:
                break
        oracle.check_overflow()

    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=float(np.percentile(lats, 99)) if lats else 0.0,
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    res.n_lat_samples = len(lats)
    res.p50_emit_ms = float(np.percentile(lats, 50)) if lats else 0.0
    res.emit_ms_device = wall / n_timed * 1e3
    stats = svc.stats()
    res.serving_retraces_after_warmup = int(retraces)
    res.reshard_retraces = int(stats["reshard_retraces"])
    res.reshard_timeline = list(svc.reshard_timeline)
    res.reshard_wall_s = round(reshard_wall_s, 3)
    res.serving_registered = int(stats.get("serving_registered", 0))
    res.serving_cancelled = int(stats.get("serving_cancelled", 0))
    res.serving_rejected = int(stats.get("serving_rejected", 0))
    res.serving_cache_hits = int(stats.get("serving_cache_hits", 0))
    res.churn_ops = int(n_ops)
    res.n_keys = K
    res.n_shards = int(n_shards)
    res.platform = jax.devices()[0].platform
    res.host_cores = _os.cpu_count()
    res.delivery_mode = EXACTLY_ONCE
    res.delivery_tags_unique = bool(len(tags) == len(set(tags)))
    res.delivery_snapshot = sink.snapshot()
    if oracle_match is not None:
        res.oracle_match = bool(oracle_match)
    res.churn_schedule = [
        ([i, "r", cmd[1], str(cmd[2]), cmd[3]] if cmd[0] == "register"
         else [i, "c", cmd[1]])
        for i, cmds in enumerate(schedule) for cmd in cmds]
    res.churn_seed = int(cfg.seed)
    if health_verdict is not None:
        res.served_health_ok = bool(health_verdict.get("healthy", False))
        res.served_drift_events = int(
            health_verdict.get("checks", {})
            .get("workload_drift", {}).get("drift_events", 0))
    finalize_observability(res, obs, lats, emitted, n_tuples=n_tuples)
    tmpdir.cleanup()
    return res


def run_shaped_ooo_cell(cfg: BenchmarkConfig, window_spec: str,
                        agg_name: str,
                        obs: Optional[_obs.Observability] = None
                        ) -> BenchResult:
    """Shaped out-of-order cell (ISSUE 5): an ADVERSARIALLY DISORDERED
    device-resident stream — every batch fully shuffled, with a bounded
    back-reach into the previous batch's event range — taken through
    ``StreamShaper.shape_device_batch`` end to end: jitted sort-and-split,
    the in-order majority through the scatter-free dense/in-order ingest,
    the late residue through the small ``ingest_device_late`` dispatch,
    plus the normal watermark cadence. This is the general-traffic
    counterpart of the shaped ``TpuEngine`` cells: the stream is NOT
    pipeline-generated, NOT sorted, and NOT aligned — the number to hold
    against ``micro.json: ingest_scatter`` (the same stream unshaped)."""
    import jax
    import jax.numpy as jnp

    from ..autotune import EngineGeometry
    from ..engine import EngineConfig, TpuWindowOperator
    from ..shaper import StreamShaper

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    B = cfg.batch_size
    n_batches = int(max(4, cfg.throughput * cfg.runtime_s // B))
    span = max(1.0, cfg.runtime_s * 1000 / n_batches)
    back = cfg.shaper_back_ms or max(1, min(cfg.max_lateness,
                                            int(span) // 8))

    # pregenerate a cycled pool of shuffled base batches ON DEVICE (the
    # stream's origin is device memory — generation cost is the load
    # generator's, excluded like every other cell); per-batch offsets are
    # added lazily on device, which is part of the source's cost model
    rng = np.random.default_rng(cfg.seed)
    P = min(n_batches, 16)
    pool = []
    for _ in range(P):
        ts = rng.integers(0, int(span) + back, size=B).astype(np.int64)
        vals = (rng.random(B) * 10_000).astype(np.float32)
        pool.append((jax.device_put(vals), jax.device_put(ts)))

    # default residue lanes at B/4: the adversarial stream's expected
    # late fraction is back/(span+back) ≈ 11%, so the static late block
    # runs near half-full — exercised every batch, never overflowing
    late_cap = cfg.shaper_late_capacity or max(64, B // 4)
    # refuse mis-sized geometries UP FRONT: at tiny spans (high
    # throughput / small batches) the integer span collapses and the
    # late fraction back/(int(span)+back) can exceed the residue lanes —
    # the run would only die in ShaperOverflow at the final drain
    exp_late = B * back / (int(span) + back)
    if exp_late * 1.5 > late_cap:
        raise ValueError(
            f"ShapedOOO geometry: expected late fraction "
            f"{back}/({int(span)}+{back}) of batch_size {B} ≈ "
            f"{exp_late:.0f} tuples ≥ late_capacity {late_cap} — lower "
            "throughput (longer span per batch), shrink shaperBackMs, or "
            "raise shaperLateCapacity")
    # one geometry derives both module configs (geometry-discipline):
    # the coupled engine/shaper knobs move as a single value
    geom = EngineGeometry(capacity=cfg.capacity, batch_size=B,
                          late_capacity=late_cap)
    op = TpuWindowOperator(config=geom.engine_config(
        EngineConfig(overflow_policy=cfg.overflow_policy)))
    for w in windows:
        op.add_window_assigner(w)
    op.add_aggregation(make_aggregation(agg_name))
    op.set_max_lateness(max(cfg.max_lateness, back + int(span)))
    shaper = StreamShaper(op, geom.shaper_config())

    def feed(i: int) -> int:
        # batch i covers [i*span - back, i*span + span): shuffled within,
        # reaching `back` ms into batch i-1's range
        off = int((i + 1) * span)
        v_dev, t_dev = pool[i % P]
        lo = off - back
        shaper.shape_device_batch(v_dev, t_dev + jnp.int64(lo), lo,
                                  off + int(span))
        return off + int(span)

    # warmup: compiles sort-split + ingest + watermark kernels
    hi = feed(0)
    hi = feed(1)
    warm_wm = hi + 1
    op.process_watermark_async(warm_wm)
    jax.device_get(op._state.n_slices)
    if obs is not None:
        op.set_observability(obs)
        obs.registry.reset_clock()

    next_wm = (warm_wm // cfg.watermark_period_ms + 1) \
        * cfg.watermark_period_ms
    pending = []
    t0 = time.perf_counter()
    for i in range(2, n_batches):
        hi = feed(i)
        while hi - back - int(span) >= next_wm:
            # watermark only once the back-reach can no longer repair it
            out = op.process_watermark_async(next_wm)
            if out[3] is not None:
                pending.append((out[0].shape[0], out[3]))
            next_wm += cfg.watermark_period_ms
    out = op.process_watermark_async(next_wm)
    if out[3] is not None:
        pending.append((out[0].shape[0], out[3]))
    emitted = 0
    fetched = jax.device_get([c for _, c in pending])
    for (T, _), cnt in zip(pending, fetched):
        emitted += int((cnt[:T] > 0).sum())
    op.check_overflow()                 # includes shaper.check()
    wall = time.perf_counter() - t0
    n_tuples = (n_batches - 2) * B
    if obs is not None:
        obs.registry.stop_clock()
        op.set_observability(None)

    # drained emit-latency samples: one shaped batch + watermark each,
    # time-shifted past the stream end (the shaped delivery path)
    lats = []
    cursor = int(next_wm + 2 * (int(span) + back))
    v0, t0_dev = pool[0]
    t_lat = time.perf_counter()
    for _ in range(LATENCY_SAMPLES_MAX):
        jax.device_get(op._state.n_slices)
        t1 = time.perf_counter()
        shaper.shape_device_batch(v0, t0_dev + jnp.int64(cursor), cursor,
                                  cursor + int(span) + back)
        out = op.process_watermark_async(cursor + int(span) + back + 1)
        if out[3] is not None:
            jax.device_get((out[3], out[4]))
        else:
            jax.device_get(op._state.n_slices)
        lats.append((time.perf_counter() - t1) * 1e3)
        cursor += 2 * (int(span) + back) + cfg.watermark_period_ms
        if (len(lats) >= LATENCY_SAMPLES_MIN
                and time.perf_counter() - t_lat > LATENCY_BUDGET_S):
            break
    op.check_overflow()

    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=float(np.percentile(lats, 99)) if lats else 0.0,
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    res.n_lat_samples = len(lats)
    res.p50_emit_ms = float(np.percentile(lats, 50)) if lats else 0.0
    res.shaper_back_ms = back
    stats = shaper.device_stats()
    res.shaper_late_routed = stats.get("late_routed", 0)
    res.shaper_reordered = stats.get("reordered", 0)
    finalize_observability(res, obs, lats, emitted, n_tuples=n_tuples)
    return res


def _aligned_inprogram_arm(cfg: BenchmarkConfig, windows, agg_name: str,
                           legacy: bool):
    """In-program comparator for the ring-fed headline (ISSUE 11 /
    ADVICE r5 finding 1): the fused AlignedStreamPipeline at the cell's
    geometry — ``(tps, gen_share)`` where ``gen_share`` is the fraction
    of the steady-state interval the STREAM GENERATOR alone accounts
    for, measured by timing the step's own generator closure
    (``_gen_active`` — the legacy arm times the pinned r4 draws) as a
    separate jit over the same rows/chunks."""
    import jax
    import jax.numpy as jnp

    from ..engine import EngineConfig
    from ..engine.pipeline import AlignedStreamPipeline

    tp = _round_throughput(
        cfg.throughput,
        AlignedStreamPipeline.slice_grid(windows, cfg.watermark_period_ms))
    p = AlignedStreamPipeline(
        windows, [make_aggregation(agg_name)],
        config=EngineConfig(capacity=cfg.capacity, annex_capacity=8,
                            min_trigger_pad=32),
        throughput=tp, wm_period_ms=cfg.watermark_period_ms,
        max_lateness=cfg.max_lateness, seed=cfg.seed, gc_every=32,
        legacy_generator=legacy)
    p.reset()
    p.run(3, collect=False)
    p.sync()
    timed = 5
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        p.run(timed, collect=False)
        p.sync()
        best = min(best, (time.perf_counter() - t0) / timed)
    p.check_overflow()

    S, d, R = p.S, p.rows_per_chunk, p.R
    gen = p._gen_active

    @jax.jit
    def probe(key):
        def body(acc, c):
            out = gen(key, c * d + jnp.arange(d, dtype=jnp.int64))
            vals = out[0] if isinstance(out, tuple) else out
            a = acc + jnp.sum(vals)
            if isinstance(out, tuple):      # legacy: offsets are live too
                a = a + jnp.sum(out[1]).astype(jnp.float32)
            return a, None
        acc, _ = jax.lax.scan(body, jnp.float32(0),
                              jnp.arange(S // d, dtype=jnp.int64))
        return acc

    key = p._interval_key(0)
    jax.device_get(probe(key))              # compile
    best_gen = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for r in range(timed):
            h = probe(jax.random.fold_in(key, r))
        jax.device_get(h)
        best_gen = min(best_gen, (time.perf_counter() - t0) / timed)
    return (p.tuples_per_interval / best,
            min(1.0, best_gen / best))


def run_ring_fed_cell(cfg: BenchmarkConfig, window_spec: str,
                      agg_name: str,
                      obs: Optional[_obs.Observability] = None
                      ) -> BenchResult:
    """Ring-fed headline cell (ISSUE 11, closes ADVICE r5 finding 1):
    the headline window class fed from the PR 7 ingest ring — a
    HOST-resident pregenerated in-order stream through
    ``BatchAccumulator.offer_block`` → ``IngestRing`` →
    ``DeviceRingFeeder`` prefetch → the batch operator — instead of the
    in-program generator, so the recorded number contains ZERO
    generator work. Comparators ride the row: the in-program fused
    pipeline at the same geometry (``inprogram_tps``), the pinned
    legacy-anchor generator arm (``legacy_anchor_tps``, ADVICE r5's
    workload-identical cross-round anchor), and the measured
    ``generator_share`` of each in-program arm's steady-state interval
    — quantifying exactly how much of the headline the generator is."""
    import jax

    from ..autotune import EngineGeometry
    from ..engine import EngineConfig, TpuWindowOperator
    from ..ingest import LineRateFeed

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    B = cfg.batch_size
    n_chunks = int(max(6, cfg.throughput * cfg.runtime_s // B))
    span = max(1.0, cfg.runtime_s * 1000 / n_chunks)
    # event time starts past the widest window span so triggers fire
    # from the first watermarks (the in-program pipelines' prefill
    # equivalent); pooled chunks cycle so pregeneration memory stays
    # bounded at any runtime
    off0 = max(w.clear_delay() for w in windows)
    rng = np.random.default_rng(cfg.seed)
    n_pools = min(n_chunks, 12)
    pools = []
    for _ in range(n_pools):
        ts = np.sort(rng.integers(0, max(1, int(span)),
                                  size=B)).astype(np.int64)
        vals = (rng.random(B) * 10_000).astype(np.float32)
        pools.append((vals, ts))

    def chunk(i):
        vals, ts = pools[i % n_pools]
        lo = off0 + int(i * span)
        return vals, ts + np.int64(lo), off0 + int((i + 1) * span)

    # one geometry derives the engine + ring configs (geometry-
    # discipline): the coupled retunable knobs move as a single value
    geom = EngineGeometry(capacity=cfg.capacity, batch_size=B,
                          ring_depth=cfg.ring_depth or 8,
                          ring_block=cfg.ring_block_size or B)
    op = TpuWindowOperator(config=geom.engine_config(
        EngineConfig(overflow_policy=cfg.overflow_policy)))
    for w in windows:
        op.add_window_assigner(w)
    op.add_aggregation(make_aggregation(agg_name))
    op.set_max_lateness(cfg.max_lateness)
    feed = LineRateFeed(op, ring=geom.ring_config())

    warm_hi = 0
    for i in (0, 1):
        v, t, warm_hi = chunk(i)
        feed.offer_block(v, t)
    op.process_watermark_async(warm_hi + 1)
    jax.device_get(op._state.n_slices)
    if obs is not None:
        op.set_observability(obs)
        obs.registry.reset_clock()
    next_wm = (warm_hi // cfg.watermark_period_ms + 2) \
        * cfg.watermark_period_ms
    pending = []
    t0 = time.perf_counter()
    for i in range(2, n_chunks):
        v, t, hi = chunk(i)
        feed.offer_block(v, t)
        while hi >= next_wm:
            out = op.process_watermark_async(next_wm)
            if out[3] is not None:
                pending.append((out[0].shape[0], out[3]))
            next_wm += cfg.watermark_period_ms
    feed.drain()
    out = op.process_watermark_async(next_wm)
    if out[3] is not None:
        pending.append((out[0].shape[0], out[3]))
    emitted = 0
    fetched = jax.device_get([c for _, c in pending])
    for (T, _), cnt in zip(pending, fetched):
        emitted += int((cnt[:T] > 0).sum())
    op.check_overflow()
    wall = time.perf_counter() - t0
    n_tuples = (n_chunks - 2) * B
    if obs is not None:
        obs.registry.stop_clock()
        op.set_observability(None)

    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=0.0,
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    res.emit_ms_device = wall / max(1, len(pending)) * 1e3
    snap = feed.snapshot()
    res.ring_full_events = int(snap["full_events"])
    res.ring_shed = int(snap["shed"])
    res.ring_blocks = int(snap["blocks"])

    # -- in-program + pinned legacy-anchor comparator arms ----------------
    res.inprogram_tps, res.generator_share = _aligned_inprogram_arm(
        cfg, windows, agg_name, legacy=False)
    try:
        (res.legacy_anchor_tps,
         res.generator_share_legacy) = _aligned_inprogram_arm(
            cfg, windows, agg_name, legacy=True)
    except NotImplementedError as e:
        res.legacy_anchor_note = f"legacy arm unavailable: {e}"
    res.ring_fed_vs_inprogram = res.tuples_per_sec / max(
        res.inprogram_tps, 1e-9)
    res.platform = jax.devices()[0].platform
    finalize_observability(res, obs, [], emitted, n_tuples=n_tuples)
    return res


def run_ring_fed_mesh_cell(cfg: BenchmarkConfig, window_spec: str,
                           agg_name: str,
                           obs: Optional[_obs.Observability] = None
                           ) -> BenchResult:
    """Ring-fed MESH cell (ISSUE 11): a HOST-resident keyed external
    stream staged through the keyed PR 7 ingest ring
    (``IngestRing(keyed=True)`` → ``RingIngestor`` →
    ``BlockSinkFeeder``) into the mesh-sharded keyed engine by LOGICAL
    key — no in-program generator anywhere in the recorded number.
    Comparators: the in-program ``MeshKeyedPipeline`` at the same
    keys/shards geometry (``inprogram_tps``) and the pinned
    legacy-anchor arm (``legacy_anchor_tps``) for cross-round context;
    ``platform``/``host_cores`` recorded — mesh scaling floors stay
    TPU-box certifications."""
    import os as _os

    import jax

    from ..engine import EngineConfig
    from ..ingest.feeder import BlockSinkFeeder, RingIngestor
    from ..ingest.ring import IngestRing, RingConfig
    from ..mesh import MeshKeyedEngine, MeshKeyedPipeline

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    K = max(4, cfg.n_keys)
    n_shards = cfg.n_shards or len(jax.devices())
    B = cfg.ring_block_size or (1 << 16)
    Bk = max(64, 1 << int(np.ceil(np.log2(max(2, 4 * B // K)))))
    eng = MeshKeyedEngine(
        n_keys=K, n_shards=n_shards,
        config=EngineConfig(capacity=max(128, min(cfg.capacity, 512)),
                            batch_size=Bk, annex_capacity=8,
                            min_trigger_pad=32))
    for w in windows:
        eng.add_window_assigner(w)
    eng.add_aggregation(make_aggregation(agg_name))
    eng.set_max_lateness(cfg.max_lateness)

    ring = IngestRing(cfg.ring_depth or 8, B, keyed=True,
                      value_dtype=np.float32)
    sink = BlockSinkFeeder(
        ring, lambda keys, vals, ts: eng.process_keyed_elements(
            keys.astype(np.int64), vals, ts))
    ingestor = RingIngestor(ring, sink, obs=obs)

    n_chunks = int(max(6, cfg.throughput * cfg.runtime_s // B))
    span = max(1.0, cfg.runtime_s * 1000 / n_chunks)
    off0 = max(w.clear_delay() for w in windows)
    rng = np.random.default_rng(cfg.seed)
    n_pools = min(n_chunks, 12)
    pools = []
    for _ in range(n_pools):
        ts = np.sort(rng.integers(0, max(1, int(span)),
                                  size=B)).astype(np.int64)
        keys = rng.integers(0, K, size=B)
        vals = (rng.random(B) * 10_000).astype(np.float32)
        pools.append((keys, vals, ts))

    def offer(i):
        keys, vals, ts = pools[i % n_pools]
        lo = off0 + int(i * span)
        ingestor.offer_block(vals, ts + np.int64(lo), keys)
        ingestor.poll()
        return off0 + int((i + 1) * span)

    hi = offer(0)
    hi = offer(1)
    eng.process_watermark_async(hi + 1)
    jax.device_get(jax.tree.leaves(eng._state)[0])
    if obs is not None:
        obs.registry.reset_clock()
    next_wm = (hi // cfg.watermark_period_ms + 2) * cfg.watermark_period_ms
    pending = []
    t0 = time.perf_counter()
    for i in range(2, n_chunks):
        hi = offer(i)
        while hi >= next_wm:
            pending.append(eng.process_watermark_async(next_wm))
            next_wm += cfg.watermark_period_ms
    ingestor.drain()
    pending.append(eng.process_watermark_async(next_wm))
    emitted = 0
    for out in pending:
        ws, we, cnt, lowered = eng.lower_results(*out)
        emitted += int((cnt > 0).sum())
    eng.check_overflow()
    wall = time.perf_counter() - t0
    n_tuples = (n_chunks - 2) * B
    if obs is not None:
        obs.registry.stop_clock()

    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=0.0,
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    res.n_keys = int(K)
    res.n_shards = int(n_shards)
    snap = ingestor.snapshot()
    res.ring_full_events = int(snap["full_events"])
    res.ring_shed = int(snap["shed"])
    res.ring_blocks = int(snap["blocks"])

    # in-program mesh comparator at the same geometry
    p = MeshKeyedPipeline(
        windows, [make_aggregation(agg_name)], n_keys=K,
        n_shards=n_shards,
        config=EngineConfig(capacity=max(128, min(cfg.capacity, 512)),
                            annex_capacity=8, min_trigger_pad=32),
        throughput=cfg.throughput, wm_period_ms=cfg.watermark_period_ms,
        max_lateness=cfg.max_lateness, seed=cfg.seed)
    p.reset()
    p.run(2, collect=False)
    p.sync()
    best = float("inf")
    for _ in range(3):
        t1 = time.perf_counter()
        p.run(3, collect=False)
        p.sync()
        best = min(best, (time.perf_counter() - t1) / 3)
    p.check_overflow()
    res.inprogram_tps = p.tuples_per_interval / best
    res.ring_fed_vs_inprogram = res.tuples_per_sec / max(
        res.inprogram_tps, 1e-9)
    try:
        res.legacy_anchor_tps, res.generator_share_legacy = \
            _aligned_inprogram_arm(cfg, windows, agg_name, legacy=True)
    except NotImplementedError as e:
        res.legacy_anchor_note = f"legacy arm unavailable: {e}"
    res.platform = jax.devices()[0].platform
    res.host_cores = _os.cpu_count()
    finalize_observability(res, obs, [], emitted, n_tuples=n_tuples)
    return res


def run_count_fused_cell(cfg: BenchmarkConfig, window_spec: str,
                         agg_name: str,
                         obs: Optional[_obs.Observability] = None
                         ) -> BenchResult:
    """Count-measure fused cell with an embedded oracle arm (ISSUE 11):
    the throughput number is the standard fused-pipeline discipline at
    the configured ``outOfOrderPct`` (``tuples_per_sec_inorder`` rides
    alongside from an in-order twin), and a SMALL replica of the same
    window/lateness geometry is differentially replayed — in-order vs
    the reference simulator, out-of-order vs the engine's record-merge
    rank semantics — recording ``oracle_match``/``oracle_windows``.
    The >= 50 M t/s ROADMAP floor stays a TPU-box certification; the
    cell records ``platform`` alongside."""
    import jax

    from ..engine import EngineConfig, TpuWindowOperator
    from ..engine.count_pipeline import CountStreamPipeline
    from .. import SlicingWindowOperator

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    econf = EngineConfig(capacity=cfg.capacity, annex_capacity=8,
                         min_trigger_pad=32,
                         overflow_policy=cfg.overflow_policy)

    def mk(throughput, ooo, lateness):
        return CountStreamPipeline(
            windows, [make_aggregation(agg_name)], config=econf,
            throughput=throughput, wm_period_ms=cfg.watermark_period_ms,
            max_lateness=lateness, seed=cfg.seed, out_of_order_pct=ooo,
            collect_device_metrics=obs is not None)

    p = mk(cfg.throughput, cfg.out_of_order_pct, cfg.max_lateness)
    res = _run_pipeline_cell(p, cfg, window_spec, agg_name,
                             "count-fused", obs=obs)

    # in-order comparator twin (best of 3 short segments)
    p0 = mk(cfg.throughput, 0.0, cfg.max_lateness)
    p0.reset()
    p0.run(2, collect=False)
    p0.sync()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        p0.run(3, collect=False)
        p0.sync()
        best = min(best, (time.perf_counter() - t0) / 3)
    p0.check_overflow()
    res.tuples_per_sec_inorder = p0.tuples_per_interval / best

    # -- oracle arm: small replica, replayed through the semantics
    # oracle for its arrival class (simulator in-order, engine OOO)
    def lowered_rows(agg, fetched, n_iv):
        sp = agg.device_spec()
        out = []
        for i in range(n_iv):
            ws, we, cnt, resi = fetched[i]
            rows = [(int(ws[j]), int(we[j]), float(np.asarray(
                sp.lower(np.asarray(resi[0][j])[None, :],
                         np.asarray([int(cnt[j])]))[0])))
                    for j in range(len(ws)) if cnt[j] > 0]
            out.append(sorted(rows))
        return out

    def oracle_rows(po, op, n_iv):
        out = []
        for i in range(n_iv):
            vs, ts = po.materialize_interval(i)
            for v, t in zip(vs, ts):
                op.process_element(float(v), int(t))
            out.append(sorted(
                (w.start, w.end, float(w.agg_values[0]))
                for w in op.process_watermark(
                    (i + 1) * po.wm_period_ms)))
        return out

    agg = make_aggregation(agg_name)
    oracle_match = True
    o_windows = 0
    n_iv = 5
    for ooo in (0.0, cfg.out_of_order_pct or 0.25):
        po = mk(2000, ooo, min(cfg.max_lateness,
                               cfg.watermark_period_ms))
        fetched = jax.device_get(po.run(n_iv))
        po.check_overflow()
        got = lowered_rows(agg, fetched, n_iv)
        if ooo == 0.0:
            op = SlicingWindowOperator()
        else:
            # record retention spans lateness + the largest count
            # window's clear delay (ms-mixed, reference parity) at the
            # oracle's tuple rate — size the record ring above it
            op = TpuWindowOperator(config=EngineConfig(
                capacity=1 << 13, batch_size=64, annex_capacity=256,
                min_trigger_pad=32, record_capacity=1 << 15))
        for w in windows:
            op.add_window_assigner(w)
        op.add_aggregation(make_aggregation(agg_name))
        op.set_max_lateness(po.max_lateness)
        ref = oracle_rows(po, op, n_iv)
        for g_rows, r_rows in zip(got, ref):
            o_windows += len(r_rows)
            if [g[:2] for g in g_rows] != [r[:2] for r in r_rows]:
                oracle_match = False
                continue
            for g, r in zip(g_rows, r_rows):
                if abs(g[2] - r[2]) > 3e-4 * max(1.0, abs(r[2])):
                    oracle_match = False
    res.oracle_match = bool(oracle_match)
    res.oracle_windows = int(o_windows)
    res.platform = jax.devices()[0].platform
    res.tpu_floor_note = ("the >= 50 M t/s sliding-count ROADMAP floor "
                          "is a TPU-box certification; this cell "
                          f"records platform={res.platform}")
    return res


class _ExactContextOracle:
    """Arrival-order scalar replay of the session / capped-session
    calculus — the reference-semantics third leg of the chaos cells'
    three-way oracle (the capped branch mirrors
    tests/test_context_windows.py::_ExactCapped; ``cap=None`` is the
    plain-session specialization, which the tuned engine and the
    generic SessionDecider both realize)."""

    def __init__(self, gap: int, cap=None):
        self.gap = int(gap)
        self.cap = int(cap) if cap is not None else None
        self.s: list = []          # [first, last, sum] sorted by first
        self.orphans: list = []    # (pos, value)

    def _fits(self, f, l, t):
        if self.cap is None:
            return True
        return (l - t if f > t else t - f) <= self.cap

    def add(self, v: float, t: int) -> None:
        g, s = self.gap, self.s
        exact = declined = False
        fit_i = -1
        for i, (f, l, _) in enumerate(s):
            if f <= t <= l:
                s[i][2] += v
                return                      # inside
            if f - g <= t <= l + g:
                if t == f - g:
                    exact = True
                elif fit_i < 0 and self._fits(f, l, t):
                    fit_i = i
                else:
                    declined = True
        if fit_i >= 0:
            f, l, acc = s[fit_i]
            if t < f:                       # start-extension
                s[fit_i][0] = t
                s[fit_i][2] = acc + v
                if fit_i > 0 and s[fit_i - 1][1] + g >= t \
                        and (self.cap is None
                             or l - s[fit_i - 1][0] <= self.cap):
                    pf, _, pacc = s.pop(fit_i - 1)
                    s[fit_i - 1][0] = pf
                    s[fit_i - 1][2] += pacc
                return
            s[fit_i][1] = t                 # end-extension
            s[fit_i][2] = acc + v
            if fit_i + 1 < len(s) and t + g >= s[fit_i + 1][0] \
                    and (self.cap is None
                         or s[fit_i + 1][1] - f <= self.cap):
                _, nl, nacc = s.pop(fit_i + 1)
                s[fit_i][1] = nl
                s[fit_i][2] += nacc
            return
        if declined or not exact:
            k = 0
            while k < len(s) and s[k][0] <= t:
                k += 1
            s.insert(k, [t, t, v])
            return
        self.orphans.append((t, v))        # exact-gap fall-through

    def sweep(self, wm: int):
        out, keep = [], []
        for f, l, acc in self.s:
            if l + self.gap < wm:
                ws, we = f, l + self.gap
                acc += sum(v for (p, v) in self.orphans if ws <= p < we)
                self.orphans = [(p, v) for (p, v) in self.orphans
                                if not (ws <= p < we)]
                out.append((ws, we, acc))
            else:
                keep.append([f, l, acc])
        self.s = keep
        return out


def _context_chaos_stream(cfg: BenchmarkConfig, gap: int, R: int,
                          n_pools: int = 16):
    """Seeded per-interval chaos pools for the context/session cells:
    ``K`` bursts per watermark interval separated by ``1.5 * gap``
    silences (so sessions actually CLOSE), an ``outOfOrderPct`` late
    fraction displaced back by up to the lateness bound (so chunks
    arrive OOO), and occasional mid-silence BRIDGE tuples delivered
    late (so live sessions actually MERGE). Returns ``(pools, K)``
    where ``pools[j] = (vals f32[R'], ts_off i64[R'])`` are
    interval-relative and cycle by interval index."""
    P = cfg.watermark_period_ms
    cycle = min(P, max(4, int(2.5 * gap)))
    K = max(1, P // cycle)
    burst = max(1, cycle - int(1.5 * gap))
    # displacement stays under half the gap so silences survive (late
    # DEPTH comes from the bridges, delivered up to a full interval
    # late); merges are driven by the mid-silence bridges, which sit
    # within gap of BOTH neighboring bursts
    back = min(cfg.max_lateness, max(1, gap // 2))
    rng = np.random.default_rng(cfg.seed)
    per_burst = max(8, R // K)
    pools = []
    for _ in range(n_pools):
        parts_t = []
        for k in range(K):
            lo = k * cycle
            ts = np.sort(rng.integers(lo, lo + burst,
                                      size=per_burst)).astype(np.int64)
            parts_t.append(ts)
        ts = np.concatenate(parts_t)
        late = rng.random(ts.size) < cfg.out_of_order_pct
        ts = np.where(late,
                      np.maximum(ts - rng.integers(0, back, size=ts.size),
                                 0), ts)
        # bridges: mid-silence tuples, delivered at the end of the
        # interval's arrival order — they MERGE the two adjacent live
        # sessions (silence = 1.5 * gap, so the midpoint is within gap
        # of both burst edges)
        bridges = [np.int64(k * cycle - int(0.75 * gap))
                   for k in range(1, K) if rng.random() < 0.35]
        if bridges:
            ts = np.concatenate([ts, np.asarray(bridges, np.int64)])
        vals = (rng.random(ts.size) * 100.0).astype(np.float32)
        pools.append((vals, ts))
    return pools, K


def run_context_chaos_cell(cfg: BenchmarkConfig, window_spec: str,
                           agg_name: str,
                           obs: Optional[_obs.Observability] = None
                           ) -> BenchResult:
    """Context/session chaos cell (ISSUE 11): a seeded host-fed stream
    that actually GAPS (silent spans close sessions), MERGES (late
    mid-silence bridges join live sessions) and arrives OUT OF ORDER
    (bounded back-displacement), through the batch operator's context
    machinery — the speculative chunked path for specs certifying
    ``speculation_params`` (GenericSession), the tuned session engine
    for ``Session``, the per-tuple scan fallback for order-dependent
    specs (CappedSession).

    Two arms: a throughput arm at the configured offered load
    (scan-bound window classes scale it down honestly — the recorded
    row carries the actual tuple count), and a three-way ORACLE arm on
    a smaller replica of the same stream class: engine vs the
    per-tuple-scan twin (bit-comparable bounds/pathway equivalence) vs
    the host reference simulator vs an independent arrival-order
    scalar replay — ``oracle_match``/``scan_match``/``oracle_windows``
    land in the result row. Speculative telemetry
    (``ctx_speculative_*``) rides the metrics section and the
    ``fallback_rate`` field."""
    import jax

    from ..core.windows import (CappedSessionWindow, GenericSessionWindow,
                                SessionWindow)
    from ..engine import EngineConfig, TpuWindowOperator
    from .. import SlicingWindowOperator

    if agg_name != "sum":
        raise NotImplementedError(
            "ContextChaos cells replay a sum oracle; aggFunctions must "
            "be ['sum']")
    windows = parse_window_spec(window_spec, seed=cfg.seed)
    if len(windows) != 1 or not isinstance(
            windows[0], (SessionWindow, GenericSessionWindow,
                         CappedSessionWindow)):
        raise NotImplementedError(
            "ContextChaos cells take exactly one Session / "
            "GenericSession / CappedSession window")
    w = windows[0]
    gap = int(w.gap)
    cap = int(w.max_span) if isinstance(w, CappedSessionWindow) else None
    spec = w.device_context_spec()
    sp = spec.speculation_params() if spec is not None else None
    if sp is not None and sp.order_free \
            and not isinstance(w, SessionWindow):
        scale = 1.0                 # speculative chunked batching
        mode = "speculative"
    elif isinstance(w, SessionWindow):
        scale = 1 / 40              # tuned chain + sequential late scan
        mode = "session"
    else:
        scale = 1 / 150             # per-tuple scan carries the OOO load
        mode = "scan"
    P = cfg.watermark_period_ms
    lateness = cfg.max_lateness
    R = max(256, int(cfg.throughput * scale))
    intervals = max(8, cfg.runtime_s)

    def mk_op(batch_size):
        op = TpuWindowOperator(config=EngineConfig(
            capacity=max(256, min(cfg.capacity, 1024)), batch_size=batch_size,
            annex_capacity=64, min_trigger_pad=32))
        op.add_window_assigner(w)
        op.add_aggregation(make_aggregation(agg_name))
        op.set_max_lateness(lateness)
        return op

    pools, K = _context_chaos_stream(cfg, gap, R)
    B = 1 << max(10, int(np.ceil(np.log2(max(2, pools[0][1].size)))))
    op = mk_op(B)

    def feed(i):
        vals, ts_off = pools[i % len(pools)]
        op.process_elements(vals, ts_off + np.int64(i) * P)
        op._flush()

    def wm_of(i):
        return (i + 1) * P - lateness

    # warmup: compile apply/chunk/sweep kernels. The sync anchor must be
    # re-read per drain: the context/session kernels DONATE their state
    # buffers, so a handle bound once would be deleted on TPU and would
    # return a stale cached host copy (no queue drain) on CPU.
    def drain():
        st = (op._ctx_states[0] if op._ctx_states
              else op._session_states[0])
        jax.device_get(st.n)

    feed(0)
    op.process_watermark_async(max(1, wm_of(0)))
    drain()
    if obs is not None:
        op.set_observability(obs)
        obs.registry.reset_clock()
    warm_stats = dict(getattr(op, "_ctx_spec_stats", {}) or {})

    pending = []
    lats = []
    SAMPLE_EVERY = 8
    n_tuples = 0
    t0 = time.perf_counter()
    for i in range(1, intervals + 1):
        feed(i)
        n_tuples += pools[i % len(pools)][1].size
        sample = i % SAMPLE_EVERY == 0
        if sample:
            drain()
            t1 = time.perf_counter()
        out = op.process_watermark_async(wm_of(i))
        ms = tuple(g[0] for g in out[1])
        pending.append(ms)
        if sample:
            jax.device_get(ms)
            lats.append((time.perf_counter() - t1) * 1e3)
    drain()
    wall = time.perf_counter() - t0
    op.check_overflow()
    emitted = int(sum(int(m) for grp in jax.device_get(pending)
                      for m in grp))
    if obs is not None:
        obs.registry.stop_clock()
        op.set_observability(None)
    stats = dict(getattr(op, "_ctx_spec_stats", {}) or {})
    for k in stats:
        stats[k] -= warm_stats.get(k, 0)

    # -- three-way oracle arm on a small replica of the stream class ------
    ocfg = BenchmarkConfig(
        name=cfg.name, throughput=max(256, 48 * K), runtime_s=cfg.runtime_s,
        watermark_period_ms=P, max_lateness=lateness, seed=cfg.seed + 1,
        out_of_order_pct=cfg.out_of_order_pct)
    o_pools, _ = _context_chaos_stream(ocfg, gap, ocfg.throughput,
                                       n_pools=8)
    o_intervals = max(intervals, 60)
    eng = mk_op(1024)
    scan = mk_op(1024)
    sim = SlicingWindowOperator()
    sim.add_window_assigner(w)
    sim.add_aggregation(make_aggregation(agg_name))
    sim.set_max_lateness(lateness)
    oracle = _ExactContextOracle(gap, cap)
    oracle_match = scan_match = True
    o_windows = 0
    for i in range(o_intervals):
        vals, ts_off = o_pools[i % len(o_pools)]
        ts = ts_off + np.int64(i) * P
        eng.process_elements(vals, ts)
        eng._flush()
        if not scan._built:
            scan._build()
        scan._ctx_planners = tuple(None for _ in scan._ctx_planners)
        scan.process_elements(vals, ts)
        scan._flush()
        for v, t in zip(vals, ts):
            sim.process_element(float(v), int(t))
            oracle.add(float(v), int(t))
        wm = max(1, wm_of(i))
        r_e = [x for x in eng.process_watermark(wm)]
        r_s = [x for x in scan.process_watermark(wm)]
        r_m = [x for x in sim.process_watermark(wm)]
        exp = oracle.sweep(wm)
        o_windows += len(exp)
        be = [(x.start, x.end) for x in r_e]
        if be != [(x.start, x.end) for x in r_s]:
            scan_match = False
        if be != [(ws, we) for (ws, we, _) in exp] \
                or be != [(x.get_start(), x.get_end()) for x in r_m]:
            oracle_match = False
            continue
        for x, y, (_, _, acc) in zip(r_e, r_s, exp):
            xv = float(x.agg_values[0]) if x.has_value() else None
            yv = float(y.agg_values[0]) if y.has_value() else None
            if (xv is None) != (yv is None) or (
                    xv is not None
                    and abs(xv - yv) > 1e-4 * max(1.0, abs(yv))):
                scan_match = False
            if xv is not None \
                    and abs(xv - acc) > 1e-3 * max(1.0, abs(acc)):
                oracle_match = False
    eng.check_overflow()
    scan.check_overflow()

    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=0.0,
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    res.n_lat_samples = len(lats)
    for k, v in latency_stats(lats).items():
        setattr(res, k, v)
    res.emit_ms_device = wall / intervals * 1e3
    res.context_mode = mode
    res.oracle_match = bool(oracle_match)
    res.scan_match = bool(scan_match)
    res.oracle_windows = int(o_windows)
    total = stats.get("speculative_tuples", 0) \
        + stats.get("fallback_tuples", 0)
    res.ctx_speculative_tuples = int(stats.get("speculative_tuples", 0))
    res.ctx_fallback_tuples = int(stats.get("fallback_tuples", 0))
    res.ctx_fallback_runs = int(stats.get("fallback_runs", 0))
    res.ctx_fallback_rate = (stats.get("fallback_tuples", 0) / total
                             if total else 0.0)
    res.platform = jax.devices()[0].platform
    finalize_observability(res, obs, lats, emitted, n_tuples=n_tuples)
    return res


def run_ingest_external_cell(cfg: BenchmarkConfig, window_spec: str,
                             agg_name: str,
                             obs: Optional[_obs.Observability] = None
                             ) -> BenchResult:
    """Line-rate external-ingest cell (ISSUE 7): an adversarially
    disordered HOST-resident stream — every chunk fully shuffled with a
    bounded back-reach into the previous chunk's event range, nothing
    pipeline-generated — taken through the full ingest edge:
    ``BatchAccumulator.offer_block`` → ``IngestRing`` →
    ``DeviceRingFeeder`` prefetch (H2D of block N+1 overlapping the
    ingest dispatch of block N) → device sort-and-split. The recorded
    comparator is the r5 host edge for exactly this stream class: the
    per-record ``process_element`` → ``BatchAccumulator.offer`` trickle
    (measured on a prefix of the same stream, rate-extrapolated) —
    ``speedup_vs_per_record`` is the ISSUE 7 ≥ 5× acceptance number.
    The device-origin comparator remains the r5 ``ingest_shaped_ooo``
    (ShapedOOO) cell; the ≥ 50 M t/s ROADMAP floor stays a TPU-box
    certification (this cell records the platform alongside)."""
    import jax

    from ..autotune import EngineGeometry
    from ..engine import EngineConfig, TpuWindowOperator
    from ..ingest import LineRateFeed

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    B = cfg.batch_size
    n_chunks = int(max(6, cfg.throughput * cfg.runtime_s // B))
    span = max(1.0, cfg.runtime_s * 1000 / n_chunks)
    back = cfg.shaper_back_ms or max(1, min(cfg.max_lateness,
                                            int(span) // 8))
    late_cap = cfg.shaper_late_capacity or max(64, B // 4)
    exp_late = B * back / (int(span) + back)
    if exp_late * 1.5 > late_cap:
        raise ValueError(
            f"IngestExternal geometry: expected late fraction "
            f"{back}/({int(span)}+{back}) of batch_size {B} ≈ "
            f"{exp_late:.0f} tuples ≥ late_capacity {late_cap} — lower "
            "throughput, shrink shaperBackMs, or raise "
            "shaperLateCapacity")
    # one geometry derives the engine/ring/shaper configs for BOTH arms
    # (geometry-discipline): coupled knobs move as a single value
    geom = EngineGeometry(capacity=cfg.capacity, batch_size=B,
                          ring_depth=cfg.ring_depth or 8,
                          ring_block=cfg.ring_block_size or B,
                          late_capacity=late_cap)

    # pregenerate the HOST-resident chunks (stream origin is host RAM;
    # generation is the load generator's cost, excluded as everywhere)
    rng = np.random.default_rng(cfg.seed)
    chunks = []
    for i in range(n_chunks):
        lo = int((i + 1) * span) - back
        ts = lo + rng.integers(0, int(span) + back, size=B).astype(np.int64)
        vals = (rng.random(B) * 10_000).astype(np.float32)
        chunks.append((vals, ts, lo, int((i + 1) * span) + int(span)))

    def mk_op():
        op = TpuWindowOperator(config=geom.engine_config(
            EngineConfig(overflow_policy=cfg.overflow_policy)))
        for w in windows:
            op.add_window_assigner(w)
        op.add_aggregation(make_aggregation(agg_name))
        op.set_max_lateness(max(cfg.max_lateness, back + 2 * int(span)))
        return op

    op = mk_op()
    feed = LineRateFeed(
        op, ring=geom.ring_config(), shaper=geom.shaper_config())

    # warmup: compiles sort-split + ingest + watermark kernels
    for i in (0, 1):
        v, t, lo, hi = chunks[i]
        feed.offer_block(v, t)
    warm_wm = chunks[1][3] + 1
    op.process_watermark_async(warm_wm)
    jax.device_get(op._state.n_slices)
    if obs is not None:
        op.set_observability(obs)
        obs.registry.reset_clock()

    next_wm = (warm_wm // cfg.watermark_period_ms + 1) \
        * cfg.watermark_period_ms
    pending = []
    occ_samples = []
    t0 = time.perf_counter()
    for i in range(2, n_chunks):
        v, t, lo, hi = chunks[i]
        feed.offer_block(v, t)
        occ_samples.append((feed.ring.occupancy,
                            feed.ring.occupancy + feed.accumulator.held))
        while hi - back - 2 * int(span) >= next_wm:
            out = op.process_watermark_async(next_wm)
            if out[3] is not None:
                pending.append((out[0].shape[0], out[3]))
            next_wm += cfg.watermark_period_ms
    feed.drain()
    out = op.process_watermark_async(next_wm)
    if out[3] is not None:
        pending.append((out[0].shape[0], out[3]))
    emitted = 0
    fetched = jax.device_get([c for _, c in pending])
    for (T, _), cnt in zip(pending, fetched):
        emitted += int((cnt[:T] > 0).sum())
    op.check_overflow()                 # shaper + ring drain-point checks
    wall = time.perf_counter() - t0
    n_tuples = (n_chunks - 2) * B
    if obs is not None:
        obs.registry.stop_clock()
        op.set_observability(None)

    # the r5 comparator: per-record offer trickle on the same stream
    # class (a prefix, rate-extrapolated — the loop is O(records) Python)
    op2 = mk_op()
    from ..shaper import StreamShaper

    StreamShaper(op2, geom.shaper_config())
    base_n = int(min(n_tuples, 200_000))
    t0 = time.perf_counter()
    fed = 0
    wm2 = next_wm
    for i in range(2, n_chunks):
        v, t, lo, hi = chunks[i]
        take = min(B, base_n - fed)
        for j in range(take):
            op2.process_element(float(v[j]), int(t[j]))
        fed += take
        if fed >= base_n:
            break
    op2.process_watermark_async(wm2 + 10 * int(span))
    jax.device_get(op2._state.n_slices)
    base_wall = time.perf_counter() - t0
    op2.check_overflow()
    baseline_tps = fed / base_wall if base_wall > 0 else 0.0

    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=0.0,
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    res.emit_ms_device = wall / max(1, len(pending)) * 1e3
    # ring occupancy is the RING alone (cross-checkable against the
    # ring_bounded invariant / ingest_ring_occupancy gauge);
    # host_staged adds the accumulator's held band — the full
    # host-side staging footprint between the source and the device
    occ = np.asarray(occ_samples if occ_samples else [(0, 0)])
    res.ring_occupancy_p50 = float(np.percentile(occ[:, 0], 50))
    res.ring_occupancy_p90 = float(np.percentile(occ[:, 0], 90))
    res.ring_occupancy_p99 = float(np.percentile(occ[:, 0], 99))
    res.host_staged_p50 = float(np.percentile(occ[:, 1], 50))
    res.host_staged_p90 = float(np.percentile(occ[:, 1], 90))
    res.host_staged_p99 = float(np.percentile(occ[:, 1], 99))
    snap = feed.snapshot()
    res.ring_full_events = int(snap["full_events"])
    res.ring_shed = int(snap["shed"])
    res.ring_blocks = int(snap["blocks"])
    res.baseline_per_record_tps = baseline_tps
    res.speedup_vs_per_record = (res.tuples_per_sec
                                 / max(baseline_tps, 1e-9))
    res.shaper_back_ms = back
    res.platform = jax.devices()[0].platform
    res.tpu_floor_note = ("the >= 50 M t/s ROADMAP floor is a TPU-box "
                          "certification; this cell records "
                          f"platform={res.platform}")
    finalize_observability(res, obs, [], emitted, n_tuples=n_tuples)
    return res


def measure_delivery_overhead(seed: int = 0, n_records: int = 3000,
                              pairs: int = 9) -> float:
    """Interleaved A/B of the exactly-once ledger on the iterable keyed
    loop (ISSUE 8 acceptance: <= 2% median on CPU): per-pair bare-loop
    vs TransactionalSink(exactly_once) wall time, returns the median
    overhead in PERCENT (negative = within noise)."""
    from ..connectors.base import (AscendingWatermarks,
                                   KeyedScottyWindowOperator)
    from ..connectors.iterable import run_keyed
    from ..core.aggregates import SumAggregation
    from ..core.windows import TumblingWindow, WindowMeasure
    from ..delivery import EXACTLY_ONCE, TransactionalSink

    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 8, size=n_records)
    vals = rng.standard_normal(n_records)
    recs = [(f"k{keys[i]}", float(vals[i]), i * 10)
            for i in range(n_records)]

    def once(with_sink: bool) -> float:
        op = KeyedScottyWindowOperator(
            windows=[TumblingWindow(WindowMeasure.Time, 100)],
            aggregations=[SumAggregation()],
            watermark_policy=AscendingWatermarks())
        sink = TransactionalSink(mode=EXACTLY_ONCE) if with_sink else None
        t0 = time.perf_counter()
        for _ in run_keyed(iter(recs), op, sink=sink):
            pass
        return time.perf_counter() - t0

    once(False), once(True)                 # warm both paths
    a_times, b_times = [], []
    for _ in range(pairs):
        a_times.append(once(False))
        b_times.append(once(True))
    a_times.sort()
    b_times.sort()
    return 100.0 * (b_times[len(b_times) // 2]
                    / a_times[len(a_times) // 2] - 1.0)


def measure_latency_overhead(seed: int = 0, throughput: int = 4_000_000,
                             intervals: int = 6, pairs: int = 16) -> float:
    """Interleaved A/B of the SAMPLING-OFF latency tracer on the
    aligned pipeline (ISSUE 14 acceptance: ≤ 2% median): per-pair
    obs-without-tracer vs obs-with-``sample_every=0`` tracer wall time
    over the same timed intervals — isolating exactly what every
    steady-state interval pays for the seams (one attribute check per
    hook, one declined ``open()`` per interval). Returns the median
    overhead in PERCENT (negative = within noise)."""
    from ..core.aggregates import SumAggregation
    from ..core.windows import SlidingWindow, WindowMeasure
    from ..engine import EngineConfig
    from ..engine.pipeline import AlignedStreamPipeline

    windows = [SlidingWindow(WindowMeasure.Time, 8000, 1000)]

    def build(with_tracer: bool):
        p = AlignedStreamPipeline(
            windows, [SumAggregation()],
            config=EngineConfig(capacity=2048, annex_capacity=8,
                                min_trigger_pad=32),
            throughput=_round_throughput(
                throughput, AlignedStreamPipeline.slice_grid(windows,
                                                             1000)),
            wm_period_ms=1000, max_lateness=0, seed=seed, gc_every=32)
        obs = _obs.Observability()
        if with_tracer:
            obs.attach_latency(sample_every=0)
        p.reset()
        p.run(2, collect=False)
        p.sync()
        p.set_observability(obs)
        return p

    pa, pb = build(False), build(True)

    def once(p) -> float:
        t0 = time.perf_counter()
        p.run(intervals, collect=False)
        p.sync()
        return time.perf_counter() - t0

    once(pa), once(pb)                       # warm both step paths
    a_times, b_times = [], []
    for i in range(pairs):
        # alternate within-pair order so slow drift (thermal, other
        # tenants on a shared core) cancels instead of biasing one arm
        if i % 2 == 0:
            a_times.append(once(pa))
            b_times.append(once(pb))
        else:
            b_times.append(once(pb))
            a_times.append(once(pa))
    pa.check_overflow()
    pb.check_overflow()
    a_times.sort()
    b_times.sort()
    return 100.0 * (b_times[len(b_times) // 2]
                    / a_times[len(a_times) // 2] - 1.0)


def measure_workload_overhead(seed: int = 0, throughput: int = 4_000_000,
                              intervals: int = 6, pairs: int = 16) -> float:
    """Interleaved A/B of the ISSUE 16 sensor plane on the aligned
    pipeline (acceptance: ≤ 2% median): per-pair bare-obs vs
    obs-with-WorkloadMonitor+DriftDetector wall time over the same timed
    intervals. The monitor samples at the pipeline's existing
    ``flight_sync`` drain point (one per ``sync``) with an audit interval
    short enough that EVERY sample closes an audit window — so the B arm
    pays the full fold (counter snapshot, feature derivation, gauge
    writes, drift judging) each sync, the worst case a production
    ``audit_interval_s`` would amortize. Returns the median overhead in
    PERCENT (negative = within noise)."""
    from ..core.aggregates import SumAggregation
    from ..core.windows import SlidingWindow, WindowMeasure
    from ..engine import EngineConfig
    from ..engine.pipeline import AlignedStreamPipeline
    from ..obs.drift import DriftDetector

    windows = [SlidingWindow(WindowMeasure.Time, 8000, 1000)]

    def build(with_monitor: bool):
        p = AlignedStreamPipeline(
            windows, [SumAggregation()],
            config=EngineConfig(capacity=2048, annex_capacity=8,
                                min_trigger_pad=32),
            throughput=_round_throughput(
                throughput, AlignedStreamPipeline.slice_grid(windows,
                                                             1000)),
            wm_period_ms=1000, max_lateness=0, seed=seed, gc_every=32)
        obs = _obs.Observability()
        if with_monitor:
            mon = obs.attach_workload(audit_interval_s=1e-9)
            mon.attach_detector(DriftDetector())
        p.reset()
        p.run(2, collect=False)
        p.sync()
        p.set_observability(obs)
        return p

    pa, pb = build(False), build(True)

    def once(p) -> float:
        t0 = time.perf_counter()
        p.run(intervals, collect=False)
        p.sync()
        return time.perf_counter() - t0

    once(pa), once(pb)                       # warm both step paths
    a_times, b_times = [], []
    for i in range(pairs):
        # alternate within-pair order so slow drift (thermal, other
        # tenants on a shared core) cancels instead of biasing one arm
        if i % 2 == 0:
            a_times.append(once(pa))
            b_times.append(once(pb))
        else:
            b_times.append(once(pb))
            a_times.append(once(pa))
    pa.check_overflow()
    pb.check_overflow()
    a_times.sort()
    b_times.sort()
    return 100.0 * (b_times[len(b_times) // 2]
                    / a_times[len(a_times) // 2] - 1.0)


def run_workload_drift_cell(cfg: BenchmarkConfig, window_spec: str,
                            agg_name: str,
                            obs: Optional[_obs.Observability] = None
                            ) -> BenchResult:
    """Workload-drift cell (ISSUE 16 acceptance): a seeded 3-phase
    shifting stream — rate ×8, then a lateness storm, then a key-skew
    flip — through the host keyed connector operator with the
    WorkloadMonitor on a ManualClock (one audit window per simulated
    second, sampled only at the per-watermark ``flight_sync`` drain
    point). The attached self-baselining :class:`DriftDetector` must
    fire on EVERY phase transition within a bounded number of audit
    windows, and a second arm replaying the stable phase for the full
    duration must fire ZERO events (the false-positive bound). A third
    arm records the interleaved sensor-plane A/B overhead on the
    aligned pipeline (:func:`measure_workload_overhead`, ≤ 2% median).

    Recorded per cell: the phase schedule with per-transition detect
    lags (``drift_detect_lags``, in audit windows), ``drift_events`` /
    ``drift_fired`` (which features fired when),
    ``drift_false_positives`` (stable arm), and
    ``workload_overhead_pct_median`` — plus the closing fingerprint in
    the ``metrics`` section like every other cell."""
    from ..connectors.base import (AscendingWatermarks,
                                   KeyedScottyWindowOperator)
    from ..obs.drift import DriftDetector
    from ..obs.workload import WorkloadMonitor
    from ..resilience.clock import ManualClock

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    P = cfg.watermark_period_ms            # 1 simulated second per audit
    r0 = max(256, int(cfg.throughput))     # stable tuples per sim second
    n_keys = max(8, cfg.n_keys or 64)
    # phase schedule in simulated seconds == audit windows (audit k folds
    # second k; second 0 arms the monitor's first window)
    phases = [("stable", 12, None),        # baseline + stable arm
              ("rate_x8", 8, "arrival_rate_per_s"),
              ("late_storm", 8, "late_share"),
              ("key_skew", 8, "key_top_share")]
    total_s = sum(n for _, n, _ in phases)
    rng = np.random.default_rng(cfg.seed)

    def second_stream(phase: str, s: int, wm: int):
        """(keys, values, ts) for simulated second ``s`` under ``phase``
        — ts ascending within the second except the lateness storm's
        injected stragglers (below the current watermark but inside
        cfg.max_lateness, so the operator repairs rather than drops)."""
        n = r0 * 8 if phase == "rate_x8" else r0
        if phase == "key_skew":
            # 80% of the load lands on one hot key, rest uniform
            hot = rng.random(n) < 0.80
            keys = rng.integers(0, n_keys, size=n)
            keys[hot] = 0
        else:
            keys = rng.integers(0, n_keys, size=n)
        ts = np.sort(rng.integers(0, P, size=n)) + np.int64(s * P)
        if phase == "late_storm" and wm > 0:
            # ~30% arrive below the watermark by up to half max_lateness
            late = rng.random(n) < 0.30
            age = rng.integers(1, max(2, cfg.max_lateness // 2),
                               size=n)
            ts = np.where(late, np.maximum(0, wm - age), ts)
        vals = (rng.random(n) * 100).astype(np.float64)
        return keys, vals, ts

    def run_arm(schedule):
        """One full stream under ``schedule`` ([(phase, seconds)]);
        returns (detector, monitor, obs, emitted, n_tuples)."""
        arm_obs = _obs.Observability()
        clock = ManualClock()
        mon = arm_obs.attach_workload(
            WorkloadMonitor(clock=clock, audit_interval_s=1.0,
                            top_k=max(1, n_keys // 8)))
        det = DriftDetector()              # self-baseline, confirm=2
        mon.attach_detector(det)
        op = KeyedScottyWindowOperator(
            windows=list(windows),
            aggregations=[make_aggregation(agg_name)],
            allowed_lateness=cfg.max_lateness,
            watermark_policy=AscendingWatermarks(),
            obs=arm_obs)
        emitted = 0
        n_tuples = 0
        s = 0
        wm = 0
        for phase, n_seconds in schedule:
            for _ in range(n_seconds):
                keys, vals, ts = second_stream(phase, s, wm)
                for j in range(len(keys)):
                    for _key, w in op.process_element(
                            int(keys[j]), float(vals[j]), int(ts[j])):
                        emitted += 1
                n_tuples += len(keys)
                wm = (s + 1) * P
                for _key, w in op.process_watermark(wm):
                    emitted += 1
                # the keyed/mesh skew feed (the mesh engine's hot-key
                # drain read does the same fold; host cells feed their
                # own per-second histogram)
                mon.observe_key_loads(np.bincount(keys,
                                                  minlength=n_keys))
                clock.advance(1.0)
                arm_obs.flight_sync(watermark=float(wm))
                s += 1
        return det, mon, arm_obs, emitted, n_tuples

    # -- drift arm: the 3-phase shifting stream --------------------------
    t0 = time.perf_counter()
    schedule = [(ph, n) for ph, n, _ in phases]
    det, mon, arm_obs, emitted, n_tuples = run_arm(schedule)
    wall = time.perf_counter() - t0
    fired_by_feature = {f["feature"]: f["audit"] for f in det.fired}
    transitions = []
    lags = {}
    all_detected = True
    boundary = 0
    for phase, n_seconds, expect in phases:
        start_audit = boundary + (0 if boundary else 1)
        boundary += n_seconds
        if expect is None:
            continue
        fired_at = fired_by_feature.get(expect)
        lag = (fired_at - start_audit + 1) if fired_at is not None \
            else None
        detected = lag is not None and 0 < lag <= 4
        all_detected = all_detected and detected
        lags[phase] = lag
        transitions.append({"phase": phase, "expect": expect,
                            "transition_audit": start_audit,
                            "fired_audit": fired_at, "lag": lag,
                            "detected": detected})

    # -- stable arm: same duration, phase A only — zero events -----------
    det_stable, _, _, _, _ = run_arm([("stable", total_s)])

    # -- sensor-plane overhead arm (aligned pipeline A/B) ----------------
    overhead = round(measure_workload_overhead(seed=cfg.seed), 2)

    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall if wall > 0 else 0.0,
        p99_emit_ms=0.0, n_windows_emitted=emitted,
        n_tuples=n_tuples, wall_s=round(wall, 3))
    res.workload_phases = [{"phase": ph, "seconds": n,
                            "expect": expect}
                           for ph, n, expect in phases]
    res.drift_events = det.events
    res.drift_fired = [{"feature": f["feature"], "audit": f["audit"],
                        "reference": round(f["reference"], 6),
                        "live": round(f["live"], 6)}
                       for f in det.fired]
    res.drift_transitions = transitions
    res.drift_detect_lags = lags
    res.drift_all_detected = bool(all_detected and transitions)
    res.drift_false_positives = det_stable.events
    res.workload_overhead_pct_median = overhead
    finalize_observability(res, arm_obs, [], 0)
    return res


def measure_attribution_overhead(seed: int = 0,
                                 throughput: int = 4_000_000,
                                 intervals: int = 4, pairs: int = 25,
                                 n_tenants: int = 4) -> float:
    """Interleaved A/B of the ISSUE 19 accounting plane in STEADY STATE
    (acceptance: ≤ 2% median): both arms drive the same served query
    grid and fetch every interval's trigger rows at the drain point —
    the work a serving loop does regardless; the B arm additionally
    folds the rows into the :class:`TenantAttribution` ledger and
    evaluates the :class:`SloPolicy` at ``flight_sync``. Returns the
    median overhead in PERCENT (negative = within noise)."""
    from ..core.aggregates import SumAggregation
    from ..core.windows import TumblingWindow, WindowMeasure
    from ..engine import EngineConfig
    from ..engine.pipeline import AlignedStreamPipeline
    from ..resilience.clock import ManualClock
    from ..serving import QueryAdmission, QueryService
    from ..serving.cache import pad_pow2

    T = WindowMeasure.Time
    P = 1000
    qwin = TumblingWindow(T, P)
    g = AlignedStreamPipeline.slice_grid([qwin], P)
    tp = _round_throughput(throughput, g)
    econf = EngineConfig(capacity=2048, annex_capacity=8,
                         min_trigger_pad=32)

    def build(with_attr: bool):
        svc = QueryService(
            [SumAggregation()], slice_grid=g, max_window_size=4 * P,
            throughput=tp, wm_period_ms=P, max_lateness=0, seed=seed,
            config=econf,
            admission=QueryAdmission(max_queries=pad_pow2(n_tenants, 8)),
            min_slots=pad_pow2(n_tenants, 8),
            min_trigger_lanes=pad_pow2(4, 8))
        for t in range(n_tenants):
            svc.register(qwin, tenant=f"t{t}")
        svc.run(6, collect=False)
        svc.sync()
        svc.mark_warm()
        o = _obs.Observability()
        clock = ManualClock()
        if with_attr:
            o.attach_attribution(clock=clock)
            o.attach_slo(delivered_share=0.9, clock=clock)
        svc.set_observability(o)
        return svc, o, clock

    a, b = build(False), build(True)

    def once(arm) -> float:
        svc, o, clock = arm
        t0 = time.perf_counter()
        out = svc.run(1, collect=True)[0]
        rows = svc.results_by_slot(out)
        if getattr(o, "attribution", None) is not None:
            svc.account_emissions(rows)
        clock.advance(1.0)
        o.flight_sync(watermark=float(svc.pipeline._interval * P))
        svc.sync()
        return time.perf_counter() - t0

    for _ in range(3):                    # warm both drain paths
        once(a), once(b)

    def sampled_median() -> float:
        a_times, b_times = [], []
        # ONE interval per timing sample, arms interleaved
        # back-to-back with alternating order: ambient drift (another
        # tenant on the core, a GC burst) lands on both arms'
        # distributions instead of biasing one, and the medians shrug
        # off the stall outliers that sink a blocked design
        for i in range(intervals * pairs):
            if i % 2 == 0:
                a_times.append(once(a))
                b_times.append(once(b))
            else:
                b_times.append(once(b))
                a_times.append(once(a))
        a_times.sort()
        b_times.sort()
        return 100.0 * (b_times[len(b_times) // 2]
                        / a_times[len(a_times) // 2] - 1.0)

    # median-of-3 rounds: one round's median still wobbles with
    # ambient load on a shared host; the middle of three rounds is
    # what the acceptance gate records
    rounds = sorted(sampled_median() for _ in range(3))
    a[0].check_overflow()
    b[0].check_overflow()
    return rounds[1]


def run_slo_churn_cell(cfg: BenchmarkConfig, window_spec: str,
                       agg_name: str,
                       obs: Optional[_obs.Observability] = None
                       ) -> BenchResult:
    """SLO-churn cell (ISSUE 19 acceptance; config
    ``bench/configurations/slo_churn.json``): ``sloTenants`` tenants
    share one served grid, each holding one tumbling query under a
    ``per_tenant_quota=1`` admission policy. The seeded HOT tenant
    misbehaves two ways every interval: it hammers ``sloHotFactor − 1``
    extra registrations past its quota (each rejection is
    tenant-attributed exactly), and its offered tuple stream —
    ``sloHotFactor ×`` a fair share — drives the PR 18
    :class:`DegradationLadder` past its audit budget so the sampled
    rung sheds tuples, apportioned to tenants by their OVERAGE above
    the fair share (only the hot tenant has any, with
    ``sloHotFactor ≥ 3``).

    Acceptance recorded on the row: the attached :class:`SloPolicy`
    (``delivered_share`` objective on a ManualClock, one tick per
    interval at the ``flight_sync`` drain point) must latch a burn for
    EXACTLY the hot tenant — ``slo_violation_detected`` with the
    violating tenant/objective/owning stage named,
    ``slo_false_positives == 0`` for every well-behaved tenant — and
    ``slo_conservation_ok`` asserts the ledger equals the engine
    counters (rejected == serving_rejected, shed == the ladder's exact
    count, windows == independently tallied tenant rows). The
    interleaved accounting-plane A/B
    (:func:`measure_attribution_overhead`, ≤ 2% median) rides along as
    ``attribution_overhead_pct_median``."""
    from ..autotune import DegradationLadder
    from ..core.windows import TumblingWindow, WindowMeasure
    from ..engine import EngineConfig
    from ..engine.pipeline import AlignedStreamPipeline
    from ..resilience.clock import ManualClock
    from ..serving import QueryAdmission, QueryService
    from ..serving.cache import pad_pow2

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    P = cfg.watermark_period_ms
    g = AlignedStreamPipeline.slice_grid(windows, P)
    tp = _round_throughput(cfg.throughput, g)
    max_size = max([4 * P] + [int(w.size) for w in windows])
    econf = EngineConfig(capacity=cfg.capacity, annex_capacity=8,
                         min_trigger_pad=32,
                         overflow_policy=cfg.overflow_policy)
    N = max(2, int(cfg.slo_tenants))
    hot = "t0"
    tenants = [f"t{i}" for i in range(N)]
    qwin = TumblingWindow(WindowMeasure.Time, P)

    svc = QueryService(
        [make_aggregation(agg_name)], slice_grid=g,
        max_window_size=max_size, throughput=tp, wm_period_ms=P,
        max_lateness=cfg.max_lateness, seed=cfg.seed, config=econf,
        admission=QueryAdmission(max_queries=pad_pow2(N + 2, 8),
                                 per_tenant_quota=1, on_reject="shed"),
        min_slots=pad_pow2(N + 2, 8),
        min_trigger_lanes=pad_pow2(4, 8))
    handles = {t: svc.register(qwin, tenant=t) for t in tenants}
    tenant_slots = {h.slot for h in handles.values()}
    warmup = max_size // P + 2
    svc.run(warmup, collect=False)
    svc.sync()
    svc.mark_warm()

    cell_obs = obs if obs is not None else _obs.Observability()
    clock = ManualClock()
    attribution = cell_obs.attach_attribution(clock=clock)
    slo = cell_obs.attach_slo(
        delivered_share=cfg.slo_delivered_share,
        burn_threshold=cfg.slo_burn_threshold, clock=clock)
    svc.set_observability(cell_obs)
    cell_obs.registry.reset_clock()
    ladder = DegradationLadder(sample_mod=4, relax_after=2, obs=cell_obs)

    # the offered sideband the ladder degrades: the hot tenant offers
    # sloHotFactor x a fair per-tenant share, so the per-audit budget
    # (total fair load + one share of headroom) is exceeded exactly
    # because of the hot tenant's overage
    base = 64
    offered = {t: base * cfg.slo_hot_factor if t == hot else base
               for t in tenants}
    total_offered = sum(offered.values())
    budget = float(base * (N + 1))
    fair = total_offered / float(N)
    overage = {t: max(0.0, n - fair) for t, n in offered.items()}

    n_timed = max(12, cfg.runtime_s)
    lats = []
    tenant_rows = 0
    t0 = time.perf_counter()
    for _ in range(n_timed):
        t1 = time.perf_counter()
        # hot tenant hammers past its quota: exact rejected attribution
        for _ in range(max(0, cfg.slo_hot_factor - 1)):
            svc.register(qwin, tenant=hot)
        out = svc.run(1, collect=True)[0]
        rows = svc.results_by_slot(out)
        tenant_rows += sum(len(r) for s, r in rows.items()
                           if s in tenant_slots)
        svc.account_emissions(rows)
        wm = float(svc.pipeline._interval * P)
        # offered sideband under the ladder; sheds carry no tenant
        # identity, so the ledger apportions them by overage weight
        shed_before = ladder.shed
        ladder.admit(np.full(total_offered, int(wm), np.int64), int(wm))
        ladder.audit(budget)
        if ladder.shed > shed_before:
            attribution.apportion_count(
                "shed", ladder.shed - shed_before, overage)
        clock.advance(1.0)
        cell_obs.flight_sync(watermark=wm)
        lats.append((time.perf_counter() - t1) * 1e3)
    wall = time.perf_counter() - t0
    svc.sync()
    svc.check_overflow()
    cell_obs.registry.stop_clock()
    n_tuples = n_timed * svc.pipeline.tuples_per_interval

    violations = slo.violations()
    hits = [v for v in violations if v["tenant"] == hot]
    false_pos = [v for v in violations if v["tenant"] != hot]
    totals = attribution.totals()
    stats = svc.stats()
    conserved = (
        attribution.conservation_ok()
        and totals["rejected"] == int(stats.get("serving_rejected", 0))
        and totals["shed"] == int(ladder.shed)
        and totals["windows"] == int(tenant_rows))

    overhead = round(measure_attribution_overhead(seed=cfg.seed), 2)

    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall if wall > 0 else 0.0,
        p99_emit_ms=float(np.percentile(lats, 99)) if lats else 0.0,
        n_windows_emitted=tenant_rows, n_tuples=n_tuples,
        wall_s=round(wall, 3))
    res.n_lat_samples = len(lats)
    res.p50_emit_ms = float(np.percentile(lats, 50)) if lats else 0.0
    res.slo_tenants = N
    res.slo_hot_tenant = hot
    res.slo_violation_detected = bool(hits)
    if hits:
        res.slo_violating_tenant = hits[0]["tenant"]
        res.slo_violating_objective = hits[0]["objective"]
        res.slo_owning_stage = hits[0]["owning_stage"]
    res.slo_false_positives = len(false_pos)
    res.slo_burn_events_total = int(
        cell_obs.counter(_obs.SLO_BURN_EVENTS).value)
    res.slo_conservation_ok = bool(conserved)
    res.serving_retraces_after_warmup = int(svc.retraces_since_warm)
    res.serving_rejected = int(stats.get("serving_rejected", 0))
    res.degrade_shed_tuples = int(ladder.shed)
    res.attribution_overhead_pct_median = overhead
    finalize_observability(res, cell_obs, lats, tenant_rows,
                           n_tuples=n_tuples)
    return res


def measure_autotune_overhead(seed: int = 0, throughput: int = 4_000_000,
                              intervals: int = 6, pairs: int = 16) -> float:
    """Interleaved A/B of the ISSUE 18 actuation plane in STEADY STATE
    (acceptance: ≤ 2% median): both arms run the full PR 16 sensor
    plane (monitor + detector, audit every sync); the B arm additionally
    folds the :class:`GeometryController` and :class:`DegradationLadder`
    once per interval — the controller with every candidate admissible
    and no drift, so every ``observe`` takes the steady-state
    short-circuit and decides NOTHING (asserted), which is exactly the
    cost a production loop pays on the vast majority of audits. Returns
    the median overhead in PERCENT (negative = within noise)."""
    from ..autotune import (ControllerPolicy, DegradationLadder,
                            EngineGeometry, GeometryController)
    from ..core.aggregates import SumAggregation
    from ..core.windows import SlidingWindow, WindowMeasure
    from ..engine import EngineConfig
    from ..engine.pipeline import AlignedStreamPipeline
    from ..obs.drift import DriftDetector

    windows = [SlidingWindow(WindowMeasure.Time, 8000, 1000)]

    def build(with_actuation: bool):
        p = AlignedStreamPipeline(
            windows, [SumAggregation()],
            config=EngineConfig(capacity=2048, annex_capacity=8,
                                min_trigger_pad=32),
            throughput=_round_throughput(
                throughput, AlignedStreamPipeline.slice_grid(windows,
                                                             1000)),
            wm_period_ms=1000, max_lateness=0, seed=seed, gc_every=32)
        obs = _obs.Observability()
        mon = obs.attach_workload(audit_interval_s=1e-9)
        mon.attach_detector(DriftDetector())
        p.reset()
        p.run(2, collect=False)
        p.sync()
        p.set_observability(obs)
        ctrl = ladder = None
        if with_actuation:
            base = EngineGeometry.from_pipeline(p)
            ctrl = GeometryController(
                {"base": base,
                 "alt": base.replace(batch_size=base.batch_size * 2)},
                lambda g, f: 1e9, current="base",
                policy=ControllerPolicy(confirm=2, cooldown=2,
                                        drift_window=3))
            ladder = DegradationLadder(sample_mod=4, relax_after=2,
                                       obs=obs)
        return p, mon, ctrl, ladder, obs

    pa, mon_a, _, _, _ = build(False)
    pb, mon_b, ctrl_b, ladder_b, obs_b = build(True)

    def once(p, mon, ctrl, ladder, obs) -> float:
        t0 = time.perf_counter()
        for _ in range(intervals):
            p.run(1, collect=False)
            if ctrl is not None:
                ladder.audit(budget=float("inf"))
                ctrl.observe(mon.features(), drifted=False, obs=obs)
        p.sync()
        return time.perf_counter() - t0

    def once_a() -> float:
        return once(pa, mon_a, None, None, None)

    def once_b() -> float:
        return once(pb, mon_b, ctrl_b, ladder_b, obs_b)

    once_a(), once_b()                       # warm both step paths
    a_times, b_times = [], []
    for i in range(pairs):
        # alternate within-pair order so slow drift (thermal, other
        # tenants on a shared core) cancels instead of biasing one arm
        if i % 2 == 0:
            a_times.append(once_a())
            b_times.append(once_b())
        else:
            b_times.append(once_b())
            a_times.append(once_a())
    pa.check_overflow()
    pb.check_overflow()
    assert ctrl_b.decisions == 0, \
        "steady-state overhead arm must decide nothing"
    a_times.sort()
    b_times.sort()
    return 100.0 * (b_times[len(b_times) // 2]
                    / a_times[len(a_times) // 2] - 1.0)


def run_autotune_shift_cell(cfg: BenchmarkConfig, window_spec: str,
                            agg_name: str,
                            obs: Optional[_obs.Observability] = None
                            ) -> BenchResult:
    """Autotune-shift cell (ISSUE 18 acceptance): the CLOSED loop —
    sensor plane (PR 16 WorkloadMonitor + DriftDetector on a
    ManualClock) → :class:`GeometryController` → real
    :func:`apply_geometry` retunes on a live supervised aligned
    pipeline — driven by a seeded 3-phase offered-load stream (stable →
    rate ×8 → lateness storm) and scored as THROUGHPUT UNDER A LATENCY
    SLO: each simulated second a geometry admits at most
    ``min(batch_size·4, late_capacity·8 / late_share)`` tuples inside
    the watermark interval (the PR 16 cost-law shape: the batch span
    bounds the on-time lane, the late lane bounds repair drains), and
    the :class:`DegradationLadder` guards every arm with that same
    budget, so overload degrades in counted rungs instead of falling
    over.

    Arms, all over the IDENTICAL seeded offered stream:

    * **adaptive** — controller on (bounded candidate set small / big /
      late), each decision actuated by a REAL ``apply_geometry`` retune
      (atomic manifest-sealed commit through a Supervisor) on the live
      pipeline vehicle; decisions land in the flight recorder.
    * **small / big / late** — every static candidate, controller off:
      each is mis-sized for at least one phase (small saturates at
      rate ×8, big's late lane collapses in the storm, late gives up
      on-time headroom), which is WHY the cell exists — no static
      geometry wins every phase, the adaptive arm must beat them ALL
      on total SLO-admitted tuples (``autotune_beats_all_statics``).
    * **stable** — the full-duration stable stream with the controller
      ON: zero decisions, zero retunes (the no-thrash contract).
    * **overhead** — :func:`measure_autotune_overhead`, the interleaved
      steady-state controller-on vs controller-off A/B (≤ 2% median).

    The actuation vehicle is a small aligned pipeline (its batch span
    retunes both directions bit-exactly — the twin-guarantee tests own
    that proof); the offered stream and SLO account are host-modeled so
    the cell stays deterministic and CPU-runnable, with ``platform``
    recorded alongside like every other certification cell."""
    import tempfile

    import jax

    from ..autotune import (ControllerPolicy, DegradationLadder,
                            EngineGeometry, GeometryController,
                            apply_geometry)
    from ..core.aggregates import SumAggregation
    from ..core.windows import TumblingWindow, WindowMeasure
    from ..engine import EngineConfig
    from ..engine.pipeline import AlignedStreamPipeline
    from ..obs.drift import DriftDetector
    from ..obs.workload import WorkloadMonitor
    from ..resilience.clock import ManualClock
    from ..resilience.supervisor import Supervisor
    from ..serving.cache import GeometryCache

    P = cfg.watermark_period_ms            # 1 simulated second per audit
    r0 = max(256, int(cfg.throughput))     # stable tuples per sim second
    # phase schedule in simulated seconds == audit windows; each shifted
    # phase is sized so its mis-matched statics pay for longer than the
    # adaptive arm's detect+confirm+relax transient
    phases = [("stable", 12, r0, 0.0),
              ("rate_x8", 8, r0 * 8, 0.0),
              ("late_storm", 12, r0 * 4, 0.5)]
    total_s = sum(n for _, n, _, _ in phases)

    # -- the actuation vehicle: a live supervised aligned pipeline -------
    pipe_windows = [TumblingWindow(WindowMeasure.Time, 50)]

    def factory(config=None):
        return AlignedStreamPipeline(
            pipe_windows, [SumAggregation()],
            config=config if config is not None else EngineConfig(
                capacity=1 << 12, batch_size=1024, annex_capacity=256,
                min_trigger_pad=32),
            throughput=20_000, wm_period_ms=100, max_lateness=100,
            seed=cfg.seed, gc_every=10 ** 9, value_scale=1024.0,
            collect_device_metrics=False)

    p0 = factory()
    p0.reset()
    base = EngineGeometry.from_pipeline(p0)
    # the bounded candidate set: one geometry per workload regime
    candidates = {
        "small": base.replace(late_capacity=256),         # batch 1024
        "big": base.replace(batch_size=8192, late_capacity=32),
        "late": base.replace(batch_size=2048, late_capacity=1024),
    }

    SLA_BATCHES = 4      # batches the step clears inside one interval
    LATE_DRAINS = 8      # late-lane repair drains per interval
    LATE_FLOOR = 1.0 / 64

    def sla_capacity(g: EngineGeometry, feats: dict) -> float:
        late_share = max(float(feats.get("late_share", 0.0)), LATE_FLOOR)
        return min(float(g.batch_size * SLA_BATCHES),
                   g.late_capacity * LATE_DRAINS / late_share)

    def admission(g: EngineGeometry, feats: dict) -> float:
        return sla_capacity(g, feats) \
            - float(feats.get("arrival_rate_per_s", 0.0))

    def second_stream(rng, phase: str, rate: int, late_frac: float,
                      s: int, wm: int):
        """(timestamps, n_late) for simulated second ``s`` — the storm's
        stragglers land below the current watermark but inside
        cfg.max_lateness (repairable, never silently droppable)."""
        ts = np.sort(rng.integers(0, P, size=rate)) + np.int64(s * P)
        n_late = 0
        if late_frac and wm > 0:
            late = rng.random(rate) < late_frac
            age = rng.integers(1, max(2, cfg.max_lateness // 2),
                               size=rate)
            ts = np.where(late, np.maximum(0, np.int64(wm) - age), ts)
            n_late = int(late.sum())
        return ts, n_late

    def run_arm(static_name, schedule, pipeline=None, supervisor=None):
        """One arm over ``schedule``; controller on iff ``static_name``
        is None, real retunes iff a pipeline vehicle is passed."""
        rng = np.random.default_rng(cfg.seed)   # identical offered
        arm_obs = _obs.Observability()          # stream in every arm
        clock = ManualClock()
        mon = arm_obs.attach_workload(
            WorkloadMonitor(clock=clock, audit_interval_s=1.0))
        det = DriftDetector()
        mon.attach_detector(det)
        ladder = DegradationLadder(sample_mod=4, relax_after=2,
                                   obs=arm_obs)
        ctrl = None
        if static_name is None:
            ctrl = GeometryController(
                candidates, admission, current="small",
                policy=ControllerPolicy(confirm=2, cooldown=2,
                                        drift_window=3))
        p = pipeline
        cache = GeometryCache() if p is not None else None
        sla = offered_total = within = transitions = last_rung = 0
        decisions_log = []
        s = 0
        for phase, n_seconds, rate, late_frac in schedule:
            for _ in range(n_seconds):
                wm = s * P
                ts, n_late = second_stream(rng, phase, rate, late_frac,
                                           s, wm)
                n = int(ts.shape[0])
                offered_total += n
                geom = ctrl.geometry if ctrl is not None \
                    else candidates[static_name]
                # the SLO account uses the second's EXACT stream stats
                # (identical across arms); only the controller runs on
                # the monitor's sensed features
                exact = {"arrival_rate_per_s": float(n),
                         "late_share": n_late / float(n)}
                cap = sla_capacity(geom, exact)
                keep = ladder.admit(ts, wm)
                kept = int(np.count_nonzero(keep))
                sla += min(kept, int(cap))
                if kept <= cap:
                    within += 1
                arm_obs.counter("ingest_tuples").inc(n)
                if n_late:
                    arm_obs.counter("late_tuples").inc(n_late)
                if p is not None:
                    p.run(1, collect=False)
                ev0 = det.events
                clock.advance(1.0)
                arm_obs.flight_sync(watermark=float((s + 1) * P))
                rung = ladder.audit(budget=cap)
                if rung != last_rung:
                    transitions += 1
                    last_rung = rung
                if ctrl is not None and mon.features():
                    g = ctrl.observe(mon.features(),
                                     drifted=det.events > ev0,
                                     obs=arm_obs)
                    if g is not None:
                        decisions_log.append({"second": s,
                                              "to": ctrl.current})
                        if p is not None:
                            p = apply_geometry(
                                p, g, factory=factory,
                                supervisor=supervisor,
                                pos=int(p._interval), cache=cache,
                                obs=arm_obs)
                            # detach: the arm's sensor counters model
                            # the OFFERED stream, not the vehicle's
                            p.set_observability(None)
                s += 1
        if p is not None:
            p.sync()
            p.check_overflow()
        assert ladder.conserved, "ladder accounting must be exact"
        return {"obs": arm_obs, "ctrl": ctrl, "ladder": ladder,
                "sla": sla, "offered": offered_total, "within": within,
                "transitions": transitions, "decisions": decisions_log}

    # -- adaptive arm: controller + real retunes on the live vehicle -----
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as ckpt_dir:
        sup = Supervisor(ckpt_dir, checkpoint_every=10 ** 9)
        adaptive = run_arm(None, phases, pipeline=p0, supervisor=sup)
    wall = time.perf_counter() - t0
    a_obs = adaptive["obs"]
    retunes = int(a_obs.counter(_obs.AUTOTUNE_RETUNES).value)
    retraces = int(a_obs.counter(_obs.AUTOTUNE_RETRACES).value)

    # -- every static candidate, controller off --------------------------
    statics = {name: run_arm(name, phases) for name in candidates}

    # -- stable arm: controller on, zero decisions is the contract -------
    stable = run_arm(None, [("stable", total_s, r0, 0.0)])

    # -- steady-state actuation-plane overhead ---------------------------
    overhead = round(measure_autotune_overhead(seed=cfg.seed), 2)

    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=adaptive["offered"] / wall if wall > 0 else 0.0,
        p99_emit_ms=0.0, n_windows_emitted=adaptive["sla"],
        n_tuples=adaptive["offered"], wall_s=round(wall, 3))
    res.autotune_phases = [{"phase": ph, "seconds": n, "rate": rate,
                            "late_frac": lf}
                           for ph, n, rate, lf in phases]
    res.autotune_decisions = adaptive["ctrl"].decisions
    res.autotune_retunes = retunes
    res.autotune_retraces = retraces
    res.autotune_schedule = adaptive["decisions"]
    res.adaptive_admitted = adaptive["sla"]
    res.static_admitted = {name: arm["sla"]
                           for name, arm in statics.items()}
    res.autotune_beats_all_statics = bool(
        adaptive["sla"] > max(arm["sla"] for arm in statics.values()))
    res.stable_decisions = stable["ctrl"].decisions
    res.stable_retunes = int(
        stable["obs"].counter(_obs.AUTOTUNE_RETUNES).value)
    res.degrade_transitions = adaptive["transitions"]
    res.degrade_shed_tuples = adaptive["ladder"].shed
    res.sla_ms = float(P)
    res.sla_met = round(adaptive["within"] / float(total_s), 4)
    res.autotune_overhead_pct_median = overhead
    res.platform = jax.devices()[0].platform
    finalize_observability(res, a_obs, [], 0)
    return res


def _flags_off_ab_overhead(cfg: BenchmarkConfig, windows, agg_name: str,
                           reps: int = 3) -> float:
    """Interleaved flags-off A/B (ISSUE 15 acceptance). Be precise about
    what this can and cannot measure: the flags are TRACE-time, so the
    two arms (default-constructed vs every ISSUE 15 flag pinned at its
    default) build byte-identical executables — the pins already prove
    the device side, and the flag plumbing's host branches run in BOTH
    arms. The recorded median is therefore the interleaved NOISE FLOOR
    of this box at the cell shape: the bound within which any residual
    flags-off host overhead is indistinguishable from zero. A median
    outside the ±2% acceptance band indicates environment instability
    (rerun), not flag overhead — a real regression in the default-off
    path shows up in the pins or the headline throughput gates, which
    is where the zero-impact claim actually rests."""
    import jax  # noqa: F401

    from ..engine import EngineConfig
    from ..engine.pipeline import AlignedStreamPipeline

    g = AlignedStreamPipeline.slice_grid(windows, cfg.watermark_period_ms)
    tp = _round_throughput(cfg.throughput, g)

    def mk(flagged_defaults: bool):
        kw = dict(pallas_sort_split=False, pallas_slice_merge=False,
                  pallas_packed=False, micro_batch=0) \
            if flagged_defaults else {}
        p = AlignedStreamPipeline(
            windows, [make_aggregation(agg_name)],
            config=EngineConfig(capacity=cfg.capacity, annex_capacity=8,
                                min_trigger_pad=32, **kw),
            throughput=tp, wm_period_ms=cfg.watermark_period_ms,
            max_lateness=cfg.max_lateness, seed=cfg.seed, gc_every=32)
        p.reset()
        p.run(1, collect=False)
        p.sync()                                   # compile + warm
        return p

    a, b = mk(False), mk(True)
    diffs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        a.run(1, collect=False)
        a.sync()
        ta = time.perf_counter() - t0
        t0 = time.perf_counter()
        b.run(1, collect=False)
        b.sync()
        tb = time.perf_counter() - t0
        diffs.append((tb - ta) / max(ta, 1e-9) * 100.0)
    a.check_overflow()
    b.check_overflow()
    return float(np.median(diffs))


def run_latency_headline_cell(cfg: BenchmarkConfig, window_spec: str,
                              agg_name: str,
                              obs: Optional[_obs.Observability] = None
                              ) -> BenchResult:
    """Latency-headline cell (ISSUE 14): the full ingest→emission edge
    at the headline window shape with the emission-latency tracer in
    EXACT mode — host records through ``BatchAccumulator.offer_block``
    → ``IngestRing`` → ``DeviceRingFeeder`` prefetch → the batch
    operator, watermarks through the synchronous emit face, every
    delivered window through a ``TransactionalSink`` — so each sampled
    chain carries the complete stage decomposition (arrival →
    ring_enqueue → ring_dequeue → dispatch → eligibility → drain →
    emit → sink). Recorded per cell: ``first_emit_p50/p99_ms``,
    ``latency_stages_ms`` (the stage decomposition),
    ``latency_conservation_ok`` (per-chain stage sums vs end-to-end),
    ``latency_overhead_pct_median`` (the sampling-off interleaved A/B
    arm), and an ``oracle_match`` arm bit-comparing the operator's
    emitted windows against the host simulator on the same stream."""
    import jax

    from ..autotune import EngineGeometry
    from ..delivery import TransactionalSink
    from ..engine import EngineConfig, TpuWindowOperator
    from ..ingest import LineRateFeed
    from ..obs.latency import CONSERVATION_TOL_MS, LatencyTracer

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    B = cfg.batch_size
    n_chunks = int(max(8, cfg.throughput * cfg.runtime_s // B))
    span = max(1.0, cfg.runtime_s * 1000 / n_chunks)
    off0 = max(w.clear_delay() for w in windows)
    rng = np.random.default_rng(cfg.seed)
    n_pools = min(n_chunks, 12)
    pools = []
    for _ in range(n_pools):
        ts = np.sort(rng.integers(0, max(1, int(span)),
                                  size=B)).astype(np.int64)
        vals = (rng.random(B) * 10_000).astype(np.float32)
        pools.append((vals, ts))

    def chunk(i):
        vals, ts = pools[i % n_pools]
        lo = off0 + int(i * span)
        return vals, ts + np.int64(lo), off0 + int((i + 1) * span)

    if obs is None:
        obs = _obs.Observability()
    tracer = obs.attach_latency(
        LatencyTracer(sample_every=1, exact_limit=1 << 30))
    # the measured arm's engine + ring configs derive from one geometry
    # (geometry-discipline); the comparator arms below intentionally run
    # at their OWN single-config shapes
    geom = EngineGeometry(capacity=cfg.capacity, batch_size=B,
                          ring_depth=cfg.ring_depth or 8,
                          ring_block=cfg.ring_block_size or B,
                          pallas_sort_split=cfg.pallas_sort_split,
                          pallas_slice_merge=cfg.pallas_slice_merge)
    op = TpuWindowOperator(config=geom.engine_config(
        EngineConfig(overflow_policy=cfg.overflow_policy)))
    for w in windows:
        op.add_window_assigner(w)
    op.add_aggregation(make_aggregation(agg_name))
    op.set_max_lateness(cfg.max_lateness)
    # obs passed explicitly: the ring/feed stamps must be live from the
    # first offered block (the operator's obs attaches post-warmup)
    feed = LineRateFeed(op, ring=geom.ring_config(), obs=obs)

    delivered = []
    sink = TransactionalSink(deliver=lambda w, e, s: delivered.append(w),
                             obs=obs)

    warm_hi = 0
    for i in (0, 1):
        v, t, warm_hi = chunk(i)
        feed.offer_block(v, t)
    for w_out in op.process_watermark(warm_hi + 1):
        pass                               # warm compile, discard output
    op.set_observability(obs)
    obs.registry.reset_clock()
    # warmup offers pre-stamped through the live feed while the compile
    # ran — the first measured chain must not inherit those
    tracer.reset_pending()

    next_wm = (warm_hi // cfg.watermark_period_ms + 2) \
        * cfg.watermark_period_ms
    chains = []
    _finalize = tracer._finalize

    def spy(chain):
        out = _finalize(chain)
        chains.append(out)
        return out

    tracer._finalize = spy
    emitted = 0
    t0 = time.perf_counter()
    for i in range(2, n_chunks):
        v, t, hi = chunk(i)
        feed.offer_block(v, t)
        while hi >= next_wm:
            outs = op.process_watermark(next_wm)
            for w_out in outs:
                if w_out.has_value() and sink.emit(w_out):
                    emitted += 1
            next_wm += cfg.watermark_period_ms
    feed.drain()
    for w_out in op.process_watermark(next_wm):
        if w_out.has_value() and sink.emit(w_out):
            emitted += 1
    op.check_overflow()                     # folds the parked chain too
    wall = time.perf_counter() - t0
    obs.registry.stop_clock()
    op.set_observability(None)
    tracer._finalize = _finalize
    n_tuples = (n_chunks - 2) * B

    # -- per-chain conservation + first-emit over the EXACT chain set ----
    fe_lats = []
    conserve_ok = True
    worst_gap = 0.0
    for c in chains:
        gap = abs(sum(c["stages"].values()) - c["end_to_end_ms"])
        worst_gap = max(worst_gap, gap)
        if gap > CONSERVATION_TOL_MS:
            conserve_ok = False
        if c["first_emit_ms"] is not None:
            fe_lats.append(c["first_emit_ms"])

    # -- host-simulator oracle arm: a small replica of the stream class --
    # (per-record Python feeding at the headline batch size would cost
    # minutes; the differential claim needs the WINDOW CLASS and the
    # emit path, not the record count)
    from ..simulator import SlicingWindowOperator

    P = cfg.watermark_period_ms
    B_o = 1024
    sim = SlicingWindowOperator()
    for w in windows:
        sim.add_window_assigner(w)
    sim.add_aggregation(make_aggregation(agg_name))
    sim.set_max_lateness(cfg.max_lateness)
    op2 = TpuWindowOperator(config=EngineConfig(
        capacity=cfg.capacity, batch_size=B_o,
        overflow_policy=cfg.overflow_policy))
    for w in windows:
        op2.add_window_assigner(w)
    op2.add_aggregation(make_aggregation(agg_name))
    op2.set_max_lateness(cfg.max_lateness)
    rng_o = np.random.default_rng(cfg.seed + 1)
    span_o = max(1, P // 2)
    n_o = 24                       # 12 watermark intervals of event time
    wm2 = None
    eng_rows, sim_rows = [], []
    for i in range(n_o):
        lo = off0 + i * span_o
        t = np.sort(rng_o.integers(0, span_o, size=B_o)) + np.int64(lo)
        # float32-exact integer values (the chaos-suite discipline):
        # window sums stay far below 2^24, so the engine's f32
        # accumulation and the simulator's float64 agree BIT-exactly
        # in any summation order
        v = rng_o.integers(0, 10, size=B_o).astype(np.float32)
        for j in range(B_o):
            sim.process_element(float(v[j]), int(t[j]))
        op2.process_elements(v, t.astype(np.int64))
        hi = lo + span_o
        if wm2 is None:
            wm2 = (off0 // P + 2) * P
        while i >= 2 and hi >= wm2:
            eng_rows += [(w.start, w.end, tuple(map(float, w.agg_values)))
                         for w in op2.process_watermark(wm2)
                         if w.has_value()]
            sim_rows += [(w.start, w.end, tuple(map(float, w.agg_values)))
                         for w in sim.process_watermark(wm2)
                         if w.has_value()]
            wm2 += P
    op2.check_overflow()
    oracle_match = sorted(eng_rows) == sorted(sim_rows) \
        and len(eng_rows) > 0

    # -- micro-batched streamed-emission arm (ISSUE 15 / ROADMAP 4) ------
    # The fused aligned pipeline at the cell's headline window shape,
    # split into cfg.microBatch (default 8) arrival-paced micro-batches
    # per interval with streamed per-interval fetches
    # (run_streamed(depth=0)): first-emit = flush dispatch -> result
    # fetch, decoupled from the interval's bulk ingest — the number the
    # whole-interval path pinned at ~70.8 ms p99 on this container
    # (BASELINE.md ISSUE 14 note). Recorded alongside: the pinned
    # legacy_anchor comparator arm, and a small host-simulator oracle
    # twin in the float-exact regime (bit-matching).
    from ..engine.pipeline import AlignedStreamPipeline

    M = cfg.micro_batch or 8
    g_mb = AlignedStreamPipeline.slice_grid(windows,
                                            cfg.watermark_period_ms)
    mb_obs = _obs.Observability()
    mb_tracer = mb_obs.attach_latency(
        LatencyTracer(sample_every=1, exact_limit=1 << 30))
    p_mb = AlignedStreamPipeline(
        windows, [make_aggregation(agg_name)],
        config=EngineConfig(capacity=cfg.capacity, annex_capacity=8,
                            min_trigger_pad=32, micro_batch=M,
                            pallas_sort_split=cfg.pallas_sort_split,
                            pallas_slice_merge=cfg.pallas_slice_merge),
        throughput=_round_throughput(cfg.throughput, g_mb),
        wm_period_ms=cfg.watermark_period_ms,
        max_lateness=cfg.max_lateness, seed=cfg.seed, gc_every=32)
    p_mb.micro_pace = True
    p_mb.run_streamed(2, depth=0)            # compile + warm
    p_mb.sync()
    p_mb.set_observability(mb_obs)
    mb_tracer.reset_pending()
    mb_chains = []
    _mb_fin = mb_tracer._finalize

    def _mb_spy(chain):
        out = _mb_fin(chain)
        mb_chains.append(out)
        return out

    mb_tracer._finalize = _mb_spy
    n_mb = 12
    t_mb = time.perf_counter()
    p_mb.run_streamed(n_mb, depth=0)
    mb_wall = time.perf_counter() - t_mb
    p_mb.sync()
    p_mb.check_overflow()
    mb_tracer._finalize = _mb_fin
    mb_fe = [c["first_emit_ms"] for c in mb_chains
             if c["first_emit_ms"] is not None]
    mb_gap = max((abs(sum(c["stages"].values()) - c["end_to_end_ms"])
                  for c in mb_chains), default=0.0)

    # oracle twin: micro-batched streamed pipeline vs the host simulator
    # in the float-exact regime (32 lanes/row, power-of-two value scale
    # — every window sum is exactly representable, so equality is
    # exact). The window is the cell's sliding CLASS scaled to the
    # twin's horizon (the headline 60 s window first triggers at
    # interval 60; a 62-interval float-exact twin would dominate cell
    # wall time for no extra differential power — the headline shape
    # itself is covered by the operator-path oracle arm above).
    from ..core.windows import SlidingWindow as _SW
    from ..core.windows import WindowMeasure as _WM

    mo_match = True
    mo_windows = 0
    P_mo = cfg.watermark_period_ms
    windows_mo = [_SW(_WM.Time, 4 * P_mo, P_mo)]
    p_mo = AlignedStreamPipeline(
        windows_mo, [make_aggregation(agg_name)],
        config=EngineConfig(capacity=cfg.capacity, annex_capacity=8,
                            min_trigger_pad=32, micro_batch=4),
        throughput=32 * 1000 // AlignedStreamPipeline.slice_grid(
            windows_mo, P_mo),
        wm_period_ms=P_mo,
        max_lateness=cfg.max_lateness, seed=cfg.seed + 2, gc_every=10 ** 9,
        value_scale=8.0)
    sim_mo = SlicingWindowOperator()
    for w in windows_mo:
        sim_mo.add_window_assigner(w)
    sim_mo.add_aggregation(make_aggregation(agg_name))
    sim_mo.set_max_lateness(cfg.max_lateness)
    mo_outs = p_mo.run_streamed(8, depth=0)
    for i, out_i in enumerate(mo_outs):
        v_mo, t_mo_arr = p_mo.materialize_interval(i)
        order = np.argsort(t_mo_arr, kind="stable")
        for v, t in zip(v_mo[order], t_mo_arr[order]):
            sim_mo.process_element(float(v), int(t))
        r_sim = {}
        for w in sim_mo.process_watermark(
                (i + 1) * cfg.watermark_period_ms):
            if w.has_value():
                r_sim.setdefault(
                    (w.get_start(), w.get_end()),
                    [float(x) for x in w.get_agg_values()])
        pipe = {(s, e): [float(x) for x in v]
                for (s, e, c, v) in p_mo.lowered_results(out_i)}
        mo_windows += len(pipe)
        if pipe != r_sim:
            mo_match = False
    p_mo.check_overflow()

    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=0.0,
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    # p99_emit_ms carries the DELIVERY number (eligibility -> sink),
    # not end-to-end chain time — a chain's end-to-end includes the
    # idle accumulation between watermarks (the 'eligibility' stage),
    # which is cadence, not emission latency
    for k, v in latency_stats(fe_lats).items():
        setattr(res, k, v)
    first_emit_stats(res, fe_lats)
    # micro-batched streamed-emission arm (fields; see arm above)
    res.microbatch_arms = M
    res.first_emit_microbatch_samples = len(mb_fe)
    if mb_fe:
        arr_mb = np.asarray(mb_fe)
        res.first_emit_microbatch_p50_ms = float(np.percentile(arr_mb, 50))
        res.first_emit_microbatch_p99_ms = float(np.percentile(arr_mb, 99))
    res.microbatch_conservation_ok = bool(mb_gap <= CONSERVATION_TOL_MS)
    res.microbatch_worst_chain_gap_ms = mb_gap
    res.microbatch_tps = n_mb * p_mb.tuples_per_interval / mb_wall
    res.microbatch_oracle_match = bool(mo_match and mo_windows > 0)
    res.microbatch_oracle_windows = mo_windows
    mb_snap = mb_obs.snapshot()
    res.microbatch_flushes = int(mb_snap.get("microbatch_flushes", 0))
    # flags-off interleaved A/B (ISSUE 15 acceptance: <= 2% median —
    # the host-side complement of the byte-identical HLO pins)
    res.flags_off_ab_pct_median = round(
        _flags_off_ab_overhead(cfg, windows, agg_name), 2)
    # the pinned legacy-anchor comparator (ADVICE r5 discipline): the
    # r4-era workload-identical arm recorded next to the micro numbers
    try:
        (res.legacy_anchor_tps,
         res.generator_share_legacy) = _aligned_inprogram_arm(
            cfg, windows, agg_name, legacy=True)
    except NotImplementedError as e:
        res.legacy_anchor_note = f"legacy arm unavailable: {e}"
    snap = obs.snapshot()
    from ..obs.latency import attribute

    attr = attribute(snap)
    res.latency_stages_ms = attr["stages"]
    res.latency_conservation_ok = bool(
        conserve_ok and attr["conservation_ok"])
    res.latency_worst_chain_gap_ms = worst_gap
    res.latency_chains = len(chains)
    res.oracle_match = bool(oracle_match)
    res.oracle_windows = len(eng_rows)
    res.latency_owner_stage = attr.get("owner")
    res.latency_overhead_pct_median = round(
        measure_latency_overhead(seed=cfg.seed), 2)
    res.platform = jax.devices()[0].platform
    res.host_cores = os.cpu_count()
    finalize_observability(res, obs, [], emitted, n_tuples=n_tuples)
    return res


def run_soak_cell(cfg: BenchmarkConfig, window_spec: str, agg_name: str,
                  obs: Optional[_obs.Observability] = None) -> BenchResult:
    """Soak cell (ISSUE 7): run the endurance harness at a configured
    offered load for ``soakSeconds`` of REAL wall time (SystemClock —
    the runner's ``--soak-seconds``/``--offered-rate`` flags size it:
    seconds in CI, hours on the box), seeded chaos mix on, and embed the
    full evidence bundle (audit history, conservation terms, healthz
    probes, findings) in the result row. A soak with findings is an
    ERROR cell — the ``obs diff`` gate also sees
    ``soak_invariant_failures`` appearing."""
    from ..ingest import RingConfig
    from ..soak import ChaosMix, SoakConfig, SoakRunner

    duration = cfg.soak_seconds or 5.0
    rate = cfg.offered_rate or 50_000.0
    window_ms = 1000
    for w in parse_window_spec(window_spec, seed=cfg.seed):
        # the soak target runs a simple tumbling workload; derive its
        # size from the cell's slide (a 60 s window would never close
        # inside a seconds-long CI soak)
        window_ms = int(getattr(w, "slide", None)
                        or getattr(w, "size", 1000))
        break
    scfg = SoakConfig(
        duration_s=float(duration), offered_rate=float(rate),
        chunk_records=max(64, min(4096, int(rate // 20) or 64)),
        audit_every_s=max(1.0, float(duration) / 10.0), seed=cfg.seed,
        chaos=ChaosMix(late_storm_every=13, poison_pct=0.01,
                       flaky_every=37),
        ring=RingConfig(depth=cfg.ring_depth or 8,
                        block_size=cfg.ring_block_size or 1024),
        window_ms=window_ms, allowed_lateness=cfg.max_lateness,
        delivery=cfg.delivery)
    if obs is not None and obs.flight is None:
        obs.flight = _obs.FlightRecorder(capacity=4096)
    runner = SoakRunner(scfg, obs=obs)
    t0 = time.perf_counter()
    report = runner.run()
    wall = time.perf_counter() - t0
    if not report["passed"]:
        raise RuntimeError(
            f"soak failed: {len(report['findings'])} invariant "
            f"finding(s) — first: {report['findings'][0]}")
    counters = report["counters"]
    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=report["seen"] / wall,
        p99_emit_ms=0.0,
        n_windows_emitted=int(counters.get("windows_emitted", 0)),
        n_tuples=report["seen"], wall_s=wall)
    res.soak_passed = report["passed"]
    res.soak_seen = report["seen"]
    res.soak_audits_n = len(report["audits"])
    res.soak_findings = report["findings"]
    res.soak_last_terms = report["audits"][-1]["terms"] \
        if report["audits"] else {}
    res.soak_healthz_unhealthy = sum(
        1 for h in report["healthz"] if h.get("status") != 200)
    res.soak_report = report
    # delivery guarantee (ISSUE 8): the mode, the sink's ledger
    # snapshot, and — in exactly_once mode — the measured interleaved
    # A/B cost of the ledger on the iterable run loop
    res.delivery_mode = cfg.delivery
    if report.get("delivery") is not None:
        res.delivery_snapshot = report["delivery"]
        res.delivery_overhead_pct_median = \
            measure_delivery_overhead(seed=cfg.seed)
    finalize_observability(res, obs, [], res.n_windows_emitted,
                           n_tuples=report["seen"])
    return res


def run_host_fed_cell(cfg: BenchmarkConfig, window_spec: str,
                      agg_name: str,
                      obs: Optional[_obs.Observability] = None
                      ) -> BenchResult:
    """Host-fed cell (SURVEY.md §7 stage 7): tuples originate in HOST
    memory as pre-packed (ts-delta u32, value f32) batches; the timed
    region covers host→device transfer + unpack + ingest + watermarks via
    the double-buffered HostFeed. The raw link bandwidth of the same
    packed layout is measured alongside — the honest comparison is the
    SATURATION RATIO (end-to-end vs raw link), since the engine sustains
    multi-G t/s from device-resident sources and any slower link makes a
    host-fed stream transport-bound."""
    import jax

    from ..engine import EngineConfig, TpuWindowOperator
    from ..engine.host_ingest import HostFeed, measure_link

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    B = cfg.batch_size
    n_batches = max(4, cfg.throughput * cfg.runtime_s // B)  # first 2 warm

    # pregenerate + pack OUTSIDE the timed region (the stream's origin is
    # host RAM; generation itself is the load generator's cost, which the
    # reference also excludes from its operator measurements)
    rng = np.random.default_rng(cfg.seed)
    span = cfg.runtime_s * 1000 / n_batches
    packed = []
    for i in range(n_batches):
        lo = int(i * span)
        ts = np.sort(rng.integers(lo, max(lo + 1, int((i + 1) * span)),
                                  size=B)).astype(np.int64)
        vals = rng.random(B).astype(np.float32) * 10_000
        packed.append(HostFeed.pack(vals, ts) + (int(ts[0]), int(ts[-1])))

    op = TpuWindowOperator(config=EngineConfig(
        capacity=cfg.capacity, batch_size=B,
        overflow_policy=cfg.overflow_policy))
    for w in windows:
        op.add_window_assigner(w)
    op.add_aggregation(make_aggregation(agg_name))
    op.set_max_lateness(cfg.max_lateness)
    feed = HostFeed(op)

    # warmup ON THE SAME operator/feed (compiles unpack + ingest +
    # watermark kernels and lands the valid-mask device constant): the
    # first two batches are the warm region; the timed region continues
    # the stream from batch 2 — the same discipline as _run_pipeline_cell
    feed.feed_packed(*packed[0])
    feed.feed_packed(*packed[1])
    warm_wm = packed[1][4] + 1
    op.process_watermark_async(warm_wm)
    jax.device_get(op._state.n_slices)
    if obs is not None:
        # attach AFTER warmup: warmup tuples must not pollute the counters,
        # and the rate denominator restarts at the measured region
        op.set_observability(obs)
        obs.registry.reset_clock()

    # timed region: pure pipelined flow (no syncs — emit latency is
    # sampled in a separate drained phase below, like _run_pipeline_cell)
    next_wm = (warm_wm // cfg.watermark_period_ms + 1) \
        * cfg.watermark_period_ms
    pending = []
    t0 = time.perf_counter()
    for (base, deltas, vals, lo, hi) in packed[2:]:
        feed.feed_packed(base, deltas, vals, lo, hi)
        while hi >= next_wm:
            out = op.process_watermark_async(next_wm)
            if out[3] is not None:
                pending.append((out[0].shape[0], out[3]))
            next_wm += cfg.watermark_period_ms
    out = op.process_watermark_async(next_wm)
    if out[3] is not None:
        pending.append((out[0].shape[0], out[3]))
    emitted = 0
    fetched = jax.device_get([c for _, c in pending])
    for (T, _), cnt in zip(pending, fetched):
        emitted += int((cnt[:T] > 0).sum())
    op.check_overflow()
    wall = time.perf_counter() - t0
    n_tuples = (n_batches - 2) * B
    if obs is not None:
        obs.registry.stop_clock()       # rates cover the timed region only
        op.set_observability(None)      # latency replays are not ingest

    # drained emit-latency samples: one packed batch + watermark each,
    # transfer included (that IS the host-fed delivery path). The first
    # batch is replayed time-shifted past the stream end.
    lats = []
    base0, deltas0, vals0, lo0, hi0 = packed[0]
    span0 = hi0 - lo0
    cursor = next_wm
    t_lat = time.perf_counter()
    for _ in range(LATENCY_SAMPLES_MAX):
        jax.device_get(op._state.n_slices)
        t1 = time.perf_counter()
        feed.feed_packed(np.int64(cursor), deltas0, vals0,
                         cursor, cursor + span0)
        out = op.process_watermark_async(cursor + span0 + 1)
        if out[3] is not None:
            jax.device_get((out[3], out[4]))
        else:
            jax.device_get(op._state.n_slices)
        lats.append((time.perf_counter() - t1) * 1e3)
        cursor += span0 + cfg.watermark_period_ms
        if (len(lats) >= LATENCY_SAMPLES_MIN
                and time.perf_counter() - t_lat > LATENCY_BUDGET_S):
            break

    # raw link measured twice — the MAX is the least-underestimated
    # ceiling, keeping the saturation
    # ratio ≤ ~1 (an achieved rate above "raw" would just mean the raw
    # probe caught a slow phase; r3 review)
    link_mbps = max(measure_link(B, n_batches=16),
                    measure_link(B, n_batches=16))
    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=float(np.percentile(lats, 99)) if lats else 0.0,
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    # transport context for the artifact (runner.run_config keeps extras)
    res.link_mbps_raw = link_mbps
    res.link_mbps_achieved = n_tuples * feed.bytes_per_tuple / wall / 1e6
    res.link_saturation = res.link_mbps_achieved / max(link_mbps, 1e-9)
    res.n_lat_samples = len(lats)
    res.p50_emit_ms = float(np.percentile(lats, 50))
    finalize_observability(res, obs, lats, emitted)
    return res


def run_keyed_host_fed_cell(cfg: BenchmarkConfig, window_spec: str,
                            agg_name: str,
                            obs: Optional[_obs.Observability] = None
                            ) -> BenchResult:
    """Keyed host-fed cell (VERDICT r3 item 7): (key, value, ts) records
    originate in HOST memory, pack into padded ``[K, Bk]`` rounds
    (``KeyedHostFeed`` — one vectorized argsort per round) and cross the
    real link; the timed region covers transfer + unpack + keyed ingest +
    watermarks, double-buffered. This is the reference benchmark's
    keyBy → operator boundary end to end
    (flinkBenchmark/BenchmarkJob.java:84-102). As with the single-stream
    host-fed cell, the honest score is the SATURATION RATIO against the
    raw link measured on the same byte volume."""
    import jax

    from ..engine import EngineConfig
    from ..engine.host_ingest import KeyedHostFeed, measure_link
    from ..parallel.keyed import KeyedTpuWindowOperator

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    K, Bk = cfg.n_keys, cfg.batch_size
    N = K * Bk * 3 // 4          # 75% round fill: binomial overflow of a
    #                              uniform key draw is negligible at Bk>=1k
    n_rounds = max(4, int(-(-cfg.throughput * cfg.runtime_s // N)))

    rng = np.random.default_rng(cfg.seed)
    span = cfg.runtime_s * 1000 / n_rounds

    op = KeyedTpuWindowOperator(K, config=EngineConfig(
        capacity=cfg.capacity, batch_size=Bk))
    for w in windows:
        op.add_window_assigner(w)
    op.add_aggregation(make_aggregation(agg_name))
    op.set_max_lateness(cfg.max_lateness)
    feed = KeyedHostFeed(op)

    packed = []
    for i in range(n_rounds):
        lo = int(i * span)
        ts = np.sort(rng.integers(lo, max(lo + 1, int((i + 1) * span)),
                                  size=N)).astype(np.int64)
        keys = rng.integers(0, K, size=N).astype(np.int64)
        vals = (rng.random(N) * 10_000).astype(np.float32)
        packed.append(feed.pack(keys, vals, ts)
                      + (int(ts[0]), int(ts[-1])))

    feed.feed_packed(*packed[0])
    feed.feed_packed(*packed[1])
    warm_wm = packed[1][5] + 1
    op.process_watermark_async(warm_wm)
    jax.device_get(op._state.n_slices)
    if obs is not None:
        obs.registry.reset_clock()      # rates start at the timed region

    next_wm = (warm_wm // cfg.watermark_period_ms + 1) \
        * cfg.watermark_period_ms
    pending = []
    t0 = time.perf_counter()
    for (base, deltas, vb, counts, lo, hi) in packed[2:]:
        feed.feed_packed(base, deltas, vb, counts, lo, hi)
        while hi >= next_wm:
            out = op.process_watermark_async(next_wm)
            if out[3] is not None:
                pending.append((out[0].shape[0], out[2]))
            next_wm += cfg.watermark_period_ms
    out = op.process_watermark_async(next_wm)
    if out[3] is not None:
        pending.append((out[0].shape[0], out[2]))
    fetched = jax.device_get([c for _, c in pending])
    emitted = 0
    for (T, _), cnt in zip(pending, fetched):
        emitted += int((np.asarray(cnt)[:, :T] > 0).sum())
    op.check_overflow()
    wall = time.perf_counter() - t0
    n_tuples = (n_rounds - 2) * N
    if obs is not None:
        obs.registry.stop_clock()       # rates cover the timed region only

    # drained emit-latency samples (transfer included — that IS the
    # keyed host-fed delivery path); first round replayed time-shifted
    lats = []
    base0, deltas0, vb0, counts0, lo0, hi0 = packed[0]
    span0 = hi0 - lo0
    cursor = next_wm
    t_lat = time.perf_counter()
    for _ in range(LATENCY_SAMPLES_MAX):
        jax.device_get(op._state.n_slices)
        t1 = time.perf_counter()
        feed.feed_packed(np.int64(cursor), deltas0, vb0, counts0,
                         int(cursor), int(cursor) + span0)
        out = op.process_watermark_async(cursor + span0 + 1)
        if out[3] is not None:
            jax.device_get(out[2])
        else:
            jax.device_get(op._state.n_slices)
        lats.append((time.perf_counter() - t1) * 1e3)
        cursor += span0 + cfg.watermark_period_ms
        if (len(lats) >= LATENCY_SAMPLES_MIN
                and time.perf_counter() - t_lat > LATENCY_BUDGET_S):
            break

    link_mbps = max(measure_link(K * Bk, n_batches=8),
                    measure_link(K * Bk, n_batches=8))
    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=float(np.percentile(lats, 99)) if lats else 0.0,
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    # the transfer moves the PADDED [K, Bk] rounds — that is the achieved
    # byte rate the saturation ratio must use
    res.link_mbps_raw = link_mbps
    res.link_mbps_achieved = (n_rounds - 2) * K * Bk * 8 / wall / 1e6
    res.link_saturation = res.link_mbps_achieved / max(link_mbps, 1e-9)
    res.n_lat_samples = len(lats)
    res.p50_emit_ms = float(np.percentile(lats, 50)) if lats else 0.0
    finalize_observability(res, obs, lats, emitted, n_tuples=n_tuples)
    return res


def run_keyed_cell(cfg: BenchmarkConfig, window_spec: str,
                   agg_name: str,
                   obs: Optional[_obs.Observability] = None) -> BenchResult:
    """Keyed-throughput cell: ``cfg.n_keys`` independent keyed operators as
    one batched device program (the reference's keyBy scaling model,
    KeyedScottyWindowOperator.java:56-66 — there a HashMap of JVM objects,
    here a [K, ...] slice-buffer batch; SURVEY.md §2.8).

    Preferred execution mode: the fused KeyedAlignedPipeline (one dispatch
    per watermark interval — the round-driven loop below pays a dispatch
    per [K, B] round). The stream is generated ON DEVICE and
    pre-partitioned per key — the same work split as the reference, where
    the host engine's keyBy partitions before Scotty sees the tuples;
    host-side partitioning is measured separately by bench.micro's
    host_pack phase."""
    from ..parallel.keyed import KeyedAlignedPipeline

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    try:
        from ..engine import EngineConfig

        p = KeyedAlignedPipeline(
            windows, [make_aggregation(agg_name)], n_keys=cfg.n_keys,
            config=EngineConfig(capacity=cfg.capacity, annex_capacity=8,
                                min_trigger_pad=32),
            throughput=cfg.throughput, wm_period_ms=cfg.watermark_period_ms,
            max_lateness=cfg.max_lateness, seed=cfg.seed)
        return _run_pipeline_cell(p, cfg, window_spec, agg_name, "keyed",
                                  obs=obs)
    except NotImplementedError:
        pass
    return _run_keyed_rounds_cell(cfg, windows, window_spec, agg_name,
                                  obs=obs)


def _run_keyed_rounds_cell(cfg: BenchmarkConfig, windows, window_spec: str,
                           agg_name: str,
                           obs: Optional[_obs.Observability] = None
                           ) -> BenchResult:
    """Round-driven keyed fallback for specs the fused keyed pipeline
    rejects: device-generated [K, B] rounds through
    KeyedTpuWindowOperator.ingest_device_round (pays per-round dispatch
    overhead — the fused pipeline is preferred)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..engine import EngineConfig
    from ..parallel import KeyedTpuWindowOperator

    K = cfg.n_keys
    B = max(64, cfg.batch_size // max(1, K))
    econf = EngineConfig(capacity=cfg.capacity, batch_size=B,
                         min_trigger_pad=32)

    op = KeyedTpuWindowOperator(n_keys=K, config=econf)
    for w in windows:
        op.add_window_assigner(w)
    op.add_aggregation(make_aggregation(agg_name))
    op.set_max_lateness(cfg.max_lateness)

    tuples_per_round = K * B
    rounds_per_wm = max(1, cfg.throughput * cfg.watermark_period_ms
                        // 1000 // tuples_per_round)
    span = cfg.watermark_period_ms / rounds_per_wm    # event-ms per round

    @jax.jit
    def gen_round(key, lo):
        u = jax.random.uniform(key, (2, K, B), dtype=jnp.float32)
        gaps = u[0] / jnp.sum(u[0], axis=1, keepdims=True) * span
        ts = (lo + jnp.cumsum(gaps.astype(jnp.float64), axis=1)) \
            .astype(jnp.int64)
        return ts, u[1] * 10_000.0

    valid = jax.device_put(np.ones((K, B), bool))
    root = jax.random.PRNGKey(cfg.seed)

    def feed_interval(i):
        base = i * cfg.watermark_period_ms
        for r in range(rounds_per_wm):
            lo = base + r * span
            ts, vals = gen_round(jax.random.fold_in(root, i * 4096 + r),
                                 jnp.float64(lo))
            op.ingest_device_round(ts, vals, valid,
                                   int(lo), int(lo + span))

    # warmup interval: compile generator + ingest + watermark kernels
    feed_interval(0)
    op.process_watermark_arrays(cfg.watermark_period_ms)
    jax.device_get(op._state.n_slices[0])
    if obs is not None:
        obs.registry.reset_clock()      # rates start at the timed region

    lats: list = []
    emitted = 0
    pending = []
    SAMPLE_EVERY = 4
    t0 = time.perf_counter()
    for i in range(1, cfg.runtime_s + 1):
        feed_interval(i)
        sample = i % SAMPLE_EVERY == 0
        if sample:                      # drained dispatch→host round trip
            jax.device_get(op._state.n_slices[0])
            t1 = time.perf_counter()
        out = op.process_watermark_async((i + 1) * cfg.watermark_period_ms)
        if sample:
            jax.device_get((out[2], out[3]))
            lats.append((time.perf_counter() - t1) * 1e3)
        pending.append(out)
    for out in pending:                 # bundled result drain
        ws, we, cnt, lowered = op.lower_results(*out)
        emitted += int((cnt > 0).sum())
    op.check_overflow()
    wall = time.perf_counter() - t0
    n_tuples = cfg.runtime_s * rounds_per_wm * tuples_per_round
    if obs is not None:
        obs.registry.stop_clock()       # rates cover the timed region only
    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=n_tuples / wall,
        p99_emit_ms=float(np.percentile(lats, 99)) if lats else 0.0,
        n_windows_emitted=emitted, n_tuples=n_tuples, wall_s=wall)
    finalize_observability(res, obs, lats, emitted, n_tuples=n_tuples)
    return res


def run_mesh_keyed_cell(cfg: BenchmarkConfig, window_spec: str,
                        agg_name: str,
                        obs: Optional[_obs.Observability] = None
                        ) -> BenchResult:
    """Mesh-sharded keyed cell (ISSUE 10): ``cfg.n_keys`` logical keys
    partitioned over ``cfg.n_shards`` device shards (0 = every local
    device), stepped under shard_map with donated carries and the
    in-executable psum global fold.

    Beyond the standard throughput/latency discipline the cell records
    the mesh contract:

    * ``scaling_ratio`` — aggregate throughput vs the SAME pipeline
      pinned to 1 shard at equal total load (the keys-as-scale-out-axis
      claim; on a multi-chip TPU mesh this is the near-linear number,
      on a virtual CPU mesh it is bounded by host cores —
      ``host_cores`` rides alongside so readers can tell);
    * ``oracle_match`` — sampled keys' lowered results bit-match between
      the sharded and 1-shard runs AND match a host-simulator replay of
      the materialized per-key stream;
    * ``rebalance_match`` — a twin run with a mid-run hot-key rebalance
      at a sync boundary emits bit-identical results;
    * ``per_shard_occupancy`` — the drain-point occupancy read;
    * ``shard_placement`` — ``[device id, key rows]`` of each shard of
      the timed pipeline's carried state, as JAX placed it.
    """
    import os as _os

    import jax

    from ..mesh import MeshKeyedPipeline

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    from ..engine import EngineConfig

    n_shards = cfg.n_shards or len(jax.devices())
    econf = EngineConfig(capacity=cfg.capacity, annex_capacity=8,
                         min_trigger_pad=32)

    def make(shards):
        return MeshKeyedPipeline(
            windows, [make_aggregation(agg_name)], n_keys=cfg.n_keys,
            n_shards=shards, config=econf, throughput=cfg.throughput,
            wm_period_ms=cfg.watermark_period_ms,
            max_lateness=cfg.max_lateness, seed=cfg.seed)

    p = make(n_shards)
    res = _run_pipeline_cell(p, cfg, window_spec, agg_name, "mesh-keyed",
                             obs=obs)
    res.n_keys = int(cfg.n_keys)
    res.n_shards = int(n_shards)
    res.per_shard_occupancy = [round(float(v), 4)
                               for v in p.shard_occupancy()]
    res.shard_placement = sorted(
        [int(sh.device.id), int(sh.data.shape[0])]
        for sh in p.state["keys"].addressable_shards)
    res.platform = jax.devices()[0].platform
    res.host_cores = _os.cpu_count()

    # -- 1-shard pin at equal total load (the scaling denominator). The
    # single [K, ...] program's wall time is allocator/page-cache noisy
    # on shared hosts, so the denominator is the BEST of three timed
    # segments — understating the ratio is the conservative direction.
    timed = max(3, min(cfg.runtime_s, 6))
    p1 = make(1)
    p1.reset()
    p1.run(3, collect=False)
    p1.sync()
    best1 = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        p1.run(timed, collect=False)
        p1.sync()
        best1 = min(best1, (time.perf_counter() - t0) / timed)
    p1.check_overflow()
    res.tuples_per_sec_1shard = p1.tuples_per_interval / best1
    res.scaling_ratio = res.tuples_per_sec / max(
        res.tuples_per_sec_1shard, 1e-9)

    # -- differential arms (short runs; bit-equality is the assertion) ----
    if cfg.n_keys < 4:
        raise ValueError(
            "MeshKeyed cells need nKeys >= 4 (the differential arms "
            "sample and swap distinct keys)")
    sample_keys = sorted({0, cfg.n_keys // 3, cfg.n_keys - 1})
    pa, pb = make(n_shards), make(1)
    pa.reset(), pb.reset()
    oracle_match = True
    from .. import SlicingWindowOperator

    sim = SlicingWindowOperator()
    for w in windows:
        sim.add_window_assigner(w)
    sim.add_aggregation(make_aggregation(agg_name))
    sim.set_max_lateness(cfg.max_lateness)
    sim_key = sample_keys[1]
    for i in range(3):
        a = pa.run(1)[0]
        b = pb.run(1)[0]
        for kk in sample_keys:
            if pa.lowered_results_for_key(a, kk) \
                    != pb.lowered_results_for_key(b, kk):
                oracle_match = False
        vals, ts = pa.materialize_interval(i, sim_key)
        order = np.argsort(ts, kind="stable")
        sim.process_elements(vals[order], ts[order])
        want = {}
        for w in sim.process_watermark((i + 1) * cfg.watermark_period_ms):
            if w.has_value():
                want.setdefault((w.get_start(), w.get_end()),
                                w.get_agg_values())
        got = {(s, e): v for (s, e, c, v)
               in pa.lowered_results_for_key(a, sim_key)}
        if set(got) != set(want):
            oracle_match = False
        else:
            for k2 in want:
                for x, y in zip(want[k2], got[k2]):
                    if abs(float(x) - float(y)) \
                            > 2e-4 * max(1.0, abs(float(x))):
                        oracle_match = False
    pa.check_overflow()
    res.oracle_match = bool(oracle_match)

    rebalance_match = True
    if getattr(cfg, "mesh_rebalance", True):
        pr, pn = make(n_shards), make(n_shards)
        pr.reset(), pn.reset()
        pr.run(2, collect=False), pn.run(2, collect=False)
        pr.sync()
        # a deterministic "hot-key" plan: the generated load is uniform,
        # so the cell validates the MECHANISM (mid-run row migration at a
        # sync boundary) — skew-driven detection is the engine API's job
        pr.rebalance([(0, cfg.n_keys // 2),
                      (1, min(cfg.n_keys // 2 + 1, cfg.n_keys - 1))])
        for i in range(2):
            a = pr.run(1)[0]
            b = pn.run(1)[0]
            for kk in (0, 1, cfg.n_keys // 2, cfg.n_keys - 1):
                if pr.lowered_results_for_key(a, kk) \
                        != pn.lowered_results_for_key(b, kk):
                    rebalance_match = False
        pr.check_overflow()
        # deliberately NOT counted as mesh_rebalances: the arm validates
        # the migration mechanism on a balanced stream — the gated counter
        # means a hot-key-DRIVEN rebalance fired, and a seeded bench run
        # must export it as zero so the obs-diff default gate stays armed
    res.rebalance_match = bool(rebalance_match)
    return res


def run_config(cfg: BenchmarkConfig, out_dir: str = "bench_results",
               echo=None, collect_metrics: bool = True,
               obs_dir: Optional[str] = None,
               serve_port: Optional[int] = None,
               flight_capacity: Optional[int] = None,
               health_lag_ms: Optional[float] = None,
               health_first_emit_ms: Optional[float] = None,
               fingerprint_ref: Optional[str] = None) -> List[dict]:
    """All cells of one config; writes result_<name>.json (each cell row
    carries a ``metrics`` section unless ``collect_metrics=False``). With
    ``obs_dir``, additionally exports a per-config JSONL time series (one
    snapshot row per cell — ``python -m scotty_tpu.obs report`` summarizes
    it) and per-cell Chrome-trace span files.

    ``serve_port`` (ISSUE 4) starts ONE live ``/metrics``·``/vars``·
    ``/healthz`` endpoint for the whole config run, always answering for
    the currently-running cell's registry (503 before the first cell,
    between cells, and after the last — the live reference is cleared as
    each cell completes); ``flight_capacity`` attaches a FlightRecorder
    of that many ring slots to every cell's Observability (wraparound
    drops surface as the gated ``flight_dropped_events`` counter);
    ``health_lag_ms`` arms the ``/healthz`` watermark-lag check;
    ``health_first_emit_ms`` arms the windowed first-emit p99 check
    (ISSUE 14 — the unhealthy verdict names the owning stage);
    ``fingerprint_ref`` (ISSUE 16) loads a recorded workload fingerprint
    (any export ``obs drift`` accepts) and attaches a WorkloadMonitor +
    DriftDetector referencing it to every cell's Observability — live
    cells then count the gated ``workload_drift_events`` whenever the
    stream moves off the certified workload point."""
    if echo is None:
        echo = _stdout
    rows = []
    cell_idx = 0
    rtt_floor = round(measure_rtt_floor(), 2)
    echo(f"  (drained device->host round-trip floor: {rtt_floor} ms — "
         "lower-bounds every emit-latency sample)")
    if obs_dir and not collect_metrics:
        echo("  (--obs-dir ignored: observability is disabled)")
        obs_dir = None
    if obs_dir:
        os.makedirs(obs_dir, exist_ok=True)
        # truncate: result_<name>.json is overwritten per run, so the
        # sibling JSONL must not accumulate stale rows across runs
        open(os.path.join(obs_dir, f"metrics_{cfg.name}.jsonl"),
             "w").close()
    live = {"obs": None}                 # the endpoint reads the live cell

    ref_fp = None
    if fingerprint_ref:
        from ..obs.drift import load_fingerprint

        ref_fp = load_fingerprint(fingerprint_ref)
        if ref_fp is None:
            echo(f"  (--fingerprint-ref {fingerprint_ref}: no workload "
                 "fingerprint found — drift baseline not armed)")
        else:
            echo(f"  drift baseline: {fingerprint_ref} "
                 f"({len(ref_fp.features)} feature(s), "
                 f"{ref_fp.audits} audit(s))")

    def make_obs():
        flight = None
        if flight_capacity:
            flight = _obs.FlightRecorder(capacity=flight_capacity)
        o = _obs.Observability(flight=flight)
        if ref_fp is not None:
            from ..obs.drift import DriftDetector

            o.attach_workload().attach_detector(
                DriftDetector(reference=ref_fp))
        live["obs"] = o
        return o

    server = None
    if serve_port is not None and collect_metrics:
        from ..obs.server import HealthPolicy, serve as _serve

        health = HealthPolicy(max_watermark_lag_ms=health_lag_ms,
                              max_first_emit_p99_ms=health_first_emit_ms)
        server = _serve(lambda: live["obs"], port=serve_port,
                        health=health)
        echo(f"  live obs endpoint: http://127.0.0.1:{server.port}"
             "/metrics | /vars | /healthz (per running cell)")
    from .. import pallas as _pallas

    try:
        # ONE interpreter-mode context across all cells (ISSUE 15 small
        # fix): the Pallas interpret choice is a run-wide property of
        # the backend — pin it once here so every cell's kernels share
        # one resolution instead of re-entering (and re-resolving) the
        # context per cell
        with _pallas.interpret_mode(not _pallas.backend_is_tpu()):
            return _run_config_cells(cfg, out_dir, echo, collect_metrics,
                                     obs_dir, make_obs, live, rows,
                                     cell_idx, rtt_floor)
    finally:
        if server is not None:
            server.close()


def _run_config_cells(cfg, out_dir, echo, collect_metrics, obs_dir,
                      make_obs, live, rows, cell_idx,
                      rtt_floor) -> List[dict]:
    for window_spec in (cfg.window_configurations or ["Tumbling(1000)"]):
        for engine in cfg.configurations:
            for agg_name in cfg.agg_functions:
                t0 = time.perf_counter()
                try:
                    res = run_cell(cfg, window_spec, agg_name, engine,
                                   collect_metrics=collect_metrics,
                                   make_obs=make_obs)
                except Exception as e:        # one bad cell must not void
                    rows.append({              # the already-computed ones
                        "name": cfg.name, "windows": window_spec,
                        "aggregation": agg_name, "engine": engine,
                        "error": f"{type(e).__name__}: {e}",
                        "cell_wall_s": round(time.perf_counter() - t0, 2)})
                    echo(f"  {window_spec:28s} {engine:10s} {agg_name:8s} "
                         f"ERROR {type(e).__name__}: {e}")
                    continue
                finally:
                    # 503 between cells: a finished cell's frozen registry
                    # must not masquerade as the live pipeline
                    live["obs"] = None
                cell = dict(res.to_dict(), engine=engine,
                            cell_wall_s=round(time.perf_counter() - t0, 2))
                cell["rtt_floor_ms"] = rtt_floor
                for extra in CELL_EXTRA_FIELDS:
                    if hasattr(res, extra):
                        cell[extra] = getattr(res, extra)
                rows.append(cell)
                cell_obs = getattr(res, "observability", None)
                if obs_dir and cell_obs is not None:
                    label = f"{window_spec}|{engine}|{agg_name}"
                    cell_obs.write_jsonl(
                        os.path.join(obs_dir, f"metrics_{cfg.name}.jsonl"),
                        label=label)
                    cell_obs.write_chrome_trace(os.path.join(
                        obs_dir, f"trace_{cfg.name}_{cell_idx}.json"))
                cell_idx += 1
                echo(f"  {window_spec:28s} {engine:10s} {agg_name:8s} "
                     f"{res.tuples_per_sec:15,.0f} t/s  "
                     f"p99={res.p99_emit_ms:8.1f} ms  "
                     f"windows={res.n_windows_emitted}")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"result_{cfg.name}.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    echo(f"  -> {path}")
    if obs_dir:
        echo(f"  -> {obs_dir}/metrics_{cfg.name}.jsonl (summarize with "
             f"`python -m scotty_tpu.obs report`)")
    return rows


def load_config(path: str) -> BenchmarkConfig:
    cfg = BenchmarkConfig.from_json(path)
    with open(path) as f:
        raw = json.load(f)
    cfg.buckets_throughput = raw.get("bucketsThroughput")
    return cfg




def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import shutil
    import tempfile

    ap = argparse.ArgumentParser(
        prog="python -m scotty_tpu.bench",
        description="Config-driven window-aggregation benchmark runner")
    ap.add_argument("configs", nargs="*",
                    help="JSON config paths (default: bundled configs)")
    ap.add_argument("--out-dir", default="bench_results")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="export per-config JSONL metrics time series + "
                         "per-cell Chrome-trace span files into DIR")
    ap.add_argument("--no-obs", action="store_true",
                    help="disable observability entirely (no metrics "
                         "section in results; the overhead A/B baseline)")
    ap.add_argument("--gate", default=None, metavar="THRESHOLDS",
                    help="regression gate: after each config runs, diff "
                         "its fresh result_<name>.json against the "
                         "baseline copy (--baseline-dir, default the "
                         "pre-run file in --out-dir) under this "
                         "threshold JSON (python -m scotty_tpu.obs diff "
                         "semantics; pass 'default' for the built-in "
                         "thresholds); exit nonzero on any regression")
    ap.add_argument("--baseline-dir", default=None, metavar="DIR",
                    help="where baseline result_<name>.json files live "
                         "(with --gate; default: --out-dir, snapshotted "
                         "before each run overwrites it)")
    ap.add_argument("--overflow-policy", default=None, metavar="POLICY",
                    choices=("fail", "shed", "grow"),
                    help="override every config's EngineConfig."
                         "overflow_policy (scotty_tpu.resilience); "
                         "'fail' is the benchmarked default")
    ap.add_argument("--serve-port", default=None, type=int, metavar="PORT",
                    help="serve a live /metrics | /vars | /healthz "
                         "endpoint for the currently-running cell "
                         "(0 = ephemeral port, printed at startup); "
                         "ignored with --no-obs")
    ap.add_argument("--flight-capacity", default=None, type=int,
                    metavar="N",
                    help="attach an N-slot flight recorder "
                         "(scotty_tpu.obs.FlightRecorder) to every "
                         "cell's Observability; ring-wraparound drops "
                         "surface as the gated flight_dropped_events "
                         "counter")
    ap.add_argument("--health-lag-ms", default=None, type=float,
                    metavar="MS",
                    help="arm the /healthz watermark-lag check "
                         "(scotty_tpu.obs.HealthPolicy): verdicts flip "
                         "unhealthy while watermark_lag_ms exceeds MS")
    ap.add_argument("--health-first-emit-ms", default=None, type=float,
                    metavar="MS",
                    help="arm the /healthz windowed first-emit check "
                         "(scotty_tpu.obs.HealthPolicy."
                         "max_first_emit_p99_ms): verdicts flip "
                         "unhealthy while p99 first-emit latency over "
                         "the recent sample window exceeds MS, naming "
                         "the stage that owns the critical path")
    ap.add_argument("--fingerprint-ref", default=None, metavar="FILE",
                    help="arm live workload-drift detection against the "
                         "fingerprint recorded in FILE (any export "
                         "`python -m scotty_tpu.obs drift` accepts: a "
                         "result_<name>.json, a /vars dump, or bare "
                         "fingerprint JSON); every cell gets a "
                         "WorkloadMonitor + DriftDetector referencing "
                         "it, and sustained excursions count the gated "
                         "workload_drift_events; ignored with --no-obs")
    ap.add_argument("--soak-seconds", default=None, type=float,
                    metavar="S",
                    help="override every config's soakSeconds (the Soak "
                         "cell's REAL wall-clock duration: seconds in "
                         "CI, hours on the box)")
    ap.add_argument("--offered-rate", default=None, type=float,
                    metavar="R",
                    help="override every config's offeredRate (Soak "
                         "cell offered load, records/second)")
    ap.add_argument("--delivery", default=None, metavar="MODE",
                    choices=("at_least_once", "exactly_once"),
                    help="override every config's delivery guarantee "
                         "for connector-backed cells (scotty_tpu."
                         "delivery, ISSUE 8): 'at_least_once' (the "
                         "benchmarked default, no ledger) or "
                         "'exactly_once' (epoch-ledger TransactionalSink "
                         "with its measured A/B overhead recorded in "
                         "the cell row)")
    args = ap.parse_args(argv)

    paths = args.configs
    if not paths:
        here = os.path.join(os.path.dirname(__file__), "configurations")
        paths = sorted(
            os.path.join(here, f) for f in os.listdir(here)
            if f.endswith(".json"))
    gate_failures = 0
    for path in paths:
        cfg = load_config(path)
        if args.overflow_policy:
            cfg.overflow_policy = args.overflow_policy
        if args.soak_seconds is not None:
            cfg.soak_seconds = args.soak_seconds
        if args.offered_rate is not None:
            cfg.offered_rate = args.offered_rate
        if args.delivery is not None:
            cfg.delivery = args.delivery
        _stdout(f"== {cfg.name} ({path})")
        baseline_snap = None
        if args.gate:
            src = os.path.join(args.baseline_dir or args.out_dir,
                               f"result_{cfg.name}.json")
            if os.path.exists(src):
                # snapshot BEFORE run_config overwrites result_<name>.json
                fd, baseline_snap = tempfile.mkstemp(suffix=".json")
                os.close(fd)
                shutil.copyfile(src, baseline_snap)
        run_config(cfg, out_dir=args.out_dir,
                   collect_metrics=not args.no_obs, obs_dir=args.obs_dir,
                   serve_port=args.serve_port,
                   flight_capacity=args.flight_capacity,
                   health_lag_ms=args.health_lag_ms,
                   health_first_emit_ms=args.health_first_emit_ms,
                   fingerprint_ref=args.fingerprint_ref)
        if args.gate:
            if baseline_snap is None:
                _stdout(f"  gate: no baseline for {cfg.name} — skipped "
                        "(first run records the baseline)")
                continue
            from ..obs.diff import diff_main

            th = None if args.gate == "default" else args.gate
            rc = diff_main(baseline_snap,
                           os.path.join(args.out_dir,
                                        f"result_{cfg.name}.json"),
                           thresholds_path=th, echo=_stdout)
            os.unlink(baseline_snap)
            if rc:
                gate_failures += 1
    if gate_failures:
        _stdout(f"GATE FAILED: {gate_failures} config(s) regressed")
        return 1
    return 0

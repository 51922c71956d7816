"""Config-driven throughput harness.

Mirrors the reference benchmark module (SURVEY.md §2.5): BenchmarkRunner's
JSON configs with the window-spec string DSL (benchmark/.../BenchmarkRunner.java:96-171),
LoadGeneratorSource (:10-87), ThroughputLogger/ThroughputStatistics (:24-49,
:3-44) — re-designed for batched device execution: the generator produces
event-time batches, the logger samples tuples/s per batch interval, and the
runner reports mean throughput + p99 window-emit latency per configuration.
"""

from __future__ import annotations

import contextlib
import json
import re
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from .. import obs as _obs
from ..obs import latency as _late
from ..core.aggregates import (
    BUILTIN_AGGREGATIONS,
    AggregateFunction,
    CountAggregation,
    DDSketchQuantileAggregation,
    HyperLogLogAggregation,
    MaxAggregation,
    MeanAggregation,
    MinAggregation,
    SumAggregation,
)
from ..core.windows import (
    FixedBandWindow,
    SessionWindow,
    SlidingWindow,
    TumblingWindow,
    Window,
    WindowMeasure,
)


# ---------------------------------------------------------------------------
# Window-spec DSL (BenchmarkRunner.java:96-171)
# ---------------------------------------------------------------------------

_SPEC_RE = re.compile(r"^\s*(\w+)\s*\(([^)]*)\)\s*$")


def parse_window_spec(spec: str, seed: int = 0) -> List[Window]:
    """Parse the reference's window-spec strings:

    ``Tumbling(size)``, ``Sliding(size,slide)``, ``Session(gap)``,
    ``FixedBand(start,size)``, ``CountTumbling(size)``,
    ``randomTumbling(n,min,max)``, ``RandomSession(n,min,max)``,
    ``randomCount(n,min,max)`` — random variants use a fixed seed like the
    reference (BenchmarkRunner.java:96-171). Specs joined with ``+`` build
    a multi-window workload cell (e.g. ``Session(1000)+Sliding(60000,1000)``
    — the BASELINE config-5 mix).
    """
    if "+" in spec:
        out: List[Window] = []
        for part in spec.split("+"):
            out.extend(parse_window_spec(part.strip(), seed=seed))
        return out
    m = _SPEC_RE.match(spec)
    if not m:
        raise ValueError(f"bad window spec: {spec!r}")
    name, args_s = m.group(1), m.group(2)
    args = [int(a) for a in args_s.replace(" ", "").split(",") if a]
    T, C = WindowMeasure.Time, WindowMeasure.Count
    rng = np.random.default_rng(seed)
    name_l = name.lower()
    if name_l == "tumbling":
        return [TumblingWindow(T, args[0])]
    if name_l == "sliding":
        return [SlidingWindow(T, args[0], args[1])]
    if name_l == "session":
        return [SessionWindow(T, args[0])]
    if name_l == "fixedband":
        return [FixedBandWindow(T, args[0], args[1])]
    if name_l == "counttumbling":
        return [TumblingWindow(C, args[0])]
    if name_l == "countsliding":
        return [SlidingWindow(C, args[0], args[1])]
    if name_l == "randomtumbling":
        n, lo, hi = args
        return [TumblingWindow(T, int(rng.integers(lo, hi)))
                for _ in range(n)]
    if name_l == "randomsession":
        n, lo, hi = args
        return [SessionWindow(T, int(rng.integers(lo, hi))) for _ in range(n)]
    if name_l == "randomcount":
        n, lo, hi = args
        return [TumblingWindow(C, int(rng.integers(lo, hi)))
                for _ in range(n)]
    if name_l == "cappedsession":
        from ..core.windows import CappedSessionWindow

        return [CappedSessionWindow(T, args[0], args[1])]
    if name_l == "genericsession":
        from ..core.windows import GenericSessionWindow

        return [GenericSessionWindow(T, args[0])]
    raise ValueError(f"unknown window spec {name!r}")


def make_aggregation(name: str) -> AggregateFunction:
    """Aggregation factory by config name (benchmark aggFunctions)."""
    key = name.lower()
    table = {
        "sum": SumAggregation, "count": CountAggregation,
        "min": MinAggregation, "max": MaxAggregation,
        "mean": MeanAggregation,
    }
    if key in table:
        return table[key]()
    if key in ("quantile", "ddsketch"):
        return DDSketchQuantileAggregation(0.5)
    if key in ("hll", "distinct"):
        return HyperLogLogAggregation(8)
    if key in ("cms", "countmin"):
        from ..core.aggregates import CountMinSketchAggregation

        # target 2500.0: an arbitrary fixed point query in the generators'
        # [0, 10000) value range — the cell measures sketch-ingest cost,
        # not the answer to one heavy hitter
        return CountMinSketchAggregation(2500.0, depth=4, width=256)
    raise ValueError(f"unknown aggregation {name!r} "
                     f"(known: {sorted(BUILTIN_AGGREGATIONS)})")


# ---------------------------------------------------------------------------
# Config (BenchmarkConfig.java:8-29)
# ---------------------------------------------------------------------------


@dataclass
class BenchmarkConfig:
    name: str = "bench"
    throughput: int = 10_000_000           # offered tuples per event-second
    runtime_s: int = 10                    # event-time seconds to simulate
    window_configurations: List[str] = field(default_factory=list)
    configurations: List[str] = field(default_factory=lambda: ["TpuEngine"])
    agg_functions: List[str] = field(default_factory=lambda: ["sum"])
    watermark_period_ms: int = 1000
    batch_size: int = 1 << 15
    capacity: int = 1 << 17
    n_keys: int = 1
    out_of_order_pct: float = 0.0
    max_lateness: int = 1000
    seed: int = 42
    #: record-buffer rows for count-measure cells (0 = EngineConfig's
    #: 4x capacity default); live records span
    #: (lateness + count clear-delays + period) x throughput
    record_capacity: int = 0
    #: {"count": N, "minGapMs": a, "maxGapMs": b} — N silent spans at random
    #: event-time positions (the reference's session gaps,
    #: LoadGeneratorSource.java:60-76, generated BenchmarkRunner.java:174-192).
    #: Without them a constant-rate stream is one session that never closes.
    session_config: Optional[dict] = None
    #: pin the r4-era generator (32-bit value draws + per-tuple offset
    #: stream) so cross-round comparisons keep one workload-identical
    #: anchor cell (ADVICE r5); aligned-pipeline cells only
    legacy_generator: bool = False
    #: EngineConfig.overflow_policy for every engine the cells build:
    #: "fail" (the benchmarked default),
    #: "shed" or "grow" (scotty_tpu.resilience) for degraded-mode A/Bs
    overflow_policy: str = "fail"
    #: ShaperConfig.late_capacity for the ShapedOOO cell (ISSUE 5);
    #: 0 = the shaper default, max(64, batch_size // 8)
    shaper_late_capacity: int = 0
    #: inter-batch disorder back-reach (event-ms) of the ShapedOOO cell's
    #: adversarial stream; 0 = min(max_lateness, batch span / 8)
    shaper_back_ms: int = 0
    #: QueryChurn cell (ISSUE 6): total register+cancel operations the
    #: seeded churn schedule performs mid-stream (the acceptance floor is
    #: >= 1000)
    churn_ops: int = 1024
    #: peak concurrently-active queries (QueryAdmission.max_queries; the
    #: slot grid is pre-padded to this, so steady-state churn never
    #: rebuckets)
    churn_max_active: int = 256
    #: tenants the churn schedule round-robins registrations over
    churn_tenants: int = 4
    #: replay the same churn schedule through an always-active superset
    #: oracle and bit-compare per-query emissions (doubles cell wall time)
    churn_oracle: bool = True
    #: ingest-ring staging depth for the IngestExternal/Soak cells
    #: (ISSUE 7); 0 = the RingConfig default (8)
    ring_depth: int = 0
    #: ring staging-block rows; 0 = the cell's batch size (IngestExternal)
    #: / 1024 (Soak)
    ring_block_size: int = 0
    #: Soak cell wall-clock duration (SystemClock seconds; the runner's
    #: --soak-seconds flag overrides); 0 = the 5 s CI default
    soak_seconds: float = 0.0
    #: Soak cell offered load (records per second; --offered-rate
    #: overrides); 0 = the 50 000/s default
    offered_rate: float = 0.0
    #: MeshKeyed cell (ISSUE 10): device shards the key axis partitions
    #: over; 0 = every local device
    n_shards: int = 0
    #: run the MeshKeyed cell's mid-run-rebalance differential arm (a
    #: twin run migrates keys at a sync boundary and emissions must
    #: bit-match the unmoved twin)
    mesh_rebalance: bool = True
    #: QueryChurnMesh cell (ISSUE 13): ``[[interval, shards], ...]`` —
    #: live reshard to ``shards`` before the named TIMED interval runs
    #: (a checkpoint-boundary operation under the cell's Supervisor);
    #: the superset oracle replays the same schedule so the global psum
    #: folds stay bit-comparable. Empty = no reshard.
    mesh_reshard_schedule: List[list] = field(default_factory=list)
    #: delivery guarantee for connector-backed cells (ISSUE 8; the
    #: runner's --delivery flag overrides): "at_least_once" (the
    #: benchmarked default — no ledger) or "exactly_once" (a
    #: TransactionalSink sequences every emission and its epoch ledger
    #: commits with each supervisor checkpoint; the cell records the
    #: ledger's overhead alongside)
    delivery: str = "at_least_once"
    #: ISSUE 15 (threaded into EngineConfig like overflowPolicy): Pallas
    #: bucketed sort-split for shaped device batches
    pallas_sort_split: bool = False
    #: Pallas segmented-reduce slice-merge for the dense-ingest fold and
    #: the aligned/keyed/mesh generator lifts
    pallas_slice_merge: bool = False
    #: micro-batches per interval for streamed emission
    #: (FusedPipelineDriver.run_streamed; 0 = whole-interval steps) —
    #: the LatencyHeadline cell's micro-batched first-emit arm reads it
    micro_batch: int = 0
    #: SloChurn cell (ISSUE 19): tenants sharing the served grid; the
    #: seeded HOT one offers ``slo_hot_factor`` times its fair share of
    #: registrations and tuples and must trip exactly its own budget
    slo_tenants: int = 6
    #: offered-load multiplier of the hot tenant vs a fair share
    slo_hot_factor: int = 8
    #: delivered-share SLO objective each tenant is held to
    slo_delivered_share: float = 0.90
    #: fast+slow burn-rate threshold that latches an slo_burn event
    slo_burn_threshold: float = 2.0

    @staticmethod
    def from_json(path: str) -> "BenchmarkConfig":
        with open(path) as f:
            raw = json.load(f)
        return BenchmarkConfig(
            name=raw.get("name", "bench"),
            throughput=raw.get("throughput", 10_000_000),
            runtime_s=raw.get("runtime", raw.get("runtime_s", 10)),
            window_configurations=raw.get("windowConfigurations", []),
            configurations=raw.get("configurations", ["TpuEngine"]),
            agg_functions=raw.get("aggFunctions", ["sum"]),
            watermark_period_ms=raw.get("watermarkPeriodMs", 1000),
            batch_size=raw.get("batchSize", 1 << 15),
            capacity=raw.get("capacity", 1 << 17),
            record_capacity=raw.get("recordCapacity", 0),
            n_keys=raw.get("nKeys", 1),
            out_of_order_pct=raw.get("outOfOrderPct", 0.0),
            max_lateness=raw.get("maxLateness", 1000),
            seed=raw.get("seed", 42),
            session_config=raw.get("sessionConfig"),
            legacy_generator=raw.get("legacyGenerator", False),
            overflow_policy=raw.get("overflowPolicy", "fail"),
            shaper_late_capacity=raw.get("shaperLateCapacity", 0),
            shaper_back_ms=raw.get("shaperBackMs", 0),
            churn_ops=raw.get("churnOps", 1024),
            churn_max_active=raw.get("churnMaxActive", 256),
            churn_tenants=raw.get("churnTenants", 4),
            churn_oracle=raw.get("churnOracle", True),
            ring_depth=raw.get("ringDepth", 0),
            ring_block_size=raw.get("ringBlockSize", 0),
            soak_seconds=raw.get("soakSeconds", 0.0),
            offered_rate=raw.get("offeredRate", 0.0),
            delivery=raw.get("delivery", "at_least_once"),
            n_shards=raw.get("nShards", 0),
            mesh_rebalance=raw.get("meshRebalance", True),
            mesh_reshard_schedule=raw.get("meshReshardSchedule", []),
            pallas_sort_split=raw.get("pallasSortSplit", False),
            pallas_slice_merge=raw.get("pallasSliceMerge", False),
            micro_batch=raw.get("microBatch", 0),
            slo_tenants=raw.get("sloTenants", 6),
            slo_hot_factor=raw.get("sloHotFactor", 8),
            slo_delivered_share=raw.get("sloDeliveredShare", 0.90),
            slo_burn_threshold=raw.get("sloBurnThreshold", 2.0),
        )


# ---------------------------------------------------------------------------
# Load generator (LoadGeneratorSource.java:10-87, device-batch edition)
# ---------------------------------------------------------------------------


def generate_batches(cfg: BenchmarkConfig):
    """Pre-generate the whole stream as numpy batches: values f32, event-time
    ms i64 (ascending, with optional bounded disorder), watermark points every
    ``watermark_period_ms`` of event time. ``cfg.session_config`` inserts
    silent event-time spans (session gaps) by stretching timestamps past
    randomly placed gap positions — the reference generator's pause
    mechanism (LoadGeneratorSource.java:60-76)."""
    rng = np.random.default_rng(cfg.seed)
    n_total = cfg.throughput * cfg.runtime_s
    B = cfg.batch_size
    n_batches = max(1, n_total // B)
    span_ms = cfg.runtime_s * 1000
    gap_starts = gap_cum = None
    if cfg.session_config:
        sc = cfg.session_config
        n_gaps = int(sc.get("count", 8))
        gmin = int(sc.get("minGapMs", 1000))
        gmax = int(sc.get("maxGapMs", 5000))
        gap_starts = np.sort(rng.integers(0, span_ms, size=n_gaps))
        gap_lens = rng.integers(gmin, max(gmin + 1, gmax), size=n_gaps)
        gap_cum = np.cumsum(gap_lens)
    batches = []
    per_batch_span = span_ms / n_batches
    for i in range(n_batches):
        lo = i * per_batch_span
        ts = np.sort(rng.integers(int(lo), int(lo + per_batch_span),
                                  size=B)).astype(np.int64)
        if gap_starts is not None:
            # every tuple past gap k shifts by the total length of gaps
            # 1..k → silent spans appear exactly at the gap positions
            idx = np.searchsorted(gap_starts, ts, side="right")
            ts = ts + np.where(idx > 0, gap_cum[np.maximum(idx - 1, 0)], 0)
        if cfg.out_of_order_pct > 0:
            late = rng.random(B) < cfg.out_of_order_pct
            ts = np.where(
                late, np.maximum(ts - rng.integers(
                    0, cfg.max_lateness, size=B), 0), ts).astype(np.int64)
        vals = rng.integers(1, 10_000, size=B).astype(np.float32)
        batches.append((vals, ts))
    return batches


def make_device_source(cfg: BenchmarkConfig):
    """Device-resident load generator — the TPU-native analogue of the
    reference's in-process LoadGeneratorSource (LoadGeneratorSource.java:10-87):
    tuples are synthesized on-chip (sorted event times via a cumulative-gap
    construction — no device sort needed), so host→device bandwidth never
    bounds the measured operator throughput, exactly as the reference's
    generator never crosses a process boundary.

    With ``cfg.out_of_order_pct > 0`` the generator emits an extra LATE
    sub-batch per base batch (that fraction of tuples, displaced back by up
    to ``cfg.max_lateness`` ms, sorted) — delivered separately so only the
    small sub-batch pays the general kernel's late/annex machinery, while
    the in-order base stream takes the dense fast path.

    Returns ``gen(i) -> (vals, ts, ts_min, ts_max)``; when OOO is enabled,
    ``gen.gen_late(i) -> (vals, ts, valid, n, ts_min, ts_max)``.
    """
    from .. import jax_config  # noqa: F401  (x64 before tracing)
    import jax
    import jax.numpy as jnp

    B = cfg.batch_size
    n_total = cfg.throughput * cfg.runtime_s
    n_batches = max(1, n_total // B)
    span_ms = max(1, cfg.runtime_s * 1000 // n_batches)
    ooo = float(cfg.out_of_order_pct)
    lateness = int(cfg.max_lateness)
    n_late = int(B * ooo)
    late_cap = max(64, 1 << (max(1, n_late) - 1).bit_length())

    @jax.jit
    def _gen(key, lo):
        gaps = jax.random.uniform(key, (B,), dtype=jnp.float32)
        gaps = gaps / jnp.sum(gaps) * span_ms
        ts = lo + jnp.cumsum(gaps).astype(jnp.int64)
        ts = jnp.minimum(ts, lo + span_ms - 1)
        vals = jax.random.uniform(key, (B,), dtype=jnp.float32) * 10_000
        return vals, ts

    @jax.jit
    def _gen_late(key, lo):
        """n_late tuples in [max(0, lo - lateness), lo), sorted — tuples of
        earlier event time arriving now."""
        u = jax.random.uniform(key, (2, late_cap), dtype=jnp.float32)
        lo_f = jnp.maximum(lo.astype(jnp.float64) - lateness, 0.0)
        ts = (lo_f + jnp.sort(u[0]).astype(jnp.float64)
              * (lo.astype(jnp.float64) - lo_f)).astype(jnp.int64)
        return u[1] * 10_000.0, ts

    root = jax.random.PRNGKey(cfg.seed)
    valid_late = None

    def gen(i: int):
        lo = np.int64(i * span_ms)
        vals, ts = _gen(jax.random.fold_in(root, i), lo)
        return vals, ts, int(lo), (i + 1) * span_ms - 1

    def gen_late(i: int):
        nonlocal valid_late
        if valid_late is None:
            v = np.zeros((late_cap,), bool)
            v[:n_late] = True
            valid_late = jax.device_put(v)
        lo = np.int64(i * span_ms)
        vals, ts = _gen_late(jax.random.fold_in(root, 1 << 20 | i), lo)
        # tuple order matches ingest_device_late(ts, vals, valid, n, ...)
        return (ts, vals, valid_late, n_late,
                max(0, int(lo) - lateness), int(lo))

    gen.n_batches = n_batches
    gen.span_ms = span_ms
    gen.gen_late = gen_late if (ooo > 0 and n_late > 0) else None
    gen.n_late = n_late
    return gen


# ---------------------------------------------------------------------------
# Throughput statistics (ThroughputStatistics.java:3-44)
# ---------------------------------------------------------------------------


@dataclass
class ThroughputStatistics:
    tuples: int = 0
    seconds: float = 0.0
    emit_latencies_ms: List[float] = field(default_factory=list)

    @property
    def mean_throughput(self) -> float:
        return self.tuples / self.seconds if self.seconds else 0.0

#: a sample is attributed to a transport STALL only above this absolute
#: floor; engine tail latency above 10×p50 but below this stays
#: engine-attributed
STALL_ABS_MS = 1000.0


def latency_stats(lats) -> dict:
    """Stall-robust latency summary (VERDICT r4 weak #5, refined per
    ADVICE r5): the raw p99 is the AUTHORITATIVE number; a trimmed
    companion excludes samples > 10×p50. Previously every trimmed sample
    was labeled a stall — silently reclassifying genuine engine tail as
    transport noise. Now ``n_stall_samples`` counts only samples that are
    both > 10×p50 AND > :data:`STALL_ABS_MS`; when raw and trimmed diverge with NO identified stall,
    ``tail_unattributed`` flags that the tail is real, engine-attributed
    latency the trimmed figure hides."""
    if not len(lats):
        return {"p99_emit_ms": 0.0, "p50_emit_ms": 0.0,
                "p99_emit_ms_trimmed": 0.0, "n_stall_samples": 0,
                "n_trimmed_samples": 0, "stall_flagged": False,
                "tail_unattributed": False}
    lats = np.asarray(lats, np.float64)
    p50 = float(np.percentile(lats, 50))
    p99 = float(np.percentile(lats, 99))
    core = lats[lats <= 10.0 * p50]
    trimmed = int(lats.size - core.size)
    stalls = int(((lats > 10.0 * p50) & (lats > STALL_ABS_MS)).sum())
    p99_t = float(np.percentile(core, 99)) if core.size else p99
    diverged = bool(p99 > 10.0 * p50)
    return {"p99_emit_ms": p99, "p50_emit_ms": p50,
            "p99_emit_ms_trimmed": p99_t, "n_stall_samples": stalls,
            "n_trimmed_samples": trimmed,
            "stall_flagged": diverged and stalls > 0,
            "tail_unattributed": diverged and stalls == 0}


def first_emit_stats(res: "BenchResult", fe_lats) -> None:
    """Fold drained first-emit samples (watermark-eligibility → first
    delivered window, ISSUE 14 — the ROADMAP item 4 bench dimension)
    onto the result row: ``first_emit_p50_ms`` / ``first_emit_p99_ms``
    / ``first_emit_samples``. Cells that measured nothing embed only
    the zero sample count — a 0.0 percentile must never pose as a
    measured latency (and a baseline of 0.0 would turn the first real
    measurement into a false ``obs diff`` regression)."""
    res.first_emit_samples = len(fe_lats)
    if fe_lats:
        arr = np.asarray(fe_lats, np.float64)
        res.first_emit_p50_ms = float(np.percentile(arr, 50))
        res.first_emit_p99_ms = float(np.percentile(arr, 99))


def finalize_observability(res: "BenchResult", obs, lats, emitted: int,
                           n_tuples: Optional[int] = None) -> None:
    """Shared cell epilogue: fold the sampled emit latencies and emission
    count into the registry, then embed the structured export on the
    result. ``n_tuples`` is passed only by cells whose operator had no
    hook points (the counter would otherwise double-count)."""
    if obs is None:
        return
    for v in lats:
        obs.histogram(_obs.EMIT_LATENCY_MS).observe(v)
    obs.counter(_obs.WINDOWS_EMITTED).inc(emitted)
    if n_tuples is not None:
        obs.counter(_obs.INGEST_TUPLES).inc(n_tuples)
    res.metrics = obs.export()
    res.observability = obs             # for exporters (not in to_dict)


@dataclass
class BenchResult:
    name: str
    windows: str
    aggregation: str
    tuples_per_sec: float
    p99_emit_ms: float
    n_windows_emitted: int
    n_tuples: int
    wall_s: float
    #: structured observability section (Observability.export(): metrics
    #: snapshot + span summary); None when observability was disabled
    metrics: Optional[dict] = None

    def to_dict(self):
        out = {
            "name": self.name, "windows": self.windows,
            "aggregation": self.aggregation,
            "tuples_per_sec": self.tuples_per_sec,
            "p99_emit_ms": self.p99_emit_ms,
            "windows_emitted": self.n_windows_emitted,
            "tuples": self.n_tuples, "wall_s": self.wall_s,
        }
        if self.metrics is not None:
            out["metrics"] = self.metrics
        return out


# ---------------------------------------------------------------------------
# Runner (BenchmarkRunner.java:20-202)
# ---------------------------------------------------------------------------


def run_benchmark(cfg: BenchmarkConfig, window_spec: str, agg_name: str,
                  engine: str = "TpuEngine",
                  warmup_batches: int = 2,
                  obs: Optional[_obs.Observability] = None,
                  collect_metrics: bool = True) -> BenchResult:
    """One (window-config × aggregation × engine) cell: feed the whole
    generated stream, watermark every ``watermark_period_ms`` event-ms,
    report mean tuples/s + p99 window-emit latency.

    Observability: unless ``collect_metrics=False``, a fresh
    :class:`scotty_tpu.obs.Observability` (or the caller's ``obs``) is
    attached to the run — engine hooks record ingest/late/watermark
    telemetry, harness phases record spans, and the structured export is
    embedded as the result's ``metrics`` section
    (``BenchResult.to_dict()["metrics"]``)."""
    import jax

    from ..core.windows import ForwardContextAware, ForwardContextFree

    if obs is None and collect_metrics:
        obs = _obs.Observability()
    _span = obs.span if obs is not None else (
        lambda name: contextlib.nullcontext())

    windows = parse_window_spec(window_spec, seed=cfg.seed)
    # out-of-order streams can use the device source too (on-device
    # displacement + re-sort) — except for count windows, whose OOO
    # handling is host-only. Session windows consume batches in arrival
    # order on the host boundary (ingest_device_batch rejects them);
    # context windows ride the device source in-order when every spec
    # certifies the chain kernel (inorder_chain_params), host-fed
    # otherwise.
    def _ctx_device_ok(w):
        sp = w.device_context_spec()
        return sp is not None and sp.inorder_chain_params() is not None

    _host_only_ooo = any(
        w.measure == WindowMeasure.Count
        or isinstance(w, (ForwardContextAware, ForwardContextFree))
        for w in windows)
    _host_fed = any(
        isinstance(w, SessionWindow)
        or (isinstance(w, (ForwardContextAware, ForwardContextFree))
            and not _ctx_device_ok(w))
        for w in windows)
    device_source = (engine == "TpuEngine" and not cfg.session_config
                     and not _host_fed
                     and (cfg.out_of_order_pct == 0 or not _host_only_ooo))
    with _span("generate"):
        if device_source:
            gen = make_device_source(cfg)
            batches = None
        else:
            batches = generate_batches(cfg)

    if engine == "TpuEngine":
        from ..engine import EngineConfig, TpuWindowOperator

        op = TpuWindowOperator(config=EngineConfig(
            capacity=cfg.capacity, batch_size=cfg.batch_size,
            record_capacity=cfg.record_capacity),
            collect_device_metrics=collect_metrics)
    elif engine == "Simulator":
        from ..simulator import SlicingWindowOperator

        op = SlicingWindowOperator()
    elif engine == "Hybrid":
        # automatic backend routing (session / count / holistic mixes run
        # on the host; device-realizable workloads on the engine) — the
        # BASELINE config-5 path. Measured with the generic sync loop.
        from ..hybrid import HybridWindowOperator

        op = HybridWindowOperator()
    else:
        raise ValueError(f"unknown engine {engine!r}")

    for w in windows:
        op.add_window_assigner(w)
    op.add_aggregation(make_aggregation(agg_name))
    op.set_max_lateness(cfg.max_lateness)
    op_has_obs = hasattr(op, "set_observability")
    if obs is not None and op_has_obs:
        op.set_observability(obs)

    # warmup: compile ingest + query + gc paths on a throwaway twin
    # (deliberately NOT given the observability hooks: warmup tuples must
    # not pollute the run's ingest/watermark counters)
    with _span("warmup"):
        if engine == "TpuEngine" and warmup_batches > 0:
            from ..engine import EngineConfig, TpuWindowOperator

            # the throwaway twin's telemetry is discarded — skip its cost
            twin = TpuWindowOperator(config=EngineConfig(
                capacity=cfg.capacity, batch_size=cfg.batch_size,
                record_capacity=cfg.record_capacity),
                collect_device_metrics=False)
            for w in windows:
                twin.add_window_assigner(w)
            twin.add_aggregation(make_aggregation(agg_name))
            twin.set_max_lateness(cfg.max_lateness)
            if device_source:
                last = 0
                for i in range(warmup_batches):
                    vals, ts, lo, hi = gen(i)
                    twin.ingest_device_batch(vals, ts, lo, hi)
                    if gen.gen_late is not None and i > 0:
                        twin.ingest_device_late(*gen.gen_late(i))
                    last = hi
                twin.process_watermark_async(last + 1)
                twin.process_watermark_async(last + cfg.watermark_period_ms + 1)
                anchor = (twin._state if twin._state is not None
                          else twin._ctx_states[0])
                jax.block_until_ready(jax.tree.leaves(anchor)[0])
            else:
                for vals, ts in batches[:warmup_batches]:
                    twin.process_elements(vals, ts)
                twin.process_watermark(int(batches[warmup_batches - 1][1][-1]) + 1)
                twin.process_watermark(int(batches[warmup_batches - 1][1][-1])
                                       + cfg.watermark_period_ms + 1)
    if obs is not None:
        # rates (*_per_s) measure the stream region, not generation/compile
        obs.registry.reset_clock()
    tracer = None
    fe_lats: List[float] = []
    if obs is not None:
        # first-emit probes (ISSUE 14): sampling-off tracer — the
        # operator seams stay one attribute check, and only the sampled
        # ticks below force a chain around their honest drained measure
        tracer = obs.latency if obs.latency is not None \
            else obs.attach_latency(sample_every=0)

    stats = ThroughputStatistics()
    n_emitted = 0
    next_wm = cfg.watermark_period_ms
    n_tuples = 0
    pending = []                 # (T, cnt_dev) handles, fetched at drain
    pending_sessions = []        # per-watermark emitted-session counts (dev)
    wm_count = 0
    SAMPLE_EVERY = 8             # emit-latency sampling cadence

    def advance_watermark(wm: int) -> None:
        """Watermark advance; on sampled ticks, measure HONEST emit latency:
        drain the device queue first, then time dispatch → results-on-host
        (the reference measures per-watermark result delivery the same way —
        its processWatermark is synchronous). Non-sampled ticks stay fully
        async so throughput is not serialized."""
        nonlocal n_emitted, wm_count
        if engine == "TpuEngine":
            sample = wm_count % SAMPLE_EVERY == 0
            lid = None
            if sample:
                anchor = (op._state if op._state is not None
                          else op._session_states[0]
                          if op._session_states else op._ctx_states[0])
                jax.device_get(                           # drain the queue
                    jax.tree.leaves(anchor)[0].ravel()[0])
                t_wm = time.perf_counter()
                if tracer is not None:
                    lid = tracer.open(force=True)
            out = op.process_watermark_async(wm)
            if lid is not None:
                # the watermark dispatch returned: its windows are
                # eligible; the sampled fetch below is their delivery
                tracer.stamp(lid, _late.STAGE_ELIGIBILITY)
            if isinstance(out[0], str) and out[0] == "session":
                ms = tuple(g[0] for g in out[1])   # per-window emit counts
                pending_sessions.append(ms)
                if sample:
                    jax.device_get(ms)
            elif isinstance(out[0], str):        # mixed grid + sessions
                _, grid, s_outs = out
                ms = tuple(g[0] for g in s_outs)
                pending_sessions.append(ms)
                if grid[3] is not None:
                    pending.append((grid[0].shape[0], grid[3]))
                if sample:
                    jax.device_get(ms)
                    if grid[3] is not None:
                        jax.device_get((grid[3], grid[4]))
            elif out[3] is not None:
                pending.append((out[0].shape[0], out[3]))
                if sample:
                    jax.device_get((out[3], out[4]))
            if sample:
                stats.emit_latencies_ms.append(
                    (time.perf_counter() - t_wm) * 1e3)
                if lid is not None:
                    tracer.stamp(lid, _late.STAGE_EMIT)
                    fin = tracer.finalize(lid)
                    if fin is not None \
                            and fin["first_emit_ms"] is not None:
                        fe_lats.append(fin["first_emit_ms"])
        else:
            t_wm = time.perf_counter()
            lid = tracer.open(force=True) if tracer is not None else None
            if lid is not None:
                tracer.stamp(lid, _late.STAGE_ELIGIBILITY)
            results = op.process_watermark(wm)
            n_emitted += sum(1 for r in results if r.has_value())
            stats.emit_latencies_ms.append(
                (time.perf_counter() - t_wm) * 1e3)
            if lid is not None:
                tracer.stamp(lid, _late.STAGE_EMIT)
                fin = tracer.finalize(lid)
                if fin is not None and fin["first_emit_ms"] is not None:
                    fe_lats.append(fin["first_emit_ms"])
        wm_count += 1

    t0 = time.perf_counter()
    with _span("stream"):
        if device_source:
            for i in range(gen.n_batches):
                vals, ts, lo, hi = gen(i)
                op.ingest_device_batch(vals, ts, lo, hi)
                n_tuples += cfg.batch_size
                if gen.gen_late is not None and i > 0:
                    late_args = gen.gen_late(i)
                    op.ingest_device_late(*late_args)
                    n_tuples += late_args[3]
                while hi >= next_wm:
                    advance_watermark(next_wm)
                    next_wm += cfg.watermark_period_ms
            batches = []
        for vals, ts in batches:
            if engine in ("TpuEngine", "Hybrid"):
                op.process_elements(vals, ts)
            else:
                for v, t in zip(vals, ts):
                    op.process_element(float(v), int(t))
            n_tuples += len(vals)
            last_ts = int(ts[-1])
            while last_ts >= next_wm:
                advance_watermark(next_wm)
                next_wm += cfg.watermark_period_ms
    # drain: one final watermark past the stream end + bundled result fetch
    with _span("drain"):
        advance_watermark(next_wm)
        if engine == "TpuEngine":
            fetched = jax.device_get([c for _, c in pending])
            for (T, _), cnt in zip(pending, fetched):
                n_emitted += int((cnt[:T] > 0).sum())
            if pending_sessions:
                n_emitted += int(sum(
                    int(m) for grp in jax.device_get(pending_sessions)
                    for m in grp))
            op.check_overflow()
    wall = time.perf_counter() - t0
    if obs is not None:
        obs.registry.stop_clock()       # rates cover the stream region only

    stats.tuples = n_tuples
    stats.seconds = wall
    res = BenchResult(
        name=cfg.name, windows=window_spec, aggregation=agg_name,
        tuples_per_sec=stats.mean_throughput,
        p99_emit_ms=0.0,                    # filled by latency_stats below
        n_windows_emitted=n_emitted, n_tuples=n_tuples, wall_s=wall)
    for k, v in latency_stats(stats.emit_latencies_ms).items():
        setattr(res, k, v)
    first_emit_stats(res, fe_lats)
    # engines without hook points (Simulator/Hybrid host paths) still
    # report harness-known ingest totals
    finalize_observability(res, obs, stats.emit_latencies_ms, n_emitted,
                           n_tuples=None if op_has_obs else n_tuples)
    return res

"""Fused stream pipeline: source → slicing → trigger/query → GC in ONE
jitted program per watermark interval.

This is the benchmark-shaped execution mode (the reference's BenchmarkJob
pipeline — LoadGeneratorSource → operator → sink inside one Flink task,
benchmark/.../BenchmarkJob.java:26-103) re-designed for the XLA dispatch
model: per-computation dispatch overhead dominates when the host drives the
device batch-by-batch (it bounds small-batch rates), so the whole
watermark interval — G generator+ingest sub-batches via ``lax.scan``,
device-side trigger enumeration, the range-query final merge, and GC —
compiles into one program whose single dispatch amortizes over millions of
tuples.

Device-side trigger enumeration: for each registered window the number of
possible triggers per interval is static (``period // grid + 2``), so
trigger (start, end) arrays are a fixed-shape grid with a validity mask —
the device-side equivalent of WindowManager's per-watermark enumeration
(WindowManager.java:104-118, TumblingWindow.java:34-39,
SlidingWindow.java:50-57).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, NamedTuple, Optional, Sequence

import numpy as np

from .. import jax_config  # noqa: F401
from .. import obs as _obs
from ..obs import flight as _flight
from ..obs import latency as _lat

from ..core.aggregates import AggregateFunction
from ..core.windows import (
    FixedBandWindow,
    SlidingWindow,
    TumblingWindow,
    WindowMeasure,
)
from .config import EngineConfig


def half_draw_parts(bits, value_scale: float):
    """The two 16-bit-granular value halves of 32-bit draws, as separate
    arrays — for consumers that must avoid the concatenation (another
    fusion breaker: lifting the halves separately kept the sub-row
    chunked interval fused, 178 → 44 ms per 800 M tuples)."""
    import jax.numpy as jnp

    sc = jnp.float32(value_scale / 65536.0)
    lo = (bits & jnp.uint32(0xffff)).astype(jnp.float32) * sc
    hi = (bits >> 16).astype(jnp.float32) * sc
    return lo, hi


def half_draw(bits, value_scale: float):
    """Expand 32-bit draws into TWO 16-bit-granular uniform values over
    ``[0, value_scale)``, laid out as blocks (lo half then hi half) along
    the LAST axis. The layout is load-bearing: a stride-2 interleave
    breaks XLA's producer fusion into dot operands (measured
    2.75 G → 0.77 G on the factored-histogram quantile cell), and the
    bucket/keyed generators must agree bit-exactly with the aligned one.
    Callers pass ``jax.random.bits(..., dtype=jnp.uint32)`` — under x64
    the default widens to uint64 and silently rescales the values."""
    import jax.numpy as jnp

    lo, hi = half_draw_parts(bits, value_scale)
    return jnp.concatenate([lo, hi], axis=-1)


def draw_uniform16(key, shape, value_scale: float):
    """The benchmark generators' value draw: ``shape`` values uniform
    over 65536 levels in ``[0, value_scale)`` via the half-draw block
    layout when ``shape[-1]`` is even (two values per 32-bit threefry
    draw), plain f32 uniforms otherwise. Every generator (aligned,
    bucket, keyed, session — device AND host-replay faces) goes through
    THIS function so the streams cannot drift."""
    import jax
    import jax.numpy as jnp

    if shape[-1] % 2 == 0:
        bits = jax.random.bits(key, shape[:-1] + (shape[-1] // 2,),
                               dtype=jnp.uint32)
        return half_draw(bits, value_scale)
    return jax.random.uniform(key, shape, dtype=jnp.float32) * value_scale


def build_trigger_grid(windows, wm_period_ms: int):
    """Device-side trigger enumeration with a static layout.

    For each window the number of possible triggers per watermark interval is
    static (``period // grid + 2``), so the per-interval (start, end) arrays
    are a fixed-shape grid with a validity mask — the device-side equivalent
    of WindowManager's per-watermark enumeration (WindowManager.java:104-118,
    TumblingWindow.java:34-39, SlidingWindow.java:50-57; ascending per window
    rather than the reference's backward walk).

    Returns ``(make_triggers(last_wm, wm) -> (ws, we, valid), T)``.
    """
    import jax.numpy as jnp

    trig_layout = []                   # (grid, size, maxk, kind)
    for w in windows:
        if isinstance(w, TumblingWindow):
            trig_layout.append((int(w.size), int(w.size),
                                wm_period_ms // int(w.size) + 1, "t"))
        elif isinstance(w, SlidingWindow):
            # +2: the reference guard is end <= wm+1 (SlidingWindow.java:54),
            # so an interval can include both boundary ends last_wm+1 and
            # wm+1 — including re-emitting a window already emitted at the
            # previous watermark (ends in (last_wm, wm+1] overlap across
            # consecutive intervals at exactly end == wm+1; reference quirk,
            # reproduced for parity).
            trig_layout.append((int(w.slide), int(w.size),
                                wm_period_ms // int(w.slide) + 2, "s"))
        elif isinstance(w, FixedBandWindow):
            trig_layout.append((int(w.start), int(w.size), 1, "b"))
        else:
            raise NotImplementedError(f"pipeline: {type(w).__name__}")

    if len(trig_layout) <= 32:
        # few windows: per-window parts, exact trigger counts
        def make_triggers(last_wm, wm):
            ws_parts, we_parts, valid_parts = [], [], []
            for (g, size, maxk, kind) in trig_layout:
                if kind == "b":
                    end = jnp.asarray([g + size], jnp.int64)
                    start = jnp.asarray([g], jnp.int64)
                    ok = (end >= last_wm) & (end <= wm)
                elif kind == "s":
                    # starts lie on the slide grid; ends = start + size are
                    # NOT multiples of the slide when size % slide != 0, so
                    # enumerate starts: smallest grid start with
                    # end > last_wm.
                    first_start = ((last_wm - size) // g + 1) * g
                    starts = first_start + g * jnp.arange(maxk,
                                                          dtype=jnp.int64)
                    ends = starts + size
                    # SlidingWindow.java:50-57 guards (note <= wm + 1)
                    ok = (starts >= 0) & (ends <= wm + 1)
                    start, end = starts, ends
                else:
                    first_end = (last_wm // g + 1) * g
                    ends = first_end + g * jnp.arange(maxk, dtype=jnp.int64)
                    starts = ends - size
                    ok = ends <= wm
                    start, end = starts, ends
                ws_parts.append(start)
                we_parts.append(end)
                valid_parts.append(ok)
            return (jnp.concatenate(ws_parts), jnp.concatenate(we_parts),
                    jnp.concatenate(valid_parts))

        return make_triggers, sum(m for _, _, m, _ in trig_layout)

    # many windows (e.g. 1000 random tumbling): a per-window op chain makes
    # the traced graph O(5·n_windows) and OOM-kills the XLA compiler. Build
    # ONE [N, K] grid per window kind instead (K = that kind's max trigger
    # count; rows padded with an invalid mask), then restore exact
    # registration order with a single static gather.
    groups = {"t": [], "s": [], "b": []}
    for idx, (g, size, maxk, kind) in enumerate(trig_layout):
        groups[kind].append((idx, g, size, maxk))
    # static row layout: (window idx, k) for each emitted slot, kind-grouped
    slot_owner = []
    for kind in ("t", "s", "b"):
        rows = groups[kind]
        if not rows:
            continue
        K = max(m for _, _, _, m in rows)
        for (idx, _, _, _) in rows:
            for k in range(K):
                slot_owner.append((idx, k))
    # permutation restoring registration order, dropping over-padded slots
    # beyond each window's own maxk
    slot_of = {ik: pos for pos, ik in enumerate(slot_owner)}
    order = []
    for idx, (g, size, maxk, kind) in enumerate(trig_layout):
        for k in range(maxk):
            order.append(slot_of[(idx, k)])
    perm = np.asarray(order, dtype=np.int64)
    T_total = perm.shape[0]

    def make_triggers_grouped(last_wm, wm):
        ws_parts, we_parts, ok_parts = [], [], []
        for kind in ("t", "s", "b"):
            rows = groups[kind]
            if not rows:
                continue
            K = max(m for _, _, _, m in rows)
            gs = jnp.asarray([g for _, g, _, _ in rows], jnp.int64)[:, None]
            szs = jnp.asarray([s for _, _, s, _ in rows],
                              jnp.int64)[:, None]
            mks = jnp.asarray([m for _, _, _, m in rows],
                              jnp.int64)[:, None]
            k = jnp.arange(K, dtype=jnp.int64)[None, :]
            if kind == "b":
                ends = gs + szs + 0 * k
                starts = gs + 0 * k
                ok = (ends >= last_wm) & (ends <= wm)
            elif kind == "s":
                first_start = ((last_wm - szs) // gs + 1) * gs
                starts = first_start + gs * k
                ends = starts + szs
                ok = (starts >= 0) & (ends <= wm + 1)
            else:
                first_end = (last_wm // gs + 1) * gs
                ends = first_end + gs * k
                starts = ends - szs
                ok = ends <= wm
            ok = ok & (k < mks)
            ws_parts.append(starts.reshape(-1))
            we_parts.append(ends.reshape(-1))
            ok_parts.append(ok.reshape(-1))
        return (jnp.concatenate(ws_parts)[perm],
                jnp.concatenate(we_parts)[perm],
                jnp.concatenate(ok_parts)[perm])

    return make_triggers_grouped, T_total


QUERY_KIND_TUMBLING = 0
QUERY_KIND_SLIDING = 1


@dataclass(frozen=True)
class SlotGeometry:
    """Static geometry of a dynamic-query slot grid (scotty_tpu.serving).

    The serving layer pads runtime window sets to power-of-two slot grids
    so register/cancel stays inside one compiled executable: ``n_slots``
    query rows, each answering up to ``triggers_per_slot`` triggers per
    watermark interval, over the fixed aligned ``slice_grid``. Everything
    here is trace-time static — changing any field is a new compile-cache
    bucket (scotty_tpu.serving.cache), never an in-place mutation.
    """

    #: padded query-slot rows ([Q] mask/param arrays; power of two)
    n_slots: int
    #: static per-slot trigger lanes K: every admitted window must satisfy
    #: ``wm_period // grid + 2 <= K`` (grid = slide for sliding windows,
    #: size for tumbling)
    triggers_per_slot: int
    #: the aligned slice grid g (ms). Admission requires every window
    #: size/slide to be a multiple — the aligned pipeline's exactness
    #: condition (window edges land on slice edges)
    slice_grid: int
    #: retention bound fed to GC in place of the static set's max
    #: ``clear_delay()`` — the largest window size admission will accept,
    #: so slices live long enough for any query registered later
    max_size: int

    def __post_init__(self):
        for f in ("n_slots", "triggers_per_slot", "slice_grid", "max_size"):
            if int(getattr(self, f)) < 1:
                raise ValueError(f"SlotGeometry.{f} must be >= 1")


class QuerySlots(NamedTuple):
    """Device-resident query table: the ``[Q]`` window-parameter rows and
    the active mask carried in the serving step's donated state. A
    register/cancel is ONE row write (``dynamic_update_slice`` via
    ``.at[i].set``) — never a retrace."""

    kinds: "jnp.ndarray"     # [Q] int32: QUERY_KIND_TUMBLING | _SLIDING
    grids: "jnp.ndarray"     # [Q] int64: slide (sliding) / size (tumbling)
    sizes: "jnp.ndarray"     # [Q] int64 window size
    active: "jnp.ndarray"    # [Q] bool


def init_query_slots(geometry: SlotGeometry,
                     rows: Optional[dict] = None) -> QuerySlots:
    """Fresh device table — all slots inactive (grid/size 1 so the masked
    per-slot trigger arithmetic never divides by zero), or uploaded from a
    host mirror dict of numpy rows (``kinds/grids/sizes/active``)."""
    import jax
    import jax.numpy as jnp

    Q = geometry.n_slots
    if rows is None:
        kinds = np.zeros((Q,), np.int32)
        grids = np.ones((Q,), np.int64)
        sizes = np.ones((Q,), np.int64)
        active = np.zeros((Q,), bool)
    else:
        kinds = np.asarray(rows["kinds"], np.int32)
        grids = np.asarray(rows["grids"], np.int64)
        sizes = np.asarray(rows["sizes"], np.int64)
        active = np.asarray(rows["active"], bool)
        if kinds.shape != (Q,):
            raise ValueError(
                f"query-table rows have {kinds.shape[0]} slots, geometry "
                f"expects {Q}")
    dev = jax.device_put((kinds, grids, sizes, active))
    return QuerySlots(jnp.asarray(dev[0]), jnp.asarray(dev[1]),
                      jnp.asarray(dev[2]), jnp.asarray(dev[3]))


def build_slot_trigger_grid(geometry: SlotGeometry, wm_period_ms: int):
    """Mask-aware trigger enumeration over a dynamic query-slot table.

    The static :func:`build_trigger_grid` bakes each window's (grid, size,
    kind) into the traced program; here they are DATA — ``[Q]`` device rows
    read from the carried :class:`QuerySlots` — so registering or
    cancelling a query never retraces. Per slot the same per-kind trigger
    formulas run over a static ``[Q, K]`` lane grid (K =
    ``geometry.triggers_per_slot``); lanes beyond a slot's own trigger
    count, and whole slots with ``active=False``, fold into the validity
    mask the query kernel already consumes.

    Trigger semantics are identical to the static builder (tumbling: ends
    on the size grid, ``end <= wm``; sliding: starts on the slide grid,
    ``start >= 0 & end <= wm + 1`` — the reference guard
    SlidingWindow.java:50-57 quirk included), so a slot's rows bit-match
    the rows a static pipeline computes for the same window.

    Returns ``(make_triggers(slots, last_wm, wm) -> (ws, we, valid), T)``
    with ``T = Q * K``; row ``q*K + k`` belongs to slot ``q``.
    """
    import jax.numpy as jnp

    Q, K = geometry.n_slots, geometry.triggers_per_slot
    P = wm_period_ms

    def make_triggers(slots: QuerySlots, last_wm, wm):
        g = slots.grids[:, None]                       # [Q, 1]
        sz = slots.sizes[:, None]
        k = jnp.arange(K, dtype=jnp.int64)[None, :]    # [1, K]
        # tumbling: ends on the size grid (grid == size)
        t_ends = (last_wm // g + 1) * g + g * k
        t_starts = t_ends - sz
        t_ok = t_ends <= wm
        # sliding: starts on the slide grid; ends = start + size are NOT
        # grid multiples when size % slide != 0, so enumerate starts
        s_starts = ((last_wm - sz) // g + 1) * g + g * k
        s_ends = s_starts + sz
        s_ok = (s_starts >= 0) & (s_ends <= wm + 1)
        sliding = (slots.kinds == QUERY_KIND_SLIDING)[:, None]
        ws = jnp.where(sliding, s_starts, t_starts)
        we = jnp.where(sliding, s_ends, t_ends)
        # exact per-slot trigger count (build_trigger_grid's maxk): the
        # static lane count K only bounds it — admission enforces K is
        # large enough for every admitted window
        maxk = P // slots.grids + jnp.where(
            slots.kinds == QUERY_KIND_SLIDING, 2, 1)
        ok = (jnp.where(sliding, s_ok, t_ok)
              & (k < maxk[:, None]) & slots.active[:, None])
        return ws.reshape(-1), we.reshape(-1), ok.reshape(-1)

    return make_triggers, Q * K


def lower_interval_columns(aggregations: Sequence[AggregateFunction],
                           interval_out):
    """Fetch one interval's trigger columns and host-lower each
    aggregation: ``(ws, we, cnt, [per-agg lowered [T] arrays])`` — the
    one place the lowering contract lives (row-shaped consumers:
    :func:`lower_interval`; slot-attributed consumers:
    ``serving.QueryService.results_by_slot``)."""
    import jax

    ws, we, cnt, results = jax.device_get(interval_out)
    lowered = []
    for agg, res in zip(aggregations, results):
        spec = agg.device_spec()
        lowered.append(np.asarray(spec.lower(res, cnt)))
    return ws, we, cnt, lowered


def lower_interval(aggregations: Sequence[AggregateFunction], interval_out):
    """Fetch + lower one interval's window results on host: list of
    (start, end, count, [per-agg final value]) for non-empty windows."""
    ws, we, cnt, lowered = lower_interval_columns(aggregations, interval_out)
    rows = []
    for i in range(ws.shape[0]):
        if cnt[i] > 0:
            rows.append((int(ws[i]), int(we[i]), int(cnt[i]),
                         [lw[i] for lw in lowered]))
    return rows


class FusedPipelineDriver:
    """Shared host driver for the fused per-interval pipelines
    (:class:`AlignedStreamPipeline`, :class:`StreamPipeline`,
    :class:`.session_pipeline.SessionStreamPipeline`,
    :class:`..parallel.keyed.KeyedAlignedPipeline`,
    :class:`..bench.buckets.BucketWindowPipeline`): stateful interval
    numbering, per-interval PRNG keying, GC cadence, and the
    device_get-based sync (a fetched scalar the step produced is the
    barrier). Subclasses set
    ``wm_period_ms``, ``max_lateness``, ``max_fixed``, ``gc_every``,
    ``seed``, implement ``_init_pipeline_state()``,
    ``_step_interval(key, i) -> result`` and ``_sync_anchor()``, and
    optionally ``_gc(bound)`` for out-of-step GC.
    """

    #: attached Observability (scotty_tpu.obs) — None = zero-overhead off.
    #: Host-side hooks fire at interval boundaries; the IN-JIT telemetry
    #: (obs/device.py DeviceMetrics) rides the carried state and is folded
    #: into the registry at sync().
    obs = None
    #: whether _sync_anchor() is the live-slice count (occupancy gauges);
    #: pipelines whose anchor is something else (count pipeline: the
    #: overflow flag) set this False
    _anchor_is_slices = True
    #: pipelines whose jitted step threads a DeviceMetrics pytree set this
    #: True (their _step takes and returns the dm as the second carry);
    #: others (buckets baseline, keyed) keep the two-value contract
    _uses_device_metrics = False
    #: static at construction: False builds the step WITHOUT the in-jit
    #: counter updates (the dm passes through untouched — the overhead
    #: A/B baseline and an escape hatch)
    collect_device_metrics = True
    #: the carried DeviceMetrics (device pytree); None until reset() on a
    #: supporting pipeline
    dm = None
    #: the jitted step contains a Pallas kernel (set by pipelines whose
    #: config enables one) — run loops count ``pallas_kernel_dispatches``
    #: host-side per dispatch when this is True
    _pallas_in_step = False
    #: arrival-paced micro-batching (``run_streamed``): bound the
    #: in-flight micro queue to one via a tiny anchor fetch per
    #: micro-dispatch — the streaming discipline of a source that
    #: delivers micro-batches at the sustainable rate (the latency
    #: bench arm turns this on; throughput runs leave it off)
    micro_pace = False
    #: device-resident dynamic-query table (:class:`QuerySlots`) carried in
    #: the serving step's donated state; None on every static pipeline
    _qstate = None
    #: times the jitted step's Python body ran — i.e. jit TRACES. The
    #: serving layer's zero-steady-state-retrace contract is asserted on
    #: this counter (scotty_tpu.serving; the churn bench records its delta)
    _trace_count = 0

    def set_observability(self, obs) -> None:
        """Attach an :class:`scotty_tpu.obs.Observability`; pass ``None``
        to detach. Telemetry recorded per interval: ``interval_step_ms``
        histogram, ``ingest_tuples`` counter; per :meth:`sync`:
        ``sync_ms`` histogram + ``slice_occupancy``/``slice_headroom``
        gauges (sync is the drain point — the one place occupancy is
        host-known without adding a device round trip) + the in-jit
        DeviceMetrics delta folded as ``device_*`` counters. Attaching
        mid-run baselines the device counters at the last drained
        snapshot, so pre-attach (warmup) tuples don't pollute the fold."""
        self.obs = obs
        if obs is not None and self._uses_device_metrics:
            self._dm_folded = getattr(self, "_dm_host", None)

    def device_metrics(self):
        """Fetch + flatten the in-jit DeviceMetrics as a ``device_*`` name
        → int dict (one device sync). None when this pipeline doesn't
        thread device telemetry or hasn't started."""
        if self.dm is None:
            return None
        import jax

        from ..obs import device as _dev

        return _dev.host_snapshot(jax.device_get(self.dm))

    def _interval_tuples(self, i: int) -> int:
        """Host-known tuple count interval ``i`` ingests (telemetry)."""
        return int(getattr(self, "tuples_per_interval", 0))

    def reset(self) -> None:
        import jax

        self._root = jax.random.PRNGKey(self.seed)
        self._interval = 0
        self._init_pipeline_state()
        if self._uses_device_metrics:
            from ..obs import device as _dev

            self.dm = _dev.init_device_metrics()
            self._dm_host = None
            self._dm_folded = None
        self._pipeline_ready = True

    def _interval_key(self, i: int):
        import jax

        # the fold-in data rides an EXPLICIT device_put: the step loop
        # runs under jax.transfer_guard("disallow") in the differential
        # tests, and the per-interval index is the one sanctioned
        # host->device upload (an implicit-transfer creep anywhere else
        # in the step fails those tests)
        return jax.random.fold_in(self._root,
                                  jax.device_put(np.uint32(i)))

    def _needs_reset(self) -> bool:
        # NOT keyed on _root: the materialize_* helpers lazily seed _root
        # on a fresh pipeline, which must not make run() skip state init
        return not getattr(self, "_pipeline_ready", False)

    def _step_interval(self, key, i: int):
        import jax

        # explicit upload of the interval scalar (same sanctioned-
        # transfer contract as _interval_key; aval unchanged, so the
        # lowered step HLO is identical — pinned by tests/hlo_pins.json)
        iv = jax.device_put(np.int64(i))
        if self._qstate is not None:
            # serving mode: the query table rides the donated carry
            (self.state, self.dm, self._qstate,
             res) = self._step(self.state, self.dm, self._qstate, key,
                               iv)
        elif self._uses_device_metrics:
            self.state, self.dm, res = self._step(self.state, self.dm, key,
                                                  iv)
        else:
            self.state, res = self._step(self.state, key, iv)
        return res

    def _sync_anchor(self):
        return self.state.n_slices

    def run(self, n_intervals: int, collect: bool = True):
        """Advance n watermark intervals (continuing from the last call —
        interval numbering is stateful, so warmup + timed + latency phases
        see one continuous stream); returns the per-interval result
        handles. Dispatch only — no sync."""
        if self._needs_reset():
            self.reset()
        out = []
        for _ in range(n_intervals):
            _i, _lid, res = self._dispatch_interval(streamed=False)
            if collect:
                out.append(res)
        return out

    def _dispatch_interval(self, streamed: bool):
        """ONE interval's dispatch + bookkeeping, shared verbatim by
        :meth:`run` and :meth:`run_streamed` (a counter/stamp/GC change
        must not silently diverge the two loops): perf timing, the
        emission-latency lineage (ISSUE 14, host-side only — the step
        HLO stays pinned byte-identical: the chain opens at dispatch,
        and the step's own watermark advance IS the eligibility moment,
        so eligibility stamps the instant the dispatch returns), the
        interval counters, the Pallas dispatch count, and the GC
        cadence. Returns ``(interval, chain_key, result_handle)``."""
        import jax

        obs = self.obs
        lat = obs.latency if obs is not None else None
        i = self._interval
        t0 = time.perf_counter() if obs is not None else 0.0
        lid = lat.open() if lat is not None else None
        res = self._dispatch_streamed(i) if streamed \
            else self._step_interval(self._interval_key(i), i)
        if lid is not None:
            lat.stamp(lid, _lat.STAGE_ELIGIBILITY)
        self._interval += 1
        if obs is not None:
            obs.histogram(_obs.INTERVAL_STEP_MS).observe(
                (time.perf_counter() - t0) * 1e3)
            obs.counter(_obs.INGEST_TUPLES).inc(self._interval_tuples(i))
            if self._pallas_in_step:
                from .. import pallas as _pl

                _pl.record_dispatch(obs)
        if self._gc is not None and self._interval % self.gc_every == 0:
            self._gc(jax.device_put(
                np.int64(self._interval * self.wm_period_ms
                         - self.max_lateness - self.max_fixed)))
        return i, lid, res

    _gc = None                      # subclasses assign when GC is a
                                    # separate kernel outside the step

    # -- micro-batched streamed emission (ROADMAP item 4, ISSUE 15) -------
    def run_streamed(self, n_intervals: int, emit=None, depth: int = 1):
        """Streamed emission: dispatch interval N+1's work while
        fetching interval N's eligible windows, instead of queueing the
        whole run behind one drain. Per interval the driver dispatches
        the step (for pipelines with ``config.micro_batch > 1`` and
        micro support — the aligned pipeline — as M micro-batch
        dispatches plus one trigger/query flush), stamps ELIGIBILITY
        the moment the watermark-advancing dispatch returns, and
        fetches each interval's results as soon as ``depth`` newer
        intervals are in flight — so first-emit latency tracks one
        interval's residual compute, not the queued run (the PR 13
        drain-stage attribution shrinks accordingly; conservation stays
        exact because every stamp is a chain delta).

        Emitted results BIT-MATCH :meth:`run` on the same construction
        (same generation keying, same fold order); ``emit(i, host)`` is
        called per fetched interval. Returns the fetched host results
        in interval order.
        """
        if self._needs_reset():
            self.reset()
        from collections import deque

        obs = self.obs
        lat = obs.latency if obs is not None else None
        pending: "deque" = deque()
        out = []
        for _ in range(n_intervals):
            pending.append(self._dispatch_interval(streamed=True))
            while len(pending) > max(0, int(depth)):
                out.append(self._fetch_streamed(pending.popleft(), emit,
                                                lat))
        while pending:
            out.append(self._fetch_streamed(pending.popleft(), emit, lat))
        return out

    def _dispatch_streamed(self, i: int):
        """One interval's async dispatch — subclasses with a real
        micro-batched step (aligned) override; the base dispatches the
        whole-interval step (streamed fetch overlap only)."""
        return self._step_interval(self._interval_key(i), i)

    def _fetch_streamed(self, entry, emit, lat):
        """Fetch one queued interval's windows (the streamed drain):
        the chain closes here — drain and emit ride the same fetch."""
        import jax

        i, lid, res = entry
        host = jax.device_get(res)
        if lat is not None:
            lat.stamp(lid, _lat.STAGE_DRAIN)
            lat.stamp(lid, _lat.STAGE_EMIT)
            lat.finalize(lid)
        if emit is not None:
            emit(i, host)
        return host

    def sync(self) -> int:
        """Drain all queued device work; returns the anchor scalar. The
        in-jit DeviceMetrics pytree rides the same fetch (no extra round
        trip) and its delta folds into the registry as ``device_*``
        counters."""
        import jax

        obs = self.obs
        t0 = time.perf_counter() if obs is not None else 0.0
        if self.dm is not None:
            from ..obs import device as _dev

            v, dm_h = jax.device_get((self._sync_anchor(), self.dm))
        else:
            dm_h = None
            v = jax.device_get(self._sync_anchor())
        v = int(v)
        if obs is not None:
            obs.histogram(_obs.SYNC_MS).observe(
                (time.perf_counter() - t0) * 1e3)
            cap = getattr(getattr(self, "config", None), "capacity", 0)
            if self._anchor_is_slices and cap:
                obs.gauge(_obs.SLICE_OCCUPANCY).set(v / cap)
                obs.gauge(_obs.SLICE_HEADROOM).set(cap - v)
        if dm_h is not None:
            snap = _dev.host_snapshot(dm_h)
            self._dm_host = snap
            if obs is not None:
                self._dm_folded = _dev.fold_into(obs.registry, snap,
                                                 self._dm_folded)
        if obs is not None:
            # flight-recorder sample rides the SAME drain (no extra device
            # sync): the watermark this pipeline has advanced to plus the
            # registry deltas since the last drain land in the ring
            obs.flight_sync(watermark=self._interval * self.wm_period_ms)
            lat = obs.latency
            if lat is not None:
                # every queued interval's chain observes this one drain
                # (the sync drains them all); the drain IS the delivery
                # point of the steady-state pipelined flow, so chains
                # close here — the stamp rides the fetch that already
                # happened, zero extra syncs
                lat.stamp_open(_lat.STAGE_DRAIN)
                lat.finalize_open()
        return v

    def enforce_overflow_policy(self, factory=None, obs=None):
        """Apply ``EngineConfig.overflow_policy`` at a drain point and
        return the pipeline to continue with.

        ``fail`` (default) — :meth:`check_overflow` as today. ``grow`` —
        when the live-slice occupancy (read at the sync this method
        performs) reaches ``config.grow_occupancy``, snapshot the carried
        state via the checkpoint pytree machinery, rebuild through
        ``factory(grown_config)`` at 2× capacity and hand back the grown
        replacement (same interval counter / RNG root / DeviceMetrics —
        the continued run is bit-identical to one pre-sized larger);
        growth is preventive and bounded by ``config.max_capacity``.
        ``shed`` has no pipeline meaning (fused pipelines generate their
        own load in-jit — there is nothing external to shed; admission-
        boundary shedding lives in TpuWindowOperator/connectors) and
        behaves like ``fail`` here.

        This method owns the drain: it always performs ONE
        :meth:`sync` (which also folds the DeviceMetrics delta and, under
        GROW, doubles as the occupancy read) before the overflow check —
        callers like the Supervisor need no separate ``sync()`` per
        checkpoint chunk. Without a ``factory`` the method degrades to
        drain + :meth:`check_overflow`.
        """
        from ..resilience.policy import OverflowPolicy, grow_pipeline

        policy = getattr(self.config, "overflow_policy", OverflowPolicy.FAIL)
        n = self.sync()
        p = self
        if (policy == OverflowPolicy.GROW and factory is not None
                and self._anchor_is_slices):
            cap = self.config.capacity
            if n >= int(cap * getattr(self.config, "grow_occupancy", 0.85)):
                p = grow_pipeline(
                    self, factory,
                    obs=obs if obs is not None else self.obs)
        p.check_overflow()
        return p


class StreamPipeline(FusedPipelineDriver):
    """One fused XLA step per watermark interval.

    ``windows``: context-free Time-measure windows (static).
    ``throughput``: offered tuples per event-second (generator rate —
    LoadGeneratorSource.java:45-57's role).
    ``wm_period_ms``: event-time between watermarks (ThroughputLogger-style
    cadence; the reference triggers per watermark, not per tuple).
    """

    _uses_device_metrics = True

    def __init__(self, windows: Sequence, aggregations: Sequence[AggregateFunction],
                 config: Optional[EngineConfig] = None,
                 throughput: int = 50_000_000, wm_period_ms: int = 1000,
                 max_lateness: int = 1000, seed: int = 0,
                 sub_batch: int = 1 << 18, out_of_order_pct: float = 0.0,
                 collect_device_metrics: bool = True):
        import jax
        import jax.numpy as jnp

        from . import core as ec
        from ..obs import device as _dev

        self.collect_device_metrics = bool(collect_device_metrics)
        self.config = config or EngineConfig()
        self.windows = list(windows)
        self.aggregations = list(aggregations)
        self.max_lateness = max_lateness
        self.wm_period_ms = wm_period_ms
        self.seed = seed
        self.out_of_order_pct = float(out_of_order_pct)

        B = sub_batch
        tuples_per_interval = throughput * wm_period_ms // 1000
        G = max(1, tuples_per_interval // B)
        # disorder: each sub-batch is followed by a small sorted LATE batch
        # (tuples displaced back by < max_lateness) — the in-order base
        # takes the cheap kernel, only the late lanes pay the general
        # kernel's late/annex machinery, and the annex folds back once per
        # interval before the query. No sort anywhere: both parts are
        # sorted by construction.
        B_late = 0
        if self.out_of_order_pct > 0:
            n = int(B * self.out_of_order_pct)
            B_late = max(64, 1 << max(0, (n - 1).bit_length()))
        self.G, self.B, self.B_late = G, B, B_late
        self.tuples_per_interval = G * (B + (int(B * self.out_of_order_pct)
                                             if B_late else 0))
        span = wm_period_ms / G            # event-ms per sub-batch

        periods, bands = [], []
        max_fixed = 0
        for w in self.windows:
            if w.measure != WindowMeasure.Time:
                raise NotImplementedError("pipeline: time-measure only")
            if isinstance(w, TumblingWindow):
                periods.append(int(w.size))
            elif isinstance(w, SlidingWindow):
                periods.append(int(w.slide))
            elif isinstance(w, FixedBandWindow):
                bands.append((int(w.start), int(w.size)))
            else:
                raise NotImplementedError(f"pipeline: {type(w).__name__}")
            max_fixed = max(max_fixed, w.clear_delay())
        spec = ec.EngineSpec(
            periods=ec.collapse_periods(periods),
            bands=tuple(sorted(set(bands))),
            count_periods=(),
            aggs=tuple(a.device_spec() for a in self.aggregations),
        )
        self.spec = spec
        C, A = self.config.capacity, self.config.annex_capacity
        ingest = ec.build_ingest(spec, C, A, assume_inorder=True)
        ingest_general = ec.build_ingest(spec, C, A) if B_late else None
        annex_merge = ec.build_annex_merge(spec, C, A) if B_late else None
        query = ec.build_query(spec, C, A)
        gc = ec.build_gc(spec, C, A)
        self._init_state = lambda: ec.init_state(spec, C, A)

        # ---- static trigger grid per window ------------------------------
        make_triggers, self.T = build_trigger_grid(self.windows, wm_period_ms)
        P = wm_period_ms
        ooo = self.out_of_order_pct
        n_late = int(B * ooo)

        valid_all = np.ones((B,), bool)
        valid_late = np.zeros((B_late,), bool)
        valid_late[:n_late] = True

        # the reference's FIRST watermark clamps its trigger range to
        # wm - maxLateness (WindowManager.java:43-45, floored at the
        # bootstrap slice start 0); later watermarks continue from the
        # previous one. Latent until max_lateness < wm_period.
        first_lw = max(0, P - max_lateness)

        cdm = self.collect_device_metrics

        def step(state, dm, key, interval_idx):
            base = interval_idx * P
            last_wm = jnp.where(interval_idx > 0, base,
                                jnp.int64(first_lw))
            wm = base + P
            n_pre = state.n_slices

            def body(carry, g):
                st, dmc = carry
                kg = jax.random.fold_in(key, g)
                lo = (base + g * span).astype(jnp.float64)
                gaps = jax.random.uniform(kg, (B,), dtype=jnp.float32)
                gaps = gaps / jnp.sum(gaps) * span
                ts = lo.astype(jnp.int64) + jnp.cumsum(gaps).astype(jnp.int64)
                vals = jax.random.uniform(kg, (B,), dtype=jnp.float32) * 10_000
                st = ingest(st, ts, vals, valid_all)
                if B_late:
                    kl = jax.random.fold_in(kg, 7)
                    u = jax.random.uniform(kl, (2, B_late),
                                           dtype=jnp.float32)
                    lo_l = jnp.maximum(lo - max_lateness, 0.0)
                    lts = (lo_l + jnp.sort(u[0]).astype(jnp.float64)
                           * (lo - lo_l)).astype(jnp.int64)
                    lvals = u[1] * 10_000.0
                    if cdm:
                        # the arrival-order running max at this point IS
                        # st.max_event_time (the base sub-batch just
                        # folded), so the age calculus matches a host
                        # replay of the same arrival order exactly
                        lmask = jnp.asarray(valid_late)
                        dmc = _dev.record_late_ages(
                            dmc, st.max_event_time - lts, lmask)
                        dmc = dmc._replace(
                            late=dmc.late + jnp.sum(lmask))
                    st = ingest_general(st, lts, lvals,
                                        jnp.asarray(valid_late))
                return (st, dmc), None

            (state, dm), _ = jax.lax.scan(body, (state, dm),
                                          jnp.arange(G))
            if B_late:
                state = annex_merge(state)
            ws, we, tmask = make_triggers(last_wm, wm)
            is_count = jnp.zeros_like(tmask)
            cnt, results = query(state, ws, we, tmask, is_count)
            bound = wm - max_lateness - max_fixed
            if cdm:
                dm = dm._replace(
                    ingested=dm.ingested
                    + jnp.int64(G * (B + (n_late if B_late else 0))),
                    triggers=dm.triggers + jnp.sum(tmask),
                    windows_nonempty=dm.windows_nonempty
                    + jnp.sum(tmask & (cnt > 0)),
                    slices_touched=dm.slices_touched + jnp.maximum(
                        state.n_slices - n_pre, 0))
            state = gc(state, jnp.int64(bound))
            if cdm:
                dm = _dev.record_occupancy(dm, state.n_slices, C)
            return state, dm, (ws, we, cnt, results)

        self._step = jax.jit(step, donate_argnums=(0, 1))
        self._root = None
        self.state = None
        self._interval = 0

    def _init_pipeline_state(self) -> None:
        self.state = self._init_state()

    def check_overflow(self) -> None:
        import jax

        if bool(jax.device_get(self.state.overflow)):
            e = RuntimeError("slice buffer overflow: raise capacity or "
                             "advance watermarks more often")
            if self.obs is not None:
                self.obs.counter(_obs.OVERFLOWS).inc()
                self.obs.record_failure(e, kind=_flight.OVERFLOW,
                                        config=self.config)
            raise e

    def materialize_interval(self, i: int):
        """Regenerate interval i's tuple stream on host (testing), in
        ARRIVAL order: per sub-batch, the B in-order lanes then that
        sub-batch's late lanes. Uses the exact jnp op sequence of the
        fused step's generator, so the replay is bit-identical — the
        oracle face the device-telemetry differential tests replay
        through the host simulator."""
        import jax
        import jax.numpy as jnp

        if self._root is None:
            self._root = jax.random.PRNGKey(self.seed)
        key = self._interval_key(i)
        P, G, B, B_late = self.wm_period_ms, self.G, self.B, self.B_late
        span = P / G
        n_late = int(B * self.out_of_order_pct) if B_late else 0
        base = np.int64(i) * P
        max_lateness = self.max_lateness

        def one(g):
            kg = jax.random.fold_in(key, g)
            lo = (base + g * span).astype(jnp.float64)
            gaps = jax.random.uniform(kg, (B,), dtype=jnp.float32)
            gaps = gaps / jnp.sum(gaps) * span
            ts = lo.astype(jnp.int64) + jnp.cumsum(gaps).astype(jnp.int64)
            vals = jax.random.uniform(kg, (B,), dtype=jnp.float32) * 10_000
            if not B_late:
                return ts, vals
            kl = jax.random.fold_in(kg, 7)
            u = jax.random.uniform(kl, (2, B_late), dtype=jnp.float32)
            lo_l = jnp.maximum(lo - max_lateness, 0.0)
            lts = (lo_l + jnp.sort(u[0]).astype(jnp.float64)
                   * (lo - lo_l)).astype(jnp.int64)
            return ts, vals, lts, u[1] * 10_000.0

        parts_v, parts_t = [], []
        for g in range(G):
            out = jax.device_get(one(jnp.int64(g)))
            parts_v.append(out[1])
            parts_t.append(out[0])
            if B_late and n_late:
                parts_v.append(out[3][:n_late])
                parts_t.append(out[2][:n_late])
        return (np.concatenate(parts_v).astype(np.float32),
                np.concatenate(parts_t).astype(np.int64))

    def lowered_results(self, interval_out) -> list:
        """Fetch + lower one interval's window results on host."""
        return lower_interval(self.aggregations, interval_out)


def _gcd_all(xs):
    import math

    g = 0
    for x in xs:
        g = math.gcd(g, int(x))
    return g


class AlignedStreamPipeline(FusedPipelineDriver):
    """Slice-aligned fused pipeline — the flagship benchmark execution mode.

    TPU-first observation: scatters (especially int64 scatters) are the worst
    op class on TPU — the general ingest kernel's duplicate-index
    scatter-combines cost ~25 ms per 262 K-tuple batch on v5e, two orders of
    magnitude over the HBM bound. But the benchmark source is a *paced*
    generator (LoadGeneratorSource.java:45-57 emits a constant rate), so the
    stream can be generated **grouped by slice**: a [rows, R] block where row
    j holds exactly the R tuples of slice ``base + j*g`` (g = the slice grid
    = gcd of every window's slide AND size — sizes included so window end
    edges always land on the grid, closing the size-not-multiple-of-slide
    containment hole of the coarse union grid). Ingest then is:

    * per-row lift + combine — a dense row reduction (VPU-friendly, fuses
      with the on-device generator, no [B] scatter anywhere), and
    * one contiguous ``dynamic_update_slice`` append of the S new slices.

    This is the same slicing algebra — one partial per slice, windows
    answered by range queries over slice partials (build_query) — with the
    segmentation done by construction instead of by searched scatter. The
    whole watermark interval (generate → slice-combine → append → trigger →
    range-query → results) is ONE XLA program; GC amortizes over
    ``gc_every`` intervals.

    Constraints (fall back to :class:`StreamPipeline` otherwise): Time-measure
    tumbling/sliding windows only; dense-lift aggregations; wm_period_ms a
    multiple of the grid g; throughput*g/1000 ≥ 1 tuple per slice.
    """

    @staticmethod
    def slice_grid(windows, wm_period_ms: int) -> int:
        """The uniform slice grid: gcd of every window's slide and size AND
        the watermark period — every window edge and every watermark lands
        on a slice boundary."""
        members = [wm_period_ms]
        for w in windows:
            if not isinstance(w, (TumblingWindow, SlidingWindow,
                                  FixedBandWindow)):
                raise NotImplementedError(
                    f"no slice grid for {type(w).__name__}")
            members.append(int(w.size))
            if isinstance(w, SlidingWindow):
                members.append(int(w.slide))
        return _gcd_all(members)

    _uses_device_metrics = True

    def __init__(self, windows: Sequence, aggregations: Sequence[AggregateFunction],
                 config: Optional[EngineConfig] = None,
                 throughput: int = 200_000_000, wm_period_ms: int = 1000,
                 max_lateness: int = 1000, seed: int = 0, gc_every: int = 32,
                 max_chunk_elems: int = 1 << 25, value_scale: float = 10_000.0,
                 out_of_order_pct: float = 0.0,
                 collect_device_metrics: bool = True,
                 legacy_generator: bool = False,
                 query_slots: Optional[SlotGeometry] = None):
        import jax
        import jax.numpy as jnp

        from . import core as ec
        from ..obs import device as _dev

        self.collect_device_metrics = bool(collect_device_metrics)
        #: ADVICE r5: the r5 generator cheapened the benchmark workload
        #: itself (16-bit half-draws, offset stream dropped), so r4→r5
        #: cell comparisons mix engine speedup with workload reduction.
        #: ``legacy_generator=True`` pins the r4-era stream cost — one
        #: full 32-bit uniform draw per VALUE plus a generated per-tuple
        #: OFFSET stream (consumed by the row's t_first/t_last extrema,
        #: which stays containment-identical on the aligned grid) — so
        #: cross-round sweeps keep one workload-identical anchor cell.
        self.legacy_generator = bool(legacy_generator)
        self.config = config or EngineConfig()
        self.windows = list(windows)
        self.aggregations = list(aggregations)
        self.max_lateness = max_lateness
        self.wm_period_ms = wm_period_ms
        self.gc_every = gc_every
        self.seed = seed
        self.out_of_order_pct = float(out_of_order_pct)
        self.value_scale = float(value_scale)
        #: Pallas segmented-reduce fold for the generator lifts
        #: (EngineConfig.pallas_slice_merge; default off keeps the step
        #: HLO byte-identical — the pin asserts it)
        self._pallas_fold = bool(getattr(self.config, "pallas_slice_merge",
                                         False))
        self._pallas_packed = self._pallas_fold and bool(
            getattr(self.config, "pallas_packed", False))
        self._pallas_in_step = self._pallas_fold
        #: micro-batched streamed emission (EngineConfig.micro_batch):
        #: M micro-dispatches + one flush per interval via run_streamed
        self._micro_batch = int(getattr(self.config, "micro_batch", 0)
                                or 0)
        if self._micro_batch <= 1:
            self._micro_batch = 0

        max_fixed = 0
        for w in self.windows:
            if w.measure != WindowMeasure.Time or not isinstance(
                    w, (TumblingWindow, SlidingWindow)):
                raise NotImplementedError(
                    "aligned pipeline: Time tumbling/sliding only; use "
                    "StreamPipeline")
            max_fixed = max(max_fixed, w.clear_delay())
        for a in self.aggregations:
            if a.device_spec() is None:
                raise NotImplementedError(
                    "aligned pipeline: device-realizable aggregations only")
        #: dynamic-query serving mode (scotty_tpu.serving): the trigger
        #: grid reads a [Q] window-parameter table + active mask carried in
        #: the step's donated state instead of baking self.windows in. The
        #: slice grid and GC retention come from the SlotGeometry so state
        #: evolution is independent of the registered set — the property
        #: that makes register/cancel a mask write. None (default) leaves
        #: the static step byte-identical.
        self._query_slots = query_slots
        self._qs_host = None
        if query_slots is None:
            g = self.slice_grid(self.windows, wm_period_ms)
        else:
            g = int(query_slots.slice_grid)
            if wm_period_ms % g:
                raise ValueError(
                    f"SlotGeometry.slice_grid {g} must divide "
                    f"wm_period_ms {wm_period_ms}")
            for w in self.windows:
                sl = int(w.slide) if isinstance(w, SlidingWindow) \
                    else int(w.size)
                if int(w.size) % g or sl % g:
                    raise ValueError(
                        f"{w}: size/slide must be multiples of the serving "
                        f"slice grid {g} ms (aligned exactness)")
            max_fixed = max(max_fixed, int(query_slots.max_size))
        if throughput * g % 1000:
            raise ValueError(
                f"throughput {throughput} is not an integer number of tuples "
                f"per {g} ms slice — the generated load would silently fall "
                "short of the requested rate")
        R = throughput * g // 1000
        if R < 1:
            raise ValueError("throughput too low: <1 tuple per slice")
        S = wm_period_ms // g
        self.grid, self.R, self.S = g, R, S
        self.max_fixed = max_fixed
        # Out-of-order mode: per interval, L extra LATE tuples — event times
        # uniform in [max(0, base - max_lateness), base), arriving at the
        # START of the interval (so their displacement never exceeds
        # max_lateness relative to the stream's max event time, the
        # reference contract WindowOperator.java:31-37). On the aligned
        # grid every covering slice row is materialized (the base stream
        # fills every row), so the late fold needs NO annex, NO sort and NO
        # search: covering rows are affine in the grid start, and the
        # combines are bounded [L]-lane scatters. t_last is deliberately
        # NOT updated by late lanes: on the aligned grid every window edge
        # is a slice edge, so t_last containment (AggregateWindowState.java:
        # 25-31) is equivalent to start containment — and skipping it
        # avoids the dominant int64 scatter (~100 ms per 1M lanes on v5e).
        L_req = int(S * R * self.out_of_order_pct)
        # Dense-agg late streams use the SEGMENT fold (r4, VERDICT r3 item
        # 5): late tuples are generated pre-grouped by slice row over the
        # contiguous lateness span, so the fold is dynamic_slice + row
        # reduce + dynamic_update_slice — zero scatters (the [L]-lane
        # scatters were ~0.6 s of the drained OOO interval). Sparse
        # (sketch) aggregations keep the scatter fold.
        self._late_span = 0
        self._late_R = 0
        if L_req and all(not a.device_spec().is_sparse
                         for a in self.aggregations):
            span = max(1, min(max_lateness // g, self.config.capacity - 1))
            self._late_span = span
            self._late_R = -(-L_req // span)       # ceil: offered is a floor
            self.n_late = span * self._late_R
        else:
            self.n_late = L_req
        self.tuples_per_interval = S * R + self.n_late

        # Sparse-lift strategy per aggregation:
        # * sum-kind sketches (DDSketch histograms) take the FACTORED
        #   MXU histogram: width = WA·WB, so the [R, width] one-hot
        #   factors into two small one-hots [R, WA]·[R, WB] and the
        #   per-row histogram is their contraction A^T·B — a batched
        #   matmul that puts the 2048-wide accumulation on the systolic
        #   array instead of a serialized scatter or a VPU-bound
        #   [R, 2048] densify (the r4 cost model, 556 M t/s ceiling).
        #   Lift temporaries shrink from R·width to R·(WA+WB).
        # * min/max sketches (HLL registers) keep the one-hot densify
        #   (budget permitting) or the flat scatter — max doesn't ride
        #   a matmul contraction.
        onehot_ok = {}
        self._factored = {}
        max_width = 1
        for a in self.aggregations:
            sp = a.device_spec()
            # multi-cell sketches (count-min) skip the factored/one-hot
            # strategies — both assume one column per lane — and take the
            # flat scatter, whose advanced-index broadcast fans the [B]
            # row ids across the d cells
            if sp.is_sparse and sp.kind == "sum" \
                    and sp.cells_per_tuple == 1:
                wa = 1 << ((sp.width.bit_length()) // 2)
                if wa * (sp.width // wa) == sp.width:
                    self._factored[sp.token] = (wa, sp.width // wa)
                    max_width = max(max_width, wa + sp.width // wa)
                    continue
            if sp.is_sparse:
                onehot_ok[sp.token] = (sp.cells_per_tuple == 1
                                       and R * sp.width <= max_chunk_elems)
                if onehot_ok[sp.token]:
                    max_width = max(max_width, sp.width)
            else:
                max_width = max(max_width, sp.width)
        # rows per generation chunk: the static heuristic picks the largest
        # divisor of S within the budget (the budget counts lifted elements,
        # so wide sketch partials shrink the chunk rather than exploding the
        # [d*R, width] lift temporary). The measured-throughput sweet spot
        # is shape-dependent beyond this model (VERDICT r3 weak-2) —
        # ``autotune_chunk()`` times candidate shapes and keeps the winner.
        self._max_width = max_width
        self._max_chunk_elems = max_chunk_elems
        d = 1
        for cand in range(1, S + 1):
            if S % cand == 0 and cand * R * max_width <= max_chunk_elems:
                d = cand
        self._heuristic_d = d
        # Sub-row chunking (r5): coarse grids put the whole interval in a
        # handful of rows (S=1, R=800M for Sliding(60s,10s) at 800M/s), so
        # even d=1 materializes a multi-GB row and the generator+reduce
        # can't tile. When one row exceeds the budget, the scan iterates
        # over n_sub sub-chunks per row (smallest divisor count bringing
        # R/n_sub within budget), keyed per ABSOLUTE (row, sub) pair —
        # the sub-chunked stream is a pure function of the pipeline
        # parameters, and materialize_interval replays it bit-exactly.
        n_sub = 1
        if R * max_width > max_chunk_elems:
            n_sub = min(-(-R * max_width // max_chunk_elems), R)
            while R % n_sub and n_sub < R:
                n_sub += 1
            # degenerate budgets (max_width > max_chunk_elems) land on
            # q = 1 lanes per chunk rather than spinning or crashing
        if self._micro_batch:
            # micro-batching dispatches the interval's sub-chunks in M
            # groups, so the generation MUST use the per-(row, sub)
            # keying on both paths — force the sub-row chunking on (and
            # divisible by M) so run() and run_streamed() draw the
            # identical stream and bit-match
            if legacy_generator:
                raise NotImplementedError(
                    "micro_batch: the legacy anchor generator is "
                    "whole-interval only (cross-round workload pin)")
            if query_slots is not None:
                raise NotImplementedError(
                    "micro_batch: serving mode steps whole intervals "
                    "(the query table rides the interval carry)")
            M = self._micro_batch
            n_sub = max(n_sub, 2)
            while n_sub <= R and (R % n_sub or (S * n_sub) % M):
                n_sub += 1
            if n_sub > R:
                raise ValueError(
                    f"micro_batch {M}: no sub-chunk count divides both "
                    f"R={R} lanes/row and M micro-batches — pick M "
                    "dividing the interval's tuple count")
        self._n_sub = n_sub

        spec = ec.EngineSpec(
            periods=(g,), bands=(), count_periods=(),
            aggs=tuple(a.device_spec() for a in self.aggregations))
        self.spec = spec
        C, A = self.config.capacity, self.config.annex_capacity
        query = ec.build_query(spec, C, A)
        self._gc_kernel = jax.jit(ec.build_gc(spec, C, A), donate_argnums=0)
        self._init_state = lambda: ec.init_state(spec, C, A)
        if query_slots is None:
            make_triggers, self.T = build_trigger_grid(self.windows,
                                                       wm_period_ms)
        else:
            make_triggers, self.T = build_slot_trigger_grid(query_slots,
                                                            wm_period_ms)
        self._make_triggers = make_triggers
        self._write_slot_fn = None
        P = wm_period_ms

        red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}

        first_lw = max(0, P - max_lateness)   # first-watermark clamp
                                              # (WindowManager.java:43-45)
        L = self.n_late
        cdm = self.collect_device_metrics

        def late_fold(state, dm, key, base):
            """Fold this interval's late tuples into their covering slices.

            Runs BEFORE the base append: at this point the top slice is the
            previous interval's last row (start == base - g), so a late
            tuple with grid start gs sits at row
            ``n_slices - 1 - (base - g - gs) / g`` — affine, no search.
            Rows behind the GC horizon cannot occur (the GC bound
            ``wm - max_lateness - max_fixed`` keeps every row the late span
            can touch). Interval 0 has no earlier span: all lanes masked.
            """
            # fold constant outside the per-row key range [0, S) so the
            # late stream never collides with a slice row's stream
            kl = jax.random.fold_in(key, 0x7fffffff)
            u = jax.random.uniform(kl, (2, L), dtype=jnp.float32)
            lo_l = jnp.maximum(base - max_lateness, 0).astype(jnp.float64)
            span_l = base.astype(jnp.float64) - lo_l
            lts = (lo_l + u[0].astype(jnp.float64) * span_l).astype(jnp.int64)
            lts = jnp.minimum(lts, base - 1)
            lvals = u[1] * value_scale
            ok = base > 0                      # scalar; interval-0 guard
            gs = lts - jnp.mod(lts, g)
            row = (state.n_slices.astype(jnp.int64) - 1
                   - (base - g - gs) // g)
            # out-of-range sentinel + identity-masked values + mode="drop":
            # masked lanes can neither combine nor clamp onto a live row.
            # Negative rows (outside the GC invariant) must hit the sentinel
            # too — JAX normalizes negative indices onto live slices.
            lane_ok = ok & (row >= 0)
            pos = jnp.where(lane_ok, row, C).astype(jnp.int32)
            d32 = jnp.zeros((C,), jnp.int32).at[pos].add(
                jnp.int32(1), mode="drop")
            partials = []
            for aspec, part in zip(spec.aggs, state.partials):
                if aspec.is_sparse:
                    col, v = aspec.lift_sparse(lvals)
                    v = jnp.where(ok, v, aspec.identity)
                    idx = (pos, col)
                else:
                    v = aspec.lift_dense(lvals)
                    v = jnp.where(ok, v, aspec.identity)
                    idx = (pos,)
                if aspec.kind == "sum":
                    part = part.at[idx].add(v, mode="drop")
                elif aspec.kind == "min":
                    part = part.at[idx].min(v, mode="drop")
                else:
                    part = part.at[idx].max(v, mode="drop")
                partials.append(part)
            n_ok = jnp.where(ok, jnp.int64(L), jnp.int64(0))
            bad = ok & jnp.any((row < 0)
                               | (row >= state.n_slices.astype(jnp.int64)))
            if cdm:
                # EXACT arrival-order lateness: the canonical stream (the
                # materialize_* replay faces) has the base tuples at their
                # row starts, so the running max entering this fold is
                # base - g; within the fold it evolves lane by lane
                # (cummax), and a lane is late iff its ts is strictly
                # below the running max at ITS arrival — the same
                # calculus a host replay of the arrival order computes.
                seed = jnp.reshape(base - g, (1,))
                rm = jax.lax.cummax(jnp.concatenate([seed, lts[:-1]]))
                late_m = ok & (lts < rm)
                dm = _dev.record_late_ages(dm, rm - lts, late_m)
                dm = dm._replace(
                    ingested=dm.ingested + n_ok,
                    late=dm.late + jnp.sum(late_m),
                    dropped=dm.dropped + jnp.sum(
                        jnp.where(ok & (row < 0), jnp.int64(1), 0)),
                    slices_touched=dm.slices_touched
                    + jnp.sum((d32 > 0).astype(jnp.int64)))
            return state._replace(
                counts=state.counts + d32.astype(jnp.int64),
                partials=tuple(partials),
                current_count=state.current_count + n_ok,
                overflow=state.overflow | bad), dm

        def gen_rows(key, rows):
            """The paced generator: R tuples per slice row (the reference's
            constant-rate LoadGeneratorSource), values uniform over 65536
            levels in [0, value_scale). Keyed per ABSOLUTE slice row (not
            per chunk), so the stream is a function of (interval, row)
            alone and any chunk regrouping (``set_rows_per_chunk``/
            ``autotune_chunk``) generates bit-identical tuples.

            The RNG is a first-order throughput term (threefry sustains
            ~9 G 32-bit lanes/s on v5e), so — as in the keyed pipeline —
            each 32-bit draw yields TWO 16-bit-granular values, and the
            per-tuple OFFSET stream is not generated at all: on the
            aligned grid every window edge is a slice edge, so intra-slice
            tuple placement is unobservable (t_last containment ≡ start
            containment) and tuples sit at their row start."""
            keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(rows)
            return jax.vmap(
                lambda k: draw_uniform16(k, (R,), value_scale))(keys)

        def gen_lanes(kk, n):
            """[n] values from one key — the sub-row chunk generator
            (same half-draw block layout as gen_rows)."""
            return draw_uniform16(kk, (n,), value_scale)

        span_l8 = self._late_span
        R_l8 = self._late_R

        def late_fold_segment(state, dm, key, base):
            """Scatter-free late fold (dense aggs): this interval's late
            tuples, R_l8 per slice row over the ``span_l8`` rows covering
            [base - max_lateness, base) — a stratified rendering of the
            same uniform late load. The target rows are CONTIGUOUS (the
            aligned base stream materializes every row), so the fold is a
            slice read + per-row reduce + slice write. RNG is keyed per
            absolute row (0x70000000 | row — disjoint from the base
            stream's per-row keys), t_last deliberately untouched (start
            containment ≡ t_last containment on the aligned grid)."""
            n = state.n_slices
            start = jnp.clip(n - span_l8, 0, C - span_l8)
            rows = (start + jnp.arange(span_l8)).astype(jnp.int64)
            row_ts = base + (rows - n.astype(jnp.int64)) * g
            lo_l = jnp.maximum(base - max_lateness, 0)
            # rows with row_ts in [lo_l, base) are always live on the
            # aligned grid (the base stream materializes every row and the
            # GC bound keeps the lateness span — `bad` below flags any
            # violation), so validity is a pure function of ts and the
            # host replay needs no GC-history row count
            valid = (row_ts >= lo_l) & (row_ts < base)
            # RNG keyed by ABSOLUTE grid index (ts/g): GC-independent and
            # disjoint from the base stream's per-interval-row keys
            keys = jax.vmap(lambda t: jax.random.fold_in(
                key, 0x70000000 + t // g))(row_ts)
            u = jax.vmap(lambda k: jax.random.uniform(
                k, (2, R_l8), dtype=jnp.float32))(keys)  # [span, 2, R]
            lvals = u[:, 0] * value_scale
            add_cnt = jnp.where(valid, jnp.int64(R_l8), 0)
            cnt_sl = jax.lax.dynamic_slice(state.counts, (start,),
                                           (span_l8,))
            counts = jax.lax.dynamic_update_slice(
                state.counts, cnt_sl + add_cnt, (start,))
            partials = []
            for aspec, part in zip(spec.aggs, state.partials):
                lifted = aspec.lift_dense(lvals.reshape(-1)).reshape(
                    span_l8, R_l8, -1)
                upd = red[aspec.kind](lifted, axis=1)      # [span, w]
                ident = jnp.asarray(aspec.identity, part.dtype)
                w = part.shape[1]
                ps = jax.lax.dynamic_slice(part, (start, jnp.int32(0)),
                                           (span_l8, w))
                if aspec.kind == "sum":
                    comb = ps + jnp.where(valid[:, None], upd, 0)
                elif aspec.kind == "min":
                    comb = jnp.minimum(ps, jnp.where(valid[:, None], upd,
                                                     ident))
                else:
                    comb = jnp.maximum(ps, jnp.where(valid[:, None], upd,
                                                     ident))
                partials.append(jax.lax.dynamic_update_slice(
                    part, comb, (start, jnp.int32(0))))
            # GC mistuning: the late span needs (base - lo_l)/g rows; fewer
            # live/covered rows means silently lost late tuples — flag it
            needed = (base - lo_l) // g
            have = jnp.minimum(n.astype(jnp.int64), jnp.int64(span_l8))
            bad = (base > 0) & (needed > have)
            if cdm:
                # EXACT arrival-order lateness (see late_fold): the
                # stratified rendering has real per-tuple offsets in the
                # replay face (materialize_interval_late u[:, 1]); replay
                # order is rows ascending, lanes in draw order. Running
                # max enters at base - g (the canonical stream's head)
                # and evolves by cummax over the flattened lane order.
                offs = jnp.clip(jnp.floor(u[:, 1] * jnp.float32(g)), 0,
                                g - 1).astype(jnp.int64)   # [span, R]
                lts_full = row_ts[:, None] + offs
                lane_ok = jnp.broadcast_to(valid[:, None], lts_full.shape)
                flat = jnp.where(lane_ok, lts_full,
                                 jnp.int64(-(1 << 62))).reshape(-1)
                seed = jnp.reshape(base - g, (1,))
                rm = jax.lax.cummax(jnp.concatenate([seed, flat[:-1]]))
                late_m = lane_ok.reshape(-1) & (flat < rm)
                dm = _dev.record_late_ages(dm, rm - flat, late_m)
                dm = dm._replace(
                    ingested=dm.ingested + jnp.sum(add_cnt),
                    late=dm.late + jnp.sum(late_m),
                    slices_touched=dm.slices_touched
                    + jnp.sum(valid.astype(jnp.int64)))
            return state._replace(
                counts=counts, partials=tuple(partials),
                current_count=state.current_count + jnp.sum(add_cnt),
                overflow=state.overflow | bad), dm

        late_fold_active = late_fold_segment if span_l8 else late_fold

        n_sub = self._n_sub
        legacy = self.legacy_generator
        if legacy and n_sub > 1:
            raise NotImplementedError(
                "legacy_generator: pick a shape whose rows fit the chunk "
                "budget (sub-row chunking postdates the r4 generator)")
        if legacy and L:
            raise NotImplementedError(
                "legacy_generator: the cross-round anchor cell is "
                "in-order (out_of_order_pct must be 0)")

        def gen_rows_legacy(key, rows):
            """The r4-era generator, pinned for the cross-round anchor
            cell (ADVICE r5): one full 32-bit uniform draw per VALUE and
            a generated per-tuple OFFSET stream (uniform in [0, g)), both
            keyed per absolute row. The offsets feed the row's
            t_first/t_last extrema — containment-identical on the aligned
            grid, but the draws stay live so the workload cost matches
            r4, not r5's halved-draw stream."""
            keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(rows)
            vals = jax.vmap(lambda k: jax.random.uniform(
                k, (R,), dtype=jnp.float32) * value_scale)(keys)
            offs = jax.vmap(lambda k: jnp.clip(jnp.floor(
                jax.random.uniform(jax.random.fold_in(k, 1), (R,),
                                   dtype=jnp.float32) * g),
                0, g - 1).astype(jnp.int64))(keys)
            return vals, offs

        def lift_chunk(flat, dd, RR):
            """Per-aggregation [dd, width] partials of a flat [dd*RR]
            value chunk — the sparse/factored/dense strategy block shared
            by row-granular and sub-row chunking."""
            parts = []
            for aspec in spec.aggs:
                if self._pallas_fold:
                    # Pallas segmented-reduce fold (ROADMAP item 4):
                    # lane blocks stream HBM→VMEM and reduce per slice
                    # row — replaces the one-hot/factored densifies AND
                    # the multi-cell sparse flat scatter below
                    from .. import pallas as _spl

                    if aspec.is_sparse:
                        col, v = aspec.lift_sparse(flat)
                        parts.append(_spl.sparse_row_fold(
                            col, v, dd, RR, aspec.width, aspec.kind,
                            aspec.identity))
                    else:
                        lifted = aspec.lift_dense(flat)
                        parts.append(_spl.row_fold(
                            lifted, dd, RR, aspec.kind, aspec.identity,
                            packed=self._pallas_packed))
                    continue
                if aspec.is_sparse and aspec.token in self._factored:
                    # factored MXU histogram (see strategy note):
                    # hist[row] = A^T·B with A, B the hi/lo one-hots
                    wa, wb = self._factored[aspec.token]
                    col, v = aspec.lift_sparse(flat)
                    hi = (col // wb).astype(jnp.int32)
                    lo = (col - hi * wb).astype(jnp.int32)
                    A = jnp.where(
                        hi[:, None] == jnp.arange(wa)[None, :],
                        v[:, None], 0.0).reshape(dd, RR, wa)  # carries v
                    Bm = (lo[:, None]
                          == jnp.arange(wb)[None, :]).astype(
                              jnp.bfloat16).reshape(dd, RR, wb)
                    hist = jnp.einsum(
                        "drk,drl->dkl", A, Bm,
                        preferred_element_type=jnp.float32)
                    parts.append(hist.reshape(dd, wa * wb))
                elif aspec.is_sparse and onehot_ok[aspec.token]:
                    # one-hot densify + row reduce (see strategy note
                    # in __init__)
                    col, v = aspec.lift_sparse(flat)
                    lifted = jnp.where(
                        col[:, None] == jnp.arange(aspec.width)[None, :],
                        v[:, None], jnp.asarray(aspec.identity,
                                                v.dtype))
                    lifted = lifted.reshape(dd, RR, -1)
                    parts.append(red[aspec.kind](lifted, axis=1))
                elif aspec.is_sparse:
                    # flat [dd*width] f32 scatter — per-lane cost only
                    col, v = aspec.lift_sparse(flat)
                    row_id = jnp.arange(dd * RR, dtype=jnp.int32) // RR
                    fi = row_id * aspec.width + col.astype(jnp.int32)
                    tgt = jnp.full((dd * aspec.width,), aspec.identity,
                                   jnp.float32)
                    if aspec.kind == "sum":
                        tgt = tgt.at[fi].add(v)
                    elif aspec.kind == "min":
                        tgt = tgt.at[fi].min(v)
                    else:
                        tgt = tgt.at[fi].max(v)
                    parts.append(tgt.reshape(dd, aspec.width))
                else:
                    lifted = aspec.lift_dense(flat).reshape(dd, RR, -1)
                    parts.append(red[aspec.kind](lifted, axis=1))
            return parts

        q_sub = R // n_sub

        def sub_chunk(key, c):
            """One (row, sub) generation+lift sub-chunk — shared verbatim
            by the whole-interval scan and the micro-batched step, so
            the two dispatch shapes draw the identical stream and their
            results bit-match."""
            row = c // n_sub
            s_i = c % n_sub
            kk = jax.random.fold_in(
                jax.random.fold_in(key, row),
                0x5f000000 + s_i)
            if q_sub % 2 == 0:
                lo, hi = half_draw_parts(
                    jax.random.bits(kk, (q_sub // 2,),
                                    dtype=jnp.uint32),
                    value_scale)
                pl = lift_chunk(lo, 1, q_sub // 2)
                ph = lift_chunk(hi, 1, q_sub // 2)
                out = []
                for aspec, a, b in zip(spec.aggs, pl, ph):
                    if aspec.kind == "sum":
                        out.append((a + b)[0])
                    elif aspec.kind == "min":
                        out.append(jnp.minimum(a, b)[0])
                    else:
                        out.append(jnp.maximum(a, b)[0])
                return tuple(out)
            flat = gen_lanes(kk, q_sub)
            return tuple(p[0] for p in lift_chunk(flat, 1, q_sub))

        def finish_interval(state, dm, qs, base, interval_idx, parts,
                            off_first_rows=None, off_last_rows=None):
            """Append the interval's folded rows + trigger/query/GC-side
            bookkeeping — the step tail, shared verbatim by the
            whole-interval step and the micro-batched flush."""
            row_starts = base + g * jnp.arange(S, dtype=jnp.int64)
            # tuples sit at their row start (the offset stream is
            # unobservable on the aligned grid and not generated — see
            # gen_rows); t_last takes the conservative row bound, which
            # gives IDENTICAL query containment for grid-aligned edges.
            # The legacy anchor generates real offsets and uses their
            # extrema instead (same containment on the aligned grid).
            t_first = row_starts if off_first_rows is None \
                else row_starts + off_first_rows
            t_last = row_starts + (g - 1) if off_last_rows is None \
                else row_starts + off_last_rows
            n = state.n_slices

            def app(buf, rows):
                idx = (n,) + (jnp.int32(0),) * (buf.ndim - 1)
                return jax.lax.dynamic_update_slice(
                    buf, rows.astype(buf.dtype), idx)

            state = state._replace(
                starts=app(state.starts, row_starts),
                ends=app(state.ends, row_starts + g),
                t_first=app(state.t_first, t_first),
                t_last=app(state.t_last, t_last),
                c_start=app(state.c_start, state.current_count
                            + R * jnp.arange(S, dtype=jnp.int64)),
                counts=app(state.counts, jnp.full((S,), R, jnp.int64)),
                partials=tuple(
                    app(p, pr)
                    for p, pr in zip(state.partials, parts)),
                n_slices=n + S,
                max_event_time=jnp.maximum(state.max_event_time, t_last[-1]),
                current_count=state.current_count + S * R,
                overflow=state.overflow | (n + S > C),
            )
            last_wm = jnp.where(interval_idx > 0, base,
                                jnp.int64(first_lw))
            if qs is None:
                ws, we, tmask = self._make_triggers(last_wm, base + P)
            else:
                ws, we, tmask = self._make_triggers(qs, last_wm, base + P)
            cnt, results = query(state, ws, we, tmask,
                                 jnp.zeros_like(tmask))
            if cdm:
                dm = dm._replace(
                    ingested=dm.ingested + jnp.int64(S * R),
                    triggers=dm.triggers + jnp.sum(tmask),
                    windows_nonempty=dm.windows_nonempty
                    + jnp.sum(tmask & (cnt > 0)),
                    slices_touched=dm.slices_touched + jnp.int64(S))
                dm = _dev.record_occupancy(dm, state.n_slices, C)
            if qs is None:
                return state, dm, (ws, we, cnt, results)
            return state, dm, qs, (ws, we, cnt, results)

        def step_impl(state, dm, qs, key, interval_idx, d):
            base = interval_idx * P
            if L:
                state, dm = late_fold_active(state, dm, key, base)

            off_first_rows = off_last_rows = None
            if n_sub > 1:
                # sub-row chunking (see __init__): q lanes of one row per
                # scan step, keyed per absolute (row, sub) pair. The two
                # 16-bit halves lift SEPARATELY and combine as partials —
                # concatenating them first is a fusion breaker that
                # materializes every chunk (measured 178 ms vs 56 ms per
                # 800 M-tuple interval); regrouping the fold is sound for
                # the commutative combine kinds (sum/min/max), and the
                # replayed stream is the same multiset at the same ts.
                def body(_, c):
                    return None, sub_chunk(key, c)

                _, stacked = jax.lax.scan(
                    body, None, jnp.arange(S * n_sub, dtype=jnp.int64))
                parts = tuple(
                    red[a.kind](p.reshape(S, n_sub, -1), axis=1)
                    for a, p in zip(spec.aggs, stacked))
            elif legacy:
                def body(_, c):
                    rows = c * d + jnp.arange(d, dtype=jnp.int64)
                    vals, offs = gen_rows_legacy(key, rows)
                    return None, (tuple(lift_chunk(vals.reshape(-1), d, R)),
                                  jnp.min(offs, axis=1),
                                  jnp.max(offs, axis=1))

                _, (stacked, off_mins, off_maxs) = jax.lax.scan(
                    body, None, jnp.arange(S // d))
                parts = tuple(p.reshape(S, -1) for p in stacked)
                off_first_rows = off_mins.reshape(S)
                off_last_rows = off_maxs.reshape(S)
            else:
                def body(_, c):
                    vals = gen_rows(
                        key, c * d + jnp.arange(d, dtype=jnp.int64))
                    return None, tuple(lift_chunk(vals.reshape(-1), d, R))

                _, stacked = jax.lax.scan(
                    body, None, jnp.arange(S // d))
                parts = tuple(p.reshape(S, -1) for p in stacked)

            return finish_interval(state, dm, qs, base, interval_idx,
                                   parts, off_first_rows, off_last_rows)

        self._step_impl = step_impl

        # -- micro-batched step (EngineConfig.micro_batch, ISSUE 15) -------
        # The interval's S*n_sub sub-chunks dispatch in M groups; the
        # per-(row, sub) slabs accumulate in a donated carry and ONE
        # flush program reduces + appends + triggers — byte-for-byte
        # finish_interval, so a streamed run bit-matches run(). Built
        # only when the flag is on: the flags-off trace set (and every
        # HLO pin) is untouched.
        if self._micro_batch:
            Mb = self._micro_batch
            T_sub = S * n_sub
            cpm = T_sub // Mb
            widths = tuple(a.width for a in spec.aggs)

            def micro_step(state, dm, slab, key, interval_idx, m):
                base = interval_idx * P
                if L:
                    state, dm = jax.lax.cond(
                        m == 0,
                        lambda sd: late_fold_active(sd[0], sd[1], key,
                                                    base),
                        lambda sd: sd,
                        (state, dm))

                def body(_, c):
                    return None, sub_chunk(key, c)

                cs = (m.astype(jnp.int64) * cpm
                      + jnp.arange(cpm, dtype=jnp.int64))
                _, stacked = jax.lax.scan(body, None, cs)
                slab = tuple(
                    jax.lax.dynamic_update_slice(
                        sl, st.astype(sl.dtype),
                        (m * cpm, jnp.int32(0)))
                    for sl, st in zip(slab, stacked))
                return state, dm, slab

            def micro_flush(state, dm, slab, key, interval_idx):
                self._trace_count += 1
                base = interval_idx * P
                parts = tuple(
                    red[a.kind](p.reshape(S, n_sub, -1), axis=1)
                    for a, p in zip(spec.aggs, slab))
                return finish_interval(state, dm, None, base,
                                       interval_idx, parts)

            self._micro_step_fn = jax.jit(micro_step,
                                          donate_argnums=(0, 1, 2))
            # the slab is consumed by the reduce, not carried through —
            # donating it would only warn (no output aliases its shape)
            self._micro_flush_fn = jax.jit(micro_flush,
                                           donate_argnums=(0, 1))
            # slab zeros materialize INSIDE a jitted thunk: an eager
            # jnp.zeros implicitly uploads its fill scalar, which the
            # transfer-guard differential arm (rightly) rejects
            self._micro_slab_init = jax.jit(lambda: tuple(
                jnp.zeros((T_sub, w), jnp.float32) for w in widths))
            self._micro_shape = (T_sub, cpm, widths)
        self._gen_rows = gen_rows
        self._gen_lanes = gen_lanes
        #: the generator the ACTIVE step closes over (legacy anchor cells
        #: trace gen_rows_legacy) — the bench's generator-share probe
        #: times exactly this stream cost (ISSUE 11; a separate jit, the
        #: pinned step HLO is untouched)
        self._gen_active = gen_rows_legacy if legacy else gen_rows
        self.set_rows_per_chunk(self._heuristic_d)
        self._root = None
        self.state = None
        self._interval = 0

    def set_rows_per_chunk(self, d: int) -> None:
        """Re-jit the interval step at a new generation-chunk shape (d slice
        rows per chunk; must divide S). State shapes and the generated
        stream are unaffected (per-row RNG keying). A FRESH closure per
        shape — jax's jit cache is keyed on the function object, so
        re-wrapping the same function would silently keep executing the
        originally traced shape (r4 review finding)."""
        import jax

        d = int(d)
        if d < 1 or self.S % d:
            raise ValueError(f"rows_per_chunk {d} must divide S={self.S}")
        self.rows_per_chunk = d
        self._n_chunks = self.S // d
        impl = self._step_impl

        if self._query_slots is None:
            def step_at_d(state, dm, key, interval_idx):
                # host-side trace counter: this body runs once per jit
                # TRACE (the serving layer's zero-retrace contract reads
                # it); no traced ops — the emitted HLO is unchanged
                self._trace_count += 1
                return impl(state, dm, None, key, interval_idx, d)

            self._step = jax.jit(step_at_d, donate_argnums=(0, 1))
        else:
            def step_at_d(state, dm, qs, key, interval_idx):
                self._trace_count += 1
                return impl(state, dm, qs, key, interval_idx, d)

            # the query table is part of the donated carry: XLA aliases it
            # straight through (it is returned untouched), so the steady-
            # state step moves zero extra bytes for it
            self._step = jax.jit(step_at_d, donate_argnums=(0, 1, 2))
        self._pipeline_ready = False

    def chunk_candidates(self, k: int = 3) -> list:
        """Up to ``k`` log-spaced candidate chunk shapes within the lifted-
        element budget, largest (the static heuristic's pick) first."""
        ds = [c for c in range(1, self.S + 1)
              if self.S % c == 0
              and c * self.R * self._max_width <= self._max_chunk_elems]
        if not ds:
            return [1]
        picks = []
        for i in range(k):
            j = round((len(ds) - 1) * (1 - i / max(k - 1, 1)))
            if ds[j] not in picks:
                picks.append(ds[j])
        return picks

    def autotune_chunk(self, reps: int = 2, candidates=None,
                       budget_s: float = None) -> dict:
        """Measure candidate chunk shapes (one compile + ``reps`` timed
        intervals each, idle-subtracted device_get syncs) and keep the
        fastest.
        The engine owns the sweet spot instead of a hand-set bench constant
        (VERDICT r3 item 3). Returns {d: seconds_per_interval}; stops early
        when ``budget_s`` wall seconds are spent, keeping the best so far."""
        import time as _time

        cands = list(candidates) if candidates else self.chunk_candidates()
        timings: dict = {}
        t_start = _time.perf_counter()
        for d in cands:
            self.set_rows_per_chunk(d)
            self.reset()
            self.run(1, collect=False)
            self.sync()                     # compile + warm
            t0 = _time.perf_counter()
            self.sync()
            idle = _time.perf_counter() - t0
            t0 = _time.perf_counter()
            self.run(reps, collect=False)
            self.sync()
            timings[d] = max((_time.perf_counter() - t0 - idle) / reps,
                             1e-9)
            if budget_s is not None \
                    and _time.perf_counter() - t_start > budget_s:
                break
        best = min(timings, key=timings.get)
        self.set_rows_per_chunk(best)
        self.reset()
        return timings

    def _init_pipeline_state(self) -> None:
        self.state = self._init_state()
        if self._query_slots is not None:
            self._qstate = init_query_slots(self._query_slots, self._qs_host)

    # -- dynamic-query serving hooks (scotty_tpu.serving) ------------------
    def set_query_rows(self, rows: Optional[dict]) -> None:
        """Bind the HOST mirror of the query table (numpy ``kinds/grids/
        sizes/active`` rows, kept by the serving layer's QueryTable — held
        by reference, so in-place row writes stay visible). ``reset()``
        and checkpoint restores re-upload the table from this mirror, so
        a restore replays the active query set."""
        if self._query_slots is None:
            raise ValueError("not a serving pipeline (query_slots=None)")
        self._qs_host = rows
        if getattr(self, "_pipeline_ready", False):
            self._qstate = init_query_slots(self._query_slots, rows)

    def write_query_slot(self, slot: int, kind: int, grid: int, size: int,
                         active: bool) -> None:
        """One-row device table write — the register/cancel hot path. The
        row index and parameters are traced arguments, so every write (any
        slot, any geometry-compatible window) reuses ONE compiled
        executable; the table buffer is donated and updated in place."""
        import jax

        if self._qstate is None:
            if self._query_slots is None:
                raise ValueError("not a serving pipeline")
            self.reset()
        if self._write_slot_fn is None:
            def w(qs, i, kind, grid, size, act):
                return QuerySlots(
                    kinds=qs.kinds.at[i].set(kind),
                    grids=qs.grids.at[i].set(grid),
                    sizes=qs.sizes.at[i].set(size),
                    active=qs.active.at[i].set(act))

            self._write_slot_fn = jax.jit(w, donate_argnums=0)
        self._qstate = self._write_slot_fn(
            self._qstate, np.int32(slot), np.int32(kind), np.int64(grid),
            np.int64(size), np.bool_(active))

    def set_slot_geometry(self, geometry: SlotGeometry) -> None:
        """Rebuild the step at a new slot-grid bucket (a counted retrace;
        scotty_tpu.serving.cache keeps the old bucket's executable warm).
        The carried slice state is untouched — its shapes are independent
        of the query set — so a rebucket continues the stream exactly."""
        if self._query_slots is None:
            raise ValueError("not a serving pipeline (query_slots=None)")
        if int(geometry.slice_grid) != self.grid:
            raise ValueError(
                f"slot-geometry slice grid {geometry.slice_grid} != the "
                f"pipeline's aligned grid {self.grid}: the slice grid is "
                "state-shaping and cannot change at a rebucket")
        ready = getattr(self, "_pipeline_ready", False)
        self._query_slots = geometry
        self._make_triggers, self.T = build_slot_trigger_grid(
            geometry, self.wm_period_ms)
        self.set_rows_per_chunk(self.rows_per_chunk)
        # rebucketing must NOT wipe mid-stream state (set_rows_per_chunk
        # marks the pipeline for reset — correct for autotuning, wrong
        # here); the caller re-uploads the re-padded table
        self._pipeline_ready = ready

    def compiled_step(self):
        """(step, make_triggers, T, geometry, rows_per_chunk) — what the
        serving compile cache stores per bucket."""
        return (self._step, self._make_triggers, self.T, self._query_slots,
                self.rows_per_chunk)

    def adopt_compiled_step(self, entry) -> None:
        """Re-enter a previously compiled bucket (cache hit): swap the
        jitted step back in WITHOUT building a fresh closure — jax's jit
        cache is keyed on the function object, so this reuses the warm
        executable and traces nothing."""
        step, make_triggers, T, geometry, d = entry
        if self._query_slots is None:
            raise ValueError("not a serving pipeline (query_slots=None)")
        if int(geometry.slice_grid) != self.grid:
            raise ValueError("cached bucket was built for a different "
                             "slice grid")
        self._step = step
        self._make_triggers = make_triggers
        self.T = T
        self._query_slots = geometry
        self.rows_per_chunk = d
        self._n_chunks = self.S // d

    def _gc(self, bound) -> None:
        self.state = self._gc_kernel(self.state, bound)

    # -- micro-batched streamed dispatch (EngineConfig.micro_batch) --------
    def _dispatch_streamed(self, i: int):
        if not self._micro_batch:
            return super()._dispatch_streamed(i)
        self.micro_start(i)
        while self._micro_m < self._micro_batch:
            self.micro_push()
        return self.micro_finish()

    def micro_start(self, i: int) -> None:
        """Open interval ``i``'s micro-batched dispatch: a fresh slab
        carry, the interval key, micro cursor at 0. The stepwise faces
        (:meth:`micro_push` / :meth:`micro_finish`) exist so the carry
        is checkpointable BETWEEN micro-batches — the resume arm of the
        differential suite snapshots mid-interval."""
        import jax

        self._micro_slab = self._micro_slab_init()
        self._micro_i = int(i)
        self._micro_key = self._interval_key(int(i))
        self._micro_iv = jax.device_put(np.int64(int(i)))
        self._micro_m = 0

    def micro_push(self) -> None:
        """Dispatch the next micro-batch (async). With
        :attr:`micro_pace` a tiny anchor fetch bounds the in-flight
        micro queue to one — the arrival-paced streaming discipline."""
        import jax

        m = jax.device_put(np.int32(self._micro_m))
        self.state, self.dm, self._micro_slab = self._micro_step_fn(
            self.state, self.dm, self._micro_slab, self._micro_key,
            self._micro_iv, m)
        self._micro_m += 1
        if self.micro_pace:
            jax.device_get(self.state.n_slices)

    def micro_finish(self):
        """Reduce the slab, append, trigger and query — the flush
        program; returns the interval's result handle (the same tuple
        shape as the whole-interval step, bit-matching it)."""
        self.state, self.dm, res = self._micro_flush_fn(
            self.state, self.dm, self._micro_slab, self._micro_key,
            self._micro_iv)
        self._micro_slab = None
        if self.obs is not None:
            self.obs.counter(_obs.MICROBATCH_FLUSHES).inc()
            fl = getattr(self.obs, "flight", None)
            if fl is not None:
                fl.record(_flight.MICROBATCH_FLUSH, "flush",
                          self._micro_batch)
        return res

    def micro_snapshot(self) -> dict:
        """Host checkpoint of the micro-batched carry, valid between
        micro-batches: device state + metrics + slab + cursors. One
        deliberate drain (this IS a checkpoint boundary)."""
        import jax

        return {
            "state": jax.device_get(self.state),
            "dm": jax.device_get(self.dm),
            "slab": jax.device_get(self._micro_slab),
            "interval": self._micro_i,
            "m": self._micro_m,
            "next_interval": self._interval,
        }

    def micro_restore(self, snap: dict) -> None:
        """Resume a :meth:`micro_snapshot` mid-interval; the continued
        run is bit-identical to the uninterrupted twin (asserted by the
        checkpoint-resume arm)."""
        import jax

        if self._needs_reset():
            self.reset()
        self.state = jax.device_put(snap["state"])
        self.dm = jax.device_put(snap["dm"])
        self._micro_slab = jax.device_put(tuple(snap["slab"]))
        self._micro_i = int(snap["interval"])
        self._micro_m = int(snap["m"])
        self._interval = int(snap["next_interval"])
        self._micro_key = self._interval_key(self._micro_i)
        self._micro_iv = jax.device_put(np.int64(self._micro_i))

    def check_overflow(self) -> None:
        import jax

        if bool(jax.device_get(self.state.overflow)):
            e = RuntimeError("slice buffer overflow: raise capacity or "
                             "gc more often")
            if self.obs is not None:
                self.obs.counter(_obs.OVERFLOWS).inc()
                self.obs.record_failure(e, kind=_flight.OVERFLOW,
                                        config=self.config)
            raise e

    def materialize_interval_late(self, i: int):
        """Regenerate interval i's LATE tuple stream on host (testing):
        returns (vals[n_late] f32, ts[n_late] i64) — the tuples the fused
        step folds in at the START of interval i, before that interval's
        base stream. Empty for interval 0 (no earlier span). Bit-identical
        to the device late_fold generator."""
        import jax
        import jax.numpy as jnp

        if self.n_late == 0 or i == 0:
            return (np.empty(0, np.float32), np.empty(0, np.int64))
        if self._root is None:
            self._root = jax.random.PRNGKey(self.seed)
        base = i * self.wm_period_ms
        lo_l = max(base - self.max_lateness, 0)
        key = self._interval_key(i)
        if self._late_span:
            # segment-fold replay: validity and RNG are pure functions of
            # the absolute grid ts, so no GC-history row count is needed
            R_late, g = self._late_R, self.grid
            first = -(-lo_l // g) * g          # first grid point >= lo_l
            row_ts = np.arange(first, base, g, dtype=np.int64)
            if row_ts.size == 0:
                return (np.empty(0, np.float32), np.empty(0, np.int64))
            keys = jax.vmap(lambda t: jax.random.fold_in(
                key, 0x70000000 + t // g))(jnp.asarray(row_ts))
            u = jax.device_get(jax.vmap(lambda k: jax.random.uniform(
                k, (2, R_late), dtype=jnp.float32))(keys))
            vals = u[:, 0] * np.float32(self.value_scale)
            offs = np.clip(np.floor(np.asarray(u[:, 1], np.float32)
                                    * np.float32(g)), 0, g - 1)
            lts = row_ts[:, None] + offs.astype(np.int64)
            return vals.reshape(-1), lts.reshape(-1)
        key = jax.random.fold_in(key, 0x7fffffff)
        u = jax.device_get(jax.random.uniform(
            key, (2, self.n_late), dtype=jnp.float32))
        lts = (np.float64(lo_l)
               + u[0].astype(np.float64) * (base - lo_l)).astype(np.int64)
        lts = np.minimum(lts, base - 1)
        return u[1] * np.float32(self.value_scale), lts

    def materialize_interval(self, i: int):
        """Regenerate interval i's tuple stream on host (testing): returns
        (vals[S*R] f32, ts[S*R] i64), row-major by slice. Uses the exact
        device RNG stream of the fused step."""
        import jax
        import jax.numpy as jnp

        if self._root is None:
            self._root = jax.random.PRNGKey(self.seed)
        key = self._interval_key(i)
        g, P, S = self.grid, self.wm_period_ms, self.S
        if self.legacy_generator:
            # legacy anchor replay: 32-bit value draws + the offset stream
            # (see gen_rows_legacy) — per-tuple ts = row start + offset
            keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(
                jnp.arange(S, dtype=jnp.int64))
            vals = np.asarray(jax.device_get(jax.vmap(
                lambda k: jax.random.uniform(
                    k, (self.R,), dtype=jnp.float32)
                * self.value_scale)(keys)))
            offs = np.asarray(jax.device_get(jax.vmap(
                lambda k: jnp.clip(jnp.floor(jax.random.uniform(
                    jax.random.fold_in(k, 1), (self.R,),
                    dtype=jnp.float32) * g), 0, g - 1)
                .astype(jnp.int64))(keys)))
            row_starts = i * P + g * np.arange(S, dtype=np.int64)
            ts = row_starts[:, None] + offs
            return vals.reshape(-1), ts.reshape(-1)
        if self._n_sub > 1:
            # sub-row chunking: per-(row, sub) keying (see step_impl) —
            # one vmapped generation over all (row, sub) pairs, not a
            # dispatch per chunk
            q = self.R // self._n_sub
            rr = jnp.repeat(jnp.arange(S, dtype=jnp.int64), self._n_sub)
            ss = jnp.tile(jnp.arange(self._n_sub, dtype=jnp.int64), S)
            vals = np.asarray(jax.device_get(jax.vmap(
                lambda r, s: self._gen_lanes(
                    jax.random.fold_in(jax.random.fold_in(key, r),
                                       0x5f000000 + s), q))(rr, ss))
            ).reshape(S, self.R)
        else:
            # per-row keying makes the stream chunk-shape-independent, so
            # one whole-interval generation replays ANY chunking bit-exact
            vals = np.asarray(jax.device_get(self._gen_rows(
                key, jnp.arange(S, dtype=jnp.int64))))
        row_starts = i * P + g * np.arange(S, dtype=np.int64)
        # tuples sit at their row start (see gen_rows: the offset stream
        # is unobservable on the aligned grid and not generated)
        ts = np.broadcast_to(row_starts[:, None], (S, self.R))
        return vals.reshape(-1), ts.reshape(-1).copy()

    def lowered_results(self, interval_out) -> list:
        """Fetch + lower one interval's window results on host."""
        return lower_interval(self.aggregations, interval_out)

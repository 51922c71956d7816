"""TPU window operator: the device-engine implementation of WindowOperator.

Host driver around the device kernels in :mod:`.core`: buffers tuples into
fixed-size batches, launches the ingest kernel, and on each watermark
enumerates triggered windows in closed form (host-side numpy — the exact
trigger order of WindowManager.processWatermark, WindowManager.java:41-80),
answers them all with one device query, and GCs the slice buffer.

Covers context-free tumbling / sliding / fixed-band windows in Time and
Count measure (any mix, in-order or out-of-order within ``max_lateness``)
and Time-measure session windows, with device-realizable aggregations.
Count workloads retain records in a device rank buffer (the closed form of
the reference's OOO ripple); count+time mixes additionally run the
arrival-order cut calculus host-side (``_mixed_cut_calculus``). Remaining
host-only classes — count-measure sessions, arbitrary-object elements,
host-only aggregates — run on the reference-semantics operator
(`scotty_tpu.simulator.SlicingWindowOperator`); `scotty_tpu.HybridWindowOperator`
picks automatically — the same role the eager/lazy decision tree plays in the
reference (SliceFactory.java:17-22).
"""

from __future__ import annotations

import time
from typing import Any, List, Optional, Sequence

import numpy as np

from .. import obs as _obs
from ..obs import flight as _flight
from ..obs import latency as _lat
from ..core.aggregates import AggregateFunction
from ..core.operator import AggregateWindow, WindowOperator
from ..core.windows import (
    LONG_MAX,
    ContextFreeWindow,
    FixedBandWindow,
    ForwardContextAware,
    ForwardContextFree,
    SessionWindow,
    SlidingWindow,
    TumblingWindow,
    Window,
    WindowMeasure,
)
from ..state import StateFactory
from .config import EngineConfig


class UnsupportedOnDevice(NotImplementedError):
    """Raised when a window/aggregation mix has no device realization."""


_KERNEL_CACHE: dict = {}


def _session_kernels(aggs, gap: int, capacity: int, late_len: int,
                     emit_cap: int):
    """Jitted session kernels (in-order ingest + late scan + sweep) for one
    registered session window, cached like _kernels."""
    import jax
    from . import sessions as es

    key = ("session", gap, tuple(a.token for a in aggs), capacity, late_len,
           emit_cap)
    hit = _KERNEL_CACHE.get(key)
    if hit is None:
        hit = (
            jax.jit(es.build_session_ingest(aggs, gap, capacity),
                    donate_argnums=0),
            jax.jit(es.build_session_late(aggs, gap, capacity, late_len),
                    donate_argnums=0),
            jax.jit(es.build_session_sweep(aggs, gap, capacity, emit_cap),
                    donate_argnums=0),
        )
        _KERNEL_CACHE[key] = hit
    return hit


def _session_dense_kernel(aggs, gap: int, capacity: int, runs: int):
    """Jitted run-bounded in-order session ingest, cached."""
    import jax
    from . import sessions as es

    key = ("session-dense", gap, tuple(a.token for a in aggs), capacity,
           runs)
    hit = _KERNEL_CACHE.get(key)
    if hit is None:
        hit = jax.jit(es.build_session_ingest_dense(aggs, gap, capacity,
                                                    runs),
                      donate_argnums=0)
        _KERNEL_CACHE[key] = hit
    return hit


def _kernels(spec, capacity: int, annex_capacity: int,
             record_capacity: int = 0):
    """Jitted kernels shared across operator instances with the same static
    spec — compilation is the dominant cost of small runs/tests."""
    import jax
    from . import core as ec

    key = (spec.periods, spec.bands, spec.count_periods, spec.session_gaps,
           spec.offset_periods, tuple(a.token for a in spec.aggs), capacity,
           annex_capacity, record_capacity)
    hit = _KERNEL_CACHE.get(key)
    if hit is None:
        hit = (
            jax.jit(ec.build_ingest(spec, capacity, annex_capacity),
                    donate_argnums=0),
            # plain query/probe: exact from slice partials while the
            # stream is in-order (cheap); record-aware variants take over
            # permanently once a late count tuple is seen
            jax.jit(ec.build_query(spec, capacity, annex_capacity, 0)),
            jax.jit(ec.build_gc(spec, capacity, annex_capacity)),
            jax.jit(ec.build_count_probe(spec, capacity)),
            jax.jit(ec.build_annex_merge(spec, capacity, annex_capacity),
                    donate_argnums=0),
            # in-order batches skip the late/annex scatter sets entirely
            # (int64 scatters dominate ingest cost — ~100 ms per 1M lanes)
            jax.jit(ec.build_ingest(spec, capacity, annex_capacity,
                                    assume_inorder=True),
                    donate_argnums=0),
            # rec-aware query: for count+time mixes ALL windows answer from
            # record rank ranges once a late tuple was seen (mix_rec)
            jax.jit(ec.build_query(spec, capacity, annex_capacity,
                                   record_capacity,
                                   mix_rec=spec.has_time_grid))
            if record_capacity else None,
            jax.jit(ec.build_count_probe(spec, capacity, record_capacity))
            if record_capacity else None,
            # count ingest with host-supplied arrival-order cut starts
            jax.jit(ec.build_ingest(spec, capacity, annex_capacity,
                                    assume_inorder=True,
                                    with_cut_starts=True),
                    donate_argnums=0)
            if record_capacity else None,
            # arrival-order row-scatter ingest (OOO count+time mixes)
            jax.jit(ec.build_ingest_rows(spec, capacity), donate_argnums=0)
            if record_capacity and spec.has_time_grid else None,
        )
        _KERNEL_CACHE[key] = hit
    return hit


def _record_kernels(record_capacity: int, capacity: int):
    """Jitted record-buffer kernels (count-measure workloads), cached."""
    import jax
    from . import core as ec

    key = ("records", record_capacity, capacity)
    hit = _KERNEL_CACHE.get(key)
    if hit is None:
        hit = (
            jax.jit(ec.build_record_merge(record_capacity),
                    donate_argnums=0),
            jax.jit(ec.build_record_gc(capacity, record_capacity),
                    donate_argnums=1),
            jax.jit(ec.build_record_append(record_capacity),
                    donate_argnums=0),
        )
        _KERNEL_CACHE[key] = hit
    return hit


def _context_kernels(aggs, spec, capacity: int, emit_cap: int):
    """Jitted generic context-window kernels (apply scan + sweep), cached
    by the spec's token — see engine/context.py."""
    import jax
    from . import context as ectx

    key = ("context", spec.token(), tuple(a.token for a in aggs), capacity,
           emit_cap)
    hit = _KERNEL_CACHE.get(key)
    if hit is None:
        hit = (
            jax.jit(ectx.build_context_apply(aggs, spec, capacity),
                    donate_argnums=0),
            jax.jit(ectx.build_context_sweep(aggs, spec, capacity,
                                             emit_cap),
                    donate_argnums=0),
        )
        _KERNEL_CACHE[key] = hit
    return hit


def _context_chunk_kernel(aggs, spec, capacity: int, chunk_len: int):
    """Jitted vectorized in-order chain kernel (one per padded chunk
    length), cached by the spec's token — see
    engine/context.py::build_context_chunk."""
    import jax
    from . import context as ectx

    key = ("context-chunk", spec.token(), tuple(a.token for a in aggs),
           capacity, chunk_len)
    hit = _KERNEL_CACHE.get(key)
    if hit is None:
        hit = jax.jit(
            ectx.build_context_chunk(aggs, spec, capacity, chunk_len),
            donate_argnums=0)
        _KERNEL_CACHE[key] = hit
    return hit


def _dm_ingest_kernel():
    """Jitted DeviceMetrics batch updater for device-resident ingest
    (ingest_device_batch / ingest_device_late): device timestamps are
    opaque to the host, so exact late counts/ages can only be computed
    in-jit. Arrival-order running max (cummax) seeded at the stream's
    host-known max event time — the same calculus a host arrival-order
    replay computes. Cached like the other kernels; zero host syncs."""
    import jax
    import jax.numpy as jnp

    from . import core as ec
    from ..obs import device as _dev

    key = ("dm_ingest",)
    hit = _KERNEL_CACHE.get(key)
    if hit is None:
        def upd(dm, ts, valid, met_pre):
            ts = jnp.asarray(ts)
            valid = jnp.asarray(valid)
            eff = jnp.where(valid, ts, jnp.int64(ec.I64_MIN))
            shifted = jnp.concatenate(
                [jnp.reshape(jnp.int64(met_pre), (1,)), eff[:-1]])
            rm = jax.lax.cummax(shifted)
            late_m = valid & (ts < rm)
            dm = _dev.record_late_ages(dm, rm - ts, late_m)
            return dm._replace(
                ingested=dm.ingested + jnp.sum(valid.astype(jnp.int64)),
                late=dm.late + jnp.sum(late_m))

        hit = jax.jit(upd, donate_argnums=0)
        _KERNEL_CACHE[key] = hit
    return hit


def _dense_kernel(spec, capacity: int, runs: int,
                  pallas_fold: bool = False, pallas_packed: bool = False):
    """Jitted scatter-free in-order ingest (build_ingest_dense), cached,
    in its int32 offset form: the caller gives it only batches that pass
    ``core.ts32_fits``. The Pallas flags are part of the cache key — a
    flags-off operator can never be handed a Pallas-bearing
    executable."""
    import jax
    from . import core as ec

    key = ("dense", spec.periods, spec.bands, spec.offset_periods,
           tuple(a.token for a in spec.aggs), capacity, runs,
           bool(pallas_fold), bool(pallas_packed))
    hit = _KERNEL_CACHE.get(key)
    if hit is None:
        hit = jax.jit(ec.build_ingest_dense(
            spec, capacity, runs, pallas_fold=pallas_fold,
            pallas_packed=pallas_packed, ts32=True), donate_argnums=0)
        _KERNEL_CACHE[key] = hit
    return hit


#: Run bounds of the dense ingest kernel above the first rung
#: (``EngineConfig.dense_ingest_runs``): an in-order batch takes the
#: smallest rung its span fits, one cached executable per rung (each one
#: more compile at set-up, so the rungs are few and far apart); a batch
#: over the last rung takes the general in-order kernel.
DENSE_RUN_LADDER = (256, 4096)


def dense_eligible(spec) -> bool:
    """Static part of the dense-ingest decision: no count/session windows,
    dense-lift aggregations only."""
    return (not spec.count_periods and not spec.session_gaps
            and all(not a.is_sparse for a in spec.aggs))


def min_grid_period(spec) -> int:
    """Smallest distance between consecutive union-grid points — the
    host-side bound for how many slices a time span can touch."""
    g = 0
    import math

    for p in spec.periods:
        g = math.gcd(g, int(p))
    for (p, r) in spec.offset_periods:
        g = math.gcd(g, int(p))
        g = math.gcd(g, int(r))
    for (bs, bsz) in spec.bands:
        g = math.gcd(g, int(bs))
        g = math.gcd(g, int(bsz))
    return max(1, g)


class TpuWindowOperator(WindowOperator):
    """Device-engine WindowOperator (SURVEY.md §7 stage 3-5).

    Same public contract as the reference SlicingWindowOperator
    (slicing/.../SlicingWindowOperator.java:21-69) plus the batched
    ``process_elements`` entry point that actually feeds the accelerator.
    """

    def __init__(self, state_factory: Optional[StateFactory] = None,
                 config: Optional[EngineConfig] = None, obs=None,
                 collect_device_metrics: Optional[bool] = None,
                 shaper=None):
        self.config = config or EngineConfig()
        self.obs = obs                      # scotty_tpu.obs.Observability
        #: stream-shaping front-end (scotty_tpu.shaper, ISSUE 5). Pass a
        #: ShaperConfig (or a prebuilt StreamShaper) to route host-fed
        #: tuples through the coalescing/sorting accumulator; watermarks
        #: drain it first and check_overflow folds its telemetry. None
        #: (default) leaves every pre-shaper path byte-identical.
        self._shaper = None
        self._shaper_feeding = False
        #: line-rate ingest feed (scotty_tpu.ingest.LineRateFeed, ISSUE
        #: 7): attaches itself at construction. Watermark dispatch drains
        #: its staged records first (same contract as the shaper) and
        #: check_overflow folds its ingest_ring_* telemetry.
        self._ingest_feed = None
        #: the in-flight emission-latency chain key (ISSUE 14): one per
        #: watermark, opened at dispatch, completed at the arrays/emit
        #: face and closed by the sink handoff (obs.latency)
        self._lat_open = None
        if shaper is not None:
            from ..shaper import ShaperConfig, StreamShaper

            if isinstance(shaper, ShaperConfig):
                StreamShaper(self, shaper)      # attaches via __init__
            elif isinstance(shaper, StreamShaper):
                shaper.op = self
                self._shaper = shaper
            else:
                raise TypeError(
                    "shaper= expects a scotty_tpu.shaper.ShaperConfig or "
                    f"StreamShaper, got {type(shaper).__name__}")
        #: device_* telemetry mode. None (default) = AUTO: collect only
        #: while an Observability is attached, so a bare operator stays
        #: zero-overhead (no dm_ingest kernel dispatch per device batch,
        #: no numpy running-max mirror per host batch). True forces
        #: collection without obs (device_metrics() consumers); False
        #: disables entirely (the overhead A/B baseline — run_benchmark
        #: propagates its collect_metrics flag here).
        self.collect_device_metrics = collect_device_metrics
        #: SHED policy hook: called as ``shed_callback(vals, ts)`` with the
        #: numpy arrays of every tuple the admission control dropped — the
        #: auditable dead-letter face (the chaos differential suite replays
        #: the surviving complement through the host oracle).
        self.shed_callback = None
        self.windows: List[ContextFreeWindow] = []
        #: per-window active mask (ISSUE 6 serving control path): the
        #: watermark trigger loop skips inactive windows, so
        #: register_window/cancel_window never touch the compiled kernels
        #: — registration order (and with it emission order) is preserved
        self._win_active: List[bool] = []
        self.aggregations: List[AggregateFunction] = []
        self.max_lateness = 1000            # WindowManager.java:24 default
        self.max_fixed_window_size = 0
        self._last_watermark = -1
        self._built = False
        self._state = None
        self._pend_vals: list = []
        self._pend_ts: list = []
        self._n_pending = 0
        # in-jit device telemetry (obs/device.py): the device pytree is
        # allocated lazily on the first device-resident batch; host-fed
        # batches accumulate the same device_* names in numpy (their ts
        # are host-visible — no extra dispatch on the hot path)
        self._dm = None
        self._dm_host_acc: dict = {}
        self._dm_folded = None

    # -- registry ----------------------------------------------------------
    def add_window_assigner(self, window: Window) -> None:
        if self._built:
            self._add_window_dynamic(window)
            return
        if isinstance(window, SessionWindow):
            # sessions run on their own bounded active-session arrays
            # (engine/sessions.py), one per registered window — any mix
            # with time-grid windows, in- or out-of-order streams.
            if window.measure != WindowMeasure.Time:
                raise UnsupportedOnDevice("count-measure sessions: host only")
            self.windows.append(window)
            self._win_active.append(True)
            return
        if isinstance(window, (ForwardContextAware, ForwardContextFree)):
            # user-defined context-aware windows run on the generic
            # active-window-array engine (engine/context.py) when they
            # provide a device face; host-only contexts fall back.
            # The device calculus runs over event TIMESTAMPS, while the
            # host face (and the reference, TupleContext.getTs(measure))
            # runs count-measure contexts over arrival positions — so a
            # non-Time measure must not silently reach the device.
            if window.window_measure != WindowMeasure.Time:
                raise UnsupportedOnDevice(
                    "count-measure context windows: host only (the device "
                    "context calculus runs over event time)")
            if window.device_context_spec() is None:
                raise UnsupportedOnDevice(
                    f"{type(window).__name__} has no device context spec "
                    "(device_context_spec() is None); use "
                    "SlicingWindowOperator or HybridWindowOperator")
            self.windows.append(window)
            self._win_active.append(True)
            return
        if not isinstance(window, (TumblingWindow, SlidingWindow,
                                   FixedBandWindow)):
            raise UnsupportedOnDevice(
                f"{type(window).__name__} has no device path; use "
                "SlicingWindowOperator or HybridWindowOperator")
        if (window.measure == WindowMeasure.Count
                and isinstance(window, FixedBandWindow)):
            raise UnsupportedOnDevice(
                "count-measure fixed-band windows have no device path; use "
                "SlicingWindowOperator")
        self.windows.append(window)
        self._win_active.append(True)
        # the reference mixes count sizes into the (ms) GC delay bound —
        # WindowManager.java:121-127 takes clearDelay() of every
        # context-free window regardless of measure; mirrored for parity.
        self.max_fixed_window_size = max(self.max_fixed_window_size,
                                         window.clear_delay())

    def _add_window_dynamic(self, window: Window) -> None:
        """Register a window mid-stream (TumblingWindowOperatorTest.java:96-145,
        SlidingWindowOperatorTest dynamic cases).

        The slice-buffer arrays are spec-independent, so the existing state
        carries over untouched; only the kernels (which close over the union
        grid) are rebuilt. Pre-addition slices stay on the coarser old grid —
        the query's t_last containment (AggregateWindowState.java:25-31)
        handles windows of the new assigner that straddle them, exactly like
        the reference. Pending host-buffered tuples are flushed through the
        OLD kernels first: the new grid applies from this call on.

        Deliberate deviation: the union grid takes effect IMMEDIATELY at
        this call. The reference caches its next slice edge
        (StreamSlicer.java min_next_edge_ts) and keeps filling the current
        coarse slice until that stale pre-addition edge is crossed — tuples
        arriving in [addition_ts, stale_edge) silently vanish from every
        window of the new assigner that ends before the stale edge. Here
        they are sliced on the new grid at once, so new-assigner windows
        see them; results are identical from the first old-grid edge after
        the addition onward.
        """
        if self._session_windows or getattr(self, "_ctx_windows", None) \
                or isinstance(window, (SessionWindow, ForwardContextAware,
                                       ForwardContextFree)):
            raise UnsupportedOnDevice(
                "dynamic addition with session/context windows needs the "
                "host operator")
        if not isinstance(window, (TumblingWindow, SlidingWindow,
                                   FixedBandWindow)):
            raise UnsupportedOnDevice(
                f"{type(window).__name__} has no device path")
        if window.measure == WindowMeasure.Count:
            raise UnsupportedOnDevice(
                "dynamic count-measure window addition needs the host "
                "operator (count slicing would need a record replay)")
        self._flush()                      # old grid for already-fed tuples
        self.windows.append(window)
        self._win_active.append(True)
        self.max_fixed_window_size = max(self.max_fixed_window_size,
                                         window.clear_delay())
        self._spec = self._grid_spec = self._compute_spec()
        C, A = self.config.capacity, self.config.annex_capacity
        RCap = self.config.records if self._has_count else 0
        (self._ingest, self._query, self._gc, self._count_at,
         self._merge, self._ingest_inorder, self._query_rec,
         self._count_at_rec, self._ingest_cut,
         self._ingest_rows) = _kernels(self._grid_spec, C, A, RCap)
        # the dense fast path closes over the union grid too
        self._dense_rungs = self._dense_ladder()
        self._min_grid = min_grid_period(self._grid_spec)
        self._ingest_dense = None

    def _serving_compatible(self, window: Window) -> bool:
        """Whether ``window`` can register against the BUILT kernels with
        no rebuild: a Time-measure tumbling/sliding window whose edges all
        land on slice cuts the existing union grid already makes —
        tumbling: size a multiple of some registered period; sliding:
        slide a multiple, and size a multiple of slide (or the residue
        grid already in the spec). Anything else goes through the
        `_add_window_dynamic` rebuild path."""
        if self._session_windows or getattr(self, "_ctx_windows", None):
            return False
        if not isinstance(window, (TumblingWindow, SlidingWindow)) \
                or window.measure != WindowMeasure.Time:
            return False
        periods = self._grid_spec.periods
        if not periods:
            return False
        if isinstance(window, SlidingWindow):
            sl, sz = int(window.slide), int(window.size)
            if not any(sl % p == 0 for p in periods):
                return False
            if sz % sl == 0:
                return True
            return (sl, sz % sl) in self._grid_spec.offset_periods
        return any(int(window.size) % p == 0 for p in periods)

    def register_window(self, window: Window, tenant: str = "default") -> int:
        """Serving control path (ISSUE 6): register a window mid-stream and
        return an opaque handle for :meth:`cancel_window` (handles are
        never reused — stale cancels raise instead of touching a
        recycled slot).

        When the window is :meth:`_serving_compatible` with the built
        union grid, registration is PURE HOST BOOKKEEPING — the compiled
        kernels are untouched and the next watermark simply enumerates
        the new window's triggers (zero retrace; the query kernel's
        trigger-pad bucket keeps it warm), reusing a cancelled
        registration's window slot when one is free. Incompatible windows
        fall back to the `_add_window_dynamic` kernel rebuild, counted as
        a ``serving_retraces``. Like the dynamic-addition path, data GC'd
        before registration is gone: the new window answers from the
        slices still retained.
        """
        if not hasattr(self, "_serving_handles"):
            self._serving_handles: dict = {}
            self._serving_next = 0
            self._win_free: list = []
        retrace = False
        if not self._built:
            self.add_window_assigner(window)
            idx = len(self.windows) - 1
        elif self._serving_compatible(window):
            self._flush()             # pending tuples precede registration
            if self._win_free:
                # recycle a cancelled registration's window slot so
                # sustained churn bounds the list (and the per-watermark
                # trigger scan) at PEAK concurrency, not total history
                idx = self._win_free.pop()
                self.windows[idx] = window
                self._win_active[idx] = True
            else:
                self.windows.append(window)
                self._win_active.append(True)
                idx = len(self.windows) - 1
            self.max_fixed_window_size = max(self.max_fixed_window_size,
                                             window.clear_delay())
        else:
            self._add_window_dynamic(window)      # kernel rebuild
            idx = len(self.windows) - 1
            retrace = True
        h = self._serving_next
        self._serving_next += 1
        self._serving_handles[h] = (idx, tenant)
        if self.obs is not None:
            self.obs.counter(_obs.SERVING_REGISTERED).inc()
            if retrace:
                self.obs.counter(_obs.SERVING_RETRACES).inc()
            self.obs.flight_event(_flight.QUERY_REGISTER,
                                  f"{tenant}:{window}", float(h))
        return h

    def cancel_window(self, handle: int, tenant: str = "default") -> None:
        """Deactivate a registered window: its triggers stop being
        enumerated from the next watermark on (a host mask write — the
        kernels, the slice state and every other window are untouched)
        and its window slot joins the recycle list. Handles are opaque
        and never reused (a stale handle raises; only
        :meth:`register_window` registrations cancel — build-time windows
        are the static contract). Session/context windows have no cancel
        path (their sweeps carry per-window device state)."""
        entry = getattr(self, "_serving_handles", {}).pop(handle, None)
        if entry is None:
            raise ValueError(
                f"unknown or already-cancelled window handle {handle}")
        idx, reg_tenant = entry
        w = self.windows[idx]
        if isinstance(w, (SessionWindow, ForwardContextAware,
                          ForwardContextFree)):
            self._serving_handles[handle] = entry     # nothing changed
            raise UnsupportedOnDevice(
                "session/context windows cannot be cancelled (their sweep "
                "state is per-registration); only grid windows support "
                "the serving control path")
        self._win_active[idx] = False
        self._win_free.append(idx)
        if self.obs is not None:
            self.obs.counter(_obs.SERVING_CANCELLED).inc()
            self.obs.flight_event(_flight.QUERY_CANCEL,
                                  f"{reg_tenant}:{w}", float(handle))

    def add_aggregation(self, window_function: AggregateFunction) -> None:
        if self._built:
            raise RuntimeError("add aggregations before first element")
        if window_function.device_spec() is None:
            raise UnsupportedOnDevice(
                f"{type(window_function).__name__} has no device realization "
                "(device_spec() is None); use SlicingWindowOperator")
        self.aggregations.append(window_function)

    def set_max_lateness(self, max_lateness: int) -> None:
        self.max_lateness = max_lateness

    def set_observability(self, obs) -> None:
        """Attach an :class:`scotty_tpu.obs.Observability` (None detaches).
        All hooks are host-side at batch/watermark boundaries — the jitted
        kernels are untouched: ``ingest_tuples``/``ingest_batch_size`` on
        ingest, ``late_tuples`` when a batch reaches below the stream's
        max event time, ``watermarks``/``watermark_lag_ms``/
        ``watermark_dispatch_ms`` per watermark, ``overflows`` on overflow,
        ``slice_occupancy``/``slice_headroom`` at the
        :meth:`check_overflow` sync point — where the in-jit ``device_*``
        telemetry (obs/device.py) also folds in. Attaching mid-run
        baselines the device counters so pre-attach (warmup) batches
        don't pollute the fold."""
        self.obs = obs
        if obs is not None and (self._dm is not None or self._dm_host_acc):
            self._dm_folded = self.device_metrics()

    # -- build -------------------------------------------------------------
    def _compute_spec(self):
        from . import core as ec

        periods = []
        bands = []
        count_periods = []
        session_gaps = []
        offset_periods = []
        for w in self.windows:
            if isinstance(w, SessionWindow):
                session_gaps.append(int(w.gap))
            elif isinstance(w, (ForwardContextAware, ForwardContextFree)):
                pass        # generic context windows own their arrays
            elif w.measure == WindowMeasure.Count:
                count_periods.append(int(w.slide)
                                     if isinstance(w, SlidingWindow)
                                     else int(w.size))
            elif isinstance(w, TumblingWindow):
                periods.append(int(w.size))
            elif isinstance(w, SlidingWindow):
                periods.append(int(w.slide))
                if w.size % w.slide:
                    # window ends off the slide grid: add their residue grid
                    # so range queries stay exact (EngineSpec.offset_periods)
                    offset_periods.append((int(w.slide),
                                           int(w.size % w.slide)))
            elif isinstance(w, FixedBandWindow):
                bands.append((int(w.start), int(w.size)))
        return ec.EngineSpec(
            periods=ec.collapse_periods(periods),
            bands=tuple(sorted(set(bands))),
            count_periods=tuple(sorted(set(count_periods))),
            aggs=tuple(a.device_spec() for a in self.aggregations),
            session_gaps=tuple(session_gaps),
            offset_periods=tuple(sorted(set(offset_periods))),
        )

    def _build(self) -> None:
        from . import core as ec
        from . import sessions as es

        if not self.windows:
            raise RuntimeError("no windows registered")
        if not self.aggregations:
            raise RuntimeError("no aggregations registered")
        self._spec = self._compute_spec()
        if any(a.cells_per_tuple > 1 for a in self._spec.aggs) and (
                self._spec.session_gaps or self._spec.count_periods
                or any(isinstance(w, (ForwardContextAware,
                                      ForwardContextFree))
                       for w in self.windows)):
            # sessions/context chains/the count record ring densify per-lane
            # one-hots ([B, width]), which assumes one cell per tuple; the
            # scatter-combine time-grid paths broadcast over the extra cells
            raise UnsupportedOnDevice(
                "multi-cell sparse aggregations (count-min) ride the "
                "time-grid paths only; use SlicingWindowOperator for "
                "session/count/context workloads")
        C, A = self.config.capacity, self.config.annex_capacity
        # Session windows run on their own per-registration active-session
        # arrays (engine/sessions.py); the grid slice buffer serves only
        # context-free windows. Stripping the gaps from the grid spec keeps
        # kernel-cache keys and the dense fast path independent of sessions.
        self._session_windows = [w for w in self.windows
                                 if isinstance(w, SessionWindow)]
        self._ctx_windows = [
            w for w in self.windows
            if isinstance(w, (ForwardContextAware, ForwardContextFree))
            and not isinstance(w, SessionWindow)]
        import dataclasses

        self._grid_spec = dataclasses.replace(self._spec, session_gaps=())
        self._has_grid = (self._grid_spec.has_time_grid
                          or bool(self._grid_spec.count_periods))
        self._pure_session = bool(self._session_windows
                                  or self._ctx_windows) \
            and not self._has_grid
        self._has_count = bool(self._grid_spec.count_periods)
        self._rec = None
        if self._has_grid:
            RCap = self.config.records if self._has_count else 0
            self._state = ec.init_state(self._grid_spec, C, A)
            (self._ingest, self._query, self._gc, self._count_at,
             self._merge, self._ingest_inorder, self._query_rec,
             self._count_at_rec, self._ingest_cut,
             self._ingest_rows) = _kernels(self._grid_spec, C, A, RCap)
            if self._has_count:
                # count windows aggregate ts-sorted rank ranges — retain
                # records (the reference's lazy-slice retention)
                self._rec = ec.init_records(RCap)
                (self._rec_merge, self._rec_gc,
                 self._rec_append) = _record_kernels(RCap, C)
        else:
            self._state = None
        if self._session_windows:
            self._emit_cap = self.config.trigger_pad(1024)
            # the late scan is SEQUENTIAL (one device step per late tuple) —
            # cap its static length well below bench batch sizes; rarer
            # larger late sets chunk through it (_feed_sessions)
            self._late_len = min(self.config.batch_size, 256)
            trips = [_session_kernels(self._spec.aggs, int(w.gap), C,
                                      self._late_len, self._emit_cap)
                     for w in self._session_windows]
            self._session_ingests = tuple(t[0] for t in trips)
            self._session_lates = tuple(t[1] for t in trips)
            self._session_sweeps = tuple(t[2] for t in trips)
            # orphan capacity rides annex_capacity: both hold the rare
            # out-of-contract-ish residue between watermarks
            self._session_states = [
                es.init_session_state(
                    self._spec.aggs, C,
                    orphan_capacity=max(64, A))
                for _ in self._session_windows]
            self._session_dense = [None] * len(self._session_windows)
        else:
            self._session_states = []
        if self._ctx_windows:
            from . import context as ectx

            if not self._session_windows:
                self._emit_cap = self.config.trigger_pad(1024)
            specs = [w.device_context_spec() for w in self._ctx_windows]
            pairs = [_context_kernels(self._spec.aggs, sp, C, self._emit_cap)
                     for sp in specs]
            self._ctx_applies = tuple(p[0] for p in pairs)
            self._ctx_sweeps = tuple(p[1] for p in pairs)
            self._ctx_specs = tuple(specs)
            self._ctx_chain = tuple(
                sp.inorder_chain_params() is not None for sp in specs)
            # speculative chunked batching (ISSUE 11): specs certifying
            # SpeculationCert get a host planner that sorts OOO chunks,
            # proves per interaction component that the vectorized chain
            # kernel reproduces the arrival-order scan, and falls back
            # to the scan only for the components it cannot prove
            self._ctx_planners = tuple(
                ectx.SpeculativePlanner(sp)
                if (sp.inorder_chain_params() is not None
                    and sp.speculation_params() is not None) else None
                for sp in specs)
            self._ctx_spec_stats = {"speculative_tuples": 0,
                                    "fallback_tuples": 0,
                                    "fallback_runs": 0}
            # clear_delay participates in the GC bound (mirroring
            # Window.clear_delay / WindowManager.java:121-127): retention
            # beyond what orphan_reach already grants is applied as a
            # per-window slack on the sweep's gc_bound, so a user decider
            # declaring a long clear_delay actually keeps its orphans.
            self._ctx_gc_slack = tuple(
                max(0, int(sp.clear_delay()) - int(sp.orphan_reach()))
                for sp in specs)
            self._ctx_states = [
                es.init_session_state(self._spec.aggs, C,
                                      orphan_capacity=max(64, A))
                for _ in specs]
        else:
            self._ctx_states = []
            self._ctx_planners = ()
            self._ctx_spec_stats = {}
        # per-watermark emission order among context windows follows their
        # REGISTRATION order (the simulator iterates contexts in that
        # order, WindowManager.java:98-118)
        self._ctx_order = []
        si = gi = 0
        for w in self.windows:
            if isinstance(w, SessionWindow):
                self._ctx_order.append(("s", si))
                si += 1
            elif isinstance(w, (ForwardContextAware, ForwardContextFree)):
                self._ctx_order.append(("g", gi))
                gi += 1
        self._dense_rungs = self._dense_ladder()
        self._min_grid = min_grid_period(self._grid_spec)
        # {runs: kernel}, built (every rung) on the first eligible batch
        self._ingest_dense = None
        self._last_count = 0
        self._host_met = None           # host mirror of max event time
        self._host_min_ts = None        # host mirror of min event time
        self._host_first_ts = None      # ts of the FIRST ARRIVAL ever
        self._host_count = 0            # host mirror of current_count
        self._annex_dirty = False       # a late tuple may sit in the annex
        self._count_late_seen = False   # sticky: rec query/probe from then on
        self._valid_dev = None          # cached all-true lane mask
        self._host_open = None          # mirror of the open slice's start
        self._device_fed = False        # device batches bypass the mirror
        # overflow-policy admission mirrors (resilience.policy): host-side
        # UPPER BOUNDS on live slices / pending annex rows, grown per
        # admitted batch and re-synced exactly (one device round trip)
        # only when a batch's projected need approaches capacity. Under
        # the default FAIL policy none of this runs.
        if self.config.overflow_policy != "fail" and (
                not self._has_grid or self._has_count or self._ctx_windows):
            raise UnsupportedOnDevice(
                f"overflow_policy={self.config.overflow_policy!r} covers "
                "time-grid (optionally session-mixed) workloads; count/"
                "context/pure-session workloads run policy 'fail' — the "
                "host admission mirror has no exact occupancy bound for "
                "their buffers")
        self._pol_slices_ub = 0
        self._pol_annex_ub = 0
        self._pol_seen_start = None
        self._built = True

    # -- device telemetry --------------------------------------------------
    @property
    def _dm_active(self) -> bool:
        """Whether the device_* telemetry collects right now (see the
        collect_device_metrics mode doc in __init__)."""
        if self.collect_device_metrics is None:
            return self.obs is not None
        return bool(self.collect_device_metrics)

    def _dm_host_add(self, name: str, delta: int) -> None:
        if delta:
            self._dm_host_acc[name] = self._dm_host_acc.get(name, 0) + delta

    def device_metrics(self) -> dict:
        """Merged in-jit + host-mirrored telemetry as a ``device_*`` name
        → int dict (syncs the device pytree if one exists)."""
        from ..obs import device as _dev

        snap = dict(self._dm_host_acc)
        if self._dm is not None:
            import jax

            for name, v in _dev.host_snapshot(
                    jax.device_get(self._dm)).items():
                snap[name] = snap.get(name, 0) + v
        return snap

    def _dm_device_update(self, ts, valid) -> None:
        """Fold one device-resident batch into the in-jit pytree (its ts
        are host-opaque; the jitted cummax kernel is the only exact
        source of late counts/ages). Zero host syncs; no-op when device
        telemetry is disabled."""
        from . import core as ec
        from ..obs import device as _dev

        if not self._dm_active:
            return
        if self._dm is None:
            self._dm = _dev.init_device_metrics()
        met = np.int64(self._host_met) if self._host_met is not None \
            else np.int64(ec.I64_MIN)
        self._dm = _dm_ingest_kernel()(self._dm, ts, valid, met)

    # -- ingest ------------------------------------------------------------
    def process_element(self, element: Any, ts: int) -> None:
        self.process_elements(np.asarray([element], dtype=np.float32),
                              np.asarray([ts], dtype=np.int64))

    @property
    def shaper(self):
        """The attached :class:`scotty_tpu.shaper.StreamShaper` (None
        when the operator runs bare)."""
        return self._shaper

    def process_elements(self, elements: Sequence, timestamps: Sequence) -> None:
        if not self._built:
            self._build()
        lat = self.obs.latency if self.obs is not None else None
        if lat is not None:
            # emission-latency lineage (ISSUE 14): record-arrival at
            # the operator boundary — unless this call IS the shaper's
            # flush re-entering (then the arrival already stamped when
            # the records first offered, and THIS moment is the
            # shaper_flush stage)
            lat.pre(_lat.STAGE_SHAPER_FLUSH if self._shaper_feeding
                    else _lat.STAGE_ARRIVAL)
        if self._shaper is not None and not self._shaper_feeding:
            # shaped ingest: the accumulator coalesces/sorts and calls
            # back into this method (reentrancy flag set) per full block
            self._shaper.offer_many(
                np.asarray(elements, dtype=np.float32).reshape(-1),
                np.asarray(timestamps, dtype=np.int64).reshape(-1))
            return
        vals = np.asarray(elements, dtype=np.float32).reshape(-1)
        tss = np.asarray(timestamps, dtype=np.int64).reshape(-1)
        if vals.shape != tss.shape:
            raise ValueError("elements/timestamps length mismatch")
        if self.obs is not None:
            self.obs.counter(_obs.INGEST_TUPLES).inc(vals.shape[0])
            self.obs.histogram(_obs.INGEST_BATCH_SIZE).observe(vals.shape[0])
        self._pend_vals.append(vals)
        self._pend_ts.append(tss)
        self._n_pending += vals.shape[0]
        B = self.config.batch_size
        while self._n_pending >= B:
            self._launch_batch(B)

    def _launch_batch(self, take: int) -> None:
        """Pop `take` tuples from the pending queue, pad to batch_size,
        ts-sort (late tuples must be grouped for the annex path), launch."""
        if self.obs is not None and self.obs.latency is not None:
            # device-work-begins pre-stamp for the next watermark's
            # emission chain (first launch since the last claim wins)
            self.obs.latency.pre(_lat.STAGE_DISPATCH)
        B = self.config.batch_size
        if len(self._pend_vals) == 1:
            vals_cat, ts_cat = self._pend_vals[0], self._pend_ts[0]
        else:
            vals_cat = np.concatenate(self._pend_vals)
            ts_cat = np.concatenate(self._pend_ts)
        batch_v, rest_v = vals_cat[:take], vals_cat[take:]
        batch_t, rest_t = ts_cat[:take], ts_cat[take:]
        self._pend_vals = [rest_v] if rest_v.size else []
        self._pend_ts = [rest_t] if rest_t.size else []
        self._n_pending -= take

        met_pre = self._host_met            # max event time BEFORE this batch
        if take and self.config.overflow_policy != "fail":
            # SHED/GROW admission control (resilience.policy) — before any
            # telemetry, so counters reflect what was actually ingested
            batch_v, batch_t, take = self._policy_admit(batch_v, batch_t,
                                                        take, met_pre)
            if take == 0:
                return
        if self.obs is not None and take and met_pre is not None:
            # late = below the stream's max event time at batch start
            # (host-side count; the device late/annex path handles them)
            n_below = int((batch_t[:take] < met_pre).sum())
            if n_below:
                self.obs.counter(_obs.LATE_TUPLES).inc(n_below)
        if take and self._dm_active:
            # device_* telemetry, host mirror (these ts are host-visible
            # pre-sort, so the exact arrival-order running-max calculus
            # costs one numpy accumulate — no extra device dispatch):
            # a tuple is late iff strictly below the running max at ITS
            # arrival; its age is the running max minus its ts
            from ..obs import device as _dev

            arr = batch_t[:take]
            seed = np.int64(met_pre) if met_pre is not None \
                else np.iinfo(np.int64).min
            rm = np.maximum.accumulate(np.concatenate(([seed], arr[:-1])))
            late_m = arr < rm
            n_late_exact = int(late_m.sum())
            self._dm_host_add(_dev.DEVICE_INGEST_TUPLES, take)
            self._dm_host_add(_dev.DEVICE_LATE_TUPLES, n_late_exact)
            if n_late_exact:
                hist = _dev.host_late_age_hist(rm[late_m] - arr[late_m])
                for name, v in zip(_dev.late_bucket_names(),
                                   hist.tolist()):
                    self._dm_host_add(name, int(v))
        if take and self._host_first_ts is None:
            self._host_first_ts = int(batch_t[0])   # arrival order, pre-sort
        intra_ooo = take > 1 and not bool(
            (batch_t[:take - 1] <= batch_t[1:take]).all())
        mixed = self._has_count and self._grid_spec.has_time_grid
        mixed_late = mixed and take and (
            intra_ooo or (met_pre is not None
                          and int(batch_t[:take].min()) < met_pre))
        if mixed_late and self._device_fed:
            # device-resident batches bypassed the host cut mirror, so the
            # arrival-order slice assignment can no longer be reconstructed
            raise UnsupportedOnDevice(
                "out-of-order count+time mixes after device-resident "
                "batches need the host operator (host cut mirror is stale)")
        if self._session_states and take:
            # sessions consume the batch in ARRIVAL order — the reference's
            # session calculus is arrival-order-dependent at exact-gap
            # boundaries (engine/sessions.py module docstring)
            self._feed_sessions(batch_v[:take], batch_t[:take], met_pre)
        if self._ctx_states and take:
            # generic context windows replay the batch in arrival order:
            # sorted in-order batches take the vectorized chunk kernel
            # when the spec certifies the greedy chain
            # (DeviceContextSpec.inorder_chain_params); everything else
            # goes through the per-tuple scan (engine/context.py)
            bt = batch_t[:take]
            inorder = bool((bt[:-1] <= bt[1:]).all()) \
                and (met_pre is None or int(bt[0]) >= met_pre)
            self._feed_contexts(batch_v[:take], bt, inorder=inorder)

        if not self._has_grid:
            # pure-session/context workloads: no slice buffer to feed,
            # so skip the grid path's full ts-sort (it was ~15% of a
            # speculative context batch) and update the host clock
            # mirrors straight from the arrival arrays
            if take:
                mx = int(batch_t[:take].max())
                mn = int(batch_t[:take].min())
                self._host_met = mx if self._host_met is None \
                    else max(self._host_met, mx)
                self._host_min_ts = mn if self._host_min_ts is None \
                    else min(self._host_min_ts, mn)
                self._host_count += take
            return

        if mixed and take:
            # arrival-order cut calculus: maintains the open-slice mirror on
            # EVERY batch; for late-containing batches it also yields the
            # per-lane slice assignment the row-scatter kernel consumes
            row_off, is_cut, cut_val, cut_c = self._mixed_cut_calculus(
                batch_t[:take], met_pre)
        if mixed_late:
            # Out-of-order count+time mix — device path (VERDICT r3 item 1).
            # The ripple (SliceManager.java:64-86) re-aligns slice content
            # to ts-sorted rank ranges; on device that is: merge the batch
            # into the record buffer by ts rank, add +1 to the row open at
            # each tuple's ARRIVAL, materialize the arrival's cuts. All
            # window values then come from record rank ranges (mix_rec
            # query) — sticky from the first late tuple.
            self._count_late_seen = True
            order = np.argsort(batch_t[:take], kind="stable")
            sort_t = np.full((B,), batch_t[:take][order[-1]], np.int64)
            sort_v = np.zeros((B,), np.float32)
            sort_t[:take] = batch_t[:take][order]
            sort_v[:take] = batch_v[:take][order]
            valid = np.zeros((B,), bool)
            valid[:take] = True
            self._rec = self._rec_merge(self._rec, sort_t, sort_v, valid)

            arr_t = np.full((B,), batch_t[take - 1], np.int64)
            arr_t[:take] = batch_t[:take]
            ro_p = np.zeros((B,), np.int32)
            ro_p[:take] = row_off
            cut_p = np.zeros((B,), bool)
            cut_p[:take] = is_cut
            cs_p = np.zeros((B,), np.int64)
            cs_p[:take] = cut_val
            cc_p = np.zeros((B,), np.int64)
            cc_p[:take] = cut_c
            self._state = self._ingest_rows(self._state, arr_t, valid,
                                            ro_p, cut_p, cs_p, cc_p)
            mx = int(batch_t[:take].max())
            mn = int(batch_t[:take].min())
            self._host_met = mx if met_pre is None else max(met_pre, mx)
            self._host_min_ts = mn if self._host_min_ts is None \
                else min(self._host_min_ts, mn)
            self._host_count += take
            return

        cut_starts = None
        if self._has_count and not self._grid_spec.has_time_grid and take:
            # count-cut slice starts = ARRIVAL-order running max event time
            # (the reference appends at maxEventTime) — computed before the
            # ts-sort erases arrival order; lane j of the sorted batch cuts
            # at count offset j, which is arrival j
            seed = np.int64(met_pre) if met_pre is not None \
                else np.iinfo(np.int64).min
            cs = np.maximum.accumulate(
                np.concatenate(([seed], batch_t[:take - 1])))
            cut_starts = np.full((B,), cs[-1], np.int64)
            cut_starts[:take] = cs

        if take and not bool((batch_t[:-1] <= batch_t[1:]).all()):
            order = np.argsort(batch_t, kind="stable")
            batch_v, batch_t = batch_v[order], batch_t[order]
        has_late = (take > 0 and met_pre is not None
                    and int(batch_t[0]) < met_pre)
        if take:
            mx = int(batch_t[take - 1]) if take < B else int(batch_t[-1])
            self._host_met = mx if self._host_met is None \
                else max(self._host_met, mx)
            mn = int(batch_t[0])
            self._host_min_ts = mn if self._host_min_ts is None \
                else min(self._host_min_ts, mn)
            self._host_count += take
        if not self._has_grid:
            return
        if has_late and not self._has_count:
            # late tuples may open annex slices → merge before next query.
            # (Count-only OOO never touches the annex, and the merge's
            # coincident-start combining would corrupt count slices, whose
            # starts legitimately repeat.)
            self._annex_dirty = True
        valid = np.ones((B,), dtype=bool)
        if take < B:
            pad_t = batch_t[-1] if take else 0
            batch_t = np.concatenate(
                [batch_t, np.full((B - take,), pad_t, np.int64)])
            batch_v = np.concatenate(
                [batch_v, np.zeros((B - take,), np.float32)])
            valid[take:] = False
        if self._has_count:
            # in-order batches append (O(B)); late-containing batches pay
            # the rank merge (O(RC) scatters) — see build_record_append
            rec_kern = self._rec_merge if has_late else self._rec_append
            self._rec = rec_kern(self._rec, batch_t, batch_v, valid)
            if cut_starts is not None:
                # count-only workloads (in- or out-of-order): the ts-sorted
                # batch through the in-order kernel IS the ripple's count
                # bookkeeping — every non-cutting lane folds into the open
                # slice (closed slices keep their fixed count ranges) and
                # count edges still cut, at arrival-order start positions.
                # OOO values come from the record buffer at query time.
                if has_late:
                    self._count_late_seen = True
                self._state = self._ingest_cut(self._state, batch_t,
                                               batch_v, valid, cut_starts)
                return
        if has_late:
            # Split the sorted batch at the lateness boundary: the late
            # prefix is usually a small fraction, but the combined general
            # kernel pays its full-lane scatter sets (in-order + late +
            # annex) for EVERY lane. Ingest the in-order tail through the
            # cheap kernels and only the late prefix through the general
            # kernel on a B/8 sub-batch — same semantics (the combined
            # kernel also folds late tuples against the already-updated
            # slice buffer). Falls back to one combined dispatch when the
            # late prefix exceeds the sub-batch.
            n_late = int(np.searchsorted(batch_t[:take], met_pre))
            late_cap = max(64, B // 8)
            if 0 < n_late <= late_cap and n_late < take:
                io_t = np.empty_like(batch_t)
                io_v = np.empty_like(batch_v)
                n_io = take - n_late
                io_t[:n_io] = batch_t[n_late:take]
                io_v[:n_io] = batch_v[n_late:take]
                io_t[n_io:] = io_t[n_io - 1]
                io_v[n_io:] = 0
                io_valid = np.zeros((B,), bool)
                io_valid[:n_io] = True
                kern, _ = self._pick_inorder_kernel(int(io_t[0]),
                                                    int(io_t[n_io - 1]))
                self._state = kern(self._state, io_t, io_v, io_valid)

                lt = np.empty((late_cap,), np.int64)
                lv = np.zeros((late_cap,), np.float32)
                lt[:n_late] = batch_t[:n_late]
                lv[:n_late] = batch_v[:n_late]
                lt[n_late:] = lt[n_late - 1]
                l_valid = np.zeros((late_cap,), bool)
                l_valid[:n_late] = True
                self._state = self._ingest(self._state, lt, lv, l_valid)
                return
            self._state = self._ingest(self._state, batch_t, batch_v, valid)
            return
        kern, _ = self._pick_inorder_kernel(
            int(batch_t[0]) if take else 0,
            int(batch_t[take - 1]) if take else 0)
        self._state = kern(self._state, batch_t, batch_v, valid)

    def _mixed_cut_calculus(self, ts: np.ndarray, met_pre):
        """Arrival-order slice-cut calculus for count+time mixed workloads
        — the host mirror of StreamSlicer.determineSlices over one batch.

        Count edges cut for EVERY tuple at the running max event time
        (StreamSlicer.java:37-44); time edges cut only for in-order tuples
        whose union-grid start exceeds the open slice's start (the engine's
        segment rule — empty grid ranges are not materialized). A lane with
        both cuts materializes one row at the later start (the intermediate
        slice would be empty). Returns per-lane ``(row_off, is_cut, start,
        cut_c)`` where ``row_off`` is the inclusive cut count (the lane's
        row is ``n_slices - 1 + row_off``) and ``cut_c`` the cutting lane's
        pre-insert global count (the new slice's fixed count start,
        SliceManager.appendSlice cStart). Also advances the persistent
        open-slice-start mirror, so it must run on every host batch of a
        mixed workload, in-order ones included.
        """
        from . import core as ec

        spec = self._grid_spec
        ts = np.asarray(ts, dtype=np.int64)
        take = ts.shape[0]
        imin = np.int64(ec.I64_MIN)
        seed = np.int64(met_pre) if met_pre is not None else imin
        # running max event time BEFORE each lane (maxEventTime is updated
        # after the tuple is processed, StreamSlicer.java:85)
        rm = np.maximum.accumulate(np.concatenate(([seed], ts[:-1])))
        inorder = ts >= rm
        c_idx = self._host_count + np.arange(take, dtype=np.int64)
        count_cut = (c_idx > 0) & (ec.host_count_grid(spec, c_idx)
                                   > ec.host_count_grid(spec, c_idx - 1))
        gs = ec.host_grid_start(spec, ts)
        open_pre = np.int64(self._host_open) \
            if self._host_open is not None else imin
        # open-start evolution = running max of fired cut values; including
        # non-firing candidates is harmless (a candidate <= the current
        # open start contributes nothing to the max)
        cand = np.where(count_cut, rm, imin)
        cand = np.maximum(cand, np.where(inorder, gs, imin))
        run = np.maximum(open_pre, np.maximum.accumulate(cand))
        open_before = np.concatenate(([open_pre], run[:-1]))
        time_cut = inorder & (gs > open_before)
        cut = count_cut | time_cut
        start = np.maximum(np.where(count_cut, rm, imin),
                           np.where(time_cut, gs, imin))
        self._host_open = int(run[-1]) if take else int(open_pre)
        row_off = np.cumsum(cut).astype(np.int32)
        return row_off, cut, start, c_idx

    def _feed_sessions(self, vals: np.ndarray, tss: np.ndarray,
                       met_pre) -> None:
        """Update every registered session window's active-session array
        with this batch, in arrival order.

        In-order tuples (at/above the running max event time) go through the
        vectorized chain kernel first; late tuples follow one at a time
        through the sequential scan kernel — processing all in-order tuples
        before the interleaved late ones provably cannot change any outcome
        (sessions.py module docstring), and within each class arrival order
        is preserved.
        """
        B = self.config.batch_size
        seed = np.int64(met_pre) if met_pre is not None \
            else np.iinfo(np.int64).min
        prev_rm = np.maximum.accumulate(
            np.concatenate((np.asarray([seed]), tss[:-1])))
        late_m = tss < prev_rm
        io_t, io_v = tss[~late_m], vals[~late_m]
        n_io = io_t.size
        if n_io:
            for lo in range(0, n_io, B):
                chunk_t, chunk_v = io_t[lo:lo + B], io_v[lo:lo + B]
                k = chunk_t.size
                pt = np.full((B,), chunk_t[-1], np.int64)
                pv = np.zeros((B,), np.float32)
                pt[:k], pv[:k] = chunk_t, chunk_v
                m = np.zeros((B,), bool)
                m[:k] = True
                gaps_t = np.diff(chunk_t) if k > 1 else \
                    np.empty(0, np.int64)
                for i, kern in enumerate(self._session_ingests):
                    # scatter-free run-bounded kernel when the chunk opens
                    # few sessions (the common bench shape: long sessions,
                    # huge batches) — same gate as the grid dense path
                    R = self.config.dense_ingest_runs
                    if R:
                        gap = int(self._session_windows[i].gap)
                        n_new = int((gaps_t > gap).sum()) + 2
                        if n_new <= R:
                            if self._session_dense[i] is None:
                                self._session_dense[i] = \
                                    _session_dense_kernel(
                                        self._spec.aggs, gap,
                                        self.config.capacity, R)
                            kern = self._session_dense[i]
                    self._session_states[i] = kern(
                        self._session_states[i], pt, pv, m)
        n_late = int(late_m.sum())
        if n_late:
            lt_all, lv_all = tss[late_m], vals[late_m]
            L = self._late_len
            for lo in range(0, n_late, L):
                chunk_t, chunk_v = lt_all[lo:lo + L], lv_all[lo:lo + L]
                k = chunk_t.size
                pt = np.full((L,), chunk_t[-1], np.int64)
                pv = np.zeros((L,), np.float32)
                pt[:k], pv[:k] = chunk_t, chunk_v
                m = np.zeros((L,), bool)
                m[:k] = True
                for i, kern in enumerate(self._session_lates):
                    self._session_states[i] = kern(
                        self._session_states[i], pt, pv, m)

    def _ctx_dispatch(self, i: int, cv: np.ndarray, ct: np.ndarray,
                      chunk: bool) -> None:
        """One padded device dispatch for context window ``i``: the
        vectorized chain kernel (``chunk=True``, sorted input) or the
        per-tuple scan (arrival-order input). Pads to a small
        power-of-two bucket, NOT the full batch size — the scan is
        sequential per lane, so a trickle flush at batch_size-length
        would pay thousands of wasted device steps (the kernels retrace
        per padded length; bucketing bounds the variants)."""
        B = self.config.batch_size
        k = ct.size
        if k == 0:
            return
        L = B if k == B else min(B, 1 << max(6, (k - 1).bit_length()))
        pt = np.full((L,), ct[-1], np.int64)
        pv = np.zeros((L,), np.float32)
        pt[:k], pv[:k] = ct, cv
        m = np.zeros((L,), bool)
        m[:k] = True
        if chunk:
            kern = _context_chunk_kernel(
                self._spec.aggs, self._ctx_specs[i],
                self.config.capacity, L)
        else:
            kern = self._ctx_applies[i]
        self._ctx_states[i] = kern(self._ctx_states[i], pt, pv, m)

    def _feed_contexts(self, vals: np.ndarray, tss: np.ndarray,
                       inorder: bool = False) -> None:
        """Apply this batch to every generic context window's active
        arrays, preserving arrival-order semantics.

        Per window: sorted in-order chunks take the vectorized chain
        kernel when the spec certifies it (inorder_chain_params — O(B)
        total work). OUT-OF-ORDER chunks of specs additionally
        certifying ``speculation_params`` go through the speculative
        planner (ISSUE 11): the chunk is sorted, segmented where
        ``decide`` provably cannot interact across the cut, safe
        segment runs execute as single chain-kernel dispatches, and
        only the segments the safety proof rejects replay through the
        per-tuple scan (in exact arrival order) — counted in the gated
        ``ctx_speculative_*`` telemetry. Everything else stays on the
        sequential scan."""
        from ..obs import (CTX_SPECULATIVE_FALLBACK_TUPLES,
                           CTX_SPECULATIVE_FALLBACKS,
                           CTX_SPECULATIVE_TUPLES)

        B = self.config.batch_size
        for i in range(len(self._ctx_states)):
            planner = self._ctx_planners[i]
            for lo in range(0, tss.size, B):
                ct, cv = tss[lo:lo + B], vals[lo:lo + B]
                if inorder and self._ctx_chain[i]:
                    self._ctx_dispatch(i, cv, ct, chunk=True)
                    if planner is not None:
                        planner.note_chunk(ct)
                        self._ctx_spec_stats["speculative_tuples"] += \
                            ct.size
                        if self.obs is not None:
                            self.obs.counter(
                                CTX_SPECULATIVE_TUPLES).inc(ct.size)
                    continue
                if planner is None:
                    self._ctx_dispatch(i, cv, ct, chunk=False)
                    continue
                for kind, idx in planner.plan(ct):
                    if kind == "chunk":
                        self._ctx_dispatch(i, cv[idx], ct[idx],
                                           chunk=True)
                        planner.note_chunk(ct[idx])
                        self._ctx_spec_stats["speculative_tuples"] += \
                            idx.size
                        if self.obs is not None:
                            self.obs.counter(
                                CTX_SPECULATIVE_TUPLES).inc(idx.size)
                    else:
                        self._ctx_dispatch(i, cv[idx], ct[idx],
                                           chunk=False)
                        planner.note_scan(ct[idx])
                        self._ctx_spec_stats["fallback_tuples"] += \
                            idx.size
                        self._ctx_spec_stats["fallback_runs"] += 1
                        if self.obs is not None:
                            self.obs.counter(
                                CTX_SPECULATIVE_FALLBACK_TUPLES).inc(
                                    idx.size)
                            self.obs.counter(
                                CTX_SPECULATIVE_FALLBACKS).inc()

    def _dense_ladder(self) -> tuple:
        """The run bounds the dense ingest kernel is built at:
        ``EngineConfig.dense_ingest_runs`` (0: no dense ingest), then the
        larger rungs of ``DENSE_RUN_LADDER`` — or the first rung alone
        under ``pallas_slice_merge`` (the Pallas segment fold unrolls its
        run loop, so its cost grows with the bound)."""
        first = self.config.dense_ingest_runs
        if not (first and self._has_grid
                and dense_eligible(self._grid_spec)):
            return ()
        if getattr(self.config, "pallas_slice_merge", False):
            return (first,)
        return (first,) + tuple(r for r in DENSE_RUN_LADDER if r > first)

    def _pick_inorder_kernel(self, ts_lo: int, ts_hi: int):
        """``(kernel, runs)`` for an in-order batch spanning
        ``[ts_lo, ts_hi]``: the scatter-free dense kernel at the smallest
        rung of the run ladder that provably bounds the batch's slice
        runs, or the general in-order kernel and 0 above the last rung
        or where the span fails the dense kernel's int32 offset test."""
        from . import core as ec

        pf = bool(getattr(self.config, "pallas_slice_merge", False))
        need = (ts_hi - ts_lo) // self._min_grid + 3
        runs = next((r for r in self._dense_rungs if need <= r), 0)
        if runs and not ec.ts32_fits(self._grid_spec, ts_lo, ts_hi):
            if self.obs is not None:
                self.obs.counter(_obs.INGEST_DENSE_TS32_FALLBACKS).inc()
            runs = 0
        if runs:
            if self._ingest_dense is None:
                self._build_dense(pf)
            if pf:
                # picked once per dispatched batch — the host-side
                # dispatch count of Pallas-bearing programs
                from .. import pallas as _pl

                _pl.record_dispatch(self.obs)
            if self.obs is not None:
                self.obs.counter(_obs.INGEST_DENSE_BATCHES).inc()
                self.obs.counter(_obs.INGEST_DENSE_TS32_BATCHES).inc()
            return self._ingest_dense[runs], runs
        if pf:
            # a flagged batch over the runs bound (or dense ingest
            # disabled) degrades to the scatter-heavy general kernel —
            # the same counted-never-silent contract as the shaper's
            # span/shape misses, gated by obs diff
            from .. import pallas as _pl

            _pl.record_fallback(self.obs, "dense_runs_bound")
        return self._ingest_inorder, 0

    def _build_dense(self, pf: bool) -> None:
        """Build the dense kernel at every rung and compile each now, by
        one dispatch on the live state of a batch with no valid lane (a
        no-op on the state): the first batch that needs a larger rung,
        possibly mid-stream, then compiles nothing. No host sync."""
        import jax

        B = self.config.batch_size
        self._ingest_dense = {
            R: _dense_kernel(self._grid_spec, self.config.capacity, R,
                             pallas_fold=pf,
                             pallas_packed=pf and bool(getattr(
                                 self.config, "pallas_packed", False)))
            for R in self._dense_rungs}
        ts, vals, valid = jax.device_put((np.zeros((B,), np.int64),
                                          np.zeros((B,), np.float32),
                                          np.zeros((B,), bool)))
        for kern in self._ingest_dense.values():
            self._state = kern(self._state, ts, vals, valid)

    # -- overflow policy (resilience.policy) -------------------------------
    #: admission slack: slices the mirror always keeps free so an exact
    #: bound slip (e.g. the annex merge materializing a boundary row) can
    #: never push the device buffers over
    _POL_SLACK = 2

    def _pol_refresh(self) -> None:
        """Re-sync the admission mirrors exactly (one deliberate device
        round trip — only paid when a batch's projected need approaches
        capacity). Pending annex rows count against the slice bound too:
        the watermark merge materializes up to one new slice per row."""
        import jax

        if self._state is None:
            return
        n, na = jax.device_get((self._state.n_slices, self._state.n_annex))
        self._pol_annex_ub = int(na)
        self._pol_slices_ub = int(n) + int(na)

    def _policy_admit(self, vals: np.ndarray, ts: np.ndarray, take: int,
                      met_pre):
        """SHED/GROW admission control at the host ingest boundary.

        The host mirror tracks UPPER BOUNDS on live slices and pending
        annex rows: an in-order batch opens at most one slice per distinct
        union-grid start above the stream head; a late tuple claims at
        most one annex row per distinct grid start (which the watermark
        merge may turn into a slice). When a batch's projected need
        exceeds the remaining headroom the mirror re-syncs exactly, then:

        * ``grow`` — double capacity (checkpoint → rebuild → restore)
          until the batch fits or ``max_capacity`` raises;
        * ``shed`` — drop late tuples first (they can only repair
          already-old windows — the lowest-watermark-impact rows), then
          tuples opening grid slices beyond the remaining headroom,
          admitting starts in ascending order. Drops are exact and
          auditable: ``resilience_shed_tuples`` + ``device_dropped_tuples``
          counters and the ``shed_callback(vals, ts)`` hook — the engine's
          results equal an oracle replay of precisely the survivors.
        """
        from . import core as ec
        from ..obs import device as _dev
        from ..resilience.policy import OverflowPolicy

        cfg = self.config
        vals, ts = vals[:take], ts[:take]
        starts = ec.host_grid_start(self._grid_spec, ts)
        late_m = (ts < met_pre) if met_pre is not None \
            else np.zeros(take, bool)
        seen = self._pol_seen_start
        io_starts = np.unique(starts[~late_m])
        if seen is not None:
            io_starts = io_starts[io_starts > seen]
        late_starts = np.unique(starts[late_m])
        slack = self._POL_SLACK
        cap_s = cfg.capacity - slack
        cap_a = cfg.annex_capacity - slack

        def over():
            return (self._pol_slices_ub + io_starts.size + late_starts.size
                    > cap_s
                    or self._pol_annex_ub + late_starts.size > cap_a)

        if over():
            self._pol_refresh()
        if over() and cfg.overflow_policy == OverflowPolicy.GROW:
            while over():
                self._grow_capacity()       # raises at max_capacity
                cap_s = self.config.capacity - slack
                cap_a = self.config.annex_capacity - slack
        elif over():                        # SHED
            drop = np.zeros(take, bool)
            if late_starts.size:            # late lanes first
                drop |= late_m
                late_starts = late_starts[:0]
            if self._pol_slices_ub + io_starts.size > cap_s:
                allowed = max(0, cap_s - self._pol_slices_ub)
                if allowed < io_starts.size:
                    drop |= (~late_m) & (starts >= io_starts[allowed])
                    io_starts = io_starts[:allowed]
            n_drop = int(drop.sum())
            if n_drop:
                if self.obs is not None:
                    self.obs.counter(_obs.RESILIENCE_SHED_TUPLES).inc(n_drop)
                    self.obs.flight_event(_flight.SHED,
                                          _obs.RESILIENCE_SHED_TUPLES,
                                          n_drop)
                if self._dm_active:
                    self._dm_host_add(_dev.DEVICE_DROPPED_TUPLES, n_drop)
                if self.shed_callback is not None:
                    self.shed_callback(vals[drop].copy(), ts[drop].copy())
                keep = ~drop
                vals, ts, starts = vals[keep], ts[keep], starts[keep]
                take = int(vals.shape[0])
        # mirror the admitted batch
        self._pol_slices_ub += io_starts.size + late_starts.size
        self._pol_annex_ub += late_starts.size
        if take and io_starts.size:
            self._pol_seen_start = int(max(
                seen if seen is not None else np.iinfo(np.int64).min,
                io_starts[-1]))
        return vals, ts, take

    def _grow_capacity(self) -> None:
        """GROW one step: snapshot the full device state via the
        checkpoint pytree machinery, rebuild every jitted kernel at the
        doubled capacity, corner-paste the old state into the fresh
        (larger) buffers and resume — host clock mirrors carry over, so
        the continued run is bit-identical to one pre-sized at the larger
        capacity (tests/test_resilience_policy.py)."""
        import contextlib

        import jax

        from ..resilience.policy import grow_engine_config, pad_tree
        from ..utils import checkpoint as _ck

        new_cfg = grow_engine_config(self.config)   # raises at max_capacity
        span = self.obs.span(_obs.RESILIENCE_GROW_SPAN) \
            if self.obs is not None else contextlib.nullcontext()
        with span:
            old_leaves = jax.device_get(
                jax.tree.flatten(_ck._full_state(self))[0])
            mirrors = {k: getattr(self, k) for k in (
                "_host_met", "_host_min_ts", "_host_first_ts", "_host_count",
                "_last_count", "_annex_dirty", "_count_late_seen",
                "_host_open", "_device_fed", "_last_watermark", "_dm",
                "_dm_host_acc", "_dm_folded", "_pol_seen_start")}
            self.config = new_cfg
            self._built = False
            self._build()                   # fresh kernels + state at 2×
            for k, v in mirrors.items():
                setattr(self, k, v)
            _ck._set_full_state(
                self, pad_tree(old_leaves, _ck._full_state(self)))
        self._pol_refresh()
        if self.obs is not None:
            self.obs.counter(_obs.RESILIENCE_GROW_EVENTS).inc()
            self.obs.flight_event(_flight.GROW, "capacity",
                                  float(self.config.capacity))

    def _flush(self) -> None:
        while self._n_pending > 0:
            self._launch_batch(min(self._n_pending, self.config.batch_size))

    def ingest_device_batch(self, vals, ts, ts_min: int, ts_max: int,
                            n_valid: Optional[int] = None,
                            valid=None) -> None:
        """Zero-copy ingest of device-resident arrays (shape [batch_size],
        ts ascending — late tuples allowed as the sorted prefix, within
        ``max_lateness``). ``ts_min``/``ts_max`` are host-known event-time
        bounds of the batch (they keep the host clock mirrors exact without
        a device sync; conservative bounds are fine). This is the path for
        device-side sources — host→device bandwidth never caps throughput.

        ``valid`` (optional) is a DEVICE-resident boolean lane mask that
        overrides the ``n_valid`` prefix mask — the stream shaper's
        sort-and-split computes its split point on device, so the mask
        cannot be host-materialized without a sync (scotty_tpu.shaper).
        Valid lanes must still be a sorted prefix with pad lanes
        repeating the last valid ts; ``n_valid`` then only feeds the
        host tuple-count mirrors (a conservative total is fine)."""
        if not self._built:
            self._build()
        if self.obs is not None and self.obs.latency is not None:
            # dispatch pre-stamp (ISSUE 14): the host-side moment this
            # device batch's ingest program is dispatched — pure Python,
            # the ingest kernel HLO is untouched
            self.obs.latency.pre(_lat.STAGE_DISPATCH)
        if self.config.overflow_policy != "fail":
            raise UnsupportedOnDevice(
                "overflow policies need host-visible timestamps for the "
                "admission mirror; device-resident ingest runs policy "
                "'fail'")
        import jax

        B = self.config.batch_size
        if self._valid_dev is None:
            self._valid_dev = jax.device_put(np.ones((B,), bool))
        n = B if n_valid is None else n_valid
        if valid is None:
            if n == B:
                valid = self._valid_dev
            else:
                # partially filled batch: lanes >= n_valid MUST be masked
                # or their pad values aggregate into real windows (lanes
                # must be a sorted prefix, pad lanes repeating the last
                # valid ts)
                m = np.zeros((B,), bool)
                m[:n] = True
                valid = jax.device_put(m)
        if self._session_states:
            raise UnsupportedOnDevice(
                "device-resident batches with session windows: use "
                "process_elements (host-fed) for session workloads")
        if self._ctx_states:
            # context windows accept device-resident batches when every
            # spec certifies the in-order chain (the chunk kernel needs
            # no host-side inspection) and the batch is in-order
            if not all(self._ctx_chain):
                raise UnsupportedOnDevice(
                    "device-resident batches with scan-only context "
                    "windows: use process_elements (host-fed)")
            if self._host_met is not None and ts_min < self._host_met:
                raise UnsupportedOnDevice(
                    "out-of-order device batches with context windows "
                    "need the host operator")
            for i in range(len(self._ctx_states)):
                kern = _context_chunk_kernel(
                    self._spec.aggs, self._ctx_specs[i],
                    self.config.capacity, B)
                self._ctx_states[i] = kern(self._ctx_states[i], ts, vals,
                                           valid)
                if self._ctx_planners[i] is not None:
                    # device-resident timestamps are host-opaque: the
                    # speculative bounds mirror cannot replay the chain
                    # walk, so the affected region goes conservatively
                    # unknown (later host OOO chunks re-prove safety
                    # only above it)
                    self._ctx_planners[i].invalidate(ts_max)
            if not self._has_grid:
                if self.obs is not None:        # pure-context ingest done
                    self.obs.counter(_obs.INGEST_TUPLES).inc(n)
                    self.obs.histogram(_obs.INGEST_BATCH_SIZE).observe(n)
                self._dm_device_update(ts, valid)
                self._host_met = ts_max if self._host_met is None \
                    else max(self._host_met, ts_max)
                self._host_min_ts = ts_min if self._host_min_ts is None \
                    else min(self._host_min_ts, ts_min)
                if self._host_first_ts is None:
                    self._host_first_ts = ts_min
                self._host_count += n
                return
        if self._has_count and self._grid_spec.has_time_grid:
            # the host cut mirror can't see device-resident timestamps; a
            # later late host batch must fall back (see _launch_batch)
            self._device_fed = True
        has_late = self._host_met is not None and ts_min < self._host_met
        if has_late:
            if self._has_count:
                raise UnsupportedOnDevice(
                    "out-of-order device batches with count-measure "
                    "windows need the host operator")
            self._annex_dirty = True
        if self.obs is not None:
            # past every reject guard: the batch is definitely ingested.
            # Device-resident ts are opaque host-side, so a back-reaching
            # batch counts whole as late at THIS host boundary — the
            # in-jit device_* counters below carry the exact count.
            self.obs.counter(_obs.INGEST_TUPLES).inc(n)
            self.obs.histogram(_obs.INGEST_BATCH_SIZE).observe(n)
            if has_late:
                self.obs.counter(_obs.LATE_TUPLES).inc(n)
        self._dm_device_update(ts, valid)
        if self._host_first_ts is None:
            self._host_first_ts = ts_min    # conservative (device ts opaque)
        self._host_met = ts_max if self._host_met is None \
            else max(self._host_met, ts_max)
        self._host_min_ts = ts_min if self._host_min_ts is None \
            else min(self._host_min_ts, ts_min)
        self._host_count += n
        if has_late:
            # general kernel: late/annex paths
            kern, kind, runs = self._ingest, "general", 0
        else:
            # dense scatter-free variant when the span bound allows
            kern, runs = self._pick_inorder_kernel(ts_min, ts_max)
            kind = "dense" if runs else "inorder"
        with _obs.program_span(self.obs, "ingest.dispatch", lanes=B,
                               n_valid=n, late=has_late, kernel=kind,
                               runs=runs, ts32=bool(runs)):
            self._state = kern(self._state, ts, vals, valid)
        if self._has_count:
            # device batches with count windows are in-order by contract
            self._rec = self._rec_append(self._rec, ts, vals, valid)

    def ingest_device_late(self, ts, vals, valid, n: int, ts_min: int,
                           ts_max: int) -> None:
        """Zero-copy ingest of a device-resident LATE sub-batch (ts sorted,
        all within ``max_lateness``; shape is the caller's static late
        capacity — typically a small fraction of batch_size, so the general
        kernel's full-lane late/annex scatters stay cheap). Companion to
        :meth:`ingest_device_batch` for device sources that separate their
        disorder from the in-order base stream."""
        if not self._built:
            self._build()
        if self.config.overflow_policy != "fail":
            raise UnsupportedOnDevice(
                "overflow policies need host-visible timestamps for the "
                "admission mirror; device-resident ingest runs policy "
                "'fail'")
        if self._has_count or self._session_states or self._ctx_states:
            raise UnsupportedOnDevice(
                "out-of-order device batches with count-measure, session "
                "or context windows need the host operator")
        if self.obs is not None:
            self.obs.counter(_obs.INGEST_TUPLES).inc(n)
            self.obs.counter(_obs.LATE_TUPLES).inc(n)
        self._dm_device_update(ts, valid)
        self._annex_dirty = True
        self._host_met = ts_max if self._host_met is None \
            else max(self._host_met, ts_max)
        self._host_min_ts = ts_min if self._host_min_ts is None \
            else min(self._host_min_ts, ts_min)
        self._host_count += n
        with _obs.program_span(self.obs, "ingest.dispatch",
                               lanes=ts.shape[0], n_valid=n, late=True,
                               kernel="general", runs=0, ts32=False):
            self._state = self._ingest(self._state, ts, vals, valid)

    # -- watermark ---------------------------------------------------------
    def process_watermark(self, watermark_ts: int) -> List[AggregateWindow]:
        ws, we, cnt, lowered = self.process_watermark_arrays(watermark_ts)
        measures = getattr(self, "_trigger_measures", None)
        out: List[AggregateWindow] = []
        for i in range(ws.shape[0]):
            has = bool(cnt[i] > 0)
            values = [lw[i] for lw in lowered] if has else []
            m = (WindowMeasure.Count
                 if measures is not None and measures.shape[0] > i
                 and measures[i] else WindowMeasure.Time)
            out.append(AggregateWindow(m, int(ws[i]), int(we[i]), values, has))
        if self._lat_open is not None and self.obs is not None \
                and self.obs.latency is not None:
            # hand the chain to the sink slot: a TransactionalSink
            # downstream stamps the first delivery and closes it; a
            # sink-less run's chain closes at the next watermark or the
            # check_overflow flush
            self.obs.latency.emitted(self._lat_open)
            self._lat_open = None
        return out

    def process_watermark_async(self, watermark_ts: int):
        """Dispatch the full watermark program with NO device→host sync on
        the time-measure path (a sync per watermark would stall the
        dispatch queue at benchmark rates). Returns
        ``(ws, we, is_count, cnt_dev, results_dev)`` where the last two are
        device arrays (padded; first ``len(ws)`` rows are live). Call
        :meth:`check_overflow` after draining a stream.

        Host-side clock mirrors replace the reference's store inspection:
        emptiness (WindowManager.java:46-49) is "no tuples ever fed"; the
        oldest-slice clamp (:51-55) only binds on the FIRST watermark —
        after any GC, oldest ≤ gc bound < last watermark — and at that point
        the oldest slice start is exactly grid_start(min ts seen).
        """
        obs = self.obs
        if obs is None:
            return self._process_watermark_dispatch(watermark_ts)
        lat = obs.latency
        if lat is not None and self._lat_open is not None:
            # an async caller never fetched the previous watermark's
            # results through this operator — close its chain as-is
            # (no drain/emit stamps) instead of leaking it to eviction
            lat.finalize(self._lat_open)
            self._lat_open = None
        t_elig = lat.clock.now() if lat is not None else 0.0
        t0 = time.perf_counter()
        out = self._process_watermark_dispatch(watermark_ts)
        if lat is not None:
            # emission-latency lineage (ISSUE 14): the watermark's
            # arrival IS the eligibility moment for every window it
            # closes — the chain opens here, claiming the pending
            # arrival/ring/shaper/dispatch pre-stamps of the records
            # this watermark sweeps (drains inside the dispatch above
            # may add late pre-stamps; finalize time-orders them). One
            # chain per watermark, completed by the arrays/emit face.
            self._lat_open = lat.open()
            lat.stamp(self._lat_open, _lat.STAGE_ELIGIBILITY, at=t_elig)
        # host-side, interval-boundary telemetry: dispatch wall time (no
        # device sync — delivery latency is the harness's emit_latency_ms),
        # watermark count, and event-time lag of the watermark behind the
        # stream head
        obs.histogram(_obs.WATERMARK_DISPATCH_MS).observe(
            (time.perf_counter() - t0) * 1e3)
        obs.counter(_obs.WATERMARKS).inc()
        obs.flight_event(_flight.WATERMARK, "watermark",
                         float(watermark_ts))
        if self._host_met is not None:
            # floored at 0: a drain watermark deliberately runs past the
            # stream end, and a last-value gauge stuck negative would make
            # the headline lag metric meaningless for the whole run
            obs.gauge(_obs.WATERMARK_LAG_MS).set(
                max(0, self._host_met - watermark_ts))
        return out

    def _process_watermark_dispatch(self, watermark_ts: int):
        if not self._built:
            self._build()
        span = _obs.program_span
        with span(self.obs, "watermark.flush_ingest", wm=watermark_ts):
            if self._shaper is not None:
                # event time is about to advance past anything still held
                # in the shaper's accumulator — drain it first (the
                # shaper's bounded-delay contract also caps how much can
                # be here)
                self._shaper.flush()
            if self._ingest_feed is not None:
                # same contract for the ingest ring: records still staged
                # (accumulator slack band, partial block, prefetch stage)
                # must land before the watermark sweeps past them
                self._ingest_feed.drain()
            self._flush()
        if self._pure_session:
            outs = self._sweep_sessions(watermark_ts)
            self._last_watermark = watermark_ts
            return ("session", outs)
        st = self._state

        last_wm = self._last_watermark
        first_watermark = last_wm == -1
        if first_watermark:                  # WindowManager.java:43-45
            last_wm = max(0, watermark_ts - self.max_lateness)

        empty = np.empty(0, dtype=np.int64)
        no_result = (empty, empty, np.empty(0, bool), None, None)
        if self._host_met is None:           # store empty: :46-49
            self._last_watermark = watermark_ts
            return self._wrap_mixed(no_result, watermark_ts)

        # The reference's first-watermark clamp to the oldest slice start
        # (WindowManager.java:51-55) reads the FIRST-INSERTED slice. For
        # time-only specs that is the bootstrap/seeded walk from
        # ``te - maxLateness`` (clamped >= 0), so the max(0, wm - lateness)
        # above already matches (clamping to grid_start(min ts) instead
        # would skip the leading empty windows the reference emits — caught
        # by randomized differential fuzzing). With a COUNT measure the
        # first-inserted slice is the count bootstrap cut at the FIRST
        # ARRIVAL's ts (StreamSlicer.java:37-44 fires before any time
        # edge), so streams starting above wm - lateness would otherwise
        # emit leading time windows the reference suppresses (caught by
        # the r4 mixed-OOO review).
        if first_watermark and self._has_count \
                and self._host_first_ts is not None:
            last_wm = max(last_wm, self._host_first_ts)

        if self._annex_dirty:
            with span(self.obs, "watermark.merge", wm=watermark_ts):
                self._state = self._merge(self._state)
            st = self._state
            self._annex_dirty = False

        # count-measure trigger bound: watermark ts → count
        # (WindowManager.java:104-118). The one remaining sync, count
        # workloads only.
        cend = None
        if self._has_count:
            cend = int(self._count_at_rec(st, self._rec,
                                          np.int64(watermark_ts))
                       if self._count_late_seen
                       else self._count_at(st, np.int64(watermark_ts)))

        with span(self.obs, "watermark.trigger", wm=watermark_ts) as ann:
            trig_s, trig_e, trig_c = [], [], []
            for w, act in zip(self.windows, self._win_active):
                if not act:
                    continue          # cancelled query: mask, not rebuild
                if isinstance(w, (SessionWindow, ForwardContextAware,
                                  ForwardContextFree)):
                    continue          # context windows emit via sweeps
                if w.measure == WindowMeasure.Count:
                    s_arr, e_arr = w.trigger_arrays(self._last_count,
                                                    cend + 1)
                    trig_c.append(np.ones(s_arr.shape[0], bool))
                else:
                    s_arr, e_arr = w.trigger_arrays(last_wm, watermark_ts)
                    trig_c.append(np.zeros(s_arr.shape[0], bool))
                trig_s.append(s_arr)
                trig_e.append(e_arr)
            ws = np.concatenate(trig_s) if trig_s else empty
            we = np.concatenate(trig_e) if trig_e else empty
            is_count = (np.concatenate(trig_c) if trig_c
                        else np.empty(0, dtype=bool))
            T = ws.shape[0]
            ann.set_metadata(T=T)
            if T > self.config.max_triggers:
                raise RuntimeError(
                    f"{T} triggered windows exceeds max_triggers="
                    f"{self.config.max_triggers}")
            if T:
                Tp = self.config.trigger_pad(T)
                ws_p = np.zeros((Tp,), np.int64)
                we_p = np.zeros((Tp,), np.int64)
                mask = np.zeros((Tp,), bool)
                ic_p = np.zeros((Tp,), bool)
                ws_p[:T], we_p[:T], mask[:T] = ws, we, True
                ic_p[:T] = is_count

        cnt_d = results = None
        if T:
            with span(self.obs, "watermark.query", wm=watermark_ts):
                cnt_d, results = self._dispatch_query(
                    st, ws, we, is_count, (ws_p, we_p, mask, ic_p))

        if self._has_count:
            self._last_count = self._host_count   # exact host mirror
        bound = (watermark_ts - self.max_lateness) - self.max_fixed_window_size
        with span(self.obs, "watermark.gc", wm=watermark_ts):
            if self._has_count:
                # records GC in rank-lockstep with the slices (reads the
                # PRE-GC slice buffer; dispatched before the slice GC)
                self._rec = self._rec_gc(st, self._rec, np.int64(bound))
            self._state = self._gc(st, np.int64(bound))
        self._last_watermark = watermark_ts
        self._trigger_measures = is_count
        return self._wrap_mixed((ws, we, is_count, cnt_d, results),
                                watermark_ts)

    def _dispatch_query(self, st, ws, we, is_count, padded):
        """Dispatch the range query over the padded trigger rows
        ``padded = (ws_p, we_p, mask, ic_p)``; returns device
        ``(counts, results)``."""
        if not (self._has_count and self._count_late_seen):
            return self._query(st, *padded)
        if not self._grid_spec.has_time_grid:
            return self._query_rec(st, self._rec, *padded)
        # the reference final-merge's batch scan bounds
        # (WindowManager.java:98-118 → LazyAggregateStore.aggregate):
        # defaults LONG_MAX/0, count default = current count; duplicates
        # shadow (see build_query)
        tm = ~is_count
        min_ts = int(ws[tm].min()) if tm.any() else LONG_MAX
        max_ts = int(we[tm].max()) if tm.any() else 0
        min_count = self._host_count
        max_count = 0
        if is_count.any():
            min_count = min(min_count, int(ws[is_count].min()))
            max_count = int(we[is_count].max())
        return self._query_rec(st, self._rec, *padded,
                               np.int64(min_ts), np.int64(max_ts),
                               np.int64(min_count), np.int64(max_count))

    def _wrap_mixed(self, grid, watermark_ts: int):
        """Append context-window sweeps to a grid watermark result when
        session/context windows are registered (emission order matches
        the simulator: context-free windows first, then context-aware —
        WindowManager.java:98-118)."""
        if not (self._session_states or self._ctx_states):
            return grid
        return ("mixed", grid, self._sweep_sessions(watermark_ts))

    def _sweep_sessions(self, watermark_ts: int):
        """Sweep every context window (tuned session paths and generic
        device-context paths) in registration order."""
        outs = []
        wm = np.int64(watermark_ts)
        gc_bound = np.int64(watermark_ts - self.max_lateness)
        for kind, i in self._ctx_order:
            if kind == "s":
                new_s, m_d, e_s, e_e, e_c, e_p = self._session_sweeps[i](
                    self._session_states[i], wm, gc_bound)
                self._session_states[i] = new_s
            else:
                new_s, m_d, e_s, e_e, e_c, e_p = self._ctx_sweeps[i](
                    self._ctx_states[i], wm,
                    gc_bound - np.int64(self._ctx_gc_slack[i]))
                self._ctx_states[i] = new_s
                if self._ctx_planners[i] is not None:
                    # the planner's bounds mirror prunes on the same
                    # certified trigger rule the device sweep applies
                    self._ctx_planners[i].sweep(watermark_ts)
            outs.append((m_d, e_s, e_e, e_c, e_p))
        return outs

    def _lat_stamp(self, stage: str) -> None:
        """Stamp one stage on the in-flight watermark chain (no-op
        without a tracer or an open chain — one attribute check)."""
        if self._lat_open is not None and self.obs is not None:
            lat = self.obs.latency
            if lat is not None:
                lat.stamp(self._lat_open, stage)

    def process_watermark_arrays(self, watermark_ts: int):
        """Synchronous watermark: returns numpy ``(starts[T], ends[T],
        counts[T], [per-agg lowered [T]])`` — one bundled device fetch."""
        with _obs.program_span(self.obs, "watermark", wm=watermark_ts):
            return self._watermark_arrays(watermark_ts)

    def _watermark_arrays(self, watermark_ts: int):
        out = self.process_watermark_async(watermark_ts)
        if isinstance(out[0], str) and out[0] == "session":
            ws, we, cnt, lowered = self._fetch_sessions(out[1], watermark_ts)
            self._trigger_measures = np.zeros((ws.shape[0],), bool)
            self._lat_stamp(_lat.STAGE_EMIT)
            return ws, we, cnt, lowered
        if isinstance(out[0], str) and out[0] == "mixed":
            _, grid, s_outs = out
            g_ws, g_we, g_cnt, g_low = self._fetch_grid(grid, watermark_ts)
            s_ws, s_we, s_cnt, s_low = self._fetch_sessions(s_outs,
                                                            watermark_ts)
            ws = np.concatenate([g_ws, s_ws])
            we = np.concatenate([g_we, s_we])
            cnt = np.concatenate([g_cnt, s_cnt])
            lowered = [np.concatenate([np.asarray(a), np.asarray(b)])
                       for a, b in zip(g_low, s_low)]
            is_count = grid[2]
            self._trigger_measures = np.concatenate(
                [is_count, np.zeros((s_ws.shape[0],), bool)])
            self._lat_stamp(_lat.STAGE_EMIT)
            return ws, we, cnt, lowered
        res = self._fetch_grid(out, watermark_ts)
        self._lat_stamp(_lat.STAGE_EMIT)
        return res

    def _fetch_grid(self, grid, watermark_ts: int):
        import jax

        span = _obs.program_span
        ws, we, is_count, cnt_d, results = grid
        T = ws.shape[0]
        lowered: List[np.ndarray] = [np.empty(0)
                                     for _ in self.aggregations] if T == 0 \
            else []
        cnt_np = np.zeros((T,), dtype=np.int64)
        if T:
            ovf_src = self._state.overflow if self._rec is None \
                else self._state.overflow | self._rec.overflow
            with span(self.obs, "watermark.fetch", wm=watermark_ts):
                cnt_h, res_h, ovf = jax.device_get((cnt_d, results,
                                                    ovf_src))
            self._lat_stamp(_lat.STAGE_DRAIN)
            self._raise_if_overflow(ovf)
            with span(self.obs, "watermark.lower", wm=watermark_ts):
                cnt_np = cnt_h[:T]
                for agg, res in zip(self.aggregations, res_h):
                    spec = agg.device_spec()
                    lowered.append(np.asarray(spec.lower(res[:T], cnt_np)))
        return ws, we, cnt_np, lowered

    def _raise_if_overflow(self, ovf) -> None:
        if bool(ovf):
            note = "" if self.config.overflow_policy == "fail" else (
                f" (overflow_policy={self.config.overflow_policy!r} could "
                "not prevent it — the raised device flag means writes were "
                "already clamped, which is unrecoverable under any policy)")
            e = RuntimeError(
                "slice/session buffer overflow: raise EngineConfig.capacity "
                "(slice rows, session rows) / annex_capacity (late annex & "
                "session orphan buffer) / batch sizing, advance watermarks "
                "more often, or set EngineConfig.overflow_policy to "
                "'shed'/'grow' (scotty_tpu.resilience)" + note)
            if self.obs is not None:
                self.obs.counter(_obs.OVERFLOWS).inc()
                self.obs.record_failure(e, kind=_flight.OVERFLOW,
                                        config=self.config)
            raise e

    def check_overflow(self) -> None:
        """One deliberate sync validating the run (async users call this
        after draining a stream)."""
        if self._shaper is not None:
            # shaper drain-point check: raises ShaperOverflow on a lost
            # late residue and folds the shaper_* telemetry
            self._shaper.check()
        if self._ingest_feed is not None:
            # ingest-ring drain-point fold (ingest_ring_* counters +
            # occupancy gauges — scotty_tpu.ingest)
            self._ingest_feed.check()
        if not self._built:
            return
        if self._state is not None:
            self._raise_if_overflow(self._state.overflow)
        if self._rec is not None:
            self._raise_if_overflow(self._rec.overflow)
        for st in getattr(self, "_session_states", ()):
            self._raise_if_overflow(st.overflow)
        for st in getattr(self, "_ctx_states", ()):
            self._raise_if_overflow(st.overflow)
        if self.obs is not None and self._state is not None:
            # this method is already a deliberate sync point, so the
            # occupancy/headroom gauges can read the live slice count
            # without introducing a new device round trip
            import jax

            n = int(jax.device_get(self._state.n_slices))
            cap = self.config.capacity
            self.obs.gauge(_obs.SLICE_OCCUPANCY).set(n / cap)
            self.obs.gauge(_obs.SLICE_HEADROOM).set(cap - n)
        if self.obs is not None:
            # same drain point: fold the device_* telemetry delta
            from ..obs import device as _dev

            self._dm_folded = _dev.fold_into(
                self.obs.registry, self.device_metrics(), self._dm_folded)
            # and sample the flight ring (zero additional device syncs —
            # the watermark advance itself was recorded at dispatch)
            self.obs.flight_sample()
            lat = self.obs.latency
            if lat is not None:
                # latency drain-point tidy: close a chain an async
                # caller left open and a parked sink handoff, fold the
                # lineage/drop totals — same discipline as the folds
                # above, zero extra syncs
                if self._lat_open is not None:
                    lat.finalize(self._lat_open)
                    self._lat_open = None
                lat.flush()

    def _fetch_sessions(self, outs, watermark_ts: int):
        """Fetch per-session-window sweep outputs; emission follows window
        registration order (the simulator's context list order)."""
        import jax

        with _obs.program_span(self.obs, "watermark.fetch", wm=watermark_ts):
            fetched = jax.device_get(
                (outs, tuple(s.overflow for s in (
                    list(self._session_states) + list(self._ctx_states)))))
        with _obs.program_span(self.obs, "watermark.lower", wm=watermark_ts):
            return self._lower_sessions(fetched)

    def _lower_sessions(self, fetched):
        self._lat_stamp(_lat.STAGE_DRAIN)
        gap_outs, ovfs = fetched
        for ovf in ovfs:
            self._raise_if_overflow(ovf)
        ws_parts, we_parts, cnt_parts = [], [], []
        low_parts = [[] for _ in self.aggregations]
        for (m, ws_h, we_h, cnt_h, res_h) in gap_outs:
            m = int(m)
            if m > self._emit_cap:
                # the second overflow raise path (ISSUE 3 satellite):
                # counted like the buffer-overflow path so dashboards and
                # the obs diff gate see it, with an actionable hint
                e = RuntimeError(
                    f"{m} sessions completed in one watermark exceeds the "
                    f"emission buffer ({self._emit_cap}); raise "
                    "EngineConfig.min_trigger_pad, advance watermarks more "
                    "often (fewer sessions complete per sweep), or run "
                    "under a scotty_tpu.resilience.Supervisor to restart "
                    "from the last checkpoint")
                if self.obs is not None:
                    self.obs.counter(_obs.OVERFLOWS).inc()
                    self.obs.record_failure(e, kind=_flight.OVERFLOW,
                                            config=self.config)
                raise e
            ws_parts.append(ws_h[:m])
            we_parts.append(we_h[:m])
            cnt_parts.append(cnt_h[:m])
            for j, (agg, res) in enumerate(zip(self.aggregations, res_h)):
                spec = agg.device_spec()
                low_parts[j].append(
                    np.asarray(spec.lower(res[:m], cnt_h[:m])))
        ws = np.concatenate(ws_parts) if ws_parts else np.empty(0, np.int64)
        we = np.concatenate(we_parts) if we_parts else np.empty(0, np.int64)
        cnt = np.concatenate(cnt_parts) if cnt_parts \
            else np.empty(0, np.int64)
        lowered = [np.concatenate(p) if p else np.empty(0) for p in low_parts]
        return ws, we, cnt, lowered

    # -- introspection -----------------------------------------------------
    @property
    def n_slices(self) -> int:
        total = 0
        if self._state is not None:
            total += int(self._state.n_slices)
        for st in getattr(self, "_session_states", ()):
            total += int(st.n)              # live sessions
        for st in getattr(self, "_ctx_states", ()):
            total += int(st.n)              # live context windows
        return total

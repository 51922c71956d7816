"""Keyed operator: keys as a leading batch dimension of one device program.

The reference scales by key partitioning delegated to the host engine — each
key gets an independent JVM operator object in a HashMap
(flink-connector/.../KeyedScottyWindowOperator.java:21,56-66; SURVEY.md §2.8).
The TPU-native equivalent: the per-key slice buffers are ONE batched array
``[K, ...]`` served by vmapped kernels, and multi-chip scaling shards the key
axis over a ``jax.sharding.Mesh`` — per-key windows need no cross-key
communication (embarrassingly parallel, exactly the reference's model), so
the sharded program runs collective-free over ICI.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

import numpy as np

from ..core.aggregates import AggregateFunction
from ..core.operator import AggregateWindow
from ..core.windows import (
    FixedBandWindow,
    SlidingWindow,
    TumblingWindow,
    Window,
    WindowMeasure,
)
from ..engine.config import EngineConfig
from ..engine.operator import UnsupportedOnDevice
from ..engine.pipeline import FusedPipelineDriver

_KERNEL_CACHE: dict = {}


class KeyedTpuWindowOperator:
    """One device program serving ``n_keys`` independent keyed operators.

    API mirrors the reference connectors' KeyedScottyWindowOperator: register
    windows + aggregations, feed ``(key, value, ts)`` tuples, advance a
    watermark to collect per-key window results.

    ``mesh``/``axis``: optional ``jax.sharding.Mesh`` whose ``axis`` shards
    the key dimension across devices (``n_keys`` must be divisible by the
    axis size).
    """

    def __init__(self, n_keys: int, config: Optional[EngineConfig] = None,
                 mesh=None, axis: str = "keys"):
        self.n_keys = int(n_keys)
        self.config = config or EngineConfig()
        self.mesh = mesh
        self.axis = axis
        self.windows: List[Window] = []
        self.aggregations: List[AggregateFunction] = []
        self.max_lateness = 1000
        self.max_fixed_window_size = 0
        self._last_watermark = -1
        self._built = False
        self._state = None
        self._pend: list = []            # list of (keys, vals, ts) np arrays
        self._n_pending = 0

    # -- registry (same contract as TpuWindowOperator) ---------------------
    def add_window_assigner(self, window: Window) -> None:
        if self._built:
            raise RuntimeError("add windows before first element")
        if not isinstance(window, (TumblingWindow, SlidingWindow,
                                   FixedBandWindow)) \
                or window.measure != WindowMeasure.Time:
            raise UnsupportedOnDevice(
                f"{window} has no keyed device path; use per-key host "
                "operators via connectors.KeyedScottyWindowOperator")
        self.windows.append(window)
        self.max_fixed_window_size = max(self.max_fixed_window_size,
                                         window.clear_delay())

    def add_aggregation(self, fn: AggregateFunction) -> None:
        if self._built:
            raise RuntimeError("add aggregations before first element")
        if fn.device_spec() is None:
            raise UnsupportedOnDevice(
                f"{type(fn).__name__} has no device realization")
        self.aggregations.append(fn)

    def set_max_lateness(self, max_lateness: int) -> None:
        self.max_lateness = max_lateness

    # -- build -------------------------------------------------------------
    def _compute_spec(self):
        from ..engine import core as ec

        periods, bands, offset_periods = [], [], []
        for w in self.windows:
            if isinstance(w, TumblingWindow):
                periods.append(int(w.size))
            elif isinstance(w, SlidingWindow):
                periods.append(int(w.slide))
                if w.size % w.slide:
                    offset_periods.append((int(w.slide),
                                           int(w.size % w.slide)))
            elif isinstance(w, FixedBandWindow):
                bands.append((int(w.start), int(w.size)))
        return ec.EngineSpec(
            periods=ec.collapse_periods(periods),
            bands=tuple(sorted(set(bands))),
            count_periods=(),
            aggs=tuple(a.device_spec() for a in self.aggregations),
            offset_periods=tuple(sorted(set(offset_periods))),
        )

    def _build(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..engine import core as ec

        self._spec = self._compute_spec()
        C, A = self.config.capacity, self.config.annex_capacity
        key = (self._spec.periods, self._spec.bands, self._spec.offset_periods,
               tuple(a.token for a in self._spec.aggs), C, A, self.n_keys,
               id(self.mesh), self.axis)
        hit = _KERNEL_CACHE.get(key)
        if hit is None:
            from ..engine.operator import dense_eligible, min_grid_period

            ingest1 = ec.build_ingest(self._spec, C, A)
            ingest_io1 = ec.build_ingest(self._spec, C, A,
                                         assume_inorder=True)
            dense_runs = (self.config.dense_ingest_runs
                          if dense_eligible(self._spec) else 0)
            ingest_dense1 = (ec.build_ingest_dense(self._spec, C, dense_runs)
                            if dense_runs else None)
            query1 = ec.build_query(self._spec, C, A)
            gc1 = ec.build_gc(self._spec, C, A)
            # sharding note: the state is device_put with
            # NamedSharding(mesh, P(axis)) below; jit propagates it through
            # the vmapped kernels, and since every op is per-key, XLA
            # partitions the whole program over the key axis with no
            # collectives (SURVEY.md §5 "distributed communication backend").
            merge1 = ec.build_annex_merge(self._spec, C, A)
            hit = (
                jax.jit(jax.vmap(ingest1)),
                jax.jit(jax.vmap(query1, in_axes=(0, None, None, None, None))),
                jax.jit(jax.vmap(gc1, in_axes=(0, None))),
                jax.jit(jax.vmap(merge1)),
                # in-order rounds skip the late/annex scatter sets — int64
                # scatters are the dominant ingest cost on TPU
                jax.jit(jax.vmap(ingest_io1)),
                (jax.jit(jax.vmap(ingest_dense1))
                 if ingest_dense1 is not None else None),
                dense_runs,
            )
            _KERNEL_CACHE[key] = hit
        (self._ingest, self._query, self._gc, self._merge,
         self._ingest_inorder, self._ingest_dense, self._dense_runs) = hit
        from ..engine.operator import min_grid_period

        self._min_grid = min_grid_period(self._spec)
        self._host_met = None
        self._annex_dirty = False

        one = ec.init_state(self._spec, C, A)
        self._state = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.n_keys,) + x.shape), one)
        if self.mesh is not None:
            shard = NamedSharding(self.mesh, P(self.axis))
            self._state = jax.device_put(self._state, shard)
        self._built = True

    # -- ingest ------------------------------------------------------------
    def process_keyed_elements(self, keys: Sequence, values: Sequence,
                               timestamps: Sequence) -> None:
        """Batched keyed ingest: ``keys`` are integer shard ids in
        ``[0, n_keys)`` (host hash-partitioning, the analogue of the host
        engine's ``keyBy``)."""
        if not self._built:
            self._build()
        k = np.asarray(keys, dtype=np.int32).reshape(-1)
        v = np.asarray(values, dtype=np.float32).reshape(-1)
        t = np.asarray(timestamps, dtype=np.int64).reshape(-1)
        self._pend.append((k, v, t))
        self._n_pending += k.shape[0]
        # flush when the densest key bucket could exceed a device batch
        if self._n_pending >= self.config.batch_size * max(1, self.n_keys // 4):
            self._flush()

    def process_element(self, key: int, value, ts: int) -> None:
        self.process_keyed_elements([key], [value], [ts])

    def _flush(self) -> None:
        if not self._n_pending:
            return
        B = self.config.batch_size
        k = np.concatenate([p[0] for p in self._pend])
        v = np.concatenate([p[1] for p in self._pend])
        t = np.concatenate([p[2] for p in self._pend])
        self._pend, self._n_pending = [], 0

        # stable partition by key, then ts-sort within key
        has_late = False
        flush_span = int(t.max()) - int(t.min()) if t.size else 0
        if t.size:
            if self._host_met is not None and int(t.min()) < self._host_met:
                # a late tuple may open an annex slice on some shard → merge
                # before the next query. (Global in-order implies per-key
                # in-order: each key's row is a subsequence of the sorted
                # stream, and per-key max event time <= the global one.)
                self._annex_dirty = True
                has_late = True
            mx = int(t.max())
            self._host_met = mx if self._host_met is None \
                else max(self._host_met, mx)
        order = np.lexsort((t, k))
        k, v, t = k[order], v[order], t[order]
        counts = np.bincount(k, minlength=self.n_keys)
        max_per_key = int(counts.max()) if counts.size else 0
        if max_per_key == 0:
            return
        # Vectorized packing: tuple j of key k lands in round pos//B,
        # lane pos%B, where pos is its rank within its key. One scatter
        # builds every round's [K, B] batch — no per-key Python loop
        # (the reference's per-key HashMap walk has no business on the
        # host side of a batched device program).
        starts = np.zeros(self.n_keys, np.int64)
        starts[1:] = np.cumsum(counts)[:-1]
        pos = np.arange(t.size, dtype=np.int64) - starts[k]
        rnd = pos // B
        lane = pos % B
        n_rounds = (max_per_key + B - 1) // B
        for r in range(n_rounds):
            # one [K, B] trio per round (not all rounds at once — a
            # hot-key-skewed flush would otherwise allocate
            # O(n_keys * max_per_key) host memory)
            m = rnd == r
            ts_b = np.zeros((self.n_keys, B), np.int64)
            vals_b = np.zeros((self.n_keys, B), np.float32)
            valid_b = np.zeros((self.n_keys, B), bool)
            ts_b[k[m], lane[m]] = t[m]
            vals_b[k[m], lane[m]] = v[m]
            valid_b[k[m], lane[m]] = True
            # pad lanes repeat the row's last valid ts → no spurious slices
            # (valid lanes are a contiguous prefix of each row; all-invalid
            # rows stay ts 0, which the ingest kernel ignores).
            row_n = valid_b.sum(axis=1)                    # [K]
            last_ts = ts_b[np.arange(self.n_keys),
                           np.maximum(row_n - 1, 0)]
            pad = ~valid_b & (row_n > 0)[:, None]
            ts_b = np.where(pad, last_ts[:, None], ts_b)
            if has_late:
                kern = self._ingest
            else:
                kern = self._ingest_inorder
                if self._ingest_dense is not None:
                    span_runs = flush_span // self._min_grid + 3
                    if span_runs <= self._dense_runs:
                        kern = self._ingest_dense
            self._state = kern(self._state, ts_b, vals_b, valid_b)

    def ingest_device_round(self, ts, vals, valid, ts_min: int,
                            ts_max: int) -> None:
        """Zero-copy ingest of one device-resident [K, B] round (row k =
        key k's tuples, ts ascending within each row, all >= the stream's
        max event time). ``ts_min``/``ts_max`` are host-known bounds that
        keep the host clocks exact without a device sync — the keyed
        analogue of TpuWindowOperator.ingest_device_batch (host→device
        bandwidth must never cap the measured operator throughput)."""
        if not self._built:
            self._build()
        if self._host_met is not None and ts_min < self._host_met:
            raise ValueError("device rounds must be in-order")
        self._host_met = ts_max if self._host_met is None \
            else max(self._host_met, ts_max)
        kern = self._ingest_inorder
        if self._ingest_dense is not None:
            if (ts_max - ts_min) // self._min_grid + 3 <= self._dense_runs:
                kern = self._ingest_dense
        self._state = kern(self._state, ts, vals, valid)

    # -- watermark ---------------------------------------------------------
    def process_watermark_async(self, watermark_ts: int):
        """Dispatch the full watermark program (trigger enumeration, query,
        GC) with NO device→host sync: returns ``(ws[T], we[T], cnt_dev,
        results_dev)`` where the device handles are [K, Tp]-padded. The
        overflow check is deferred — async users call
        :meth:`check_overflow` after a drain."""
        if not self._built:
            self._build()
        self._flush()
        if self._annex_dirty:
            self._state = self._merge(self._state)
            self._annex_dirty = False
        st = self._state

        last_wm = self._last_watermark
        if last_wm == -1:
            last_wm = max(0, watermark_ts - self.max_lateness)

        trig_s, trig_e = [], []
        for w in self.windows:
            s_arr, e_arr = w.trigger_arrays(last_wm, watermark_ts)
            trig_s.append(s_arr)
            trig_e.append(e_arr)
        empty = np.empty(0, dtype=np.int64)
        ws = np.concatenate(trig_s) if trig_s else empty
        we = np.concatenate(trig_e) if trig_e else empty
        T = ws.shape[0]

        cnt_d = results = None
        if T:
            Tp = self.config.trigger_pad(T)
            ws_p = np.zeros((Tp,), np.int64)
            we_p = np.zeros((Tp,), np.int64)
            mask = np.zeros((Tp,), bool)
            ws_p[:T], we_p[:T], mask[:T] = ws, we, True
            cnt_d, results = self._query(st, ws_p, we_p, mask,
                                         np.zeros((Tp,), bool))

        bound = (watermark_ts - self.max_lateness) - self.max_fixed_window_size
        self._state = self._gc(st, np.int64(bound))
        self._last_watermark = watermark_ts
        return ws, we, cnt_d, results

    def lower_results(self, ws, we, cnt_d, results):
        """Fetch + lower one async watermark's handles: (ws, we,
        counts[K, T], lowered per agg [K, T])."""
        T = ws.shape[0]
        cnt_np = np.zeros((self.n_keys, 0), np.int64)
        lowered: List[np.ndarray] = []
        if T:
            import jax

            cnt_h, res_h = jax.device_get((cnt_d, results))
            cnt_np = np.asarray(cnt_h)[:, :T]
            for agg, res in zip(self.aggregations, res_h):
                spec = agg.device_spec()
                r = np.asarray(res)[:, :T, :]          # [K, T, w]
                flat = spec.lower(r.reshape(-1, r.shape[-1]),
                                  cnt_np.reshape(-1))
                lowered.append(np.asarray(flat).reshape(self.n_keys, T))
        return ws, we, cnt_np, lowered

    def check_overflow(self) -> None:
        shaper = getattr(self, "_attached_shaper", None)
        if shaper is not None:
            # a StreamShaper feeding shape_device_round registers here:
            # its sticky row-overflow flag (a key exceeded the round
            # size — tuples were dropped by the scatter) must surface at
            # this drain point, never silently (scotty_tpu.shaper)
            shaper.check()
        if self._state is not None and bool(
                np.any(np.asarray(self._state.overflow))):
            raise RuntimeError("slice buffer overflow on some key shard")

    def process_watermark_arrays(self, watermark_ts: int):
        """Synchronous watermark: (window_starts[T], window_ends[T],
        counts[K, T], lowered per agg [K, T]) — all keys answered by one
        device query, mirroring the connectors' all-keys watermark loop
        (flink-connector KeyedScottyWindowOperator.java:72-86)."""
        out = self.lower_results(*self.process_watermark_async(watermark_ts))
        self.check_overflow()
        return out

    def process_watermark(self, watermark_ts: int):
        """Object results: list of (key, AggregateWindow), non-empty windows
        only — the emit contract of the reference connectors (they collect
        only hasValue results, flink KeyedScottyWindowOperator.java:79-82)."""
        ws, we, cnt, lowered = self.process_watermark_arrays(watermark_ts)
        # vectorized extraction (VERDICT r5 item 7): one nonzero scan over
        # the [K, T] count grid + per-agg fancy-index gathers replace the
        # K×T Python double loop — at 64K keys the dense scan dominated
        # emit when most (key, trigger) cells are empty
        kk_idx, t_idx = np.nonzero(cnt > 0)
        cols = [np.asarray(lw)[kk_idx, t_idx] for lw in lowered]
        ws_nz = ws[t_idx]
        we_nz = we[t_idx]
        out = []
        for j, kk in enumerate(kk_idx.tolist()):
            out.append((kk, AggregateWindow(
                WindowMeasure.Time, int(ws_nz[j]), int(we_nz[j]),
                [c[j] for c in cols], True)))
        return out


class KeyedAlignedPipeline(FusedPipelineDriver):
    """Fused keyed benchmark pipeline: one XLA dispatch per watermark
    interval serving ``n_keys`` independent keyed operators.

    The keyed edition of :class:`..engine.pipeline.AlignedStreamPipeline`:
    each key's paced generator emits R tuples per slice row (the reference's
    per-key constant-rate source after keyBy partitioning), so per-key
    ingest is a dense [K, S, R] row reduction + one contiguous append into
    the [K, C] slice buffers — no scatters — and every key's triggered
    windows are answered by ONE vmapped range query. Per-dispatch overhead
    amortizes over the whole interval instead of over one [K, B] round.

    ``mesh``/``axis``: optional Mesh sharding of the key dimension — the
    program is per-key pointwise, so XLA partitions it collective-free
    (SURVEY.md §2.8 (b)).
    """

    def __init__(self, windows: Sequence, aggregations: Sequence[AggregateFunction],
                 n_keys: int, config: Optional[EngineConfig] = None,
                 throughput: int = 64_000_000, wm_period_ms: int = 1000,
                 max_lateness: int = 1000, seed: int = 0, gc_every: int = 8,
                 max_chunk_elems: int = 1 << 24,
                 value_scale: float = 10_000.0, mesh=None, axis: str = "keys"):
        import jax
        import jax.numpy as jnp

        from ..engine import core as ec
        from ..engine.pipeline import AlignedStreamPipeline, \
            build_trigger_grid

        self.config = config or EngineConfig()
        self.windows = list(windows)
        self.aggregations = list(aggregations)
        self.n_keys = K = int(n_keys)
        self.wm_period_ms = P = wm_period_ms
        self.max_lateness = max_lateness
        self.gc_every = gc_every
        self.seed = seed
        self.mesh, self.axis = mesh, axis
        self.value_scale = float(value_scale)

        max_fixed = 0
        for w in self.windows:
            if w.measure != WindowMeasure.Time or not isinstance(
                    w, (TumblingWindow, SlidingWindow)):
                raise NotImplementedError(
                    "keyed aligned pipeline: time tumbling/sliding only")
            max_fixed = max(max_fixed, w.clear_delay())
        aggs = tuple(a.device_spec() for a in self.aggregations)
        if any(a is None for a in aggs):
            raise NotImplementedError(
                "keyed aligned pipeline: device-realizable aggregations "
                "only")
        g = AlignedStreamPipeline.slice_grid(self.windows, P)
        per_key = throughput // K
        R = per_key * g // 1000
        if R < 1:
            raise NotImplementedError("throughput too low: <1 tuple/slice/key")
        S = P // g
        self.grid, self.R, self.S = g, R, S
        self.max_fixed = max_fixed
        self.tuples_per_interval = K * S * R

        spec = ec.EngineSpec(periods=(g,), bands=(), count_periods=(),
                             aggs=aggs)
        self.spec = spec
        C, A = self.config.capacity, self.config.annex_capacity
        query1 = ec.build_query(spec, C, A)
        gc1 = ec.build_gc(spec, C, A)
        self._gc_kernel = jax.jit(
            jax.vmap(gc1, in_axes=(0, None)), donate_argnums=0)
        make_triggers, self.T = build_trigger_grid(self.windows, P)

        # R-chunking keeps the [K, S, Rc, width] lift temporary bounded
        # (the budget counts LIFTED elements, like the other pipelines;
        # sparse lifts scatter into flat per-row targets — per-lane cost
        # only — so they count as width 1, like the session pipeline)
        max_width = max(1 if a.is_sparse else a.width for a in aggs)
        n_chunks = 1
        while (K * S * (R // n_chunks) * max_width) > max_chunk_elems \
                and n_chunks < R:
            n_chunks += 1
        while R % n_chunks:
            n_chunks += 1
        Rc = R // n_chunks
        self._n_chunks, self._rc = n_chunks, Rc
        first_lw = max(0, P - max_lateness)
        red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}
        #: Pallas segmented-reduce fold for the per-chunk lifts
        #: (EngineConfig.pallas_slice_merge — ROADMAP item 4; default
        #: off keeps the keyed step byte-identical)
        pallas_fold = bool(getattr(self.config, "pallas_slice_merge",
                                   False))
        pallas_packed = pallas_fold and bool(
            getattr(self.config, "pallas_packed", False))
        self._pallas_in_step = pallas_fold

        def gen_vals(kg):
            """[K, S, Rc] generated values. The RNG is the measured
            bottleneck of this pipeline (threefry sustains ~9 G 32-bit
            lanes/s on v5e), so each 32-bit draw yields TWO 16-bit-granular
            values — halving the threefry lanes per tuple. The load
            generator's value distribution stays uniform (65536 levels
            over [0, value_scale)); aggregates are f32 throughout."""
            from ..engine.pipeline import draw_uniform16

            return draw_uniform16(kg, (K, S, Rc), value_scale)

        def step(state, key, interval_idx):
            base = interval_idx * P

            def body(parts_c, c):
                vals = gen_vals(jax.random.fold_in(key, c))
                flat = vals.reshape(-1)                  # [K*S*Rc]
                new_parts = []
                for aspec, acc in zip(aggs, parts_c):
                    if pallas_fold:
                        # Pallas segmented-reduce fold: the [K*S] slice
                        # rows are equal Rc-lane segments by
                        # construction — lane blocks stream HBM→VMEM,
                        # multi-cell sketch lifts densify in VMEM
                        # instead of the flat per-row scatter below
                        from .. import pallas as _spl

                        if aspec.is_sparse:
                            col, v = aspec.lift_sparse(flat)
                            upd = _spl.sparse_row_fold(
                                col, v, K * S, Rc, aspec.width,
                                aspec.kind, aspec.identity).reshape(
                                    K, S, aspec.width)
                        else:
                            upd = _spl.row_fold(
                                aspec.lift_dense(flat), K * S, Rc,
                                aspec.kind, aspec.identity,
                                packed=pallas_packed).reshape(K, S, -1)
                    elif aspec.is_sparse:
                        # flat per-row scatter (the aligned pipeline's
                        # generic sketch fold): one f32 scatter lane per
                        # generated tuple — multi-cell sketches (count-
                        # min) broadcast the [lanes] row ids across their
                        # d cells via advanced indexing
                        col, v = aspec.lift_sparse(flat)
                        row_id = jnp.arange(K * S * Rc,
                                            dtype=jnp.int32) // Rc
                        fi = row_id * aspec.width + col.astype(jnp.int32)
                        tgt = jnp.full((K * S * aspec.width,),
                                       aspec.identity, jnp.float32)
                        if aspec.kind == "sum":
                            tgt = tgt.at[fi].add(v)
                        elif aspec.kind == "min":
                            tgt = tgt.at[fi].min(v)
                        else:
                            tgt = tgt.at[fi].max(v)
                        upd = tgt.reshape(K, S, aspec.width)
                    else:
                        lifted = aspec.lift_dense(flat) \
                            .reshape(K, S, Rc, -1)
                        upd = red[aspec.kind](lifted, axis=2)  # [K, S, w]
                    if aspec.kind == "sum":
                        new_parts.append(acc + upd)
                    elif aspec.kind == "min":
                        new_parts.append(jnp.minimum(acc, upd))
                    else:
                        new_parts.append(jnp.maximum(acc, upd))
                return tuple(new_parts), None

            init = tuple(jnp.full((K, S, a.width), a.identity, jnp.float32)
                         for a in aggs)
            parts, _ = jax.lax.scan(body, init, jnp.arange(n_chunks))

            row_starts = base + g * jnp.arange(S, dtype=jnp.int64)
            # every window edge is a slice edge on the aligned grid, so
            # t_last containment (we > t_last ⟺ we > start) is identical
            # for ANY intra-slice tuple placement — the per-tuple offset
            # stream is unobservable and not generated (it was half the
            # RNG bill); tuples sit at their row start, t_last takes the
            # conservative row bound
            off_lo = jnp.zeros((K, S), jnp.int64)
            off_hi = jnp.full((K, S), g - 1, jnp.int64)
            n = state.n_slices                                   # [K] i32

            def app1(buf, rows, nn):
                idx = (nn,) + (jnp.int32(0),) * (buf.ndim - 1)
                return jax.lax.dynamic_update_slice(
                    buf, rows.astype(buf.dtype), idx)

            # vmapped per-key-index appends: the index vector n is constant
            # across keys, but the K·S scatter lanes this lowers to are
            # three orders of magnitude below the generated-lane count — a
            # shared-scalar-index slab DUS was tried for VERDICT r5 item 7
            # and measured ~30% SLOWER on the CPU backend (dynamic-start
            # slab updates defeat in-place fusion); the keyed cell's emit
            # gap is generation/lift-bound, not append-bound.
            app = jax.vmap(app1)
            rs_k = jnp.broadcast_to(row_starts, (K, S))
            state = state._replace(
                starts=app(state.starts, rs_k, n),
                ends=app(state.ends, rs_k + g, n),
                t_first=app(state.t_first, rs_k + off_lo, n),
                t_last=app(state.t_last, rs_k + off_hi, n),
                c_start=app(state.c_start, state.current_count[:, None]
                            + R * jnp.arange(S, dtype=jnp.int64)[None, :],
                            n),
                counts=app(state.counts,
                           jnp.full((K, S), R, jnp.int64), n),
                partials=tuple(app(p, pr, n)
                               for p, pr in zip(state.partials, parts)),
                n_slices=n + S,
                max_event_time=jnp.maximum(
                    state.max_event_time, rs_k[:, -1] + off_hi[:, -1]),
                current_count=state.current_count + S * R,
                overflow=state.overflow | (n + S > C),
            )
            last_wm = jnp.where(interval_idx > 0, base, jnp.int64(first_lw))
            ws, we, tmask = make_triggers(last_wm, base + P)
            cnt, results = jax.vmap(
                query1, in_axes=(0, None, None, None, None))(
                state, ws, we, tmask, jnp.zeros_like(tmask))
            return state, (ws, we, cnt, results)

        self._step = jax.jit(step, donate_argnums=0)
        self._init_state = lambda: self._broadcast(ec.init_state(spec, C, A))
        self._root = None
        self.state = None
        self._interval = 0

    def _broadcast(self, one):
        import jax
        import jax.numpy as jnp

        st = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.n_keys,) + x.shape), one)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            st = jax.device_put(st, NamedSharding(self.mesh, P(self.axis)))
        return st

    def _init_pipeline_state(self) -> None:
        self.state = self._init_state()

    def _gc(self, bound) -> None:
        self.state = self._gc_kernel(self.state, bound)

    def _sync_anchor(self):
        return self.state.n_slices[0]        # [K]-batched: one key's scalar

    def check_overflow(self) -> None:
        import jax

        if bool(np.any(jax.device_get(self.state.overflow))):
            raise RuntimeError("slice buffer overflow on some key shard")

    def materialize_interval(self, i: int, key_idx: int):
        """Regenerate key ``key_idx``'s tuple stream for interval i on host
        (testing): (vals f32, ts i64), row-major by slice row."""
        import jax
        import jax.numpy as jnp

        if self._root is None:
            self._root = jax.random.PRNGKey(self.seed)
        key = jax.random.fold_in(self._root, i)
        g, S, Rc, P = self.grid, self.S, self._rc, self.wm_period_ms
        vals_all, ts_all = [], []
        for c in range(self._n_chunks):
            kg = jax.random.fold_in(key, jnp.int64(c))
            from ..engine.pipeline import draw_uniform16

            vals = np.asarray(jax.device_get(draw_uniform16(
                kg, (self.n_keys, S, Rc), self.value_scale)[key_idx]))
            row_starts = i * P + g * np.arange(S, dtype=np.int64)
            # tuples sit at their row start (the offset stream is
            # unobservable on the aligned grid and not generated)
            ts = np.broadcast_to(row_starts[:, None], (S, Rc))
            vals_all.append(vals.reshape(-1))
            ts_all.append(ts.reshape(-1))
        return np.concatenate(vals_all), np.concatenate(ts_all)

    def lowered_results_for_key(self, interval_out, key_idx: int) -> list:
        """Fetch + lower one interval's window results for one key."""
        import jax

        ws, we, cnt, results = jax.device_get(interval_out)
        cnt_k = cnt[key_idx]
        rows = []
        lowered = []
        for agg, res in zip(self.aggregations, results):
            spec = agg.device_spec()
            lowered.append(np.asarray(spec.lower(res[key_idx], cnt_k)))
        for i in range(ws.shape[0]):
            if cnt_k[i] > 0:
                rows.append((int(ws[i]), int(we[i]), int(cnt_k[i]),
                             [lw[i] for lw in lowered]))
        return rows

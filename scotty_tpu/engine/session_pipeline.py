"""Fused session-workload pipeline: ONE XLA dispatch per watermark interval
for session windows (optionally mixed with time-grid windows).

TPU-first observation driving the design: per-lane scatter work is the only
ingest cost class that scales with the tuple count (f32 scatters ~6-12 ms
per 1M lanes on v5e, int64 ~15-20× worse — measured, docs/DESIGN.md and
bench_results/micro.json), and every dispatch pays a fixed host
overhead. A session benchmark stream is a
constant-rate generator with occasional SILENT SPANS (the reference's
session-gap mechanism, LoadGeneratorSource.java:60-76): at benchmark rates
the inter-arrival time between consecutive tuples (~µs) never approaches a
session gap (~seconds), so sessions can only break at the injected silent
spans. This pipeline quantizes silent spans to whole watermark intervals,
which makes each live interval's tuples one contiguous chain segment:

* per interval, ONE shared fold per aggregation covers every registered
  session window — a dense reduction for sum-kind lifts, a single [B]-lane
  f32 scatter into the sketch width for sparse lifts (HLL registers,
  DDSketch buckets);
* each session window then updates at most ONE row of its bounded
  active-session array (extend the open session, or close it and open a new
  one when the preceding silence exceeded that window's gap) — the
  in-order specialization of SessionContext.updateContext
  (SessionWindow.java:40-84) at interval granularity;
* completed sessions emit via the shared sweep kernel
  (engine/sessions.py:build_session_sweep — trigger semantics
  SessionWindow.java:107-116);
* time-grid windows in the mix ride the slice-aligned append of
  AlignedStreamPipeline (no scatters at all) over the SAME generated
  tuples; silent intervals append nothing, so grid windows over silence
  emit empty exactly like the reference (empty windows are not emitted).

Generality note: this execution mode covers the benchmark-shaped session
workload (in-order stream, silence-separated sessions). Arbitrary
out-of-order session streams run on TpuWindowOperator's session kernels
(engine/sessions.py late scan) or the host oracle — the decision tree in
hybrid.py.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from .. import jax_config  # noqa: F401
from .. import obs as _obs
from ..obs import flight as _flight

from ..core.aggregates import AggregateFunction
from ..core.windows import (
    SessionWindow,
    SlidingWindow,
    TumblingWindow,
    WindowMeasure,
)
from .config import EngineConfig
from .pipeline import FusedPipelineDriver, build_trigger_grid


class SessionStreamPipeline(FusedPipelineDriver):
    """One fused step per watermark interval for session(-mix) workloads.

    ``session_config``: {"count": N, "minGapMs": a, "maxGapMs": b} — the
    reference benchmark's silent-span parameters (BenchmarkRunner.java:
    174-192). Spans are placed by a seeded schedule over a cyclic horizon
    and quantized to whole intervals (lengths rounded UP, so a span meant
    to exceed a session gap still does).
    """

    _uses_device_metrics = True

    def __init__(self, windows: Sequence, aggregations: Sequence[AggregateFunction],
                 config: Optional[EngineConfig] = None,
                 throughput: int = 32_000_000, wm_period_ms: int = 1000,
                 max_lateness: int = 1000, seed: int = 0,
                 session_config: Optional[dict] = None, gc_every: int = 32,
                 max_chunk_elems: int = 1 << 25,
                 value_scale: float = 10_000.0,
                 collect_device_metrics: bool = True):
        import jax
        import jax.numpy as jnp

        from . import core as ec
        from . import sessions as es
        from ..obs import device as _dev

        self.collect_device_metrics = bool(collect_device_metrics)
        self.config = config or EngineConfig()
        self.windows = list(windows)
        self.aggregations = list(aggregations)
        self.max_lateness = max_lateness
        self.wm_period_ms = wm_period_ms
        self.gc_every = gc_every
        self.seed = seed
        self.value_scale = float(value_scale)

        self.session_windows = [w for w in self.windows
                                if isinstance(w, SessionWindow)]
        grid_windows = [w for w in self.windows
                       if not isinstance(w, SessionWindow)]
        for w in self.session_windows:
            if w.measure != WindowMeasure.Time:
                raise NotImplementedError("count-measure sessions: host only")
        max_fixed = 0
        for w in grid_windows:
            if w.measure != WindowMeasure.Time or not isinstance(
                    w, (TumblingWindow, SlidingWindow)):
                raise NotImplementedError(
                    "session pipeline: time tumbling/sliding mixes only")
            max_fixed = max(max_fixed, w.clear_delay())
        aggs = tuple(a.device_spec() for a in self.aggregations)
        if any(a is None for a in aggs):
            raise NotImplementedError("device-realizable aggregations only")
        if any(a.cells_per_tuple > 1 for a in aggs):
            # the session chain kernel and the one-hot segment reduce both
            # assume one sparse cell per tuple
            raise NotImplementedError(
                "session pipeline: multi-cell sparse aggregations "
                "(count-min) are unsupported; use the time-grid pipelines")

        # ---- generator layout (slice-aligned rows, like the aligned
        # pipeline; for pure-session workloads an artificial row grid keeps
        # intra-interval inter-arrival far below any session gap) ----------
        P = wm_period_ms
        members = [P] + [int(w.size) for w in grid_windows] \
            + [int(w.slide) for w in grid_windows
               if isinstance(w, SlidingWindow)]
        import math

        g = 0
        for m in members:
            g = math.gcd(g, m)
        if self.session_windows:
            min_gap = min(int(w.gap) for w in self.session_windows)
            # row span must stay well under the smallest session gap so
            # rows are never mistaken for silence (inter-arrival <= 2 rows)
            while g > max(1, min_gap // 4):
                for dv in range(2, g + 1):
                    if g % dv == 0:
                        g //= dv
                        break
        R = throughput * g // 1000     # rounded down to whole tuples/row;
                                       # accounting uses the exact S*R
        if R < 1:
            raise NotImplementedError("throughput too low: <1 tuple per row")
        S = P // g
        self.grid, self.R, self.S = g, R, S
        self.tuples_per_interval = S * R

        # ---- silent-span schedule (cyclic, host-precomputed) -------------
        # No session_config → no silent spans (a constant-rate stream; note
        # sessions then never complete — callers route such workloads
        # elsewhere, bench/runner.py Hybrid branch)
        sc = session_config or {"count": 0}
        n_gaps = int(sc.get("count", 8))
        gmin = int(sc.get("minGapMs", 1500))
        gmax = int(sc.get("maxGapMs", 4000))
        rng = np.random.default_rng(seed)
        lens_iv = np.maximum(1, -(-rng.integers(
            gmin, max(gmin + 1, gmax), size=n_gaps) // P))  # ceil → intervals
        # cyclic horizon sized so silence is ~40% of intervals — the
        # reference's pause density in benchmark terms; gap starts random
        horizon = max(16, int(lens_iv.sum() / 0.4) + 1)
        silent = np.zeros(horizon, bool)
        for ln in lens_iv:
            # keep each span's configured length: draw a start that fits
            # before the horizon end instead of truncating there (ADVICE r3);
            # interval 0 stays non-silent so the first interval carries tuples
            hi = max(2, horizon - int(ln) + 1)
            pos = int(rng.integers(1, hi))
            silent[pos:pos + int(ln)] = True
        silent[0] = False
        self._silent = silent
        self._horizon = horizon
        #: timed regions shorter than this may see zero completed sessions
        #: (a session only completes after a silent span)
        self.min_timed_intervals = 16 if self.session_windows else 0
        self.max_fixed = max_fixed

        # ---- kernels ------------------------------------------------------
        # the grid buffer only ever holds rows younger than the GC horizon
        # (widest window + lateness + gc cadence); the query's log-sweep
        # sparse table scales with the BUFFER capacity, so clamping it to
        # the live span (instead of inheriting the generic config default,
        # sized for 60k-window suites) removes almost all query cost on
        # session-mix shapes (r4 — the hll mix cell was sweep-bound)
        need_rows = (max_fixed + max_lateness) // g + S * (gc_every + 2) + 8
        C = min(self.config.capacity,
                1 << max(4, (need_rows - 1).bit_length()))
        A = self.config.annex_capacity
        self.has_grid = bool(grid_windows)
        # pure-session mode anchors the live-SESSION count, whose capacity
        # is the session array's, not config.capacity — the driver's
        # occupancy gauges would misreport headroom, so they stay off there
        self._anchor_is_slices = self.has_grid
        spec = ec.EngineSpec(
            periods=(g,) if self.has_grid else (), bands=(),
            count_periods=(), aggs=aggs)
        self.spec = spec
        if self.has_grid:
            query = ec.build_query(spec, C, A)
            self._gc_kernel = jax.jit(ec.build_gc(spec, C, A),
                                      donate_argnums=0)
            make_triggers, self.T = build_trigger_grid(grid_windows, P)
        self._init_grid = (lambda: ec.init_state(spec, C, A)) \
            if self.has_grid else (lambda: None)
        E = self.config.trigger_pad(1024)
        self._emit_cap = E
        gaps = [int(w.gap) for w in self.session_windows]
        self._gaps = gaps
        # live sessions per window are bounded by open + completed-awaiting-
        # sweep (swept every interval) — a few rows, not the slice-buffer
        # capacity; small arrays keep HBM use and per-sweep gather work tiny
        SC_CAP = min(C, 512)
        sweeps = [es.build_session_sweep(aggs, gp, SC_CAP, E) for gp in gaps]
        self._sc_cap = SC_CAP
        self._init_sessions = lambda: [
            es.init_session_state(aggs, SC_CAP, orphan_capacity=8)
            for _ in gaps]

        # rows per generation chunk (divisor of S within the lift budget).
        # Sparse lifts scatter into flat [d*width] targets — per-lane cost
        # only — so they count as width 1 here; dense lifts materialize
        # [d*R, width].
        max_width = max(1 if a.is_sparse else a.width for a in aggs)
        d = 1
        for cand in range(1, S + 1):
            if S % cand == 0 and cand * R * max_width <= max_chunk_elems:
                d = cand
        n_chunks = S // d
        self._d, self._n_chunks = d, n_chunks
        first_lw = max(0, P - max_lateness)

        # Narrow sparse sketches (HLL's 256 registers) take a sub-batched
        # one-hot segment reduce instead of the flat [B]-lane scatter: the
        # scatter costs ~7 ms per M lanes on v5e regardless of target size
        # (the r3 hll cell's ceiling), while a [q, width] masked reduce is
        # bandwidth/VPU-bound — ~6× cheaper at width<=512 (VERDICT r3
        # item 4). Wide sketches (DDSketch 2048) keep the scatter: their
        # one-hot would blow the traffic up past the scatter cost.
        onehot_q = {}
        for a in aggs:
            if a.is_sparse and a.width <= 512:
                qmax = min(R, max(1, max_chunk_elems // a.width))
                for q in range(qmax, 0, -1):
                    if R % q == 0:
                        break
                if q >= 1024:          # too-small sub-batches can't amortize
                    onehot_q[a.token] = q
        self._onehot_q = onehot_q

        def gen_chunk(key, c):
            """[d, R] values for chunk c. Values take the half-draw block
            layout (two 16-bit values per 32-bit draw — the shared RNG
            cost model, engine/pipeline.half_draw); event times are PACED
            within each slice row (tuple j at offset j·g//R — the
            reference's constant-rate LoadGeneratorSource arrival clock),
            so the per-tuple offset stream costs nothing and the row
            extrema are closed form."""
            from .pipeline import draw_uniform16

            return draw_uniform16(jax.random.fold_in(key, c), (d, R),
                                  value_scale)

        # paced intra-row offsets: first tuple at the row start, last at
        # (R-1)·g//R — deterministic, identical for every row
        off_first = 0
        off_last = ((R - 1) * g) // R

        cdm = self.collect_device_metrics

        def step(grid_state, sess_states, dm, key, interval_idx, live):
            """live: i1 scalar — False = silent interval (no tuples)."""
            base = interval_idx * P
            wm = base + P
            if cdm:
                dm = dm._replace(
                    ingested=dm.ingested
                    + jnp.where(live, jnp.int64(S * R), 0),
                    silent_intervals=dm.silent_intervals
                    + jnp.where(live, 0, jnp.int64(1)),
                    slices_touched=dm.slices_touched + jnp.where(
                        live,
                        jnp.int64((S if self.has_grid else 0) + len(gaps)),
                        0))

            def gen_and_fold(_):
                def body(carry, c):
                    vals = gen_chunk(key, c)
                    flat = vals.reshape(-1)
                    parts = []
                    for aspec in spec.aggs:
                        red = {"sum": jnp.sum, "min": jnp.min,
                               "max": jnp.max}[aspec.kind]
                        if aspec.is_sparse \
                                and aspec.token in onehot_q:
                            # sub-batched one-hot segment reduce (see the
                            # strategy note in __init__): q tuples at a
                            # time, [q, width] masked reduce, one-row
                            # combine into the [d, width] row partials
                            q = onehot_q[aspec.token]
                            per_row = R // q
                            ident = jnp.asarray(aspec.identity,
                                                jnp.float32)

                            def sub(acc, j, _a=aspec, _q=q, _pr=per_row,
                                    _ident=ident, _flat=flat):
                                seg = jax.lax.dynamic_slice(
                                    _flat, (j * _q,), (_q,))
                                col, v = _a.lift_sparse(seg)
                                oh = col[:, None] == jnp.arange(
                                    _a.width, dtype=col.dtype)[None, :]
                                row = j // _pr
                                if _a.kind == "sum":
                                    upd = jnp.sum(
                                        jnp.where(oh, v[:, None], 0),
                                        axis=0)
                                    return acc.at[row].add(upd), None
                                # min/max sketch values are small exact
                                # integers (HLL rho <= 32): the [q, width]
                                # masked reduce runs in bf16 — half the
                                # VPU/HBM traffic of f32, no precision loss
                                vb = v.astype(jnp.bfloat16)
                                ib = _ident.astype(jnp.bfloat16)
                                if _a.kind == "min":
                                    upd = jnp.min(
                                        jnp.where(oh, vb[:, None], ib),
                                        axis=0).astype(jnp.float32)
                                    return acc.at[row].min(upd), None
                                upd = jnp.max(
                                    jnp.where(oh, vb[:, None], ib),
                                    axis=0).astype(jnp.float32)
                                return acc.at[row].max(upd), None

                            init_pr = jnp.full((d, aspec.width),
                                               aspec.identity, jnp.float32)
                            pr, _ = jax.lax.scan(
                                sub, init_pr,
                                jnp.arange((d * R) // q, dtype=jnp.int32))
                        elif aspec.is_sparse:
                            # per-row sketch partials via ONE flat [B]-lane
                            # f32 scatter (never a dense [B, width] lift)
                            col, v = aspec.lift_sparse(flat)
                            row_id = jnp.arange(
                                d * R, dtype=jnp.int32) // R
                            fi = row_id * aspec.width \
                                + col.astype(jnp.int32)
                            tgt = jnp.full((d * aspec.width,),
                                           aspec.identity, jnp.float32)
                            if aspec.kind == "sum":
                                tgt = tgt.at[fi].add(v)
                            elif aspec.kind == "min":
                                tgt = tgt.at[fi].min(v)
                            else:
                                tgt = tgt.at[fi].max(v)
                            pr = tgt.reshape(d, aspec.width)
                        else:
                            lifted = aspec.lift_dense(flat).reshape(d, R, -1)
                            pr = red(lifted, axis=1)              # [d, w]
                        parts.append(pr)
                    return carry, tuple(parts)

                _, parts = jax.lax.scan(
                    body, None, jnp.arange(n_chunks))
                # the interval-wide fold shared by every session window
                # derives from the STACKED row partials ([n_chunks, d, w]
                # — tiny), never from the lifted lanes: a second consumer
                # of the [q, width] one-hot producer makes XLA DUPLICATE
                # it into both fusions, doubling the step's flops
                # (measured 9.1 -> 17.7 GFLOP, 44 -> 74 ms on the hll
                # mix cell — the r4 'mix at half the pure-session rate'
                # mystery, VERDICT r4 weak #3)
                comb = []
                for aspec, pstack in zip(spec.aggs, parts):
                    red = {"sum": jnp.sum, "min": jnp.min,
                           "max": jnp.max}[aspec.kind]
                    comb.append(red(pstack, axis=(0, 1)))
                comb = tuple(comb)
                return comb, parts

            def no_fold(_):
                comb = tuple(jnp.full((a.width,), a.identity, jnp.float32)
                             for a in spec.aggs)
                parts = tuple(jnp.full((S // d, d, a.width), a.identity,
                                       jnp.float32) for a in spec.aggs)
                return comb, parts

            comb, parts = jax.lax.cond(live, gen_and_fold, no_fold, None)
            row_starts = base + g * jnp.arange(S, dtype=jnp.int64)
            t_first_iv = base + off_first          # first tuple ts (paced)
            t_last_iv = base + (S - 1) * g + off_last
            n_tuples = jnp.where(live, jnp.int64(S * R), 0)

            # ---- grid append (aligned, zero-scatter) ---------------------
            if self.has_grid:
                st = grid_state
                n = st.n_slices

                def app(buf, rows):
                    idx = (n,) + (jnp.int32(0),) * (buf.ndim - 1)
                    return jax.lax.dynamic_update_slice(
                        buf, rows.astype(buf.dtype), idx)

                appended = st._replace(
                    starts=app(st.starts, row_starts),
                    ends=app(st.ends, row_starts + g),
                    t_first=app(st.t_first, row_starts + off_first),
                    t_last=app(st.t_last, row_starts + off_last),
                    c_start=app(st.c_start, st.current_count
                                + R * jnp.arange(S, dtype=jnp.int64)),
                    counts=app(st.counts, jnp.full((S,), R, jnp.int64)),
                    partials=tuple(
                        app(p, pr.reshape(S, -1))
                        for p, pr in zip(st.partials, parts)),
                    n_slices=n + S,
                    max_event_time=jnp.maximum(st.max_event_time, t_last_iv),
                    current_count=st.current_count + S * R,
                    overflow=st.overflow | (n + S > C),
                )
                grid_state = jax.tree.map(
                    lambda a, b: jnp.where(live, a, b), appended, st)
                last_wm = jnp.where(interval_idx > 0, base,
                                    jnp.int64(first_lw))
                ws, we, tmask = make_triggers(last_wm, wm)
                cnt, results = query(grid_state, ws, we, tmask,
                                     jnp.zeros_like(tmask))
            else:
                ws = jnp.zeros((0,), jnp.int64)
                we = jnp.zeros((0,), jnp.int64)
                cnt = jnp.zeros((0,), jnp.int64)
                results = tuple(jnp.zeros((0, a.width), jnp.float32)
                                for a in spec.aggs)

            if cdm and self.has_grid:
                dm = dm._replace(
                    triggers=dm.triggers + jnp.sum(tmask),
                    windows_nonempty=dm.windows_nonempty
                    + jnp.sum(tmask & (cnt > 0)))
                dm = _dev.record_occupancy(dm, grid_state.n_slices, C)

            # ---- session updates: at most one row per window -------------
            new_states = []
            ws_parts, we_parts, cnt_parts = [ws], [we], [cnt]
            res_parts = [results]
            for gap, sweep, sst in zip(gaps, sweeps, sess_states):
                n_s = sst.n
                open_last = jnp.where(
                    n_s > 0, sst.last[jnp.maximum(n_s - 1, 0)],
                    jnp.int64(-(1 << 62)))
                chain = live & (n_s > 0) & (t_first_iv - open_last <= gap)
                fresh = live & ~chain
                row = jnp.where(chain, n_s - 1, n_s).astype(jnp.int32)
                upd = jnp.where(live, row, SC_CAP)   # out of range = drop
                first = sst.first.at[upd].min(
                    jnp.where(live, t_first_iv, 1 << 62), mode="drop")
                last = sst.last.at[upd].max(
                    jnp.where(live, t_last_iv, -(1 << 62)), mode="drop")
                counts = sst.counts.at[upd].add(n_tuples, mode="drop")
                partials = []
                for aspec, part, fv in zip(spec.aggs, sst.partials, comb):
                    fv = jnp.where(live, fv, jnp.asarray(
                        aspec.identity, jnp.float32))
                    if aspec.kind == "sum":
                        part = part.at[upd].add(fv, mode="drop")
                    elif aspec.kind == "min":
                        part = part.at[upd].min(fv, mode="drop")
                    else:
                        part = part.at[upd].max(fv, mode="drop")
                    partials.append(part)
                sst = sst._replace(
                    first=first, last=last, counts=counts,
                    partials=tuple(partials),
                    n=(n_s + jnp.where(fresh, 1, 0)).astype(jnp.int32),
                    overflow=sst.overflow | (fresh & (n_s >= SC_CAP)))
                sst, m, e_s, e_e, e_c, e_p = sweep(
                    sst, jnp.int64(wm), jnp.int64(wm - max_lateness))
                new_states.append(sst)
                ws_parts.append(e_s)
                we_parts.append(e_e)
                cnt_parts.append(e_c)
                res_parts.append(e_p)
                if cdm:
                    # every completed session is both a trigger and a
                    # non-empty window (empty sessions don't exist)
                    m64 = jnp.asarray(m, jnp.int64)
                    dm = dm._replace(
                        triggers=dm.triggers + m64,
                        windows_nonempty=dm.windows_nonempty + m64)

            out = (jnp.concatenate(ws_parts), jnp.concatenate(we_parts),
                   jnp.concatenate(cnt_parts),
                   tuple(jnp.concatenate([r[i] for r in res_parts])
                         for i in range(len(spec.aggs))))
            return grid_state, new_states, dm, out

        self._step = jax.jit(step, donate_argnums=(0, 1, 2)) \
            if self.has_grid else jax.jit(step, donate_argnums=(1, 2))
        self._root = None
        self.state = None
        self.sess_states = None
        self._interval = 0

    # -- driver-facing interface (FusedPipelineDriver hooks) ---------------
    def _init_pipeline_state(self) -> None:
        self.state = self._init_grid()
        self.sess_states = self._init_sessions()

    def _step_interval(self, key, i: int):
        import jax

        # explicit device_put of the per-interval scalars — the one
        # sanctioned h2d upload under the differential tests'
        # jax.transfer_guard("disallow") (same avals: HLO unchanged,
        # pinned by tests/hlo_pins.json)
        iv, live = jax.device_put((np.int64(i),
                                   np.bool_(self.live(i))))
        self.state, self.sess_states, self.dm, res = self._step(
            self.state, self.sess_states, self.dm, key, iv, live)
        return res

    def _gc(self, bound) -> None:
        if self.has_grid:
            self.state = self._gc_kernel(self.state, bound)

    def _sync_anchor(self):
        return self.state.n_slices if self.has_grid \
            else self.sess_states[0].n

    def live(self, i: int) -> bool:
        return not bool(self._silent[i % self._horizon])

    def _interval_tuples(self, i: int) -> int:
        """Telemetry: silent intervals carry no tuples — counting them at
        the flat per-interval rate would overstate ``ingest_tuples`` by
        the silence fraction; count them (``silent_intervals``) instead."""
        if not self.live(i):
            if self.obs is not None:
                self.obs.counter(_obs.SILENT_INTERVALS).inc()
            return 0
        return int(self.tuples_per_interval)

    def tuples_in_range(self, i0: int, i1: int) -> int:
        return sum(self.tuples_per_interval
                   for i in range(i0, i1) if self.live(i))

    def check_overflow(self) -> None:
        import jax

        flags = [s.overflow for s in self.sess_states]
        if self.has_grid:
            flags.append(self.state.overflow)
        if any(bool(v) for v in jax.device_get(flags)):
            e = RuntimeError(
                "slice/session buffer overflow: raise capacity. (GROW's "
                "occupancy trigger watches the slice anchor only, so "
                "session-row pressure on this pipeline cannot be "
                "prevented by overflow_policy='grow'; a raised flag is "
                "unrecoverable under any policy)")
            if self.obs is not None:
                self.obs.counter(_obs.OVERFLOWS).inc()
                self.obs.record_failure(e, kind=_flight.OVERFLOW,
                                        config=self.config)
            raise e

    def materialize_interval(self, i: int):
        """Regenerate interval i's tuple stream on host (testing): returns
        (vals f32, ts i64), row-major by slice row — EMPTY for silent
        intervals. Bit-identical to the device generator."""
        import jax
        import jax.numpy as jnp

        if not self.live(i):
            return np.empty(0, np.float32), np.empty(0, np.int64)
        if self._root is None:
            self._root = jax.random.PRNGKey(self.seed)
        key = jax.random.fold_in(self._root, i)
        g, d, R, P = self.grid, self._d, self.R, self.wm_period_ms
        from .pipeline import draw_uniform16

        vals_all, ts_all = [], []
        paced = (np.arange(R, dtype=np.int64) * g) // R
        for c in range(self._n_chunks):
            kg = jax.random.fold_in(key, jnp.int64(c))
            vals = np.asarray(jax.device_get(draw_uniform16(
                kg, (d, R), self.value_scale)))
            row_starts = (i * P + g * (c * d + np.arange(d, dtype=np.int64)))
            # paced intra-row event times (see gen_chunk)
            ts = row_starts[:, None] + paced[None, :]
            vals_all.append(vals.reshape(-1))
            ts_all.append(ts.reshape(-1))
        return np.concatenate(vals_all), np.concatenate(ts_all)

    def lowered_results(self, interval_out) -> list:
        from .pipeline import lower_interval

        return lower_interval(self.aggregations, interval_out)

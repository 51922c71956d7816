"""Device data model + kernels of the TPU slicing engine.

This is the TPU-first re-design of the reference's slicing hot paths
(slicing/.../StreamSlicer.java:36-86, SliceManager.java:47-87,
LazyAggregateStore.java:83-111 — see SURVEY.md §3.1/§3.3):

* The slice store is a **sorted linear buffer in HBM** with static capacity:
  ``starts[C]`` (slice start edges, ascending, LONG_MAX-padded), per-slice
  record counts, observed ts extents, and one fixed-width partial-aggregate
  matrix ``partials[C, width]`` per registered aggregation.

* **Ingest** processes a whole batch of tuples in one fused kernel: each
  tuple's slice start is the latest window-grid point ≤ its timestamp
  (closed-form over all registered context-free windows — the vectorized
  equivalent of the reference's ``assignNextWindowStart`` min-loop,
  StreamSlicer.java:103-116); segment boundaries fall where that grid start
  changes; partial aggregates fold in via duplicate-index scatter-combine
  (the associativity of ``combine`` is the license, AggregateFunction.java:19-34).
  Empty grid ranges are *not* materialized — an absent slice contributes the
  combine identity, which is exactly what the reference's empty slices
  contribute (LazyAggregateStore.java:83-111 merges nothing from them).

* **Window results** replace the reference's O(#slices × #windows) nested
  final-merge loop with range queries over the sorted buffer: a window
  [ws, we) covers exactly the slices with ``ws <= start < we`` (slice edges
  are window-grid points, so slices never straddle a window boundary), hence

  - sum-like aggregations (sum/count/mean/DDSketch histograms) answer all
    triggered windows at once from one prefix-sum: ``P[hi] - P[lo]``;
  - min/max-like aggregations (min/max/HLL registers) use a log-sweep
    sparse-table: L = log2(C) doubling levels, each window answered at its
    level with two gathers.

* **GC** (WindowManager.clearAfterWatermark, WindowManager.java:82-95) is a
  masked roll of the buffer.

Out-of-order tuples within ``max_lateness`` need no edge repair for
context-free windows (Shift/Add/Delete modifications only originate from
context-aware windows — WindowContext.java:19-63): a late tuple folds into
the existing covering slice (scatter-combine), or — when its grid range was
never materialized — into a small unsorted *annex* that is merged into the
main buffer at the next watermark.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Sequence

import numpy as np

import jax

from .. import jax_config  # noqa: F401  (x64 + compile cache, import-order safe)

import jax.numpy as jnp

from ..core.aggregates import DeviceAggregateSpec
from ..core.windows import LONG_MAX

I64_MAX = np.int64(LONG_MAX)
I64_MIN = np.int64(-(1 << 62))  # headroom so comparisons can't overflow


# ---------------------------------------------------------------------------
# Static spec
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class EngineSpec:
    """Trace-time-static description of the registered windows/aggregations.

    ``periods``: slide/size of every time-measure tumbling/sliding window —
    their union grid defines the fixed slice edges (StreamSlicer.java:103-116).
    ``bands``: (start, size) of time-measure fixed-band windows (their two
    one-shot edges, FixedBandWindow.java:36-48).
    ``count_periods``: count-measure window grids (StreamSlicer.java:88-101).
    ``aggs``: device realization of each aggregation, in registration order.
    ``session_gaps``: gaps of session windows (pure-session device path).
    """

    periods: tuple[int, ...]
    bands: tuple[tuple[int, int], ...]
    count_periods: tuple[int, ...]
    aggs: tuple[DeviceAggregateSpec, ...]
    session_gaps: tuple[int, ...] = ()
    #: (period, offset) residue grids: window END edges of sliding windows
    #: whose size is not a multiple of their slide land at
    #: k*slide + (size % slide) — off the slide grid. Adding these edges to
    #: the slice grid keeps every window boundary on a slice edge, so range
    #: queries are EXACT. Deliberate deviation from the reference, which
    #: slices on the slide grid only and silently DROPS the straddling
    #: slice's in-window tuples (AggregateWindowState.java:25-31 t_last
    #: containment) — see VERDICT r1 item 6.
    offset_periods: tuple[tuple[int, int], ...] = ()

    @property
    def has_time_grid(self) -> bool:
        return bool(self.periods or self.bands or self.offset_periods)

    @property
    def pure_session(self) -> bool:
        return bool(self.session_gaps) and not self.has_time_grid \
            and not self.count_periods


def collapse_periods(periods) -> tuple:
    """Many-window grids: slicing on the union of N period grids costs N
    int64 mods per tuple (emulated int64 makes this the per-tuple hot cost
    at e.g. 1000 random tumbling windows). The GCD grid is a SUPERSET of
    every period grid — every window edge is a multiple of its period,
    hence of the gcd — so slicing on it alone is exactly as correct (finer
    slices, same range-query answers). Collapse when the period count is
    large; keep the union for few windows (their union grid is sparser
    than the gcd's, fewer slices)."""
    import math

    ps = tuple(sorted(set(int(p) for p in periods)))
    if len(ps) <= 32:
        return ps
    g = 0
    for p in ps:
        g = math.gcd(g, p)
    return (max(1, g),)


def grid_start(spec: EngineSpec, ts: jnp.ndarray) -> jnp.ndarray:
    """Latest union-grid point ≤ ts (vectorized; [B] -> [B]).

    Equivalent to the latest slice edge the reference would have placed at or
    before ts. Clamped to ≥ 0 to mirror the reference's initial slice at 0
    (SliceManager.java empty-store bootstrap) — device streams use ts ≥ 0.
    """
    cands = [jnp.zeros_like(ts)]
    if spec.periods:
        # chunk the period axis so [B, K] temporaries stay bounded when many
        # concurrent windows are registered (e.g. 1000 random tumbling sizes)
        pall = np.asarray(sorted(set(spec.periods)), dtype=np.int64)
        for i in range(0, len(pall), 128):
            p = jnp.asarray(pall[i:i + 128])
            cands.append(jnp.max(ts[:, None] - jnp.mod(ts[:, None], p[None, :]),
                                 axis=1))
    for (p, r) in spec.offset_periods:
        # largest point ≤ ts congruent to r (mod p), clamped to ≥ 0
        cands.append(jnp.maximum(ts - jnp.mod(ts - r, p), 0))
    for (bs, bsz) in spec.bands:
        c = jnp.where(ts >= bs + bsz, jnp.int64(bs + bsz),
                      jnp.where(ts >= bs, jnp.int64(bs), jnp.int64(0)))
        cands.append(c)
    if spec.session_gaps:
        # session slice edges are data-dependent; handled by the session path
        pass
    return functools.reduce(jnp.maximum, cands)


def host_grid_start(spec: EngineSpec, ts: np.ndarray) -> np.ndarray:
    """Numpy mirror of :func:`grid_start` for host-side cut calculus
    (the out-of-order count+time mixed path precomputes per-lane slice
    assignments in arrival order — see operator._mixed_cut_calculus)."""
    ts = np.asarray(ts, dtype=np.int64)
    best = np.zeros_like(ts)
    for p in spec.periods:
        np.maximum(best, ts - ts % np.int64(p), out=best)
    for (p, r) in spec.offset_periods:
        np.maximum(best, np.maximum(ts - (ts - r) % np.int64(p), 0),
                   out=best)
    for (bs, bsz) in spec.bands:
        c = np.where(ts >= bs + bsz, np.int64(bs + bsz),
                     np.where(ts >= bs, np.int64(bs), np.int64(0)))
        np.maximum(best, c, out=best)
    return best


def host_count_grid(spec: EngineSpec, c: np.ndarray) -> np.ndarray:
    """Numpy mirror of the ingest kernel's count-grid function ``cgs``."""
    c2 = np.maximum(np.asarray(c, dtype=np.int64), 0)
    best = np.zeros_like(c2)
    for p in spec.count_periods:
        np.maximum(best, c2 - c2 % np.int64(p), out=best)
    return best


def next_edge(spec: EngineSpec, s: jnp.ndarray) -> jnp.ndarray:
    """Earliest union-grid point strictly > s — the closing edge of a slice
    opened at s (SliceManager.appendSlice end bookkeeping)."""
    cands = [jnp.full_like(s, I64_MAX)]
    if spec.periods:
        pall = np.asarray(sorted(set(spec.periods)), dtype=np.int64)
        for i in range(0, len(pall), 128):
            p = jnp.asarray(pall[i:i + 128])
            cands.append(jnp.min(s[:, None] - jnp.mod(s[:, None], p[None, :])
                                 + p[None, :], axis=1))
    for (p, r) in spec.offset_periods:
        # smallest point > s congruent to r (mod p)
        cands.append(s + p - jnp.mod(s - r, p))
    for (bs, bsz) in spec.bands:
        for pt in (bs, bs + bsz):
            c = jnp.where(s < pt, jnp.int64(pt), I64_MAX)
            cands.append(c)
    return functools.reduce(jnp.minimum, cands)


# ---------------------------------------------------------------------------
# Device state
# ---------------------------------------------------------------------------


class SliceBufferState(NamedTuple):
    """The slice store as a pytree of device arrays (one key shard).

    Sorted main buffer [C] + unsorted out-of-order annex [A]; scalar clocks
    mirror WindowManager/StreamSlicer bookkeeping (WindowManager.java:16-33,
    StreamSlicer.java:27-34).
    """

    starts: jnp.ndarray        # i64[C] slice start edge; LONG_MAX = unused
    ends: jnp.ndarray          # i64[C] closing grid edge (informational)
    t_first: jnp.ndarray       # i64[C] min observed record ts
    t_last: jnp.ndarray        # i64[C] max observed record ts
    c_start: jnp.ndarray       # i64[C] arrival index of first record (count measure)
    counts: jnp.ndarray        # i64[C] records per slice
    partials: tuple            # per agg: f32[C, width]
    ax_starts: jnp.ndarray     # i64[A] annex slice starts (unsorted)
    ax_counts: jnp.ndarray     # i64[A]
    ax_partials: tuple         # per agg: f32[A, width]
    n_slices: jnp.ndarray      # i32 scalar
    n_annex: jnp.ndarray       # i32 scalar
    max_event_time: jnp.ndarray  # i64 scalar
    current_count: jnp.ndarray   # i64 scalar
    overflow: jnp.ndarray        # bool scalar — capacity exhausted


def init_state(spec: EngineSpec, capacity: int, annex_capacity: int,
               dtype=jnp.float32) -> SliceBufferState:
    C, A = capacity, annex_capacity
    return SliceBufferState(
        starts=jnp.full((C,), I64_MAX, dtype=jnp.int64),
        ends=jnp.full((C,), I64_MAX, dtype=jnp.int64),
        t_first=jnp.full((C,), I64_MAX, dtype=jnp.int64),
        t_last=jnp.full((C,), I64_MIN, dtype=jnp.int64),
        c_start=jnp.full((C,), I64_MAX, dtype=jnp.int64),
        counts=jnp.zeros((C,), dtype=jnp.int64),
        partials=tuple(jnp.full((C, a.width), a.identity, dtype=dtype)
                       for a in spec.aggs),
        ax_starts=jnp.full((A,), I64_MAX, dtype=jnp.int64),
        ax_counts=jnp.zeros((A,), dtype=jnp.int64),
        ax_partials=tuple(jnp.full((A, a.width), a.identity, dtype=dtype)
                          for a in spec.aggs),
        n_slices=jnp.int32(0),
        n_annex=jnp.int32(0),
        max_event_time=jnp.int64(I64_MIN),
        current_count=jnp.int64(0),
        overflow=jnp.bool_(False),
    )


def _combine_scatter(arr: jnp.ndarray, pos: jnp.ndarray, vals: jnp.ndarray,
                     kind: str) -> jnp.ndarray:
    """Duplicate-index scatter with the aggregation's combine — this IS the
    in-slice fold of AggregateValueState.addElement (AggregateValueState.java:23-31),
    batched."""
    if kind == "sum":
        return arr.at[pos].add(vals)
    if kind == "min":
        return arr.at[pos].min(vals)
    if kind == "max":
        return arr.at[pos].max(vals)
    raise ValueError(f"unknown combine kind {kind!r}")


def _lift(agg: DeviceAggregateSpec, vals: jnp.ndarray, valid: jnp.ndarray):
    """Apply the aggregation's vectorized lift, masking padded lanes to the
    combine identity. Returns (dense[B, w], None) or (None, (col[B], val[B]))."""
    if agg.is_sparse:
        col, v = agg.lift_sparse(vals)
        v = jnp.where(valid, v, agg.identity)
        return None, (col, v)
    lifted = agg.lift_dense(vals)
    lifted = jnp.where(valid[:, None], lifted, agg.identity)
    return lifted, None


# ---------------------------------------------------------------------------
# Ingest kernel
# ---------------------------------------------------------------------------


def build_ingest(spec: EngineSpec, capacity: int, annex_capacity: int,
                 assume_inorder: bool = False,
                 with_cut_starts: bool = False):
    """Batched in-order + late-tuple ingest.

    Replaces the per-tuple hot loop StreamSlicer.determineSlices →
    SliceManager.processElement (SURVEY.md §3.1) with one fused device
    program over a [B] batch. Requirements: ``ts`` ascending within the batch
    (the host driver sorts when out-of-order is enabled) and every ts within
    ``max_lateness`` of the stream's max event time (reference contract,
    WindowOperator.java:31-37).

    ``assume_inorder=True`` compiles out the late/annex machinery — for
    callers that guarantee a fully ascending stream (e.g. the fused pipeline
    whose device generator is ascending by construction).

    ``with_cut_starts=True`` (count-measure workloads) adds a fifth input:
    per-lane count-cut slice starts precomputed by the host in ARRIVAL
    order (``max(met, arrival_ts[0..j-1])`` for the lane cutting at count
    offset ``j``) — the reference appends count-cut slices at its
    arrival-order ``maxEventTime`` (StreamSlicer.java:37-44), which a
    ts-sorted batch cannot reconstruct on device.
    """
    C, A = capacity, annex_capacity

    def ingest(state: SliceBufferState, ts: jnp.ndarray, vals: jnp.ndarray,
               valid: jnp.ndarray,
               cut_starts: jnp.ndarray = None) -> SliceBufferState:
        B = ts.shape[0]
        s = grid_start(spec, ts)

        n = state.n_slices
        open_start = jnp.where(
            n > 0, state.starts[jnp.maximum(n - 1, 0)], jnp.int64(I64_MIN))

        # ---- split batch: in-order tail vs late tuples -------------------
        # The reference's in-order predicate: te >= maxEventTime
        # (StreamSlicer.java:139-141). The host driver ts-sorts each batch,
        # so late tuples form a prefix relative to the stream's max event
        # time at batch entry. A late tuple with ts >= open_start folds into
        # the OPEN slice (the reference's covering-slice insert,
        # SliceManager.java:64-76) — comparing on ts, not grid_start(ts),
        # matters after a dynamic window addition where the open slice is
        # coarser than the current union grid (grid_start(ts) can exceed
        # open_start while ts sits inside the open slice's span; opening a
        # new slice there would interleave slice spans and break the
        # t_last sort order the query's containment bound relies on).
        if assume_inorder:
            late = jnp.zeros_like(valid)
            pin = jnp.zeros_like(valid)
        else:
            behind = valid & (ts < state.max_event_time)
            late = behind & (ts < open_start)
            pin = behind & ~late

        # ---- count-measure edges (StreamSlicer.java:37-44,88-101) --------
        # Arrival index of each tuple (count before insertion); a count edge
        # is cut when the latest count-grid point changes between consecutive
        # arrivals. The new slice starts at the cutting tuple's event ts —
        # the reference starts count-cut slices at maxEventTime.
        c_idx = (state.current_count
                 + jnp.cumsum(valid.astype(jnp.int64)) - valid)
        if spec.count_periods:
            cp = jnp.asarray(np.asarray(spec.count_periods, dtype=np.int64))

            def cgs(c):
                c2 = jnp.maximum(c, 0)
                return jnp.max(c2[:, None] - jnp.mod(c2[:, None], cp[None, :]),
                               axis=1)

            count_flag = valid & (c_idx > 0) & (cgs(c_idx) > cgs(c_idx - 1))
        else:
            count_flag = jnp.zeros_like(valid)

        # ---- in-order segment path (SURVEY.md §3.1) ----------------------
        # A count-cut slice starts at the PREVIOUS max event time — the
        # reference appends it at maxEventTime before updating it
        # (StreamSlicer.java:37-44,84-85); a same-tuple time edge may push
        # the start further (the intermediate slice would be empty).
        prev_ts = jnp.concatenate(
            [jnp.where(state.max_event_time == I64_MIN, ts[:1],
                       state.max_event_time[None]), ts[:-1]])
        if spec.pure_session:
            # pure-session slicing (eager session case,
            # SliceFactory.java:17-22): a new slice — which IS a session —
            # opens when the inter-arrival gap exceeds the session gap
            # (SessionContext.updateContext, SessionWindow.java:40-84,
            # in-order specialization). Slice start = first tuple's ts.
            gap = jnp.int64(spec.session_gaps[0])
            first_ever = (jnp.arange(B) == 0) & (n == 0)
            newflag = valid & (first_ever | (ts - prev_ts > gap))
            io_s = ts
            k = jnp.cumsum(newflag.astype(jnp.int32))
            pos = jnp.clip((n - 1) + k, 0, C - 1)
            overflow = state.overflow | (((n - 1) + k[-1]) >= C)
            io_valid = valid
            one = jnp.where(io_valid, jnp.int64(1), jnp.int64(0))
            starts = state.starts.at[pos].min(jnp.where(valid, io_s, I64_MAX))
            ends = state.ends
            counts = state.counts.at[pos].add(one)
            t_last = state.t_last.at[pos].max(
                jnp.where(io_valid, ts, I64_MIN))
            t_first = state.t_first.at[pos].min(
                jnp.where(io_valid, ts, I64_MAX))
            c_start = state.c_start.at[pos].min(
                jnp.where(io_valid, c_idx, I64_MAX))
            partials = []
            for agg, part in zip(spec.aggs, state.partials):
                dense, sparse = _lift(agg, vals, io_valid)
                if sparse is None:
                    part = _combine_scatter(part, pos, dense, agg.kind)
                else:
                    col, v = sparse
                    part = _combine_scatter(part, (pos, col), v, agg.kind)
                partials.append(part)
            return state._replace(
                starts=starts, ends=ends, t_first=t_first, t_last=t_last,
                c_start=c_start, counts=counts, partials=tuple(partials),
                n_slices=(n + k[-1]).astype(jnp.int32),
                max_event_time=jnp.maximum(
                    state.max_event_time,
                    jnp.max(jnp.where(valid, ts, I64_MIN))),
                current_count=state.current_count
                + jnp.sum(valid.astype(jnp.int64)),
                overflow=overflow,
            )
        # late AND pinned lanes anchored to the open slice: late lanes so
        # they never trigger a spurious edge (they're io_valid-masked),
        # pinned lanes because they genuinely insert there
        io_s = jnp.where(late | pin, open_start, s)
        # count-cut slices start at the RUNNING MAX event time (the
        # reference appends at maxEventTime, StreamSlicer.java:37-44): a
        # raw prev_ts would place a cut fired by a late lane BELOW earlier
        # starts and break the sorted-starts invariant the probe/GC
        # searchsorted on. For in-order batches cummax(prev_ts) == prev_ts;
        # disordered count batches pass exact arrival-order cut starts.
        run_max = cut_starts if with_cut_starts else jax.lax.cummax(prev_ts)
        io_s = jnp.where(count_flag & ~late, jnp.maximum(io_s, run_max),
                         io_s)
        prev = jnp.concatenate([open_start[None], io_s[:-1]])
        newflag = ((io_s > prev) | (count_flag & ~late)) & valid
        k = jnp.cumsum(newflag.astype(jnp.int32))
        pos = jnp.clip((n - 1) + k, 0, C - 1)
        overflow = state.overflow | (((n - 1) + k[-1]) >= C)

        io_valid = valid & ~late
        one = jnp.where(io_valid, jnp.int64(1), jnp.int64(0))
        if spec.count_periods and not spec.has_time_grid:
            # pure-count slices: only count-cutting lanes (and the stream's
            # first tuple, matching the reference's bootstrap-at-first-ts)
            # define a slice start. Non-cut lanes carry grid_start(ts) == 0,
            # and min-scattering that into the open slice would zero every
            # start — breaking the ts-based GC bound and watermark probe.
            first_lane = (jnp.arange(B) == 0) & (n == 0)
            start_val = jnp.where(count_flag & ~late, io_s,
                                  jnp.where(first_lane, ts, I64_MAX))
        else:
            start_val = io_s
        starts = state.starts.at[pos].min(
            jnp.where(valid, start_val, I64_MAX))
        # pinned lanes don't define a new slice: keep the open slice's
        # closing edge as recorded at creation (post-dynamic-addition it is
        # coarser than next_edge under the current union grid)
        ends = state.ends.at[pos].min(
            jnp.where(valid & ~pin & ~late, next_edge(spec, io_s), I64_MAX))
        counts = state.counts.at[pos].add(one)
        t_last = state.t_last.at[pos].max(jnp.where(io_valid, ts, I64_MIN))
        # int64 scatters cost ~100 ms per 1M lanes on v5e — only maintain
        # the fields something reads. t_first feeds nothing outside the
        # session branch; c_start only the count-measure probe/containment.
        if spec.count_periods:
            t_first = state.t_first.at[pos].min(
                jnp.where(io_valid, ts, I64_MAX))
            c_start = state.c_start.at[pos].min(
                jnp.where(io_valid, c_idx, I64_MAX))
        else:
            t_first = state.t_first
            c_start = state.c_start

        partials = []
        for agg, part in zip(spec.aggs, state.partials):
            dense, sparse = _lift(agg, vals, io_valid)
            if sparse is None:
                part = _combine_scatter(part, pos, dense, agg.kind)
            else:
                col, v = sparse
                part = _combine_scatter(part, (pos, col), v, agg.kind)
            partials.append(part)

        if assume_inorder:
            return SliceBufferState(
                starts=starts, ends=ends, t_first=t_first, t_last=t_last,
                c_start=c_start, counts=counts, partials=tuple(partials),
                ax_starts=state.ax_starts, ax_counts=state.ax_counts,
                ax_partials=state.ax_partials,
                n_slices=(n + k[-1]).astype(jnp.int32),
                n_annex=state.n_annex,
                max_event_time=jnp.maximum(
                    state.max_event_time,
                    jnp.max(jnp.where(valid, ts, I64_MIN))),
                current_count=state.current_count
                + jnp.sum(valid.astype(jnp.int64)),
                overflow=overflow,
            )

        # ---- late path ---------------------------------------------------
        # Covering main-buffer slice: the last slice with start <= ts whose
        # recorded closing edge still reaches past ts (ts < ends[lo]) — the
        # engine equivalent of findSliceIndexByTimestamp
        # (LazyAggregateStore.java:29-37). Under a static spec this equals
        # "a slice with start == grid_start(ts) exists"; after a dynamic
        # window addition it also covers pre-addition coarse slices, which
        # the reference likewise keeps folding late tuples into. If no
        # covering slice exists (the grid range was never materialized),
        # the tuple goes to the annex under the current union grid.
        new_state_partials = partials
        lo_raw = jnp.searchsorted(starts, ts, side="right") - 1
        lo = jnp.clip(lo_raw, 0, C - 1)
        covered = late & (lo_raw >= 0) & (starts[lo] <= ts) & (ts < ends[lo])
        cov_pos = jnp.where(covered, lo, C - 1)          # C-1 lane is masked
        cov_one = jnp.where(covered, jnp.int64(1), jnp.int64(0))
        counts = counts.at[cov_pos].add(cov_one)
        t_last = t_last.at[cov_pos].max(jnp.where(covered, ts, I64_MIN))
        if spec.count_periods:
            t_first = t_first.at[cov_pos].min(
                jnp.where(covered, ts, I64_MAX))
        partials2 = []
        for agg, part in zip(spec.aggs, new_state_partials):
            dense, sparse = _lift(agg, vals, covered)
            if sparse is None:
                part = _combine_scatter(part, cov_pos, dense, agg.kind)
            else:
                col, v = sparse
                part = _combine_scatter(part, (cov_pos, col), v, agg.kind)
            partials2.append(part)

        # Annex: late tuples with no covering slice, segmented by grid start.
        # The batch is ts-sorted, so equal grid starts are adjacent.
        ax = late & ~covered
        ax_prev = jnp.concatenate([jnp.full((1,), I64_MIN), s[:-1]])
        ax_new = ax & ((s != ax_prev)
                       | ~jnp.concatenate([jnp.zeros((1,), bool), ax[:-1]]))
        ax_k = jnp.cumsum(ax_new.astype(jnp.int32))
        ax_pos = jnp.clip(state.n_annex + ax_k - 1, 0, A - 1)
        ax_pos = jnp.where(ax, ax_pos, A - 1)
        overflow = overflow | ((state.n_annex + ax_k[-1]) > A)
        ax_one = jnp.where(ax, jnp.int64(1), jnp.int64(0))
        ax_starts = state.ax_starts.at[ax_pos].min(jnp.where(ax, s, I64_MAX))
        ax_counts = state.ax_counts.at[ax_pos].add(ax_one)
        ax_partials = []
        for agg, part in zip(spec.aggs, state.ax_partials):
            dense, sparse = _lift(agg, vals, ax)
            if sparse is None:
                part = _combine_scatter(part, ax_pos, dense, agg.kind)
            else:
                col, v = sparse
                part = _combine_scatter(part, (ax_pos, col), v, agg.kind)
            ax_partials.append(part)

        return SliceBufferState(
            starts=starts, ends=ends, t_first=t_first, t_last=t_last,
            c_start=c_start, counts=counts, partials=tuple(partials2),
            ax_starts=ax_starts, ax_counts=ax_counts,
            ax_partials=tuple(ax_partials),
            n_slices=(n + k[-1]).astype(jnp.int32),
            n_annex=(state.n_annex + ax_k[-1]).astype(jnp.int32),
            max_event_time=jnp.maximum(
                state.max_event_time,
                jnp.max(jnp.where(valid, ts, I64_MIN))),
            current_count=state.current_count
            + jnp.sum(valid.astype(jnp.int64)),
            overflow=overflow,
        )

    return ingest


#: Largest run bound whose dense-ingest fold is a one-hot product over the
#: [B, R] lane-by-run grid; above it the fold is a segmented scan, whose
#: cost does not grow with R.
ONE_HOT_FOLD_RUNS = 16

_SEGMENT_OPS = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _segment_fold(kind: str, k: jnp.ndarray, lifted: jnp.ndarray,
                  last: jnp.ndarray) -> jnp.ndarray:
    """Per-run combine of ``lifted[B, w]`` under the sorted run ids
    ``k[B]``: a segmented inclusive scan (each run restarts at its first
    lane), read at each run's last lane ``last[R]`` -> ``[R, w]``. Sums
    add in float32 along the scan's tree; min/max are exact."""
    op = _SEGMENT_OPS[kind]
    head = jnp.concatenate([jnp.ones((1,), bool), k[1:] != k[:-1]])

    def combine(a, b):
        a_head, a_val = a
        b_head, b_val = b
        return a_head | b_head, jnp.where(b_head[:, None], b_val,
                                          op(a_val, b_val))

    _, acc = jax.lax.associative_scan(combine, (head, lifted))
    return acc[jnp.clip(last, 0, k.shape[0] - 1)]


I32_MIN, I32_MAX = -(1 << 31), (1 << 31) - 1


def ts32_fits(spec: EngineSpec, ts_lo: int, ts_hi: int) -> bool:
    """Whether a batch spanning ``[ts_lo, ts_hi]`` keeps every int32
    quantity of the narrow dense kernel (``build_ingest_dense(ts32=True)``)
    exact: the offsets ``ts - ts_lo``, each offset plus its period residue,
    and the batch's grid starts relative to ``ts_lo``. A grid period
    ``p`` bounds how far a grid start lies before its timestamp
    (``p - 1``); with bands alone the bound is the first lane's own
    distance to its band edge."""
    if ts_lo < 0:
        return False
    reach = max([int(p) for p in spec.periods]
                + [int(p) for p, _ in spec.offset_periods], default=0)
    if not reach:
        reach = ts_lo - int(host_grid_start(spec, np.int64([ts_lo]))[0])
    return ts_hi - ts_lo + reach < (1 << 31)


def _rebase32(t0: jnp.ndarray, x) -> jnp.ndarray:
    """``x - t0`` as int32, clamped to the int32 range (a point far before
    or after the batch compares the same way as its clamped offset)."""
    return jnp.clip(x - t0, I32_MIN, I32_MAX).astype(jnp.int32)


def _grid_offset32(spec: EngineSpec, d: jnp.ndarray,
                   t0: jnp.ndarray) -> jnp.ndarray:
    """``grid_start(spec, t0 + d) - t0`` in int32, for offsets ``d[B]``
    from ``t0`` under the :func:`ts32_fits` test: each period's residue
    ``t0 mod p`` is one scalar int64 op, and ``ts mod p == (d + t0 mod p)
    mod p`` keeps the per-lane work in int32. The clamp to >= 0 is the
    rebased zero."""
    zero = _rebase32(t0, jnp.int64(0))
    cands = [jnp.broadcast_to(zero, d.shape)]
    if spec.periods:
        pall = np.asarray(sorted(set(spec.periods)), dtype=np.int64)
        for i in range(0, len(pall), 128):
            p = pall[i:i + 128]
            r = jnp.mod(t0, jnp.asarray(p)).astype(jnp.int32)
            p32 = jnp.asarray(p.astype(np.int32))
            cands.append(jnp.max(
                d[:, None] - jax.lax.rem(d[:, None] + r[None, :],
                                         p32[None, :]), axis=1))
    for (p, r) in spec.offset_periods:
        q = jnp.mod(t0 - r, p).astype(jnp.int32)
        cands.append(d - jax.lax.rem(d + q, jnp.int32(p)))
    for (bs, bsz) in spec.bands:
        lo = _rebase32(t0, jnp.int64(bs))
        hi = _rebase32(t0, jnp.int64(bs + bsz))
        cands.append(jnp.where(d >= hi, hi, jnp.where(d >= lo, lo, zero)))
    return functools.reduce(jnp.maximum, cands)


def build_ingest_dense(spec: EngineSpec, capacity: int, runs: int,
                       pallas_fold: bool = False,
                       pallas_packed: bool = False,
                       ts32: bool = False):
    """In-order ingest without large scatters — the keyed/batched fast path.

    int64 scatters cost ~100 ms per 1M lanes on v5e (no native int64: XLA
    emulates with i32 pairs), which makes the generic kernel's per-field
    [B]-lane scatters the dominant ingest cost. In-order batches touch only
    a CONTIGUOUS run of slice rows [n-1, n-1+k_last], so when the host can
    bound the number of runs (``k_last < runs`` — it knows the batch's time
    span and the minimum grid period), every slice field reduces to

    * run boundaries: two vmapped ``searchsorted`` over the sorted run ids
      + gathers (t_last = ts at a run's last lane; start/end at its first),
    * partials, for ``runs <= ONE_HOT_FOLD_RUNS``: a [B, R] one-hot matmul
      at ``HIGHEST`` precision (float32 sums on the MXU) for sum-like
      aggregations, a masked [B, R, w] reduction for min/max; for larger
      bounds a segmented scan read at each run's last lane
      (:func:`_segment_fold`), which builds no [B, R] temporary,
    * one [R]-lane scatter per field into the buffer (R rows vs B lanes —
      two to four orders of magnitude fewer scatter lanes).

    Contract (host-checked): ts ascending, all ts >= max_event_time, no
    count-measure or session windows, dense-lift aggregations, and the
    batch spans < ``runs`` new slices (the kernel raises the overflow flag
    if the bound is violated).

    ``pallas_fold=True`` (``EngineConfig.pallas_slice_merge``) replaces
    the per-run one-hot matmul / masked [B, R, w] reduction with the
    Pallas segmented-reduce kernel
    (:func:`scotty_tpu.pallas.build_segment_fold`): lane blocks stream
    HBM→VMEM double-buffered into one [R, w] accumulator — the tiny
    [R]-lane buffer scatter stays. Default OFF keeps this builder's
    lowering byte-identical. ``pallas_packed`` streams the lifted
    values as bf16 (toleranced, see ``pallas.packed_tolerance``).

    ``ts32=True`` does the per-lane timestamp arithmetic in int32, on
    offsets from the batch's first timestamp (:func:`_grid_offset32`): on
    v5e, which has no 64-bit integer unit, the int64 grid-start ``mod``
    over every lane is the kernel's largest op. Only ``[R]``-lane and
    scalar work stays int64; the state after each batch is bit-identical
    to the default form's. Its contract adds the :func:`ts32_fits` span
    test, a valid prefix, and ``max_event_time`` read at the last valid
    lane. Default OFF keeps the int64 form's lowering byte-identical.
    """
    C, R = capacity, runs

    def ingest(state: SliceBufferState, ts: jnp.ndarray, vals: jnp.ndarray,
               valid: jnp.ndarray) -> SliceBufferState:
        B = ts.shape[0]
        if ts32:
            # the low words' difference is ts - t0 exactly (ts32_fits)
            t0 = ts[0]
            s = _grid_offset32(spec, ts.astype(jnp.int32)
                               - t0.astype(jnp.int32), t0)
        else:
            s = grid_start(spec, ts)
        n = state.n_slices
        open_start = jnp.where(
            n > 0, state.starts[jnp.maximum(n - 1, 0)], jnp.int64(I64_MIN))
        if ts32:
            open_start = _rebase32(t0, open_start)

        prev = jnp.concatenate([open_start[None], s[:-1]])
        newflag = (s > prev) & valid
        k = jnp.cumsum(newflag.astype(jnp.int32))          # run id per lane
        k_last = k[-1]
        # valid prefix len (the int64 form sums in int64, as jnp.sum
        # promotes int32)
        row_n = (jnp.sum(valid, dtype=jnp.int32) if ts32
                 else jnp.sum(valid.astype(jnp.int32)))

        r_idx = jnp.arange(R, dtype=jnp.int32)
        first = jnp.searchsorted(k, r_idx, side="left")
        last = jnp.minimum(
            jnp.searchsorted(k, r_idx, side="right") - 1, row_n - 1)
        cnt_r = jnp.maximum(last - first + 1, 0).astype(jnp.int64)
        live = cnt_r > 0

        t_last_r = ts[jnp.clip(last, 0, B - 1)]
        start_r = s[jnp.clip(first, 0, B - 1)]
        if ts32:
            start_r = start_r.astype(jnp.int64) + t0
        ends_r = next_edge(spec, start_r)

        rows = jnp.clip((n - 1) + r_idx, 0, C - 1)
        starts = state.starts.at[rows].min(
            jnp.where(live, start_r, I64_MAX))
        ends = state.ends.at[rows].min(jnp.where(live, ends_r, I64_MAX))
        counts = state.counts.at[rows].add(jnp.where(live, cnt_r, 0))
        t_last = state.t_last.at[rows].max(
            jnp.where(live, t_last_r, I64_MIN))

        partials = []
        for agg, part in zip(spec.aggs, state.partials):
            lifted, sparse = _lift(agg, vals, valid)
            assert sparse is None, "dense ingest needs dense-lift aggs"
            if pallas_fold:
                from ..pallas import build_segment_fold

                fold = build_segment_fold(
                    B, R, part.shape[1], agg.kind, agg.identity,
                    packed=pallas_packed)
                # invalid lanes alias run k_last with identity-masked
                # values (the _lift mask above), so their combine is a
                # no-op — same guarantee the live mask gives the XLA
                # branches below
                upd = fold(k, lifted).astype(part.dtype)
                part = _combine_scatter(part, rows, upd, agg.kind)
            elif R > ONE_HOT_FOLD_RUNS:
                ident = jnp.asarray(agg.identity, part.dtype)
                upd = _segment_fold(agg.kind, k, lifted.astype(part.dtype),
                                    last)                    # [R, w]
                upd = jnp.where(live[:, None], upd, ident)
                part = _combine_scatter(part, rows, upd, agg.kind)
            elif agg.kind == "sum":
                oh = (k[:, None] == r_idx[None, :]).astype(part.dtype)
                upd = jnp.matmul(oh.T, lifted,               # [R, w] — MXU
                                 precision=jax.lax.Precision.HIGHEST)
                upd = jnp.where(live[:, None], upd, 0)
                part = part.at[rows].add(upd)
            else:
                oh = k[:, None] == r_idx[None, :]            # [B, R]
                ident = jnp.asarray(agg.identity, part.dtype)
                masked = jnp.where(oh[:, :, None], lifted[:, None, :],
                                   ident)                    # [B, R, w]
                op_ = jnp.min if agg.kind == "min" else jnp.max
                upd = op_(masked, axis=0)                    # [R, w]
                upd = jnp.where(live[:, None], upd, ident)
                part = _combine_scatter(part, rows, upd, agg.kind)
            partials.append(part)

        # ts32: ts ascends over the valid prefix, whose last lane holds
        # the batch's max and whose length is its count
        return state._replace(
            starts=starts, ends=ends, counts=counts, t_last=t_last,
            partials=tuple(partials),
            n_slices=(n + k_last).astype(jnp.int32),
            max_event_time=jnp.maximum(
                state.max_event_time,
                jnp.where(row_n > 0, ts[jnp.maximum(row_n - 1, 0)],
                          jnp.int64(I64_MIN)) if ts32
                else jnp.max(jnp.where(valid, ts, I64_MIN))),
            current_count=state.current_count
            + (row_n.astype(jnp.int64) if ts32
               else jnp.sum(valid.astype(jnp.int64))),
            overflow=(state.overflow | (((n - 1) + k_last) >= C)
                      | (k_last > R - 1)),
        )

    return ingest


def build_ingest_rows(spec: EngineSpec, capacity: int):
    """Arrival-order ingest with host-precomputed slice assignment — the
    out-of-order count+time MIXED path.

    The reference handles a late tuple under a count measure by inserting
    it into its ts-covering slice and rippling the ts-max record of every
    later slice forward (SliceManager.java:64-86). The ripple is an
    insertion-sort step: after it, slice k holds exactly the ts-sorted
    ranks ``[c_start_k, c_start_k + counts_k)`` — for count+time mixes
    too, because ripples move ts-max records forward only, preserving the
    global content ordering, while the grid ``tStart`` edges stay put.
    The net slice-metadata effect of ANY tuple (late or in-order) is
    therefore: +1 record to the slice that is OPEN at its arrival, plus
    whatever new slices its arrival cuts (count edges for every tuple,
    StreamSlicer.java:37-44; time edges for in-order tuples only,
    StreamSlicer.java:47-82). The host computes those cuts in arrival
    order (operator._mixed_cut_calculus — it knows the running max event
    time, the open-slice start, and the running count); this kernel just
    scatters them. Aggregate VALUES are answered from the record buffer's
    rank ranges from then on (``build_query(..., mix_rec=True)``), so the
    partial-aggregate matrices are deliberately left stale.

    Inputs (arrival order, NOT ts-sorted): per-lane assigned row offset
    ``row_off`` (inclusive cut count — lane's row = n_slices-1+row_off),
    ``is_cut``, cut ``start`` values and the cutting lane's pre-insert
    global count ``cut_c``.
    """
    C = capacity

    def ingest(state: SliceBufferState, ts: jnp.ndarray,
               valid: jnp.ndarray, row_off: jnp.ndarray,
               is_cut: jnp.ndarray, cut_start: jnp.ndarray,
               cut_c: jnp.ndarray) -> SliceBufferState:
        # values are NOT taken: they live in the record buffer and every
        # answer on this path is a rank-range query — no point paying the
        # H2D transfer of a [B] float array that would only be discarded
        n = state.n_slices
        row = (n - 1).astype(jnp.int32) + row_off
        pos = jnp.clip(row, 0, C - 1)
        pos = jnp.where(valid, pos, C).astype(jnp.int32)  # sentinel + drop
        cut = valid & is_cut
        one = jnp.where(valid, jnp.int64(1), jnp.int64(0))
        counts = state.counts.at[pos].add(one, mode="drop")
        starts = state.starts.at[pos].min(
            jnp.where(cut, cut_start, I64_MAX), mode="drop")
        ends = state.ends.at[pos].min(
            jnp.where(cut, next_edge(spec, cut_start), I64_MAX),
            mode="drop")
        c_start = state.c_start.at[pos].min(
            jnp.where(cut, cut_c, I64_MAX), mode="drop")
        k_last = jnp.max(jnp.where(valid, row_off, 0))
        return state._replace(
            starts=starts, ends=ends, counts=counts, c_start=c_start,
            n_slices=(n + k_last).astype(jnp.int32),
            max_event_time=jnp.maximum(
                state.max_event_time,
                jnp.max(jnp.where(valid, ts, I64_MIN))),
            current_count=state.current_count
            + jnp.sum(valid.astype(jnp.int64)),
            overflow=state.overflow | (((n - 1) + k_last) >= C),
        )

    return ingest


# ---------------------------------------------------------------------------
# Query kernel (watermark final-merge)
# ---------------------------------------------------------------------------


def _range_combine(tbl: jnp.ndarray, lo: jnp.ndarray, length: jnp.ndarray,
                   op, ident, levels: int):
    """Min/max over row ranges [lo, lo+length) of ``tbl`` via a log-sweep
    sparse table: each query answered at level floor(log2(len)) with two
    gathers; the table doubles per level."""
    N = tbl.shape[0]
    kbits = jnp.where(
        length > 0,
        jnp.floor(jnp.log2(jnp.maximum(length, 1)
                           .astype(jnp.float64))).astype(jnp.int32),
        -1)
    res = jnp.full((lo.shape[0], tbl.shape[1]), ident, tbl.dtype)
    hi = lo + length
    for lvl in range(levels):
        size = 1 << lvl
        sel = (kbits == lvl)
        a = tbl[jnp.clip(lo, 0, N - 1)]
        b = tbl[jnp.clip(hi - size, 0, N - 1)]
        res = jnp.where(sel[:, None], op(a, b), res)
        if size < N:
            shifted = jnp.concatenate(
                [tbl[size:],
                 jnp.full((size, tbl.shape[1]), ident, tbl.dtype)])
            tbl = op(tbl, shifted)
    return res


def build_query(spec: EngineSpec, capacity: int, annex_capacity: int,
                record_capacity: int = 0, mix_rec: bool = False):
    """All triggered windows answered at once.

    Replaces LazyAggregateStore.aggregate's O(#slices × #windows) nested
    combine loop (LazyAggregateStore.java:83-111) with
    - prefix-sum range queries for sum-like partials,
    - a log-sweep sparse table for min/max-like partials,
    over the sorted slice buffer, plus a masked fold over the (small) annex.

    With ``record_capacity`` set (count-measure workloads), count-window
    VALUES come from ts-sorted rank ranges of the record buffer — the
    closed form of the reference's out-of-order ripple (see
    :class:`RecordBuffer`); slice counts still provide containment and
    emptiness.

    With ``mix_rec`` (count+time mixed workloads after a late tuple), TIME
    windows also answer from record rank ranges: the ripple re-aligns slice
    CONTENT to ts-sorted rank ranges (so the partial matrices are stale),
    and each slice's post-ripple ``tLast`` — what the reference's
    containment reads, AggregateWindowState.java:25-31 — is the ts of its
    last rank, ``rts[c_start + counts - 1 - base]``. The mix query also
    takes the trigger batch's scan bounds ``(min_ts, max_ts, min_count,
    max_count)``: the reference's final-merge loop only walks slices in
    ``[findSliceIndexByTimestamp(minTs) ∧ findSliceByCount(minCount),
    findSliceIndexByTimestamp(maxTs) ∨ findSliceByCount(maxCount)]``
    (LazyAggregateStore.java:83-92, WindowManager.java:98-118), and find*
    returns the LAST slice at a duplicated edge — so a non-empty slice
    whose start duplicates ``min_ts`` (count cut + time cut at one point)
    is SHADOWED out of every window of that batch. Reproduced exactly.
    """
    C, A = capacity, annex_capacity
    # levels must include log2(N) itself: a range spanning the WHOLE table
    # (length == N, N a power of two) is answered at that level
    L = max(1, C.bit_length())
    RC = record_capacity
    use_rec = RC > 0 and bool(spec.count_periods)
    Lr = max(1, RC.bit_length()) if use_rec else 0
    assert not (mix_rec and not use_rec), "mix_rec needs the record buffer"

    def answer(state: SliceBufferState, rec, ws: jnp.ndarray,
               we: jnp.ndarray, tmask: jnp.ndarray, is_count: jnp.ndarray,
               scan=None):
        lo_t = jnp.searchsorted(state.starts, ws, side="left")
        # Upper containment bound per the reference: a slice is covered iff
        # window.end > slice.tLast (AggregateWindowState.java:25-31).
        # When every window edge is a slice-grid point this equals
        # ``starts < we`` (records never cross next_edge), but after a
        # DYNAMIC window addition pre-addition slices are coarser than the
        # new union grid and may straddle new window boundaries — t_last
        # containment then excludes them exactly like the reference does.
        # t_last is nondecreasing over live rows (t_last[i] < starts[i+1]
        # <= t_last[i+1]); pad rows are masked to LONG_MAX to keep the
        # array sorted for searchsorted.
        live = jnp.arange(C) < state.n_slices
        if mix_rec:
            # post-ripple tLast, derived from the record buffer (stored
            # t_last is pre-ripple). Live rows always hold >= 1 record
            # (every cut lane lands in its own new row), so the derived
            # array is nondecreasing like rts itself.
            last_rank = jnp.clip(state.c_start + state.counts - 1 - rec.base,
                                 0, RC - 1)
            live_t_last = jnp.where(live, rec.rts[last_rank], I64_MAX)
        else:
            live_t_last = jnp.where(live, state.t_last, I64_MAX)
        hi_t = jnp.searchsorted(live_t_last, we, side="left")
        # Count containment (AggregateWindowState.java:25-31 Count branch):
        # window [ws, we] covers slices with c_start >= ws and
        # c_last = c_start + counts <= we; both arrays are nondecreasing
        # in-order, so the covered set is a contiguous index range.
        cs_end = jnp.where(state.c_start < I64_MAX,
                           state.c_start + state.counts, I64_MAX)
        lo_c = jnp.searchsorted(state.c_start, ws, side="left")
        hi_c = jnp.searchsorted(cs_end, we, side="right")
        lo = jnp.where(is_count, jnp.minimum(lo_c, hi_c), lo_t)
        hi = jnp.where(is_count, hi_c, hi_t)
        if mix_rec:
            # the reference's batch scan bounds (see docstring): find* walk
            # from the END, so duplicated edges resolve to the LAST slice
            # — searchsorted(side='right') - 1
            (min_ts, max_ts, min_count, max_count) = scan
            n1 = jnp.maximum(state.n_slices - 1, 0)
            si = jnp.minimum(
                jnp.maximum(
                    jnp.searchsorted(state.starts, min_ts, side="right") - 1,
                    0),
                jnp.searchsorted(state.c_start, min_count,
                                 side="right") - 1)
            si = jnp.maximum(si, 0)
            ei = jnp.maximum(
                jnp.minimum(
                    n1,
                    jnp.searchsorted(state.starts, max_ts, side="right") - 1),
                jnp.searchsorted(state.c_start, max_count,
                                 side="right") - 1)
            lo = jnp.maximum(lo, si)
            hi = jnp.minimum(hi, ei + 1)
        # a coarse pre-addition slice spanning the whole window gives
        # hi < lo (start < ws and t_last >= we): the window covers nothing
        hi = jnp.maximum(hi, lo)
        length = hi - lo

        cnt_prefix = jnp.concatenate(
            [jnp.zeros((1,), jnp.int64), jnp.cumsum(state.counts)])
        cnt = cnt_prefix[hi] - cnt_prefix[lo]

        # The annex is guaranteed empty here: the host dispatches the
        # annex-merge kernel before any query once a late tuple was ingested
        # (an O(T × A) masked annex scan in this kernel costs seconds at
        # benchmark trigger counts — measured 2.2 s at T=65k, A=4k).
        if use_rec:
            live_r = jnp.arange(RC) < rec.n
            # rank range of the covered slices: c_start of the first covered
            # slice (absolute counts) → buffer row; extent = covered count
            rlo = jnp.clip(state.c_start[jnp.clip(lo, 0, C - 1)] - rec.base,
                           0, RC)
            rec_rows = (jnp.ones_like(is_count) if mix_rec else is_count)
            rlen = jnp.where(rec_rows, jnp.clip(cnt, 0, RC - rlo), 0)

        results = []
        for agg, part in zip(spec.aggs, state.partials):
            op = jnp.minimum if agg.kind == "min" else jnp.maximum
            ident = jnp.asarray(agg.identity, part.dtype)
            if mix_rec:
                res = None          # partials are stale; records only
            elif agg.kind == "sum":
                P = jnp.concatenate(
                    [jnp.zeros((1, part.shape[1]), part.dtype),
                     jnp.cumsum(part, axis=0)])
                res = P[hi] - P[lo]
            else:
                res = _range_combine(part, lo, length, op, agg.identity, L)
            if use_rec:
                # count windows: aggregate the ts-sorted rank range directly
                if agg.is_sparse:
                    col, v = agg.lift_sparse(rec.rvals)
                    lifted = jnp.full((RC, part.shape[1]), agg.identity,
                                      part.dtype)
                    lifted = _combine_scatter(
                        lifted, (jnp.arange(RC), col),
                        jnp.where(live_r, v, agg.identity), agg.kind)
                else:
                    lifted = agg.lift_dense(rec.rvals)
                    lifted = jnp.where(live_r[:, None], lifted, agg.identity)
                if agg.kind == "sum":
                    Pr = jnp.concatenate(
                        [jnp.zeros((1, part.shape[1]), part.dtype),
                         jnp.cumsum(lifted, axis=0)])
                    rres = Pr[rlo + rlen] - Pr[rlo]
                else:
                    rres = _range_combine(lifted, rlo, rlen, op,
                                          agg.identity, Lr)
                res = rres if mix_rec \
                    else jnp.where(is_count[:, None], rres, res)
            results.append(jnp.where(tmask[:, None], res, ident))

        return jnp.where(tmask, cnt, 0), tuple(results)

    if mix_rec:
        def query(state, rec, ws, we, tmask, is_count,
                  min_ts, max_ts, min_count, max_count):
            return answer(state, rec, ws, we, tmask, is_count,
                          (min_ts, max_ts, min_count, max_count))
    elif use_rec:
        def query(state, rec, ws, we, tmask, is_count):
            return answer(state, rec, ws, we, tmask, is_count)
    else:
        def query(state, ws, we, tmask, is_count):
            return answer(state, None, ws, we, tmask, is_count)
    return query


# ---------------------------------------------------------------------------
# GC / annex-merge kernel
# ---------------------------------------------------------------------------


def build_annex_merge(spec: EngineSpec, capacity: int, annex_capacity: int):
    """Fold the out-of-order annex back into the sorted main buffer.

    Re-sorts the concatenated (main ++ annex) buffer by start — annex entries
    either coincide with an existing start (combine) or fill a
    previously-empty grid range (insert). The host dispatches this only on
    watermarks after a late tuple actually entered the annex (the device
    sort is expensive on TPU), so in-order streams never pay for it.
    """
    C, A = capacity, annex_capacity

    def merge(st: SliceBufferState) -> SliceBufferState:
        cat_starts = jnp.concatenate([st.starts, st.ax_starts])
        order = jnp.argsort(cat_starts)          # stable; LONG_MAX sinks
        sorted_starts = cat_starts[order]
        # coincident starts → combine into one slice: segment by value
        prev = jnp.concatenate([jnp.full((1,), I64_MIN), sorted_starts[:-1]])
        newflag = (sorted_starts > prev) & (sorted_starts < I64_MAX)
        seg = jnp.cumsum(newflag.astype(jnp.int32)) - 1      # [C+A]
        seg = jnp.clip(seg, 0, C - 1)
        n_new = jnp.max(jnp.where(newflag, seg + 1, 0)).astype(jnp.int32)

        uniq_starts = jnp.full((C,), I64_MAX, jnp.int64).at[seg].min(
            jnp.where(newflag, sorted_starts, I64_MAX))
        cat_ends = jnp.concatenate([st.ends, next_edge(spec, st.ax_starts)])
        uniq_ends = jnp.full((C,), I64_MAX, jnp.int64).at[seg].min(
            cat_ends[order])
        cat_tf = jnp.concatenate([st.t_first, st.ax_starts])
        uniq_tf = jnp.full((C,), I64_MAX, jnp.int64).at[seg].min(cat_tf[order])
        # pad annex rows hold I64_MAX starts; mask them to I64_MIN or the
        # max-scatter below would poison the last real slice's t_last
        cat_tl = jnp.concatenate(
            [st.t_last, jnp.where(st.ax_starts < I64_MAX, st.ax_starts,
                                  I64_MIN)])
        uniq_tl = jnp.full((C,), I64_MIN, jnp.int64).at[seg].max(cat_tl[order])
        cat_cnt = jnp.concatenate([st.counts, st.ax_counts])
        uniq_cnt = jnp.zeros((C,), jnp.int64).at[seg].add(cat_cnt[order])
        cat_cs = jnp.concatenate(
            [st.c_start, jnp.full((A,), I64_MAX, jnp.int64)])
        uniq_cs = jnp.full((C,), I64_MAX, jnp.int64).at[seg].min(
            cat_cs[order])

        new_partials = []
        for agg, part, ax_part in zip(spec.aggs, st.partials,
                                      st.ax_partials):
            cat = jnp.concatenate([part, ax_part])[order]
            tgt = jnp.full((C, part.shape[1]), agg.identity, part.dtype)
            new_partials.append(_combine_scatter(tgt, seg, cat, agg.kind))

        return st._replace(
            starts=uniq_starts, ends=uniq_ends, t_first=uniq_tf,
            t_last=uniq_tl, counts=uniq_cnt, c_start=uniq_cs,
            partials=tuple(new_partials),
            ax_starts=jnp.full((A,), I64_MAX, jnp.int64),
            ax_counts=jnp.zeros((A,), jnp.int64),
            ax_partials=tuple(
                jnp.full((A, a.width), a.identity, p.dtype)
                for a, p in zip(spec.aggs, st.ax_partials)),
            n_slices=n_new, n_annex=jnp.int32(0),
        )

    return merge


def build_gc(spec: EngineSpec, capacity: int, annex_capacity: int):
    """Drop slices behind the GC bound (WindowManager.clearAfterWatermark,
    WindowManager.java:82-95 -> LazyAggregateStore.removeSlices :138-146):
    a masked roll of the buffer. Assumes the annex was merged first when
    non-empty."""
    C, A = capacity, annex_capacity

    def gc(state: SliceBufferState, bound: jnp.ndarray) -> SliceBufferState:
        # ---- drop slices behind the bound --------------------------------
        # keep the slice covering `bound` (removeSlices deletes [0, index)).
        idx = jnp.searchsorted(state.starts, bound, side="right") - 1
        k = jnp.clip(idx, 0, jnp.maximum(state.n_slices - 1, 0)).astype(jnp.int32)

        def roll(a, fill):
            rolled = jnp.roll(a, -k, axis=0)
            keep = jnp.arange(a.shape[0]) < (a.shape[0] - k)
            if a.ndim == 1:
                return jnp.where(keep, rolled, fill)
            return jnp.where(keep[:, None], rolled, fill)

        return state._replace(
            starts=roll(state.starts, I64_MAX),
            ends=roll(state.ends, I64_MAX),
            t_first=roll(state.t_first, I64_MAX),
            t_last=roll(state.t_last, I64_MIN),
            c_start=roll(state.c_start, I64_MAX),
            counts=roll(state.counts, 0),
            partials=tuple(roll(p, a.identity)
                           for a, p in zip(spec.aggs, state.partials)),
            n_slices=state.n_slices - k,
        )

    return gc

# ---------------------------------------------------------------------------
# Record buffer (count-measure workloads)
# ---------------------------------------------------------------------------


class RecordBuffer(NamedTuple):
    """Raw (ts, value) records in ascending-ts order — retained only while
    count-measure windows are registered, mirroring the reference's lazy
    record retention (SliceFactory.java:17-22: count measure forces lazy
    slices). Count windows aggregate ts-sorted RANK ranges: the reference's
    out-of-order ripple (SliceManager.java:77-85) shifts the ts-max element
    of every later slice forward so each slice keeps its fixed count range —
    i.e. after any repairs, slice k holds exactly the ts-sorted ranks
    ``[c_start_k, c_start_k + counts_k)``. The engine answers count windows
    directly from this buffer instead of materializing the shifts."""

    rts: jnp.ndarray      # i64[RC] record timestamps, ascending; pad I64_MAX
    rvals: jnp.ndarray    # f32[RC] record values
    n: jnp.ndarray        # i32 scalar — live record count
    base: jnp.ndarray     # i64 scalar — absolute count index of row 0
    overflow: jnp.ndarray


def init_records(record_capacity: int) -> RecordBuffer:
    RC = record_capacity
    return RecordBuffer(
        rts=jnp.full((RC,), I64_MAX, dtype=jnp.int64),
        rvals=jnp.zeros((RC,), dtype=jnp.float32),
        n=jnp.int32(0),
        base=jnp.int64(0),
        overflow=jnp.bool_(False),
    )


def build_record_merge(record_capacity: int):
    """Merge a ts-sorted batch into the sorted record buffer (stable:
    existing records precede batch records at equal ts — insertion order,
    like the reference's TreeSet walk)."""
    RC = record_capacity

    def merge(rec: RecordBuffer, ts: jnp.ndarray, vals: jnp.ndarray,
              valid: jnp.ndarray) -> RecordBuffer:
        B = ts.shape[0]
        n = rec.n
        live = jnp.arange(RC) < n
        bts = jnp.where(valid, ts, I64_MAX)
        nb = jnp.sum(valid.astype(jnp.int32))
        # final position of each existing record: own rank + batch records
        # strictly before it (ties: batch goes after → side='left')
        pos_old = jnp.arange(RC) + jnp.searchsorted(bts, rec.rts,
                                                    side="left")
        pos_old = jnp.where(live, pos_old, RC)          # dead rows drop
        # final position of each batch record: own rank + existing records
        # at-or-before it (side='right')
        pos_new = jnp.arange(B) + jnp.searchsorted(
            jnp.where(live, rec.rts, I64_MAX), bts, side="right")
        pos_new = jnp.where(valid, pos_new, RC)
        rts = jnp.full((RC,), I64_MAX, jnp.int64)
        rts = rts.at[pos_old].set(rec.rts, mode="drop")
        rts = rts.at[pos_new].set(bts, mode="drop")
        rvals = jnp.zeros((RC,), rec.rvals.dtype)
        rvals = rvals.at[pos_old].set(rec.rvals, mode="drop")
        rvals = rvals.at[pos_new].set(vals.astype(rec.rvals.dtype),
                                      mode="drop")
        return RecordBuffer(
            rts=rts, rvals=rvals, n=(n + nb).astype(jnp.int32),
            base=rec.base, overflow=rec.overflow | ((n + nb) > RC))

    return merge


def build_record_append(record_capacity: int):
    """In-order record append: a ts-sorted batch at/above the stream's max
    event time lands as one contiguous ``dynamic_update_slice`` — O(B),
    versus the general rank merge's O(RC) int64 scatters (~113 ms per M
    lanes on v5e), which made every in-order count batch pay the whole
    buffer (r4). Pad lanes are written beyond ``n + nb`` and are dead:
    every record reader masks by ``rec.n``. The write block must fit —
    ``overflow`` is raised with one batch of headroom, since a clamped
    ``dynamic_update_slice`` would land misaligned."""
    RC = record_capacity

    def append(rec: RecordBuffer, ts: jnp.ndarray, vals: jnp.ndarray,
               valid: jnp.ndarray) -> RecordBuffer:
        B = ts.shape[0]
        nb = jnp.sum(valid.astype(jnp.int32))
        if B > RC:
            # tiny buffers (tests): the contiguous block can't fit the
            # operand — fall back to a [B]-lane drop-mode scatter
            pos = rec.n + jnp.arange(B, dtype=jnp.int32)
            pos = jnp.where(valid, pos, RC)
            rts = rec.rts.at[pos].set(ts, mode="drop")
            rvals = rec.rvals.at[pos].set(vals.astype(rec.rvals.dtype),
                                          mode="drop")
            ovf = rec.n + nb > RC
        else:
            rts = jax.lax.dynamic_update_slice(rec.rts, ts, (rec.n,))
            rvals = jax.lax.dynamic_update_slice(
                rec.rvals, vals.astype(rec.rvals.dtype), (rec.n,))
            ovf = rec.n + B > RC
        return RecordBuffer(
            rts=rts, rvals=rvals, n=(rec.n + nb).astype(jnp.int32),
            base=rec.base, overflow=rec.overflow | ovf)

    return append


def build_record_gc(capacity: int, record_capacity: int):
    """Drop records behind the slice-GC bound, keeping ranks aligned with
    the surviving slices: the new base is the first surviving slice's
    ``c_start`` (computed from the PRE-GC slice buffer, same bound as
    :func:`build_gc`)."""
    C, RC = capacity, record_capacity

    def rgc(state: SliceBufferState, rec: RecordBuffer,
            bound: jnp.ndarray) -> RecordBuffer:
        idx = jnp.searchsorted(state.starts, bound, side="right") - 1
        k = jnp.clip(idx, 0, jnp.maximum(state.n_slices - 1, 0))
        new_base = state.c_start[k]
        new_base = jnp.where(new_base < I64_MAX, new_base, rec.base)
        d = jnp.clip(new_base - rec.base, 0, RC).astype(jnp.int32)

        def roll(a, fill):
            rolled = jnp.roll(a, -d, axis=0)
            keep = jnp.arange(a.shape[0]) < (a.shape[0] - d)
            return jnp.where(keep, rolled, fill)

        return RecordBuffer(
            rts=roll(rec.rts, I64_MAX), rvals=roll(rec.rvals, 0),
            n=(rec.n - d).astype(jnp.int32), base=new_base,
            overflow=rec.overflow)

    return rgc


# ---------------------------------------------------------------------------
# Watermark → count probe
# ---------------------------------------------------------------------------


def build_count_probe(spec: EngineSpec, capacity: int,
                      record_capacity: int = 0):
    """Convert a watermark timestamp to a count bound for count-measure
    triggering (WindowManager.java:110-115): locate the slice covering the
    watermark; if its last observed record is at/after the watermark, step
    back one slice; the bound is that slice's last count.

    With ``record_capacity`` (the out-of-order count path), the slice's
    "last observed record" comes from the record buffer — after the
    reference's ripple, slice k's last record is the ts-sorted rank
    ``c_start_k + counts_k - 1``, whereas the arrival-order ``t_last``
    field keeps pre-ripple maxima."""
    RC = record_capacity

    def count_at(state: SliceBufferState, wm: jnp.ndarray) -> jnp.ndarray:
        idx = jnp.searchsorted(state.starts, wm, side="right") - 1
        idx = jnp.clip(idx, 0, capacity - 1)
        step = (state.t_last[idx] >= wm) & (idx > 0)
        idx = jnp.where(step, idx - 1, idx)
        return state.c_start[idx] + state.counts[idx]

    if not RC:
        return count_at

    def count_at_rec(state: SliceBufferState, rec: RecordBuffer,
                     wm: jnp.ndarray) -> jnp.ndarray:
        def t_last_of(i):
            r = jnp.clip(state.c_start[i] + state.counts[i] - 1 - rec.base,
                         0, RC - 1)
            return rec.rts[r]

        idx = jnp.searchsorted(state.starts, wm, side="right") - 1
        idx = jnp.clip(idx, 0, capacity - 1)
        step = (t_last_of(idx) >= wm) & (idx > 0)
        idx = jnp.where(step, idx - 1, idx)
        return state.c_start[idx] + state.counts[idx]

    return count_at_rec

# ---------------------------------------------------------------------------
# Session sweep (pure-session watermark path)
# ---------------------------------------------------------------------------


def build_session_sweep(spec: EngineSpec, capacity: int, emit_cap: int):
    """Trigger + emit + GC for the pure-session device path.

    Sessions whose ``t_last + gap < watermark`` are complete
    (SessionContext.triggerWindows, SessionWindow.java:107-116). In-order,
    completed sessions form a prefix of the slice buffer, so emission is a
    prefix gather of length m and GC is a roll by m. Emitted window bounds
    are ``[t_first, t_last + gap)``.

    Returns (new_state, m, starts[E], ends[E], counts[E], partials…[E]) with
    E = ``emit_cap`` static rows (rows ≥ m are padding).
    """
    C, E = capacity, emit_cap
    gap = int(spec.session_gaps[0])

    def sweep(state: SliceBufferState, wm: jnp.ndarray):
        live = jnp.arange(C) < state.n_slices
        done = live & (state.t_last + gap < wm)
        m = jnp.sum(done.astype(jnp.int32))        # prefix length
        idx = jnp.arange(E)
        sel = jnp.clip(idx, 0, C - 1)
        e_starts = jnp.where(idx < m, state.t_first[sel], I64_MAX)
        e_ends = jnp.where(idx < m, state.t_last[sel] + gap, I64_MAX)
        e_counts = jnp.where(idx < m, state.counts[sel], 0)
        e_partials = tuple(p[sel] for p in state.partials)
        em_overflow = m > E

        def roll(a, fill):
            rolled = jnp.roll(a, -m, axis=0)
            keep = jnp.arange(a.shape[0]) < (a.shape[0] - m)
            if a.ndim == 1:
                return jnp.where(keep, rolled, fill)
            return jnp.where(keep[:, None], rolled, fill)

        new_state = state._replace(
            starts=roll(state.starts, I64_MAX),
            ends=roll(state.ends, I64_MAX),
            t_first=roll(state.t_first, I64_MAX),
            t_last=roll(state.t_last, I64_MIN),
            c_start=roll(state.c_start, I64_MAX),
            counts=roll(state.counts, 0),
            partials=tuple(roll(p, a.identity)
                           for a, p in zip(spec.aggs, state.partials)),
            n_slices=state.n_slices - m,
            overflow=state.overflow | em_overflow,
        )
        return new_state, m, e_starts, e_ends, e_counts, e_partials

    return sweep

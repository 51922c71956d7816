"""Per-phase microbenchmarks — the JMH analogue.

The reference pins per-element operator cost with JMH
(benchmark/.../microbenchmark/SlicingWindowOperatorBenchmark.java:37-52,
AggregationStoreBenchmark.java); here the phases worth isolating are device
kernels and the host glue around them, so perf work on the full pipeline
stops being blind (VERDICT r1 item 9):

* ``ingest_scatter``    — general batched ingest kernel (scatter-combine)
* ``ingest_aligned``    — slice-aligned generate+reduce+append step
  (AlignedStreamPipeline's fused interval, amortized per tuple)
* ``query``             — range-query kernel at benchmark trigger counts
* ``annex_merge``       — out-of-order annex fold (device sort path)
* ``gc``                — slice-buffer roll
* ``host_pack``         — keyed host packing (lexsort + [K, B] scatter),
  no device work
* ``shape_sort_split``  — the shaper's jitted sort-and-split alone
  (scotty_tpu.shaper.device, ISSUE 5)
* ``ingest_shaped_ooo`` — a DISORDERED device-resident stream through
  the shaper end-to-end (sort-split + dense in-order ingest + late
  residue) — the number to hold against ``ingest_scatter``, which is
  what the same stream costs unshaped

Run: ``python -m scotty_tpu.bench.micro [--out bench_results/micro.json]``.
Each phase reports mean/min ms per dispatch and derived tuples/s where
meaningful. Shapes default to the headline-benchmark scale; ``--small``
switches to CPU-test shapes.
"""

from __future__ import annotations

import json
import time
from typing import Callable, Optional

import numpy as np


def _time_phase(fn: Callable[[], None], sync: Callable[[], None],
                iters: int, warmup: int = 2,
                drain: Optional[Callable[[], None]] = None) -> dict:
    """Amortized per-dispatch timing: ``iters`` back-to-back dispatches,
    ONE true sync (``sync`` must be a ``jax.device_get`` of a value the
    work produced). The final sync's round trip is
    measured on an idle queue and subtracted; the per-dispatch mean
    still includes per-dispatch overhead.

    ``drain`` retires the WHOLE async dispatch queue (block_until_ready
    over every live device value of the run) before the timed sections.
    ``sync`` alone only waits for this phase's own output — work queued
    by a PREVIOUS section can still be in flight behind it, and that
    work then lands inside this phase's "idle-queue" sync measurement
    (micro.json showed query.sync_ms 124.8 ms > its own mean_ms 70.7 ms
    — queued prior work misattributed to a later section's sync)."""
    for _ in range(warmup):
        fn()
    sync()
    if drain is not None:
        drain()                         # the queue is now REALLY idle
    t0 = time.perf_counter()
    sync()                              # idle-queue sync = pure round trip
    sync_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    sync()
    total_ms = (time.perf_counter() - t0) * 1e3
    # floor at ~timer resolution: on a fast host with tiny shapes the
    # subtraction can land at/below 0, and a 0 mean poisons every derived
    # rate downstream (VERDICT r3 weak-1). ``floored`` marks the phase so
    # a derived rate is recognizably a bound, not a measurement.
    raw = (total_ms - sync_ms) / iters
    mean = max(raw, 1e-4)
    return {"mean_ms": float(mean), "sync_ms": float(sync_ms),
            "iters": iters, "floored": bool(raw < 1e-4)}


def _rate(n: float, mean_ms: float) -> float:
    """Items/s from an amortized per-dispatch mean (mean_ms is floored at
    timer resolution by _time_phase, so this can't divide by zero)."""
    return n / (mean_ms / 1e3)


def run_micro(small: bool = False, iters: int = 20, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    from ..core.aggregates import SumAggregation
    from ..core.windows import SlidingWindow, WindowMeasure
    from ..engine import core as ec
    from ..engine.config import EngineConfig
    from ..engine.pipeline import AlignedStreamPipeline

    if small:                      # CPU-test shapes
        C, A, B, Tq = 1 << 10, 64, 1 << 10, 128
        throughput, wm_period = 200_000, 1000
        window = SlidingWindow(WindowMeasure.Time, 60_000, 1000)
    else:                          # headline-benchmark shapes
        C, A, B, Tq = 1 << 17, 1 << 12, 1 << 18, 1 << 16
        throughput, wm_period = 200_000_000, 1000
        window = SlidingWindow(WindowMeasure.Time, 60_000, 1)

    spec = ec.EngineSpec(periods=(1,) if not small else (1000,), bands=(),
                         count_periods=(),
                         aggs=(SumAggregation().device_spec(),))
    rng = np.random.default_rng(seed)
    results: dict = {"shapes": {"capacity": C, "annex": A, "batch": B,
                                "triggers": Tq, "small": small}}

    # every live device value of the run, as thunks: the inter-section
    # dispatch-queue drain blocks on ALL of them, so no section's timing
    # inherits queued work from a previous section (see _time_phase)
    live_thunks: list = []

    def drain():
        vals = [t() for t in live_thunks]
        for leaf in jax.tree_util.tree_leaves(
                [v for v in vals if v is not None]):
            if hasattr(leaf, "block_until_ready"):
                leaf.block_until_ready()

    # ---- ingest (general scatter path) -----------------------------------
    ingest = jax.jit(ec.build_ingest(spec, C, A), donate_argnums=0)
    grid = spec.periods[0]
    ts0 = np.sort(rng.integers(0, B * 2, size=B)).astype(np.int64)
    vals = rng.random(B).astype(np.float32)
    valid = np.ones((B,), bool)
    holder = {"st": ec.init_state(spec, C, A), "i": 0}

    def do_ingest():
        # fresh ts range each call so the buffer doesn't overflow the cap
        off = holder["i"] * 2 * B
        holder["i"] += 1
        holder["st"] = ingest(holder["st"], ts0 + off, vals, valid)

    def sync():
        jax.device_get(holder["st"].n_slices)

    live_thunks.append(lambda: holder["st"])
    r = _time_phase(do_ingest, sync, iters, drain=drain)
    r["tuples_per_s"] = _rate(B, r["mean_ms"])
    results["ingest_scatter"] = r

    # ---- gc (amortizes the buffer back down) ------------------------------
    gc = jax.jit(ec.build_gc(spec, C, A), donate_argnums=0)

    def do_gc():
        holder["st"] = gc(holder["st"], np.int64(holder["i"] * 2 * B))

    results["gc"] = _time_phase(do_gc, sync, iters, drain=drain)

    # ---- query ------------------------------------------------------------
    query = jax.jit(ec.build_query(spec, C, A))
    # refill a few batches so the buffer has content
    for _ in range(3):
        do_ingest()
    ws = (np.arange(Tq, dtype=np.int64) % (B // 2)) * grid
    we = ws + grid * 16
    mask = np.ones((Tq,), bool)
    ic = np.zeros((Tq,), bool)
    out_holder = {}

    def do_query():
        out_holder["out"] = query(holder["st"], ws, we, mask, ic)

    def sync_q():
        jax.device_get(out_holder["out"][0][0])

    live_thunks.append(lambda: out_holder.get("out"))
    r = _time_phase(do_query, sync_q, iters, drain=drain)
    r["windows_per_s"] = _rate(Tq, r["mean_ms"])
    results["query"] = r

    # ---- annex merge ------------------------------------------------------
    merge = jax.jit(ec.build_annex_merge(spec, C, A), donate_argnums=0)

    def do_merge():
        holder["st"] = merge(holder["st"])

    results["annex_merge"] = _time_phase(do_merge, sync, iters, drain=drain)

    # ---- aligned fused interval ------------------------------------------
    p = AlignedStreamPipeline(
        [window], [SumAggregation()],
        config=EngineConfig(capacity=C, annex_capacity=8, min_trigger_pad=32),
        throughput=throughput, wm_period_ms=wm_period, gc_every=8, seed=seed)
    p.reset()
    p.run(2, collect=False)        # compile + warm
    p.sync()

    def do_aligned():
        p.run(1, collect=False)

    def _pipeline_drain():
        p.sync()
        return None

    live_thunks.append(_pipeline_drain)
    r = _time_phase(do_aligned, lambda: p.sync(), iters, drain=drain)
    r["tuples_per_s"] = _rate(p.tuples_per_interval, r["mean_ms"])
    results["ingest_aligned"] = r
    p.check_overflow()

    # ---- host packing (no device work) ------------------------------------
    K = 64
    Np = B
    keys = rng.integers(0, K, size=Np).astype(np.int32)
    kts = np.sort(rng.integers(0, 1 << 20, size=Np)).astype(np.int64)
    kvals = rng.random(Np).astype(np.float32)

    def do_pack():
        order = np.lexsort((kts, keys))
        k2, v2, t2 = keys[order], kvals[order], kts[order]
        counts = np.bincount(k2, minlength=K)
        starts = np.zeros(K, np.int64)
        starts[1:] = np.cumsum(counts)[:-1]
        pos = np.arange(t2.size, dtype=np.int64) - starts[k2]
        Bk = 1 << 10
        rnd, lane = pos // Bk, pos % Bk
        m = rnd == 0
        ts_b = np.zeros((K, Bk), np.int64)
        ts_b[k2[m], lane[m]] = t2[m]
        return ts_b

    r = _time_phase(do_pack, lambda: None, iters, drain=drain)
    r["tuples_per_s"] = _rate(Np, r["mean_ms"])
    results["host_pack"] = r

    # ---- raw scatter costs (the numbers behind docs/DESIGN.md's "no
    # int64 scatter on the hot path" decisions) ----------------------------
    Bs = B
    pos = jnp.asarray(rng.integers(0, C, size=Bs).astype(np.int32))
    fv = jnp.asarray(rng.random(Bs).astype(np.float32))
    iv = jnp.asarray(rng.integers(0, 1 << 40, size=Bs).astype(np.int64))
    sc_holder = {
        "f32": jnp.zeros((C,), jnp.float32),
        "i64": jnp.full((C,), np.int64(1) << 60),
    }
    scatter_f32 = jax.jit(lambda a: a.at[pos].add(fv), donate_argnums=0)
    scatter_i64 = jax.jit(lambda a: a.at[pos].min(iv), donate_argnums=0)

    def do_sf():
        sc_holder["f32"] = scatter_f32(sc_holder["f32"])

    live_thunks.append(lambda: (sc_holder["f32"], sc_holder["i64"]))
    r = _time_phase(do_sf, lambda: jax.device_get(sc_holder["f32"][0]),
                    iters, drain=drain)
    r["lanes"] = Bs
    results["scatter_f32_add"] = r

    def do_si():
        sc_holder["i64"] = scatter_i64(sc_holder["i64"])

    r = _time_phase(do_si, lambda: jax.device_get(sc_holder["i64"][0]),
                    iters, drain=drain)
    r["lanes"] = Bs
    results["scatter_i64_min"] = r

    # ---- shaper sort-and-split kernel alone (ISSUE 5) --------------------
    from ..shaper.device import I64_MIN, init_shaper_stats, \
        sort_split_kernel

    late_cap = max(64, B // 8)
    ss_kern = sort_split_kernel(B, late_cap)
    ts_ooo = rng.integers(0, B * 2, size=B).astype(np.int64)  # UNSORTED
    ss_holder = {"stats": init_shaper_stats()}
    cut0 = np.int64(I64_MIN)

    def do_ss():
        out = ss_kern(ss_holder["stats"], ts_ooo, vals, valid, cut0, cut0)
        ss_holder["stats"] = out[0]
        ss_holder["out"] = out[1:]

    def sync_ss():
        jax.device_get(ss_holder["out"][0][0])

    live_thunks.append(lambda: (ss_holder["stats"],
                                ss_holder.get("out")))
    r = _time_phase(do_ss, sync_ss, iters, drain=drain)
    r["tuples_per_s"] = _rate(B, r["mean_ms"])
    results["shape_sort_split"] = r

    # ---- shaped OOO ingest end-to-end (ISSUE 5) --------------------------
    # the SAME disordered device-resident stream class ingest_scatter
    # pays the general kernel for: per-batch uniform draws (unsorted
    # arrival order) with a bounded back-reach into the previous batch's
    # range, taken through StreamShaper.shape_device_batch — sort-split
    # + dense/in-order ingest + the small late-residue dispatch
    from ..autotune import EngineGeometry
    from ..engine import TpuWindowOperator
    from ..shaper import StreamShaper

    from ..core.windows import TumblingWindow

    span = 2 * B                    # event-ms per batch (ingest_scatter's)
    back = max(1, span // 32)       # bounded inter-batch disorder reach
    # the shaped arm's engine + shaper configs derive from one geometry
    # (geometry-discipline): coupled knobs move as a single value
    geom_sh = EngineGeometry(capacity=C, batch_size=B,
                             min_trigger_pad=32, late_capacity=late_cap)
    op_sh = TpuWindowOperator(config=geom_sh.engine_config(
        EngineConfig(annex_capacity=A)))
    # a window whose grid keeps ~iters un-GC'd batches inside `capacity`
    # (the timed loop never watermarks; the grid-1 sliding spec of the
    # scatter cell would blow the slice buffer at full shapes)
    w_grid = max(1000, span // 8)
    op_sh.add_window_assigner(TumblingWindow(WindowMeasure.Time, w_grid))
    op_sh.add_aggregation(SumAggregation())
    op_sh.set_max_lateness(span + back)
    shaper = StreamShaper(op_sh, geom_sh.shaper_config())
    ts_sh = rng.integers(0, span + back, size=B).astype(np.int64)
    sh2 = {"i": 1}                  # start a span in so ts never go < 0

    def do_shaped():
        off = sh2["i"] * span
        sh2["i"] += 1
        # batch i covers [i*span - back, i*span + span): the `back` head
        # reaches into batch i-1's range — the actually-late fraction
        shaper.shape_device_batch(vals, ts_sh + (off - back),
                                  off - back, off + span)

    def sync_sh():
        jax.device_get(op_sh._state.n_slices)

    live_thunks.append(lambda: op_sh._state)
    r = _time_phase(do_shaped, sync_sh, iters, drain=drain)
    r["tuples_per_s"] = _rate(B, r["mean_ms"])
    r["late_capacity"] = late_cap
    if results["ingest_scatter"]["mean_ms"] > 0:
        r["speedup_vs_scatter"] = (results["ingest_scatter"]["mean_ms"]
                                   / r["mean_ms"])
    results["ingest_shaped_ooo"] = r
    shaper.check()
    op_sh.check_overflow()

    # ---- Pallas vs XLA twins (ISSUE 15) ----------------------------------
    # Correctness is the claim these cells certify on CPU: both arms run
    # the identical stream, the Pallas arm under interpreter mode
    # (pl.pallas_call(..., interpret=True) — resolve_interpret picks it
    # on every non-TPU backend), honestly tagged. The relative timing of
    # an interpreted kernel against native XLA says nothing about TPU
    # speed — those floors stay TPU-box certifications (PR 5/7/10
    # discipline) — so the recorded comparator is bit-equality plus the
    # per-dispatch means, both platform-tagged.
    from .. import pallas as _spl

    Bp = min(B, 1 << 14)                 # bitonic network depth ~ log^2 B
    late_p = max(64, Bp // 8)
    ts_p = rng.integers(0, Bp * 2, size=Bp).astype(np.int64)
    vals_p = rng.random(Bp).astype(np.float32)
    valid_p = np.ones((Bp,), bool)
    cut_p = np.int64(Bp)                 # half the span is "late"

    from ..shaper.device import build_sort_split, init_shaper_stats

    ss_xla = jax.jit(build_sort_split(Bp, late_p), donate_argnums=0)
    ss_pls = jax.jit(_spl.build_pallas_sort_split(Bp, late_p),
                     donate_argnums=0)
    hold = {"sx": init_shaper_stats(), "sp": init_shaper_stats()}

    def do_ss_xla():
        out = ss_xla(hold["sx"], ts_p, vals_p, valid_p, cut_p, cut_p)
        hold["sx"], hold["ox"] = out[0], out[1:]

    def do_ss_pls():
        out = ss_pls(hold["sp"], ts_p, vals_p, valid_p, cut_p, cut_p,
                     np.int64(0))
        hold["sp"], hold["op"] = out[0], out[1:]

    live_thunks.append(lambda: (hold.get("ox"), hold.get("op")))
    r = _time_phase(do_ss_xla, lambda: jax.device_get(hold["ox"][0][0]),
                    iters, drain=drain)
    r["tuples_per_s"] = _rate(Bp, r["mean_ms"])
    r["lanes"] = Bp
    results["sort_split_xla_twin"] = r
    r = _time_phase(do_ss_pls, lambda: jax.device_get(hold["op"][0][0]),
                    iters, drain=drain)
    r["tuples_per_s"] = _rate(Bp, r["mean_ms"])
    r["lanes"] = Bp
    r["pallas_interpret"] = _spl.resolve_interpret(None)
    r["bit_match_vs_xla"] = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.device_get(hold["ox"]),
                        jax.device_get(hold["op"])))
    results["sort_split_pallas"] = r

    # segmented fold: per-row reduce of an [rows, lanes] value block —
    # the aligned/keyed/mesh lift shape (equal segments by construction)
    rows_f, lanes_f = 256, 1024
    flat_f = jnp.asarray(rng.integers(0, 1 << 10, size=(
        rows_f * lanes_f, 1)).astype(np.float32))
    fold_xla = jax.jit(lambda v: jnp.sum(
        v.reshape(rows_f, lanes_f, 1), axis=1))
    fold_pls = jax.jit(lambda v: _spl.row_fold(
        v, rows_f, lanes_f, "sum", 0.0))
    fhold: dict = {}

    def do_f_xla():
        fhold["x"] = fold_xla(flat_f)

    def do_f_pls():
        fhold["p"] = fold_pls(flat_f)

    live_thunks.append(lambda: (fhold.get("x"), fhold.get("p")))
    r = _time_phase(do_f_xla, lambda: jax.device_get(fhold["x"][0][0]),
                    iters, drain=drain)
    r["tuples_per_s"] = _rate(rows_f * lanes_f, r["mean_ms"])
    results["segment_fold_xla_twin"] = r
    r = _time_phase(do_f_pls, lambda: jax.device_get(fhold["p"][0][0]),
                    iters, drain=drain)
    r["tuples_per_s"] = _rate(rows_f * lanes_f, r["mean_ms"])
    r["rows"], r["lanes"] = rows_f, lanes_f
    r["pallas_interpret"] = _spl.resolve_interpret(None)
    r["bit_match_vs_xla"] = bool(np.array_equal(
        np.asarray(jax.device_get(fhold["x"])),
        np.asarray(jax.device_get(fhold["p"]))))
    results["segment_fold_pallas"] = r

    results["platform"] = jax.devices()[0].platform
    return results


def main(argv: Optional[list] = None, echo=None) -> int:
    import argparse
    import os

    from ..utils import stdout_echo

    if echo is None:
        echo = stdout_echo

    ap = argparse.ArgumentParser(prog="python -m scotty_tpu.bench.micro")
    ap.add_argument("--out", default="bench_results/micro.json")
    ap.add_argument("--small", action="store_true",
                    help="CPU-test shapes instead of benchmark shapes")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args(argv)

    res = run_micro(small=args.small, iters=args.iters)
    for phase, r in res.items():
        if not isinstance(r, dict) or "mean_ms" not in r:
            continue
        extra = ""
        if "tuples_per_s" in r:
            extra = f"  {r['tuples_per_s']:16,.0f} tuples/s"
        elif "windows_per_s" in r:
            extra = f"  {r['windows_per_s']:16,.0f} windows/s"
        echo(f"{phase:16s} mean={r['mean_ms']:9.3f} ms/dispatch"
             f"{extra}")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    echo(f"-> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

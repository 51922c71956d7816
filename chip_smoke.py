#!/usr/bin/env python
"""Chip smoke: drive the slicing engine's main paths once on the TPU and
check every result against an independent host reference.

Default (one chip), in order, stopping at the first failure:

(a) host-fed operator — ``TpuWindowOperator`` through its public calls
    at the ``host_fed.json`` deployment (Tumbling(1000)+Sliding(5000,1000)
    sum, batch 65536, capacity 131072, lateness 1000), 10 event-seconds
    at 131072 tuples/s with 5 % of tuples late within the lateness bound
    (``multi_agg_out_of_order.json``'s disorder). Every watermark's
    windows must equal the ``SlicingWindowOperator`` simulator's.
(b) fused headline — ``AlignedStreamPipeline`` at ``bench.py``'s config
    (Sliding(60000,1): 60,000 concurrent windows) with the offered load
    pinned at ``OFFERED_SWEEP[0]``. The first windows close in
    intervals 59 and 60; theirs are recomputed on the host from the
    replayed stream of intervals 0-60, and the intervals after the
    pre-roll must emit.
(c) keyed — ``KeyedAlignedPipeline`` at ``keyed_throughput.json`` (1024
    keys, capacity 128); two keys' windows are recomputed on the host
    from their replayed streams.

``--chips 4`` runs instead (d): the mesh keyed cell at ``mesh_keyed.json``
(65,536 keys over a 4-chip mesh, rebalance on) with its host-oracle and
mid-run-rebalance arms, a check that the cell's pipeline holds one shard
on every chip, and
``GlobalTpuWindowOperator``'s cross-shard combine against the host total.

One process drives the chip. Each phase prints one JSON line; the last
line is ``{"ok": true, "device": {...}}`` and is printed only when every
phase passed on a TPU. Anything else exits non-zero.
"""

import argparse
import json
import pathlib
import sys
import time

import numpy as np

WINDOWS = "Tumbling(1000)+Sliding(5000,1000)"
CONFIGS = pathlib.Path(__file__).resolve().parent / "scotty_tpu" / "bench" \
    / "configurations"


def config(name):
    from scotty_tpu.bench.harness import BenchmarkConfig

    return BenchmarkConfig.from_json(str(CONFIGS / name))


def _windows():
    from scotty_tpu import SlidingWindow, TumblingWindow, WindowMeasure

    return [TumblingWindow(WindowMeasure.Time, 1000),
            SlidingWindow(WindowMeasure.Time, 5000, 1000)]


def _close(want, got, rel):
    return abs(float(want) - float(got)) <= rel * max(1.0, abs(float(want)))


def _compare(r_sim, r_eng, wm):
    """tests/test_engine_differential.py::compare: same windows in the
    same order, same has_value, values within rel 1e-5."""
    if len(r_sim) != len(r_eng):
        raise AssertionError(f"@wm={wm}: simulator emitted {len(r_sim)} "
                             f"windows, engine {len(r_eng)}")
    for a, b in zip(r_sim, r_eng):
        if (a.get_start(), a.get_end(), a.has_value()) != \
                (b.get_start(), b.get_end(), b.has_value()):
            raise AssertionError(f"@wm={wm}: {a} != {b}")
        if a.has_value():
            for x, y in zip(a.get_agg_values(), b.get_agg_values()):
                if not _close(x, y, 1e-5):
                    raise AssertionError(f"@wm={wm}: {a} != {b}")


def host_fed(cfg=None, seconds=10, ooo=0.05, seed=0):
    """Phase (a). Values are small integers so every window sum is exact
    in f32 and the comparison cannot fail on rounding."""
    from scotty_tpu import SlicingWindowOperator, SumAggregation
    from scotty_tpu.engine import EngineConfig, TpuWindowOperator

    cfg = cfg or config("host_fed.json")
    batch, lateness = cfg.batch_size, cfg.max_lateness
    rng = np.random.default_rng(seed)
    n_batches = seconds * cfg.throughput // batch
    span = seconds * 1000 / n_batches
    sim = SlicingWindowOperator()
    eng = TpuWindowOperator(config=EngineConfig(capacity=cfg.capacity,
                                                batch_size=batch))
    for op in (sim, eng):
        for w in _windows():
            op.add_window_assigner(w)
        op.add_aggregation(SumAggregation())
        op.set_max_lateness(lateness)

    t0 = time.perf_counter()
    first_s = None
    head, next_wm, checked, late_total = -1, 1000, 0, 0
    for i in range(n_batches):
        lo = int(i * span)
        ts = np.sort(rng.integers(lo, int((i + 1) * span), size=batch))
        late = rng.random(batch) < ooo
        late_total += int(late.sum())
        ts = np.where(late, np.maximum(
            ts - rng.integers(0, lateness, size=batch), 0), ts)
        vals = rng.integers(1, 16, size=batch).astype(np.float32)
        eng.process_elements(vals, ts)
        sim.process_elements(vals.tolist(), ts.tolist())
        head = max(head, int(ts.max()))
        while next_wm <= head:
            r_eng = eng.process_watermark(next_wm)
            if first_s is None:
                first_s = time.perf_counter() - t0
            _compare(sim.process_watermark(next_wm), r_eng, next_wm)
            checked += sum(w.has_value() for w in r_eng)
            next_wm += 1000
    eng.check_overflow()
    if checked == 0:
        raise AssertionError("host-fed: no window carried a value")
    return {"tuples": n_batches * batch, "late_tuples": late_total,
            "windows_checked": checked, "first_watermark_s": first_s,
            "wall_s": time.perf_counter() - t0}


def _log(msg):
    """Progress on stderr: event, seconds since start, peak host RSS."""
    import resource

    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
    print(f"[chip_smoke {time.perf_counter() - _T0:.1f}s rss {rss:.2f}GiB] "
          f"{msg}", file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def _row_sums(p):
    """A function of ``i``: every slice row's sum in interval ``i`` of an
    aligned pipeline — the pipeline's own RNG replay reduced by a plain
    ``jnp.sum`` in a separate program, so only ``S`` sums cross to the
    host (the 800 M values of an interval would not). The host builds
    every window from these."""
    import jax
    import jax.numpy as jnp

    if p._n_sub != 1 or p.legacy_generator or p.out_of_order_pct:
        raise ValueError("row replay covers the plain paced generator only")
    fn = jax.jit(lambda k, rows: jnp.sum(p._gen_rows(k, rows), axis=1))
    rows = jnp.arange(p.S, dtype=jnp.int64)
    return lambda i: np.asarray(jax.device_get(
        fn(p._interval_key(i), rows)), np.float64)


def headline(offered=None, extra=3):
    """Phase (b): bench.py's pipeline, offered load pinned. A sliding
    window is emitted only once its whole 60 s span lies at or after
    time 0, so the first windows close in intervals 59 (one) and 60 (one
    per slide); both are recomputed from the host sums of all 61
    intervals' replayed rows."""
    import jax

    import bench

    offered = offered or bench.OFFERED_SWEEP[0]
    p = bench.build(offered)
    P, g, R = p.wm_period_ms, p.grid, p.R
    (w,) = p.windows
    first = int(w.size) // P - 1
    p.reset()
    t0 = time.perf_counter()
    p.run(1, collect=False)
    p.sync()
    first_s = time.perf_counter() - t0
    _log("headline: first interval done")
    p.run(first - 1, collect=False)
    checked_outs = p.run(2, collect=True)
    p.run(bench.WARMUP_INTERVALS - first - 2, collect=False)
    outs = p.run(extra, collect=True)
    p.sync()
    run_s = time.perf_counter() - t0
    cnts = jax.device_get([o[2] for o in outs])
    emitted = int(sum(int((c > 0).sum()) for c in cnts))
    p.check_overflow()
    if emitted == 0:
        raise AssertionError("headline: no window emitted after pre-roll")
    _log(f"headline: {bench.WARMUP_INTERVALS + extra} intervals run")

    t1 = time.perf_counter()
    row_sums = _row_sums(p)
    sums = np.concatenate([row_sums(i) for i in range(first + 2)])
    prefix = np.concatenate([[0.0], np.cumsum(sums)])
    checked = 0
    for i, out in zip((first, first + 1), checked_outs):
        want = {}
        for s, e in zip(*w.trigger_arrays(i * P, (i + 1) * P)):
            # the stream seen so far: a window may end one past the
            # watermark (the reference's trigger rule), past the data
            hi = min(int(e), (i + 1) * P) // g
            want[(int(s), int(e))] = ((hi - s // g) * R,
                                      prefix[hi] - prefix[s // g])
        got = {(s, e): (c, v[0])
               for (s, e, c, v) in p.lowered_results(out)}
        if not want or set(got) != set(want):
            raise AssertionError(
                f"headline interval {i}: {len(got)} windows emitted, "
                f"{len(want)} expected")
        for k, (c, v) in want.items():
            if got[k][0] != c or not _close(v, got[k][1], 2e-4):
                raise AssertionError(f"headline {k}: {got[k]} != {(c, v)}")
        checked += len(want)
    return {"offered_per_event_s": offered,
            "intervals": bench.WARMUP_INTERVALS + extra,
            "tuples_per_interval": p.tuples_per_interval,
            "windows_checked": checked, "windows_emitted": emitted,
            "first_interval_s": first_s, "run_s": run_s,
            "check_s": time.perf_counter() - t1,
            "wall_s": time.perf_counter() - t0}


def keyed(cfg=None, intervals=7):
    """Phase (c): every emitted window of two keys against the host sum
    of that key's replayed stream (Sliding(5000) needs 5 intervals)."""
    from scotty_tpu import SumAggregation
    from scotty_tpu.engine import EngineConfig
    from scotty_tpu.parallel.keyed import KeyedAlignedPipeline

    cfg = cfg or config("keyed_throughput.json")
    n_keys = cfg.n_keys
    p = KeyedAlignedPipeline(_windows(), [SumAggregation()], n_keys=n_keys,
                             config=EngineConfig(capacity=cfg.capacity),
                             throughput=cfg.throughput,
                             wm_period_ms=cfg.watermark_period_ms,
                             max_lateness=cfg.max_lateness, seed=cfg.seed)
    keys = (0, n_keys - 1)
    streams = {k: ([], []) for k in keys}
    p.reset()
    t0 = time.perf_counter()
    first_s = None
    checked = 0
    for i in range(intervals):
        out = p.run(1)[0]
        for k in keys:
            v, t = p.materialize_interval(i, k)
            streams[k][0].append(v)
            streams[k][1].append(t)
            vals = np.concatenate(streams[k][0]).astype(np.float64)
            ts = np.concatenate(streams[k][1])
            for (s, e, c, r) in p.lowered_results_for_key(out, k):
                m = (ts >= s) & (ts < e)
                if c != int(m.sum()) or not _close(vals[m].sum(), r[0],
                                                   2e-4):
                    raise AssertionError(
                        f"keyed key {k} [{s},{e}): ({c}, {r[0]}) != "
                        f"({int(m.sum())}, {vals[m].sum()})")
                checked += 1
        if first_s is None:
            first_s = time.perf_counter() - t0
    p.check_overflow()
    if checked == 0:
        raise AssertionError("keyed: no window emitted")
    return {"n_keys": n_keys, "intervals": intervals,
            "tuples_per_interval": p.tuples_per_interval,
            "windows_checked": checked, "first_interval_s": first_s,
            "wall_s": time.perf_counter() - t0}


def mesh(n_chips=4, cfg=None):
    """Phase (d): the mesh keyed cell and the global combine over
    ``n_chips`` devices, one shard per chip."""
    import jax
    from jax.sharding import Mesh

    from scotty_tpu import SumAggregation
    from scotty_tpu.bench.runner import run_mesh_keyed_cell
    from scotty_tpu.engine import EngineConfig
    from scotty_tpu.parallel import GlobalTpuWindowOperator

    devs = jax.devices()[:n_chips]
    t0 = time.perf_counter()
    cfg = cfg or config("mesh_keyed.json")
    cfg.n_shards = n_chips
    r = run_mesh_keyed_cell(cfg, WINDOWS, "sum")
    _log("mesh: keyed cell done")
    if not (r.oracle_match and r.rebalance_match):
        raise AssertionError(f"mesh cell: oracle_match={r.oracle_match} "
                             f"rebalance_match={r.rebalance_match}")

    placement = [tuple(x) for x in r.shard_placement]
    if placement != [(i, cfg.n_keys // n_chips)
                     for i in sorted(d.id for d in devs)]:
        raise AssertionError(f"mesh state not one shard per chip: "
                             f"{placement}")

    rng = np.random.default_rng(0)
    gop = GlobalTpuWindowOperator(
        n_shards=n_chips, mesh=Mesh(np.array(devs), ("shards",)),
        config=EngineConfig(capacity=512, batch_size=1024))
    gop.add_window_assigner(_windows()[0])
    gop.add_aggregation(SumAggregation())
    total = want = 0.0
    for sec in range(10):
        ts = np.sort(rng.integers(sec * 1000, (sec + 1) * 1000, size=8192))
        vals = rng.integers(1, 16, size=ts.size).astype(np.float32)
        gop.process_elements(vals, ts)
        want += float(vals.sum(dtype=np.float64))
        total += sum(float(w.get_agg_values()[0])
                     for w in gop.process_watermark((sec + 1) * 1000)
                     if w.has_value())
    if total != want:
        raise AssertionError(f"global combine {total} != host {want}")
    return {"n_keys": r.n_keys, "n_shards": r.n_shards,
            "shard_placement": r.shard_placement,
            "windows_emitted": r.n_windows_emitted,
            "oracle_match": r.oracle_match,
            "rebalance_match": r.rebalance_match,
            "global_total": float(total), "wall_s": time.perf_counter() - t0}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh path (d) instead of (a)-(c)")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    phases = ([("mesh", lambda: mesh(args.chips))] if args.chips == 4 else
              [("host_fed", host_fed), ("headline", headline),
               ("keyed", keyed)])
    for name, run in phases:
        _log(f"{name}: start")
        info = run()
        print(json.dumps({"phase": name, "ok": True, **info}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

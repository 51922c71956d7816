"""Run one benchmark cell once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process: make the cell's traffic from the seed, build the cell's
entry, warm its shapes (from the compile cache in
``benchmark/.jax_cache``) with a pre-roll of one window span, then hand
the entry one chunk (one watermark period of event time) and its
watermark at a time, as fast as it accepts them, for ``--seconds``. Afterwards the plain reference
replays the same hand-offs and every window the timed watermarks
emitted is compared with it.

``--trace 1`` takes a profiler trace of the window and prints the
cell's per-layer metrics instead of its end-to-end ones.

Options for rehearsal and tests only: ``--allow-cpu`` (run without an
accelerator), ``--tiny`` (the configuration's ``rehearsal`` sizes) and
``--control bfloat16`` (compare the reference computed in bfloat16 in
place of the program's answers; it must read ``correct: false``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import traffic  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--control", choices=("bfloat16",), default=None)
    return ap.parse_args(argv)


def check_devices(jax, chips: int, allow_cpu: bool):
    devs = jax.devices()
    if devs[0].platform == "cpu" and not allow_cpu:
        raise SystemExit("benchmark: JAX found no accelerator")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"sees {len(devs)}")
    return devs[:chips]


def run_cell(args, entry_hook=None):
    """One run; returns the result dict. ``entry_hook(entry)`` lets a
    test break the timed path underneath."""
    bench = harness.load_benchmark()
    cell, config = harness.find_cell(bench, args.workload)
    config = harness.scaled(config, args.tiny)
    phases = {}
    jax = harness.setup_jax()
    devs = check_devices(jax, int(cell["chips"]), args.allow_cpu)
    compiles = harness.Compiles()
    phases["jax_init"] = time.perf_counter() - T_START

    mix = traffic.load(cell["traffic"])
    pool = traffic.make_pool(config, mix, args.seed)
    pools = {"pre": pool.strided(int(mix["preroll_stride"])), "main": pool}
    windows = harness.windows_of(config)
    entry_mod = harness.load_module(HERE / "entries" / f"{mix['entry']}.py")
    phases["traffic"] = time.perf_counter() - T_START
    entry = entry_mod.build(config, mix, pools, windows)
    phases["entry"] = time.perf_counter() - T_START
    if entry_hook is not None:
        entry_hook(entry)
    period = pool.period_ms
    widest = max(int(w["size"]) for w in windows)
    n_pre = -(-widest // period) + int(mix.get("preroll_extra_chunks", 2))

    log = []                               # hand-offs, in order
    spans = harness.Spans()
    for c in range(n_pre):
        entry.ingest(c, "pre")
        entry.watermark((c + 2) * period)
        log.append(("pre", c, (c + 2) * period))
        if c == 0:
            phases["first_preroll_chunk"] = time.perf_counter() - T_START
    counters0 = entry.counters()
    compiles0 = compiles.n

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        spans.annotate = True
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    got, lat = [], []
    tuples = 0
    c = n_pre
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    while True:
        with spans("ingest"):
            tuples += entry.ingest(c, "main")
        wm = (c + 2) * period
        with spans("watermark"):
            got.append(entry.watermark(wm))
        lat.append(spans.t["watermark"][-1])
        log.append(("main", c, wm))
        c += 1
        if time.perf_counter() - t0 >= args.seconds:
            break
    t1 = time.perf_counter()
    if args.trace:
        jax.profiler.stop_trace()
        spans.annotate = False
    window_s = t1 - t0
    counters1 = entry.counters()
    compiled_in_window = compiles.n - compiles0
    mem = [d.memory_stats() or {} for d in devs]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    entry.finish()
    del entry
    gc.collect()

    # -- the plain reference, after the window --------------------------
    ref_mod = harness.load_module(
        HERE / "reference" / f"{config['reference']}.py")
    aggs = list(config["aggregations"])
    n_timed = len(got)

    def replay(precision):
        ref = ref_mod.WindowReference(windows, aggs,
                                      int(config["max_lateness_ms"]),
                                      precision=precision)
        bins = {k: [ref_mod.bins_of(t, v, aggs, precision)
                    for t, v in zip(pl.ts, pl.vals)]
                for k, pl in pools.items()}
        out = []
        for which, c_, wm in log:
            p, off = pool.chunk(c_)
            ref.arrive(off, bins[which][p])
            out.append(ref.watermark(wm))
        return out[-n_timed:]

    t_ref = time.perf_counter()
    want = replay("float64")
    if args.control:
        got = replay(args.control)
    checks, attempted, failed = harness.compare(got, want, aggs)
    ref_s = time.perf_counter() - t_ref
    limits = config["limits"]
    shed = counters1.get("ring_shed", 0) - counters0.get("ring_shed", 0)
    failed += int(shed > 0)
    correct = failed == 0 and all(checks[k] <= limits[k] for k in checks)

    metrics = {}
    dev0 = devs[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed)}
    if not args.trace:
        lat_ms = np.asarray(lat) * 1e3
        values = {"throughput": tuples / window_s,
                  "emit_p95_ms": float(np.percentile(lat_ms, 95)),
                  "emit_p50_ms": float(np.percentile(lat_ms, 50)),
                  "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if args.workload in m.get("workloads", [args.workload]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        trace_mod = harness.load_module(HERE / "trace.py")
        tr = trace_mod.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        ctx = trace_mod.Context(
            trace=tr, spans=spans.t, window_s=window_s, tuples=tuples,
            watermarks=n_timed, config=config,
            counters={k: counters1[k] - counters0.get(k, 0)
                      for k in counters1},
            device_kind=dev0.device_kind)
        for m in bench["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            reader = harness.load_module(HERE / "metrics" / f"{m['name']}.py")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["breakdown"] = tr.breakdown()
        trace_notes = {
            "launched_s": {k: tr.launched_device_s(k) for k in spans.t},
            "layer_s": {k: tr.layer_device_s(k)
                        for k in ("ingest", "watermark", "benchmark",
                                  "unattributed")},
            "modules": len(tr.modules)}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in checks}
    result["checks"]["failed"] = {"value": int(failed), "limit": 0}
    notes = {"tuples": tuples, "watermarks": n_timed,
             "setup_phases_s": {k: round(v, 3) for k, v in phases.items()},
             "emit_ms": [round(x * 1e3, 2) for x in lat],
             "compiles_in_window": compiled_in_window,
             "reference_s": round(ref_s, 3), "counters": counters1,
             "spans_s": {k: float(np.sum(v)) for k, v in spans.t.items()}}
    if args.trace:
        notes["trace"] = trace_notes
    return result, notes


def main(argv=None):
    args = parse(argv)
    result, notes = run_cell(args)
    harness.eprint("notes " + json.dumps(notes, default=str))
    for k, v in result["checks"].items():
        harness.eprint(f"check {k} {v['value']} limit {v['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

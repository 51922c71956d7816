"""Bucket-baseline correctness + config-driven runner end-to-end (CPU)."""

import json
import os

import numpy as np
import pytest

from scotty_tpu import (
    DDSketchQuantileAggregation,
    MaxAggregation,
    SlidingWindow,
    SumAggregation,
    TumblingWindow,
    WindowMeasure,
)
from scotty_tpu.bench.buckets import BucketWindowPipeline
from scotty_tpu.engine import EngineConfig
from scotty_tpu.engine.pipeline import AlignedStreamPipeline

Time = WindowMeasure.Time
CFG = EngineConfig(capacity=1 << 12, annex_capacity=8, min_trigger_pad=32)


def test_buckets_match_aligned():
    """Same generator stream; no sharing vs slicing must agree per window."""
    windows = [SlidingWindow(Time, 60, 20), TumblingWindow(Time, 50)]
    mk = lambda: [SumAggregation(), MaxAggregation()]  # noqa: E731
    a = AlignedStreamPipeline(windows, mk(), config=CFG, throughput=3000,
                              wm_period_ms=100, gc_every=10 ** 9)
    b = BucketWindowPipeline(windows, mk(), throughput=3000,
                             wm_period_ms=100, chunk=1 << 10)
    a.reset()
    b.reset()
    for i in range(6):
        ra = a.lowered_results(a.run(1)[0])
        rb = b.lowered_results(b.run(1)[0])
        assert [(s, e, c) for s, e, c, _ in ra] == \
            [(s, e, c) for s, e, c, _ in rb], (i, ra, rb)
        for (_, _, _, va), (_, _, _, vb) in zip(ra, rb):
            for x, y in zip(va, vb):
                assert float(x) == pytest.approx(float(y), rel=1e-4)


def test_buckets_prefill_equals_run():
    windows = [TumblingWindow(Time, 40)]
    b1 = BucketWindowPipeline(windows, [SumAggregation()], throughput=2000,
                              wm_period_ms=40, chunk=1 << 10)
    b2 = BucketWindowPipeline(windows, [SumAggregation()], throughput=2000,
                              wm_period_ms=40, chunk=1 << 10)
    b1.reset()
    b2.reset()
    b1.prefill(4)
    b2.run(4, collect=False)
    r1 = b1.lowered_results(b1.run(1)[0])
    r2 = b2.lowered_results(b2.run(1)[0])
    assert r1 == r2


def test_aligned_sketch_quantile():
    """Sparse (one-hot densified) sketch lift on the aligned pipeline:
    uniform values → median ≈ scale/2 within DDSketch relative accuracy."""
    p = AlignedStreamPipeline(
        [TumblingWindow(Time, 50)], [DDSketchQuantileAggregation(0.5)],
        config=CFG, throughput=20_000, wm_period_ms=100, gc_every=10 ** 9)
    p.reset()
    rows = []
    for i in range(3):
        rows += p.lowered_results(p.run(1)[0])
    assert rows, "no windows emitted"
    for (_s, _e, c, vals) in rows:
        assert c == 1000                      # 50 ms × 20 tuples/ms
        assert vals[0] == pytest.approx(5000, rel=0.25)


def test_runner_end_to_end(tmp_path):
    """python -m scotty_tpu.bench on a tiny config: every cell completes,
    emits windows, and writes result_<name>.json."""
    cfg_path = tmp_path / "tiny.json"
    cfg_path.write_text(json.dumps({
        "name": "tiny",
        "throughput": 30_000,
        "bucketsThroughput": 10_000,
        "runtime": 3,
        "windowConfigurations": ["Sliding(60,20)", "Tumbling(50)"],
        "configurations": ["TpuEngine", "Buckets"],
        "aggFunctions": ["sum"],
        "watermarkPeriodMs": 100,
        "capacity": 4096,
    }))
    from scotty_tpu.bench import load_config, run_config

    cfg = load_config(str(cfg_path))
    rows = run_config(cfg, out_dir=str(tmp_path / "out"),
                      echo=lambda *a, **k: None)
    assert len(rows) == 4                     # 2 windows × 2 engines × 1 agg
    for row in rows:
        assert row["tuples_per_sec"] > 0
        assert row["windows_emitted"] > 0, row
        assert row["p99_emit_ms"] > 0
    out = tmp_path / "out" / "result_tiny.json"
    assert out.exists()
    assert len(json.loads(out.read_text())) == 4


def test_runner_serve_and_flight_flags(tmp_path, capsys):
    """--serve-port/--flight-capacity (ISSUE 4 satellite): the runner
    starts the live endpoint for the run, attaches a flight recorder to
    every cell's Observability, and the run completes with the endpoint
    announced and the server torn down."""
    cfg_path = tmp_path / "flight.json"
    cfg_path.write_text(json.dumps({
        "name": "flight",
        "throughput": 30_000,
        "runtime": 2,
        "windowConfigurations": ["Tumbling(50)"],
        "configurations": ["TpuEngine"],
        "aggFunctions": ["sum"],
        "watermarkPeriodMs": 100,
        "capacity": 4096,
    }))
    from scotty_tpu.bench.runner import main as runner_main

    rc = runner_main([str(cfg_path), "--out-dir", str(tmp_path / "out"),
                      "--serve-port", "0", "--flight-capacity", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "live obs endpoint: http://127.0.0.1:" in out
    rows = json.loads((tmp_path / "out" / "result_flight.json").read_text())
    assert len(rows) == 1 and "error" not in rows[0]
    # the flight recorder rode the cell: a 2-slot ring wraps on the very
    # first drain sample, and the wraparound count is REPORTED in the
    # cell's embedded metrics (the obs diff gate sees it) — never silent
    assert rows[0]["metrics"]["metrics"]["flight_dropped_events"] > 0


def test_runner_ooo_fallback(tmp_path):
    """outOfOrderPct > 0 routes to the batch-at-a-time annex path."""
    cfg_path = tmp_path / "ooo.json"
    cfg_path.write_text(json.dumps({
        "name": "ooo",
        "throughput": 20_000,
        "runtime": 2,
        "windowConfigurations": ["Tumbling(200)"],
        "configurations": ["TpuEngine"],
        "aggFunctions": ["sum"],
        "watermarkPeriodMs": 500,
        "batchSize": 4096,
        "capacity": 4096,
        "outOfOrderPct": 0.05,
        "maxLateness": 1000,
    }))
    from scotty_tpu.bench import load_config, run_config

    cfg = load_config(str(cfg_path))
    rows = run_config(cfg, out_dir=str(tmp_path / "out"),
                      echo=lambda *a, **k: None)
    assert rows[0]["windows_emitted"] > 0


def test_micro_suite_small():
    """Per-phase microbenchmarks run and report every phase (VERDICT r1
    item 9 — SlicingWindowOperatorBenchmark.java:37-52 analogue)."""
    from scotty_tpu.bench.micro import run_micro

    res = run_micro(small=True, iters=1)
    for phase in ("ingest_scatter", "ingest_aligned", "query",
                  "annex_merge", "gc", "host_pack"):
        assert phase in res, phase
        assert res[phase]["mean_ms"] > 0
    assert res["ingest_scatter"]["tuples_per_s"] > 0
    assert res["query"]["windows_per_s"] > 0


def test_band_spec_runs_through_fused_stream_pipeline():
    """FixedBand specs can't use the slice-aligned pipeline; they must still
    run fused (one dispatch per interval via StreamPipeline), not
    batch-at-a-time (VERDICT r1: StreamPipeline was dead code)."""
    from scotty_tpu.bench.harness import BenchmarkConfig
    from scotty_tpu.bench.runner import run_cell

    cfg = BenchmarkConfig(name="band", throughput=100_000, runtime_s=3,
                          batch_size=1 << 12, capacity=1 << 12,
                          watermark_period_ms=1000)
    res = run_cell(cfg, "FixedBand(500,1000)+Tumbling(1000)", "sum",
                   "TpuEngine")
    assert res.n_windows_emitted > 0
    assert res.tuples_per_sec > 0


def test_charts_render_from_results(tmp_path):
    """Chart generation consumes the runner's JSON schema and writes both
    figures (charts/*.png parity with the reference README figures)."""
    import json

    matplotlib = pytest.importorskip("matplotlib")  # noqa: F841
    from scotty_tpu.bench.charts import main as charts_main

    res = tmp_path / "results"
    res.mkdir()
    sliding = []
    for sl in (60000, 10000, 1000, 500, 250, 100, 1):
        for eng, tps in (("TpuEngine", 4e9), ("Buckets", 5e5)):
            sliding.append({"windows": f"Sliding(60000,{sl})",
                            "engine": eng, "tuples_per_sec": tps})
    (res / "result_sliding-suite.json").write_text(json.dumps(sliding))
    tumbling = []
    for n in (1, 10, 100, 1000):
        for eng, tps in (("TpuEngine", 4e9), ("Buckets", 2e6)):
            tumbling.append({"windows": f"randomTumbling({n},1000,20000)",
                             "engine": eng, "tuples_per_sec": tps})
    (res / "result_random-tumbling.json").write_text(json.dumps(tumbling))

    out = tmp_path / "charts"
    charts_main(results_dir=str(res), out_dir=str(out))
    assert (out / "sliding_suite.png").stat().st_size > 10_000
    assert (out / "concurrent_tumbling.png").stat().st_size > 10_000


def test_runner_count_measure_cells(tmp_path):
    """Count-measure cells (VERDICT r3 item 6): the randomCount DSL routes
    through the record-buffer path, in-order AND out-of-order, including
    the r4 count+time OOO mix — small shapes of
    bench/configurations/count_measure*.json."""
    import json as _json

    from scotty_tpu.bench import load_config, run_config

    for ooo in (0.0, 0.05):
        cfg_path = tmp_path / f"count{int(ooo*100)}.json"
        cfg_path.write_text(_json.dumps({
            "name": f"count{int(ooo*100)}",
            "throughput": 20_000,
            "runtime": 3,
            "windowConfigurations": ["CountTumbling(70)",
                                     "CountTumbling(70)+Tumbling(500)"],
            "configurations": ["TpuEngine"],
            "aggFunctions": ["sum"],
            "watermarkPeriodMs": 500,
            "batchSize": 4096,
            "capacity": 8192,
            "recordCapacity": 1 << 17,
            "outOfOrderPct": ooo,
            "maxLateness": 1000,
        }))
        cfg = load_config(str(cfg_path))
        rows = run_config(cfg, out_dir=str(tmp_path / "out"),
                          echo=lambda *a, **k: None)
        for row in rows:
            assert "error" not in row, row
            assert row["windows_emitted"] > 0, (ooo, row)
            assert row["tuples_per_sec"] > 0


def test_runner_context_chaos_cells(tmp_path):
    """ISSUE 11: the ContextChaos engine runs all three window classes
    (speculative generic, tuned session, scan-bound capped) at tiny
    shapes with the three-way oracle arm green and the speculative
    telemetry serialized."""
    import json as _json

    from scotty_tpu.bench import load_config, run_config

    cfg_path = tmp_path / "ctx.json"
    cfg_path.write_text(_json.dumps({
        "name": "ctx",
        "throughput": 30_000,
        "runtime": 8,
        "windowConfigurations": ["GenericSession(120)",
                                 "CappedSession(150,400)"],
        "configurations": ["ContextChaos"],
        "aggFunctions": ["sum"],
        "watermarkPeriodMs": 1000,
        "batchSize": 65536,
        "capacity": 1024,
        "outOfOrderPct": 0.2,
        "maxLateness": 1000,
    }))
    rows = run_config(load_config(str(cfg_path)),
                      out_dir=str(tmp_path / "out"),
                      echo=lambda *a, **k: None)
    assert len(rows) == 2
    for row in rows:
        assert "error" not in row, row
        assert row["oracle_match"] and row["scan_match"], row
        assert row["windows_emitted"] > 0 and row["oracle_windows"] > 0
        assert "ctx_fallback_rate" in row
    assert rows[0]["context_mode"] == "speculative"
    assert rows[1]["context_mode"] == "scan"


def test_runner_count_fused_and_ring_fed_cells(tmp_path):
    """ISSUE 11: the CountFused (sliding count + oracle arm) and RingFed
    (external headline + in-program/legacy comparators + generator
    share) engines run end-to-end at tiny shapes."""
    import json as _json

    from scotty_tpu.bench import load_config, run_config

    cfg_path = tmp_path / "sc.json"
    cfg_path.write_text(_json.dumps({
        "name": "sc",
        "throughput": 20_000,
        "runtime": 4,
        "windowConfigurations": ["CountSliding(700,200)"],
        "configurations": ["CountFused"],
        "aggFunctions": ["sum"],
        "watermarkPeriodMs": 500,
        "batchSize": 4096,
        "capacity": 8192,
        "outOfOrderPct": 0.1,
        "maxLateness": 300,
    }))
    rows = run_config(load_config(str(cfg_path)),
                      out_dir=str(tmp_path / "out"),
                      echo=lambda *a, **k: None)
    assert len(rows) == 1 and "error" not in rows[0], rows
    assert rows[0]["oracle_match"] and rows[0]["windows_emitted"] > 0
    assert rows[0]["tuples_per_sec_inorder"] > 0

    cfg_path = tmp_path / "rf.json"
    cfg_path.write_text(_json.dumps({
        "name": "rf",
        "throughput": 200_000,
        "runtime": 4,
        "windowConfigurations": ["Sliding(4000,1000)"],
        "configurations": ["RingFed"],
        "aggFunctions": ["sum"],
        "watermarkPeriodMs": 1000,
        "batchSize": 32768,
        "capacity": 8192,
        "maxLateness": 1000,
    }))
    rows = run_config(load_config(str(cfg_path)),
                      out_dir=str(tmp_path / "out"),
                      echo=lambda *a, **k: None)
    assert len(rows) == 1 and "error" not in rows[0], rows
    row = rows[0]
    assert row["windows_emitted"] > 0
    assert row["inprogram_tps"] > 0 and 0.0 < row["generator_share"] <= 1.0
    assert row["legacy_anchor_tps"] > 0


def test_runner_latency_headline_cell(tmp_path, monkeypatch):
    """ISSUE 14: the LatencyHeadline engine runs end-to-end at a tiny
    shape — full stage decomposition with exact conservation, measured
    first-emit dimension, oracle arm green, and the cell JSON embeds
    the standing latency fields. The interleaved overhead arm is
    monkeypatched (it compiles two extra aligned pipelines — measured
    for real by the recorded artifact, not per CI run)."""
    import json as _json

    from scotty_tpu.bench import load_config, run_config
    from scotty_tpu.bench import runner as _runner

    monkeypatch.setattr(_runner, "measure_latency_overhead",
                        lambda **kw: 0.0)
    cfg_path = tmp_path / "lh.json"
    cfg_path.write_text(_json.dumps({
        "name": "lh",
        "throughput": 100_000,
        "runtime": 4,
        "windowConfigurations": ["Sliding(4000,1000)"],
        "configurations": ["LatencyHeadline"],
        "aggFunctions": ["sum"],
        "watermarkPeriodMs": 1000,
        "batchSize": 16384,
        "capacity": 8192,
        "maxLateness": 1000,
    }))
    rows = run_config(load_config(str(cfg_path)),
                      out_dir=str(tmp_path / "out"),
                      echo=lambda *a, **k: None)
    assert len(rows) == 1 and "error" not in rows[0], rows
    row = rows[0]
    assert row["oracle_match"] and row["oracle_windows"] > 0
    assert row["latency_conservation_ok"]
    assert row["latency_chains"] > 0
    assert row["first_emit_samples"] > 0
    assert row["first_emit_p99_ms"] >= row["first_emit_p50_ms"] > 0
    stages = row["latency_stages_ms"]
    # the full edge decomposes: ring + dispatch + delivery stages
    for s in ("ring_enqueue", "ring_dequeue", "eligibility", "drain",
              "emit", "sink"):
        assert s in stages, (s, sorted(stages))
    # written cell JSON carries the dimension (the standing-field check)
    disk = _json.load(open(tmp_path / "out" / "result_lh.json"))
    assert disk[0]["first_emit_p99_ms"] == row["first_emit_p99_ms"]
    # and `obs latency` attributes the written artifact, exit 0
    from scotty_tpu.obs.report import main as obs_main

    assert obs_main(["latency",
                     str(tmp_path / "out" / "result_lh.json")]) == 0


def test_latency_stats_stall_robust():
    """VERDICT r4 weak #5: a transport stall in the sample set must not be
    the only published percentile — trimmed companion + stall count."""
    import numpy as np

    from scotty_tpu.bench.harness import latency_stats

    lats = [50.0] * 49 + [26720.0]          # one documented transport stall
    s = latency_stats(lats)
    assert s["stall_flagged"]
    assert s["n_stall_samples"] == 1
    assert s["p99_emit_ms_trimmed"] <= 51.0
    assert s["p99_emit_ms"] > 10000          # raw stays honest

    healthy = latency_stats(np.linspace(40, 60, 100))
    assert not healthy["stall_flagged"]
    assert healthy["n_stall_samples"] == 0


def test_assume_inorder_deprecated():
    import pytest

    from scotty_tpu.hybrid import HybridWindowOperator

    with pytest.warns(DeprecationWarning, match="assume_inorder"):
        HybridWindowOperator(assume_inorder=True)

"""Host→device ingest pipeline (SURVEY.md §7 stage 7) tests.

Correctness: a host-resident stream through HostFeed's packed
transfer+unpack path must produce the same windows as the simulator.
Transport: the end-to-end host-fed cell must saturate the raw link —
the engine adds (nearly) nothing on top of device_put of the same bytes.
"""

import numpy as np
import pytest

from scotty_tpu import (
    MeanAggregation,
    SlicingWindowOperator,
    SlidingWindow,
    SumAggregation,
    TumblingWindow,
    WindowMeasure,
)
from scotty_tpu.engine import EngineConfig, TpuWindowOperator
from scotty_tpu.engine.host_ingest import HostFeed, measure_link

Time = WindowMeasure.Time


def test_host_feed_matches_simulator():
    rng = np.random.default_rng(3)
    B = 256
    windows = [TumblingWindow(Time, 100), SlidingWindow(Time, 300, 100)]
    op = TpuWindowOperator(config=EngineConfig(
        capacity=1 << 10, batch_size=B, annex_capacity=8,
        min_trigger_pad=32))
    sim = SlicingWindowOperator()
    for o in (op, sim):
        for w in windows:
            o.add_window_assigner(w)
        o.add_aggregation(SumAggregation())
        o.add_aggregation(MeanAggregation())
        o.set_max_lateness(100)
    feed = HostFeed(op)

    next_wm = 100
    for i in range(8):
        lo = i * 130
        ts = np.sort(rng.integers(lo, lo + 130, size=B)).astype(np.int64)
        vals = rng.random(B).astype(np.float32) * 100
        feed.feed(vals, ts)
        sim.process_elements(vals, ts)
        while int(ts[-1]) >= next_wm:
            want = [(w.get_start(), w.get_end(),
                     [float(v) for v in w.get_agg_values()])
                    for w in sim.process_watermark(next_wm)
                    if w.has_value()]
            ws, we, cnt, lowered = op.process_watermark_arrays(next_wm)
            got = [(int(ws[j]), int(we[j]),
                    [float(lw[j]) for lw in lowered])
                   for j in range(ws.shape[0]) if cnt[j] > 0]
            assert [(s, e) for s, e, _ in want] == \
                   [(s, e) for s, e, _ in got], next_wm
            for (_, _, a), (_, _, b) in zip(want, got):
                for x, y in zip(a, b):
                    # f32 device accumulation vs the f64 host oracle
                    assert x == pytest.approx(y, rel=2e-3), next_wm
            next_wm += 100
    op.check_overflow()


def test_host_feed_delta_packing_roundtrip():
    ts = np.asarray([5, 5, 7, 1000, 10**7], np.int64) + 3_000_000_000_000
    vals = np.arange(5, dtype=np.float32)
    base, deltas, v = HostFeed.pack(vals, ts)
    assert deltas.dtype == np.uint32
    assert (base + deltas.astype(np.int64) == ts).all()


def test_host_fed_cell_saturates_link():
    """End-to-end host-fed throughput must reach a meaningful fraction of
    the raw device_put bandwidth of the same packed bytes — the pipeline
    is transport-bound by design)."""
    from scotty_tpu.bench.harness import BenchmarkConfig
    from scotty_tpu.bench.runner import run_host_fed_cell

    import jax

    cfg = BenchmarkConfig(name="hf", throughput=1 << 17, runtime_s=4,
                          batch_size=1 << 14, capacity=1 << 12,
                          watermark_period_ms=1000)
    r = run_host_fed_cell(cfg, "Tumbling(1000)", "sum")
    assert r.n_windows_emitted > 0
    assert r.link_mbps_raw > 0
    assert r.link_saturation > 0
    if jax.devices()[0].platform != "cpu":
        # generous bound: transfers + unpack + ingest should not cost more
        # than ~3x the bare link. Only meaningful where the link IS the bottleneck: on the
        # CPU backend "transfer" is a ~250 MB/s in-process memcpy while
        # ingest compute bounds the region, so saturation is inherently
        # tiny there (this test sat unreported behind the pre-PR2
        # checkpoint abort — the bound never held on CPU).
        assert r.link_saturation > 0.3, (r.link_saturation, r.link_mbps_raw)


def test_keyed_host_feed_matches_per_key_results():
    """KeyedHostFeed packs (key, value, ts) records into padded [K, Bk]
    rounds; results must equal per-key host operators fed the same tuples
    (VERDICT r3 item 7 — the keyed host boundary end to end)."""
    import numpy as np

    from scotty_tpu import SlicingWindowOperator, SumAggregation, TumblingWindow, WindowMeasure
    from scotty_tpu.engine import EngineConfig
    from scotty_tpu.engine.host_ingest import KeyedHostFeed
    from scotty_tpu.parallel.keyed import KeyedTpuWindowOperator

    K, Bk = 4, 64
    rng = np.random.default_rng(5)
    N = 300
    ts = np.sort(rng.integers(0, 5000, size=N)).astype(np.int64)
    keys = rng.integers(0, K, size=N).astype(np.int64)
    vals = rng.random(N).astype(np.float32)

    op = KeyedTpuWindowOperator(K, config=EngineConfig(
        capacity=1 << 10, batch_size=Bk, min_trigger_pad=32))
    op.add_window_assigner(TumblingWindow(WindowMeasure.Time, 1000))
    op.add_aggregation(SumAggregation())
    op.set_max_lateness(1000)
    feed = KeyedHostFeed(op)
    for lo in range(0, N, 150):
        sl = slice(lo, lo + 150)
        feed.feed(keys[sl], vals[sl], ts[sl])
    ws, we, cnt, lowered = op.process_watermark_arrays(6000)

    sims = [SlicingWindowOperator() for _ in range(K)]
    for s in sims:
        s.add_window_assigner(TumblingWindow(WindowMeasure.Time, 1000))
        s.add_aggregation(SumAggregation())
        s.set_max_lateness(1000)
    for k, v, t in zip(keys, vals, ts):
        sims[k].process_element(float(v), int(t))
    for k in range(K):
        want = {(w.get_start(), w.get_end()): float(w.get_agg_values()[0])
                for w in sims[k].process_watermark(6000) if w.has_value()}
        got = {(int(s), int(e)): float(v)
               for s, e, c, v in zip(ws, we, cnt[k], lowered[0][k])
               if c > 0}
        assert got == pytest.approx(want), (k, want, got)


def test_keyed_host_feed_rejects_out_of_range_keys():
    """ADVICE r4 (low): keys outside [0, K) get a clear contract error,
    not an opaque broadcast failure from bincount."""
    import pytest

    from scotty_tpu import SumAggregation, TumblingWindow, WindowMeasure
    from scotty_tpu.engine import EngineConfig
    from scotty_tpu.engine.host_ingest import KeyedHostFeed
    from scotty_tpu.parallel.keyed import KeyedTpuWindowOperator

    op = KeyedTpuWindowOperator(4, config=EngineConfig(
        capacity=1 << 8, batch_size=8, min_trigger_pad=32))
    op.add_window_assigner(TumblingWindow(WindowMeasure.Time, 100))
    op.add_aggregation(SumAggregation())
    feed = KeyedHostFeed(op)
    ts = np.arange(3, dtype=np.int64)
    vals = np.ones(3, np.float32)
    with pytest.raises(ValueError, match="out of range"):
        feed.pack(np.array([0, 1, 4]), vals, ts)
    with pytest.raises(ValueError, match="out of range"):
        feed.pack(np.array([-1, 1, 2]), vals, ts)
    # ISSUE 5 satellite: a round holding BOTH negative and >= K keys must
    # report both offending value classes plus the out-of-range count —
    # the old single-value message picked whichever end it checked first
    with pytest.raises(ValueError) as exc:
        feed.pack(np.array([-3, 9, 1]), vals, ts)
    msg = str(exc.value)
    assert "-3" in msg and "9" in msg
    assert "2 tuple(s)" in msg

"""Ingest-ring differential + behavior tests (ISSUE 7).

The oracle discipline of the rest of the suite: the vectorized
``offer_block`` path must be EXACTLY equivalent to record-at-a-time
offers, the ring-staged run loops must bit-match their synchronous
(unstaged) twins on every connector, shed survivors must replay to the
same results through a plain loop, and the device-side
``LineRateFeed`` must bit-match ``process_elements``. Chaos values are
small integers (exact in float32) so every comparison is exact.
"""

import asyncio

import numpy as np
import pytest

from scotty_tpu.connectors.base import (
    AscendingWatermarks,
    GlobalScottyWindowOperator,
    KeyedScottyWindowOperator,
)
from scotty_tpu.connectors.iterable import (
    IDLE_TICK,
    collect_global,
    collect_keyed,
    run_keyed,
)
from scotty_tpu.core.aggregates import SumAggregation
from scotty_tpu.core.windows import TumblingWindow, WindowMeasure
from scotty_tpu.ingest import (
    BlockSinkFeeder,
    IngestRing,
    LineRateFeed,
    RingConfig,
    RingFull,
    RingIngestor,
)
from scotty_tpu.obs import Observability
from scotty_tpu.resilience import chaos
from scotty_tpu.resilience.clock import ManualClock
from scotty_tpu.shaper import BatchAccumulator, ShaperConfig

Time = WindowMeasure.Time


def _bounded_ooo(seed, n, step=20, jitter=400):
    rng = chaos.rng_of(seed)
    base = np.arange(n) * step
    ts = np.maximum(base + rng.integers(-jitter, jitter, n), 0)
    vals = rng.integers(0, 100, n)
    return vals.astype(np.float32), ts.astype(np.int64)


# ---------------------------------------------------------------------------
# BatchAccumulator.offer_block ≡ record-at-a-time offers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("slack,delay", [(0, None), (150, None),
                                         (150, 100.0), (0, 50.0)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_offer_block_bitmatches_per_record_path(slack, delay, seed):
    vals, ts = _bounded_ooo(seed, 500, step=10, jitter=200)
    blocks_a, blocks_b = [], []
    ca, cb = ManualClock(), ManualClock()
    a = BatchAccumulator(32, lambda v, t: blocks_a.append((v.copy(),
                                                           t.copy())),
                         slack_ms=slack, max_delay_ms=delay, clock=ca)
    b = BatchAccumulator(32, lambda v, t: blocks_b.append((v.copy(),
                                                           t.copy())),
                         slack_ms=slack, max_delay_ms=delay, clock=cb)
    for chunk in np.array_split(np.arange(500), 13):
        for i in chunk:                 # the record-at-a-time path
            a.offer(float(vals[i]), int(ts[i]))
        b.offer_block(vals[chunk], ts[chunk])   # one vectorized block
        ca.advance(0.03)
        cb.advance(0.03)
        a.poll()
        b.poll()
    a.drain()
    b.drain()
    assert len(blocks_a) == len(blocks_b)
    for (va, ta), (vb, tb) in zip(blocks_a, blocks_b):
        assert np.array_equal(va, vb) and np.array_equal(ta, tb)
    assert (a.flushes, a.reordered, a.held_highwater, a.fill_ratios) \
        == (b.flushes, b.reordered, b.held_highwater, b.fill_ratios)


def test_offer_block_expired_deadline_boundary_matches():
    """An already-expired deadline drains after the NEXT record in the
    per-record path; offer_block must hit the same block boundary."""
    blocks_a, blocks_b = [], []
    ca, cb = ManualClock(), ManualClock()
    a = BatchAccumulator(16, lambda v, t: blocks_a.append(t.tolist()),
                         max_delay_ms=50.0, clock=ca)
    b = BatchAccumulator(16, lambda v, t: blocks_b.append(t.tolist()),
                         max_delay_ms=50.0, clock=cb)
    a.offer(1.0, 10)
    b.offer_block([1.0], [10])
    ca.advance(1.0)                     # deadline long expired
    cb.advance(1.0)
    vals = np.arange(5, dtype=np.float32)
    ts = np.arange(5, dtype=np.int64) * 100 + 20
    for v, t in zip(vals, ts):
        a.offer(float(v), int(t))
    b.offer_block(vals, ts)
    a.drain()
    b.drain()
    assert blocks_a == blocks_b
    # the drain fired right after the first new record, not at block end
    assert blocks_a[0] == [10, 20]


def test_offer_block_keyed_object_payloads():
    ts = np.arange(50, dtype=np.int64) * 7
    blocks_a, blocks_b = [], []
    a = BatchAccumulator(8, lambda k, v, t: blocks_a.append(
        (list(k), list(v), t.tolist())), keyed=True, value_dtype=None)
    b = BatchAccumulator(8, lambda k, v, t: blocks_b.append(
        (list(k), list(v), t.tolist())), keyed=True, value_dtype=None)
    keys = [f"k{i % 3}" for i in range(50)]
    payloads = [(i, i * 2) for i in range(50)]   # tuple payloads survive
    for i in range(50):
        a.offer([payloads[i]], [int(ts[i])], keys=[keys[i]])
    b.offer_block(payloads, ts, keys=keys)
    a.drain()
    b.drain()
    assert blocks_a == blocks_b


# ---------------------------------------------------------------------------
# IngestRing mechanics
# ---------------------------------------------------------------------------


def test_ring_fill_commit_take_free_fifo_and_accounting():
    ring = IngestRing(3, 4)
    assert ring.offer_block(np.arange(10, dtype=np.float32),
                            np.arange(10, dtype=np.int64)) == 10
    assert ring.blocks == 2 and ring.occupancy == 10
    blk = ring.take()
    assert blk.seq == 0 and blk.n == 4
    assert blk.ts.tolist()[:4] == [0, 1, 2, 3]
    assert (blk.ts_min, blk.ts_max) == (0, 3)
    ring.free(blk)
    assert ring.delivered == 4 and ring.occupancy == 6
    blk2 = ring.take()
    with pytest.raises(ValueError):     # FIFO free enforced
        b3 = ring.take()
        assert b3 is None or True
        ring.free(type(blk2)(blk2.seq + 5, blk2.vals, blk2.ts, None,
                             blk2.n, 0, 0))
    ring.free(blk2)
    assert ring.flush_open()            # 2 records still open
    blk3 = ring.take()
    assert blk3.n == 2
    ring.free(blk3)
    assert ring.occupancy == 0
    snap = ring.snapshot()
    assert snap["offered"] == snap["delivered"] == 10


def test_ring_full_is_a_signal_not_an_exception():
    ring = IngestRing(2, 4)
    accepted = ring.offer_block(np.zeros(20, np.float32),
                                np.arange(20, dtype=np.int64))
    assert accepted == 8                 # depth*block_size credits
    assert not ring.has_space()
    assert ring.full_events == 1
    assert ring.offer_one(1.0, 99) is False
    assert ring.full_events == 2
    blk = ring.take()
    ring.free(blk)
    assert ring.has_space()


def test_ring_offer_one_scalar_path():
    ring = IngestRing(2, 3, keyed=True, value_dtype=None)
    for i in range(5):
        assert ring.offer_one((i, "payload"), i * 10, key=f"k{i}")
    blk = ring.take()
    assert blk.n == 3 and list(blk.keys[:3]) == ["k0", "k1", "k2"]
    assert list(blk.vals[:3]) == [(0, "payload"), (1, "payload"),
                                  (2, "payload")]
    ring.free(blk)
    assert ring.occupancy == 2


# ---------------------------------------------------------------------------
# RingIngestor policies
# ---------------------------------------------------------------------------


def _sink_collector(collected):
    return lambda vals, tss: collected.append((np.asarray(vals).copy(),
                                               np.asarray(tss).copy()))


def test_policy_block_never_loses_records():
    collected = []
    ring = IngestRing(2, 4, value_dtype=np.float32)
    feeder = BlockSinkFeeder(ring, _sink_collector(collected))
    ing = RingIngestor(ring, feeder, policy="block", pump_at=0)
    vals, ts = np.arange(40, dtype=np.float32), np.arange(40,
                                                          dtype=np.int64)
    assert ing.offer_block(vals, ts) == 40
    ing.drain()
    merged = np.concatenate([t for _, t in collected])
    assert merged.tolist() == ts.tolist()      # everything, in order
    assert ing.shed == 0 and ring.full_events > 0


def test_policy_shed_exact_counts_and_survivor_oracle():
    collected, shed = [], []
    ring = IngestRing(2, 4, value_dtype=np.float32)
    feeder = BlockSinkFeeder(ring, _sink_collector(collected))
    ing = RingIngestor(ring, feeder, policy="shed", pump_at=0,
                       shed_callback=lambda v, t, k: shed.append(
                           (np.asarray(v, np.float32).copy(),
                            np.asarray(t, np.int64).copy())))
    vals, ts = np.arange(40, dtype=np.float32), np.arange(40,
                                                          dtype=np.int64)
    accepted = ing.offer_block(vals, ts)
    assert accepted == 8                 # ring capacity
    assert ing.shed == 32
    ing.drain()
    survivors = np.concatenate([t for _, t in collected])
    shed_ts = np.concatenate([t for _, t in shed])
    # exact conservation: survivors + shed == offered, disjoint, ordered
    assert survivors.tolist() == ts[:8].tolist()
    assert shed_ts.tolist() == ts[8:].tolist()
    snap = ing.snapshot()
    assert snap["offered"] == 8 and snap["shed"] == 32
    assert snap["delivered"] == 8 and snap["occupancy"] == 0


def test_policy_fail_raises_ring_full():
    ring = IngestRing(2, 2, value_dtype=np.float32)
    feeder = BlockSinkFeeder(ring, lambda v, t: None)
    ing = RingIngestor(ring, feeder, policy="fail", pump_at=0)
    with pytest.raises(RingFull):
        ing.offer_block(np.zeros(10, np.float32),
                        np.arange(10, dtype=np.int64))


def test_consumer_stall_trips_watchdog():
    """A slow consumer delivery under blocking backpressure counts a
    resilience_stall_events exactly like a stalled source (PR 3)."""
    clock = ManualClock()
    obs = Observability()
    ring = IngestRing(2, 2, value_dtype=np.float32)

    def slow_sink(vals, tss):
        clock.advance(3.0)               # consumer takes 3 clock-seconds

    feeder = BlockSinkFeeder(ring, slow_sink)
    ing = RingIngestor(ring, feeder, policy="block", pump_at=0, obs=obs,
                       clock=clock, stall_timeout_s=1.0)
    ing.offer_block(np.zeros(10, np.float32),
                    np.arange(10, dtype=np.int64))
    ing.check()                          # drain-point fold
    snap = obs.registry.snapshot()
    assert snap["resilience_stall_events"] >= 1
    assert snap["ingest_ring_full_events"] >= 1


def test_ring_telemetry_folds_exactly_once():
    obs = Observability()
    collected = []
    ring = IngestRing(4, 4, value_dtype=np.float32)
    feeder = BlockSinkFeeder(ring, _sink_collector(collected))
    ing = RingIngestor(ring, feeder, policy="block", pump_at=1, obs=obs)
    ing.offer_block(np.zeros(10, np.float32), np.arange(10,
                                                        dtype=np.int64))
    ing.drain()
    ing.check()                          # double fold must not double count
    snap = obs.registry.snapshot()
    assert snap["ingest_ring_offered"] == 10
    assert snap["ingest_ring_delivered"] == 10
    assert snap["ingest_ring_blocks"] == 3
    assert snap["ingest_ring_occupancy"] == 0


# ---------------------------------------------------------------------------
# ring-staged connector loops ≡ synchronous oracle (every connector)
# ---------------------------------------------------------------------------


def _keyed_recs(seed, n=300):
    vals, ts = _bounded_ooo(seed, n)
    keys = chaos.rng_of(seed + 1).integers(0, 3, n)
    return [(f"k{int(k)}", float(v), int(t))
            for k, v, t in zip(keys, vals, ts)]


def _mk_keyed():
    return KeyedScottyWindowOperator(
        windows=[TumblingWindow(Time, 1000)],
        aggregations=[SumAggregation()], allowed_lateness=1000,
        watermark_policy=AscendingWatermarks())


def _mk_global():
    return GlobalScottyWindowOperator(
        windows=[TumblingWindow(Time, 1000)],
        aggregations=[SumAggregation()], allowed_lateness=1000,
        watermark_policy=AscendingWatermarks())


_KEY = lambda kw: (kw[0], kw[1].start, kw[1].end,        # noqa: E731
                   tuple(kw[1].agg_values))
_GKEY = lambda w: (w.start, w.end, tuple(w.agg_values))  # noqa: E731


@pytest.mark.parametrize("shaper", [None,
                                    ShaperConfig(batch_size=64,
                                                 slack_ms=1000)])
@pytest.mark.parametrize("seed", [5, 6])
def test_iterable_keyed_ring_bitmatches_unstaged(shaper, seed):
    recs = _keyed_recs(seed)
    out_r = collect_keyed(iter(recs), _mk_keyed(), final_watermark=30_000,
                          ingest_ring=RingConfig(depth=4, block_size=16),
                          shaper=shaper)
    out_p = collect_keyed(iter(recs), _mk_keyed(), final_watermark=30_000,
                          shaper=shaper)
    assert sorted(map(_KEY, out_r)) == sorted(map(_KEY, out_p))


@pytest.mark.parametrize("seed", [7])
def test_iterable_global_ring_bitmatches_unstaged(seed):
    vals, ts = _bounded_ooo(seed, 300)
    recs = [(float(v), int(t)) for v, t in zip(vals, ts)]
    out_r = collect_global(iter(recs), _mk_global(),
                           final_watermark=30_000,
                           ingest_ring=RingConfig(depth=4, block_size=16),
                           shaper=ShaperConfig(batch_size=64,
                                               slack_ms=1000))
    out_p = collect_global(iter(recs), _mk_global(),
                           final_watermark=30_000,
                           shaper=ShaperConfig(batch_size=64,
                                               slack_ms=1000))
    assert sorted(map(_GKEY, out_r)) == sorted(map(_GKEY, out_p))


def test_kafka_ring_bitmatches_unstaged():
    from scotty_tpu.connectors.kafka import KafkaScottyWindowOperator

    records = chaos.make_records(seed=3, n=150, keys=3, period_ms=40)
    got, ref = [], []
    op_r = _mk_keyed()
    KafkaScottyWindowOperator(operator=op_r).run(
        records, got.append,
        ingest_ring=RingConfig(depth=4, block_size=16))
    got += op_r.process_watermark(30_000)
    op_p = _mk_keyed()
    KafkaScottyWindowOperator(operator=op_p).run(records, ref.append)
    ref += op_p.process_watermark(30_000)
    assert sorted(map(_KEY, got)) == sorted(map(_KEY, ref))


def test_asyncio_ring_bitmatches_unstaged():
    from scotty_tpu.connectors.asyncio_connector import run_keyed_async

    recs = _keyed_recs(9, n=200)

    async def source():
        for r in recs:
            yield r

    def run(ring):
        out = []
        op = _mk_keyed()
        asyncio.run(run_keyed_async(source(), op, out.append,
                                    ingest_ring=ring))
        out += op.process_watermark(30_000)
        return out

    out_r = run(RingConfig(depth=4, block_size=16))
    out_p = run(None)
    assert sorted(map(_KEY, out_r)) == sorted(map(_KEY, out_p))


def test_run_loop_shed_survivors_replay_to_identical_results():
    """policy='shed' with manual pumping: the loop sheds everything past
    the ring's capacity; replaying JUST the survivors through a plain
    loop must produce bit-identical windows (the PR 3 shed-oracle
    discipline at the host edge)."""
    recs = _keyed_recs(11, n=120)
    shed = []
    op_r = _mk_keyed()
    out_r = list(run_keyed(
        iter(recs), op_r,
        ingest_ring=RingConfig(depth=2, block_size=8, policy="shed",
                               pump_at=0),
        shed_callback=lambda v, t, k: shed.extend(
            zip(list(k), list(v), [int(x) for x in t]))))
    out_r += op_r.process_watermark(30_000)
    n_shed = len(shed)
    assert n_shed == 120 - 16            # exactly past-capacity records
    shed_set = {(k, v, t) for k, v, t in shed}
    survivors = [r for r in recs if (r[0], r[1], r[2]) not in shed_set]
    assert len(survivors) == 16
    out_p = collect_keyed(iter(survivors), _mk_keyed(),
                          final_watermark=30_000)
    assert sorted(map(_KEY, out_r)) == sorted(map(_KEY, out_p))


# ---------------------------------------------------------------------------
# idle ticks: a quiet source still flushes on time (ManualClock per loop)
# ---------------------------------------------------------------------------


def _attach_deadline_shaper(op, clock, max_delay_ms=100.0):
    op.attach_shaper(ShaperConfig(batch_size=64,
                                  max_delay_ms=max_delay_ms), clock=clock)
    return op


def test_iterable_idle_tick_flushes_deadline():
    clock = ManualClock()
    op = _attach_deadline_shaper(_mk_keyed(), clock)
    flushed_at_tick = {}

    def source():
        yield ("a", 1.0, 100)
        clock.advance(0.2)               # deadline expires, source quiet
        yield IDLE_TICK
        flushed_at_tick["held"] = op._shaper.held
        flushed_at_tick["flushes"] = op._shaper.accumulator.flushes
        yield ("a", 2.0, 5000)

    list(run_keyed(source(), op))
    # the tick itself flushed the held record — before record 2 arrived
    assert flushed_at_tick == {"held": 0, "flushes": 1}


def test_global_idle_tick_flushes_deadline():
    from scotty_tpu.connectors.iterable import run_global

    clock = ManualClock()
    op = _mk_global()
    op.attach_shaper(ShaperConfig(batch_size=64, max_delay_ms=100.0),
                     clock=clock)
    seen = {}

    def source():
        yield (1.0, 100)
        clock.advance(0.2)
        yield IDLE_TICK
        seen["held"] = op._shaper.held
        yield (2.0, 5000)

    list(run_global(source(), op))
    assert seen == {"held": 0}


def test_kafka_poll_timeout_flushes_deadline():
    from scotty_tpu.connectors.kafka import KafkaScottyWindowOperator
    from scotty_tpu.resilience.chaos import _Record

    clock = ManualClock()
    op = _attach_deadline_shaper(_mk_keyed(), clock)
    state = {"polls": 0, "held_at_empty_poll": None}

    class FakePollConsumer:
        def poll(self, timeout_ms=None):
            state["polls"] += 1
            if state["polls"] == 1:
                return {"tp0": [_Record("a", "1", 100)]}
            clock.advance(0.2)           # quiet topic, clock marches on
            if state["polls"] == 3:
                # by the SECOND empty poll the first one's idle tick
                # must have flushed the held record
                state["held_at_empty_poll"] = op._shaper.held
                return {"tp0": [_Record("a", "2", 5000)]}
            return {}

    KafkaScottyWindowOperator(operator=op).run(
        FakePollConsumer(), lambda item: None, max_records=2,
        idle_poll_ms=50)
    assert state["held_at_empty_poll"] == 0


def test_asyncio_idle_poll_flushes_deadline():
    from scotty_tpu.connectors.asyncio_connector import run_keyed_async

    clock = ManualClock()
    op = _attach_deadline_shaper(_mk_keyed(), clock)
    seen = {}

    async def main():
        gate = asyncio.Event()

        async def source():
            yield ("a", 1.0, 100)
            clock.advance(0.2)           # deadline expired; source silent
            await gate.wait()
            yield ("a", 2.0, 5000)

        async def release():
            # wait until the idle tick flushed, then open the gate
            for _ in range(200):
                await asyncio.sleep(0.005)
                if op._shaper is not None and op._shaper.held == 0 \
                        and op._shaper.accumulator.flushes >= 1:
                    break
            seen["held"] = op._shaper.held
            seen["flushes"] = op._shaper.accumulator.flushes
            gate.set()

        await asyncio.gather(
            run_keyed_async(source(), op, lambda item: None,
                            idle_poll_s=0.01),
            release())

    asyncio.run(main())
    assert seen["held"] == 0 and seen["flushes"] >= 1


def test_ring_idle_tick_flushes_open_partial_block_through_deadline():
    """Records staged in the ring's OPEN partial block must reach the
    operator (and its max_delay_ms machinery) on an idle tick — the
    whole bounded-delay chain, end to end (code-review regression)."""
    clock = ManualClock()
    op = _mk_keyed()
    op.attach_shaper(ShaperConfig(batch_size=64, max_delay_ms=100.0),
                     clock=clock)
    seen = {}

    def source():
        yield ("a", 1.0, 100)
        yield ("a", 2.0, 150)            # both < block_size: open block
        yield IDLE_TICK                  # tick 1: ring → shaper
        seen["ring_after_tick1"] = op._shaper.held
        clock.advance(0.2)               # shaper deadline expires, quiet
        yield IDLE_TICK                  # tick 2: deadline flush
        seen["flushes"] = op._shaper.accumulator.flushes
        seen["held"] = op._shaper.held

    list(run_keyed(source(), op,
                   ingest_ring=RingConfig(depth=4, block_size=16)))
    # tick 1 committed the OPEN ring block into the operator (the
    # records reached the shaper — they no longer wait for stream end);
    # tick 2's poll then fired the shaper's own deadline
    assert seen == {"ring_after_tick1": 2, "flushes": 1, "held": 0}


def test_ring_trickling_source_honors_bounded_delay():
    """A slow-but-ACTIVE source never idles, so without an open-block
    stage deadline its records would sit un-committed in the ring for a
    whole block — the run-loop ring inherits the attached shaper's
    max_delay_ms on the same clock, evaluated on every offer
    (code-review regression)."""
    clock = ManualClock()
    op = _attach_deadline_shaper(_mk_keyed(), clock)
    seen = {}

    def source():
        yield ("a", 1.0, 100)
        clock.advance(0.2)               # > max_delay; source stays busy
        yield ("a", 2.0, 200)            # trips the ring stage deadline:
        seen["in_acc"] = op._shaper.held  # both records now held past it
        clock.advance(0.2)               # accumulator deadline expires
        yield ("a", 3.0, 5000)           # arrival (never an idle tick)
        seen["flushes"] = op._shaper.accumulator.flushes
        seen["held"] = op._shaper.held

    list(run_keyed(source(), op,
                   ingest_ring=RingConfig(depth=4, block_size=16)))
    # record 2's offer committed the open ring block into the
    # accumulator; record 3's arrival evaluated the accumulator
    # deadline (per-arrival parity) and flushed the held records —
    # end-to-end bound <= one ring stage + one accumulator stage
    assert seen["in_acc"] == 2
    assert seen["flushes"] >= 1 and seen["held"] == 0


def test_linerate_feed_rejects_mismatched_block_size():
    """A ring block_size != the operator's batch_size would crash the
    compiled device kernels with an opaque shape error at the first
    dispatched block — refuse it up front (code-review regression)."""
    import scotty_tpu as st
    from scotty_tpu.engine.config import EngineConfig

    op = st.engine.TpuWindowOperator(
        config=EngineConfig(capacity=1 << 10, batch_size=64,
                            annex_capacity=128, min_trigger_pad=32))
    with pytest.raises(ValueError, match="block_size=32 must equal"):
        LineRateFeed(op, ring=RingConfig(depth=4, block_size=32))


def test_ring_drain_paths_count_windows_emitted():
    """Windows yielded from the end-of-stream ring drain (a stream
    shorter than block_size stages EVERYTHING until then) must count
    into the connector-boundary windows_emitted exactly like the
    unstaged loop's — obs-diff parity between ring and non-ring runs
    (code-review regression)."""
    recs = _keyed_recs(11, n=40)         # << default block_size
    obs_p, obs_r = Observability(), Observability()
    out_p = list(run_keyed(iter(recs), _mk_keyed(), obs=obs_p))
    out_r = list(run_keyed(iter(recs), _mk_keyed(), obs=obs_r,
                           ingest_ring=RingConfig(depth=4)))
    assert len(out_p) == len(out_r)
    snap_p = obs_p.registry.snapshot()
    snap_r = obs_r.registry.snapshot()
    assert snap_p.get("windows_emitted", 0) > 0
    assert snap_r.get("windows_emitted", 0) \
        == snap_p.get("windows_emitted", 0)
    assert snap_r.get("ingest_tuples", 0) == snap_p.get("ingest_tuples", 0)


def test_ring_partial_block_delivery_survives_slot_recycling():
    """A partial block delivered mid-stream (idle tick) lands in the
    shaper accumulator's slack band and outlives its ring slot — which
    the producer then overwrites as the ring wraps. The sink must own
    its arrays outright or those held records silently corrupt
    (code-review regression: a depth-2 ring emitted sum 219 where the
    unstaged loop emits 486)."""
    def mk():
        return KeyedScottyWindowOperator(
            windows=[TumblingWindow(Time, 100)],
            aggregations=[SumAggregation()], allowed_lateness=1000,
            watermark_policy=AscendingWatermarks())

    recs = [("a", 100.0, 1), ("a", 200.0, 2), IDLE_TICK] + \
        [("a", float(10 + i), 3 + i) for i in range(12)] + \
        [("a", 1.0, 500)]
    plain = [r for r in recs if r is not IDLE_TICK]
    out_p = list(run_keyed(iter(plain), mk(),
                           shaper=ShaperConfig(batch_size=64)))
    # depth=2 x block_size=4: the idle tick parks 2 records in the
    # accumulator, then the next 8 offers wrap the ring over their slot
    out_r = list(run_keyed(iter(recs), mk(),
                           shaper=ShaperConfig(batch_size=64),
                           ingest_ring=RingConfig(depth=2,
                                                  block_size=4)))
    assert sorted(map(_KEY, out_r)) == sorted(map(_KEY, out_p))


def test_ring_offer_block_preserves_tuple_payloads():
    """Equal-length tuple payloads must arrive downstream verbatim, not
    flattened into ndarray rows (code-review regression — the block and
    scalar paths must agree)."""
    got = []
    ing = RingIngestor.for_sink(
        RingConfig(depth=2, block_size=2),
        lambda keys, vals, tss: got.extend(zip(list(keys), list(vals))),
        keyed=True)
    ing.offer_block([(1, 2), (3, 4), (5, 6)], [100, 200, 300],
                    keys=["a", "b", "c"])
    ing.drain()
    assert got == [("a", (1, 2)), ("b", (3, 4)), ("c", (5, 6))]
    assert all(type(v) is tuple for _, v in got)


def test_kafka_polling_mode_still_flags_stalls():
    """idle_poll_ms must not disable the stall watchdog: a dead producer
    shows as accumulated quiet time across empty polls and flags
    resilience_stall_events (code-review regression)."""
    from scotty_tpu.connectors.kafka import KafkaScottyWindowOperator
    from scotty_tpu.resilience.chaos import _Record

    clock = ManualClock()
    obs = Observability()
    op = _mk_keyed()
    op.obs = obs
    state = {"polls": 0}

    class DeadProducerConsumer:
        def poll(self, timeout_ms=None):
            state["polls"] += 1
            if state["polls"] == 1:
                return {"tp0": [_Record("a", "1", 100)]}
            clock.advance(0.5)           # each empty poll: 0.5 s quiet
            if state["polls"] >= 16:     # producer comes back eventually
                return {"tp0": [_Record("a", "2", 5000)]}
            return {}

    KafkaScottyWindowOperator(operator=op).run(
        DeadProducerConsumer(), lambda item: None, max_records=2,
        idle_poll_ms=50, stall_timeout_s=2.0, clock=clock)
    snap = obs.registry.snapshot()
    # ~7 s of quiet at a 2 s budget → at least two flagged stalls
    assert snap["resilience_stall_events"] >= 2


def test_kafka_polling_mode_confluent_positional_seconds():
    """confluent_kafka's ``Consumer.poll(timeout)`` takes positional
    SECONDS and no ``timeout_ms`` kwarg; polling mode must fall back to
    that face instead of crashing on the very consumers the bare-record
    branch exists for (code-review regression)."""
    from scotty_tpu.connectors.kafka import KafkaScottyWindowOperator
    from scotty_tpu.resilience.chaos import _Record

    clock = ManualClock()
    op = _attach_deadline_shaper(_mk_keyed(), clock)
    state = {"polls": 0, "timeouts": [], "held_at_empty_poll": None}

    class FakeConfluentConsumer:
        def poll(self, timeout):         # positional seconds, no kwargs
            state["polls"] += 1
            state["timeouts"].append(timeout)
            if state["polls"] == 1:
                return _Record("a", "1", 100)     # one bare record
            clock.advance(0.2)
            if state["polls"] == 3:
                state["held_at_empty_poll"] = op._shaper.held
                return _Record("a", "2", 5000)
            return None

    n = KafkaScottyWindowOperator(operator=op).run(
        FakeConfluentConsumer(), lambda item: None, max_records=2,
        idle_poll_ms=50)
    assert n == 2
    # the fallback converted ms → seconds for the positional face
    assert state["timeouts"][-1] == pytest.approx(0.05)
    # and the empty-poll idle tick still flushed the held record
    assert state["held_at_empty_poll"] == 0


def test_bounded_queue_default_and_unbounded_flight_mark():
    from scotty_tpu.connectors.asyncio_connector import (
        DEFAULT_QUEUE_MAXSIZE,
        bounded_queue,
        queue_source,
    )
    from scotty_tpu.obs import FlightRecorder

    async def main():
        q = bounded_queue()
        assert q.maxsize == DEFAULT_QUEUE_MAXSIZE
        with pytest.raises(ValueError):
            bounded_queue(0)
        # producer-side contract: put_nowait raises at the bound
        small = bounded_queue(1)
        small.put_nowait(1)
        with pytest.raises(asyncio.QueueFull):
            small.put_nowait(2)
        # an unbounded queue is flight-marked, a bounded one is not
        obs = Observability(flight=FlightRecorder(capacity=64))
        unbounded = asyncio.Queue()
        await unbounded.put(None)        # sentinel terminates immediately
        async for _ in queue_source(unbounded, obs=obs):
            pass
        marks = [e for e in obs.flight.events()
                 if e["name"] == "queue_source_unbounded"]
        assert len(marks) == 1

    asyncio.run(main())


# ---------------------------------------------------------------------------
# LineRateFeed (device path): prefetch ring ≡ process_elements oracle
# ---------------------------------------------------------------------------


from scotty_tpu.engine import EngineConfig  # noqa: E402
from scotty_tpu.engine.operator import TpuWindowOperator  # noqa: E402

SMALL = EngineConfig(capacity=1 << 12, batch_size=64, annex_capacity=256,
                     min_trigger_pad=32)


def _mk_device_op():
    op = TpuWindowOperator(config=SMALL)
    op.add_window_assigner(TumblingWindow(Time, 1000))
    op.add_aggregation(SumAggregation())
    op.set_max_lateness(2000)
    return op


def _windows_dict(ws, we, cnt, lowered):
    return {(int(s), int(e)): (int(c), tuple(float(x) for x in row))
            for s, e, c, *row in zip(ws, we, cnt, *lowered) if c > 0}


@pytest.mark.parametrize("shaped", [True, False])
@pytest.mark.parametrize("seed", [0, 1])
def test_linerate_feed_bitmatches_process_elements(shaped, seed):
    if shaped:
        vals, ts = _bounded_ooo(seed, 1000, step=20, jitter=400)
        shaper = ShaperConfig(slack_ms=500)
    else:
        # in-order mode: strict ascending stream (the sorted fast path)
        ts = (np.arange(1000) * 20).astype(np.int64)
        vals = chaos.rng_of(seed).integers(0, 100, 1000) \
            .astype(np.float32)
        shaper = None
    op1 = _mk_device_op()
    feed = LineRateFeed(op1, ring=RingConfig(depth=4), shaper=shaper)
    for i in range(0, 1000, 100):
        feed.offer_block(vals[i:i + 100], ts[i:i + 100])
    # mid-stream watermark exercises the drain-at-watermark wiring
    mid = _windows_dict(*op1.process_watermark_arrays(int(ts[500])))
    out1 = _windows_dict(*op1.process_watermark_arrays(30_000))
    op1.check_overflow()

    op2 = _mk_device_op()
    op2.process_elements(vals[:500], ts[:500])
    # the oracle sees the same records split at the same watermark: the
    # feed drains everything held at its watermark, so records 0..499
    # land before it and 500.. after
    mid2_idx = 500
    mid2 = _windows_dict(*op2.process_watermark_arrays(int(ts[500])))
    op2.process_elements(vals[mid2_idx:], ts[mid2_idx:])
    out2 = _windows_dict(*op2.process_watermark_arrays(30_000))
    op2.check_overflow()
    assert mid == mid2
    assert out1 == out2
    snap = feed.snapshot()
    assert snap["offered"] == 1000 and snap["occupancy"] == 0
    assert snap["shed"] == 0


def test_obs_diff_gates_ring_and_soak_counters(tmp_path):
    import json

    from scotty_tpu.obs.diff import DEFAULT_THRESHOLDS, diff_exports

    for name in ("ingest_ring_shed", "ingest_ring_full_events",
                 "soak_invariant_failures"):
        assert name in DEFAULT_THRESHOLDS["metrics"]
    base = tmp_path / "base.json"
    cand = tmp_path / "cand.json"
    row = {"name": "cell", "windows": "w", "engine": "e",
           "aggregation": "sum", "tuples_per_sec": 100.0}
    base.write_text(json.dumps([row]))
    cand.write_text(json.dumps([dict(row, ingest_ring_shed=5,
                                     soak_invariant_failures=1)]))
    bad = {f["metric"] for f in diff_exports(str(base), str(cand))
           if f["status"] == "regressed"}
    assert {"ingest_ring_shed", "soak_invariant_failures"} <= bad


def test_ingest_external_runner_cell_smoke():
    from scotty_tpu.bench.harness import BenchmarkConfig
    from scotty_tpu.bench.runner import run_ingest_external_cell

    cfg = BenchmarkConfig(
        name="t", throughput=60_000, runtime_s=2, batch_size=4096,
        capacity=1 << 14, watermark_period_ms=500, max_lateness=500,
        seed=3)
    res = run_ingest_external_cell(cfg, "Sliding(2000,500)", "sum")
    assert res.tuples_per_sec > 0
    assert res.speedup_vs_per_record > 0
    assert res.ring_shed == 0
    assert res.ring_occupancy_p99 >= res.ring_occupancy_p50 >= 0


def test_soak_runner_cell_smoke():
    from scotty_tpu.bench.harness import BenchmarkConfig
    from scotty_tpu.bench.runner import run_soak_cell

    cfg = BenchmarkConfig(name="t", soak_seconds=1.0,
                          offered_rate=4000.0, seed=3)
    res = run_soak_cell(cfg, "Sliding(2000,500)", "sum")
    assert res.soak_passed and res.soak_findings == []
    assert res.soak_seen >= 4000
    t = res.soak_last_terms
    assert t["seen"] == (t["delivered"] + t["shed"] + t["held"]
                         + t["dead_lettered"] + t["abandoned"])


def test_linerate_feed_deadline_poll_flushes():
    clock = ManualClock()
    op = _mk_device_op()
    feed = LineRateFeed(op, ring=RingConfig(depth=4),
                        shaper=ShaperConfig(max_delay_ms=100.0),
                        clock=clock)
    feed.offer_block(np.arange(5, dtype=np.float32),
                     np.arange(5, dtype=np.int64) * 10)
    assert feed.held == 5
    clock.advance(0.2)
    feed.poll()                          # idle tick: deadline flush
    assert feed.accumulator.held == 0
    assert feed.held == 0                # delivered through to the device
    # first-watermark convention enumerates triggers from wm -
    # max_lateness, so stay within reach of the [0, 1000) window
    out = _windows_dict(*op.process_watermark_arrays(1_500))
    assert out                           # the records actually landed
    op.check_overflow()

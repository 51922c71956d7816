"""The dense in-order ingest kernel at every rung of its run ladder.

``build_ingest_dense`` folds an in-order batch into the contiguous slice
rows it touches; ``TpuWindowOperator`` builds it at
``EngineConfig.dense_ingest_runs`` and the larger rungs of
``DENSE_RUN_LADDER`` (16, 256, 4096 by default) and gives each in-order batch the smallest rung its time span
provably fits. Covered here:

* the kernel against the general in-order kernel
  (``build_ingest(assume_inorder=True)``) at every rung: slice metadata
  bit-identical, min/max identical, sums within float32 rounding, for a
  full batch, a batch with a valid prefix, and a device mask as the
  shaper's sort-and-split hands its in-order block over;
* the kernel's int32 offset form (``ts32=True``, the one the operator
  runs) against its int64 form: the state after every batch equal field
  by field, over the grid specs the operator builds and timestamps near
  0, past 2**31 and 2**40, and after a gap that leaves the open slice
  far before the batch;
* the operator's choice: a ``Sliding(600, 1)`` stream whose batches span
  about 150 runs takes the dense kernel (``ingest_dense_batches``, the
  ``ingest.dispatch`` span's ``kernel``/``runs`` args), a batch over the
  top rung falls back to the general kernel with the same windows, and
  so does a batch whose span fails the int32 offset test (counted, and
  named by the span's ``ts32`` arg);
* every ``dot_general`` of the kernel runs at ``HIGHEST`` precision (on
  the TPU a default-precision float32 dot is one bfloat16 pass);
* once the dense path is built, the first dispatch at each rung compiles
  nothing.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scotty_tpu import (MaxAggregation, MinAggregation, SlidingWindow,
                        SumAggregation, TumblingWindow, WindowMeasure)
from scotty_tpu import obs as _obs
from scotty_tpu.engine import EngineConfig, TpuWindowOperator
from scotty_tpu.engine import core as ec
from scotty_tpu.engine import operator as eop
from scotty_tpu.shaper import device as sdev

Time = WindowMeasure.Time
C, A = 1 << 14, 64
AGGS = {"sum": (SumAggregation,),
        "sum_min_max": (SumAggregation, MinAggregation, MaxAggregation)}
#: per rung: lanes per batch and event-ms per batch, so that a batch opens
#: about 60 % of the rung's runs with about 16 tuples per run or fewer
RUNG_BATCH = {16: (160, 10), 256: (2048, 150), 4096: (4096, 2500)}
META = ("starts", "ends", "counts", "t_last", "t_first", "c_start",
        "n_slices", "overflow", "max_event_time", "current_count")


def _spec(aggs: str):
    return ec.EngineSpec(periods=(1,), bands=(), count_periods=(),
                         aggs=tuple(a().device_spec() for a in AGGS[aggs]))


@functools.lru_cache(maxsize=None)
def _kernels(aggs: str, runs: int):
    spec = _spec(aggs)
    return (jax.jit(ec.build_ingest(spec, C, A, assume_inorder=True)),
            jax.jit(ec.build_ingest_dense(spec, C, runs)))


def _batches(B: int, span: int, mode: str, seed: int = 0):
    """Six in-order batches ``(ts, vals, valid)``; the last one partial
    unless ``mode == "full"``. ``"device_mask"`` hands every batch over
    as the shaper's sort-and-split does: shuffled arrival order sorted on
    the device, pad lanes repeating the last valid ts with a real value,
    the valid mask a device array."""
    rng = np.random.default_rng(seed)
    split = sdev.sort_split_kernel(B, 64) if mode == "device_mask" else None
    stats = sdev.init_shaper_stats() if split else None
    t0, out = 0, []
    for i in range(6):
        n = B if (mode == "full" or i < 5) else B // 3
        ts = np.sort(rng.integers(t0, t0 + span, B)).astype(np.int64)
        vals = (rng.random(B) * 10000).astype(np.float32)
        t0 += span
        if split is None:
            valid = np.arange(B) < n
            ts[n:] = ts[n - 1]
            out.append((ts, vals, valid))
            continue
        perm = rng.permutation(B)
        stats, io_ts, io_vals, io_valid, *_ = split(
            stats, ts[perm], vals[perm], np.arange(B) < n,
            np.int64(ec.I64_MIN), np.int64(ec.I64_MIN))
        out.append((io_ts, io_vals, io_valid))
    return out


@pytest.mark.parametrize("mode", ["full", "valid_prefix", "device_mask"])
@pytest.mark.parametrize("aggs", ["sum", "sum_min_max"])
@pytest.mark.parametrize("runs", [16, 256, 4096])
def test_dense_kernel_matches_general_inorder(runs, aggs, mode):
    general, dense = _kernels(aggs, runs)
    B, span = RUNG_BATCH[runs]
    want = got = ec.init_state(_spec(aggs), C, A)
    for ts, vals, valid in _batches(B, span, mode, seed=runs):
        want = general(want, ts, vals, valid)
        got = dense(got, ts, vals, valid)
    want, got = jax.device_get((want, got))
    assert 0.4 * runs * 6 < got.n_slices <= runs * 6   # the rung is used
    assert not got.overflow
    for f in META:
        np.testing.assert_array_equal(getattr(got, f), getattr(want, f),
                                      err_msg=f)
    n = int(got.n_slices)
    for agg, g, w in zip(AGGS[aggs], got.partials, want.partials):
        if agg is SumAggregation:
            np.testing.assert_allclose(g[:n], w[:n], rtol=1e-6, atol=0)
            np.testing.assert_array_equal(g[n:], w[n:])
        else:
            np.testing.assert_array_equal(g, w)


GAP = 1 << 33
#: grid specs of the int32 offset cases, by name -> (periods,
#: offset_periods, bands as (start, size) offsets from the stream's base)
NARROW_SPECS = {
    "ms": ((1,), (), ()),
    "several": ((3, 7, 10), (), ()),
    # 1,000 tumbling sizes collapse to their gcd (here 4)
    "gcd1000": (ec.collapse_periods(
        4 * np.random.default_rng(0).integers(250, 5000, 1000)), (), ()),
    # Sliding(25, 10): window ends off the slide grid
    "offset": ((10,), ((10, 5),), ()),
    "bands": ((), (), ((-5, 70), (200, 300), (GAP - 2, 400))),
}
NARROW_BASES = {"near0": 0, "2e31": (1 << 31) + 12345,
                "2e40": (1 << 40) + 7, "gap": (1 << 31) + 99}
#: (runs, aggs, lanes, batch span in grid steps), one per case in turn
NARROW_RUNGS = ((16, "sum_min_max", 160, 10),
                (256, "sum", 2048, 150),
                (4096, "sum_min_max", 4096, 2500),
                (256, "sum_min_max", 2048, 150))
NARROW_CASES = [(k, b) + NARROW_RUNGS[i % len(NARROW_RUNGS)]
                for i, (k, b) in enumerate(
                    (k, b) for k in NARROW_SPECS for b in NARROW_BASES)]


@functools.lru_cache(maxsize=None)
def _dense_pair(kind: str, base: int, aggs: str, runs: int):
    periods, offs, bands = NARROW_SPECS[kind]
    spec = ec.EngineSpec(
        periods=tuple(int(p) for p in periods), count_periods=(),
        offset_periods=offs,
        bands=tuple((max(base + bs, 0), sz) for bs, sz in bands),
        aggs=tuple(a().device_spec() for a in AGGS[aggs]))
    return spec, tuple(jax.jit(ec.build_ingest_dense(spec, C, runs, ts32=f))
                       for f in (False, True))


@pytest.mark.parametrize(
    "kind,base,runs,aggs,B,steps", NARROW_CASES,
    ids=[f"{k}-{b}-r{r}-{a}" for k, b, r, a, *_ in NARROW_CASES])
def test_narrow_form_is_bit_identical(kind, base, runs, aggs, B, steps):
    base = NARROW_BASES[base]
    spec, (wide, narrow) = _dense_pair(kind, base, aggs, runs)
    span = steps * eop.min_grid_period(spec)
    rng = np.random.default_rng(runs + base % 997)
    want = got = ec.init_state(spec, C, A)
    t0 = base
    for i in range(6):
        n = B if i < 5 else B // 3                  # the last one partial
        ts = np.sort(rng.integers(t0, t0 + span, B)).astype(np.int64)
        ts[n:] = ts[n - 1]
        vals = (rng.random(B) * 10000).astype(np.float32)
        valid = np.arange(B) < n
        assert ec.ts32_fits(spec, int(ts[0]), int(ts[n - 1]))
        want = wide(want, ts, vals, valid)
        got = narrow(got, ts, vals, valid)
        for f, g, w in zip(want._fields, jax.device_get(got),
                           jax.device_get(want)):
            for gl, wl in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
                np.testing.assert_array_equal(gl, wl, err_msg=f"{f} @{i}")
        # the gap case jumps 2**33 ms after its first batch: the open
        # slice then starts far before the next batch's first timestamp
        t0 += span + (GAP if NARROW_BASES["gap"] == base and i == 0 else 0)
    got = jax.device_get(got)
    assert got.n_slices >= 2 and not got.overflow


def test_ts32_fits_bounds_the_offsets():
    spec = _spec("sum")
    assert ec.ts32_fits(spec, 0, (1 << 31) - 2)
    assert not ec.ts32_fits(spec, 0, (1 << 31) - 1)
    assert not ec.ts32_fits(spec, -1, 10)
    wide = dataclasses.replace(spec, periods=(1 << 30,))
    assert ec.ts32_fits(wide, 1 << 40, (1 << 40) + (1 << 30) - 1)
    assert not ec.ts32_fits(wide, 1 << 40, (1 << 40) + (1 << 30))
    # bands alone: the first lane's distance to its band edge counts
    bands = dataclasses.replace(spec, periods=(), bands=((100, 50),))
    lo = 150 + (1 << 31) - 1000
    assert ec.ts32_fits(bands, lo, lo + 999)
    assert not ec.ts32_fits(bands, lo, lo + 1000)


def _operator(dense_runs: int = 16, obs=None, batch: int = 2048,
              pallas: bool = False):
    op = TpuWindowOperator(
        config=EngineConfig(capacity=1 << 13, annex_capacity=64,
                            batch_size=batch, min_trigger_pad=32,
                            dense_ingest_runs=dense_runs,
                            pallas_slice_merge=pallas), obs=obs)
    op.add_window_assigner(SlidingWindow(Time, 600, 1))
    for a in AGGS["sum_min_max"]:
        op.add_aggregation(a())
    op.set_max_lateness(600)
    return op


def _stream(spans, B: int = 2048, seed: int = 3):
    """In-order batches of ``B`` tuples, batch i spanning ``spans[i]``
    event-ms."""
    rng = np.random.default_rng(seed)
    t0 = 0
    for span in spans:
        ts = np.sort(rng.integers(t0, t0 + span, B)).astype(np.int64)
        yield (rng.random(B) * 1000).astype(np.float32), ts
        t0 += span


def _windows(op, spans, watermark_every: int = 2):
    out = []
    t_hi = 0
    for i, (vals, ts) in enumerate(_stream(spans)):
        op.process_elements(vals, ts)
        t_hi = int(ts[-1])
        if i % watermark_every == watermark_every - 1:
            out += [(w.start, w.end, w.has_value(),
                     tuple(float(v) for v in w.agg_values))
                    for w in op.process_watermark(t_hi - 50)]
    op.check_overflow()
    return out


def test_operator_takes_dense_rung_and_falls_back_over_the_top():
    spans = [148] * 6 + [5000] + [148] * 3       # ~150 runs; one > 4096
    o = _obs.Observability()
    op = _operator(obs=o)
    picked = []
    pick = op._pick_inorder_kernel

    def spy(lo, hi):
        kern, runs = pick(lo, hi)
        picked.append(runs)
        return kern, runs

    op._pick_inorder_kernel = spy
    got = _windows(op, spans)
    assert picked == [256] * 6 + [0] + [256] * 3
    assert o.counter(_obs.INGEST_DENSE_BATCHES).value == 9
    # the same stream through the general in-order kernel alone
    want = _windows(_operator(dense_runs=0), spans)
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g[:3] == w[:3]
        # a window sums ~8,000 float32 values through slice partials added
        # in another order: its rounding, not a lost or extra tuple (one
        # value is ~1e-4 of a window)
        np.testing.assert_allclose(g[3], w[3], rtol=1e-5)


def test_dispatch_span_names_kernel_and_runs(monkeypatch):
    seen = []
    real = _obs.program_span

    def record(obs, name, **args):
        if name == "ingest.dispatch":
            seen.append((args["kernel"], args["runs"], args["late"],
                         args["ts32"]))
        return real(obs, name, **args)

    monkeypatch.setattr(_obs, "program_span", record)
    B = 2048
    o = _obs.Observability()
    op = _operator(batch=B, obs=o)
    batches = list(_stream([148, 148, 5000], B=B))
    for vals, ts in batches:
        op.ingest_device_batch(jnp.asarray(vals), jnp.asarray(ts),
                               int(ts[0]), int(ts[-1]))
    vals, ts = batches[-1]                  # 1 ms behind the head: late
    late_ts = ts[-16:] - 1
    op.ingest_device_late(jnp.asarray(late_ts), jnp.asarray(vals[-16:]),
                          jnp.ones(16, bool), 16, int(late_ts[0]),
                          int(late_ts[-1]))
    op.check_overflow()
    assert seen == [("dense", 256, False, True), ("dense", 256, False, True),
                    ("inorder", 0, False, False), ("general", 0, True, False)]
    assert o.counter(_obs.INGEST_DENSE_TS32_BATCHES).value == 2
    assert o.counter(_obs.INGEST_DENSE_TS32_FALLBACKS).value == 0

    # on a grid of 2**30 ms, a batch spanning 2**31 ms fits the first rung
    # (5 runs) but not the int32 offsets: the general kernel, counted
    seen.clear()
    o = _obs.Observability()
    wide = TpuWindowOperator(
        config=EngineConfig(capacity=1 << 10, annex_capacity=64,
                            batch_size=B, min_trigger_pad=32), obs=o)
    wide.add_window_assigner(TumblingWindow(Time, 1 << 30))
    wide.add_aggregation(SumAggregation())
    rng = np.random.default_rng(5)
    for lo, hi in ((0, 1 << 29), ((1 << 29) + 1, (1 << 29) + (1 << 31))):
        ts = np.sort(rng.integers(lo, hi + 1, B)).astype(np.int64)
        ts[0], ts[-1] = lo, hi
        wide.ingest_device_batch(jnp.ones(B, jnp.float32), jnp.asarray(ts),
                                 lo, hi)
    wide.check_overflow()
    assert seen == [("dense", 16, False, True), ("inorder", 0, False, False)]
    assert o.counter(_obs.INGEST_DENSE_TS32_BATCHES).value == 1
    assert o.counter(_obs.INGEST_DENSE_TS32_FALLBACKS).value == 1
    assert o.counter(_obs.INGEST_DENSE_BATCHES).value == 1


@pytest.mark.parametrize("first,pallas,want", [
    (0, False, ()), (16, False, (16, 256, 4096)), (300, False, (300, 4096)),
    (8192, False, (8192,)), (16, True, (16,))])
def test_dense_ladder_follows_first_rung(first, pallas, want):
    assert eop.DENSE_RUN_LADDER == (256, 4096)
    op = _operator(dense_runs=first, pallas=pallas)
    op._build()
    assert op._dense_rungs == want


@pytest.mark.parametrize("runs", [16, 256, 4096])
def test_dense_kernel_dots_run_at_highest_precision(runs):
    spec = _spec("sum_min_max")
    B = 1024
    text = jax.jit(ec.build_ingest_dense(spec, C, runs)).lower(
        ec.init_state(spec, C, A), np.zeros(B, np.int64),
        np.zeros(B, np.float32), np.ones(B, bool)).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    if runs <= ec.ONE_HOT_FOLD_RUNS:
        assert dots, "the first rung folds sums with a one-hot product"
    for ln in dots:
        assert "f32" not in ln or "HIGHEST" in ln, ln


class _CountCompiles:
    n = 0
    registered = False

    @classmethod
    def listen(cls):
        if not cls.registered:
            def on(event, duration, **kw):
                if event == "/jax/core/compile/backend_compile_duration":
                    cls.n += 1

            jax.monitoring.register_event_duration_secs_listener(on)
            cls.registered = True
        return cls


def test_no_compile_at_first_dispatch_of_each_rung():
    counter = _CountCompiles.listen()
    B = 2048
    op = _operator(batch=B)
    stream = _stream([2000, 148, 8, 2500, 148, 5], B=B)
    vals, ts = next(stream)
    op.process_elements(vals, ts)                # builds every rung
    assert set(op._ingest_dense) == {16, 256, 4096}
    before = counter.n
    picked = []
    for i, (vals, ts) in enumerate(stream):
        _, runs = op._pick_inorder_kernel(int(ts[0]), int(ts[-1]))
        picked.append(runs)
        if i % 2:
            op.process_elements(vals, ts)
        else:
            op.ingest_device_batch(jnp.asarray(vals), jnp.asarray(ts),
                                   int(ts[0]), int(ts[-1]))
    jax.block_until_ready(op._state)
    assert picked == [256, 16, 4096, 256, 16]
    assert counter.n == before
    op.check_overflow()

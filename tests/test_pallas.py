"""Interpreter-mode differential suite for the Pallas hot-path kernels
(ISSUE 15 tentpole).

Every kernel runs here under Pallas interpreter mode (the CPU backend
resolution — ``scotty_tpu.pallas.resolve_interpret``) and is held, over
a chaos-seeded out-of-order corpus, against BOTH its XLA twin and a
host (numpy) oracle:

* sort-split: bit-match lane for lane (the bitonic (bucket, lane)
  network order IS the stable-sort order);
* segmented folds: bit-match in the float-exact regime (integer-valued
  f32 lanes with bounded sums — the chaos-suite discipline), and the
  bf16 ``packed`` arm bounded by the DERIVED tolerance
  (``pallas.packed_tolerance``), asserted as-is;
* the flagged-on pipelines (aligned / keyed / dense-ingest operator)
  bit-match their flags-off twins in the exact regime (power-of-two
  value scale, lane counts whose sums stay exactly representable);
* fallback arms: a batch span over the 31-bit bucket budget and a
  non-power-of-two batch size each route to the XLA twin, counted as
  ``pallas_fallbacks`` — never silent.
"""

import numpy as np
import pytest

import scotty_tpu.obs as obs_mod
from scotty_tpu import (
    MaxAggregation,
    MinAggregation,
    SlidingWindow,
    SumAggregation,
    TumblingWindow,
    WindowMeasure,
)
from scotty_tpu.engine import EngineConfig, TpuWindowOperator
from scotty_tpu.engine.config import EngineConfig as _EC  # noqa: F401
from scotty_tpu.shaper import ShaperConfig, StreamShaper
from scotty_tpu.shaper import device as shdev

Time = WindowMeasure.Time


def _leaves_equal(a, b):
    import jax

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# sort-split: pallas vs XLA twin vs host oracle over the chaos OOO corpus
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B", [128, 1024])
@pytest.mark.parametrize("seed", [0, 1, 2, 7])
def test_sort_split_differential_chaos(seed, B):
    """B=128 is one 128-lane row; B=1024 spans eight, so strides of a
    row or more take the cross-row partner path."""
    import jax

    from scotty_tpu import pallas as spl

    rng = np.random.default_rng(seed)
    L = {128: 32, 1024: 512}[B]           # room for every late lane
    lo = int(rng.integers(0, 1000))
    span = int(rng.integers(10, 4000))
    ts = rng.integers(lo, lo + span, size=B).astype(np.int64)
    # duplicates on purpose: stability is part of the contract
    ts[rng.random(B) < 0.3] = lo + int(rng.integers(0, span))
    vals = rng.random(B).astype(np.float32)
    valid = rng.random(B) < 0.85
    cut = np.int64(lo + span // 3)
    seed_met = cut

    xla = jax.jit(shdev.build_sort_split(B, L), donate_argnums=0)
    pls = jax.jit(spl.build_pallas_sort_split(B, L), donate_argnums=0)
    out_x = xla(shdev.init_shaper_stats(), ts, vals, valid, cut, seed_met)
    out_p = pls(shdev.init_shaper_stats(), ts, vals, valid, cut, seed_met,
                np.int64(lo))
    _leaves_equal(out_x, out_p)

    # host oracle: stable argsort of the sentinel-masked key
    key = np.where(valid, ts, np.int64(shdev.TS_SENTINEL))
    order = np.argsort(key, kind="stable")
    sort_ts, sort_vals = key[order], vals[order]
    n_valid = int(valid.sum())
    n_late = min(int(np.searchsorted(sort_ts, cut, side="left")), n_valid)
    (_, io_ts, io_vals, io_valid, l_ts, l_vals, l_valid) = [
        np.asarray(x) for x in out_p]
    assert int(np.asarray(io_valid).sum()) == n_valid - n_late
    assert int(np.asarray(l_valid).sum()) == n_late
    np.testing.assert_array_equal(
        io_ts[:n_valid - n_late], sort_ts[n_late:n_valid])
    np.testing.assert_array_equal(
        io_vals[:n_valid - n_late], sort_vals[n_late:n_valid])
    np.testing.assert_array_equal(l_ts[:n_late], sort_ts[:n_late])
    np.testing.assert_array_equal(l_vals[:n_late], sort_vals[:n_late])


@pytest.mark.parametrize("axis", [0, 1])
def test_pallas_roll_rotates_like_jnp_roll(axis):
    """The bitonic network takes ``out[p] = in[p - shift]`` as
    ``pltpu.roll``'s direction when it picks a partner's rotation."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x = np.arange(8 * 128, dtype=np.int32).reshape(8, 128)

    def kernel(x_ref, o_ref):
        o_ref[...] = pltpu.roll(x_ref[...], np.int32(3), axis)

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
        interpret=True)(x)
    np.testing.assert_array_equal(np.asarray(got), np.roll(x, 3, axis))


def test_sort_split_rejects_non_power_of_two():
    from scotty_tpu import pallas as spl

    with pytest.raises(ValueError):
        spl.build_pallas_sort_split(100, 16)


def test_sort_span_budget():
    from scotty_tpu import pallas as spl

    assert spl.sort_span_fits(0)
    assert spl.sort_span_fits((1 << 31) - 3)
    assert not spl.sort_span_fits(1 << 31)
    assert not spl.sort_span_fits(-1)


# ---------------------------------------------------------------------------
# segmented folds: pallas vs XLA twin vs numpy oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["sum", "min", "max"])
def test_row_fold_differential(kind):
    import jax
    import jax.numpy as jnp

    from scotty_tpu import pallas as spl

    rng = np.random.default_rng(3)
    rows, lanes, W = 16, 48, 3
    lifted = rng.integers(0, 16, size=(rows * lanes, W)).astype(np.float32)
    ident = {"sum": 0.0, "min": np.float32(np.finfo(np.float32).max),
             "max": np.float32(-np.finfo(np.float32).max)}[kind]
    red = {"sum": np.sum, "min": np.min, "max": np.max}[kind]
    oracle = red(lifted.reshape(rows, lanes, W).astype(np.float64), axis=1)
    twin = np.asarray(jax.device_get({"sum": jnp.sum, "min": jnp.min,
                                      "max": jnp.max}[kind](
        jnp.asarray(lifted).reshape(rows, lanes, W), axis=1)))
    got = np.asarray(jax.jit(lambda v: spl.row_fold(
        v, rows, lanes, kind, identity=ident))(lifted))
    np.testing.assert_array_equal(got, twin)          # XLA twin
    np.testing.assert_array_equal(got, oracle)        # host oracle (exact)


def test_row_fold_packed_bf16_tolerance_derived():
    import jax

    from scotty_tpu import pallas as spl

    rng = np.random.default_rng(11)
    rows, lanes, W = 8, 64, 2
    lifted = (rng.random((rows * lanes, W)).astype(np.float32) * 100.0)
    exact = np.sum(lifted.reshape(rows, lanes, W).astype(np.float64),
                   axis=1)
    got = np.asarray(jax.jit(lambda v: spl.row_fold(
        v, rows, lanes, "sum", identity=0.0, packed=True))(lifted))
    tol = spl.packed_tolerance(lanes, float(np.abs(lifted).max()), "sum")
    err = float(np.abs(got - exact).max())
    assert err <= tol, (err, tol)
    # the derived bound is TIGHT enough to mean something: a full f32
    # bit-match would make the packed arm pointless to tolerate
    assert tol < float(np.abs(exact).max())


@pytest.mark.parametrize("cells", [1, 3])
def test_sparse_fold_differential(cells):
    import jax

    from scotty_tpu import pallas as spl

    rng = np.random.default_rng(5)
    rows, lanes, width = 6, 32, 24
    N = rows * lanes
    col = rng.integers(0, width, size=(cells, N)).astype(np.int32)
    val = rng.integers(0, 9, size=(cells, N)).astype(np.float32)
    oracle = np.zeros((rows, width), np.float64)
    for d in range(cells):
        for i in range(N):
            oracle[i // lanes, col[d, i]] += val[d, i]
    c_in = col[0] if cells == 1 else col
    v_in = val[0] if cells == 1 else val
    got = np.asarray(jax.jit(lambda c, v: spl.sparse_row_fold(
        c, v, rows, lanes, width, "sum", 0.0))(c_in, v_in))
    np.testing.assert_array_equal(got, oracle)


def test_segment_fold_differential_variable_runs():
    import jax

    from scotty_tpu import pallas as spl

    rng = np.random.default_rng(9)
    B, R, W = 192, 8, 2
    # sorted run ids with empty runs and an invalid tail aliasing the
    # last run with identity values (the _lift mask contract)
    k = np.sort(rng.choice([0, 1, 3, 4, 7], size=B)).astype(np.int32)
    lifted = rng.integers(0, 7, size=(B, W)).astype(np.float32)
    lifted[-10:] = 0.0                     # identity-masked invalid lanes
    fold = spl.build_segment_fold(B, R, W, "sum", identity=0.0)
    got = np.asarray(jax.jit(fold)(k, lifted))
    oracle = np.zeros((R, W), np.float64)
    for i in range(B):
        oracle[k[i]] += lifted[i]
    np.testing.assert_array_equal(got, oracle)


# ---------------------------------------------------------------------------
# flagged-on pipelines bit-match their flags-off twins (exact regime)
# ---------------------------------------------------------------------------


def _aligned(**flags):
    from scotty_tpu.engine.pipeline import AlignedStreamPipeline

    return AlignedStreamPipeline(
        [SlidingWindow(Time, 400, 100)],
        [SumAggregation(), MinAggregation(), MaxAggregation()],
        config=EngineConfig(capacity=1 << 12, annex_capacity=256,
                            min_trigger_pad=32, **flags),
        throughput=2560, wm_period_ms=200, max_lateness=200, seed=3,
        gc_every=10 ** 9, value_scale=8.0)


def test_aligned_pallas_fold_bit_matches_flags_off():
    import jax

    p_off = _aligned()
    r_off = [jax.device_get(r) for r in p_off.run(4)]
    p_off.sync()
    p_on = _aligned(pallas_slice_merge=True)
    r_on = [jax.device_get(r) for r in p_on.run(4)]
    p_on.sync()
    _leaves_equal(r_off, r_on)
    p_on.check_overflow()


def test_keyed_pallas_fold_bit_matches_flags_off():
    import jax

    from scotty_tpu.parallel.keyed import KeyedAlignedPipeline

    def mk(**flags):
        return KeyedAlignedPipeline(
            [TumblingWindow(Time, 100)],
            [SumAggregation(), MinAggregation()],
            n_keys=4,
            config=EngineConfig(capacity=1 << 10, annex_capacity=32,
                                min_trigger_pad=32, **flags),
            throughput=4 * 64 * 10, wm_period_ms=200, max_lateness=200,
            seed=1, gc_every=10 ** 9, value_scale=4.0)

    a = mk()
    ra = [jax.device_get(r) for r in a.run(3)]
    a.sync()
    b = mk(pallas_slice_merge=True)
    rb = [jax.device_get(r) for r in b.run(3)]
    b.sync()
    _leaves_equal(ra, rb)
    assert b._pallas_in_step


def test_mesh_pallas_fold_bit_matches_flags_off():
    import jax

    from scotty_tpu.mesh import MeshKeyedPipeline

    def mk(**flags):
        return MeshKeyedPipeline(
            [TumblingWindow(Time, 100)], [SumAggregation()],
            n_keys=16, n_shards=8,
            config=EngineConfig(capacity=1 << 10, batch_size=32,
                                annex_capacity=32, min_trigger_pad=32,
                                **flags),
            throughput=16 * 40, wm_period_ms=200, max_lateness=200,
            seed=5, gc_every=10 ** 9, value_scale=4.0)

    a = mk()
    ra = [jax.device_get(r) for r in a.run(3)]
    a.sync()
    b = mk(pallas_slice_merge=True)
    rb = [jax.device_get(r) for r in b.run(3)]
    b.sync()
    _leaves_equal(ra, rb)


def _run_shaped_stream(pallas: bool, obs=None, n_batches=6, back=200):
    """A chaos OOO device stream through StreamShaper → operator →
    watermark emissions; returns the emitted window rows."""
    B = 256
    op = TpuWindowOperator(config=EngineConfig(
        capacity=1 << 10, annex_capacity=256, batch_size=B,
        min_trigger_pad=32, pallas_sort_split=pallas))
    op.add_window_assigner(TumblingWindow(Time, 100))
    op.add_aggregation(SumAggregation())
    op.set_max_lateness(600)
    if obs is not None:
        op.set_observability(obs)
    sh = StreamShaper(op, ShaperConfig(late_capacity=160), obs=obs)
    rng = np.random.default_rng(7)
    out = []
    for i in range(n_batches):
        lo, hi = i * 500, (i + 1) * 500
        ts = rng.integers(max(0, lo - back), hi, size=B).astype(np.int64)
        vals = rng.integers(0, 7, size=B).astype(np.float32)
        sh.shape_device_batch(vals, ts, max(0, lo - back), hi)
        if i >= 2:
            out += [(w.start, w.end, tuple(map(float, w.agg_values)))
                    for w in op.process_watermark(hi - 300)
                    if w.has_value()]
    sh.check()
    op.check_overflow()
    return out


def test_shaper_pallas_end_to_end_bit_match_and_counts():
    o = obs_mod.Observability()
    base = _run_shaped_stream(False)
    flagged = _run_shaped_stream(True, obs=o)
    assert base == flagged and len(base) > 0
    snap = o.snapshot()
    assert snap.get("pallas_kernel_dispatches", 0) >= 6
    assert "pallas_fallbacks" not in snap or snap["pallas_fallbacks"] == 0


def test_shaper_pallas_span_fallback_counted():
    """A batch whose host-known span overflows the 31-bit bucket budget
    must fall back to the XLA twin — counted, results identical."""
    B = 128
    o = obs_mod.Observability()
    op = TpuWindowOperator(config=EngineConfig(
        capacity=1 << 10, annex_capacity=128, batch_size=B,
        min_trigger_pad=32, pallas_sort_split=True))
    op.add_window_assigner(TumblingWindow(Time, 1 << 32))
    op.add_aggregation(SumAggregation())
    op.set_max_lateness(1 << 33)
    op.set_observability(o)
    sh = StreamShaper(op, ShaperConfig(late_capacity=64), obs=o)
    rng = np.random.default_rng(0)
    hi = (1 << 31) + 10_000                # span > 2^31: budget miss
    ts = rng.integers(0, hi, size=B).astype(np.int64)
    sh.shape_device_batch(rng.random(B).astype(np.float32), ts, 0, hi)
    sh.check()
    op.check_overflow()
    snap = o.snapshot()
    assert snap.get("pallas_fallbacks", 0) == 1
    assert snap.get("pallas_kernel_dispatches", 0) in (0, None) or \
        snap.get("pallas_kernel_dispatches", 0) == 0


def test_shaper_pallas_shape_fallback_disables_once():
    """A non-power-of-two batch size is a build-time property: ONE
    counted fallback, then the shaper stays on the XLA twin."""
    B = 192                                 # not a power of two
    o = obs_mod.Observability()
    op = TpuWindowOperator(config=EngineConfig(
        capacity=1 << 10, annex_capacity=128, batch_size=B,
        min_trigger_pad=32, pallas_sort_split=True))
    op.add_window_assigner(TumblingWindow(Time, 100))
    op.add_aggregation(SumAggregation())
    op.set_max_lateness(600)
    op.set_observability(o)
    sh = StreamShaper(op, ShaperConfig(late_capacity=64), obs=o)
    rng = np.random.default_rng(0)
    for i in range(3):
        lo, hi = i * 500, (i + 1) * 500
        ts = rng.integers(lo, hi, size=B).astype(np.int64)
        sh.shape_device_batch(rng.random(B).astype(np.float32), ts, lo, hi)
    sh.check()
    op.check_overflow()
    snap = o.snapshot()
    assert snap.get("pallas_fallbacks", 0) == 1
    assert not sh._pallas_sort


def test_dense_ingest_pallas_fold_bit_match():
    """The operator's scatter-free dense kernel with the Pallas segment
    fold bit-matches the XLA twin over an in-order stream."""
    def run(flag):
        B = 256
        op = TpuWindowOperator(config=EngineConfig(
            capacity=1 << 10, annex_capacity=64, batch_size=B,
            min_trigger_pad=32, pallas_slice_merge=flag))
        op.add_window_assigner(TumblingWindow(Time, 100))
        op.add_aggregation(SumAggregation())
        op.set_max_lateness(100)
        rng = np.random.default_rng(2)
        out = []
        for i in range(4):
            lo, hi = i * 500, (i + 1) * 500
            ts = np.sort(rng.integers(lo, hi, size=B)).astype(np.int64)
            vals = rng.integers(0, 9, size=B).astype(np.float32)
            op.process_elements(vals, ts)
            if i >= 1:
                out += [(w.start, w.end, tuple(map(float, w.agg_values)))
                        for w in op.process_watermark(hi - 100)
                        if w.has_value()]
        op.check_overflow()
        return out

    base, flagged = run(False), run(True)
    assert base == flagged and len(base) > 0


def test_interpret_mode_context():
    from scotty_tpu import pallas as spl

    assert spl.resolve_interpret(True) is True
    assert spl.resolve_interpret(False) is False
    before = spl.resolve_interpret(None)
    with spl.interpret_mode(True):
        assert spl.resolve_interpret(None) is True
        with spl.interpret_mode(False):
            assert spl.resolve_interpret(None) is False
        assert spl.resolve_interpret(None) is True
    assert spl.resolve_interpret(None) == before

"""Compile the main-path programs for a described TPU v5e, no chip needed.

Interpret-mode tests cannot see what the TPU compiler refuses (block
shapes off the (8, 128) tiling, primitives Mosaic cannot lower, programs
that do not fit the device). Here each program is lowered at the width a
deployment runs and compiled for a ``v5e:2x2`` topology: the four Pallas
kernels with ``interpret=False``, the dense ingest kernel at each rung
of the operator's run ladder (in its int64 and its int32 offset form),
the ``entry()`` ingest step, the
headline ``AlignedStreamPipeline`` step at capacity ``1 << 17``, and the
mesh keyed step over the four described chips. Nothing runs; a pass says
the chip's compiler accepts the program, not that it is right or fast.

The topology is described inside a fixture (never at import: only one
process may load the TPU library, and every xdist worker imports this
file), and the persistent compilation cache is off around these compiles
(their entries could not be read back without a chip).
"""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _shapes(tree, sharding):
    import jax

    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        np.shape(x), x.dtype, sharding=sharding), tree)


#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10 ** 9


def _fits(compiled):
    """The program's own footprint on one chip is within its HBM (the
    compiler counts one program, not what else the process holds)."""
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return compiled


def _compile(fn, *args):
    import jax

    return _fits(jax.jit(fn).lower(*args).compile())


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# -- the four Pallas kernels -------------------------------------------------


def test_sort_split_compiles(one_chip):
    import jax.numpy as jnp

    from scotty_tpu.pallas import build_pallas_sort_split
    from scotty_tpu.shaper.device import init_shaper_stats

    B = 1024
    fn = build_pallas_sort_split(B, B // 8, interpret=False)
    c = _compile(fn, _shapes(init_shaper_stats(), one_chip),
                 _sds((B,), jnp.int64, one_chip),
                 _sds((B,), jnp.float32, one_chip),
                 _sds((B,), jnp.bool_, one_chip),
                 *[_sds((), jnp.int64, one_chip)] * 3)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("width,kind,packed", [(1, "sum", False),
                                               (3, "max", False),
                                               (1, "sum", True)])
def test_row_fold_compiles(one_chip, width, kind, packed):
    import jax.numpy as jnp

    from scotty_tpu.pallas import row_fold

    rows, lanes = 64, 1024
    c = _compile(lambda v: row_fold(v, rows, lanes, kind, packed=packed,
                                    interpret=False),
                 _sds((rows * lanes, width), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("cells", [1, 3])
def test_sparse_row_fold_compiles(one_chip, cells):
    import jax.numpy as jnp

    from scotty_tpu.pallas import sparse_row_fold

    rows, lanes, width = 64, 1024, 256
    c = _compile(lambda c_, v: sparse_row_fold(c_, v, rows, lanes, width,
                                               "sum", 0.0, interpret=False),
                 _sds((cells, rows * lanes), jnp.int32, one_chip),
                 _sds((cells, rows * lanes), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("runs,width", [(64, 1), (8, 128)])
def test_segment_fold_compiles(one_chip, runs, width):
    import jax.numpy as jnp

    from scotty_tpu.pallas import build_segment_fold

    B = 65536
    fold = build_segment_fold(B, runs, width, "sum", interpret=False)
    c = _compile(fold, _sds((B,), jnp.int32, one_chip),
                 _sds((B, width), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("runs", [16, 256, 4096])
def test_dense_ingest_rung_compiles(one_chip, runs):
    """The operator's dense in-order ingest at each rung of its run
    ladder, at the benchmark cells' capacity and batch, sum+min+max."""
    import jax
    import jax.numpy as jnp

    from scotty_tpu import MaxAggregation, MinAggregation, SumAggregation
    from scotty_tpu.engine import core as ec

    spec = ec.EngineSpec(
        periods=(1,), bands=(), count_periods=(),
        aggs=tuple(a().device_spec() for a in (
            SumAggregation, MinAggregation, MaxAggregation)))
    C, A, B = 1 << 17, 1 << 12, 1 << 18
    state = _shapes(jax.eval_shape(lambda: ec.init_state(spec, C, A)),
                    one_chip)
    _compile(ec.build_ingest_dense(spec, C, runs), state,
             _sds((B,), jnp.int64, one_chip),
             _sds((B,), jnp.float32, one_chip),
             _sds((B,), jnp.bool_, one_chip))


@pytest.mark.parametrize("runs", [16, 256, 4096])
def test_dense_ingest_narrow_rung_compiles(one_chip, runs):
    """The same rungs in the int32 offset form the operator builds: the
    only int64 ops over the batch's lanes read the timestamps (the low
    words, the first lane, the last valid lane, each run's last lane)."""
    import re

    import jax
    import jax.numpy as jnp

    from scotty_tpu import MaxAggregation, MinAggregation, SumAggregation
    from scotty_tpu.engine import core as ec

    spec = ec.EngineSpec(
        periods=(1,), bands=(), count_periods=(),
        aggs=tuple(a().device_spec() for a in (
            SumAggregation, MinAggregation, MaxAggregation)))
    C, A, B = 1 << 17, 1 << 12, 1 << 18
    state = _shapes(jax.eval_shape(lambda: ec.init_state(spec, C, A)),
                    one_chip)
    args = (state, _sds((B,), jnp.int64, one_chip),
            _sds((B,), jnp.float32, one_chip),
            _sds((B,), jnp.bool_, one_chip))
    fn = ec.build_ingest_dense(spec, C, runs, ts32=True)
    text = jax.jit(fn).lower(*args).as_text()
    wide = {m.group(1) for ln in text.splitlines()
            if f"tensor<{B}xi64>" in ln and "func.func" not in ln
            for m in [re.search(r"stablehlo\.(\w+)", ln)]}
    assert wide == {"slice", "convert", "gather", "dynamic_slice"}, wide
    _compile(fn, *args)


# -- whole XLA steps ---------------------------------------------------------


def test_entry_ingest_step_compiles(one_chip):
    import __graft_entry__

    fn, args = __graft_entry__.entry()
    _compile(fn, *_shapes(args, one_chip))


@pytest.mark.parametrize("pallas", [False, True])
def test_headline_step_compiles(one_chip, pallas):
    """bench.py's pipeline at the first offered load of its sweep, with
    its slice merge in XLA and in the Pallas fold. The pipeline is built
    here on the CPU, so the Pallas kernels are pinned out of interpret
    mode while it traces."""
    import dataclasses

    import bench
    from scotty_tpu.engine.pipeline import AlignedStreamPipeline
    from scotty_tpu.pallas import interpret_mode

    p = bench.build(bench.OFFERED_SWEEP[0])
    assert p.config.capacity == 1 << 17
    with interpret_mode(False):
        if pallas:
            p = AlignedStreamPipeline(
                p.windows, p.aggregations,
                config=dataclasses.replace(p.config,
                                           pallas_slice_merge=True),
                throughput=bench.OFFERED_SWEEP[0], wm_period_ms=1000,
                gc_every=32, seed=0)
        p.reset()
        args = _shapes((p.state, p.dm, p._interval_key(0), np.int64(0)),
                       one_chip)
        c = _fits(p._step.lower(*args).compile())
    assert ("tpu_custom_call" in c.as_text()) == pallas


def test_mesh_keyed_step_compiles(one_chip, topo):
    """mesh_keyed.json's pipeline with one key shard on each of the four
    described chips; the state's shapes come from ``eval_shape``."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from scotty_tpu import (SlidingWindow, SumAggregation, TumblingWindow,
                            WindowMeasure)
    from scotty_tpu.engine import EngineConfig
    from scotty_tpu.mesh import MeshKeyedPipeline

    mesh = Mesh(np.array(topo.devices), ("keys",))
    Time = WindowMeasure.Time
    p = MeshKeyedPipeline(
        [TumblingWindow(Time, 1000), SlidingWindow(Time, 5000, 1000)],
        [SumAggregation()], n_keys=65536, mesh=mesh,
        config=EngineConfig(capacity=64, annex_capacity=8),
        throughput=1 << 24, wm_period_ms=1000, max_lateness=1000)
    keyed = NamedSharding(mesh, P("keys"))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=keyed),
        jax.eval_shape(p._init_state))
    rep = NamedSharding(mesh, P())
    c = p._step.lower(state, _sds((2,), np.uint32, rep),
                      _sds((), np.int64, rep)).compile()
    _fits(c)                               # bytes per device
    assert "all-reduce" in c.as_text()     # the in-executable global fold

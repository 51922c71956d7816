"""The benchmark's own checks, at the configurations' rehearsal sizes on
the CPU:

* every cell runs end to end and reads ``correct: true``;
* the control (the reference computed in bfloat16 in the program's
  place) reads ``correct: false`` in every cell;
* with the timed path broken underneath, a run reads ``correct:
  false``: ingest that leaves the state unchanged, half of every chunk
  left out, one window's answer altered where it is produced (one chip:
  no exchange between chips to leave out);
* the trace reduction reads the recorded chip trace in ``fixtures/``.

Run: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``
"""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def run(cell, seed=1234567890123, control=None, hook=None):
    run_mod = harness.load_module(HERE / "run.py")
    args = run_mod.parse(["--workload", cell, "--seed", str(seed),
                          "--seconds", "1", "--allow-cpu", "--tiny"]
                         + (["--control", control] if control else []))
    result, _ = run_mod.run_cell(args, entry_hook=hook)
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct(cell):
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) >= {"throughput", "emit_p95_ms", "setup_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    r = run(cell, control="bfloat16")
    assert not r["correct"], r["checks"]


def _state_unchanged(entry):
    entry.op.ingest_device_batch = lambda *a, **k: None
    entry.op.ingest_device_late = lambda *a, **k: None


def _half_left_out(entry):
    import jax.numpy as jnp

    op = entry.op
    real = op.ingest_device_batch

    def half(vals, ts, ts_min, ts_max, n_valid=None, valid=None):
        B = op.config.batch_size
        n = B if n_valid is None else n_valid
        if valid is None:
            valid = np.arange(B) < n
        keep = jnp.asarray(valid) & (jnp.arange(B) < n // 2)
        real(vals, ts, ts_min, ts_max, n_valid=n, valid=keep)

    op.ingest_device_batch = half


def _answer_altered(entry):
    real = entry.op.process_watermark_arrays
    seen = [0]

    def altered(wm):
        ws, we, cnt, low = real(wm)
        seen[0] += 1
        if seen[0] == 12 and ws.shape[0]:
            low[0] = np.array(low[0], copy=True)
            low[0][ws.shape[0] // 2] *= np.float32(1.001)
        return ws, we, cnt, low

    entry.op.process_watermark_arrays = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _answer_altered],
                         ids=["state_unchanged", "half_left_out",
                              "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault):
    r = run(cell, hook=fault)
    assert not r["correct"], r["checks"]


def test_trace_reduction_reads_the_chip_fixture():
    trace = harness.load_module(HERE / "trace.py")
    fixtures = sorted((HERE / "fixtures").glob("*.xplane.pb"))
    assert fixtures, "no recorded chip trace in benchmark/fixtures"
    tr = trace.reduce_file(fixtures[0])
    assert tr.n_devices >= 1
    assert 0 < tr.busy_s <= tr.window_s
    assert tr.launched_device_s("watermark") > 0
    assert tr.layer_device_s("ingest") > 0
    b = tr.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert len(b["idle_gaps"]) <= 10

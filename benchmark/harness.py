"""The benchmark's fixed machinery: it finds a cell's files by the names
in ``BENCHMARK.json``, drives the cell's entry in a closed loop, times
it, decides ``correct`` against the plain reference, and prints the
result line.

What belongs to one configuration, traffic mix, entry or per-layer
metric lives in files of its own, found by name:

* ``benchmark/configs/<config>.json``  (the ``file`` of the config entry)
* ``benchmark/traffic/<traffic>.json``  (read by ``traffic.py``)
* ``benchmark/entries/<entry>.py``      (named by the traffic mix)
* ``benchmark/reference/<module>.py``   (named by the configuration)
* ``benchmark/metrics/<metric>.py``     (one per per-layer metric)
"""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(ROOT / cfg_entry["file"]) as f:
        config = json.load(f)
    return cell, config


def windows_of(config: dict) -> list:
    """The configuration's windows as plain dicts. ``random_tumbling``
    expands Scotty's ``randomTumbling(n, min, max)`` the way its
    benchmark runner does: ``n`` sizes drawn with a fixed seed that the
    configuration names (a copy of the expansion, so that no change to
    the program's parser can change the cell)."""
    out = []
    for w in config["windows"]:
        if w["kind"] == "random_tumbling":
            rng = np.random.default_rng(int(w["seed"]))
            out.extend({"kind": "tumbling",
                        "size": int(rng.integers(w["min"], w["max"]))}
                       for _ in range(int(w["n"])))
        else:
            out.append(dict(w))
    return out


def scaled(config: dict, tiny: bool) -> dict:
    """The configuration as run; ``tiny`` applies its ``rehearsal``
    block (the CPU rehearsal and the tests only)."""
    if not tiny:
        return config
    out = dict(config)
    out.update(config.get("rehearsal", {}))
    return out


CACHE_DIR = HERE / ".jax_cache"


def setup_jax():
    """Point JAX's persistent compilation cache at the benchmark's own
    fixed directory in the checkout (before JAX is imported), cache every
    program, and never evict (an evicting cache fails on entries that
    another writer left without access stamps)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    import scotty_tpu.jax_config  # noqa: F401  (x64, as the engine needs)

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


class Spans:
    """Host spans the benchmark records around its calls into the
    system: durations on the host clock, and, while a trace is taken,
    ``TraceAnnotation`` ranges named ``bench.<name>`` in the trace."""

    def __init__(self):
        self.t = {}
        self.annotate = False

    def __call__(self, name):
        return _Span(self, name)


class _Span:
    __slots__ = ("spans", "name", "t0", "ann")

    def __init__(self, spans, name):
        self.spans, self.name = spans, name

    def __enter__(self):
        self.ann = None
        if self.spans.annotate:
            import jax

            self.ann = jax.profiler.TraceAnnotation("bench." + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.spans.t.setdefault(self.name, []).append(dt)
        return False


class Compiles:
    """Counts backend compilations (a compile inside the window is a
    fault of the warm-up)."""

    def __init__(self):
        import jax

        self.n = 0

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


def compare(got, want, aggs):
    """The numbers that decide ``correct``, over every watermark of the
    window. ``got``/``want``: lists of ``(ws, we, cnt, {agg: vals})``."""
    if len(got) != len(want):
        raise AssertionError("watermark logs differ in length")
    set_gap = count_gap = minmax_gap = 0
    sum_gap = 0.0
    attempted = failed = 0
    for (g_ws, g_we, g_cnt, g_v), (w_ws, w_we, w_cnt, w_v) in zip(got, want):
        attempted += w_ws.shape[0]
        n = min(g_ws.shape[0], w_ws.shape[0])
        same = (g_ws[:n] == w_ws[:n]) & (g_we[:n] == w_we[:n])
        bad = int(n - same.sum()) + abs(g_ws.shape[0] - w_ws.shape[0])
        set_gap += bad
        ok_cnt = same & (np.asarray(g_cnt[:n]) == w_cnt[:n])
        count_gap += int(same.sum() - ok_cnt.sum())
        failed += int(w_ws.shape[0] - ok_cnt.sum())
        live = ok_cnt & (w_cnt[:n] > 0)
        if "sum" in aggs and live.any():
            ref = w_v["sum"][:n][live]
            gap = np.abs(np.asarray(g_v["sum"][:n], np.float64)[live] - ref)
            sum_gap = max(sum_gap, float(np.max(
                gap / np.maximum(np.abs(ref), 1e-30))))
        for a in ("min", "max"):
            if a in aggs and live.any():
                ref = w_v[a][:n][live].astype(np.float32)
                minmax_gap += int(np.sum(
                    np.asarray(g_v[a][:n], np.float32)[live] != ref))
    checks = {"window_set_gap": set_gap, "count_gap": count_gap,
              "sum_rel_gap": sum_gap}
    if "min" in aggs or "max" in aggs:
        checks["minmax_gap"] = minmax_gap
    return checks, attempted, failed


def eprint(*a):
    print(*a, file=sys.stderr, flush=True)

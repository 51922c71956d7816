"""The one traffic generator: a pool of event-time chunks made from the
seed, replayed with event-time offsets.

A traffic mix is a data file under ``benchmark/traffic/`` (see
``load``). The configuration fixes the density (tuples per event
second) and the watermark period; one chunk is one watermark period of
event time. Chunk ``c`` of the stream is pool entry ``c % pool_chunks``
shifted by ``(c + 1) * period`` milliseconds, so every chunk holds the
same number of tuples and the pool is made once, in set-up.

Within a chunk, tuples arrive in timestamp order, except that
``late_share`` of them (an exact count, at positions drawn from the
seed) carry a timestamp moved back by 1 to ``late_reach`` times the
configuration's ``max_lateness_ms``, less one, milliseconds: they
arrive after the watermark that passed their time, within the
lateness the deployment allows.
"""

from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str) -> dict:
    """The traffic mix ``benchmark/traffic/<name>.json``."""
    path = HERE / "traffic" / f"{name}.json"
    with open(path) as f:
        mix = json.load(f)
    mix.setdefault("late_share", 0.0)
    mix.setdefault("late_reach", 0.0)
    mix.setdefault("pool_chunks", 4)
    mix.setdefault("preroll_stride", 1)
    return mix


@dataclass
class Pool:
    """``ts[p]`` (int64, relative to the chunk's start) and ``vals[p]``
    (float32) of each pool chunk, in arrival order."""

    period_ms: int
    per_chunk: int
    ts: list
    vals: list

    def chunk(self, c: int):
        """Pool index and event-time offset of stream chunk ``c``."""
        return c % len(self.ts), (c + 1) * self.period_ms

    def strided(self, k: int) -> "Pool":
        """Every ``k``-th tuple of each chunk: the same event-time span,
        late share and arrival order, ``1/k`` of the density (the
        pre-roll fills the slice store, whose size follows event time
        and not density, at a fraction of the cost)."""
        ts = [t[::k].copy() for t in self.ts]
        return Pool(self.period_ms, ts[0].shape[0], ts,
                    [v[::k].copy() for v in self.vals])


def make_pool(config: dict, mix: dict, seed: int) -> Pool:
    period = int(config["watermark_period_ms"])
    n = int(config["density_per_event_s"]) * period // 1000
    vmax = float(config.get("value_max", 10000.0))
    rng = np.random.default_rng(int(seed))
    n_late = int(round(float(mix["late_share"]) * n))
    reach = int(round(float(mix["late_reach"])
                      * int(config["max_lateness_ms"])))
    ts_all, vals_all = [], []
    for _ in range(int(mix["pool_chunks"])):
        per_ms = rng.multinomial(n, np.full(period, 1.0 / period))
        ts = np.repeat(np.arange(period, dtype=np.int64), per_ms)
        if n_late:
            late = rng.choice(n, size=n_late, replace=False)
            ts[late] -= rng.integers(1, reach, size=n_late)
        vals = rng.random(n, dtype=np.float32) * np.float32(vmax)
        ts_all.append(ts)
        vals_all.append(vals)
    return Pool(period, n, ts_all, vals_all)

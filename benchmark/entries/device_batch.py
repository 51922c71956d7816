"""Entry: the stream already on the device. Each pool is copied to HBM
in set-up as ``[batches, batch_size]`` blocks per chunk; each chunk is
shifted to its event time by one small jitted call (the benchmark's
generator work, module ``jit_shift_chunk``) and handed over batch by
batch through ``TpuWindowOperator.ingest_device_batch``. Watermarks go
through ``process_watermark_arrays``. No record crosses the host
edge."""

from __future__ import annotations

import numpy as np

from window_operator import build_operator

class _DevicePool:
    def __init__(self, pool, B):
        import jax

        n = pool.per_chunk
        nb = -(-n // B)
        self.pool = pool
        self.n_valid = [min(B, n - i * B) for i in range(nb)]
        self.ts, self.vals, self.bounds = [], [], []
        for ts, vals in zip(pool.ts, pool.vals):
            if np.any(ts[1:] < ts[:-1]):
                raise ValueError("device_batch traffic must be in order")
            t = np.empty(nb * B, np.int64)
            v = np.zeros(nb * B, np.float32)
            t[:n], v[:n] = ts, vals
            t[n:] = ts[-1]              # pad lanes repeat the last ts
            t, v = t.reshape(nb, B), v.reshape(nb, B)
            self.ts.append(jax.device_put(t))
            self.vals.append([jax.device_put(v[i]) for i in range(nb)])
            self.bounds.append([(int(t[i, 0]), int(t[i, -1]))
                                for i in range(nb)])

        def shift_chunk(t, off):
            return tuple(t[i] + off for i in range(nb))

        self.shift = jax.jit(shift_chunk)
        jax.block_until_ready(self.shift(self.ts[0],
                                         jax.device_put(np.int64(0))))


class Entry:
    def __init__(self, config, mix, pools, windows):
        self.op = build_operator(config, windows)
        self.aggs = list(config["aggregations"])
        B = int(config["batch_size"])
        self.dev = {k: _DevicePool(p, B) for k, p in pools.items()}

    def ingest(self, c: int, which: str) -> int:
        import jax

        d = self.dev[which]
        p, off = d.pool.chunk(c)
        ts = d.shift(d.ts[p], jax.device_put(np.int64(off)))
        for i, (t, v) in enumerate(zip(ts, d.vals[p])):
            lo, hi = d.bounds[p][i]
            self.op.ingest_device_batch(v, t, lo + off, hi + off,
                                        n_valid=d.n_valid[i])
        return d.pool.per_chunk

    def watermark(self, wm: int):
        ws, we, cnt, low = self.op.process_watermark_arrays(wm)
        return ws, we, cnt, dict(zip(self.aggs, low))

    def counters(self) -> dict:
        return {}

    def finish(self) -> None:
        self.op.check_overflow()


def build(config, mix, pools, windows):
    return Entry(config, mix, pools, windows)

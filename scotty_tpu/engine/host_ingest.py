"""Host→device ingest pipeline (SURVEY.md §7 stage 7): double-buffered
transfers of packed tuple batches overlapping the previous batch's ingest.

**This is the PRE-SHAPED fast path**: both feeds hard-error on unsorted
input (``pack`` raises on any descending timestamp) because they exist to
saturate the link with zero per-tuple host work. A stream that is not
already sorted-and-batched belongs to the general entry point,
:class:`scotty_tpu.shaper.StreamShaper` (ISSUE 5) — its accumulator
coalesces and sorts irregular host records into exactly the blocks these
feeds want, and its device sort-and-split shapes device-resident batches
without a host round trip.

The reference's LoadGeneratorSource emits tuples in-process
(benchmark/.../LoadGeneratorSource.java:10-87) — there IS no host→device
boundary in the reference. On TPU the boundary is real, and this module is
the framework's story for streams that originate in host memory:

* **Packing**: an in-order batch ships as ``(base i64 scalar, ts-delta
  u32[B], value f32[B])`` — 8 bytes/tuple instead of 12; deltas are exact
  while the batch spans < 2^32 ms (~49 days).
* **Double buffering**: ``feed()`` issues the H2D transfers and the
  unpack+ingest dispatch WITHOUT any device sync, so batch i+1's transfer
  overlaps batch i's ingest kernel under the runtime's async dispatch
  queue. The slice-engine state advances through the same donated-buffer
  kernels as device-resident sources.
* **Transport saturation is the design target**: the ingest kernels
  sustain multi-G tuples/s from device-resident sources (bench.py), so a
  host-fed stream is transport-bound on any link slower than that.
  ``measure_link()`` reports the raw ``device_put`` bandwidth of the same
  packed buffers; an end-to-end rate close to it means the pipeline adds
  ~nothing on top of the link, so the saturation ratio, not the absolute
  host-fed rate, says what the engine costs.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from .. import jax_config  # noqa: F401

from .operator import TpuWindowOperator


class HostFeed:
    """Double-buffered packed feed into a :class:`TpuWindowOperator`.

    Batches must be in-order (ascending ts, each batch at/above the
    previous batch's max) and exactly ``op.config.batch_size`` long —
    the operator's zero-copy device-batch contract.
    """

    def __init__(self, op: TpuWindowOperator):
        import jax
        import jax.numpy as jnp

        self.op = op
        self._unpack = jax.jit(
            lambda base, d: jnp.int64(base) + d.astype(jnp.int64))
        self.bytes_per_tuple = 8          # u32 delta + f32 value

    @staticmethod
    def pack(vals: np.ndarray, ts: np.ndarray):
        """Host-side packing: (base, deltas u32, vals f32).

        Raises ValueError when the in-order / <2^32-ms-span contract is
        violated — a silent u32 wrap would corrupt timestamps (ADVICE r3).
        """
        base = np.int64(ts[0])
        wide = np.asarray(ts, dtype=np.int64) - base
        if int(wide.max()) >= 1 << 32 or (wide.size > 1
                                          and (np.diff(wide) < 0).any()):
            raise ValueError(
                "HostFeed.pack: unsorted ts or span >= 2**32 ms — the "
                "in-order contract is violated and a u32 delta would wrap "
                "or feed a stale ts_max downstream (ADVICE r3)")
        deltas = wide.astype(np.uint32)
        return base, deltas, np.ascontiguousarray(vals, dtype=np.float32)

    def feed_packed(self, base: np.int64, deltas: np.ndarray,
                    vals: np.ndarray, ts_min: int, ts_max: int) -> None:
        """Transfer + dispatch one packed batch; returns without syncing."""
        import jax

        d_dev = jax.device_put(deltas)
        v_dev = jax.device_put(vals)
        ts_dev = self._unpack(base, d_dev)
        self.op.ingest_device_batch(v_dev, ts_dev, ts_min, ts_max)

    def feed(self, vals: np.ndarray, ts: np.ndarray) -> None:
        base, deltas, v = self.pack(vals, ts)
        self.feed_packed(base, deltas, v, int(ts[0]), int(ts[-1]))


class KeyedHostFeed:
    """Double-buffered packed feed into a ``KeyedTpuWindowOperator``
    (VERDICT r3 item 7): host-side (key, value, ts) records pack into one
    ``[K, Bk]`` round per transfer — u32 ts-deltas + f32 values, padded
    rows masked on device from a tiny per-key count vector.

    Packing is fully vectorized (one stable argsort by key + a fancy-index
    write — the stream is globally ts-ascending, so a stable key sort
    leaves each key's run ascending), the reference's keyBy→operator
    boundary (flinkBenchmark/BenchmarkJob.java:84-102) with the transport
    explicit.
    """

    def __init__(self, op):
        import jax
        import jax.numpy as jnp

        self.op = op
        K, Bk = op.n_keys, op.config.batch_size
        self.K, self.Bk = K, Bk
        self._unpack = jax.jit(
            lambda base, d: jnp.int64(base) + d.astype(jnp.int64))
        self._mask = jax.jit(
            lambda row_n: jnp.arange(Bk)[None, :] < row_n[:, None])
        self.bytes_per_tuple = 8          # u32 delta + f32 value (pre-pad)

    def pack(self, keys: np.ndarray, vals: np.ndarray, ts: np.ndarray):
        """(base, deltas u32[K, Bk], vals f32[K, Bk], counts i32[K]).
        Contract: ts globally ascending, < 2**32 ms span, every per-key
        count <= Bk (ValueError otherwise)."""
        K, Bk = self.K, self.Bk
        base = np.int64(ts[0])
        wide = np.asarray(ts, dtype=np.int64) - base
        if int(wide.max()) >= 1 << 32 or (wide.size > 1
                                          and (np.diff(wide) < 0).any()):
            raise ValueError("KeyedHostFeed.pack: unsorted ts or span >= "
                             "2**32 ms violates the in-order contract")
        order = np.argsort(keys, kind="stable")
        k2 = np.asarray(keys, np.int64)[order]
        if k2.size and (k2[-1] >= K or k2[0] < 0):
            # a round can hold BOTH negative and >= K keys — report every
            # offending value class plus the out-of-range count, not just
            # whichever end the old single-value message happened to pick
            bad = (k2 < 0) | (k2 >= K)
            offenders = []
            if k2[0] < 0:
                offenders.append(int(k2[0]))
            if k2[-1] >= K:
                offenders.append(int(k2[-1]))
            raise ValueError(
                f"KeyedHostFeed.pack: {int(bad.sum())} tuple(s) with keys "
                f"out of range [0, {K}); offending value(s): "
                f"{', '.join(str(o) for o in offenders)}")
        counts = np.bincount(k2, minlength=K)
        if counts.max(initial=0) > Bk:
            raise ValueError(
                f"KeyedHostFeed.pack: a key holds {int(counts.max())} "
                f"tuples > round size {Bk}; shrink rounds or raise "
                "batch_size")
        row_starts = np.zeros((K,), np.int64)
        row_starts[1:] = np.cumsum(counts)[:-1]
        pos = np.arange(k2.size, dtype=np.int64) - row_starts[k2]
        deltas = np.zeros((K, Bk), np.uint32)
        deltas[k2, pos] = wide[order].astype(np.uint32)
        vb = np.zeros((K, Bk), np.float32)
        vb[k2, pos] = np.asarray(vals, np.float32)[order]
        return base, deltas, vb, counts.astype(np.int32)

    def feed_packed(self, base, deltas, vb, counts, ts_min: int,
                    ts_max: int) -> None:
        """Transfer + dispatch one packed round; returns without syncing."""
        import jax

        d_dev = jax.device_put(deltas)
        v_dev = jax.device_put(vb)
        rn = jax.device_put(counts)
        self.op.ingest_device_round(self._unpack(base, d_dev), v_dev,
                                    self._mask(rn), ts_min, ts_max)

    def feed(self, keys, vals, ts) -> None:
        base, d, v, c = self.pack(keys, vals, ts)
        self.feed_packed(base, d, v, c, int(ts[0]), int(ts[-1]))


def measure_link(batch_size: int, n_batches: int = 8) -> float:
    """Raw host→device bandwidth of the packed layout (MB/s): device_put
    of (u32, f32) pairs, consumed by a trivial device reduction so the
    measurement can't complete before the bytes actually land."""
    import jax
    import jax.numpy as jnp

    consume = jax.jit(lambda d, v: jnp.sum(d) + jnp.sum(v).astype(jnp.int64))
    deltas = np.arange(batch_size, dtype=np.uint32)
    vals = np.random.default_rng(0).random(batch_size).astype(np.float32)
    int(consume(jax.device_put(deltas), jax.device_put(vals)))  # warm
    t0 = time.perf_counter()
    acc = []
    for _ in range(n_batches):
        acc.append(consume(jax.device_put(deltas), jax.device_put(vals)))
    jax.device_get(acc)
    dt = time.perf_counter() - t0
    return n_batches * batch_size * 8 / dt / 1e6

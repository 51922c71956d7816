"""Bucketed bitonic sort-split: the shaper's hot path as a Pallas kernel.

The XLA shaper kernel (:func:`scotty_tpu.shaper.device.build_sort_split`)
pays one full-block stable ``lax.sort`` over int64 timestamps per batch.
TPUs have no native int64 compare — XLA emulates the sort key with i32
pairs, roughly doubling the compare-exchange cost of every network
stage. The ShapedOOO contract already bounds how far a batch's
timestamps can spread (the host passes conservative ``[ts_min, ts_max)``
bounds to every shaped batch, and disorder reaches back at most
``max_lateness``), so the batch's timestamps compress losslessly into a
**coarse bucket key**: ``local = ts - ts_min`` fits 31 bits whenever the
batch span does. The kernel then:

* buckets every lane by that int32 coarse timestamp (invalid lanes take
  the max key, so they sink to the tail exactly like the XLA twin's
  ``TS_SENTINEL`` lanes),
* runs a bitonic merge network over native int32 ``(bucket, lane)``
  pairs entirely in VMEM — the lane id breaks ties, which makes the
  network order IDENTICAL to the XLA twin's stable sort (equal
  timestamps keep arrival order), and the compare-exchange partners
  come from lane/row rotations of a ``[rows, 128]`` layout (no gathers
  on the hot loop),
* emits the permutation and the sorted bucket keys; the wrapper
  reconstructs the sorted int64 timestamps from ``ts_min`` + bucket and
  splits against the operator's max-event-time mirror (``cut``) with
  byte-for-byte the same arithmetic as the XLA twin.

Batches whose span exceeds the 31-bit budget (or whose batch size is
not a power of two) take the XLA twin — the host decides from the
bounds it already holds, counted as ``pallas_fallbacks``, never silent.
"""

from __future__ import annotations

import numpy as np

from . import resolve_interpret

#: usable bits of the int32 bucket key (the top value is the
#: invalid-lane sentinel, so a span must stay strictly below it)
SORT_KEY_BITS = 31
_INVALID_KEY = np.int32(2**31 - 1)


def sort_span_fits(span: int) -> bool:
    """Whether a host-known batch timestamp span fits the bucket-key
    budget (the per-batch pallas-vs-fallback decision the shaper makes
    from bounds it already holds — no device sync)."""
    return 0 <= int(span) < int(_INVALID_KEY) - 1


def _layout(B: int):
    """The kernel's 2-D ``[rows, lanes]`` view of a ``[B]`` batch: full
    128-lane rows (the vreg width), fewer lanes only for a tiny batch."""
    lanes = min(B, 128)
    return B // lanes, lanes


def _bitonic_argsort_kernel(B: int):
    """Kernel body: ascending bitonic network over (key, lane) pairs.

    ``B`` is a static power of two, laid out ``[rows, lanes]``. The
    partner of position ``p`` at stride ``j`` is ``p ^ j``: a rotation
    by ``j`` along the lane axis (``j < lanes``) or by ``j / lanes``
    along the row axis. ``pltpu.roll`` rotates like ``jnp.roll``
    (``out[p] = in[p - shift]``), so a position with bit ``j`` clear
    reads its partner ``p + j`` from the backward rotation and one with
    it set reads ``p - j`` from the forward one (tests/test_pallas.py
    checks the direction). Keys and lane ids stay int32 in VMEM
    throughout.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    rows, lanes = _layout(B)

    def kernel(k_ref, perm_ref, sk_ref):
        k = k_ref[...]
        ids = (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
               * lanes
               + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
        idx = ids
        size = 2
        while size <= B:
            j = size // 2
            while j >= 1:
                axis, step = (1, j) if j < lanes else (0, j // lanes)
                # int32 shifts: Mosaic's rotate takes no int64 operand
                back = np.int32((rows, lanes)[axis] - step)
                step = np.int32(step)
                low = (ids & j) == 0

                def partner(a):
                    return jnp.where(low, pltpu.roll(a, back, axis),
                                     pltpu.roll(a, step, axis))

                pk, pi = partner(k), partner(idx)
                want_min = low == ((ids & size) == 0)
                # (key, lane) pairs are unique, so "mine > partner" is
                # a total order — no equality arm needed
                mine_gt = (k > pk) | ((k == pk) & (idx > pi))
                take = mine_gt == want_min
                k = jnp.where(take, pk, k)
                idx = jnp.where(take, pi, idx)
                j //= 2
            size *= 2
        perm_ref[...] = idx
        sk_ref[...] = k

    return kernel


def _argsort_call(B: int, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kernel = _bitonic_argsort_kernel(B)
    shape = _layout(B)

    def argsort(k32):
        perm, sk = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct(shape, jnp.int32),
                       jax.ShapeDtypeStruct(shape, jnp.int32)),
            interpret=resolve_interpret(interpret),
        )(k32.reshape(shape))
        return perm.reshape(B), sk.reshape(B)

    return argsort


def build_pallas_sort_split(batch_size: int, late_capacity: int,
                            interpret=None):
    """The Pallas twin of :func:`shaper.device.build_sort_split`.

    ``(stats, ts[B], vals[B], valid[B], cut, seed, lo) -> (stats',
    io_ts[B], io_vals[B], io_valid[B], late_ts[L], late_vals[L],
    late_valid[L])`` — the one extra input ``lo`` is the host-known
    lower timestamp bound (``ts_min``); callers must have checked
    ``sort_span_fits(ts_max - ts_min)`` and fall back to the XLA twin
    otherwise. Outputs bit-match the XLA twin lane for lane (the
    bitonic (bucket, lane) order IS the stable sort order).

    Raises ``ValueError`` at build time when ``batch_size`` is not a
    power of two (the bitonic network needs one; the shaper counts
    that as a build-time fallback).
    """
    import jax
    import jax.numpy as jnp

    from ..shaper.device import I64_MIN, TS_SENTINEL, ShaperStats

    B, L = int(batch_size), int(late_capacity)
    if B < 2 or B & (B - 1):
        raise ValueError(
            f"pallas sort-split needs a power-of-two batch size, got {B}")
    argsort = _argsort_call(B, interpret)

    def sort_split(stats: ShaperStats, ts, vals, valid, cut, seed, lo):
        ts = jnp.asarray(ts)
        vals = jnp.asarray(vals)
        valid = jnp.asarray(valid)
        cut = jnp.int64(cut)
        lo64 = jnp.int64(lo)
        # coarse bucket key: the host-certified span bound makes the
        # clip a no-op on in-contract batches (it exists so a violated
        # bound degrades to a mis-bucketed sort, never UB)
        local = jnp.clip(ts - lo64, 0, jnp.int64(_INVALID_KEY) - 1)
        k32 = jnp.where(valid, local.astype(jnp.int32), _INVALID_KEY)
        perm, sk = argsort(k32)
        sort_ts = jnp.where(sk == _INVALID_KEY, jnp.int64(TS_SENTINEL),
                            lo64 + sk.astype(jnp.int64))
        sort_vals = vals[perm]

        # -- split + stats: byte-for-byte the XLA twin's arithmetic ----
        n_valid = jnp.sum(valid.astype(jnp.int32))
        n_late = jnp.minimum(
            jnp.searchsorted(sort_ts, cut, side="left").astype(jnp.int32),
            n_valid)
        lane = jnp.arange(B, dtype=jnp.int32)
        last = jnp.maximum(n_valid - 1, 0)
        idx_io = jnp.minimum(lane + n_late, last)
        io_ts = sort_ts[idx_io]
        io_vals = sort_vals[idx_io]
        io_valid = lane < (n_valid - n_late)
        io_ts = jnp.where(n_valid > n_late, io_ts, cut)

        lanel = jnp.arange(L, dtype=jnp.int32)
        idx_l = jnp.minimum(lanel, jnp.maximum(n_late - 1, 0))
        late_ts = jnp.where(n_late > 0, sort_ts[idx_l], cut)
        late_vals = sort_vals[idx_l]
        late_valid = lanel < n_late

        eff = jnp.where(valid, ts, jnp.int64(I64_MIN))
        shifted = jnp.concatenate(
            [jnp.reshape(jnp.int64(seed), (1,)), eff[:-1]])
        rm = jax.lax.cummax(shifted)
        n_reord = jnp.sum((valid & (ts < rm)).astype(jnp.int64))
        stats = stats._replace(
            seen=stats.seen + n_valid.astype(jnp.int64),
            reordered=stats.reordered + n_reord,
            late_routed=stats.late_routed + n_late.astype(jnp.int64),
            slack_overflow=stats.slack_overflow | (n_late > L))
        return (stats, io_ts, io_vals, io_valid,
                late_ts, late_vals, late_valid)

    return sort_split

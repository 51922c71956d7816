"""Segmented-reduce slice-merge kernels (the scatter-fold replacement).

The engine's slice-merge hot paths all reduce lanes into per-slice-row
partials. XLA-from-jnp renders them as duplicate-index scatter-combines
(``engine/core.py::_combine_scatter``), one-hot matmuls
(``build_ingest_dense``), or flat per-row scatters (the PR 10
multi-cell sparse lift in the aligned/keyed/mesh generators) — scatter
being the worst op class on TPU (micro.json: f32 add ~6 ms, int64 min
~113 ms per 1M lanes). These kernels stream lane blocks HBM→VMEM
through the Pallas grid pipeline (double-buffered by construction) and
reduce each block into a VMEM row accumulator — no scatter anywhere:

* :func:`row_fold` — equal segments: ``lanes`` consecutive lanes per
  slice row (the aligned/keyed/mesh paced generators segment by
  construction). Grid ``(rows, chunks)``; each chunk folds straight
  into its row's output block.
* :func:`sparse_row_fold` — the multi-cell sparse lift: per lane a
  sketch column (count-min: ``cells`` columns) densifies against the
  row's width INSIDE VMEM (one [block, width] compare per cell) instead
  of scattering per lane.
* :func:`build_segment_fold` — variable segments bounded by ``runs``
  (the ``build_ingest_dense`` contract: an in-order batch touches a
  contiguous run range): sorted run ids, one [width, runs] accumulator.

``packed=True`` streams the lifted values as bf16 — half the HBM
traffic per lane; the accumulator stays f32, so the only precision loss
is the one rounding of each streamed value to bf16 (the differential
suite derives that bound from the mantissa width and asserts it).
int64 fields never enter these kernels: counts ride int32 lanes at the
call sites and widen on the host side of the fold.

Interpreter mode on non-TPU backends is resolved by
:func:`..pallas.resolve_interpret` — tier-1 gates correctness on CPU;
speed claims stay TPU-box certifications.
"""

from __future__ import annotations

import numpy as np

#: block index for a whole axis: int32, because under x64 a bare ``0``
#: becomes an int64 that Mosaic's index maps cannot return
_I0 = np.int32(0)


def _chunk(lanes: int, cap: int = 512) -> int:
    """The lane-block size (the streaming granularity): the largest
    divisor of ``lanes`` at most ``cap`` that is a multiple of 128 (the
    vreg lane width), else all of ``lanes`` — a block dimension must be
    tile-aligned or span the whole axis."""
    lanes, cap = int(lanes), int(cap)
    for b in range(min(lanes, cap) // 128 * 128, 0, -128):
        if lanes % b == 0:
            return b
    return lanes


def _reducer(kind: str):
    import jax.numpy as jnp

    if kind == "sum":
        return jnp.sum, jnp.add
    if kind == "min":
        return jnp.min, jnp.minimum
    if kind == "max":
        return jnp.max, jnp.maximum
    raise ValueError(f"unknown combine kind {kind!r}")


# Layout: every streamed operand keeps its lanes on the minor (128-wide)
# axis and the small aggregate width / cell count on the axis above it.
# The natural ``[lanes, width]`` layout would pad a width-1 operand to
# 128 lanes in HBM (a 128x blow-up); here each block reduces along its
# lanes into a ``(width, 1)`` column, and outputs are ``[rows, width,
# 1]`` so a block spans both tiled axes whole.


def row_fold(lifted, rows: int, lanes: int, kind: str,
             identity=0.0, packed: bool = False, interpret=None):
    """Equal-segment fold: ``lifted [rows*lanes, width] -> [rows, width]``
    reduced per row with ``kind`` — the Pallas twin of
    ``red[kind](lifted.reshape(rows, lanes, -1), axis=1)``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from . import resolve_interpret

    rows, lanes = int(rows), int(lanes)
    lifted = jnp.asarray(lifted)
    W = int(lifted.shape[-1])
    if packed:
        lifted = lifted.astype(jnp.bfloat16)
    lb = _chunk(lanes)
    chunks = lanes // lb
    red, comb = _reducer(kind)
    ident = float(identity)

    def kernel(v_ref, o_ref):
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _init():
            o_ref[...] = jnp.full((W, 1), ident, jnp.float32)

        vb = v_ref[...].astype(jnp.float32)          # [W, lb]
        o_ref[...] = comb(o_ref[...], red(vb, axis=1, keepdims=True))

    out = pl.pallas_call(
        kernel,
        grid=(rows, chunks),
        in_specs=[pl.BlockSpec((None, W, lb), lambda r, c: (r, _I0, c))],
        out_specs=pl.BlockSpec((None, W, 1), lambda r, c: (r, _I0, _I0)),
        out_shape=jax.ShapeDtypeStruct((rows, W, 1), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(lifted.reshape(rows, lanes, W).transpose(0, 2, 1))
    return out.reshape(rows, W)


def sparse_row_fold(col, val, rows: int, lanes: int, width: int,
                    kind: str, identity, interpret=None):
    """Multi-cell sparse fold: per-lane sketch columns densified in
    VMEM — ``col/val [cells, rows*lanes] -> [rows, width]``. The Pallas
    twin of the flat per-row scatter (``tgt.at[row*width + col].add``).
    Single-cell callers pass 1-D ``col``/``val``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from . import resolve_interpret

    rows, lanes, width = int(rows), int(lanes), int(width)
    col = jnp.asarray(col)
    val = jnp.asarray(val)
    if col.ndim == 1:
        col = col[None, :]
        val = val[None, :]
    cells = int(col.shape[0])
    lb = _chunk(lanes, cap=max(128, (1 << 16) // max(width, 1)))
    chunks = lanes // lb
    red, comb = _reducer(kind)
    ident = float(identity)

    def kernel(c_ref, v_ref, o_ref):
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _init():
            o_ref[...] = jnp.full((width, 1), ident, jnp.float32)

        acc = o_ref[...]
        wrows = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
        for d in range(cells):                       # static cell loop
            cb = c_ref[d:d + 1, :]                   # [1, lb]
            vb = v_ref[d:d + 1, :]
            dense = jnp.where(cb == wrows, vb, ident)   # [width, lb]
            acc = comb(acc, red(dense, axis=1, keepdims=True))
        o_ref[...] = acc

    def by_row(x, dtype):
        return x.astype(dtype).reshape(cells, rows, lanes).transpose(1, 0, 2)

    blk = pl.BlockSpec((None, cells, lb), lambda r, c: (r, _I0, c))
    out = pl.pallas_call(
        kernel,
        grid=(rows, chunks),
        in_specs=[blk, blk],
        out_specs=pl.BlockSpec((None, width, 1),
                               lambda r, c: (r, _I0, _I0)),
        out_shape=jax.ShapeDtypeStruct((rows, width, 1), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(by_row(col, jnp.int32), by_row(val, jnp.float32))
    return out.reshape(rows, width)


def build_segment_fold(batch: int, runs: int, width: int, kind: str,
                       identity=0.0, packed: bool = False,
                       interpret=None):
    """Variable-segment fold under the dense-ingest runs bound:
    ``(k[batch] sorted run ids, lifted[batch, width]) -> [runs, width]``.

    Invalid lanes carry identity-masked values (the caller's existing
    ``_lift`` mask), so any run id they alias combines a no-op. One
    [width, runs] VMEM accumulator lives across the lane-chunk grid;
    the tiny [runs]-lane buffer update stays with the caller.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from . import resolve_interpret

    B, R, W = int(batch), int(runs), int(width)
    lb = _chunk(B)
    chunks = B // lb
    red, comb = _reducer(kind)
    ident = float(identity)

    def kernel(k_ref, v_ref, o_ref):
        c = pl.program_id(0)

        @pl.when(c == 0)
        def _init():
            o_ref[...] = jnp.full((W, R), ident, jnp.float32)

        kb = k_ref[...]                              # [1, lb]
        vb = v_ref[...].astype(jnp.float32)          # [W, lb]
        for r in range(R):                           # static runs loop
            upd = red(jnp.where(kb == r, vb, ident), axis=1,
                      keepdims=True)                 # [W, 1]
            o_ref[:, r:r + 1] = comb(o_ref[:, r:r + 1], upd)

    def fold(k, lifted):
        lifted = jnp.asarray(lifted)
        if packed:
            lifted = lifted.astype(jnp.bfloat16)
        out = pl.pallas_call(
            kernel,
            grid=(chunks,),
            in_specs=[
                pl.BlockSpec((1, lb), lambda c: (_I0, c)),
                pl.BlockSpec((W, lb), lambda c: (_I0, c)),
            ],
            out_specs=pl.BlockSpec((W, R), lambda c: (_I0, _I0)),
            out_shape=jax.ShapeDtypeStruct((W, R), jnp.float32),
            interpret=resolve_interpret(interpret),
        )(jnp.asarray(k, jnp.int32).reshape(1, B), lifted.T)
        return out.T

    return fold


#: bf16 unit roundoff (8 mantissa bits): each streamed value rounds
#: once; the accumulator stays f32, so the row error is bounded by the
#: lane count times one rounding — derived, and asserted as-is by the
#: differential suite.
BF16_EPS = 2.0 ** -8


def packed_tolerance(lanes: int, max_abs: float, kind: str) -> float:
    """The asserted bf16-packing error bound for one folded row
    (sum: ``lanes`` roundings accumulate; min/max: at most one)."""
    if kind in ("min", "max"):
        return float(max_abs) * BF16_EPS
    return float(lanes) * float(max_abs) * BF16_EPS

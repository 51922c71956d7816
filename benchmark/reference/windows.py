"""Plain reference for the benchmark's window deployments.

Time-measure tumbling and sliding windows over one stream, keyed by
nothing, answered from per-millisecond bins of the tuples that have
*arrived* when each watermark is handed over. It follows the upstream
Scotty library's semantics, written here from its Java sources:

* ``WindowManager.processWatermark``: the first watermark treats
  ``max(0, wm - maxLateness)`` as the last one; a store that never saw a
  tuple emits nothing; triggered windows come window by window, in
  registration order.
* ``TumblingWindow.triggerWindows``: every ``[s, s + size)`` with
  ``s >= lastStart`` and ``s + size <= wm``, ascending, where
  ``lastStart = last - mod(last + size, size)``.
* ``SlidingWindow.triggerWindows``: walk down from
  ``wm - mod(wm + slide, slide)`` by ``slide`` while
  ``s + size > last``, keeping ``s >= 0`` and ``s + size <= wm + 1``.
* A window's answer is the aggregate of every tuple with
  ``s <= ts < e`` that arrived before the watermark (a late tuple
  whose window has already fired does not fire it again).

It imports nothing of the system under test. ``precision="bfloat16"``
computes the same answers in bfloat16: values, per-millisecond sums and
window sums are rounded to it. That is the benchmark's control.
"""

from __future__ import annotations

import numpy as np

AGGS = ("sum", "min", "max")


def _bf16(x):
    import ml_dtypes

    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


def tumbling_triggers(size: int, last: int, wm: int):
    last_start = last - (last + size) % size
    starts = []
    s = last_start
    while s + size <= wm:
        starts.append(s)
        s += size
    starts = np.asarray(starts, np.int64)
    return starts, starts + size


def sliding_triggers(size: int, slide: int, last: int, wm: int):
    top = wm - (wm + slide) % slide
    # the Java loop: for s = top, top - slide, ... while s + size > last
    n = max(0, -(-(top + size - last) // slide))
    starts = top - slide * np.arange(n, dtype=np.int64)
    keep = (starts >= 0) & (starts + size <= wm + 1)
    starts = starts[keep]
    return starts, starts + size


def triggers(window: dict, last: int, wm: int):
    if window["kind"] == "tumbling":
        return tumbling_triggers(int(window["size"]), last, wm)
    if window["kind"] == "sliding":
        return sliding_triggers(int(window["size"]), int(window["slide"]),
                                last, wm)
    raise ValueError(f"no reference for window kind {window['kind']!r}")


def bins_of(ts, vals, aggs, precision="float64"):
    """Per-millisecond bins of one block of tuples: ``(lo, count, sum,
    min, max)`` over ``[lo, ts.max()]`` (``min``/``max`` only when asked
    for; empty bins hold +inf / -inf)."""
    ts = np.asarray(ts, np.int64)
    v = np.asarray(vals, np.float64)
    if precision == "bfloat16":
        v = _bf16(v)
    lo = int(ts.min())
    rel = ts - lo
    n = int(rel.max()) + 1
    cnt = np.bincount(rel, minlength=n).astype(np.int64)
    out = {"count": cnt}
    if "sum" in aggs:
        s = np.bincount(rel, weights=v, minlength=n)
        out["sum"] = _bf16(s) if precision == "bfloat16" else s
    if "min" in aggs or "max" in aggs:
        order = np.argsort(rel, kind="stable")
        r, w = rel[order], v[order]
        first = np.flatnonzero(np.r_[True, r[1:] != r[:-1]])
        if "min" in aggs:
            mn = np.full(n, np.inf)
            mn[r[first]] = np.minimum.reduceat(w, first)
            out["min"] = mn
        if "max" in aggs:
            mx = np.full(n, -np.inf)
            mx[r[first]] = np.maximum.reduceat(w, first)
            out["max"] = mx
    return lo, out


class WindowReference:
    """One deployment's windows, fed in arrival order: :meth:`arrive`
    for each block of bins, :meth:`watermark` for each watermark."""

    def __init__(self, windows, aggs, max_lateness: int,
                 precision: str = "float64"):
        self.windows = list(windows)
        self.aggs = tuple(aggs)
        for a in self.aggs:
            if a not in AGGS:
                raise ValueError(f"no reference for aggregation {a!r}")
        self.max_lateness = int(max_lateness)
        self.precision = precision
        self.last_wm = -1
        self.seen = False
        self._n = 0
        self._grow(1 << 16)

    def _grow(self, n: int) -> None:
        fill = {"count": 0, "sum": 0.0, "min": np.inf, "max": -np.inf}
        old = getattr(self, "bins", None)
        self.bins = {}
        for k in ("count",) + self.aggs:
            arr = np.full(n, fill[k],
                          np.int64 if k == "count" else np.float64)
            if old is not None:
                arr[:self._n] = old[k]
            self.bins[k] = arr
        self._n = n

    def arrive(self, base: int, block_bins) -> None:
        """Add a block's bins (from :func:`bins_of`) shifted by ``base``
        milliseconds."""
        lo, b = block_bins
        lo += int(base)
        hi = lo + b["count"].shape[0]
        if lo < 0:
            raise ValueError("event time before 0")
        while hi > self._n:
            self._grow(2 * self._n)
        sl = slice(lo, hi)
        self.bins["count"][sl] += b["count"]
        if "sum" in self.aggs:
            self.bins["sum"][sl] += b["sum"]
        for a, fold in (("min", np.minimum), ("max", np.maximum)):
            if a in self.aggs:
                fold(self.bins[a][sl], b[a], out=self.bins[a][sl])
        self.seen = True

    def watermark(self, wm: int):
        """``(starts, ends, counts, {agg: values})`` of every window this
        watermark triggers, in emission order."""
        last = self.last_wm if self.last_wm >= 0 \
            else max(0, wm - self.max_lateness)
        self.last_wm = wm
        empty = np.empty(0, np.int64)
        if not self.seen:
            return empty, empty, empty, {a: np.empty(0) for a in self.aggs}
        parts = [triggers(w, last, wm) for w in self.windows]
        ws = np.concatenate([p[0] for p in parts])
        we = np.concatenate([p[1] for p in parts])
        if ws.size == 0:
            return ws, we, empty, {a: np.empty(0) for a in self.aggs}
        hi = int(we.max())
        while hi > self._n:
            self._grow(2 * self._n)
        lo = int(ws.min())
        pc = np.r_[0, np.cumsum(self.bins["count"][lo:hi])]
        cnt = pc[we - lo] - pc[ws - lo]
        vals = {}
        if "sum" in self.aggs:
            ps = np.r_[0.0, np.cumsum(self.bins["sum"][lo:hi])]
            s = ps[we - lo] - ps[ws - lo]
            vals["sum"] = _bf16(s) if self.precision == "bfloat16" else s
        for a, red in (("min", np.min), ("max", np.max)):
            if a in self.aggs:
                arr = self.bins[a]
                vals[a] = np.asarray([red(arr[s:e]) if e > s else np.nan
                                      for s, e in zip(ws, we)])
        return ws, we, cnt, vals

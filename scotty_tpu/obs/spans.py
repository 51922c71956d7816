"""Span/timer API: lightweight wall-time phase attribution.

``SpanRecorder.span("ingest")`` times a host-side phase; spans nest (a
per-thread stack tracks depth/parentage) and export as Chrome-trace /
Perfetto JSON (``chrome://tracing``, https://ui.perfetto.dev). Every span
also opens a ``jax.profiler.TraceAnnotation`` named ``scotty.<name>``, so
the same phases sit on the profiler's clock inside a captured device
trace; without an active profiler session the annotation is inert.

:func:`program_span` is the one face the served path uses: the
annotation always, plus the recorded :class:`Span` when an
:class:`~scotty_tpu.obs.Observability` is attached.

Host wall-time only by design: nothing here may enter a jitted code path —
spans wrap *dispatch* regions, and device time is attributed by the
jax.profiler composition, not by this clock.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from typing import List

PREFIX = "scotty."


def _annotation(name: str, args: dict):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(PREFIX + name, **args)


def program_span(obs, name: str, **args):
    """Context manager around one stage of the served path: a profiler
    annotation ``scotty.<name>`` carrying ``args`` (inert without a
    profiler session), recorded in ``obs``'s span recorder too when
    ``obs`` is not None. It yields the annotation, whose
    ``set_metadata(**more)`` adds args known only inside the span."""
    if obs is None:
        return _annotation(name, args)
    return obs.span(name, **args)


class Span:
    """One closed span: ``t0``/``dur`` are seconds relative to the
    recorder's epoch."""

    __slots__ = ("name", "t0", "dur", "depth", "tid")

    def __init__(self, name: str, t0: float, dur: float, depth: int,
                 tid: int):
        self.name = name
        self.t0 = t0
        self.dur = dur
        self.depth = depth
        self.tid = tid

    def __repr__(self):  # pragma: no cover - debug aid
        return (f"Span({self.name!r}, t0={self.t0:.6f}, "
                f"dur={self.dur * 1e3:.3f}ms, depth={self.depth})")


class SpanRecorder:
    """Collects :class:`Span` records; thread-safe; bounded by
    ``max_spans`` (oldest kept — a runaway per-interval span loop must not
    grow without limit, mirroring the bounded metrics reservoir)."""

    def __init__(self, max_spans: int = 65536, clock=time.perf_counter):
        self._clock = clock
        self._epoch = clock()
        self._lock = threading.Lock()
        self._local = threading.local()
        self._dropped = 0
        self.max_spans = int(max_spans)
        self.spans: List[Span] = []

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Time a phase. Nested calls record increasing ``depth``; the
        inner span closes (and is appended) before the outer one, so
        Chrome-trace viewers reconstruct the flame from timestamps.
        ``args`` ride on the phase's profiler annotation, which the
        context manager yields."""
        stack = self._stack()
        depth = len(stack)
        stack.append(name)
        with _annotation(name, args) as ann:
            t0 = self._clock()
            try:
                yield ann
            finally:
                dur = self._clock() - t0
                stack.pop()
                with self._lock:
                    if len(self.spans) < self.max_spans:
                        self.spans.append(Span(
                            name, t0 - self._epoch, dur, depth,
                            threading.get_ident()))
                    else:
                        self._dropped += 1

    def record_span(self, name: str, t0_rel: float, dur: float,
                    depth: int = 0) -> None:
        """Append one ALREADY-CLOSED span (seconds relative to the
        recorder's epoch) — the post-hoc face the emission-latency
        tracer uses to land ``latency/<stage>`` spans in the Chrome
        trace without having wrapped the region in a context manager.
        Bounded exactly like :meth:`span`."""
        with self._lock:
            if len(self.spans) < self.max_spans:
                self.spans.append(Span(name, float(t0_rel), float(dur),
                                       depth, threading.get_ident()))
            else:
                self._dropped += 1

    # -- export -----------------------------------------------------------
    def summary(self) -> dict:
        """Per-name aggregate: count / total / mean / max milliseconds."""
        out: dict = {}
        with self._lock:
            spans = list(self.spans)
            dropped = self._dropped
        for s in spans:
            row = out.setdefault(s.name, {"count": 0, "total_ms": 0.0,
                                          "max_ms": 0.0})
            row["count"] += 1
            row["total_ms"] += s.dur * 1e3
            row["max_ms"] = max(row["max_ms"], s.dur * 1e3)
        for row in out.values():
            row["mean_ms"] = row["total_ms"] / row["count"]
        if dropped:
            out["_dropped_spans"] = dropped
        return out

    def to_chrome_trace(self) -> List[dict]:
        """Complete-event (``"ph": "X"``) list in Chrome-trace JSON; wrap
        as ``{"traceEvents": [...]}`` or pass to :meth:`dump_chrome_trace`.
        Timestamps/durations are microseconds per the format."""
        with self._lock:
            spans = list(self.spans)
        return [{"name": s.name, "ph": "X", "ts": s.t0 * 1e6,
                 "dur": s.dur * 1e6, "pid": 0, "tid": s.tid,
                 "args": {"depth": s.depth}} for s in spans]

    def dump_chrome_trace(self, path: str) -> None:
        # scotty: allow(fsio-discipline) — trace export for tooling
        # (chrome://tracing), not committed state: no manifest records
        # it and no restore ever reads it back
        with open(path, "w") as f:
            # scotty: allow(fsio-discipline) — same export exemption
            json.dump({"traceEvents": self.to_chrome_trace(),
                       "displayTimeUnit": "ms"}, f)

    def clear(self) -> None:
        with self._lock:
            self.spans = []
            self._dropped = 0

"""Program spans on the served path: each stage of ingest and watermark
opens a ``scotty.<stage>`` profiler annotation (recorded in the span
recorder too when an ``Observability`` is attached), nested on the
calling thread, with the stage's counts as args."""

from __future__ import annotations

import gc
import glob

import numpy as np
import pytest

from scotty_tpu.core.aggregates import SumAggregation
from scotty_tpu.core.windows import TumblingWindow, WindowMeasure
from scotty_tpu.engine import EngineConfig
from scotty_tpu.engine.operator import TpuWindowOperator
from scotty_tpu.ingest import LineRateFeed, RingConfig
from scotty_tpu.obs import Observability, Span, SpanRecorder
from scotty_tpu.resilience import chaos
from scotty_tpu.shaper import ShaperConfig

SMALL = EngineConfig(capacity=1 << 12, batch_size=64, annex_capacity=256,
                     min_trigger_pad=32)
N = 640
WATERMARKS = (4000, 8000, 12_000)
CUTS = (0, 214, 428, N)

INGEST = {"ingest.offer", "ingest.ring_full", "ingest.stage",
          "ingest.transfer_wait", "ingest.dispatch"}
WATERMARK = {"watermark", "watermark.flush_ingest", "watermark.trigger",
             "watermark.query", "watermark.gc", "watermark.fetch",
             "watermark.lower"}
EXPECTED = {
    "inorder": INGEST | WATERMARK,
    "shaped": INGEST | WATERMARK | {"shaper.split", "watermark.merge"},
    "device": {"ingest.dispatch"} | WATERMARK,
}
ARGS = {"ingest.offer": {"n"}, "ingest.stage": {"bytes"},
        "ingest.dispatch": {"lanes", "n_valid", "late"},
        "watermark.trigger": {"wm", "T"}}


def _stream(mode):
    rng = chaos.rng_of(7)
    vals = rng.integers(0, 100, N).astype(np.float32)
    base = np.arange(N, dtype=np.int64) * 20
    if mode == "shaped":
        # disorder beyond the accumulator's slack: some blocks reach back
        # behind the operator's head and take the late dispatch
        base = np.maximum(base + rng.integers(-400, 400, N), 0)
    return vals, base.astype(np.int64)


def _run(mode, obs=None):
    """Feed the stream in three chunks, each followed by a watermark;
    returns every watermark's ``(ws, we, cnt, lowered)``."""
    import jax

    vals, ts = _stream(mode)
    op = TpuWindowOperator(config=SMALL, obs=obs)
    op.add_window_assigner(TumblingWindow(WindowMeasure.Time, 1000))
    op.add_aggregation(SumAggregation())
    op.set_max_lateness(2000)
    feed = None
    if mode != "device":
        shaper = ShaperConfig(slack_ms=100) if mode == "shaped" else None
        feed = LineRateFeed(op, ring=RingConfig(depth=2, prefetch=2),
                            shaper=shaper)
    B = SMALL.batch_size
    out = []
    for lo, hi, wm in zip(CUTS, CUTS[1:], WATERMARKS):
        if feed is not None:
            feed.offer_block(vals[lo:hi], ts[lo:hi])
        else:
            for i in range(lo, hi, B):
                v = np.zeros(B, np.float32)
                t = np.full(B, ts[min(i + B, hi) - 1], np.int64)
                n = min(B, hi - i)
                v[:n], t[:n] = vals[i:i + n], ts[i:i + n]
                op.ingest_device_batch(jax.device_put(v), jax.device_put(t),
                                       int(t[0]), int(t[-1]), n_valid=n)
        out.append(op.process_watermark_arrays(wm))
    op.check_overflow()
    return out


def _traced(mode, tmp_path, obs=None):
    """Run under the profiler; returns the answers and the ``scotty.*``
    host events as ``(name, start, end, args, thread)``."""
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        out = _run(mode, obs)
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("scotty."):
                    events.append((ev.name[len("scotty."):], ev.start_ns,
                                   ev.end_ns, dict(ev.stats), line.name))
    return out, events


def _inside(child, parent):
    return (child[4] == parent[4] and parent[1] <= child[1]
            and child[2] <= parent[2])


def _same_answers(a, b):
    assert len(a) == len(b)
    for (ws1, we1, c1, l1), (ws2, we2, c2, l2) in zip(a, b):
        np.testing.assert_array_equal(ws1, ws2)
        np.testing.assert_array_equal(we1, we2)
        np.testing.assert_array_equal(c1, c2)
        for x, y in zip(l1, l2):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("mode", ["inorder", "shaped", "device"])
def test_spans_nest_with_args_in_the_profiler_trace(mode, tmp_path):
    _, events = _traced(mode, tmp_path)
    names = {e[0] for e in events}
    assert names == EXPECTED[mode]
    for e in events:
        assert ARGS.get(e[0], set()) <= set(e[3]), e
    wms = [e for e in events if e[0] == "watermark"]
    assert [e[3]["wm"] for e in wms] == list(WATERMARKS)
    for e in events:
        if e[0].startswith("watermark."):
            parents = [p for p in wms if _inside(e, p)]
            assert len(parents) == 1, e
            assert e[3]["wm"] == parents[0][3]["wm"]
    offers = [e for e in events if e[0] in ("ingest.offer",
                                            "watermark.flush_ingest")]
    for e in events:
        if e[0] in ("ingest.ring_full", "ingest.stage",
                    "ingest.transfer_wait") or (
                e[0] in ("ingest.dispatch", "shaper.split")
                and mode != "device"):
            assert any(_inside(e, p) for p in offers), e
    dispatch = [e[3] for e in events if e[0] == "ingest.dispatch"]
    assert all(d["lanes"] >= d["n_valid"] >= 0 for d in dispatch)
    assert sum(d["n_valid"] for d in dispatch
               if str(d["late"]) in ("0", "False", "false")) == N
    if mode == "shaped":
        assert any(str(d["late"]) in ("1", "True", "true") for d in dispatch)
    if mode != "device":
        assert sum(e[3]["n"] for e in events if e[0] == "ingest.offer") == N


@pytest.mark.parametrize("mode", ["inorder", "shaped"])
def test_recorder_sees_the_same_spans(mode, tmp_path):
    obs = Observability()
    out, events = _traced(mode, tmp_path, obs)
    summary = obs.spans.summary()
    counts = {}
    for e in events:
        counts[e[0]] = counts.get(e[0], 0) + 1
    assert {k: v["count"] for k, v in summary.items()} == counts
    _same_answers(out, _run(mode))


@pytest.mark.parametrize("mode", ["inorder", "shaped", "device"])
def test_no_profiler_no_obs_keeps_nothing_and_answers_alike(mode, tmp_path):
    traced, _ = _traced(mode, tmp_path)
    gc.collect()
    before = sum(isinstance(o, Span) for o in gc.get_objects())
    plain = _run(mode)
    gc.collect()
    assert sum(isinstance(o, Span) for o in gc.get_objects()) == before
    _same_answers(plain, traced)
    # and both match the engine's own host-fed path on the same stream
    vals, ts = _stream(mode)
    op = TpuWindowOperator(config=SMALL)
    op.add_window_assigner(TumblingWindow(WindowMeasure.Time, 1000))
    op.add_aggregation(SumAggregation())
    op.set_max_lateness(2000)
    if mode == "shaped":
        return          # the shaped feed's split points are its own
    want = []
    for lo, hi, wm in zip(CUTS, CUTS[1:], WATERMARKS):
        op.process_elements(vals[lo:hi], ts[lo:hi])
        want.append(op.process_watermark_arrays(wm))
    _same_answers(plain, want)


def test_recorder_span_yields_its_annotation():
    rec = SpanRecorder()
    with rec.span("watermark.trigger", wm=3) as ann:
        ann.set_metadata(T=5)
    [s] = rec.spans
    assert s.name == "watermark.trigger" and s.depth == 0

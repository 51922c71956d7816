"""Reduction of the program's own spans in a JAX profiler trace.

The served path opens a ``scotty.<stage>`` profiler annotation at each
layer boundary (``scotty_tpu.obs.program_span``): ``ingest.offer``,
``ingest.ring_full``, ``ingest.stage``, ``ingest.transfer_wait``,
``shaper.split``, ``ingest.dispatch`` (args ``lanes``, ``n_valid``,
``late``) and, per watermark (arg ``wm``), ``watermark`` with its
children ``flush_ingest``, ``merge``, ``trigger`` (arg ``T``),
``query``, ``gc``, ``fetch`` and ``lower``. This module reads them
beside ``trace.py``'s ``bench.*`` reduction, whose window, busy time and
launch attribution it reuses unchanged:

* each device program goes to the innermost program span around its
  launch;
* each idle gap is labelled ``<bench span>/<innermost scotty span>``
  (the bench name alone where no program span encloses its midpoint);
* the per-stage numbers of ``readings`` (below).

Two faces:

    python3 benchmark/program_spans.py <file.xplane.pb>
    python3 benchmark/program_spans.py --workload <cell> --seed <n> \\
        --seconds <s> [--keep <dir>]

The first reduces a recorded trace. The second runs ``run.py``'s cell
with ``--trace 1``, keeps the trace it takes (in ``--keep``), prints the
run's result line and then one line of span readings.
"""

from __future__ import annotations

import bisect
import json
import pathlib
import statistics
import sys
from dataclasses import dataclass, field

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

PREFIX = "scotty."
WATERMARK_CHILDREN = ("flush_ingest", "merge", "trigger", "query", "gc",
                      "fetch", "lower")
WATERMARK_PROGRAMS = ("watermark.merge", "watermark.query", "watermark.gc")
H2D = ("ingest.stage", "ingest.transfer_wait")


@dataclass
class ProgramSpans:
    """The ``scotty.*`` host spans of one trace, by start (times in ns on
    the profiler's clock), with each span's parent, and the reduced
    ``trace.Trace`` of the same file."""

    trace: object
    spans: list = field(default_factory=list)     # (name, s, e, args)
    starts: list = field(default_factory=list)
    parent: list = field(default_factory=list)    # index or -1

    def in_window(self, name=None):
        t0, t1 = self.trace.t0, self.trace.t1
        return [sp for sp in self.spans if t0 <= sp[1] and sp[2] <= t1
                and (name is None or sp[0] == name)]

    def innermost(self, t):
        """Index of the innermost program span around host time ``t``,
        or -1."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.spans[i][2] < t:
            i = self.parent[i]
        return i

    def label(self, t) -> str:
        bench = self.trace._span_at(t) or "outside bench spans"
        i = self.innermost(t)
        return bench if i < 0 else f"{bench}/{PREFIX}{self.spans[i][0]}"

    def launched_device_s(self, names) -> float:
        """Device seconds (clipped to the window, averaged over devices)
        of the programs whose launch lies innermost in a span named in
        ``names``."""
        tr = self.trace
        total = 0.0
        for d, name, s, e, run in tr._in_window():
            t = tr.enqueue.get(run)
            if t is None:
                continue
            i = self.innermost(t)
            if i >= 0 and self.spans[i][0] in names:
                total += min(e, tr.t1) - max(s, tr.t0)
        return total * 1e-9 / max(1, tr.n_devices)

    def idle_gaps(self, top: int = 10) -> list:
        """The longest device idle gaps in the window, each labelled by
        the bench span and the innermost program span around its
        midpoint (as ``trace.Trace.breakdown`` finds them)."""
        tr = self.trace
        mods = sorted(tr._in_window(), key=lambda m: (m[0], m[2]))
        gaps = []
        for dev in range(tr.n_devices):
            end = tr.t0
            for d, _, s, e, _ in mods:
                if d != dev:
                    continue
                if s > end:
                    gaps.append((s - end, end, s))
                end = max(end, e)
            if tr.t1 > end:
                gaps.append((tr.t1 - end, end, tr.t1))
        gaps.sort(reverse=True)
        return [[self.label((a + b) / 2), g * 1e-9] for g, a, b in gaps[:top]]

    def watermarks(self) -> list:
        """Per timed watermark: ``{"ms": span, "at": start, child: ms,
        ...}``."""
        out = {}
        t0, t1 = self.trace.t0, self.trace.t1
        for idx, sp in enumerate(self.spans):
            if not (t0 <= sp[1] and sp[2] <= t1):
                continue
            if sp[0] == "watermark":
                row = out.setdefault(idx, {})
                row["ms"] = (sp[2] - sp[1]) * 1e-6
                row["at"] = (sp[1], sp[2])
            elif sp[0].startswith("watermark."):
                p = self.parent[idx]
                if p >= 0 and self.spans[p][0] == "watermark":
                    row = out.setdefault(p, {})
                    key = sp[0][len("watermark."):]
                    row[key] = row.get(key, 0.0) + (sp[2] - sp[1]) * 1e-6
        return [out[k] for k in sorted(out) if "ms" in out[k]]


def reduce_file(path) -> ProgramSpans:
    from jax.profiler import ProfileData

    trace_mod = harness.load_module(HERE / "trace.py")
    ps = ProgramSpans(trace=trace_mod.reduce_file(path))
    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = [(ev.name[len(PREFIX):], ev.start_ns, ev.end_ns,
                      dict(ev.stats)) for ev in line.events
                     if ev.name.startswith(PREFIX)]
            if found:
                lines.append(found)
    # the served path runs on one thread; nesting is per thread
    for found in lines:
        base = len(ps.spans)
        found.sort(key=lambda x: (x[1], -x[2]))
        stack = []
        for j, sp in enumerate(found):
            # a span on the stack that ends before this one does not
            # contain it
            while stack and found[stack[-1]][2] < sp[2]:
                stack.pop()
            ps.parent.append(base + stack[-1] if stack else -1)
            stack.append(j)
        ps.spans.extend(found)
    order = sorted(range(len(ps.spans)), key=lambda k: ps.spans[k][1])
    remap = {old: new for new, old in enumerate(order)}
    ps.parent = [remap.get(ps.parent[k], -1) for k in order]
    ps.spans = [ps.spans[k] for k in order]
    ps.starts = [sp[1] for sp in ps.spans]
    return ps


def readings(ps: ProgramSpans) -> dict:
    """The per-stage numbers, or {} where the trace holds no program
    spans:

    * ``watermark.fetch_wait_ms``: median over timed watermarks of the
      ``watermark.fetch`` span;
    * ``watermark.host_ms``: median of ``watermark`` minus its fetch;
    * ``watermark.program_device_ms``: device time per watermark of the
      programs launched inside ``watermark.merge``/``query``/``gc``;
    * ``ingest.h2d_share``: % of the window inside ``ingest.stage`` or
      ``ingest.transfer_wait`` (their union);
    * ``kernel.ingest_fill``: sum of ``n_valid`` over sum of ``lanes``
      of the ``ingest.dispatch`` spans, in %;
    * ``check``: the self-consistency numbers (children's cover of each
      watermark span, and program spans against the bench spans).
    """
    tr = ps.trace
    wms = ps.watermarks()
    if not wms or tr.window_s <= 0:
        return {}
    out = {
        "watermark.fetch_wait_ms": statistics.median(
            w.get("fetch", 0.0) for w in wms),
        "watermark.host_ms": statistics.median(
            w["ms"] - w.get("fetch", 0.0) for w in wms),
        "watermark.program_device_ms":
            1e3 * ps.launched_device_s(WATERMARK_PROGRAMS) / len(wms),
    }
    h2d = [(sp[1], sp[2]) for sp in ps.in_window() if sp[0] in H2D]
    if h2d:
        out["ingest.h2d_share"] = 100.0 * _union_ns(h2d) * 1e-9 / tr.window_s
    disp = [sp[3] for sp in ps.in_window("ingest.dispatch")]
    lanes = sum(int(a.get("lanes", 0)) for a in disp)
    if lanes:
        out["kernel.ingest_fill"] = 100.0 * sum(
            int(a.get("n_valid", 0)) for a in disp) / lanes
    bench = {}
    for name, s, e in tr.spans:
        bench[name] = bench.get(name, 0.0) + (e - s) * 1e-9
    covers = [sum(w.get(k, 0.0) for k in WATERMARK_CHILDREN) / w["ms"]
              for w in wms if w["ms"] > 0]
    spans_s = {}
    for sp in ps.in_window():
        spans_s[sp[0]] = spans_s.get(sp[0], 0.0) + (sp[2] - sp[1]) * 1e-9
    check = {"watermarks": len(wms), "children_cover_min": min(covers),
             "children_cover_median": statistics.median(covers),
             "watermark_over_bench": spans_s.get("watermark", 0.0)
             / bench.get("bench.watermark", float("nan"))}
    if "ingest.offer" in spans_s:
        check["offer_over_bench"] = (spans_s["ingest.offer"]
                                     / bench.get("bench.ingest",
                                                 float("nan")))
    slow = _slow_watermarks(ps, wms)
    out["check"] = check
    out["spans_s"] = spans_s
    out["watermark_ms"] = [round(w["ms"], 3) for w in wms]
    if slow:
        out["slow_watermarks"] = slow
    return out


def _slow_watermarks(ps, wms, factor=1.5) -> list:
    """Watermarks over ``factor`` times the median: each with its
    children's times and its three longest inner spans at any depth, so
    the stage that stalled is named."""
    med = statistics.median(w["ms"] for w in wms)
    out = []
    for w in wms:
        if w["ms"] <= factor * med:
            continue
        s, e = w["at"]
        inner = sorted(((sp[2] - sp[1]) * 1e-6, sp[0]) for sp in ps.spans
                       if s <= sp[1] and sp[2] <= e and sp[0] != "watermark")
        row = {k: round(v, 3) for k, v in w.items() if k != "at"}
        row["longest"] = [[n, round(ms, 3)] for ms, n in inner[::-1][:3]]
        out.append(row)
    return out


def _union_ns(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        s = max(s, end)
        if e > s:
            total += e - s
            end = e
    return total


def report(path) -> dict:
    ps = reduce_file(path)
    out = readings(ps)
    out["idle_gaps"] = ps.idle_gaps()
    return out


def traced_run(argv, keep=None) -> tuple:
    """Run one cell of ``run.py`` with ``--trace 1`` and return its
    result, its notes and the span report of its trace. ``run.py``
    removes the trace once it has read it; here the directory is
    reduced first (and copied to ``keep``, when given)."""
    import shutil

    run_mod = harness.load_module(HERE / "run.py")
    args = run_mod.parse(list(argv) + ["--trace", "1"])
    got = {}
    remove = shutil.rmtree

    def reduce_then_remove(path, *a, **kw):
        if "bench-trace-" in str(path) and "report" not in got:
            [f] = pathlib.Path(path).glob("**/*.xplane.pb")
            got["report"] = report(f)
            if keep is not None:
                dst = pathlib.Path(keep)
                dst.mkdir(parents=True, exist_ok=True)
                shutil.copy(f, dst / f"{args.workload}-{args.seed}.xplane.pb")
        return remove(path, *a, **kw)

    shutil.rmtree = reduce_then_remove
    try:
        result, notes = run_mod.run_cell(args)
    finally:
        shutil.rmtree = remove
    return result, notes, got.get("report", {})


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if argv and not argv[0].startswith("--"):
        print(json.dumps(report(argv[0])))
        return 0
    keep = None
    if "--keep" in argv:
        i = argv.index("--keep")
        keep, argv = argv[i + 1], argv[:i] + argv[i + 2:]
    result, notes, rep = traced_run(argv, keep)
    print("notes " + json.dumps(notes, default=str), file=sys.stderr)
    print(json.dumps(result))
    print(json.dumps({"workload": argv[argv.index("--workload") + 1],
                      "program_spans": rep}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

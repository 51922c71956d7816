"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

Read from the trace:

* device busy time: the union of the program executions on the
  ``XLA Modules`` line of each ``/device:TPU:<n>`` plane, inside the
  traced window, averaged over the devices;
* the traced window: from the start of the first ``bench.*`` host span
  (the benchmark's ``TraceAnnotation`` around each call into the
  system) to the end of the last;
* which host span launched each program: a program execution carries a
  ``run_id``; the host's ``DoEnqueueProgram`` event with that ``run_id``
  runs on a queue thread inside an ``IssueSequencedEvent`` whose flow
  leads back to the ``tpu::System::Execute`` call on the launching
  thread; the ``bench.*`` span around that call launched the program
  (the enqueue time stands in where the flow is missing);
* which layer each program belongs to, by its module name, through
  ``layers.json`` (module names lose their ``(hash)`` suffix first).
"""

from __future__ import annotations

import bisect
import glob
import json
import pathlib
import re
from dataclasses import dataclass, field

HERE = pathlib.Path(__file__).resolve().parent
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(hlo: str) -> str:
    """``%fusion.2 f32[131072,1]`` from an HLO instruction's text."""
    name, _, rest = hlo.partition(" = ")
    out = re.sub(r"\{[^}]*\}", "", rest.split(" ")[0]) if rest else ""
    return f"{name} {out.strip('(,')}".strip()


def load_layers():
    with open(HERE / "layers.json") as f:
        table = json.load(f)["modules"]
    return [(re.compile(pat), layer) for pat, layer in table]


def layer_of(name: str, table) -> str:
    for pat, layer in table:
        if pat.search(name):
            return layer
    return "unattributed"


def union_s(intervals, lo, hi) -> float:
    total, end = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total * 1e-9


@dataclass
class Trace:
    """One reduced trace (times in ns on the profiler's clock)."""

    modules: list = field(default_factory=list)   # (dev, name, s, e, run)
    ops: list = field(default_factory=list)       # (dev, name, s, e)
    enqueue: dict = field(default_factory=dict)   # run_id -> host ns
    spans: list = field(default_factory=list)     # (name, s, e), by start
    starts: list = field(default_factory=list)    # the spans' starts
    n_devices: int = 0
    t0: float = 0.0
    t1: float = 0.0

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        per_dev = [union_s([(s, e) for d, _, s, e, _ in self.modules
                            if d == dev], self.t0, self.t1)
                   for dev in range(self.n_devices)]
        return sum(per_dev) / max(1, len(per_dev))

    def _in_window(self):
        return [m for m in self.modules if m[3] > self.t0 and m[2] < self.t1]

    def _span_at(self, t):
        """Name of the ``bench.*`` span around host time ``t``."""
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.spans[i][2] >= t:
            return self.spans[i][0]
        return None

    def launched_device_s(self, span: str) -> float:
        """Device seconds of the programs launched inside ``bench.<span>``
        spans (clipped to the window; averaged over devices)."""
        want = "bench." + span
        total = 0.0
        for d, name, s, e, run in self._in_window():
            t = self.enqueue.get(run)
            if t is not None and self._span_at(t) == want:
                total += min(e, self.t1) - max(s, self.t0)
        return total * 1e-9 / max(1, self.n_devices)

    def layer_device_s(self, layer: str) -> float:
        """Device seconds of the programs of one ``layers.json`` layer."""
        table = load_layers()
        total = sum(min(e, self.t1) - max(s, self.t0)
                    for d, name, s, e, _ in self._in_window()
                    if layer_of(name, table) == layer)
        return total * 1e-9 / max(1, self.n_devices)

    def breakdown(self) -> dict:
        """The device operations that took most time (``module:op``) and
        the longest idle gaps, each labelled by the host span around
        it."""
        mods = sorted(self._in_window(), key=lambda m: (m[0], m[2]))
        by_dev = {}
        for d, name, s, e, _ in mods:
            by_dev.setdefault(d, ([], []))
            by_dev[d][0].append(s)
            by_dev[d][1].append(name)
        per_op = {}
        for d, op, s, e in self.ops:
            if e <= self.t0 or s >= self.t1:
                continue
            st, names = by_dev.get(d, ([], []))
            i = bisect.bisect_right(st, s) - 1
            key = (names[i] if i >= 0 else "?") + ":" + op
            per_op[key] = per_op.get(key, 0.0) + (min(e, self.t1)
                                                   - max(s, self.t0))
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
        gaps = []
        for dev in range(self.n_devices):
            end = self.t0
            for d, name, s, e, _ in mods:
                if d != dev:
                    continue
                if s > end:
                    gaps.append((s - end, end, s))
                end = max(end, e)
            if self.t1 > end:
                gaps.append((self.t1 - end, end, self.t1))
        gaps.sort(reverse=True)
        idle = [[self._span_at((a + b) / 2) or "outside bench spans",
                 g * 1e-9] for g, a, b in gaps[:10]]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": idle}


def reduce_file(path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    tr = Trace()
    devices = {}
    launch, enq = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(plane.name, len(devices))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        run = dict(ev.stats).get("run_id")
                        tr.modules.append((dev, module_name(ev.name),
                                           ev.start_ns, ev.end_ns, run))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        tr.ops.append((dev, op_name(ev.name),
                                       ev.start_ns, ev.end_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                issued = []                         # (start, end, flow)
                for ev in line.events:
                    name = ev.name
                    if name.startswith("bench."):
                        tr.spans.append((name, ev.start_ns, ev.end_ns))
                    elif name == "tpu::System::Execute":
                        flow = dict(ev.stats).get("_p")
                        if flow is not None:
                            launch[flow] = ev.start_ns
                    elif name == "tpu::System::Execute=>IssueSequencedEvent":
                        issued.append((ev.start_ns, ev.end_ns,
                                       dict(ev.stats).get("_c")))
                    elif name == "DoEnqueueProgram":
                        run = dict(ev.stats).get("run_id")
                        if run is not None:
                            enq.append((run, ev.start_ns, issued[-1]
                                        if issued else None))
    for run, t_enq, issue in enq:
        # the Python thread's launch: the enqueue runs on a queue thread,
        # inside the issue event that the launch's flow points to
        t_launch = None
        if issue is not None and issue[0] <= t_enq <= issue[1]:
            t_launch = launch.get(issue[2])
        tr.enqueue.setdefault(run, t_enq if t_launch is None else t_launch)
    tr.n_devices = len(devices)
    tr.spans.sort(key=lambda x: x[1])
    tr.starts = [s for _, s, _ in tr.spans]
    if tr.spans:
        tr.t0 = tr.spans[0][1]
        tr.t1 = max(e for _, _, e in tr.spans)
    return tr


def reduce_dir(trace_dir) -> Trace:
    files = glob.glob(str(pathlib.Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    return reduce_file(files[0])


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    trace: Trace
    spans: dict
    window_s: float
    tuples: int
    watermarks: int
    config: dict
    counters: dict
    device_kind: str

    @property
    def peaks(self) -> dict:
        with open(HERE / "peaks.json") as f:
            table = json.load(f)["devices"]
        if self.device_kind not in table:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           "in benchmark/peaks.json")
        return table[self.device_kind]

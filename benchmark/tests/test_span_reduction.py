"""The program-span reduction (``benchmark/program_spans.py``) on the
traces recorded on the chip in ``fixtures/``:

* on the trace of a program without spans (``sliding60k-served``), the
  ``trace.py`` readings are the ones recorded before the spans existed,
  the span readings are empty and the idle gaps keep their ``bench.*``
  labels;
* on a trace with program spans (``tumbling1000-ooo-served-spans``),
  every span reading is finite and the idle gaps carry ``scotty.*``
  names.

Run: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``
"""

from __future__ import annotations

import math
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import program_spans  # noqa: E402

FIXTURES = HERE / "fixtures"
OLD = FIXTURES / "sliding60k-served.xplane.pb"
NEW = FIXTURES / "tumbling1000-ooo-served-spans.xplane.pb"
READINGS = ("watermark.fetch_wait_ms", "watermark.host_ms",
            "watermark.program_device_ms", "ingest.h2d_share",
            "kernel.ingest_fill")


def test_old_fixture_reads_as_before():
    tr = harness.load_module(HERE / "trace.py").reduce_file(OLD)
    assert (tr.busy_s, tr.window_s, tr.launched_device_s("watermark"),
            tr.layer_device_s("ingest")) == (
        2.4145345490000003, 2.448431954, 0.693452994, 2.409532629)
    ps = program_spans.reduce_file(OLD)
    assert ps.spans == [] and program_spans.readings(ps) == {}
    assert ps.idle_gaps() == tr.breakdown()["idle_gaps"]


def test_new_fixture_gives_every_reading():
    rep = program_spans.report(NEW)
    for name in READINGS:
        assert math.isfinite(rep[name]) and rep[name] >= 0, name
    assert 0 < rep["kernel.ingest_fill"] <= 100
    assert rep["check"]["children_cover_min"] > 0.9
    labels = [g[0] for g in rep["idle_gaps"]]
    assert labels and all("/scotty." in g for g in labels), labels


def test_innermost_span_attribution():
    ps = program_spans.reduce_file(NEW)
    wm = [i for i, sp in enumerate(ps.spans) if sp[0] == "watermark"]
    fetch = [i for i, sp in enumerate(ps.spans)
             if sp[0] == "watermark.fetch"]
    assert wm and fetch
    for i in fetch:
        s, e = ps.spans[i][1], ps.spans[i][2]
        assert ps.innermost((s + e) / 2) == i
        assert ps.spans[ps.parent[i]][0] == "watermark"
        assert ps.spans[i][3]["wm"] == ps.spans[ps.parent[i]][3]["wm"]

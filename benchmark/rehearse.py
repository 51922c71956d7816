"""CPU rehearsal: run every cell of ``BENCHMARK.json`` end to end at its
configuration's rehearsal sizes, as the driver would (one process per
run, the result read from the last line), with and without trace, and
once with the bfloat16 control, which must read ``correct: false``.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

KEYS = ("correct", "attempted", "failed", "metrics", "device")


def run(cell, *extra):
    cmd = [sys.executable, str(HERE / "run.py"), "--workload", cell,
           "--seed", "2147483659", "--seconds", "2", "--allow-cpu",
           "--tiny", *extra]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=HERE.parent, timeout=600)
    if p.returncode != 0:
        raise SystemExit(f"{cell} {extra}: exit {p.returncode}\n"
                         f"{p.stderr[-3000:]}")
    line = json.loads(p.stdout.strip().splitlines()[-1])
    missing = [k for k in KEYS if k not in line]
    if missing:
        raise SystemExit(f"{cell} {extra}: result lacks {missing}")
    return line


def main():
    bad = 0
    for w in harness.load_benchmark()["workloads"]:
        cell = w["name"]
        for extra, want in (((), True), (("--trace", "1"), True),
                            (("--control", "bfloat16"), False)):
            line = run(cell, *extra)
            ok = line["correct"] is want
            bad += not ok
            print(f"{'ok ' if ok else 'BAD'} {cell} {' '.join(extra) or '-'}"
                  f" correct={line['correct']} "
                  f"metrics={sorted(line['metrics'])}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

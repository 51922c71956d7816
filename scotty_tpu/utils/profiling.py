"""Profiler hooks: jax.profiler traces around engine phases (SURVEY.md §5 —
replaces the reference's log-scraping AnalyzeTool flow with real device
traces)."""

from __future__ import annotations

import contextlib
import re
from typing import Iterator, Optional


@contextlib.contextmanager
def trace(log_dir: Optional[str] = None) -> Iterator[None]:
    """Capture a jax.profiler trace (viewable in TensorBoard / Perfetto)
    around a benchmark run; no-op when log_dir is None."""
    if log_dir is None:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


_RATE_RE = re.compile(r"That's ([\d,]+) elements/second/chip")


def analyze_log(text: str) -> dict:
    """AnalyzeTool parity (benchmark/.../AnalyzeTool.java:12-63): scrape
    throughput samples from harness logs, return summary statistics.

    .. deprecated:: 0.2
       Log scraping is the pre-obs fallback. New code should read the
       structured exports instead: ``python -m scotty_tpu.obs report``
       over a :class:`scotty_tpu.obs.JsonlExporter` file or a bench
       result's embedded ``metrics`` section."""
    import warnings

    warnings.warn(
        "analyze_log is deprecated; use the structured metrics exports "
        "(scotty_tpu.obs) and `python -m scotty_tpu.obs report` instead",
        DeprecationWarning, stacklevel=2)
    import numpy as np

    rates = [float(m.group(1).replace(",", ""))
             for m in _RATE_RE.finditer(text)]
    if not rates:
        return {"n": 0}
    arr = np.asarray(rates)
    return {"n": len(rates), "mean": float(arr.mean()),
            "min": float(arr.min()), "max": float(arr.max()),
            "std": float(arr.std())}

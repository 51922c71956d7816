"""Aux subsystems (SURVEY.md §5): checkpoint/resume, metrics, profiling."""


def stdout_echo(msg) -> None:
    """The shared default echo sink: one line to stdout. Every CLI-facing
    module (bench runner/micro/charts, obs diff) routes output through an
    overridable ``echo`` parameter defaulting to THIS function — the
    engine-silence lint (tests/test_no_print_in_engine.py) forbids bare
    ``print(`` in those trees, and a single sink keeps the contract (str
    coercion, newline, flush behavior) from diverging per module."""
    import sys

    sys.stdout.write(str(msg) + "\n")


from .checkpoint import (  # noqa: E402
    restore_engine_operator,
    restore_host_operator,
    save_engine_operator,
    save_host_operator,
)
from .metrics import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    ThroughputLogger,
)
from .profiling import analyze_log, trace

__all__ = [
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "ThroughputLogger", "analyze_log", "stdout_echo",
    "trace", "restore_engine_operator", "restore_host_operator",
    "save_engine_operator", "save_host_operator",
]

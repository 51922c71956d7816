"""Builds the configuration's ``TpuWindowOperator`` (shared by the
entries that drive one)."""

from __future__ import annotations


def build_operator(config: dict, windows: list):
    from scotty_tpu import (MaxAggregation, MinAggregation, SlidingWindow,
                            SumAggregation, TumblingWindow, WindowMeasure)
    from scotty_tpu.engine import EngineConfig, TpuWindowOperator

    ec = EngineConfig(capacity=int(config["capacity"]),
                      batch_size=int(config["batch_size"]),
                      min_trigger_pad=int(config["min_trigger_pad"]),
                      annex_capacity=int(config.get("annex_capacity",
                                                    1 << 12)))
    op = TpuWindowOperator(config=ec)
    T = WindowMeasure.Time
    for w in windows:
        if w["kind"] == "tumbling":
            op.add_window_assigner(TumblingWindow(T, int(w["size"])))
        else:
            op.add_window_assigner(SlidingWindow(T, int(w["size"]),
                                                 int(w["slide"])))
    table = {"sum": SumAggregation, "min": MinAggregation,
             "max": MaxAggregation}
    for a in config["aggregations"]:
        op.add_aggregation(table[a]())
    op.set_max_lateness(int(config["max_lateness_ms"]))
    return op

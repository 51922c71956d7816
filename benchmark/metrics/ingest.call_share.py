"""Share of the window's wall time spent inside the cell's ingest entry
(``offer_block`` or ``ingest_device_batch``): the benchmark's own span
around each hand-off, summed, over the window. Moves ``throughput``."""


def read(ctx):
    spans = ctx.spans.get("ingest")
    if not spans:
        return None
    return 100.0 * sum(spans) / ctx.window_s

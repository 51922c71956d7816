"""Times per second the producer found the ingest ring full: the delta
of ``LineRateFeed.snapshot()["full_events"]`` over the window. Moves
``throughput``; only the cells fed through the ring have it."""


def read(ctx):
    if "ring_full_events" not in ctx.counters:
        return None
    return ctx.counters["ring_full_events"] / ctx.window_s

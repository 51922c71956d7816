"""Device time per watermark of the programs that the benchmark's
watermark spans launched (drain, annex merge, range query, GC), from
the profiler trace. Moves ``emit_p95_ms``."""


def read(ctx):
    t = ctx.trace.launched_device_s("watermark")
    if not t or not ctx.watermarks:
        return None
    return 1e3 * t / ctx.watermarks

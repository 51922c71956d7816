"""Share of the traced window in which no operation ran on the device:
1 - (union of busy intervals on the device planes) / (traced window),
averaged over the chips used. Moves ``throughput``."""


def read(ctx):
    tr = ctx.trace
    if tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)

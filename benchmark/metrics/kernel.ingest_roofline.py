"""Share of its roofline that the ingest side reaches: the least HBM
time to read each tuple's value and timestamp once (bytes from the
configuration's dtypes, bandwidth from ``peaks.json``), over the device
time, in the trace, of the programs that ingest (the ``ingest`` layer
of ``layers.json``). Moves ``throughput``."""

import numpy as np


def read(ctx):
    dev_s = ctx.trace.layer_device_s("ingest")
    if not dev_s:
        return None
    per_tuple = (np.dtype(ctx.config["value_dtype"]).itemsize
                 + np.dtype(ctx.config["timestamp_dtype"]).itemsize)
    least_s = ctx.tuples * per_tuple / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / dev_s

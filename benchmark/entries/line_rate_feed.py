"""Entry: host records through ``LineRateFeed`` (accumulator, ingest
ring, device prefetch; the device sort-and-split when the configuration
names a shaper) into ``TpuWindowOperator``, watermarks through
``process_watermark_arrays``. Records are handed over one chunk (one
watermark period) at a time with ``offer_block``; the ring policy is
``block``, so the loop is closed."""

from __future__ import annotations

import numpy as np

from window_operator import build_operator

class Entry:
    def __init__(self, config, mix, pools, windows):
        from scotty_tpu.ingest import LineRateFeed, RingConfig

        self.op = build_operator(config, windows)
        shaper = None
        if config.get("shaper"):
            from scotty_tpu.shaper import ShaperConfig

            shaper = ShaperConfig(**config["shaper"])
        self.feed = LineRateFeed(
            self.op, ring=RingConfig(depth=int(config["ring_depth"]),
                                     policy="block"),
            shaper=shaper)
        self.pools = pools
        self.aggs = list(config["aggregations"])

    def ingest(self, c: int, which: str) -> int:
        pool = self.pools[which]
        p, off = pool.chunk(c)
        self.feed.offer_block(pool.vals[p], pool.ts[p] + np.int64(off))
        return pool.per_chunk

    def watermark(self, wm: int):
        ws, we, cnt, low = self.op.process_watermark_arrays(wm)
        return ws, we, cnt, dict(zip(self.aggs, low))

    def counters(self) -> dict:
        return {"ring_full_events": int(
            self.feed.snapshot()["full_events"]),
            "ring_shed": int(self.feed.snapshot()["shed"])}

    def finish(self) -> None:
        self.op.check_overflow()


def build(config, mix, pools, windows):
    return Entry(config, mix, pools, windows)

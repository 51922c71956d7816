"""LineRateFeed: the end-to-end line-rate host boundary.

One object wires the whole ingest edge for a
:class:`~scotty_tpu.engine.operator.TpuWindowOperator`:

``host records (any order)``
→ :class:`~scotty_tpu.shaper.BatchAccumulator` (vectorized
``offer_block`` fill, reorder-slack sort, bounded-delay flush)
→ :class:`~.ring.IngestRing` (bounded preallocated staging, credit-based
backpressure, exact accounting)
→ :class:`~.feeder.DeviceRingFeeder` (``jax.device_put`` prefetch of
block N+1 overlapping the ingest dispatch of block N; shaped via the
device sort-and-split when a :class:`~scotty_tpu.shaper.ShaperConfig` is
given, plain in-order ingest otherwise).

This replaces the per-record ``process_elements`` trickle for streams
the engine does not generate: the only Python-level work per record is
an amortized array-slice copy, every buffer is bounded, ring-full
propagates to the caller as backpressure (or sheds, exactly counted),
and the operator's existing drain points fold the telemetry.

Attaching: construction sets ``op._ingest_feed``, so the operator's
watermark dispatch drains staged records first (the same contract as an
attached shaper) and ``check_overflow`` folds ``ingest_ring_*``.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .. import obs as _obs
from ..obs import latency as _lat
from ..resilience.clock import Clock, SystemClock
from .feeder import DeviceRingFeeder, RingIngestor
from .ring import IngestRing, RingConfig


class LineRateFeed:
    """See module docstring. ``ring`` sizes the staging
    (``block_size=None`` = the operator's ``config.batch_size``);
    ``shaper`` (a :class:`~scotty_tpu.shaper.ShaperConfig`) supplies the
    reorder slack / bounded delay for the accumulator AND arms the
    jitted device sort-and-split for intra-block disorder — without it
    the feed is the strict in-order fast path (sorted blocks, bounded
    cross-block back-reach riding the general kernel's late prefix)."""

    def __init__(self, op, ring: Optional[RingConfig] = None,
                 shaper=None, obs=None, clock: Optional[Clock] = None,
                 pace_steps: Optional[int] = None,
                 shed_callback=None, on_stall=None):
        from ..shaper import BatchAccumulator, ShaperConfig, StreamShaper

        ring = ring or RingConfig()
        self.op = op
        self.clock = clock or SystemClock()
        obs = obs if obs is not None else getattr(op, "obs", None)
        self.obs = obs
        B = ring.block_size or op.config.batch_size
        if B != op.config.batch_size:
            raise ValueError(
                f"ring block_size={B} must equal the operator's "
                f"config.batch_size={op.config.batch_size}: the device "
                "ingest/sort-split kernels are compiled for that block "
                "shape (leave block_size=None to inherit it)")
        self.ring = IngestRing(ring.depth, B, keyed=False,
                               value_dtype=np.float32)
        self._dev_shaper = None
        slack_ms, max_delay_ms = 0, None
        if shaper is not None:
            if not isinstance(shaper, ShaperConfig):
                raise TypeError(
                    "LineRateFeed shaper= expects a ShaperConfig, got "
                    f"{type(shaper).__name__}")
            slack_ms, max_delay_ms = shaper.slack_ms, shaper.max_delay_ms
            import dataclasses

            # the StreamShaper here serves ONLY the device sort-and-split
            # + its drain-point check; host coalescing lives in OUR
            # accumulator (construction attaches it to the operator, so
            # check_overflow raises on a lost late residue)
            self._dev_shaper = StreamShaper(
                op, dataclasses.replace(shaper, batch_size=B), obs=obs,
                clock=self.clock)
        self.feeder = DeviceRingFeeder(
            self.ring, op, shaper=self._dev_shaper,
            prefetch=ring.prefetch, pace_steps=pace_steps)
        self.ingestor = RingIngestor(
            self.ring, self.feeder, policy=ring.policy,
            pump_at=ring.pump_at, obs=obs, clock=self.clock,
            stall_timeout_s=ring.stall_timeout_s,
            shed_callback=shed_callback, on_stall=on_stall)
        self.accumulator = BatchAccumulator(
            B, self._to_ring, slack_ms=slack_ms,
            max_delay_ms=max_delay_ms, clock=self.clock)
        self._deadline_seen = 0
        op._ingest_feed = self

    def _to_ring(self, vals, ts) -> None:
        self.ingestor.offer_block(vals, ts)
        if self._dev_shaper is None:
            # in-order mode: each accumulator flush must stay its own
            # (sorted) device block — coalescing two drains in one slot
            # could interleave event-time ranges the plain ingest kernels
            # cannot re-sort. The shaped mode sorts on device, so there
            # partial flushes may share a slot.
            if self.ring.flush_open():
                self.ingestor.poll()

    def _propagate_deadline(self) -> None:
        """A bounded-delay drain must reach the DEVICE, not stop in a
        partial ring block: when the accumulator's deadline fired, push
        everything staged through (commit the open block, dispatch the
        prefetch stage)."""
        df = self.accumulator.deadline_flushes
        if df != self._deadline_seen:
            self._deadline_seen = df
            self.ingestor.drain()

    # -- producer face -----------------------------------------------------
    def offer_block(self, vals, ts) -> None:
        """Offer a chunk of host records (any timestamp order within the
        configured slack/shaper tolerance)."""
        if self.obs is not None and self.obs.latency is not None:
            # record-arrival pre-stamp (ISSUE 14): the line-rate feed
            # IS the connector boundary for externally-fed streams
            self.obs.latency.pre(_lat.STAGE_ARRIVAL)
        with _obs.program_span(self.obs, "ingest.offer", n=len(ts)):
            self.accumulator.offer_block(vals, ts)
            self._propagate_deadline()

    def poll(self) -> None:
        """Idle tick: evaluate the bounded-delay deadline + move committed
        blocks along (a quiet source still flushes on time)."""
        self.accumulator.poll()
        self._propagate_deadline()
        self.ingestor.poll()

    def drain(self) -> None:
        """Flush everything held (accumulator slack band, partial ring
        block, prefetch stage). The operator's watermark dispatch calls
        this — event time is about to advance past staged records."""
        self.accumulator.drain()
        self.ingestor.drain()

    def check(self) -> None:
        """Drain-point telemetry fold (``check_overflow`` hook)."""
        self.ingestor.check()

    # -- introspection -----------------------------------------------------
    @property
    def held(self) -> int:
        """Records buffered host-side (accumulator + ring)."""
        return self.accumulator.held + self.ring.occupancy

    def snapshot(self) -> dict:
        snap = self.ingestor.snapshot()
        snap["accumulator_held"] = self.accumulator.held
        return snap

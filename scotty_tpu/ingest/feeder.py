"""Ring consumers + the policy-bearing producer facade.

Three pieces close the credit loop around :class:`~.ring.IngestRing`:

* :class:`DeviceRingFeeder` — the host→device prefetch stage. Taking a
  committed block issues its ``jax.device_put`` transfer immediately and
  *defers the ingest dispatch* until the next block's transfer has been
  issued, so block N+1's H2D copy overlaps block N's ingest kernel under
  the runtime's async dispatch queue (classic double buffering at
  ``prefetch=1``; deeper staging with larger ``prefetch``). A slot's
  credit returns only after its transfer completed
  (``block_until_ready`` on the *transferred arrays*, not the engine
  state — the ingest dispatch stays async; results drain only at the
  operator's existing drain points). Blocks route through
  ``StreamShaper.shape_device_batch`` when the operator carries an
  attached device shaper (unshaped streams sort-and-split on device) and
  through ``TpuWindowOperator.ingest_device_batch`` otherwise (sorted
  blocks — the accumulator upstream produces exactly those).
* :class:`BlockSinkFeeder` — the host-consumer variant for the connector
  run loops: a taken block replays into ``sink(vals, ts[, keys])``
  (typically the operator's vectorized ``process_block``) and frees
  immediately.
* :class:`RingIngestor` — the producer facade every wiring site uses:
  ``offer``/``offer_block`` land records in the ring; ring-full engages
  the configured policy — **block** pumps the consumer until a credit
  frees (the synchronous-loop realization of "pause the source"),
  **shed** drops the remainder with exact counts and a callback so an
  oracle can replay the survivors, **fail** raises
  :class:`~.ring.RingFull`. A blocked-credit wait (or slow consumer
  delivery) exceeding ``stall_timeout_s`` on the injectable clock trips
  the PR 3 stall watchdog (``resilience_stall_events`` + ``stall``
  flight event) — a stalled consumer is flagged exactly like a stalled
  source.
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Optional

from .. import obs as _obs
from ..obs import flight as _flight
from ..obs import latency as _lat
from ..resilience.clock import Clock, SystemClock
from ..resilience.connectors import flag_stall
from .ring import IngestRing, RingBlock, RingConfig, RingFull


class BlockSinkFeeder:
    """Host consumer: replay each committed block into ``sink`` and free
    its credit. ``sink(vals, ts)`` (or ``sink(keys, vals, ts)`` for a
    keyed ring) receives COPIES it owns outright — a sink may retain
    them (a shaper-attached ``process_block`` parks them in the
    accumulator's slack band past this call, while the freed slot
    recycles to the producer and is overwritten)."""

    def __init__(self, ring: IngestRing, sink: Callable, obs=None):
        self.ring = ring
        self.sink = sink
        self.obs = obs

    def _deliver(self, blk: RingBlock) -> None:
        if self.obs is not None and self.obs.latency is not None:
            # ring-dequeue pre-stamp (ISSUE 14): the block leaves the
            # staging ring for the downstream sink
            self.obs.latency.pre(_lat.STAGE_RING_DEQUEUE)
        n = blk.n
        if self.ring.keyed:
            self.sink(blk.keys[:n].copy(), blk.vals[:n].copy(),
                      blk.ts[:n].copy())
        else:
            self.sink(blk.vals[:n].copy(), blk.ts[:n].copy())
        self.ring.free(blk)

    def pump(self, limit: Optional[int] = None) -> int:
        """Deliver committed blocks (all of them, or up to ``limit``);
        returns blocks delivered."""
        n = 0
        while limit is None or n < limit:
            blk = self.ring.take()
            if blk is None:
                break
            self._deliver(blk)
            n += 1
        return n

    def reclaim(self, n_credits: int = 1) -> int:
        """Force-free credits (the blocking-backpressure path). For a
        host sink, delivering IS freeing."""
        return self.pump(n_credits)

    def drain(self) -> int:
        """Deliver everything committed (the stream-end path)."""
        return self.pump()


class DeviceRingFeeder:
    """Prefetching host→device consumer (module docstring).

    ``op`` is a :class:`~scotty_tpu.engine.operator.TpuWindowOperator`;
    when it carries an attached :class:`~scotty_tpu.shaper.StreamShaper`
    (or one is passed explicitly) blocks dispatch through
    ``shape_device_batch`` — the jitted sort-and-split absorbs arbitrary
    intra-block disorder, so the accumulator upstream only needs its
    slack band for *cross*-block ordering. Without a shaper, blocks go
    straight to ``ingest_device_batch`` (sorted blocks; bounded
    cross-block back-reach rides the general kernel's sorted late
    prefix, within ``max_lateness``).

    ``pace_steps`` (optional) bounds ingest dispatches in flight: every
    that-many dispatches, wait on the engine state handle — real
    device-side backpressure for sources faster than the device (the
    wait is a pacing ``block_until_ready``, not a value fetch).
    """

    def __init__(self, ring: IngestRing, op, shaper=None,
                 prefetch: int = 1, pace_steps: Optional[int] = None):
        if ring.keyed or ring.value_dtype is None:
            raise ValueError(
                "DeviceRingFeeder consumes unkeyed float32 rings; keyed/"
                "object streams replay through BlockSinkFeeder")
        self.ring = ring
        self.op = op
        self.shaper = shaper if shaper is not None \
            else getattr(op, "_shaper", None)
        self.prefetch = int(prefetch)
        self.pace_steps = pace_steps
        self._staged: deque = deque()   # (blk, v_dev, t_dev)
        self._since_pace = 0
        import jax

        # the CPU backend's device_put of an aligned numpy array is
        # zero-copy: the device array would alias the slot, which
        # recycles to the producer before the async ingest has read it
        self._copy_slot = jax.default_backend() == "cpu"

    def _stage(self, blk: RingBlock) -> None:
        import jax

        n, B = blk.n, self.ring.block_size
        if n == 0:
            self.ring.free(blk)
            return
        if n < B:
            # pad lanes must repeat the last valid ts (the device-batch
            # contract) — the slot's tail still holds a previous block
            blk.ts[n:] = blk.ts[n - 1]
            blk.vals[n:] = 0.0
        with _obs.program_span(getattr(self.op, "obs", None),
                               "ingest.stage",
                               bytes=blk.vals.nbytes + blk.ts.nbytes):
            vals, ts = blk.vals, blk.ts
            if self._copy_slot:
                vals, ts = vals.copy(), ts.copy()
            v_dev = jax.device_put(vals)
            t_dev = jax.device_put(ts)
        self._staged.append((blk, v_dev, t_dev))

    def _dispatch_oldest(self) -> int:
        op_obs = getattr(self.op, "obs", None)
        if op_obs is not None and op_obs.latency is not None:
            # ring-dequeue pre-stamp (ISSUE 14): the oldest staged
            # block's ingest is about to dispatch
            op_obs.latency.pre(_lat.STAGE_RING_DEQUEUE)
        blk, v_dev, t_dev = self._staged.popleft()
        if self.shaper is not None:
            self.shaper.shape_device_batch(v_dev, t_dev, blk.ts_min,
                                           blk.ts_max, n_valid=blk.n)
        else:
            self.op.ingest_device_batch(v_dev, t_dev, blk.ts_min,
                                        blk.ts_max, n_valid=blk.n)
        # the slot's numpy buffer recycles to the producer: wait for the
        # TRANSFER only (the ingest dispatch above stays async)
        with _obs.program_span(op_obs, "ingest.transfer_wait"):
            v_dev.block_until_ready()
            t_dev.block_until_ready()
        self.ring.free(blk)
        self._since_pace += 1
        if self.pace_steps is not None \
                and self._since_pace >= self.pace_steps:
            self._since_pace = 0
            state = getattr(self.op, "_state", None)
            if state is not None:
                with _obs.program_span(op_obs, "ingest.transfer_wait"):
                    state.n_slices.block_until_ready()
        return 1

    def pump(self, limit: Optional[int] = None) -> int:
        """Move committed blocks into the prefetch stage, dispatching (and
        freeing) the oldest staged block whenever the stage exceeds
        ``prefetch``. Returns credits freed."""
        freed = 0
        taken = 0
        while limit is None or freed < limit:
            blk = self.ring.take()
            if blk is None:
                break
            self._stage(blk)
            taken += 1
            while len(self._staged) > self.prefetch:
                freed += self._dispatch_oldest()
        return freed

    def reclaim(self, n_credits: int = 1) -> int:
        """Force-dispatch staged blocks to free credits NOW (the blocking
        backpressure path)."""
        freed = 0
        while freed < n_credits and self._staged:
            freed += self._dispatch_oldest()
        return freed

    def drain(self) -> int:
        """Stage + dispatch everything (stream end / checkpoint): after
        this, the ring is empty and every block's ingest is dispatched —
        the caller's existing drain point (``check_overflow`` /
        watermark fetch) does the one deliberate sync."""
        freed = self.pump()
        while self._staged:
            freed += self._dispatch_oldest()
        return freed


class RingIngestor:
    """Producer facade: records in, policy on full, exact accounting out
    (module docstring). ``shed_callback(vals, ts, keys_or_None)`` sees
    every shed record — the oracle-replay tests rebuild the survivor
    stream from it."""

    def __init__(self, ring: IngestRing, feeder, policy: str = "block",
                 pump_at: int = 1, obs=None,
                 clock: Optional[Clock] = None,
                 stall_timeout_s: Optional[float] = None,
                 shed_callback: Optional[Callable] = None,
                 on_stall: Optional[Callable] = None,
                 stage_deadline_s: Optional[float] = None):
        if policy not in ("block", "shed", "fail"):
            raise ValueError(f"unknown ring policy {policy!r}")
        self.ring = ring
        self.feeder = feeder
        self.policy = policy
        self.pump_at = int(pump_at)
        self.obs = obs
        self.clock = clock or SystemClock()
        self.stall_timeout_s = stall_timeout_s
        self.shed_callback = shed_callback
        self.on_stall = on_stall
        #: bounded-delay honesty for the OPEN staging block (the
        #: connector wiring sets it from the attached shaper's
        #: ``max_delay_ms``): a slow-but-active source never idles, so
        #: without this its records could sit un-committed for a whole
        #: block. End-to-end worst case is one ring stage + one
        #: accumulator stage ≤ 2 × max_delay_ms.
        self.stage_deadline_s = stage_deadline_s
        self._open_since: Optional[float] = None
        self.shed = 0                   # records shed (exact)
        self._folded: dict = {}

    @classmethod
    def for_sink(cls, config: RingConfig, sink: Callable, keyed: bool,
                 obs=None, clock: Optional[Clock] = None,
                 shed_callback: Optional[Callable] = None,
                 block_size_default: int = 1024,
                 on_stall: Optional[Callable] = None,
                 stage_deadline_s: Optional[float] = None) -> "RingIngestor":
        """The connector wiring: a keyed/object ring draining into
        ``sink`` (the operator's block replay)."""
        B = config.block_size or block_size_default
        ring = IngestRing(config.depth, B, keyed=keyed, value_dtype=None)
        feeder = BlockSinkFeeder(ring, sink, obs=obs)
        return cls(ring, feeder, policy=config.policy,
                   pump_at=config.pump_at, obs=obs, clock=clock,
                   stall_timeout_s=config.stall_timeout_s,
                   shed_callback=shed_callback, on_stall=on_stall,
                   stage_deadline_s=stage_deadline_s)

    # -- producing ---------------------------------------------------------
    def _lat_enqueue(self) -> None:
        if self.obs is not None and self.obs.latency is not None:
            # ring-enqueue pre-stamp (ISSUE 14): oldest record accepted
            # into the staging ring since the last chain claim
            self.obs.latency.pre(_lat.STAGE_RING_ENQUEUE)

    def offer_one(self, val, ts, key=None) -> bool:
        """One record in; returns False iff it was SHED (policy='shed'
        while full). Blocking policy never loses the record."""
        self._lat_enqueue()
        while not self.ring.offer_one(val, ts, key):
            if not self._on_full([val], [ts],
                                 None if key is None else [key]):
                return False
        self._check_stage_deadline()
        self._auto_pump()
        return True

    def offer_block(self, vals, ts, keys=None) -> int:
        """A chunk of records in; returns how many were accepted (the
        rest — nonzero only under policy='shed' — were shed, counted and
        handed to ``shed_callback``)."""
        v, t, k = self.ring.coerce_block(vals, ts, keys)
        self._lat_enqueue()
        pos, n = 0, t.size
        while pos < n:
            pos += self.ring.offer_block(
                v[pos:], t[pos:], None if k is None else k[pos:])
            if pos < n and not self._on_full(
                    v[pos:], t[pos:], None if k is None else k[pos:]):
                break
        self._check_stage_deadline()
        self._auto_pump()
        return pos

    def _on_full(self, vals, ts, keys) -> bool:
        """Ring-full: engage the policy. Returns True when the producer
        may retry (a credit was freed), False when the remainder was
        shed."""
        if self.obs is not None:
            self.obs.flight_event(_flight.RING_FULL, "ingest_ring",
                                  float(self.ring.occupancy))
        if self.policy == "fail":
            self._fold()
            raise RingFull(
                f"ingest ring full ({self.ring.depth} blocks x "
                f"{self.ring.block_size} records) under policy='fail' — "
                "use 'block' for backpressure or 'shed' for bounded loss")
        if self.policy == "shed":
            n = len(ts)
            self.shed += n
            if self.shed_callback is not None:
                self.shed_callback(vals, ts, keys)
            if self.obs is not None:
                self.obs.flight_event(_flight.RING_SHED, "ingest_ring",
                                      float(n))
            return False
        # block: pump moves committed blocks along; if every credit is
        # checked out, force the consumer to finish one. The whole
        # freeing operation is timed — the wait IS the backpressure, and
        # a long one is a flagged consumer stall (PR 3 watchdog)
        t0 = self.clock.now()
        with _obs.program_span(self.obs, "ingest.ring_full"):
            self.feeder.pump()
            freed = True
            if not self.ring.has_space():
                freed = bool(self.feeder.reclaim(1))
        gap = self.clock.now() - t0
        if self.stall_timeout_s is not None and gap > self.stall_timeout_s:
            flag_stall(self.obs, "ingest_ring_consumer", gap,
                       self.on_stall)
        if not freed and not self.ring.has_space():
            raise RuntimeError(
                "ingest ring consumer freed no credits while the "
                "ring is full — the consumer is wedged")
        return True

    def _check_stage_deadline(self) -> None:
        """Commit the open block once its oldest record has waited
        ``stage_deadline_s`` (constructor note) — evaluated on every
        offer, the same points the unstaged loop evaluates the
        accumulator's deadline. An early commit only changes block
        boundaries, never record order, so results are unaffected."""
        if self.stage_deadline_s is None:
            return
        if self.ring._fill == 0:
            self._open_since = None
            return
        now = self.clock.now()
        if self._open_since is None:
            self._open_since = now
        elif now - self._open_since >= self.stage_deadline_s:
            self.ring.flush_open()
            self.feeder.pump()
            self._open_since = None

    def _auto_pump(self) -> None:
        if self.pump_at == 0:           # manual pumping (RingConfig doc)
            return
        if self.ring.committed_blocks >= self.pump_at:
            t0 = self.clock.now()
            self.feeder.pump()
            gap = self.clock.now() - t0
            if self.stall_timeout_s is not None \
                    and gap > self.stall_timeout_s:
                flag_stall(self.obs, "ingest_ring_consumer", gap,
                           self.on_stall)

    # -- drain points ------------------------------------------------------
    def poll(self) -> None:
        """Idle tick: commit the open partial block and move everything
        along. The source is quiet, so batching has nothing to wait
        for — records staged here must reach the consumer NOW or a
        bounded-delay deadline downstream (the shaper's
        ``max_delay_ms``) could never see them."""
        self.ring.flush_open()
        self._open_since = None
        self.feeder.pump()
        self._fold()

    def drain(self) -> None:
        """Stream end / checkpoint: commit the open partial block,
        deliver everything, fold telemetry. After this
        ``occupancy == 0`` — the conservation identity's ``held`` term
        collapses to the accumulator/shaper side."""
        self.ring.flush_open()
        self._open_since = None
        self.feeder.drain()
        self._fold()

    def check(self) -> None:
        """Drain-point telemetry fold (the operator's ``check_overflow``
        hook calls this — same discipline as ``StreamShaper.check``)."""
        self._fold()

    def snapshot(self) -> dict:
        snap = self.ring.snapshot()
        snap["shed"] = self.shed
        return snap

    def _fold(self) -> None:
        obs = self.obs
        if obs is None:
            return
        r = self.ring
        for name, total in (
                (_obs.INGEST_RING_OFFERED, r.offered),
                (_obs.INGEST_RING_DELIVERED, r.delivered),
                (_obs.INGEST_RING_BLOCKS, r.blocks),
                (_obs.INGEST_RING_FULL_EVENTS, r.full_events),
                (_obs.INGEST_RING_SHED, self.shed)):
            last = self._folded.get(name, 0)
            if total > last:
                obs.counter(name).inc(total - last)
                self._folded[name] = total
        obs.gauge(_obs.INGEST_RING_OCCUPANCY).set(r.occupancy)
        obs.gauge(_obs.INGEST_RING_HIGHWATER).set(r.highwater)

"""StreamShaper: the facade that takes unshaped streams to engine rate.

``StreamShaper`` sits between a source and a window operator and makes
"unshaped out-of-order stream in, fused-kernel rate out" the default
path:

* **Device batches** (:meth:`StreamShaper.shape_device_batch`): one
  jitted sort-and-split (:func:`.device.build_sort_split`) against the
  operator's current max event time routes the in-order majority through
  the scatter-free dense/in-order ingest
  (``TpuWindowOperator.ingest_device_batch``) and the compacted late
  residue through ``ingest_device_late`` on a small static lane count —
  the O(B) general scatter kernel is paid only on the actually-late
  fraction. Zero host syncs on the hot path; the split masks live on
  device and empty blocks are masked no-op dispatches.
* **Host records** (:meth:`offer` / :meth:`offer_many`): a
  :class:`.host.BatchAccumulator` coalesces irregular connector records
  into full sorted ``batch_size`` blocks with a reorder-slack band and a
  bounded-delay flush on the injectable resilience Clock, replacing the
  per-record ``process_element`` trickle.
* **Keyed rounds** (:meth:`shape_device_round`): flat (key, value, ts)
  device arrays become the padded ``[K, Bk]`` round layout of
  ``KeyedTpuWindowOperator.ingest_device_round`` on device.

Telemetry rides the obs contract (``shaper_reordered_tuples``,
``shaper_flushes``, ``shaper_held_tuples``, ``shaper_late_routed``,
``shaper_slack_overflows``, ``shaper_fill_ratio``) and the flight
recorder (flush / held-highwater / slack-overflow events), all folded at
the existing drain points — :meth:`check` is wired into
``TpuWindowOperator.check_overflow`` when the shaper is attached.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from .. import obs as _obs
from ..obs import flight as _flight
from ..obs import latency as _latn
from ..resilience.clock import Clock, SystemClock
from . import device as _dev
from .host import BatchAccumulator


class ShaperOverflow(RuntimeError):
    """A batch's late residue exceeded the static late capacity — tuples
    were lost on device and the run is invalid (the FAIL-policy analogue
    of the engine's buffer overflow)."""


@dataclass(frozen=True)
class ShaperConfig:
    """Static shaper configuration.

    * ``slack_ms`` — reorder-slack band: on size-triggered host flushes,
      records newer than ``max_ts_seen - slack_ms`` are held back so
      stragglers within the slack still merge in sorted order.
    * ``max_delay_ms`` — bounded-delay flush on the (injectable) clock.
      The deadline is EVALUATED when records arrive (:meth:`StreamShaper.
      offer`/``offer_many``), on :meth:`StreamShaper.poll`, and on any
      drain — a synchronous run loop blocked in its source iterator has
      no execution to evaluate it on, so a fully silent source flushes
      at the next record, an external ``poll()`` tick, or loop end.
      ``None`` = size/drain-triggered flushes only.
    * ``late_capacity`` — static device lanes for the late residue per
      shaped batch (0 = ``max(64, batch_size // 8)``, the same bound the
      engine's host split path uses). Exceeding it raises
      :class:`ShaperOverflow` at the next drain point.
    * ``late_routing`` — ``"split"`` (default): sort-and-split, late
      residue through the small general-kernel dispatch; ``"combined"``:
      sort only, the whole batch through one general-kernel dispatch
      (the engine's pre-shaper fallback — mainly an A/B lever).
    * ``batch_size`` — host coalescing block size (``None`` = the
      operator's ``config.batch_size``).
    * ``pallas_sort_split`` — route device batches through the Pallas
      bucketed bitonic sort-split (ROADMAP item 4) instead of the XLA
      ``lax.sort`` kernel. ``None`` (default) inherits the operator's
      ``EngineConfig.pallas_sort_split`` — so the flag stays OFF (and
      the dispatched programs byte-identical) unless a config turns it
      on. Batches whose host-known span exceeds the 31-bit bucket
      budget fall back per batch (``pallas_fallbacks``).
    """

    slack_ms: int = 0
    max_delay_ms: Optional[float] = None
    late_capacity: int = 0
    late_routing: str = "split"
    batch_size: Optional[int] = None
    pallas_sort_split: Optional[bool] = None

    def __post_init__(self):
        if self.late_routing not in ("split", "combined"):
            raise ValueError(
                f"unknown late_routing {self.late_routing!r}: expected "
                "'split' or 'combined'")


class StreamShaper:
    """Sort-and-split front-end for one operator (or a bare ``sink``).

    ``op`` is a :class:`~scotty_tpu.engine.TpuWindowOperator` (host +
    device paths) or a ``KeyedTpuWindowOperator`` (keyed rounds); pass
    ``sink=`` instead to use the host accumulator standalone (the
    connector wiring does — blocks are delivered as ``sink(vals, ts)``
    or ``sink(keys, vals, ts)`` with ``keyed=True``).

    Constructing a shaper over a ``TpuWindowOperator`` ATTACHES it: the
    operator's ``process_elements`` routes through the accumulator,
    watermarks drain held records first, and ``check_overflow`` folds the
    shaper's device stats (raising :class:`ShaperOverflow` on a lost
    late residue).
    """

    def __init__(self, op=None, config: Optional[ShaperConfig] = None,
                 obs=None, clock: Optional[Clock] = None, sink=None,
                 keyed: bool = False, value_dtype=np.float32):
        if op is None and sink is None:
            raise ValueError("StreamShaper needs an operator or a sink")
        self.op = op
        self.config = config or ShaperConfig()
        self._own_obs = obs
        self.clock = clock or SystemClock()
        self.keyed = keyed
        B = self.config.batch_size
        if B is None:
            cfg = getattr(op, "config", None)
            B = getattr(cfg, "batch_size", None) if cfg is not None else None
        if B is None:
            raise ValueError(
                "ShaperConfig.batch_size is required without an operator")
        self.batch_size = int(B)
        self.late_capacity = self.config.late_capacity \
            or max(64, self.batch_size // 8)
        self._sink = sink
        self.accumulator = BatchAccumulator(
            self.batch_size, self._deliver, slack_ms=self.config.slack_ms,
            max_delay_ms=self.config.max_delay_ms, clock=self.clock,
            keyed=keyed, value_dtype=value_dtype)
        self._dev_stats = None          # lazily-allocated device pytree
        self._valid_all = None          # cached all-true device lane mask
        p = self.config.pallas_sort_split
        if p is None:
            cfg = getattr(op, "config", None)
            p = bool(getattr(cfg, "pallas_sort_split", False))
        #: resolved Pallas routing for device batches; flips False once
        #: on a build-time shape miss (counted), per-batch span misses
        #: fall back per dispatch
        self._pallas_sort = bool(p)
        self._stats_folded: dict = {}   # last obs-folded telemetry values
        self._feeding = False
        self._held_hw_recorded = 0
        # attach to a TpuWindowOperator-shaped op (duck-typed: it owns the
        # reentrancy flag the shaped process_elements path checks); any
        # other operator (e.g. KeyedTpuWindowOperator) gets the generic
        # hook its check_overflow drain point consults, so a sticky
        # device overflow can never pass a drain silently
        if op is not None:
            if hasattr(op, "_shaper_feeding"):
                op._shaper = self
            else:
                op._attached_shaper = self

    # -- obs ---------------------------------------------------------------
    @property
    def obs(self):
        if self._own_obs is not None:
            return self._own_obs
        return getattr(self.op, "obs", None)

    # -- host path ---------------------------------------------------------
    def offer(self, value, ts, key=None) -> int:
        """Buffer one host record; returns blocks flushed."""
        return self.offer_many([value], [ts],
                               None if key is None else [key])

    def _lat_arrival(self) -> None:
        obs = self.obs
        if obs is not None and obs.latency is not None:
            # record-arrival pre-stamp (ISSUE 14): oldest record to
            # enter the accumulator since the last chain claim (the
            # operator's process_elements stamps the same moment for
            # host-fed paths; setdefault keeps the earliest)
            obs.latency.pre(_latn.STAGE_ARRIVAL)

    def offer_many(self, vals, ts, keys=None) -> int:
        """Buffer a chunk of host records; flushes full sorted blocks
        (plus any expired bounded-delay flush) into the operator/sink."""
        self._lat_arrival()
        n = self.accumulator.offer(vals, ts, keys=keys)
        self._record_host_telemetry()
        return n

    def offer_block(self, vals, ts, keys=None) -> int:
        """Buffer one staged block of host records through the
        accumulator's vectorized block-fill path (ISSUE 7) — exactly
        equivalent to per-record offers, without the per-record Python
        work. The ingest-ring replay path lands whole blocks here."""
        self._lat_arrival()
        n = self.accumulator.offer_block(vals, ts, keys=keys)
        self._record_host_telemetry()
        return n

    def poll(self) -> int:
        """Idle-source tick: fire an expired bounded-delay flush even
        when no new records arrive."""
        n = self.accumulator.poll()
        if n:
            self._record_host_telemetry()
        return n

    def flush(self) -> int:
        """Force-drain everything held (watermark/stream-end path)."""
        n = self.accumulator.drain()
        self._record_host_telemetry()
        return n

    @property
    def held(self) -> int:
        return self.accumulator.held

    def _deliver(self, *block) -> None:
        obs = self.obs
        if obs is not None:
            size = block[-1].shape[0]
            if obs.latency is not None:
                # shaper-flush pre-stamp (ISSUE 14): the block leaves
                # the accumulator for the operator/sink
                obs.latency.pre(_latn.STAGE_SHAPER_FLUSH)
            obs.counter(_obs.SHAPER_FLUSHES).inc()
            obs.histogram(_obs.SHAPER_FILL_RATIO).observe(
                size / self.batch_size)
            obs.flight_event(_flight.SHAPER_FLUSH, _obs.SHAPER_FLUSHES,
                             float(size))
        if self._sink is not None:
            self._sink(*block)
            return
        vals, ts = block
        op = self.op
        if hasattr(op, "_shaper_feeding"):
            op._shaper_feeding = True
            try:
                op.process_elements(vals, ts)
            finally:
                op._shaper_feeding = False
        else:
            op.process_elements(vals, ts)

    def _record_host_telemetry(self) -> None:
        obs = self.obs
        if obs is None:
            return
        acc = self.accumulator
        self._fold_counter(_obs.SHAPER_REORDERED_TUPLES,
                           "host_reordered", acc.reordered)
        obs.gauge(_obs.SHAPER_HELD_TUPLES).set(acc.held)
        if acc.held_highwater > self._held_hw_recorded:
            self._held_hw_recorded = acc.held_highwater
            obs.flight_event(_flight.SHAPER_HELD, _obs.SHAPER_HELD_TUPLES,
                             float(acc.held_highwater))

    def _fold_counter(self, name: str, key: str, total) -> None:
        last = self._stats_folded.get(key, 0)
        if total > last:
            self.obs.counter(name).inc(total - last)
            self._stats_folded[key] = total

    # -- device path -------------------------------------------------------
    def shape_device_batch(self, vals, ts, ts_min: int, ts_max: int,
                           n_valid: Optional[int] = None) -> None:
        """Shape + ingest one device-resident batch (shape
        ``[batch_size]``, arbitrary timestamp order). ``ts_min`` /
        ``ts_max`` are host-known conservative event-time bounds (same
        contract as ``ingest_device_batch``); ``n_valid`` marks a
        partially-filled batch (valid records must be a prefix).

        One jitted sort-and-split, then: in-order block through the
        dense/in-order kernels, late residue (if the bounds admit any)
        through the small ``ingest_device_late`` dispatch. No host syncs;
        the slack-overflow flag is read back at :meth:`check`.
        """
        op = self.op
        if op is None or not hasattr(op, "ingest_device_batch"):
            raise TypeError(
                "shape_device_batch needs a TpuWindowOperator")
        if not op._built:
            op._build()
        B = op.config.batch_size
        if self._dev_stats is None:
            self._dev_stats = _dev.init_shaper_stats()
        n = B if n_valid is None else int(n_valid)
        if n == 0:
            return
        if n == B:
            # cached device-resident constant: a fresh host mask would
            # pay an allocation + H2D transfer on every shaped batch of
            # the zero-host-sync hot path (same trick as the operator's
            # _valid_dev)
            if self._valid_all is None:
                import jax

                self._valid_all = jax.device_put(np.ones((B,), bool))
            valid = self._valid_all
        else:
            valid = np.zeros((B,), bool)
            valid[:n] = True
        met_pre = op._host_met
        late_possible = met_pre is not None and ts_min < met_pre
        seed = np.int64(met_pre) if met_pre is not None \
            else np.int64(_dev.I64_MIN)
        combined = self.config.late_routing == "combined"
        # the split cut: the operator's current max event time. Without
        # history (or when the host bounds prove nothing is late, or in
        # combined routing) cut = I64_MIN makes the kernel a pure sort.
        cut = np.int64(met_pre) if (late_possible and not combined) \
            else np.int64(_dev.I64_MIN)
        kern = None
        if self._pallas_sort:
            from .. import pallas as _pl

            if not _pl.sort_span_fits(int(ts_max) - int(ts_min)):
                # this batch's span overflows the 31-bit bucket key —
                # per-batch fallback to the XLA twin, counted
                _pl.record_fallback(self.obs, "sort_split_span")
            else:
                try:
                    kern = _dev.sort_split_kernel(
                        B, self.late_capacity, pallas=True)
                except ValueError:
                    # batch size can't take the bitonic network (not a
                    # power of two): a build-time property of this
                    # shaper — disable for the run, count once
                    self._pallas_sort = False
                    _pl.record_fallback(self.obs, "sort_split_shape")
        with _obs.program_span(self.obs, "shaper.split"):
            if kern is not None:
                from .. import pallas as _pl

                _pl.record_dispatch(self.obs)
                (self._dev_stats, io_ts, io_vals, io_valid,
                 l_ts, l_vals, l_valid) = kern(
                     self._dev_stats, ts, vals, valid, cut, seed,
                     np.int64(ts_min))
            else:
                kern = _dev.sort_split_kernel(B, self.late_capacity)
                (self._dev_stats, io_ts, io_vals, io_valid,
                 l_ts, l_vals, l_valid) = kern(self._dev_stats, ts, vals,
                                               valid, cut, seed)
        if not late_possible:
            # provably nothing late: the sorted batch is fully in-order
            op.ingest_device_batch(io_vals, io_ts, ts_min, ts_max,
                                   n_valid=n, valid=io_valid)
            return
        if combined:
            # sorted whole batch through the general kernel (the
            # engine's own has_late route picks it from ts_min < met)
            op.ingest_device_batch(io_vals, io_ts, ts_min, ts_max,
                                   n_valid=n, valid=io_valid)
            return
        # split routing: in-order block first (the late kernel folds
        # against the updated slice buffer, same order as the host path)
        op.ingest_device_batch(io_vals, io_ts, met_pre, ts_max,
                               n_valid=n, valid=io_valid)
        op.ingest_device_late(l_ts, l_vals, l_valid, 0, ts_min,
                              max(ts_min, met_pre - 1))

    def shape_device_round(self, keys, vals, ts, ts_min: int,
                           ts_max: int, n_valid: Optional[int] = None
                           ) -> None:
        """Keyed device shaping: flat (key, value, ts) arrays of one
        round become the padded ``[K, Bk]`` layout on device and feed
        ``KeyedTpuWindowOperator.ingest_device_round``. Handles
        intra-round disorder (any timestamp order within the round);
        cross-round order follows the keyed operator's contract
        (``ts_min`` at/above the previous round's ``ts_max``)."""
        import jax.numpy as jnp

        op = self.op
        if op is None or not hasattr(op, "ingest_device_round"):
            raise TypeError(
                "shape_device_round needs a KeyedTpuWindowOperator")
        K, Bk = op.n_keys, op.config.batch_size
        if self._dev_stats is None:
            self._dev_stats = _dev.init_shaper_stats()
        ts = jnp.asarray(ts)
        N = ts.shape[0]
        n = N if n_valid is None else int(n_valid)
        valid = np.zeros((N,), bool)
        valid[:n] = True
        # the keyed operator allocates its host clock mirrors lazily at
        # first build — before that nothing has been ingested
        met_pre = getattr(op, "_host_met", None)
        seed = np.int64(met_pre) if met_pre is not None \
            else np.int64(_dev.I64_MIN)
        kern = _dev.keyed_round_kernel(K, Bk)
        self._dev_stats, ts_round, vals_round, mask = kern(
            self._dev_stats, keys, ts, vals, valid, seed)
        op.ingest_device_round(ts_round, vals_round, mask, ts_min, ts_max)

    # -- drain-point checks ------------------------------------------------
    def device_stats(self) -> dict:
        """Fetched device-shaper telemetry (one deliberate sync; drain
        points only). Empty dict before the first shaped device batch."""
        if self._dev_stats is None:
            return {}
        import jax

        return _dev.stats_snapshot(jax.device_get(self._dev_stats))

    def check(self) -> None:
        """Drain-point validation + telemetry fold: raises
        :class:`ShaperOverflow` when a late residue was lost, folds the
        device stats into the obs registry (``shaper_*`` names)."""
        snap = self.device_stats()
        obs = self.obs
        if obs is not None and snap:
            self._fold_counter(_obs.SHAPER_REORDERED_TUPLES,
                               "dev_reordered", snap["reordered"])
            self._fold_counter(_obs.SHAPER_LATE_ROUTED,
                               "dev_late_routed", snap["late_routed"])
        if snap.get("slack_overflow"):
            e = ShaperOverflow(
                "shaper device overflow — a batch's late residue "
                f"exceeded late_capacity={self.late_capacity} lanes, or "
                "a keyed round held more tuples for one key than the "
                "round size; tuples were lost on device. Raise "
                "ShaperConfig.late_capacity / the keyed batch_size, "
                "widen the host reorder slack (slack_ms), or route the "
                "stream through late_routing='combined'")
            if obs is not None:
                obs.counter(_obs.SHAPER_SLACK_OVERFLOWS).inc()
                obs.flight_event(_flight.SHAPER_OVERFLOW,
                                 _obs.SHAPER_SLACK_OVERFLOWS, 1.0)
                obs.record_failure(e, kind=_flight.SHAPER_OVERFLOW,
                                   config=getattr(self.op, "config", None))
            raise e

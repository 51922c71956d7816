"""MeshKeyedEngine: the keyed window operator stepped under ``shard_map``.

:class:`~scotty_tpu.parallel.keyed.KeyedTpuWindowOperator` scales keys by
handing ONE jitted program a ``[K, ...]`` state and letting GSPMD
propagate a ``NamedSharding`` through it. That works while the program is
perfectly per-key pointwise — but it leaves the partitioning implicit:
nothing PINS the per-shard program, a future op can silently introduce a
resharding, and there is no seam for cross-shard folds or key migration.
This engine makes the sharding explicit and owned:

* every kernel runs under ``jax.shard_map`` over the mesh's key axis —
  the per-shard program is the vmapped keyed kernel over that shard's
  ``K // n_shards`` rows, compiled once, collective-free;
* the carried state is DONATED through every step (ingest, GC, annex
  merge), so steady state moves zero extra HBM bytes;
* :meth:`query_global` folds all-shard window totals with
  ``psum``/``pmin``/``pmax`` INSIDE the executable — the
  ``parallel/global_op.py`` seam, now on the keyed path;
* a :class:`~scotty_tpu.mesh.routing.RoutingTable` decides which logical
  key occupies which physical row. Host batches route through its host
  mirror; device-resident rounds route through its device mirror (one
  gather inside the jitted ingest — never a host sync);
* per-key load (the state's own ``current_count``) is read at the
  existing drain points, hot keys are detected against the shard mean,
  and a rebalance — a row-swap permutation — is applied ONLY at a
  Supervisor checkpoint boundary (:meth:`checkpoint_and_rebalance`), so
  a crash mid-rebalance restores the pre-move bundle and a rebalanced
  restore bit-matches an unmoved oracle (tests/test_mesh.py).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import obs as _obs
from ..obs import flight as _flight
from ..engine.config import EngineConfig
from ..parallel.keyed import KeyedTpuWindowOperator
from .routing import RoutingTable, plan_rebalance


def _shard_map():
    from jax import shard_map
    return shard_map


def halving_sum(x, axis: int):
    """Sum ``x`` over ``axis`` by repeated halving: a pairwise tree whose
    grouping depends only on that axis's length. XLA's own reduce splits
    by the whole operand's shape (on CPU, a ``[2, S, 200]`` shard and a
    ``[16, S, 200]`` shard group the 200 adds differently), so a shard's
    per-slice sums would change bits with its key count; this keeps the
    mesh steps bit-identical under any shard count."""
    import jax
    import jax.numpy as jnp

    n = x.shape[axis]
    while n > 1:
        h = n // 2
        y = jax.lax.slice_in_dim(x, 0, h, axis=axis) \
            + jax.lax.slice_in_dim(x, h, 2 * h, axis=axis)
        if n % 2:
            y = jnp.concatenate(
                [y, jax.lax.slice_in_dim(x, 2 * h, n, axis=axis)], axis)
        x, n = y, h + n % 2
    return jnp.squeeze(x, axis)


def _mesh_token(mesh, axis: str) -> tuple:
    """Hashable identity of a mesh for kernel-cache keys: the device ids
    + axis name (two make_mesh calls over the same devices ARE the same
    topology — keying on object identity would defeat the cache)."""
    return (tuple(int(d.id) for d in mesh.devices.flat), axis)


#: jitted shard_map kernels keyed on (spec, shapes, mesh) — engines in a
#: test suite or bench cell rebuild freely without recompiling
_MESH_KERNEL_CACHE: dict = {}


def make_row_permuter(template_tree, sharding):
    """The ONE jitted row-permutation gather both rebalance paths use
    (engine state and pipeline carry): ``fn(tree, perm_i32)`` returns the
    tree with every leaf's leading axis gathered by ``perm``, re-laid to
    ``sharding`` (XLA lowers the cross-shard rows to collective permutes
    on a real mesh). Deliberately NOT donated: it runs only at checkpoint
    boundaries, and a cross-shard gather cannot alias in place."""
    import jax

    def permute(tree, p):
        return jax.tree.map(lambda x: x[p], tree)

    out_sh = jax.tree.map(lambda _: sharding, template_tree)
    jitted = jax.jit(permute, out_shardings=out_sh)

    def run(tree, perm):
        return jitted(tree, jax.device_put(
            np.asarray(perm, dtype=np.int32)))

    return run


class MeshKeyedEngine(KeyedTpuWindowOperator):
    """Keyed windows over a sharded device mesh (see module docstring).

    ``n_shards`` defaults to every local device; ``n_keys`` must be a
    multiple of it. The public keyed API is unchanged —
    ``process_keyed_elements`` takes LOGICAL keys and results come back
    attributed to logical keys — routing is an implementation detail the
    table owns.
    """

    def __init__(self, n_keys: int, n_shards: Optional[int] = None,
                 config: Optional[EngineConfig] = None, mesh=None,
                 axis: str = "keys", obs=None):
        import jax

        if mesh is not None:
            n_shards = mesh.devices.size
        elif n_shards is None:
            n_shards = len(jax.devices())
        if mesh is None:
            from ..parallel import make_mesh

            mesh = make_mesh(axis, n_devices=n_shards)
        super().__init__(n_keys=n_keys, config=config, mesh=mesh, axis=axis)
        self.n_shards = int(n_shards)
        self.routing = RoutingTable(self.n_keys, self.n_shards)
        self.obs = obs
        self._load_base = np.zeros(self.n_keys, np.int64)
        self._permute_fn = None
        self._router_fn = None
        self._dev_key_at = None
        self._global_query_fn = None

    def set_observability(self, obs) -> None:
        self.obs = obs

    def _count(self, name: str, n: int = 1) -> None:
        if self.obs is not None:
            self.obs.counter(name).inc(n)

    def _flight(self, kind: str, name: str, value: float = 0.0) -> None:
        if self.obs is not None:
            self.obs.flight_event(kind, name, value)

    # -- build: shard_map kernels over the key axis -------------------------
    def _sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(self.axis))

    def _build(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..engine import core as ec
        from ..engine.operator import dense_eligible, min_grid_period

        self._spec = self._compute_spec()
        C, A = self.config.capacity, self.config.annex_capacity
        dense_runs = (self.config.dense_ingest_runs
                      if dense_eligible(self._spec) else 0)
        key = (self._spec.periods, self._spec.bands,
               self._spec.offset_periods,
               tuple(ag.token for ag in self._spec.aggs), C, A,
               self.n_keys, dense_runs,
               _mesh_token(self.mesh, self.axis))
        hit = _MESH_KERNEL_CACHE.get(key)
        if hit is None:
            shard_map = _shard_map()
            a = self.axis

            ingest1 = ec.build_ingest(self._spec, C, A)
            ingest_io1 = ec.build_ingest(self._spec, C, A,
                                         assume_inorder=True)
            ingest_dense1 = (ec.build_ingest_dense(self._spec, C,
                                                   dense_runs)
                            if dense_runs else None)
            query1 = ec.build_query(self._spec, C, A)
            gc1 = ec.build_gc(self._spec, C, A)
            merge1 = ec.build_annex_merge(self._spec, C, A)

            def smap(fn, in_specs, out_specs, donate=None):
                """One sharded kernel: fn runs per shard over its local
                rows (vmap is shape-polymorphic, so the SAME per-key
                kernels the unsharded operator jits serve each shard's
                block)."""
                wrapped = shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                    out_specs=out_specs)
                if donate is not None:
                    return jax.jit(wrapped, donate_argnums=donate)
                return jax.jit(wrapped)

            st, rnd = P(a), P(a)
            hit = (
                smap(lambda s, t, v, m: jax.vmap(ingest1)(s, t, v, m),
                     (st, rnd, rnd, rnd), st, donate=(0,)),
                smap(lambda s, t, v, m: jax.vmap(ingest_io1)(s, t, v, m),
                     (st, rnd, rnd, rnd), st, donate=(0,)),
                (smap(lambda s, t, v, m: jax.vmap(ingest_dense1)(s, t, v,
                                                                 m),
                      (st, rnd, rnd, rnd), st, donate=(0,))
                 if ingest_dense1 is not None else None),
                smap(lambda s, ws, we, m, ic: jax.vmap(
                    query1, in_axes=(0, None, None, None, None))(
                        s, ws, we, m, ic),
                     (st, P(), P(), P(), P()), (st, st)),
                # GC donates too: it runs every watermark on the buffer
                smap(lambda s, b: jax.vmap(gc1, in_axes=(0, None))(s, b),
                     (st, P()), st, donate=(0,)),
                smap(lambda s: jax.vmap(merge1)(s), (st,), st,
                     donate=(0,)),
                dense_runs,
            )
            _MESH_KERNEL_CACHE[key] = hit
        (self._ingest, self._ingest_inorder, self._ingest_dense,
         self._query, self._gc, self._merge, self._dense_runs) = hit

        self._min_grid = min_grid_period(self._spec)
        self._host_met = None
        self._annex_dirty = False

        one = ec.init_state(self._spec, C, A)
        st0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.n_keys,) + x.shape), one)
        self._state = jax.device_put(st0, self._sharding())
        self._built = True

    # -- routed ingest -------------------------------------------------------
    def process_keyed_elements(self, keys: Sequence, values: Sequence,
                               timestamps: Sequence) -> None:
        """Batched keyed ingest by LOGICAL key: the host mirror of the
        routing table translates keys to physical rows, then the shared
        vectorized packing builds the per-shard ``[K, B]`` rounds."""
        if not self._built:
            self._build()
        phys = self.routing.rows_of(np.asarray(keys).reshape(-1))
        super().process_keyed_elements(phys, values, timestamps)

    def ingest_device_round(self, ts, vals, valid, ts_min: int,
                            ts_max: int, logical_major: bool = True) -> None:
        """Zero-copy ingest of one device-resident ``[K, B]`` round. With
        ``logical_major=True`` (the external contract) row ``k`` holds
        logical key ``k``'s tuples and the round is routed to physical
        rows through the DEVICE routing table — one gather inside the
        jitted path, no host sync; ``False`` feeds pre-routed physical
        rows (the internal fast path)."""
        if not self._built:
            self._build()
        if logical_major:
            import jax

            if self._router_fn is None:
                sh = self._sharding()

                def route(t, v, m, key_at):
                    return t[key_at], v[key_at], m[key_at]

                self._router_fn = jax.jit(route, out_shardings=(sh, sh, sh))
            if self._dev_key_at is None:    # invalidated by rebalances
                self._dev_key_at = jax.device_put(
                    np.asarray(self.routing.key_at, np.int32))
            ts, vals, valid = self._router_fn(ts, vals, valid,
                                              self._dev_key_at)
        super().ingest_device_round(ts, vals, valid, ts_min, ts_max)

    # -- results (logical attribution) ---------------------------------------
    def process_watermark_arrays(self, watermark_ts: int):
        """Synchronous watermark with LOGICAL-key rows: the physical
        ``[K, T]`` counts/lowered columns come back permuted so row ``k``
        is logical key ``k`` — one fancy-index gather on the fetched host
        arrays (the vectorized extraction path, VERDICT r5 item 7)."""
        ws, we, cnt, lowered = super().process_watermark_arrays(watermark_ts)
        row_of = self.routing.row_of
        return ws, we, cnt[row_of], [lw[row_of] for lw in lowered]

    # -- cross-shard global fold (the global_op.py seam, keyed path) ---------
    def query_global(self, window_starts, window_ends):
        """All-shard window totals for explicit ``[T]`` trigger arrays:
        per-shard vmapped range queries fold over local rows, then
        ``psum``/``pmin``/``pmax`` over the mesh axis INSIDE the
        executable. Returns ``(counts[T], [per-agg [T] lowered])`` on
        host — one fetch at this drain-point-shaped call."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        if not self._built:
            self._build()
        self._flush()
        if self._annex_dirty:
            self._state = self._merge(self._state)
            self._annex_dirty = False
        ws = np.asarray(window_starts, np.int64).reshape(-1)
        we = np.asarray(window_ends, np.int64).reshape(-1)
        T = ws.shape[0]
        Tp = self.config.trigger_pad(max(T, 1))
        ws_p = np.zeros((Tp,), np.int64)
        we_p = np.zeros((Tp,), np.int64)
        mask = np.zeros((Tp,), bool)
        ws_p[:T], we_p[:T], mask[:T] = ws, we, True

        if self._global_query_fn is None:
            from ..engine import core as ec

            query1 = ec.build_query(self._spec, self.config.capacity,
                                    self.config.annex_capacity)
            kinds = tuple(ag.kind for ag in self._spec.aggs)
            red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}
            coll = {"sum": jax.lax.psum, "min": jax.lax.pmin,
                    "max": jax.lax.pmax}
            a = self.axis

            def sharded(state, ws, we, m):
                cnt, results = jax.vmap(
                    query1, in_axes=(0, None, None, None, None))(
                        state, ws, we, m, jnp.zeros_like(m))
                cnt_g = jax.lax.psum(jnp.sum(cnt, axis=0), a)
                merged = tuple(coll[k](red[k](r, axis=0), a)
                               for k, r in zip(kinds, results))
                return cnt_g, merged

            self._global_query_fn = jax.jit(_shard_map()(
                sharded, mesh=self.mesh,
                in_specs=(P(a), P(), P(), P()), out_specs=P()))
        cnt_d, merged_d = self._global_query_fn(self._state, ws_p, we_p,
                                                mask)
        cnt_h, merged_h = jax.device_get((cnt_d, merged_d))
        cnt = np.asarray(cnt_h)[:T]
        lowered = []
        for agg, m in zip(self.aggregations, merged_h):
            spec = agg.device_spec()
            lowered.append(np.asarray(spec.lower(np.asarray(m)[:T], cnt)))
        return cnt, lowered

    # -- hot keys + rebalance -------------------------------------------------
    def key_loads(self) -> np.ndarray:
        """Per-LOGICAL-key tuples ingested since the last checkpoint mark
        — read from the state's own ``current_count`` at this drain point
        (one fetch; the same sync cadence as ``check_overflow``)."""
        if not self._built:
            return np.zeros(self.n_keys, np.int64)
        self._flush()
        cc = np.asarray(self._state.current_count)          # [K] physical
        logical = cc[self.routing.row_of].astype(np.int64)
        return logical - self._load_base

    def mark_load_baseline(self) -> None:
        """Reset the hot-key window (called at every checkpoint commit so
        detection reflects load SINCE the last safe rebalance point)."""
        if self._built:
            self._flush()       # buffered rounds belong to the OLD window
            cc = np.asarray(self._state.current_count)
            self._load_base = cc[self.routing.row_of].astype(np.int64)

    def detect_hot_keys(self, max_moves: int = 64,
                        imbalance_threshold: float = 1.25):
        """``(swaps, stats)`` — the greedy plan over the current load
        window. Hot keys found are counted (``mesh_hot_keys``) and
        flight-recorded; an empty plan means balanced."""
        loads = self.key_loads()
        swaps, stats = plan_rebalance(
            self.routing, loads, max_moves=max_moves,
            imbalance_threshold=imbalance_threshold)
        if self.obs is not None:
            self.obs.gauge(_obs.MESH_SHARD_IMBALANCE).set(
                float(stats["imbalance_before"]))
            # workload fingerprint (ISSUE 16): this is already THE
            # drain-point key_loads read — feed the skew features from
            # the same host array, zero extra device access
            if self.obs.workload is not None:
                self.obs.workload.observe_key_loads(loads)
        if swaps:
            self._count(_obs.MESH_HOT_KEYS, len(stats["hot_keys"]))
            for k in stats["hot_keys"]:
                self._flight(_flight.MESH_HOT_KEY, str(k), float(loads[k]))
        return swaps, stats

    def _permute_state(self, perm: np.ndarray):
        if self._permute_fn is None:
            self._permute_fn = make_row_permuter(self._state,
                                                 self._sharding())
        return self._permute_fn(self._state, perm)

    def rebalance(self, swaps: Sequence[Tuple[int, int]]) -> dict:
        """Apply a swap plan: permute the state rows (one jitted gather —
        XLA lowers the cross-shard rows to collective permutes on a real
        mesh) and install the new routing table. MUST be called at a
        checkpoint boundary only (:meth:`checkpoint_and_rebalance`
        enforces it); pending unflushed rounds are rejected because a
        crash mid-move could not replay them from the committed bundle."""
        if not self._built:
            raise RuntimeError("nothing to rebalance: engine not built")
        if self._n_pending:
            raise RuntimeError(
                "rebalance with pending unflushed rounds: commit a "
                "checkpoint first (rebalances happen only at checkpoint "
                "boundaries)")
        swaps = list(swaps)
        if not swaps:
            return {"moved": 0}
        new_table = self.routing.swapped(swaps)
        perm = new_table.permutation_from(self.routing)
        self._state = self._permute_state(perm)
        self.routing = new_table
        self._dev_key_at = None             # device mirror of the OLD map
        # the load window rides logical keys, so it survives the move
        self._count(_obs.MESH_REBALANCES)
        self._count(_obs.MESH_KEYS_MOVED, 2 * len(swaps))
        self._flight(_flight.MESH_REBALANCE, f"{len(swaps)}swaps",
                     2 * len(swaps))
        return {"moved": 2 * len(swaps)}

    # -- checkpoint boundary ----------------------------------------------
    def save(self, path: str) -> None:
        from ..utils.checkpoint import save_mesh_engine

        save_mesh_engine(self, path)

    def restore(self, path: str, verify: bool = True) -> None:
        from ..utils.checkpoint import restore_mesh_engine

        restore_mesh_engine(self, path, verify=verify)

    def checkpoint_and_rebalance(self, supervisor, pos: int,
                                 max_moves: int = 64,
                                 imbalance_threshold: float = 1.25,
                                 offset: Optional[int] = None) -> dict:
        """The one sanctioned rebalance flow: commit an atomic verified
        checkpoint of the CURRENT layout through the supervisor (manifest
        seal, lineage GC — the PR 3/PR 8 machinery), then detect hot keys
        over the load window and apply the swap plan. A crash anywhere
        inside the move restores the just-committed bundle — whose meta
        records state in LOGICAL key order, so the restore lands
        correctly under whatever routing the restarted engine holds."""
        self._flush()
        supervisor.commit_checkpoint(pos, self.save, offset=offset)
        swaps, stats = self.detect_hot_keys(
            max_moves=max_moves, imbalance_threshold=imbalance_threshold)
        stats = dict(stats)
        stats.update(self.rebalance(swaps) if swaps else {"moved": 0})
        self.mark_load_baseline()
        return stats

    # -- telemetry ----------------------------------------------------------
    def shard_occupancy(self) -> np.ndarray:
        """Per-shard live-slice occupancy fraction (drain-point read —
        rides the same fetch cadence as check_overflow)."""
        if not self._built:
            return np.zeros(self.n_shards)
        n = np.asarray(self._state.n_slices).reshape(
            self.n_shards, self.routing.rows_per_shard)
        occ = n.astype(np.float64) / float(self.config.capacity)
        out = occ.mean(axis=1)
        if self.obs is not None:
            for s, v in enumerate(out):
                self.obs.gauge(f"mesh_shard_occupancy_{s}").set(float(v))
        return out

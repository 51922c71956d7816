"""MeshKeyedPipeline: the fused keyed benchmark pipeline under shard_map.

The mesh edition of :class:`~scotty_tpu.parallel.keyed.KeyedAlignedPipeline`
— one XLA dispatch per watermark interval serving ``n_keys`` independent
keyed operators — with three deliberate differences:

* the step runs under ``jax.shard_map`` over the mesh's key axis with the
  whole carry DONATED: the per-shard program (generate → lift → append →
  trigger → range-query over that shard's ``K // n_shards`` rows) is
  explicit, pinned (tests/hlo_pins.json ``mesh`` entry) and
  collective-free except the global fold below;
* each interval additionally folds ALL-shard window totals with
  ``psum``/``pmin``/``pmax`` inside the executable — the
  ``parallel/global_op.py`` seam riding the keyed step, so the scaling
  bench certifies the collective path too, not just the pointwise one;
* the generated stream is keyed by the LOGICAL key id (a ``[K]`` id
  vector carried with the state), NOT the physical row: the workload is
  invariant under shard count and routing, which is what lets the
  scaling cell compare 8 shards vs 1 shard at equal total load and lets
  a mid-run hot-key rebalance leave emissions bit-identical
  (tests/test_mesh.py).

Rebalance contract: :meth:`rebalance` permutes the carried rows (one
jitted gather — collective permutes on a real mesh) and must only run at
a checkpoint boundary; :meth:`save`/:meth:`restore` write the canonical
logical-key-order snapshot (utils/checkpoint.py ``save_mesh_state``), so
restores re-permute into ANY shard count or routing.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from ..core.aggregates import AggregateFunction
from ..core.windows import SlidingWindow, TumblingWindow, WindowMeasure
from ..engine.config import EngineConfig
from ..engine.pipeline import FusedPipelineDriver
from .routing import RoutingTable
from .engine import _mesh_token, _shard_map, halving_sum

#: jitted (step, gc) per (windows, aggs, shapes, mesh) — bench cells and
#: test suites build several pipeline twins without recompiling
_STEP_CACHE: dict = {}


class MeshKeyedPipeline(FusedPipelineDriver):
    """Fused keyed pipeline sharded over a device mesh (module docstring).

    Carried state: ``{"buf": SliceBufferState[K, ...], "keys": i32[K]}``
    — ``keys[r]`` is the logical key at physical row ``r`` (the routing
    table's device mirror, donated through the step like the serving
    layer's query table: aliased pass-through, zero steady-state bytes).
    """

    def __init__(self, windows: Sequence,
                 aggregations: Sequence[AggregateFunction],
                 n_keys: int, n_shards: Optional[int] = None,
                 config: Optional[EngineConfig] = None,
                 throughput: int = 64_000_000, wm_period_ms: int = 1000,
                 max_lateness: int = 1000, seed: int = 0, gc_every: int = 8,
                 max_chunk_elems: int = 1 << 24,
                 value_scale: float = 10_000.0, mesh=None,
                 axis: str = "keys"):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..engine import core as ec
        from ..engine.pipeline import AlignedStreamPipeline, \
            build_trigger_grid, draw_uniform16

        if mesh is not None:
            n_shards = mesh.devices.size
        elif n_shards is None:
            n_shards = len(jax.devices())
        if mesh is None:
            from ..parallel import make_mesh

            mesh = make_mesh(axis, n_devices=n_shards)
        self.mesh, self.axis = mesh, axis
        self.n_shards = int(n_shards)
        self.config = config or EngineConfig()
        self.windows = list(windows)
        self.aggregations = list(aggregations)
        self.n_keys = K = int(n_keys)
        self.routing = RoutingTable(K, self.n_shards)
        self.wm_period_ms = P_ms = wm_period_ms
        self.max_lateness = max_lateness
        self.gc_every = gc_every
        self.seed = seed
        self.value_scale = float(value_scale)

        max_fixed = 0
        for w in self.windows:
            if w.measure != WindowMeasure.Time or not isinstance(
                    w, (TumblingWindow, SlidingWindow)):
                raise NotImplementedError(
                    "mesh keyed pipeline: time tumbling/sliding only")
            max_fixed = max(max_fixed, w.clear_delay())
        aggs = tuple(a.device_spec() for a in self.aggregations)
        if any(a is None for a in aggs):
            raise NotImplementedError(
                "mesh keyed pipeline: device-realizable aggregations only")
        g = AlignedStreamPipeline.slice_grid(self.windows, P_ms)
        per_key = throughput // K
        R = per_key * g // 1000
        if R < 1:
            raise NotImplementedError(
                "throughput too low: <1 tuple/slice/key")
        S = P_ms // g
        self.grid, self.R, self.S = g, R, S
        self.max_fixed = max_fixed
        self.tuples_per_interval = K * S * R

        spec = ec.EngineSpec(periods=(g,), bands=(), count_periods=(),
                             aggs=aggs)
        self.spec = spec
        C, A = self.config.capacity, self.config.annex_capacity
        query1 = ec.build_query(spec, C, A)
        gc1 = ec.build_gc(spec, C, A)
        make_triggers, self.T = build_trigger_grid(self.windows, P_ms)

        # chunking bounds the [Kl, S, Rc, width] lift temporary per shard
        # (sparse lifts scatter — width 1 in the budget, like keyed)
        max_width = max(1 if a.is_sparse else a.width for a in aggs)
        n_chunks = 1
        while (K * S * (R // n_chunks) * max_width) > max_chunk_elems \
                and n_chunks < R:
            n_chunks += 1
        while R % n_chunks:
            n_chunks += 1
        Rc = R // n_chunks
        self._n_chunks, self._rc = n_chunks, Rc

        #: Pallas segmented-reduce fold for the per-shard lifts
        #: (EngineConfig.pallas_slice_merge); part of the step cache
        #: key — a flags-off pipeline can never adopt a Pallas-bearing
        #: executable (or vice versa)
        pallas_fold = bool(getattr(self.config, "pallas_slice_merge",
                                   False))
        pallas_packed = pallas_fold and bool(
            getattr(self.config, "pallas_packed", False))
        self._pallas_in_step = pallas_fold

        win_tok = tuple((type(w).__name__, int(w.size),
                         int(getattr(w, "slide", 0))) for w in self.windows)
        cache_key = (win_tok, tuple(ag.token for ag in aggs), K, C, A,
                     R, S, g, P_ms, max_lateness, self.value_scale,
                     # chunking is part of the traced program AND of the
                     # host replay keying — a cache hit across different
                     # max_chunk_elems budgets would silently pair one
                     # chunking's device stream with the other's replay
                     n_chunks, Rc,
                     pallas_fold, pallas_packed,
                     _mesh_token(mesh, axis))
        first_lw = max(0, P_ms - max_lateness)
        red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}
        coll = {"sum": jax.lax.psum, "min": jax.lax.pmin,
                "max": jax.lax.pmax}
        shard_map = _shard_map()
        a_name = axis
        sharding = NamedSharding(mesh, P(axis))
        self._sharding = sharding

        def gen_chunk(kg, kids):
            """[Kl, S, Rc] values for one chunk: threefry keyed by the
            LOGICAL key id (fold_in(chunk_key, kid)), so every key's
            stream is identical under any shard count, routing, or
            rebalance — the invariance all differential cells rest on."""
            keys_k = jax.vmap(lambda kid: jax.random.fold_in(
                kg, kid.astype(jnp.uint32)))(kids)
            return jax.vmap(
                lambda k: draw_uniform16(k, (S, Rc), value_scale))(keys_k)

        def shard_body(state, key, interval_idx):
            buf, kids = state["buf"], state["keys"]
            Kl = kids.shape[0]
            base = interval_idx * P_ms

            def body(parts_c, c):
                vals = gen_chunk(jax.random.fold_in(key, c), kids)
                flat = vals.reshape(-1)
                new_parts = []
                for aspec, acc in zip(aggs, parts_c):
                    if pallas_fold:
                        # Pallas segmented-reduce fold per shard (the
                        # keyed pipeline's routing, under shard_map)
                        from .. import pallas as _spl

                        if aspec.is_sparse:
                            col, v = aspec.lift_sparse(flat)
                            upd = _spl.sparse_row_fold(
                                col, v, Kl * S, Rc, aspec.width,
                                aspec.kind, aspec.identity).reshape(
                                    Kl, S, aspec.width)
                        else:
                            upd = _spl.row_fold(
                                aspec.lift_dense(flat), Kl * S, Rc,
                                aspec.kind, aspec.identity,
                                packed=pallas_packed).reshape(Kl, S, -1)
                    elif aspec.is_sparse:
                        col, v = aspec.lift_sparse(flat)
                        row_id = jnp.arange(Kl * S * Rc,
                                            dtype=jnp.int32) // Rc
                        fi = row_id * aspec.width + col.astype(jnp.int32)
                        tgt = jnp.full((Kl * S * aspec.width,),
                                       aspec.identity, jnp.float32)
                        if aspec.kind == "sum":
                            tgt = tgt.at[fi].add(v)
                        elif aspec.kind == "min":
                            tgt = tgt.at[fi].min(v)
                        else:
                            tgt = tgt.at[fi].max(v)
                        upd = tgt.reshape(Kl, S, aspec.width)
                    else:
                        lifted = aspec.lift_dense(flat) \
                            .reshape(Kl, S, Rc, -1)
                        # sums fold in a fixed order so the bits
                        # do not depend on the shard's key count
                        upd = halving_sum(lifted, 2) \
                            if aspec.kind == "sum" \
                            else red[aspec.kind](lifted, axis=2)
                    if aspec.kind == "sum":
                        new_parts.append(acc + upd)
                    elif aspec.kind == "min":
                        new_parts.append(jnp.minimum(acc, upd))
                    else:
                        new_parts.append(jnp.maximum(acc, upd))
                return tuple(new_parts), None

            # the carry turns per-shard (varying over the key axis) in
            # the body, so its replicated initial value is cast to match
            init = jax.lax.pcast(
                tuple(jnp.full((Kl, S, ag.width), ag.identity, jnp.float32)
                      for ag in aggs), (a_name,), to="varying")
            parts, _ = jax.lax.scan(body, init, jnp.arange(n_chunks))

            row_starts = base + g * jnp.arange(S, dtype=jnp.int64)
            n = buf.n_slices                                  # [Kl] i32

            def app1(b, rows, nn):
                idx = (nn,) + (jnp.int32(0),) * (b.ndim - 1)
                return jax.lax.dynamic_update_slice(
                    b, rows.astype(b.dtype), idx)

            app = jax.vmap(app1)
            rs_k = jnp.broadcast_to(row_starts, (Kl, S))
            buf = buf._replace(
                starts=app(buf.starts, rs_k, n),
                ends=app(buf.ends, rs_k + g, n),
                t_first=app(buf.t_first, rs_k, n),
                t_last=app(buf.t_last, rs_k + (g - 1), n),
                c_start=app(buf.c_start, buf.current_count[:, None]
                            + R * jnp.arange(S, dtype=jnp.int64)[None, :],
                            n),
                counts=app(buf.counts, jnp.full((Kl, S), R, jnp.int64),
                           n),
                partials=tuple(app(p, pr, n)
                               for p, pr in zip(buf.partials, parts)),
                n_slices=n + S,
                max_event_time=jnp.maximum(
                    buf.max_event_time, rs_k[:, -1] + (g - 1)),
                current_count=buf.current_count + S * R,
                overflow=buf.overflow | (n + S > C),
            )
            last_wm = jnp.where(interval_idx > 0, base, jnp.int64(first_lw))
            ws, we, tmask = make_triggers(last_wm, base + P_ms)
            cnt, results = jax.vmap(
                query1, in_axes=(0, None, None, None, None))(
                buf, ws, we, tmask, jnp.zeros_like(tmask))
            # the cross-shard fold: all-keys window totals INSIDE the
            # executable (psum over ICI on a real mesh) — the
            # global_op.py seam certified by the mesh bench cell
            gcnt = jax.lax.psum(jnp.sum(cnt, axis=0), a_name)
            gparts = tuple(
                coll[ag.kind](red[ag.kind](r, axis=0), a_name)
                for ag, r in zip(aggs, results))
            return ({"buf": buf, "keys": kids},
                    (ws, we, cnt, results, gcnt, gparts))

        Pa = P(axis)
        state_spec = {"buf": Pa, "keys": Pa}
        hit = _STEP_CACHE.get(cache_key)
        if hit is None:
            # a pallas_call output carries no varying-axis type, so the
            # flagged-on step turns the vma check off (out_specs pin
            # every output's sharding explicitly); flags-off keeps it
            step_kw = {"check_vma": False} if pallas_fold else {}
            hit = (
                jax.jit(shard_map(
                    shard_body, mesh=mesh,
                    in_specs=(state_spec, P(), P()),
                    out_specs=(state_spec, (P(), P(), Pa, Pa, P(), P())),
                    **step_kw),
                    donate_argnums=0),
                jax.jit(shard_map(
                    lambda st, b: {"buf": jax.vmap(
                        gc1, in_axes=(0, None))(st["buf"], b),
                        "keys": st["keys"]},
                    mesh=mesh, in_specs=(state_spec, P()),
                    out_specs=state_spec),
                    donate_argnums=0),
            )
            _STEP_CACHE[cache_key] = hit
        self._step, self._gc_fn = hit
        self._permute_fn = None
        self._root = None
        self.state = None
        self._interval = 0

        def init_state():
            one = ec.init_state(spec, C, A)
            buf = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (K,) + x.shape), one)
            kids = jnp.asarray(self.routing.key_at, jnp.int32)
            return jax.device_put({"buf": buf, "keys": kids}, sharding)

        self._init_state = init_state

    def _init_pipeline_state(self) -> None:
        self.state = self._init_state()

    def _gc(self, bound) -> None:
        self.state = self._gc_fn(self.state, bound)

    def _sync_anchor(self):
        return self.state["buf"].n_slices[0]

    def check_overflow(self) -> None:
        import jax

        if bool(np.any(jax.device_get(self.state["buf"].overflow))):
            raise RuntimeError("slice buffer overflow on some key shard")

    # -- rebalance (checkpoint boundaries only) -----------------------------
    def rebalance(self, swaps: Sequence[Tuple[int, int]]) -> None:
        """Permute the carried rows to a swapped routing table (one
        jitted gather; the generated stream rides the logical key ids, so
        subsequent emissions are bit-identical to a never-rebalanced run
        modulo row attribution — which :meth:`lowered_results_for_key`
        resolves through the table). Call at checkpoint boundaries only:
        a crash mid-permute must restore the committed pre-move bundle."""
        if not swaps:
            return
        if self.state is None:
            raise RuntimeError("pipeline not started")
        from .engine import make_row_permuter

        new_table = self.routing.swapped(list(swaps))
        perm = new_table.permutation_from(self.routing)
        if self._permute_fn is None:
            self._permute_fn = make_row_permuter(self.state,
                                                 self._sharding)
        self.state = self._permute_fn(self.state, perm)
        self.routing = new_table

    # -- checkpoint (canonical logical order; shard-count-portable) --------
    def save(self, path: str) -> None:
        from ..utils.checkpoint import save_mesh_state

        if self.state is None or self._root is None:
            raise ValueError("pipeline not started; nothing to checkpoint")
        save_mesh_state(self.state["buf"], self.routing, path, {
            "pipeline": type(self).__name__,
            "interval": int(self._interval), "seed": int(self.seed),
            "root": np.asarray(self._root).tolist(),
        })

    def restore(self, path: str, verify: bool = True) -> None:
        import jax
        import jax.numpy as jnp

        from ..utils.checkpoint import load_mesh_state

        self.reset()
        tree, meta = load_mesh_state(path, self.state["buf"], self.routing,
                                     verify=verify)
        if int(self.seed) != meta["seed"]:
            raise ValueError("seed mismatch: the restored stream would "
                             "differ")
        self.state = jax.device_put(
            {"buf": tree, "keys": jnp.asarray(self.routing.key_at,
                                              jnp.int32)},
            self._sharding)
        self._interval = meta["interval"]
        self._root = jnp.asarray(np.asarray(meta["root"], np.uint32))

    # -- host replay + result attribution ----------------------------------
    def materialize_interval(self, i: int, key_idx: int):
        """Regenerate LOGICAL key ``key_idx``'s interval-i stream on host
        (testing): (vals f32, ts i64) — bit-identical to the device
        generator under any shard count/routing."""
        import jax
        import jax.numpy as jnp

        from ..engine.pipeline import draw_uniform16

        if self._root is None:
            self._root = jax.random.PRNGKey(self.seed)
        key = self._interval_key(i)
        vals_all, ts_all = [], []
        row_starts = i * self.wm_period_ms \
            + self.grid * np.arange(self.S, dtype=np.int64)
        for c in range(self._n_chunks):
            kk = jax.random.fold_in(
                jax.random.fold_in(key, jnp.int64(c)),
                jnp.uint32(key_idx))
            vals = np.asarray(jax.device_get(draw_uniform16(
                kk, (self.S, self._rc), self.value_scale)))
            vals_all.append(vals.reshape(-1))
            ts_all.append(np.broadcast_to(
                row_starts[:, None], (self.S, self._rc)).reshape(-1))
        return np.concatenate(vals_all), np.concatenate(ts_all)

    def lowered_results_for_key(self, interval_out, key_idx: int) -> list:
        """Fetch + lower one interval's window results for one LOGICAL
        key (row attribution through the routing table). The fetch
        duration folds into the owning shard's
        ``latency_shard_<s>_emit_ms`` histogram (ISSUE 14 — the
        per-shard stamp at the psum drain, on the tracer's injectable
        clock; host-side only, the shard_map step HLO stays pinned)."""
        import jax

        lat = self.obs.latency if self.obs is not None else None
        t0 = lat.clock.now() if lat is not None else 0.0
        ws, we, cnt, results = jax.device_get(interval_out[:4])
        if lat is not None:
            shard = int(self.routing.row_of[key_idx]) \
                // self.routing.rows_per_shard
            lat.shard_fold(shard, (lat.clock.now() - t0) * 1e3)
        r = int(self.routing.row_of[key_idx])
        cnt_k = cnt[r]
        lowered = [np.asarray(agg.device_spec().lower(res[r], cnt_k))
                   for agg, res in zip(self.aggregations, results)]
        rows = []
        for i in range(ws.shape[0]):
            if cnt_k[i] > 0:
                rows.append((int(ws[i]), int(we[i]), int(cnt_k[i]),
                             [lw[i] for lw in lowered]))
        return rows

    def lowered_global(self, interval_out) -> list:
        """Fetch + lower the interval's cross-shard global fold: list of
        (start, end, count, [per-agg all-keys value]) for non-empty
        windows — the psum seam's host face."""
        import jax

        ws, we = jax.device_get(interval_out[:2])
        gcnt, gparts = jax.device_get(interval_out[4:6])
        lowered = [np.asarray(agg.device_spec().lower(gp, gcnt))
                   for agg, gp in zip(self.aggregations, gparts)]
        rows = []
        for i in range(ws.shape[0]):
            if gcnt[i] > 0:
                rows.append((int(ws[i]), int(we[i]), int(gcnt[i]),
                             [lw[i] for lw in lowered]))
        return rows

    def shard_occupancy(self) -> np.ndarray:
        """Per-shard mean live-slice occupancy (drain-point read)."""
        import jax

        n = np.asarray(jax.device_get(self.state["buf"].n_slices)).reshape(
            self.n_shards, self.routing.rows_per_shard)
        return n.astype(np.float64).mean(axis=1) / float(
            self.config.capacity)

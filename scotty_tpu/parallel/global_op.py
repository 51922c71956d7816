"""Global (non-keyed) windows over a sharded stream.

The reference's GlobalScottyWindowOperator runs ONE operator instance for the
whole stream (flink-connector/.../GlobalScottyWindowOperator.java:16-85) —
single-threaded, so its throughput is one core's. The TPU-native redesign
splits the stream round-robin across shards, each shard folds its share into
its own slice buffer, and window results combine across shards at watermark
time with the aggregation's own ``combine`` — a tree/``psum``-style reduction
over the shard axis that XLA lowers to ICI collectives when the shard axis is
device-sharded (SURVEY.md §5: "global windows become psum/segment_sum
collectives over ICI").

Correctness license: ``combine`` associativity + commutativity over slices
(AggregateFunction.java:19-34) — any tuple may fold into any shard's slice
for the same [ws, we) range query result.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from ..core.operator import AggregateWindow
from ..core.windows import WindowMeasure
from ..engine.config import EngineConfig
from .keyed import KeyedTpuWindowOperator


class GlobalTpuWindowOperator(KeyedTpuWindowOperator):
    """Non-keyed windows, sharded execution, collective merge."""

    def __init__(self, n_shards: int = 8, config: Optional[EngineConfig] = None,
                 mesh=None, axis: str = "shards"):
        super().__init__(n_keys=n_shards, config=config, mesh=mesh, axis=axis)
        self._rr = 0
        self._global_query = None

    def _build_global_query(self):
        """ONE jitted watermark program: vmapped per-shard range query +
        cross-shard combine. Without a mesh the combine is an axis-0
        reduction; with a mesh it runs under ``shard_map`` with
        ``psum``/``pmin``/``pmax`` over the shard axis, which XLA lowers to
        a fused all-reduce over ICI — the SURVEY §5 "global windows become
        psum collectives" design, now actually inside the executable
        (VERDICT r1 item 8: the combine used to run eagerly outside jit)."""
        import jax
        import jax.numpy as jnp

        from ..engine import core as ec

        query1 = ec.build_query(self._spec, self.config.capacity,
                                self.config.annex_capacity)
        kinds = tuple(a.kind for a in self._spec.aggs)
        red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}

        def local_block(state, ws, we, mask):
            cnt, results = jax.vmap(
                query1, in_axes=(0, None, None, None, None))(
                    state, ws, we, mask, jnp.zeros_like(mask))
            cnt_g = jnp.sum(cnt, axis=0)
            merged = tuple(red[k](r, axis=0)
                           for k, r in zip(kinds, results))
            return cnt_g, merged

        if self.mesh is None:
            return jax.jit(local_block)

        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        coll = {"sum": jax.lax.psum, "min": jax.lax.pmin,
                "max": jax.lax.pmax}
        axis = self.axis

        def sharded(state, ws, we, mask):
            cnt_l, merged_l = local_block(state, ws, we, mask)
            cnt_g = jax.lax.psum(cnt_l, axis)
            merged = tuple(coll[k](m, axis)
                           for k, m in zip(kinds, merged_l))
            return cnt_g, merged

        smapped = shard_map(
            sharded, mesh=self.mesh,
            in_specs=(P(axis), P(), P(), P()),
            out_specs=P())
        return jax.jit(smapped)

    def process_elements(self, values: Sequence, timestamps: Sequence) -> None:
        """Round-robin the stream across shards (order within a shard stays
        ascending because the driver ts-sorts each device batch)."""
        v = np.asarray(values, dtype=np.float32).reshape(-1)
        t = np.asarray(timestamps, dtype=np.int64).reshape(-1)
        n = v.shape[0]
        shard = (np.arange(self._rr, self._rr + n) % self.n_keys).astype(np.int32)
        self._rr = (self._rr + n) % self.n_keys
        self.process_keyed_elements(shard, v, t)

    def process_element(self, element, ts: int) -> None:  # type: ignore[override]
        self.process_elements([element], [ts])

    def process_watermark(self, watermark_ts: int) -> List[AggregateWindow]:
        """Combine per-shard range-query results across the shard axis."""
        ws, we, cnt, _ = self.process_watermark_arrays_combined(watermark_ts)
        out: List[AggregateWindow] = []
        for i in range(ws.shape[0]):
            has = bool(cnt[i] > 0)
            values = self._lowered_global[i] if has else []
            out.append(AggregateWindow(WindowMeasure.Time, int(ws[i]),
                                       int(we[i]), values, has))
        return out

    def process_watermark_arrays_combined(self, watermark_ts: int):
        if not self._built:
            self._build()
        self._flush()
        if self._annex_dirty:
            self._state = self._merge(self._state)
            self._annex_dirty = False
        st = self._state
        if bool(np.any(np.asarray(st.overflow))):
            raise RuntimeError("slice buffer overflow on some shard")

        last_wm = self._last_watermark
        if last_wm == -1:
            last_wm = max(0, watermark_ts - self.max_lateness)

        trig_s, trig_e = [], []
        for w in self.windows:
            s_arr, e_arr = w.trigger_arrays(last_wm, watermark_ts)
            trig_s.append(s_arr)
            trig_e.append(e_arr)
        empty = np.empty(0, dtype=np.int64)
        ws = np.concatenate(trig_s) if trig_s else empty
        we = np.concatenate(trig_e) if trig_e else empty
        T = ws.shape[0]

        cnt_g = np.zeros((0,), np.int64)
        self._lowered_global: list = []
        lowered_cols: List[np.ndarray] = []
        if T:
            import jax

            if self._global_query is None:
                self._global_query = self._build_global_query()
            Tp = self.config.trigger_pad(T)
            ws_p = np.zeros((Tp,), np.int64)
            we_p = np.zeros((Tp,), np.int64)
            mask = np.zeros((Tp,), bool)
            ws_p[:T], we_p[:T], mask[:T] = ws, we, True
            cnt_d, merged_d = self._global_query(st, ws_p, we_p, mask)
            cnt_h, merged_h = jax.device_get((cnt_d, merged_d))  # one fetch
            cnt_g = np.asarray(cnt_h)[:T]
            for agg, merged in zip(self.aggregations, merged_h):
                spec = agg.device_spec()
                lowered_cols.append(
                    np.asarray(spec.lower(np.asarray(merged)[:T], cnt_g)))
            self._lowered_global = [
                [col[i] for col in lowered_cols] for i in range(T)]

        bound = (watermark_ts - self.max_lateness) - self.max_fixed_window_size
        self._state = self._gc(st, np.int64(bound))
        self._last_watermark = watermark_ts
        return ws, we, cnt_g, lowered_cols

"""Overflow policies + capacity-growth machinery.

The XLA engine's buffers are statically shaped (EngineConfig), so the
reference's grow-on-demand ArrayList store (LazyAggregateStore.java:148-157)
has no direct analogue: the seed behavior was one fail-fast ``RuntimeError``
at the overflow drain points. This module makes that a *policy*:

``FAIL``
    today's behavior, still the default everywhere (benchmarked mode).
``SHED``
    degrade gracefully: admission control at the HOST ingest boundary
    drops the lowest-watermark-impact tuples (late tuples first — they
    can only repair already-old windows — then tuples opening slices
    beyond the remaining headroom), counting exact drops in DeviceMetrics
    (``device_dropped_tuples``) and the registry
    (``resilience_shed_tuples``) so results stay auditable: the engine's
    output is bit-equal to a replay of exactly the surviving tuples.
    Shedding is only meaningful where an external stream crosses into the
    engine (TpuWindowOperator host batches, connectors); the fused
    pipelines generate their own load in-jit — there is nothing external
    to shed — so they treat SHED like FAIL.
``GROW``
    snapshot the carried state via the checkpoint pytree machinery,
    rebuild the jitted kernels at doubled capacity, corner-paste the old
    state into the fresh (larger) buffers and resume — bounded by
    ``EngineConfig.max_capacity`` so an unbounded overload cannot
    OOM-spiral. Growth is PREVENTIVE (it fires at the existing drain
    points / admission checks before any buffer clamps a write): a raised
    device overflow flag means data was already lost and stays fatal
    under every policy.

All policy work is gated host-side on ``config.overflow_policy``; under
``FAIL`` the jitted steps and the per-batch host path are byte-identical
to the seed (the bench A/B bound).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .. import obs as _obs
from ..obs import flight as _flight


class OverflowPolicy:
    """String constants (kept plain so EngineConfig stays a frozen,
    JSON-friendly dataclass)."""

    FAIL = "fail"
    SHED = "shed"
    GROW = "grow"
    ALL = (FAIL, SHED, GROW)

    @staticmethod
    def validate(policy: str) -> str:
        if policy not in OverflowPolicy.ALL:
            raise ValueError(
                f"unknown overflow_policy {policy!r}: expected one of "
                f"{OverflowPolicy.ALL}")
        return policy


def max_capacity_of(config) -> int:
    """The GROW bound: explicit ``max_capacity`` or 8× the configured
    capacity (three doublings) when unset."""
    return int(config.max_capacity) or 8 * int(config.capacity)


def grow_engine_config(config):
    """The next GROW step: capacity and annex_capacity doubled (an
    explicit record_capacity doubles too; the 4×capacity default scales
    by itself). Raises when the bound is already reached.

    The grown config PINS ``max_capacity`` to the resolved bound: an
    implicit bound (max_capacity=0 → 8× capacity) must anchor to the
    ORIGINAL capacity, not drift upward with every doubling — otherwise
    a sustained overload grows forever until OOM, the exact spiral the
    bound exists to stop."""
    bound = max_capacity_of(config)
    if 2 * config.capacity > bound:
        raise RuntimeError(
            f"overflow_policy='grow' reached max_capacity={bound} "
            f"(capacity={config.capacity}); raise EngineConfig.max_capacity "
            "or shed load upstream")
    return dataclasses.replace(
        config,
        capacity=2 * config.capacity,
        annex_capacity=2 * config.annex_capacity,
        max_capacity=bound,
        record_capacity=(2 * config.record_capacity
                         if config.record_capacity else 0))


def pad_tree(old_host_leaves, fresh_tree):
    """Corner-paste checkpointed leaves into a freshly-initialized larger
    state: for each leaf pair, the old content lands in the leading corner
    and the tail keeps the fresh init values (buffer rows beyond the live
    prefix are inert by construction, so a grown state is exactly the
    state a pre-sized run would have reached). Scalars (equal shapes) are
    taken from the old leaves. Returns XLA-owned device copies safe to
    feed into donating kernels."""
    import jax

    from ..utils.checkpoint import _device_copy

    fresh_leaves, treedef = jax.tree.flatten(fresh_tree)
    if len(old_host_leaves) != len(fresh_leaves):
        raise ValueError(
            f"grow: state has {len(old_host_leaves)} leaves but the grown "
            f"template expects {len(fresh_leaves)} — same windows/"
            "aggregations required")
    out = []
    for old, fresh in zip(old_host_leaves, fresh_leaves):
        old = np.asarray(old)
        tpl = np.asarray(fresh)
        if old.shape == tpl.shape:
            out.append(old.astype(tpl.dtype, copy=False))
            continue
        if old.ndim != tpl.ndim or any(
                o > t for o, t in zip(old.shape, tpl.shape)):
            raise ValueError(
                f"grow: leaf shape {old.shape} does not embed in grown "
                f"template {tpl.shape}")
        merged = tpl.copy()
        merged[tuple(slice(0, s) for s in old.shape)] = old
        out.append(merged)
    return _device_copy(jax.tree.unflatten(treedef, out))


def grow_pipeline(pipeline, factory, obs=None):
    """GROW a fused pipeline: snapshot its carried state (the checkpoint
    pytree — see utils/checkpoint.py ``_pipeline_tree``), build a
    replacement via ``factory(grown_config)``, corner-paste the state into
    the larger buffers and hand back the replacement mid-stream (same
    interval counter, same RNG root, same DeviceMetrics → the continued
    run is bit-identical to one pre-sized at the larger capacity).

    ``factory`` must construct the same pipeline class with the same
    constructor arguments except ``config``.
    """
    import contextlib

    import jax

    from ..utils.checkpoint import _device_copy, _pipeline_tree

    obs = obs if obs is not None else getattr(pipeline, "obs", None)
    new_config = grow_engine_config(pipeline.config)
    span = obs.span(_obs.RESILIENCE_GROW_SPAN) if obs is not None \
        else contextlib.nullcontext()
    with span:
        old_leaves = jax.device_get(
            jax.tree.flatten(_pipeline_tree(pipeline))[0])
        grown = factory(new_config)
        if type(grown) is not type(pipeline):
            raise ValueError(
                f"grow factory built {type(grown).__name__}, expected "
                f"{type(pipeline).__name__}")
        grown.reset()
        restored = pad_tree(old_leaves, _pipeline_tree(grown))
        grown.state = restored["state"]
        if restored["sessions"]:
            grown.sess_states = restored["sessions"]
        grown._interval = pipeline._interval
        grown._root = pipeline._root
        if getattr(pipeline, "dm", None) is not None:
            grown.dm = _device_copy(pipeline.dm)
        grown._dm_host = getattr(pipeline, "_dm_host", None)
        grown._dm_folded = getattr(pipeline, "_dm_folded", None)
        if getattr(pipeline, "obs", None) is not None:
            grown.obs = pipeline.obs
    if obs is not None:
        obs.counter(_obs.RESILIENCE_GROW_EVENTS).inc()
        obs.flight_event(_flight.GROW, "capacity",
                         float(new_config.capacity))
    return grown


def backoff_delay(attempt: int, base_s: float, max_s: float,
                  jitter: float, rng) -> float:
    """Bounded exponential backoff with multiplicative jitter:
    ``min(base * 2^(attempt-1), max) * (1 + jitter * u)``, ``u`` drawn
    from the caller's seeded ``rng`` — deterministic under a fixed seed,
    de-synchronized across real deployments."""
    d = min(base_s * (2.0 ** max(0, attempt - 1)), max_s)
    if jitter:
        d *= 1.0 + jitter * float(rng.random())
    return d

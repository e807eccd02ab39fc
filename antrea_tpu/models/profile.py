"""Churn-loop phase profiler for the stateful pipeline.

The round-5 verdict's weak #1: the churn regime runs at ~0.5x the north
star and ~3x below what the component numbers predict, and the slow-path
loop had never been profiled.  This module attributes the churn-step time
to named phases WITHOUT host-side timers (they would time dispatch and
fetch, not the device — utils/timing.py): the slow path is compiled at a
chain of cumulative phase masks (models/pipeline.PH_*), each variant is
timed on-device with `device_loop_time`, and the per-phase cost is the
telescoped difference between adjacent masks — so the phase breakdown sums
EXACTLY to the full-step time by construction, and an independent
full-step measurement cross-checks the chain (bench_profile.py gates on
+-15% agreement).

Workload shape mirrors bench.measure_churn: a warmed hot set (established
traffic, fast-path hits) with a rolling window of genuinely fresh flows
from a pool replacing the first `n_new` lanes every step — every timed
iteration pays the same miss work regardless of which phases are masked.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.timing import device_loop_time
from . import pipeline as pl

# The cumulative mask chain: phase k's cost = t(chain[k]) - t(chain[k-1]).
# Order matters — each mask is a superset of the previous, and PH_EVICT
# rides last because the eviction audit reads the commit's insert targets.
PHASE_CHAIN: tuple[tuple[str, int], ...] = (
    ("fast_path", 0),
    ("miss_detect", pl.PH_SLOW),
    ("service_lb", pl.PH_SLOW | pl.PH_LB),
    # PH_CLS_SUM: the classifier's aggregate (summary) phase alone — a
    # ~zero-cost entry unless the meta carries a prune budget (round 7),
    # where it splits summary-gather cost from candidate-gather cost.
    ("classify_summary", pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM),
    ("classify", pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS),
    ("cache_commit",
     pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS | pl.PH_COMMIT),
    ("eviction_scan", pl.PH_ALL),
)

# Async-regime chain (datapath/slowpath): the floor is the decoupled FAST
# step (phases=0 — misses admitted, not classified), then each drain
# phase adds one PH_ bit to the COALESCED drain step, which runs the
# fresh window as ONE slow-path round (miss_chunk == drain batch) instead
# of the sync path's many chunked rounds.  Same telescoped-differencing
# honesty property; same PH_* bit set (tools/check_phases.py gates the
# two chains and the pipeline masks against each other).
ASYNC_PHASE_CHAIN: tuple[tuple[str, int], ...] = (
    ("async_fast_path", 0),
    ("drain_miss_detect", pl.PH_SLOW),
    ("drain_service_lb", pl.PH_SLOW | pl.PH_LB),
    ("drain_classify_summary", pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM),
    ("drain_classify", pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS),
    ("drain_cache_commit",
     pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS | pl.PH_COMMIT),
    ("drain_eviction_scan", pl.PH_ALL),
)

# Overlapped-regime chain (round 6, ROADMAP item 2): the double-buffered
# cadence — every timed iteration dispatches the FAST step of window i
# and the DRAIN of window i-1 (one-step-deferred commit, the two-slot
# pending-commit staging of datapath/slowpath), with the drain compiled
# at meta.drain_reclaim=True (the fused eviction+aging commit pass).
# Because the drain of window i-1 has no data dependency on the fast
# step of window i's OUTPUTS (only on the carried state), XLA is free to
# pipeline the two dispatches; the telescoped chain then attributes what
# the overlap actually hides — if drain phases telescope to ~0 over the
# async chain's costs, the serialization was removed; if they reappear,
# it was not.  Same honesty property, same PH_* bit set
# (tools/check_phases.py gates all three chains).
OVERLAP_PHASE_CHAIN: tuple[tuple[str, int], ...] = (
    ("overlap_fast_path", 0),
    ("overlap_miss_detect", pl.PH_SLOW),
    ("overlap_service_lb", pl.PH_SLOW | pl.PH_LB),
    ("overlap_classify_summary", pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM),
    ("overlap_classify",
     pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS),
    ("overlap_cache_commit",
     pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS | pl.PH_COMMIT),
    ("overlap_evict_age", pl.PH_ALL),
)

# Maintenance-regime chain (the unified background plane, ROADMAP item 5):
# the async cadence with the scheduler's fused maintenance pass
# (pl.maintain_scan — the cache-maintain task's full-table aging +
# stale-generation revalidation) riding EVERY timed iteration, chain
# entry 0 included.  Because the rider is a constant across all entries,
# the telescoped differences still attribute the pure drain phases (the
# rider cancels), the chain end is the full maintenance-cadence step (the
# honesty gate's target), and `maint_fast_path` minus a rider-free fast
# step — reported as `maintenance_s` by profile_churn_maintenance — is
# the scheduler's own attributed cost.  Same PH_* bit set
# (tools/check_phases.py gates all four chains).
MAINT_PHASE_CHAIN: tuple[tuple[str, int], ...] = (
    ("maint_fast_path", 0),
    ("maint_miss_detect", pl.PH_SLOW),
    ("maint_service_lb", pl.PH_SLOW | pl.PH_LB),
    ("maint_classify_summary", pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM),
    ("maint_classify", pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS),
    ("maint_cache_commit",
     pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS | pl.PH_COMMIT),
    ("maint_sweep", pl.PH_ALL),
)


# Prune-regime chain (round 7, ROADMAP item 2's kernel half): the async
# drain cadence over a prune_budget > 0 meta, with the classify entry
# SPLIT at the two-level kernel's seam — `prune_summary_gather` adds
# PH_CLS_SUM (aggregate rows gathered + ANDed, short-circuit defaults,
# no candidate work) and `prune_candidate_gather` adds PH_CLS on top
# (the K-superblock candidate gather, the first-match scan, and the
# pow2-rung fallback redispatches).  Telescoping their difference IS the
# candidate-path cost the aggregate layer was built to bound; the ±15%
# gate (bench_profile.py --mode prune) cross-checks the attribution.
PRUNE_PHASE_CHAIN: tuple[tuple[str, int], ...] = (
    ("prune_fast_path", 0),
    ("prune_miss_detect", pl.PH_SLOW),
    ("prune_service_lb", pl.PH_SLOW | pl.PH_LB),
    ("prune_summary_gather", pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM),
    ("prune_candidate_gather",
     pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS),
    ("prune_cache_commit",
     pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS | pl.PH_COMMIT),
    ("prune_evict", pl.PH_ALL),
)


# One-kernel (fused) regime chain (round 8, ROADMAP item 1): the async
# drain cadence over a meta.onepass=True meta.  The phases honor the
# same PH_ bits — the LB probe chain, the aggregate (summary) gathers,
# the commit scatters and the eviction audit are still maskable XLA
# stages around the kernel — but `fused_onepass` (the PH_CLS add) is
# deliberately ONE entry: probe decode, candidate DMA, first-match,
# resolve and commit-row packing have no interior dispatch boundaries
# left to telescope, which is the point of the fusion.  Diffing this
# chain against PRUNE_PHASE_CHAIN attributes exactly what the one-pass
# removed (the staged kernel's classify/commit materialization
# boundaries); the ±15% gate applies via bench_profile.py --mode fused.
FUSED_PHASE_CHAIN: tuple[tuple[str, int], ...] = (
    ("fused_fast_path", 0),
    ("fused_miss_detect", pl.PH_SLOW),
    ("fused_service_lb", pl.PH_SLOW | pl.PH_LB),
    ("fused_summary_gather", pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM),
    ("fused_onepass", pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS),
    ("fused_commit",
     pl.PH_SLOW | pl.PH_LB | pl.PH_CLS_SUM | pl.PH_CLS | pl.PH_COMMIT),
    ("fused_evict", pl.PH_ALL),
)


def _dev_cols(batch) -> tuple:
    """PacketBatch -> the pipeline's flipped/typed device columns."""
    from ..utils import ip as iputil

    return (
        jnp.asarray(iputil.flip_u32(batch.src_ip)),
        jnp.asarray(iputil.flip_u32(batch.dst_ip)),
        jnp.asarray(batch.proto.astype(np.int32)),
        jnp.asarray(batch.src_port.astype(np.int32)),
        jnp.asarray(batch.dst_port.astype(np.int32)),
    )


def profile_churn(
    meta: pl.PipelineMeta,
    state: pl.PipelineState,
    drs,
    dsvc,
    hot: tuple,
    pool: Optional[tuple] = None,
    *,
    n_new: Optional[int] = None,
    now0: int = 1000,
    gen: int = 0,
    k_small: int = 2,
    k_big: int = 8,
    repeats: int = 2,
    chain: tuple = PHASE_CHAIN,
) -> dict:
    """Per-phase churn-loop breakdown -> structured dict.

    hot/pool are 5-column tuples (src_f, dst_f, proto, sport, dport) of
    device arrays — hot is the established set (warmed before timing),
    pool supplies fresh flows (one lane per distinct flow); each timed
    step replaces the first n_new hot lanes with the next rolling pool
    window, so every iteration pays n_new genuine misses.  pool=None
    times a pure fast-path (never-miss) regime — the slow-path phases
    then measure only the lax.cond dispatch floor.

    The state is treated functionally: the caller's `state` is never
    mutated (warmup operates on a local copy of the carried pytree).
    """
    B = int(hot[0].shape[0])
    if pool is not None:
        pool_len = int(pool[0].shape[0])
        if n_new is None:
            n_new = max(1, B // 8)
        if n_new > B or n_new >= pool_len:
            raise ValueError(
                f"n_new={n_new} must fit the batch ({B}) and pool "
                f"({pool_len})"
            )
    else:
        pool_len = 0
        n_new = 0

    # Warm the hot set (full-phase steps) so timed hot lanes are cache
    # hits: two passes — classify + commit, then a hit pass to settle the
    # partner-refresh stamps.
    full = meta._replace(phases=pl.PH_ALL)
    st = state
    for w in range(2):
        st, _ = pl.pipeline_step(
            st, drs, dsvc, *hot, jnp.int32(now0 - 2 + w), jnp.int32(gen),
            meta=full,
        )

    def timed(mask: int) -> float:
        m = meta._replace(phases=mask)

        def body(i, carry):
            # acc leads the carry: device_loop_time fetches the FIRST leaf
            # to detect completion (utils/timing.py), so it must change
            # every iteration.
            acc, cst, drs_, dsvc_, hcols, pcols = carry
            if n_new:
                off = (acc[1] * n_new) % (pool_len - n_new)

                def mix(hcol, pcol):
                    fresh = jax.lax.dynamic_slice(pcol, (off,), (n_new,))
                    return jnp.concatenate([hcol[: B - n_new], fresh])

                cols = tuple(mix(h, p) for h, p in zip(hcols, pcols))
            else:
                cols = hcols
            cst, o = pl._pipeline_step(
                cst, drs_, dsvc_, *cols, now0 + i, gen, meta=m,
            )
            acc = acc.at[0].add(o["code"].sum(dtype=jnp.int32) + o["n_miss"])
            acc = acc.at[1].add(1)
            return (acc, cst, drs_, dsvc_, hcols, pcols)

        pcols = pool if pool is not None else hot  # unused when n_new == 0
        carry = (jnp.zeros(8, jnp.int32), st, drs, dsvc, hot, pcols)
        return device_loop_time(
            body, carry, k_small=k_small, k_big=k_big, repeats=repeats
        )

    cumulative: dict[str, float] = {}
    phases: dict[str, float] = {}
    prev = 0.0
    for name, mask in chain:
        t = timed(mask)
        cumulative[name] = t
        # Raw telescoped difference: may go slightly negative under run-to-
        # run jitter; kept UNCLAMPED so the phase sum equals the chain-end
        # time exactly (the honesty property bench_profile gates on).
        phases[name] = t - prev
        prev = t
    total = cumulative[chain[-1][0]]
    return {
        "batch": B,
        "fresh_per_step": n_new,
        "phases_s": phases,
        "cumulative_s": cumulative,
        "total_s": total,
        "pps": B / total,
        "phase_fractions": {k: v / total for k, v in phases.items()},
    }


def profile_churn_async(
    meta: pl.PipelineMeta,
    state: pl.PipelineState,
    drs,
    dsvc,
    hot: tuple,
    pool: tuple,
    *,
    n_new: Optional[int] = None,
    now0: int = 1000,
    gen: int = 0,
    k_small: int = 2,
    k_big: int = 8,
    repeats: int = 2,
    chain: tuple = ASYNC_PHASE_CHAIN,
) -> dict:
    """Per-phase breakdown of the ASYNC churn regime (datapath/slowpath).

    Models the engine's steady cadence — every step is one decoupled FAST
    dispatch over the mixed batch (phases=0: hot lanes hit, the n_new
    fresh lanes are admitted unclassified) plus one COALESCED drain
    dispatch over exactly that fresh window (miss_chunk == n_new, a
    single slow-path round).  chain[0] times the fast dispatch alone; the
    drain entries then add one PH_ bit at a time to the drain dispatch,
    so `drain_miss_detect` carries the drain call's fixed costs (its own
    lookup pass + dispatch) and the rest attribute like the sync chain.
    Telescoped differencing: phase sums equal the chain-end (full async
    step) time by construction.
    """
    B = int(hot[0].shape[0])
    if pool is None:
        raise ValueError("async profiling needs a fresh-flow pool "
                         "(the regime under study is miss handling)")
    pool_len = int(pool[0].shape[0])
    if n_new is None:
        n_new = max(1, B // 8)
    if n_new > B or n_new >= pool_len:
        raise ValueError(
            f"n_new={n_new} must fit the batch ({B}) and pool ({pool_len})"
        )

    full = meta._replace(phases=pl.PH_ALL)
    meta_fast = meta._replace(phases=0)
    st = state
    for w in range(2):
        st, _ = pl.pipeline_step(
            st, drs, dsvc, *hot, jnp.int32(now0 - 2 + w), jnp.int32(gen),
            meta=full,
        )

    def timed(mask: int, with_drain: bool) -> float:
        m_drain = meta._replace(phases=mask, miss_chunk=n_new)

        def body(i, carry):
            acc, cst, drs_, dsvc_, hcols, pcols = carry
            off = (acc[1] * n_new) % (pool_len - n_new)
            fresh = tuple(
                jax.lax.dynamic_slice(pc, (off,), (n_new,)) for pc in pcols
            )
            cols = tuple(
                jnp.concatenate([h[: B - n_new], f])
                for h, f in zip(hcols, fresh)
            )
            cst, o = pl._pipeline_step(
                cst, drs_, dsvc_, *cols, now0 + i, gen, meta=meta_fast,
            )
            acc = acc.at[0].add(o["code"].sum(dtype=jnp.int32) + o["n_miss"])
            if with_drain:
                cst, od = pl._pipeline_step(
                    cst, drs_, dsvc_, *fresh, now0 + i, gen, meta=m_drain,
                )
                acc = acc.at[0].add(
                    od["code"].sum(dtype=jnp.int32) + od["n_miss"]
                )
            acc = acc.at[1].add(1)
            return (acc, cst, drs_, dsvc_, hcols, pcols)

        carry = (jnp.zeros(8, jnp.int32), st, drs, dsvc, hot, pool)
        return device_loop_time(
            body, carry, k_small=k_small, k_big=k_big, repeats=repeats
        )

    cumulative: dict[str, float] = {}
    phases: dict[str, float] = {}
    prev = 0.0
    for j, (name, mask) in enumerate(chain):
        t = timed(mask, with_drain=j > 0)
        cumulative[name] = t
        phases[name] = t - prev  # unclamped (honesty property; see sync)
        prev = t
    total = cumulative[chain[-1][0]]
    return {
        "mode": "async",
        "batch": B,
        "fresh_per_step": n_new,
        "drain_batch": n_new,
        "phases_s": phases,
        "cumulative_s": cumulative,
        "total_s": total,
        "pps": B / total,
        "phase_fractions": {k: v / total for k, v in phases.items()},
    }


def profile_churn_overlap(
    meta: pl.PipelineMeta,
    state: pl.PipelineState,
    drs,
    dsvc,
    hot: tuple,
    pool: tuple,
    *,
    n_new: Optional[int] = None,
    now0: int = 1000,
    gen: int = 0,
    k_small: int = 2,
    k_big: int = 8,
    repeats: int = 2,
    chain: tuple = OVERLAP_PHASE_CHAIN,
) -> dict:
    """Per-phase breakdown of the OVERLAPPED churn regime (round 6).

    Models the double-buffered engine cadence: iteration i dispatches the
    decoupled FAST step over the mixed batch (phases=0, window i's fresh
    lanes admitted unclassified) and then the COALESCED drain of window
    i-1 — the one-step commit deferral of the two-slot pending-commit
    staging, under which drain i-1's scatters carry no data dependency on
    fast step i's outputs and XLA can pipeline the dispatches.  The drain
    runs at meta.drain_reclaim=True (fused eviction+aging accounting).
    The chain telescopes exactly like the async chain, so diffing the two
    breakdowns attributes the overlap win phase by phase.

    Semantics note: window i's verdicts land one iteration late (the
    lost-update guard makes them visible to iteration i+1's lookups via
    the carried state), which is exactly the engine's staged-commit
    observable behavior — the profiled program IS the production cadence.
    """
    B = int(hot[0].shape[0])
    if pool is None:
        raise ValueError("overlap profiling needs a fresh-flow pool "
                         "(the regime under study is miss handling)")
    pool_len = int(pool[0].shape[0])
    if n_new is None:
        n_new = max(1, B // 8)
    if n_new > B or n_new >= pool_len:
        raise ValueError(
            f"n_new={n_new} must fit the batch ({B}) and pool ({pool_len})"
        )

    full = meta._replace(phases=pl.PH_ALL)
    meta_fast = meta._replace(phases=0)
    st = state
    for w in range(2):
        st, _ = pl.pipeline_step(
            st, drs, dsvc, *hot, jnp.int32(now0 - 2 + w), jnp.int32(gen),
            meta=full,
        )

    def timed(mask: int, with_drain: bool) -> float:
        m_drain = meta._replace(phases=mask, miss_chunk=n_new,
                                drain_reclaim=True)

        def body(i, carry):
            acc, cst, drs_, dsvc_, hcols, pcols = carry
            off = (acc[1] * n_new) % (pool_len - n_new)
            # Window i-1 (the deferred commit): acc[1] counts completed
            # iterations, so the "previous" offset trails by one window —
            # iteration 0 re-drains the warmed hot prefix (same cost
            # shape, no semantic weight in a timing loop).
            off_prev = (jnp.maximum(acc[1] - 1, 0) * n_new) % (
                pool_len - n_new)
            fresh = tuple(
                jax.lax.dynamic_slice(pc, (off,), (n_new,)) for pc in pcols
            )
            prev = tuple(
                jax.lax.dynamic_slice(pc, (off_prev,), (n_new,))
                for pc in pcols
            )
            cols = tuple(
                jnp.concatenate([h[: B - n_new], f])
                for h, f in zip(hcols, fresh)
            )
            cst, o = pl._pipeline_step(
                cst, drs_, dsvc_, *cols, now0 + i, gen, meta=meta_fast,
            )
            acc = acc.at[0].add(o["code"].sum(dtype=jnp.int32) + o["n_miss"])
            if with_drain:
                cst, od = pl._pipeline_step(
                    cst, drs_, dsvc_, *prev, now0 + i, gen, meta=m_drain,
                )
                acc = acc.at[0].add(
                    od["code"].sum(dtype=jnp.int32) + od["n_miss"]
                )
            acc = acc.at[1].add(1)
            return (acc, cst, drs_, dsvc_, hcols, pcols)

        carry = (jnp.zeros(8, jnp.int32), st, drs, dsvc, hot, pool)
        return device_loop_time(
            body, carry, k_small=k_small, k_big=k_big, repeats=repeats
        )

    cumulative: dict[str, float] = {}
    phases: dict[str, float] = {}
    prev = 0.0
    for j, (name, mask) in enumerate(chain):
        t = timed(mask, with_drain=j > 0)
        cumulative[name] = t
        phases[name] = t - prev  # unclamped (honesty property; see sync)
        prev = t
    total = cumulative[chain[-1][0]]
    return {
        "mode": "overlap",
        "batch": B,
        "fresh_per_step": n_new,
        "drain_batch": n_new,
        "phases_s": phases,
        "cumulative_s": cumulative,
        "total_s": total,
        "pps": B / total,
        "phase_fractions": {k: v / total for k, v in phases.items()},
    }


def profile_churn_maintenance(
    meta: pl.PipelineMeta,
    state: pl.PipelineState,
    drs,
    dsvc,
    hot: tuple,
    pool: tuple,
    *,
    n_new: Optional[int] = None,
    now0: int = 1000,
    gen: int = 0,
    k_small: int = 2,
    k_big: int = 8,
    repeats: int = 2,
    chain: tuple = MAINT_PHASE_CHAIN,
) -> dict:
    """Per-phase breakdown of the MAINTENANCE cadence (the unified
    background plane, datapath/maintenance.py): the async churn cadence
    with the scheduler's fused maintenance pass (pl.maintain_scan — one
    full-table aging + stale-generation revalidation, the cache-maintain
    task) riding every timed iteration, chain entry 0 included.

    Attribution: the rider is constant across chain entries, so the
    telescoped differences still isolate the pure drain phases (the
    rider cancels), while `maintenance_s` — maint_fast_path minus a
    separately-timed rider-FREE fast step — is the background plane's
    own attributed per-step cost.  Diffing this breakdown against the
    async chain's shows the consolidation's overhead phase by phase;
    sums still equal the chain-end time by construction (the honesty
    property bench_profile.py gates at ±15%)."""
    B = int(hot[0].shape[0])
    if pool is None:
        raise ValueError("maintenance profiling needs a fresh-flow pool "
                         "(the regime under study is steady churn)")
    pool_len = int(pool[0].shape[0])
    if n_new is None:
        n_new = max(1, B // 8)
    if n_new > B or n_new >= pool_len:
        raise ValueError(
            f"n_new={n_new} must fit the batch ({B}) and pool ({pool_len})"
        )

    full = meta._replace(phases=pl.PH_ALL)
    meta_fast = meta._replace(phases=0)
    st = state
    for w in range(2):
        st, _ = pl.pipeline_step(
            st, drs, dsvc, *hot, jnp.int32(now0 - 2 + w), jnp.int32(gen),
            meta=full,
        )

    def timed(mask: int, with_drain: bool, with_maint: bool) -> float:
        m_drain = meta._replace(phases=mask, miss_chunk=n_new)

        def body(i, carry):
            acc, cst, drs_, dsvc_, hcols, pcols = carry
            off = (acc[1] * n_new) % (pool_len - n_new)
            fresh = tuple(
                jax.lax.dynamic_slice(pc, (off,), (n_new,)) for pc in pcols
            )
            cols = tuple(
                jnp.concatenate([h[: B - n_new], f])
                for h, f in zip(hcols, fresh)
            )
            cst, o = pl._pipeline_step(
                cst, drs_, dsvc_, *cols, now0 + i, gen, meta=meta_fast,
            )
            acc = acc.at[0].add(o["code"].sum(dtype=jnp.int32) + o["n_miss"])
            if with_drain:
                cst, od = pl._pipeline_step(
                    cst, drs_, dsvc_, *fresh, now0 + i, gen, meta=m_drain,
                )
                acc = acc.at[0].add(
                    od["code"].sum(dtype=jnp.int32) + od["n_miss"]
                )
            if with_maint:
                # The maintenance rider: ONE fused full-table pass per
                # step (pl.maintain_scan's traced body).  gen is
                # unchanged and `now` advances 1/step against hour-scale
                # timeouts, so the pass costs real work but reclaims
                # nothing — cost without semantic disturbance.
                cst, n_aged, n_stale = pl._maintain_scan(
                    cst, jnp.int32(now0 + i), jnp.int32(gen),
                    timeouts=meta.timeouts,
                )
                acc = acc.at[0].add(n_aged + n_stale)
            acc = acc.at[1].add(1)
            return (acc, cst, drs_, dsvc_, hcols, pcols)

        carry = (jnp.zeros(8, jnp.int32), st, drs, dsvc, hot, pool)
        return device_loop_time(
            body, carry, k_small=k_small, k_big=k_big, repeats=repeats
        )

    cumulative: dict[str, float] = {}
    phases: dict[str, float] = {}
    prev = 0.0
    for j, (name, mask) in enumerate(chain):
        t = timed(mask, with_drain=j > 0, with_maint=True)
        cumulative[name] = t
        phases[name] = t - prev  # unclamped (honesty property; see sync)
        prev = t
    # The background plane's own attributed cost: the rider-free fast
    # step diffed against the chain's rider-bearing entry 0.
    t_fast_bare = timed(0, with_drain=False, with_maint=False)
    maintenance_s = cumulative[chain[0][0]] - t_fast_bare
    total = cumulative[chain[-1][0]]
    return {
        "mode": "maintenance",
        "batch": B,
        "fresh_per_step": n_new,
        "drain_batch": n_new,
        "maintenance_s": maintenance_s,
        "maintenance_fraction": maintenance_s / total,
        "phases_s": phases,
        "cumulative_s": cumulative,
        "total_s": total,
        "pps": B / total,
        "phase_fractions": {k: v / total for k, v in phases.items()},
    }


def profile_churn_prune(
    meta: pl.PipelineMeta,
    state: pl.PipelineState,
    drs,
    dsvc,
    hot: tuple,
    pool: tuple,
    *,
    n_new: Optional[int] = None,
    now0: int = 1000,
    gen: int = 0,
    k_small: int = 2,
    k_big: int = 8,
    repeats: int = 2,
    chain: tuple = PRUNE_PHASE_CHAIN,
) -> dict:
    """Per-phase breakdown of the PRUNED churn regime (round 7): the
    async drain cadence (profile_churn_async's exact body) over a
    prune_budget > 0 meta, attributed on PRUNE_PHASE_CHAIN so the
    classify cost splits at the two-level kernel's seam —
    `prune_summary_gather` (aggregate rows + AND + short-circuit) vs
    `prune_candidate_gather` (K-superblock gather + first-match scan +
    fallback redispatches).  Same telescoped-sum honesty property; the
    ±15% gate applies via bench_profile.py --mode prune."""
    if meta.match.prune_budget <= 0:
        raise ValueError(
            "profile_churn_prune needs a prune_budget > 0 meta (the "
            "two-level kernel is compiled out at 0)")
    out = profile_churn_async(
        meta, state, drs, dsvc, hot, pool, n_new=n_new, now0=now0, gen=gen,
        k_small=k_small, k_big=k_big, repeats=repeats, chain=chain,
    )
    out["mode"] = "prune"
    out["prune_budget"] = meta.match.prune_budget
    return out


def profile_churn_fused(
    meta: pl.PipelineMeta,
    state: pl.PipelineState,
    drs,
    dsvc,
    hot: tuple,
    pool: tuple,
    *,
    n_new: Optional[int] = None,
    now0: int = 1000,
    gen: int = 0,
    k_small: int = 2,
    k_big: int = 8,
    repeats: int = 2,
    chain: tuple = FUSED_PHASE_CHAIN,
) -> dict:
    """Per-phase breakdown of the ONE-KERNEL churn regime (round 8): the
    async drain cadence (profile_churn_async's exact body) over a
    meta.onepass=True meta, attributed on FUSED_PHASE_CHAIN.  The
    `fused_onepass` entry is the whole in-VMEM pass (probe decode +
    candidate DMA + first-match + resolve + commit-row packing) — one
    number by design, since the fusion removed the interior stage
    boundaries the staged chains telescope.  Same telescoped-sum honesty
    property; the ±15% gate applies via bench_profile.py --mode fused."""
    if not meta.onepass:
        raise ValueError(
            "profile_churn_fused needs a one-pass meta (fused=True with "
            "prune_budget > 0)")
    out = profile_churn_async(
        meta, state, drs, dsvc, hot, pool, n_new=n_new, now0=now0, gen=gen,
        k_small=k_small, k_big=k_big, repeats=repeats, chain=chain,
    )
    out["mode"] = "fused"
    out["prune_budget"] = meta.match.prune_budget
    return out

"""MeshDatapath: the full stateful datapath promoted onto the device mesh.

PR 8 left exactly one sharded component: the stateless classifier
(parallel/mesh.py).  This module is the multichip serving engine — a
`TpuflowDatapath` whose EVERY plane runs against the 2-D (data × rule)
mesh, so a pod slice serves as one fleet of switches (PAPER.md L0: the
datapath OVS implements in C, scaled the way the reference scales by
adding nodes):

  stateful fast path   conntrack/affinity tables carry the leading (D,)
                       axis `parallel/mesh.py` always anticipated; each
                       data shard owns a PRIVATE slots slice.  A
                       deterministic, direction-symmetric 5-tuple hash
                       (`mesh.shard_of_tuples`) routes every packet to
                       its home shard on the traffic path, so a flow's
                       entries live in exactly one shard's table and
                       direct-mapped-cache semantics stay sound per
                       shard.  Hash-skew overflow lanes "spill" to other
                       shards with `no_commit` set (never caching
                       foreign) and then take a bounded HOME-ROUTED
                       retry dispatch (`_spill_retry`), so skew never
                       strands an established flow on provisional
                       verdicts.
  sharded slow path    one bounded miss queue PER data replica
                       (`MeshSlowPath`); a drain pops one block per
                       replica, classifies all of them in ONE sharded
                       dispatch (each replica's chunk in its own batch
                       slice), and publishes via a MESH-WIDE epoch swap:
                       a single epoch counter plus the state pytree
                       published by the one dispatch means every replica
                       flips generation atomically.  Re-missed flows
                       re-enqueue idempotently (the PR 6 lost-update
                       guard, now spanning shards: the deterministic
                       endpoint hash makes the re-classification commit
                       the identical entry in the identical home shard).
  replica-gated commit the canary classifies its probe set on EVERY data
                       replica (probes tiled over the data axis inside
                       shard_map, so each replica's own devices walk
                       their own table copies) and datapath/commit.py
                       diffs each replica against the scalar Oracle —
                       ONE replica's mismatch vetoes the bundle and the
                       rollback restores the (D,)-sharded snapshot, i.e.
                       ALL replicas, keeping the PR 4/5 self-healing
                       ladder provable under sharding.
  striped audit        the PR 5 audit cursor runs over the GLOBAL slot
                       space D*S, striped g -> (replica g % D, local
                       slot g // D), so every scheduler-budgeted window
                       advances coverage on all replicas simultaneously;
                       the tensor scrub folds the sharded tensors
                       logically (one digest covers every shard).
  rule-axis capacity   `_place_rules` pads + shards the incidence words
                       over ``rule`` (ops/match.to_device word_multiple)
                       for the whole pipeline — fast path, drains,
                       canary and audit fresh-walks all combine hits via
                       `lax.pmin` over the rule axis, so capacity scales
                       past 100k rules exactly as the HBM math in
                       parallel/mesh.py promises.

Everything else — commit/audit/maintenance plane state machines, the
membership delta bookkeeping, persistence, metrics counting — is
INHERITED from TpuflowDatapath: the planes were built plane-owner-
agnostic (PR 7's one-scheduler refactor was precisely for this port).

Round-8 additions (the PR 9 follow-ups + the elastic plane):
  * the engine now serves the FULL per-packet walk — SpoofGuard -> policy/
    service pipeline -> L2/L3 forward -> Output — through one sharded
    dispatch (`_mesh_step_full_fn`): forwarding is stateless per-packet
    and shards trivially over data with replicated topology tables, so
    `install_topology` works exactly like single-chip;
  * incremental group deltas take the O(delta) slot path on the mesh:
    the per-slot rule masks upload sharded on the same word axis as the
    incidence they patch (`_place_delta`), so pod churn never forces a
    recompile here either — overflow and named-port folds still recompile
    (canary-gated), as on single-chip;
  * the data axis RESIZES under live traffic (parallel/reshard.py):
    `reshard_begin(D')` builds the target mesh and serves dual-topology
    (in-flight batches resolve against the old affinity generation while
    a budgeted maintenance task migrates flow-cache rows to their target
    ring homes); a replica-resolved canary + a migrated-row audit certify
    the target before `shard_of_tuples` flips generation in one
    mesh-wide epoch swap, and a veto aborts back to the old mesh.
    TENANT worlds ride every resize (grow, shrink, failover evacuation):
    each world migrates under its own `_world_ctx` with per-world dirty
    tracking and a per-world certified cutover — one world's canary veto
    latches only that world on its old topology (`_TENANT_WORLD_FIELDS`
    carries `_mesh`/`_n_data`/`_topo_gen`, so a latched world SERVES on
    its own mesh; `tenant_reshard_resync` re-homes it later), while the
    fleet and every certified sibling flip.

Known mesh limits (documented, test-pinned):
  * v4-only (like the async slow path); dual_stack raises ConfigError.
  * overlap_commits/autotune_drain are single-chip knobs (the mesh drain
    is already one fused sharded dispatch per replica set).
  * DNAT'd service reply legs can land off-shard and re-classify — the
    ECMP-asymmetry analog; see the README multichip failure-model row.
"""

from __future__ import annotations

import time
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compiler.topology import FWD_TUNNEL
from ..config import ConfigError
from ..datapath.interface import StepResult
from ..datapath.maintenance import MaintenanceTask
from ..datapath.slowpath import ADMIT_DROP, MissQueue, SlowPathEngine
from ..datapath.tpuflow import TpuflowDatapath, _rids
from ..observability.telemetry import classify_regime
from ..observability.tracing import (SP_ACCOUNT, SP_ATTRIBUTE, SP_DISPATCH,
                                     SP_DONE, SP_FETCH, SP_STAGE, SP_UPLOAD,
                                     SP_WAIT, SS_RETRY, SS_ROUTE,
                                     construct_span)
from ..models import forwarding as fw
from ..models import pipeline as pl
from ..ops import hashing
from ..ops import match as m
from ..ops.match import placed_meta, to_host
from ..packet import PacketBatch
from ..utils import ip as iputil
from .mesh import (
    DATA,
    RULE,
    _drs_specs,
    _fwd_specs,
    _pmin_rule,
    _shard_map,
    _state_specs,
    _svc_specs,
    make_mesh,
    shard_of_tuples,
    shard_state,
)
from .failover import FailoverPlane
from .reshard import ReshardPlane, resync_world


# --------------------------------------------------------------------------
# Cached compiled kernels.  Keyed by (Mesh, PipelineMeta/StaticMeta) — both
# hashable — so every MeshDatapath on the same mesh with the same shapes
# shares ONE jitted program per variant (the jit-identity discipline the
# single-chip engine gets from module-level pipeline_step): installs that
# keep rule shapes re-use the compiled step, and the drain has one program
# per chunk rung, never a recompile storm.  The caches are BOUNDED: rule
# shapes change across bundle churn (each distinct meta.match retains its
# compiled executables), so an unbounded cache would grow host+device
# memory for the agent's whole lifetime; eviction just re-traces on the
# next use of a long-unseen shape.
# --------------------------------------------------------------------------

@lru_cache(maxsize=32)
def _mesh_step_fn(mesh, meta: pl.PipelineMeta):
    """The sharded stateful step: fast path, drains and sync slow path
    are all this one builder at different metas (phases / miss_chunk /
    drain_reclaim), exactly like the single-chip pipeline_step."""
    lane = P(DATA)

    # The XLA module is named after this function (`jit_mesh_step`): a
    # device trace tells the drain's program from the full walk's.
    def mesh_step(state, drs, dsvc, src_f, dst_f, proto, sport, dport, now,
                  gen, valid, no_commit, flags, lens):
        local = jax.tree.map(lambda x: x[0], state)
        local, out = pl._pipeline_step(
            local, drs, dsvc, src_f, dst_f, proto, sport, dport, now, gen,
            meta=meta, hit_combine=_pmin_rule, valid=valid,
            no_commit=no_commit, flags=flags,
            lens=lens if meta.count_flow_stats else None,
        )
        # scalar per shard -> (D,) vector of per-data-shard counts (the
        # prune keys exist iff the meta carries a prune budget)
        for k in ("n_miss", "n_evict", "n_reclaim", "round_lanes",
                  "n_prune_skips", "n_prune_fb", "prune_cand_hist",
                  "tel_probe_hit", "tel_probe_stale", "tel_probe_miss",
                  "tel_dma_hb", "tel_chance_bumps"):
            if k in out:
                out[k] = out[k][None]
        return jax.tree.map(lambda x: x[None], local), out

    return jax.jit(_shard_map(
        mesh_step,
        mesh=mesh,
        in_specs=(_state_specs(),
                  _drs_specs(agg=meta.match.prune_budget > 0),
                  _svc_specs(),
                  lane, lane, lane, lane, lane, P(), P(),
                  lane, lane, lane, lane),
        out_specs=(_state_specs(), P(DATA)),
    ))


@lru_cache(maxsize=32)
def _mesh_step_full_fn(mesh, meta: pl.PipelineMeta, has_arp: bool):
    """The sharded FULL per-packet walk (SpoofGuard/ARP -> policy/service
    pipeline -> L2/L3 forward -> Output, models/forwarding
    ._pipeline_step_full) — the mesh twin of the single-chip step().
    Forwarding is stateless per-packet, so it shards trivially over the
    data axis with replicated topology tables; the rule axis participates
    only in the classification pmin, exactly as in the policy-only step.
    `has_arp` keys the variant the way the single-chip step's conditional
    ARP lane does — pure-IP batches keep the no-ARP program."""
    lane = P(DATA)

    # XLA module `jit_mesh_step_full`: the served step AND its spill
    # retry (one function at two batch shapes), and nothing else.
    def mesh_step_full(state, drs, dsvc, dft, src_f, dst_f, proto, sport,
                       dport, in_port, now, gen, flags, arp_op, valid,
                       no_commit, lens, prune_excl):
        local = jax.tree.map(lambda x: x[0], state)
        local, out = fw._pipeline_step_full(
            local, drs, dsvc, dft, src_f, dst_f, proto, sport, dport,
            in_port, now, gen, flags,
            arp_op if has_arp else None,
            lens if meta.count_flow_stats else None,
            meta=meta, hit_combine=_pmin_rule, valid=valid,
            no_commit=no_commit, prune_exclude=prune_excl,
        )
        # The same egress record as the one-chip step (models/forwarding
        # .pack_egress), lanes on the blocks' second axis; the scalars one
        # column a replica.  What rides beside it is per-shard scalars
        # (the prune keys exist iff the meta carries a prune budget, the
        # telemetry keys iff it carries telemetry): -> (D,) vectors.
        rec, rest = fw.pack_egress(out)
        rec = rec._replace(scalars=rec.scalars[:, None])
        rest = {k: v[None] for k, v in rest.items()}
        return jax.tree.map(lambda x: x[None], local), rec, rest

    block = P(None, DATA)
    return jax.jit(_shard_map(
        mesh_step_full,
        mesh=mesh,
        in_specs=(_state_specs(),
                  _drs_specs(agg=meta.match.prune_budget > 0),
                  _svc_specs(), _fwd_specs(),
                  lane, lane, lane, lane, lane, lane, P(), P(),
                  lane, lane, lane, lane, lane, lane),
        out_specs=(_state_specs(), fw.EgressRecord(block, block, block),
                   P(DATA)),
    ))


@lru_cache(maxsize=8)
def _mesh_canary_fn(mesh, match_meta, fused):
    """Per-replica canary classify: probes tiled over the data axis, each
    replica's devices walking their own physical table copies; verdicts
    land (D * n,) and reshape to (D, n) for datapath/commit.py's
    replica-resolved diff.  One XLA compile per rule-table SHAPE (probes
    are padded to a fixed lane count by the commit plane, so repeated
    installs of same-shaped bundles share the program).  `fused` carries
    the instance's serving-consumer discipline — a fused engine's probes
    must certify the pallas consumer the step kernel uses, not the
    shadow XLA path (the fused consumer is shard-aware, so it composes
    with the pmin seam like the serving dispatch)."""
    def mesh_canary(drs, src_f, dst_f, proto, dport):
        return m.classify_batch(
            drs, src_f, dst_f, proto, dport, meta=match_meta,
            hit_combine=_pmin_rule, fused=fused,
        )["code"]

    return jax.jit(_shard_map(
        mesh_canary,
        mesh=mesh,
        in_specs=(_drs_specs(agg=match_meta.prune_budget > 0),
                  P(DATA), P(DATA), P(DATA), P(DATA)),
        out_specs=P(DATA),
    ))


# The vmapped maintenance/census helpers are keyed by at most the
# timeout tuple (reconfigured rarely, but each distinct value retains a
# compiled executable) — bounded like the step/canary caches above so a
# timeout-churning control plane can never grow device memory without
# limit (the analysis `bounded-cache` pass gates this).

@lru_cache(maxsize=8)
def _vmapped_maintain(timeouts):
    return jax.jit(jax.vmap(partial(pl._maintain_scan, timeouts=timeouts),
                            in_axes=(0, None, None)))


@lru_cache(maxsize=1)
def _vmapped_revalidate():
    return jax.jit(jax.vmap(pl._revalidate_scan, in_axes=(0, None)))


@lru_cache(maxsize=8)
def _vmapped_age(timeouts):
    return jax.jit(jax.vmap(partial(pl._age_scan, timeouts=timeouts),
                            in_axes=(0, None)))


@lru_cache(maxsize=1)
def _vmapped_cache_stats():
    return jax.jit(jax.vmap(pl._cache_stats))


def _shard_placement(shard: np.ndarray, n_data: int):
    """Batch lanes -> mesh slots under the shard-affinity hash.

    Every packet whose home shard has free capacity (B / D lanes per
    shard) lands in its home slice; hash-skew overflow packets SPILL into
    other shards' free slots and are flagged (the caller classifies them
    with no_commit, so a foreign shard never caches a stray flow).

    -> (perm, inv, spill): perm maps slot -> packet index, inv maps
    packet -> slot, spill flags slots holding an off-home packet."""
    B = shard.size
    C = B // n_data
    order = np.argsort(shard, kind="stable")
    counts = np.bincount(shard, minlength=n_data)
    bounds = np.concatenate([[0], np.cumsum(counts)])
    perm = np.empty(B, np.int64)
    spill = np.zeros(B, bool)
    leftovers, free = [], []
    for r in range(n_data):
        seg = order[bounds[r]:bounds[r + 1]]
        home = min(seg.size, C)
        perm[r * C:r * C + home] = seg[:home]
        if home < C:
            free.append(np.arange(r * C + home, (r + 1) * C))
        if seg.size > home:
            leftovers.append(seg[home:])
    if leftovers:
        lv = np.concatenate(leftovers)
        fs = np.concatenate(free)[:lv.size]  # conservation: |free| == |left|
        perm[fs] = lv
        spill[fs] = True
    inv = np.empty(B, np.int64)
    inv[perm] = np.arange(B)
    return perm, inv, spill


class _MeshQueueView:
    """Aggregate read surface over the per-replica miss queues, so the
    shared Datapath plumbing (dump_miss_queue, trace overlay, stats)
    keeps its single-queue contract.  `base` carries the cumulative
    counters of a PREVIOUS queue generation across a reshard cutover
    (the queue set is rebuilt at the new replica width; the meters must
    not reset or double-count the re-route pops)."""

    def __init__(self, queues: list[MissQueue], base: Optional[dict] = None):
        self.queues = queues
        self._base = base or {"admitted_total": 0, "overflows_total": 0,
                              "drained_total": 0}

    @property
    def depth(self) -> int:
        return sum(q.depth for q in self.queues)

    @property
    def capacity(self) -> int:
        return sum(q.capacity for q in self.queues)

    @property
    def admitted_total(self) -> int:
        return self._base["admitted_total"] + sum(
            q.admitted_total for q in self.queues)

    @property
    def overflows_total(self) -> int:
        return self._base["overflows_total"] + sum(
            q.overflows_total for q in self.queues)

    @property
    def drained_total(self) -> int:
        return self._base["drained_total"] + sum(
            q.drained_total for q in self.queues)

    def dump(self) -> list[dict]:
        return [row for q in self.queues for row in q.dump()]

    def contains(self, *tup) -> bool:
        return any(q.contains(*tup) for q in self.queues)


class MeshSlowPath(SlowPathEngine):
    """Per-replica miss queues + mesh-wide epoch swap.

    One engine, D bounded queues (miss_queue_slots is PER REPLICA).  The
    epoch plane stays a single counter: a drain classifies one popped
    block per replica in ONE sharded dispatch and `_publish` bumps that
    one counter — the mesh-wide swap.  Atomicity is by construction: the
    next lookup on ANY replica consumes the state pytree that dispatch
    published, never a mix."""

    def __init__(self, owner, n_data: int, *, capacity: int,
                 admission: str, drain_batch: int,
                 source_rate=None, source_burst=None):
        # capacity=1 seed: the base queue is immediately replaced by the
        # per-replica set below (its buffer would be dead weight).
        super().__init__(owner, capacity=1, admission=admission,
                         drain_batch=drain_batch, source_rate=source_rate,
                         source_burst=source_burst)
        self.n_data = int(n_data)
        self._q_capacity = int(capacity)  # per-replica; resize() reuses it
        self.queues = [MissQueue(capacity) for _ in range(self.n_data)]
        self.queue = _MeshQueueView(self.queues)

    # -- admission: route by home shard --------------------------------------

    def admit(self, cols: dict, miss_mask, now: int, shard=None):
        if shard is None:
            raise ValueError(
                "mesh admission requires the batch's shard assignment "
                "(shard_of_tuples ids)")
        self._seen_now = max(self._seen_now, int(now))
        if self._published_at == 0:
            self._published_at = int(now)
        mask = np.asarray(miss_mask, bool)
        # Per-source rate limiting is replica-independent (the bucket
        # keys on the source prefix, not the home shard): ONE batch-wide
        # pass ahead of the per-replica early-drop ramps, mirroring the
        # single-chip admission order.
        base = mask
        mask = self._source_limit(cols, mask, now)
        if self.deny_sink is not None and mask.sum() < base.sum():
            self.deny_sink(cols, base & ~mask, "source-limit", now)
        # admission="drop": the hash coin is replica-independent — one
        # batch-wide compute, thresholded per replica below (each
        # replica's OWN queue depth drives its early-drop ramp; capacity
        # is per-replica, so is the floor).
        coin = (self._drop_coin(cols, mask.shape[0])
                if self.admission == ADMIT_DROP and mask.any() else None)
        admitted = dropped = 0
        for r in range(self.n_data):
            mr = mask & (np.asarray(shard) == r)
            if not mr.any():
                continue
            mr0 = mr
            mr, _shed = self._early_drop(cols, mr, self.queues[r], coin=coin)
            if self.deny_sink is not None and _shed:
                self.deny_sink(cols, mr0 & ~mr, "early-drop", now)
            if not mr.any():
                continue
            a, d = self.queues[r].admit(cols, mr, self.epoch, int(now))
            admitted += a
            dropped += d
            if d:
                self._emit("queue-overflow", replica=int(r), dropped=int(d),
                           depth=int(self.queues[r].depth), at=int(now))
                if self.deny_sink is not None:
                    over = np.zeros(mr.shape, bool)
                    over[np.nonzero(mr)[0][a:]] = True
                    self.deny_sink(cols, over, "queue-overflow", now)
        return admitted, dropped

    # -- epoch plane: the mesh-wide swap -------------------------------------

    def _publish(self, now: int) -> None:
        self.epoch += 1
        self._published_at = int(now)
        self._seen_now = max(self._seen_now, int(now))
        self._emit("mesh-epoch-swap", epoch=int(self.epoch),
                   replicas=int(self.n_data), at=int(now))

    # -- drain: one block per replica, one sharded dispatch ------------------

    def begin_drain(self, now: int, n: Optional[int] = None) -> bool:
        if self._inflight is not None:
            raise RuntimeError("a drain batch is already in flight")
        # The popped chunk rides the in-flight record: an explicit n >
        # drain_batch must size the drain dispatch's per-replica lane
        # slices too, or one replica's rows would overflow into the
        # next replica's slice (and its foreign cache).
        chunk = int(n) if n is not None else self.drain_batch
        blocks = [q.pop(chunk) for q in self.queues]
        if all(b is None for b in blocks):
            return False
        self._inflight = (blocks, chunk, self.epoch,
                          int(self.owner.generation))
        self._seen_now = max(self._seen_now, int(now))
        self._emit("drain-begin",
                   n=sum(len(b["src_ip"]) for b in blocks if b is not None),
                   replicas=sum(b is not None for b in blocks),
                   epoch=int(self.epoch), gen=int(self.owner.generation))
        return True

    def finish_drain(self, now: int) -> dict:
        if self._inflight is None:
            raise RuntimeError("no drain batch in flight")
        blocks, chunk, _epoch0, gen0 = self._inflight
        self._inflight = None
        k = sum(len(b["src_ip"]) for b in blocks if b is not None)
        stale = int(self.owner.generation) != gen0
        if stale:
            self.stale_reclassified_total += k
        self.owner._drain_classify(blocks, int(now), chunk=chunk)
        self.drains_total += 1
        self.drain_hist.observe(k)
        self._emit("drain-finish", drained=k,
                   stale_reclassified=k if stale else 0, deferred=0)
        self._publish(now)
        return {"drained": k, "stale_reclassified": k if stale else 0}

    # -- elastic resharding: re-home the queue set ---------------------------

    def resize(self, n_data: int, home_fn, now: int) -> tuple[int, int]:
        """Rebuild the per-replica queue set at a new data-axis width and
        re-home every queued miss under the new topology map (the flip
        half of the reshard cutover, parallel/reshard.py) -> (requeued,
        dropped).  Rows move VERBATIM (epoch/enq_ts preserved — these are
        re-routes, not re-admissions, so admitted_total is untouched);
        the previous generation's cumulative meters carry over through
        the view's base.  A shrink can overflow the smaller aggregate
        capacity: overflow rows tail-drop with accounting, the ordinary
        bounded-queue contract (the flow re-admits on its next miss)."""
        base = {"admitted_total": self.queue.admitted_total,
                "overflows_total": self.queue.overflows_total,
                "drained_total": self.queue.drained_total}
        blocks = [q.pop(q.depth) for q in self.queues]
        self.n_data = int(n_data)
        self.queues = [MissQueue(self._q_capacity)
                       for _ in range(self.n_data)]
        self.queue = _MeshQueueView(self.queues, base)
        requeued = dropped = 0
        for b in blocks:
            if b is None:
                continue
            home = np.asarray(home_fn(b))
            for r in range(self.n_data):
                idx = np.nonzero(home == r)[0]
                if idx.size == 0:
                    continue
                t, d = self.queues[r].requeue(b, idx)
                requeued += t
                dropped += d
        if dropped:
            self._emit("queue-overflow", dropped=int(dropped),
                       depth=int(self.queue.depth), at=int(now))
        return requeued, dropped

    def evacuate_replica(self, dead: int, home_fn, now: int
                         ) -> tuple[int, int]:
        """Requeue a quarantined replica's queued misses VERBATIM onto
        the survivor queues (parallel/failover.py quarantine): same
        re-route-not-re-admission contract as resize(), but the queue
        set itself survives — only the dead replica's rows move, homed
        by the survivor-ring map.  Overflow rows tail-drop with
        accounting (the flow re-admits on its next miss) -> (requeued,
        dropped)."""
        q = self.queues[dead]
        block = q.pop(q.depth)
        if block is None:
            return 0, 0
        home = np.asarray(home_fn(block))
        requeued = dropped = 0
        for r in range(self.n_data):
            if r == dead:
                continue
            idx = np.nonzero(home == r)[0]
            if idx.size == 0:
                continue
            t, d = self.queues[r].requeue(block, idx)
            requeued += t
            dropped += d
        if dropped:
            self._emit("queue-overflow", dropped=int(dropped),
                       depth=int(self.queue.depth), at=int(now))
        return requeued, dropped

    def stats(self) -> dict:
        s = super().stats()
        s["replicas"] = self.n_data
        s["replica_depths"] = [q.depth for q in self.queues]
        return s


class MeshDatapath(TpuflowDatapath):
    """TpuflowDatapath served SPMD over a (data × rule) mesh.

    Same Datapath surface, same planes, same knobs — minus the
    single-chip-only ones (module docstring).  `miss_queue_slots` is
    per-replica; `flow_slots`/`aff_slots` are per-replica table sizes
    (global capacity = D × slots, which is what `cache_stats`/
    `audit_stats` report)."""

    # The mesh engine's per-world swap set: the single-chip members plus
    # the TOPOLOGY slice — a world serves on its OWN mesh at its own
    # width/generation (the per-world topology latch of
    # parallel/reshard.py), so _mesh/_n_data/_topo_gen/
    # _replica_audit_entries/_fo_mask must swap with it.  Pure literal:
    # the analysis tenant + reshard passes parse it dependency-free.
    _TENANT_WORLD_FIELDS = (
        "_ps", "_cps", "_drs", "_meta", "_meta_step", "_state", "_gen",
        "_has_named_ports", "_n_deltas", "_delta_host", "_name_gids",
        "_gid_ident", "_group_members", "_static_blocks", "_member_meta",
        "_tally_in", "_tally_out",
        "_default_allow", "_default_deny", "_evictions", "_reclaims",
        "_state_mutations", "_pipe_kw", "_persist_dirty",
        "_mesh", "_n_data", "_topo_gen", "_replica_audit_entries",
        "_fo_mask",
    )

    @construct_span
    def __init__(self, ps=None, services=None, *, mesh=None, n_data: int = 2,
                 n_rule: int = 1, devices=None, reshard_budget: int = 256,
                 failover: bool = False, failover_knobs=None, **kw):
        if kw.get("dual_stack"):
            raise ConfigError(
                "the mesh datapath is v4-only (like the async slow path); "
                "dual-stack nodes keep the single-chip engine")
        if kw.get("overlap_commits") or kw.get("autotune_drain"):
            raise ConfigError(
                "overlap_commits/autotune_drain are single-chip knobs: the "
                "mesh drain is already one fused sharded dispatch per "
                "replica set")
        if int(reshard_budget) <= 0:
            raise ConfigError(
                f"reshard_budget must be positive (rows per maintenance "
                f"tick), got {reshard_budget}")
        self._mesh = mesh if mesh is not None else make_mesh(
            n_data, n_rule, devices)
        self._n_data = int(self._mesh.shape[DATA])
        self._n_rule = int(self._mesh.shape[RULE])
        self._replica_audit_entries = [0] * self._n_data
        self._spill_lanes_total = 0
        self._spill_retried_total = 0
        # Elastic resharding plane (parallel/reshard.py): the affinity
        # topology generation (0 = the boot dense map; every resized
        # topology elects on the consistent ring), the in-flight plane,
        # and the cumulative meters that outlive individual planes.
        self._reshard_budget = int(reshard_budget)
        self._topo_gen = 0
        self._reshard = None
        self._reshard_canary = None  # (mesh, drs, match_meta, D) redirect
        self._reshard_cutovers = 0
        self._reshard_aborts = 0
        self._reshard_migrated_total = 0
        self._reshard_catchup_total = 0
        self._reshard_requeued_total = 0
        self._reshard_resident_rows = 0
        self._last_reshard_span = None
        self._reshard_tenant_rows_total = 0
        self._reshard_tenant_vetoes = 0
        # Chaos hook (arm_reshard_faults): (FaultPlan, site prefix) for
        # the per-tenant forced-canary-veto sites.
        self._reshard_faults = None
        # Per-world survivor-mask latch (parallel/failover.mask_shard's
        # world branch): (dead old-topology index, survivor width,
        # survivor generation).  A WORLD field — _world_ctx swaps it —
        # and always None on the default world (the fleet mask covers
        # it).
        self._fo_mask = None
        # Replica-loss failover plane (parallel/failover.py): None when
        # disabled — every traffic-path touch is gated on the field, so
        # the disabled engine's step HLO is bit-identical.
        self._failover = None
        super().__init__(ps, services, **kw)
        if failover:
            self._failover = FailoverPlane(self, **(failover_knobs or {}))
            self._maintenance.register(MaintenanceTask(
                "replica-health", self._maint_replica_health,
                budget=max(self._failover.probe_count * self._n_data, 1),
                priority=4, shed_when_degraded=False))

    # -- placement hooks (the whole tensor estate lands on the mesh) ---------

    def _init_pipeline_state(self, flow_slots: int, aff_slots: int):
        return shard_state(pl.init_state(flow_slots, aff_slots), self._mesh)

    def _pin_state(self, state: pl.PipelineState) -> pl.PipelineState:
        """Re-assert the (D,)-sharded placement after host-orchestrated
        transforms (vmap scans, audit writebacks) — a no-op transfer when
        the sharding already matches."""
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self._mesh, s)),
            state, _state_specs())

    def _place_rules_on(self, mesh, cps):
        """Host build + rung padding + sharded placement onto `mesh`.
        `_place_rules` calls it at the serving mesh; the reshard plane
        calls it at the TARGET mesh to re-home a tenant world's
        rung-packed rule window (parallel/reshard._ensure_world_rules),
        so rung-shared shapes — and their XLA executables — survive a
        resize."""
        with self._commit_span("tables"):
            host, meta = to_host(cps, word_multiple=self._n_rule,
                                 delta_slots=self._delta_slots,
                                 prune_budget=self._prune_budget)
            # Tenant worlds: entry-axis rung padding between host build
            # and sharded placement (datapath/tenancy._pad_tables — no-op
            # on the default world), composing with the word_multiple
            # padding above so tenant shapes stay rung-determined ON the
            # mesh too.
            host = self._pad_tables(host)
        drs = self._upload_tables(
            lambda t: jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                t, _drs_specs(agg=self._prune_budget > 0)),
            host)
        return drs, placed_meta(meta, drs)

    def _place_rules(self, cps):
        return self._place_rules_on(self._mesh, cps)

    def _place_services(self, dsvc: pl.DeviceServiceTables):
        repl = NamedSharding(self._mesh, P())
        self._shared_mesh = self._mesh  # where the shared tables live
        self._shared_remap = None
        return jax.tree.map(lambda x: jax.device_put(x, repl), dsvc)

    def _place_forwarding(self, dft):
        # Forwarding tables are the small, read-mostly side (one node's
        # pods + routes): replicated whole, like the service tables.
        repl = NamedSharding(self._mesh, P())
        self._shared_mesh = self._mesh
        self._shared_remap = None
        return jax.tree.map(lambda x: jax.device_put(x, repl), dft)

    def _shared_tables(self):
        """(dsvc, dft) placed on the SERVING mesh.  The live copies sit
        on the fleet mesh; a tenant world latched behind a resize (the
        per-world topology latch) serves on its own old mesh, so the
        replicated tables re-place there on first use — cached until
        the fleet tables or the serving mesh change.  The default path
        returns the live copies untouched (HLO pin)."""
        if self._mesh is getattr(self, "_shared_mesh", self._mesh):
            return self._dsvc, self._dft
        hit = self._shared_remap
        if (hit is not None and hit[0] is self._mesh
                and hit[1] is self._dsvc and hit[2] is self._dft):
            return hit[3], hit[4]
        repl = NamedSharding(self._mesh, P())
        dsvc = jax.tree.map(lambda x: jax.device_put(x, repl), self._dsvc)
        dft = jax.tree.map(lambda x: jax.device_put(x, repl), self._dft)
        self._shared_remap = (self._mesh, self._dsvc, self._dft, dsvc, dft)
        return dsvc, dft

    def _audit_dsvc(self):
        return self._shared_tables()[0]

    def _place_delta(self, dt):
        # The O(delta) slot path works unchanged on the mesh: the host
        # mirror's per-slot rule masks are built at the PADDED word width
        # (the match meta's w_in/w_out reflect to_device's word_multiple
        # padding), so each append re-places the whole small table with
        # the word axis sharded exactly like the incidence it patches —
        # pod churn never forces a recompile here either.
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self._mesh, s)),
            dt, _drs_specs().ip_delta)

    # -- tenancy hooks (datapath/tenancy.TenantedDatapath) -------------------

    def _tenant_init_world(self, spec, ps) -> None:
        super()._tenant_init_world(spec, ps)
        # A fresh world is fleet-aligned (its export carries the live
        # _mesh/_n_data/_topo_gen as-is) but must own its OWN audit-entry
        # list and mask latch — exporting the engine's list object would
        # alias every world's counters to the fleet's.
        self._replica_audit_entries = [0] * int(self._n_data)
        self._fo_mask = None

    def _make_slowpath(self, *, capacity, admission, drain_batch,
                       source_rate=None, source_burst=None,
                       **_single_chip_knobs):
        # autotune/overlap were rejected as ConfigError in __init__, so
        # the ignored kwargs here are always their inert defaults.
        return MeshSlowPath(self, self._n_data, capacity=capacity,
                            admission=admission, drain_batch=drain_batch,
                            source_rate=source_rate,
                            source_burst=source_burst)

    # -- the sharded step ----------------------------------------------------

    def _step(self, batch: PacketBatch, now: int, valid=None) -> StepResult:
        tr = self._steptrace
        # ---- stage: host columns, shard routing and the permutation --------
        tr.phase(SP_STAGE)
        D = self._n_data
        B = batch.size
        if B % D:
            raise ValueError(
                f"batch size {B} is not divisible by the data-axis size {D}")
        # Serving-batcher padding mask (canonical sizes are pow2 >= D, so
        # divisibility holds): padded lanes join the kernel's per-lane
        # validity in PERMUTED order and are excluded from the home-routed
        # spill retry below — a padding lane never caches anywhere.
        ext = None if valid is None else np.asarray(valid, bool)
        self._v6_lanes(batch)  # v4-only guard (dual_stack is always False)
        lens = np.maximum(batch.lens(), 0)
        flags = np.asarray(batch.flags()).astype(np.int32)
        in_ports = np.asarray(batch.in_ports()).astype(np.int32)
        has_arp = batch.arp_op is not None
        arp = (np.asarray(batch.arp_ops()).astype(np.int32) if has_arp
               else np.zeros(B, np.int32))
        tr.sub(SS_ROUTE)  # hash, failover mask, placement, permutation
        shard = shard_of_tuples(batch.src_ip, batch.dst_ip, batch.proto,
                                batch.src_port, batch.dst_port, D,
                                self._topo_gen, tenant=self._tenant_id())
        # Replica-loss failover (parallel/failover.py): lanes homed on a
        # quarantined replica re-home HOST-SIDE onto the survivor ring —
        # the step HLO is untouched (bit-identical with the plane off).
        fo = self._failover
        fo_masked = None
        if fo is not None:
            shard, fo_masked = fo.mask_shard(
                batch.src_ip, batch.dst_ip, batch.proto, batch.src_port,
                batch.dst_port, shard, tenant=self._tenant_id())
        perm, inv, spill = _shard_placement(shard, D)
        src = batch.src_ip[perm].astype(np.uint32)
        dst = batch.dst_ip[perm].astype(np.uint32)
        # The fused walk derives the mcast/teardown commit gating and the
        # SpoofGuard/ARP/IGMP validity masks itself (models/forwarding);
        # the engine contributes only the spill rule — an off-home lane
        # classifies but never caches in a foreign shard.
        lanes = (iputil.flip_u32(src), iputil.flip_u32(dst),
                 batch.proto[perm].astype(np.int32),
                 batch.src_port[perm].astype(np.int32),
                 batch.dst_port[perm].astype(np.int32), in_ports[perm],
                 flags[perm], arp[perm],
                 np.ones(B, bool) if ext is None else ext[perm], spill,
                 lens[perm].astype(np.int32), spill)
        tr.sub_end()
        stepf = _mesh_step_full_fn(self._mesh, self._meta_step, has_arp)
        dsvc, dft = self._shared_tables()

        # ---- upload: the columns go into the sharded call as numpy, so the
        # CALL uploads them (as `_spill_retry`'s does): they are counted
        # here, where they are handed over, and their time falls in
        # `dispatch` — only the two scalars transfer in this phase. -------
        tr.phase(SP_UPLOAD)
        for x in lanes:
            tr.uploaded(x)
        scalars = (self._upload_i32(now), self._upload_i32(self._gen))

        # ---- dispatch / wait / fetch (the tpuflow boundaries) --------------
        tr.phase(SP_DISPATCH)
        state, rec, rest = stepf(self._state, self._drs, dsvc, dft,
                                 *lanes[:6], *scalars, *lanes[6:])
        self._state = state
        self._state_mutations += 1
        fw.start_egress_copies(rec, rest)
        tr.phase(SP_WAIT)
        jax.block_until_ready((rec, rest))
        tr.phase(SP_FETCH)
        (words, narrow, counts), rest = fw.fetch_egress(rec, rest, tr.fetched)
        tr.phase(SP_ACCOUNT)
        if fo is not None:
            # Dispatch-liveness deadline: a stalled sharded dispatch is a
            # wedge symptom.  Fed from the dispatch + wait + fetch phases
            # just stamped (upload included: the call uploads) — no clock
            # pair of its own.
            fo.note_dispatch(tr.since(SP_DISPATCH), now)
        self._account_counts(counts)
        # Spilled lanes are EXCLUDED from this dispatch's prune evidence
        # (prune_exclude=spill above): their foreign-shard walk is not
        # the serving walk, and the home-routed retry below accounts
        # them instead — each lane feeds the PruneAutotuner band exactly
        # once, from the walk production actually serves (round 8; the
        # PR 10 dedupe kept the foreign evidence instead).
        self._prune_account(rest)
        # Telemetry counters ride (D,) per-replica beside the record.
        # Spilled lanes are excluded from this dispatch's counters too
        # (same prune_exclude=spill mask): their serving probe is the
        # home-routed retry's, which accounts them (each lane's probe is
        # metered exactly once, from the walk that serves it).
        tel_o = {k: v for k, v in rest.items() if k.startswith("tel_")}
        # Back to packet order, on the packed blocks: two gathers, not one
        # a field.
        words = np.take(words, inv, axis=1)
        narrow = np.take(narrow, inv, axis=1)
        spilled = perm[np.nonzero(
            spill if ext is None else spill & ext[perm])[0]]  # off-home
        if spilled.size:
            tr.sub(SS_RETRY)  # staging, the call, its wait and fetch, merge
            self._spill_retry(batch, words, narrow, spilled, shard, flags,
                              in_ports, arp, has_arp, lens, now)
            tr.sub_end()
        # The fields, once, after the merge: row views of the two blocks.
        o = fw.unpack_egress(words, narrow)
        # Recomputed from the MERGED per-lane mask: a retried lane's miss
        # image is its home-shard one, not the foreign always-miss.
        n_miss = tr.n_miss = int(o["miss"].sum())
        if fo_masked is not None:
            # The evacuation re-miss burst: dead-resident flows pay one
            # re-miss each on their survivor home (bounded, metered).
            fo.note_remiss(np.count_nonzero((o["miss"] != 0)[fo_masked]))
        # Dirty-row tracking for an in-flight resize (parallel/reshard):
        # every lane's home (replica, slot) may be refreshed/committed/
        # torn down by this step after its migration window — record it
        # so the cutover catch-up sweeps the touched set, not O(slots).
        if self._reshard is not None:
            self._note_reshard_touched(
                shard, batch.src_ip, batch.dst_ip, batch.proto,
                batch.src_port, batch.dst_port,
                committed=o.get("committed"), dnat_f=o.get("dnat_ip_f"),
                dnat_port=o.get("dnat_port"))
        pending = None
        if self._async:
            pending = o["miss"]
            # Route each admitted miss to its HOME replica's queue — a
            # spilled lane's drain then classifies and commits it on the
            # shard that owns it.  Tenant worlds: quota-clamped admission
            # + the tenant id column (datapath/tenancy — no-ops on the
            # default world).  The queue set is SHARED at the FLEET
            # width: a LATCHED world computes homes at its own width, and
            # MeshSlowPath.admit silently never admits ids >= n_data —
            # clamp onto the fleet's queues (the queue index is transport
            # only; the drain re-splits per tenant and re-lays rows out
            # on the world's own topology at classify time, so no
            # verdict ever sees this index).
            sp_n = self._slowpath.n_data
            admitted, _dropped = self._slowpath.admit(
                self._queue_cols(batch, batch.flags(), lens,
                                 tenant=self._tenant_id()),
                self._tenant_admit_mask(pending != 0), now,
                shard=shard if sp_n == D else shard % sp_n)
            self._tenant_note_admitted(admitted, _dropped)
        if self._telemetry is not None:
            # Engine/tenant scopes classify from the MERGED per-lane miss
            # image (a retried lane's miss is its home-shard one); each
            # replica additionally classifies from its own home lanes, so
            # a single cold shard reads cold even when the mesh-wide
            # regime is steady.
            self._telemetry_account({**tel_o, "n_miss": n_miss}, B)
            miss_rep = np.bincount(shard[o["miss"] != 0], minlength=D)
            cnt_rep = np.bincount(shard, minlength=D)
            for d in range(D):
                self._telemetry.note_regime(
                    f"replica{d}",
                    classify_regime(int(cnt_rep[d]), int(miss_rep[d])))
        in_ids = self._cps.ingress.rule_ids
        out_ids = self._cps.egress.rule_ids
        self._count_metrics(o, in_ids, out_ids, lens, pending=pending)
        if self._deny is not None:
            self._deny_verdicts(batch, o["code"], pending, now)
        # ---- attribute: rule ids and the StepResult ------------------------
        tr.phase(SP_ATTRIBUTE)
        unflip = iputil.unflip_u32_array
        res = StepResult(
            code=o["code"],
            est=o["est"],
            pending=pending,
            reply=o["reply"],
            reject_kind=o["reject_kind"],
            snat=o["snat"],
            dsr=o["dsr"],
            svc_idx=o["svc_idx"],
            dnat_ip=unflip(o["dnat_ip_f"]),
            dnat_port=o["dnat_port"],
            ingress_rule=_rids(self._cps.ingress, o["ingress_rule"]),
            egress_rule=_rids(self._cps.egress, o["egress_rule"]),
            committed=o["committed"],
            n_miss=n_miss,
            spoofed=o["spoofed"],
            punt=o["punt"],
            mcast_idx=o["mcast_idx"],
            l7_redirect=o["l7_redirect"],
            fwd_kind=o["fwd_kind"],
            out_port=o["out_port"],
            # peer_f is zeroed for non-deliverable lanes in the kernel; the
            # (kind==TUNNEL & deliverable) gate avoids un-flipping that 0.
            peer_ip=np.where(
                (o["fwd_kind"] == FWD_TUNNEL) & (o["out_port"] != -1),
                unflip(o["peer_f"]), 0,
            ).astype(np.uint32),
            dec_ttl=o["dec_ttl"],
            tc_act=o["tc_act"],
            tc_port=o["tc_port"],
        )
        tr.phase(SP_DONE)
        return res

    def _spill_retry(self, batch: PacketBatch, words: np.ndarray,
                     narrow: np.ndarray, spilled: np.ndarray,
                     shard: np.ndarray, flags: np.ndarray,
                     in_ports: np.ndarray, arp: np.ndarray, has_arp: bool,
                     lens: np.ndarray, now: int) -> None:
        """Second, bounded, HOME-ROUTED dispatch for hash-skew overflow.

        Spilled lanes' main-dispatch image is a foreign-shard walk: they
        can never see their home cache entry, so without this pass an
        established flow caught in skew would serve provisional verdicts
        forever (fatal under admission="hold": true-ALLOW traffic reads
        as DROP).  Here each replica gets ITS OWN spilled lanes — home
        placement by construction — padded to a power-of-two rung so the
        compile-variant count stays O(log(B/D)).  Per-shard overflow
        beyond one full home slice (B/D lanes; the all-flows-one-shard
        pathology) keeps the documented provisional-spill semantics
        rather than cascading dispatches.  Merges the retried lanes'
        records into the two egress blocks (packet order), in place."""
        D = self._n_data
        C = batch.size // D
        by_shard = [spilled[shard[spilled] == r] for r in range(D)]
        m = max(x.size for x in by_shard)
        rung = min(C, max(16, 1 << (m - 1).bit_length()))
        take = [x[:rung] for x in by_shard]
        Bm = D * rung
        idx = np.zeros(Bm, np.int64)
        valid = np.zeros(Bm, bool)
        for r, x in enumerate(take):
            idx[r * rung:r * rung + x.size] = x
            valid[r * rung:r * rung + x.size] = True
        src = batch.src_ip[idx].astype(np.uint32)
        dst = batch.dst_ip[idx].astype(np.uint32)
        proto = batch.proto[idx].astype(np.int32)
        rflags = flags[idx]
        stepf = _mesh_step_full_fn(self._mesh, self._meta_step, has_arp)
        dsvc, dft = self._shared_tables()
        # The retry's transfers are the step's too (it runs inside the
        # `account` phase): counted where the dispatch issues them (the
        # columns go in as numpy, so the call itself uploads them).
        tr = self._steptrace
        lanes = [tr.uploaded(x) for x in (
            iputil.flip_u32(src), iputil.flip_u32(dst), proto,
            batch.src_port[idx].astype(np.int32),
            batch.dst_port[idx].astype(np.int32), in_ports[idx],
            rflags, arp[idx], valid, np.zeros(idx.size, bool),
            lens[idx].astype(np.int32), ~valid)]
        state, rec, rest = stepf(
            self._state, self._drs, dsvc, dft, *lanes[:6],
            self._upload_i32(now), self._upload_i32(self._gen), *lanes[6:])
        self._state = state
        self._state_mutations += 1
        fw.start_egress_copies(rec, rest)
        (words2, narrow2, counts), rest = fw.fetch_egress(rec, rest,
                                                          tr.fetched)
        self._account_counts(counts)
        # The retry owns the retried lanes' prune evidence (the main
        # dispatch excluded them via prune_exclude=spill): each lane is
        # metered exactly once, from its HOME (serving) walk — counting
        # both walks would double a retried lane's evidence and skew the
        # PruneAutotuner band toward the foreign always-miss shape
        # (regression-pinned by the skew-batch case in
        # tests/test_match_fused.py).  Padding lanes are excluded via
        # prune_exclude=~valid above.
        self._prune_account(rest)
        if self._telemetry is not None:
            # The retry owns the retried lanes' PROBE counters too (the
            # main dispatch masked them out, same as the prune evidence);
            # padding lanes ride excluded via prune_exclude=~valid.
            self._telemetry.account(rest)
        sel = np.nonzero(valid)[0]
        pkts = idx[sel]
        words[:, pkts] = np.take(words2, sel, axis=1)
        narrow[:, pkts] = np.take(narrow2, sel, axis=1)
        tr.spill_lanes = int(spilled.size)
        tr.retry_lanes = int(sel.size)
        self._spill_lanes_total += tr.spill_lanes
        self._spill_retried_total += tr.retry_lanes

    def _account_counts(self, counts: np.ndarray) -> None:
        """Fold one sharded call's (4, D) scalar block, the step's or its
        spill retry's.  `n_miss` is not read: the step recomputes it from
        the MERGED per-lane mask."""
        per = dict(zip(fw.EGRESS_SCALARS, counts.sum(axis=1).tolist()))
        self._evictions += per["n_evict"]
        self._reclaims += per["n_reclaim"]
        self._steptrace.round_lanes += per["round_lanes"]

    # -- sharded slow-path callbacks -----------------------------------------

    def _drain_classify(self, blocks: list, now: int,
                        chunk: Optional[int] = None):
        """Classify one popped block PER REPLICA in a single sharded
        drain dispatch (each replica's chunk is its slice of the batch
        axis) and publish the new (D,)-sharded cache state — the commit
        half of the mesh-wide epoch swap.  Padding lanes ride masked out
        via `valid`; all lanes are home lanes (admission routed them), so
        there is no spill term here.  `chunk` is the pop size the engine
        pinned at begin_drain (an explicit begin_drain(n) may exceed
        drain_batch; each replica's lane slice must be that wide).

        Tenant rows (datapath/tenancy): blocks carrying tenant ids
        partition per tenant and each tenant's per-replica sub-blocks
        classify inside its world — zero cost without tenant worlds."""
        split = self._tenant_drain_split_blocks(blocks)
        if split is not None:
            return self._tenant_drain_dispatch_blocks(split, now, chunk)
        t0 = time.perf_counter()
        sp = self._slowpath
        chunk = int(chunk) if chunk is not None else sp.drain_batch
        D = self._n_data
        Bd = D * chunk
        valid = np.zeros(Bd, bool)

        def col(name, dtype=np.int32):
            out = np.zeros(Bd, dtype)
            for r, b in enumerate(blocks):
                if b is None:
                    continue
                k = len(b["src_ip"])
                out[r * chunk:r * chunk + k] = (
                    np.asarray(b[name])[:k].astype(dtype))
            return out

        for r, b in enumerate(blocks):
            if b is not None:
                valid[r * chunk:r * chunk + len(b["src_ip"])] = True
        src = col("src_ip", np.uint32)
        dst = col("dst_ip", np.uint32)
        proto = col("proto")
        sport = col("src_port")
        dport = col("dst_port")
        flags = col("flags")
        lens = np.maximum(col("lens"), 0)
        no_commit = pl.no_commit_mask(dst, proto, flags)
        drainf = _mesh_step_fn(self._mesh, self._drain_meta(chunk))
        dsvc, _dft = self._shared_tables()
        state, out = drainf(
            self._state, self._drs, dsvc,
            iputil.flip_u32(src), iputil.flip_u32(dst), proto, sport, dport,
            jnp.int32(now), jnp.int32(self._gen),
            valid, no_commit, flags, lens,
        )
        self._state = state
        self._state_mutations += 1
        o = {k: np.asarray(v) for k, v in out.items()}
        self._evictions += int(o["n_evict"].sum())
        self._reclaims += int(o["n_reclaim"].sum())
        self._prune_account(o)
        in_ids = self._cps.ingress.rule_ids
        out_ids = self._cps.egress.rule_ids
        sel = valid
        self._count_metrics(
            {k: o[k][sel] for k in ("code", "ingress_rule", "egress_rule")},
            in_ids, out_ids, lens[sel],
        )
        if self._telemetry is not None:
            # One sharded dispatch drains every replica at once: fold its
            # counters and its wall seconds into the engine's "drain"
            # regime (never deferred here — overlap staging is
            # single-chip).
            self._telemetry.account(o)
            self._telemetry.observe_scoped(
                "engine", "drain", time.perf_counter() - t0)
        # Dirty-row tracking for an in-flight resize: a drain COMMITS
        # rows (both conntrack directions) after their migration window.
        if self._reshard is not None:
            replica = (np.arange(Bd) // chunk).astype(np.int32)
            self._note_reshard_touched(
                replica[valid], src[valid], dst[valid], proto[valid],
                sport[valid], dport[valid],
                committed=o["committed"][valid],
                dnat_f=o["dnat_ip_f"][valid],
                dnat_port=o["dnat_port"][valid])
        return None  # never deferred: overlap staging is single-chip

    def _epoch_maintain(self, now: int) -> tuple[int, int]:
        st, n_aged, n_stale = _vmapped_maintain(self._meta.timeouts)(
            self._state, jnp.int32(now), jnp.int32(self._gen))
        self._state = self._pin_state(st)
        self._state_mutations += 1
        return int(np.asarray(n_aged).sum()), int(np.asarray(n_stale).sum())

    def _epoch_revalidate(self) -> int:
        st, n = _vmapped_revalidate()(self._state, jnp.int32(self._gen))
        self._state = self._pin_state(st)
        self._state_mutations += 1
        return int(np.asarray(n).sum())

    def _epoch_age_scan(self, now: int) -> int:
        st, n = _vmapped_age(self._meta.timeouts)(
            self._state, jnp.int32(now))
        self._state = self._pin_state(st)
        self._state_mutations += 1
        return int(np.asarray(n).sum())

    # -- commit plane hooks --------------------------------------------------

    def _canary_classify(self, batch: PacketBatch, now: int) -> np.ndarray:
        """REPLICA-RESOLVED fresh-walk verdicts: the probe set is tiled
        over the data axis and classified inside shard_map, so each data
        replica's own devices walk their own physical copies of the rule
        tables -> (D, n) codes.  datapath/commit.py diffs every row
        against the Oracle; any replica's mismatch vetoes the bundle for
        the whole mesh (the rollback restores the sharded snapshot — all
        replicas)."""
        del now  # probes are stateless fresh walks
        # A reshard plane certifying its TARGET topology redirects the
        # probe walk onto the target placement (parallel/reshard.py sets
        # _reshard_canary around the commit plane's _canary call): the
        # same replica-resolved diff and veto machinery then gates the
        # cutover the way it gates every bundle.
        tgt = self._reshard_canary
        if tgt is None:
            mesh, drs, mm, D = (self._mesh, self._drs, self._meta.match,
                                self._n_data)
        else:
            mesh, drs, mm, D = tgt
        n = batch.size
        fn = _mesh_canary_fn(mesh, mm, self._meta.fused)
        got = fn(drs,
                 np.tile(iputil.flip_u32(batch.src_ip), D),
                 np.tile(iputil.flip_u32(batch.dst_ip), D),
                 np.tile(batch.proto.astype(np.int32), D),
                 np.tile(batch.dst_port.astype(np.int32), D))
        return np.asarray(got).reshape(D, n)

    # -- audit plane hooks (striped cursor + per-replica state) --------------

    def _audit_rule_digests(self) -> dict:
        """Checksum digests over the HOST view of each sharded tensor
        group: the jitted XOR reduce cannot lower across device shards on
        every backend (CPU rejects cross-shard xor reductions), so the
        mesh scrub gathers and folds host-side.  The logical-bytes
        contract is unchanged — state corruption on any replica's private
        slice lands in the gathered view; per-device divergence of a
        REPLICATED tensor is (as on single-chip) the canary's to catch,
        which the replica-resolved canary does."""
        leaves = jax.tree_util.tree_leaves
        return {
            "drs": pl.tensor_digest(np.asarray(x) for x in leaves(self._drs)),
            "dsvc": pl.tensor_digest(
                np.asarray(x) for x in leaves(self._dsvc)),
            "dft": pl.tensor_digest(np.asarray(x) for x in leaves(self._dft)),
        }

    def _audit_state_digest(self) -> int:
        return pl.tensor_digest(
            np.asarray(x) for x in jax.tree_util.tree_leaves(self._state))

    def _audit_slots(self) -> int:
        return self._n_data * self._meta.flow_slots

    def _audit_window(self, cursor: int, k: int, now: int) -> list[dict]:
        """Striped window over the GLOBAL slot space: global slot g lives
        at (replica g % D, local slot g // D), so one budgeted window
        advances audit coverage on every replica simultaneously and
        `audit_cursor_coverage_ratio` keeps its meaning fleet-wide."""
        D, S = self._n_data, self._meta.flow_slots
        G = D * S
        cursor %= G
        rows: list[dict] = []
        for r in range(D):
            first = cursor + ((r - cursor) % D)
            if first >= cursor + k:
                continue
            count = (cursor + k - first + D - 1) // D
            local_start = first // D
            local = jax.tree.map(lambda x, r=r: x[r], self._state)
            keys_d, meta_d, ts_d = pl.audit_gather(
                local, jnp.int32(local_start % S), window=count)
            got = self._decode_audit_rows(
                keys_d, meta_d, ts_d, now,
                lambda i, r=r, ls=local_start: (((ls + i) % S) * D + r))
            self._replica_audit_entries[r] += len(got)
            rows.extend(got)
        rows.sort(key=lambda e: (e["slot"] - cursor) % G)
        return rows

    def _audit_fresh(self, rows: list, now: int) -> list[dict]:
        """Fresh-walk re-proof per HOME replica: each audited row is
        re-proved against its owning replica's local state slice (the
        affinity view that classified it), through the shared eager
        trace machinery."""
        by_replica: dict[int, list[int]] = {}
        for i, e in enumerate(rows):
            by_replica.setdefault(e["slot"] % self._n_data, []).append(i)
        out: list = [None] * len(rows)
        for r, idxs in sorted(by_replica.items()):
            local = jax.tree.map(lambda x, r=r: x[r], self._state)
            got = self._audit_fresh_state(local, [rows[i] for i in idxs], now)
            for i, rec in zip(idxs, got):
                out[i] = rec
        return out

    def _audit_evict(self, slots: list) -> None:
        D = self._n_data
        groups: dict[int, list[int]] = {}
        for g in slots:
            groups.setdefault(int(g) % D, []).append(int(g) // D)
        st = self._state
        for r, ls in sorted(groups.items()):
            n = max(1, len(ls))
            padded = np.full(1 << (n - 1).bit_length(), -1, np.int32)
            padded[:len(ls)] = np.asarray(ls, np.int32)
            local = jax.tree.map(lambda x, r=r: x[r], st)
            new_local, _n = pl.audit_evict(local, jnp.asarray(padded))
            st = jax.tree.map(lambda full, nl, r=r: full.at[r].set(nl),
                              st, new_local)
        self._state = self._pin_state(st)
        self._state_mutations += 1

    def _audit_corrupt(self, kind: str, now: Optional[int] = None) -> str:
        if kind == "tensor":
            return super()._audit_corrupt(kind, now)
        # Verdict-bit flip on ONE replica's private state slice — real
        # replica-local corruption only the striped audit cursor can see.
        D = self._n_data
        flow = self._state.flow
        keys_all = np.asarray(flow.keys)
        _, M1C, _, _ = pl._meta_cols(self._meta.key_words - 2)
        for r in range(D):
            keys = keys_all[r, :-1].astype(np.int64)
            if now is not None:
                meta_np = np.asarray(flow.meta[r])[:-1].astype(np.int64)
                ts_np = np.asarray(flow.ts[r])[:-1]
                live, _egen = self._live_mask(keys, meta_np, ts_np, now)
            else:
                kpg = keys[:, -1]
                gen_w = self._gen % pl.GEN_ETERNAL
                egen = (kpg >> 9) & pl.GEN_ETERNAL
                live = (kpg != 0) & ((egen == pl.GEN_ETERNAL)
                                     | (egen == gen_w))
            idx = np.nonzero(live)[0]
            if idx.size == 0:
                continue
            slot = int(idx[0])
            mta = self._state.flow.meta
            self._state = self._state._replace(flow=self._state.flow._replace(
                meta=mta.at[r, slot, M1C].set(mta[r, slot, M1C] ^ 1)))
            return (f"flipped cached verdict bit of replica {r} "
                    f"slot {slot}")
        return super()._audit_corrupt("tensor")

    def corrupt_replica(self, replica: int) -> str:
        """Chaos helper: flip the rule-side table copies held by ONE data
        replica's devices — real per-device divergence of a logically
        replicated tensor (the HBM-bit-flip-on-one-chip model).  The next
        replica-resolved canary (install gate or watchdog) diverges on
        exactly this replica and vetoes, rolling back / degrading the
        WHOLE mesh; recovery is the ordinary canary-gated recompile,
        whose fresh placement rebuilds every copy from the host mirror.
        The mutation counter is deliberately not bumped — silent
        corruption is the thing being modeled."""
        devs = set(self._mesh.devices[replica, :].flat)

        def flip(arr):
            bufs = []
            for s in arr.addressable_shards:
                buf = np.array(s.data)
                if s.device in devs:
                    buf = buf ^ 1
                bufs.append(jax.device_put(buf, s.device))
            return jax.make_array_from_single_device_arrays(
                arr.shape, arr.sharding, bufs)

        drs = self._drs
        self._drs = drs._replace(
            ingress=drs.ingress._replace(action=flip(drs.ingress.action)),
            egress=drs.egress._replace(action=flip(drs.egress.action)),
            iso_in=drs.iso_in._replace(val=flip(drs.iso_in.val)),
            iso_out=drs.iso_out._replace(val=flip(drs.iso_out.val)),
        )
        return (f"flipped rule-side device copies held by data replica "
                f"{replica}")

    # -- host-side observability over the (D,) axis --------------------------

    def dump_flows(self, now: int) -> list[dict]:
        return [e for r in range(self._n_data)
                for e in self._dump_flows_state(
                    jax.tree.map(lambda x, r=r: x[r], self._state), now)]

    def cache_stats(self) -> dict:
        per = _vmapped_cache_stats()(self._state)
        c = {k: int(np.asarray(v).sum()) for k, v in per.items()}
        c["evictions"] = self._evictions
        c["reclaims"] = self._reclaims
        return c

    def _tenant_occupied(self, fields: dict) -> int:
        """Snapshot-state occupancy, (D,)-summed (tenancy tenant_stats —
        the scrape path must never swap worlds)."""
        per = _vmapped_cache_stats()(fields["_state"])
        return int(np.asarray(per["occupied"]).sum())

    def _tenant_drain_dispatch_blocks(self, split: dict, now: int,
                                      chunk) -> None:
        """Mesh override of the per-tenant drain dispatch: a LATCHED
        world (per-world topology latch, parallel/reshard.py) serves on
        its own mesh at its own width — the fleet-indexed per-replica
        layout the queues popped is transport only, so such a world's
        rows re-split onto the world's OWN topology before its drain
        classifies (verdict-safe by construction: homes are re-derived
        from the tuple columns the rows carry verbatim)."""
        fleet = (self._n_data, self._topo_gen)
        for tid, subs in sorted(split.items()):
            n = sum(len(b["src_ip"]) for b in subs if b is not None)
            if tid == 0:
                self._drain_classify(subs, now, chunk=chunk)
                continue
            with self._world_ctx(tid) as w:
                if (self._n_data, self._topo_gen) != fleet:
                    wsubs, chunk_w = self._relayout_world_blocks(subs)
                    self._drain_classify(wsubs, now, chunk=chunk_w)
                else:
                    self._drain_classify(subs, now, chunk=chunk)
                w.queued = max(0, w.queued - n)
        return None

    def _relayout_world_blocks(self, subs: list):
        """Concatenate a latched world's per-replica sub-blocks and
        re-split them by the world's OWN affinity topology (the
        tenant-salted ring at the world's width/generation, the world's
        survivor mask applied) -> (blocks, chunk).  Runs inside the
        world's ctx.  The chunk is pow2-rounded from the max per-replica
        count so the drain's compile-variant set stays O(log), the spill
        retry's rung discipline."""
        rows = [b for b in subs if b is not None]
        block = {c: np.concatenate([np.asarray(b[c]) for b in rows])
                 for c in rows[0]}
        cols = (block["src_ip"].astype(np.uint32),
                block["dst_ip"].astype(np.uint32),
                block["proto"].astype(np.int32),
                block["src_port"].astype(np.int32),
                block["dst_port"].astype(np.int32))
        home = shard_of_tuples(*cols, self._n_data, self._topo_gen,
                               tenant=self._tenant_id())
        if self._failover is not None:
            home, _m = self._failover.mask_shard(
                *cols, home, tenant=self._tenant_id())
        out = []
        mx = 1
        for r in range(self._n_data):
            idx = np.nonzero(home == r)[0]
            if idx.size == 0:
                out.append(None)
                continue
            out.append({c: v[idx] for c, v in block.items()})
            mx = max(mx, int(idx.size))
        chunk = 1 << max(4, (mx - 1).bit_length())
        return out, chunk

    def trace(self, batch: PacketBatch, now: int) -> list[dict]:
        if not self._gates.enabled("Traceflow"):
            raise RuntimeError("Traceflow feature gate is disabled")
        D = self._n_data
        shard = shard_of_tuples(batch.src_ip, batch.dst_ip, batch.proto,
                                batch.src_port, batch.dst_port, D,
                                self._topo_gen, tenant=self._tenant_id())
        if self._failover is not None:
            # Trace what serving serves: quarantined-home lanes re-home
            # onto the survivor ring exactly like _step.
            shard, _m = self._failover.mask_shard(
                batch.src_ip, batch.dst_ip, batch.proto, batch.src_port,
                batch.dst_port, shard, tenant=self._tenant_id())
        out: list = [None] * batch.size
        for r in range(D):
            idx = np.nonzero(shard == r)[0]
            if idx.size == 0:
                continue
            sub = PacketBatch.from_packets(
                [batch.packet(int(i)) for i in idx])
            local = jax.tree.map(lambda x, r=r: x[r], self._state)
            for i, rec in zip(idx, self._trace_batch(local, sub, now)):
                out[int(i)] = rec
        return out

    # -- elastic resharding plane (parallel/reshard.py) ----------------------

    def _note_reshard_touched(self, replica, src, dst, proto, sport, dport,
                              committed=None, dnat_f=None,
                              dnat_port=None) -> None:
        """Record the home (replica, local slot) of every lane a live
        dispatch may have refreshed/committed/torn down, plus — for
        conntrack-committed lanes — the REPLY-direction entry's slot
        (keyed on the post-DNAT swapped tuple, written in the same
        replica's slice).  Conservative: marking an untouched slot just
        re-sweeps one row at catch-up; the one write class NOT derivable
        host-side is the deferred partner-refresh ts stamp (its slot
        comes from cached meta) — a missed ts refresh is the documented
        verdict-safe staleness class, re-proved by the revalidator."""
        plane = self._reshard
        if plane is None:
            return
        tid = self._tenant_id()
        if plane.dirty_all_for(tid):
            return
        N = self._meta.flow_slots
        src = np.asarray(src).astype(np.uint32)
        dst = np.asarray(dst).astype(np.uint32)
        proto = np.asarray(proto).astype(np.int32)
        sport = np.asarray(sport).astype(np.int32)
        dport = np.asarray(dport).astype(np.int32)
        h = hashing.flow_hash(src, dst, proto, sport, dport, xp=np)
        plane.note_touched(np.asarray(replica),
                           (h & np.uint32(N - 1)).astype(np.int64),
                           tenant=tid)
        if committed is None or dnat_f is None:
            return
        com = np.asarray(committed) != 0
        if not com.any():
            return
        dnat = iputil.unflip_u32_array(np.asarray(dnat_f)[com])
        dp = np.asarray(dnat_port)[com].astype(np.int32)
        rh = hashing.flow_hash(dnat.astype(np.uint32), src[com], proto[com],
                               dp, sport[com], xp=np)
        plane.note_touched(np.asarray(replica)[com],
                           (rh & np.uint32(N - 1)).astype(np.int64),
                           tenant=tid)

    def _remap_cached_attribution(self, old_in: list, old_out: list,
                                  old_bits: int) -> None:
        # Same-ids-in-same-order is the base method's no-op fast path
        # (services-only bundles, degraded-recovery recompiles): zero
        # cache rows rewritten, so the bounded dirty set must survive.
        changed = (list(old_in) != list(self._cps.ingress.rule_ids)
                   or list(old_out) != list(self._cps.egress.rule_ids))
        super()._remap_cached_attribution(old_in, old_out, old_bits)
        # A mid-resize bundle that REALLY remapped attribution touched
        # the WHOLE cache: no bounded dirty set covers that — fall back
        # to the full catch-up sweep (metered; the pre-tracking shape).
        # Per-world: only the world that remapped degrades to the full
        # walk.
        if changed and self._reshard is not None:
            self._reshard.note_all_dirty(tenant=self._tenant_id())

    def reshard_begin(self, n_data: int, devices=None) -> dict:
        """Begin a LIVE resize of the data axis to `n_data` replicas.

        Constructs the target mesh and the next affinity-hash generation
        (dual-topology serving: in-flight batches keep resolving against
        the old topology), and registers the budgeted `reshard-migrate`
        maintenance task that walks the flow-cache/conntrack tables and
        re-commits rows to their target ring homes — live TENANT worlds
        included, each under its own `_world_ctx` with its own certified
        per-world cutover.  The fleet flips only after the target passes
        its replica-resolved canary and a migrated-row audit sweep; a
        default-world veto aborts back to the old mesh with the
        generation unchanged, while a tenant world's veto latches only
        that world.  -> the plane's status dict."""
        if self._reshard is not None:
            raise RuntimeError(
                "a reshard is already in flight; wait for its cutover or "
                "abort it first (reshard_abort)")
        if self.degraded:
            raise RuntimeError(
                "datapath is degraded (serving last-known-good): the "
                "cutover gate could never certify a target topology — "
                "recover before resizing")
        plane = ReshardPlane(self, int(n_data), devices=devices)
        self._install_reshard_plane(plane)
        return plane.status()

    def _install_reshard_plane(self, plane) -> None:
        """Adopt a constructed ReshardPlane — the ordinary reshard_begin
        above, or the failover plane's emergency evacuation/certified
        readmission (which build their planes directly: the evacuation
        must skip reshard_begin's degraded refusal by design — see
        parallel/failover.py) — and register its budgeted migration
        task."""
        self._reshard = plane
        self._maintenance.register(MaintenanceTask(
            "reshard-migrate", self._maint_reshard,
            budget=self._reshard_budget, priority=4,
            shed_when_degraded=True))

    def _maint_reshard(self, now: int, budget: int) -> int:
        """The reshard plane's maintenance-task runner: budgeted
        migration windows while migrating, then the certified cutover
        (true cost reported unclamped — the scheduler's overrun path
        meters it, the canary/scrub discipline)."""
        plane = self._reshard
        if plane is None:
            return 0
        return plane.advance(now, budget)

    def reshard_status(self):
        """The in-flight resize's progress (None when no resize is in
        flight) — see ReshardPlane.status."""
        return None if self._reshard is None else self._reshard.status()

    def reshard_abort(self, reason: str = "operator abort") -> None:
        """Abandon the in-flight resize: the old mesh keeps serving, the
        affinity generation never flips, target structures are dropped."""
        if self._reshard is None:
            raise RuntimeError("no reshard in flight")
        self._reshard.abort(reason)

    def arm_reshard_faults(self, plan, name: str) -> None:
        """Chaos hook (tests): arm the per-tenant forced-canary-veto
        sites f"{name}.tenant_canary.t{tid}" consulted by the per-world
        cutover certification (parallel/reshard._certify_world) — a
        deterministic single-world veto without corrupting device
        state."""
        self._reshard_faults = (plan, str(name))
        plan.bind_recorder(getattr(self, "_flightrec", None))

    def tenant_reshard_resync(self, tid: int, now: int) -> dict:
        """Re-home ONE latched tenant world onto the current fleet
        topology (the readmission half of a per-world canary veto): the
        full migrate + certify + flip walk for just that world, under
        the same veto rules — a second veto re-latches, journaled.
        Refused while a fleet resize is in flight (the plane's own
        per-world migration would race this walk)."""
        if self._reshard is not None:
            raise RuntimeError(
                "a reshard is in flight; the latched world re-certifies "
                "at that plane's cutover — wait for it")
        return resync_world(self, int(tid), int(now))

    def _finish_reshard(self, plane) -> None:
        """Plane lifecycle callback (cutover or abort): unregister the
        migration task and fold the plane's meters into the engine's."""
        if self._reshard is plane:
            self._reshard = None
            self._maintenance.unregister("reshard-migrate")
        if self._failover is not None:
            # Evacuation/readmission outcomes fold into the failover
            # state machine; ordinary resizes pass through untouched.
            self._failover.note_reshard_finished(plane)

    def reshard_stats(self) -> dict:
        """Elastic-mesh observability (schema-stable whether or not a
        resize is in flight): the live affinity-topology generation,
        migration progress/volume, resident target rows, and cutover/
        abort counters — rendered as the reshard metric families."""
        plane = self._reshard
        st = plane.status() if plane is not None else None
        migrated = self._reshard_migrated_total + (
            plane.migrated_rows if plane is not None else 0)
        return {
            "topology_generation": self._topo_gen,
            "active": int(plane is not None),
            "phase": None if st is None else st["phase"],
            "target_n_data": None if st is None else st["n_data_to"],
            "progress_ratio": 0.0 if st is None else st["progress_ratio"],
            "migrated_rows_total": migrated,
            # Cutover catch-up volume: slots the dirty-row sweep walked
            # (the full O(slots) fallback only after a whole-cache
            # write — the production-boundedness meter of ROADMAP 3).
            "catchup_rows_total": self._reshard_catchup_total + (
                plane.catchup_scanned if plane is not None else 0),
            "resident_rows": (plane.resident_rows if plane is not None
                              else self._reshard_resident_rows),
            "requeued_total": self._reshard_requeued_total,
            "cutovers_total": self._reshard_cutovers,
            "aborts_total": self._reshard_aborts,
            "last_span": self._last_reshard_span,
            # Tenant-labeled resize observability: rows migrated into
            # tenant worlds (folded at flip; the live plane's in-flight
            # rows ride on top), per-world cutover vetoes, and the live
            # plane's world count.
            "tenant_rows_total": self._reshard_tenant_rows_total + (
                plane.tenant_rows() if plane is not None else 0),
            "tenant_vetoes_total": self._reshard_tenant_vetoes,
            "tenant_worlds_migrating": (len(plane.worlds)
                                        if plane is not None else 0),
        }

    def mesh_stats(self) -> dict:
        """Shard-labeled observability (rendered as the replica-labeled
        metric families in observability/metrics.py): per-replica
        miss-queue depth, replica-resolved canary mismatches, and
        audited-entry volume under the striped cursor."""
        cp = self._commit
        depths = ([q.depth for q in self._slowpath.queues]
                  if self._slowpath is not None else [0] * self._n_data)
        return {
            "mesh": {"data": self._n_data, "rule": self._n_rule},
            "devices": self._n_data * self._n_rule,
            # Hash-skew pressure: lanes placed off-home, and how many of
            # them the bounded home-routed retry dispatch re-served
            # (equal counters = no lane ever kept foreign semantics).
            "spill_lanes_total": self._spill_lanes_total,
            "spill_retried_total": self._spill_retried_total,
            "replica_miss_queue_depth": depths,
            "replica_canary_mismatches": {
                int(r): int(n)
                for r, n in (cp.replica_mismatches.items()
                             if cp is not None else ())},
            "replica_audit_entries": list(self._replica_audit_entries),
        }

    # -- replica-loss failover plane (parallel/failover.py) ------------------

    def _maint_replica_health(self, now: int, budget: int) -> int:
        """The `replica-health` maintenance-task runner: one probe round
        per grant, plus evacuation begin/retry and auto-readmission
        (NOT shed when degraded — a degraded mesh is exactly when
        replica loss must still be detected)."""
        fo = self._failover
        if fo is None:
            return 0
        return fo.advance(now, budget)

    def arm_failover_faults(self, plan, name: str) -> None:
        """FlakyDatapath hook: arm the f"{name}.replica_dead" /
        f"{name}.replica_wedge" sites on the failover plane (no-op when
        the plane is disabled)."""
        if self._failover is not None:
            self._failover.arm(plan, name)

    def failover_stats(self) -> dict:
        """Replica-loss failover observability (schema-stable whether or
        not the plane is enabled; rendered as the failover metric
        families in observability/metrics.py and GET /failover)."""
        fo = self._failover
        if fo is None:
            return {"enabled": 0, "n_shards": 0, "phase": "disabled",
                    "quarantined_shard": None, "mask_active": 0,
                    "probes_total": 0, "probe_failures_total": 0,
                    "slow_dispatches_total": 0, "quarantines_total": 0,
                    "evacuations_total": 0, "readmissions_total": 0,
                    "remiss_total": 0, "requeued_total": 0,
                    "fail_streaks": {}, "probe_rounds": 0,
                    "probe_history": [],
                    "tenants_pending_evacuation": []}
        return {"enabled": 1, "n_shards": fo._orig_n, **fo.status()}

    def failover_readmit(self) -> dict:
        """Operator re-admission (GET /failover?readmit=1, `antctl
        failover --readmit`): pre-flip heal unmasks; an evacuated
        replica rejoins via the ordinary certified grow-resize — never
        a blind flip.  -> refreshed failover stats."""
        if self._failover is None:
            raise RuntimeError(
                "the failover plane is not enabled (failover=True)")
        st = self._failover.readmit(mode="operator")
        return {"enabled": 1, "n_shards": self._failover._orig_n, **st}

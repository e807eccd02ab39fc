"""Device-mesh scale-out of the tpuflow datapath (SPMD over ICI).

The reference scales by *distributing the control plane* — per-Node span
dissemination (ref: /root/reference/docs/design/architecture.md:57-60) —
while every node's OVS evaluates the full local rule set.  On TPU the
equivalent scale axes map onto a 2-D `jax.sharding.Mesh`:

  ``data`` axis — the packet-batch axis (DP analog of per-Node sharding):
      each shard classifies its own slice of the batch and owns a *private*
      conntrack/affinity table slice.  Direct-mapped-cache semantics make
      this sound: a connection always hashes to the same data shard's table
      only if the same flow lands on the same shard, and when it doesn't the
      miss merely re-classifies (same verdict, deterministic endpoint hash).

  ``rule`` axis — the rule-word axis (TP analog of conjunctive factoring):
      the rule-incidence tables are sharded on their WORD (trailing) axis;
      each shard gathers + ANDs only its local slice of every incidence row
      and the global first-match indices are a single `lax.pmin` all-reduce
      over ICI per evaluation phase — six i32 (B,) vectors per batch,
      negligible next to the gather bytes.

The interval bounds / iso / service tables are replicated (they are the
small, read-mostly side), the incidence words are sharded (they are the
memory that grows with rule count).

HBM capacity math (measured on the 100k-rule bench world, v5e = 16 GB):
  * incidence tables: 558 MB total = six (NB+1, W) u32 tables; both NB
    (interval count) and W (rule words) grow ~linearly in rule count, so
    incidence bytes grow ~QUADRATICALLY: ~5.6 KB/rule at 100k rules,
    ~56 KB/rule at 1M.  Sharding the word axis divides exactly this term
    by the rule-axis size R (tests/test_parallel_scale.py asserts the
    per-shard byte accounting at bench scale).
  * replicated side: interval bounds+iso ~1.4 MB, service tables ~2 MB at
    5k services — noise.
  * per-DATA-shard conntrack state: 36 B/slot (keys 4x4 + meta 4x4 + ts 4)
    = 151 MB at the bench's 2^22 slots; the data axis divides the slot
    budget, not the rule state.
  Single-chip ceiling: ~14 GB of incidence -> ~1.6M rules; an 8-way rule
  axis lifts that to ~4.5M rules per direction pair (capped earlier by the
  32-bit attribution packing, models/pipeline.rule_split) — rule
  state beyond one chip's HBM is exactly what the axis buys, the way the
  reference relies on OVS's shared tables + megaflow cache.

State layout under shard_map: conn/aff arrays gain a leading (D,) axis
sharded over ``data``; shard d sees its (slots+1,) slice.  Verdicts after the
pmin are bitwise identical on every rule shard, so state updates computed
from them are replicated over ``rule`` by construction (check_vma cannot
prove this, hence check_vma=False).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compiler.compile import CompiledPolicySet
from ..compiler.services import ServiceTables
from ..compiler.topology import ForwardingTables
from ..models import forwarding as fw
from ..models import pipeline as pl
from ..ops import hashing
from ..ops import match as m

DATA, RULE = "data", "rule"


def _shard_map(body, *, mesh, in_specs, out_specs):
    """The one shard_map entry point, with the replication check off.

    Why it is disabled (the ONE place this is argued): every sharded
    kernel here combines its per-phase first-match hit tensors with
    `lax.pmin` over ``rule`` before anything downstream consumes them, so
    verdicts — and every state update computed from them — are bitwise
    identical on all rule shards BY CONSTRUCTION.  check_vma cannot prove
    replication established through a collective in the body, so it would
    reject these (correct) programs; the invariant is instead enforced
    empirically by the parity suites (tests/test_parallel.py,
    tests/test_mesh_datapath.py), which diff the sharded outputs
    bit-for-bit against the single-chip kernels."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# Shard-affinity hash (the multichip traffic path, datapath engine in
# meshpath.py): a deterministic, direction-SYMMETRIC 5-tuple -> data-shard
# map, so both conntrack legs of a connection (src/dst and ports swapped)
# land on the shard that owns the connection's cache entries and
# direct-mapped-cache semantics stay sound per shard.  The salt is
# distinct from the cache-slot hash salt on purpose: shard id and slot
# index must stay decorrelated, or shard r would only ever populate slots
# ≡ r (mod D) and lose (D-1)/D of its private table.
SHARD_AFFINITY_SALT = 0x6D657368  # "mesh"

# Consistent-ring salt (elastic resharding, parallel/reshard.py): the
# virtual-point layout of the device-side shard ring.  Distinct from both
# the affinity and cache-slot salts so ring position, home shard and slot
# index stay pairwise decorrelated.
SHARD_RING_SALT = 0x72696E67  # "ring"

# Virtual points per data shard on the consistent ring — the device-side
# twin of agent/memberlist._VNODES (the reference's consistenthash
# weight), raised so the per-shard load spread tightens to ~±10%.
RING_VNODES = 128


@lru_cache(maxsize=32)  # host arrays keyed by axis width: pure function
def _ring(n_data: int):  # of n_data, so eviction just recomputes — bounded
    """The consistent-hash ring for a data-axis size: (points, owners),
    points sorted ascending.  The device-side port of the reference's
    memberlist election (agent/memberlist.ConsistentHash; ref
    pkg/agent/memberlist/cluster.go:89): each shard owns RING_VNODES
    virtual points whose positions depend ONLY on (shard id, vnode) — so
    growing D -> D' adds the new shards' points and moves exactly the
    keys those points claim, and shrinking removes them and redistributes
    exactly their keys.  Every other key keeps its owner, which is what
    bounds the reshard migration volume to the resized fraction."""
    ids = np.arange(n_data * RING_VNODES, dtype=np.uint32)
    with np.errstate(over="ignore"):
        # Golden-ratio pre-scramble: FNV over tiny SEQUENTIAL ints
        # clusters badly in the u32 ordering the ring sorts by (measured:
        # a 4-shard ring landed 6.5%/42% shares on the raw mix), so the
        # vnode id is spread across the word first.  The scramble depends
        # only on the id, preserving the generation-independence of each
        # shard's points (the minimal-movement property).
        pts = hashing.fnv_mix(
            [ids * np.uint32(0x9E3779B9),
             np.full(ids.shape, SHARD_RING_SALT, np.uint32)], xp=np)
    order = np.argsort(pts, kind="stable")
    return pts[order], (ids[order] // np.uint32(RING_VNODES)).astype(np.int32)


def _tuple_hash(src_ip, dst_ip, proto, sport, dport):
    """The direction-symmetric 5-tuple key hash behind shard_of_tuples."""
    with np.errstate(over="ignore"):
        ea = hashing.fnv_mix(
            [np.asarray(src_ip), np.asarray(sport)], xp=np)
        eb = hashing.fnv_mix(
            [np.asarray(dst_ip), np.asarray(dport)], xp=np)
        return hashing.fnv_mix(
            [np.minimum(ea, eb), np.maximum(ea, eb),
             np.asarray(proto).astype(np.uint32)
             ^ np.uint32(SHARD_AFFINITY_SALT)],
            xp=np,
        )


def shard_of_tuples(src_ip, dst_ip, proto, sport, dport, n_data: int,
                    topo_gen: int = 0, tenant: int = 0):
    """Host-side (numpy) data-shard assignment for a batch of 5-tuples.

    Symmetric under direction reversal: the forward leg (c -> s) and the
    reply leg (s -> c) hash identically, so non-DNAT connections are
    fully shard-affine in both directions.  DNAT'd service replies
    (endpoint -> client; the frontend address is gone from the tuple) can
    land off-shard and re-classify — the ECMP-asymmetry analog, see the
    README multichip failure-model row.

    `topo_gen` versions the shard election (elastic resharding,
    parallel/reshard.py): generation 0 — the boot topology — keeps the
    dense mod map below; every RESIZED topology (generation >= 1) elects
    owners on the consistent ring (`_ring`), the memberlist ownership
    shape, so consecutive resizes move only the ring-minimal key
    fraction.  During a live reshard the old and new maps resolve side
    by side — in-flight batches against (D_old, g), migration routing
    against (D_new, g+1).

    `tenant` folds the owning policy world's id into the key hash
    (datapath/tenancy.py): two tenants presenting the same 5-tuple are
    DIFFERENT connections and must decorrelate across shards like any
    other key material.  Batch-constant, so direction symmetry is
    preserved; 0 (the default world) leaves the hash bit-identical to
    the untenanted map.  The golden-ratio pre-scramble spreads the small
    sequential ids across the word (the `_ring` lesson — raw small ints
    cluster in u32 order)."""
    h = _tuple_hash(src_ip, dst_ip, proto, sport, dport)
    if tenant:
        with np.errstate(over="ignore"):
            h = hashing.fnv_mix(
                [h, np.full(h.shape, np.uint32(int(tenant))
                            * np.uint32(0x9E3779B9), np.uint32)], xp=np)
    if topo_gen == 0:
        return (h % np.uint32(n_data)).astype(np.int32)
    pts, owners = _ring(int(n_data))
    # First virtual point clockwise of the key — bisect semantics
    # identical to agent/memberlist.ConsistentHash.get.
    i = np.searchsorted(pts, h, side="right") % len(pts)
    return owners[i]


def make_mesh(n_data: int, n_rule: int, devices=None) -> Mesh:
    """(n_data x n_rule) mesh over `devices`, default the default
    backend's.  Too few devices raises: a virtual-CPU dryrun mesh is
    built only from `devices=jax.devices("cpu")` passed by the caller,
    never substituted for missing chips."""
    need = n_data * n_rule
    if devices is None:
        devices = jax.devices()
    if len(devices) < need:
        raise ValueError(
            f"need {need} devices for a {n_data}x{n_rule} mesh, have "
            f"{len(devices)} ({devices[0].platform if devices else 'none'})")
    arr = np.asarray(devices[:need]).reshape(n_data, n_rule)
    return Mesh(arr, (DATA, RULE))


# PartitionSpecs for each pytree.  EVERY field of every sharded pytree is
# enumerated explicitly (no `len(fields)` splat): tools/check_mesh.py
# parses these functions textually and fails the build when a NamedTuple
# grows a field that has neither an explicit spec below nor a reasoned
# entry in MESH_SPEC_ALLOWLIST — a new single-chip state field can no
# longer ship replicated-by-accident.

# Fields deliberately WITHOUT an explicit kwarg in the spec builders,
# keyed "Class.field" (names collide across the tracked NamedTuples),
# each with the reason it needs no spec.  Pure literal: tools/
# check_mesh.py parses it with ast.literal_eval, dependency-free.  Empty
# today — every field of every sharded pytree is enumerated.
MESH_SPEC_ALLOWLIST: dict = {}


def _drs_specs(agg: bool = False) -> m.DeviceRuleSet:
    def dim():
        # Interval bounds (v4 + v6 lexicographic) replicated, incidence
        # words sharded — bounds are the small side in both families.
        # The aggregate level (round-7 pruning) shards on ITS word axis
        # exactly like the incidence it summarizes: to_device pads W to a
        # word_multiple*TILE_WORDS multiple (ops/match._width; AGG_BLOCK
        # divides the tile), so each rule shard's agg slice covers
        # precisely its own inc words and no aggregate word straddles a
        # shard boundary.  agg=False worlds carry agg=None
        # (an EMPTY pytree node), matching the unpruned table pytree.
        return m.DimTable(bounds=P(), bounds6=P(), inc=P(None, RULE),
                          agg=P(None, RULE) if agg else None)

    dd = m.DeviceDirection(
        at=dim(),
        peer=dim(),
        svc=dim(),
        action=P(),  # small flat gather table, replicated (indexed post-pmin)
        l7=P(),  # same discipline as action
        word_idx=P(RULE),
    )
    iso = m.IsoTable(bounds=P(), bounds6=P(), val=P())
    return m.DeviceRuleSet(
        ingress=dd,
        egress=dd,
        iso_in=iso,
        iso_out=iso,
        # Delta ranges/signs replicated; the per-slot rule masks shard on
        # the same word axis as the incidence tables they patch.
        ip_delta=m.DeltaTable(
            lo_f=P(),
            hi_f=P(),
            sign=P(),
            iso=P(),
            at_in=P(None, RULE),
            peer_in=P(None, RULE),
            at_out=P(None, RULE),
            peer_out=P(None, RULE),
            n=P(),
            fam=P(),
            lo6_w=P(),
            hi6_w=P(),
        ),
    )


def _svc_specs() -> pl.DeviceServiceTables:
    # Service tables are the small, read-mostly side: replicated whole,
    # every field named so check_mesh.py can prove coverage.
    return pl.DeviceServiceTables(
        uip_f=P(),
        ppk=P(),
        slot_svc=P(),
        n_ep=P(),
        has_ep=P(),
        aff_timeout=P(),
        ep_base=P(),
        ep_ip_f=P(),
        ep_port=P(),
        slot_snat=P(),
        prog_svc=P(),
        prog_dsr=P(),
        uip6_w=P(),
        ppk6=P(),
        slot_svc6=P(),
        slot_snat6=P(),
        ep_ipw_f=P(),
    )


def _state_specs() -> pl.PipelineState:
    # Stateful tables gain a leading (D,) axis sharded over ``data``:
    # each data shard owns a PRIVATE (slots+1, ...) slice — its own
    # direct-mapped flow cache and affinity table.
    flow = pl.FlowCache(
        keys=P(DATA, None),
        meta=P(DATA, None),
        ts=P(DATA, None),
        pkts=P(DATA, None),
        octets=P(DATA, None),
        pkts_hi=P(DATA, None),
        octets_hi=P(DATA, None),
    )
    aff = pl.AffinityTable(
        key_client=P(DATA, None),
        key_svc=P(DATA, None),
        ep=P(DATA, None),
        ts=P(DATA, None),
    )
    return pl.PipelineState(flow=flow, aff=aff)


def shard_rule_set(cps: CompiledPolicySet, mesh: Mesh,
                   prune_budget: int = 0):
    """Compile + place rule tensors on the mesh -> (drs, StaticMeta)."""
    n_rule = mesh.shape[RULE]
    drs, meta = m.to_device(cps, word_multiple=n_rule,
                            prune_budget=prune_budget)
    specs = _drs_specs(agg=prune_budget > 0)
    drs = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), drs, specs
    )
    # Re-stamp: the operands now live on the MESH, whose platform can
    # differ from the default backend's (virtual-CPU dryrun on a TPU host).
    return drs, m.placed_meta(meta, drs)


def shard_state(state: pl.PipelineState, mesh: Mesh) -> pl.PipelineState:
    """Replicate-free placement: add the leading data axis and shard it."""
    n_data = mesh.shape[DATA]
    state = jax.tree.map(lambda x: jnp.broadcast_to(x, (n_data,) + x.shape), state)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        state,
        _state_specs(),
    )


def _pmin_rule(h: jax.Array) -> jax.Array:
    return lax.pmin(h, RULE)


def make_sharded_classifier(cps: CompiledPolicySet, mesh: Mesh,
                            prune_budget: int = 0):
    """Stateless sharded classification: -> (fn(src_f, dst_f, proto, dport), drs).

    fn is jitted over the mesh; inputs are (B,) arrays with B divisible by the
    data axis size; outputs land sharded over ``data``.  prune_budget > 0
    builds + shards the aggregate tables and runs the two-level pruned
    walk per shard (candidates and fallback stay shard-local; the pmin
    combine is unchanged).
    """
    drs, meta = shard_rule_set(cps, mesh, prune_budget=prune_budget)
    dspec = _drs_specs(agg=prune_budget > 0)

    def body(drs, src_f, dst_f, proto, dport):
        return m.classify_batch(
            drs, src_f, dst_f, proto, dport, meta=meta, hit_combine=_pmin_rule
        )

    shmapped = _shard_map(
        body,
        mesh=mesh,
        in_specs=(dspec, P(DATA), P(DATA), P(DATA), P(DATA)),
        out_specs=P(DATA),
    )
    jitted = jax.jit(shmapped)

    def fn(src_f, dst_f, proto, dport):
        return jitted(drs, src_f, dst_f, proto, dport)

    return fn, drs


def _fwd_specs() -> fw.DeviceForwardingTables:
    # Forwarding tables are the small, read-mostly side (pods + nodes of
    # ONE node's world): replicated, like the interval-bounds tables.
    return fw.DeviceForwardingTables(
        *([P()] * len(fw.DeviceForwardingTables._fields))
    )


def _build_sharded_step(cps, svc, mesh, ft, flow_slots, aff_slots,
                        ct_timeout_s, miss_chunk, fused=False,
                        prune_budget=0):
    """Shared builder behind make_sharded_pipeline[_full] — one place for
    the capacity check, placement, meta/state construction and shard_map
    scaffolding so the two public variants can never drift."""
    bits_in = pl.rule_split(cps)
    drs, match_meta = shard_rule_set(cps, mesh, prune_budget=prune_budget)
    dspec = _drs_specs(agg=prune_budget > 0)
    repl = NamedSharding(mesh, P())
    dsvc = jax.tree.map(
        lambda x: jax.device_put(x, repl), pl.svc_to_device(svc)
    )
    dft = None
    if ft is not None:
        dft = jax.tree.map(
            lambda x: jax.device_put(x, repl), fw.fwd_to_device(ft)
        )
    meta = pl.PipelineMeta(
        match=match_meta,
        flow_slots=flow_slots,
        aff_slots=aff_slots,
        ct_timeout_s=ct_timeout_s,
        miss_chunk=miss_chunk,
        # The fused consumer is shard-aware (global word offsets ride
        # word_idx), so the sharded walk keeps the cold-path win.
        fused=fused,
        rule_bits_in=bits_in,
    )
    state = shard_state(pl.init_state(flow_slots, aff_slots), mesh)

    def finish(local, out):
        # scalar per shard -> (D,) vector of per-data-shard counts (the
        # prune keys exist iff prune_budget > 0; the hist vector gains
        # the same leading axis and is summed host-side)
        for k in ("n_miss", "n_evict", "n_reclaim", "round_lanes",
                  "n_prune_skips", "n_prune_fb", "prune_cand_hist"):
            if k in out:
                out[k] = out[k][None]
        return jax.tree.map(lambda x: x[None], local), out

    if ft is None:
        def body(state, drs, dsvc, src_f, dst_f, proto, sport, dport,
                 now, gen):
            # Local view: strip the leading data axis (size 1 per shard).
            local = jax.tree.map(lambda x: x[0], state)
            local, out = pl._pipeline_step(
                local, drs, dsvc, src_f, dst_f, proto, sport, dport,
                now, gen, meta=meta, hit_combine=_pmin_rule,
            )
            return finish(local, out)

        in_specs = (
            _state_specs(), dspec, _svc_specs(),
            P(DATA), P(DATA), P(DATA), P(DATA), P(DATA), P(), P(),
        )
    else:
        def body(state, drs, dsvc, dft, src_f, dst_f, proto, sport,
                 dport, in_port, flags, arp_op, now, gen):
            local = jax.tree.map(lambda x: x[0], state)
            local, out = fw._pipeline_step_full(
                local, drs, dsvc, dft, src_f, dst_f, proto, sport, dport,
                in_port, now, gen, flags, arp_op,
                meta=meta, hit_combine=_pmin_rule,
            )
            return finish(local, out)

        in_specs = (
            _state_specs(), dspec, _svc_specs(), _fwd_specs(),
            P(DATA), P(DATA), P(DATA), P(DATA), P(DATA), P(DATA), P(DATA),
            P(DATA), P(), P(),
        )

    step = jax.jit(_shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(_state_specs(), P(DATA)),
    ))
    return step, state, drs, dsvc, dft


def make_sharded_pipeline(
    cps: CompiledPolicySet,
    svc: ServiceTables,
    mesh: Mesh,
    *,
    flow_slots: int = 1 << 20,
    aff_slots: int = 1 << 18,
    ct_timeout_s: int = 3600,
    miss_chunk: int = 4096,
    fused: bool = False,
    prune_budget: int = 0,
):
    """Full stateful datapath step, SPMD over (data, rule).

    -> (step, state, (drs, dsvc)); step(state, drs, dsvc, src_f, dst_f,
    proto, sport, dport, now, gen) -> (state', out) exactly like the
    single-chip `models.pipeline.make_pipeline`, with per-data-shard
    flow-cache/affinity tables.  Each data shard takes its own slow path
    only when ITS slice of the batch has cache misses.
    """
    step, state, drs, dsvc, _dft = _build_sharded_step(
        cps, svc, mesh, None, flow_slots, aff_slots, ct_timeout_s,
        miss_chunk, fused=fused, prune_budget=prune_budget,
    )
    return step, state, (drs, dsvc)


def make_sharded_pipeline_full(
    cps: CompiledPolicySet,
    svc: ServiceTables,
    ft: ForwardingTables,
    mesh: Mesh,
    *,
    flow_slots: int = 1 << 20,
    aff_slots: int = 1 << 18,
    ct_timeout_s: int = 3600,
    miss_chunk: int = 4096,
    fused: bool = False,
    prune_budget: int = 0,
):
    """The FULL per-packet walk (SpoofGuard -> policy/service pipeline ->
    L2/L3 forward -> Output, models/forwarding._pipeline_step_full), SPMD
    over (data, rule) — the production multi-chip step.

    -> (step, state, (drs, dsvc, dft)); step(state, drs, dsvc, dft, src_f,
    dst_f, proto, sport, dport, in_port, flags, arp_op, now, gen) ->
    (state', out) — flags/arp_op are the TCP-teardown and ARP lane columns
    (zeros when absent), sharded over data like the rest of the batch.
    Forwarding is stateless per-packet, so it shards trivially over the
    data axis with replicated topology tables; the rule axis participates
    only in the classification pmin, exactly as in make_sharded_pipeline.
    """
    step, state, drs, dsvc, dft = _build_sharded_step(
        cps, svc, mesh, ft, flow_slots, aff_slots, ct_timeout_s,
        miss_chunk, fused=fused, prune_budget=prune_budget,
    )
    return step, state, (drs, dsvc, dft)

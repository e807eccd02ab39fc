"""TpuflowDatapath: the TPU kernel behind the Datapath boundary.

Owns the device tensors (rules, services, flow-cache/conntrack state) for
one datapath instance and realizes the bundle/commit semantics of the
reference's OVS binding layer:

  install_bundle   == AddFlowsInBundle + bundle commit
                      (/root/reference/pkg/ovs/openflow/ofctrl_bridge.go:468):
                      compile -> (drs', dsvc', gen+1) swap.  The swap is
                      atomic by construction — the next step() call sees
                      either the old or the new tensors, never a mix.
  apply_group_delta== the incremental address-group watch delta
                      (docs/design/architecture.md:61-62): O(affected
                      columns) host work + a five-small-array device upload
                      (ops/match.DeltaTable), no recompile; overflow folds
                      into a full recompile (megaflow-revalidation analog).
  generation       == the cookie round (pkg/agent/openflow/cookie/
                      allocator.go:76-135): bumping it invalidates cached
                      denials while established connections persist.

Attribution across bundles: cached rule attribution follows rule IDENTITY
— install_bundle remaps stored indices old->new by stable rule id
(_remap_cached_attribution) and drops attribution for vanished rules, so
established hits keep reporting the rule that actually decided them (a
deliberate strengthening over OVS ct_label, whose conj_id may dangle after
its rule is gone; ref network_policy.go ct_label persistence).
"""

from __future__ import annotations

import functools
import time
from collections import Counter
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..apis.controlplane import GroupMember, PROTO_TCP
from ..apis.service import ServiceEntry
from ..compiler.compile import ACT_ALLOW, ACT_DROP, compile_policy_set
from ..compiler.ir import PolicySet
from ..compiler.services import compile_services
from ..compiler import topology
from ..compiler.topology import FWD_TUNNEL, Topology, compile_topology
from ..models import forwarding as fwd
from ..models import pipeline as pl
from ..observability.flightrec import emit_into
from ..observability.metrics import Histogram
from ..observability.telemetry import TelemetryPlane
from ..observability.tracing import (SP_ACCOUNT, SP_ATTRIBUTE, SP_DISPATCH,
                                     SP_DONE, SP_FETCH, SP_STAGE, SP_UPLOAD,
                                     SP_WAIT, StepTracer, construct_span)
from ..ops.match import (PRUNE_HIST_BOUNDS, PRUNE_LADDER, DeltaTable,
                         PruneAutotuner, placed_meta, to_host)
from ..packet import Packet, PacketBatch
from ..utils import ip as iputil
from ..config import ConfigError
from . import persist
from .audit import AuditableDatapath
from .commit import TransactionalDatapath, pad_probes
from .interface import (Datapath, DatapathStats, DatapathType, StepResult,
                        WideStepResult)
from .maintenance import MaintainableDatapath
from .slowpath import ADMIT_HOLD
from .tenancy import TenantedDatapath, TenantSpec


def _rid(ids: list, idx: int):
    """Stored rule INDEX -> stable rule id, None for default/vanished —
    the one attribution-resolution rule shared by dump/trace/audit."""
    return ids[idx] if 0 <= idx < len(ids) and ids[idx] else None


def _rids(dt, idx: np.ndarray) -> list:
    """_rid over a whole column of stored rule INDICES of direction `dt`
    (a DirectionTensors): one gather from its rule_id_table, every index
    outside [0, len(rule_ids)) — default, or carried by a cached entry
    from an older generation — clamped onto the table's trailing None.
    A list of str | None, one per lane: both engines' StepResult."""
    table = dt.rule_id_table
    n = table.shape[0] - 1
    return table[np.where((idx >= 0) & (idx < n), idx, n)].tolist()


class TpuflowDatapath(TenantedDatapath, MaintainableDatapath,
                      TransactionalDatapath, AuditableDatapath,
                      persist.PersistableDatapath, Datapath):
    # The complete per-world swap set of this engine (datapath/tenancy:
    # everything a tenant's own spec/tensors/commit bookkeeping touches;
    # tools/check_tenant.py pins the required members).  Deliberately
    # absent = shared across worlds: _services/_dsvc (the platform
    # service view), _topo/_ft/_rt/_dft (forwarding), the prune plane,
    # the slow-path queue and every scheduler/observability object.
    _TENANT_WORLD_FIELDS = (
        "_ps", "_cps", "_drs", "_meta", "_meta_step", "_state", "_gen",
        "_has_named_ports", "_n_deltas", "_delta_host", "_name_gids",
        "_gid_ident", "_group_members", "_static_blocks", "_member_meta",
        "_stats_in", "_stats_out", "_bytes_in", "_bytes_out",
        "_default_allow", "_default_deny", "_evictions", "_reclaims",
        "_state_mutations", "_pipe_kw", "_persist_dirty",
    )

    @construct_span
    def __init__(
        self,
        ps: Optional[PolicySet] = None,
        services: Optional[list[ServiceEntry]] = None,
        *,
        flow_slots: int = 1 << 20,
        aff_slots: int = 1 << 18,
        ct_timeout_s: int = 3600,
        miss_chunk: int = 4096,
        delta_slots: int = 128,
        ct_syn_timeout_s=None,
        ct_other_new_s=None,
        ct_other_est_s=None,
        fused: bool = False,
        node_ips: Optional[list[str]] = None,
        node_name: str = "",
        persist_dir: Optional[str] = None,
        feature_gates=None,
        topology: Optional[Topology] = None,
        dual_stack: bool = False,
        async_slowpath: bool = False,
        miss_queue_slots: int = 1 << 16,
        admission: str = "forward",
        drain_batch: int = 4096,
        autotune_drain: bool = False,
        autotune_bounds: Optional[tuple] = None,
        overlap_commits: bool = False,
        canary_probes: int = 64,
        audit_window: int = 64,
        audit_divergence_trip: Optional[int] = None,
        maint_budget: Optional[int] = None,
        maint_clock=None,
        flightrec_slots: int = 1024,
        realization_slots: int = 256,
        prune_budget: int = 0,
        autotune_prune: bool = False,
        second_chance: bool = False,
        telemetry: bool = False,
        miss_source_rate: Optional[float] = None,
        miss_source_burst: Optional[int] = None,
        serving_batcher: bool = False,
        canonical_sizes=None,
        flush_depth: Optional[int] = None,
        flush_deadline: Optional[int] = None,
        serving_ring_slots: Optional[int] = None,
    ):
        from ..features import DEFAULT_GATES

        # Knob-combo validation up front (one typed ConfigError at
        # construction, not a failure deep in the first drain/scan): the
        # audit divergence trip escalates through a CANARY-GATED full
        # recompile — with probing disabled that recovery could never
        # certify, so an explicit trip alongside canary_probes=0 is a
        # contradiction.  (canary_probes=0 with the trip left default
        # stays legal: the default plane simply never trips without
        # probes to disagree with.)
        if canary_probes == 0 and audit_divergence_trip is not None:
            raise ConfigError(
                "canary_probes=0 disables the canary, but "
                "audit_divergence_trip escalation recovers through a "
                "canary-gated recompile — enable probes or drop the "
                "explicit trip"
            )
        audit_divergence_trip = (8 if audit_divergence_trip is None
                                 else audit_divergence_trip)
        # Aggregated-bitmap match pruning (ops/match round 7): K = max
        # candidate superblocks per lane/direction; 0 compiles the
        # aggregate layer out entirely (the existing kernel, bit-for-bit).
        # autotune_prune retunes K on PRUNE_LADDER from the measured
        # fallback rate (one jit-cached classify variant per rung).
        if prune_budget < 0:
            raise ConfigError(
                f"prune_budget must be >= 0, got {prune_budget}")
        if autotune_prune and prune_budget <= 0:
            raise ConfigError(
                "autotune_prune retunes the aggregate-prune K budget, but "
                "prune_budget=0 disables the aggregate layer — set an "
                "initial prune_budget (e.g. 4) to autotune from")
        # fused=True over an aggregate-pruned (prune_budget > 0) world
        # feeds the pruned classify's candidate matrices to the Pallas
        # consumer (ops/match._pruned_consumer_call).  That pair has only
        # ever been held to the oracle on v4 worlds, so with dual_stack
        # it is rejected outright rather than served unproven.
        if fused and dual_stack and prune_budget > 0:
            raise ConfigError(
                "fused=True with prune_budget > 0 is v4-only (drop fused "
                "or prune_budget, or dual_stack)")
        self._prune_tuner = None
        if autotune_prune:
            self._prune_tuner = PruneAutotuner(prune_budget)
            prune_budget = self._prune_tuner.budget  # snap to the ladder
        self._prune_budget = int(prune_budget)
        self._prune_skips = 0
        self._prune_fallbacks = 0
        self._prune_classified = 0
        self._prune_retunes = 0
        self._prune_hist = Histogram(bounds=PRUNE_HIST_BOUNDS)
        self._gates = feature_gates or DEFAULT_GATES
        # Per-entry traffic counters ride the FlowExporter gate: volumes
        # cost a hit-path column gather+scatter, paid only when the
        # observability plane consumes them (flowexporter/types.go:59).
        self._flow_stats = self._gates.enabled("FlowExporter")
        # Dual-stack switches the flow cache to wide (10-column) keys and
        # enables v6 service frontends / forwarding tables (the reference
        # is dual-stack when both families are configured,
        # proxier.go:1379-1465 / route_linux.go).  Static per instance:
        # pure-v4 nodes keep the narrow fast path compiled unchanged.
        self._dual_stack = dual_stack
        # Async slow path (datapath/slowpath): step() runs ONLY the fast
        # path; misses are admitted to the bounded queue with a provisional
        # verdict and classified later by drain_slowpath() in coalesced
        # batches (shared plumbing on the Datapath base).
        # autotune_drain: drain_batch seeds a hysteresis controller that
        # retunes the coalesced chunk against queue pressure, padding to a
        # closed pre-compiled rung ladder (no recompile storms).
        # overlap_commits: the round-6 double-buffer — drain commits are
        # dispatched with the state DONATED and their host-side
        # materialization deferred in a two-slot ring, so classify of
        # batch N+1 dispatches before blocking on the commit of batch N.
        self._init_slowpath(async_slowpath, dual_stack, miss_queue_slots,
                            admission, drain_batch, autotune_drain,
                            autotune_bounds, overlap_commits,
                            miss_source_rate, miss_source_burst)
        # Node identity: NodePort frontends bind to these addresses and
        # externalTrafficPolicy=Local filters endpoints to this node
        # (ref proxier.go nodePortAddresses / externalPolicyLocal).
        self._node_ips = list(node_ips or [])
        self._node_name = node_name
        self._delta_slots = delta_slots
        self._pipe_kw = dict(
            flow_slots=flow_slots, aff_slots=aff_slots,
            ct_timeout_s=ct_timeout_s, miss_chunk=miss_chunk,
            ct_syn_timeout_s=ct_syn_timeout_s,
            ct_other_new_s=ct_other_new_s,
            ct_other_est_s=ct_other_est_s,
            # Cache misses classify through the fused pallas consumer
            # (ops/match cold-path study); off by default so CPU-bound
            # suites avoid interpret-mode pallas.  With prune_budget > 0
            # it consumes the pruned classify's candidate matrices (the
            # combo check above already rejected dual_stack).
            fused=fused,
            # Thrash-resistant replacement (the 2-bit second-chance
            # counter, models/pipeline CHANCE_SHIFT); off by default so
            # the compiled step stays bit-identical.
            second_chance=second_chance,
            # Hot-path telemetry counters (observability/telemetry.py);
            # off by default — telemetry=False lowers bit-identical.
            telemetry=telemetry,
        )
        self._ps = ps if ps is not None else PolicySet()
        self._services = list(services or [])
        self._topo = topology  # None -> snapshot topology, else empty
        self._gen = 0
        # Restart recovery (cookie-round analog, datapath/persist.py): when
        # constructed WITHOUT explicit state, reload the last committed
        # snapshot and resume with a MONOTONIC generation; flow-cache state
        # is dropped (re-classifies, never re-verdicts differently).
        self._init_persist(persist_dir, ps, services)
        self._state = self._init_pipeline_state(flow_slots, aff_slots)
        # Per-rule packet counters (IngressMetric/EgressMetric analog),
        # keyed by stable rule id so they survive bundle renumbering.
        self._stats_in: Counter = Counter()
        self._stats_out: Counter = Counter()
        self._bytes_in: Counter = Counter()
        self._bytes_out: Counter = Counter()
        self._default_allow = 0
        self._default_deny = 0
        self._evictions = 0
        # Dead rows (idle-expired / stale-gen) reclaimed by overlapped
        # drain inserts — the n_reclaim split of meta.drain_reclaim.
        self._reclaims = 0
        # Classify-batch latency (scraped as the
        # antrea_tpu_datapath_step_seconds histogram): wall time of step()
        # as the CALLER sees it — dispatch + device walk + host fetch (the
        # np.asarray conversions force completion), i.e. the latency the
        # dissemination/observability planes actually wait out.
        self.step_hist = Histogram()
        # The step's own spans (observability/tracing.StepTracer): the
        # `step` span that feeds step_hist, its seven host phases and the
        # transfer counters, kept for the last 4,096 steps.  Always on,
        # like step_hist; host-side only.
        self._steptrace = StepTracer()
        if self._topo is None:
            self._topo = Topology()
        self._compile_rules()
        self._compile_services()
        self._compile_topology()
        # Observability plane BEFORE the commit/audit planes: they journal
        # transitions and stamp realization spans through these objects
        # from their very first transaction (observability/flightrec.py +
        # tracing.py).  flightrec_slots=0 / realization_slots=0 disable —
        # both are pure host-side state, so the compiled step HLO is
        # bit-identical either way (latch = one int compare per step).
        self._init_observability(flightrec_slots, realization_slots)
        # Hot-path telemetry accumulator (observability/telemetry.py):
        # pairs with the telemetry kernel knob above; built BEFORE the
        # maintenance scheduler so _init_maintenance can register the
        # sentinel sweep against it.
        if telemetry:
            self._telemetry = TelemetryPlane()
        # Commit plane LAST: the boot state (possibly persistence-restored)
        # is the last-known-good baseline every later commit retains.
        self._init_commit_plane(canary_probes=canary_probes)
        # Audit plane after the commit plane: the boot tensors anchor the
        # checksum scrub's golden digests (datapath/audit.py).
        self._init_audit_plane(audit_window=audit_window,
                               audit_divergence_trip=audit_divergence_trip)
        # Maintenance scheduler LAST: its default tasks close over the
        # slow-path engine, commit plane and audit plane above
        # (datapath/maintenance.py — the ONE background plane).
        self._init_maintenance(maint_budget=maint_budget,
                               maint_clock=maint_clock)
        # Tenancy plane (datapath/tenancy.py): pure host-side registry —
        # an engine without tenant worlds serves bit-identically.
        self._init_tenancy()
        # Serving batcher (serving/batcher.py): canonical-shape admission
        # in front of the jitted step.  Off (the default) the plane is
        # never touched and step() stays bit-identical; knobs apply when
        # the batcher materializes (eagerly with serving_batcher=True,
        # lazily on first step_tenants).
        self._init_serving(serving_batcher,
                           canonical_sizes=canonical_sizes,
                           flush_depth=flush_depth,
                           flush_deadline=flush_deadline,
                           ring_slots=serving_ring_slots)

    # -- placement hooks (overridden by the mesh engine, parallel/meshpath) --

    def _init_pipeline_state(self, flow_slots: int, aff_slots: int):
        """Fresh pipeline state on the engine's device layout (the mesh
        engine returns the (D,)-leading sharded placement instead)."""
        return pl.init_state(flow_slots, aff_slots,
                             key_words=10 if self._dual_stack else 4)

    def _place_rules(self, cps):
        """Compile -> device rule tensors + match meta on this engine's
        layout (mesh engine: word-axis padding + sharded placement).
        Tenant worlds interpose entry-axis rung padding between the host
        build and device placement (datapath/tenancy._pad_tables — a
        no-op on the default world, preserving the untenanted pytree
        bit-for-bit)."""
        with self._commit_span("tables"):
            host, match_meta = to_host(cps, delta_slots=self._delta_slots,
                                       prune_budget=self._prune_budget)
            host = self._pad_tables(host)
        drs = self._upload_tables(
            lambda t: jax.tree_util.tree_map(jnp.asarray, t), host)
        return drs, placed_meta(match_meta, drs)

    def _upload_tables(self, place, host_tables):
        """`place(host_tables)` -> the tables on the device, waited for: the
        commit transaction's `upload` sub-span and its `table_bytes`
        (observability/tracing.COMMIT_SUBSPANS).  The bytes are those the
        devices hold (a replicated table counts once a replica).  The
        constructor's boot tables come through here too, outside any
        transaction: nothing is recorded then."""
        with self._commit_span("upload") as span:
            placed = jax.block_until_ready(place(host_tables))
            span.nbytes = sum(
                shard.data.nbytes for x in jax.tree_util.tree_leaves(placed)
                for shard in x.addressable_shards)
        return placed

    def _place_services(self, dsvc: pl.DeviceServiceTables):
        """Device service-table placement hook (mesh engine: replicated
        NamedSharding on the mesh)."""
        return dsvc

    def _place_forwarding(self, dft: fwd.DeviceForwardingTables):
        """Forwarding-table placement hook (mesh engine: replicated on
        the mesh, like the service tables — forwarding is the small,
        read-mostly side and shards trivially over data)."""
        return dft

    # -- Datapath ------------------------------------------------------------

    @property
    def datapath_type(self) -> DatapathType:
        return DatapathType.TPUFLOW

    @property
    def generation(self) -> int:
        return self._gen

    def _install_bundle_impl(self, ps=None, services=None) -> int:
        # Compile stage of the commit plane (datapath/commit.py): the plane
        # owns canary gating, rollback, and settle-time persistence; this
        # impl compiles and swaps only.
        # Compile-before-assign (the install_topology convention): the
        # service tables compile from the STAGED list first, and
        # self._services/_dsvc commit only after every compile in the
        # bundle has succeeded — a rejected bundle leaves spec and device
        # tables consistent on the previous value.  The staged list also
        # feeds the rule compile: toServices lowering is service-indexed
        # (compiler svcref_ranges), so rules in this bundle must see the
        # NEW service view.
        staged = list(services) if services is not None else None
        staged_dsvc = None
        if staged is not None:
            staged_dsvc = self._upload_tables(
                lambda st: self._place_services(pl.svc_to_device(st)),
                compile_services(staged, node_ips=self._node_ips,
                                 node_name=self._node_name))
        if ps is not None:
            old_in = self._cps.ingress.rule_ids
            old_out = self._cps.egress.rule_ids
            old_bits = self._meta.rule_bits_in
            self._ps = ps
            self._compile_rules(services=staged)
            # Cached flow-entry attribution follows rule IDENTITY across the
            # renumbering bundle: remap stored indices old->new by stable
            # rule id; vanished rules lose attribution (the oracle twin
            # applies the same identity rule in PipelineOracle.update, so
            # stats/l7 attribution of established hits cannot drift).
            self._remap_cached_attribution(old_in, old_out, old_bits)
        elif staged is not None and self._cps.has_svcref:
            # Service-only bundle under toServices rules: reference
            # indices shift with the service list — recompile rules (ids
            # unchanged, so no attribution remap is needed).
            self._compile_rules(services=staged)
        if staged is not None:
            self._services = staged
            self._dsvc = staged_dsvc
        self._gen += 1
        if self._slowpath is not None:
            # Revalidation plane: the swap marks the cache epoch stale;
            # stale-gen denials die lazily (lookup gen compare) and their
            # slots are reclaimed by the next drain's revalidation pass —
            # established entries survive, nothing is flushed.
            self._slowpath.mark_stale(self._gen)
        return self._gen

    def _remap_cached_attribution(self, old_in: list, old_out: list,
                                  old_bits: int) -> None:
        """`old_bits`: the packed column's split under the OLD rule set
        (pl.rule_split); the rewrite packs under the new one."""
        if (list(old_in) == list(self._cps.ingress.rule_ids)
                and list(old_out) == list(self._cps.egress.rule_ids)):
            return  # same ids in the same order: nothing to rewrite
        new_in = {rid: i for i, rid in enumerate(self._cps.ingress.rule_ids)}
        new_out = {rid: i for i, rid in enumerate(self._cps.egress.rule_ids)}

        def remap_arr(old_ids: list, new_pos: dict) -> np.ndarray:
            # Index space is the STORED +1 encoding: 0 = no attribution.
            arr = np.zeros(len(old_ids) + 1, np.int32)
            for i, rid in enumerate(old_ids):
                pos = new_pos.get(rid, -1) if rid else -1
                arr[i + 1] = pos + 1 if pos >= 0 else 0
            return arr

        r_in = jnp.asarray(remap_arr(old_in, new_in))
        r_out = jnp.asarray(remap_arr(old_out, new_out))
        meta = self._state.flow.meta
        _, _, RC, _ = pl._meta_cols(self._meta.key_words - 2)
        # Ellipsis indexing: the rules column is the trailing axis both on
        # the single-chip (slots+1, 4) layout and the mesh engine's
        # (D, slots+1, 4) sharded layout.
        # Stored indices are the +1 encoding on both sides of the remap.
        vi, vo = (jnp.clip(v + 1, 0, r.shape[0] - 1) for v, r in zip(
            pl._unpack_rules(meta[..., RC], old_bits), (r_in, r_out)))
        self._state = self._state._replace(flow=self._state.flow._replace(
            meta=meta.at[..., RC].set(pl._pack_rules(
                r_in[vi] - 1, r_out[vo] - 1, self._meta.rule_bits_in))
        ))
        self._state_mutations += 1

    def _apply_group_delta_impl(self, group_name, added_ips, removed_ips) -> int:
        # Incremental compile stage of the commit plane: the plane snapshots
        # the retained generation first, so a delta that throws mid-apply
        # (bad member string, compile fault) is rolled back to a no-op
        # instead of leaving tensors half-mutated.
        gids = self._name_gids.get(group_name, [])
        if not gids and group_name not in self._group_members:
            raise KeyError(f"unknown group {group_name!r}")
        rows: list[tuple[tuple[int, int], int, int]] = []  # (range, gid, sign)
        own = self._group_members.setdefault(group_name, Counter())
        ranges_before = self._ranges_of(group_name)
        # Named-port rules bind membership to per-member port values via
        # synthetic narrowed groups (compiler/ir.resolve_named_ports) whose
        # interned columns a raw-group delta cannot patch — and whose
        # membership can change even when the raw group's merged ranges do
        # not.  With named ports in play every delta is a full resync (the
        # OracleDatapath twin applies the same rule).  v6 members take the
        # SAME O(1) slot path as v4: DeltaTable carries a family-tagged
        # lexicographic lane (ops/match.DeltaTable.fam/lo6_w/hi6_w), so v6
        # pod churn never forces a recompile.
        need_recompile = self._has_named_ports

        for ip in added_ips:
            r = iputil.cidr_to_range(ip)
            if not _contains(self._ranges_of(group_name), r):
                for gid in gids:
                    if not self._covered_by_others(gid, group_name, r):
                        rows.append((r, gid, +1))
            own[ip] += 1
        for ip in removed_ips:
            if own[ip] <= 0:
                continue
            own[ip] -= 1
            if own[ip] == 0:
                del own[ip]
            r = iputil.cidr_to_range(ip)
            residual = self._ranges_of(group_name)
            if _contains(residual, r):
                continue  # another member/block still provides this range
            if _overlaps(residual, r):
                # Partial residual coverage (overlapping CIDR members): a
                # whole-range clear would be wrong — fold via full compile.
                need_recompile = True
                continue
            for gid in gids:
                if self._covered_by_others(gid, group_name, r):
                    continue
                if self._partially_covered_by_others(gid, group_name, r):
                    need_recompile = True
                else:
                    rows.append((r, gid, -1))

        self._sync_ps_members(group_name)
        if not need_recompile and self._ranges_of(group_name) == ranges_before:
            # Net no-op delta (refcount-only re-add, or an add+remove of the
            # same range cancelling within one call): no verdict can differ,
            # so keep the generation — bumping would needlessly invalidate
            # every cached DENY entry — and DISCARD any cancelling rows
            # rather than burn delta slots on them.  The skip condition is
            # "the group's merged range set is unchanged" — the same
            # observable rule OracleDatapath applies, so the differential
            # harness sees identical generations (a changed group whose
            # ranges are covered by sibling groups still bumps, on both).
            return self._gen
        if need_recompile or self._n_deltas + len(rows) > self._delta_slots:
            # Fold everything into a fresh compile (the revalidation event)
            # — membership mirrors are already current.
            self._compile_rules()
        elif rows:
            self._append_deltas(rows)
        self._gen += 1
        if self._slowpath is not None:
            self._slowpath.mark_stale(self._gen)
        # Incremental deltas do NOT rewrite the snapshot (that would turn
        # the O(delta) path into O(total-state) disk I/O per event): the
        # authoritative crash-recovery source for membership churn is the
        # AGENT's filestore replay (filestore.go model); the datapath
        # snapshot catches up on the next bundle commit or checkpoint().
        # The GENERATION is journaled by the commit plane's settle stage
        # (cookie-round append) AFTER the canary certifies this delta.
        return self._gen

    def install_topology(self, topo: Topology) -> None:
        # Compile BEFORE assigning: a rejected topology (overlapping CIDRs,
        # duplicate pods) must leave spec (self._topo, backs trace) and
        # device tables consistent on the previous value.
        ft = compile_topology(topo)
        self._topo = topo
        self._ft = ft
        self._rt = topology.resolve_topology(topo)
        self._dft = self._place_forwarding(fwd.fwd_to_device(ft))
        self._persist_topology()
        # The forwarding tensors changed legitimately: re-anchor the
        # checksum scrub's golden digests (datapath/audit.py).
        self._audit_refresh_golden()

    def _v6_lanes(self, batch: PacketBatch):
        """Batch -> the pipeline's v6 lane tuple on the device (or None)."""
        v6 = self._v6_host(batch)
        return None if v6 is None else tuple(jnp.asarray(x) for x in v6)

    def _v6_host(self, batch: PacketBatch):
        """Batch -> the HOST columns of the pipeline's v6 lane tuple (or
        None).  Dual-stack instances ALWAYS materialize the wide lanes
        (the key layout is static); narrow instances reject v6-carrying
        batches loudly."""
        if not self._dual_stack:
            if batch.has_v6:
                raise ValueError(
                    "batch carries v6 lanes but this datapath is v4-only; "
                    "construct it with dual_stack=True"
                )
            return None
        B = batch.size
        if batch.src_ip6 is None:
            z = iputil.flip_u32(np.zeros((B, 4), np.uint32))
            return (z, z, np.zeros(B, np.int32))
        return (iputil.flip_u32(batch.src_ip6),
                iputil.flip_u32(batch.dst_ip6), batch.is6)

    def _upload(self, x):
        """Host column -> device array (None passes), counted by the
        step tracer where the transfer is issued."""
        return None if x is None else jnp.asarray(self._steptrace.uploaded(x))

    def _upload_i32(self, v: int):
        """A scalar argument of the step: a transfer (and a tiny device
        program) of its own, counted like the columns."""
        return jnp.int32(self._steptrace.uploaded(np.int32(v)))

    def step(self, batch: PacketBatch, now: int, *, valid=None) -> StepResult:
        # The `step` span: its end - start is the ONE clock pair that
        # feeds step_hist and the telemetry fold below.
        tr = self._steptrace
        tr.begin(batch.size)
        try:
            # Traffic time drives the maintenance tick clock (one clock
            # domain: flow-cache aging and FQDN expiry stamp with THIS
            # now).
            self._maintenance.observe(now)
            if self._realization is not None:
                # First-hit latch (realization tracing): the first LIVE
                # batch classified under a new bundle generation closes
                # its spans.  One int compare per step after the latch;
                # host-side only, so the compiled step HLO is
                # bit-identical with tracing off.
                self._realization.first_hit(self._gen, batch.size)
            return self._step(batch, now, valid=valid)
        finally:
            # Close the span FIRST: a raising _step leaves a closed
            # record in the ring, whatever the folds below do.
            dt = tr.end()
            self.step_hist.observe(dt)
            if self._telemetry is not None:
                # Fold the SAME wall seconds into every (scope, regime)
                # the batch classified under (_telemetry_account queued
                # them during _step).
                self._telemetry.observe_step(dt)

    def _step(self, batch: PacketBatch, now: int, valid=None) -> StepResult:
        tr = self._steptrace
        # ---- stage: the host columns, numpy only ---------------------------
        tr.phase(SP_STAGE)
        # One materialization of the per-lane byte lengths, clamped
        # (negative pkt_len must never decrement a monotonic counter).
        lens = np.maximum(batch.lens(), 0)
        flags = batch.flags()
        cols = (iputil.flip_u32(batch.src_ip), iputil.flip_u32(batch.dst_ip),
                batch.proto.astype(np.int32),
                batch.src_port.astype(np.int32),
                batch.dst_port.astype(np.int32), batch.in_ports())
        # Only materialize the ARP lane when the batch carries ARP —
        # pure-IP batches keep the round-3 compiled program.
        arp = batch.arp_ops() if batch.arp_op is not None else None
        v6 = self._v6_host(batch)
        if batch.is6 is not None:  # a narrow engine was handed none set
            tr.v6_lanes = int(np.count_nonzero(batch.is6))
        # Serving-batcher padding mask: padded lanes ride the spoof
        # discipline (no state commit / miss admission / counters); None
        # traces the identical program, so the unbatched path stays
        # HLO-bit-identical.
        vmask = None if valid is None else np.asarray(valid, bool)

        # ---- upload: one transfer per column, counted where issued ---------
        tr.phase(SP_UPLOAD)
        up = self._upload
        args = [up(c) for c in cols]
        args += [self._upload_i32(now), self._upload_i32(self._gen),
                 up(flags), up(arp), up(lens if self._flow_stats else None)]
        v6 = None if v6 is None else tuple(up(x) for x in v6)
        vmask = up(vmask)

        # ---- dispatch: the jitted call until it returns, and the egress
        # copies started behind it -------------------------------------------
        tr.phase(SP_DISPATCH)
        state, rec, rest = fwd.pipeline_step_full_packed(
            self._state, self._drs, self._dsvc, self._dft, *args,
            meta=self._meta_step, v6=v6, valid=vmask,
        )
        self._state = state
        self._state_mutations += 1
        fwd.start_egress_copies(rec, rest)
        # ---- wait: the host waiting for the device.  The first fetch
        # below would wait for the whole executable anyway; this only
        # tells the waiting from the copies. --------------------------------
        tr.phase(SP_WAIT)
        jax.block_until_ready((rec, rest))
        # ---- fetch: the record's three blocks land (one copy each, and one
        # per optional output beside them); the fields are row views ---------
        tr.phase(SP_FETCH)
        blocks, rest = fwd.fetch_egress(rec, rest, tr.fetched)
        o = {**fwd.unpack_egress(*blocks), **rest}
        # ---- account: counters, admission, telemetry, per-rule stats -------
        tr.phase(SP_ACCOUNT)
        tr.n_miss = int(o["n_miss"])
        tr.round_lanes = int(o["round_lanes"])
        self._evictions += int(o["n_evict"])
        self._prune_account(o)
        pending = None
        if self._async:
            # Admit the fast step's miss lanes to the bounded queue (the
            # upcall handoff); their outputs carry the provisional
            # admission verdict (miss_code) until a drain classifies the
            # flow.  Overflowed admissions are counted, never blocked on.
            # Tenant worlds: the admission mask is clamped to the
            # tenant's in-queue quota and the queued rows carry the
            # tenant id, so drains classify them in their owner's world
            # (datapath/tenancy — both are no-ops on the default world).
            pending = o["miss"]
            admitted, _dropped = self._slowpath.admit(
                self._queue_cols(batch, flags, lens,
                                 tenant=self._tenant_id()),
                self._tenant_admit_mask(pending != 0), now,
            )
            self._tenant_note_admitted(admitted, _dropped)
        # Telemetry AFTER the admission block: sheds this batch just
        # caused (early-drop / source-limit / overflow) classify IT as
        # attack-shed, not the next one.
        self._telemetry_account(o, batch.size)
        in_ids = self._cps.ingress.rule_ids
        out_ids = self._cps.egress.rule_ids
        self._count_metrics(o, in_ids, out_ids, lens, pending=pending)
        if self._deny is not None:
            self._deny_verdicts(batch, o["code"], pending, now)

        # ---- attribute: rule ids and the StepResult ------------------------
        tr.phase(SP_ATTRIBUTE)
        unflip = iputil.unflip_u32_array

        # peer_f / peer_w are zeroed for non-deliverable lanes in the
        # kernel; the (kind==TUNNEL & deliverable) gate avoids un-flipping
        # that 0 (and reports 0, not the mapped-zero key).
        tunnel = (o["fwd_kind"] == FWD_TUNNEL) & (o["out_port"] != -1)
        # A dual-stack engine hands the wide word rows on as they landed:
        # the per-lane key lists are built when first read
        # (interface.WideStepResult), never here.
        result = StepResult
        if self._dual_stack:
            result = functools.partial(WideStepResult, o["dnat_w_f"],
                                       o["peer_w"], tunnel)

        res = result(
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
            n_miss=tr.n_miss,
            spoofed=o["spoofed"],
            punt=o["punt"],
            mcast_idx=o["mcast_idx"],
            l7_redirect=o["l7_redirect"],
            fwd_kind=o["fwd_kind"],
            out_port=o["out_port"],
            peer_ip=np.where(tunnel, unflip(o["peer_f"]), 0).astype(np.uint32),
            dec_ttl=o["dec_ttl"],
            tc_act=o["tc_act"],
            tc_port=o["tc_port"],
        )
        tr.phase(SP_DONE)
        return res

    def stats(self) -> DatapathStats:
        return DatapathStats(
            ingress=dict(self._stats_in),
            egress=dict(self._stats_out),
            ingress_bytes=dict(self._bytes_in),
            egress_bytes=dict(self._bytes_out),
            default_allow=self._default_allow,
            default_deny=self._default_deny,
        )

    def dump_flows(self, now: int) -> list[dict]:
        """Live flow-cache entries decoded to host dicts — the conntrack
        dump the reference's flow exporter polls
        (pkg/agent/flowexporter/connections/conntrack_linux.go).  'Live' =
        within the idle timeout; reply-direction entries carry reply=True
        and their un-DNAT frontend in dnat_ip/dnat_port."""
        return self._dump_flows_state(self._state, now)

    def _dump_flows_state(self, state: pl.PipelineState, now: int) -> list[dict]:
        """dump_flows over an explicit state pytree (the mesh engine calls
        this once per data shard with the shard's local slice)."""
        flow = state.flow
        keys = np.asarray(flow.keys)[:-1].astype(np.int64)
        meta = np.asarray(flow.meta)[:-1].astype(np.int64)
        ts = np.asarray(flow.ts)[:-1]
        # 64-bit volumes from the two i32 limbs (FlowCache docstring): the
        # low limb's U32 view plus the carry limb shifted up.
        pkts = (np.asarray(flow.pkts)[:-1].astype(np.uint32).astype(np.int64)
                + (np.asarray(flow.pkts_hi)[:-1].astype(np.int64) << 32))
        octets = (np.asarray(flow.octets)[:-1].astype(np.uint32).astype(np.int64)
                  + (np.asarray(flow.octets_hi)[:-1].astype(np.int64) << 32))
        A = self._meta.key_words - 2
        DC, M1C, RC, ZC = pl._meta_cols(A)
        kpg = keys[:, A + 1]
        live, entry_gen = self._live_mask(keys, meta, ts, now)
        out = []

        def unflip_ip(v: int) -> str:
            return iputil.u32_to_ip(iputil.unflip_u32(v))

        def wide_ip(row) -> str:
            """4 flipped word lanes -> address string (mapped form = v4)."""
            w = [iputil.unflip_u32(int(x)) for x in row]
            v = (w[0] << 96) | (w[1] << 64) | (w[2] << 32) | w[3]
            if (v >> 32) == 0xFFFF:
                return iputil.u32_to_ip(v & 0xFFFFFFFF)
            return iputil.key_to_ip(iputil.V6_OFF + v)

        for i in np.nonzero(live)[0]:
            pg = int(kpg[i])
            gen = (pg >> 9) & pl.GEN_ETERNAL
            # Shared bit-layout decoders (single source of truth with the
            # kernel's row packing); wide worlds decode word quadruples.
            code, svc_idx, dnat_port = pl._unpack_meta1(int(meta[i, M1C]))
            rule_in, rule_out = pl._unpack_rules(
                int(meta[i, RC]), self._meta.rule_bits_in)
            if A == 2:
                src, dst = unflip_ip(keys[i, 0]), unflip_ip(keys[i, 1])
                dnat = unflip_ip(meta[i, DC])
            else:
                src, dst = wide_ip(keys[i, 0:4]), wide_ip(keys[i, 4:8])
                dnat = wide_ip(meta[i, 0:4])
            out.append({
                "src": src,
                "dst": dst,
                "sport": (int(keys[i, A]) >> 16) & 0xFFFF,
                "dport": int(keys[i, A]) & 0xFFFF,
                "proto": pg & 0xFF,
                "reply": bool(pg & (1 << 31)),
                "committed": gen == pl.GEN_ETERNAL,
                "code": code,
                "svc_idx": svc_idx,
                "dnat_ip": dnat,
                "dnat_port": dnat_port,
                "ingress_rule": _rid(self._cps.ingress.rule_ids, rule_in),
                "egress_rule": _rid(self._cps.egress.rule_ids, rule_out),
                "last_seen": int(ts[i]),
                # Per-direction traffic volumes (OriginalPackets/
                # OriginalBytes analog); zeros when the FlowExporter gate
                # is off (counting disabled).
                "packets": int(pkts[i]),
                "bytes": int(octets[i]),
            })
        return out

    def mcast_group(self, idx: int) -> Optional[dict]:
        """Resolve a StepResult.mcast_idx to its replication set (the
        MulticastOutput bucket list, ref pkg/agent/openflow/multicast.go)."""
        return topology.mcast_group_of(self._rt, idx)

    def cache_stats(self) -> dict:
        """Flow-cache census + cumulative evictions (weak-#5 surface):
        occupied/committed/denial entry counts, slot count, and live
        entries overwritten by a different tuple since construction."""
        c = {k: int(v) for k, v in pl.cache_stats(self._state).items()}
        c["evictions"] = self._evictions
        c["reclaims"] = self._reclaims
        return c

    # -- aggregated-bitmap prune plane (ops/match round 7) -------------------

    def _emit(self, kind: str, **fields) -> None:
        """Flight-recorder shim (the per-plane literal-kind discipline
        tools/check_events.py greps for)."""
        emit_into(self, kind, **fields)

    def prune_stats(self) -> Optional[dict]:
        """Prune-plane observability (None when prune_budget=0, so the
        scrape surface only exists where the plane does): skip/fallback
        volume, the live K rung, retunes, and the candidate-superblock
        histogram object for the metrics renderer."""
        if self._prune_budget <= 0:
            return None
        return {
            "budget": self._prune_budget,
            "skips_total": self._prune_skips,
            "fallbacks_total": self._prune_fallbacks,
            "classified_total": self._prune_classified,
            "retunes_total": self._prune_retunes,
            "autotune": int(self._prune_tuner is not None),
            "hist": self._prune_hist,
        }

    def _prune_account(self, o: dict) -> None:
        """Fold one dispatch's prune counters (pipeline output keys, which
        exist iff prune_budget > 0; (D,)-vector shaped on the mesh) into
        the plane's meters and feed the K autotuner one decision point."""
        if self._prune_budget <= 0 or "n_prune_skips" not in o:
            return
        self._prune_skips += int(np.asarray(o["n_prune_skips"]).sum())
        fb = int(np.asarray(o["n_prune_fb"]).sum())
        self._prune_fallbacks += fb
        hist = np.asarray(o["prune_cand_hist"], np.int64)
        hist = hist.reshape(-1, len(PRUNE_HIST_BOUNDS) + 2).sum(axis=0)
        self._prune_hist.add_counts(hist[:-1], float(hist[-1]))
        classified = int(hist[:-1].sum())
        self._prune_classified += classified
        # The K autotuner observes DEFAULT-world evidence only: a retune
        # is a meta swap, and a tenant world's swapped-in meta must not
        # diverge the engine-wide K bookkeeping (tenant worlds inherit
        # the engine's budget at their next compile).
        if self._prune_tuner is not None and self._active_tenant is None:
            new = self._prune_tuner.observe(classified, fb)
            if new != self._prune_budget:
                self._retune_prune(new)

    def _retune_prune(self, budget: int) -> None:
        """Swap the prune K rung: a META-only change (the aggregate tables
        are K-independent), so jit caches one classify/step variant per
        ladder rung and retuning can never trigger a recompile storm.
        Journaled as `prune-retune` — the autotune analog for this plane."""
        old, self._prune_budget = self._prune_budget, int(budget)
        mm = self._meta.match._replace(prune_budget=self._prune_budget)
        self._meta = self._meta._replace(match=mm)
        self._meta_step = self._meta_step._replace(match=mm)
        self._prune_retunes += 1
        self._emit("prune-retune", budget_from=int(old),
                   budget_to=int(self._prune_budget),
                   fallbacks_total=int(self._prune_fallbacks),
                   classified_total=int(self._prune_classified))

    # -- async slow path (datapath/slowpath engine callbacks) ----------------
    # (drain_slowpath / dump_miss_queue / slowpath_stats live on the
    # Datapath base; only the classify/scan callbacks are per-engine.)

    def _drain_meta(self, chunk: int) -> pl.PipelineMeta:
        """The drain-step meta for one coalesced chunk rung: a single
        slow-path round (miss_chunk == chunk) with the fused
        eviction+aging commit pass (drain_reclaim)."""
        return self._meta._replace(miss_chunk=int(chunk), drain_reclaim=True)

    def _drain_classify(self, block: dict, now: int):
        """Classify + commit one popped queue block through the coalesced
        drain step (ONE slow-path round at miss_chunk == the engine's
        current chunk rung, the fused consumer fed a full batch) and
        publish the new cache state — the epoch-swap commit.  Padding
        lanes ride masked out via `valid` (they neither refresh nor
        commit, like SpoofGuard lanes).

        Overlapped mode (overlap_commits): the step is dispatched with
        the state DONATED (pl.pipeline_step_donated — XLA aliases the
        commit scatters in place instead of copying the cache columns)
        and the new state pytree published immediately, which is the
        lost-update guard: batch N+1's lookups consume these arrays as a
        data dependency.  The host-side materialization of the OUTPUTS
        (metrics, eviction accounting) is returned as a deferred
        finalizer for the engine's two-slot staging; a flow whose packets
        re-missed before this commit landed is simply re-enqueued and
        re-classified — idempotent by the deterministic endpoint hash.

        Tenant rows (datapath/tenancy): a popped block carrying tenant
        ids partitions per tenant and each sub-block classifies inside
        its owner's world — zero cost without tenant worlds."""
        split = self._tenant_drain_split(block)
        if split is not None:
            return self._tenant_drain_dispatch(split, now)
        t0 = time.perf_counter()
        # Scope captured at DISPATCH time: a deferred finalize must fold
        # under the tenant world that classified it, not whichever world
        # is active when the staged commit lands.
        tel_tid = self._tenant_id() if self._telemetry is not None else 0
        k = len(block["src_ip"])
        D = self._slowpath.drain_batch
        if k > D:
            # An explicit begin_drain(n > drain_batch) popped a wider
            # block: pad to the next power-of-two rung so the whole
            # block classifies (bounded compile variants) instead of
            # overflowing the drain_batch-sized lanes and losing the
            # already-popped rows.
            D = 1 << (k - 1).bit_length()

        def pad(col, dtype=np.int32):
            out = np.zeros(D, dtype)
            out[:k] = np.asarray(col)[:k].astype(dtype)
            return out

        src = pad(block["src_ip"], np.uint32)
        dst = pad(block["dst_ip"], np.uint32)
        proto = pad(block["proto"])
        sport = pad(block["src_port"])
        dport = pad(block["dst_port"])
        flags = pad(block["flags"])
        lens = np.maximum(pad(block["lens"]), 0)
        valid = np.arange(D) < k
        # Same no-commit gating the synchronous walk applies
        # (models/forwarding.py): multicast misses classify-but-never-cache,
        # and a FIN/RST-flagged TCP miss never establishes.
        no_commit = pl.no_commit_mask(dst, proto, flags)
        step_fn = (pl.pipeline_step_donated if self._overlap
                   else pl.pipeline_step)
        state, out = step_fn(
            self._state,
            self._drs,
            self._dsvc,
            jnp.asarray(iputil.flip_u32(src)),
            jnp.asarray(iputil.flip_u32(dst)),
            jnp.asarray(proto),
            jnp.asarray(sport),
            jnp.asarray(dport),
            jnp.int32(now),
            jnp.int32(self._gen),
            meta=self._drain_meta(D),
            valid=jnp.asarray(valid),
            no_commit=jnp.asarray(no_commit),
            flags=jnp.asarray(flags),
            lens=jnp.asarray(lens) if self._flow_stats else None,
        )
        self._state = state
        self._state_mutations += 1
        # Attribution tables captured at DISPATCH time: a bundle swap that
        # lands while this commit is staged must not remap the verdicts
        # this drain actually classified under.
        in_ids = self._cps.ingress.rule_ids
        out_ids = self._cps.egress.rule_ids

        def finalize():
            o = {key: np.asarray(v) for key, v in out.items()}
            self._evictions += int(o["n_evict"])
            self._reclaims += int(o["n_reclaim"])
            self._prune_account(o)
            # Each queued packet's REAL attribution counts exactly once,
            # here (its fast-step image was provisional and uncounted).
            sel = valid
            self._count_metrics(
                {key: o[key][sel]
                 for key in ("code", "ingress_rule", "egress_rule")},
                in_ids, out_ids, lens[sel],
            )
            if self._telemetry is not None:
                # A drain is its own dispatch, not a traffic batch: fold
                # its counters and its dispatch-to-materialization wall
                # seconds straight into the "drain" regime (the fifth
                # regime classify_regime never produces).
                self._telemetry.account(o)
                dt = time.perf_counter() - t0
                self._telemetry.observe_scoped("engine", "drain", dt)
                if tel_tid:
                    self._telemetry.observe_scoped(
                        f"tenant:{tel_tid}", "drain", dt)

        if self._overlap:
            return finalize
        finalize()
        return None

    def _epoch_maintain(self, now: int) -> tuple[int, int]:
        """Fused aging + stale-generation revalidation: ONE pass over the
        cache (pl.maintain_scan) where the engine used to run two."""
        state, n_aged, n_stale = pl.maintain_scan(
            self._state, jnp.int32(now), jnp.int32(self._gen),
            timeouts=self._meta.timeouts,
        )
        self._state = state
        self._state_mutations += 1
        return int(n_aged), int(n_stale)

    def _epoch_revalidate(self) -> int:
        state, n = pl.revalidate_scan(self._state, jnp.int32(self._gen))
        self._state = state
        self._state_mutations += 1
        return int(n)

    def _epoch_age_scan(self, now: int) -> int:
        state, n = pl.age_scan(self._state, jnp.int32(now),
                               timeouts=self._meta.timeouts)
        self._state = state
        self._state_mutations += 1
        return int(n)

    # -- commit plane hooks (datapath/commit.py) ------------------------------

    def _commit_snapshot(self, group: Optional[str] = None) -> dict:
        """The retained last-known-good generation: every attribute a
        bundle/delta commit can touch.  Device tensors and compiled
        products are immutable (replaced wholesale, never mutated), so
        they snapshot by reference; host-side membership bookkeeping and
        the in-place-mutated group member lists are copied.  `state`
        covers the flow-cache attribution remap a bundle performs
        (_remap_cached_attribution) — restoring the reference restores the
        pre-remap attribution exactly (no traffic steps mid-transaction).

        `group` scopes a DELTA snapshot to the touched group — the delta
        path mutates in place only that group's Counter and member lists
        (everything else is replaced wholesale, even on an overflow
        recompile), so copying all membership mirrors would turn the
        O(delta) path into O(total-membership) host work.  Rows the failed
        delta wrote into `_delta_host` past the restored `n_deltas` are
        dead (the kernel gates on n) and overwritten by the next append."""
        if group is None:
            ps_members = [
                (g, list(g.members))
                for table in (self._ps.address_groups,
                              self._ps.applied_to_groups)
                for g in table.values()
            ]
            group_members = {k: Counter(v)
                             for k, v in self._group_members.items()}
            delta_host = {k: v.copy() for k, v in self._delta_host.items()}
            touched = None
        else:
            ps_members = [
                (g, list(g.members))
                for g in (self._ps.address_groups.get(group),
                          self._ps.applied_to_groups.get(group))
                if g is not None
            ]
            group_members = self._group_members  # dict ref + touched entry
            delta_host = self._delta_host
            own = self._group_members.get(group)
            touched = (group, None if own is None else Counter(own))
        return {
            "gen": self._gen,
            "ps": self._ps,
            "ps_members": ps_members,
            "services": self._services,
            "cps": self._cps,
            "drs": self._drs,
            "dsvc": self._dsvc,
            "meta": self._meta,
            "meta_step": self._meta_step,
            "state": self._state,
            "has_named_ports": self._has_named_ports,
            "n_deltas": self._n_deltas,
            "delta_host": delta_host,
            "name_gids": self._name_gids,
            "gid_ident": self._gid_ident,
            "group_members": group_members,
            "touched": touched,
            "static_blocks": self._static_blocks,
            "member_meta": (self._member_meta if group is not None else
                            {k: dict(v) for k, v in self._member_meta.items()}),
        }

    def _commit_restore(self, snap: dict) -> None:
        self._gen = snap["gen"]
        self._ps = snap["ps"]
        for g, members in snap["ps_members"]:
            g.members = members
        self._services = snap["services"]
        self._cps = snap["cps"]
        self._drs = snap["drs"]
        self._dsvc = snap["dsvc"]
        self._meta = snap["meta"]
        self._meta_step = snap["meta_step"]
        # A prune retune between snapshot and restore must not leave the
        # K bookkeeping diverged from the restored metas — and the
        # autotuner must be RE-SEEDED at the restored rung, or its stale
        # index would silently retune back to the pre-rollback rung on
        # the next dispatch with no fresh fallback-rate evidence.
        self._prune_budget = snap["meta"].match.prune_budget
        if self._prune_tuner is not None:
            self._prune_tuner = PruneAutotuner(self._prune_budget)
        self._state = snap["state"]
        self._has_named_ports = snap["has_named_ports"]
        self._n_deltas = snap["n_deltas"]
        self._delta_host = snap["delta_host"]
        self._name_gids = snap["name_gids"]
        self._gid_ident = snap["gid_ident"]
        self._group_members = snap["group_members"]
        if snap["touched"] is not None:
            name, ctr = snap["touched"]
            if ctr is None:
                self._group_members.pop(name, None)
            else:
                self._group_members[name] = ctr
        self._static_blocks = snap["static_blocks"]
        self._member_meta = snap["member_meta"]
        self._state_mutations += 1

    def _canary_classify(self, batch: PacketBatch, now: int) -> np.ndarray:
        """Fresh-walk verdict of each probe through the CURRENT compiled
        tables, state untouched.  Runs EAGERLY (unjitted): the canary
        fires on every commit and rule-table shapes change per bundle, so
        a jitted probe would pay an XLA compile per install; eager
        execution walks the same compiled TABLES, which is what the
        canary certifies.  Narrow (v4-only) instances classify through the
        bare match kernel — probes avoid service frontends, so the
        ServiceLB/cache stages of the trace walk certify nothing and
        would only tax the delta path's latency bound; dual-stack
        instances take the full trace walk (its wide-lane plumbing is the
        part worth certifying there)."""
        src_f = jnp.asarray(iputil.flip_u32(batch.src_ip))
        dst_f = jnp.asarray(iputil.flip_u32(batch.dst_ip))
        proto = jnp.asarray(batch.proto.astype(np.int32))
        dport = jnp.asarray(batch.dst_port.astype(np.int32))
        if not self._dual_stack:
            cls = pl.classify_batch(
                self._drs, src_f, dst_f, proto, dport,
                meta=self._meta.match,
                # The canary certifies the SERVING consumer: a fused
                # instance's probes walk the same pallas consumer the
                # step kernel uses, not the shadow XLA path (the round-8
                # discipline _pipeline_trace already applies for the
                # dual-stack/audit walks below).
                fused=self._meta.fused,
            )
            return np.asarray(cls["code"])
        o = pl._pipeline_trace(
            self._state,
            self._drs,
            self._dsvc,
            src_f,
            dst_f,
            proto,
            jnp.asarray(batch.src_port.astype(np.int32)),
            dport,
            jnp.int32(now),
            jnp.int32(self._gen),
            meta=self._meta,
            v6=self._v6_lanes(batch),
        )
        return np.asarray(o["fresh_code"])

    # -- audit plane hooks (datapath/audit.py) --------------------------------

    def _audit_slots(self) -> int:
        return self._meta.flow_slots

    def _audit_rule_digests(self) -> dict:
        """Checksum-scrub digests of every rule-side mutable device tensor
        group (SCRUB_MANIFEST): the compiled rule set (delta table
        included), the service tables, and the forwarding tables."""
        leaves = jax.tree_util.tree_leaves
        return {
            "drs": pl.tensor_digest(leaves(self._drs)),
            "dsvc": pl.tensor_digest(leaves(self._dsvc)),
            "dft": pl.tensor_digest(leaves(self._dft)),
        }

    def _audit_state_digest(self) -> int:
        """Digest of the state-side tensors (PipelineState: flow cache,
        affinity table, two-limb counters) — pinned by the plane to the
        accounted-mutation counter."""
        return pl.tensor_digest(jax.tree_util.tree_leaves(self._state))

    def _audit_reupload(self) -> None:
        """Rule-side self-heal: rebuild every rule-side device tensor from
        its HOST mirror — the compiled policy set (cps), the committed
        service list, the compiled topology, and the delta-table host
        mirror.  Pure tensor re-uploads: no XLA recompile, no generation
        change, nothing a caller can observe but the healed bytes."""
        drs, _match_meta = self._place_rules(self._cps)
        self._drs = drs
        self._upload_delta_table()
        self._compile_services()
        self._dft = self._place_forwarding(fwd.fwd_to_device(self._ft))

    def _live_mask(self, keys, meta, ts, now):
        """The ONE liveness predicate over decoded (int64) entry rows,
        shared by dump_flows and the audit window: occupied, within the
        per-STATE idle timeout (entry_timeout — a half-open TCP entry past
        its syn lifetime is dead to lookups and must not be dumped or
        audited), AND valid under the current generation (stale-gen
        denials survive in the table after a bundle but are dead to
        lookups — decoding them would resolve their packed rule indices
        against the NEW rule table).  -> (live mask, entry generations)."""
        A = self._meta.key_words - 2
        ZC = pl._meta_cols(A)[3]
        kpg = keys[:, A + 1]
        entry_gen = (kpg >> 9) & pl.GEN_ETERNAL
        gen_w = self._gen % pl.GEN_ETERNAL
        tmo = pl.entry_timeout(
            (meta[:, ZC] >> 29) & 1, kpg & 0xFF, self._meta.timeouts, xp=np
        )
        live = (
            (kpg != 0)
            & ((now - ts) <= tmo)
            & ((entry_gen == pl.GEN_ETERNAL) | (entry_gen == gen_w))
        )
        return live, entry_gen

    def _wide_row_key(self, row) -> int:
        """4 flipped word lanes -> combined-keyspace int (mapped form = v4);
        the int twin of dump_flows' wide_ip decode."""
        w = [iputil.unflip_u32(int(x)) for x in row]
        v = (w[0] << 96) | (w[1] << 64) | (w[2] << 32) | w[3]
        return v & 0xFFFFFFFF if (v >> 32) == 0xFFFF else iputil.V6_OFF + v

    def _audit_window(self, cursor: int, k: int, now: int) -> list[dict]:
        """Decode `k` consecutive flow-cache slots from `cursor` (wrapping)
        into the audit row schema (datapath/audit.AuditPlane._check_rows).
        LIVE entries only, under the same liveness rule as dump_flows
        (occupied, within the per-state idle timeout, valid generation) —
        dead rows are dead to lookups already and carry nothing to
        re-prove.  The window gather runs on device (pl.audit_gather);
        only k rows transfer to the host."""
        N = self._meta.flow_slots
        keys_d, meta_d, ts_d = pl.audit_gather(
            self._state, jnp.int32(cursor % N), window=k)
        return self._decode_audit_rows(keys_d, meta_d, ts_d, now,
                                       lambda i: (cursor + i) % N)

    def _decode_audit_rows(self, keys_d, meta_d, ts_d, now,
                           slot_of) -> list[dict]:
        """Gathered window tensors -> audit row dicts; `slot_of` maps a
        window-relative index to the row's slot id (the mesh engine maps
        to GLOBAL striped slot ids, see parallel/meshpath.py)."""
        keys = np.asarray(keys_d).astype(np.int64)
        meta = np.asarray(meta_d).astype(np.int64)
        ts = np.asarray(ts_d)
        A = self._meta.key_words - 2
        DC, M1C, RC, _ZC = pl._meta_cols(A)
        kpg = keys[:, A + 1]
        live, entry_gen = self._live_mask(keys, meta, ts, now)
        in_ids = self._cps.ingress.rule_ids
        out_ids = self._cps.egress.rule_ids
        aff_tmo = np.asarray(self._dsvc.aff_timeout)
        rows = []
        for i in np.nonzero(live)[0]:
            pg = int(kpg[i])
            code, svc_idx, dnat_port = pl._unpack_meta1(int(meta[i, M1C]))
            rule_in, rule_out = pl._unpack_rules(
                int(meta[i, RC]), self._meta.rule_bits_in)
            if A == 2:
                src = iputil.unflip_u32(int(keys[i, 0]))
                dst = iputil.unflip_u32(int(keys[i, 1]))
                dnat = iputil.unflip_u32(int(meta[i, DC]))
            else:
                src = self._wide_row_key(keys[i, 0:4])
                dst = self._wide_row_key(keys[i, 4:8])
                dnat = self._wide_row_key(meta[i, 0:4])
            rows.append({
                "slot": slot_of(int(i)),
                "src": int(src),
                "dst": int(dst),
                "proto": pg & 0xFF,
                "sport": (int(keys[i, A]) >> 16) & 0xFFFF,
                "dport": int(keys[i, A]) & 0xFFFF,
                "code": code,
                "svc": svc_idx,
                "dnat_ip": int(dnat),
                "dnat_port": dnat_port,
                "rule_in": _rid(in_ids, rule_in),
                "rule_out": _rid(out_ids, rule_out),
                "committed": int(entry_gen[i]) == pl.GEN_ETERNAL,
                "reply": bool(pg & (1 << 31)),
                # Session affinity on the cached program: the fresh
                # re-proof reads the CURRENT affinity table, so divergence
                # on these rows may be drift, not corruption (audit.py
                # counts them outside the degrade trip).
                "aff": bool(0 <= svc_idx < aff_tmo.shape[0]
                            and aff_tmo[svc_idx] > 0),
            })
        return rows

    def _audit_fresh(self, rows: list, now: int) -> list[dict]:
        """Fresh-walk re-proof of audited entries through the CURRENT
        compiled tables — the canary's EAGER `_pipeline_trace` machinery
        (rule-table shapes change per bundle, so a jitted probe would pay
        an XLA compile per install); state untouched."""
        return self._audit_fresh_state(self._state, rows, now)

    def _audit_dsvc(self):
        """Service tables for the audit re-proof — a placement hook: the
        mesh engine substitutes copies on the SERVING mesh when a
        latched tenant world audits against rules still placed on its
        own old mesh (parallel/meshpath._shared_tables)."""
        return self._dsvc

    def _audit_fresh_state(self, state: pl.PipelineState, rows: list,
                           now: int) -> list[dict]:
        """_audit_fresh over an explicit state pytree (the mesh engine
        re-proves each row against its home replica's local slice).

        This function owns the probe SHAPE: the row count varies per scan
        (the denials of a window, split per replica on a mesh), so the
        rows are cycled up to a power-of-two lane count, at least the
        audit window — ONE shape for every cursor scan, a pow2 rung above
        it for a full sweep or a reshard certification — whose kernels the
        eager walk compiles once (commit.pad_probes); only the real lanes
        are returned."""
        if not rows:
            return []
        pkts = [Packet(src_ip=r["src"], dst_ip=r["dst"], proto=r["proto"],
                       src_port=r["sport"], dst_port=r["dport"])
                for r in rows]
        lanes = 1 << (max(len(pkts), self._audit.window) - 1).bit_length()
        batch = PacketBatch.from_packets(pad_probes(pkts, lanes))
        o = pl._pipeline_trace(
            state,
            self._drs,
            self._audit_dsvc(),
            jnp.asarray(iputil.flip_u32(batch.src_ip)),
            jnp.asarray(iputil.flip_u32(batch.dst_ip)),
            jnp.asarray(batch.proto.astype(np.int32)),
            jnp.asarray(batch.src_port.astype(np.int32)),
            jnp.asarray(batch.dst_port.astype(np.int32)),
            jnp.int32(now),
            jnp.int32(self._gen),
            meta=self._meta,
            v6=self._v6_lanes(batch),
        )
        o = {key: np.asarray(v) for key, v in o.items()}
        in_ids = self._cps.ingress.rule_ids
        out_ids = self._cps.egress.rule_ids
        out = []
        for i in range(len(rows)):
            no_ep = bool(o["no_ep"][i])
            if self._dual_stack:
                dnat = self._wide_row_key(o["dnat_w_f"][i])
            else:
                dnat = iputil.unflip_u32(int(o["dnat_ip_f"][i]))
            out.append({
                "code": int(o["fresh_code"][i]),
                "svc": int(o["svc_idx"][i]),
                "dnat_ip": int(dnat),
                "dnat_port": int(o["dnat_port"][i]),
                # SvcReject precedes the policy tables: no attribution for
                # it — the same gating the commit path applied at insert.
                "rule_in": (None if no_ep
                            else _rid(in_ids, int(o["ingress_rule"][i]))),
                "rule_out": (None if no_ep
                             else _rid(out_ids, int(o["egress_rule"][i]))),
            })
        return out

    def _audit_evict(self, slots: list) -> None:
        """Repair divergent entries by eviction (jitted masked key-clear,
        pl.audit_evict) — the flows reclassify lazily on their next
        packet.  Padded to a power-of-two lane count so repeat repairs
        share compiled kernels."""
        n = max(1, len(slots))
        padded = np.full(1 << (n - 1).bit_length(), -1, np.int32)
        padded[:len(slots)] = np.asarray(slots, np.int32)
        state, _n = pl.audit_evict(self._state, jnp.asarray(padded))
        self._state = state
        self._state_mutations += 1

    def _audit_corrupt(self, kind: str, now: Optional[int] = None) -> str:
        """Chaos-tier injection (site f"{name}.cache"): REAL, unaccounted
        damage the audit scan must then detect and repair.  kind "tensor"
        flips one service-table word — the canary-BLIND tensor class
        (canary probes deliberately avoid service frontends), which only
        the checksum scrub can see; any other kind flips a sampled cached
        verdict bit (invisible to fresh-tuple canaries by construction).
        `now` scopes the victim to FULLY-live rows (the _live_mask rule) —
        a flip on an idle-expired row the audit window skips would break
        the site contract that the scan detects its own injection.  The
        mutation counter is deliberately NOT bumped — silent corruption is
        the thing being modeled."""
        if kind == "tensor":
            if int(self._dsvc.ep_port.shape[0]) > 0:
                col = self._dsvc.ep_port
                self._dsvc = self._dsvc._replace(
                    ep_port=col.at[0].set(col[0] ^ 1))
                return "flipped dsvc.ep_port[0] bit 0"
            # No services: flip a word in the (quiescent) delta table — a
            # verdict-inert region no probe can reach, only the scrub.
            d = self._drs.ip_delta
            self._drs = self._drs._replace(ip_delta=d._replace(
                lo_f=d.lo_f.at[0].set(d.lo_f[0] ^ 1)))
            return "flipped drs.ip_delta.lo_f[0] bit 0"
        keys = np.asarray(self._state.flow.keys)[:-1].astype(np.int64)
        kpg = keys[:, -1]
        if now is not None:
            meta_np = np.asarray(self._state.flow.meta)[:-1].astype(np.int64)
            ts_np = np.asarray(self._state.flow.ts)[:-1]
            live, _egen = self._live_mask(keys, meta_np, ts_np, now)
        else:
            gen_w = self._gen % pl.GEN_ETERNAL
            egen = (kpg >> 9) & pl.GEN_ETERNAL
            live = (kpg != 0) & ((egen == pl.GEN_ETERNAL) | (egen == gen_w))
        idx = np.nonzero(live)[0]
        if idx.size == 0:
            return self._audit_corrupt("tensor")
        slot = int(idx[0])
        _, M1C, _, _ = pl._meta_cols(self._meta.key_words - 2)
        m = self._state.flow.meta
        self._state = self._state._replace(flow=self._state.flow._replace(
            meta=m.at[slot, M1C].set(m[slot, M1C] ^ 1)))
        return f"flipped cached verdict bit of slot {slot}"

    def trace(self, batch: PacketBatch, now: int) -> list[dict]:
        """Traceflow analog: per-packet stage observations, state untouched.

        Reports the FRESH pipeline walk (ServiceLB + classifier) for every
        packet plus the cache-lookup overlay; for cache-hit packets the
        effective `code` is the cached one while dnat/rule fields show what
        a fresh walk would decide (a probe, not a replay of commit state).
        """
        if not self._gates.enabled("Traceflow"):
            raise RuntimeError("Traceflow feature gate is disabled")
        return self._trace_batch(self._state, batch, now)

    def _trace_batch(self, state: pl.PipelineState, batch: PacketBatch,
                     now: int) -> list[dict]:
        """trace() over an explicit state pytree (the mesh engine traces
        each packet against its home shard's local slice)."""
        o = pl.pipeline_trace(
            state,
            self._drs,
            self._dsvc,
            jnp.asarray(iputil.flip_u32(batch.src_ip)),
            jnp.asarray(iputil.flip_u32(batch.dst_ip)),
            jnp.asarray(batch.proto.astype(np.int32)),
            jnp.asarray(batch.src_port.astype(np.int32)),
            jnp.asarray(batch.dst_port.astype(np.int32)),
            jnp.int32(now),
            jnp.int32(self._gen),
            meta=self._meta,
            v6=self._v6_lanes(batch),
        )
        o = {k: np.asarray(v) for k, v in o.items()}
        in_ids = self._cps.ingress.rule_ids
        out_ids = self._cps.egress.rule_ids

        from ..compiler.topology import oracle_forward, oracle_spoof

        in_ports = batch.in_ports()
        out = []
        for i in range(batch.size):
            # Forwarding observations via the scalar spec (read-only slow
            # path; identical semantics to the fused kernel — test-enforced
            # via the step() parity suite).  Addresses flow as combined
            # keys (family-agnostic spec).
            p = batch.packet(i)
            if self._dual_stack:
                dnat_u = self._wide_row_key(o["dnat_w_f"][i])
                cached_dnat = self._wide_row_key(o["cached_dnat_w_f"][i])
            else:
                dnat_u = iputil.unflip_u32(o["dnat_ip_f"][i])
                cached_dnat = iputil.unflip_u32(o["cached_dnat_ip_f"][i])
            # Forward-leg destination mirrors step(): non-reply cache hits
            # route by the CACHED entry's DNAT resolution (service updates
            # after commit must not flip the reported forwarding); replies
            # go to their literal dst; misses use the fresh walk.
            if o["reply"][i]:
                eff_dst = p.dst_ip
            elif o["cache_hit"][i]:
                eff_dst = cached_dnat
            else:
                eff_dst = dnat_u
            spoofed = oracle_spoof(self._rt, p.src_ip, int(in_ports[i]))
            f = oracle_forward(self._rt, eff_dst, int(in_ports[i]))
            # Async overlay: is this exact 5-tuple sitting in the miss
            # queue awaiting classification?  (Always False when
            # synchronous — there is no queue.)
            queued = (
                self._slowpath is not None
                and self._slowpath.queue.contains(
                    int(p.src_ip), int(p.dst_ip), int(batch.proto[i]),
                    int(batch.src_port[i]), int(batch.dst_port[i]))
            )
            out.append({
                "queued": queued,
                "cache_hit": bool(o["cache_hit"][i]),
                "est": bool(o["est"][i]),
                "reply": bool(o["reply"][i]),
                "reject_kind": int(o["reject_kind"][i]),
                "snat": int(o["snat"][i]),
                "dsr": int(o["dsr"][i]),
                "svc_idx": int(o["svc_idx"][i]),
                "no_ep": bool(o["no_ep"][i]),
                "dnat_ip": dnat_u,
                "dnat_port": int(o["dnat_port"][i]),
                "egress_code": int(o["egress_code"][i]),
                "egress_rule": _rid(out_ids, int(o["egress_rule"][i])),
                "ingress_code": int(o["ingress_code"][i]),
                "ingress_rule": _rid(in_ids, int(o["ingress_rule"][i])),
                "fresh_code": int(o["fresh_code"][i]),
                "code": int(o["code"][i]),
                "spoofed": spoofed,
                "fwd_kind": f["kind"],
                "out_port": f["out_port"],
            })
        return out

    # -- internals -----------------------------------------------------------

    def _count_metrics(self, o: dict, in_ids: list, out_ids: list,
                       lens=None, pending=None) -> None:
        if not self._gates.enabled("NetworkPolicyStats"):
            return
        # SpoofGuard drops and IGMP punts happen BEFORE the policy tables
        # (stage order) and must not pollute NetworkPolicy metrics.
        spoofed = o.get("spoofed")
        not_spoofed = None if spoofed is None else (spoofed == 0)
        punt = o.get("punt")
        if punt is not None and not_spoofed is not None:
            not_spoofed = not_spoofed & (punt == 0)
        for key, ids, ctr, bctr in (
            ("ingress_rule", in_ids, self._stats_in, self._bytes_in),
            ("egress_rule", out_ids, self._stats_out, self._bytes_out),
        ):
            idx = o[key]
            # Cached entries can carry attribution indices from an older
            # generation (ct_label semantics); clamp to the current table.
            ok = (idx >= 0) & (idx < len(ids))
            vals = idx[ok]
            if vals.size:
                bc = np.bincount(vals, minlength=len(ids))
                # Byte volumes ride the same attribution (pkg/apis/stats
                # bytes counters): weighted bincount over packet lengths.
                bb = (np.bincount(vals, weights=lens[ok],
                                  minlength=len(ids))
                      if lens is not None else None)
                # One fold a DISTINCT rule the step named (tens of
                # thousands where every namespace has rules of its own):
                # plain Python ints from one tolist() a column, never a
                # numpy scalar an iteration.
                hit = np.nonzero(bc)[0]
                counts = bc[hit].tolist()
                volumes = bb[hit].tolist() if bb is not None else None
                for k, r in enumerate(hit.tolist()):
                    rid = ids[r]
                    if rid:
                        ctr[rid] += counts[k]
                        if volumes is not None and volumes[k]:
                            bctr[rid] += int(volumes[k])
        none_mask = (o["ingress_rule"] < 0) & (o["egress_rule"] < 0)
        if not_spoofed is not None:
            none_mask = none_mask & not_spoofed
        if pending is not None:
            # Queue-admitted miss lanes carry a PROVISIONAL verdict; the
            # real one is counted once, at drain time (_drain_classify).
            none_mask = none_mask & (pending == 0)
        self._default_allow += int(((o["code"] == 0) & none_mask).sum())
        self._default_deny += int(((o["code"] != 0) & none_mask).sum())

    def _compile_rules(self, services=None) -> None:
        """services: the service view toServices lowering resolves against
        — None means the currently-committed list; install_bundle passes
        its STAGED list so a mixed bundle compiles consistently."""
        with self._commit_span("rules"):
            self._has_named_ports = any(
                s.port_name
                for p in self._ps.policies for r in p.rules
                for s in r.services
            )
            cps = compile_policy_set(
                self._ps,
                services=self._services if services is None else services,
            )
            # Tenant worlds: pad phase capacities onto pow2 rungs BEFORE
            # the capacity check and placement (datapath/tenancy — no-op
            # on the default world).
            cps = self._pad_cps(cps)
            bits_in = pl.rule_split(cps)
        drs, match_meta = self._place_rules(cps)
        self._cps = cps
        self._drs = drs
        self._meta = pl.PipelineMeta(
            match=match_meta,
            flow_slots=self._pipe_kw["flow_slots"],
            aff_slots=self._pipe_kw["aff_slots"],
            ct_timeout_s=self._pipe_kw["ct_timeout_s"],
            miss_chunk=self._pipe_kw["miss_chunk"],
            ct_syn_timeout_s=self._pipe_kw["ct_syn_timeout_s"],
            ct_other_new_s=self._pipe_kw["ct_other_new_s"],
            ct_other_est_s=self._pipe_kw["ct_other_est_s"],
            fused=self._pipe_kw["fused"],
            key_words=10 if self._dual_stack else 4,
            count_flow_stats=self._flow_stats,
            second_chance=bool(self._pipe_kw["second_chance"]),
            telemetry=bool(self._pipe_kw["telemetry"]),
            rule_bits_in=bits_in,
        )
        # Async-mode step/drain variants of the meta: the FAST step
        # compiles the whole slow path out (defer_misses — misses keep the
        # admission policy's provisional image, models/pipeline miss_code)
        # and the DRAIN step classifies one coalesced queue batch in a
        # SINGLE slow-path round (miss_chunk == drain_batch), amortizing
        # the per-round fixed costs; drain_reclaim
        # fuses the aging/revalidation of touched rows into its commit
        # pass (round 6).  With the autotuner on, drain chunks move on a
        # closed rung ladder — _drain_meta derives the per-rung meta on
        # demand (PipelineMeta is a hashable NamedTuple, so jit caches
        # one compiled drain variant per rung, never a recompile storm).
        if self._async:
            self._meta_step = self._meta._replace(
                defer_misses=True,
                miss_code=(ACT_DROP
                           if self._slowpath.admission == ADMIT_HOLD
                           else ACT_ALLOW),
            )
        else:
            self._meta_step = self._meta
        # Reset incremental bookkeeping: the compile folded all prior deltas.
        D = self._delta_slots
        self._n_deltas = 0
        self._delta_host = {
            "lo_f": np.full(D, 2**31 - 1, np.int32),
            "hi_f": np.full(D, -(2**31), np.int32),
            "sign": np.zeros(D, np.int32),
            "iso": np.zeros(D, np.int32),
            "at_in": np.zeros((D, match_meta.w_in), np.uint32),
            "peer_in": np.zeros((D, match_meta.w_in), np.uint32),
            "at_out": np.zeros((D, match_meta.w_out), np.uint32),
            "peer_out": np.zeros((D, match_meta.w_out), np.uint32),
            "fam": np.zeros(D, np.int32),
            "lo6_w": np.full((D, 4), 2**31 - 1, np.int32),
            "hi6_w": np.full((D, 4), -(2**31), np.int32),
        }
        self._name_gids: dict[str, list[int]] = {}
        self._gid_ident = dict(cps.gid_ident)
        for gid, (_kind, names, _static) in self._gid_ident.items():
            for n in names:
                self._name_gids.setdefault(n, []).append(gid)
        # Membership mirrors for coverage checks and overflow recompiles.
        # Counter of member ip/cidr STRINGS (refcounted: two pods may share
        # an IP transiently); per-group static ipBlocks tracked separately
        # (they change only via install_bundle).
        self._group_members: dict[str, Counter] = {}
        self._static_blocks: dict[str, list[tuple[int, int]]] = {}
        # Exemplar GroupMember per (group, ip) so _sync_ps_members rebuilds
        # full members (node/namespace/name intact), not ip-only husks.
        self._member_meta: dict[str, dict[str, GroupMember]] = {}
        for name, g in self._ps.address_groups.items():
            c = Counter()
            meta = self._member_meta.setdefault(name, {})
            for m in g.members:
                c[m.ip] += 1
                meta.setdefault(m.ip, m)
            self._group_members[name] = c
            blocks: list[tuple[int, int]] = []
            for b in g.ip_blocks:
                blocks.extend(iputil.ipblock_to_ranges(b.cidr, b.excepts))
            self._static_blocks[name] = blocks
        for name, g in self._ps.applied_to_groups.items():
            meta = self._member_meta.setdefault(name, {})
            for m in g.members:
                meta.setdefault(m.ip, m)
            if name in self._group_members:
                continue  # same-named AddressGroup => same selector/members
            c = Counter()
            for m in g.members:
                c[m.ip] += 1
            self._group_members[name] = c

    def _compile_services(self) -> None:
        self._dsvc = self._place_services(pl.svc_to_device(compile_services(
            self._services, node_ips=self._node_ips, node_name=self._node_name
        )))

    def _compile_topology(self) -> None:
        # Atomic swap, like rule bundles: the next step() sees either the
        # old or the new forwarding tables, never a mix.  The host copy
        # backs trace() (slow-path observability, scalar spec functions).
        self._ft = compile_topology(self._topo)
        self._rt = topology.resolve_topology(self._topo)
        self._dft = self._place_forwarding(fwd.fwd_to_device(self._ft))

    def _ranges_of(self, name: str) -> list[tuple[int, int]]:
        """Current merged ranges of a named group (members + static blocks)."""
        mem = self._group_members.get(name)
        rs: list[tuple[int, int]] = []
        if mem is not None:
            rs.extend(iputil.cidr_to_range(s) for s, c in mem.items() if c > 0)
        rs.extend(self._static_blocks.get(name, ()))
        return iputil.merge_ranges(rs)

    def _covered_by_others(self, gid: int, exclude: str, r: tuple[int, int]) -> bool:
        _kind, names, static = self._gid_ident[gid]
        if _contains(iputil.merge_ranges(list(static)), r):
            return True
        return any(
            _contains(self._ranges_of(n), r) for n in names if n != exclude
        )

    def _partially_covered_by_others(self, gid: int, exclude: str, r) -> bool:
        _kind, names, static = self._gid_ident[gid]
        if _overlaps(iputil.merge_ranges(list(static)), r):
            return True
        return any(
            _overlaps(self._ranges_of(n), r) for n in names if n != exclude
        )

    def _rule_mask(self, gids: np.ndarray, gid: int, w: int) -> np.ndarray:
        """(w,) u32 bitmap of rules whose dim gid == gid (the pre-resolved
        per-dimension delta mask the kernel ORs/clears on gathered rows);
        packed by the kernel's own bit layout (ops/match._inc_mask)."""
        from ..ops.match import _inc_mask

        return _inc_mask(np.nonzero(gids == gid)[0], w)

    def _append_deltas(self, rows) -> None:
        h = self._delta_host
        cps = self._cps
        mm = self._meta.match
        for (lo, hi), gid, sign in rows:
            i = self._n_deltas
            if lo >= iputil.V6_OFF:
                # v6 slot: lexicographic word bounds, family-tagged
                # (cidr_to_range never spans families).
                h["fam"][i] = 1
                h["lo6_w"][i] = iputil.key_to_flipped_words(lo)
                h["hi6_w"][i] = iputil.key_to_flipped_words(hi - 1)
            else:
                h["fam"][i] = 0
                h["lo_f"][i] = iputil.flip_u32(np.uint32(lo))
                h["hi_f"][i] = iputil.flip_u32(np.uint32(hi - 1))  # inclusive
            h["sign"][i] = sign
            h["at_in"][i] = self._rule_mask(cps.ingress.at_gid, gid, mm.w_in)
            h["peer_in"][i] = self._rule_mask(cps.ingress.peer_gid, gid, mm.w_in)
            h["at_out"][i] = self._rule_mask(cps.egress.at_gid, gid, mm.w_out)
            h["peer_out"][i] = self._rule_mask(cps.egress.peer_gid, gid, mm.w_out)
            h["iso"][i] = (1 if gid == cps.iso_in_gid else 0) | (
                2 if gid == cps.iso_out_gid else 0
            )
            self._n_deltas += 1
        self._upload_delta_table()

    def _upload_delta_table(self) -> None:
        """Upload the host delta mirror (_delta_host/_n_deltas) as the
        device DeltaTable — shared by the incremental append path and the
        audit plane's rule-side self-heal (which rebuilds `drs` from the
        compiled set and must re-apply the pending deltas)."""
        self._drs = self._drs._replace(
            ip_delta=self._place_delta(self._build_delta_table()))

    def _build_delta_table(self) -> DeltaTable:
        """The host delta mirror as an (unplaced) device DeltaTable — the
        one construction shared by _upload_delta_table and the reshard
        plane's target-topology placement (parallel/reshard.py, which
        must carry the pending deltas onto the target mesh)."""
        h = self._delta_host
        return DeltaTable(
            lo_f=jnp.asarray(h["lo_f"]),
            hi_f=jnp.asarray(h["hi_f"]),
            sign=jnp.asarray(h["sign"]),
            iso=jnp.asarray(h["iso"]),
            at_in=jnp.asarray(h["at_in"]),
            peer_in=jnp.asarray(h["peer_in"]),
            at_out=jnp.asarray(h["at_out"]),
            peer_out=jnp.asarray(h["peer_out"]),
            n=jnp.int32(self._n_deltas),
            fam=jnp.asarray(h["fam"]),
            lo6_w=jnp.asarray(h["lo6_w"]),
            hi6_w=jnp.asarray(h["hi6_w"]),
        )

    def _place_delta(self, dt: DeltaTable) -> DeltaTable:
        """Delta-table placement hook (mesh engine: re-place on the mesh
        with the word-axis specs so incremental uploads stay sharded)."""
        return dt

    # -- tenancy hook (datapath/tenancy.TenantedDatapath) --------------------

    def _tenant_init_world(self, spec: TenantSpec, ps) -> None:
        """Re-initialize the swapped-out engine fields as a fresh rule
        world for `spec`: its own compiled (rung-padded) tensors, its
        own quota-rung state tables, zeroed counters, generation 0.  The
        caller (tenant_create) holds the saved world and restores it in
        its finally; placement goes through the engine hooks, so the
        mesh engine builds sharded worlds with no code of its own."""
        self._ps = ps
        self._gen = 0
        self._pipe_kw = dict(self._pipe_kw, flow_slots=spec.quota,
                             aff_slots=spec.aff_quota)
        self._stats_in = Counter()
        self._stats_out = Counter()
        self._bytes_in = Counter()
        self._bytes_out = Counter()
        self._default_allow = 0
        self._default_deny = 0
        self._evictions = 0
        self._reclaims = 0
        self._state_mutations = 0
        self._persist_dirty = False
        self._compile_rules()
        self._state = self._init_pipeline_state(spec.quota, spec.aff_quota)

    def _tenant_occupied(self, fields: dict) -> int:
        """Occupancy of a SNAPSHOTTED world state (datapath/tenancy
        tenant_stats — the scrape path must never swap worlds)."""
        return int(pl.cache_stats(fields["_state"])["occupied"])

    def _sync_ps_members(self, name: str) -> None:
        """Keep the held PolicySet's group membership in line with the
        membership mirror so an overflow-triggered recompile sees current
        membership."""
        own = self._group_members.get(name, Counter())
        meta = self._member_meta.get(name, {})
        members = [
            meta.get(s) or GroupMember(ip=s)
            for s, cnt in sorted(own.items())
            for _ in range(cnt)
        ]
        ag = self._ps.address_groups.get(name)
        if ag is not None:
            ag.members = list(members)
        atg = self._ps.applied_to_groups.get(name)
        if atg is not None:
            atg.members = list(members)


def _contains(ranges: list[tuple[int, int]], r: tuple[int, int]) -> bool:
    lo, hi = r
    return any(lo >= lo2 and hi <= hi2 for lo2, hi2 in ranges)


def _overlaps(ranges: list[tuple[int, int]], r: tuple[int, int]) -> bool:
    lo, hi = r
    return any(lo < hi2 and hi > lo2 for lo2, hi2 in ranges)

"""Rule compiler: PolicySet -> match tensors.

This is the TPU analog of the reference's flow-generation layer: where
pkg/agent/openflow/network_policy.go compiles PolicyRules into OVS
conjunction(id, k/n) flows with shared conjMatchFlowContexts
(/root/reference/pkg/agent/openflow/network_policy.go:325,:442), we compile
the same rule structure into:

  * an elementary-interval table over the u32 IP space with a bit-packed
    per-interval group-membership matrix (the shared, factored address sets —
    O(|addresses| + |rules|) storage, SURVEY.md section 2.6), and
  * per-direction rule arrays whose ORDER encodes priority (tier, policy
    priority, rule index, uid) — the tensor variant of OVS flow priorities,
    sidestepping the reference's dynamic priority reassignment
    (network_policy.go:1873 ReassignFlowPriorities) entirely: inserting a
    rule is a recompile of cheap host-side arrays, not a priority shuffle.

Evaluation phases are contiguous segments of the rule arrays:
  [0, n_phase0)           Antrea-native non-Baseline rules, priority-sorted
  [n_phase0, +n_k8s)      K8s NP allow rules (any-match semantics)
  [.., +n_baseline)       Baseline-tier rules, priority-sorted

Unsigned-compare note: packet IPs use the full u32 range, but TPUs want i32
lanes; we flip the sign bit (x ^ 0x80000000) on both boundaries and packet
columns so signed compares give unsigned order.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import cached_property

import numpy as np

from ..apis.controlplane import (
    PROTO_ICMP,
    PROTO_SCTP,
    PROTO_TCP,
    PROTO_UDP,
    Direction,
    NetworkPolicy,
    NetworkPolicyPeer,
    NetworkPolicyRule,
    RuleAction,
    Service,
)
from ..utils import ip as iputil
from .ir import PolicySet, rule_id

# Action encoding shared with oracle.VerdictCode (+ PASS).
ACT_ALLOW = 0
ACT_DROP = 1
ACT_REJECT = 2
ACT_PASS = 3

_ACTION_CODE = {
    RuleAction.ALLOW: ACT_ALLOW,
    RuleAction.DROP: ACT_DROP,
    RuleAction.REJECT: ACT_REJECT,
    RuleAction.PASS: ACT_PASS,
}

# The any/match-all range set spans the COMBINED dual-stack keyspace
# (utils/ip.py: v4 at [0, 2^32), v6 offset above), so an any-peer matches
# both families; consumers that are v4-scoped (the svc key space, the
# introspection tables) clip it harmlessly.
FULL_SPACE = ((0, iputil.KEYSPACE_END),)

_PORT_PROTOS = (PROTO_TCP, PROTO_UDP, PROTO_SCTP)

# Service-reference sub-space of the svc key dimension (the toServices
# lowering; ref controlplane ServiceReference + the agent's ServiceGroupID
# conjunction).  Ordinary svc keys are (proto << 16 | dst_port) < 2^24;
# keys at SVCREF_BASE + service_index express "the lane's ServiceLB
# resolution IS service i" — probed by the pipeline with a SECOND svc-dim
# key derived from the lane's resolved LB program (ops/match.classify_batch
# svc_ref), so the two sub-spaces can never cross-match.  SVCREF_NONE is
# the probe key of lanes with no service resolution: above every
# reference (and every port key), inside only the match-all group — which
# is correct, since a rule without port constraints matches any lane.
SVCREF_BASE = 1 << 24
SVCREF_NONE = 1 << 30


def svcref_ranges(
    refs, svc_index: dict
) -> tuple[tuple[int, int], ...]:
    """toServices references -> merged svc-key ranges in the reference
    sub-space.  Unresolvable references (service unknown to this datapath)
    contribute nothing — all-unresolved peers match no traffic, like the
    reference's dangling ServiceReference."""
    ranges = [
        (SVCREF_BASE + idx, SVCREF_BASE + idx + 1)
        for ref in refs
        for idx in svc_index.get((ref.namespace, ref.name), ())
    ]
    return _merge(ranges)


def service_index_of(services) -> dict:
    """(namespace, name) -> list of service indices for toServices
    resolution (every entry sharing the identity — e.g. the per-family
    slices of a dual-stack Service — is referenced together, matching the
    scalar oracle's identity compare).  Unnamed services are not
    referenceable (no identity to match)."""
    idx: dict[tuple[str, str], list[int]] = {}
    for i, s in enumerate(services or ()):
        if s.name:
            idx.setdefault((s.namespace, s.name), []).append(i)
    return idx


def _svc_key_ranges(services: list[Service]) -> tuple[tuple[int, int], ...]:
    """Service list -> merged ranges over the (proto << 16 | dst_port) key.

    Mirrors oracle._service_matches: ports constrain only TCP/UDP/SCTP;
    other protocols match port-carrying entries unconditionally.
    Empty list = match-all (types.go:299 Service semantics).
    """
    if not services:
        return FULL_SPACE
    ranges: list[tuple[int, int]] = []

    def whole_proto(p: int):
        ranges.append((p << 16, (p + 1) << 16))

    for s in services:
        protos = [s.protocol] if s.protocol is not None else list(range(256))
        for p in protos:
            if p == PROTO_ICMP and s.icmp_type is not None:
                # ICMP type/code constraint (Service.ICMPType/ICMPCode,
                # types.go:311): ICMP lanes carry (type << 8) | code in
                # the dst_port column, so this is a plain key range.
                lo = s.icmp_type << 8
                if s.icmp_code is not None:
                    lo |= s.icmp_code
                    hi = lo + 1
                else:
                    hi = lo + 256  # any code under this type
                ranges.append(((p << 16) + lo, (p << 16) + hi))
            elif s.port is None or p not in _PORT_PROTOS:
                whole_proto(p)
            else:
                hi = s.end_port if s.end_port is not None else s.port
                # Arithmetic add, not OR: min(hi,65535)+1 can be 0x10000,
                # which OR'd into p<<16 would corrupt the key for odd protos.
                ranges.append(((p << 16) + s.port, (p << 16) + min(hi, 65535) + 1))
    return _merge(ranges)


def _merge(ranges) -> tuple[tuple[int, int], ...]:
    return tuple(iputil.merge_ranges(ranges))


class _GroupSpace:
    """Content-addressed range-set -> dense group-id space.

    The dedup is the tensor analog of the reference's shared
    conjMatchFlowContext cache (network_policy.go:342-400): identical address
    sets used by many rules get one bitmap column, not one per rule.

    Two addressing modes:
      * value-addressed (ident=None): immutable range sets (inline ipBlocks,
        the any/empty groups) dedup by value;
      * identity-addressed (ident=tuple): sets built from NAMED groups dedup
        by constituent names, NOT by current value — two different
        AddressGroups with coincidentally identical members must keep
        separate bitmap columns, or an incremental membership delta to one
        would corrupt the other.  `ident_of` records the provenance each
        updatable gid was built from (consumed by the incremental-update
        path, datapath/tpuflow.py).
    """

    def __init__(self) -> None:
        self._ids: dict[tuple, int] = {}
        self.groups: list[tuple[tuple[int, int], ...]] = []
        self.ident_of: dict[int, tuple] = {}
        self.empty = self.intern(())
        self.any = self.intern(FULL_SPACE)

    def intern(self, ranges: tuple[tuple[int, int], ...], ident: tuple = None) -> int:
        key = ("val", ranges) if ident is None else ident
        gid = self._ids.get(key)
        if gid is None:
            gid = len(self.groups)
            self._ids[key] = gid
            self.groups.append(ranges)
            if ident is not None:
                self.ident_of[gid] = ident
        return gid

def build_group_tables(groups: list) -> tuple[np.ndarray, np.ndarray]:
    """(interval x group) membership tables for a gid-indexed range-set list
    -> (bounds (NB,) u64, bitmap (NB+1, ceil(G/32)) u32).

    Introspection/debug surface only: the classification kernel consumes the
    per-dimension RULE-incidence tables built in ops/match instead, so this
    O(intervals x groups) construction must stay off the compile path (it is
    reached lazily via CompiledPolicySet.ip_bitmap etc.)."""
    pts: set[int] = set()
    for ranges in groups:
        for lo, hi in ranges:
            # Introspection stays v4-scoped (the kernel's dual-stack tables
            # are built in ops/match._dim_table_host); v6 boundary points
            # (combined keyspace >= 2^32, utils/ip.py) are out of range for
            # this u64 debug table.
            if lo >= (1 << 32):
                continue
            pts.add(lo)
            if hi < (1 << 32):
                pts.add(hi)
    bounds = np.array(sorted(pts), dtype=np.uint64)
    n_iv = len(bounds) + 1
    gw = max(1, (len(groups) + 31) // 32)
    bitmap = np.zeros((n_iv, gw), dtype=np.uint32)
    for gid, ranges in enumerate(groups):
        w, b = gid >> 5, np.uint32(1 << (gid & 31))
        for lo, hi in ranges:
            lo, hi = int(lo), min(int(hi), 1 << 32)  # v4 clip (see above)
            if lo >= hi:
                continue
            start = int(np.searchsorted(bounds, lo, side="right"))
            end = int(np.searchsorted(bounds, hi - 1, side="right"))
            bitmap[start : end + 1, w] |= b
    return bounds, bitmap


@dataclass
class DirectionTensors:
    """Rule arrays for one direction; order == evaluation order."""

    at_gid: np.ndarray  # (R,) i32 — appliedTo group (tested vs pod column)
    peer_gid: np.ndarray  # (R,) i32 — peer group (tested vs peer column)
    svc_gid: np.ndarray  # (R,) i32
    action: np.ndarray  # (R,) i32
    n_phase0: int
    n_k8s: int
    n_baseline: int
    rule_ids: list[str] = field(default_factory=list)
    # (R,) i32 0/1 — L7-inspection redirect mark of each rule (ref
    # NetworkPolicyRule.L7Protocols; seam network_policy.go:2213).
    l7: np.ndarray = None

    @property
    def n_rules(self) -> int:
        return int(self.at_gid.shape[0])

    @cached_property
    def rule_id_table(self) -> np.ndarray:
        """(len(rule_ids) + 1,) object table for resolving a whole index
        column at once: entry r is rule_ids[r] (the same str object), or
        None where that id is empty (a padding row); the LAST entry is
        None, the slot every out-of-range index is clamped to.  Built on
        first use and kept with the rule_ids it was built from — they are
        never mutated after construction, so an engine that swaps its
        compiled set (install, rollback, tenant world) swaps the table
        with it."""
        table = np.empty(len(self.rule_ids) + 1, dtype=object)
        table[:-1] = [rid or None for rid in self.rule_ids]
        return table


@dataclass
class CompiledPolicySet:
    """Everything the classification kernel needs, as host numpy arrays."""

    ingress: DirectionTensors
    egress: DirectionTensors
    iso_in_gid: int
    iso_out_gid: int
    n_ip_groups: int
    n_svc_groups: int
    # Interned range sets, indexed by gid (consumed by the incidence-table
    # build in ops/match.to_host): ip_groups over the u32 IP space,
    # svc_groups over the (proto << 16 | dst_port) key space.
    ip_groups: list = field(default_factory=list)
    svc_groups: list = field(default_factory=list)
    # Introspection: named AddressGroup -> ip-group id (bitmap column).
    ag_gids: dict[str, int] = field(default_factory=dict)
    # Provenance of identity-addressed gids (see _GroupSpace): gid ->
    # ("agu"|"atgu", sorted constituent group names, static extra ranges).
    # The incremental-update path uses this to find every bitmap column a
    # named-group membership delta must patch.
    gid_ident: dict[int, tuple] = field(default_factory=dict)
    # Any egress rule lowered a toServices peer into the svc-reference
    # sub-space: the pipeline must derive + probe the second svc-dim key
    # (ops/match StaticMeta.svcref), and a SERVICE-set change must
    # recompile rules (reference indices shift with the service list).
    has_svcref: bool = False

    # -- lazy (interval x group) introspection tables (test/debug surface) --
    # The kernel reads the rule-incidence tables from ops/match, never these;
    # building them eagerly would put O(intervals x groups) host work on
    # every compile, including delta-overflow recompiles.
    _ip_tables: tuple = field(default=None, repr=False, compare=False)
    _svc_tables: tuple = field(default=None, repr=False, compare=False)

    def _ip(self) -> tuple:
        if self._ip_tables is None:
            b64, bm = build_group_tables(self.ip_groups)
            self._ip_tables = (_flip(b64.astype(np.uint32)), bm)
        return self._ip_tables

    def _svc(self) -> tuple:
        if self._svc_tables is None:
            b64, bm = build_group_tables(self.svc_groups)
            self._svc_tables = (b64.astype(np.int32), bm)
        return self._svc_tables

    @property
    def ip_bounds(self) -> np.ndarray:  # (NB,) i32, sign-flipped
        return self._ip()[0]

    @property
    def ip_bitmap(self) -> np.ndarray:  # (NB+1, GW) u32
        return self._ip()[1]

    @property
    def svc_bounds(self) -> np.ndarray:  # (SB,) i32 (keys < 2^24, no flip)
        return self._svc()[0]

    @property
    def svc_bitmap(self) -> np.ndarray:  # (SB+1, SW) u32
        return self._svc()[1]


_flip = iputil.flip_u32


# ---------------------------------------------------------------------------
# Phase-capacity padding (the multi-tenant packing layer, round 9)
# ---------------------------------------------------------------------------

# Smallest non-empty phase capacity: rule counts below this share one
# rung, so small tenants collapse onto one compiled program.
PHASE_RUNG_FLOOR = 8


def phase_cap(n: int, floor: int = PHASE_RUNG_FLOOR) -> int:
    """Natural phase rule count -> its pow2 capacity rung (0 stays 0)."""
    if n <= 0:
        return 0
    return max(floor, 1 << (n - 1).bit_length())


def _pad_direction_phases(dt: DirectionTensors, caps: tuple[int, int, int],
                          pad_ip_gid: int, pad_svc_gid: int
                          ) -> DirectionTensors:
    n0, nk, nb = dt.n_phase0, dt.n_k8s, dt.n_baseline
    segs = [(0, n0, caps[0] - n0), (n0, n0 + nk, caps[1] - nk),
            (n0 + nk, n0 + nk + nb, caps[2] - nb)]

    def stitch(arr: np.ndarray, pad_val) -> np.ndarray:
        pieces = []
        for a, b, pad in segs:
            pieces.append(arr[a:b])
            if pad:
                pieces.append(np.full(pad, pad_val, arr.dtype))
        return np.concatenate(pieces) if pieces else arr

    ids: list[str] = []
    for a, b, pad in segs:
        ids.extend(dt.rule_ids[a:b])
        ids.extend("" for _ in range(pad))
    return DirectionTensors(
        at_gid=stitch(dt.at_gid, pad_ip_gid),
        peer_gid=stitch(dt.peer_gid, pad_ip_gid),
        svc_gid=stitch(dt.svc_gid, pad_svc_gid),
        action=stitch(dt.action, ACT_DROP),
        n_phase0=caps[0],
        n_k8s=caps[1],
        n_baseline=caps[2],
        rule_ids=ids,
        l7=None if dt.l7 is None else stitch(dt.l7, 0),
    )


def pad_compiled_phases(cps: CompiledPolicySet) -> CompiledPolicySet:
    """Pad each direction's phase segments to pow2 capacity rungs.

    The pipeline's static jit signature carries the per-phase rule
    counts (ops/match.StaticMeta.in_phases/out_phases): without
    quantization every tenant's rule world would compile its own XLA
    program.  Padding inserts inert rules AT THE END of each phase —
    bound to a fresh EMPTY address/service group, so they paint no
    interval, set no incidence bit and can never decide a verdict — and
    order within a phase is preserved, so first-match semantics (and the
    decided rule's stable id) are bit-identical to the unpadded compile
    (the tenancy parity suite pins this).  Pad positions carry the empty
    rule id "" (resolved to None by attribution, like a vanished rule).

    Returns a new CompiledPolicySet whose phase counts are the rung
    capacities; composes with entry-axis padding
    (ops/match.pad_ruleset_entries) to make the whole compiled shape a
    function of the rung alone."""
    in_caps = (phase_cap(cps.ingress.n_phase0), phase_cap(cps.ingress.n_k8s),
               phase_cap(cps.ingress.n_baseline))
    out_caps = (phase_cap(cps.egress.n_phase0), phase_cap(cps.egress.n_k8s),
                phase_cap(cps.egress.n_baseline))
    ip_groups = list(cps.ip_groups) + [[]]  # the empty pad group
    svc_groups = list(cps.svc_groups) + [[]]
    pad_ip = len(ip_groups) - 1
    pad_svc = len(svc_groups) - 1
    return CompiledPolicySet(
        ingress=_pad_direction_phases(cps.ingress, in_caps, pad_ip, pad_svc),
        egress=_pad_direction_phases(cps.egress, out_caps, pad_ip, pad_svc),
        iso_in_gid=cps.iso_in_gid,
        iso_out_gid=cps.iso_out_gid,
        n_ip_groups=len(ip_groups),
        n_svc_groups=len(svc_groups),
        ip_groups=ip_groups,
        svc_groups=svc_groups,
        ag_gids=dict(cps.ag_gids),
        gid_ident=dict(cps.gid_ident),
        has_svcref=cps.has_svcref,
    )


def compile_policy_set(ps: PolicySet, services=None) -> CompiledPolicySet:
    """services (list[ServiceEntry], optional): the datapath's Service view,
    consumed ONLY by toServices peer lowering (svcref_ranges) — policies
    without toServices compile identically with or without it."""
    from .ir import resolve_named_ports

    ps = resolve_named_ports(ps)
    ip_space = _GroupSpace()
    svc_space = _GroupSpace()
    svc_index = service_index_of(services)
    has_svcref = False

    ag_ranges: dict[str, tuple[tuple[int, int], ...]] = {
        name: tuple(g.ranges()) for name, g in ps.address_groups.items()
    }
    # Intern every named group up front so each has a stable bitmap column;
    # identity-addressed (the group is mutable via membership deltas).
    ag_gids = {
        name: ip_space.intern(r, ident=("agu", (name,), ()))
        for name, r in ag_ranges.items()
    }
    atg_ranges: dict[str, tuple[tuple[int, int], ...]] = {}
    for name, g in ps.applied_to_groups.items():
        atg_ranges[name] = _merge(
            [iputil.cidr_to_range(m.ip) for m in g.members]
        )

    def applied_gid(policy: NetworkPolicy, rule: NetworkPolicyRule) -> int:
        names = tuple(sorted(rule.applied_to_groups or policy.applied_to_groups))
        ranges: list[tuple[int, int]] = []
        for n in names:
            ranges.extend(atg_ranges.get(n, ()))
        if not names:
            return ip_space.empty
        return ip_space.intern(_merge(ranges), ident=("atgu", names, ()))

    def peer_repr(peer: NetworkPolicyPeer) -> int:
        """-> gid.  Literal ipBlocks fold INTO the interned group (they
        become extra elementary-interval boundaries + incidence bits at the
        same cost as named-group members) — the conjMatchFlowContext sharing
        applies to blocks too, and the kernel needs no inline-range path
        (round-2 verdict: 2 inline slots x a full per-rule scan was the
        wrong trade at 100k rules)."""
        if peer.is_any:
            return ip_space.any
        block_ranges: list[tuple[int, int]] = []
        for b in peer.ip_blocks:
            block_ranges.extend(iputil.ipblock_to_ranges(b.cidr, b.excepts))
        group_ranges: list[tuple[int, int]] = []
        names = tuple(sorted(peer.address_groups))
        for n in names:
            group_ranges.extend(ag_ranges.get(n, ()))
        static = _merge(block_ranges) if block_ranges else ()
        group_ranges.extend(block_ranges)
        if not names:
            # Pure-block peer (or dangling empty): nothing mutable, so
            # value-addressed dedup applies.
            return ip_space.empty if not group_ranges else ip_space.intern(
                _merge(group_ranges)
            )
        return ip_space.intern(_merge(group_ranges), ident=("agu", names, static))

    # -- collect rules per direction, phase-tagged ---------------------------

    rows: dict[Direction, dict[int, list]] = {
        Direction.IN: {0: [], 1: [], 2: []},
        Direction.OUT: {0: [], 1: [], 2: []},
    }
    for p in ps.policies:
        for i, r in enumerate(p.rules):
            if p.is_k8s:
                phase, sort_key = 1, ()
            elif p.is_baseline:
                phase, sort_key = 2, (p.tier_priority, p.priority, r.priority, p.uid)
            else:
                phase, sort_key = 0, (p.tier_priority, p.priority, r.priority, p.uid)
            if r.peer.to_services:
                # toServices lowering: the peer's IP dimension is ANY (the
                # match rides entirely on the lane's ServiceLB resolution)
                # and its svc dimension is the reference sub-space
                # (admission guarantees exclusivity with ports/other peer
                # forms, and egress-only).
                if r.direction != Direction.OUT:
                    raise ValueError(
                        f"policy {p.uid} rule {i}: toServices peers are "
                        f"egress-only"
                    )
                if r.peer.address_groups or r.peer.ip_blocks or r.services:
                    # The admission webhook enforces this upstream; a
                    # controlplane object arriving without it must fail
                    # loud, never silently drop the non-service peers.
                    raise ValueError(
                        f"policy {p.uid} rule {i}: toServices is exclusive "
                        f"of other peers and of rule ports"
                    )
                has_svcref = True
                pg = ip_space.any
                sg = svc_space.intern(svcref_ranges(r.peer.to_services,
                                                    svc_index))
            else:
                pg = peer_repr(r.peer)
                sg = svc_space.intern(_svc_key_ranges(r.services))
            row = (
                sort_key,
                applied_gid(p, r),
                pg,
                sg,
                _ACTION_CODE[r.action],
                rule_id(p, i),
                1 if r.l7_protocols else 0,
            )
            rows[r.direction][phase].append(row)

    # -- isolation groups (K8s default-deny membership) ----------------------

    def iso_gid(direction: Direction) -> int:
        names: set[str] = set()
        for p in ps.policies:
            if p.is_k8s and direction in p.policy_types:
                names.update(p.applied_to_groups)
        if not names:
            return ip_space.empty
        ranges: list[tuple[int, int]] = []
        for n in sorted(names):
            ranges.extend(atg_ranges.get(n, ()))
        # Identity-addressed like any ATG union, so pod churn in a K8s
        # policy's appliedTo also patches the isolation column incrementally.
        return ip_space.intern(_merge(ranges), ident=("atgu", tuple(sorted(names)), ()))

    iso_in = iso_gid(Direction.IN)
    iso_out = iso_gid(Direction.OUT)

    # -- emit per-direction arrays -------------------------------------------

    def emit(direction: Direction) -> DirectionTensors:
        ordered = []
        for phase in (0, 1, 2):
            seg = rows[direction][phase]
            if phase != 1:
                seg = sorted(seg, key=lambda t: t[0])
            ordered.extend(seg)
        n0 = len(rows[direction][0])
        nk = len(rows[direction][1])
        nb = len(rows[direction][2])
        R = max(1, len(ordered))
        at = np.full(R, ip_space.empty, dtype=np.int32)
        pg = np.full(R, ip_space.empty, dtype=np.int32)
        sg = np.full(R, svc_space.empty, dtype=np.int32)
        act = np.full(R, ACT_DROP, dtype=np.int32)
        l7 = np.zeros(R, dtype=np.int32)
        ids: list[str] = [""] * R
        for j, (_, a, g, s, ac, rid, l7f) in enumerate(ordered):
            at[j], pg[j], sg[j], act[j], ids[j], l7[j] = a, g, s, ac, rid, l7f
        return DirectionTensors(
            at_gid=at,
            peer_gid=pg,
            svc_gid=sg,
            action=act,
            n_phase0=n0,
            n_k8s=nk,
            n_baseline=nb,
            rule_ids=ids,
            l7=l7,
        )

    # NOTE: emit() interns nothing new (all gids interned above), so the
    # lazy introspection tables (ip_bounds/ip_bitmap/...) are complete
    # whenever first touched.
    t_in = emit(Direction.IN)
    t_out = emit(Direction.OUT)

    return CompiledPolicySet(
        ingress=t_in,
        egress=t_out,
        iso_in_gid=iso_in,
        iso_out_gid=iso_out,
        n_ip_groups=len(ip_space.groups),
        n_svc_groups=len(svc_space.groups),
        ip_groups=list(ip_space.groups),
        svc_groups=list(svc_space.groups),
        ag_gids=ag_gids,
        gid_ident=dict(ip_space.ident_of),
        has_svcref=has_svcref,
    )

"""Batched forwarding stage: SpoofGuard -> (pipeline) -> L2/L3 forward -> Output.

Device twin of compiler/topology.py's scalar spec — the forwarding tables
the reference programs as OVS L2ForwardingCalc / L3Forwarding / SpoofGuard /
TrafficControl / L3DecTTL / Output entries
(/root/reference/pkg/agent/openflow/pipeline.go:114-195), evaluated here as
two searchsorted probes + row gathers per packet, fused into the same XLA
program as the policy pipeline (`pipeline_step_full`) so the whole
per-packet walk is one device dispatch.

Placement of SpoofGuard matters for state parity: in the reference it sits
BEFORE conntrack/policy tables (framework.go stage order), so a spoofed
packet must neither refresh nor commit conntrack state — realized by
threading its mask as the pipeline's `valid` lane mask, which excludes
those lanes from cache refresh, slow-path classification and commit (a
spoofed ALLOW that committed an eternal entry would est-bypass a later
deny for the legitimate tuple).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.compile import ACT_ALLOW, ACT_DROP
from ..compiler.topology import (
    ARP_OP_REQUEST,
    FIRST_POD_OFPORT,
    FWD_ARP_FLOOD,
    FWD_ARP_REPLY,
    FWD_DROP_MCAST,
    FWD_DROP_SPOOF,
    FWD_DROP_UNKNOWN,
    FWD_GATEWAY,
    FWD_LOCAL,
    FWD_MCAST,
    FWD_PUNT,
    FWD_TUNNEL,
    MCAST_HI_F,
    MCAST_LO_F,
    OFPORT_GATEWAY,
    OFPORT_REPLICATE,
    OFPORT_TUNNEL,
    PROTO_IGMP,
    TC_REDIRECT,
    ForwardingTables,
)
from ..ops.scopes import device_scope
from . import pipeline as pl


class DeviceForwardingTables(NamedTuple):
    lp_ip_f: jax.Array
    lp_port: jax.Array
    lp_tc_in: jax.Array
    lp_tc_eg: jax.Array
    n_lp: jax.Array
    rn_lo_f: jax.Array
    rn_hi_f: jax.Array
    rn_peer_f: jax.Array
    n_rn: jax.Array
    local_range_f: jax.Array
    mc_ip_f: jax.Array
    n_mc: jax.Array
    arp_ip_f: jax.Array
    n_arp: jax.Array
    lp6_ipw: jax.Array
    lp6_port: jax.Array
    lp6_tc_in: jax.Array
    lp6_tc_eg: jax.Array
    n_lp6: jax.Array
    rn6_lo_w: jax.Array
    rn6_hi_w: jax.Array
    rn6_peer_w: jax.Array
    n_rn6: jax.Array
    local_range6_w: jax.Array
    nd_ipw: jax.Array
    n_nd: jax.Array


def fwd_to_device(ft: ForwardingTables) -> DeviceForwardingTables:
    return DeviceForwardingTables(*[jnp.asarray(c) for c in ft])


def _lex_le(a: jax.Array, b: jax.Array) -> jax.Array:
    """Lexicographic a <= b over the trailing 4-word axis (per-word
    sign-flipped i32, so signed compares give unsigned order — the same
    contract as ops/match._searchsorted6)."""
    lt = a < b
    eq = a == b
    return lt[..., 0] | (eq[..., 0] & (lt[..., 1] | (eq[..., 1] & (
        lt[..., 2] | (eq[..., 2] & (lt[..., 3] | eq[..., 3]))))))


def _lp_row(dft: DeviceForwardingTables, ip_f: jax.Array):
    """-> (row, known) local-pod probe by flipped IP."""
    cap = dft.lp_ip_f.shape[0]
    row = jnp.clip(jnp.searchsorted(dft.lp_ip_f, ip_f), 0, cap - 1)
    known = (row < dft.n_lp[0]) & (dft.lp_ip_f[row] == ip_f)
    return row, known


def _row_eq_wide(table: jax.Array, n: jax.Array, xw: jax.Array):
    """-> (row, known) exact 4-word row match (all-pairs — per-node v6
    tables are small: the shape ops/match._searchsorted6 keeps up to its
    flat size)."""
    cap = table.shape[0]
    eq = (table[None, :, :] == xw[:, None, :]).all(axis=2)  # (B, cap)
    eq = eq & (jnp.arange(cap, dtype=jnp.int32) < n[0])[None, :]
    known = eq.any(axis=1)
    row = jnp.argmax(eq, axis=1).astype(jnp.int32)
    return row, known


def spoof_lookup(dft: DeviceForwardingTables, src_f: jax.Array, in_port: jax.Array,
                 src_w=None, is6=None):
    """SpoofGuard (ref pipeline.go SpoofGuard): packets entering on a pod
    ofport must source an IP bound to that port.  Resolves the pod by
    source IP (the table is a per-family ip<->ofport bijection, enforced
    at compile); v6 lanes resolve in the lexicographic sub-table."""
    row, known = _lp_row(dft, src_f)
    pod_in = in_port >= FIRST_POD_OFPORT
    spoof4 = pod_in & (~known | (dft.lp_port[row] != in_port))
    if src_w is None:
        return spoof4
    row6, known6 = _row_eq_wide(dft.lp6_ipw, dft.n_lp6, src_w)
    spoof6 = pod_in & (~known6 | (dft.lp6_port[row6] != in_port))
    return jnp.where(is6 != 0, spoof6, spoof4)


def forwarding_lookup(
    dft: DeviceForwardingTables, dst_f: jax.Array, in_port: jax.Array
):
    """L2ForwardingCalc + L3Forwarding + L3DecTTL
    -> dict(kind, out_port, peer_f, dec_ttl, lp_row, is_local)."""
    row, is_local = _lp_row(dft, dst_f)
    rcap = dft.rn_lo_f.shape[0]
    r = jnp.clip(jnp.searchsorted(dft.rn_hi_f, dst_f), 0, rcap - 1)
    in_rn = (
        (r < dft.n_rn[0])
        & (dft.rn_lo_f[r] <= dst_f)
        & (dst_f <= dft.rn_hi_f[r])
    )
    in_local_cidr = (dft.local_range_f[0] <= dst_f) & (
        dst_f <= dft.local_range_f[1]
    )
    # Multicast (ref pipeline.go MulticastRouting/MulticastOutput): a
    # 224.0.0.0/4 dst resolves against the joined-group table; a hit
    # replicates (the consumer resolves the port list from mcast_idx), a
    # miss drops.  Precedence over the unicast branches — mcast addresses
    # can't collide with pod IPs or podCIDRs.
    is_mc = (dst_f >= MCAST_LO_F) & (dst_f <= MCAST_HI_F)
    mcap = dft.mc_ip_f.shape[0]
    mrow = jnp.clip(jnp.searchsorted(dft.mc_ip_f, dst_f), 0, mcap - 1)
    mc_hit = is_mc & (mrow < dft.n_mc[0]) & (dft.mc_ip_f[mrow] == dst_f)
    mcast_idx = jnp.where(mc_hit, mrow, -1).astype(jnp.int32)

    kind = jnp.where(
        is_mc,
        jnp.where(mc_hit, FWD_MCAST, FWD_DROP_MCAST),
        jnp.where(
            is_local,
            FWD_LOCAL,
            jnp.where(
                in_rn,
                FWD_TUNNEL,
                jnp.where(in_local_cidr, FWD_DROP_UNKNOWN, FWD_GATEWAY),
            ),
        ),
    ).astype(jnp.int32)
    out_port = jnp.where(
        is_mc,
        jnp.where(mc_hit, OFPORT_REPLICATE, -1),
        jnp.where(
            is_local,
            dft.lp_port[row],
            jnp.where(
                in_rn,
                OFPORT_TUNNEL,
                jnp.where(in_local_cidr, -1, OFPORT_GATEWAY),
            ),
        ),
    ).astype(jnp.int32)
    peer_f = jnp.where(in_rn & ~is_local & ~is_mc, dft.rn_peer_f[r], 0)
    # L3DecTTL: every routed leg — egress via tunnel/gateway, or local
    # delivery of traffic that ARRIVED routed (tunnel/gateway ingress).
    # Multicast replication does not decrement here (the reference's
    # multicast pipeline skips L3DecTTL).
    routed_in = (in_port == OFPORT_TUNNEL) | (in_port == OFPORT_GATEWAY)
    dec_ttl = jnp.where(
        is_mc,
        0,
        jnp.where(is_local, routed_in, in_rn | (kind == FWD_GATEWAY)),
    ).astype(jnp.int32)
    return {
        "kind": kind,
        "out_port": out_port,
        "peer_f": peer_f,
        "dec_ttl": dec_ttl,
        "lp_row": row,
        "is_local": is_local,
        "is_mc": is_mc,
        "mcast_idx": mcast_idx,
    }


def forwarding_lookup6(
    dft: DeviceForwardingTables, dst_w: jax.Array, in_port: jax.Array
):
    """The v6 leg of L2ForwardingCalc + L3Forwarding + L3DecTTL (ref
    route_linux.go v6 routes): exact local-pod match in the lexicographic
    table, inclusive [lo, hi] word-interval match for remote v6 podCIDRs,
    local-CIDR unknown-pod drop, gateway default.  No v6 multicast table
    (ff00::/8 replication is not modeled — those lanes take the gateway
    default).  -> same dict shape as forwarding_lookup, with peer_w
    ((B, 4)) instead of peer_f."""
    row, is_local = _row_eq_wide(dft.lp6_ipw, dft.n_lp6, dst_w)
    rcap = dft.rn6_lo_w.shape[0]
    ge_lo = _lex_le(dft.rn6_lo_w[None, :, :], dst_w[:, None, :])
    le_hi = _lex_le(dst_w[:, None, :], dft.rn6_hi_w[None, :, :])
    in_row = ge_lo & le_hi & (
        jnp.arange(rcap, dtype=jnp.int32) < dft.n_rn6[0])[None, :]
    in_rn = in_row.any(axis=1)
    r = jnp.argmax(in_row, axis=1).astype(jnp.int32)
    in_local_cidr = (
        _lex_le(dft.local_range6_w[0][None, :], dst_w)
        & _lex_le(dst_w, dft.local_range6_w[1][None, :])
    )
    kind = jnp.where(
        is_local,
        FWD_LOCAL,
        jnp.where(
            in_rn,
            FWD_TUNNEL,
            jnp.where(in_local_cidr, FWD_DROP_UNKNOWN, FWD_GATEWAY),
        ),
    ).astype(jnp.int32)
    out_port = jnp.where(
        is_local,
        dft.lp6_port[row],
        jnp.where(
            in_rn,
            OFPORT_TUNNEL,
            jnp.where(in_local_cidr, -1, OFPORT_GATEWAY),
        ),
    ).astype(jnp.int32)
    peer_w = jnp.where((in_rn & ~is_local)[:, None], dft.rn6_peer_w[r], 0)
    routed_in = (in_port == OFPORT_TUNNEL) | (in_port == OFPORT_GATEWAY)
    dec_ttl = jnp.where(
        is_local, routed_in, in_rn | (kind == FWD_GATEWAY)
    ).astype(jnp.int32)
    return {
        "kind": kind,
        "out_port": out_port,
        "peer_w": peer_w,
        "dec_ttl": dec_ttl,
        "lp_row": row,
        "is_local": is_local,
    }


def tc_lookup(
    dft: DeviceForwardingTables,
    src_f: jax.Array,
    dst_row: jax.Array,
    dst_is_local: jax.Array,
):
    """TrafficControl mark (ref trafficcontrol controller): dst pod's
    ingress word wins, else src pod's egress word.  -> packed word."""
    srow, sknown = _lp_row(dft, src_f)
    w_in = jnp.where(dst_is_local, dft.lp_tc_in[dst_row], 0)
    w_eg = jnp.where(sknown, dft.lp_tc_eg[srow], 0)
    return jnp.where(w_in != 0, w_in, w_eg)


def tc_lookup6(
    dft: DeviceForwardingTables,
    src_w: jax.Array,
    dst_row6: jax.Array,
    dst_is_local6: jax.Array,
):
    """tc_lookup's v6 leg over the lexicographic pod table."""
    srow, sknown = _row_eq_wide(dft.lp6_ipw, dft.n_lp6, src_w)
    w_in = jnp.where(dst_is_local6, dft.lp6_tc_in[dst_row6], 0)
    w_eg = jnp.where(sknown, dft.lp6_tc_eg[srow], 0)
    return jnp.where(w_in != 0, w_in, w_eg)


def _pipeline_step_full(
    state: pl.PipelineState,
    drs,
    dsvc,
    dft: DeviceForwardingTables,
    src_f: jax.Array,
    dst_f: jax.Array,
    proto: jax.Array,
    sport: jax.Array,
    dport: jax.Array,
    in_port: jax.Array,
    now: jax.Array,
    gen: jax.Array,
    flags: jax.Array = None,
    arp_op: jax.Array = None,
    lens: jax.Array = None,
    *,
    meta: pl.PipelineMeta,
    hit_combine=None,
    v6=None,
    valid=None,
    no_commit=None,
    prune_exclude=None,
):
    """Full per-packet walk: SpoofGuard/ARP -> (IGMP punt) -> policy/
    service pipeline -> forwarding -> Output; one jit, one dispatch.

    `valid`/`no_commit` are OPTIONAL external lane masks ANDed/ORed into
    the internally derived ones (spoof/ARP/IGMP exclusion, multicast +
    FIN/RST commit gating): the mesh engine threads its padding mask and
    the spill never-cache-foreign rule through them
    (parallel/meshpath.py).  None — every single-chip call site — traces
    the identical program as before they existed.

    arp_op lanes (ref pipeline.go ARPSpoofGuard/ARPResponder, :114-195):
    ARP is handled BEFORE the IP pipeline — sender-IP spoof gating via the
    same port binding, then the responder answers requests for addresses
    this node owns (gateway/local pods/remote node IPs) back out the
    ingress port; everything else floods (OFPP_NORMAL).  ARP lanes touch
    no conntrack/policy state.

    v6 (dual-stack pipelines): the (src6w_f, dst6w_f, is6) lane extension.
    v6 lanes spoof-guard / forward / TC through the lexicographic
    sub-tables; arp_op on a v6 lane models Neighbor Discovery (NS=1 answers
    from the nd table, the ARPResponder twin)."""
    with device_scope("forwarding"):
        if v6 is not None:
            src6w, dst6w, is6 = v6
            saddr_w = pl._wide_words(src_f, src6w, is6)
            daddr_w = pl._wide_words(dst_f, dst6w, is6)
            m6 = is6 != 0
            spoof = spoof_lookup(dft, src_f, in_port, src_w=saddr_w, is6=is6)
        else:
            is6 = None
            spoof = spoof_lookup(dft, src_f, in_port)
        # IGMP membership traffic is punted to the controller, never forwarded
        # (ref packetin.go PacketInCategoryIGMP; pkg/agent/multicast snooping):
        # excluded from the policy pipeline like spoofed lanes so reports
        # neither commit conntrack state nor count as policy verdicts.
        is_arp = (arp_op > 0) if arp_op is not None else None
        igmp = ~spoof & (proto == PROTO_IGMP)
        if is_arp is not None:
            igmp = igmp & ~is_arp
        # Multicast data traffic bypasses conntrack (multicast.go): classified
        # every step, never cached.  The 224/4 window is a v4 range — v6 lanes
        # carry a don't-care narrow dst and must not alias into it.
        is_mc = (dst_f >= MCAST_LO_F) & (dst_f <= MCAST_HI_F)
        if is6 is not None:
            is_mc = is_mc & ~m6
        no_commit_l = is_mc
        if flags is not None:
            # A FIN/RST-flagged TCP miss classifies but never ESTABLISHES a
            # connection (a closing segment is not a new flow); established
            # hits tear down inside the pipeline (pl._TEARDOWN_FLAGS path).
            no_commit_l = no_commit_l | (
                (proto == pl.PROTO_TCP) & ((flags & pl._TEARDOWN_FLAGS) != 0)
            )
        if no_commit is not None:
            no_commit_l = no_commit_l | no_commit
        valid_l = ~spoof & ~igmp
        if is_arp is not None:
            valid_l = valid_l & ~is_arp
        if valid is not None:
            valid_l = valid_l & valid
    state, out = pl._pipeline_step(
        state, drs, dsvc, src_f, dst_f, proto, sport, dport, now, gen,
        meta=meta, hit_combine=hit_combine, valid=valid_l,
        no_commit=no_commit_l, flags=flags, v6=v6, lens=lens,
        prune_exclude=prune_exclude,
    )
    with device_scope("forwarding"):
        code = jnp.where(spoof, ACT_DROP, out["code"]).astype(jnp.int32)
        # Forward toward the packet's effective destination: the DNAT-resolved
        # endpoint — except reply-direction hits, whose dnat fields carry the
        # SOURCE un-rewrite; a reply forwards to its literal dst (the client).
        eff_dst = jnp.where(out["reply"] == 1, dst_f, out["dnat_ip_f"])
        fwd = forwarding_lookup(dft, eff_dst, in_port)
        peer_w = None
        if is6 is not None:
            # v6 lanes forward by their wide effective destination through the
            # lexicographic tables; merge per family.
            eff_dst_w = jnp.where((out["reply"] == 1)[:, None], daddr_w,
                                  out["dnat_w_f"])
            fwd6 = forwarding_lookup6(dft, eff_dst_w, in_port)
            fwd = {
                "kind": jnp.where(m6, fwd6["kind"], fwd["kind"]),
                "out_port": jnp.where(m6, fwd6["out_port"], fwd["out_port"]),
                "peer_f": jnp.where(m6, 0, fwd["peer_f"]),
                "dec_ttl": jnp.where(m6, fwd6["dec_ttl"], fwd["dec_ttl"]),
                "lp_row": fwd["lp_row"],
                "is_local": jnp.where(m6, fwd6["is_local"], fwd["is_local"]),
                "is_mc": fwd["is_mc"] & ~m6,
                "mcast_idx": jnp.where(m6, -1, fwd["mcast_idx"]),
                "lp_row6": fwd6["lp_row"],
                "is_local6": fwd6["is_local"] & m6,
            }
            # Wide peer view: v4 tunnel peers in mapped form, v6 peers native.
            peer_w = jnp.where(
                m6[:, None], fwd6["peer_w"],
                pl._wide_words(fwd["peer_f"], None, None),
            )
        kind = jnp.where(
            spoof, FWD_DROP_SPOOF, jnp.where(igmp, FWD_PUNT, fwd["kind"])
        ).astype(jnp.int32)
        if is_arp is not None:
            # ARPResponder: answered requests reply out the ingress port;
            # unanswered (or reply-opcode) ARP floods.  ARPSpoofGuard already
            # resolved in `spoof` (sender IP vs port binding).  v6 lanes model
            # Neighbor Discovery: NS (op 1) answers from the nd table — the
            # NDP twin of the responder (route_linux.go v6 neighbors).
            acap = dft.arp_ip_f.shape[0]
            arow = jnp.clip(jnp.searchsorted(dft.arp_ip_f, dst_f), 0, acap - 1)
            answer = (
                is_arp & ~spoof
                & (arow < dft.n_arp[0]) & (dft.arp_ip_f[arow] == dst_f)
                & (arp_op == ARP_OP_REQUEST)
            )
            if is6 is not None:
                _ndrow, nd_known = _row_eq_wide(dft.nd_ipw, dft.n_nd, daddr_w)
                answer6 = (
                    is_arp & ~spoof & nd_known & (arp_op == ARP_OP_REQUEST)
                )
                answer = jnp.where(m6, answer6, answer)
            kind = jnp.where(
                is_arp & ~spoof,
                jnp.where(answer, FWD_ARP_REPLY, FWD_ARP_FLOOD),
                kind,
            ).astype(jnp.int32)
        deliverable = (code == ACT_ALLOW) & (
            (kind == FWD_LOCAL) | (kind == FWD_TUNNEL) | (kind == FWD_GATEWAY)
            | (kind == FWD_MCAST)
        )
        uni_deliverable = deliverable & (kind != FWD_MCAST)
        tc_base = tc_lookup(dft, src_f, fwd["lp_row"], fwd["is_local"])
        if is6 is not None:
            tc_base = jnp.where(
                m6,
                tc_lookup6(dft, saddr_w, fwd["lp_row6"], fwd["is_local6"]),
                tc_base,
            )
        tc_w = jnp.where(uni_deliverable, tc_base, 0)
        tc_act = tc_w & 3
        tc_port = tc_w >> 2
        out_port = jnp.where(deliverable, fwd["out_port"], -1)
        if is_arp is not None:
            out_port = jnp.where(kind == FWD_ARP_REPLY, in_port, out_port)
        # Redirect replaces the output port (ref TrafficControl redirect action:
        # the packet leaves via the target device instead of its computed port).
        out_port = jnp.where(tc_act == TC_REDIRECT, tc_port, out_port)
        # L7 redirect mark (ref network_policy.go:2213 l7NPTrafficControlFlows
        # — the reg0 L7 bit + VLAN handoff to the L7 engine): set when the
        # DECIDING allow rule carries L7 protocols.  Resolved by attribution
        # index against the CURRENT rule table — cached hits inherit the
        # ct_label caveat documented on stats (datapath/tpuflow.py).
        def l7_of(dd, idx):
            n = dd.l7.shape[0]
            safe = jnp.clip(idx, 0, n - 1)
            return jnp.where((idx >= 0) & (idx < n), dd.l7[safe], 0)

        l7 = jnp.where(
            code == ACT_ALLOW,
            l7_of(drs.ingress, out["ingress_rule"])
            | l7_of(drs.egress, out["egress_rule"]),
            0,
        ).astype(jnp.int32)

        out.update(
            code=code,
            reject_kind=pl.reject_kind_of(code, proto),
            spoofed=spoof.astype(jnp.int32),
            l7_redirect=l7,
            punt=igmp.astype(jnp.int32),
            fwd_kind=kind,
            out_port=out_port.astype(jnp.int32),
            peer_f=jnp.where(uni_deliverable, fwd["peer_f"], 0),
            dec_ttl=jnp.where(uni_deliverable, fwd["dec_ttl"], 0),
            tc_act=tc_act,
            tc_port=tc_port,
            mcast_idx=jnp.where(deliverable, fwd["mcast_idx"], -1),
        )
        if peer_w is not None:
            # Wide tunnel-peer view (v6 podCIDR rows may tunnel over either
            # family); zeroed like peer_f for non-deliverable lanes.
            out["peer_w"] = jnp.where(uni_deliverable[:, None], peer_w, 0)
    return state, out


pipeline_step_full = jax.jit(
    _pipeline_step_full, static_argnames=("meta", "hit_combine")
)


# -- the served step's egress record -------------------------------------------
# What the host reads back from EVERY served step, as three arrays instead
# of one per output: the word-wide lane columns as rows of one i32 block,
# the flags and small enums as rows of one i8 block (a row is a contiguous
# view on the host: nothing to unpack), the scalar counters as one vector.
# The ONE place the layout is spelled, in tracing.STEP_RECORD's style:
# pack_egress writes by it, unpack_egress and the engines' byte counts
# read by it.  (field, block, row, bits, signed): a value the width cannot
# hold would wrap silently in the cast, so tests/test_egress_record.py
# holds every enum constant of a narrow field against its width.
EGRESS_WORDS = (
    "svc_idx", "dnat_ip_f", "dnat_port", "ingress_rule", "egress_rule",
    "out_port", "peer_f", "tc_port", "mcast_idx",
)
EGRESS_NARROW = (
    "code", "est", "reply", "reject_kind", "committed", "miss", "snat", "dsr",
    "spoofed", "l7_redirect", "punt", "fwd_kind", "dec_ttl", "tc_act",
)
EGRESS_SCALARS = ("n_miss", "n_evict", "n_reclaim", "round_lanes")
EGRESS_RECORD = tuple(
    (field, block, row, bits, True)
    for block, fields, bits in (("words", EGRESS_WORDS, 32),
                                ("narrow", EGRESS_NARROW, 8),
                                ("scalars", EGRESS_SCALARS, 32))
    for row, field in enumerate(fields)
)


class EgressRecord(NamedTuple):
    words: jax.Array  # (len(EGRESS_WORDS), B) i32
    narrow: jax.Array  # (len(EGRESS_NARROW), B) i8
    scalars: jax.Array  # (len(EGRESS_SCALARS),) i32


def pack_egress(out: dict):
    """The step's output dict -> (EgressRecord, rest): every field of
    EGRESS_RECORD packed into its block, and whatever else the dict holds
    (outputs that exist only under an option: the prune and telemetry
    counters, dual-stack's wide `dnat_w_f` / `peer_w`) handed on as it is.
    Runs inside the jitted step, so the host fetches three arrays."""
    with device_scope("egress"):
        rec = EgressRecord(
            jnp.stack([out[f].astype(jnp.int32) for f in EGRESS_WORDS]),
            jnp.stack([out[f].astype(jnp.int8) for f in EGRESS_NARROW]),
            jnp.stack([out[f].astype(jnp.int32) for f in EGRESS_SCALARS]),
        )
    packed = {field for field, *_ in EGRESS_RECORD}
    return rec, {k: v for k, v in out.items() if k not in packed}


def start_egress_copies(rec: EgressRecord, rest: dict) -> None:
    """Start the device->host copies of a dispatched step's egress: they
    run behind the program, all at once, as soon as it has written them —
    nobody waits for a round trip an output."""
    for a in (*rec, *rest.values()):
        a.copy_to_host_async()


def fetch_egress(rec: EgressRecord, rest: dict, fetched):
    """The egress landed on the host -> ((words, narrow, scalars), rest)
    as numpy, every copy handed through `fetched` (the step tracer's
    transfer counter) once."""
    return (tuple(fetched(np.asarray(a)) for a in rec),
            {k: fetched(np.asarray(v)) for k, v in rest.items()})


def unpack_egress(words, narrow, scalars=()) -> dict:
    """The fetched blocks (numpy) -> the step's output dict, field by
    field a ROW VIEW of its block: no copy, and a narrow field keeps its
    block's i8 — readers compare by value.  `scalars` is the one-chip
    step's (4,); the mesh folds its (4, D) block itself and leaves it out."""
    o = dict(zip(EGRESS_WORDS, words))
    o.update(zip(EGRESS_NARROW, narrow))
    o.update(zip(EGRESS_SCALARS, scalars))
    return o


def _pipeline_step_full_packed(*args, **kw):
    """The SERVED step: `_pipeline_step_full` and `pack_egress` in one
    program -> (state, EgressRecord, rest).  The XLA module is named after
    this function (`jit__pipeline_step_full_packed`)."""
    state, out = _pipeline_step_full(*args, **kw)
    return (state, *pack_egress(out))


pipeline_step_full_packed = jax.jit(
    _pipeline_step_full_packed, static_argnames=("meta", "hit_combine")
)

"""The tpuflow staged datapath pipeline (the flagship "model").

One jitted step processes a packet batch through the stage semantics the
reference realizes as OVS tables
(/root/reference/pkg/agent/openflow/framework.go:96-118 stages,
pipeline.go:114-195 tables), re-architected around the same two-tier design
OVS itself uses for performance — a per-flow exact-match cache in front of
the full classifier (OVS's EMC/megaflow cache + kernel conntrack, which the
reference leans on for its own datapath performance;
docs/design/ovs-pipeline.md conntrack sections):

  FAST PATH (every packet, pure gathers — the throughput path):
    unified flow cache keyed by the 5-tuple.  A hit yields the cached
    verdict, DNAT resolution, rule attribution and service id.  Entries are
    generation-tagged:
      * ALLOW entries are inserted with the ETERNAL generation — they are
        the conntrack-committed connections, and a hit is exactly the
        ct_state -new+est policy-table bypass of the reference
        (docs/design/ovs-pipeline.md:1685-1691): established connections
        keep flowing (and keep their DNAT endpoint) across policy changes.
      * DROP/REJECT entries carry the rule generation — a control-plane
        bundle commit bumps `gen`, instantly invalidating every cached
        denial (the megaflow revalidation analog) while leaving
        established-connection state untouched.

  SLOW PATH (cache misses only, under lax.cond so it costs nothing in
  steady state; chunked by a while_loop for cold batches):
    ServiceLB     exact-match frontend lookup, session affinity (learn-flow
                  analog, ref pipeline.go serviceLearnFlow), endpoint
                  selection by deterministic 5-tuple hash (group select
                  analog), no-endpoint reject (SvcReject analog).
    EndpointDNAT  rewrite dst to the chosen endpoint (ct(commit,nat)).
    Egress/Ingress security
                  the conjunctive-match classification kernel (ops/match)
                  on the POST-DNAT tuple.
    Commit        verdict + DNAT + rule ids inserted into the flow cache
                  (ConntrackCommit analog; denials are cached too, as OVS
                  caches drop megaflows).

State is carried functionally: step(state, ...) -> (state', verdicts).
Tables are direct-mapped hash tables in device memory as SEPARATE (N+1,)
i32 columns — on TPU, independent 1-D gathers are markedly faster than
row-packed (N, 8) gathers (measured on v5e), and the +1 row is a write dump
for masked scatters.  A slot collision evicts (cache semantics — a miss
just re-classifies; endpoint choice is a deterministic hash, so re-derived
state is identical).

Batch semantics are "simultaneous arrival": lookups see the state at batch
start, inserts apply at batch end, last-writer-wins deterministically on
within-batch slot duplicates (see _scatter_last).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..apis.controlplane import PROTO_TCP
from ..compiler.compile import ACT_ALLOW, ACT_REJECT, CompiledPolicySet
from ..compiler.services import ServiceTables
from ..ops import hashing
from ..ops.match import (_FUSE_TB, PRUNE_HIST_BOUNDS, DeviceRuleSet,
                         StaticMeta, classify_batch, to_device, to_host)
from ..ops.scopes import device_scope

# Python ints, never eager jnp scalars: see the BIG comment in ops/match.py.
MISS = -1
# Generation tag reserved for conntrack-committed (ALLOW) entries; rule
# generations are taken mod GEN_ETERNAL so they never collide with it.
GEN_BITS = 22
GEN_ETERNAL = (1 << GEN_BITS) - 1
# Bit 31 of the packed proto/gen key word marks a REPLY-direction entry
# (the reverse-tuple conntrack row committed alongside every ALLOW — the
# ct reply-direction state of the reference's ConntrackZone/UnSNAT tables,
# /root/reference/pkg/agent/openflow/pipeline.go UnSNAT/ConntrackState;
# docs/design/ovs-pipeline.md ct sections).
REPLY_BIT = -(2**31)

# Flow-entry meta column 3 layout: bit 31 SNAT mark, bit 30 DSR mark,
# bit 29 CONFIRMED (two-way traffic seen — the kernel-conntrack
# SYN_SENT -> ESTABLISHED transition; set on the first reply-direction hit
# and propagated to the partner entry), bits 0-28 the partner-refresh
# stamp (seconds mod 2^29; ages compare in mod arithmetic, exact for any
# live entry).
PREF_MASK = (1 << 29) - 1
CONF_BIT = 1 << 29
DSR_BIT = 1 << 30

# Thrash-resistant replacement (round 8, opt-in via second_chance): a
# 2-bit saturating collision counter in meta3 bits 27-28.  A live,
# CONFIRMED (two-way-traffic) established entry survives a colliding
# insert while its counter is below CHANCE_MAX — the challenger simply
# stays uncached (cache semantics: it re-classifies on its next packet)
# and the counter bumps once per commit pass; the entry's own next hit
# resets it.  An ACTIVE established flow therefore cannot be evicted by
# a gen_cache_thrash storm (its hits keep resetting the counter), while
# an idle-but-unconfirmed or silent entry yields after CHANCE_MAX
# collisions — bounded protection, never a wedged slot.  With the knob
# on, the partner-refresh stamp narrows to bits 0-26 (mod-2^27 age
# arithmetic, exact for any live entry); off (the default) keeps the
# full PREF_MASK layout and the compiled step bit-identical.
CHANCE_SHIFT = 27
CHANCE_MAX = 3
CHANCE_MASK = CHANCE_MAX << CHANCE_SHIFT
PREF_MASK_CHANCE = (1 << CHANCE_SHIFT) - 1

# REJECT synthesis kinds (ref pkg/agent/controller/networkpolicy/reject.go:
# TCP gets an RST, everything else an ICMP port-unreachable).
REJECT_NONE = 0
REJECT_TCP_RST = 1
REJECT_ICMP_UNREACH = 2

# TCP wire flag bits consumed by conntrack teardown.
TCP_FIN = 0x01
TCP_RST = 0x04
_TEARDOWN_FLAGS = TCP_FIN | TCP_RST


def no_commit_mask(dst, proto, flags, xp=np):
    """Never-cacheable lanes of a v4 miss batch: multicast destinations
    (conntrack bypass) and FIN/RST-flagged TCP misses (a closing segment
    is not a new flow).  The ONE host-side commit-gating expression the
    drain/fast-dispatch paths share — tpuflow and the mesh engine both
    consume it; the fused device walk derives its own family-aware
    variant in models/forwarding.py."""
    return ((xp.asarray(dst) >> 28) == 0xE) | (
        (xp.asarray(proto) == PROTO_TCP)
        & ((xp.asarray(flags) & _TEARDOWN_FLAGS) != 0)
    )

def _prune_bucket_counts(cand: jax.Array, mask: jax.Array) -> jax.Array:
    """Per-lane candidate-superblock counts -> per-bucket counts PLUS a
    trailing value-sum element: (len(PRUNE_HIST_BOUNDS)+2,) i32.  Bucket
    indexing replicates observability.metrics.Histogram.observe's
    bisect_left over the SAME bounds (ops/match.PRUNE_HIST_BOUNDS), so
    the device counts merge into the host histogram loss-free
    (Histogram.add_counts)."""
    bounds = jnp.asarray(PRUNE_HIST_BOUNDS, jnp.int32)
    idx = (cand[:, None] > bounds[None, :]).sum(axis=1)  # == bisect_left
    mi = mask.astype(jnp.int32)
    counts = jnp.zeros(len(PRUNE_HIST_BOUNDS) + 1, jnp.int32).at[idx].add(mi)
    vsum = (cand * mi).sum(dtype=jnp.int32)
    return jnp.concatenate([counts, vsum[None]])


def reject_kind_of(code, proto, xp=jnp):
    """REJECT synthesis kind for a verdict (scalar or array): TCP -> RST,
    anything else -> ICMP port-unreachable; 0 when not a REJECT."""
    return xp.where(
        code == ACT_REJECT,
        xp.where(proto == PROTO_TCP, REJECT_TCP_RST, REJECT_ICMP_UNREACH),
        REJECT_NONE,
    )


class FlowCache(NamedTuple):
    """Direct-mapped unified flow cache, row-packed for the fast path.

    Layout chosen from measurement on v5e (see docstring history): the fast
    path is gather-bound, and one (N, 4) ROW gather is ~10-30x faster than
    four 1-D column gathers (contiguous 16B reads vs four scattered 4B
    reads), while row SCATTERS are slow and 1-D column scatters fast — so
    the hit-path write (ts refresh) keeps its own column and full-entry
    writes (inserts) happen only on the miss path where the batch is small.

      keys (N+1, 4) i32: [src_f, dst_f, sport<<16|dport, proto|0x100|gen<<9]
        key_pg packs proto (8 bits + valid bit 8) with the entry generation
        (GEN_BITS): zero rows (valid bit unset) can never match a packet.
        Bit 31 (REPLY_BIT) marks a reply-direction entry (below).
      meta (N+1, 4) i32: [dnat_ip_f, meta1, rules,
                          snat<<31|dsr<<30|conf<<29|pref]
        meta1 = code(2) | (svc_idx+1)(14) | dnat_port(16)
        rules = (rule_in+1)(16) | (rule_out+1)(16); 0 = default/none
        pref = last partner-refresh attempt seconds mod 2^29 (29 bits;
        ages compare in mod arithmetic, exact for any live entry);
        bits 31/30 cache the frontend SNAT mark and the DSR delivery mark
        at commit time, so an established connection keeps both marks even
        if later service updates renumber LB programs (the ct-mark
        persistence analog — both marks live in ct_mark in the reference);
        bit 29 is the conntrack CONFIRMED state (see CONF_BIT)
      ts   (N+1,)  i32: last-seen seconds (refreshed on every hit)
      pkts/octets + pkts_hi/octets_hi (N+1,) i32: per-DIRECTION traffic
        counters (conntrack OriginalPackets/OriginalBytes,
        flowexporter/types.go:59) in 64-bit little-endian limb pairs —
        the low limb is the u32 view of the i32 column, the high limb
        carries the overflow, so volumes accumulate to 2^63 like the
        kernel's u64 counters instead of saturating at i32 (the old
        documented 2GB bound).  TPU lanes stay i32 (no x64 dependency);
        the hit path adds with a wrapping scatter + one carry per slot
        (_wide_add), exact as long as ONE entry receives < 2^32 bytes
        within a single batch.  1-D columns because the hit path updates
        them with fast column scatters (the layout rationale above);
        zero-cost when PipelineMeta.count_flow_stats is off (the update
        compiles out).

    dst in keys is the ORIGINAL (pre-DNAT) dst; dnat_ip_f the resolved one.

    Every ALLOW commit also inserts a REPLY-direction entry (conntrack
    commits both directions): keyed on the post-DNAT tuple with ports
    swapped (endpoint ip, client ip, ep_port<<16|client_port), REPLY_BIT
    set, eternal generation; its meta carries the UN-DNAT rewrite — the
    original frontend (pre-DNAT dst ip / dst port) that the reply packet's
    source must be restored to.  A reply hit is an established-connection
    hit (est bypass of the policy tables) with `reply`=1 in the output.
    Occupancy cost: a committed connection takes two slots, as kernel
    conntrack keys both tuple directions.
    """

    keys: jax.Array
    meta: jax.Array
    ts: jax.Array
    pkts: jax.Array
    octets: jax.Array
    pkts_hi: jax.Array
    octets_hi: jax.Array


class AffinityTable(NamedTuple):
    """Session-affinity learn table (slow-path only)."""

    key_client: jax.Array  # (M+1,) sign-flipped client ip
    key_svc: jax.Array  # (M+1,) service index
    ep: jax.Array  # endpoint slot within the service bucket row
    ts: jax.Array  # creation seconds (hard timeout, no refresh — learn-flow)


class PipelineState(NamedTuple):
    flow: FlowCache
    aff: AffinityTable


class DeviceServiceTables(NamedTuple):
    uip_f: jax.Array
    ppk: jax.Array
    slot_svc: jax.Array
    n_ep: jax.Array
    has_ep: jax.Array
    aff_timeout: jax.Array
    ep_base: jax.Array  # (P,) offsets into the flat endpoint arrays
    ep_ip_f: jax.Array  # (E,) flat — unbounded endpoints per program
    ep_port: jax.Array  # (E,) flat
    slot_snat: jax.Array  # (NU, MAXP) 0/1 per-frontend SNAT-mark flag
    prog_svc: jax.Array  # (P,) owning service index per program (toServices)
    prog_dsr: jax.Array  # (P,) 0/1 per-program DSR delivery flag
    # v6 frontend sub-table + wide endpoint words (compiler/services.py
    # dual-stack split; (0, ...) shapes compile the v6 probe out).
    uip6_w: jax.Array  # (NU6, 4) sorted lex, per-word flipped
    ppk6: jax.Array  # (NU6, MAXP6)
    slot_svc6: jax.Array
    slot_snat6: jax.Array
    ep_ipw_f: jax.Array  # (E, 4) wide flipped words, every endpoint


class PipelineMeta(NamedTuple):
    match: StaticMeta
    flow_slots: int
    aff_slots: int
    # Per-state conntrack lifetimes (the kernel's nf_conntrack_tcp_timeout_*
    # distinctions, polled by the reference's flow exporter via
    # conntrack_linux.go): ct_timeout_s is the TCP ESTABLISHED (confirmed)
    # lifetime; syn covers half-open TCP (committed, no reply seen);
    # other_* cover non-TCP (kernel UDP unreplied/stream).  None = inherit
    # ct_timeout_s (per-state handling compiles out entirely).
    ct_timeout_s: int
    miss_chunk: int  # the WIDEST slow-path round (round_ladder)
    ct_syn_timeout_s: Optional[int] = None
    ct_other_new_s: Optional[int] = None
    ct_other_est_s: Optional[int] = None
    # Classify cache misses through the fused pallas consumer
    # (ops/match.classify_batch fused=True) — shard-aware: composes with
    # the rule-axis hit_combine seam via global word offsets.
    fused: bool = False
    # Maintain per-entry packet/byte counters (FlowCache.pkts/octets).
    # Off by default: counting adds a column gather + two scatters to the
    # hit path, the cost the kernel pays only when the observability
    # plane (FlowExporter gate) wants volumes.
    count_flow_stats: bool = False
    # Flow-cache key row width: 4 (v4-only: [src, dst, pp, pg]) or 10
    # (dual-stack: [s0..s3, d0..d3, pp, pg] — addresses in wide v4-mapped
    # word form, the xxreg3 analog).  Static, so pure-v4 worlds compile the
    # narrow fast path unchanged.
    key_words: int = 4
    # The ASYNC engine's fast step (datapath/slowpath): the slow path is
    # compiled out — misses keep the fast-path default image (miss_code),
    # are admitted to the miss queue, and are classified later by a
    # coalesced drain step, which like every synchronous step runs the
    # slow path.  Set by the engine for that one program, not an option.
    defer_misses: bool = False
    # Fast-path default verdict for UNclassified miss lanes (only
    # observable under defer_misses, i.e. in the async fast step):
    # the miss-queue admission policy — ACT_ALLOW = provisional
    # default-forward (the OVS "normal" upcall treatment), ACT_DROP =
    # hold until the background engine classifies (datapath/slowpath).
    miss_code: int = ACT_ALLOW
    # Overlapped-drain maintenance fusion (ROADMAP item 2): the commit
    # pass already gathers each insert target's old key row for the
    # eviction audit; with drain_reclaim set it additionally reads the
    # target's ts/conf and splits overwrites of DEAD rows (idle-expired
    # per the per-state timeout, or stale-generation denials — both
    # already invisible to lookups) out of `n_evict` into `n_reclaim`.
    # The drain round thus ages and revalidates the rows it touches in
    # the one pass that already holds them, and the engine's dedicated
    # full-table scans (age_scan/revalidate_scan) collapse into ONE fused
    # maintain_scan run only on epoch-stale heal.  Off (False) for
    # synchronous steps so their compiled program is unchanged.
    drain_reclaim: bool = False
    # Thrash-resistant replacement (the 2-bit second-chance counter, see
    # CHANCE_SHIFT above).  False keeps the compiled step bit-identical.
    second_chance: bool = False
    # Hot-path telemetry (observability/telemetry.py): the step emits
    # cheap in-kernel counter outputs — cache probe hit/stale/miss
    # splits and second-chance protection bumps (tel_dma_hb is carried
    # but always 0: its kernel is gone) — as tel_* keys in the output dict.
    # Everything is derived XLA-side from values the step already
    # gathers (kr0/ts0 from _cache_lookup, the guard's protected mask),
    # so False compiles the whole plane out: no extra gathers, no extra
    # outputs, HLO bit-identical — the same discipline as every knob
    # above.
    telemetry: bool = False
    # Bits of the packed rule-attribution column that hold the ingress
    # index (rule_split(cps): 16 unless one direction has 65,534 rules or
    # more).  Not an option: the engine derives it from the rule set.
    rule_bits_in: int = 16

    @property
    def pref_mask(self) -> int:
        """Effective partner-refresh stamp mask: the second-chance
        counter (bits 27-28) narrows it; off keeps the full layout."""
        return PREF_MASK_CHANCE if self.second_chance else PREF_MASK

    @property
    def timeouts(self) -> tuple[int, int, int, int]:
        """(tcp_syn, tcp_est, other_new, other_est), Nones resolved."""
        t = self.ct_timeout_s
        return (
            self.ct_syn_timeout_s if self.ct_syn_timeout_s is not None else t,
            t,
            self.ct_other_new_s if self.ct_other_new_s is not None else t,
            self.ct_other_est_s if self.ct_other_est_s is not None else t,
        )


def svc_to_host(st: ServiceTables) -> DeviceServiceTables:
    """Numpy-resident variant (zero device placement; see ops/match.to_host)."""
    return DeviceServiceTables(
        uip_f=np.asarray(st.uip_f),
        ppk=np.asarray(st.ppk),
        slot_svc=np.asarray(st.slot_svc),
        n_ep=np.asarray(st.n_ep),
        has_ep=np.asarray(st.has_ep),
        aff_timeout=np.asarray(st.aff_timeout),
        ep_base=np.asarray(st.ep_base),
        ep_ip_f=np.asarray(st.ep_ip_f),
        ep_port=np.asarray(st.ep_port),
        slot_snat=np.asarray(st.slot_snat),
        prog_svc=np.asarray(st.prog_svc),
        prog_dsr=np.asarray(st.prog_dsr),
        uip6_w=np.asarray(st.uip6_w),
        ppk6=np.asarray(st.ppk6),
        slot_svc6=np.asarray(st.slot_svc6),
        slot_snat6=np.asarray(st.slot_snat6),
        ep_ipw_f=np.asarray(st.ep_ipw_f),
    )


def svc_to_device(st: ServiceTables) -> DeviceServiceTables:
    return jax.tree_util.tree_map(jnp.asarray, svc_to_host(st))


def init_state(
    flow_slots: int = 1 << 20, aff_slots: int = 1 << 18, xp=jnp,
    key_words: int = 4,
) -> PipelineState:
    def zeros(n):
        return xp.zeros(n + 1, dtype=xp.int32)

    wide = key_words > 4
    flow = FlowCache(
        keys=xp.zeros((flow_slots + 1, key_words), dtype=xp.int32),
        # Wide worlds store the 4-word DNAT resolution in meta cols 0-3
        # ([w0..w3, meta1, rules, zcol, pad] — padded to 8 so the row
        # gather stays a power-of-two stride); narrow keeps the 4-col
        # layout documented on FlowCache.
        meta=xp.zeros((flow_slots + 1, 8 if wide else 4), dtype=xp.int32),
        ts=zeros(flow_slots),
        pkts=zeros(flow_slots),
        octets=zeros(flow_slots),
        pkts_hi=zeros(flow_slots),
        octets_hi=zeros(flow_slots),
    )
    aff = AffinityTable(
        # Wide worlds key affinity on the client's 4-word form (v6
        # clients need all 128 bits — a truncated key would mis-affine
        # across colliding clients).
        key_client=(xp.zeros((aff_slots + 1, 4), dtype=xp.int32)
                    if wide else zeros(aff_slots)),
        key_svc=zeros(aff_slots),
        ep=zeros(aff_slots),
        ts=zeros(aff_slots),
    )
    return PipelineState(flow=flow, aff=aff)


def _meta_cols(A: int) -> tuple[int, int, int, int]:
    """Meta-row column indices (dn_narrow, meta1, rules, zcol) for an
    address width — the ONE place the narrow/wide meta layouts are
    defined (narrow: [dnat_ip, m1, rules, z]; wide: [w0..w3, m1, rules,
    z, pad], with the narrow dnat view = wide word 3, the v4-mapped
    column)."""
    return (0, 1, 2, 3) if A == 2 else (3, 4, 5, 6)


def _raw_bits(x_f: jax.Array) -> jax.Array:
    """Sign-flipped i32 -> i32 whose u32 reinterpretation is the raw value."""
    return x_f ^ jnp.int32(-(2**31))


# RFC 4291 v4-mapped word constants in FLIPPED lane space: flip(0) and
# flip(0xffff).  Single source of truth with utils/ip.key_to_flipped_words
# (the oracle-side projection) — parity-critical.
_MAP0 = -(2**31)
_MAPF = -(2**31) + 0xFFFF


def _wide_words(col_f: jax.Array, w6, is6) -> jax.Array:
    """(B,) flipped v4 column + optional (B,4) flipped v6 words + family
    mask -> (B, 4) wide address words (v4 lanes in v4-mapped form).  The
    ONE device-side implementation of the wide projection; every wide-key
    construction (fast path, reverse commit, partner probe, trace) must go
    through here."""
    m = jnp.stack([
        jnp.full_like(col_f, _MAP0), jnp.full_like(col_f, _MAP0),
        jnp.full_like(col_f, _MAPF), col_f,
    ], axis=1)
    if w6 is None:
        return m
    return jnp.where((is6 != 0)[:, None], w6, m)


# The narrow rung of the round ladder is miss_chunk over this.  Two rungs
# and no more: every rung is one more body of the round in every step
# program a process builds, which it traces, lowers and loads anew at each
# start (measured on the v5e, PERF.md s6 PRs 31-32: ~0.8 s a body on one
# chip; ~8 s on the four-chip mesh when its step and retry rungs built it).
_LADDER_NARROW = 8


def round_ladder(miss_chunk: int, batch: int) -> tuple:
    """The widths a slow-path round may run at, widest first.

    The misses are served in rounds of `miss_chunk` lanes; the LAST round
    carries what is left and runs at the narrowest rung that holds it (the
    lanes dropped are padding: `valid` false, every scatter aimed at the
    dump row), so the partition of the misses — and with it every output
    and every slot of the state; the dump row holds junk either way — is
    what the single rung `(miss_chunk,)` gives.  The narrow rung is an
    eighth of miss_chunk (4096 -> 512: a node's steady state misses a few
    hundred lanes a step) where that is a multiple of the fused consumer's
    tile (ops/match._FUSE_TB: `fused` takes a rung without padding it),
    and no rung is wider than the batch can fill: a step of `batch` lanes
    has at most that many misses, so it keeps the narrowest rung that
    holds them and those below it."""
    rungs = (miss_chunk,)
    if miss_chunk % (_LADDER_NARROW * _FUSE_TB) == 0:
        rungs += (miss_chunk // _LADDER_NARROW,)
    top = min(w for w in rungs if w >= min(miss_chunk, batch))
    return tuple(w for w in rungs if w <= top)


def _winner_mask(n_slots, slots, mask, dump):
    """Deterministic last-writer-wins for duplicate slots in one batch."""
    B = slots.shape[0]
    slots_m = jnp.where(mask, slots, dump)
    order = jnp.arange(B, dtype=jnp.int32)
    winner = jnp.full((n_slots + 1,), -1, jnp.int32).at[slots_m].max(order)
    return (winner[slots_m] == order) & mask


def _scatter_last(arr, slots, vals, mask, dump):
    """Masked 1-D scatter with last-writer-wins on duplicate slots."""
    is_winner = _winner_mask(arr.shape[0] - 1, slots, mask, dump)
    return arr.at[jnp.where(is_winner, slots, dump)].set(vals)


def _scatter_last_rows(arr, slots, rows, mask, dump):
    """Masked row scatter ((M, K) payload into (N+1, K)) with
    last-writer-wins; used only on the miss path where M is small (row
    scatters are slow on TPU — see FlowCache layout rationale)."""
    is_winner = _winner_mask(arr.shape[0] - 1, slots, mask, dump)
    return arr.at[jnp.where(is_winner, slots, dump)].set(rows)


def _second_chance_guard(flow: FlowCache, slot2, keys2, ins2, now, meta, A,
                         dump):
    """Thrash-resistant replacement (meta.second_chance): suppress
    inserts whose direct-mapped target is a LIVE, CONFIRMED established
    entry still under its 2-bit collision budget, and bump that entry's
    counter once per commit pass (winner-deduplicated).  The challenger
    stays uncached — cache semantics, it re-classifies on its next
    packet — so a gen_cache_thrash storm cannot evict an active
    established flow on first collision.  -> (flow', ins2').

    Known divergence (cache-topology observable, verdict-safe): the
    chunked sync path runs one commit pass PER ROUND, so a step whose
    misses span multiple miss_chunk rounds can bump a slot once per
    round while the scalar twin bumps once per step — colliding
    challengers in a later round may then evict an entry the oracle
    keeps.  The evicted flow re-misses and re-classifies to the same
    verdict (the PR 6 lost-update discipline); single-round passes match
    the oracle exactly.

    -> (flow', ins2', n_protected) — n_protected is the lane count the
    guard suppressed this pass (the telemetry `chance_bumps` counter),
    None unless meta.telemetry so the off path traces no extra ops."""
    ZC = _meta_cols(A)[3]
    tgt2 = jnp.where(ins2, slot2, dump)
    okr = flow.keys[tgt2]
    om3 = flow.meta[tgt2, ZC]
    id3 = 0xFF | REPLY_BIT
    tuple_differs = (
        (okr[:, : A + 1] != keys2[:, : A + 1]).any(axis=1)
        | ((okr[:, A + 1] & id3) != (keys2[:, A + 1] & id3))
    )
    ogen = (okr[:, A + 1] >> 9) & GEN_ETERNAL
    otmo = entry_timeout((om3 >> 29) & 1, okr[:, A + 1] & 0xFF,
                         meta.timeouts)
    cnt = (om3 >> CHANCE_SHIFT) & CHANCE_MAX
    protected = (
        ins2
        & (okr[:, A + 1] != 0)
        & tuple_differs
        & (ogen == GEN_ETERNAL)
        & (((om3 >> 29) & 1) != 0)
        & ((now - flow.ts[tgt2]) <= otmo)
        & (cnt < CHANCE_MAX)
    )
    ins2 = ins2 & ~protected
    n_protected = (protected.sum(dtype=jnp.int32) if meta.telemetry
                   else None)
    # One counter bump per protected slot per pass.
    win = _winner_mask(flow.keys.shape[0] - 1, slot2, protected, dump)
    bt = jnp.where(win, slot2, dump)
    cur = flow.meta[bt, ZC]
    newc = jnp.minimum(((cur >> CHANCE_SHIFT) & CHANCE_MAX) + 1, CHANCE_MAX)
    meta_col = (cur & ~CHANCE_MASK) | (newc << CHANCE_SHIFT)
    return (flow._replace(meta=flow.meta.at[bt, ZC].set(meta_col)), ins2,
            n_protected)


def _pack_meta1(code, svc_idx, dnat_port):
    return code | ((svc_idx + 1) << 2) | (dnat_port << 16)


def _unpack_meta1(m1):
    code = m1 & 3
    svc_idx = ((m1 >> 2) & 0x3FFF) - 1
    dnat_port = (m1 >> 16) & 0xFFFF
    return code, svc_idx, dnat_port


def _pack_rules(rule_in, rule_out, bits_in: int = 16):
    # The two rule indices share one 32-bit column: the ingress one in the
    # low `bits_in` bits, the egress one above (rule_split, invoked by
    # every pipeline constructor, finds the split that fits both
    # directions or refuses the rule set; callers composing to_device +
    # _pack_rules directly must call it themselves).
    # Stored +1 so the zero row means "no rule" (MISS).
    return (rule_in + 1) | ((rule_out + 1) << bits_in)


def _unpack_rules(rp, bits_in: int = 16):
    return ((rp & ((1 << bits_in) - 1)) - 1,
            ((rp >> bits_in) & ((1 << (32 - bits_in)) - 1)) - 1)


class PolicyCapacityError(ValueError):
    """A compiled policy set exceeds a hard datapath capacity bound (e.g.
    the 16-bit packed rule-attribution space).  DETERMINISTIC: the same
    bundle fails the same way every time, so the agent classifies it as a
    permanent (poison-bundle) rejection and reports a Failed realization
    upstream instead of burning its retry/backoff loop on it
    (agent/controller.sync).  Subclasses ValueError for callers that
    predate the typed error."""


def rule_split(cps: CompiledPolicySet) -> int:
    """-> the bits of the packed rule-attribution column (_pack_rules) that
    hold the ingress index; the egress index has the other 32 - bits.

    16/16 wherever both directions fit 16 bits (n_rules < 0xFFFE, stored
    +1 with the all-ones value kept free), so every such world keeps the
    one layout and the one compiled program it always had.  A rule set
    lopsided past that (upstream's xLargeScale shape: 75,000 ingress rules
    and no egress rule) gives the wide direction the bits the narrow one
    leaves: the split is a function of the two counts alone.  Raises
    PolicyCapacityError where no split holds both."""
    need_in, need_out = ((dt.n_rules + 2).bit_length()
                         for dt in (cps.ingress, cps.egress))
    if need_in <= 16 and need_out <= 16:
        return 16
    if need_in + need_out > 32:
        raise PolicyCapacityError(
            f"flow-cache rule packing holds both directions' rule indices "
            f"in 32 bits; {cps.ingress.n_rules} ingress and "
            f"{cps.egress.n_rules} egress rules need {need_in} + {need_out}"
            f"; split the policy set across datapath instances (per-Node "
            f"span dissemination keeps per-instance rule counts bounded in "
            f"the reference, architecture.md:57-60)"
        )
    return need_in if need_in > 16 else 32 - need_out


def make_pipeline(
    cps: CompiledPolicySet,
    svc: ServiceTables,
    *,
    flow_slots: int = 1 << 20,
    aff_slots: int = 1 << 18,
    ct_timeout_s: int = 3600,
    miss_chunk: int = 4096,
    host: bool = False,
    ct_syn_timeout_s: Optional[int] = None,
    ct_other_new_s: Optional[int] = None,
    ct_other_est_s: Optional[int] = None,
    fused: bool = False,
    dual_stack: bool = False,
    count_flow_stats: bool = False,
    prune_budget: int = 0,
    second_chance: bool = False,
    telemetry: bool = False,
):
    """-> (step fn, initial PipelineState, (DeviceRuleSet, DeviceServiceTables)).

    step(state, drs, dsvc, src_f, dst_f, proto, sport, dport, now, gen) ->
    (state', out dict).  drs/dsvc are explicit args so a control-plane bundle
    commit is just "call with the new tensors + a bumped gen" — the
    double-buffered rule-swap analog of OVS bundle transactions
    (ofctrl_bridge.go:468); bumping gen invalidates cached denials while
    established (ALLOW) entries persist, per conntrack semantics.

    host=True keeps every tensor numpy-resident (no device placement) — for
    compile checks on hosts whose accelerator runtime may be broken; jit
    places numpy leaves itself at call time.
    """
    bits_in = rule_split(cps)
    if host:
        drs, match_meta = to_host(cps, prune_budget=prune_budget)
        dsvc = svc_to_host(svc)
    else:
        drs, match_meta = to_device(cps, prune_budget=prune_budget)
        dsvc = svc_to_device(svc)
    meta = PipelineMeta(
        match=match_meta,
        flow_slots=flow_slots,
        aff_slots=aff_slots,
        ct_timeout_s=ct_timeout_s,
        miss_chunk=miss_chunk,
        ct_syn_timeout_s=ct_syn_timeout_s,
        ct_other_new_s=ct_other_new_s,
        ct_other_est_s=ct_other_est_s,
        fused=fused,
        key_words=10 if dual_stack else 4,
        count_flow_stats=count_flow_stats,
        second_chance=second_chance,
        telemetry=telemetry,
        rule_bits_in=bits_in,
    )
    state = init_state(flow_slots, aff_slots, xp=np if host else jnp,
                       key_words=meta.key_words)

    def step(state, drs, dsvc, src_f, dst_f, proto, sport, dport, now, gen,
             v6=None, lens=None):
        return pipeline_step(
            state, drs, dsvc, src_f, dst_f, proto, sport, dport, now, gen,
            meta=meta, v6=v6, lens=lens,
        )

    step.meta = meta  # expose for callers embedding the step in larger jits
    return step, state, (drs, dsvc)


@device_scope("service_lb")
def _service_lb(
    aff: AffinityTable,
    dsvc: DeviceServiceTables,
    h: jax.Array,
    src_f: jax.Array,
    dst_f: jax.Array,
    proto: jax.Array,
    dport: jax.Array,
    now: jax.Array,
    aff_slots: int,
    wide=None,
):
    """ServiceLB + affinity + endpoint choice for a (miss) sub-batch.

    svc_idx is an LB-program index (compiler/services.py): ClusterIP
    frontends resolve to the cluster view (== service index), external
    frontends (LoadBalancer IP / NodePort) to their per-policy shadow view.

    dsr flags lanes whose program is a DSR delivery program (ref
    pipeline.go:145 DSRServiceMarkTable): the endpoint is SELECTED (it
    drives forwarding and policy) but the packet's L3 destination is NOT
    rewritten and no SNAT applies — dnat_ip/dnat_port then carry the
    delivery endpoint, with the no-rewrite semantic signaled by the flag.

    wide (dual-stack worlds): (saddr, daddr, is6) — the lanes' 4-word
    address forms.  v4 lanes probe the narrow frontend table exactly as
    in v4-only mode; v6 lanes probe the lexicographic v6 sub-table
    (dsvc.uip6_w — the metaProxier family split, proxier.go:1379-1465)
    and their DNAT resolution is the endpoint's wide word row.

    -> (svc_idx, no_ep, dnat_ip_f, dnat_port, snat, dsr, dnat_w, learn)
    — dnat_w is the wide post-DNAT dst ((M, 4), None in v4-only mode).
    """
    saddr = daddr = is6 = None
    if wide is not None:
        saddr, daddr, is6 = wide
    row = jnp.searchsorted(dsvc.uip_f, dst_f, side="left")
    row = jnp.clip(row, 0, dsvc.uip_f.shape[0] - 1)
    ip_is_svc = dsvc.uip_f[row] == dst_f
    key = (proto << 16) + dport
    slot_eq = dsvc.ppk[row] == key[:, None]  # (M, MAXP)
    slot_found = slot_eq.any(axis=1)
    slot_col = jnp.argmax(slot_eq, axis=1)
    hit_lane = ip_is_svc & slot_found
    if is6 is not None:
        # v6 lanes carry a don't-care v4 dst column: never match narrow.
        hit_lane = hit_lane & (is6 == 0)
    svc_idx = jnp.where(hit_lane, dsvc.slot_svc[row, slot_col], MISS)
    snat_sel = jnp.where(hit_lane, dsvc.slot_snat[row, slot_col], 0)

    if is6 is not None and dsvc.uip6_w.shape[0] > 0:
        # v6 frontend probe: exact 4-word match (all-pairs — the v6
        # frontend table is small: the shape ops/match._searchsorted6
        # keeps up to its flat size).
        eq6 = (dsvc.uip6_w[None, :, :] == daddr[:, None, :]).all(axis=2)
        ip6_hit = eq6.any(axis=1)
        row6 = jnp.argmax(eq6, axis=1)
        slot_eq6 = dsvc.ppk6[row6] == key[:, None]
        hit6 = (is6 != 0) & ip6_hit & slot_eq6.any(axis=1)
        col6 = jnp.argmax(slot_eq6, axis=1)
        svc_idx = jnp.where(hit6, dsvc.slot_svc6[row6, col6], svc_idx)
        snat_sel = jnp.where(hit6, dsvc.slot_snat6[row6, col6], snat_sel)

    is_svc = svc_idx >= 0
    svc_safe = jnp.clip(svc_idx, 0, dsvc.n_ep.shape[0] - 1)
    no_ep = is_svc & (dsvc.has_ep[svc_safe] == 0)

    # Session affinity (ClientIP, hard timeout) — the learn-flow analog.
    aff_on = is_svc & (dsvc.aff_timeout[svc_safe] > 0)
    if saddr is None:
        src_raw = _raw_bits(src_f)
        ah = hashing.fnv_mix([src_raw, svc_safe], xp=jnp)
    else:
        # Wide client hash: all 4 raw words + the program — the oracle
        # twin mixes the identical sequence (PipelineOracle.fresh_walk).
        ah = hashing.fnv_mix(
            [_raw_bits(saddr[:, i]) for i in range(4)] + [svc_safe], xp=jnp
        )
    aslot = (ah & jnp.uint32(aff_slots - 1)).astype(jnp.int32)
    # Entry liveness = stored ep+1 > 0 (works even for learns at now == 0).
    # A stored ep slot >= the service's current endpoint count is stale
    # (endpoints shrank since the learn) — treat as a miss and re-select, the
    # analog of AntreaProxy's stale learn-flow/conntrack cleanup on endpoint
    # deletion (ref proxier.go syncProxyRules endpoint-change handling).
    if saddr is None:
        client_match = aff.key_client[aslot] == src_f
    else:
        client_match = (aff.key_client[aslot] == saddr).all(axis=1)
    aff_hit = (
        aff_on
        & (aff.ep[aslot] > 0)
        & (aff.ep[aslot] - 1 < dsvc.n_ep[svc_safe])
        & client_match
        & (aff.key_svc[aslot] == svc_idx)
        & ((now - aff.ts[aslot]) <= dsvc.aff_timeout[svc_safe])
    )
    hash_ep = (h.astype(jnp.int32) & jnp.int32(0x7FFFFFFF)) % dsvc.n_ep[svc_safe]
    ep_col = jnp.where(aff_hit, aff.ep[aslot] - 1, hash_ep)
    # Flat indirect endpoint gather — no per-program endpoint cap (the
    # reference's group buckets are unbounded, serviceEndpointGroup).
    eidx = jnp.clip(dsvc.ep_base[svc_safe] + ep_col, 0, dsvc.ep_ip_f.shape[0] - 1)

    use_ep = is_svc & ~no_ep
    dnat_ip = jnp.where(use_ep, dsvc.ep_ip_f[eidx], dst_f)
    dnat_port = jnp.where(use_ep, dsvc.ep_port[eidx], dport)
    dnat_w = None
    if saddr is not None:
        # Wide post-DNAT dst: v4 lanes map their narrow resolution; v6
        # service lanes gather the endpoint's wide row; v6 non-service
        # lanes keep their literal dst words.
        dnat_w = jnp.where(
            (use_ep & (is6 != 0))[:, None],
            dsvc.ep_ipw_f[eidx],
            _wide_words(dnat_ip, daddr, is6),
        )
    # SNAT is a property of the matched FRONTEND entry (NodePort/LB under
    # ETP=Cluster), not of the endpoint program.
    snat = jnp.where(use_ep, snat_sel, 0)
    # DSR is a property of the PROGRAM (dedicated per-service DSR view),
    # so fast-path hits can recover it from the cached svc_idx alone.
    dsr = jnp.where(use_ep, dsvc.prog_dsr[svc_safe], 0)
    learn = {
        "mask": aff_on & ~aff_hit & ~no_ep,
        "aslot": aslot,
        "client": src_f if saddr is None else saddr,
        "svc": svc_idx,
        "ep": ep_col + 1,  # stored +1: 0 means empty slot
    }
    return svc_idx, no_ep, dnat_ip, dnat_port, snat, dsr, dnat_w, learn


def _svc_ref_of(svc_idx: jax.Array, dsvc: DeviceServiceTables) -> jax.Array:
    """toServices probe identity (ops/match svcref contract): the lane's
    resolved LB program mapped to its OWNING service index via prog_svc;
    MISS (-1) for non-service lanes.  The ONE implementation shared by
    step and trace so the probe-key contract cannot drift between them."""
    return jnp.where(
        svc_idx >= 0,
        dsvc.prog_svc[jnp.clip(svc_idx, 0, dsvc.prog_svc.shape[0] - 1)],
        MISS,
    )


def entry_timeout(conf, proto, timeouts, xp=jnp):
    """Per-entry idle timeout from the CONFIRMED bit + protocol (scalar or
    array): the kernel's per-state conntrack lifetime selection.  Single
    source of truth for step/trace/dump on both datapaths."""
    t_syn, t_est, t_onew, t_oest = timeouts
    is_tcp = proto == PROTO_TCP
    return xp.where(
        is_tcp,
        xp.where(conf != 0, t_est, t_syn),
        xp.where(conf != 0, t_oest, t_onew),
    )


def _cache_lookup(flow, slot, addr, pp, pg_cur, pg_est, now, proto, meta):
    """Shared fast-path flow-cache probe for step and trace (single source of
    truth for the FlowCache row layout).

    addr is the packet's (B, A) address-column matrix — A=2 ([src_f,
    dst_f]) in v4-only worlds, A=8 (wide word form) in dual-stack worlds;
    key rows are [addr..., pp, pg].

    -> (hit, est, rpl, meta_row (B,4), key_row, ts_col) where meta_row/
    key_row/ts_col are the gathered cache rows.  rpl flags
    reply-direction (reverse-tuple) hits: their meta row carries the un-DNAT rewrite (original service
    frontend ip/port) instead of a DNAT resolution.

    Freshness is per-state (entry_timeout): half-open TCP and non-TCP
    entries can carry shorter lifetimes than confirmed connections.  With
    uniform timeouts (the default) the per-lane selection compiles out.
    """
    A = addr.shape[1]
    kr = flow.keys[slot]  # (B, A+2) row gather
    kpg = kr[:, A + 1]
    pg_rpl = pg_est | REPLY_BIT
    key_hit = (
        (kr[:, :A] == addr).all(axis=1)
        & (kr[:, A] == pp)
        & ((kpg == pg_cur) | (kpg == pg_est) | (kpg == pg_rpl))
    )
    mr = flow.meta[slot]
    _, _, _, ZC = _meta_cols(A)
    tmo = meta.timeouts
    if tmo[0] == tmo[1] == tmo[2] == tmo[3]:
        timeout = tmo[1]  # uniform: scalar, no per-lane work
    else:
        timeout = entry_timeout((mr[:, ZC] >> 29) & 1, proto, tmo)
    fresh = (now - flow.ts[slot]) <= timeout
    hit = key_hit & fresh
    est = hit & ((kpg == pg_est) | (kpg == pg_rpl))
    rpl = hit & (kpg == pg_rpl)
    return hit, est, rpl, mr, kr, flow.ts[slot]


def _pipeline_step(
    state: PipelineState,
    drs: DeviceRuleSet,
    dsvc: DeviceServiceTables,
    src_f: jax.Array,
    dst_f: jax.Array,
    proto: jax.Array,
    sport: jax.Array,
    dport: jax.Array,
    now: jax.Array,  # scalar i32 seconds
    gen: jax.Array,  # scalar i32 rule-set generation (bundle commit counter)
    *,
    meta: PipelineMeta,
    hit_combine=None,
    valid=None,
    no_commit=None,
    flags=None,
    v6=None,
    lens=None,
    prune_exclude=None,
):
    flow, aff = state.flow, state.aff
    B = src_f.shape[0]
    N = meta.flow_slots
    M = meta.miss_chunk
    dump = N
    A = meta.key_words - 2  # address columns: 2 (v4) / 8 (dual-stack wide)

    with device_scope("fast_path"), device_scope("probe"):
        src_raw = _raw_bits(src_f)
        dst_raw = _raw_bits(dst_f)
        pp = (sport << 16) | dport
        gen_w = jnp.asarray(gen, jnp.int32) % GEN_ETERNAL  # never == GEN_ETERNAL

        # ---- fast path: flow-cache lookup (2 row gathers + 1 column gather) ----
        if A == 2:
            if v6 is not None:
                raise ValueError(
                    "v6 lanes require a dual_stack pipeline "
                    "(make_pipeline(dual_stack=True))"
                )
            saddr = daddr = is6 = None  # wide-mode-only locals
            addr = jnp.stack([src_f, dst_f], axis=1)
            h = hashing.flow_hash(src_raw, dst_raw, proto, sport, dport, xp=jnp)
        else:
            # Wide (dual-stack) addressing: every lane is a 4-word v4-mapped /
            # v6 quadruple (sign-flipped per word, utils/ip.key_to_words).
            if v6 is not None:
                src6w, dst6w, is6 = v6
            else:
                is6 = jnp.zeros_like(src_f)
                src6w = dst6w = None
            saddr = _wide_words(src_f, src6w, is6)
            daddr = _wide_words(dst_f, dst6w, is6)
            addr = jnp.concatenate([saddr, daddr], axis=1)
            h = hashing.flow_hash_wide(
                [addr[:, i] for i in range(8)], proto, sport, dport, xp=jnp
            )
        slot = (h & jnp.uint32(N - 1)).astype(jnp.int32)
        pg_cur = proto | 0x100 | (gen_w << 9)
        pg_est = proto | 0x100 | (GEN_ETERNAL << 9)
        hit, est, rpl, mr, kr0, ts0 = _cache_lookup(
            flow, slot, addr, pp, pg_cur, pg_est, now, proto, meta
        )
        if valid is not None:
            # Lane mask (SpoofGuard gating, models/forwarding.py): excluded
            # lanes neither refresh nor commit any state and take the fast-path
            # default image — the stage order of the reference, where
            # SpoofGuard drops happen BEFORE conntrack/policy tables.
            hit = hit & valid
            est = est & valid
            rpl = rpl & valid
        tel_on = meta.telemetry
        if tel_on:
            # Probe-split telemetry (hit / stale / miss), recomputed XLA-side
            # from the SAME gathered key rows the probe decoded (kr0), so it
            # costs three reductions and zero extra gathers.  `stale` = the
            # key matched but the entry aged out (the megaflow-revalidation
            # signal: the flow was cached and expired under traffic);
            # generation-stale denials count as plain misses — they are
            # invisible to lookups by design, not aged occupancy.  Lanes
            # another dispatch owns (mesh spill retries, prune_exclude) and
            # valid-masked lanes are excluded, the exactly-once discipline
            # prune metering already follows.
            tv = jnp.ones(B, bool) if valid is None else (valid != 0)
            if prune_exclude is not None:
                tv = tv & ~prune_exclude
            kpg0 = kr0[:, A + 1]
            key_hit0 = (
                (kr0[:, :A] == addr).all(axis=1)
                & (kr0[:, A] == pp)
                & ((kpg0 == pg_cur) | (kpg0 == pg_est)
                   | (kpg0 == (pg_est | REPLY_BIT)))
            )
            tel_probe_hit = (hit & tv).sum(dtype=jnp.int32)
            tel_probe_stale = (key_hit0 & ~hit & tv).sum(dtype=jnp.int32)
            tel_probe_miss = (~key_hit0 & tv).sum(dtype=jnp.int32)
        DC, M1C, RC, ZC = _meta_cols(A)
        c_code, c_svc, c_dport = _unpack_meta1(mr[:, M1C])
        # Narrow dnat view: the v4 value (wide worlds: word 3, the v4-mapped
        # column — a don't-care for v6 lanes, whose consumers read c_dnat_w).
        c_dnat_ip = mr[:, DC]
        c_dnat_w = mr[:, 0:4] if A == 8 else None
        c_rule_in, c_rule_out = _unpack_rules(mr[:, RC],
                                                 meta.rule_bits_in)

    with device_scope("fast_path"), device_scope("refresh"):
        # Idle-timeout refresh for hits.
        flow = flow._replace(ts=flow.ts.at[jnp.where(hit, slot, dump)].set(now))

        if meta.second_chance:
            # Second-chance reset: a hit is the entry's "referenced" event —
            # clear the 2-bit collision counter so active flows keep their
            # protection (the CLOCK-algorithm reference bit, see CHANCE_SHIFT).
            ZC_ = _meta_cols(A)[3]
            tgt_h = jnp.where(hit, slot, dump)
            flow = flow._replace(meta=flow.meta.at[tgt_h, ZC_].set(
                flow.meta[tgt_h, ZC_] & ~CHANCE_MASK))

        if meta.count_flow_stats:
            # Per-direction traffic counters (conntrack OriginalPackets/
            # OriginalBytes, flowexporter/types.go:59): every hit adds to ITS
            # entry's columns.  64-bit accumulation in two i32 limbs (the
            # kernel's u64 counters; the old i32 saturation capped volumes at
            # 2GB): the low limb adds with a wrapping scatter, and one carry
            # per slot propagates into the high limb — exact as long as one
            # entry receives < 2^32 bytes within a SINGLE batch (a per-batch
            # bound, not a lifetime cap).
            lv = jnp.zeros(B, jnp.int32) if lens is None else lens
            ctgt = jnp.where(hit, slot, dump)
            cwin = _winner_mask(N, slot, hit, dump)  # one carry writer per slot

            def wide_add(lo, hi, add):
                old = lo[ctgt]
                lo = lo.at[ctgt].add(add)
                # u32 view shrank => the slot's low limb wrapped exactly once.
                carried = lo[ctgt].astype(jnp.uint32) < old.astype(jnp.uint32)
                hi = hi.at[jnp.where(cwin & carried, ctgt, dump)].add(1)
                return lo, hi

            new_pk, new_pkh = wide_add(flow.pkts, flow.pkts_hi,
                                       jnp.ones(B, jnp.int32))
            new_oc, new_och = wide_add(flow.octets, flow.octets_hi,
                                       jnp.maximum(lv, 0))
            flow = flow._replace(pkts=new_pk, octets=new_oc,
                                 pkts_hi=new_pkh, octets_hi=new_och)

        # Conntrack refreshes BOTH tuple directions on traffic in either
        # direction (one kernel-ct connection == our two cache entries): an
        # active connection's reply leg must not idle out while forward traffic
        # keeps flowing (ovs-pipeline.md:1200 — reply traffic of an established
        # connection is never policy-dropped).  Refreshing the partner on EVERY
        # hit would add a key gather + ts scatter to the throughput path
        # (~20% measured on v5e), so it is DEFERRED: meta[:,3] (pref) records
        # the last partner-refresh attempt, and the partner walk runs only for
        # lanes older than ct_timeout/2 — under lax.cond, so batches with no
        # due lane pay nothing.  Sound because a verified refresh also
        # resurrects a stale-but-unevicted partner: the connection provably
        # stayed active (this entry's own freshness), matching kernel ct which
        # would have refreshed the shared entry at every packet.  The partner
        # slot is recomputed from the cached DNAT meta and its key VERIFIED
        # before the refresh, so an unrelated entry that evicted the partner is
        # never life-extended.
        #   fwd est hit:  partner = reply entry (dnat_ip, src, dnat_port, sport)
        #   reply hit:    partner = fwd entry (dst=client, frontend ip/port)
        p_half = max(1, meta.ct_timeout_s // 2)
        pmask = meta.pref_mask
        c_pref = mr[:, ZC] & pmask  # strip the cached snat/dsr(/chance) bits
        # Age in mod-2^29 arithmetic (PREF_MASK; bits 0-28 carry pref, bit 29
        # is CONFIRMED in the meta3 layout; under second_chance the stamp
        # narrows to bits 0-26): exact whenever the true age < the mask
        # width, which the idle timeout guarantees for any live entry.
        p_need = est & (((now - c_pref) & pmask) >= p_half)

        def partner_probe(keys, mask):
            """Derive each lane's PARTNER tuple (the other conntrack direction
            of its hit entry, un/re-DNAT applied) and key-verify it against
            `keys` — shared by the deferred partner refresh and the FIN/RST
            teardown so the two can never drift.  -> (p_slot, live_mask).

            Dual-stack: the cached meta rows carry the 4-word DNAT / un-DNAT
            resolution (c_dnat_w), so the wide partner tuple is the exact
            structural mirror of the narrow one — forward hits pair with
            (dnat, src), reply hits with (dst, cached frontend)."""
            p_sport = jnp.where(rpl, dport, c_dport)
            p_dport = jnp.where(rpl, c_dport, sport)
            p_pg = jnp.where(rpl, pg_est, pg_est | REPLY_BIT)
            if A == 2:
                p_src = jnp.where(rpl, dst_f, c_dnat_ip)
                p_dst = jnp.where(rpl, c_dnat_ip, src_f)
                p_addr = jnp.stack([p_src, p_dst], axis=1)
                p_h = hashing.flow_hash(
                    _raw_bits(p_src), _raw_bits(p_dst), proto, p_sport, p_dport,
                    xp=jnp,
                )
            else:
                rplw = (rpl != 0)[:, None]
                p_srcw = jnp.where(rplw, daddr, c_dnat_w)
                p_dstw = jnp.where(rplw, c_dnat_w, saddr)
                p_addr = jnp.concatenate([p_srcw, p_dstw], axis=1)
                p_h = hashing.flow_hash_wide(
                    [p_addr[:, i] for i in range(8)], proto, p_sport, p_dport,
                    xp=jnp,
                )
            p_slot = (p_h & jnp.uint32(N - 1)).astype(jnp.int32)
            pkr = keys[p_slot]
            live = (
                mask
                & (pkr[:, :A] == p_addr).all(axis=1)
                & (pkr[:, A] == ((p_sport << 16) | p_dport))
                & (pkr[:, A + 1] == p_pg)
            )
            return p_slot, live

        def partner_refresh(flow):
            p_slot, p_live = partner_probe(flow.keys, p_need)
            if meta.second_chance:
                # Read the CURRENT meta for the preserved high bits: the
                # hit-path reset above already cleared the chance counter on
                # this very slot, and re-stamping from the start-of-batch
                # snapshot would resurrect it.
                tgt_p = jnp.where(p_need, slot, dump)
                return flow._replace(
                    ts=flow.ts.at[jnp.where(p_live, p_slot, dump)].set(now),
                    meta=flow.meta.at[tgt_p, ZC].set(
                        (now & pmask) | (flow.meta[tgt_p, ZC] & ~pmask)
                    ),
                )
            return flow._replace(
                ts=flow.ts.at[jnp.where(p_live, p_slot, dump)].set(now),
                # Attempt-time update even when the partner is gone, so an
                # evicted partner doesn't drag the walk into every batch.
                # Preserve the cached snat/dsr bits alongside the new stamp.
                meta=flow.meta.at[jnp.where(p_need, slot, dump), ZC].set(
                    (now & pmask) | (mr[:, ZC] & ~pmask)
                ),
            )

        flow = jax.lax.cond(p_need.any(), partner_refresh, lambda f: f, flow)

        # SYN_SENT -> ESTABLISHED confirmation (the kernel ct state machine's
        # two-way-traffic transition): the FIRST reply-direction hit proves the
        # peer answered; set CONF on the hit entry and its verified partner so
        # both directions graduate to the confirmed lifetime.  Once per
        # connection -> under lax.cond, zero steady-state cost.
        conf_need = rpl & (((mr[:, ZC] >> 29) & 1) == 0)

        def confirm(flow):
            # OR into the CURRENT meta (partner_refresh may have just stamped
            # pref on this very slot; clobbering it with the start-of-batch
            # snapshot would diverge from the scalar oracle's pref=now).
            m = flow.meta
            tgt0 = jnp.where(conf_need, slot, dump)
            m = m.at[tgt0, ZC].set(m[tgt0, ZC] | CONF_BIT)
            c_slot, c_live = partner_probe(flow.keys, conf_need)
            tgt = jnp.where(c_live, c_slot, dump)
            m = m.at[tgt, ZC].set(m[tgt, ZC] | CONF_BIT)
            return flow._replace(meta=m)

        flow = jax.lax.cond(conf_need.any(), confirm, lambda f: f, flow)

        # TCP connection teardown (conntrack close): a FIN or RST on an
        # established entry removes BOTH tuple directions after this packet's
        # own (still-established) verdict — subsequent same-tuple packets
        # re-classify under the CURRENT policy instead of est-bypassing a
        # connection that no longer exists.  Conservative vs kernel ct (which
        # walks FIN_WAIT/TIME_WAIT): trailing segments of a closing connection
        # re-classify; nothing ever bypasses policy MORE than the kernel.
        # Out-of-window teardown cost: zero when no lane carries the flags.
        if flags is not None:
            td = est & (proto == PROTO_TCP) & ((flags & _TEARDOWN_FLAGS) != 0)

            def teardown(flow):
                keys = flow.keys.at[jnp.where(td, slot, dump)].set(0)
                t_slot, t_live = partner_probe(keys, td)
                keys = keys.at[jnp.where(t_live, t_slot, dump)].set(0)
                return flow._replace(keys=keys)

            flow = jax.lax.cond(td.any(), teardown, lambda f: f, flow)

    with device_scope("fast_path"), device_scope("assemble"):
        miss = ~hit if valid is None else (~hit & valid)
        n_miss = miss.sum(dtype=jnp.int32)

        # Fast-path output images (+1 dump element for masked slow-path scatter).
        def outbuf(vals):
            return jnp.concatenate([vals, jnp.zeros((1,), jnp.int32)])

        # ADMITTED miss lanes default to meta.miss_code: ACT_ALLOW in
        # synchronous mode (overwritten by the slow path anyway), the
        # admission policy's provisional verdict in the async fast step
        # (defer_misses: misses queued for the background engine —
        # datapath/slowpath).  Valid-masked lanes (SpoofGuard/ARP/IGMP-punt,
        # handled BEFORE the pipeline) are NOT misses and keep the plain
        # ALLOW image their kind overrides expect (forwarding.py) — a hold
        # policy must never report DROP for a lane it never evaluated.
        out_code = outbuf(jnp.where(
            hit, c_code, jnp.where(miss, meta.miss_code, ACT_ALLOW)
        ))
        out_svc = outbuf(jnp.where(hit, c_svc, MISS))
        out_dnat_ip = outbuf(jnp.where(hit, c_dnat_ip, dst_f))
        out_dnat_port = outbuf(jnp.where(hit, c_dport, dport))
        out_rule_in = outbuf(jnp.where(hit, c_rule_in, MISS))
        out_rule_out = outbuf(jnp.where(hit, c_rule_out, MISS))
        out_committed = outbuf(jnp.zeros(B, jnp.int32))
        # SNAT mark cached in meta3's sign bit at commit time; reply-direction
        # hits carry the un-SNAT implicitly via the restored frontend tuple.
        c_snat = (mr[:, ZC] >> 31) & 1
        out_snat = outbuf(jnp.where(hit & ~rpl, c_snat, 0))
        # DSR delivery mark, pinned into the entry at commit time exactly like
        # the SNAT mark (meta3 bit 30): service updates that renumber LB
        # programs cannot flip an established connection's delivery mode.
        c_dsr = (mr[:, ZC] >> 30) & 1
        out_dsr = outbuf(jnp.where(hit & ~rpl, c_dsr, 0))
        # Wide DNAT image ((B+1, 4), wide worlds only): cache hits read the
        # cached word row, misses default to the literal dst words and are
        # overwritten by the slow path.
        if A == 8:
            out_dnat_w = jnp.concatenate(
                [jnp.where(hit[:, None], c_dnat_w, daddr),
                 jnp.zeros((1, 4), jnp.int32)], axis=0,
            )
        else:
            out_dnat_w = None

    # Round-7 prune observability (python-static: zero ops, zero extra
    # outputs when the budget is 0 — the HLO-identity contract).
    prune_on = meta.match.prune_budget > 0
    # Telemetry appends two slow-path counters LAST (tel_dma_hb,
    # tel_chance_bumps) — after the wide-DNAT image and the prune trio —
    # so every existing position is unchanged when the knob is off.
    n_extra = ((1 if A == 8 else 0) + (3 if prune_on else 0)
               + (2 if tel_on else 0))
    # The fixed head of the slow path's output tuple (nine images, n_evict,
    # n_reclaim, round_lanes) and of the rounds' carry (r, lanes, the two
    # counters, flow, aff, the nine images); the optional outputs follow.
    N_OUT, N_CARRY = 12, 15
    # A step that is one shard of a sharded program (a `hit_combine` seam
    # is given: parallel/meshpath's step, its spill-retry rungs and its
    # drains) keeps the single rung.  Its host waits for the slowest
    # replica and its foreign walks run full rounds, so the narrow rung
    # buys ~2 % there; and on the four-chip v5e host the two-body program's
    # top-rung spill retry cost the HOST +22 ms a step (PERF.md s6, PR 32).
    ladder = round_ladder(M, B)[:2 if hit_combine is None else 1]

    # ---- slow path: ServiceLB + classify + commit, misses only -------------
    def slow(args):
        flow, aff, outs = args
        (out_code, out_svc, out_dnat_ip, out_dnat_port, out_rule_in,
         out_rule_out, out_committed, out_snat, out_dsr, n_evict0,
         n_reclaim0, lanes0) = outs[:N_OUT]
        pos = N_OUT
        out_dnat_w = None
        if A == 8:
            out_dnat_w = outs[pos]
            pos += 1
        if prune_on:
            pr_sk0, pr_fb0, pr_hist0 = outs[pos:pos + 3]
            pos += 3
        if tel_on:
            tel_hb0, tel_sc0 = outs[pos:pos + 2]
        # Batch semantics: affinity LOOKUPS see start-of-batch state even
        # across slow-path rounds; learns land in the carried table.
        aff_snap = aff
        midx = jnp.nonzero(miss, size=B, fill_value=B)[0].astype(jnp.int32)

        # One round at width W: the lanes midx[r*M : r*M + W].  W is M in
        # every round but the last, which takes the narrowest rung of
        # round_ladder that holds what is left; the lanes the narrow rung
        # drops are padding (`valid` false below), so the result is the
        # single rung's, bit for bit.
        def round_body(W, carry):
            (r, lanes, n_evict, n_reclaim, flow, aff, out_code, out_svc,
             out_dnat_ip, out_dnat_port, out_rule_in, out_rule_out,
             out_committed, out_snat, out_dsr) = carry[:N_CARRY]
            pos = N_CARRY
            out_dnat_w = None
            if A == 8:
                out_dnat_w = carry[pos]
                pos += 1
            if prune_on:
                pr_sk, pr_fb, pr_hist = carry[pos:pos + 3]
                pos += 3
            if tel_on:
                tel_hb, tel_sc = carry[pos:pos + 2]
            idx = jax.lax.dynamic_slice(
                jnp.concatenate([midx, jnp.full((W,), B, jnp.int32)]),
                (r * M,),
                (W,),
            )
            valid = idx < B
            safe = jnp.clip(idx, 0, B - 1)
            s_f = src_f[safe]
            d_f = dst_f[safe]
            p_m = proto[safe]
            sp_m = sport[safe]
            dp_m = dport[safe]
            h_m = h[safe]
            slot_m = slot[safe]
            pp_m = pp[safe]
            if meta.count_flow_stats:
                lv_m = (jnp.zeros(W, jnp.int32) if lens is None
                        else jnp.maximum(lens[safe], 0))
            if A == 8:
                saddr_m = saddr[safe]
                daddr_m = daddr[safe]
                is6_m = is6[safe]
                wide_m = (saddr_m, daddr_m, is6_m)
            else:
                is6_m = None
                wide_m = None

            (svc_idx, no_ep, dnat_ip, dnat_port, snat_m, dsr_m, dnat_w,
             learn) = _service_lb(
                aff_snap, dsvc, h_m, s_f, d_f, p_m, dp_m, now,
                meta.aff_slots, wide=wide_m,
            )

            # Lanes classify on their POST-DNAT tuple (EndpointDNAT
            # before the policy tables, ref pipeline.go table order);
            # v6 lanes' post-DNAT words (dnat_w) double as the
            # classifier's v6 lanes (same flipped-word layout the
            # interval tables expect).
            cls = classify_batch(
                drs, s_f, dnat_ip, p_m, dnat_port,
                meta=meta.match, hit_combine=hit_combine,
                # The fused consumer is shard-aware (global word
                # offsets from word_idx), so it composes with
                # hit_combine.
                fused=meta.fused,
                v6=None if wide_m is None else (saddr_m, dnat_w, is6_m),
                svc_ref=_svc_ref_of(svc_idx, dsvc),
            )
            code = jnp.where(
                no_ep, ACT_REJECT, cls["code"]).astype(jnp.int32)
            # SvcReject happens in EndpointDNAT, BEFORE the policy
            # tables (ref pipeline.go table order): no rule
            # attribution for it.
            rule_in = jnp.where(no_ep, MISS, cls["ingress_rule"])
            rule_out = jnp.where(no_ep, MISS, cls["egress_rule"])
            if prune_on:
                # Prune observability (valid lanes only — padding lanes
                # classify garbage tuples and must not meter).
                # prune_exclude (round 8): lanes another dispatch owns
                # the evidence for — the mesh's spilled lanes, whose
                # HOME-routed retry re-walks them — are excluded here so
                # the PruneAutotuner band sees each lane's SERVING walk
                # exactly once (parallel/meshpath._spill_retry).
                pv = valid
                if prune_exclude is not None:
                    pv = pv & ~prune_exclude[safe]
                pr_sk = pr_sk + (cls["prune_skip"] & pv).sum(
                    dtype=jnp.int32)
                pr_fb = pr_fb + (cls["prune_fb"] & pv).sum(
                    dtype=jnp.int32)
                pr_hist = pr_hist + _prune_bucket_counts(
                    cls["prune_cand"], pv)

            # no_commit lanes (multicast dst — the reference's multicast
            # pipeline bypasses conntrack entirely, pkg/agent/openflow/
            # multicast.go) classify fresh every time: no cache entry in
            # either direction, and `committed` reports 0.
            committed_m = code == ACT_ALLOW
            ins = valid
            if no_commit is not None:
                nc_m = no_commit[safe]
                committed_m = committed_m & ~nc_m
                ins = ins & ~nc_m

            # Scatter results into the output images.
            tgt = jnp.where(valid, idx, B)
            out_code = out_code.at[tgt].set(code)
            out_svc = out_svc.at[tgt].set(svc_idx)
            out_dnat_ip = out_dnat_ip.at[tgt].set(dnat_ip)
            if A == 8:
                out_dnat_w = out_dnat_w.at[tgt].set(dnat_w)
            out_dnat_port = out_dnat_port.at[tgt].set(dnat_port)
            out_rule_in = out_rule_in.at[tgt].set(rule_in)
            out_rule_out = out_rule_out.at[tgt].set(rule_out)
            out_committed = out_committed.at[tgt].set(committed_m.astype(jnp.int32))
            out_snat = out_snat.at[tgt].set(snat_m)
            out_dsr = out_dsr.at[tgt].set(dsr_m)

            # Insert into the flow cache: ALLOW entries as ETERNAL
            # (conntrack commit), denials tagged with the current gen.
            @device_scope("cache_commit")
            def do_commit(flow, aff, n_evict, n_reclaim, tel_sc):
                egen = jnp.where(committed_m, GEN_ETERNAL, gen_w)
                pg_ins = p_m | 0x100 | (egen << 9)
                m1 = _pack_meta1(code, svc_idx, dnat_port)
                rules_p = _pack_rules(rule_in, rule_out,
                                      meta.rule_bits_in)
                # Column 3 = snat(31) | dsr(30) | pref (the commit
                # freshens both directions; the frontend SNAT mark and the
                # DSR delivery mark are pinned here for the connection's
                # lifetime).
                pref_col = jnp.full((W,), now & pmask, jnp.int32)
                zcol = (pref_col
                        | jnp.where(snat_m > 0, REPLY_BIT, 0)
                        | jnp.where(dsr_m > 0, DSR_BIT, 0))
                if A == 2:
                    addr_m = jnp.stack([s_f, d_f], axis=1)
                    meta_rows = jnp.stack([dnat_ip, m1, rules_p, zcol], axis=1)
                else:
                    addr_m = jnp.concatenate([saddr_m, daddr_m], axis=1)
                    # Wide meta row: [dn_w0..3, m1, rules, z, pad] — the
                    # 4-word DNAT resolution IS the narrow column's role
                    # (word 3 doubles as the v4 view, _meta_cols).
                    meta_rows = jnp.concatenate(
                        [dnat_w,
                         jnp.stack([m1, rules_p, zcol,
                                    jnp.zeros((W,), jnp.int32)], axis=1)],
                        axis=1,
                    )
                key_rows = jnp.concatenate(
                    [addr_m, pp_m[:, None], pg_ins[:, None]], axis=1
                )

                # Conntrack commits BOTH directions (ref ConntrackCommit +
                # reply-direction ct state, docs/design/ovs-pipeline.md ct
                # sections): alongside every ALLOW, insert the
                # reverse-tuple entry keyed on the POST-DNAT tuple with
                # ports swapped (endpoint -> client), whose meta carries
                # the un-DNAT rewrite — the original frontend (pre-DNAT
                # dst ip/port) the reply's source must be restored to
                # (UnSNAT/EndpointDNAT reverse).  DSR connections commit
                # NO reply leg: the endpoint answers the client directly
                # and the reply never re-traverses this node (ref
                # pipeline.go:698-708 DSR flows bypass the reply path).
                rev_ins = ins & committed_m & (dsr_m == 0)
                if A == 2:
                    rev_h = hashing.flow_hash(
                        _raw_bits(dnat_ip), _raw_bits(s_f), p_m, dnat_port,
                        sp_m, xp=jnp,
                    )
                    rev_addr = jnp.stack([dnat_ip, s_f], axis=1)
                    rev_meta = jnp.stack(
                        [d_f, _pack_meta1(code, svc_idx, dp_m), rules_p,
                         pref_col], axis=1,
                    )
                else:
                    # Reverse tuple in wide form: src = the 4-word DNAT
                    # resolution (v6 endpoints included), dst = the
                    # client; the reverse meta carries the ORIGINAL
                    # frontend words (daddr) — the un-DNAT rewrite replies
                    # restore.
                    rev_addr = jnp.concatenate([dnat_w, saddr_m], axis=1)
                    rev_h = hashing.flow_hash_wide(
                        [rev_addr[:, i] for i in range(8)], p_m, dnat_port,
                        sp_m, xp=jnp,
                    )
                    rev_meta = jnp.concatenate(
                        [daddr_m,
                         jnp.stack([_pack_meta1(code, svc_idx, dp_m),
                                    rules_p, pref_col,
                                    jnp.zeros((W,), jnp.int32)],
                                   axis=1)],
                        axis=1,
                    )
                rev_slot = (rev_h & jnp.uint32(N - 1)).astype(jnp.int32)
                rev_pg = p_m | 0x100 | (GEN_ETERNAL << 9) | REPLY_BIT
                rev_keys = jnp.concatenate(
                    [rev_addr, ((dnat_port << 16) | sp_m)[:, None],
                     rev_pg[:, None]], axis=1
                )

                # Interleave per-packet [fwd_i, rev_i] so last-writer-wins
                # slot collisions resolve in the same order as the
                # oracle's per-packet insert sequence (parity on eviction
                # races).
                MC = 4 if A == 2 else 8
                slot2 = jnp.stack([slot_m, rev_slot], axis=1).reshape(2 * W)
                keys2 = jnp.stack([key_rows, rev_keys], axis=1).reshape(
                    2 * W, A + 2)
                meta2 = jnp.stack([meta_rows, rev_meta], axis=1).reshape(
                    2 * W, MC)
                ins2 = jnp.stack([ins, rev_ins], axis=1).reshape(2 * W)

                if meta.second_chance:
                    flow, ins2, sc_n = _second_chance_guard(
                        flow, slot2, keys2, ins2, now, meta, A, dump)
                    if tel_on:
                        tel_sc = tel_sc + sc_n

                with device_scope("eviction_scan"):
                    # Eviction accounting (round-2 verdict weak #5:
                    # quantify the direct-mapped collision cost): an
                    # insert over a live entry whose TUPLE differs (cols
                    # 0-2 + proto/direction bits of col 3 — a same-tuple
                    # rewrite is an update, not an eviction).
                    tgt2 = jnp.where(ins2, slot2, dump)
                    okr = flow.keys[tgt2]
                    id3 = 0xFF | REPLY_BIT
                    tuple_differs = (
                        (okr[:, : A + 1] != keys2[:, : A + 1]).any(axis=1)
                        | ((okr[:, A + 1] & id3) != (keys2[:, A + 1] & id3))
                    )
                    overwrote = ins2 & (okr[:, A + 1] != 0) & tuple_differs
                    if meta.drain_reclaim:
                        # Fused maintenance (overlapped drain): a target
                        # row that is DEAD to lookups — idle-expired per
                        # its per-state timeout, or a stale-generation
                        # denial — is reclaimed occupancy, not a live
                        # eviction; the drain round ages/revalidates the
                        # rows it touches in the pass that already
                        # gathered them (the ts/conf reads ride the same
                        # tgt2 the audit uses).
                        om3 = flow.meta[tgt2, ZC]
                        otmo = entry_timeout(
                            (om3 >> 29) & 1, okr[:, A + 1] & 0xFF,
                            meta.timeouts,
                        )
                        ogen = (okr[:, A + 1] >> 9) & GEN_ETERNAL
                        dead = ((now - flow.ts[tgt2]) > otmo) | (
                            (ogen != GEN_ETERNAL) & (ogen != gen_w)
                        )
                        n_reclaim = n_reclaim + (overwrote & dead).sum(
                            dtype=jnp.int32)
                        overwrote = overwrote & ~dead
                    n_evict = n_evict + overwrote.sum(dtype=jnp.int32)

                if meta.count_flow_stats:
                    # Fresh entries start at this packet's contribution on
                    # the forward leg; the reply leg starts empty (its own
                    # direction's traffic hasn't flowed yet).  High limbs
                    # reset to zero — a reused slot must not inherit the
                    # evicted entry's carry.
                    pk2 = jnp.stack(
                        [jnp.ones(W, jnp.int32), jnp.zeros(W, jnp.int32)],
                        axis=1).reshape(2 * W)
                    oc2 = jnp.stack(
                        [lv_m, jnp.zeros(W, jnp.int32)],
                        axis=1).reshape(2 * W)
                    z2 = jnp.zeros(2 * W, jnp.int32)
                    new_pkts = _scatter_last(flow.pkts, slot2, pk2, ins2,
                                             dump)
                    new_octets = _scatter_last(flow.octets, slot2, oc2,
                                               ins2, dump)
                    new_pkts_hi = _scatter_last(flow.pkts_hi, slot2, z2,
                                                ins2, dump)
                    new_octets_hi = _scatter_last(flow.octets_hi, slot2, z2,
                                                  ins2, dump)
                else:
                    new_pkts, new_octets = flow.pkts, flow.octets
                    new_pkts_hi, new_octets_hi = flow.pkts_hi, flow.octets_hi
                flow = FlowCache(
                    keys=_scatter_last_rows(flow.keys, slot2, keys2, ins2, dump),
                    meta=_scatter_last_rows(flow.meta, slot2, meta2, ins2, dump),
                    ts=_scatter_last(flow.ts, slot2, jnp.full((2 * W,), now, jnp.int32), ins2, dump),
                    pkts=new_pkts,
                    octets=new_octets,
                    pkts_hi=new_pkts_hi,
                    octets_hi=new_octets_hi,
                )
                lm = learn["mask"] & valid
                adump = meta.aff_slots
                if A == 2:
                    new_client = _scatter_last(
                        aff.key_client, learn["aslot"], learn["client"], lm,
                        adump)
                else:
                    new_client = _scatter_last_rows(
                        aff.key_client, learn["aslot"], learn["client"], lm,
                        adump)
                aff = AffinityTable(
                    key_client=new_client,
                    key_svc=_scatter_last(aff.key_svc, learn["aslot"], learn["svc"], lm, adump),
                    ep=_scatter_last(aff.ep, learn["aslot"], learn["ep"], lm, adump),
                    ts=_scatter_last(aff.ts, learn["aslot"], jnp.full((W,), now, jnp.int32), lm, adump),
                )
                return flow, aff, n_evict, n_reclaim, tel_sc

            flow, aff, n_evict, n_reclaim, tel_sc = do_commit(
                flow, aff, n_evict, n_reclaim, tel_sc if tel_on else None)
            return (r + 1, lanes + W, n_evict, n_reclaim, flow, aff,
                    out_code, out_svc, out_dnat_ip, out_dnat_port,
                    out_rule_in, out_rule_out, out_committed, out_snat,
                    out_dsr) + (
                    (out_dnat_w,) if A == 8 else ()) + (
                    (pr_sk, pr_fb, pr_hist) if prune_on else ()) + (
                    (tel_hb, tel_sc) if tel_on else ())

        carry = (jnp.int32(0), lanes0, n_evict0, n_reclaim0, flow, aff,
                 out_code, out_svc, out_dnat_ip, out_dnat_port, out_rule_in,
                 out_rule_out, out_committed, out_snat, out_dsr) + (
                 (out_dnat_w,) if A == 8 else ()) + (
                 (pr_sk0, pr_fb0, pr_hist0) if prune_on else ()) + (
                 (tel_hb0, tel_sc0) if tel_on else ())
        # Two loops, each a `while` of its own (a device trace tells slow
        # path from fast path by nesting under one).  The wide one runs
        # every round that has more lanes left than the narrow rung holds:
        # the full rounds, and a last round too wide for the narrow rung.
        # The narrow one runs once, where it holds what is left, else not
        # at all; n_miss <= M is one round, as with a single rung.  The
        # narrow loop sits inside a conditional of its own: two `while`s
        # side by side in one computation cost the wide one its
        # loop-invariant operands' place in fast memory on the v5e (the
        # lane columns: +0.3 ms a full round, PERF.md s6 PRs 31-32); the
        # conditional passes the tables through and copies none.
        def rounds(W, more_than):
            return lambda c: jax.lax.while_loop(
                lambda c: n_miss - c[0] * M > more_than,
                partial(round_body, W), c)

        narrow = ladder[1] if len(ladder) > 1 else 0
        carry = rounds(ladder[0], narrow)(carry)
        if narrow:
            carry = jax.lax.cond(n_miss - carry[0] * M > 0,
                                 rounds(narrow, 0), lambda c: c, carry)
        (_, lanes, n_evict, n_reclaim, flow, aff, out_code, out_svc,
         out_dnat_ip, out_dnat_port, out_rule_in, out_rule_out,
         out_committed, out_snat, out_dsr) = carry[:N_CARRY]
        return flow, aff, (out_code, out_svc, out_dnat_ip, out_dnat_port,
                           out_rule_in, out_rule_out, out_committed,
                           out_snat, out_dsr, n_evict, n_reclaim,
                           lanes) + tuple(
                           carry[N_CARRY:N_CARRY + n_extra])

    def noop(args):
        return args

    with device_scope("miss_detect"):
        slow_init = (flow, aff, (out_code, out_svc, out_dnat_ip, out_dnat_port,
                                 out_rule_in, out_rule_out, out_committed,
                                 out_snat, out_dsr, jnp.int32(0),
                                 jnp.int32(0), jnp.int32(0)) + (
                                 (out_dnat_w,) if A == 8 else ()) + ((
                                 jnp.int32(0), jnp.int32(0),
                                 jnp.zeros(len(PRUNE_HIST_BOUNDS) + 2,
                                           jnp.int32)) if prune_on else ()) + (
                                 (jnp.int32(0), jnp.int32(0))
                                 if tel_on else ()))
        if meta.defer_misses:
            # The async fast step: misses keep the fast-path default image
            # and commit nothing; the engine queues them for a drain step.
            flow, aff, outs = slow_init
        else:
            flow, aff, outs = jax.lax.cond(n_miss > 0, slow, noop, slow_init)
    (out_code, out_svc, out_dnat_ip, out_dnat_port,
     out_rule_in, out_rule_out, out_committed, out_snat, out_dsr,
     n_evict, n_reclaim, round_lanes) = outs[:N_OUT]
    if A == 8:
        out_dnat_w = outs[N_OUT]

    with device_scope("fast_path"), device_scope("assemble"):
        final_code = out_code[:B]
        out = {
            "code": final_code,
            "est": est.astype(jnp.int32),
            # Reply-direction hit: dnat_ip_f/dnat_port carry the UN-DNAT rewrite
            # (the frontend tuple the reply's SOURCE is restored to), not a
            # destination rewrite.
            "reply": rpl.astype(jnp.int32),
            # REJECT synthesis kind (reject.go analog), derived from the
            # packet's own proto so cached REJECT hits get the right kind too.
            "reject_kind": reject_kind_of(final_code, proto),
            "svc_idx": out_svc[:B],
            "dnat_ip_f": out_dnat_ip[:B],
            "dnat_port": out_dnat_port[:B],
            "ingress_rule": out_rule_in[:B],
            "egress_rule": out_rule_out[:B],
            "committed": out_committed[:B],
            # Per-lane cache-miss mask (1 = this lane took / would take the
            # slow path).  In synchronous mode an informational overlay; in
            # the async fast step (defer_misses) it is the miss-queue
            # ADMISSION mask the engine consumes (datapath/slowpath).
            "miss": miss.astype(jnp.int32),
            # SNAT-mark classification (pipeline.go SNATMark analog): external
            # frontend traffic under ETP=Cluster needs masquerade on egress.
            "snat": out_snat[:B],
            # DSR delivery mark (pipeline.go:145 DSRServiceMarkTable): forward
            # toward dnat_ip_f (the selected endpoint) but do NOT rewrite the
            # L3 destination and do NOT SNAT; the endpoint owns the VIP and
            # replies straight to the client (pipeline.go:698-708).
            "dsr": out_dsr[:B],
            "n_miss": n_miss,
            # Live entries overwritten by a different tuple this step (the
            # direct-mapped collision cost; weak-#5 measurement surface).
            "n_evict": n_evict,
            # Dead rows (idle-expired / stale-gen) reclaimed by inserts —
            # split out of n_evict only under meta.drain_reclaim (the
            # overlapped drain's fused maintenance); always 0 otherwise.
            "n_reclaim": n_reclaim,
            # Lanes the slow-path rounds were run at, padding included: the
            # sum of their widths (round_ladder).  n_miss over it is how
            # full the rounds were.
            "round_lanes": round_lanes,
        }
        if prune_on:
            pos = N_OUT + (1 if A == 8 else 0)
            # Round-7 prune observability, aggregated over the slow-path
            # rounds (valid lanes only): aggregate-AND-zero short circuits,
            # full-width fallback redispatches, and the candidate-superblock
            # bucket counts + value sum (_prune_bucket_counts layout).  Keys
            # exist iff prune_budget > 0, so the unpruned step's output
            # pytree — and its compiled HLO — is unchanged.
            out["n_prune_skips"] = outs[pos]
            out["n_prune_fb"] = outs[pos + 1]
            out["prune_cand_hist"] = outs[pos + 2]
        if A == 8:
            # Wide (4-word) DNAT resolution — the full-address view v6
            # consumers (forwarding, StepResult) read; v4 lanes' word 3 equals
            # dnat_ip_f.  Reply hits carry the un-DNAT frontend words.
            out["dnat_w_f"] = out_dnat_w[:B]
        if tel_on:
            # Hot-path telemetry counters (observability/telemetry.py
            # TELEMETRY_COUNTERS): keys exist iff meta.telemetry, so the off
            # path's output pytree — and its compiled HLO — is unchanged.
            # The prune trio above doubles as the telemetry candidate-hist /
            # skip / fallback source when prune_budget > 0.
            pos_t = N_OUT + (1 if A == 8 else 0) + (3 if prune_on else 0)
            out["tel_probe_hit"] = tel_probe_hit
            out["tel_probe_stale"] = tel_probe_stale
            out["tel_probe_miss"] = tel_probe_miss
            out["tel_dma_hb"] = outs[pos_t]
            out["tel_chance_bumps"] = outs[pos_t + 1]
    return PipelineState(flow=flow, aff=aff), out


# jit wrapper: meta is static.
pipeline_step = jax.jit(_pipeline_step, static_argnames=("meta", "hit_combine"))

# Overlapped-drain variant with the STATE argument DONATED (the donated
# carries of SNIPPETS [3]'s pjit shape): the drain rewrites keys/meta/ts
# wholesale, so without donation XLA must allocate fresh output buffers
# for ~150MB of cache columns per drain and the dispatch pipeline stalls
# on the copies.  Donation lets XLA alias the scatters in place and
# pipeline drain N's commit under batch N+1's dispatch.  Callers MUST
# drop every reference to the passed state (the datapath's single-owner
# `self._state` discipline guarantees this between host calls; the
# commit plane's snapshots live only inside an install transaction,
# during which no drain runs).
pipeline_step_donated = jax.jit(
    _pipeline_step, static_argnames=("meta", "hit_combine"),
    donate_argnums=(0,),
)


def _cache_stats(state: PipelineState):
    """On-demand flow-cache census (full scan — not for the per-step path):
    occupancy, committed (eternal-gen, incl. reply) and denial entries."""
    kpg = state.flow.keys[:-1, -1]  # pg is the LAST key column (any width)
    valid = kpg != 0
    gen = (kpg >> 9) & GEN_ETERNAL
    est = valid & (gen == GEN_ETERNAL)
    return {
        "occupied": valid.sum(dtype=jnp.int32),
        "committed": est.sum(dtype=jnp.int32),
        "denials": (valid & ~est).sum(dtype=jnp.int32),
        "slots": jnp.int32(kpg.shape[0]),
    }


cache_stats = jax.jit(_cache_stats)


def _live_rows(keys: jax.Array) -> jax.Array:
    """Occupied-entry mask over the full (N+1,) row space with the dump
    row (index N, the masked-scatter junk target) excluded."""
    kpg = keys[:, -1]
    n = kpg.shape[0]
    return (kpg != 0) & (jnp.arange(n, dtype=jnp.int32) < n - 1)


def _age_scan(state: PipelineState, now: jax.Array, *, timeouts):
    """Off-hot-step aging scan (datapath/slowpath epoch plane): physically
    clear entries idle past their per-state conntrack lifetime.

    Semantics-neutral by construction: an expired entry is already dead to
    lookups (_cache_lookup freshness check), so clearing it changes no
    verdict — it reclaims the slot, turning a later insert over it from an
    "eviction" into plain occupancy.  The synchronous datapath never runs
    this (expiry-by-lookup suffices); the async engine runs it between
    drains and publishes the result via epoch swap.

    -> (state', n_reclaimed).
    """
    flow = state.flow
    kpg = flow.keys[:, -1]
    conf = (flow.meta[:, _meta_cols(flow.keys.shape[1] - 2)[3]] >> 29) & 1
    tmo = entry_timeout(conf, kpg & 0xFF, timeouts)
    expired = _live_rows(flow.keys) & ((now - flow.ts) > tmo)
    keys = jnp.where(expired[:, None], 0, flow.keys)
    return (
        state._replace(flow=flow._replace(keys=keys)),
        expired.sum(dtype=jnp.int32),
    )


age_scan = jax.jit(_age_scan, static_argnames=("timeouts",))


def _revalidate_scan(state: PipelineState, gen: jax.Array):
    """Off-hot-step revalidation (datapath/slowpath epoch plane): clear
    DENIAL entries whose generation predates the current bundle.

    Stale-gen denials are already dead to lookups (the megaflow
    revalidation analog — _cache_lookup's gen compare), so this is the
    lazy slot-reclaim a bundle swap schedules instead of flushing the
    cache; established (eternal-gen) entries, reply legs included, are
    untouched — the flows-survive-churn invariant.  -> (state', n_cleared).
    """
    flow = state.flow
    kpg = flow.keys[:, -1]
    egen = (kpg >> 9) & GEN_ETERNAL
    gen_w = jnp.asarray(gen, jnp.int32) % GEN_ETERNAL
    stale = (
        _live_rows(flow.keys) & (egen != GEN_ETERNAL) & (egen != gen_w)
    )
    keys = jnp.where(stale[:, None], 0, flow.keys)
    return (
        state._replace(flow=flow._replace(keys=keys)),
        stale.sum(dtype=jnp.int32),
    )


revalidate_scan = jax.jit(_revalidate_scan)


def _maintain_scan(state: PipelineState, now: jax.Array, gen: jax.Array,
                   *, timeouts):
    """FUSED off-hot-step maintenance (ROADMAP item 2 / round 6): one pass
    over the flow cache performing both the aging scan and the
    stale-generation revalidation that previously ran as two separate
    full-table transforms — keys/meta/ts are each read ONCE and the keys
    written once, halving the HBM traffic of an epoch-stale heal.

    Semantics-neutral exactly like its two parents: both row classes are
    already dead to lookups (freshness / gen compare), so clearing them
    changes no verdict.  A row that is both expired AND stale counts as
    aged (the partition the oracle twin applies in the same order).

    -> (state', n_aged, n_revalidated).
    """
    flow = state.flow
    kpg = flow.keys[:, -1]
    live = _live_rows(flow.keys)
    conf = (flow.meta[:, _meta_cols(flow.keys.shape[1] - 2)[3]] >> 29) & 1
    tmo = entry_timeout(conf, kpg & 0xFF, timeouts)
    expired = live & ((now - flow.ts) > tmo)
    egen = (kpg >> 9) & GEN_ETERNAL
    gen_w = jnp.asarray(gen, jnp.int32) % GEN_ETERNAL
    stale = (
        live & (egen != GEN_ETERNAL) & (egen != gen_w) & ~expired
    )
    keys = jnp.where((expired | stale)[:, None], 0, flow.keys)
    return (
        state._replace(flow=flow._replace(keys=keys)),
        expired.sum(dtype=jnp.int32),
        stale.sum(dtype=jnp.int32),
    )


maintain_scan = jax.jit(_maintain_scan, static_argnames=("timeouts",))


# ---- audit plane transforms (datapath/audit.py) ---------------------------
# The continuous revalidator runs OFF the hot step, like age_scan and
# canary_scan: nothing here is reachable from pipeline_step, so with the
# audit plane idle the compiled step is bit-identical to a plane-less
# build (tests/test_cache_audit.py verifies the lowered HLO).


def _audit_gather(state: PipelineState, cursor: jax.Array, *, window: int):
    """Rotating-cursor window gather for the cache revalidation scan: rows
    [cursor, cursor+window) of the flow cache (mod slot count, dump row
    excluded) -> (keys, meta, ts) — the device side of one audit step; the
    host decodes and re-proves the sampled entries."""
    N = state.flow.keys.shape[0] - 1
    idx = (jnp.arange(window, dtype=jnp.int32) + cursor) % N
    return state.flow.keys[idx], state.flow.meta[idx], state.flow.ts[idx]


audit_gather = jax.jit(_audit_gather, static_argnames=("window",))


def _audit_evict(state: PipelineState, slots: jax.Array):
    """Repair-by-eviction for divergent audited entries: clear the key rows
    of `slots` ((K,) i32, -1 padding ignored) so the flows reclassify
    lazily on their next packet — the mark_stale discipline; the cached
    value is never trusted, never patched in place.  -> (state', n)."""
    N = state.flow.keys.shape[0] - 1
    live = (slots >= 0) & (slots < N)
    tgt = jnp.where(live, slots, N)
    keys = state.flow.keys.at[tgt].set(0)
    return (
        state._replace(flow=state.flow._replace(keys=keys)),
        live.sum(dtype=jnp.int32),
    )


audit_evict = jax.jit(_audit_evict)


def _digest_pair(arr: jax.Array) -> jax.Array:
    """Any array -> (2,) i32 [xor-fold, wrapping sum] over its elements as
    i32 words (32-bit dtypes bitcast, others value-cast — determinism is
    what the digest needs, not bit fidelity): the Fletcher-style pair the
    tensor scrub compares — XOR catches any single bit flip, the
    order-weighted-by-nothing sum catches the paired flips XOR folds out.
    Both folds run over every axis of the array where it lies: flattening
    it first would materialise a copy of it (two, with the bitcast: 2.4 GB
    beside a 1.2 GB rule table, the peak of the device's memory)."""
    if arr.dtype != jnp.int32:
        arr = (jax.lax.bitcast_convert_type(arr, jnp.int32)
               if arr.dtype.itemsize == 4 else arr.astype(jnp.int32))
    return jnp.stack([
        jax.lax.reduce(arr, jnp.int32(0), jax.lax.bitwise_xor,
                       tuple(range(arr.ndim))),
        jnp.sum(arr, dtype=jnp.int32),
    ])


_digest_fold = jax.jit(_digest_pair)


def tensor_digest(leaves) -> int:
    """Checksum-scrub digest of a pytree-leaf iterable: per-leaf jitted
    XOR/sum folds (device-side; only two scalars transfer back per leaf)
    combined into one host int.  Shape-stable per bundle, so the folds hit
    the jit cache on every scan after the first."""
    h = 0
    for leaf in leaves:
        arr = jnp.asarray(leaf)
        if arr.size == 0:
            xor, s = 0, 0
        else:
            pair = np.asarray(_digest_fold(arr))
            xor, s = int(pair[0]) & 0xFFFFFFFF, int(pair[1]) & 0xFFFFFFFF
        h = (h * 1000003 + xor) & 0xFFFFFFFFFFFFFFFF
        h = (h * 1000003 + s) & 0xFFFFFFFFFFFFFFFF
    return h


def _pipeline_trace(
    state: PipelineState,
    drs: DeviceRuleSet,
    dsvc: DeviceServiceTables,
    src_f: jax.Array,
    dst_f: jax.Array,
    proto: jax.Array,
    sport: jax.Array,
    dport: jax.Array,
    now: jax.Array,
    gen: jax.Array,
    *,
    meta: PipelineMeta,
    hit_combine=None,
    v6=None,
):
    """Read-only per-packet stage trace (the Traceflow analog,
    ref framework.go:328-338): every packet is walked through ServiceLB and
    the full classifier regardless of cache state, and the cache lookup is
    reported alongside — no state is mutated, like a Traceflow probe marked
    to bypass conntrack commit.
    """
    flow, aff = state.flow, state.aff
    N = meta.flow_slots
    A = meta.key_words - 2
    src_raw = _raw_bits(src_f)
    dst_raw = _raw_bits(dst_f)
    pp = (sport << 16) | dport
    gen_w = jnp.asarray(gen, jnp.int32) % GEN_ETERNAL

    if A == 2:
        if v6 is not None:
            raise ValueError(
                "v6 lanes require a dual_stack pipeline "
                "(make_pipeline(dual_stack=True))"
            )
        is6 = None
        saddr = daddr = None
        addr = jnp.stack([src_f, dst_f], axis=1)
        h = hashing.flow_hash(src_raw, dst_raw, proto, sport, dport, xp=jnp)
    else:
        if v6 is not None:
            src6w, dst6w, is6 = v6
        else:
            is6 = jnp.zeros_like(src_f)
            src6w = dst6w = None
        saddr = _wide_words(src_f, src6w, is6)
        daddr = _wide_words(dst_f, dst6w, is6)
        addr = jnp.concatenate([saddr, daddr], axis=1)
        h = hashing.flow_hash_wide(
            [addr[:, i] for i in range(8)], proto, sport, dport, xp=jnp
        )
    slot = (h & jnp.uint32(N - 1)).astype(jnp.int32)
    pg_cur = proto | 0x100 | (gen_w << 9)
    pg_est = proto | 0x100 | (GEN_ETERNAL << 9)
    hit, est, rpl, mr, _kr, _ts = _cache_lookup(
        flow, slot, addr, pp, pg_cur, pg_est, now, proto, meta
    )
    DC, M1C, _RC, _ZC = _meta_cols(A)
    c_code, c_svc, c_dport = _unpack_meta1(mr[:, M1C])

    svc_idx, no_ep, dnat_ip, dnat_port, snat, dsr, dnat_w, _learn = _service_lb(
        aff, dsvc, h, src_f, dst_f, proto, dport, now, meta.aff_slots,
        wide=None if A == 2 else (saddr, daddr, is6),
    )
    cls = classify_batch(
        drs, src_f, dnat_ip, proto, dnat_port,
        meta=meta.match, hit_combine=hit_combine,
        # The twin walk carries the instance's fused meta (round 8): a
        # fused datapath's canary/audit probes then exercise the SAME
        # pallas consumers the serving kernel uses, so the PR 4/5 planes
        # certify the serving configuration, not a shadow XLA path.
        fused=meta.fused,
        v6=None if A == 2 else (saddr, dnat_w, is6),
        svc_ref=_svc_ref_of(svc_idx, dsvc),
    )
    fresh_code = jnp.where(no_ep, ACT_REJECT, cls["code"]).astype(jnp.int32)
    code = jnp.where(hit, c_code, fresh_code)
    out = {
        "cache_hit": hit.astype(jnp.int32),
        "est": est.astype(jnp.int32),
        "reply": rpl.astype(jnp.int32),
        "cached_code": jnp.where(hit, c_code, -1),
        # Cached DNAT resolution (meta row), so trace consumers can derive
        # forwarding for hit lanes from the entry the STEP path would use
        # (service updates after commit may make the fresh walk differ).
        "cached_dnat_ip_f": mr[:, DC],
        "cached_dnat_port": c_dport,
        "svc_idx": svc_idx,
        "no_ep": no_ep.astype(jnp.int32),
        "dnat_ip_f": dnat_ip,
        "dnat_port": dnat_port,
        "snat": snat,
        "dsr": dsr,
        "egress_code": cls["egress_code"],
        "egress_rule": cls["egress_rule"],
        "ingress_code": cls["ingress_code"],
        "ingress_rule": cls["ingress_rule"],
        "fresh_code": fresh_code,
        "code": code,
        "reject_kind": reject_kind_of(code, proto),
    }
    if A == 8:
        out["dnat_w_f"] = dnat_w
        out["cached_dnat_w_f"] = mr[:, 0:4]
    return out


pipeline_trace = jax.jit(_pipeline_trace, static_argnames=("meta", "hit_combine"))

"""Batched conjunctive-match classification kernel (the tpuflow hot path).

This is the TPU execution of what OVS does per-packet in C: walk the policy
tables and produce a verdict.  The kernel is gather-structured (round-3
redesign; the round-2 kernel was a lax.scan over rule chunks testing per-rule
group bits plus a (B, C, K) inline-range broadcast, and topped out at 176k
pps @ 100k rules — 0.018x the 10M target):

  1. per-dimension interval lookup: searchsorted over the dimension's OWN
     elementary-interval boundaries (appliedTo / peer over the u32 IP space,
     service over the (proto << 16 | dst_port) key space);
  2. one row gather per dimension from that dimension's bit-packed
     RULE-INCIDENCE table: inc[iv] is a bitmap over rules — bit r set iff
     rule r's interned group for this dimension contains interval iv.  This
     is the factored address-set sharing of the reference's conjunction
     engine (/root/reference/pkg/agent/openflow/network_policy.go:325,:442),
     transposed from (interval -> groups) to (interval -> rule bits) at
     compile time so the kernel never walks groups at all;
  3. AND the three rows -> per-packet rule-match bitmap (B, ceil(R/32));
  4. per-evaluation-phase first-set-bit (isolate-lowest-bit + popcount +
     min-reduce) replicating the OVS table order:
     AntreaPolicy{In,E}gressRule -> K8s {In,E}gressRule + isolation
     default-deny -> Baseline -> default allow
     (ref: /root/reference/pkg/agent/openflow/pipeline.go:114-195).

Per packet the work is three ~R/32-word row gathers per direction plus a
handful of vector word ops — HBM-streaming-bound with no per-rule scan, no
data-dependent control flow, and no gather along the lane axis (row gathers
along the major axis are the fast pattern on TPU; see the FlowCache layout
rationale in models/pipeline.py).  Inline peer CIDR blocks are folded into
interned groups by the compiler, so they are ordinary incidence bits here.

All arrays are i32/u32 lanes; IPs are sign-flipped so signed compares give
unsigned order (see compiler/compile.py).  Everything is static-shaped and
jit-compatible; batch size is the only trace-time variable.
"""

from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.compile import (
    ACT_ALLOW,
    ACT_DROP,
    ACT_PASS,
    CompiledPolicySet,
    DirectionTensors,
)
from ..utils import ip as iputil
from .scopes import device_scope

# "No match" sentinel for first-match indices.  Deliberately a PYTHON int,
# not an eager jnp scalar: a concrete device array captured by a jitted
# function becomes a buffer-backed executable constant instead of an HLO
# literal; Python scalars trace to literals.
BIG = 1 << 30

_ALL1 = 0xFFFFFFFF

# Aggregated-bitmap pruning (round 7; ABV-style two-level incidence).
# One aggregate BIT summarizes one incidence WORD (32 rules); one
# aggregate WORD therefore summarizes a 32-word SUPERBLOCK (1024 rules),
# which is the granularity the candidate gather fetches at.  Aggregate
# bits are conservative: never a false negative (a zero aggregate AND
# proves no-match), possibly a false positive (the candidate gather then
# finds an all-zero AND and the lane takes the default verdict).
AGG_BLOCK = 32

# The minor size of the TPU's (8, 128) tile of 32-bit words, and the unit of
# every rule-word axis (`_width`).  With default layouts the TPU places a
# 2-D array in the dimension order that pads least under that tile: a
# (rows, W) table whose W is no multiple of 128 (1,866 words pad by 2.9 %,
# 33,433 rows by 0.3 %) is laid out COLUMN-major, and a step that gathers
# whole rows (`classify.candidate`) transposes all of it first, every
# step.  A tiled W pads nothing as the minor dimension, so the table is
# placed row-major and the gather reads it where it lies.  Any table
# gathered by row is born with such a width.
TILE_WORDS = 128
assert TILE_WORDS % AGG_BLOCK == 0

# The K-budget autotuner's closed rung ladder (one jit-cached classify
# variant per rung, like the drain CHUNK_LADDER) and its hysteresis.
PRUNE_LADDER = (1, 2, 4, 8, 16)
PRUNE_STICKY = 2
# Fallback-rate pressure band: above the high-water mark the budget
# presses UP (too many full-width redispatches), below the low-water
# mark it presses DOWN (budget head-room wasted on candidate volume).
PRUNE_FB_HIGH = 0.05
PRUNE_FB_LOW = 0.005

# Candidate-superblock histogram bucket bounds (per-lane max over the
# two directions) — shared by the device-side bucket counts
# (models/pipeline._prune_bucket_counts) and the host Histogram they
# merge into, so the exposition buckets can never drift.
PRUNE_HIST_BOUNDS = (0, 1, 2, 4, 8, 16, 32)

# Smallest in-kernel fallback rung (pow2 ladder, x4 steps up to the
# batch size): unresolved lanes are compacted and redispatched at full
# incidence width inside ONE lax.switch branch — the in-jit analog of
# the PR 9 _spill_retry pow2-rung host dispatch.
_FB_MIN = 64


class DimTable(NamedTuple):
    """One match dimension: interval bounds + rule-incidence rows.

    Dual-stack (ref pipeline.go IPv6 table, fields.go:184-185 xxreg3): the
    incidence rows concatenate the v4 interval space (rows 0..NB4) and the
    v6 interval space (rows NB4+1..NB4+1+NB6) — v6 boundaries live in a
    separate 4-word lexicographic table, and once a packet resolves to an
    interval INDEX everything downstream is family-blind.  bounds6 always
    exists (possibly 0 rows; the v6 space then has the single whole-space
    interval, still painted by family-spanning groups like any-peer)."""

    bounds: jax.Array  # (NB4,) i32 ascending (sign-flipped for IP dims)
    # (NB6, 4) i32 — v6 boundaries as per-word sign-flipped u32 quadruples,
    # ascending lexicographically.  Empty (0, 4) for the svc dimension.
    bounds6: jax.Array
    inc: jax.Array  # (NB4+1+NB6+1, W) u32 — rule bitmap per interval
    # Aggregate level (round 7, built only under prune_budget > 0 so the
    # unpruned pytree — and every jit signature over it — is unchanged):
    # (rows, W/AGG_BLOCK) u32, bit j of word s set iff inc word
    # s*AGG_BLOCK+j is nonzero (build_agg is the ONE builder, shared with
    # the consistency property tests).  W is a TILE_WORDS multiple (of
    # which AGG_BLOCK is a divisor), so superblocks never straddle the row
    # end (or a rule-axis shard boundary — see _width).
    agg: Optional[jax.Array] = None


class DeviceDirection(NamedTuple):
    at: DimTable  # appliedTo, probed with the pod-side IP
    peer: DimTable  # peer, probed with the other side's IP
    svc: DimTable  # service, probed with (proto << 16 | dst_port)
    action: jax.Array  # (W*32,) i32 flat, for post-resolve gather
    # (W*32,) i32 0/1 L7-redirect mark per rule, replicated like `action`
    # (indexed post-pmin by the deciding rule).
    l7: jax.Array
    # (W,) global word index — carried as data (not an arange built in the
    # kernel) so a rule-axis shard_map slice still knows its global rule
    # offsets and cross-shard first-match combines stay a plain lax.pmin.
    word_idx: jax.Array


class IsoTable(NamedTuple):
    """K8s default-deny isolation membership (one bit per packet);
    dual-stack like DimTable (val rows = K4+1+K6+1)."""

    bounds: jax.Array  # (K4,) i32 sign-flipped
    bounds6: jax.Array  # (K6, 4) i32 per-word sign-flipped
    val: jax.Array  # (K4+1+K6+1,) i32 0/1


class DeltaTable(NamedTuple):
    """Fixed-capacity incremental membership-delta table (device-resident).

    The TPU answer to the reference's incremental address-group watch deltas
    (docs/design/architecture.md:61-62): a pod joining/leaving a group does
    NOT recompile any interval table — the host appends one slot carrying
    the affected ip range plus PRE-RESOLVED per-dimension rule masks (the
    bitmaps of rules whose at/peer gid is the patched group), and the kernel
    patches the gathered incidence rows before the AND, so every consumer
    sees the updated membership.  A full recompile (bundle commit) folds the
    deltas back into the tables and clears this — the megaflow-revalidation
    analog, triggered on capacity overflow.

    Slots apply in append order inside a dynamic-trip-count loop (`n`), so
    zero pending deltas cost zero iterations and a later delta for the same
    rule bit wins.  Empty slots: sign == 0.

    Dual-stack: a slot is single-family (`fam`) — v4 slots compare the
    narrow range, v6 slots the 4-word lexicographic one (same pre-resolved
    masks either way), so v6 pod churn stays O(1) instead of forcing a
    recompile.
    """

    lo_f: jax.Array  # (D,) sign-flipped i32, inclusive (v4 slots)
    hi_f: jax.Array  # (D,) sign-flipped i32, inclusive
    sign: jax.Array  # (D,) i32 — +1 set, -1 clear, 0 empty
    iso: jax.Array  # (D,) i32 — bit0: patches iso_in, bit1: patches iso_out
    at_in: jax.Array  # (D, W_in) u32 rule mask for the ingress appliedTo dim
    peer_in: jax.Array  # (D, W_in)
    at_out: jax.Array  # (D, W_out)
    peer_out: jax.Array  # (D, W_out)
    n: jax.Array  # () i32 — active slots
    fam: jax.Array  # (D,) i32 — 0: v4 slot, 1: v6 slot
    lo6_w: jax.Array  # (D, 4) per-word flipped, inclusive (v6 slots)
    hi6_w: jax.Array  # (D, 4)


class DeviceRuleSet(NamedTuple):
    """Device-resident compiled rule tensors (the double-buffered side of a
    bundle commit; ref bundle semantics: pkg/ovs/openflow/ofctrl_bridge.go:468)."""

    ingress: DeviceDirection
    egress: DeviceDirection
    iso_in: IsoTable
    iso_out: IsoTable
    ip_delta: DeltaTable


class StaticMeta(NamedTuple):
    """Trace-time constants (not pytree leaves)."""

    in_phases: tuple[int, int, int]  # (n_phase0, n_k8s, n_baseline)
    out_phases: tuple[int, int, int]
    w_in: int  # ingress rule words (incl. shard padding)
    w_out: int
    delta_slots: int = 0
    # Platform the rule tensors were PLACED on (placed_meta; the sharded
    # builders record their mesh's).  None = not placed yet — jit then
    # puts the leaves on the default backend.  pallas_interpret reads it.
    platform: "str | None" = None
    # Egress rules include toServices lowerings (compiler SVCREF_BASE
    # sub-space): classify_batch probes the egress svc dimension with a
    # SECOND key derived from the lane's ServiceLB resolution.  Static so
    # svcref-free rule sets compile the extra gather out entirely.
    svcref: bool = False
    # Two-level aggregate pruning (round 7): K = max candidate
    # superblocks gathered per lane and direction; 0 compiles the whole
    # aggregate layer out (the tables are then not even built — agg is
    # None and the classify HLO is bit-identical to the pre-aggregate
    # kernel).  Runtime-retunable on PRUNE_LADDER by swapping the meta
    # (one jit-cached variant per rung; the tables are K-independent).
    prune_budget: int = 0


def empty_delta(slots: int, w_in: int, w_out: int, xp=jnp) -> DeltaTable:
    return DeltaTable(
        lo_f=xp.full((slots,), 2**31 - 1, dtype=xp.int32),
        hi_f=xp.full((slots,), -(2**31), dtype=xp.int32),
        sign=xp.zeros((slots,), dtype=xp.int32),
        iso=xp.zeros((slots,), dtype=xp.int32),
        at_in=xp.zeros((slots, w_in), dtype=xp.uint32),
        peer_in=xp.zeros((slots, w_in), dtype=xp.uint32),
        at_out=xp.zeros((slots, w_out), dtype=xp.uint32),
        peer_out=xp.zeros((slots, w_out), dtype=xp.uint32),
        n=xp.zeros((), dtype=xp.int32),
        fam=xp.zeros((slots,), dtype=xp.int32),
        lo6_w=xp.full((slots, 4), 2**31 - 1, dtype=xp.int32),
        hi6_w=xp.full((slots, 4), -(2**31), dtype=xp.int32),
    )


def placed_meta(meta: StaticMeta, drs) -> StaticMeta:
    """`meta` stamped with the platform `drs` actually lives on."""
    leaf = jax.tree_util.tree_leaves(drs)[0]
    return meta._replace(platform=next(iter(leaf.devices())).platform)


def pallas_interpret(meta: StaticMeta) -> bool:
    """The ONE interpret-mode decision for every pallas_call in the repo:
    kernels compile through Mosaic where the operands live on a TPU and
    run under the Pallas interpreter everywhere else (CPU tests, the
    virtual-CPU mesh dryrun on a TPU host).  Never true on a TPU — a
    kernel the compiler refuses raises, it does not fall back."""
    return (meta.platform or jax.default_backend()) != "tpu"


# ---------------------------------------------------------------------------
# Host-side table construction
# ---------------------------------------------------------------------------


def _rules_by_gid(gids: np.ndarray) -> dict[int, np.ndarray]:
    order = np.argsort(gids, kind="stable").astype(np.int64)
    sg = gids[order]
    uniq, starts = np.unique(sg, return_index=True)
    out: dict[int, np.ndarray] = {}
    for i, g in enumerate(uniq):
        end = starts[i + 1] if i + 1 < len(uniq) else len(sg)
        out[int(g)] = order[starts[i] : end]
    return out


def _inc_mask(rule_idx: np.ndarray, w: int) -> np.ndarray:
    """Rule indices -> (w,) u32 bitmap."""
    inc = np.zeros(w, dtype=np.uint32)
    np.bitwise_or.at(inc, rule_idx >> 5, (1 << (rule_idx & 31)).astype(np.uint32))
    return inc


def build_agg(inc: np.ndarray) -> np.ndarray:
    """(rows, W) u32 incidence -> (rows, ceil(W/AGG_BLOCK)) u32 aggregate:
    bit j of aggregate word s == (inc word s*AGG_BLOCK+j) != 0.  The ONE
    aggregate builder — to_host, the delta kernel's on-the-fly mask
    aggregation (_agg_mask) and the consistency property tests all follow
    this definition, so table/aggregate divergence is a scrub finding,
    never a construction ambiguity."""
    inc = np.asarray(inc)
    rows, w = inc.shape
    s = -(-w // AGG_BLOCK)
    pad = s * AGG_BLOCK - w
    if pad:
        inc = np.pad(inc, ((0, 0), (0, pad)))
    nz = (inc.reshape(rows, s, AGG_BLOCK) != 0).astype(np.uint32)
    return (nz << np.arange(AGG_BLOCK, dtype=np.uint32)[None, None, :]).sum(
        axis=2, dtype=np.uint32)  # disjoint bits: sum == OR


_V6_OFF = iputil.V6_OFF
_V6_END = 1 << 128  # exclusive end of the v6-relative space


def _span_list(bounds: list, lo: int, hi: int) -> tuple[int, int]:
    """[lo, hi) range -> inclusive interval-row span [a, b] over a SORTED
    python-int bounds list (bisect 'right' index space, row i covering
    (bounds[i-1], bounds[i]])."""
    import bisect

    a = bisect.bisect_right(bounds, lo)
    b = bisect.bisect_right(bounds, hi - 1)
    return a, b


def _family_split(lo: int, hi: int):
    """Combined-keyspace [lo, hi) -> (v4 part or None, v6-relative part or
    None); family-spanning ranges (any-peer) contribute to both."""
    v4 = v6 = None
    if lo < (1 << 32):
        v4 = (lo, min(hi, 1 << 32))
    if hi > _V6_OFF:
        v6 = (max(lo, _V6_OFF) - _V6_OFF, hi - _V6_OFF)
    return v4, v6


def _dual_bounds(range_lists) -> tuple[list, list]:
    """Boundary points of both families from combined ranges."""
    p4: set[int] = set()
    p6: set[int] = set()
    for ranges in range_lists:
        for lo, hi in ranges:
            r4, r6 = _family_split(int(lo), int(hi))
            if r4 is not None:
                p4.add(r4[0])
                if r4[1] < (1 << 32):
                    p4.add(r4[1])
            if r6 is not None:
                p6.add(r6[0])
                if r6[1] < _V6_END:
                    p6.add(r6[1])
    return sorted(p4), sorted(p6)


def _v6_words(vals: list) -> np.ndarray:
    """Sorted v6-relative ints -> (N, 4) sign-flipped i32 word quadruples
    (lexicographic order preserved word-wise)."""
    out = np.zeros((len(vals), 4), dtype=np.uint32)
    for i, v in enumerate(vals):
        out[i] = [(v >> 96) & 0xFFFFFFFF, (v >> 64) & 0xFFFFFFFF,
                  (v >> 32) & 0xFFFFFFFF, v & 0xFFFFFFFF]
    return iputil.flip_u32(out)


def _paint(b4: list, b6: list, lo: int, hi: int, write) -> None:
    """Paint combined range [lo, hi) into the dual interval row space via
    the write(row_a, row_b) callback: v4 rows [0..len(b4)], v6 rows
    [len(b4)+1 ..]."""
    r4, r6 = _family_split(int(lo), int(hi))
    if r4 is not None and r4[0] < r4[1]:
        a, b = _span_list(b4, *r4)
        write(a, b)
    if r6 is not None and r6[0] < r6[1]:
        a, b = _span_list(b6, *r6)
        off = len(b4) + 1
        write(off + a, off + b)


def _dim_table_host(gids: np.ndarray, groups: list, w: int, ip_dim: bool,
                    agg: bool = False) -> DimTable:
    """Build one dimension's (bounds, bounds6, incidence) triple.

    Only the groups this dimension actually uses contribute boundary points,
    so each dimension's interval table stays as small as its own address
    structure (the appliedTo dimension is typically far coarser than peer).
    """
    by = _rules_by_gid(gids)
    b4, b6 = _dual_bounds(groups[g] for g in by)
    if not ip_dim:
        # svc keys live entirely below 2^32; no v6 sub-space.
        b6 = []
    n_rows = len(b4) + 1 + (len(b6) + 1 if ip_dim else 0)
    inc = np.zeros((n_rows, w), dtype=np.uint32)
    for g, rr in by.items():
        ranges = groups[g]
        if not ranges or rr.size == 0:
            continue
        gmask = _inc_mask(rr, w)
        nzw = np.nonzero(gmask)[0]
        vals = gmask[nzw]

        def write(a, b):
            inc[a : b + 1][:, nzw] |= vals

        for lo, hi in ranges:
            if ip_dim:
                _paint(b4, b6, lo, hi, write)
            else:
                a, b = _span_list(b4, int(lo), int(hi))
                write(a, b)
    if ip_dim:
        bounds = iputil.flip_u32(np.array(b4, dtype=np.uint64).astype(np.uint32))
        bounds6 = _v6_words(b6)
    else:
        bounds = np.array(b4, dtype=np.int64).astype(np.int32)
        bounds6 = np.zeros((0, 4), dtype=np.int32)
    return DimTable(bounds=bounds, bounds6=bounds6, inc=inc,
                    agg=build_agg(inc) if agg else None)


def _iso_host(gid: int, groups: list) -> IsoTable:
    ranges = groups[gid]
    b4, b6 = _dual_bounds([ranges])
    val = np.zeros(len(b4) + 1 + len(b6) + 1, dtype=np.int32)

    def write(a, b):
        val[a : b + 1] = 1

    for lo, hi in ranges:
        _paint(b4, b6, lo, hi, write)
    return IsoTable(
        bounds=iputil.flip_u32(np.array(b4, dtype=np.uint64).astype(np.uint32)),
        bounds6=_v6_words(b6),
        val=val,
    )


def _direction_host(
    dt: DirectionTensors, cps: CompiledPolicySet, w: int, agg: bool = False
) -> DeviceDirection:
    action = np.full(w * 32, ACT_DROP, dtype=np.int32)
    action[: dt.n_rules] = dt.action
    l7 = np.zeros(w * 32, dtype=np.int32)
    if dt.l7 is not None:
        l7[: dt.n_rules] = dt.l7
    return DeviceDirection(
        at=_dim_table_host(dt.at_gid, cps.ip_groups, w, ip_dim=True, agg=agg),
        peer=_dim_table_host(dt.peer_gid, cps.ip_groups, w, ip_dim=True,
                             agg=agg),
        svc=_dim_table_host(dt.svc_gid, cps.svc_groups, w, ip_dim=False,
                            agg=agg),
        action=action,
        l7=l7,
        word_idx=np.arange(w, dtype=np.int32),
    )


def _width(n_rules: int, word_multiple: int) -> int:
    """Rule words a direction's tables are built with: the rule count's
    words rounded up to TILE_WORDS for each of `word_multiple` rule-axis
    shards.  That one unit carries every alignment the consumers need: the
    row-major placement of the row-gathered `inc` tables (TILE_WORDS), an
    even split over the rule axis whose per-shard slice is itself tiled,
    and, because AGG_BLOCK divides TILE_WORDS, aggregate words that never
    straddle a row end or a shard boundary under pruning."""
    words = max(1, -(-n_rules // 32))
    unit = word_multiple * TILE_WORDS
    return -(-words // unit) * unit


def to_host(
    cps: CompiledPolicySet,
    word_multiple: int = 1,
    delta_slots: int = 0,
    prune_budget: int = 0,
) -> tuple[DeviceRuleSet, StaticMeta]:
    """Numpy-resident variant of to_device: the same pytree, zero device
    placement (jit accepts numpy leaves and places them itself — used by the
    driver's compile-check entry() so a broken accelerator runtime can still
    build example args).

    word_multiple pads each direction's rule-word count to a multiple (so
    the incidence word axis divides evenly across a rule-parallel mesh
    axis).  delta_slots reserves capacity for incremental membership deltas
    (see DeltaTable); 0 compiles the delta machinery out entirely.
    prune_budget > 0 builds the aggregate tables (DimTable.agg) and enables
    the two-level pruned classify at K = prune_budget candidate superblocks
    per lane and direction; 0 builds the exact pre-aggregate pytree.
    """
    agg = prune_budget > 0
    w_in = _width(cps.ingress.n_rules, word_multiple)
    w_out = _width(cps.egress.n_rules, word_multiple)
    drs = DeviceRuleSet(
        ingress=_direction_host(cps.ingress, cps, w_in, agg=agg),
        egress=_direction_host(cps.egress, cps, w_out, agg=agg),
        iso_in=_iso_host(cps.iso_in_gid, cps.ip_groups),
        iso_out=_iso_host(cps.iso_out_gid, cps.ip_groups),
        ip_delta=empty_delta(max(delta_slots, 1), w_in, w_out, xp=np),
    )
    meta = StaticMeta(
        in_phases=(cps.ingress.n_phase0, cps.ingress.n_k8s, cps.ingress.n_baseline),
        out_phases=(cps.egress.n_phase0, cps.egress.n_k8s, cps.egress.n_baseline),
        w_in=w_in,
        w_out=w_out,
        delta_slots=delta_slots,
        svcref=cps.has_svcref,
        prune_budget=prune_budget,
    )
    return drs, meta


def to_device(
    cps: CompiledPolicySet,
    word_multiple: int = 1,
    delta_slots: int = 0,
    prune_budget: int = 0,
) -> tuple[DeviceRuleSet, StaticMeta]:
    host, meta = to_host(cps, word_multiple, delta_slots, prune_budget)
    drs = jax.tree_util.tree_map(jnp.asarray, host)
    return drs, placed_meta(meta, drs)


# ---------------------------------------------------------------------------
# Entry-axis rung padding (round 9, the multi-tenant packing layer)
# ---------------------------------------------------------------------------

# Smallest entry-axis rung a padded table lands on: interval-boundary
# counts below this all share one shape, so small tenants collapse onto
# one compiled program instead of one per distinct group structure.
ENTRY_RUNG_FLOOR = 16

# Padding boundary values: the MAXIMUM of each key space.  searchsorted
# side='right' counts bounds <= x, so every x below the maximum resolves
# to its original interval row unchanged; x == maximum lands past the
# pad block, which is why the padder REPLICATES the top row's incidence
# across the whole pad region (any bisect variant then reads the same
# row content).  For IP dims the flipped-space max is the flip of
# 255.255.255.255 == int32 max; svc keys live below 2^24, so int32 max
# is unreachable there outright.
_PAD_BOUND = 2**31 - 1


def _entry_cap(n: int, floor: int = ENTRY_RUNG_FLOOR) -> int:
    """Natural entry count -> its pow2 rung (0 stays 0: an empty v6
    sub-table is a SHAPE, shared by every all-v4 world on the rung)."""
    if n <= 0:
        return 0
    return max(floor, 1 << (n - 1).bit_length())


def _pad_rows(rows: np.ndarray, at: int, count: int) -> np.ndarray:
    """Insert `count` replicas of row `at` directly after it."""
    if count <= 0:
        return rows
    return np.concatenate(
        [rows[: at + 1], np.repeat(rows[at : at + 1], count, axis=0),
         rows[at + 1 :]], axis=0)


def _pad_dim_table(tab: DimTable, cap4: int, cap6: int) -> DimTable:
    bounds = np.asarray(tab.bounds)
    bounds6 = np.asarray(tab.bounds6)
    inc = np.asarray(tab.inc)
    nb4, nb6 = bounds.shape[0], bounds6.shape[0]
    ip_dim = inc.shape[0] == nb4 + 1 + nb6 + 1  # svc dims have no v6 rows
    p4 = max(0, cap4 - nb4)
    p6 = max(0, cap6 - nb6) if ip_dim else 0
    if p4 == 0 and p6 == 0:
        return tab
    if p4:
        bounds = np.concatenate(
            [bounds, np.full(p4, _PAD_BOUND, bounds.dtype)])
        inc = _pad_rows(inc, nb4, p4)  # replicate the v4 top row
    if p6:
        bounds6 = np.concatenate(
            [bounds6, np.full((p6, 4), _PAD_BOUND, bounds6.dtype)], axis=0)
        inc = _pad_rows(inc, inc.shape[0] - 1, p6)  # replicate the v6 top row
    return DimTable(
        bounds=bounds, bounds6=bounds6, inc=inc,
        agg=build_agg(inc) if tab.agg is not None else None)


def _pad_iso_table(tab: IsoTable, cap4: int, cap6: int) -> IsoTable:
    bounds = np.asarray(tab.bounds)
    bounds6 = np.asarray(tab.bounds6)
    val = np.asarray(tab.val)
    nb4, nb6 = bounds.shape[0], bounds6.shape[0]
    p4 = max(0, cap4 - nb4)
    p6 = max(0, cap6 - nb6)
    if p4 == 0 and p6 == 0:
        return tab
    if p4:
        bounds = np.concatenate(
            [bounds, np.full(p4, _PAD_BOUND, bounds.dtype)])
        val = _pad_rows(val, nb4, p4)
    if p6:
        bounds6 = np.concatenate(
            [bounds6, np.full((p6, 4), _PAD_BOUND, bounds6.dtype)], axis=0)
        val = _pad_rows(val, val.shape[0] - 1, p6)
    return IsoTable(bounds=bounds, bounds6=bounds6, val=val)


def pad_ruleset_entries(
    drs: DeviceRuleSet, cap4: Optional[int] = None,
    cap6: Optional[int] = None,
) -> tuple[DeviceRuleSet, tuple[int, int]]:
    """Pad every dimension's ENTRY axes (interval boundaries + incidence
    rows) of a HOST ruleset to pow2 rungs -> (padded drs, (cap4, cap6)).

    The word axis is already rung-shaped by the caller (padded rule
    counts through `_width`); this pads the other jit-signature axes —
    per-dim boundary counts, which otherwise vary with each tenant's
    group structure — so two rule worlds on the same rung produce
    IDENTICAL tensor shapes and share one compiled program (the
    multi-tenant shared-compile contract, datapath/tenancy.py).  Padding
    is semantically invisible: pad boundaries sit at the key-space
    maximum and every pad row replicates its neighbor's incidence, so no
    probe can resolve to different rule bits (regression-pinned by the
    tenancy parity suite).  Aggregate tables are rebuilt from the padded
    incidence (build_agg is the one builder, so the scrub/property tests
    keep their consistency contract)."""
    dims = [drs.ingress.at, drs.ingress.peer, drs.ingress.svc,
            drs.egress.at, drs.egress.peer, drs.egress.svc]
    isos = [drs.iso_in, drs.iso_out]
    if cap4 is None:
        cap4 = _entry_cap(max(
            [np.asarray(t.bounds).shape[0] for t in dims + isos]))
    if cap6 is None:
        cap6 = _entry_cap(max(
            [np.asarray(t.bounds6).shape[0] for t in dims + isos]))

    def pad_dir(d: DeviceDirection) -> DeviceDirection:
        return d._replace(
            at=_pad_dim_table(d.at, cap4, cap6),
            peer=_pad_dim_table(d.peer, cap4, cap6),
            svc=_pad_dim_table(d.svc, cap4, cap6),
        )

    return drs._replace(
        ingress=pad_dir(drs.ingress),
        egress=pad_dir(drs.egress),
        iso_in=_pad_iso_table(drs.iso_in, cap4, cap6),
        iso_out=_pad_iso_table(drs.iso_out, cap4, cap6),
    ), (int(cap4), int(cap6))


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _lex_le_words(a, b) -> jax.Array:
    """Lexicographic a <= b over four words, the most significant first,
    indexed on the LEADING axis (per-word flipped i32)."""
    a0, a1, a2, a3 = a
    b0, b1, b2, b3 = b
    return (a0 < b0) | ((a0 == b0) & ((a1 < b1) | ((a1 == b1) & (
        (a2 < b2) | ((a2 == b2) & (a3 <= b3))))))


def _lex_le4(a: jax.Array, b: jax.Array) -> jax.Array:
    """Lexicographic a <= b over a trailing 4-word axis (per-word flipped
    i32 — the same compare _searchsorted6 builds from)."""
    return _lex_le_words([a[..., w] for w in range(4)],
                         [b[..., w] for w in range(4)])


def _delta_lane_match(ip_f, dt: DeltaTable, i, wide):
    """Lanes slot i's range covers: v4 slots compare the narrow column of
    v4 lanes; v6 slots the wide words of v6 lanes (family-pure slots —
    the dual-stack membership test, shared by rows and iso)."""
    m4 = (ip_f >= dt.lo_f[i]) & (ip_f <= dt.hi_f[i])
    if wide is None:
        return m4
    xw, is6 = wide
    m4 = m4 & (is6 == 0) & (dt.fam[i] == 0)
    m6 = (
        (is6 != 0) & (dt.fam[i] == 1)
        & _lex_le4(dt.lo6_w[i][None, :], xw)
        & _lex_le4(xw, dt.hi6_w[i][None, :])
    )
    return m4 | m6


def _over_active_slots(dt: DeltaTable, body, init):
    """`body` folded over the active delta slots, in append order.  The
    eager fresh walks (canary, audit re-proof) trace `body` anew on every
    call — it closes over the probe columns — and so compile the loop
    again each time; with no slot active (a concrete n of 0: the state
    between a bundle and the next membership delta) they skip it.  Under
    jit n is traced and the loop lowers as it always did."""
    if not isinstance(dt.n, jax.core.Tracer) and int(dt.n) == 0:
        return init
    return jax.lax.fori_loop(0, dt.n, body, init)


def _patch_rows(rows: jax.Array, ip_f: jax.Array, dt: DeltaTable, masks,
                wide=None) -> jax.Array:
    """Apply the active delta slots to gathered incidence rows (B, W).
    wide = (xw (B,4), is6) in dual-stack worlds — the dimension's lane
    words, so v6 slots patch v6 lanes."""

    def body(i, rows):
        m = _delta_lane_match(ip_f, dt, i, wide)
        mask = masks[i][None, :]
        s = dt.sign[i]
        rows = jnp.where((m & (s > 0))[:, None], rows | mask, rows)
        rows = jnp.where((m & (s < 0))[:, None], rows & ~mask, rows)
        return rows

    return _over_active_slots(dt, body, rows)


def _patch_iso(bit: jax.Array, ip_f: jax.Array, dt: DeltaTable, which: int,
               wide=None) -> jax.Array:
    def body(i, bit):
        m = (
            _delta_lane_match(ip_f, dt, i, wide)
            & (((dt.iso[i] >> which) & 1) == 1)
        )
        s = dt.sign[i]
        bit = jnp.where(m & (s > 0), 1, bit)
        bit = jnp.where(m & (s < 0), 0, bit)
        return bit

    return _over_active_slots(dt, body, bit)


def _agg_mask(mask_w: jax.Array) -> jax.Array:
    """(W,) u32 delta rule mask -> (W/AGG_BLOCK,) u32 aggregate mask, the
    device-side twin of build_agg over one row (delta-slot aggregate
    patching needs no new DeltaTable fields — the aggregate of a slot's
    pre-resolved mask is derived in-kernel from the mask itself, so the
    two can never drift)."""
    s = mask_w.shape[0] // AGG_BLOCK
    nz = (mask_w.reshape(s, AGG_BLOCK) != 0).astype(jnp.uint32)
    j = jnp.arange(AGG_BLOCK, dtype=jnp.uint32)[None, :]
    return (nz << j).sum(axis=1, dtype=jnp.uint32)  # disjoint bits: sum==OR


def _patch_agg(rows: jax.Array, ip_f: jax.Array, dt: DeltaTable, masks,
               wide=None) -> jax.Array:
    """Delta-slot aggregate patching of gathered aggregate rows (B, S):
    SET slots OR their aggregate mask in (a new member may light words the
    compiled table left dark — skipping this would be a false NEGATIVE);
    CLEAR slots leave the aggregate alone (a stale set bit is a legal
    false positive — the candidate gather fetches the full words, applies
    the full-width clear, and finds no match)."""

    def body(i, rows):
        m = _delta_lane_match(ip_f, dt, i, wide) & (dt.sign[i] > 0)
        am = _agg_mask(masks[i])[None, :]
        return jnp.where(m[:, None], rows | am, rows)

    return _over_active_slots(dt, body, rows)


def _patch_cand(cw: jax.Array, widx: jax.Array, ip_f: jax.Array,
                dt: DeltaTable, masks, wide=None) -> jax.Array:
    """_patch_rows over CANDIDATE-shaped rows (B, K, AGG_BLOCK): each
    slot's (W,) mask is gathered at the lanes' candidate word indices
    `widx` so set AND clear apply at full precision on exactly the words
    the pruned path fetched."""

    def body(i, cw):
        m = _delta_lane_match(ip_f, dt, i, wide)
        mw = masks[i][widx]  # (B, K, AGG_BLOCK) gather from (W,)
        s = dt.sign[i]
        cw = jnp.where((m & (s > 0))[:, None, None], cw | mw, cw)
        cw = jnp.where((m & (s < 0))[:, None, None], cw & ~mw, cw)
        return cw

    return _over_active_slots(dt, body, cw)


def _phase_first_from_base(mu: jax.Array, base: jax.Array, phases):
    """Per-phase first-set-bit over words with PER-ELEMENT global rule
    bases: mu (..., n) u32 match words, base (..., n) i32 = global word
    index * 32.  The _phase_hits/_phase_scan_tile_dyn mask discipline
    applied element-wise — shared by the pruned candidate scan (XLA and
    pallas consumer alike) so the three first-match paths cannot drift.
    -> 3 x (...,) i32 global rule indices (BIG = no match)."""

    def first_bounded(lo_rule, hi_rule):
        k_lo = jnp.clip(lo_rule - base, 0, 32)
        k_hi = jnp.clip(hi_rule - base, 0, 32)
        mask_lo = jnp.where(
            k_lo <= 0,
            jnp.uint32(_ALL1),
            ~((jnp.uint32(1) << jnp.minimum(k_lo, 31).astype(jnp.uint32))
              - jnp.uint32(1)),
        )
        mask_lo = jnp.where(k_lo >= 32, jnp.uint32(0), mask_lo)
        mask_hi = jnp.where(
            k_hi >= 32,
            jnp.uint32(_ALL1),
            (jnp.uint32(1) << jnp.clip(k_hi, 0, 31).astype(jnp.uint32))
            - jnp.uint32(1),
        )
        mw = mu & mask_lo & mask_hi
        lsb = mw & (jnp.uint32(0) - mw)
        tz = jax.lax.population_count(lsb - jnp.uint32(1))
        v = jnp.where(mw == jnp.uint32(0), BIG, base + tz.astype(jnp.int32))
        return jnp.min(v, axis=-1)

    n0, nk, _nb = phases
    return (
        first_bounded(0, n0),
        first_bounded(n0, n0 + nk),
        first_bounded(n0 + nk, 1 << 30),
    )


class PruneAutotuner:
    """Bounded hysteresis controller for the prune K budget (the
    DrainAutotuner pattern, fed by the measured fallback rate instead of
    queue depth).  Pure decision logic: observe(classified, fallbacks)
    -> the budget for subsequent classifies.  One rung per move, only
    after `sticky` consecutive same-direction pressure signals; empty
    windows hold."""

    def __init__(self, initial: int, sticky: int = PRUNE_STICKY,
                 fb_high: float = PRUNE_FB_HIGH, fb_low: float = PRUNE_FB_LOW):
        self.rungs = list(PRUNE_LADDER)
        self.idx = min(
            range(len(self.rungs)),
            key=lambda i: (abs(self.rungs[i] - int(initial)), self.rungs[i]),
        )
        self.sticky = int(sticky)
        self.fb_high = float(fb_high)
        self.fb_low = float(fb_low)
        self._streak = 0
        self.decisions_up = 0
        self.decisions_down = 0

    @property
    def budget(self) -> int:
        return self.rungs[self.idx]

    def observe(self, classified: int, fallbacks: int) -> int:
        if classified <= 0:
            return self.budget  # empty window: no signal, streak kept
        rate = fallbacks / classified
        if rate > self.fb_high:
            signal = 1
        elif rate < self.fb_low:
            signal = -1
        else:
            signal = 0
        if signal == 0 or (self._streak and (signal > 0) != (self._streak > 0)):
            self._streak = signal
            return self.budget
        self._streak += signal
        if self._streak >= self.sticky and self.idx < len(self.rungs) - 1:
            self.idx += 1
            self.decisions_up += 1
            self._streak = 0
        elif self._streak <= -self.sticky and self.idx > 0:
            self.idx -= 1
            self.decisions_down += 1
            self._streak = 0
        return self.budget


def _dim_index(tab, x: jax.Array, x6w, is6) -> jax.Array:
    """Interval row index for one dimension: searchsorted in the v4
    sub-space, or (for v6 lanes) the lexicographic v6 sub-space offset by
    the v4 rows (DimTable dual-stack layout).  x6w=None = no v6 lanes for
    this probe (pure-v4 batch, or the family-blind svc key space).  The
    ONE derivation — shared by the full-width and pruned classify paths
    so the v6 index math cannot drift between them."""
    i4 = _searchsorted_right(tab.bounds, x)
    if x6w is None:
        return i4
    i6 = tab.bounds.shape[0] + 1 + _searchsorted6(tab.bounds6, x6w)
    return jnp.where(is6 != 0, i6, i4)


def _svcref_key(svc_key: jax.Array, svc_ref) -> jax.Array:
    """The toServices SECOND probe key (compiler SVCREF_BASE contract):
    the lane's ServiceLB-resolved service index mapped into the reference
    sub-space, SVCREF_NONE for non-service lanes.  The ONE derivation —
    shared by the full-width and pruned classify paths so the probe-key
    contract cannot drift between them."""
    from ..compiler.compile import SVCREF_BASE, SVCREF_NONE

    if svc_ref is None:
        return jnp.full_like(svc_key, SVCREF_NONE)
    return jnp.where(svc_ref >= 0, SVCREF_BASE + svc_ref, SVCREF_NONE)


def _phase_hits(match: jax.Array, word_idx: jax.Array, phases: tuple[int, int, int]):
    """match (B, W) u32 -> per-phase first-set global rule index (BIG = none).

    First-match-in-priority-order == lowest set bit: rule order encodes
    priority (compiler/compile.py), bit r of word w is global rule 32w+r.
    """
    n0, nk, _nb = phases
    base = word_idx * 32  # (W,) i32

    def mask_lt(n: int) -> jax.Array:
        """(W,) u32 — bits whose global rule index < n."""
        k = jnp.clip(n - base, 0, 32)
        m = (jnp.uint32(1) << jnp.minimum(k, 31).astype(jnp.uint32)) - jnp.uint32(1)
        return jnp.where(k >= 32, jnp.uint32(_ALL1), m)

    m0 = mask_lt(n0)
    mhi = mask_lt(n0 + nk)
    phase_masks = (m0, mhi & ~m0, ~mhi)

    def first(pm: jax.Array) -> jax.Array:
        mw = match & pm[None, :]
        lsb = mw & (jnp.uint32(0) - mw)
        tz = jax.lax.population_count(lsb - jnp.uint32(1))  # 32 when mw == 0
        idx = base[None, :] + tz.astype(jnp.int32)
        idx = jnp.where(mw == jnp.uint32(0), BIG, idx)
        return idx.min(axis=1)

    return tuple(first(pm) for pm in phase_masks)


# Study notes, rounds 3-8.  Every timing, rate and bandwidth figure below
# this line is from July 2026, on an earlier runtime, not re-measured on
# today's jax 0.9.0 / libtpu 0.0.34; the structural arguments (what fuses,
# what Mosaic can express) are what the code still relies on.
#
# Optimization note (measured on v5e, 100k rules, B=32k): replacing the
# three full-width masked scans with STATIC per-phase word slices (phases
# are contiguous rule ranges, so each phase only owns words
# [lo//32, ceil(hi/32))) was tried and is ~1.5x SLOWER (8.3ms vs 5.6ms per
# batch) — the slices break XLA's fusion of gather -> AND -> scan into one
# streaming loop and force the (B, W) match tensor to materialize.
#
# Negative result (round 3, measured on the 100k-rule bench world): a
# TWO-LEVEL incidence hierarchy (per-dimension 32-word block summaries,
# AND the summaries, walk only candidate blocks) does NOT pay: per-DIM
# summary density is 0.90/0.94/1.00 (at/peer/svc), so the summary AND
# leaves ~86% of blocks as candidates (51 of 59 per packet) even though
# true matches average 0.7 rules/packet — the sparsity lives in the 3-way
# intersection, which is only knowable after the gathers the hierarchy
# was meant to avoid.
#
# Round-4 cold-path study (100k-rule bench world, B=32k; July 2026,
# earlier runtime, not re-measured).  Cost decomposition of the round-3
# classifier at 7.0ms/batch (4.6M pps): searchsorted 0.77ms; the 6 row
# gathers ALONE are 4.4ms —
# XLA's gather engine runs at ~84% of HBM peak but counts double, because
# gather output always round-trips HBM (read 1.23GB + write 1.23GB), and
# every unfused consumer re-reads it.  Attempts to eliminate the
# write-back, each DEAD by measurement:
#   1. Pallas scalar-prefetch pipelined per-row loads (grid over packet
#      tiles, BlockSpec index_map from prefetched interval indices):
#      38 GB/s — the per-DMA fixed cost is ~200ns/row and 196k rows/batch
#      need <8ns each.  No DMA-descriptor path can fetch scattered ~7KB
#      rows at line rate; only XLA's gather engine can.
#   2. In-VMEM dynamic gather (tpu.dynamic_gather via take_along_axis):
#      Mosaic lowers it INTRA-VREG ONLY — sublane gathers beyond 8 rows
#      and lane gathers beyond 128 lanes crash the backend.  Arbitrary
#      VMEM table gathers are unavailable on this toolchain.
#   3. Cluster-compressed incidence (u8 ids into VMEM-resident distinct
#      sub-row tables, expanded by intra-vreg lane gather): per-128-word
#      chunk the bench world has 850-3240 DISTINCT sub-rows per dimension
#      — far beyond the 128-lane gather reach.  Genuine entropy.
#   4. Rule-triple dedup (rules sharing (at,peer,svc) gids have identical
#      match conditions; per-phase triple bitmaps ordered by first-rule
#      priority preserve first-match-=-first-bit): distinct-triple ratio
#      measured 1.00x — every rule is a unique triple here.  Zero width
#      reduction.
#   5. MXU one-hot expansion (radix-partitioned packets x 128-row blocks):
#      O(B x 128 x W) FLOPs = ~4ms at bf16 peak before sort costs.  The
#      128x FLOP blowup over the gather's O(B x W) never pays.
# Roofline conclusion: per-packet row volume is ~37.5KB (irreducible —
# notes 2-4 above rule out structural sparsity), and the only functional
# fetch path (XLA gather) doubles it.  2 x 37.5KB at the measured
# 684 GB/s is 9.1M pps for the gather alone, before searchsorted and the
# scan — so ~10M pps cold is out of reach on this chip/toolchain, and the
# remaining winnable margin was the unfused-consumer re-reads.  That win
# is taken by classify_batch_fused below: XLA performs the 6 gathers, ONE
# pallas kernel consumes each gathered byte exactly once (AND + per-phase
# first-set-bit in VMEM, contiguous 1MB block DMAs), measured 6.3ms vs
# 7.1ms (5.2M vs 4.6M pps).
#
# Round-5 follow-up (round-4 verdict weak #1 asked whether the
# 1.9ms/batch of non-gather time could be overlapped or folded; same
# world, B=32k, /tmp/cold_study.py methodology):
#   Measured decomposition: searchsorted ALONE 1.52ms; searchsorted +
#   6 gathers + a reduction FUSED into the gather loops 4.44ms; fused
#   end-to-end 6.80ms.  4.44 equals the round-4 "gathers alone" bound —
#   i.e. searchsorted is ALREADY hidden under the gather streams (its
#   1.52ms of VPU compare work overlaps the DMA wavefronts inside XLA's
#   fused loops).  Verdict idea (a), "overlap searchsorted with the
#   gather stream", is therefore already in effect; there is no further
#   cross-op overlap to program — a TensorCore runs one XLA op at a
#   time, and fusion is the only overlap mechanism exposed.
#   Verdict idea (b), "fold the two-level searchsorted's in-block finish
#   into the consumer kernel": the in-block finish needs a per-lane
#   dynamic 256-word window from the bounds table — exactly the
#   arbitrary-VMEM-gather shape note 2 above measured as unavailable
#   (Mosaic dynamic_gather is intra-vreg only).  Dead by the same wall.
#   New idea (c), AND the three gathered rows IN XLA and hand the pallas
#   consumer ONE matrix per direction (hoping gather->AND fuses and
#   halves the consumer's read volume): measured 7.61ms — WORSE than the
#   6-input consumer.  XLA materializes all six gather outputs AND the
#   two AND results (multi-consumer gathers don't fuse into one loop),
#   adding ~12.5KB/packet of traffic instead of removing any.
# Residual: end-to-end minus the gather bound is 2.36ms — the pallas
# consumer's re-read of the 37.5KB/packet the gathers materialized
# (37.5KB x 32k / 684 GB/s = 1.75ms floor + tile scheduling).  Removing
# it requires gathering INTO the consumer, which note 1 bounds at
# 38 GB/s.  The cold ceiling on this chip/toolchain therefore stands at
# ~4.8-5.4M pps as shipped, with ~7.4M the hard gather-bound limit.
#
# Round-6 overlap study (the churn gap is SERIALIZATION, not kernel
# speed — the July 2026 record put steady_churn at 4.97M pps = 26.4ms per
# 131k batch (earlier runtime, not re-measured), vs the Amdahl
# prediction of the measured parts: 5.7ms fast step
# + ~3.4ms for one coalesced 16k drain = 9.1ms, ~14M pps.  The ~17ms gap
# is the drain pipeline running IN SEQUENCE with the fast path: lookup
# pass, classify, commit scatters, eviction gather, plus the engine's
# two separate full-table maintenance scans and the per-call output
# fetch blocking the next dispatch).  What was restructured, and what
# was ruled out:
#   OVERLAPPED (shipped, models/pipeline + datapath/slowpath):
#   (a) eviction-scan + aging + revalidation folded into the drain's
#       commit pass (meta.drain_reclaim): the eviction audit already
#       gathers each insert target's old key row; reading its ts/conf in
#       the same pass classifies dead rows (idle-expired / stale-gen) as
#       reclaims, so the engine's stale-epoch heal needs ONE fused
#       maintain_scan (age + revalidate in a single keys/meta/ts read)
#       instead of two full passes over PipelineState — at 2^22 slots
#       that removes ~150MB of HBM traffic per heal.
#   (b) the drain dispatched with the STATE DONATED
#       (pl.pipeline_step_donated): without donation every per-call
#       drain allocates fresh output buffers for the rewritten cache
#       columns (~150MB at 2^22 slots) and copies; donation lets XLA
#       alias the scatters in place — the eager-dispatch analog of the
#       fori_loop carry aliasing the bench already enjoyed.
#   (c) one-step commit deferral (two-slot staging): drain of window i-1
#       dispatches after fast step i with no dependency on its OUTPUTS
#       (only the carried state), and the host-side materialization of
#       drain outputs retires two slots later — so the host never blocks
#       the device pipeline on np.asarray between fast and drain, and
#       XLA/the runtime can pipeline the dispatch stream.  Verdict
#       visibility lags exactly one window (the admitted lanes' flows
#       were pending anyway); state visibility is immediate via the
#       carried pytree (the lost-update guard).
#   NOT overlapped, dead by the same walls as rounds 4-5:
#   (d) lowering the commit scatters into the pallas classify consumer
#       (one kernel classifying + writing the cache): Mosaic on this
#       toolchain has no arbitrary-VMEM-scatter path, the same wall as
#       note 2's intra-vreg-only dynamic_gather — and the flow cache is
#       64MB+ per column, far beyond VMEM residency anyway.
#   (e) true cross-op concurrency: a TensorCore runs one XLA op at a
#       time, so "overlap" here means removing redundant passes, copies
#       and host round-trips from the serial schedule, not co-executing
#       fast and drain.  Not measured on a chip: the overlapped cadence
#       has no on-chip record.
#
# Round-7: aggregated-bitmap pruning (ROADMAP item 2's kernel half; the
# two-level classify shipped below as _classify_pruned).  Why this is NOT
# the round-3 negative result re-tried: round 3 summarized at 32-WORD
# block granularity (one bit per 1024 rules), where per-dim summary
# density was 0.90/0.94/1.00 and the AND left 86% of blocks candidates.
# The round-7 aggregate is one bit per WORD (32 rules) — 32x finer — and
# the 32-word superblock is the candidate unit only for the SECOND
# gather's shape (contiguous 128B block rows, the fast TPU gather
# pattern), not for the pruning decision: a superblock is live iff its
# aggregate WORD is nonzero, i.e. iff at least one of its 32
# word-granular AND bits survives.  The ABV lesson (aggregated bit
# vectors over sparse rule bitmaps) is that the 3-way AND at word
# granularity is what is sparse, and that is knowable from ~W/32 words
# per dimension instead of W.  Volume math at the bench world (W=3136
# agg-padded, S=98): phase 1 gathers 6 x 98 u32 = ~2.4KB/packet (vs
# ~75KB full-width, XLA's gather write-back doubling both); phase 2 at
# K=4 adds 6 x 128 words = ~3KB for lanes the aggregate AND leaves live
# — ~12x less candidate-path row volume, moving the ~7.4M pps hard
# gather bound (round 4) past the 10M target, while the
# aggregate-AND-zero short circuit drops the adversarial/all-miss
# regime to phase-1 volume alone.  Exactness is structural, not
# statistical: aggregate bits admit false positives (the candidate
# gather then finds an all-zero AND -> default verdict) but never false
# negatives, and lanes whose candidate count exceeds K redispatch at
# full width in a pow2-rung lax.switch (the PR 9 _spill_retry shape,
# in-jit), metered as match_prune_fallbacks_total and fed to the
# K-budget autotuner (PruneAutotuner, the PR 6 DrainAutotuner pattern).
# Not measured on a chip.  The pruned consumer compiles and serves
# oracle-exact on a v5e (chip_smoke.py, PR 21); its rate and fallback
# rate there are not measured.
#
# Round-8 tried the whole slow path as ONE Pallas pass (probe decode,
# aggregate AND, double-buffered candidate-superblock DMA, first match,
# resolve and commit-row packing in VMEM) to remove the HBM round trips
# between XLA's stages.  It never lowered on a v5e (PR 21, jax 0.9.0 /
# libtpu 0.0.34: Pallas has no TPU lowering for the in-kernel lax.top_k,
# and with that replaced Mosaic refuses the 32-word candidate-row DMA —
# AGG_BLOCK is a quarter of a 128-lane tile, a table-layout redesign, not
# a local fix) and is gone.


def _resolve(action: jax.Array, hits, pod_iso: jax.Array):
    """Phase resolution -> (code (B,), rule_idx (B,) [-1 = default])."""
    h0, hk, hb = hits
    a0 = action[jnp.clip(h0, 0, action.shape[0] - 1)]
    ab = action[jnp.clip(hb, 0, action.shape[0] - 1)]
    has0 = h0 < BIG
    hask = hk < BIG
    hasb = hb < BIG

    decided0 = has0 & (a0 != ACT_PASS)
    decidedb = hasb & (ab != ACT_PASS)

    # K8s NP rules are any-match ALLOW within the isolation model.
    k8s_code = jnp.where(hask, ACT_ALLOW, ACT_DROP)
    k8s_rule = jnp.where(hask, hk, -1)

    code = jnp.where(
        decided0,
        a0,
        jnp.where(
            pod_iso == 1,
            k8s_code,
            jnp.where(decidedb, ab, ACT_ALLOW),
        ),
    )
    rule = jnp.where(
        decided0,
        h0,
        jnp.where(
            pod_iso == 1,
            k8s_rule,
            jnp.where(decidedb, hb, -1),
        ),
    )
    return code.astype(jnp.int32), rule.astype(jnp.int32)


_SS_FLAT = 4096  # boundaries up to which all pairs beat the two levels
_SS_BLOCK = 256  # ~sqrt(NB) at the 100k-rule scale; compares/pkt = NB/256+256


def _searchsorted_right(bounds: jax.Array, x: jax.Array) -> jax.Array:
    """TPU-tuned searchsorted(side='right').

    jnp's default 'scan' (binary-search) method lowers to a sequential
    gather loop that is ~40x slower on TPU than an all-pairs compare-reduce
    for our table sizes (measured on v5e: 10.9 ms vs 0.28 ms at B=32k,
    NB=33k).  compare_all is O(B*NB) and wins up to a few thousand bounds;
    beyond that a TWO-LEVEL blocked search cuts the compare volume ~128x:
    compare_all over the ~NB/256 block maxima picks the block, one (B, 256)
    row gather + mask-count finishes inside it.  Both levels are streaming
    VPU work with static shapes (vmap/shard_map friendly).
    """
    nb = bounds.shape[0]
    if nb <= _SS_FLAT:
        return jnp.searchsorted(bounds, x, side="right", method="compare_all")
    K = _SS_BLOCK
    nblk = -(-nb // K)
    pad = nblk * K - nb
    # Pads sit at int32 max; they are masked out of the in-block count, so a
    # genuine max-valued bound (flip of 0xFFFFFFFF) still counts correctly.
    bp = jnp.concatenate(
        [bounds, jnp.full((pad,), 2**31 - 1, bounds.dtype)]
    ).reshape(nblk, K)
    blk = jnp.searchsorted(bp[:, -1], x, side="right", method="compare_all")
    blk_c = jnp.minimum(blk, nblk - 1)
    window = bp[blk_c]  # (B, K) row gather
    off = jnp.arange(K, dtype=jnp.int32)
    valid = (blk_c[:, None] * K + off[None, :]) < nb
    inblock = ((window <= x[:, None]) & valid).sum(axis=1, dtype=jnp.int32)
    return blk_c * K + inblock


# v6 rows a block.  On the chip 128 and 256 read alike, 512 worse (PERF.md s6).
_SS6_BLOCK = 128


@device_scope("classify.index6")
def _searchsorted6(bounds6: jax.Array, xw: jax.Array) -> jax.Array:
    """Lexicographic searchsorted(side='right') over 4-word v6 boundaries.

    bounds6 (N, 4) and xw (B, 4) are per-word sign-flipped i32, so word-wise
    signed compares give unsigned lexicographic order.  The same two sizes
    as _searchsorted_right, and the same indices from both: up to _SS_FLAT
    boundaries an all-pairs compare-count; beyond (a peer dimension of a
    100k-rule dual-stack node holds ~15k) the all-pairs compare over the
    ~N/K block-last rows picks the block, one gather of that block a lane
    and the compare-count inside it finish.  A block is a row of 4*K words,
    word plane after word plane, so the gathered window has K as its minor
    axis and never the 4 words.
    """
    n = bounds6.shape[0]
    if n == 0:
        return jnp.zeros(xw.shape[0], dtype=jnp.int32)
    if n <= _SS_FLAT:
        leq = _lex_le4(bounds6[None, :, :], xw[:, None, :])  # (B, N)
        return leq.sum(axis=1, dtype=jnp.int32)
    K = _SS6_BLOCK
    nblk = -(-n // K)
    # This function's own pads are all-ones rows, as pad_ruleset_entries'
    # rows and a genuine ffff:...:ffff boundary are: position alone tells
    # them apart (valid, below).
    planes = jnp.concatenate(
        [bounds6, jnp.full((nblk * K - n, 4), _PAD_BOUND, bounds6.dtype)]
    ).T.reshape(4, nblk, K)
    x = xw.T[:, :, None]  # (4, B, 1)
    blk = _lex_le_words(planes[:, None, :, -1], x).sum(axis=1, dtype=jnp.int32)
    blk_c = jnp.minimum(blk, nblk - 1)
    blocks = planes.transpose(1, 0, 2).reshape(nblk, 4 * K)
    window = blocks[blk_c].reshape(-1, 4, K).transpose(1, 0, 2)  # (4, B, K)
    off = jnp.arange(K, dtype=jnp.int32)
    valid = (blk_c[:, None] * K + off[None, :]) < n
    inblock = (_lex_le_words(window, x) & valid).sum(axis=1, dtype=jnp.int32)
    return blk_c * K + inblock


@device_scope("classify")
def classify_batch(
    drs: DeviceRuleSet,
    src_ip_f: jax.Array,  # (B,) sign-flipped i32
    dst_ip_f: jax.Array,
    proto: jax.Array,  # (B,) i32
    dst_port: jax.Array,  # (B,) i32
    *,
    meta: StaticMeta,
    hit_combine=None,
    fused: bool = False,
    v6=None,
    svc_ref=None,
):
    """-> dict with final/egress/ingress codes and deciding rule indices.

    Codes use the oracle encoding: 0 allow, 1 drop, 2 reject.

    hit_combine, if given, is applied to each per-phase first-match hit
    tensor between the word scan and phase resolution — the rule-parallel
    seam: a shard_map caller passes ``lambda h: lax.pmin(h, 'rule')`` so
    each rule shard ANDs only its local incidence words and the global first
    match is an all-reduce over ICI (the TPU analog of OVS evaluating one
    shared table).

    v6, if given, is the dual-stack lane extension (ref pipeline.go IPv6
    table): a (src6w_f (B,4), dst6w_f (B,4), is6 (B,)) tuple of per-word
    sign-flipped v6 addresses plus the family mask.  v6 lanes resolve in
    each dimension's v6 interval sub-space; their v4-lane inputs are
    ignored.  None = pure-v4 batch (zero extra work — the v4 interval rows
    come first, so indices need no adjustment).

    fused=True consumes the gathered rows through the pallas consumer
    kernel (one read per gathered byte; see the cold-path study above).
    Composes with hit_combine's rule-axis sharding: each shard's kernel
    receives its global word offset (word_idx[0], carried as data for
    exactly this) and emits GLOBAL rule indices, so the pmin all-reduce
    combines them like the XLA-scan path — the sharded walk keeps the
    fused cold-path win.  Delta patching composes (it runs on the
    gathered rows before the consumer).  Off-TPU the kernel runs in
    interpret mode (slow; parity tests only).

    meta.prune_budget > 0 routes through the two-level aggregated-bitmap
    path (_classify_pruned, round 7).
    """
    if meta.prune_budget > 0 and drs.ingress.at.agg is not None:
        return _classify_pruned(
            drs, src_ip_f, dst_ip_f, proto, dst_port, meta=meta,
            hit_combine=hit_combine, fused=fused, v6=v6, svc_ref=svc_ref,
        )
    ing, eg = drs.ingress, drs.egress
    svc_key = (proto << 16) | dst_port
    if v6 is not None:
        src6w, dst6w, is6 = v6
    else:
        is6 = None

    def dim_row(tab: DimTable, x: jax.Array, x6w=None) -> jax.Array:
        # x6w is None for the svc dimension (the (proto<<16|port) key
        # space is shared by both families — no v6 sub-space).
        return tab.inc[_dim_index(tab, x, x6w, is6)]

    def iso_bit(tab: IsoTable, x: jax.Array, x6w=None) -> jax.Array:
        return tab.val[_dim_index(tab, x, x6w, is6)]

    with device_scope("classify.candidate"):
        # Ingress: pod = dst, peer = src.  Egress: pod = src, peer = dst.
        s6 = src6w if v6 is not None else None
        d6 = dst6w if v6 is not None else None
        in_at = dim_row(ing.at, dst_ip_f, d6)
        in_peer = dim_row(ing.peer, src_ip_f, s6)
        in_svc = dim_row(ing.svc, svc_key)
        out_at = dim_row(eg.at, src_ip_f, s6)
        out_peer = dim_row(eg.peer, dst_ip_f, d6)
        out_svc = dim_row(eg.svc, svc_key)
        if meta.svcref:
            # toServices probe (the ServiceGroupID-conjunction analog): a
            # second egress svc-dim gather keyed on the lane's ServiceLB
            # resolution in the reference sub-space.  OR is exact — ordinary
            # port ranges live below SVCREF_BASE and reference ranges at
            # SVCREF_BASE + idx, so each rule can match via exactly one of
            # the two probes (compiler/compile.py SVCREF_BASE contract).
            out_svc = out_svc | dim_row(eg.svc, _svcref_key(svc_key, svc_ref))
        iso_in = iso_bit(drs.iso_in, dst_ip_f, d6)
        iso_out = iso_bit(drs.iso_out, src_ip_f, s6)

        if meta.delta_slots > 0:
            # Incremental membership deltas patch the gathered rows, so peer/
            # appliedTo/isolation consumers all see post-delta membership.
            # Slots are family-pure: v4 slots patch v4 lanes on the narrow
            # column, v6 slots patch v6 lanes on their wide words — v6 pod
            # churn stays O(1), no recompile (DeltaTable docstring).
            d = drs.ip_delta
            wide_d = None if v6 is None else (d6, is6)
            wide_s = None if v6 is None else (s6, is6)
            in_at = _patch_rows(in_at, dst_ip_f, d, d.at_in, wide_d)
            in_peer = _patch_rows(in_peer, src_ip_f, d, d.peer_in, wide_s)
            out_at = _patch_rows(out_at, src_ip_f, d, d.at_out, wide_s)
            out_peer = _patch_rows(out_peer, dst_ip_f, d, d.peer_out, wide_d)
            iso_in = _patch_iso(iso_in, dst_ip_f, d, 0, wide_d)
            iso_out = _patch_iso(iso_out, src_ip_f, d, 1, wide_s)

    with device_scope("classify.scan"):
        if fused:
            shard = hit_combine is not None
            in_hits, out_hits = _fused_hits(
                (in_at, in_peer, in_svc), (out_at, out_peer, out_svc), meta,
                w0_in=ing.word_idx[0] if shard else None,
                w0_out=eg.word_idx[0] if shard else None,
            )
        else:
            in_hits = _phase_hits(
                in_at & in_peer & in_svc, ing.word_idx, meta.in_phases
            )
            out_hits = _phase_hits(
                out_at & out_peer & out_svc, eg.word_idx, meta.out_phases
            )

        if hit_combine is not None:
            in_hits = tuple(hit_combine(h) for h in in_hits)
            out_hits = tuple(hit_combine(h) for h in out_hits)

        in_code, in_rule = _resolve(ing.action, in_hits, iso_in)
        out_code, out_rule = _resolve(eg.action, out_hits, iso_out)

        final = jnp.where(out_code != ACT_ALLOW, out_code, in_code)
    return {
        "code": final,
        "egress_code": out_code,
        "egress_rule": out_rule,
        "ingress_code": in_code,
        "ingress_rule": in_rule,
    }


# ---------------------------------------------------------------------------
# Fused consumer kernel (the round-4 cold-path lever; see the study above):
# XLA performs the row gathers, one pallas kernel then consumes each
# gathered byte exactly once — AND + per-phase first-set-bit entirely in
# VMEM, fed by contiguous ~1MB block DMAs instead of XLA's materialize-and-
# re-read consumer chain.
# ---------------------------------------------------------------------------

_FUSE_TB = 128  # packet rows per grid step (~4.8MB of VMEM blocks, 2x buffered)


def _phase_scan_tile(m, w, phases):
    """(TB, w) i32 match tile -> per-phase first-set global rule index.

    Phases are contiguous rule ranges, so each phase owns a STATIC word
    slice; only its two boundary words need bit masking.  Inside pallas
    there is no XLA-fusion concern (the round-3 negative result on static
    slices was about breaking XLA loop fusion), so the sliced form wins.
    """
    mu = m.astype(jnp.uint32)

    def first_bounded(lo_rule, hi_rule):
        if lo_rule >= hi_rule:
            return jnp.full((m.shape[0],), BIG, jnp.int32)
        lo_w, hi_w = lo_rule // 32, -(-hi_rule // 32)
        sub = mu[:, lo_w:hi_w]
        base = jax.lax.broadcasted_iota(
            jnp.int32, (m.shape[0], hi_w - lo_w), 1
        ) * 32 + lo_w * 32
        k_lo = jnp.clip(lo_rule - base, 0, 32)
        k_hi = jnp.clip(hi_rule - base, 0, 32)
        mask_lo = jnp.where(
            k_lo <= 0,
            jnp.uint32(_ALL1),
            ~((jnp.uint32(1) << jnp.minimum(k_lo, 31).astype(jnp.uint32))
              - jnp.uint32(1)),
        )
        mask_lo = jnp.where(k_lo >= 32, jnp.uint32(0), mask_lo)
        mask_hi = jnp.where(
            k_hi >= 32,
            jnp.uint32(_ALL1),
            (jnp.uint32(1) << jnp.clip(k_hi, 0, 31).astype(jnp.uint32))
            - jnp.uint32(1),
        )
        mw = sub & mask_lo & mask_hi
        lsb = mw & (jnp.uint32(0) - mw)
        tz = jax.lax.population_count(lsb - jnp.uint32(1))
        v = jnp.where(mw == jnp.uint32(0), BIG, base + tz.astype(jnp.int32))
        return jnp.min(v, axis=1)

    n0, nk, _nb = phases
    return (
        first_bounded(0, n0),
        first_bounded(n0, n0 + nk),
        first_bounded(n0 + nk, w * 32),
    )


def _phase_scan_tile_dyn(m, w, phases, w0):
    """_phase_scan_tile with a DYNAMIC global word offset (the rule-axis
    shard seam): this tile's words are global words [w0, w0+w), so phase
    boundaries cannot be static slices — each phase masks the full width
    by its global-rule window instead (the _phase_hits mask discipline,
    inside VMEM).  w0 is a traced scalar from word_idx, NOT a python int."""
    mu = m.astype(jnp.uint32)
    base = (jax.lax.broadcasted_iota(jnp.int32, (m.shape[0], w), 1)
            + w0) * 32

    def first_bounded(lo_rule, hi_rule):
        k_lo = jnp.clip(lo_rule - base, 0, 32)
        k_hi = jnp.clip(hi_rule - base, 0, 32)
        mask_lo = jnp.where(
            k_lo <= 0,
            jnp.uint32(_ALL1),
            ~((jnp.uint32(1) << jnp.minimum(k_lo, 31).astype(jnp.uint32))
              - jnp.uint32(1)),
        )
        mask_lo = jnp.where(k_lo >= 32, jnp.uint32(0), mask_lo)
        mask_hi = jnp.where(
            k_hi >= 32,
            jnp.uint32(_ALL1),
            (jnp.uint32(1) << jnp.clip(k_hi, 0, 31).astype(jnp.uint32))
            - jnp.uint32(1),
        )
        mw = mu & mask_lo & mask_hi
        lsb = mw & (jnp.uint32(0) - mw)
        tz = jax.lax.population_count(lsb - jnp.uint32(1))
        v = jnp.where(mw == jnp.uint32(0), BIG, base + tz.astype(jnp.int32))
        return jnp.min(v, axis=1)

    n0, nk, _nb = phases
    # Baseline phase upper bound: unbounded (padding words carry zero bits).
    return (
        first_bounded(0, n0),
        first_bounded(n0, n0 + nk),
        first_bounded(n0 + nk, 1 << 30),
    )


@lru_cache(maxsize=32)
def _consumer_call(b, w_in, w_out, in_phases, out_phases, interpret,
                   sharded):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tb = _FUSE_TB
    if sharded:
        # Shard-aware variant: two SMEM scalars carry each direction's
        # global word offset (word_idx[0] — data, so the SAME compiled
        # kernel serves every rule shard under shard_map).
        def kernel(ia, ip_, is_, oa, op_, os_, w0i, w0o, o_ref):
            i0, ik, ib = _phase_scan_tile_dyn(
                ia[:] & ip_[:] & is_[:], w_in, in_phases, w0i[0, 0])
            o0, ok_, ob = _phase_scan_tile_dyn(
                oa[:] & op_[:] & os_[:], w_out, out_phases, w0o[0, 0])
            o_ref[:] = jnp.stack(
                [i0, ik, ib, o0, ok_, ob,
                 jnp.zeros_like(i0), jnp.zeros_like(i0)], axis=1,
            )

        extra = [pl.BlockSpec((1, 1), lambda i: (0, 0),
                              memory_space=pltpu.SMEM)] * 2
    else:
        def kernel(ia, ip_, is_, oa, op_, os_, o_ref):
            i0, ik, ib = _phase_scan_tile(
                ia[:] & ip_[:] & is_[:], w_in, in_phases)
            o0, ok_, ob = _phase_scan_tile(
                oa[:] & op_[:] & os_[:], w_out, out_phases)
            o_ref[:] = jnp.stack(
                [i0, ik, ib, o0, ok_, ob,
                 jnp.zeros_like(i0), jnp.zeros_like(i0)], axis=1,
            )

        extra = []

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, 8), jnp.int32),
        grid=(b // tb,),
        in_specs=[pl.BlockSpec((tb, w), lambda i: (i, 0))
                  for w in (w_in, w_in, w_in, w_out, w_out, w_out)] + extra,
        out_specs=pl.BlockSpec((tb, 8), lambda i: (i, 0)),
        interpret=interpret,
        name="classify_consumer",
    )


def _fused_hits(rows_in, rows_out, meta: StaticMeta, w0_in=None, w0_out=None):
    """6 gathered row sets -> (in_hits, out_hits) via the fused consumer.

    Pads the batch to the tile multiple (tiny worlds / odd slow-path
    chunks); interpret mode keeps the kernel testable off-TPU.

    w0_in/w0_out (traced scalars): each direction's global word offset —
    pass word_idx[0] under rule-axis shard_map so the kernel emits GLOBAL
    rule indices that compose with the hit_combine pmin (the shard seam;
    None = single-chip, offsets statically zero).  Widths come from the
    rows themselves (per-shard width != meta.w_* under sharding).
    """
    b = rows_in[0].shape[0]
    w_in = rows_in[0].shape[1]
    w_out = rows_out[0].shape[1]
    pad = (-b) % _FUSE_TB
    if pad:
        rows_in = tuple(jnp.pad(r, ((0, pad), (0, 0))) for r in rows_in)
        rows_out = tuple(jnp.pad(r, ((0, pad), (0, 0))) for r in rows_out)
    sharded = w0_in is not None
    call = _consumer_call(
        b + pad, w_in, w_out, meta.in_phases, meta.out_phases,
        pallas_interpret(meta), sharded,
    )
    if sharded:
        scal = lambda x: jnp.asarray(x, jnp.int32).reshape(1, 1)  # noqa: E731
        hits = call(*rows_in, *rows_out, scal(w0_in), scal(w0_out))[:b]
    else:
        hits = call(*rows_in, *rows_out)[:b]
    return (hits[:, 0], hits[:, 1], hits[:, 2]), (hits[:, 3], hits[:, 4], hits[:, 5])


# ---------------------------------------------------------------------------
# Two-level aggregated-bitmap pruning (round 7; see the study notes above).
# Phase 1 gathers only the aggregate rows (~W/32 words per dimension), ANDs
# them per direction, and proves most lanes no-match outright; phase 2
# gathers the K lowest candidate superblocks (K x AGG_BLOCK words) and
# finishes the first-match scan on them; lanes with more than K candidate
# superblocks redispatch at full width inside a pow2-rung lax.switch (the
# in-jit analog of the PR 9 _spill_retry shape) so verdicts are always
# exact — the aggregate layer can cost a fallback, never flip a verdict.
# ---------------------------------------------------------------------------


@lru_cache(maxsize=32)
def _pruned_consumer_call(b, kw_in, kw_out, in_phases, out_phases, interpret):
    """Pallas consumer for the pruned candidate matrices: per direction,
    3 x (B, K*AGG_BLOCK) candidate words + 1 x (B, K*AGG_BLOCK) i32
    per-element rule-base matrix (global word index * 32 — the base folds
    in the rule-shard word offset, so one compiled kernel serves every
    shard and emits GLOBAL rule indices for the pmin seam)."""
    from jax.experimental import pallas as pl

    tb = _FUSE_TB

    def kernel(ia, ip_, is_, bi, oa, op_, os_, bo, o_ref):
        i0, ik, ib = _phase_first_from_base(
            ia[:] & ip_[:] & is_[:], bi[:], in_phases)
        o0, ok_, ob = _phase_first_from_base(
            oa[:] & op_[:] & os_[:], bo[:], out_phases)
        o_ref[:] = jnp.stack(
            [i0, ik, ib, o0, ok_, ob,
             jnp.zeros_like(i0), jnp.zeros_like(i0)], axis=1,
        )

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, 8), jnp.int32),
        grid=(b // tb,),
        in_specs=[pl.BlockSpec((tb, w), lambda i: (i, 0))
                  for w in (kw_in, kw_in, kw_in, kw_in,
                            kw_out, kw_out, kw_out, kw_out)],
        out_specs=pl.BlockSpec((tb, 8), lambda i: (i, 0)),
        interpret=interpret,
        name="classify_pruned_consumer",
    )


def _classify_pruned(
    drs: DeviceRuleSet,
    src_ip_f: jax.Array,
    dst_ip_f: jax.Array,
    proto: jax.Array,
    dst_port: jax.Array,
    *,
    meta: StaticMeta,
    hit_combine=None,
    fused: bool = False,
    v6=None,
    svc_ref=None,
):
    """Two-level pruned classify (classify_batch's round-7 fast path).

    Exactness: an aggregate bit is set iff its incidence word is nonzero
    (build_agg), so a zero aggregate AND proves a zero full AND (no false
    negatives) and candidates are a superset of match words.  Candidate
    superblocks are scanned LOWEST-FIRST (first-match priority == lowest
    set bit), so any phase hit found within the K lowest candidates is
    the true first match; only a phase that found NOTHING on a lane with
    more than K candidates is unproven — those lanes redispatch at full
    width.  Delta slots patch the aggregate rows conservatively (SET ORs
    the slot's aggregate mask; CLEAR leaves false-positive bits for the
    candidate gather's full-width clear to resolve).

    Returns the classify_batch dict plus per-lane prune observability
    (REPLICATED over the rule axis under hit_combine — skip combines as
    AND, fb as OR, cand as the per-shard MAX, all through the same
    min-combine the hits use):
      prune_skip (B,) bool — both directions proved no-match by the
                             aggregate AND alone (the short-circuit lanes)
      prune_fb   (B,) bool — lane took the full-width fallback redispatch
                             (on ANY rule shard)
      prune_cand (B,) i32  — candidate superblocks, max over directions
                             and rule shards (what the per-shard K budget
                             must cover)
    """
    ing, eg = drs.ingress, drs.egress
    B = src_ip_f.shape[0]
    K = meta.prune_budget
    svc_key = (proto << 16) | dst_port
    if v6 is not None:
        src6w, dst6w, is6 = v6
    else:
        src6w = dst6w = is6 = None

    def dim_idx(tab, x, x6w):
        return _dim_index(tab, x, x6w, is6)

    with device_scope("classify.summary"):
        iv_in_at = dim_idx(ing.at, dst_ip_f, dst6w)
        iv_in_peer = dim_idx(ing.peer, src_ip_f, src6w)
        iv_in_svc = dim_idx(ing.svc, svc_key, None)
        iv_out_at = dim_idx(eg.at, src_ip_f, src6w)
        iv_out_peer = dim_idx(eg.peer, dst_ip_f, dst6w)
        iv_out_svc = dim_idx(eg.svc, svc_key, None)
        iv_ref = None
        if meta.svcref:
            iv_ref = dim_idx(eg.svc, _svcref_key(svc_key, svc_ref), None)

        iso_in = drs.iso_in.val[dim_idx(drs.iso_in, dst_ip_f, dst6w)]
        iso_out = drs.iso_out.val[dim_idx(drs.iso_out, src_ip_f, src6w)]

        d = drs.ip_delta if meta.delta_slots > 0 else None
        wide_d = None if v6 is None else (dst6w, is6)
        wide_s = None if v6 is None else (src6w, is6)
        if d is not None:
            iso_in = _patch_iso(iso_in, dst_ip_f, d, 0, wide_d)
            iso_out = _patch_iso(iso_out, src_ip_f, d, 1, wide_s)

    # Per-direction dimension wiring: (tables, interval rows, probe ip
    # column + wide words per ip dim, delta masks, phases).  Ingress: pod
    # = dst probes appliedTo, peer = src; egress mirrored.
    dir_in = dict(
        dd=ing, iv_at=iv_in_at, iv_peer=iv_in_peer, iv_svc=iv_in_svc,
        iv_ref=None, ip_at=dst_ip_f, ip_peer=src_ip_f, w_at=wide_d,
        w_peer=wide_s, m_at=None if d is None else d.at_in,
        m_peer=None if d is None else d.peer_in, phases=meta.in_phases,
    )
    dir_out = dict(
        dd=eg, iv_at=iv_out_at, iv_peer=iv_out_peer, iv_svc=iv_out_svc,
        iv_ref=iv_ref, ip_at=src_ip_f, ip_peer=dst_ip_f, w_at=wide_s,
        w_peer=wide_d, m_at=None if d is None else d.at_out,
        m_peer=None if d is None else d.peer_out, phases=meta.out_phases,
    )

    def agg_and(dc):
        a = dc["dd"].at.agg[dc["iv_at"]]
        p = dc["dd"].peer.agg[dc["iv_peer"]]
        s = dc["dd"].svc.agg[dc["iv_svc"]]
        if dc["iv_ref"] is not None:
            s = s | dc["dd"].svc.agg[dc["iv_ref"]]
        if d is not None:
            a = _patch_agg(a, dc["ip_at"], d, dc["m_at"], dc["w_at"])
            p = _patch_agg(p, dc["ip_peer"], d, dc["m_peer"], dc["w_peer"])
        g = a & p & s
        return g, (g != jnp.uint32(0)).sum(axis=1, dtype=jnp.int32)

    with device_scope("classify.summary"):
        g_in, nc_in = agg_and(dir_in)
        g_out, nc_out = agg_and(dir_out)
        BIGS = jnp.full((B,), BIG, jnp.int32)
        no_fb = jnp.zeros((B,), bool)

    @device_scope("classify.candidate")
    def cand_mats(dc, g):
        """Phase-2 candidate gather for one direction -> ((ca, cp, cs,
        base) flattened to (B, Ke*AGG_BLOCK), Ke); the caller derives the
        fallback mask from nc vs Ke."""
        dd = dc["dd"]
        S = dd.at.agg.shape[1]
        Ke = min(K, S)
        w = dd.at.inc.shape[1]  # == S * AGG_BLOCK (agg-padded width)
        score = jnp.where(
            g != jnp.uint32(0),
            jax.lax.broadcasted_iota(jnp.int32, (B, S), 1),
            S,
        )
        neg, _idx = jax.lax.top_k(-score, Ke)
        cand = -neg  # (B, Ke) ascending superblock ids, S = fill
        valid = cand < S
        candc = jnp.minimum(cand, S - 1)

        def cwords(tab, iv_):
            inc2 = tab.inc.reshape(-1, AGG_BLOCK)
            return inc2[iv_[:, None] * S + candc]  # (B, Ke, 32) block rows

        ca = cwords(dd.at, dc["iv_at"])
        cp = cwords(dd.peer, dc["iv_peer"])
        cs = cwords(dd.svc, dc["iv_svc"])
        if dc["iv_ref"] is not None:
            cs = cs | cwords(dd.svc, dc["iv_ref"])
        if d is not None:
            widx = jnp.minimum(
                candc[:, :, None] * AGG_BLOCK
                + jnp.arange(AGG_BLOCK, dtype=jnp.int32)[None, None, :],
                w - 1,
            )
            ca = _patch_cand(ca, widx, dc["ip_at"], d, dc["m_at"],
                             dc["w_at"])
            cp = _patch_cand(cp, widx, dc["ip_peer"], d, dc["m_peer"],
                             dc["w_peer"])
        # Fill candidates must contribute nothing: zero ONE dim (the AND
        # kills the rest); done after delta patching on purpose.
        ca = jnp.where(valid[:, :, None], ca, jnp.uint32(0))
        j = jnp.arange(AGG_BLOCK, dtype=jnp.int32)[None, None, :]
        base = (dd.word_idx[0] + candc[:, :, None] * AGG_BLOCK + j) * 32
        flat = lambda x: x.reshape(B, Ke * AGG_BLOCK)  # noqa: E731
        return (flat(ca), flat(cp), flat(cs), flat(base)), Ke

    def full_dir_hits(dc, safe):
        """Full-width fallback walk of the compacted lanes `safe`."""
        dd = dc["dd"]
        ra = dd.at.inc[dc["iv_at"][safe]]
        rp = dd.peer.inc[dc["iv_peer"][safe]]
        rs = dd.svc.inc[dc["iv_svc"][safe]]
        if dc["iv_ref"] is not None:
            rs = rs | dd.svc.inc[dc["iv_ref"][safe]]
        if d is not None:
            def sub(wd):
                return None if wd is None else (wd[0][safe], wd[1][safe])

            ra = _patch_rows(ra, dc["ip_at"][safe], d, dc["m_at"],
                             sub(dc["w_at"]))
            rp = _patch_rows(rp, dc["ip_peer"][safe], d, dc["m_peer"],
                             sub(dc["w_peer"]))
        return _phase_hits(ra & rp & rs, dd.word_idx, dc["phases"])

    with device_scope("classify.scan"):
        def phase2(_):
            mats_in, ke_in = cand_mats(dir_in, g_in)
            mats_out, ke_out = cand_mats(dir_out, g_out)
            if fused:
                pad = (-B) % _FUSE_TB
                if pad:
                    mats_in = tuple(jnp.pad(x, ((0, pad), (0, 0)))
                                    for x in mats_in)
                    mats_out = tuple(jnp.pad(x, ((0, pad), (0, 0)))
                                     for x in mats_out)
                call = _pruned_consumer_call(
                    B + pad, ke_in * AGG_BLOCK, ke_out * AGG_BLOCK,
                    meta.in_phases, meta.out_phases,
                    pallas_interpret(meta),
                )
                hits = call(*mats_in, *mats_out)[:B]
                hits6 = tuple(hits[:, i] for i in range(6))
            else:
                ia, ipr, isv, bi = mats_in
                oa, opr, osv, bo = mats_out
                hits6 = (_phase_first_from_base(ia & ipr & isv, bi,
                                                meta.in_phases)
                         + _phase_first_from_base(oa & opr & osv, bo,
                                                  meta.out_phases))
            fb = (nc_in > ke_in) | (nc_out > ke_out)
            fb_idx = jnp.nonzero(fb, size=B, fill_value=B)[0].astype(
                jnp.int32)
            n_fb = fb.sum(dtype=jnp.int32)
            rungs = []
            r = _FB_MIN
            while r < B:
                rungs.append(r)
                r *= 4
            rungs = sorted(set(min(r, B) for r in rungs + [B]))

            def apply_rung(r):
                def go(h6):
                    idx = fb_idx[:r]
                    safe = jnp.minimum(idx, B - 1)
                    ih = full_dir_hits(dir_in, safe)
                    oh = full_dir_hits(dir_out, safe)
                    tgt = jnp.where(idx < B, idx, B)  # B drops (OOB)
                    return tuple(
                        cur.at[tgt].set(new, mode="drop")
                        for cur, new in zip(h6, ih + oh)
                    )

                return go

            branches = [lambda h6: h6] + [apply_rung(r) for r in rungs]
            sel = jnp.where(
                n_fb == 0,
                0,
                1 + sum(((n_fb > r).astype(jnp.int32) for r in rungs[:-1]),
                        start=jnp.int32(0)),
            )
            hits6 = jax.lax.switch(sel, branches, hits6)
            return hits6 + (fb,)

        def all_dead(_):
            # Aggregate-AND-zero short circuit for the whole batch (the
            # adversarial / default-deny cold shape): no candidate
            # gather, no fallback — straight to the default verdicts.
            return (BIGS,) * 6 + (no_fb,)

        res = jax.lax.cond(
            ((nc_in > 0) | (nc_out > 0)).any(), phase2, all_dead, None
        )
        in_hits, out_hits, fb = res[0:3], res[3:6], res[6]

        skip = ((nc_in == 0) & (nc_out == 0)).astype(jnp.int32)
        cand = jnp.maximum(nc_in, nc_out)
        fbi = fb.astype(jnp.int32)
        if hit_combine is not None:
            in_hits = tuple(hit_combine(h) for h in in_hits)
            out_hits = tuple(hit_combine(h) for h in out_hits)
            # The prune observables are SHARD-LOCAL under rule sharding
            # (each shard prunes its own aggregate slice); emitting them raw
            # would violate the replicated-output contract every other
            # output keeps via the pmin (mesh._shard_map).  Combine
            # them through the SAME min-combine: skip is an AND (min of
            # 0/1 — no shard had a candidate), fallback an OR (1 - min of
            # the complement — ANY shard redispatched), and cand the MAX
            # per-shard count (min of the negation) — the quantity the
            # per-shard K budget must actually cover, which is what the
            # autotuner and the histogram exist to answer.
            skip = hit_combine(skip)
            fbi = 1 - hit_combine(1 - fbi)
            cand = -hit_combine(-cand)

        in_code, in_rule = _resolve(ing.action, in_hits, iso_in)
        out_code, out_rule = _resolve(eg.action, out_hits, iso_out)
        final = jnp.where(out_code != ACT_ALLOW, out_code, in_code)
    return {
        "code": final,
        "egress_code": out_code,
        "egress_rule": out_rule,
        "ingress_code": in_code,
        "ingress_rule": in_rule,
        "prune_skip": skip > 0,
        "prune_fb": fbi > 0,
        "prune_cand": cand,
    }


def flip_ips(a: np.ndarray) -> np.ndarray:
    """Host helper: u32 IP array -> sign-flipped i32 (kernel input layout)."""
    return iputil.flip_u32(a)


# meta is static (plain ints/tuples, hashable); drs is a traced pytree arg so
# the big incidence tensors stay runtime inputs instead of baked-in constants.
_classify_jit = jax.jit(
    classify_batch,
    static_argnames=("meta", "hit_combine", "fused"),
)


def make_classifier(cps: CompiledPolicySet):
    """-> (fn(src_f, dst_f, proto, dport, v6=None) -> verdict dict, DRS)."""
    drs, meta = to_device(cps)

    def fn(src_f, dst_f, proto, dport, v6=None):
        return _classify_jit(drs, src_f, dst_f, proto, dport, meta=meta,
                             v6=v6)

    return fn, drs

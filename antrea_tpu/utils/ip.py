"""IP helpers used across the compiler, oracle and kernels — dual-stack.

Host-side address arithmetic happens in ONE combined keyspace of plain
python ints (the reference is dual-stack throughout its pipeline,
pkg/agent/openflow/pipeline.go IPv6 table / fields.go:184-185 xxreg3):

    IPv4  ->  [0, 2^32)             (the address itself)
    IPv6  ->  [2^32, 2^32 + 2^128)  (V6_OFF + the 128-bit address)

so CIDR sets of EITHER family become half-open [lo, hi) ranges in the same
space and every range consumer — merging, ipBlocks, group interning, the
oracle's membership checks — is family-agnostic for free.  The device side
splits the combined boundary points back into a u32 interval table (v4)
and a 4xu32 lexicographic interval table (v6) at compile time
(ops/match._dim_table_host); packets then resolve to interval INDICES and
everything downstream is family-blind.

Device lanes are i32; v4 values flip the sign bit so signed compares give
unsigned order, v6 values flip the sign bit of EACH of their 4 words
(lexicographic order is preserved word-wise).
"""

from __future__ import annotations

import ipaddress
from typing import Iterable

U32_MAX = 0xFFFFFFFF
# IPv6 offset in the combined keyspace (see module docstring).
V6_OFF = 1 << 32
# Exclusive end of the combined keyspace: v4 space + offset v6 space.
KEYSPACE_END = V6_OFF + (1 << 128)


def ip_to_u32(ip: str) -> int:
    """'10.1.2.3' -> u32."""
    return int(ipaddress.IPv4Address(ip))


def u32_to_ip(v: int) -> str:
    return str(ipaddress.IPv4Address(v & U32_MAX))


def is_v6(ip: str) -> bool:
    return ":" in ip


def ip_to_key(ip: str) -> int:
    """Address of either family -> combined-keyspace int."""
    if is_v6(ip):
        return V6_OFF + int(ipaddress.IPv6Address(ip))
    return int(ipaddress.IPv4Address(ip))


def key_is_v6(key: int) -> bool:
    return key >= V6_OFF


def key_to_ip(key: int) -> str:
    if key >= V6_OFF:
        return str(ipaddress.IPv6Address(key - V6_OFF))
    return str(ipaddress.IPv4Address(key))


def key_to_words(key: int) -> tuple[int, int, int, int]:
    """Combined key -> 4 u32 words, v4 in RFC 4291 v4-mapped form
    (::ffff:a.b.c.d) so a v4 address and its mapped-v6 twin — the same
    host by definition — share one wide representation, and no other v6
    address can alias a v4 one."""
    if key >= V6_OFF:
        v = key - V6_OFF
        return ((v >> 96) & U32_MAX, (v >> 64) & U32_MAX,
                (v >> 32) & U32_MAX, v & U32_MAX)
    return (0, 0, 0xFFFF, key & U32_MAX)


def parse_cidr(cidr: str) -> tuple[int, int]:
    """'10.0.0.0/8' -> (base_u32, prefix_len). Bare IPs become /32.
    IPv4-only callers (service frontends, topology) — policy/range paths
    go through cidr_to_range, which is dual-stack."""
    if "/" not in cidr:
        return ip_to_u32(cidr), 32
    net = ipaddress.IPv4Network(cidr, strict=False)
    return int(net.network_address), net.prefixlen


def cidr_to_range(cidr: str) -> tuple[int, int]:
    """CIDR of either family -> half-open [lo, hi) combined-keyspace range.
    For v4, hi may be 2**32 (whole-v4-space end); for v6, hi may be
    KEYSPACE_END."""
    if is_v6(cidr):
        if "/" not in cidr:
            base, plen = V6_OFF + int(ipaddress.IPv6Address(cidr)), 128
        else:
            net = ipaddress.IPv6Network(cidr, strict=False)
            base, plen = V6_OFF + int(net.network_address), net.prefixlen
        size = 1 << (128 - plen)
        lo = V6_OFF + ((base - V6_OFF) & ~(size - 1))
        return lo, lo + size
    base, plen = parse_cidr(cidr)
    size = 1 << (32 - plen)
    lo = base & ~(size - 1) & U32_MAX
    return lo, lo + size


def cidr_to_range_v4(cidr: str) -> tuple[int, int]:
    """cidr_to_range restricted to IPv4, raising a CLEAR error on v6 input
    — for consumers whose data plane surface is v4-only (topology pod
    CIDRs, ExternalIPPool allocation, capture filters, wireguard allowed
    IPs); the policy/range plane uses the dual-stack cidr_to_range."""
    if is_v6(cidr):
        raise ValueError(f"IPv6 CIDR {cidr!r} is not supported here "
                         "(v4-only surface)")
    return cidr_to_range(cidr)


def merge_ranges(ranges: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sort + merge half-open ranges; drops empty (lo >= hi) ranges.

    The single merge implementation shared by the oracle, the compiler and
    the group machinery — they must agree on range semantics exactly.
    """
    merged: list[tuple[int, int]] = []
    for lo, hi in sorted(ranges):
        if lo >= hi:
            continue
        if merged and lo <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], hi))
        else:
            merged.append((lo, hi))
    return merged


def cidrs_to_ranges(cidrs: Iterable[str]) -> list[tuple[int, int]]:
    """CIDR list -> sorted, merged half-open ranges (set semantics: union)."""
    return merge_ranges(cidr_to_range(c) for c in cidrs)


def ipblock_to_ranges(cidr: str, excepts: Iterable[str] = ()) -> list[tuple[int, int]]:
    """IPBlock {cidr, except[]} -> disjoint ranges (cidr minus excepts).

    Ref semantics: pkg/apis/controlplane/types.go:376 (IPBlock with Except).
    """
    lo, hi = cidr_to_range(cidr)
    holes = cidrs_to_ranges(excepts)
    out: list[tuple[int, int]] = []
    cur = lo
    for hlo, hhi in holes:
        hlo, hhi = max(hlo, lo), min(hhi, hi)
        if hlo >= hhi:
            continue
        if cur < hlo:
            out.append((cur, hlo))
        cur = max(cur, hhi)
    if cur < hi:
        out.append((cur, hi))
    return out


def ip_in_ranges(ip_u32: int, ranges: Iterable[tuple[int, int]]) -> bool:
    return any(lo <= ip_u32 < hi for lo, hi in ranges)


def flip_u32(a):
    """u32 array -> sign-flipped i32 preserving unsigned order under signed
    compares.  THE encoding contract between compiler and kernels: every
    device-side IP/bound is stored flipped; keep exactly one implementation."""
    import numpy as np

    return (np.asarray(a, dtype=np.uint32) ^ np.uint32(0x80000000)).view(np.int32)


def unflip_u32(v) -> int:
    """Scalar inverse of flip_u32 (plain-int space, numpy-2 safe): the
    stored sign-flipped i32 value back to its u32 address."""
    return (int(v) ^ 0x80000000) & 0xFFFFFFFF


def unflip_u32_array(col):
    """Vectorized inverse of flip_u32: a column of stored sign-flipped
    i32 lanes back to u32 addresses — the one implementation both
    engines' StepResult builders share (the encoding contract lives
    here, next to flip_u32)."""
    import numpy as np

    return (np.asarray(col).astype(np.int32)
            ^ np.int32(-(2 ** 31))).astype(np.uint32)


def words_to_keys(words, keep=True) -> list:
    """(B, 4) u32 word rows -> per-lane combined keys (Python ints: a v6
    key exceeds any numpy lane), 0 on the lanes a `keep` mask leaves out —
    the column inverse of key_to_words.  A v4-mapped row IS its word 3;
    any other row is V6_OFF + its 128-bit value, put together from two
    64-bit halves so that only the lanes carrying a real v6 address cost a
    Python operation, and those one shift and one add."""
    import numpy as np

    w = np.asarray(words).astype(np.uint64)
    keep = np.broadcast_to(np.asarray(keep, bool), w.shape[:1])
    mapped = (w[:, 0] == 0) & (w[:, 1] == 0) & (w[:, 2] == 0xFFFF)
    keys = np.where(keep, w[:, 3], 0).tolist()
    wide = np.nonzero(keep & ~mapped)[0]
    hi = ((w[wide, 0] << np.uint64(32)) | w[wide, 1]).tolist()
    lo = ((w[wide, 2] << np.uint64(32)) | w[wide, 3]).tolist()
    for i, h, l in zip(wide.tolist(), hi, lo):
        keys[i] = V6_OFF + ((h << 64) | l)
    return keys


def key_to_flipped_words(key: int) -> tuple[int, int, int, int]:
    """key_to_words with each word sign-flipped — the exact i32 lane values
    the device stores, for host/oracle twins that must hash or compare the
    same bits (returned as SIGNED i32-range ints)."""
    return tuple(
        ((w ^ 0x80000000) & U32_MAX) - (1 << 32)
        if (w ^ 0x80000000) & 0x80000000 else (w ^ 0x80000000)
        for w in key_to_words(key)
    )


def canon_key(key: int) -> int:
    """Collapse a v4-mapped v6 address (::ffff:a.b.c.d) to its v4 int —
    the combined-keyspace equivalence the wide word form induces (they are
    the same host, RFC 4291); all other keys unchanged."""
    if key >= V6_OFF:
        v = key - V6_OFF
        if (v >> 32) == 0xFFFF:
            return v & U32_MAX
    return key

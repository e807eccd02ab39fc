"""`policy_flows` for a dual-stack node: the same closed loop, the same six
classes in the same shares at the same ranks, with a stated share of the
lanes on v6 (`v6_lane_share` of ALL lanes).

What is `policy_flows`' stays its: the proposals, Service legs and classes of
the v4 conversations (`_classes`, handed the world and the reference as a v4
reader sees them), the class of every rank (`_class_of_rank`), the arrivals
(`_refill`), the sampled lanes, the warm-up.  What is added:

  v6 conversations  proposed the same two ways: by allow rules (a member's v6
             address of the rule's applied-to group; a member's v6 address of
             its peer group, or an address of its v6 ipBlock; its port) and
             uniformly over the pods' v6 addresses and the externals of
             2001:db8::/32; sorted by the reference's verdict on the 128-bit
             addresses into {pod-to-pod, external} x {allowed, denied}, and
             the allowed ones by the number of directions a rule decided.
             Both ends of a conversation have one family.  Service
             conversations are v4: the deployment's Services are SingleStack.
  family     the Service classes carry none of the v6 lanes, so in every
             other class `v6_lane_share / (1 - svc_fraction)` of the weight
             is v6 (4/7 at 0.40 and 0.3).  Within a class the family of a
             rank is assigned as `_class_of_rank` assigns the classes: each
             rank to the family furthest under its share of the class's
             weight so far.  So every seed offers the same work, the Zipf
             head is not all of one family, and `np100k.churn` is the control
             rank for rank.  The arrivals' template table holds each class's
             two families in the same shares.
  columns    `src_ip6`, `dst_ip6` ((B, 4) u32, big-endian words, zero on a v4
             lane) and `is6` ((B,) i32) beside the v4 columns (zero on a v6
             lane), as `PacketBatch` carries a mixed batch.

A template is a row of thirteen u32, laid out as the columns are cut from it:
`is6`, `src_ip`, `dst_ip` (zero on a v6 template), the source's four words,
the destination's four (zero on a v4 one), protocol, destination port.  The
arrivals' pool is one such array a refill and its columns are views of it.
"""

from __future__ import annotations

import copy
import dataclasses
import ipaddress
import math
import os

import numpy as np

from manifest import load_module

_flows = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "policy_flows.py"))
class_shares = _flows.class_shares
ALLOW = _flows.ALLOW
_EXTERNAL6_HI = 0x20010DB8 << 32  # 2001:db8::/32, high limb
_M32 = np.uint64(0xFFFFFFFF)


def _words(h, lo):
    """(high, low) u64 limbs -> (n, 4) big-endian u32 words, as int64."""
    s = np.uint64(32)
    return np.stack([h >> s, h & _M32, lo >> s, lo & _M32],
                    axis=1).astype(np.int64)


def _rand64(rng, n):
    return rng.integers(0, 1 << 64, size=n, dtype=np.uint64)


def _from_rules6(rng, ref, members6, n):
    """n v6 flows proposed by allow rules -> (n, 10): the two ends' words,
    protocol, destination port."""
    out = []
    for d in ("In", "Out"):
        # A v4 ipBlock proposes no v6 flow: its v6 range is empty.
        allow = [(ph, np.nonzero((ph.action == ALLOW)
                                 & (ph.is_group | ph.block6))[0])
                 for ph in ref.phases[d] if ph.n]
        if not sum(len(rows) for _, rows in allow):
            continue

        def col(name, first_service=False):
            return np.concatenate([
                (getattr(ph, name)[0] if first_service and len(ph.s_proto)
                 else np.zeros(ph.n, np.int64) if first_service
                 else getattr(ph, name))[rows] for ph, rows in allow])

        m = n // 2
        pick = rng.integers(0, len(col("atg")), size=m)
        width = members6.shape[1]
        pod = members6[col("atg")[pick], rng.integers(0, width, size=m)]
        in_group = members6[col("peer_g")[pick],
                            rng.integers(0, width, size=m)]
        # An address of the block: its base with random host bits.
        lo_h, lo_l = col("lo6h")[pick], col("lo6l")[pick]
        in_block = np.stack([
            lo_h | (_rand64(rng, m) & (col("hi6h")[pick] ^ lo_h)),
            lo_l | (_rand64(rng, m) & (col("hi6l")[pick] ^ lo_l))], axis=1)
        peer = np.where(col("is_group")[pick][:, None], in_group, in_block)
        src, dst = (peer, pod) if d == "In" else (pod, peer)
        any_port = col("any_svc")[pick]
        c_proto, c_port = _flows._ports(rng, m)
        proto = np.where(any_port, c_proto, col("s_proto", True)[pick])
        dport = np.where(any_port, c_port, _flows._between(
            rng, col("s_lo", True)[pick], col("s_hi", True)[pick]))
        out.append(np.concatenate([
            _words(src[:, 0], src[:, 1]), _words(dst[:, 0], dst[:, 1]),
            proto[:, None], dport[:, None]], axis=1))
    return np.concatenate(out) if out else np.zeros((0, 10), np.int64)


def _uniform6(rng, pods6, n, pod_to_pod):
    """n v6 flows over pods and externals with no regard to policy."""
    src = pods6[rng.integers(0, len(pods6), size=n)]
    dst = pods6[rng.integers(0, len(pods6), size=n)]
    ext = np.stack([np.uint64(_EXTERNAL6_HI) | (_rand64(rng, n) & _M32),
                    _rand64(rng, n)], axis=1)
    external = rng.random(n) > pod_to_pod
    ext_src = external & (rng.random(n) < 0.5)
    src = np.where(ext_src[:, None], ext, src)
    dst = np.where((external & ~ext_src)[:, None], ext, dst)
    proto, port = _flows._ports(rng, n)
    dport = np.where(rng.random(n) < 0.7, port,
                     rng.integers(1, 65536, size=n))
    return np.concatenate([
        _words(src[:, 0], src[:, 1]), _words(dst[:, 0], dst[:, 1]),
        proto[:, None], dport[:, None]], axis=1)


def _classes6(rng, world, ref, p):
    """-> {(kind, allowed): ((n, 10) distinct v6 templates, in how many
    directions a rule decided each)} for kind in pod, ext."""
    at6 = {str(ipaddress.IPv6Address(v)): i
           for i, v in enumerate(sorted(set(world.pods6)))}
    members6 = np.array([[ref.pods6[at6[ip]] for ip, _, _ in g if ":" in ip]
                         for g in world.groups], np.uint64)
    n = int(p["proposals"])
    plain = np.unique(np.concatenate([
        _from_rules6(rng, ref, members6, n - n // 4),
        _uniform6(rng, ref.pods6, n // 4, p["pod_to_pod_fraction"])]), axis=0)
    code, named = ref.classify_named(plain[:, 0:4], plain[:, 4:8],
                                     plain[:, 8], plain[:, 9])
    both = ((ref._pod_index(ref._address(plain[:, 0:4])) < ref.n_addr)
            & (ref._pod_index(ref._address(plain[:, 4:8])) < ref.n_addr))
    return {(kind, allowed): (plain[mask & ((code == ALLOW) == allowed)],
                              named[mask & ((code == ALLOW) == allowed)])
            for allowed in (True, False)
            for kind, mask in (("pod", both), ("ext", ~both))}


def _as_v4_reads_it(world, ref):
    """The world without the groups' v6 members and the reference without
    the rules whose ipBlock is v6 (they match no v4 packet, so every v4
    verdict and rule id stays): what `policy_flows._classes` proposes v4
    conversations from."""
    ref4 = copy.copy(ref)
    ref4.phases = {d: tuple(ph.sub(np.nonzero(ph.is_group | ph.block4)[0])
                            for ph in phases)
                   for d, phases in ref.phases.items()}
    world4 = dataclasses.replace(world, groups=[
        tuple(m for m in g if ":" not in m[0]) for g in world.groups])
    return world4, ref4


def _row(fam, flows):
    """Templates of one family ((n, 4) v4 | (n, 10) v6) -> the
    thirteen-column rows."""
    out = np.zeros((len(flows), 13), np.int64)
    if fam:
        out[:, 0], out[:, 3:] = 1, flows
    else:
        out[:, 1:3], out[:, 11:] = flows[:, :2], flows[:, 2:]
    return out


class Traffic(_flows.Traffic):
    def __init__(self, params: dict, world, seed: int, reference):
        p = self.p = params
        self.seed = seed
        self.batch = int(p["batch"])
        self.fresh_lanes = int(p.get("fresh_lanes", 0))
        # Of every class but the Service ones, this share of the weight.
        v6 = float(p["v6_lane_share"]) / (1.0 - p["svc_fraction"])
        if not 0.0 <= v6 <= 1.0:
            raise ValueError("v6_lane_share passes what is not Service")
        rng = np.random.default_rng([seed, 0])
        found4 = _flows._classes(rng, *_as_v4_reads_it(world, reference), p)
        found6 = _classes6(rng, world, reference, p)
        # The classes, their shares and their split by named directions are
        # policy_flows' own, taken from the v4 proposals.
        shares, pools = {}, {}  # (kind, sign) -> share; (kind, sign, fam)
        for (kind, allowed), share in class_shares(p).items():
            rows, named = found4[kind, allowed]
            rows6, named6 = found6.get((kind, allowed),
                                       (np.zeros((0, 10), np.int64), None))
            if not allowed:
                if len(rows):
                    shares[kind, "-"] = share
                    pools[kind, "-", 0] = _row(0, rows)
                    pools[kind, "-", 1] = _row(1, rows6)
                continue
            if not len(rows):
                raise ValueError(f"no proposal gave an allowed {kind} flow")
            if kind != "svc" and v6 and not len(rows6):
                raise ValueError(f"no proposal gave an allowed v6 {kind} "
                                 f"flow")
            eighths = {k: round(8 * float(np.mean(named == k)))
                       for k in (0, 1, 2)}
            for k, e in eighths.items():
                if e:
                    shares[kind, f"+{k}"] = share * e / sum(eighths.values())
                    pools[kind, f"+{k}", 0] = _row(0, rows[named == k])
                    # v6 conversations of the same cost where the proposals
                    # gave any, else the kind's allowed ones.
                    same = rows6[named6 == k] if len(rows6) else rows6
                    pools[kind, f"+{k}", 1] = _row(
                        1, same if len(same) else rows6)
        fam_share = {c: (v6 if c[0] != "svc" and len(pools[c + (1,)])
                         else 0.0) for c in shares}
        table = {}
        for c, s in shares.items():
            for fam, f in ((0, 1.0 - fam_share[c]), (1, fam_share[c])):
                pool = pools[c + (fam,)]
                if f:
                    table[c + (fam,)] = pool[rng.permutation(len(pool))[
                        :max(1, round(p["templates"] * s * f))]]
        self.summary = "templates " + ", ".join(
            f"{k}{a}v{6 if fam else 4} {len(rows)}/{len(pools[k, a, fam])}"
            for (k, a, fam), rows in table.items())

        # -- the hot flows, by rank ---------------------------------------
        n_hot = int(p["universe_flows"])
        weights = np.arange(1, n_hot + 1, dtype=np.float64) ** -p["zipf_s"]
        self.rank_class = _flows._class_of_rank(weights, shares)
        self.rank_v6 = np.zeros(n_hot, bool)
        by_class = {}
        for i, c in enumerate(self.rank_class):
            by_class.setdefault(c, []).append(i)
        hot = np.zeros((n_hot, 13), np.uint32)
        for c, ranks in by_class.items():
            ranks = np.array(ranks, np.int64)
            f = fam_share[c]
            fams = (_flows._class_of_rank(weights[ranks],
                                          {0: 1.0 - f, 1: f})
                    if 0.0 < f < 1.0 else [int(f)] * len(ranks))
            self.rank_v6[ranks] = np.array(fams, bool)
            for fam in (0, 1):
                at = ranks[np.array(fams) == fam]
                rows = table.get(c + (fam,))
                if len(at):
                    hot[at] = rows[rng.integers(0, len(rows), size=len(at))]
        self.rank_weight = weights / weights.sum()
        hot_sport = rng.integers(1024, _flows._FRESH_PORT0, size=n_hot)
        cdf = np.cumsum(weights) / weights.sum()
        self.ring = []
        for _ in range(int(p["ring"])):
            idx = np.minimum(np.searchsorted(cdf, rng.random(self.batch)),
                             n_hot - 1)
            self.ring.append(self._columns(hot[idx], hot_sport[idx]))

        # -- the arrivals, the sampled lanes: policy_flows' ------------------
        self._table = np.concatenate(list(table.values())).astype(np.uint32)
        if len(np.unique(self._table, axis=0)) != len(self._table):
            raise ValueError("a template sits in two classes: an arrival "
                             "would be sent twice")
        self._pool = None
        self._pool_at = 0
        self._refills = 0
        self._stride = self.batch // max(1, self.fresh_lanes)
        if self.fresh_lanes:
            if self._stride < 2 or self.batch % self.fresh_lanes:
                raise ValueError("fresh_lanes has to divide batch, and be "
                                 "at most half of it")
            if math.gcd(len(self._table), _flows._SCRAMBLE) != 1:
                raise ValueError("the template table's size shares a factor "
                                 "with the scramble")
            self._refill()
        self.step_no = 0
        k = int(p["sample_lanes_per_step"])
        k_fresh = k // 2 if self.fresh_lanes else 0
        srng = np.random.default_rng([seed, 2])
        other = srng.integers(0, self.batch - self.fresh_lanes,
                              size=(4096, k - k_fresh))
        if self.fresh_lanes:
            other += other // (self._stride - 1) + 1
        self._sample = np.concatenate([
            srng.integers(0, max(1, self.fresh_lanes), size=(4096, k_fresh))
            * self._stride, other], axis=1)

    @staticmethod
    def _columns(flows, sport) -> dict:
        return {"src_ip": flows[:, 1], "dst_ip": flows[:, 2],
                "proto": flows[:, 11].astype(np.int32),
                "src_port": sport.astype(np.int32),
                "dst_port": flows[:, 12].astype(np.int32),
                "src_ip6": flows[:, 3:7], "dst_ip6": flows[:, 7:11],
                "is6": flows[:, 0].astype(np.int32)}

    def next_batch(self):
        """-> (columns, sampled lanes, True where a sampled lane is fresh)."""
        hot = self.ring[self.step_no % len(self.ring)]
        lanes = self._sample[self.step_no % len(self._sample)]
        self.step_no += 1
        n = self.fresh_lanes
        if not n:
            return hot, lanes, np.zeros(len(lanes), bool)
        if self._pool_at + n > len(self._pool["proto"]):
            self._refill()
        a = self._pool_at
        self._pool_at += n
        cols = {c: v.copy() for c, v in hot.items()}
        for c, v in cols.items():
            v[::self._stride] = self._pool[c][a:a + n]
        return cols, lanes, lanes % self._stride == 0

"""The one general traffic generator: a closed loop of fixed-size batches of
flows with a STATED mix of verdicts, drawn from the seed.

A node's steady state is allowed traffic: connections the installed policy
lets through, most of them established, beside a small share of denied
probes.  Flows drawn without regard to the policy are not that (under the
np100k deployment's 12.5k isolating K8s NetworkPolicies 99 % of them are
denials), so the generator reads the policy:

  templates  (src, dst, proto, dst port) conversations.  Allow rules PROPOSE
             them (a member of the rule's applied-to group, a member of its
             peer group or an address of its ipBlock, its port; a rule for
             any port takes the port of a Service endpoint at the
             destination, or a common one), uniform draws over pods and
             external addresses propose more, and a proposal that ends on a
             Service's endpoint also proposes the flow to that Service's
             ClusterIP.  The plain reference (`reference.py`, the whole
             policy, post-DNAT, every endpoint of the Service) sorts them
             into six classes: {pod-to-pod, Service, external} x {allowed,
             denied}.  A Service flow counts only where ALL its endpoints
             agree, since which endpoint serves it is the load balancer's
             choice.  The mix states each class's share of the lanes
             (`svc_fraction`, `pod_to_pod_fraction`, `denied_share`).  An
             allowed class is split once more by the number of directions
             (0, 1, 2) in which a rule decided the flow and not a default:
             a lane that names a rule costs a datapath's host more than one
             that names none.  That split is the world's own (all 2 under
             np100k's isolation, mixed under acnp10k's default allow), taken
             from the proposals in eighths so that every seed gets the same
             (a proportion close to an odd sixteenth could fall either way on
             a rare seed; acnp10k's Service flows, 79.4-80.1 % with none,
             sit three standard deviations from 13/16).
  hot flows  `universe_flows` flows, each a template with a source port of
             its own in [1024, 32768): the connections open on the node.
             Flow k carries a share ~ k**-zipf_s of the hot lanes.  Which
             class sits at which rank is fixed by the shares alone (each
             rank goes to the class furthest under its share), so every
             seed offers the same mix of work: what the seed draws is the
             templates, which template a rank carries, the order of the
             lanes, the arrivals and the lanes the comparison samples.
             A mix that names a `flow_seed` draws the templates, the
             template of each rank and the hot source ports from it, and
             only the ring's lanes and the sampled lanes from the run's
             seed: the hot set is then the mix's, as the rule world is the
             configuration's.  A sharded engine needs that: which replica the
             Zipf head's elephants hash to decides how many lanes overflow it,
             so a hot set per seed is a different load per seed (PERF.md s2).
  fresh      `fresh_lanes` lanes of every batch (0 = none; every
             batch/fresh_lanes-th lane, so arrivals lie among the other
             packets and not in one half) are connections
             never seen before in the run: arrival number i is template
             (i * odd) mod T with source port 32768 + i div T, so no fresh
             flow equals a hot flow or another fresh flow, and the
             comparison may hold `est` of a fresh lane to 0.  The classes
             have their stated shares among the T templates.

A traffic mix is a JSON file of these parameters; this module is found by
the file's `generator` key.
"""

from __future__ import annotations

import math

import numpy as np

from reference import ALLOW
from world import ip_u32

PROTO_TCP, PROTO_UDP = 6, 17
_COLS = ("src_ip", "dst_ip", "proto", "src_port", "dst_port")
_COMMON_PORTS = np.array([80, 443, 8080, 53, 5432], np.int64)
_FRESH_PORT0 = 32768
_SCRAMBLE = 0x9E3779B1  # prime: coprime to any table size below it
_KINDS = ("pod", "svc", "ext")


def _between(rng, lo, hi):
    """One whole number in [lo, hi] per row; lo where the range is empty."""
    return lo + (rng.random(len(lo)) * np.maximum(hi - lo + 1, 0)).astype(
        np.int64)


def _ports(rng, n):
    proto = np.where(rng.random(n) < 0.85, PROTO_TCP, PROTO_UDP)
    return proto.astype(np.int64), rng.choice(_COMMON_PORTS, size=n)


class _Services:
    """The world's Services as columns, and their endpoints flattened."""

    def __init__(self, services):
        if not any(s.endpoints for s in services):
            raise ValueError("the generator needs a world with Services")
        self.ip = np.array([ip_u32(s.cluster_ip) for s in services], np.int64)
        self.port = np.array([s.port for s in services], np.int64)
        self.proto = np.array([s.proto for s in services], np.int64)
        self.n_ep = np.array([len(s.endpoints) for s in services], np.int64)
        self.start = np.cumsum(self.n_ep) - self.n_ep
        flat = [(ip_u32(ip), port, si) for si, s in enumerate(services)
                for ip, port in s.endpoints]
        self.ep_ip, self.ep_port, self.ep_svc = (
            np.array(c, np.int64) for c in zip(*flat))
        # One entry per endpoint address and per (address, proto, port), that
        # of the Service with the fewest endpoints: the likeliest to agree.
        few = np.lexsort((self.n_ep[self.ep_svc], self.ep_ip))
        self.at_ip, first = np.unique(self.ep_ip[few], return_index=True)
        self.at_entry = few[first]
        key = self._key(self.ep_ip, self.proto[self.ep_svc], self.ep_port)
        few = np.lexsort((self.n_ep[self.ep_svc], key))
        self.keys, first = np.unique(key[few], return_index=True)
        self.key_svc = self.ep_svc[few[first]]

    @staticmethod
    def _key(ip, proto, port):
        return (ip << 24) | (proto << 16) | port

    def endpoint_at(self, ip):
        """-> (proto, port) of an endpoint at each address, -1 where none."""
        pos = np.minimum(np.searchsorted(self.at_ip, ip), len(self.at_ip) - 1)
        e = self.at_entry[pos]
        hit = self.at_ip[pos] == ip
        return (np.where(hit, self.proto[self.ep_svc[e]], -1),
                np.where(hit, self.ep_port[e], -1))

    def behind(self, ip, proto, port):
        """-> a Service that has (ip, port) as an endpoint, or -1."""
        key = self._key(ip, proto, port)
        pos = np.minimum(np.searchsorted(self.keys, key), len(self.keys) - 1)
        return np.where(self.keys[pos] == key, self.key_svc[pos], -1)


def _from_rules(rng, ref, members, svc, n):
    """n flows proposed by allow rules, as (src, dst, proto, dport)."""
    out = []
    for d in ("In", "Out"):
        allow = [(ph, np.nonzero(ph.action == ALLOW)[0])
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
        width = members.shape[1]
        pod = members[col("atg")[pick], rng.integers(0, width, size=m)]
        peer = np.where(
            col("is_group")[pick],
            members[col("peer_g")[pick], rng.integers(0, width, size=m)],
            _between(rng, col("lo")[pick], col("hi")[pick]))
        src, dst = (peer, pod) if d == "In" else (pod, peer)
        any_port = col("any_svc")[pick]
        ep_proto, ep_port = svc.endpoint_at(dst)
        to_ep = any_port & (ep_port >= 0) & (rng.random(m) < 0.5)
        c_proto, c_port = _ports(rng, m)
        proto = np.where(to_ep, ep_proto, np.where(
            any_port, c_proto, col("s_proto", True)[pick]))
        dport = np.where(to_ep, ep_port, np.where(
            any_port, c_port, _between(rng, col("s_lo", True)[pick],
                                       col("s_hi", True)[pick])))
        out.append(np.stack([src, dst, proto, dport], axis=1))
    return np.concatenate(out) if out else np.zeros((0, 4), np.int64)


def _uniform(rng, pods, n, pod_to_pod):
    """n flows over pods and external addresses with no regard to policy."""
    src, dst = rng.choice(pods, size=n), rng.choice(pods, size=n)
    ext = rng.integers(0, 1 << 32, size=n)
    external = rng.random(n) > pod_to_pod
    ext_src = external & (rng.random(n) < 0.5)
    src = np.where(ext_src, ext, src)
    dst = np.where(external & ~ext_src, ext, dst)
    proto, port = _ports(rng, n)
    dport = np.where(rng.random(n) < 0.7, port,
                     rng.integers(1, 65536, size=n))
    return np.stack([src, dst, proto, dport], axis=1)


def _classes(rng, world, ref, p):
    """-> {(kind, allowed): ((n, 4) distinct templates, in how many
    directions a rule decided each)}."""
    svc = _Services(world.services)
    members = np.array([[ip_u32(ip) for ip, _, _ in g] for g in world.groups],
                       np.int64)
    n = int(p["proposals"])
    plain = np.concatenate([
        _from_rules(rng, ref, members, svc, n - n // 4),
        _uniform(rng, ref.pods, n // 4, p["pod_to_pod_fraction"])])
    front, _ = ref.resolve(plain[:, 1], plain[:, 2], plain[:, 3])
    plain = np.unique(plain[(front < 0) & ((plain[:, 1] >> 28) != 0xE)],
                      axis=0)
    # Service flows: (src, Service) behind a proposal's destination, and
    # uniform ones; each is held against every endpoint of its Service.
    behind = svc.behind(plain[:, 1], plain[:, 2], plain[:, 3])
    k = n // 16
    pairs = np.unique(np.concatenate([
        np.stack([plain[:, 0], behind], axis=1)[behind >= 0],
        np.stack([rng.choice(plain[:, 0], size=k),
                  rng.integers(0, len(svc.ip), size=k)], axis=1)]), axis=0)
    n_ep = svc.n_ep[pairs[:, 1]]
    of_pair = np.repeat(np.arange(len(pairs)), n_ep)
    e = (svc.start[pairs[of_pair, 1]] + np.arange(len(of_pair))
         - np.repeat(np.cumsum(n_ep) - n_ep, n_ep))
    legs = np.stack([pairs[of_pair, 0], svc.ep_ip[e],
                     svc.proto[pairs[of_pair, 1]], svc.ep_port[e]], axis=1)
    lanes, inv = np.unique(np.concatenate([plain, legs]), axis=0,
                           return_inverse=True)
    inv = inv.reshape(-1)
    code, named = ref.classify_named(lanes[:, 0], lanes[:, 1], lanes[:, 2],
                                     lanes[:, 3])
    ok, named = (code == ALLOW)[inv], named[inv]
    ok_plain, ok_legs = ok[:len(plain)], ok[len(plain):]
    named_plain, named_legs = named[:len(plain)], named[len(plain):]
    n_pods = len(ref.pods)
    both = ((ref._pod_index(plain[:, 0]) < n_pods)
            & (ref._pod_index(plain[:, 1]) < n_pods))
    out = {}
    for allowed in (True, False):
        for kind, mask in (("pod", both), ("ext", ~both)):
            mask = mask & (ok_plain == allowed)
            out[kind, allowed] = plain[mask], named_plain[mask]
    n_ok = np.bincount(of_pair, weights=ok_legs, minlength=len(pairs))
    # A Service flow's legs have to agree on that count as on the verdict.
    least, most = (np.full(len(pairs), x) for x in (9, -1))
    np.minimum.at(least, of_pair, named_legs)
    np.maximum.at(most, of_pair, named_legs)
    v = pairs[:, 1]
    as_flow = np.stack([pairs[:, 0], svc.ip[v], svc.proto[v], svc.port[v]],
                       axis=1)
    yes = (n_ep > 0) & (n_ok == n_ep) & (least == most)
    no = n_ok == 0  # no endpoint: rejected
    out["svc", True] = as_flow[yes], least[yes]
    out["svc", False] = as_flow[no], np.zeros(int(no.sum()), np.int64)
    return out


def class_shares(p: dict) -> dict:
    """The stated share of the lanes of each (kind, allowed) class."""
    s, pp, q = p["svc_fraction"], p["pod_to_pod_fraction"], p["denied_share"]
    kind = {"pod": (1 - s) * pp, "svc": s, "ext": (1 - s) * (1 - pp)}
    return {(k, a): kind[k] * ((1 - q) if a else q)
            for k in _KINDS for a in (True, False) if kind[k]}


def _class_of_rank(weights, shares: dict) -> list:
    """Each rank to the class furthest under its share of the weight so far:
    the same for every seed."""
    names = list(shares)
    have = dict.fromkeys(names, 0.0)
    total, out = 0.0, []
    for w in weights.tolist():
        total += w
        c = max(names, key=lambda c: shares[c] * total - have[c])
        have[c] += w
        out.append(c)
    return out


class Traffic:
    def __init__(self, params: dict, world, seed: int, reference):
        p = self.p = params
        self.seed = seed
        self.batch = int(p["batch"])
        self.fresh_lanes = int(p.get("fresh_lanes", 0))
        flow_seed = p.get("flow_seed")
        rng = np.random.default_rng(
            [seed if flow_seed is None else int(flow_seed), 0])
        shares, pools = {}, {}
        found = _classes(rng, world, reference, p)
        for (kind, allowed), share in class_shares(p).items():
            rows, named = found[kind, allowed]
            if not allowed:
                # A class of denied probes that no proposal fell into (the
                # external ones are few under a policy that allows by
                # default) goes without: a fraction of a per cent of the
                # lanes on that seed, never a run that cannot start.
                if len(rows):
                    shares[kind, "-"], pools[kind, "-"] = share, rows
                continue
            if not len(rows):
                raise ValueError(f"no proposal gave an allowed {kind} flow")
            eighths = {k: round(8 * float(np.mean(named == k)))
                       for k in (0, 1, 2)}
            for k, e in eighths.items():
                if e:
                    shares[kind, f"+{k}"] = share * e / sum(eighths.values())
                    pools[kind, f"+{k}"] = rows[named == k]
        # As many templates of each class as its share asks for, or all the
        # class has: a short class is short alone.
        table = {c: pools[c][rng.permutation(len(pools[c]))[
            :max(1, round(p["templates"] * s))]] for c, s in shares.items()}
        self.summary = "templates " + ", ".join(
            f"{k}{a} {len(table[k, a])}/{len(pools[k, a])}"
            for (k, a) in table)

        # -- the hot flows, by rank ---------------------------------------
        n_hot = int(p["universe_flows"])
        weights = np.arange(1, n_hot + 1, dtype=np.float64) ** -p["zipf_s"]
        cls = _class_of_rank(weights, shares)
        hot = np.zeros((n_hot, 4), np.int64)
        for c, rows in table.items():
            ranks = np.array([i for i, x in enumerate(cls) if x == c],
                             np.int64)
            hot[ranks] = rows[rng.integers(0, len(rows), size=len(ranks))]
        hot_sport = rng.integers(1024, _FRESH_PORT0, size=n_hot)
        cdf = np.cumsum(weights) / weights.sum()
        if flow_seed is not None:  # the hot set was the mix's; the lanes are
            rng = np.random.default_rng([seed, 1])  # the run's
        self.ring = []
        for _ in range(int(p["ring"])):
            idx = np.minimum(np.searchsorted(cdf, rng.random(self.batch)),
                             n_hot - 1)
            self.ring.append(self._columns(hot[idx], hot_sport[idx]))

        # -- the arrivals ---------------------------------------------------
        self._table = np.concatenate(list(table.values()))
        self._pool = None
        self._pool_at = 0
        self._refills = 0
        self._stride = self.batch // max(1, self.fresh_lanes)
        if self.fresh_lanes:
            if self._stride < 2 or self.batch % self.fresh_lanes:
                raise ValueError("fresh_lanes has to divide batch, and be "
                                 "at most half of it")
            if math.gcd(len(self._table), _SCRAMBLE) != 1:
                raise ValueError("the template table's size shares a factor "
                                 "with the scramble")
            self._refill()
        self.step_no = 0
        # Which lanes of each step the comparison will look at: drawn here,
        # from the seed, before any answer exists.  Half of them fresh.
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
        return {"src_ip": flows[:, 0].astype(np.uint32),
                "dst_ip": flows[:, 1].astype(np.uint32),
                "proto": flows[:, 2].astype(np.int32),
                "src_port": sport.astype(np.int32),
                "dst_port": flows[:, 3].astype(np.int32)}

    def _refill(self) -> None:
        n, size = int(self.p["fresh_pool_flows"]), len(self._table)
        i = np.arange(self._refills * n, (self._refills + 1) * n,
                      dtype=np.int64)
        sport = _FRESH_PORT0 + i // size
        if sport[-1] >= 65536:
            raise ValueError("fresh flows outran their source ports")
        self._pool = self._columns(self._table[(i * _SCRAMBLE) % size], sport)
        self._pool_at = 0
        self._refills += 1

    @property
    def fresh_at(self):
        """The lanes of a batch that carry fresh flows."""
        return np.arange(self.fresh_lanes) * self._stride

    @property
    def refills(self) -> int:
        """Fresh pools built so far; above 1, the window paid for one."""
        return self._refills

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
        cols = {c: hot[c].copy() for c in _COLS}
        for c in _COLS:
            cols[c][::self._stride] = self._pool[c][a:a + n]
        return cols, lanes, lanes % self._stride == 0

    def warmup(self):
        """One pass over the hot ring, then a few fresh steps: every shape
        and both paths are compiled and the hot flows are cached."""
        for hot in self.ring:
            yield hot
        for _ in range(int(self.p.get("warm_fresh_steps", 0))):
            yield self.next_batch()[0]

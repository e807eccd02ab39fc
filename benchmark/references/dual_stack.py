"""The plain reference of the dual-stack node (`worlds/dual_stack.py`): the
first deployments' statement of Antrea's semantics (`reference.py`, whose
header gives the evaluation order: Antrea tiers, K8s isolation, Baseline,
default allow; egress Drop/Reject wins) over addresses of two families, in
numpy, with nothing of the program in it.

A v4 address is an int64 as there.  A v6 address is 128 bits: it comes in as
the batch carries it, four big-endian u32 words ((n, 4)), and is compared as
two u64 limbs (high, low) in lexicographic order.  What differs from the v4
reference, each marked DEPARTURE below:

  * an ipBlock is a CIDR of ONE family: it has an empty range in the other;
  * group membership is by ADDRESS: a pod's two addresses are two columns of
    the membership matrix, each set only where the group lists it;
  * no Service is reached over v6 (the deployment's Services are SingleStack
    v4): a v6 packet resolves to no Service and keeps its destination;
  * the statements about a sampled lane (`failed_statements`) are the first
    deployments' nine on a v4 lane, and their v6 reading on a v6 lane.
"""

from __future__ import annotations

import ipaddress

import numpy as np

import correct

ALLOW, DROP, REJECT, PASS = 0, 1, 2, 3
PROTO_TCP = 6
_ACTION = {"Allow": ALLOW, "Drop": DROP, "Reject": REJECT, "Pass": PASS}
_BASELINE = 253
_LANE_BLOCK = 64
_U64 = (1 << 64) - 1
_EMPTY = (1, 0)  # an inclusive range that holds no address, either family


def limbs(words) -> tuple:
    """(n, 4) big-endian u32 words -> (high, low) u64 limbs."""
    w = np.asarray(words).astype(np.uint64)
    return ((w[:, 0] << np.uint64(32)) | w[:, 1],
            (w[:, 2] << np.uint64(32)) | w[:, 3])


def _limbs_of(values: list) -> tuple:
    """128-bit Python ints -> (high, low) u64 limbs."""
    return (np.array([v >> 64 for v in values], np.uint64),
            np.array([v & _U64 for v in values], np.uint64))


def _ge(h, lo, b_h, b_l):
    return (h > b_h) | ((h == b_h) & (lo >= b_l))


def _le(h, lo, b_h, b_l):
    return (h < b_h) | ((h == b_h) & (lo <= b_l))


def _cidr_range(cidr: str) -> tuple:
    """-> (family 4 | 6, first address, last address) as plain integers."""
    net = ipaddress.ip_network(cidr, strict=False)
    return (net.version, int(net.network_address),
            int(net.broadcast_address))


class _Phase:
    """The rules of one (direction, phase), in evaluation order, as columns."""

    _RANGES = ("lo", "hi", "xlo", "xhi")

    def __init__(self, rows: list):
        n = self.n = len(rows)
        self.ids = np.array([rid for rid, _, _ in rows], object)
        self.atg = np.array([p.applied_to for _, p, _ in rows], np.int64)
        self.action = np.array([_ACTION[r.action] for _, _, r in rows],
                               np.int64)
        self.is_group = np.zeros(n, bool)
        self.peer_g = np.zeros(n, np.int64)
        # DEPARTURE: a range and its hole once per family, inclusive; the
        # family the ipBlock is not of keeps the empty range (1, 0).
        r4, r6 = ({k: [_EMPTY[i % 2]] * n for i, k in enumerate(self._RANGES)}
                  for _ in "46")
        n_svc = max([len(r.services) for _, _, r in rows] or [0])
        self.any_svc = np.zeros(n, bool)
        self.s_proto = np.full((n_svc, n), -1, np.int64)
        self.s_lo = np.ones((n_svc, n), np.int64)
        self.s_hi = np.zeros((n_svc, n), np.int64)
        for i, (_, _, r) in enumerate(rows):
            if r.peer[0] == "group":
                self.is_group[i] = True
                self.peer_g[i] = r.peer[1]
            else:
                fam, lo, hi = _cidr_range(r.peer[1])
                into = r4 if fam == 4 else r6
                into["lo"][i], into["hi"][i] = lo, hi
                if len(r.peer[2]) > 1:
                    raise ValueError("one except per ipBlock in this world")
                for exc in r.peer[2]:
                    x_fam, into["xlo"][i], into["xhi"][i] = _cidr_range(exc)
                    if x_fam != fam:
                        raise ValueError("an except of the other family")
            self.any_svc[i] = not r.services
            for s, (proto, port, end) in enumerate(r.services):
                self.s_proto[s, i] = proto
                self.s_lo[s, i] = port
                self.s_hi[s, i] = port if end is None else end
        for k in self._RANGES:
            setattr(self, k, np.array(r4[k], np.int64))
            h, lo = _limbs_of(r6[k])
            setattr(self, k + "6h", h)
            setattr(self, k + "6l", lo)
        # Which family a rule's ipBlock is of (neither: a group peer).
        self.block4 = self.lo <= self.hi
        self.block6 = _le(self.lo6h, self.lo6l, self.hi6h, self.hi6l)

    def sub(self, rows: np.ndarray) -> "_Phase":
        """The rules `rows` (ascending, so still in evaluation order)."""
        p = object.__new__(_Phase)
        p.n = len(rows)
        for name, v in vars(self).items():
            if name != "n":
                p.__dict__[name] = v[..., rows]
        return p

    def _in_cidr(self, peer):
        if not isinstance(peer, tuple):  # v4, as in reference.py
            ip = peer[None, :]
            return ((ip >= self.lo[:, None]) & (ip <= self.hi[:, None])
                    & ~((ip >= self.xlo[:, None]) & (ip <= self.xhi[:, None])))
        h, lo = peer[0][None, :], peer[1][None, :]

        def inside(a, b):
            return (_ge(h, lo, getattr(self, a + "6h")[:, None],
                        getattr(self, a + "6l")[:, None])
                    & _le(h, lo, getattr(self, b + "6h")[:, None],
                          getattr(self, b + "6l")[:, None]))

        return inside("lo", "hi") & ~inside("xlo", "xhi")

    def match(self, member, pod_i, peer_i, peer, proto, dport):
        """-> (n, lanes) bool.  `peer` is an int64 column (v4) or a pair of
        u64 limb columns (v6)."""
        m = member[self.atg[:, None], pod_i[None, :]]
        m &= np.where(self.is_group[:, None],
                      member[self.peer_g[:, None], peer_i[None, :]],
                      self._in_cidr(peer))
        svc = np.broadcast_to(self.any_svc[:, None], m.shape).copy()
        for s in range(len(self.s_proto)):
            svc |= ((proto[None, :] == self.s_proto[s][:, None])
                    & (dport[None, :] >= self.s_lo[s][:, None])
                    & (dport[None, :] <= self.s_hi[s][:, None]))
        m &= svc
        return m

    def first(self, *lanes):
        """-> (matched, action, row index) of the first matching rule."""
        if not self.n:
            z = np.zeros(len(lanes[1]), np.int64)
            return z.astype(bool), z, z
        m = self.match(*lanes)
        idx = m.argmax(axis=0)
        return m[idx, np.arange(m.shape[1])], self.action[idx], idx


def _take(addr, rows):
    return (addr[0][rows], addr[1][rows]) if isinstance(addr, tuple) \
        else addr[rows]


class Reference:
    def __init__(self, world, keep_policy=None):
        self.pods = np.array(sorted(set(world.pods)), np.int64)
        pods6 = sorted(set(world.pods6))
        self.pods6 = np.stack(_limbs_of(pods6), axis=1)  # (n6, 2), sorted
        n4, n6 = len(self.pods), len(pods6)
        self.n_addr = n4 + n6
        # DEPARTURE: member[g, address index]: the v4 pod addresses, then
        # the v6 ones; the last column is "not a pod's address".
        self.member = np.zeros((len(world.groups), self.n_addr + 1), bool)
        at6 = {v: n4 + i for i, v in enumerate(pods6)}
        for gi, members in enumerate(world.groups):
            for ip, _, _ in members:
                a = ipaddress.ip_address(ip)
                col = (int(np.searchsorted(self.pods, int(a)))
                       if a.version == 4 else at6[int(a)])
                self.member[gi, col] = True
        policies = [p for i, p in enumerate(world.policies)
                    if keep_policy is None or keep_policy(i)]
        self.isolated = {}
        self.phases = {}
        for d in ("In", "Out"):
            iso = np.zeros(self.n_addr + 1, bool)
            antrea, k8s, baseline = [], [], []
            for p in policies:
                if p.kind == "knp" and d in p.policy_types:
                    iso |= self.member[p.applied_to]
                for i, r in enumerate(p.rules):
                    if r.direction != d:
                        continue
                    row = (f"{p.uid}/{d}/{i}", p, r)
                    if p.kind == "knp":
                        k8s.append(row)
                    elif p.tier == _BASELINE:
                        baseline.append(row)
                    else:
                        antrea.append(row)

            def order(row):
                _, p, r = row
                return (p.tier, p.priority, r.priority, p.uid)

            iso[self.n_addr] = False
            self.isolated[d] = iso
            self.phases[d] = (_Phase(sorted(antrea, key=order)), _Phase(k8s),
                              _Phase(sorted(baseline, key=order)))
        # ClusterIP frontends and endpoint sets: v4, as in reference.py.
        v4 = ipaddress.IPv4Address
        self.front = np.array(
            [self._front_key(int(v4(s.cluster_ip)), s.proto, s.port)
             for s in world.services], np.int64)
        if len(set(self.front.tolist())) != len(self.front):
            raise ValueError("duplicate Service frontend")
        self.front_order = np.argsort(self.front)
        self.n_ep = np.array([len(s.endpoints) for s in world.services],
                             np.int64)
        self.ep_keys = np.unique(np.array(
            [(si << 48) | (int(v4(ip)) << 16) | port
             for si, s in enumerate(world.services)
             for ip, port in s.endpoints] or [-1], np.int64))

    @staticmethod
    def _front_key(ip, proto, port):
        return (ip << 24) | (proto << 16) | port

    def _pod_index(self, addr) -> np.ndarray:
        """Address column of either family -> its column of `member`."""
        if not isinstance(addr, tuple):
            i = np.minimum(np.searchsorted(self.pods, addr),
                           len(self.pods) - 1)
            return np.where(self.pods[i] == addr, i, self.n_addr)
        # 128 bits: the table's rows and the lanes' numbered together, so a
        # lane that equals a table row shares its number.
        n6 = len(self.pods6)
        _, inv = np.unique(
            np.concatenate([self.pods6, np.stack(addr, axis=1)]), axis=0,
            return_inverse=True)
        inv = inv.reshape(-1)
        slot = np.full(inv.max() + 1, self.n_addr, np.int64)
        slot[inv[:n6]] = len(self.pods) + np.arange(n6)
        return slot[inv[n6:]]

    def resolve(self, dst, proto, dport):
        """-> (service index or -1, True where that Service has no
        endpoint), for v4 destinations."""
        if not len(self.front):
            none = np.full(len(dst), -1, np.int64)
            return none, np.zeros(len(dst), bool)
        key = self._front_key(dst.astype(np.int64), proto.astype(np.int64),
                              dport.astype(np.int64))
        pos = np.minimum(np.searchsorted(self.front, key,
                                         sorter=self.front_order),
                         len(self.front) - 1)
        cand = self.front_order[pos]
        svc = np.where(self.front[cand] == key, cand, -1)
        return svc, (svc >= 0) & (self.n_ep[np.maximum(svc, 0)] == 0)

    def is_endpoint(self, svc, ip, port):
        key = ((svc.astype(np.int64) << 48) | (ip.astype(np.int64) << 16)
               | port.astype(np.int64))
        return np.isin(key, self.ep_keys)

    def _direction(self, d, pod, peer, proto, dport):
        """-> (code, deciding rule id or None, True where a rule of this
        direction matched and decided) per lane; `pod` and `peer` are
        address columns of one family."""
        n = len(proto)
        code = np.zeros(n, np.int64)
        rule = np.full(n, None, object)
        named = np.zeros(n, bool)
        pod_all, peer_all = self._pod_index(pod), self._pod_index(peer)
        by_pod = np.argsort(pod_all, kind="stable")
        for a in range(0, n, _LANE_BLOCK):
            b = by_pod[a:a + _LANE_BLOCK]
            pod_i = pod_all[b]
            applied = self.member[:, np.unique(pod_i)].any(axis=1)
            antrea, k8s, baseline = (
                ph.sub(np.nonzero(applied[ph.atg])[0])
                for ph in self.phases[d])
            lanes = (self.member, pod_i, peer_all[b], _take(peer, b),
                     proto[b], dport[b])
            hit, act, idx = antrea.first(*lanes)
            final = hit & (act != PASS)
            iso = self.isolated[d][pod_i] & ~final
            allowed = (k8s.match(*lanes).any(axis=0) if k8s.n
                       else np.zeros(len(pod_i), bool))
            b_hit, b_act, b_idx = baseline.first(*lanes)
            b_final = b_hit & (b_act != PASS) & ~final & ~iso
            c = np.where(final, act, np.where(
                iso, np.where(allowed, ALLOW, DROP),
                np.where(b_final, b_act, ALLOW)))
            r = np.full(len(pod_i), None, object)
            for ids, mask, which in ((antrea.ids, final, idx),
                                     (baseline.ids, b_final, b_idx)):
                for lane in np.nonzero(mask)[0]:
                    r[lane] = ids[which[lane]]
            code[b], rule[b] = c, r
            named[b] = final | (iso & allowed) | b_final
        return code, rule, named

    @staticmethod
    def _address(a):
        a = np.asarray(a)
        return limbs(a) if a.ndim == 2 else a.astype(np.int64)

    def _both(self, src, dst, proto, dport):
        src, dst = self._address(src), self._address(dst)
        if isinstance(src, tuple) != isinstance(dst, tuple):
            raise ValueError("both ends of a packet have one family")
        proto, dport = (np.asarray(x).astype(np.int64) for x in (proto, dport))
        return (self._direction("Out", src, dst, proto, dport),
                self._direction("In", dst, src, proto, dport))

    def classify(self, src, dst, proto, dport):
        """Policy over post-DNAT packets of ONE family (int64 columns: v4;
        (n, 4) word columns: v6) -> (code, deciding direction "Out" | "In" |
        None, the denying rule's id or None)."""
        (e_code, e_rule, _), (i_code, i_rule, _) = self._both(src, dst, proto,
                                                              dport)
        egress = e_code != ALLOW
        code = np.where(egress, e_code, i_code)
        by = np.where(egress, "Out", np.where(i_code != ALLOW, "In", None))
        return code, by, np.where(egress, e_rule, i_rule)

    def classify_named(self, src, dst, proto, dport):
        """-> (code, in how many of the packet's two directions a rule
        decided: 0, 1 or 2)."""
        (e_code, _, e_named), (i_code, _, i_named) = self._both(
            src, dst, proto, dport)
        return (np.where(e_code != ALLOW, e_code, i_code),
                e_named.astype(np.int64) + i_named)

    # -- the statements about a sampled lane ---------------------------------

    def failed_statements(self, s: dict) -> dict:
        """-> {statement: bool per sampled lane, True where it fails}; fills
        s["ref_code"] and s["ref_rule"].  A v4 lane is held to the first
        deployments' nine statements (`correct.failed_statements`, with this
        reference behind them); a v6 lane to `_failed6`."""
        n = len(s["code"])
        is6 = (np.asarray(s["is6"]) != 0 if "is6" in s
               else np.zeros(n, bool))
        parts = []
        for mask in (~is6, is6):
            if mask.any():
                sub = {k: np.asarray(v)[mask] for k, v in s.items()}
                part = (self._failed6(sub) if mask is is6
                        else correct.failed_statements(self, sub))
                parts.append((mask, part, sub))
        bad = {k: np.zeros(n, bool) for _, part, _ in parts for k in part}
        s["ref_code"] = np.zeros(n, np.int64)
        s["ref_rule"] = np.full(n, None, object)
        for mask, part, sub in parts:
            for k, v in part.items():
                bad[k][mask] = v
            s["ref_code"][mask] = sub["ref_code"]
            s["ref_rule"][mask] = sub["ref_rule"]
        return bad

    def _failed6(self, s: dict) -> dict:
        """A v6 lane, statement for statement beside the v4 nine."""
        proto = np.asarray(s["proto"], np.int64)
        dport = np.asarray(s["dst_port"], np.int64)
        code = np.asarray(s["code"], np.int64)
        est = np.asarray(s["est"], np.int64)
        fresh = np.asarray(s["fresh"], bool)
        bad = {}
        # DEPARTURE (service, dnat): no Service is reached over v6, so the
        # lane names none and keeps its destination port.  (Its post-DNAT
        # ADDRESS is 128 bits wide and not among the answers the harness
        # samples, which carry `dnat_ip`, 32 bits: PERF.md s7.)
        bad["service"] = np.asarray(s["svc_idx"], np.int64) != -1
        bad["dnat"] = np.asarray(s["dnat_port"], np.int64) != dport
        # DEPARTURE (code): the first match on the 128-bit addresses, once
        # per distinct packet.
        pkt = np.concatenate([
            np.asarray(s["src_ip6"]).astype(np.int64),
            np.asarray(s["dst_ip6"]).astype(np.int64),
            proto[:, None], dport[:, None]], axis=1)
        uniq, inv = np.unique(pkt, axis=0, return_inverse=True)
        inv = inv.reshape(-1)
        u_code, u_by, u_rule = self.classify(uniq[:, 0:4], uniq[:, 4:8],
                                             uniq[:, 8], uniq[:, 9])
        ref_code = s["ref_code"] = u_code[inv]
        bad["code"] = code != ref_code
        in_rule = np.asarray(s["ingress_rule"], object)
        out_rule = np.asarray(s["egress_rule"], object)
        by, rule = u_by[inv], u_rule[inv]
        denied = ref_code != ALLOW
        bad["rule"] = denied & (np.where(by == "Out", out_rule, in_rule)
                                != rule)
        s["ref_rule"] = np.where(denied, rule, None)
        want_kind = np.where(code == REJECT,
                             np.where(proto == PROTO_TCP, 1, 2), 0)
        bad["reject_kind"] = (np.asarray(s["reject_kind"], np.int64)
                              != want_kind)
        bad["marks"] = (np.asarray(s["reply"], np.int64) != 0) | (
            np.asarray(s["snat"], np.int64) != 0)
        # DEPARTURE (est, committed): no multicast exemption; the traffic
        # sends no packet to ff00::/8.
        bad["est"] = ~np.isin(est, (0, 1)) | (
            (est == 1) & ((code != ALLOW) | fresh))
        bad["committed"] = np.asarray(s["committed"], np.int64) != (
            (code == ALLOW) & (est == 0)).astype(np.int64)
        return bad

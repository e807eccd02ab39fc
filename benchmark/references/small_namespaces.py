"""The plain reference of the xLargeScale cluster (`worlds/small_namespaces
.py`): the first deployments' statement of Antrea's semantics (`reference.py`,
whose header gives them: Service resolution, then per direction the Antrea
tiers, K8s isolation with any matching allow, Baseline, default allow; egress
Drop/Reject wins), in numpy, with nothing of the program in it.

The SEMANTICS are `reference.py`'s, statement for statement; the harness's
suite holds the two equal lane for lane, rule ids included, on worlds small
enough for both.  What differs is the storage, each marked DEPARTURE below:
that reference holds membership as a dense (groups x pods) matrix and finds
the rules a block of lanes can meet by a pass over all of it, 5 GB and 75,000
rules a block for a world of 50,000 groups over 100,000 pods.  Here

  * membership is a sorted list of (group, pod) pairs: `member[g, p]` is a
    binary search;
  * a pod's groups and a group's rules are index lists, so a block of lanes
    meets the rules applied to its own pods' groups and reads no other.

It defines no `failed_statements`: the statements about a sampled lane are
`correct.py`'s nine (on a world without Services `resolve` answers -1 on
every lane and the DNAT target has to be untouched).

`keep_policy` builds the same reference over a subset of the policies: the
control of `tests/control.py`.
"""

from __future__ import annotations

import numpy as np

from world import ip_u32

ALLOW, DROP, REJECT, PASS = 0, 1, 2, 3
_ACTION = {"Allow": ALLOW, "Drop": DROP, "Reject": REJECT, "Pass": PASS}
_BASELINE = 253
_LANE_BLOCK = 512


def _cidr_range(cidr: str) -> tuple:
    ip, plen = cidr.split("/")
    plen = int(plen)
    mask = (0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF if plen else 0
    lo = ip_u32(ip) & mask
    return lo, lo | (~mask & 0xFFFFFFFF)


def _lists(of: np.ndarray, n: int) -> tuple:
    """Index lists: -> (rows, start) such that rows[start[k]:start[k + 1]]
    are the positions i with of[i] == k, ascending, for k in [0, n)."""
    rows = np.argsort(of, kind="stable")
    return rows, np.searchsorted(of[rows], np.arange(n + 1))


def _gather(lists: tuple, keys: np.ndarray) -> np.ndarray:
    """The concatenated lists of `keys`."""
    rows, start = lists
    a, n = start[keys], start[keys + 1] - start[keys]
    at = np.repeat(a - (np.cumsum(n) - n), n) + np.arange(int(n.sum()))
    return rows[at]


class _Membership:
    """DEPARTURE: (group, pod index) pairs, sorted; pod index `n_pods` is
    "not a pod" and is in no group."""

    def __init__(self, groups: list, pods: np.ndarray):
        self.n_pods = len(pods)
        g = np.repeat(np.arange(len(groups)), [len(m) for m in groups])
        ips = np.array([ip_u32(ip) for m in groups for ip, _, _ in m],
                       np.int64)
        p = np.searchsorted(pods, ips)
        self.keys = np.unique(g * (self.n_pods + 1) + p)
        self.groups_of_pod = _lists(p, self.n_pods + 1)
        self._group = g

    def __call__(self, g, p) -> np.ndarray:
        """member[g, p], broadcast."""
        key = np.asarray(g) * (self.n_pods + 1) + np.asarray(p)
        at = np.minimum(np.searchsorted(self.keys, key), len(self.keys) - 1)
        return self.keys[at] == key

    def pods_of(self, g: int) -> np.ndarray:
        a, b = np.searchsorted(self.keys, [g * (self.n_pods + 1),
                                           (g + 1) * (self.n_pods + 1)])
        return self.keys[a:b] - g * (self.n_pods + 1)

    def groups_of(self, pods: np.ndarray) -> np.ndarray:
        """The groups that one of `pods` (indices) is in."""
        return np.unique(self._group[_gather(self.groups_of_pod, pods)])


class _Phase:
    """The rules of one (direction, phase), in evaluation order, as columns
    (`reference._Phase`'s, name for name: the generator reads them)."""

    def __init__(self, rows: list, n_groups: int = 0):
        n = len(rows)
        self.n = n
        self.ids = np.array([rid for rid, _, _ in rows], object)
        self.atg = np.array([p.applied_to for _, p, _ in rows], np.int64)
        self.action = np.array([_ACTION[r.action] for _, _, r in rows],
                               np.int64)
        self.is_group = np.zeros(n, bool)
        self.peer_g = np.zeros(n, np.int64)
        # Inclusive u32 ranges in int64; an empty hole is (1, 0).
        self.lo = np.ones(n, np.int64)
        self.hi = np.zeros(n, np.int64)
        self.xlo = np.ones(n, np.int64)
        self.xhi = np.zeros(n, np.int64)
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
                self.lo[i], self.hi[i] = _cidr_range(r.peer[1])
                if len(r.peer[2]) > 1:
                    raise ValueError("one except per ipBlock in this world")
                for exc in r.peer[2]:
                    self.xlo[i], self.xhi[i] = _cidr_range(exc)
            self.any_svc[i] = not r.services
            for s, (proto, port, end) in enumerate(r.services):
                self.s_proto[s, i] = proto
                self.s_lo[s, i] = port
                self.s_hi[s, i] = port if end is None else end
        # DEPARTURE: the rules applied to each group, as an index list.
        self.of_group = _lists(self.atg, n_groups)

    def sub(self, rows: np.ndarray) -> "_Phase":
        """The rules `rows` (ascending, so still in evaluation order)."""
        p = object.__new__(_Phase)
        p.n = len(rows)
        for name, v in vars(self).items():
            if name not in ("n", "of_group"):
                p.__dict__[name] = v[..., rows]
        return p

    def applied_to(self, groups: np.ndarray) -> "_Phase":
        return self.sub(np.sort(_gather(self.of_group, groups)))

    def match(self, member, pod_i, peer_i, peer_ip, proto, dport):
        """-> (n, lanes) bool."""
        m = member(self.atg[:, None], pod_i[None, :])
        ip = peer_ip[None, :]
        in_cidr = ((ip >= self.lo[:, None]) & (ip <= self.hi[:, None])
                   & ~((ip >= self.xlo[:, None]) & (ip <= self.xhi[:, None])))
        m &= np.where(self.is_group[:, None],
                      member(self.peer_g[:, None], peer_i[None, :]), in_cidr)
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


class Reference:
    def __init__(self, world, keep_policy=None):
        self.pods = np.array(sorted(set(world.pods)), np.int64)
        n_pods, n_groups = len(self.pods), len(world.groups)
        self.member = _Membership(world.groups, self.pods)
        policies = [p for i, p in enumerate(world.policies)
                    if keep_policy is None or keep_policy(i)]
        self.isolated = {}
        self.phases = {}
        for d in ("In", "Out"):
            iso = np.zeros(n_pods + 1, bool)
            antrea, k8s, baseline = [], [], []
            for p in policies:
                if p.kind == "knp" and d in p.policy_types:
                    iso[self.member.pods_of(p.applied_to)] = True
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

            self.isolated[d] = iso
            self.phases[d] = (_Phase(sorted(antrea, key=order), n_groups),
                              _Phase(k8s, n_groups),
                              _Phase(sorted(baseline, key=order), n_groups))
        # ClusterIP frontends and endpoint sets, as sorted integer keys.
        self.front = np.array(
            [self._front_key(ip_u32(s.cluster_ip), s.proto, s.port)
             for s in world.services], np.int64)
        if len(set(self.front.tolist())) != len(self.front):
            raise ValueError("duplicate Service frontend")
        self.front_order = np.argsort(self.front)
        self.n_ep = np.array([len(s.endpoints) for s in world.services],
                             np.int64)
        self.ep_keys = np.unique(np.array(
            [(si << 48) | (ip_u32(ip) << 16) | port
             for si, s in enumerate(world.services)
             for ip, port in s.endpoints] or [-1], np.int64))

    @staticmethod
    def _front_key(ip, proto, port):
        return (ip << 24) | (proto << 16) | port

    def _pod_index(self, ips: np.ndarray) -> np.ndarray:
        i = np.searchsorted(self.pods, ips)
        i = np.minimum(i, len(self.pods) - 1)
        return np.where(self.pods[i] == ips, i, len(self.pods))

    def resolve(self, dst, proto, dport):
        """-> (service index or -1, True where that Service has no
        endpoint)."""
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

    def _direction(self, d, pod_ip, peer_ip, proto, dport):
        """-> (code, deciding rule id or None, True where a rule of this
        direction matched and decided: what a datapath attributes the lane
        to, an allowing K8s rule included) per lane."""
        n = len(pod_ip)
        code = np.zeros(n, np.int64)
        rule = np.full(n, None, object)
        named = np.zeros(n, bool)
        pod_all = self._pod_index(pod_ip)
        by_pod = np.argsort(pod_all, kind="stable")
        for a in range(0, n, _LANE_BLOCK):
            b = by_pod[a:a + _LANE_BLOCK]
            pod_i = pod_all[b]
            # DEPARTURE: the block's rules from its pods' own groups.
            applied = self.member.groups_of(np.unique(pod_i))
            antrea, k8s, baseline = (ph.applied_to(applied)
                                     for ph in self.phases[d])
            lanes = (self.member, pod_i, self._pod_index(peer_ip[b]),
                     peer_ip[b], proto[b], dport[b])
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

    def _both(self, src, dst, proto, dport):
        src, dst = src.astype(np.int64), dst.astype(np.int64)
        proto, dport = proto.astype(np.int64), dport.astype(np.int64)
        return (self._direction("Out", src, dst, proto, dport),
                self._direction("In", dst, src, proto, dport))

    def classify(self, src, dst, proto, dport):
        """Policy over post-DNAT packets -> (code, deciding direction "Out"
        | "In" | None, the denying rule's id or None)."""
        (e_code, e_rule, _), (i_code, i_rule, _) = self._both(src, dst, proto,
                                                              dport)
        egress = e_code != ALLOW
        code = np.where(egress, e_code, i_code)
        by = np.where(egress, "Out", np.where(i_code != ALLOW, "In", None))
        return code, by, np.where(egress, e_rule, i_rule)

    def classify_named(self, src, dst, proto, dport):
        """-> (code, in how many of the packet's two directions a rule
        decided: 0, 1 or 2 — the host work a datapath has in attributing
        and counting the lane)."""
        (e_code, _, e_named), (i_code, _, i_named) = self._both(
            src, dst, proto, dport)
        return (np.where(e_code != ALLOW, e_code, i_code),
                e_named.astype(np.int64) + i_named)

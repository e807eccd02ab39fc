"""`policy_flows` for a world without Services (`worlds/small_namespaces.py`):
the same closed loop, the same scheme, without the Service classes.

What is `policy_flows`' stays its, line for line: `Traffic` here IS its
`Traffic` (the stated class shares, the split of an allowed class by the
directions a rule decided, as many templates a class as its share asks for,
the class of every rank, the hot ring, the arrivals with a source port of
their own, the sampled lanes, the warm-up).  `manifest.load_module` executes
a file anew for every caller, so the copy of `policy_flows` loaded below is
this module's own, and the one function that module looks up by name to
PROPOSE and SORT the templates, `_classes`, is given this module's in its
place.  `policy_flows._classes` cannot serve: it builds the Service legs
first and refuses a world that has no Service with an endpoint.

Proposals, sorted by the plain reference into {pod-to-pod, external} x
{allowed, denied} (a mix for this generator states `svc_fraction` 0, so
`class_shares` names no Service class):

  by the allow rules   half of them: a member of the rule's applied-to group,
             a member of its peer group or an address of its ipBlock, its
             port (a rule for any port: a common one).  Under a policy that
             allows only inside a namespace nothing else finds an allowed
             pod-to-pod conversation: two pods drawn from 100,000 share a
             namespace four times in 100,000.
  beside them   an eighth of those keep the rule's two pods and take another
             port, an eighth keep the port and take the peer from the
             applied-to group itself: the misdirected and the same-label
             connections of a namespace's own pods, which the rule does not
             name and isolation denies.
  uniform    the other half, `policy_flows._uniform`'s draws over pods and
             external addresses with no regard to policy, half of them with
             an external end: pod to external (allowed: nothing isolates
             egress), external to pod and pod to a pod of another namespace
             (denied by isolation).  Half, and not `pod_to_pod_fraction`:
             no rule proposes the allowed external class, so the uniform
             draws alone have to fill its share of the templates.
"""

from __future__ import annotations

import os

import numpy as np

from manifest import load_module
from world import ip_u32

_flows = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "policy_flows.py"))
ALLOW = _flows.ALLOW


def _from_rules(rng, ref, members, n):
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

        pick = rng.integers(0, len(col("atg")), size=n)
        width = members.shape[1]
        pod = members[col("atg")[pick], rng.integers(0, width, size=n)]
        peer = np.where(
            col("is_group")[pick],
            members[col("peer_g")[pick], rng.integers(0, width, size=n)],
            _flows._between(rng, col("lo")[pick], col("hi")[pick]))
        any_port = col("any_svc")[pick]
        c_proto, c_port = _flows._ports(rng, n)
        proto = np.where(any_port, c_proto, col("s_proto", True)[pick])
        dport = np.where(any_port, c_port, _flows._between(
            rng, col("s_lo", True)[pick], col("s_hi", True)[pick]))
        # Beside the rule: another port; a peer of the pod's own group.
        beside = rng.integers(0, 8, size=n)
        dport = np.where(beside == 0, rng.integers(1, 65536, size=n), dport)
        peer = np.where(beside == 1, members[
            col("atg")[pick], rng.integers(0, width, size=n)], peer)
        src, dst = (peer, pod) if d == "In" else (pod, peer)
        out.append(np.stack([src, dst, proto, dport], axis=1))
    if not out:
        return np.zeros((0, 4), np.int64)
    both = np.concatenate(out)
    return both[rng.permutation(len(both))[:n]]


def _classes(rng, world, ref, p):
    """-> {(kind, allowed): ((n, 4) distinct templates, in how many
    directions a rule decided each)} for kind in pod, ext."""
    if p["svc_fraction"]:
        raise ValueError("this generator sends no Service flow: the mix has "
                         "to state svc_fraction 0")
    members = np.array([[ip_u32(ip) for ip, _, _ in g] for g in world.groups],
                       np.int64)
    n = int(p["proposals"])
    plain = np.concatenate([_from_rules(rng, ref, members, n - n // 2),
                            _flows._uniform(rng, ref.pods, n // 2, 0.5)])
    front, _ = ref.resolve(plain[:, 1], plain[:, 2], plain[:, 3])
    plain = np.unique(plain[(front < 0) & ((plain[:, 1] >> 28) != 0xE)],
                      axis=0)
    code, named = ref.classify_named(plain[:, 0], plain[:, 1], plain[:, 2],
                                     plain[:, 3])
    n_pods = len(ref.pods)
    both = ((ref._pod_index(plain[:, 0]) < n_pods)
            & (ref._pod_index(plain[:, 1]) < n_pods))
    return {(kind, allowed): (plain[mask & ((code == ALLOW) == allowed)],
                              named[mask & ((code == ALLOW) == allowed)])
            for allowed in (True, False)
            for kind, mask in (("pod", both), ("ext", ~both))}


_flows._classes = _classes
Traffic = _flows.Traffic

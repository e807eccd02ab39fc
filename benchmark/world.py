"""The deployment's world as plain data, from a seed.

A copy of the program's seeded generators (`antrea_tpu/simulator/genpolicy
.gen_cluster`, `genservice.gen_services`), kept here so that a later PR can
change the program and not the yardstick.  The draws are made in the same
order, so the same seed gives the same cluster (the harness's test holds the
two equal).  What comes out is plain data — ints, strings, tuples — which
the reference (`reference.py`) reads directly; `to_program` is the one place
where it is turned into the program's input types.
"""

from __future__ import annotations

import random
from dataclasses import dataclass, field
from typing import Optional

PROTO_TCP, PROTO_UDP = 6, 17
# Antrea tier priorities (crd/v1beta1 Tier; lower runs first); Baseline is
# evaluated after the K8s NetworkPolicies.
TIERS = (50, 100, 150, 200, 250)
TIER_APPLICATION = 250
TIER_BASELINE = 253


def ip_str(u32: int) -> str:
    return ".".join(str((u32 >> s) & 0xFF) for s in (24, 16, 8, 0))


def ip_u32(s: str) -> int:
    a, b, c, d = (int(x) for x in s.split("."))
    return (a << 24) | (b << 16) | (c << 8) | d


def pod_ip(node_idx: int, pod_idx: int) -> str:
    # One /24 podCIDR per node.
    return f"10.{node_idx // 256}.{node_idx % 256}.{pod_idx + 2}"


@dataclass(frozen=True)
class Rule:
    direction: str  # "In" | "Out"
    peer: tuple  # ("group", gi) | ("cidr", "a.b.c.d/n", (except cidrs...))
    services: tuple  # ((proto, port, end_port|None), ...); () = any
    action: str  # "Allow" | "Drop" | "Reject" | "Pass"
    priority: int  # rule index for ACNP rules, -1 for K8s


@dataclass(frozen=True)
class Policy:
    uid: str
    kind: str  # "acnp" | "knp"
    namespace: str
    rules: tuple
    applied_to: int  # group index
    tier: Optional[int] = None
    priority: Optional[float] = None
    policy_types: tuple = ()  # K8s only: directions that isolate


@dataclass(frozen=True)
class Service:
    cluster_ip: str
    port: int
    proto: int
    endpoints: tuple  # ((ip, port), ...)
    affinity_s: int
    name: str
    namespace: str


@dataclass
class World:
    pods: list = field(default_factory=list)  # u32
    nodes: list = field(default_factory=list)
    groups: list = field(default_factory=list)  # gi -> ((ip, node, pod), ...)
    policies: list = field(default_factory=list)
    services: list = field(default_factory=list)


def gen_cluster(n_rules: int, *, n_nodes: int, pods_per_node: int,
                pods_per_group: int = 8, rules_per_policy: int = 4,
                cidr_fraction: float = 0.3, acnp_fraction: float = 0.5,
                with_tiers: bool = True, seed: int = 0) -> World:
    rng = random.Random(seed)
    w = World()
    w.nodes = [f"node-{i}" for i in range(n_nodes)]
    w.pods = [ip_u32(pod_ip(n, p)) for n in range(n_nodes)
              for p in range(pods_per_node)]
    n_groups = max(4, min(4096, (n_rules // 4) or 4))
    for _ in range(n_groups):
        members = []
        for _ in range(pods_per_group):
            n = rng.randrange(n_nodes)
            p = rng.randrange(pods_per_node)
            members.append((pod_ip(n, p), w.nodes[n], f"pod-{n}-{p}"))
        w.groups.append(tuple(members))
    tiers = list(TIERS) if with_tiers else [TIER_APPLICATION]

    def rand_peer() -> tuple:
        if rng.random() < cidr_fraction:
            plen = rng.choice([8, 12, 16, 20, 24, 28, 32])
            base = rng.getrandbits(32)
            cidr = f"{ip_str(base)}/{plen}"
            if rng.random() < 0.2:
                return ("cidr", cidr, (f"{ip_str(base)}/{min(plen + 4, 32)}",))
            return ("cidr", cidr, ())
        return ("group", rng.randrange(n_groups))

    def rand_services() -> tuple:
        r = rng.random()
        if r < 0.25:
            return ()
        proto = rng.choice([PROTO_TCP, PROTO_TCP, PROTO_UDP])
        port = rng.choice([80, 443, 8080, 53, 5432, rng.randrange(1024, 60000)])
        if r < 0.4:
            return ((proto, port, port + rng.randrange(1, 64)),)
        return ((proto, port, None),)

    made = pi = 0
    while made < n_rules:
        k = min(rules_per_policy, n_rules - made)
        is_acnp = rng.random() < acnp_fraction
        rules = []
        for ri in range(k):
            direction = "In" if rng.random() < 0.6 else "Out"
            peer = rand_peer()
            services = rand_services()
            action = (rng.choices(["Allow", "Drop", "Reject", "Pass"],
                                  weights=[0.55, 0.3, 0.05, 0.1])[0]
                      if is_acnp else "Allow")
            rules.append(Rule(direction, peer, services, action,
                              ri if is_acnp else -1))
        atg = rng.randrange(n_groups)
        if is_acnp:
            # The draw for the Baseline tier comes before the choice, as in
            # the original's `tiers + ([BASELINE] if random() < 0.1 ...)`.
            pool = tiers + ([TIER_BASELINE] if rng.random() < 0.1 else [])
            tier = rng.choice(pool)
            w.policies.append(Policy(
                uid=f"acnp-{pi}", kind="acnp", namespace="", rules=tuple(rules),
                applied_to=atg, tier=tier,
                priority=round(rng.uniform(1, 150), 2)))
        else:
            dirs = tuple(sorted({r.direction for r in rules}))
            w.policies.append(Policy(
                uid=f"knp-{pi}", kind="knp",
                namespace=f"ns-{rng.randrange(32)}", rules=tuple(rules),
                applied_to=atg, policy_types=dirs))
        made += k
        pi += 1
    return w


def gen_services(n_services: int, pods: list, *, max_endpoints: int = 8,
                 affinity_fraction: float = 0.3, no_ep_fraction: float = 0.02,
                 seed: int = 0) -> list:
    rng = random.Random(seed)
    out = []
    for i in range(n_services):
        ip = f"10.{96 + (i // 65536)}.{(i // 256) % 256}.{i % 256}"
        proto = PROTO_TCP if rng.random() < 0.9 else PROTO_UDP
        port = rng.choice([80, 443, 8080, 9090, 5432,
                           rng.randrange(1024, 32768)])
        if rng.random() < no_ep_fraction:
            eps = ()
        else:
            n_ep = rng.randrange(1, max_endpoints + 1)
            eps = tuple((ip_str(rng.choice(pods)), rng.choice([8080, 80, 9376]))
                        for _ in range(n_ep))
        out.append(Service(
            cluster_ip=ip, port=port, proto=proto, endpoints=eps,
            affinity_s=300 if rng.random() < affinity_fraction else 0,
            name=f"svc-{i}", namespace=f"ns-{i % 32}"))
    return out


def build_world(params: dict, seed: int) -> World:
    """`params` is the configuration file's `world` group."""
    p = dict(params)
    n_services = p.pop("n_services")
    svc_kw = {k: p.pop(k) for k in ("max_endpoints", "affinity_fraction",
                                    "no_ep_fraction") if k in p}
    w = gen_cluster(p.pop("n_rules"), seed=seed, **p)
    w.services = gen_services(n_services, w.pods, seed=seed + 1, **svc_kw)
    return w


def to_program(w: World):
    """-> (PolicySet, [ServiceEntry]): the program's input types."""
    from antrea_tpu.apis import controlplane as cp
    from antrea_tpu.apis.service import Endpoint, ServiceEntry
    from antrea_tpu.compiler.ir import PolicySet

    ps = PolicySet()
    for gi, members in enumerate(w.groups):
        ms = [cp.GroupMember(ip=ip, node=node, pod_name=pod)
              for ip, node, pod in members]
        ps.address_groups[f"ag-{gi}"] = cp.AddressGroup(name=f"ag-{gi}",
                                                        members=ms)
        ps.applied_to_groups[f"atg-{gi}"] = cp.AppliedToGroup(
            name=f"atg-{gi}", members=ms)

    def peer_of(peer: tuple) -> cp.NetworkPolicyPeer:
        if peer[0] == "group":
            return cp.NetworkPolicyPeer(address_groups=[f"ag-{peer[1]}"])
        return cp.NetworkPolicyPeer(
            ip_blocks=[cp.IPBlock(cidr=peer[1], excepts=tuple(peer[2]))])

    for pol in w.policies:
        rules = []
        for r in pol.rules:
            d = cp.Direction(r.direction)
            rules.append(cp.NetworkPolicyRule(
                direction=d,
                from_peer=(peer_of(r.peer) if d == cp.Direction.IN
                           else cp.NetworkPolicyPeer()),
                to_peer=(peer_of(r.peer) if d == cp.Direction.OUT
                         else cp.NetworkPolicyPeer()),
                services=[cp.Service(protocol=pr, port=po, end_port=ep)
                          for pr, po, ep in r.services],
                action=cp.RuleAction(r.action), priority=r.priority))
        atg = [f"atg-{pol.applied_to}"]
        if pol.kind == "acnp":
            ps.policies.append(cp.NetworkPolicy(
                uid=pol.uid, name=pol.uid, type=cp.NetworkPolicyType.ACNP,
                rules=rules, applied_to_groups=atg, tier_priority=pol.tier,
                priority=pol.priority))
        else:
            ps.policies.append(cp.NetworkPolicy(
                uid=pol.uid, name=pol.uid, namespace=pol.namespace,
                type=cp.NetworkPolicyType.K8S, rules=rules,
                applied_to_groups=atg,
                policy_types=[cp.Direction(d) for d in pol.policy_types]))
    services = [ServiceEntry(
        cluster_ip=s.cluster_ip, port=s.port, protocol=s.proto,
        endpoints=[Endpoint(ip=ip, port=port) for ip, port in s.endpoints],
        affinity_timeout_s=s.affinity_s, name=s.name, namespace=s.namespace)
        for s in w.services]
    return ps, services

"""The world of a node in a Kubernetes IPv4/IPv6 dual-stack cluster under
Antrea: the first deployments' cluster (`world.build_world`, draw for draw)
where every Pod has one address of each family.

  pods      pod p of node n keeps its v4 address and gets
            fd00:10:0:<n>::<p+2>: one /64 podCIDR a node under fd00:10::/48
            (kubernetes.io dual-stack: a Pod has one address a family).
  groups    every AddressGroup / AppliedToGroup holds BOTH addresses of each
            of its pods: Antrea's GroupMember carries a pod's IPs; here, as
            in the program's own dual-stack tests, two members a pod.
  ipBlocks  an ipBlock is a CIDR of ONE family.  A share `v6_cidr_share` of
            the cluster's ipBlock peers, drawn from the seed, is a v6 CIDR
            under 2001:db8::/32 (the externals' range): the v4 block's 32
            base bits become bits 32..63 of the address and its prefix
            length n becomes 32 + n, so a v6 block covers the share 2**-n
            of its family's externals that its v4 twin covered of the v4
            ones; its except keeps its place (n + 4).
  Services  v4 ClusterIPs and v4 endpoints: `ipFamilyPolicy: SingleStack`
            on the primary range, Kubernetes' default for a Service that
            names no policy.

Plain data as in `world.py`; `to_program` is the one place where it becomes
the program's input types (the program's parser takes either family's text).
"""

from __future__ import annotations

import dataclasses
import ipaddress
import random

import world

EXTERNAL6 = int(ipaddress.IPv6Address("2001:db8::"))  # /32: documentation
POD6 = int(ipaddress.IPv6Address("fd00:10::"))  # /48, a /64 a node


def pod_ip6(node_idx: int, pod_idx: int) -> str:
    return str(ipaddress.IPv6Address(POD6 | (node_idx << 64) | (pod_idx + 2)))


def cidr6(cidr4: str) -> str:
    """The v6 twin of a v4 block: same base bits, 32 bits further down."""
    ip, plen = cidr4.split("/")
    return (str(ipaddress.IPv6Address(EXTERNAL6 | (world.ip_u32(ip) << 64)))
            + f"/{32 + int(plen)}")


@dataclasses.dataclass
class World(world.World):
    pods6: list = dataclasses.field(default_factory=list)  # 128-bit ints


def build_world(params: dict, seed: int) -> World:
    p = dict(params)
    v6_share = p.pop("v6_cidr_share")
    w = world.build_world(p, seed)
    n_per = p["pods_per_node"]
    twin = {world.pod_ip(n, i): pod_ip6(n, i)
            for n in range(p["n_nodes"]) for i in range(n_per)}
    groups = [g + tuple((twin[ip], node, pod) for ip, node, pod in g)
              for g in w.groups]
    rng = random.Random(seed + 2)  # +1 draws the Services

    def family(rule: world.Rule) -> world.Rule:
        if rule.peer[0] != "cidr" or rng.random() >= v6_share:
            return rule
        _, cidr, excepts = rule.peer
        return dataclasses.replace(rule, peer=(
            "cidr", cidr6(cidr), tuple(cidr6(x) for x in excepts)))

    policies = [dataclasses.replace(pol, rules=tuple(
        family(r) for r in pol.rules)) for pol in w.policies]
    return World(
        pods=w.pods, nodes=w.nodes, groups=groups, policies=policies,
        services=w.services,
        pods6=[int(ipaddress.IPv6Address(twin[world.ip_str(ip)]))
               for ip in w.pods])


to_program = world.to_program

"""The world of upstream's xLargeScale cluster: many small namespaces, each
closed on itself by K8s NetworkPolicies.

The shape is `antrea-io/antrea`
`pkg/controller/networkpolicy/networkpolicy_controller_perf_test.go`,
`TestInitXLargeScaleWithSmallNamespaces`: 25,000 namespaces, 100,000 Pods,
75,000 NetworkPolicies.  The objects of ONE namespace are this repo's reading
of that test (`bench_controller.populate`, restated here as plain data: the
yardstick does not read the program's controller):

  pods      `pods_per_namespace` pods; pod j carries the label
            app-(j mod `labels_per_namespace`), lives at
            10.<i div 256>.<i mod 256>.<j + 1> (one /24-style block a
            namespace) and on node (i * pods + j) mod `n_nodes`.
  groups    one a (namespace, label): group i * labels + l holds the pods
            of namespace i that carry app-l.  A policy's podSelector and a
            rule's peer selector both resolve to such a group, so it is an
            AppliedToGroup and an AddressGroup at once, as in `world.py`.
  policies  `policies_per_namespace` K8s NetworkPolicies; policy k selects
            app-(k mod labels) and has ONE ingress rule: from the pods of
            app-((k + 1) mod labels) of its own namespace, TCP/80.  It names
            no egress rule and no policyTypes, so it isolates its pods for
            ingress only (the Kubernetes default for a policy without an
            egress section).  With three policies over two labels the third
            restates the first under another uid, as the source's do.
  Services  none: the source has none.

Nothing is drawn: the seed is taken and not used, the world is the source's.
Plain data in `world.py`'s own types, which the plain reference reads;
`to_program` is `world.py`'s (the one place the program's types appear).
"""

from __future__ import annotations

import world

to_program = world.to_program


def pod_ip(ns: int, pod: int) -> str:
    return f"10.{(ns >> 8) & 255}.{ns & 255}.{pod + 1}"


def build_world(params: dict, seed: int) -> world.World:
    """`params` is the configuration file's `world` group."""
    del seed  # nothing is drawn
    n_ns = int(params["n_namespaces"])
    n_pods = int(params["pods_per_namespace"])
    n_labels = int(params["labels_per_namespace"])
    n_pols = int(params["policies_per_namespace"])
    n_nodes = int(params["n_nodes"])
    if params["n_services"]:
        raise ValueError("this world has no Service")
    if n_ns > 1 << 16 or n_pods > 254:
        raise ValueError("a namespace's block is 10.<ns div 256>.<ns mod "
                         "256>.0/24")
    w = world.World(nodes=[f"node-{i}" for i in range(n_nodes)])
    for i in range(n_ns):
        members = [(pod_ip(i, j), w.nodes[(i * n_pods + j) % n_nodes],
                    f"ns-{i}/pod-{j}") for j in range(n_pods)]
        w.pods.extend(world.ip_u32(ip) for ip, _, _ in members)
        w.groups.extend(tuple(members[label::n_labels])
                        for label in range(n_labels))
        for k in range(n_pols):
            w.policies.append(world.Policy(
                uid=f"np-{i}-{k}", kind="knp", namespace=f"ns-{i}",
                rules=(world.Rule("In", ("group", i * n_labels
                                         + (k + 1) % n_labels),
                                  ((world.PROTO_TCP, 80, None),), "Allow",
                                  -1),),
                applied_to=i * n_labels + k % n_labels,
                policy_types=("In",)))
    return w

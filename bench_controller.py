#!/usr/bin/env python
"""Control-plane scale benchmark: full NP compute at the reference's
xLargeScale shape (networkpolicy_controller_perf_test.go:46-52 —
25k namespaces / 100k pods / 75k NetworkPolicies; reference: 5.84-6.42 s,
1522-1708 MB, Go).

Prints ONE json line.  vs_baseline is wall / 6.13s (the
midpoint of the reference's recorded range) — LOWER is better here, so the
ratio is reported as reference_time / our_time (>1 means faster than the
reference).

Run: python bench_controller.py [--small]

Realization regime (PR 8 span plumbing; ROADMAP item 3's measurable
target): `--fleet N [--churn K]` drives N fake agents (simulator/fleet)
through a K-policy churn storm and reports the fleet-wide p99 of
controller-commit (WatchEvent.ts) -> agent-realized latency as
`realization_p99_s` — the number the "p99 < 1s at 10k agents" soak bar
is judged on.  LOWER is better; vs_baseline is 1.0s / p99.
"""

import json
import sys
import time
import tracemalloc

from antrea_tpu.apis.crd import (
    K8sNetworkPolicy,
    K8sNPRule,
    K8sPeer,
    LabelSelector,
    Namespace,
    Pod,
    PortSpec,
)
from antrea_tpu.controller.networkpolicy import NetworkPolicyController

REF_SECONDS = 6.13  # midpoint of 5.84-6.42 (networkpolicy_controller_perf_test.go)


def populate(ctrl, n_ns: int, pods_per_ns: int, nps_per_ns: int) -> int:
    n_events = 0

    def count(_ev):
        nonlocal n_events
        n_events += 1

    ctrl.subscribe(count)
    for i in range(n_ns):
        ns = f"ns-{i}"
        ctrl.upsert_namespace(Namespace(name=ns, labels={"team": f"t{i % 50}"}))
        for j in range(pods_per_ns):
            ctrl.upsert_pod(Pod(
                name=f"pod-{j}", namespace=ns,
                labels={"app": f"app-{j % 2}"},
                ip=f"10.{(i >> 8) & 255}.{i & 255}.{j + 1}",
                node=f"node-{(i * pods_per_ns + j) % 64}",
            ))
        for k in range(nps_per_ns):
            ctrl.upsert_k8s_policy(K8sNetworkPolicy(
                uid=f"np-{i}-{k}", name=f"np-{k}", namespace=ns,
                pod_selector=LabelSelector.make({"app": f"app-{k % 2}"}),
                ingress=[K8sNPRule(
                    peers=[K8sPeer(pod_selector=LabelSelector.make(
                        {"app": f"app-{(k + 1) % 2}"}))],
                    ports=[PortSpec(protocol=6, port=80)],
                )],
            ))
    return n_events


REALIZATION_TARGET_S = 1.0  # ROADMAP item 3: p99 < 1s at 10k agents


def _argval(flag: str, default: int) -> int:
    if flag in sys.argv:
        idx = sys.argv.index(flag) + 1
        if idx >= len(sys.argv) or not sys.argv[idx].lstrip("-").isdigit():
            sys.exit(f"usage: {flag} N (integer value required)")
        return int(sys.argv[idx])
    return default


def fleet_realization(n_agents: int, churn: int = 64) -> dict:
    """Churn-storm realization regime: N inproc fake agents watching one
    RamStore fed by the real controller; every round upserts one policy
    and pumps the fleet, so each event's WatchEvent.ts -> table-apply
    latency lands in the per-agent realization histograms."""
    from antrea_tpu.dissemination.store import RamStore
    from antrea_tpu.simulator.fleet import FakeAgentFleet

    store = RamStore()
    ctrl = NetworkPolicyController()
    ctrl.subscribe(store.apply)
    nodes = [f"node-{i}" for i in range(n_agents)]
    ctrl.upsert_namespace(Namespace(name="bench", labels={"team": "t0"}))
    for i, node in enumerate(nodes):
        ctrl.upsert_pod(Pod(
            name=f"pod-{i}", namespace="bench",
            labels={"app": f"app-{i % 2}"},
            ip=f"10.{(i >> 8) & 255}.{i & 255}.1", node=node,
        ))
    fleet = FakeAgentFleet(store, nodes)
    fleet.pump()  # drain the snapshot replay before the measured storm
    t0 = time.perf_counter()
    for k in range(churn):
        ctrl.upsert_k8s_policy(K8sNetworkPolicy(
            uid=f"np-{k}", name=f"np-{k}", namespace="bench",
            pod_selector=LabelSelector.make({"app": f"app-{k % 2}"}),
            ingress=[K8sNPRule(
                peers=[K8sPeer(pod_selector=LabelSelector.make(
                    {"app": f"app-{(k + 1) % 2}"}))],
                ports=[PortSpec(protocol=6, port=80)],
            )],
        ))
        fleet.pump()
    wall = time.perf_counter() - t0
    hist = fleet.realization_hist()
    # Empty-histogram guard (churn 0, or every delivered event
    # unstamped): there is no p99 to report — rounding/ratio math on a
    # vacuous quantile would either crash or fabricate a perfect-zero
    # latency.  Emit a null metric with the unstamped count so the soak
    # harness sees "no signal", never "0 s p99".
    empty = hist.count == 0
    p99 = None if empty else hist.quantile(0.99)
    return {
        "metric": "realization_p99_s",
        "value": None if empty else round(p99, 6),
        "unit": "s",
        "vs_baseline": (round(REALIZATION_TARGET_S / p99, 4)
                        if p99 else None),
        "extra": {
            "n_agents": n_agents,
            "churn_events": churn,
            "events_delivered": fleet.total_events(),
            "events_measured": hist.count,
            "unstamped_excluded": fleet.realization_unstamped_total(),
            "p50_s": None if empty else round(hist.quantile(0.5), 6),
            "storm_wall_s": round(wall, 3),
            "target_s": REALIZATION_TARGET_S,
        },
    }


def fleet_storm(n_agents: int, churn: int, rounds: int,
                transport: str = "netwire") -> dict:
    """Fault-injected churn-storm soak (ROADMAP item 2): N agents — over
    the production mTLS wire by default — watching one RamStore behind a
    bounded, chunked, admission-gated DisseminationServer, driven through
    `rounds` storms that each force fleet-wide watcher overflow
    (churn > cap), with FaultPlan socket resets arming a slice of the
    fleet.  Reports `realization_p99_s` plus the resync/coalesce meters
    proving the storm was metered, not replayed."""
    import tempfile

    from antrea_tpu.apis.crd import Pod
    from antrea_tpu.controller.status import StatusAggregator
    from antrea_tpu.dissemination.faults import FaultPlan
    from antrea_tpu.dissemination.netwire import (
        Backoff,
        DisseminationServer,
        make_ca,
    )
    from antrea_tpu.dissemination.store import RamStore
    from antrea_tpu.simulator.fleet import (
        FakeAgentFleet,
        _storm_policy,
        run_churn_storm,
    )

    cap = 64
    resync_concurrency = max(4, n_agents // 32)
    store = RamStore()
    ctrl = NetworkPolicyController()
    ctrl.subscribe(store.apply)
    nodes = [f"node-{i}" for i in range(n_agents)]
    ctrl.upsert_namespace(Namespace(name="bench", labels={"team": "t0"}))
    for i, node in enumerate(nodes):
        ctrl.upsert_pod(Pod(
            name=f"pod-{i}", namespace="bench", labels={"app": "web"},
            ip=f"10.{(i >> 8) & 255}.{i & 255}.1", node=node,
        ))
    # Deterministic chaos on ~1% of the fleet: socket resets on send and
    # recv, absorbed by the reconnect + re-list path mid-storm.
    plan = FaultPlan(seed=7)
    chaos_n = max(1, n_agents // 100)
    for node in nodes[:: max(1, n_agents // chaos_n)][:chaos_n]:
        plan.prob(f"{node}.send", 0.05, "reset", times=2)
        plan.prob(f"{node}.recv", 0.05, "reset", times=2)
    t0 = time.perf_counter()
    srv = None
    if transport == "netwire":
        certdir = tempfile.mkdtemp(prefix="storm-pki-")
        make_ca(certdir)
        srv = DisseminationServer(
            store, certdir, status_aggregator=StatusAggregator(ctrl),
            watcher_max_pending=cap, resync_chunk=256,
            resync_concurrency=resync_concurrency,
            drain_max=256, send_budget=int(100_000))
        fleet = FakeAgentFleet(
            None, nodes, transport="netwire", server=srv, certdir=certdir,
            fault_plan=plan,
            backoff_factory=lambda n: Backoff(base=0.01, cap=0.1, node=n))
    else:
        fleet = FakeAgentFleet(store, nodes, max_pending=cap)
    try:
        fleet.pump()
        meters = run_churn_storm(
            ctrl, fleet, nodes, rounds=rounds, churn=churn,
            cap=cap, resync_concurrency=resync_concurrency,
            max_cycles=2000)
        # Live tail: the storm injects everything before pumping, so its
        # deliveries are all re-list replays — unstamped by design, never
        # guessed into the histogram.  Steady-state realization (the
        # ROADMAP "p99 < 1s" bar) is measured here instead: same-key
        # rewrites against the reconverged fleet, one pump per commit.
        for j in range(20):
            ctrl.upsert_antrea_policy(_storm_policy(
                "storm-0", f"203.1.{j}.0/24"))
            fleet.pump()
        fleet.pump()
    finally:
        fleet.stop()
        if srv is not None:
            srv.close()
    wall = time.perf_counter() - t0
    meters.pop("realization_p99_s")
    p99 = fleet.realization_p99_s()
    measured = fleet.realization_hist().count
    empty = measured == 0
    return {
        "metric": "realization_p99_s",
        "value": None if empty else round(p99, 6),
        "unit": "s",
        "vs_baseline": (round(REALIZATION_TARGET_S / p99, 4)
                        if not empty and p99 else None),
        "extra": {
            "regime": "storm",
            "transport": transport,
            "n_agents": n_agents,
            "watcher_cap": cap,
            "resync_concurrency": resync_concurrency,
            "faults_injected": plan.count(),
            "events_measured": measured,
            "storm_wall_s": round(wall, 3),
            "target_s": REALIZATION_TARGET_S,
            **meters,
        },
    }


def main():
    small = "--small" in sys.argv
    if "--fleet" in sys.argv and "--storm" in sys.argv:
        transport = ("inproc" if "--transport" in sys.argv
                     and sys.argv[sys.argv.index("--transport") + 1]
                     == "inproc" else "netwire")
        print(json.dumps(fleet_storm(
            _argval("--fleet", 1000), churn=_argval("--churn", 128),
            rounds=_argval("--storm", 3), transport=transport)))
        return
    if "--fleet" in sys.argv:
        print(json.dumps(fleet_realization(
            _argval("--fleet", 1000), churn=_argval("--churn", 64))))
        return
    n_ns = 2500 if small else 25000
    ctrl = NetworkPolicyController()
    # The controller's live state is acyclic (dataclasses + string-keyed
    # dicts) so refcounting reclaims everything; the cyclic collector only
    # re-scans the linearly-growing heap on every threshold crossing,
    # turning the build quadratic (measured 1.7x at 12.5k namespaces,
    # worse at 25k).  Go's benchmark runs with a concurrent GC that does
    # not stop the build this way.
    import gc

    gc.disable()
    # tracemalloc instruments every allocation (~5x slowdown measured);
    # only pay for it when the memory number is requested.
    track_mem = "--mem" in sys.argv
    if track_mem:
        tracemalloc.start()
    t0 = time.perf_counter()
    n_events = populate(ctrl, n_ns=n_ns, pods_per_ns=4, nps_per_ns=3)
    wall = time.perf_counter() - t0
    peak = 0
    if track_mem:
        _cur, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
    ps = ctrl.policy_set()
    print(json.dumps({
        "metric": "controller_full_np_compute_seconds",
        "value": round(wall, 2),
        "unit": "s",
        "vs_baseline": round(REF_SECONDS / wall, 4),
        "extra": {
            "n_namespaces": n_ns,
            "n_pods": n_ns * 4,
            "n_policies": len(ps.policies),
            "n_applied_to_groups": len(ps.applied_to_groups),
            "n_address_groups": len(ps.address_groups),
            "n_events": n_events,
            "peak_mb": round(peak / 1e6, 1) if track_mem else None,
            "reference_seconds": REF_SECONDS,
        },
    }))


if __name__ == "__main__":
    main()

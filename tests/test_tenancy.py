"""Multi-tenant serving plane (datapath/tenancy.py) — the round-9
acceptance suite.

The three contract pillars, each proved as a test:

  * PARITY — a packed N-tenant instance serves every tenant bitwise
    like N independent single-tenant instances (scalar oracle, tpuflow
    sync, tpuflow async and mesh modes).  Rung padding (phase
    capacities, entry axes) must be semantically invisible.
  * ISOLATION — one tenant's churn/attack storm evicts ZERO of another
    tenant's established flows (structural per-world quota tables) and
    its miss-queue admissions clamp at its in-queue quota (metered +
    journaled); one tenant's canary veto rolls back and degrades ONLY
    that tenant.
  * SHARED COMPILES — over 64 uneven tenants, XLA step-executable count
    equals the occupied rung-signature count, never the tenant count.
"""

import copy

import numpy as np
import pytest

from antrea_tpu.compiler.ir import PolicySet
from antrea_tpu.config import ConfigError
from antrea_tpu.datapath import OracleDatapath, TpuflowDatapath
from antrea_tpu.datapath.commit import CanaryMismatchError
from antrea_tpu.dissemination.faults import FaultPlan
from antrea_tpu.packet import PacketBatch
from antrea_tpu.simulator import gen_cluster, gen_traffic
from antrea_tpu.simulator.traffic import gen_cache_thrash, gen_syn_flood

QUOTA = 1 << 8
AFFQ = 1 << 6


def _worlds(n=2, base_seed=11, rule_counts=(8, 70)):
    """n tenant worlds: (cluster, services=None) with uneven rule sets."""
    return [gen_cluster(rule_counts[i % len(rule_counts)], n_nodes=2,
                        pods_per_node=8, seed=base_seed + i)
            for i in range(n)]


def _batch(cluster, n, seed):
    return gen_traffic(cluster.pod_ips, n, n_flows=max(8, n // 2),
                       seed=seed)


def _packed(cls, clusters, **kw):
    dp = cls(flow_slots=1 << 10, aff_slots=1 << 8, flightrec_slots=256,
             realization_slots=16, **kw)
    tids = [dp.tenant_create(f"t{i}", copy.deepcopy(c.ps), quota=QUOTA,
                             aff_quota=AFFQ)
            for i, c in enumerate(clusters)]
    return dp, tids


def _single(cls, cluster, **kw):
    return cls(copy.deepcopy(cluster.ps), flow_slots=QUOTA, aff_slots=AFFQ,
               flightrec_slots=0, realization_slots=0, **kw)


def _assert_result_parity(a, b, *, est=True, rules=True):
    assert a.code.tolist() == b.code.tolist()
    if est:
        assert a.est.tolist() == b.est.tolist()
        assert a.committed.tolist() == b.committed.tolist()
        assert a.reply.tolist() == b.reply.tolist()
    assert a.svc_idx.tolist() == b.svc_idx.tolist()
    assert a.dnat_ip.tolist() == b.dnat_ip.tolist()
    assert a.dnat_port.tolist() == b.dnat_port.tolist()
    assert a.reject_kind.tolist() == b.reject_kind.tolist()
    if rules:
        # Stable rule IDS (not indices): rung padding renumbers indices
        # but attribution resolves to the identical id strings.
        assert a.ingress_rule == b.ingress_rule
        assert a.egress_rule == b.egress_rule


@pytest.mark.parametrize("cls", [OracleDatapath, TpuflowDatapath])
def test_packed_vs_single_tenant_parity(cls):
    """Acceptance pillar 1: every tenant in a packed instance matches an
    independent single-tenant instance bitwise — fresh round (miss +
    classify + commit) AND established round (cache hits), rule-id
    attribution and per-rule stats included."""
    clusters = _worlds()
    dp, tids = _packed(cls, clusters)
    singles = [_single(cls, c) for c in clusters]
    for rnd, now in enumerate((100, 101)):
        for i, (tid, c) in enumerate(zip(tids, clusters)):
            b = _batch(c, 64, seed=40 + i)
            got = dp.tenant_step(tid, b, now)
            want = singles[i].step(b, now)
            _assert_result_parity(got, want)
    for i, tid in enumerate(tids):
        got = dp.tenant_datapath_stats(tid)
        want = singles[i].stats()
        assert got.ingress == want.ingress
        assert got.egress == want.egress
        assert got.default_allow == want.default_allow
        assert got.default_deny == want.default_deny
        # The conntrack dump decodes identically (same quota rung).
        assert (sorted(map(str, dp.tenant_dump_flows(tid, 102)))
                == sorted(map(str, singles[i].dump_flows(102))))


@pytest.mark.parametrize("cls", [OracleDatapath, TpuflowDatapath])
def test_packed_async_parity(cls):
    """Pillar 1 in ASYNC slow-path mode: tenant misses carry the tenant
    column through the shared queue, drains classify each row in its
    owner's world, and the post-drain cache matches the single-tenant
    async twin's."""
    # Same-shaped worlds (distinct seeds): the drain partition + queue
    # tenant column are under test here, not rung diversity (the sync
    # parity test owns that) — one rung halves the compile volume.
    clusters = _worlds(2, rule_counts=(8, 8))
    kw = dict(async_slowpath=True, miss_queue_slots=1 << 10,
              drain_batch=64)
    dp, tids = _packed(cls, clusters, **kw)
    singles = [_single(cls, c, **kw) for c in clusters]
    bats = [_batch(c, 48, seed=60 + i) for i, c in enumerate(clusters)]
    for i, tid in enumerate(tids):
        got = dp.tenant_step(tid, bats[i], 100)
        want = singles[i].step(bats[i], 100)
        _assert_result_parity(got, want, rules=False)
        assert got.pending.tolist() == want.pending.tolist()
    # ONE drain on the packed engine classifies BOTH tenants' rows in
    # their own worlds; each single drains its own queue.
    dp.drain_slowpath(101)
    for s in singles:
        s.drain_slowpath(101)
    for i, tid in enumerate(tids):
        got = dp.tenant_step(tid, bats[i], 102)
        want = singles[i].step(bats[i], 102)
        _assert_result_parity(got, want)
        assert (sorted(map(str, dp.tenant_dump_flows(tid, 102)))
                == sorted(map(str, singles[i].dump_flows(102))))


def test_packed_mesh_parity():
    """Pillar 1 on the mesh: verdict fields are bitwise vs a
    single-tenant mesh twin.  est/committed are cache-TOPOLOGY
    observables (the tenant shard salt legitimately re-homes flows, the
    PR 9 convention) — the FIRST round, where no cache exists, is
    asserted in full."""
    from antrea_tpu.parallel.meshpath import MeshDatapath

    clusters = _worlds(2, rule_counts=(12, 12))
    dp = MeshDatapath(n_data=2, n_rule=1, flow_slots=QUOTA, aff_slots=AFFQ,
                      flightrec_slots=64, realization_slots=0)
    tids = [dp.tenant_create(f"t{i}", copy.deepcopy(c.ps), quota=QUOTA,
                             aff_quota=AFFQ)
            for i, c in enumerate(clusters)]
    # ONE twin suffices for the parity diff (construction is the
    # expensive part — mesh step variants compile per rule shape); the
    # second tenant serves interleaved to prove world separation.
    single = MeshDatapath(copy.deepcopy(clusters[0].ps), n_data=2,
                          n_rule=1, flow_slots=QUOTA, aff_slots=AFFQ,
                          flightrec_slots=0, realization_slots=0)
    bats = [_batch(c, 64, seed=70 + i) for i, c in enumerate(clusters)]
    for now in (100, 101):
        dp.tenant_step(tids[1], bats[1], now)  # interleaved other world
        got = dp.tenant_step(tids[0], bats[0], now)
        want = single.step(bats[0], now)
        # est/committed are cache-TOPOLOGY observables on the mesh
        # (the tenant shard salt re-homes lanes, changing per-shard
        # collision/spill patterns — the PR 9 convention); VERDICT
        # fields and rule-id attribution must stay bitwise.
        _assert_result_parity(got, want, est=False)
    # Established serving works in the packed worlds (volume, not lanes).
    for tid, b in zip(tids, bats):
        assert int(dp.tenant_step(tid, b, 102).est.sum()) > 0


def test_mixed_batch_step_tenants():
    """step_tenants partitions a mixed-tenant batch per world and merges
    lane-exact: every lane equals its per-tenant dispatch image."""
    clusters = _worlds(2, rule_counts=(10, 24))
    dp, tids = _packed(TpuflowDatapath, clusters)
    twin, twin_tids = _packed(TpuflowDatapath, clusters)
    b0 = _batch(clusters[0], 32, seed=80)
    b1 = _batch(clusters[1], 32, seed=81)
    mixed = PacketBatch(
        src_ip=np.concatenate([b0.src_ip, b1.src_ip]),
        dst_ip=np.concatenate([b0.dst_ip, b1.dst_ip]),
        proto=np.concatenate([b0.proto, b1.proto]),
        src_port=np.concatenate([b0.src_port, b1.src_port]),
        dst_port=np.concatenate([b0.dst_port, b1.dst_port]),
    )
    lane_tids = np.concatenate([np.full(32, tids[0]), np.full(32, tids[1])])
    # Shuffle so the partition actually reorders lanes.
    perm = np.random.default_rng(5).permutation(64)
    mixed = PacketBatch(**{
        f: getattr(mixed, f)[perm]
        for f in ("src_ip", "dst_ip", "proto", "src_port", "dst_port")})
    lane_tids = lane_tids[perm]
    merged = dp.step_tenants(lane_tids, mixed, 100)
    # Expectation: each tenant's lanes, extracted in the SAME partition
    # order step_tenants uses, stepped through an identical twin.
    want_code = np.empty(64, np.int64)
    want_miss = 0
    for tid, twin_tid in zip(tids, twin_tids):
        lanes = np.nonzero(lane_tids == tid)[0]
        sub = PacketBatch(**{
            f: getattr(mixed, f)[lanes]
            for f in ("src_ip", "dst_ip", "proto", "src_port", "dst_port")})
        want = twin.tenant_step(twin_tid, sub, 100)
        want_code[lanes] = np.asarray(want.code)
        want_miss += want.n_miss
    assert merged.code.tolist() == want_code.tolist()
    assert merged.n_miss == want_miss


def test_isolation_attack_storm_evicts_nothing_cross_tenant():
    """Acceptance pillar 2 (quota isolation): tenant A's SYN-flood +
    cache-thrash storm — never-repeating tuples, flow universe >> its
    quota — evicts ZERO of tenant B's established flows; A's queue
    admissions clamp at its in-queue quota, metered and journaled."""
    clusters = _worlds(2, rule_counts=(6, 6))
    dp, (tid_a, tid_b) = _packed(
        TpuflowDatapath, clusters, async_slowpath=True,
        miss_queue_slots=1 << 10, drain_batch=128)
    # B establishes a hot set — SETTLED: step/drain until no lane is
    # pending, so nothing of B's sits in the shared queue when the storm
    # starts (a leftover B row draining mid-storm would be B's own
    # legitimate commit, not cross-tenant damage).
    b_hot = _batch(clusters[1], 64, seed=90)
    for now in (100, 102, 104):
        r_est = dp.tenant_step(tid_b, b_hot, now)
        dp.drain_slowpath(now + 1)
    est0 = int(r_est.est.sum())
    assert est0 > 0
    assert dp.tenant_stats()[tid_b]["queued"] == 0
    evict_b0 = dp.tenant_stats()[tid_b]["evictions_total"]
    flows_b0 = sorted(map(str, dp.tenant_dump_flows(tid_b, 104)))
    # A storms: never-repeating SYN flood + thrash universe >> quota.
    seq = 0
    for rnd in range(6):
        flood = gen_syn_flood(clusters[0].pod_ips, 256, start_seq=seq,
                              seed=1)
        seq += 256
        dp.tenant_step(tid_a, flood, 104 + rnd)
        thrash = gen_cache_thrash(clusters[0].pod_ips, 256,
                                  n_flows=QUOTA * 16, seed=rnd)
        dp.tenant_step(tid_a, thrash, 104 + rnd)
        dp.drain_slowpath(110 + rnd)
    st = dp.tenant_stats()
    # The clamp engaged (A's backlog exceeded its in-queue quota)...
    assert st[tid_a]["quota_clamps_total"] > 0
    kinds = {e["kind"] for e in dp.flightrecorder_events()}
    assert "tenant-quota-clamp" in kinds
    # ... and B lost NOTHING: zero NEW evictions, identical flow table,
    # every established flow still serves from cache.
    assert st[tid_b]["evictions_total"] == evict_b0
    assert sorted(map(str, dp.tenant_dump_flows(tid_b, 115))) == flows_b0
    r_after = dp.tenant_step(tid_b, b_hot, 116)
    assert int(r_after.est.sum()) >= est0
    # A's own world absorbed the damage (evictions inside its quota).
    assert st[tid_a]["evictions_total"] > 0


@pytest.mark.parametrize("cls", [OracleDatapath, TpuflowDatapath])
def test_tenant_canary_veto_rolls_back_only_that_tenant(cls):
    """Acceptance pillar 2 (blast radius): a canary mismatch on tenant
    A's install rolls back and degrades ONLY tenant A — tenant B and the
    default world keep their generations and stay serviceable — and A
    recovers via an ordinary re-install."""
    clusters = _worlds(2, rule_counts=(10, 10))
    dp, (tid_a, tid_b) = _packed(cls, clusters)
    ps_a2 = copy.deepcopy(clusters[0].ps)
    plan = FaultPlan(seed=1)
    plan.after("dp.canary", 0, "fail", times=1)
    dp.arm_commit_faults(plan, "dp")
    with pytest.raises(CanaryMismatchError):
        dp.tenant_install_bundle(tid_a, ps_a2)
    st = dp.tenant_stats()
    assert st[tid_a]["degraded"] == 1
    assert st[tid_a]["generation"] == 0  # rolled back, not advanced
    assert st[tid_a]["rollbacks_total"] == 1
    # Blast radius: B and the default world untouched.
    assert st[tid_b]["degraded"] == 0
    assert st[tid_b]["generation"] == 0
    assert not dp.degraded
    assert dp.generation == 0
    assert dp.tenant_install_bundle(tid_b, copy.deepcopy(
        clusters[1].ps)) == 1
    assert dp.tenant_stats()[tid_a]["degraded"] == 1  # B's pass ≠ A's cure
    kinds = {e["kind"] for e in dp.flightrecorder_events()}
    assert "tenant-rollback" in kinds
    # Recovery: the fault is exhausted; a re-install passes its canary
    # and lifts ONLY A's quarantine.
    assert dp.tenant_install_bundle(tid_a, ps_a2) == 1
    st = dp.tenant_stats()
    assert st[tid_a]["degraded"] == 0
    assert st[tid_a]["generation"] == 1


def test_shared_compile_executables_track_rungs_not_tenants():
    """Acceptance pillar 3 over 64 uneven tenants: XLA step-executable
    growth equals the occupied rung-signature count — compile cost is a
    function of the rung ladder, never of tenant count."""
    from antrea_tpu.models import forwarding as fwd_model

    # 4 world SHAPES (uneven rule counts on distinct rungs), 16 tenants
    # each: every tenant compiles its own tables, but same-rung tenants
    # must share one executable.
    shapes = [gen_cluster(n, n_nodes=2, pods_per_node=8, seed=s)
              for n, s in ((6, 1), (20, 2), (45, 3), (100, 4))]
    dp = TpuflowDatapath(flow_slots=1 << 10, aff_slots=1 << 8,
                         flightrec_slots=0, realization_slots=0)
    exec0 = fwd_model.pipeline_step_full_packed._cache_size()
    tids = []
    for i in range(64):
        c = shapes[i % 4]
        tids.append((dp.tenant_create(f"t{i}", copy.deepcopy(c.ps),
                                      quota=QUOTA, aff_quota=AFFQ), c))
    assert dp.tenant_count == 64
    rungs = dp.tenant_rungs()
    assert len(rungs) == 4  # one signature per world shape
    b = {id(c): _batch(c, 32, seed=77) for c in shapes}
    for tid, c in tids:
        dp.tenant_step(tid, b[id(c)], 100)
    execs = fwd_model.pipeline_step_full_packed._cache_size() - exec0
    assert execs == len(rungs), (
        f"{execs} step executables for 64 tenants on {len(rungs)} rungs "
        f"— compile count must track rungs, not tenants")


def test_pad_rung_floor_collapses_small_worlds():
    """Two tenants with DIFFERENT small rule counts land on the same
    rung (phase floor + entry floor) — the padding itself is what makes
    them shape-identical."""
    c1 = gen_cluster(3, n_nodes=2, pods_per_node=4, seed=21)
    c2 = gen_cluster(3, n_nodes=2, pods_per_node=4, seed=21)
    dp = TpuflowDatapath(flow_slots=1 << 10, aff_slots=1 << 8,
                         flightrec_slots=0, realization_slots=0)
    dp.tenant_create("a", copy.deepcopy(c1.ps), quota=QUOTA)
    dp.tenant_create("b", copy.deepcopy(c2.ps), quota=QUOTA)
    assert len(dp.tenant_rungs()) == 1


def test_tenant_config_rejections():
    dp = TpuflowDatapath(flow_slots=1 << 10, aff_slots=1 << 8,
                         flightrec_slots=0, realization_slots=0)
    with pytest.raises(ConfigError):
        dp.tenant_create("bad", quota=100)  # not pow2
    with pytest.raises(ConfigError):
        dp.tenant_create("bad", quota=256, aff_quota=100)
    # toServices tenants are rejected (shared service view).
    from antrea_tpu.apis.controlplane import (
        Direction, NetworkPolicy, NetworkPolicyPeer, NetworkPolicyRule,
        RuleAction, ServiceReference)

    ps = PolicySet()
    ps.policies.append(NetworkPolicy(
        uid="svc-ref", name="svc-ref",
        rules=[NetworkPolicyRule(
            direction=Direction.OUT,
            to_peer=NetworkPolicyPeer(
                to_services=[ServiceReference(namespace="d", name="s")]),
            action=RuleAction.ALLOW)],
    ))
    with pytest.raises(ConfigError):
        dp.tenant_create("svcref", ps, quota=256)
    # ... and the INSTALL path enforces the same admission rule (a later
    # push must not slip a svcref world past the create-time gate).
    tid = dp.tenant_create("clean", quota=256)
    with pytest.raises(ConfigError):
        dp.tenant_install_bundle(tid, ps)
    assert dp.tenant_stats()[tid]["generation"] == 0
    # Dual-stack engines have no tenant worlds (v4-only, like async).
    ds = TpuflowDatapath(flow_slots=1 << 8, aff_slots=1 << 6,
                         dual_stack=True, flightrec_slots=0,
                         realization_slots=0)
    with pytest.raises(ConfigError):
        ds.tenant_create("v6", quota=256)


def test_tenant_maintenance_task_registered_and_runs():
    """The 'tenant-maintain' task joins the scheduler on first
    tenant_create only, and its granted ticks age tenant worlds through
    the ordinary DRR discipline."""
    dp = TpuflowDatapath(flow_slots=1 << 10, aff_slots=1 << 8,
                         flightrec_slots=64, realization_slots=0)
    assert "tenant-maintain" not in dp._maintenance.task_names
    c = gen_cluster(8, n_nodes=2, pods_per_node=4, seed=31)
    tid = dp.tenant_create("t", copy.deepcopy(c.ps), quota=QUOTA)
    assert "tenant-maintain" in dp._maintenance.task_names
    b = _batch(c, 32, seed=32)
    dp.tenant_step(tid, b, 100)
    occupied0 = dp.tenant_cache_stats(tid)["occupied"]
    assert occupied0 > 0
    # Far past the idle timeout: the rotated fused maintain pass must
    # physically reclaim the expired rows of the tenant world.
    ran = 0
    for i in range(8):
        out = dp.maintenance_tick(now=100 + 3600 * (i + 2))
        ran += out["ran"].get("tenant-maintain", 0)
    assert ran > 0
    assert dp.tenant_cache_stats(tid)["occupied"] == 0


def test_tenant_metrics_rendered_and_registered():
    from antrea_tpu.observability.metrics import render_metrics

    clusters = _worlds(1, rule_counts=(8,))
    dp, (tid,) = _packed(TpuflowDatapath, clusters)
    dp.tenant_step(tid, _batch(clusters[0], 16, seed=41), 100)
    text = render_metrics(dp, node="n1")
    assert f'antrea_tpu_tenant_worlds{{node="n1"}} 1' in text
    for fam in ("antrea_tpu_tenant_generation",
                "antrea_tpu_tenant_flow_quota_slots",
                "antrea_tpu_tenant_flow_occupied",
                "antrea_tpu_tenant_quota_clamps_total"):
        assert f'{fam}{{tenant="{tid}",node="n1"}}' in text
    # Untenanted datapaths keep the surface absent entirely.
    bare = TpuflowDatapath(flow_slots=1 << 8, aff_slots=1 << 6,
                           flightrec_slots=0, realization_slots=0)
    assert "antrea_tpu_tenant_" not in render_metrics(bare, node="n1")


# The tenant/event/metric drift gates (tools/check_tenant.py et al. ->
# analysis passes `tenant`/`events`/`metrics`) run once for the whole
# tier-1 suite in tests/test_static_analysis.py.


def test_bench_controller_fleet_empty_histogram_guard():
    """A churn-0 (or all-unstamped) fleet run emits a NULL metric with
    the unstamped count — never a fabricated 0-second p99, never a
    crash."""
    import importlib.util
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "bench_controller", root / "bench_controller.py")
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)
    out = bc.fleet_realization(2, churn=0)
    assert out["value"] is None
    assert out["vs_baseline"] is None
    assert out["extra"]["events_measured"] == 0
    assert "unstamped_excluded" in out["extra"]
    # The normal path still reports a real quantile.
    out2 = bc.fleet_realization(2, churn=3)
    assert out2["extra"]["events_measured"] > 0
    assert out2["value"] is not None


def test_default_world_unchanged_by_tenancy():
    """The default world of a tenanted engine serves bit-identically to
    an untenanted instance — worlds swap fully out."""
    c = gen_cluster(20, n_nodes=2, pods_per_node=8, seed=51)
    dp = TpuflowDatapath(copy.deepcopy(c.ps), flow_slots=1 << 10,
                         aff_slots=1 << 8, flightrec_slots=64,
                         realization_slots=0)
    twin = TpuflowDatapath(copy.deepcopy(c.ps), flow_slots=1 << 10,
                           aff_slots=1 << 8, flightrec_slots=0,
                           realization_slots=0)
    t = dp.tenant_create("t", copy.deepcopy(c.ps), quota=QUOTA)
    b = _batch(c, 48, seed=52)
    bt = _batch(c, 48, seed=53)
    dp.tenant_step(t, bt, 99)  # interleave tenant traffic
    r1 = dp.step(b, 100)
    w1 = twin.step(b, 100)
    dp.tenant_step(t, bt, 100)
    r2 = dp.step(b, 101)
    w2 = twin.step(b, 101)
    _assert_result_parity(r1, w1)
    _assert_result_parity(r2, w2)
    assert dp.cache_stats() == twin.cache_stats()


def test_overlap_deferred_drain_metrics_land_in_owner_world():
    """Overlap mode: a tenant drain's DEFERRED finalizer (the two-slot
    staging retires it long after the dispatch's world swap exited) must
    re-enter the owning world — its rule metrics/verdict counters land
    in the tenant, never in whichever world is active at retire time."""
    clusters = _worlds(1, rule_counts=(12,))
    dp, (tid,) = _packed(
        TpuflowDatapath, clusters, async_slowpath=True,
        miss_queue_slots=1 << 10, drain_batch=64, overlap_commits=True)
    b = _batch(clusters[0], 32, seed=95)
    dp.tenant_step(tid, b, 100)
    dp.drain_slowpath(101)
    dp.flush_slowpath()  # retire the staged tenant finalizer
    got = dp.tenant_datapath_stats(tid)
    base = dp.stats()
    # The drained rows' verdicts were counted exactly once, in the
    # tenant's world; the default world saw none of them.
    assert (got.default_allow + got.default_deny
            + sum(got.ingress.values()) + sum(got.egress.values())) > 0
    assert base.default_allow == 0 and base.default_deny == 0
    assert base.ingress == {} and base.egress == {}
    # And parity with a single-tenant overlap twin still holds.
    twin = _single(TpuflowDatapath, clusters[0], async_slowpath=True,
                   miss_queue_slots=1 << 10, drain_batch=64,
                   overlap_commits=True)
    twin.step(b, 100)
    twin.drain_slowpath(101)
    twin.flush_slowpath()
    want = twin.stats()
    assert got.ingress == want.ingress and got.egress == want.egress
    assert got.default_allow == want.default_allow
    assert got.default_deny == want.default_deny


def test_tenant_stats_is_snapshot_based_never_swaps_worlds():
    """tenant_stats serves the /metrics scrape path, which runs on the
    apiserver's handler THREAD: it must read the stored world snapshots
    only — callable even while a world swap is active (previously the
    occupancy decode entered _world_ctx and would either raise the
    nesting guard or interleave with the engine thread's swap)."""
    clusters = _worlds(1, rule_counts=(8,))
    dp, (tid,) = _packed(TpuflowDatapath, clusters)
    dp.tenant_step(tid, _batch(clusters[0], 16, seed=43), 100)
    with dp._world_ctx(tid):
        st = dp.tenant_stats()  # mid-swap scrape: must not nest/raise
    assert st[tid]["occupied"] > 0
    # Consistent with the swap-based operator surface once quiescent.
    assert st[tid]["occupied"] == dp.tenant_cache_stats(tid)["occupied"]

"""Multichip datapath (parallel/meshpath.MeshDatapath): tier-1 coverage.

Runs on the 8 virtual CPU devices conftest.py forces
(`XLA_FLAGS=--xla_force_host_platform_device_count=8`), so the
sharded-vs-single-chip verdict parity, the mesh-wide epoch swap and the
replica-canary veto are exercised in CI without a TPU — unlike
tests/test_parallel.py (raw kernel parity, slow tier), these cases drive
the full ENGINE: commit plane, per-replica slow path, striped audit and
the maintenance scheduler on the mesh.

The partition-spec drift gate (analysis pass `mesh`: every sharded pytree
field has an explicit PartitionSpec or a reasoned waiver) and the
jax.shard_map / make_mesh assertions.
"""

import pathlib
import sys

import jax
import numpy as np
import pytest

from antrea_tpu.config import ConfigError
from antrea_tpu.datapath.commit import CanaryMismatchError
from antrea_tpu.datapath.tpuflow import TpuflowDatapath
from antrea_tpu.observability.metrics import render_metrics
from antrea_tpu.oracle.interpreter import Oracle
from antrea_tpu.parallel import MeshDatapath, mesh as pm
from antrea_tpu.simulator.genpolicy import gen_cluster
from antrea_tpu.simulator.genservice import gen_services
from antrea_tpu.simulator.traffic import gen_traffic

# One mesh + one knob set for every engine in this module: the jitted
# sharded step/canary builders cache by (mesh, meta), so all engines
# share ONE compiled program per variant instead of recompiling per test.
KW = dict(flow_slots=1 << 10, aff_slots=1 << 8, canary_probes=16)


@pytest.fixture(scope="module")
def world():
    cluster = gen_cluster(60, n_nodes=4, pods_per_node=8, seed=7)
    services = gen_services(8, cluster.pod_ips, seed=11)
    return cluster, services


@pytest.fixture(scope="module")
def mesh():
    return pm.make_mesh(2, 2, devices=jax.devices("cpu")[:4])


@pytest.fixture(scope="module")
def batch(world):
    cluster, services = world
    return gen_traffic(cluster.pod_ips, 256, n_flows=96, seed=3,
                       services=services, svc_fraction=0.3)


def _mesh_dp(world, mesh, **extra):
    cluster, services = world
    return MeshDatapath(cluster.ps, services, mesh=mesh, **KW, **extra)


# --------------------------------------------------------------------------
# Satellites: the drift gate + the shard_map / make_mesh contract
# --------------------------------------------------------------------------

# The partition-spec coverage gate (tools/check_mesh.py -> analysis pass
# `mesh`) runs once for the whole tier-1 suite in
# tests/test_static_analysis.py.


def test_mesh_runs_on_jax_shard_map(monkeypatch):
    """parallel/mesh calls the public `jax.shard_map` directly with the
    replication check off (mesh._shard_map argues why), and make_mesh
    never substitutes other devices for ones the default backend lacks."""
    from jax.sharding import PartitionSpec as P

    calls = []
    real = jax.shard_map

    def spy(f, **kw):
        calls.append(kw)
        return real(f, **kw)

    monkeypatch.setattr(jax, "shard_map", spy)
    mesh2 = pm.make_mesh(2, 1, devices=jax.devices("cpu")[:2])
    f = pm._shard_map(lambda x: x * 2, mesh=mesh2, in_specs=P(pm.DATA),
                      out_specs=P(pm.DATA))
    assert np.asarray(f(np.arange(4))).tolist() == [0, 2, 4, 6]
    assert [c["check_vma"] for c in calls] == [False]
    with pytest.raises(ValueError, match="need 16 devices"):
        pm.make_mesh(4, 4)  # conftest forces 8


def test_shard_affinity_hash_symmetric_and_spread():
    rng = np.random.default_rng(5)
    src = rng.integers(1, 2 ** 32, 4096, dtype=np.uint32)
    dst = rng.integers(1, 2 ** 32, 4096, dtype=np.uint32)
    proto = np.full(4096, 6, np.int32)
    sport = rng.integers(1024, 65535, 4096).astype(np.int32)
    dport = rng.integers(1, 1024, 4096).astype(np.int32)
    fwd = pm.shard_of_tuples(src, dst, proto, sport, dport, 4)
    # Deterministic + direction-symmetric: the reply leg (src/dst and
    # ports swapped) homes to the same shard as the forward leg.
    again = pm.shard_of_tuples(src, dst, proto, sport, dport, 4)
    rev = pm.shard_of_tuples(dst, src, proto, dport, sport, 4)
    np.testing.assert_array_equal(fwd, again)
    np.testing.assert_array_equal(fwd, rev)
    # Spread: no shard starves or hogs (4096 tuples over 4 shards).
    counts = np.bincount(fwd, minlength=4)
    assert counts.min() > 800 and counts.max() < 1300, counts


# --------------------------------------------------------------------------
# Tentpole: sharded full-pipeline verdict parity
# --------------------------------------------------------------------------

def test_sync_mesh_verdict_parity_vs_single_chip(world, mesh, batch):
    """The sharded stateful pipeline (per-shard private caches, pmin over
    the rule axis) serves bitwise-identical VERDICTS to the single-chip
    engine: code, service resolution, DNAT and rule attribution, across
    repeat steps.  (est/committed are cache-TOPOLOGY observables — which
    lanes sit in which direct-mapped table — and legitimately differ
    between one 2^10 table and two private 2^10 shards.)"""
    cluster, services = world
    mdp = _mesh_dp(world, mesh)
    sdp = TpuflowDatapath(cluster.ps, services, **KW)
    for t in range(2):
        rm = mdp.step(batch, 100 + t)
        rs = sdp.step(batch, 100 + t)
        for k in ("code", "svc_idx", "dnat_ip", "dnat_port"):
            np.testing.assert_array_equal(
                np.asarray(getattr(rm, k)), np.asarray(getattr(rs, k)),
                err_msg=f"step{t}:{k}")
        assert rm.ingress_rule == rs.ingress_rule, f"step{t}"
        assert rm.egress_rule == rs.egress_rule, f"step{t}"
    # The stateful fast path engaged: repeat flows hit their home shard.
    assert int(np.asarray(rm.est).sum()) > 0
    # Global census spans every replica's private table.
    c = mdp.cache_stats()
    assert c["slots"] == 2 * KW["flow_slots"]
    assert c["occupied"] > 0


def test_sync_mesh_verdict_parity_vs_oracle(world, mesh):
    """Shard-for-shard scalar-oracle parity on non-service traffic (the
    svc-free lanes are the ones the policy-only interpreter models)."""
    cluster, _services = world
    mdp = _mesh_dp(world, mesh)
    tr = gen_traffic(cluster.pod_ips, 128, n_flows=64, seed=13)
    oracle = Oracle(cluster.ps)
    for t in range(2):  # step 2 re-proves CACHED verdicts against fresh
        codes = np.asarray(mdp.step(tr, 200 + t).code)
        for i in range(tr.size):
            assert codes[i] == int(oracle.classify(tr.packet(i)).code), i


def test_spill_lanes_classify_but_never_cache_foreign(world, mesh):
    """Hash-skew overflow: a batch whose flows all home to ONE shard
    spills half its lanes to the other replica, which must classify them
    correctly (verdict parity holds) but never cache them — foreign
    tables stay empty, so direct-mapped semantics stay per-shard sound."""
    cluster, services = world
    mdp = _mesh_dp(world, mesh)
    sdp = TpuflowDatapath(cluster.ps, services, **KW)
    big = gen_traffic(cluster.pod_ips, 512, n_flows=256, seed=17)
    shard = pm.shard_of_tuples(big.src_ip, big.dst_ip, big.proto,
                               big.src_port, big.dst_port, 2)
    idx = np.nonzero(shard == 0)[0][:64]
    assert idx.size == 64, "seed must yield >= 64 shard-0 flows"
    skew = gen_traffic(cluster.pod_ips, 512, n_flows=256, seed=17).subset(idx) \
        if hasattr(big, "subset") else None
    if skew is None:
        from antrea_tpu.packet import PacketBatch

        skew = PacketBatch.from_packets([big.packet(int(i)) for i in idx])
    rm = mdp.step(skew, 300)
    rs = sdp.step(skew, 300)
    np.testing.assert_array_equal(np.asarray(rm.code), np.asarray(rs.code))
    # All 64 lanes home to replica 0 with 32 slots of home capacity
    # (B/D) — replica 1 classified the spill but cached NOTHING.
    occ = np.asarray(mdp._state.flow.keys)[:, :-1, -1] != 0
    assert occ[1].sum() == 0, "foreign shard cached a spilled flow"
    assert occ[0].sum() > 0


def test_mesh_rule_stats_equal_the_step_results_with_a_spill(world, mesh):
    """Per-rule packet and byte counters on the mesh, where half the
    lanes spill and their retry runs, equal a fold of the StepResults'
    rule ids and the batch's packet lengths."""
    from antrea_tpu.packet import PacketBatch

    cluster, _services = world
    mdp = _mesh_dp(world, mesh)
    big = gen_traffic(cluster.pod_ips, 512, n_flows=256, seed=17)
    shard = pm.shard_of_tuples(big.src_ip, big.dst_ip, big.proto,
                               big.src_port, big.dst_port, 2)
    idx = np.nonzero(shard == 0)[0][:64]
    skew = PacketBatch.from_packets([big.packet(int(i)) for i in idx])
    skew.pkt_len = (np.arange(64, dtype=np.int32) * 7) % 50
    want = ({}, {}), ({}, {})
    for t in range(2):
        res = mdp.step(skew, 300 + t)
        for (pk, by), col in zip(want, (res.ingress_rule, res.egress_rule)):
            for rid, ln in zip(col, skew.pkt_len.tolist()):
                if rid is not None:
                    pk[rid] = pk.get(rid, 0) + 1
                    by[rid] = by.get(rid, 0) + ln
    recs = mdp.step_trace()["records"][-2:]
    assert all(r["retry_lanes"] > 0 for r in recs), recs
    st = mdp.stats()
    (pin, bin_), (pout, bout) = want
    assert st.ingress == pin and st.egress == pout
    assert st.ingress_bytes == {k: v for k, v in bin_.items() if v}
    assert st.egress_bytes == {k: v for k, v in bout.items() if v}
    assert sum(pin.values()) + sum(pout.values()) > 0


def test_spill_hold_admission_serves_cached_verdicts(world, mesh):
    """admission="hold" under hash skew: after a drain, spilled
    ESTABLISHED flows must serve their real cached verdicts through the
    home-routed retry dispatch — not provisional DROP forever
    (regression: spilled lanes used to re-miss on the foreign shard on
    every step)."""
    from antrea_tpu.packet import PacketBatch

    cluster, _services = world
    adp = _mesh_dp(world, mesh, async_slowpath=True, admission="hold",
                   miss_queue_slots=1 << 12, drain_batch=256)
    # Single-chip async-hold twin: all 64 flows home to shard 0, whose
    # private table is the same size with the same slot hash — so the
    # twin has the IDENTICAL direct-mapped collision set, and pending/
    # verdicts must match lane-for-lane (collision victims legitimately
    # re-miss on both engines; spill must add NOTHING on top).
    sdp = TpuflowDatapath(cluster.ps, None, async_slowpath=True,
                          admission="hold", miss_queue_slots=1 << 12,
                          drain_batch=256, **KW)
    big = gen_traffic(cluster.pod_ips, 512, n_flows=256, seed=17)
    shard = pm.shard_of_tuples(big.src_ip, big.dst_ip, big.proto,
                               big.src_port, big.dst_port, 2)
    idx = np.nonzero(shard == 0)[0][:64]
    skew = PacketBatch.from_packets([big.packet(int(i)) for i in idx])
    for dp in (adp, sdp):
        dp.step(skew, 100)
        dp.drain_slowpath(101)
    r = adp.step(skew, 102)
    rs = sdp.step(skew, 102)
    np.testing.assert_array_equal(np.asarray(r.pending),
                                  np.asarray(rs.pending))
    np.testing.assert_array_equal(np.asarray(r.code), np.asarray(rs.code))
    # The drained flows serve their REAL verdicts through the retry
    # dispatch: far fewer pending lanes than the 32 spilled ones.
    assert int(np.asarray(r.pending).sum()) < 8
    ms = adp.mesh_stats()
    assert ms["spill_retried_total"] == ms["spill_lanes_total"] > 0


# --------------------------------------------------------------------------
# Tentpole: sharded slow path + mesh-wide epoch swap
# --------------------------------------------------------------------------

def test_async_mesh_drain_and_mesh_wide_epoch_swap(world, mesh, batch):
    cluster, services = world
    adp = _mesh_dp(world, mesh, async_slowpath=True,
                   miss_queue_slots=1 << 12, drain_batch=256)
    r0 = adp.step(batch, 100)
    sp0 = adp.slowpath_stats()
    # Per-replica bounded queues: every miss admitted to its HOME shard.
    assert int(np.asarray(r0.pending).sum()) == sum(sp0["replica_depths"])
    assert all(d > 0 for d in sp0["replica_depths"])
    epoch0 = sp0["epoch"]
    st = adp.drain_slowpath(101)
    assert st["drained"] == sum(sp0["replica_depths"])
    # ONE swap flipped every replica: single epoch bump, journaled as a
    # mesh-epoch-swap event carrying the replica count.
    assert adp.slowpath_stats()["epoch"] == epoch0 + 1
    swaps = adp.flightrecorder_events(kind="mesh-epoch-swap")
    assert swaps and swaps[-1]["replicas"] == 2
    # Drained verdicts serve from the cache now.
    r1 = adp.step(batch, 102)
    assert int(np.asarray(r1.est).sum()) > 0
    assert int(np.asarray(r1.pending).sum()) < int(np.asarray(r0.pending).sum())


def test_mesh_drain_with_oversized_explicit_pop_stays_home(world, mesh):
    """begin_drain(n) with n > drain_batch widens each replica's lane
    slice to n (the popped chunk rides the in-flight record): no
    replica's rows may overflow into the next replica's slice — i.e.
    every committed entry must sit in its HOME replica's private table
    (regression: the layout used to assume drain_batch)."""
    import jax

    from antrea_tpu.utils import ip as iputil

    cluster, _services = world
    adp = _mesh_dp(world, mesh, async_slowpath=True,
                   miss_queue_slots=1 << 12, drain_batch=128)
    tr = gen_traffic(cluster.pod_ips, 512, n_flows=256, seed=29)
    adp.step(tr, 100)
    sp = adp._slowpath
    assert sp.begin_drain(101, n=512)
    out = sp.finish_drain(102)
    assert out["drained"] > 128  # the oversized pop actually took effect
    for r in range(2):
        local = jax.tree.map(lambda x, r=r: x[r], adp._state)
        for e in adp._dump_flows_state(local, 103):
            home = pm.shard_of_tuples(
                np.array([iputil.ip_to_key(e["src"])], np.uint32),
                np.array([iputil.ip_to_key(e["dst"])], np.uint32),
                np.array([e["proto"]]), np.array([e["sport"]]),
                np.array([e["dport"]]), 2)[0]
            assert home == r, (r, e)


def test_mesh_epoch_swap_mid_drain_reclassifies_stale(world, mesh):
    """A bundle swap landing between begin_drain and finish_drain pins
    the in-flight per-replica blocks stale: they re-classify under the
    NEW tensors on every replica (counted, never published stale), and
    re-missed flows re-enqueue idempotently — the PR 6 lost-update guard
    across shards."""
    cluster, services = world
    adp = _mesh_dp(world, mesh, async_slowpath=True,
                   miss_queue_slots=1 << 12, drain_batch=256)
    tr = gen_traffic(cluster.pod_ips, 256, n_flows=64, seed=21)
    adp.step(tr, 100)
    sp = adp._slowpath
    assert sp.begin_drain(101)
    gen0 = adp.generation
    adp.install_bundle(cluster.ps, services)
    assert adp.generation == gen0 + 1
    out = sp.finish_drain(102)
    assert out["stale_reclassified"] == out["drained"] > 0
    # Idempotent re-enqueue: re-step the same traffic, drain again — the
    # same flows re-classify into the same home slots, state stays
    # coherent and verdicts stay oracle-true.
    adp.step(tr, 103)
    adp.drain_slowpath(104)
    oracle = Oracle(cluster.ps)
    codes = np.asarray(adp.step(tr, 105).code)
    pend = np.asarray(adp.step(tr, 105).pending)
    for i in range(tr.size):
        if not pend[i]:
            assert codes[i] == int(oracle.classify(tr.packet(i)).code), i


# --------------------------------------------------------------------------
# Tentpole: replica-gated commit plane (veto + fleet rollback)
# --------------------------------------------------------------------------

def test_replica_canary_veto_rolls_back_all_replicas(world, mesh):
    """Chaos: rule-table corruption on ONE replica's device copies.  A
    services-only install (rules NOT recompiled, so the corrupt copies
    survive into the candidate) must be vetoed by that replica's canary
    row — and the rollback restores the sharded snapshot, i.e. every
    replica: the generation is unchanged fleet-wide and the datapath is
    degraded until a full recompile re-places clean tensors."""
    cluster, services = world
    vdp = _mesh_dp(world, mesh)
    desc = vdp.corrupt_replica(1)
    assert "replica 1" in desc
    gen0 = vdp.generation
    with pytest.raises(CanaryMismatchError) as ei:
        vdp.install_bundle(None, gen_services(
            8, cluster.pod_ips, seed=12))
    replicas = sorted({m["replica"] for m in ei.value.mismatches
                       if "replica" in m})
    assert replicas == [1], ei.value.mismatches[:3]
    assert vdp.generation == gen0  # ONE veto rolled back ALL replicas
    assert vdp.degraded
    assert vdp.commit_stats()["replica_mismatches"].get(1, 0) > 0
    # Recovery: the full-bundle recompile re-places every copy from the
    # host mirror and its canary re-certifies all replicas.
    vdp.install_bundle(cluster.ps, services)
    assert not vdp.degraded


def test_replica_veto_watchdog_chain_in_journal(world, mesh):
    """The live-bundle watchdog catches silent per-replica corruption
    between installs, and the flight recorder reconstructs the causal
    chain — replica-canary-veto -> degrade -> recompile commit ->
    recover — in sequence order, with the scheduler's degraded-recompile
    task driving recovery."""
    cluster, services = world
    vdp = _mesh_dp(world, mesh)
    vdp.corrupt_replica(0)
    scan = vdp.canary_scan(recover=False)
    assert scan["mismatches"] > 0 and scan["degraded"]
    assert vdp.commit_stats()["replica_mismatches"].get(0, 0) > 0
    out = vdp.maintenance_tick(now=100)
    assert out["ran"].get("degraded-recompile") == 1
    assert not vdp.degraded
    kinds = [e["kind"] for e in vdp.flightrecorder_events()]
    chain = [k for k in kinds if k in ("replica-canary-veto", "degrade",
                                      "recover")]
    assert chain == ["replica-canary-veto", "degrade", "recover"], kinds


# --------------------------------------------------------------------------
# Tentpole: striped audit cursor across replicas
# --------------------------------------------------------------------------

def test_striped_audit_detects_and_repairs_replica_corruption(world, mesh,
                                                              batch):
    cluster, services = world
    mdp = _mesh_dp(world, mesh)
    sdp = TpuflowDatapath(cluster.ps, services, **KW)
    mdp.step(batch, 100)
    sdp.step(batch, 100)
    desc = mdp._audit_corrupt("verdict", now=101)
    assert "replica" in desc
    out = mdp.maintenance_force_audit(now=101)
    assert out["divergences"] >= 1 and out["repaired"] >= 1
    # The striped cursor walked EVERY replica's slice in the one sweep.
    assert out["scanned"] == 2 * KW["flow_slots"]
    ms = mdp.mesh_stats()
    assert all(n > 0 for n in ms["replica_audit_entries"]), ms
    # Eviction + lazy reclassify reconverges: verdicts match single-chip.
    rm = mdp.step(batch, 102)
    rs = sdp.step(batch, 102)
    np.testing.assert_array_equal(np.asarray(rm.code), np.asarray(rs.code))
    # A second sweep is clean.
    out2 = mdp.maintenance_force_audit(now=103)
    assert out2["divergences"] == 0


# --------------------------------------------------------------------------
# Surfaces + config validation
# --------------------------------------------------------------------------

def test_mesh_observability_surfaces(world, mesh, batch):
    mdp = _mesh_dp(world, mesh, async_slowpath=True,
                   miss_queue_slots=1 << 10, drain_batch=256)
    mdp.step(batch, 100)
    text = render_metrics(mdp, node="n0")
    for fam in ("antrea_tpu_replica_miss_queue_depth",
                "antrea_tpu_replica_canary_mismatches_total",
                "antrea_tpu_replica_audit_entries_total"):
        assert f'{fam}{{replica="0",node="n0"}}' in text, fam
        assert f'{fam}{{replica="1",node="n0"}}' in text, fam
    ms = mdp.mesh_stats()
    assert ms["mesh"] == {"data": 2, "rule": 2} and ms["devices"] == 4
    # The aggregate queue view backs the shared dump/trace plumbing.
    assert len(mdp.dump_miss_queue()) == sum(ms["replica_miss_queue_depth"])
    # Single-chip commit stats keep the (empty) replica field — schema
    # stable for scrapers either way.
    sdp = TpuflowDatapath(None, None, **KW)
    assert sdp.commit_stats()["replica_mismatches"] == {}


def test_mesh_config_rejections(world, mesh):
    cluster, services = world
    with pytest.raises(ConfigError, match="v4-only"):
        _mesh_dp(world, mesh, dual_stack=True)
    with pytest.raises(ConfigError, match="single-chip knobs"):
        _mesh_dp(world, mesh, async_slowpath=True, overlap_commits=True)
    with pytest.raises(ConfigError, match="single-chip knobs"):
        _mesh_dp(world, mesh, async_slowpath=True, autotune_drain=True)
    with pytest.raises(ConfigError, match="reshard_budget"):
        _mesh_dp(world, mesh, reshard_budget=0)
    mdp = _mesh_dp(world, mesh)
    with pytest.raises(ValueError, match="not divisible"):
        mdp.step(gen_traffic(cluster.pod_ips, 7, n_flows=7, seed=2), 100)


def _fwd_topo(n_pods=3):
    from antrea_tpu.compiler.topology import NodeRoute, Topology

    return Topology(
        node_name="node-a",
        gateway_ip="10.10.0.1",
        pod_cidr="10.10.0.0/24",
        local_pods=[(f"10.10.0.{5 + i}", 3 + i) for i in range(n_pods)],
        remote_nodes=[NodeRoute(name="node-b", node_ip="192.168.1.2",
                                pod_cidr="10.10.1.0/24")],
    )


def test_mesh_forwarding_full_walk_parity(world, mesh):
    """PR 9 follow-up (satellite): the mesh engine serves the FULL
    per-packet walk — SpoofGuard -> policy/service -> L2/L3 forward ->
    Output — through one sharded dispatch, bitwise-identical to the
    single-chip engine on every forwarding observable, and
    install_topology swaps atomically like single-chip."""
    from antrea_tpu.compiler.topology import OFPORT_TUNNEL
    from antrea_tpu.packet import PacketBatch
    from antrea_tpu.utils import ip as iputil

    cluster, services = world
    topo = _fwd_topo(3)
    mdp = _mesh_dp(world, mesh, topology=topo)
    sdp = TpuflowDatapath(cluster.ps, services, **KW, topology=topo)
    rows = [
        ("10.10.0.5", "10.10.0.6", 3),   # pod->pod local
        ("10.10.0.5", "10.10.1.9", 3),   # pod->remote (tunnel)
        ("10.10.0.6", "8.8.8.8", 4),     # pod->external via gateway
        ("10.10.0.5", "10.10.0.99", 3),  # local CIDR, no such pod
        ("10.10.1.9", "10.10.0.5", OFPORT_TUNNEL),  # tunnel ingress
        ("10.10.0.9", "10.10.0.6", 3),   # SPOOF: src not bound to port 3
        ("10.10.0.7", "10.10.0.5", 5),
        ("10.10.0.6", "10.10.0.7", 4),
    ]
    b = PacketBatch(
        src_ip=np.array([iputil.ip_to_u32(s) for s, _, _ in rows],
                        np.uint32),
        dst_ip=np.array([iputil.ip_to_u32(d) for _, d, _ in rows],
                        np.uint32),
        proto=np.full(len(rows), 6, np.int32),
        src_port=np.full(len(rows), 40000, np.int32),
        dst_port=np.full(len(rows), 80, np.int32),
        in_port=np.array([p for _, _, p in rows], np.int32),
    )
    for t in (100, 101):  # step 2: cached-entry path through the walk
        rm, rs = mdp.step(b, t), sdp.step(b, t)
        for f in ("code", "spoofed", "fwd_kind", "out_port", "peer_ip",
                  "dec_ttl", "tc_act", "tc_port", "punt", "mcast_idx",
                  "l7_redirect", "dnat_ip", "dnat_port"):
            np.testing.assert_array_equal(
                np.asarray(getattr(rm, f)), np.asarray(getattr(rs, f)),
                err_msg=f"step{t}:{f}")
    assert int(np.asarray(rm.spoofed).sum()) == 1  # the guard engaged
    # Topology swap: both engines recompute identically (replicated
    # placement re-lands on the mesh through _place_forwarding).
    topo2 = _fwd_topo(2)
    mdp.install_topology(topo2)
    sdp.install_topology(topo2)
    rm, rs = mdp.step(b, 102), sdp.step(b, 102)
    for f in ("code", "spoofed", "fwd_kind", "out_port", "dec_ttl"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rm, f)), np.asarray(getattr(rs, f)),
            err_msg=f)


def test_mesh_group_delta_o1_slot_path_with_parity(world, mesh):
    """PR 9 follow-up (satellite): incremental deltas take the O(delta)
    device slot path ON THE MESH — the per-slot rule masks upload sharded
    on the word axis (no recompile fold) — still canary-gated, still
    generation-bumping, with verdict AND attribution parity on the
    delta-affected tuples."""
    from antrea_tpu.packet import PacketBatch
    from antrea_tpu.utils import ip as iputil

    cluster, services = world
    mdp = _mesh_dp(world, mesh)
    sdp = TpuflowDatapath(cluster.ps, services, **KW)
    group = sorted(cluster.ps.address_groups)[0]
    fresh_ip = "172.31.9.9"
    cps0 = mdp._cps
    g1 = mdp.apply_group_delta(group, [fresh_ip], [])
    g2 = sdp.apply_group_delta(group, [fresh_ip], [])
    assert g1 == g2 == 1
    # The slot path, not a fold: the compiled set is untouched and one
    # delta slot is occupied — same bookkeeping as the single-chip twin.
    assert mdp._cps is cps0
    assert mdp._n_deltas == sdp._n_deltas >= 1
    tr = gen_traffic(cluster.pod_ips, 128, n_flows=64, seed=23)
    rm, rs = mdp.step(tr, 100), sdp.step(tr, 100)
    np.testing.assert_array_equal(np.asarray(rm.code), np.asarray(rs.code))
    assert rm.ingress_rule == rs.ingress_rule
    # The delta-affected tuples themselves (fresh member as src and dst).
    pods = sorted(cluster.pod_ips)[:2]
    key = iputil.ip_to_u32(fresh_ip)
    pod_u = [p if not isinstance(p, str) else iputil.ip_to_u32(p)
             for p in pods]
    db = PacketBatch(
        src_ip=np.array([key, pod_u[0]], np.uint32),
        dst_ip=np.array([pod_u[1], key], np.uint32),
        proto=np.full(2, 6, np.int32),
        src_port=np.full(2, 40000, np.int32),
        dst_port=np.full(2, 80, np.int32),
    )
    rm, rs = mdp.step(db, 101), sdp.step(db, 101)
    np.testing.assert_array_equal(np.asarray(rm.code), np.asarray(rs.code))
    assert rm.ingress_rule == rs.ingress_rule
    assert rm.egress_rule == rs.egress_rule
    # Removal leg clears through the slot path too, and the journal
    # carries the canary-gated delta commits (flightrec assertion).
    mdp.apply_group_delta(group, [], [fresh_ip])
    sdp.apply_group_delta(group, [], [fresh_ip])
    rm, rs = mdp.step(db, 102), sdp.step(db, 102)
    np.testing.assert_array_equal(np.asarray(rm.code), np.asarray(rs.code))
    ev = [e for e in mdp.flightrecorder_events(kind="commit")
          if e.get("delta")]
    assert ev and ev[-1]["outcome"] == "ok" and ev[-1]["stage"] == "settle"


# --------------------------------------------------------------------------
# np100k_mesh4's shape at a tiny size: four data replicas, rules replicated,
# a Zipf(1)-headed batch whose head overflows one replica's home slice
# --------------------------------------------------------------------------

KW4 = dict(flow_slots=1 << 12, aff_slots=1 << 8, canary_probes=16)


@pytest.fixture(scope="module")
def zipf_run(world):
    """Two steps of one skewed batch through the 4x1 mesh, the one-chip
    engine and the scalar twin -> (batch, spilled lanes, engines, results,
    the mesh's two step records)."""
    from antrea_tpu.datapath import OracleDatapath
    from antrea_tpu.packet import PacketBatch
    from antrea_tpu.parallel.meshpath import _shard_placement

    cluster, services = world
    pool = gen_traffic(cluster.pod_ips, 512, n_flows=256, seed=17,
                       services=services, svc_fraction=0.3)
    cols = np.stack([pool.src_ip.astype(np.int64), pool.dst_ip, pool.proto,
                     pool.src_port, pool.dst_port], 1)
    _, first = np.unique(cols, axis=0, return_index=True)
    flows = np.sort(first)[:64]  # 64 distinct connections
    w = 1.0 / np.arange(1, 65)  # Zipf(1): the first takes 21 % of the lanes
    lane_flow = np.random.default_rng(1).choice(flows, 256, p=w / w.sum())
    lane_flow[:8] = flows[:8]
    batch = PacketBatch.from_packets([pool.packet(int(i))
                                      for i in lane_flow])
    shard = pm.shard_of_tuples(batch.src_ip, batch.dst_ip, batch.proto,
                               batch.src_port, batch.dst_port, 4)
    assert np.bincount(shard, minlength=4).max() > 64  # one home overflows
    perm, _inv, spill = _shard_placement(shard, 4)
    mdp = MeshDatapath(cluster.ps, services, n_data=4, n_rule=1,
                       devices=jax.devices("cpu")[:4], **KW4)
    sdp = TpuflowDatapath(cluster.ps, services, **KW4)
    odp = OracleDatapath(cluster.ps, services, **KW4)
    results = [[dp.step(batch, 100 + t) for dp in (mdp, sdp, odp)]
               for t in range(2)]
    return (batch, shard, perm[spill], mdp, results,
            mdp.step_trace()["records"])


@pytest.mark.parametrize("t", [0, 1])
def test_zipf_head_overflow_answers_like_one_chip_and_oracle(zipf_run, t):
    """Sharding changes no answer: verdict, Service resolution, DNAT and
    rule ids of EVERY lane equal the one-chip engine's and the scalar
    twin's in both steps; so do `est` and `committed` — but for the retried
    lanes of the FIRST step: the retry is a second dispatch, so a lane
    whose flow's home lanes were committed a moment earlier in the same
    call reads est=1/committed=0 where an unsharded batch reads 0/1
    (allowed, once more, by the same walk).  Every spilled lane was
    re-served from home in the same call."""
    batch, _shard, spilled, _mdp, results, rec = zipf_run
    rm, rs, ro = results[t]
    for other in (rs, ro):
        for k in ("code", "svc_idx", "dnat_ip", "dnat_port", "reject_kind"):
            np.testing.assert_array_equal(
                np.asarray(getattr(rm, k)), np.asarray(getattr(other, k)),
                err_msg=f"step{t}:{k}")
        assert rm.ingress_rule == other.ingress_rule
        assert rm.egress_rule == other.egress_rule
        est, com = np.asarray(rm.est) != 0, np.asarray(rm.committed) != 0
        est_o = np.asarray(other.est) != 0
        com_o = np.asarray(other.committed) != 0
        home = np.ones(batch.size, bool)
        home[spilled] = t > 0
        np.testing.assert_array_equal(est[home], est_o[home])
        np.testing.assert_array_equal(com[home], com_o[home])
        np.testing.assert_array_equal(est | com, est_o | com_o)
    assert rec["spill_lanes"][t] == spilled.size > 0
    assert rec["retry_lanes"][t] == rec["spill_lanes"][t]
    if t:  # after two steps every spilled flow is established
        allowed = np.asarray(rm.code) == 0
        assert allowed[spilled].any()
        assert (np.asarray(rm.est)[spilled] != 0)[allowed[spilled]].all()


def test_replica_tables_hold_every_committed_flow_once_at_home(zipf_run):
    """The share tied to the whole: after the run the four private tables
    together hold every committed flow exactly once, in the replica that
    `shard_of_tuples` names — none stranded in a foreign replica by the
    spill, none twice."""
    from antrea_tpu.utils import ip as iputil

    batch, shard, _spilled, mdp, results, _rec = zipf_run
    held = {}
    for r in range(4):
        local = jax.tree.map(lambda x, r=r: x[r], mdp._state)
        for e in mdp._dump_flows_state(local, 103):
            key = (iputil.ip_to_key(e["src"]), iputil.ip_to_key(e["dst"]),
                   e["proto"], e["sport"], e["dport"])
            held.setdefault(key, []).append(r)
    assert sum(len(v) for v in held.values()) == len(mdp.dump_flows(103))
    # A committed flow: allowed, so est in the second step (its first-step
    # lanes read committed, or est where the retry served them).
    rm = results[1][0]
    committed = (np.asarray(rm.code) == 0) & (np.asarray(rm.est) != 0)
    first = results[0][0]
    assert ((np.asarray(first.committed) != 0)
            <= committed).all() and committed.sum() > 16
    for i in np.nonzero(committed)[0]:
        key = (int(batch.src_ip[i]), int(batch.dst_ip[i]),
               int(batch.proto[i]), int(batch.src_port[i]),
               int(batch.dst_port[i]))
        assert held.get(key) == [int(shard[i])], (i, key, held.get(key))
    assert all(len(v) == 1 for v in held.values())

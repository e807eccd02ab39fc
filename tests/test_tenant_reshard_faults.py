"""Tenant worlds under a fault (the other half of tests/test_tenant_reshard.py,
which keeps the fault-free grow and shrink): one world's cutover canary
veto aborts ONLY that world; quarantine on a tenanted mesh proceeds to a
real evacuation shrink and certified readmission; a world vetoing the
EVACUATION cutover serves masked until resynced; the chaos soak.

A file of its own because pytest-xdist's `loadfile` hands out whole
files: the worlds, meshes and helpers are test_tenant_reshard's.
"""

import numpy as np
import pytest

from antrea_tpu.dissemination.faults import FaultPlan
from antrea_tpu.oracle.interpreter import Oracle
from antrea_tpu.simulator.traffic import gen_syn_flood, gen_traffic

from test_tenant_reshard import (  # noqa: F401 — fixtures
    _mesh_dp, _resize_under_traffic, _step_all_in_parity, _tenants, batch,
    mesh, tenant_batches, tenant_clusters, world)

FO_KW = dict(probe_fails=2, readmit_passes=2, retry_ticks=2)


# --------------------------------------------------------------------------
# Per-tenant certified cutover: one world's veto aborts ONLY its world.
# --------------------------------------------------------------------------

def test_single_tenant_canary_veto_aborts_only_that_world(
        world, mesh, batch, tenant_clusters, tenant_batches):
    dp = _mesh_dp(world, mesh)
    tids = _tenants(dp, tenant_clusters, n=3)
    oracles = [Oracle(tenant_clusters[i].ps) for i in range(3)]
    tbs = tenant_batches[:3]
    dp.step(batch, 100)
    for i, tid in enumerate(tids):
        dp.tenant_step(tid, tbs[i], 100)

    victim = tids[1]
    plan = FaultPlan(seed=9)
    plan.every(f"n0.tenant_canary.t{victim}", 1, "forced", times=1)
    dp.arm_reshard_faults(plan, "n0")

    dp.reshard_begin(4)
    t = _resize_under_traffic(dp, batch, tids, tbs, oracles, 101)
    # The FLEET flipped — one tenant's veto never aborts the resize.
    assert dp._n_data == 4 and dp._topo_gen == 1

    ts = dp.tenant_stats()
    assert ts[victim]["latched"] == 1
    assert ts[victim]["topology_generation"] == 0
    assert ts[victim]["reshard_vetoes_total"] == 1
    for tid in tids:
        if tid != victim:
            assert ts[tid]["latched"] == 0
            assert ts[tid]["topology_generation"] == 1

    # Journal chain pinned: the veto emits tenant-rollback THEN
    # tenant-reshard-veto for the victim, the other worlds flip, the
    # fleet cutover lands last; no fleet-wide abort.
    ev = dp.flightrecorder_events()
    kinds = [e["kind"] for e in ev]
    assert "reshard-abort" not in kinds
    vetoes = [e for e in ev if e["kind"] == "tenant-reshard-veto"]
    assert len(vetoes) == 1 and vetoes[0]["tenant"] == victim
    rollbacks = [e for e in ev if e["kind"] == "tenant-rollback"]
    assert any(e["tenant"] == victim for e in rollbacks)
    assert kinds.index("tenant-rollback") < kinds.index("tenant-reshard-veto")
    cut = {e["tenant"] for e in ev if e["kind"] == "tenant-reshard-cutover"}
    assert cut == {tid for tid in tids if tid != victim}
    assert kinds.index("tenant-reshard-veto") < kinds.index("reshard-cutover")

    # The latched world keeps serving its OLD topology in parity.
    _step_all_in_parity(dp, tids, tbs, oracles, t, "post-veto")

    # Resync re-migrates + re-certifies + flips the latched world.
    res = dp.tenant_reshard_resync(victim, t + 1)
    assert res.get("resynced") == 1, res
    ts = dp.tenant_stats()
    assert ts[victim]["latched"] == 0
    assert ts[victim]["topology_generation"] == dp._topo_gen
    _step_all_in_parity(dp, tids, tbs, oracles, t + 2, "post-resync")
    # A second resync is a fleet-aligned no-op.
    assert dp.tenant_reshard_resync(victim, t + 3).get(
        "reason") == "fleet-aligned"


# --------------------------------------------------------------------------
# Failover composition: quarantine on a tenanted mesh proceeds to a
# REAL evacuation shrink and certified readmission grows back.
# --------------------------------------------------------------------------

def test_quarantine_evacuates_and_readmits_with_live_worlds(
        world, mesh, batch, tenant_clusters, tenant_batches):
    dp = _mesh_dp(world, mesh, failover=True, failover_knobs=FO_KW)
    tids = _tenants(dp, tenant_clusters, n=2)
    oracles = [Oracle(tenant_clusters[i].ps) for i in range(2)]
    tbs = tenant_batches[:2]
    dp.step(batch, 100)
    for i, tid in enumerate(tids):
        dp.tenant_step(tid, tbs[i], 100)

    plan = FaultPlan(seed=5)
    plan.every("n0.replica_dead", 1, "r1", times=6)
    dp.arm_failover_faults(plan, "n0")

    t, seen_pending = 101, None
    while dp.failover_stats()["phase"] != "evacuated":
        dp.step(batch, t)
        _step_all_in_parity(dp, tids, tbs, oracles, t, "mid-evac")
        fs = dp.failover_stats()
        if fs["phase"] in ("quarantined", "evacuating") \
                and seen_pending is None:
            seen_pending = fs["tenants_pending_evacuation"]
        dp.maintenance_tick(now=t)
        t += 1
        assert t < 400, dp.failover_stats()

    # While quarantined, GET /failover names every world still awaiting
    # the evacuation flip; after the flip the list is empty.
    assert seen_pending == sorted(tids)
    assert dp.failover_stats()["tenants_pending_evacuation"] == []
    ts = dp.tenant_stats()
    for tid in tids:
        assert ts[tid]["latched"] == 0
        assert ts[tid]["topology_generation"] == dp._topo_gen
    _step_all_in_parity(dp, tids, tbs, oracles, t, "post-evac")

    # Per-world quarantine context journaled alongside the fleet event.
    q = [e for e in dp.flightrecorder_events()
         if e["kind"] == "replica-quarantine" and "tenant" in e]
    assert {e["tenant"] for e in q} == set(tids)

    # Fault site exhausted -> probes pass -> certified readmission
    # grows back the same tenant-aware way.
    while dp.failover_stats()["phase"] != "healthy":
        dp.step(batch, t)
        _step_all_in_parity(dp, tids, tbs, oracles, t, "readmit")
        dp.maintenance_tick(now=t)
        t += 1
        assert t < 800, dp.failover_stats()
    assert dp._n_data == 2
    ts = dp.tenant_stats()
    for tid in tids:
        assert ts[tid]["latched"] == 0
        assert ts[tid]["topology_generation"] == dp._topo_gen
    _step_all_in_parity(dp, tids, tbs, oracles, t, "post-readmit")


@pytest.mark.chaos
def test_evacuation_veto_masks_only_that_world_until_resync(
        world, mesh, batch, tenant_clusters, tenant_batches):
    """A world vetoing the EVACUATION cutover pins its per-world
    _fo_mask (dead old-topology index, survivor width, survivor gen)
    and serves MASKED on its own old topology — verdict-safe — while
    the fleet and the other world complete the shrink; resync evacuates
    it for real using the pinned skip mapping."""
    dp = _mesh_dp(world, mesh, failover=True, failover_knobs=FO_KW)
    tids = _tenants(dp, tenant_clusters, n=2)
    oracles = [Oracle(tenant_clusters[i].ps) for i in range(2)]
    tbs = tenant_batches[:2]
    dp.step(batch, 100)
    for i, tid in enumerate(tids):
        dp.tenant_step(tid, tbs[i], 100)

    plan = FaultPlan(seed=5)
    plan.every("n0.replica_dead", 1, "r1", times=6)
    dp.arm_failover_faults(plan, "n0")
    vplan = FaultPlan(seed=9)
    vplan.every(f"n0.tenant_canary.t{tids[0]}", 1, "forced", times=1)
    dp.arm_reshard_faults(vplan, "n0")

    t = 101
    while dp.failover_stats()["phase"] != "evacuated":
        dp.step(batch, t)
        _step_all_in_parity(dp, tids, tbs, oracles, t, "mid-evac")
        dp.maintenance_tick(now=t)
        t += 1
        assert t < 400, dp.failover_stats()

    ts = dp.tenant_stats()
    assert ts[tids[0]]["latched"] == 1
    assert ts[tids[1]]["latched"] == 0
    assert dp.failover_stats()["tenants_pending_evacuation"] == [tids[0]]
    # Masked serving on the old topology stays in parity.
    _step_all_in_parity(dp, tids, tbs, oracles, t, "latched-masked")

    res = dp.tenant_reshard_resync(tids[0], t + 1)
    assert res.get("resynced") == 1, res
    assert dp.tenant_stats()[tids[0]]["latched"] == 0
    assert dp.failover_stats()["tenants_pending_evacuation"] == []
    _step_all_in_parity(dp, tids, tbs, oracles, t + 2, "post-resync")


# --------------------------------------------------------------------------
# Chaos soak (satellite): replica kill under 8 live worlds with mixed
# SYN-flood + steady traffic through quarantine -> evacuate -> readmit.
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_soak_replica_kill_under_syn_flood_eight_worlds(
        world, mesh, tenant_clusters, tenant_batches):
    cluster, services = world
    dp = _mesh_dp(world, mesh, failover=True, failover_knobs=FO_KW,
                  async_slowpath=True, miss_queue_slots=1 << 10,
                  drain_batch=128)
    tids = _tenants(dp, tenant_clusters)
    oracles = [Oracle(c.ps) for c in tenant_clusters]
    tbs = list(tenant_batches)
    steady = gen_traffic(cluster.pod_ips, 128, n_flows=48, seed=3,
                         services=services, svc_fraction=0.3)
    dp.step(steady, 100)
    for i, tid in enumerate(tids):
        dp.tenant_step(tid, tbs[i], 100)
    for k in range(8):
        dp.drain_slowpath(101 + k)
    est_before = {}
    for i, tid in enumerate(tids):
        r = dp.tenant_step(tid, tbs[i], 110)
        est_before[tid] = np.asarray(r.est).astype(bool).copy()
        assert est_before[tid].any(), f"w{tid} established nothing"

    # The maintenance grant splits across the default world + 8 tenant
    # worlds, so the evacuation shrink needs ~9x the migration ticks of
    # the untenanted arc — keep the replica dead well past the flip
    # (times=6 would heal BEFORE it and merely unmask).
    plan = FaultPlan(seed=5)
    plan.every("n0.replica_dead", 1, "r1", times=40)
    dp.arm_failover_faults(plan, "n0")

    t, seq, phases = 111, 0, set()
    while True:
        # Adversarial default-world load: never-repeating 5-tuples so
        # every lane is a miss-queue admission, round-robined with the
        # steady established mix.
        if t % 2:
            dp.step(gen_syn_flood(cluster.pod_ips, 128, start_seq=seq), t)
            seq += 128
        else:
            dp.step(steady, t)
        # Every world serves every tick; zero non-parity verdicts
        # through the whole quarantine -> evacuate -> readmit arc.
        _step_all_in_parity(dp, tids, tbs, oracles, t, "soak")
        phases.add(dp.failover_stats()["phase"])
        dp.maintenance_tick(now=t)
        t += 1
        # Phase is sampled per tick but quarantine -> evacuation and
        # evacuated -> readmitting are sub-tick transitions (the PR 19
        # loop closure auto-proceeds inside one maintenance tick), so
        # the JOURNAL is the arc's ground truth: done once the replica
        # was quarantined, evacuated AND certified back in, and the
        # plane reads healthy again.
        if dp.failover_stats()["phase"] == "healthy":
            kinds = {e["kind"] for e in dp.flightrecorder_events()}
            if {"replica-quarantine", "replica-evacuate",
                    "replica-readmit"} <= kinds:
                break
        assert t < 1200, (dp.failover_stats(), sorted(phases))
    assert phases - {"healthy"}, "the fault never perturbed serving"
    # Soak on for a tail of mixed traffic at full width post-recovery.
    for _ in range(12):
        if t % 2:
            dp.step(gen_syn_flood(cluster.pod_ips, 128, start_seq=seq), t)
            seq += 128
        else:
            dp.step(steady, t)
        _step_all_in_parity(dp, tids, tbs, oracles, t, "soak-tail")
        dp.maintenance_tick(now=t)
        t += 1
    assert dp._n_data == 2

    # Established-flow continuity: rows homed on the DEAD replica
    # re-miss by design (the skip-replica evacuation migrates nothing
    # from it — verdict-safe re-classification, parity held every tick
    # above), so a world's cache can run cold mid-arc; once the re-miss
    # burst drains, every world's established set is back in full.
    for _ in range(3):  # serve -> drain rounds settle the burst (the
        for i, tid in enumerate(tids):   # flood shares the bounded
            dp.tenant_step(tid, tbs[i], t)  # queue, so one pass can't)
        for k in range(8):
            dp.drain_slowpath(t)
            t += 1
    kept = total = 0
    for i, tid in enumerate(tids):
        r = dp.tenant_step(tid, tbs[i], t)
        est = np.asarray(r.est).astype(bool)
        assert est.any(), f"w{tid} serves nothing from cache post-soak"
        kept += int(est[est_before[tid]].sum())
        total += int(est_before[tid].sum())
    assert kept / total > 0.85, (kept, total)
    kinds = [e["kind"] for e in dp.flightrecorder_events()]
    assert "replica-quarantine" in kinds
    assert "replica-evacuate" in kinds
    assert "replica-readmit" in kinds
    assert "tenant-reshard-veto" not in kinds

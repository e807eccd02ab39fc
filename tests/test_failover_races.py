"""Replica-loss failover racing something else (the other half of
tests/test_failover.py, which keeps the kill / evacuate / readmit story
and the operator surfaces): a kill mid-drain requeues the dead queue, a
kill preempts an in-flight ordinary resize, a corrupted survivor vetoes
the evacuation and the old mesh serves, a probe that heals before the
flip unmasks without a resize.

A file of its own because pytest-xdist's `loadfile` hands out whole
files: the world, mesh and helpers are test_failover's.
"""

import numpy as np

from antrea_tpu.datapath.tpuflow import TpuflowDatapath
from antrea_tpu.dissemination.faults import FaultPlan
from antrea_tpu.observability.metrics import render_metrics
from antrea_tpu.simulator.traffic import gen_traffic

from test_failover import (
    ASYNC_KW, FO_KW, KW, _chain_indices, _kill, _mesh_dp, _run_until,
    _verdict_parity, batch, mesh, world)  # noqa: F401 — fixtures


# --------------------------------------------------------------------------
# Chaos: kill mid-drain (async) — queues requeue, serialization holds
# --------------------------------------------------------------------------

def test_replica_kill_mid_drain_requeues_dead_queue(world, mesh):
    """Async chaos: kill the replica while its miss queue holds
    undrained rows and a drain is PINNED in flight.  The scheduler's one
    serialization point defers the whole tick (no quarantine mid-drain);
    after finish_drain the quarantine requeues the dead queue VERBATIM
    onto survivors, the evacuation carries them across the flip, and the
    post-flip drain classifies every row oracle-true."""
    from antrea_tpu.oracle.interpreter import Oracle

    cluster, _services = world
    mdp = _mesh_dp(world, mesh, **ASYNC_KW, failover=True,
                   failover_knobs=FO_KW)
    tr = gen_traffic(cluster.pod_ips, 256, n_flows=128, seed=31)
    mdp.step(tr, 100)  # misses sit queued, undrained
    assert mdp.slowpath_stats()["replica_depths"][1] > 0
    _kill(mdp, replica=1)

    sp = mdp._slowpath
    assert sp.begin_drain(101, 32)  # PARTIAL drain pinned in flight
    out = mdp.maintenance_tick(now=102)
    assert out["blocked"] == "inflight-drain"
    assert "replica-health" in out["deferred"]
    assert mdp.failover_stats()["phase"] == "healthy"  # nothing probed
    sp.finish_drain(103)
    st1 = mdp.slowpath_stats()
    depth1, dead_depth = st1["depth"], st1["replica_depths"][1]
    assert depth1 > 0 and dead_depth > 0  # backlog survived the drain

    # Drive the probe task DIRECTLY to the quarantine (a full tick would
    # first run the drain task and empty the queues — here the dead
    # queue must still hold its backlog when the quarantine requeues it).
    mdp._maint_replica_health(104, 64)
    mdp._maint_replica_health(105, 64)
    st = mdp.failover_stats()
    assert st["phase"] in ("quarantined", "evacuating"), st
    assert st["requeued_total"] == dead_depth  # verbatim, none dropped
    sps = mdp.slowpath_stats()
    assert sps["depth"] == depth1  # nothing lost: survivors hold it all
    assert sps["replica_depths"][1] == 0  # the dead queue is empty

    t = _run_until(mdp, 106, "evacuated")
    sps = mdp.slowpath_stats()
    assert len(sps["replica_depths"]) == 1
    mdp.drain_slowpath(t)
    oracle = Oracle(cluster.ps)
    r = mdp.step(tr, t + 1)
    codes, pend = np.asarray(r.code), np.asarray(r.pending)
    assert (pend == 0).sum() > 0
    for i in range(tr.size):
        if not pend[i]:
            assert codes[i] == int(oracle.classify(tr.packet(i)).code), i


# --------------------------------------------------------------------------
# Chaos: kill mid-(ordinary)-resize — the emergency preempts the elective
# --------------------------------------------------------------------------

def test_replica_kill_preempts_inflight_ordinary_resize(world, mesh, batch):
    """Mid-resize chaos: an elective grow to 4 is mid-migration when the
    replica dies.  The quarantine ABORTS the elective resize (its target
    may involve the dead replica) and installs the emergency evacuation
    in its place; the journal shows the preemption between quarantine
    and the emergency begin."""
    cluster, services = world
    mdp = _mesh_dp(world, mesh, failover=True, failover_knobs=FO_KW)
    sdp = TpuflowDatapath(cluster.ps, services, **KW)
    mdp.step(batch, 100)
    sdp.step(batch, 100)
    mdp.reshard_begin(4)
    mdp.maintenance_tick(now=101)  # a migration window runs
    assert mdp.reshard_status()["phase"] in ("migrate", "catchup")
    _kill(mdp, replica=1)
    t = _run_until(mdp, 102, "evacuated", sdp=sdp, batch=batch)
    assert mdp._n_data == 1
    rs = mdp.reshard_stats()
    assert rs["aborts_total"] == 1 and rs["cutovers_total"] == 1
    ev = mdp.flightrecorder_events()
    kinds = [e["kind"] for e in ev]
    idx = _chain_indices(kinds, [
        "reshard-begin", "replica-quarantine", "reshard-abort",
        "reshard-begin", "reshard-cutover", "replica-evacuate"])
    assert "quarantine preempts" in ev[idx[2]]["reason"]
    assert "skip_replica" not in ev[idx[0]]  # the elective grow
    assert ev[idx[3]]["skip_replica"] == 1   # the emergency shrink
    _verdict_parity(mdp.step(batch, t), sdp.step(batch, t), "post-preempt")


# --------------------------------------------------------------------------
# Chaos: corrupted survivor vetoes the emergency cutover
# --------------------------------------------------------------------------

def test_corrupted_survivor_vetoes_evacuation_old_mesh_serves(world, mesh,
                                                              batch):
    """The certified-emergency bar: corrupt the SURVIVOR topology's rule
    copies mid-evacuation.  The replica-resolved canary vetoes the flip
    — the OLD mesh keeps serving with the dead replica masked (parity
    holds), quarantine stays pending — and the scheduled retry builds a
    fresh, clean survivor topology that completes."""
    cluster, services = world
    mdp = _mesh_dp(world, mesh, failover=True, failover_knobs=FO_KW)
    sdp = TpuflowDatapath(cluster.ps, services, **KW)
    mdp.step(batch, 100)
    sdp.step(batch, 100)
    _kill(mdp, replica=1)
    t = _run_until(mdp, 101, "evacuating", sdp=sdp, batch=batch)
    desc = mdp._reshard.corrupt_target(0)  # the lone survivor replica
    assert "replica 0" in desc
    t = _run_until(mdp, t, "quarantined", sdp=sdp, batch=batch)
    # Vetoed: old topology, mask still serving, quarantine pending.
    st = mdp.failover_stats()
    assert mdp._n_data == 2 and mdp._topo_gen == 0
    assert st["mask_active"] == 1 and st["quarantined_shard"] == 1
    assert st["evacuations_total"] == 0
    assert mdp.reshard_stats()["aborts_total"] == 1
    kinds = [e["kind"] for e in mdp.flightrecorder_events()]
    _chain_indices(kinds, ["replica-quarantine", "reshard-begin",
                           "replica-canary-veto", "reshard-abort"])
    assert "replica-evacuate" not in kinds
    _verdict_parity(mdp.step(batch, t), sdp.step(batch, t), "masked-serving")
    # The quarantined gauge reads 1 for the dead shard while pending.
    text = render_metrics(mdp, node="n0")
    assert 'antrea_tpu_failover_quarantined{shard="1"' in text
    for line in text.splitlines():
        if line.startswith('antrea_tpu_failover_quarantined{shard="1"'):
            assert line.rsplit(" ", 1)[1] == "1", line
    # The retry (after retry_ticks) places FRESH target rules and flips.
    t = _run_until(mdp, t + 1, "evacuated", sdp=sdp, batch=batch)
    st = mdp.failover_stats()
    assert st["evacuations_total"] == 1 and mdp._n_data == 1
    _verdict_parity(mdp.step(batch, t), sdp.step(batch, t), "post-retry")


# --------------------------------------------------------------------------
# Readmission: pre-flip heal unmasks; operator surface drives the resize
# --------------------------------------------------------------------------

def test_probe_heal_before_flip_unmasks_without_resize(world, mesh, batch):
    """A probe false-positive heals BEFORE the evacuation cuts over:
    readmission is just dropping the mask — the in-flight evacuation
    aborts, the topology generation never moves, and the journal records
    the unmask-gated readmit."""
    cluster, services = world
    mdp = _mesh_dp(world, mesh, failover=True, failover_knobs=FO_KW)
    sdp = TpuflowDatapath(cluster.ps, services, **KW)
    mdp.step(batch, 100)
    sdp.step(batch, 100)
    plan = FaultPlan(seed=5)  # exactly 2 failed rounds, then clean
    plan.after("n0.replica_dead", 0, "r1", times=2)
    mdp.arm_failover_faults(plan, "n0")
    t = _run_until(mdp, 101, "evacuating", sdp=sdp, batch=batch)
    assert mdp.failover_stats()["mask_active"] == 1
    t = _run_until(mdp, t, "healthy", sdp=sdp, batch=batch)
    st = mdp.failover_stats()
    assert mdp._n_data == 2 and mdp._topo_gen == 0  # never flipped
    assert st["readmissions_total"] == 1 and st["evacuations_total"] == 0
    assert st["mask_active"] == 0
    ev = mdp.flightrecorder_events()
    readmits = [e for e in ev if e["kind"] == "replica-readmit"]
    assert len(readmits) == 1
    assert readmits[0]["gate"] == "unmask" and readmits[0]["replica"] == 1
    aborts = [e for e in ev if e["kind"] == "reshard-abort"]
    assert any("healed" in e["reason"] for e in aborts)
    _verdict_parity(mdp.step(batch, t), sdp.step(batch, t), "post-unmask")

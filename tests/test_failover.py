"""Replica-loss failover plane (parallel/failover.py): tier-1 + chaos.

Kill a data replica UNDER LIVE TRAFFIC — mid-churn, mid-drain and
mid-(ordinary)-resize — and hold the PR bar: bitwise verdict parity vs
the single-chip twin and the scalar oracle on every classified lane,
est continuity for survivor-resident established flows, a bounded
asserted re-miss burst for the dead replica's flows, a canary-certified
emergency cutover (a corrupted survivor vetoes and the OLD mesh keeps
serving with quarantine pending), certified re-admission (auto and
operator), and a journal that reconstructs the probe-fail -> quarantine
-> evacuate -> readmit causal chain from events alone.

The kills that race a drain, a resize, a corrupted survivor or a healing
probe are in tests/test_failover_races.py (`loadfile` hands out whole
files), which borrows the world and helpers below.

Engines share the module-scoped mesh + KW so the jitted sharded step
builders (keyed by (mesh, meta)) compile once per variant.
"""

import json
import pathlib
import sys
import urllib.request

import jax
import numpy as np
import pytest

from antrea_tpu.datapath.tpuflow import TpuflowDatapath
from antrea_tpu.dissemination.faults import FaultPlan
from antrea_tpu.observability.metrics import render_metrics
from antrea_tpu.parallel import MeshDatapath, mesh as pm
from antrea_tpu.simulator.genpolicy import gen_cluster
from antrea_tpu.simulator.genservice import gen_services
from antrea_tpu.simulator.traffic import gen_traffic

KW = dict(flow_slots=1 << 10, aff_slots=1 << 8, canary_probes=16)
ASYNC_KW = dict(async_slowpath=True, miss_queue_slots=1 << 12,
                drain_batch=256)
# Fast state machine for tests: quarantine on 2 consecutive failed
# probes, readmit after 2 quiet rounds, retry a vetoed evacuation after
# 2 ticks.
FO_KW = dict(probe_fails=2, readmit_passes=2, retry_ticks=2)


# Rows a maintenance tick may migrate.  These suites assert what holds
# DURING and AFTER a resize (parity every tick, no established-flow loss,
# the journal chain), not how many ticks one takes: at the engine's
# default of 256 a 2x1024-slot resize took 13 ticks, each re-proving its
# audit window through the eager walk; at 1024 it still spans several
# ticks with traffic between them, and the tick loops below assert it.
RESHARD_BUDGET = 1024
MIN_RESIZE_TICKS = 3


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
    return MeshDatapath(cluster.ps, services, mesh=mesh,
                        **{"reshard_budget": RESHARD_BUDGET, **KW, **extra})


def _kill(mdp, replica=1, times=-1, seed=5):
    """Arm a deterministic persistent death of `replica` (every probe
    round reads it as diverged) -> the plan, for quiesce()/re-arm."""
    plan = FaultPlan(seed=seed)
    plan.every("n0.replica_dead", 1, f"r{replica}", times=times)
    mdp.arm_failover_faults(plan, "n0")
    return plan


def _run_until(mdp, t, phase, sdp=None, batch=None, deadline=500):
    """Tick (stepping live traffic each tick when batch is given, with
    parity against the twin) until the plane reaches `phase`."""
    while mdp.failover_stats()["phase"] != phase:
        if batch is not None:
            rm = mdp.step(batch, t)
            if sdp is not None:
                _verdict_parity(rm, sdp.step(batch, t), f"t={t}")
        mdp.maintenance_tick(now=t)
        t += 1
        assert t < deadline, mdp.failover_stats()
    return t


def _verdict_parity(rm, rs, msg=""):
    """Bitwise verdict parity on every CLASSIFIED lane (pending lanes
    compare pending-for-pending — which lanes re-miss under a topology
    change is a cache-topology observable, the test_reshard caveat)."""
    ok = np.ones(len(np.asarray(rm.code)), bool)
    if rm.pending is not None:
        ok = (np.asarray(rm.pending) == 0) & (np.asarray(rs.pending) == 0)
    for k in ("code", "svc_idx", "dnat_ip", "dnat_port"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rm, k))[ok], np.asarray(getattr(rs, k))[ok],
            err_msg=f"{msg}:{k}")
    ing_m = [r for r, o in zip(rm.ingress_rule, ok) if o]
    ing_s = [r for r, o in zip(rs.ingress_rule, ok) if o]
    egr_m = [r for r, o in zip(rm.egress_rule, ok) if o]
    egr_s = [r for r, o in zip(rs.egress_rule, ok) if o]
    assert ing_m == ing_s, msg
    assert egr_m == egr_s, msg
    return ok


def _slots(b, n_slots=1 << 10):
    """Flow-cache slot per lane (the D-independent direct-mapped hash:
    models/pipeline.py line ~974) — collision EXCLUSION evidence for the
    est-continuity watch: a lane whose slot another flow claims can be
    evicted by ordinary direct-mapped dynamics (the test_reshard
    cache-topology caveat), which is not a failover flap."""
    from antrea_tpu.ops import hashing

    h = hashing.flow_hash(
        np.asarray(b.src_ip, np.uint32), np.asarray(b.dst_ip, np.uint32),
        np.asarray(b.proto), np.asarray(b.src_port),
        np.asarray(b.dst_port), xp=np)
    return (h & np.uint32(n_slots - 1)).astype(np.int64)


def _claim_cols(b, r=None, n_slots=1 << 10):
    """Per-lane slot columns a batch's commits may CLAIM: the forward
    lookup slot plus the reply-row slot (committed allow flows insert a
    reverse entry keyed on the DNAT endpoint —
    models/pipeline._fused_pack_rows: flow_hash(dnat_ip, src, proto,
    dnat_port, sport)).  Both the plain and the DNAT'd reverse variants
    ride along (over-exclusion only shrinks the watch)."""
    from antrea_tpu.ops import hashing

    src = np.asarray(b.src_ip, np.uint32)
    dst = np.asarray(b.dst_ip, np.uint32)
    proto = np.asarray(b.proto)
    sport, dport = np.asarray(b.src_port), np.asarray(b.dst_port)
    cols = [_slots(b, n_slots),
            (hashing.flow_hash(dst, src, proto, dport, sport, xp=np)
             & np.uint32(n_slots - 1)).astype(np.int64)]
    if r is not None:
        dn = np.asarray(r.dnat_ip, np.uint32)
        dp = np.asarray(r.dnat_port)
        cols.append((hashing.flow_hash(dn, src, proto, dp, sport, xp=np)
                     & np.uint32(n_slots - 1)).astype(np.int64))
    return cols


def _chain_indices(kinds, chain):
    """Assert every kind in `chain` occurs, in causal order; -> indices."""
    idx, pos = [], -1
    for want in chain:
        nxt = next((i for i in range(pos + 1, len(kinds))
                    if kinds[i] == want), None)
        assert nxt is not None, (want, kinds)
        idx.append(nxt)
        pos = nxt
    return idx


# --------------------------------------------------------------------------
# Satellite: the plane off is free — same compiled step, disabled surface
# --------------------------------------------------------------------------

def test_failover_disabled_is_free_and_surfaces_disabled_shape(world, mesh,
                                                               batch):
    """The acceptance floor: with the plane disabled (the default) the
    mesh serves the IDENTICAL compiled step — the step builder cache is
    keyed by (mesh, meta, has_arp) only, and a failover-enabled twin
    resolves to the very same jitted executable (byte-identical HLO by
    construction), with bitwise-equal step results.  The disabled
    observability surface reports the stable disabled shape."""
    from antrea_tpu.parallel.meshpath import _mesh_step_full_fn

    a = _mesh_dp(world, mesh)
    b = _mesh_dp(world, mesh, failover=True)
    assert a._meta_step == b._meta_step
    for has_arp in (False, True):
        assert (_mesh_step_full_fn(a._mesh, a._meta_step, has_arp)
                is _mesh_step_full_fn(b._mesh, b._meta_step, has_arp))
    ra, rb = a.step(batch, 100), b.step(batch, 100)
    for k in ("code", "svc_idx", "dnat_ip", "dnat_port", "est"):
        np.testing.assert_array_equal(np.asarray(getattr(ra, k)),
                                      np.asarray(getattr(rb, k)), k)
    st = a.failover_stats()
    assert st["enabled"] == 0 and st["phase"] == "disabled"
    assert st["quarantined_shard"] is None and st["probes_total"] == 0
    with pytest.raises(RuntimeError, match="failover"):
        a.failover_readmit()
    # Disabled plane renders NO failover metric families.
    assert "antrea_tpu_failover" not in render_metrics(a, node="n0")


def test_healthy_mesh_probes_clean_and_never_quarantines(world, mesh, batch):
    """The false-positive floor: an unfaulted mesh probes clean round
    after round — zero probe failures, zero quarantines, phase healthy —
    and the replica-health task is metered in the tick ledger."""
    mdp = _mesh_dp(world, mesh, failover=True)
    mdp.step(batch, 100)
    for t in range(101, 107):
        out = mdp.maintenance_tick(now=t)
        assert out["ran"].get("replica-health", 0) > 0
    st = mdp.failover_stats()
    assert st["phase"] == "healthy" and st["enabled"] == 1
    assert st["probe_failures_total"] == 0
    assert st["quarantines_total"] == 0
    assert st["probes_total"] >= 12  # 2 replicas x 6 rounds
    assert len(st["probe_history"]) == 6
    assert all(rec["failed"] == [] for rec in st["probe_history"])


# --------------------------------------------------------------------------
# Tentpole: replica kill mid-churn -> quarantine -> evacuate -> readmit
# --------------------------------------------------------------------------

def test_replica_kill_mid_churn_evacuates_and_readmits(world, mesh, batch):
    """The acceptance soak: kill replica 1 under live churn.  Probes
    fail consecutively -> quarantine masks it out of serving at once;
    the ring evacuation (certified shrink, no source migration) flips to
    the survivor topology; healing the fault auto-readmits via the
    certified grow-resize.  Every step holds bitwise parity vs the
    single-chip twin; survivor-resident established flows NEVER flap;
    the dead replica's flows re-establish within a bounded re-miss
    burst; and the journal alone reconstructs the causal chain."""
    cluster, services = world
    mdp = _mesh_dp(world, mesh, failover=True, failover_knobs=FO_KW)
    sdp = TpuflowDatapath(cluster.ps, services, **KW)
    for dp in (mdp, sdp):  # establish the hot set (sync: commit in-step)
        dp.step(batch, 100)
        dp.step(batch, 101)
    # Survivor-resident flows: homed off the doomed replica at gen 0.
    home0 = pm.shard_of_tuples(batch.src_ip, batch.dst_ip, batch.proto,
                               batch.src_port, batch.dst_port, 2, 0)
    surv = home0 != 1
    est0 = np.asarray(mdp.step(batch, 102).est) != 0
    rhot = sdp.step(batch, 102)
    # Never-flap watch: survivor-resident established lanes whose slot no
    # dead-resident flow claims — masking re-homes the dead replica's
    # flows INTO the survivor table, and a direct-mapped same-slot
    # collision evicting the resident is the documented cache-topology
    # observable (test_reshard caveat), not a failover flap.  What the
    # plane itself guarantees: masking and the cutover never disturb a
    # survivor row (D-independent slot hash, order-preserving survivor
    # indexing), so uncontended slots stay est through the WHOLE story.
    slot_hot = _slots(batch)
    dead_claims = np.unique(np.concatenate(
        [c[home0 == 1] for c in _claim_cols(batch, rhot)]))
    watch = surv & est0 & ~np.isin(slot_hot, dead_claims)
    assert watch.sum() > 0 and (~surv).sum() > 0  # both sides populated

    plan = _kill(mdp, replica=1)
    t = 103
    i = 0
    while mdp.failover_stats()["phase"] != "evacuated":
        churn = gen_traffic(cluster.pod_ips, 128, n_flows=64, seed=900 + i)
        rc_m, rc_s = mdp.step(churn, t), sdp.step(churn, t)
        _verdict_parity(rc_m, rc_s, f"churn t={t}")
        rm, rs = mdp.step(batch, t), sdp.step(batch, t)
        _verdict_parity(rm, rs, f"hot t={t}")
        # Survivor-resident established flows never flap — modulo this
        # round's churn lanes contending the same direct-mapped slot
        # (the hot step reclaims such a slot in-round; next round it
        # reads est again).
        churn_claims = np.unique(np.concatenate(_claim_cols(churn, rc_s)))
        ok_round = watch & ~np.isin(slot_hot, churn_claims)
        assert np.asarray(rm.est)[ok_round].all(), f"survivor flap t={t}"
        mdp.maintenance_tick(now=t)
        t += 1
        i += 1
        assert t < 500, mdp.failover_stats()
    assert i >= MIN_RESIZE_TICKS, i  # quarantine + evacuation ran mid-churn
    st = mdp.failover_stats()
    assert mdp._n_data == 1 and st["quarantines_total"] == 1
    assert st["evacuations_total"] == 1 and st["mask_active"] == 0
    # Bounded re-miss burst: only lanes homed on the dead replica ever
    # re-missed through the mask, and each flow re-establishes once —
    # the burst can never exceed the masked-lane population (hot set +
    # the churn lanes that eventually classified on survivors).
    assert 0 < st["remiss_total"] <= int((home0 == 1).sum()) + 64 * i
    # ... and it STOPS: the survivor topology serves the re-established
    # set from cache, no further re-misses after the flip settles.
    rm = mdp.step(batch, t)
    _verdict_parity(rm, sdp.step(batch, t), "post-evac")
    assert np.asarray(rm.est)[watch].all()
    settled = mdp.failover_stats()["remiss_total"]
    rm = mdp.step(batch, t + 1)
    assert mdp.failover_stats()["remiss_total"] == settled
    assert np.asarray(rm.est).sum() > 0
    sdp.step(batch, t + 1)

    # Heal -> auto-readmission via the ORDINARY certified grow-resize.
    plan.quiesce()
    t0 = t + 2
    t = _run_until(mdp, t0, "healthy", sdp=sdp, batch=batch)
    assert t - t0 >= MIN_RESIZE_TICKS, t - t0  # readmitted under traffic
    st = mdp.failover_stats()
    assert mdp._n_data == 2 and st["readmissions_total"] == 1
    assert st["quarantined_shard"] is None
    rm, rs = mdp.step(batch, t), sdp.step(batch, t)
    _verdict_parity(rm, rs, "post-readmit")
    assert np.asarray(rm.est)[watch].all()  # still no survivor flap

    # The journal reconstructs the chain from events alone — probe
    # failures precede the quarantine, the quarantine precedes the
    # skip-source evacuation resize, its certified cutover precedes the
    # evacuation record, and the readmission closes the story.
    ev = mdp.flightrecorder_events()
    kinds = [e["kind"] for e in ev]
    idx = _chain_indices(kinds, [
        "replica-probe-fail", "replica-quarantine", "reshard-begin",
        "reshard-cutover", "replica-evacuate", "reshard-begin",
        "reshard-cutover", "replica-readmit"])
    assert ev[idx[1]]["replica"] == 1
    assert ev[idx[2]]["skip_replica"] == 1  # the emergency shrink
    assert "skip_replica" not in ev[idx[5]]  # the ordinary readmit grow
    assert ev[idx[4]]["replica"] == 1
    assert ev[idx[7]]["gate"] == "resize" and ev[idx[7]]["mode"] == "auto"

    # Metric families render; the quarantined gauge is back to zero.
    text = render_metrics(mdp, node="n0")
    for fam in ("antrea_tpu_failover_quarantined",
                "antrea_tpu_failover_probes_total",
                "antrea_tpu_failover_probe_failures_total",
                "antrea_tpu_failover_quarantines_total",
                "antrea_tpu_failover_evacuations_total",
                "antrea_tpu_failover_readmissions_total",
                "antrea_tpu_failover_remiss_total"):
        assert fam in text, fam
    for line in text.splitlines():
        if line.startswith("antrea_tpu_failover_quarantined{"):
            assert line.rsplit(" ", 1)[1] == "0", line

    # Post-readmission verdicts are oracle-true on every classified
    # non-service lane (the scalar Oracle deliberately does not model
    # ServiceLB DNAT — service lanes are covered by the bitwise twin
    # parity above, the commit-canary discipline).
    from antrea_tpu.oracle.interpreter import Oracle
    oracle = Oracle(cluster.ps)
    r = mdp.step(batch, t + 1)
    pend = (np.zeros(batch.size, bool) if r.pending is None
            else np.asarray(r.pending) != 0)
    plain = np.asarray(r.svc_idx) < 0
    assert (~pend & plain).sum() > 0
    for i in range(batch.size):
        if not pend[i] and plain[i]:
            assert int(np.asarray(r.code)[i]) == int(
                oracle.classify(batch.packet(i)).code), i


def test_operator_readmit_via_api_and_bundle_surfaces(world, mesh, batch,
                                                      tmp_path):
    """Operator-driven readmission end to end: auto_readmit off, the
    evacuated mesh stays at D-1 until GET /failover?readmit=1 (the
    antctl path) triggers the certified grow — and the failover surface
    rides the apiserver handler thread and the support bundle."""
    from antrea_tpu.agent.apiserver import AgentApiServer
    from antrea_tpu.observability.supportbundle import collect_bundle

    cluster, services = world
    mdp = _mesh_dp(world, mesh, failover=True,
                   failover_knobs={**FO_KW, "auto_readmit": False})
    mdp.step(batch, 100)
    plan = _kill(mdp, replica=1)
    t = _run_until(mdp, 101, "evacuated")
    plan.quiesce()
    for tt in range(t, t + 6):  # auto_readmit off: nothing moves
        mdp.step(batch, tt)
        mdp.maintenance_tick(now=tt)
    assert mdp.failover_stats()["phase"] == "evacuated"

    srv = AgentApiServer(mdp, node="n1").start()
    try:
        body = json.loads(urllib.request.urlopen(
            srv.address + "/failover").read())
        assert body["enabled"] == 1 and body["phase"] == "evacuated"
        assert body["quarantined_shard"] == 1 and body["n_shards"] == 2
        assert body["probe_history"]
        # PR 20: the surface names worlds still awaiting the evacuation
        # flip — an untenanted mesh serves the key with an empty list.
        assert body["tenants_pending_evacuation"] == []
        kicked = json.loads(urllib.request.urlopen(
            srv.address + "/failover?readmit=1").read())
        assert kicked["phase"] == "readmitting"
    finally:
        srv.close()
    t = _run_until(mdp, t + 6, "healthy", batch=batch)
    st = mdp.failover_stats()
    assert mdp._n_data == 2 and st["readmissions_total"] == 1
    ev = [e for e in mdp.flightrecorder_events()
          if e["kind"] == "replica-readmit"]
    assert ev[-1]["mode"] == "operator" and ev[-1]["gate"] == "resize"

    out = tmp_path / "bundle.tar.gz"
    members = collect_bundle(mdp, str(out), node="n1", now=t)
    assert "failover.json" in members


# --------------------------------------------------------------------------
# Satellite: maintenance stats pin — late-registered tasks always render
# --------------------------------------------------------------------------

def test_maintenance_stats_render_late_registered_tasks(world, mesh, batch):
    """The task-table omission bug: tasks registered AFTER boot (the
    failover plane's emergency reshard-migrate, registered from inside a
    running tick) must render in maintenance_stats()/GET /maintenance —
    the snapshot iterates a stable copy on the handler thread, never the
    live dict."""
    import urllib.request as rq

    from antrea_tpu.agent.apiserver import AgentApiServer

    mdp = _mesh_dp(world, mesh, failover=True, failover_knobs=FO_KW)
    mdp.step(batch, 100)
    ms = mdp.maintenance_stats()
    assert "replica-health" in ms["tasks"]
    _kill(mdp, replica=1)
    t = _run_until(mdp, 101, "evacuating")
    # The emergency migrate task was registered mid-lifecycle (from the
    # replica-health runner's quarantine) — it must be visible NOW.
    ms = mdp.maintenance_stats()
    assert "reshard-migrate" in ms["tasks"]
    assert "replica-health" in ms["tasks"]
    assert ms["scheduler_lag"] >= 0.0
    srv = AgentApiServer(mdp, node="n1").start()
    try:
        body = json.loads(rq.urlopen(srv.address + "/maintenance").read())
        assert "reshard-migrate" in body["tasks"]
        fo = json.loads(rq.urlopen(srv.address + "/failover").read())
        assert fo["phase"] == "evacuating"
        assert fo["tenants_pending_evacuation"] == []
    finally:
        srv.close()

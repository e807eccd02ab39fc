"""Elastic mesh resharding (parallel/reshard.py): tier-1 + chaos tier.

Resize the data axis UNDER TRAFFIC on the 8 forced host devices —
grow 2→4 and shrink 4→2 mid-churn, mid-drain and mid-commit — holding
the PR bar: bitwise verdict parity for every established flow (no flap,
no parity loss), a vetoed cutover aborts back to the old topology with
the generation unchanged, and the reshard manifest gate
(tools/check_reshard.py) stays green.

The resizes that abort, defer or pause are in
tests/test_reshard_aborts.py (`loadfile` hands out whole files), which
borrows the world and helpers below.

Engines share the module-scoped meshes + KW so the jitted sharded step
builders (keyed by (mesh, meta)) compile once per variant.
"""

import pathlib
import sys

import jax
import numpy as np
import pytest

from antrea_tpu.datapath.tpuflow import TpuflowDatapath
from antrea_tpu.observability.metrics import render_metrics
from antrea_tpu.parallel import MeshDatapath, mesh as pm
from antrea_tpu.simulator.genpolicy import gen_cluster
from antrea_tpu.simulator.genservice import gen_services
from antrea_tpu.simulator.traffic import gen_traffic

KW = dict(flow_slots=1 << 10, aff_slots=1 << 8, canary_probes=16)
ASYNC_KW = dict(async_slowpath=True, miss_queue_slots=1 << 12,
                drain_batch=256)


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


def _run_to_completion(mdp, t, deadline=400):
    """Tick the maintenance plane until the in-flight resize finishes
    (cutover or abort) -> the next free packet-clock instant."""
    while mdp.reshard_status() is not None:
        mdp.maintenance_tick(now=t)
        t += 1
        assert t < deadline, mdp.reshard_status()
    return t


def _verdict_parity(rm, rs, msg=""):
    """Bitwise verdict parity on every CLASSIFIED lane.  Lanes pending on
    either engine carry the provisional admission verdict — which lanes
    re-miss after an eviction is a cache-TOPOLOGY observable (one 2^10
    table vs D private 2^10 shards evict differently under churn, the
    PR 9 est/committed caveat), so pending lanes compare pending-for-
    pending via the miss image, never verdict-for-verdict."""
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


# --------------------------------------------------------------------------
# Satellites: the manifest gate + the versioned consistent-ring election
# --------------------------------------------------------------------------

# The reshard-manifest gate (tools/check_reshard.py -> analysis pass
# `reshard`) runs once for the whole tier-1 suite in
# tests/test_static_analysis.py.


def test_versioned_ring_symmetric_deterministic_minimal_movement():
    """shard_of_tuples' topology generations: gen 0 keeps the PR 9 dense
    map bit-for-bit; gen >= 1 elects on the consistent ring — still
    deterministic and direction-symmetric, and growing the member set
    moves ONLY the keys the new shards' virtual points claim (the
    memberlist ownership property the migration budget rests on)."""
    rng = np.random.default_rng(5)
    src = rng.integers(1, 2 ** 32, 4096, dtype=np.uint32)
    dst = rng.integers(1, 2 ** 32, 4096, dtype=np.uint32)
    proto = np.full(4096, 6, np.int32)
    sport = rng.integers(1024, 65535, 4096).astype(np.int32)
    dport = rng.integers(1, 1024, 4096).astype(np.int32)
    for gen in (1, 2):
        fwd = pm.shard_of_tuples(src, dst, proto, sport, dport, 4, gen)
        again = pm.shard_of_tuples(src, dst, proto, sport, dport, 4, gen)
        rev = pm.shard_of_tuples(dst, src, proto, dport, sport, 4, gen)
        np.testing.assert_array_equal(fwd, again)
        np.testing.assert_array_equal(fwd, rev)
    # The ring depends on the MEMBER SET, not the generation number:
    # two ring generations at the same D elect identically.
    np.testing.assert_array_equal(
        pm.shard_of_tuples(src, dst, proto, sport, dport, 4, 1),
        pm.shard_of_tuples(src, dst, proto, sport, dport, 4, 2))
    # Consistent-hash minimal movement: every key owned by a surviving
    # shard under ring(4) keeps its owner under ring(2) — shrink moves
    # exactly the removed shards' keys, grow the mirror image.
    own4 = pm.shard_of_tuples(src, dst, proto, sport, dport, 4, 1)
    own2 = pm.shard_of_tuples(src, dst, proto, sport, dport, 2, 1)
    stay = own4 < 2
    np.testing.assert_array_equal(own4[stay], own2[stay])
    moved = float((~stay).sum()) / own4.size
    assert 0.3 < moved < 0.7, moved  # ~half the keys, the grown fraction
    # Load spread on the ring stays serviceable (RING_VNODES points).
    counts = np.bincount(own4, minlength=4)
    assert counts.min() > 512, counts
    # gen 0 is bit-stable: the dense mod map of PR 9.
    h_mod = pm.shard_of_tuples(src, dst, proto, sport, dport, 4)
    np.testing.assert_array_equal(
        h_mod, pm.shard_of_tuples(src, dst, proto, sport, dport, 4, 0))


# --------------------------------------------------------------------------
# Tentpole: grow + shrink mid-churn with zero established-flow loss
# --------------------------------------------------------------------------

def test_grow_and_shrink_mid_churn_zero_established_flow_loss(world, mesh,
                                                              batch):
    """The acceptance bar: grow 2→4 then shrink 4→2 executed MID-CHURN
    (fresh flows admitted and drained while migration windows run), with
    bitwise verdict parity for all established flows on every step, the
    established set still served from cache after each cutover, and the
    miss queues re-homed across the flip."""
    cluster, services = world
    adp = _mesh_dp(world, mesh, **ASYNC_KW)
    sdp = TpuflowDatapath(cluster.ps, services, **KW, **ASYNC_KW)
    for dp in (adp, sdp):  # establish the hot set
        dp.step(batch, 100)
        dp.drain_slowpath(101)

    def churn_until_done(t, seed0):
        i = 0
        while adp.reshard_status() is not None:
            churn = gen_traffic(cluster.pod_ips, 128, n_flows=64,
                                seed=seed0 + i)
            ra, rb = adp.step(churn, t), sdp.step(churn, t)
            # Pending lanes carry the provisional admission verdict, a
            # cache-topology observable; classified lanes must agree.
            ok = ((np.asarray(ra.pending) == 0)
                  & (np.asarray(rb.pending) == 0))
            np.testing.assert_array_equal(np.asarray(ra.code)[ok],
                                          np.asarray(rb.code)[ok])
            # The ESTABLISHED set never flaps mid-migration.
            ea, eb = adp.step(batch, t), sdp.step(batch, t)
            _verdict_parity(ea, eb, f"mid-churn t={t}")
            adp.maintenance_tick(now=t)
            t += 1
            i += 1
            assert t < 600
        assert i >= MIN_RESIZE_TICKS, i  # the resize ran mid-churn
        return t

    adp.reshard_begin(4)
    t = churn_until_done(102, 500)
    assert adp._n_data == 4 and adp._topo_gen == 1
    rs = adp.reshard_stats()
    assert rs["cutovers_total"] == 1 and rs["migrated_rows_total"] > 0
    # Established flows survived the grow: served from the MIGRATED
    # cache, in parity, with the hot set overwhelmingly classified
    # (only direct-mapped collision losers may re-pend, the documented
    # cache dynamic — never a verdict change on a classified lane).
    ra, rb = adp.step(batch, t), sdp.step(batch, t)
    ok = _verdict_parity(ra, rb, "post-grow")
    assert float(ok.mean()) > 0.85, float(ok.mean())
    assert int(np.asarray(ra.est).sum()) > 0
    for dp in (adp, sdp):
        dp.drain_slowpath(t + 1)

    adp.reshard_begin(2)  # ring -> ring: the minimal-movement leg
    t = churn_until_done(t + 2, 700)
    assert adp._n_data == 2 and adp._topo_gen == 2
    # Classified lanes stay bitwise-true straight off the flip, and the
    # MIGRATED entries serve immediately (est hits with no re-drain) —
    # the zero-established-flow-loss claim.  No classified-FRACTION bar
    # here: the churn universe deliberately thrashes the halved capacity
    # (4x1024 slots of est+churn entries merged into 2x1024; the
    # single-chip twin thrashes its lone 1024-slot table even harder),
    # and which lanes re-pend under thrash is the documented
    # cache-topology observable, not a parity loss.
    ra = adp.step(batch, t)
    _verdict_parity(ra, sdp.step(batch, t), "post-shrink")
    assert int(np.asarray(ra.est).sum()) > 0
    for dp in (adp, sdp):
        dp.drain_slowpath(t + 1)
    ra, rb = adp.step(batch, t + 2), sdp.step(batch, t + 2)
    _verdict_parity(ra, rb, "post-shrink-drained")
    assert int(np.asarray(ra.est).sum()) > 0
    assert adp.reshard_stats()["cutovers_total"] == 2
    # The journal carries both full lifecycles in causal order.
    kinds = [e["kind"] for e in adp.flightrecorder_events()
             if e["kind"].startswith("reshard")]
    assert kinds == ["reshard-begin", "reshard-migrated", "reshard-cutover",
                     "reshard-begin", "reshard-migrated", "reshard-cutover"]


def test_reshard_requeues_pending_misses_to_new_homes(world, mesh):
    """Queued (not-yet-classified) misses survive the flip: the cutover
    re-homes every row under the target ring (verbatim, not re-admitted)
    and a post-flip drain classifies them on their owning replicas with
    oracle-true verdicts."""
    from antrea_tpu.oracle.interpreter import Oracle

    cluster, _services = world
    adp = _mesh_dp(world, mesh, **ASYNC_KW)
    tr = gen_traffic(cluster.pod_ips, 256, n_flows=128, seed=31)
    adp.step(tr, 100)  # misses sit queued, undrained
    depth0 = adp.slowpath_stats()["depth"]
    assert depth0 > 0
    adp.reshard_begin(4)
    t = _run_to_completion(adp, 101)
    st = adp.slowpath_stats()
    assert st["depth"] == depth0  # nothing lost crossing the flip
    assert adp.reshard_stats()["requeued_total"] == depth0
    assert len(st["replica_depths"]) == 4
    adp.drain_slowpath(t)
    oracle = Oracle(cluster.ps)
    r = adp.step(tr, t + 1)
    codes, pend = np.asarray(r.code), np.asarray(r.pending)
    for i in range(tr.size):
        if not pend[i]:
            assert codes[i] == int(oracle.classify(tr.packet(i)).code), i


# --------------------------------------------------------------------------
# Observability: metric families, span, scheduler accounting
# --------------------------------------------------------------------------

def test_reshard_observability_surfaces(world, mesh, batch):
    cluster, _services = world
    mdp = _mesh_dp(world, mesh)
    text = render_metrics(mdp, node="n0")
    for fam in ("antrea_tpu_reshard_topology_generation",
                "antrea_tpu_reshard_active",
                "antrea_tpu_reshard_progress_ratio",
                "antrea_tpu_reshard_migrated_rows_total",
                "antrea_tpu_reshard_resident_rows",
                "antrea_tpu_reshard_cutovers_total",
                "antrea_tpu_reshard_aborts_total"):
        assert f'{fam}{{node="n0"}}' in text, fam
    # Single-chip engines carry NO reshard surface (schema gated on the
    # plane existing, like prune_stats).
    sdp = TpuflowDatapath(None, None, **KW)
    assert "antrea_tpu_reshard" not in render_metrics(sdp, node="n0")
    mdp.step(batch, 100)
    mdp.reshard_begin(4)
    assert render_metrics(mdp, node="n0").count(
        'antrea_tpu_reshard_active{node="n0"} 1') == 1
    t = _run_to_completion(mdp, 101)
    # The resize span: stages clamp monotonic and telescope to total,
    # recorded on the realization tracer beside policy spans.
    span = mdp.reshard_stats()["last_span"]
    assert span["n_data_from"] == 2 and span["n_data_to"] == 4
    total = span["migrate_s"] + span["certify_s"] + span["cutover_s"]
    assert abs(total - span["total_s"]) < 1e-9
    assert all(span[k] >= 0 for k in ("migrate_s", "certify_s",
                                      "cutover_s"))
    assert mdp.realization_stats()["last_resize"] == span
    # The migration ran as a BUDGETED scheduler task, not a free lunch.
    tasks = mdp.maintenance_stats()["tasks"]
    assert "reshard-migrate" not in tasks  # unregistered after cutover
    ticks = [e for e in mdp.flightrecorder_events(kind="maint-tick")
             if "reshard-migrate" in e.get("ran", {})]
    assert ticks, "migration never ran under the scheduler"
    assert max(e["ran"]["reshard-migrate"]
               for e in ticks[:-1] or ticks) <= 4096  # deficit-capped
    del t


# --------------------------------------------------------------------------
# Round-9 residue burn-down: dirty-row catch-up + off-shard DNAT reply legs
# --------------------------------------------------------------------------


def test_dirty_row_tracking_wiring(world, mesh):
    """Tier-1 wiring of the dirty-row plane: live dispatches mark their
    home (replica, slot) pairs into the reshard plane's bitmap, a
    same-ids bundle leaves the bounded set intact, a renumbering bundle
    (the whole-cache attribution remap) flips the full-sweep fallback
    and clears it — all without waiting out a full resize (the end-to-
    end catch-up meter is the slow-tier integration test below)."""
    cluster, services = world
    mdp = _mesh_dp(world, mesh)
    hot = gen_traffic(cluster.pod_ips, 96, n_flows=48, seed=898)
    mdp.step(hot, 100)
    mdp.reshard_begin(4)
    assert mdp.reshard_stats()["catchup_rows_total"] == 0
    st0 = mdp._reshard.status()
    assert st0["dirty_rows"] == 0 and st0["dirty_all"] is False
    mdp.step(hot, 101)  # live traffic mid-resize -> dirty marks
    st1 = mdp._reshard.status()
    assert 0 < st1["dirty_rows"] < 2 * KW["flow_slots"] // 2
    mdp.install_bundle(cluster.ps)  # same ids in same order: no remap
    assert mdp._reshard.dirty_all is False
    ps2 = gen_cluster(60, n_nodes=4, pods_per_node=8, seed=78).ps
    mdp.install_bundle(ps2)  # renumbering bundle: real remap
    assert mdp._reshard.dirty_all is True
    assert mdp._reshard.status()["dirty_rows"] == 0
    mdp.reshard_abort("wiring pinned")
    text = render_metrics(mdp, node="n0")
    assert 'antrea_tpu_reshard_catchup_rows_total{node="n0"}' in text


@pytest.mark.slow
def test_dirty_row_catchup_sweeps_touched_set_not_all_slots(world, mesh,
                                                            batch):
    """ROADMAP item 3's production residue: the cutover catch-up sweep
    walks the DIRTY set — rows the engine recorded as touched
    (committed/refreshed/torn down) after their migration window —
    instead of re-walking all O(slots), metered as
    `reshard_catchup_rows_total`; a mid-resize attribution remap (the
    whole-cache write no bounded set covers) falls back to the full
    sweep, metered identically."""
    cluster, services = world
    mdp = _mesh_dp(world, mesh)
    # A lean private hot set (the module batch would migrate 3x the
    # rows through the certify sweep for no extra coverage here).
    hot = gen_traffic(cluster.pod_ips, 96, n_flows=48, seed=899)
    mdp.step(hot, 100)
    r0 = mdp.step(hot, 101)
    G_grow = 2 * KW["flow_slots"]
    mdp.reshard_begin(4)
    # Live steps mid-migration: their touched (replica, slot) pairs —
    # fwd tuples + committed reply legs — form the dirty set.
    t = 102
    for i in range(2):
        mdp.step(gen_traffic(cluster.pod_ips, 64, n_flows=32,
                             seed=900 + i), t)
        mdp.maintenance_tick(now=t)
        t += 1
    t = _run_to_completion(mdp, t)
    rs = mdp.reshard_stats()
    assert rs["cutovers_total"] == 1
    # Bounded by the touched set (3 x 64 lanes x <= 2 directions + the
    # est-set refreshes), FAR under the full slot space — the whole
    # point of dirty tracking.
    assert 0 < rs["catchup_rows_total"] < G_grow // 2, rs
    # Continuity held: the established set serves its pre-resize
    # verdicts off the migrated cache (the mid-churn test holds the
    # full twin-parity bar; this pins the dirty sweep didn't lose rows).
    r1 = mdp.step(hot, t)
    np.testing.assert_array_equal(np.asarray(r1.code), np.asarray(r0.code))
    assert int(np.asarray(r1.est).sum()) > 0
    # Whole-cache fallback wiring: a mid-resize bundle whose rule
    # renumbering remaps cached attribution dirties EVERYTHING — the
    # bounded set clears and the catch-up will take the full O(slots)
    # walk (the pre-tracking shape, still metered); a same-ids bundle
    # must NOT degrade the bounded set.
    mdp.reshard_begin(2)
    mdp.maintenance_tick(now=t)  # at least one migration window first
    mdp.step(hot, t + 1)  # repopulate some dirty rows
    assert mdp._reshard.dirty_all is False
    mdp.install_bundle(cluster.ps)  # same ids in same order: no remap
    assert mdp._reshard.dirty_all is False
    ps2 = gen_cluster(60, n_nodes=4, pods_per_node=8, seed=77).ps
    mdp.install_bundle(ps2)  # renumbering bundle: real remap
    assert mdp._reshard.dirty_all is True
    assert mdp._reshard.status()["dirty_rows"] == 0
    mdp.reshard_abort("fallback wiring pinned; full-sweep path is the "
                      "pre-PR-12 behavior")
    text = render_metrics(mdp, node="n0")
    assert 'antrea_tpu_reshard_catchup_rows_total{node="n0"}' in text


def test_offshard_dnat_reply_leg_reclassifies_to_identical_verdict(world,
                                                                   mesh):
    """The documented ECMP-asymmetry analog, pinned: a DNAT'd service
    reply leg (endpoint -> client; the frontend address is gone from the
    tuple) can land OFF-SHARD and re-classify.  The contract: the
    re-classification yields the IDENTICAL verdict a fresh scalar walk
    of the reply tuple gives (never a wrong verdict), and processing the
    off-shard reply never flaps the forward leg's established entry."""
    from antrea_tpu.oracle.interpreter import Oracle
    from antrea_tpu.packet import PacketBatch

    cluster, services = world
    mdp = _mesh_dp(world, mesh)
    fwd = gen_traffic(cluster.pod_ips, 256, n_flows=128, seed=41,
                      services=services, svc_fraction=1.0)
    mdp.step(fwd, 100)
    r = mdp.step(fwd, 101)
    svc = (np.asarray(r.svc_idx) >= 0) & (np.asarray(r.est) == 1) & (
        np.asarray(r.dnat_ip) != fwd.dst_ip)  # genuinely DNAT-rewritten
    assert svc.any()
    # The reply tuple: endpoint -> client, ports swapped through DNAT.
    rep = PacketBatch(
        src_ip=np.asarray(r.dnat_ip).astype(np.uint32),
        dst_ip=fwd.src_ip,
        proto=fwd.proto,
        src_port=np.asarray(r.dnat_port).astype(np.int32),
        dst_port=fwd.src_port,
    )
    home_fwd = pm.shard_of_tuples(fwd.src_ip, fwd.dst_ip, fwd.proto,
                                  fwd.src_port, fwd.dst_port, 2)
    home_rep = pm.shard_of_tuples(rep.src_ip, rep.dst_ip, rep.proto,
                                  rep.src_port, rep.dst_port, 2)
    off = svc & (home_fwd != home_rep)
    assert off.any(), "no off-shard reply leg in this world — widen it"
    rr = mdp.step(rep, 102)
    oracle = Oracle(cluster.ps)
    codes = np.asarray(rr.code)
    est_r = np.asarray(rr.est)
    checked = 0
    for i in np.nonzero(off)[0]:
        # Off-shard: the flow's own reply entry is invisible (it lives
        # on the forward leg's home shard).  An aliased est hit is
        # possible — the reply tuple may coincide with ANOTHER flow's
        # committed entry on ITS home shard (correct by that entry's own
        # semantics); every non-aliased lane must re-classify FRESH to
        # the verdict the scalar oracle gives the reply tuple.
        if est_r[i]:
            continue
        checked += 1
        assert codes[i] == int(oracle.classify(rep.packet(int(i))).code), i
    assert checked > 0, "every off-shard reply aliased — widen the world"
    on = svc & (home_fwd == home_rep)
    if on.any():
        # On-shard replies hit their conntrack entry (the est bypass).
        assert est_r[np.nonzero(on)[0]].all()
    # No flap: the FORWARD legs keep their verdicts bitwise.  The reply
    # step's own fresh commits may direct-map-collide with a forward
    # entry on a shared shard (the ordinary bounded-cache dynamic — that
    # lane re-classifies to the identical verdict, asserted below); the
    # established set must otherwise survive intact.
    r2 = mdp.step(fwd, 103)
    sel = np.nonzero(svc)[0]
    np.testing.assert_array_equal(np.asarray(r2.code)[sel],
                                  np.asarray(r.code)[sel])
    assert float(np.asarray(r2.est)[sel].mean()) > 0.9

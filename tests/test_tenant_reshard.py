"""Tenant-aware elastic resharding (PR 20): resize, evacuate and fail
over with tenant worlds LIVE.

The two mutual-refusal ``ConfigError``s are gone: ``reshard_begin``
accepts a tenanted mesh (every world's (D,)-sharded state migrates
under its own ``_world_ctx`` with the generation-composable tenant
salt), and ``tenant_create`` accepts a resharding mesh (the newborn is
adopted mid-flight via ``note_world_created``).  Cutover certification
is per-world: each tenant runs its own replica-resolved canary, a veto
aborts ONLY that world — journaled ``tenant-rollback`` + per-world
topology-generation latch — while certified worlds flip; the latched
world keeps serving its old topology in parity until
``tenant_reshard_resync``.

The failover composition closes the PR 19 loop: quarantine on a
tenanted mesh proceeds to a real evacuation shrink and certified
readmission grows back; a world vetoing the EVACUATION cutover pins a
per-world ``_fo_mask`` and serves masked (skip-replica ring on its own
old topology) until resynced.

This file holds the fault-free grow and shrink and the do-no-harm pins;
the vetoes, the quarantine / evacuation stories and the chaos soak are
in tests/test_tenant_reshard_faults.py (`loadfile` hands out whole
files), which borrows the worlds and helpers below.

Engines share the module-scoped meshes + KW so the jitted sharded step
builders (keyed by (mesh, meta)) compile once per variant; tenant
worlds share one quota rung so the rung-packed rule windows share one
XLA executable before, during and after every resize.
"""

import numpy as np
import pytest

import jax

from antrea_tpu.observability.metrics import render_metrics
from antrea_tpu.oracle.interpreter import Oracle
from antrea_tpu.parallel import MeshDatapath, mesh as pm
from antrea_tpu.simulator.genpolicy import gen_cluster
from antrea_tpu.simulator.genservice import gen_services
from antrea_tpu.simulator.traffic import gen_traffic

KW = dict(flow_slots=1 << 8, aff_slots=1 << 6, canary_probes=8)
N_WORLDS = 8  # the acceptance floor: >= 8 live tenant worlds


# Rows a maintenance tick may migrate.  These suites assert what holds
# DURING and AFTER a resize (parity every tick, no established-flow loss,
# the journal chain), not how many ticks one takes: at the engine's
# default of 256 a nine-world resize took 10 ticks, each re-proving its
# audit window through the eager walk; at 1024 it still spans several
# ticks with traffic between them, and the tick loops below assert it.
RESHARD_BUDGET = 1024
MIN_RESIZE_TICKS = 3


@pytest.fixture(scope="module")
def world():
    cluster = gen_cluster(40, n_nodes=4, pods_per_node=6, seed=7)
    services = gen_services(4, cluster.pod_ips, seed=11)
    return cluster, services


@pytest.fixture(scope="module")
def mesh():
    return pm.make_mesh(2, 2, devices=jax.devices("cpu")[:4])


@pytest.fixture(scope="module")
def batch(world):
    cluster, services = world
    return gen_traffic(cluster.pod_ips, 128, n_flows=48, seed=3,
                       services=services, svc_fraction=0.3)


@pytest.fixture(scope="module")
def tenant_clusters():
    """Uneven worlds: same seed family as the reshard smoke, one shared
    quota rung (64) so every world's rule window packs into the SAME
    padded executable."""
    return [gen_cluster(20, n_nodes=2, pods_per_node=5, seed=100 + i)
            for i in range(N_WORLDS)]


@pytest.fixture(scope="module")
def tenant_batches(tenant_clusters):
    # World 2's default seed (52) draws an all-denied batch against its
    # policy set — denied flows never establish, which would starve the
    # continuity assertions; seed 56 gives it the usual allow/deny mix.
    return [gen_traffic(c.pod_ips, 64, n_flows=24,
                        seed=56 if i == 2 else 50 + i)
            for i, c in enumerate(tenant_clusters)]


def _mesh_dp(world, mesh, **extra):
    cluster, services = world
    return MeshDatapath(cluster.ps, services, mesh=mesh,
                        **{"reshard_budget": RESHARD_BUDGET, **KW, **extra})


def _tenants(dp, tenant_clusters, n=N_WORLDS):
    return [dp.tenant_create(f"w{i}", tenant_clusters[i].ps, quota=64)
            for i in range(n)]


# Want-code memo: the oracle verdict of a FIXED packet against a FIXED
# policy world is deterministic, and these suites re-serve the same
# batches every tick — classify each (world, batch) once, compare every
# step.  Values keep the oracle/batch refs so ids can't be recycled.
_WANT = {}


def _want_codes(oracle, tb):
    key = (id(oracle), id(tb))
    hit = _WANT.get(key)
    if hit is None or hit[0] is not oracle or hit[1] is not tb:
        codes = np.asarray([int(oracle.classify(tb.packet(j)).code)
                            for j in range(tb.size)])
        _WANT[key] = hit = (oracle, tb, codes)
    return hit[2]


def _parity(oracle, tb, r, msg):
    """Bitwise verdict parity vs the per-world oracle on every
    CLASSIFIED lane (pending lanes carry the provisional admission
    verdict until the async drain lands — the PR 9 caveat)."""
    codes = np.asarray(r.code)
    want = _want_codes(oracle, tb)
    pend = (np.zeros(tb.size, bool) if r.pending is None
            else np.broadcast_to(np.asarray(r.pending).astype(bool),
                                 (tb.size,)))
    live = ~pend
    if not (codes[live] == want[live]).all():
        j = int(np.argmax(live & (codes != want)))
        raise AssertionError((msg, j, int(codes[j]), int(want[j])))


def _step_all_in_parity(dp, tids, tbs, oracles, t, msg):
    for i, tid in enumerate(tids):
        _parity(oracles[i], tbs[i], dp.tenant_step(tid, tbs[i], t),
                f"{msg} w{tid} t={t}")


def _resize_under_traffic(dp, batch, tids, tbs, oracles, t, deadline=900):
    """Drive the in-flight resize to completion, serving the default
    world AND every tenant world each tick, parity-checked throughout."""
    t0 = t
    while dp.reshard_status() is not None:
        dp.step(batch, t)
        _step_all_in_parity(dp, tids, tbs, oracles, t, "mid-resize")
        dp.maintenance_tick(now=t)
        t += 1
        assert t < deadline, dp.reshard_status()
    assert t - t0 >= MIN_RESIZE_TICKS, t - t0  # traffic between the ticks
    return t


# --------------------------------------------------------------------------
# Tentpole acceptance: grow + shrink with >= 8 live worlds, newborn
# adoption mid-flight, established-flow continuity, journal chain.
# --------------------------------------------------------------------------

def test_grow_and_shrink_with_eight_live_tenant_worlds(
        world, mesh, batch, tenant_clusters, tenant_batches):
    # Nine worlds' rows a tick: each resize below still takes three ticks
    # (migrate, catch up and certify, cut over), every one of them between
    # a round of traffic in all worlds (_resize_under_traffic asserts it).
    dp = _mesh_dp(world, mesh, async_slowpath=True,
                  miss_queue_slots=1 << 10, drain_batch=128,
                  reshard_budget=4 * RESHARD_BUDGET)
    tids = _tenants(dp, tenant_clusters)
    oracles = [Oracle(c.ps) for c in tenant_clusters]
    tbs = list(tenant_batches)

    # Establish flows in every world, then drain the shared miss queue
    # EMPTY (one drain moves only drain_batch rows; 9 worlds queue ~6x
    # that) so est is loadbearing in every world.
    dp.step(batch, 100)
    for i, tid in enumerate(tids):
        dp.tenant_step(tid, tbs[i], 100)
    for k in range(8):
        dp.drain_slowpath(101 + k)
    est_before = {}
    for i, tid in enumerate(tids):
        r = dp.tenant_step(tid, tbs[i], 110)
        _parity(oracles[i], tbs[i], r, f"pre w{tid}")
        est_before[tid] = np.asarray(r.est).astype(bool).copy()
        assert est_before[tid].any(), f"w{tid} established nothing"

    # Grow 2 -> 4 under traffic; the old refusal is GONE.
    dp.reshard_begin(4)
    t = _resize_under_traffic(dp, batch, tids, tbs, oracles, 111)
    assert dp._n_data == 4 and dp._topo_gen == 1

    st = dp.reshard_stats()
    assert st["tenant_rows_total"] > 0
    assert st["tenant_vetoes_total"] == 0
    assert st["tenant_worlds_migrating"] == 0
    ts = dp.tenant_stats()
    for tid in tids:
        assert ts[tid]["latched"] == 0
        assert ts[tid]["topology_generation"] == 1
        assert ts[tid]["reshard_rows_total"] > 0

    # Zero established-flow loss: the migrated entries serve straight
    # off the flip (est hits, no re-drain) in every world.  Only
    # direct-mapped collision losers may re-pend on the re-homed slot
    # layout — the documented cache-topology dynamic, never a verdict
    # change on a classified lane (parity held every tick above).
    kept = total = 0
    for i, tid in enumerate(tids):
        r = dp.tenant_step(tid, tbs[i], t)
        _parity(oracles[i], tbs[i], r, f"post-grow w{tid}")
        now_est = np.asarray(r.est).astype(bool)
        assert now_est.any(), f"w{tid} serves nothing from cache"
        kept += int(now_est[est_before[tid]].sum())
        total += int(est_before[tid].sum())
    assert kept / total > 0.85, (kept, total)

    # Shrink 4 -> 2 with a NEWBORN world created mid-flight: the other
    # old refusal is gone too — tenant_create adopts into the plane.
    dp.reshard_begin(2)
    nc = gen_cluster(20, n_nodes=2, pods_per_node=5, seed=777)
    ntid = dp.tenant_create("newborn", nc.ps, quota=64)
    tids.append(ntid)
    tbs.append(gen_traffic(nc.pod_ips, 64, n_flows=24, seed=88))
    oracles.append(Oracle(nc.ps))
    t = _resize_under_traffic(dp, batch, tids, tbs, oracles, t)
    assert dp._n_data == 2 and dp._topo_gen == 2
    ts = dp.tenant_stats()
    for tid in tids:
        assert ts[tid]["latched"] == 0
        assert ts[tid]["topology_generation"] == 2
    for i, tid in enumerate(tids):
        _parity(oracles[i], tbs[i], dp.tenant_step(tid, tbs[i], t),
                f"post-shrink w{tid}")

    # Journal chain: each resize begins, migrates, flips every world,
    # then flips the fleet — and no world ever vetoed or rolled back.
    kinds = [e["kind"] for e in dp.flightrecorder_events()]
    assert kinds.count("reshard-begin") == 2
    assert kinds.count("reshard-cutover") == 2
    # 8 worlds on the grow + 9 on the shrink (newborn adopted).
    assert kinds.count("tenant-reshard-cutover") == N_WORLDS + N_WORLDS + 1
    assert "tenant-reshard-veto" not in kinds
    assert "tenant-rollback" not in kinds
    assert "reshard-abort" not in kinds
    cut = [e for e in dp.flightrecorder_events()
           if e["kind"] == "tenant-reshard-cutover"]
    assert {e["tenant"] for e in cut} == set(tids)

    # Tenant-labeled reshard metrics render.
    text = render_metrics(dp, node="n0")
    assert "antrea_tpu_reshard_tenant_rows_total" in text
    assert "antrea_tpu_tenant_topology_generation" in text
    assert "antrea_tpu_tenant_latched" in text


# --------------------------------------------------------------------------
# The do-no-harm pins: untenanted resize and failover=False trace the
# IDENTICAL compiled step as HEAD (cache-identity = byte-identical HLO).
# --------------------------------------------------------------------------

def test_untenanted_paths_share_the_compiled_step(world, mesh, batch):
    from antrea_tpu.parallel.meshpath import _mesh_step_full_fn

    a = _mesh_dp(world, mesh)                 # plain HEAD shape
    b = _mesh_dp(world, mesh, failover=True)  # failover plane armed
    assert a._meta_step == b._meta_step
    for has_arp in (False, True):
        assert (_mesh_step_full_fn(a._mesh, a._meta_step, has_arp)
                is _mesh_step_full_fn(b._mesh, b._meta_step, has_arp))
    ra, rb = a.step(batch, 100), b.step(batch, 100)
    for k in ("code", "svc_idx", "dnat_ip", "dnat_port", "est"):
        np.testing.assert_array_equal(np.asarray(getattr(ra, k)),
                                      np.asarray(getattr(rb, k)), k)
    # An untenanted resize serves through the same cached builders the
    # whole way: the step fn resolved at the target width is the same
    # object any untenanted engine at that width resolves.
    a.reshard_begin(4)
    t = 101
    while a.reshard_status() is not None:
        a.step(batch, t)
        a.maintenance_tick(now=t)
        t += 1
        assert t < 400
    assert a._n_data == 4
    c = MeshDatapath(world[0].ps, world[1],
                     mesh=pm.make_mesh(4, 2, devices=jax.devices("cpu")),
                     **KW)
    for has_arp in (False, True):
        assert (_mesh_step_full_fn(a._mesh, a._meta_step, has_arp)
                is _mesh_step_full_fn(c._mesh, c._meta_step, has_arp))

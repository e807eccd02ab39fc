"""Elastic resharding, the ways a resize does NOT run to its cutover (the
other half of tests/test_reshard.py, which keeps the resizes that do): a
vetoed cutover aborts to the old topology, a resize defers whole against
an in-flight drain, absorbs installs and deltas mid-commit, pauses while
the datapath is degraded, and `reshard_begin` rejects what it cannot do.

A file of its own because pytest-xdist's `loadfile` hands out whole
files: the world, mesh and helpers are test_reshard's.
"""

import pytest

from antrea_tpu.datapath.tpuflow import TpuflowDatapath
from antrea_tpu.simulator.genservice import gen_services
from antrea_tpu.simulator.traffic import gen_traffic

from test_reshard import (
    ASYNC_KW, KW, _mesh_dp, _run_to_completion, _verdict_parity, batch, mesh,
    world)  # noqa: F401 — fixtures


# --------------------------------------------------------------------------
# Chaos tier: vetoed cutover, mid-drain serialization, mid-commit installs
# --------------------------------------------------------------------------

def test_vetoed_cutover_aborts_to_old_topology(world, mesh, batch):
    """Chaos: rule-table corruption on ONE target replica's device
    copies.  The cutover canary's row for that replica diverges and
    vetoes the flip — the old mesh keeps serving (healthy, not even
    degraded), the affinity generation never moves, and the journal
    reconstructs reshard-begin -> replica-canary-veto -> reshard-abort.
    A clean retry then resizes successfully."""
    cluster, services = world
    vdp = _mesh_dp(world, mesh)
    sdp = TpuflowDatapath(cluster.ps, services, **KW)
    vdp.step(batch, 100)
    sdp.step(batch, 100)
    vdp.reshard_begin(4)
    desc = vdp._reshard.corrupt_target(1)
    assert "target" in desc and "replica 1" in desc
    t = _run_to_completion(vdp, 101)
    assert vdp._n_data == 2 and vdp._topo_gen == 0  # generation unchanged
    rs = vdp.reshard_stats()
    assert rs["aborts_total"] == 1 and rs["cutovers_total"] == 0
    assert not vdp.degraded  # the OLD mesh was never implicated
    kinds = [e["kind"] for e in vdp.flightrecorder_events()]
    chain = [k for k in kinds if k in ("reshard-begin",
                                       "replica-canary-veto",
                                       "reshard-abort")]
    assert chain == ["reshard-begin", "replica-canary-veto",
                     "reshard-abort"], kinds
    # Old topology still serving in parity.
    _verdict_parity(vdp.step(batch, t), sdp.step(batch, t), "post-abort")
    # Clean retry: fresh target placement, certified, flipped.
    vdp.reshard_begin(4)
    t = _run_to_completion(vdp, t + 1)
    assert vdp._n_data == 4 and vdp._topo_gen == 1
    _verdict_parity(vdp.step(batch, t), sdp.step(batch, t), "post-retry")


def test_reshard_defers_whole_against_inflight_drain(world, mesh):
    """Mid-drain chaos: a migration window must never interleave with a
    pinned drain block — the scheduler's ONE serialization point defers
    the whole tick (blocked, metered), and migration resumes after
    finish_drain."""
    cluster, _services = world
    adp = _mesh_dp(world, mesh, **ASYNC_KW)
    tr = gen_traffic(cluster.pod_ips, 256, n_flows=128, seed=37)
    adp.step(tr, 100)
    adp.reshard_begin(4)
    sp = adp._slowpath
    assert sp.begin_drain(101)
    out = adp.maintenance_tick(now=102)
    assert out["blocked"] == "inflight-drain"
    assert "reshard-migrate" in out["deferred"]
    assert adp.reshard_status()["progress_ratio"] == 0.0
    sp.finish_drain(103)
    out = adp.maintenance_tick(now=104)
    assert out["ran"].get("reshard-migrate", 0) > 0
    _run_to_completion(adp, 105)
    assert adp._n_data == 4


def test_reshard_mid_commit_absorbs_installs_and_deltas(world, mesh, batch):
    """Mid-commit chaos: a full bundle install AND an O(delta) group
    patch land BETWEEN migration windows.  The lazily-placed target
    tensors re-place at certification (gen-checked), the catch-up sweep
    re-syncs remapped attribution, and post-cutover verdicts/attribution
    match a single-chip twin that saw the identical sequence."""
    cluster, services = world
    mdp = _mesh_dp(world, mesh)
    sdp = TpuflowDatapath(cluster.ps, services, **KW)
    mdp.step(batch, 100)
    sdp.step(batch, 100)
    mdp.reshard_begin(4)
    mdp.maintenance_tick(now=101)  # a partial migration window
    assert 0 < mdp.reshard_status()["progress_ratio"] < 1
    # Mid-resize bundle: same world re-installed (renumbering bundle,
    # exercises the cached-attribution remap) + a fresh services set.
    services2 = gen_services(8, cluster.pod_ips, seed=12)
    mdp.install_bundle(cluster.ps, services2)
    sdp.install_bundle(cluster.ps, services2)
    # Mid-resize O(delta) patch.
    group = sorted(cluster.ps.address_groups)[0]
    mdp.apply_group_delta(group, ["172.31.9.9"], [])
    sdp.apply_group_delta(group, ["172.31.9.9"], [])
    t = _run_to_completion(mdp, 102)
    assert mdp._n_data == 4 and mdp._topo_gen == 1
    assert mdp.generation == sdp.generation
    _verdict_parity(mdp.step(batch, t), sdp.step(batch, t), "post-cutover")
    tr = gen_traffic(cluster.pod_ips, 128, n_flows=64, seed=41)
    _verdict_parity(mdp.step(tr, t + 1), sdp.step(tr, t + 1), "fresh")


def test_degraded_datapath_pauses_and_rejects_reshard(world, mesh, batch):
    """Resizing is gated on a certifiable commit plane: reshard_begin
    refuses while degraded, and an in-flight resize sheds its task (the
    degraded-mode priority inversion) until recovery."""
    from antrea_tpu.datapath.commit import CanaryMismatchError

    cluster, services = world
    mdp = _mesh_dp(world, mesh)
    mdp.step(batch, 100)
    mdp.corrupt_replica(1)
    with pytest.raises(CanaryMismatchError):
        mdp.install_bundle(None, gen_services(8, cluster.pod_ips, seed=12))
    assert mdp.degraded
    with pytest.raises(RuntimeError, match="degraded"):
        mdp.reshard_begin(4)
    # Recover, begin, then degrade MID-resize: the task sheds.
    mdp.install_bundle(cluster.ps, services)
    assert not mdp.degraded
    mdp.reshard_begin(4)
    mdp._commit.degraded = True
    out = mdp.maintenance_tick(now=101)
    assert "reshard-migrate" in out["shed"]
    assert mdp.reshard_status()["progress_ratio"] == 0.0
    mdp._commit.degraded = False
    t = _run_to_completion(mdp, 102)
    assert mdp._n_data == 4


def test_reshard_begin_rejections(world, mesh):
    mdp = _mesh_dp(world, mesh)
    with pytest.raises(ValueError, match="equals the current"):
        mdp.reshard_begin(2)
    with pytest.raises(ValueError, match="devices"):
        mdp.reshard_begin(64)  # 64 x 2 devices cannot exist here
    with pytest.raises(RuntimeError, match="no reshard"):
        mdp.reshard_abort()
    mdp.reshard_begin(4)
    with pytest.raises(RuntimeError, match="already in flight"):
        mdp.reshard_begin(4)
    mdp.reshard_abort("test teardown")
    assert mdp.reshard_status() is None
    assert mdp.reshard_stats()["aborts_total"] == 1

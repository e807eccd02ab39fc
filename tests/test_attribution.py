"""Rule attribution of a step: stored rule INDICES -> stable rule ids.

`tpuflow._rid` states the rule for one lane; `tpuflow._rids` applies it to a
whole output column by one gather from the direction's `rule_id_table`
(compiler/compile.DirectionTensors), built once per compiled rule set.

What is held:
  * the gather equals `_rid` lane by lane, types and object identity
    included, on every edge of the index space and on every shape of id
    list;
  * the table lives and dies with the compiled set: the same object from
    step to step, another after a renumbering bundle, the retained one
    after a rollback — and a cached flow's attribution follows the rule's
    identity through all of it;
  * both engines resolve through the one function.
"""

import copy

import numpy as np
import pytest

import jax

from antrea_tpu.compiler.compile import DirectionTensors
from antrea_tpu.datapath import TpuflowDatapath
from antrea_tpu.datapath import tpuflow
from antrea_tpu.datapath.commit import CanaryMismatchError
from antrea_tpu.datapath.tpuflow import _rid, _rids
from antrea_tpu.dissemination import FaultPlan
from antrea_tpu.simulator import gen_cluster, gen_services, gen_traffic

# tests/test_steptrace.py's world and knobs: the same compiled programs.
B = 256
KW = dict(flow_slots=1 << 10, aff_slots=1 << 8, canary_probes=8,
          miss_chunk=64)


@pytest.fixture(scope="module")
def world():
    cluster = gen_cluster(120, n_nodes=4, pods_per_node=8, seed=7)
    return (cluster, gen_services(8, cluster.pod_ips, seed=2),
            gen_traffic(cluster.pod_ips, B, n_flows=96, seed=3))


# -- the resolver against the scalar rule --------------------------------------

ID_LISTS = {
    "no_rules": [],
    "one": ["p/In/0"],
    "one_empty": [""],
    "all_empty": [""] * 5,
    "padded": ["a/In/0", "", "b/In/0", "b/In/1", "", "", "c/Out/3"],
    "large": [f"np-{i % 97}/In/{i}" if i % 11 else "" for i in range(4099)],
}


def _edges(n, rng):
    i32 = np.iinfo(np.int32)
    return np.array([-1, 0, n - 1, n, n + 1, n + 1_000_000, i32.max, i32.min,
                     -2, 1], np.int32)


COLUMNS = {
    "edges": _edges,
    "all_default": lambda n, rng: np.full(64, -1, np.int32),
    "in_range": lambda n, rng: rng.integers(0, max(n, 1), 4096).astype(np.int32),
    "around_range": lambda n, rng: rng.integers(-3, n + 4, 4096).astype(np.int32),
    "anywhere": lambda n, rng: rng.integers(
        np.iinfo(np.int32).min, np.iinfo(np.int32).max, 4096, dtype=np.int64,
    ).astype(np.int32),
    "no_lanes": lambda n, rng: np.zeros(0, np.int32),
}


def _direction(ids):
    z = np.zeros(len(ids), np.int32)
    return DirectionTensors(at_gid=z, peer_gid=z, svc_gid=z, action=z,
                            n_phase0=len(ids), n_k8s=0, n_baseline=0,
                            rule_ids=ids)


@pytest.mark.parametrize("column", list(COLUMNS))
@pytest.mark.parametrize("ids", list(ID_LISTS))
def test_gather_equals_the_scalar_rule(ids, column):
    ids = ID_LISTS[ids]
    idx = COLUMNS[column](len(ids), np.random.default_rng(len(ids)))
    dt = _direction(ids)
    got = _rids(dt, idx)
    want = [_rid(ids, int(i)) for i in idx]
    assert type(got) is list and len(got) == idx.shape[0]
    assert got == want
    # str or None, and the very objects of rule_ids: nothing was copied,
    # boxed or turned into a numpy scalar on the way.
    assert all(g is w for g, w in zip(got, want))
    assert {type(g) for g in got} <= {str, type(None)}
    table = dt.rule_id_table
    assert table.dtype == object and table.shape == (len(ids) + 1,)
    assert table[-1] is None and dt.rule_id_table is table


# -- the table's lifetime on an engine -----------------------------------------

def _tables(dp):
    return dp._cps.ingress.rule_id_table, dp._cps.egress.rule_id_table


def _holds(dp):
    """The engine's tables state its CURRENT rule ids."""
    for d in (dp._cps.ingress, dp._cps.egress):
        t = d.rule_id_table
        assert len(t) == len(d.rule_ids) + 1 and t[-1] is None
        assert all(a is (b or None) for a, b in zip(t, d.rule_ids))


def test_table_follows_the_installed_rule_set(world):
    cluster, services, batch = world
    dp = TpuflowDatapath(copy.deepcopy(cluster.ps), services, **KW)
    plan = FaultPlan()
    dp.arm_commit_faults(plan, "n1")
    dp.step(batch, now=10)
    t0 = _tables(dp)
    r1 = dp.step(batch, now=11)
    assert all(a is b for a, b in zip(_tables(dp), t0))
    _holds(dp)
    # An established lane that a rule allowed, and the policy of that rule.
    named = [i for i in range(B) if r1.est[i] and r1.ingress_rule[i]]
    assert named, "the batch caches no flow that names an ingress rule"
    uid = r1.ingress_rule[named[0]].split("/")[0]
    old_ids = list(dp._cps.ingress.rule_ids)

    # A bundle without that policy renumbers every rule behind it.
    ps2 = copy.deepcopy(cluster.ps)
    ps2.policies = [p for p in ps2.policies if p.uid != uid]
    assert len(ps2.policies) == len(cluster.ps.policies) - 1
    dp.install_bundle(ps=ps2)
    t2 = _tables(dp)
    assert all(a is not b for a, b in zip(t2, t0))
    _holds(dp)
    new_ids = dp._cps.ingress.rule_ids
    moved = [rid for rid in new_ids
             if rid and old_ids.index(rid) != new_ids.index(rid)]
    assert moved, "the bundle renumbered nothing"
    r2 = dp.step(batch, now=12)
    kept = gone = 0
    for i in named:
        if not r2.est[i]:
            continue  # evicted or re-classified: not the cached attribution
        was = r1.ingress_rule[i]
        if was.startswith(uid + "/"):
            # The deciding rule vanished: the cached index is stale, the
            # lane names no rule.
            assert r2.ingress_rule[i] is None
            gone += 1
        else:
            assert r2.ingress_rule[i] == was
            kept += was in moved
    assert gone and kept

    # A bundle that fails its canary: the retained set's table is back,
    # the failed bundle's went with it.
    plan.after("n1.canary", plan.hits("n1.canary"), "fail", times=1)
    with pytest.raises(CanaryMismatchError):
        dp.install_bundle(ps=copy.deepcopy(cluster.ps))
    assert all(a is b for a, b in zip(_tables(dp), t2))
    _holds(dp)
    r3 = dp.step(batch, now=13)
    assert [r3.ingress_rule[i] for i in named if r2.est[i] and r3.est[i]] == [
        r2.ingress_rule[i] for i in named if r2.est[i] and r3.est[i]]


# -- one resolver, both engines ------------------------------------------------

def test_both_engines_resolve_through_the_one_function(world, monkeypatch):
    if len(jax.devices("cpu")) < 4:
        pytest.skip("needs 4 virtual CPU devices")
    from antrea_tpu.parallel import MeshDatapath, meshpath

    assert meshpath._rids is tpuflow._rids
    cluster, services, batch = world
    calls = []

    def spy(dt, idx):
        calls.append((dt, _rids(dt, idx)))
        return calls[-1][1]

    monkeypatch.setattr(tpuflow, "_rids", spy)
    monkeypatch.setattr(meshpath, "_rids", spy)
    single = TpuflowDatapath(cluster.ps, services, **KW)
    mesh = MeshDatapath(cluster.ps, services, n_data=2, n_rule=2,
                        devices=jax.devices("cpu")[:4], **KW)
    results = []
    for dp in (single, mesh):
        del calls[:]
        res = dp.step(batch, now=10)
        # Two calls a step, one a direction, and the StepResult carries
        # what they returned: no second path builds these lists.
        assert len(calls) == 2
        assert calls[0][0] is dp._cps.ingress and calls[1][0] is dp._cps.egress
        assert res.ingress_rule is calls[0][1]
        assert res.egress_rule is calls[1][1]
        results.append(res)
    assert results[0].ingress_rule == results[1].ingress_rule
    assert results[0].egress_rule == results[1].egress_rule
    assert any(results[0].ingress_rule) and any(results[0].egress_rule)

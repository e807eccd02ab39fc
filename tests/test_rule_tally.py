"""Per-rule NetworkPolicy counters kept as arrays indexed by rule
(datapath/rule_tally.RuleTally): `stats()` must equal a fold of every
step's `StepResult.ingress_rule` / `egress_rule` and the batch's packet
lengths — across many steps, a renumbering bundle whose cached entries
still carry old indices, empty rule ids, zero-length packets, tenant
worlds and a drain — and reading it must change nothing."""

import copy

import numpy as np
import pytest

from antrea_tpu.datapath import TpuflowDatapath
from antrea_tpu.datapath.rule_tally import RuleTally
from antrea_tpu.simulator import gen_cluster, gen_traffic
from antrea_tpu.simulator.genservice import gen_services

KW = dict(flow_slots=1 << 10, aff_slots=1 << 8, miss_chunk=64)


@pytest.fixture(scope="module")
def world():
    cluster = gen_cluster(120, n_nodes=4, pods_per_node=8, seed=3)
    return cluster, gen_services(8, cluster.pod_ips, seed=4)


def _batch(cluster, services, n, seed, max_len=1500):
    b = gen_traffic(cluster.pod_ips, n, n_flows=max(8, n // 3), seed=seed,
                    services=services, svc_fraction=0.3)
    b.pkt_len = np.random.default_rng(seed).integers(
        0, max_len + 1, n).astype(np.int32)
    return b


class Fold:
    """The expected counters: one packet and its length per lane that a
    StepResult attributes to a rule id, summed by id."""

    def __init__(self):
        self.packets = ({}, {})
        self.volumes = ({}, {})

    def add(self, res, batch):
        lens = np.maximum(batch.lens(), 0).tolist()
        for d, col in enumerate((res.ingress_rule, res.egress_rule)):
            assert len(col) == len(lens)
            for rid, ln in zip(col, lens):
                if rid is not None:
                    self.packets[d][rid] = self.packets[d].get(rid, 0) + 1
                    self.volumes[d][rid] = self.volumes[d].get(rid, 0) + ln
        return res

    def check(self, st):
        assert st.ingress == self.packets[0]
        assert st.egress == self.packets[1]
        # A byte key only where some volume was counted.
        assert st.ingress_bytes == {k: v for k, v in self.volumes[0].items()
                                    if v}
        assert st.egress_bytes == {k: v for k, v in self.volumes[1].items()
                                   if v}
        assert all(type(v) is int for t in (st.ingress, st.egress,
                                            st.ingress_bytes, st.egress_bytes)
                   for v in t.values())


def _live(tally):
    packets, volumes, ids, pk, by = tally._live
    return (dict(packets), dict(volumes), ids, pk.copy(), by.copy())


def test_stats_equal_the_step_results_over_many_steps(world):
    cluster, services = world
    dp = TpuflowDatapath(copy.deepcopy(cluster.ps), services, **KW)
    want = Fold()
    for i in range(6):
        b = _batch(cluster, services, 160, seed=70 + i)
        want.add(dp.step(b, now=100 + i), b)
    want.check(dp.stats())
    assert sum(want.packets[0].values()) + sum(want.packets[1].values()) > 0


def test_renumbering_bundle_with_cached_old_indices(world):
    """A bundle that drops and reorders rules while the flow cache still
    holds entries stamped with the old numbering: the old arrays fold
    through the OLD ids, later steps count through the new ones."""
    cluster, services = world
    dp = TpuflowDatapath(copy.deepcopy(cluster.ps), services, **KW)
    want = Fold()
    b = _batch(cluster, services, 192, seed=81)
    want.add(dp.step(b, now=100), b)
    old = dp._cps.ingress.rule_ids
    ps = copy.deepcopy(cluster.ps)
    # Drop every third policy; the survivors' K8s rows keep list order,
    # reversed here, so the phases renumber too.
    ps.policies = [p for i, p in enumerate(ps.policies) if i % 3][::-1]
    dp.install_bundle(ps=ps)
    new = dp._cps.ingress.rule_ids
    assert new is not old
    moved = [r for r in set(old) & set(new) if r and old.index(r)
             != new.index(r)]
    assert moved, "the bundle must renumber surviving rules"
    res = want.add(dp.step(b, now=101), b)
    assert np.asarray(res.est).any(), "cached entries must serve the rerun"
    b2 = _batch(cluster, services, 160, seed=82)
    want.add(dp.step(b2, now=102), b2)
    want.check(dp.stats())


def test_empty_rule_id_counts_nowhere(world):
    """A rule row with an empty id (what rung padding carries) counts in
    no per-rule table, like an index out of range."""
    cluster, services = world
    dp = TpuflowDatapath(copy.deepcopy(cluster.ps), services, **KW)
    b = _batch(cluster, services, 160, seed=91)
    probe = TpuflowDatapath(copy.deepcopy(cluster.ps), services, **KW)
    hit = [r for r in probe.step(b, now=100).ingress_rule if r]
    assert hit
    # Before the first step: no table has been built from the ids yet.
    ids = dp._cps.ingress.rule_ids
    blank = ids.index(max(set(hit), key=hit.count))
    ids[blank] = ""
    want = Fold()
    for i in range(2):
        want.add(dp.step(b, now=100 + i), b)
    st = dp.stats()
    want.check(st)
    assert "" not in st.ingress and None not in st.ingress


def test_zero_length_packets_leave_no_byte_keys(world):
    cluster, services = world
    dp = TpuflowDatapath(copy.deepcopy(cluster.ps), services, **KW)
    want = Fold()
    for i in range(2):
        b = _batch(cluster, services, 128, seed=95 + i, max_len=0)
        want.add(dp.step(b, now=100 + i), b)
    st = dp.stats()
    want.check(st)
    assert st.ingress and st.ingress_bytes == {} and st.egress_bytes == {}


def test_reading_twice_changes_nothing(world):
    cluster, services = world
    dp = TpuflowDatapath(copy.deepcopy(cluster.ps), services, **KW)
    for i in range(2):
        dp.step(_batch(cluster, services, 128, seed=101 + i), now=100 + i)
    before = [_live(t) for t in (dp._tally_in, dp._tally_out)]
    objs = [t._live for t in (dp._tally_in, dp._tally_out)]
    a, b = dp.stats(), dp.stats()
    assert a == b and a.ingress
    assert all(t._live is o for t, o in zip((dp._tally_in, dp._tally_out),
                                            objs))
    for (p0, v0, i0, pk0, by0), t in zip(before,
                                         (dp._tally_in, dp._tally_out)):
        p1, v1, i1, pk1, by1 = t._live
        assert (p1, v1) == (p0, v0) and i1 is i0
        np.testing.assert_array_equal(pk1, pk0)
        np.testing.assert_array_equal(by1, by0)


def test_tenant_worlds_count_apart():
    QUOTA, AFFQ = 1 << 8, 1 << 6
    clusters = [gen_cluster(n, n_nodes=2, pods_per_node=8, seed=11 + i)
                for i, n in enumerate((8, 70))]
    dp = TpuflowDatapath(flow_slots=1 << 10, aff_slots=1 << 8)
    tids = [dp.tenant_create(f"t{i}", copy.deepcopy(c.ps), quota=QUOTA,
                             aff_quota=AFFQ)
            for i, c in enumerate(clusters)]
    want = [Fold() for _ in tids]
    for rnd in range(2):
        for i, (tid, c) in enumerate(zip(tids, clusters)):
            b = gen_traffic(c.pod_ips, 64, n_flows=32, seed=40 + i + rnd)
            b.pkt_len = np.full(64, 60 + i, np.int32)
            want[i].add(dp.tenant_step(tid, b, 100 + rnd), b)
    for tid, w in zip(tids, want):
        w.check(dp.tenant_datapath_stats(tid))
    base = dp.stats()
    assert base.ingress == {} and base.egress == {}
    assert base.ingress_bytes == {} and base.egress_bytes == {}


def test_async_drain_counts_once(world):
    """Queued misses count at their drain, under the ids captured at its
    dispatch: the totals equal a synchronous twin's fold."""
    cluster, services = world
    sync = TpuflowDatapath(copy.deepcopy(cluster.ps), services, **KW)
    dp = TpuflowDatapath(copy.deepcopy(cluster.ps), services, **KW,
                         async_slowpath=True, miss_queue_slots=1 << 10,
                         drain_batch=64)
    want = Fold()
    b = _batch(cluster, services, 96, seed=111)
    want.add(sync.step(b, now=100), b)
    dp.step(b, now=100)
    dp.drain_slowpath(101)
    dp.flush_slowpath()
    want.check(dp.stats())
    want.check(sync.stats())


def test_tally_folds_through_the_list_it_was_bound_to():
    """Unit: ids shared by two indices sum, empty ids are skipped, and a
    new list object folds the old arrays through the OLD list."""
    t = RuleTally()
    a = ["r0", "", "r0", "r3"]
    t.add(a, np.array([0, 1, 2, 2, 3]), np.array([5, 7, 0, 1, 0]))
    assert t.read() == ({"r0": 3, "r3": 1}, {"r0": 6})
    b = ["r3", "r0"]
    t.add(b, np.array([0, 1]), None)
    assert t.read() == ({"r0": 4, "r3": 2}, {"r0": 6})
    t.add(a, np.array([3]), np.array([9]))
    assert t.read() == ({"r0": 4, "r3": 3}, {"r0": 6, "r3": 9})

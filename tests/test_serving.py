"""Serving batcher (ROADMAP item 3, batching half): canonical-shape
admission in front of the engines.

Pillars pinned here:

1. COMPILE BOUND — 64 uneven tenant worlds under `gen_bursty` arrivals
   produce at most rungs x len(canonical_sizes) XLA step executables
   (counted via the jit cache), never one per traffic-shaped lane count.
2. LANE EXACTNESS — `step_tenants` through the batcher de-interleaves
   back to per-lane verdicts that match the oracle AND the unbatched
   per-tenant dispatch, `n_miss` bookkeeping included; padded lanes are
   masked (`valid`), never visible in results or state.
3. DEADLINE DETERMINISM — the depth-OR-deadline flush runs on the
   maintenance tick clock, so a `FaultClock` drives a deadline flush at
   the EXACT configured tick, replayably.
4. OFF == OFF — with the batcher off (or merely unused), `step()` traces
   the identical program: zero new executables, identical verdicts.
5. PLANE EXCLUSION — elastic reshard and tenant creation refuse each
   other symmetrically with typed ConfigErrors naming the other plane.
"""

import copy

import numpy as np
import pytest

import jax

from antrea_tpu.config import ConfigError
from antrea_tpu.datapath import OracleDatapath, TpuflowDatapath
from antrea_tpu.dissemination.faults import FaultClock
from antrea_tpu.serving import ServingBatcher
from antrea_tpu.simulator import gen_cluster, gen_traffic
from antrea_tpu.simulator.traffic import gen_bursty

QUOTA = 1 << 8
AFFQ = 1 << 6
KW = dict(flow_slots=1 << 10, aff_slots=1 << 8, flightrec_slots=256,
          realization_slots=0)


def _dp(cls, cluster=None, **extra):
    kw = dict(KW)
    kw.update(extra)
    ps = None if cluster is None else copy.deepcopy(cluster.ps)
    return cls(ps, **kw) if ps is not None else cls(**kw)


def _batch(cluster, n, seed):
    return gen_traffic(cluster.pod_ips, n, n_flows=max(8, n // 2),
                       seed=seed)


# ---------------------------------------------------------------------------
# config surface


def test_batcher_config_rejections():
    dummy = object()
    with pytest.raises(ConfigError):
        ServingBatcher(dummy, canonical_sizes=())
    with pytest.raises(ConfigError):
        ServingBatcher(dummy, canonical_sizes=(8, 24))  # 24 not pow2
    with pytest.raises(ConfigError):
        ServingBatcher(dummy, canonical_sizes=(32, 8))  # not ascending
    with pytest.raises(ConfigError):
        ServingBatcher(dummy, canonical_sizes=(8, 8))  # duplicate rung
    with pytest.raises(ConfigError):
        ServingBatcher(dummy, flush_depth=0)
    with pytest.raises(ConfigError):
        ServingBatcher(dummy, flush_deadline=0)
    with pytest.raises(ConfigError):
        ServingBatcher(dummy, canonical_sizes=(8,), flush_depth=8,
                       ring_slots=4)  # ring can't hold one flush


def test_submit_unknown_tenant_raises():
    c = gen_cluster(6, n_nodes=2, pods_per_node=4, seed=41)
    dp = _dp(TpuflowDatapath, c, serving_batcher=True,
             canonical_sizes=(8,))
    with pytest.raises(KeyError):
        dp.serving_batcher().submit(_batch(c, 4, seed=1), 0.0, tenant=99)


# ---------------------------------------------------------------------------
# pillar 1: the compile bound


def test_compile_bound_64_uneven_tenants_under_bursty():
    """64 tenants on 4 rung shapes, trickling bursty arrivals: XLA step
    executables stay under rungs x len(canonical_sizes) — the ladder is
    the bound, traffic shape is irrelevant."""
    from antrea_tpu.models import forwarding as fwd_model

    shapes = [gen_cluster(n, n_nodes=2, pods_per_node=8, seed=s)
              for n, s in ((6, 1), (20, 2), (45, 3), (100, 4))]
    ladder = (8, 32)
    dp = _dp(TpuflowDatapath, None, flightrec_slots=0,
             serving_batcher=True, canonical_sizes=ladder,
             flush_deadline=2)
    exec0 = fwd_model.pipeline_step_full_packed._cache_size()
    tids = []
    for i in range(64):
        c = shapes[i % 4]
        tids.append(dp.tenant_create(f"t{i}", copy.deepcopy(c.ps),
                                     quota=QUOTA, aff_quota=AFFQ))
    assert dp.tenant_count == 64
    rungs = dp.tenant_rungs()
    assert len(rungs) == 4

    # Bursty per-tenant trickle: uneven 1..6-lane sub-batches — WITHOUT
    # the ladder each distinct lane count per rung would compile fresh.
    sched = gen_bursty(shapes[0].pod_ips, 10, tenants=64, burst_lanes=6,
                       seed=17)
    now = 100
    served = 0
    for entry in sched:
        now += 1
        if entry is None:
            continue
        idx, batch = entry
        res = dp.step_tenants(np.asarray([tids[int(i)] for i in idx]),
                              batch, now)
        served += int(np.asarray(res.code).shape[0])
    assert served == sum(e[0].size for e in sched if e is not None)

    execs = fwd_model.pipeline_step_full_packed._cache_size() - exec0
    bound = len(rungs) * len(ladder)
    assert 0 < execs <= bound, (
        f"{execs} step executables for 64 bursty tenants — the batcher "
        f"must bound compiles by rungs x ladder ({bound}), not traffic")
    st = dp.serving_stats()
    assert st["submitted_lanes"] == served
    assert st["shed_lanes"] == 0  # step_tenants path is lossless


# ---------------------------------------------------------------------------
# pillar 2: lane exactness


def _as_rows(res):
    """Per-lane comparable rows from a StepResult (scalar columns)."""
    code = np.asarray(res.code)
    est = np.asarray(res.est)
    committed = np.asarray(res.committed)
    return list(zip(code.tolist(), est.tolist(), committed.tolist(),
                    list(res.ingress_rule), list(res.egress_rule)))


@pytest.mark.parametrize("cls", [TpuflowDatapath, OracleDatapath])
def test_step_tenants_lane_exact_vs_unbatched(cls):
    """The batched mixed-tenant step returns exactly what per-tenant
    unbatched dispatch returns, lane for lane, and n_miss sums once per
    dispatch (not per padded lane)."""
    c0 = gen_cluster(8, n_nodes=2, pods_per_node=8, seed=11)
    c1 = gen_cluster(14, n_nodes=2, pods_per_node=8, seed=12)
    mk = lambda: _dp(cls, c0, serving_batcher=True,  # noqa: E731
                     canonical_sizes=(8, 32), flush_deadline=2)
    dp_b, dp_u = mk(), mk()
    t_b = dp_b.tenant_create("a", copy.deepcopy(c1.ps), quota=QUOTA,
                             aff_quota=AFFQ)
    t_u = dp_u.tenant_create("a", copy.deepcopy(c1.ps), quota=QUOTA,
                             aff_quota=AFFQ)

    batch = _batch(c0, 24, seed=5)
    lane_tids = np.asarray([0, t_b] * 12)
    res = dp_b.step_tenants(lane_tids, batch, 1.0)
    assert np.asarray(res.code).shape[0] == 24

    # Unbatched reference: same lanes through plain step/tenant_step.
    from antrea_tpu.datapath.tenancy import _sub_batch
    rows = [None] * 24
    n_miss = 0
    for tid_ref, tid_sel in ((0, 0), (t_u, t_b)):
        sel = np.nonzero(lane_tids == tid_sel)[0]
        sub = _sub_batch(batch, sel)
        r = (dp_u.step(sub, 1.0) if tid_ref == 0
             else dp_u.tenant_step(tid_ref, sub, 1.0))
        n_miss += int(r.n_miss)
        for lane, row in zip(sel, _as_rows(r)):
            rows[int(lane)] = row
    assert _as_rows(res) == rows
    assert int(res.n_miss) == n_miss  # padded lanes never count as misses


def test_step_tenants_oracle_parity_bursty():
    """Batched tpuflow == batched oracle over a bursty multi-tenant
    schedule (stateful across ticks: flow-cache hits included)."""
    c0 = gen_cluster(8, n_nodes=2, pods_per_node=8, seed=21)
    c1 = gen_cluster(12, n_nodes=2, pods_per_node=8, seed=22)
    dps = {}
    for cls in (TpuflowDatapath, OracleDatapath):
        dp = _dp(cls, c0, serving_batcher=True, canonical_sizes=(8, 32),
                 flush_deadline=2)
        t = dp.tenant_create("a", copy.deepcopy(c1.ps), quota=QUOTA,
                             aff_quota=AFFQ)
        dps[cls] = (dp, t)
    sched = gen_bursty(c0.pod_ips, 8, tenants=2, burst_lanes=5, seed=29)
    now = 10
    for entry in sched:
        now += 1
        if entry is None:
            continue
        idx, batch = entry
        outs = []
        for dp, t in dps.values():
            tids = np.where(np.asarray(idx) == 0, 0, t)
            outs.append(dp.step_tenants(tids, batch, now))
        a, b = outs
        assert np.array_equal(np.asarray(a.code), np.asarray(b.code))
        assert np.array_equal(np.asarray(a.committed),
                              np.asarray(b.committed))
        assert int(a.n_miss) == int(b.n_miss)


# ---------------------------------------------------------------------------
# pillar 3: deadline determinism on the FaultClock


def test_deadline_flush_at_exact_faultclock_tick():
    clk = FaultClock(start=0)
    c = gen_cluster(6, n_nodes=2, pods_per_node=4, seed=31)
    dp = _dp(TpuflowDatapath, c, serving_batcher=True,
             canonical_sizes=(8,), flush_deadline=3, maint_clock=clk)
    b = dp.serving_batcher()
    assert "serving-flush" in dp.maintenance.task_names

    tickets = b.submit(_batch(c, 3, seed=2), 0.0)  # sub-depth: waits
    assert (tickets >= 0).all()
    for _ in range(2):  # ticks 1, 2: due at neither
        clk.advance()
        assert b.tick_flush(0.0, budget=4) == 0
        assert all(b.poll(int(t)) is None for t in tickets)
        assert dp.serving_stats()["staged_lanes"] == 3
    clk.advance()  # tick 3 == flush_deadline: flush fires NOW
    assert b.tick_flush(0.0, budget=4) == 1
    outs = [b.poll(int(t)) for t in tickets]
    assert all(o is not None for o in outs)
    ev = dp._flightrec.events(kind="batch-flush")
    assert ev and ev[-1]["reason"] == "deadline"
    assert ev[-1]["age_ticks"] == 3
    # Flushed AT the deadline, not past it: no exceeded event.
    assert dp._flightrec.events(kind="batch-deadline-exceeded") == []
    assert dp.serving_stats()["flushes"]["deadline"] == 1


def test_deadline_exceeded_meters_and_emits():
    clk = FaultClock(start=0)
    c = gen_cluster(6, n_nodes=2, pods_per_node=4, seed=32)
    dp = _dp(TpuflowDatapath, c, serving_batcher=True,
             canonical_sizes=(8,), flush_deadline=2, maint_clock=clk)
    b = dp.serving_batcher()
    b.submit(_batch(c, 2, seed=3), 0.0)
    for _ in range(5):  # starve the flush well past the deadline
        clk.advance()
    assert b.tick_flush(0.0, budget=4) == 1
    ev = dp._flightrec.events(kind="batch-deadline-exceeded")
    assert len(ev) == 1 and ev[0]["age_ticks"] == 5
    assert dp.serving_stats()["deadline_exceeded"] == 1


def test_depth_flush_and_ring_overflow_shed():
    c = gen_cluster(6, n_nodes=2, pods_per_node=4, seed=33)
    dp = _dp(TpuflowDatapath, c, serving_batcher=True,
             canonical_sizes=(8,), flush_depth=8, serving_ring_slots=16,
             flush_deadline=64)
    b = dp.serving_batcher()
    b.submit(_batch(c, 8, seed=4), 1.0)
    assert b.tick_flush(1.0, budget=4) == 1  # depth-due, deadline far off
    st = dp.serving_stats()
    assert st["flushes"]["depth"] == 1 and st["staged_lanes"] == 0

    # shed=True: lanes beyond ring_slots tail-drop with -1 tickets.
    tk = b.submit(_batch(c, 20, seed=5), 2.0)
    assert (tk[:16] >= 0).all() and (tk[16:] == -1).all()
    assert dp.serving_stats()["shed_lanes"] == 4
    # shed=False on the same overflow force-flushes instead of dropping.
    tk2 = b.submit(_batch(c, 20, seed=6), 3.0, shed=False)
    assert (tk2 >= 0).all()
    assert dp.serving_stats()["flushes"]["overflow"] >= 1


# ---------------------------------------------------------------------------
# pillar 4: batcher off == bit-identical step


def test_step_traces_identically_with_batcher_configured():
    """`step()` with the batcher merely configured compiles ZERO new
    executables vs the batcher-less engine and returns identical
    verdicts — the unbatched path is untouched (valid=None traces the
    same program)."""
    from antrea_tpu.models import forwarding as fwd_model

    c = gen_cluster(10, n_nodes=2, pods_per_node=8, seed=51)
    batch = _batch(c, 32, seed=7)
    dp_off = _dp(TpuflowDatapath, c)
    r_off = dp_off.step(batch, 1.0)
    exec0 = fwd_model.pipeline_step_full_packed._cache_size()
    dp_on = _dp(TpuflowDatapath, c, serving_batcher=True,
                canonical_sizes=(8, 32))
    r_on = dp_on.step(batch, 1.0)
    assert fwd_model.pipeline_step_full_packed._cache_size() == exec0, (
        "step() with the batcher configured must reuse the exact "
        "executable of the batcher-less engine (valid=None is not a "
        "program change)")
    assert np.array_equal(np.asarray(r_off.code), np.asarray(r_on.code))
    assert int(r_off.n_miss) == int(r_on.n_miss)


# ---------------------------------------------------------------------------
# pillar 5: reshard-vs-tenant composition (PR 20 — the PR 18 mutual
# refusals are GONE; tests/test_tenant_reshard.py drives the full arcs)


@pytest.fixture(scope="module")
def mesh_world():
    from antrea_tpu.parallel import MeshDatapath, mesh as pm
    from antrea_tpu.simulator.genservice import gen_services

    cluster = gen_cluster(30, n_nodes=4, pods_per_node=8, seed=61)
    services = gen_services(4, cluster.pod_ips, seed=62)
    mesh = pm.make_mesh(2, 2, devices=jax.devices("cpu")[:4])
    return MeshDatapath, cluster, services, mesh


def test_reshard_begin_accepts_tenants(mesh_world):
    MeshDatapath, cluster, services, mesh = mesh_world
    mdp = MeshDatapath(cluster.ps, services, mesh=mesh,
                       flow_slots=1 << 10, aff_slots=1 << 8,
                       canary_probes=16)
    c1 = gen_cluster(6, n_nodes=2, pods_per_node=4, seed=63)
    tid = mdp.tenant_create("t", copy.deepcopy(c1.ps), quota=QUOTA)
    mdp.reshard_begin(4)  # the old tenancy-plane refusal is gone
    assert mdp.reshard_status() is not None
    assert mdp.reshard_stats()["tenant_worlds_migrating"] == 1
    assert mdp.tenant_stats()[tid]["latched"] == 0


def test_tenant_create_adopts_during_reshard(mesh_world):
    MeshDatapath, cluster, services, mesh = mesh_world
    mdp = MeshDatapath(cluster.ps, services, mesh=mesh,
                       flow_slots=1 << 10, aff_slots=1 << 8,
                       canary_probes=16)
    mdp.reshard_begin(4)
    c1 = gen_cluster(6, n_nodes=2, pods_per_node=4, seed=64)
    # The old resharding-plane refusal is gone: the newborn world is
    # adopted mid-flight (reshard.note_world_created) so the cutover
    # flips and certifies it with the rest of the fleet.
    tid = mdp.tenant_create("t", copy.deepcopy(c1.ps), quota=QUOTA)
    assert mdp.reshard_status() is not None
    assert mdp.reshard_stats()["tenant_worlds_migrating"] == 1
    assert tid in mdp.tenant_stats()


@pytest.mark.parametrize("cls", [TpuflowDatapath, OracleDatapath])
def test_tenant_create_ignores_reshard_marker_both_engines(cls):
    """The tenancy-side refusal is gone engine-generically: an in-flight
    reshard marker no longer blocks world creation (the mesh plane
    adopts via note_world_created; single-chip engines carry no plane to
    join, so creation simply proceeds)."""
    c = gen_cluster(6, n_nodes=2, pods_per_node=4, seed=65)
    dp = _dp(cls, c)
    dp._reshard = object()  # simulate an in-flight resize marker
    c1 = gen_cluster(6, n_nodes=2, pods_per_node=4, seed=66)
    tid = dp.tenant_create("t", copy.deepcopy(c1.ps), quota=QUOTA)
    assert tid in dp.tenant_stats()


# ---------------------------------------------------------------------------
# traffic generator + observability surfaces


def test_gen_bursty_deterministic_and_tenant_scoped():
    c = gen_cluster(6, n_nodes=2, pods_per_node=4, seed=71)
    s1 = gen_bursty(c.pod_ips, 12, tenants=3, seed=9)
    s2 = gen_bursty(c.pod_ips, 12, tenants=[0, 1, 2], seed=9)
    assert len(s1) == 12
    for a, b in zip(s1, s2):
        assert (a is None) == (b is None)
        if a is None:
            continue
        assert np.array_equal(a[0], b[0])
        assert np.array_equal(np.asarray(a[1].src_ip),
                              np.asarray(b[1].src_ip))
        assert set(np.unique(a[0])) <= {0, 1, 2}
        assert a[0].shape[0] == a[1].size
    assert any(e is not None for e in s1)


def test_serving_metrics_render_and_stats():
    c0 = gen_cluster(8, n_nodes=2, pods_per_node=8, seed=81)
    c1 = gen_cluster(10, n_nodes=2, pods_per_node=8, seed=82)
    dp = _dp(TpuflowDatapath, c0, serving_batcher=True,
             canonical_sizes=(8, 32), flush_deadline=2)
    t = dp.tenant_create("a", copy.deepcopy(c1.ps), quota=QUOTA,
                         aff_quota=AFFQ)
    batch = _batch(c0, 12, seed=8)
    dp.step_tenants(np.asarray([0, t] * 6), batch, 1.0)

    st = dp.serving_stats()
    assert st["submitted_lanes"] == 12
    assert st["flushed_lanes"] == 12
    assert set(st["worlds"]) == {0, t}
    assert st["worlds"][t]["flushed_lanes"] == 6

    from antrea_tpu.observability.metrics import render_metrics
    txt = render_metrics(dp, node="n0")
    for fam in ("antrea_tpu_serving_submitted_lanes_total",
                "antrea_tpu_serving_dispatches_total",
                "antrea_tpu_serving_flushes_total",
                "antrea_tpu_serving_wait_ticks_bucket"):
        assert fam in txt, f"{fam} missing from exposition"
    # Engines without the batcher render no serving families.
    dp_off = _dp(TpuflowDatapath, c0)
    assert "antrea_tpu_serving" not in render_metrics(dp_off, node="n0")

"""Two-level aggregated-bitmap match pruning (ISSUE 10 tentpole,
ops/match round 7): bitwise verdict/attribution parity of the pruned
path against the unpruned kernel and the scalar oracle, the adversarial
worlds (100% fallback, crafted aggregate false positive), the
aggregate/incidence consistency property (deltas + mesh word-sharding
included), HLO bit-identity at prune_budget=0, canary/audit
certification of the pruned path, and the K-budget autotuner."""

import numpy as np
import pytest

from antrea_tpu.apis.controlplane import Direction, GroupMember, RuleAction
from antrea_tpu.compiler.compile import compile_policy_set
from antrea_tpu.config import ConfigError
from antrea_tpu.datapath import OracleDatapath, TpuflowDatapath
from antrea_tpu.models import pipeline as pl
from antrea_tpu.observability.metrics import render_metrics
from antrea_tpu.ops import match as m
from antrea_tpu.oracle import Oracle
from antrea_tpu.simulator import gen_cluster, gen_traffic

from fixtures_reachability import _ps, acnp, ag, atg, peer, rule

import jax.numpy as jnp

PARITY_KEYS = ("code", "egress_code", "egress_rule", "ingress_code",
               "ingress_rule")


def _classify(drs, meta, tr, fused=False, **kw):
    out = m._classify_jit(
        drs,
        m.flip_ips(tr.src_ip),
        m.flip_ips(tr.dst_ip),
        tr.proto.astype(np.int32),
        tr.dst_port.astype(np.int32),
        meta=meta, fused=fused, **kw,
    )
    return {k: np.asarray(v) for k, v in out.items()}


def _assert_parity(o_ref, o_pruned, ctx):
    for k in PARITY_KEYS:
        assert np.array_equal(o_ref[k], o_pruned[k]), (ctx, k)


# ---------------------------------------------------------------------------
# Kernel parity: pruned vs unpruned vs oracle, fallback path included
# ---------------------------------------------------------------------------


# The adversarial regimes below run once through the XLA scan of the
# candidate matrices and once through their Pallas consumer (`fused`):
# a consumer that mis-reads a fill candidate or a fallback lane shows here.
BOTH_CONSUMERS = pytest.mark.parametrize("fused", [False, True],
                                         ids=["xla_scan", "fused"])


@BOTH_CONSUMERS
def test_pruned_kernel_parity_and_fallback(fused):
    """A multi-superblock world at K=1 exercises the pow2-rung fallback;
    K=4 exercises the pure candidate path — both must be bitwise equal
    to the unpruned kernel, and spot-equal to the scalar oracle."""
    cluster = gen_cluster(2500, seed=2)
    cps = compile_policy_set(cluster.ps)
    tr = gen_traffic(cluster.pod_ips, batch=192, seed=3)
    drs0, meta0 = m.to_device(cps)
    o0 = _classify(drs0, meta0, tr)
    saw_fb = False
    for k in (1, 4):
        drs1, meta1 = m.to_device(cps, prune_budget=k)
        assert drs1.ingress.at.agg is not None
        o1 = _classify(drs1, meta1, tr, fused=fused)
        _assert_parity(o0, o1, f"K={k}")
        saw_fb = saw_fb or o1["prune_fb"].any()
    assert saw_fb, "the world never exercised the fallback redispatch"
    oracle = Oracle(cluster.ps)
    for i in range(0, tr.size, 4):
        assert int(o1["code"][i]) == int(oracle.classify(tr.packet(i)).code)


def test_pruned_fused_consumer_parity():
    cluster = gen_cluster(400, seed=5)
    cps = compile_policy_set(cluster.ps)
    tr = gen_traffic(cluster.pod_ips, batch=128, seed=6)
    drs0, meta0 = m.to_device(cps)
    drs1, meta1 = m.to_device(cps, prune_budget=2)
    o0 = _classify(drs0, meta0, tr)
    o1 = _classify(drs1, meta1, tr, fused=True)
    _assert_parity(o0, o1, "fused")


# ---------------------------------------------------------------------------
# Adversarial worlds
# ---------------------------------------------------------------------------


def _dense_ps(n_rules: int):
    """Every rule applies to `web` from ANY peer on any service: every
    incidence word is nonzero in all three dimensions for a matching
    probe, so every superblock is a candidate (the 100%-fallback world
    at small K)."""
    rules = [rule(Direction.IN, peer(), action=RuleAction.ALLOW)
             for _ in range(n_rules)]
    return _ps(
        [acnp("dense", ["at_web"], rules)],
        applied_groups=[atg("at_web", "web")],
    )


@BOTH_CONSUMERS
def test_dense_world_full_fallback_parity(fused):
    # > 1024 ingress rules => at least 2 superblocks; every one a
    # candidate for web-bound traffic, so K=1 lanes ALL fall back.
    ps = _dense_ps(1100)
    cps = compile_policy_set(ps)
    from antrea_tpu.packet import Packet, PacketBatch

    pkts = [Packet(src_ip=0x0A0A0000 + i, dst_ip=0x0A0A0007, proto=6,
                   src_port=31000 + i, dst_port=80) for i in range(64)]
    batch = PacketBatch.from_packets(pkts)
    tr = batch  # same column surface as gen_traffic's batch
    drs0, meta0 = m.to_device(cps)
    drs1, meta1 = m.to_device(cps, prune_budget=1)
    assert drs1.ingress.at.agg.shape[1] >= 2
    o0 = _classify(drs0, meta0, tr)
    o1 = _classify(drs1, meta1, tr, fused=fused)
    _assert_parity(o0, o1, "dense")
    # 100% fallback: the degenerate case degrades to the unpruned
    # dispatch shape (ONE bounded full-width redispatch covering every
    # lane), never to a wrong verdict.
    assert o1["prune_fb"].all()
    assert not o1["prune_skip"].any()
    oracle = Oracle(ps)
    assert int(o1["code"][0]) == int(oracle.classify(pkts[0]).code) == 0
    # Both engines: the datapaths agree step-for-step on this world too.
    dp = TpuflowDatapath(ps, flow_slots=1 << 8, aff_slots=1 << 6,
                         miss_chunk=16, prune_budget=1, fused=fused,
                         canary_probes=0, flightrec_slots=0,
                         realization_slots=0)
    od = OracleDatapath(ps, flow_slots=1 << 8, prune_budget=1,
                        canary_probes=0, flightrec_slots=0,
                        realization_slots=0)
    r, ro = dp.step(batch, now=1), od.step(batch, now=1)
    assert list(r.code) == list(ro.code)
    assert dp.prune_stats()["fallbacks_total"] == batch.size


@BOTH_CONSUMERS
def test_aggregate_false_positive_world(fused):
    """Per-dimension aggregate bits all set on the same word, 3-way AND
    empty: the candidate gather must find nothing and the lane must take
    the DEFAULT verdict with zero fallbacks — a false positive costs a
    narrow gather, never a verdict."""
    ps = _ps(
        [acnp("fp", ["at_web"], [
            rule(Direction.IN, peer("g_a"), action=RuleAction.DROP),
        ]),
         acnp("fp2", ["at_db"], [
             rule(Direction.IN, peer("g_b"), action=RuleAction.DROP),
         ])],
        addr_groups=[ag("g_a", "client"), ag("g_b", "other")],
        applied_groups=[atg("at_web", "web"), atg("at_db", "db")],
    )
    cps = compile_policy_set(ps)
    from antrea_tpu.packet import Packet, PacketBatch

    # src = other (matches ONLY fp2's peer bit), dst = web (matches ONLY
    # fp's appliedTo bit): every dimension's aggregate word is nonzero,
    # the AND is empty.
    pkt = Packet(src_ip=0x0A0A0105, dst_ip=0x0A0A0007, proto=6,
                 src_port=31000, dst_port=80)
    batch = PacketBatch.from_packets([pkt] * 8)
    drs1, meta1 = m.to_device(cps, prune_budget=4)
    o1 = _classify(drs1, meta1, batch, fused=fused)
    drs0, meta0 = m.to_device(cps)
    o0 = _classify(drs0, meta0, batch)
    _assert_parity(o0, o1, "false-positive")
    assert not o1["prune_skip"].any()  # the aggregate AND was NOT zero
    assert not o1["prune_fb"].any()
    assert int(o1["code"][0]) == int(Oracle(ps).classify(pkt).code) == 0
    assert int(o1["ingress_rule"][0]) == -1  # default, no attribution


# ---------------------------------------------------------------------------
# Aggregate/incidence consistency property (build_agg is the invariant)
# ---------------------------------------------------------------------------


def _assert_agg_consistent(drs):
    for dd in (drs.ingress, drs.egress):
        for tab in (dd.at, dd.peer, dd.svc):
            inc = np.asarray(tab.inc)
            assert inc.shape[1] % m.TILE_WORDS == 0  # AGG_BLOCK divides it
            assert np.array_equal(np.asarray(tab.agg), m.build_agg(inc))


def test_agg_rebuilds_from_incidence_after_deltas_and_sharding():
    cluster = gen_cluster(300, seed=7)
    dp = TpuflowDatapath(cluster.ps, flow_slots=1 << 8, aff_slots=1 << 6,
                         miss_chunk=16, prune_budget=2, canary_probes=0,
                         flightrec_slots=0, realization_slots=0)
    _assert_agg_consistent(dp._drs)
    # O(1) group delta: tables untouched, aggregate still consistent,
    # and the DELTA path (not a recompile) was actually taken.
    name = next(iter(dp._group_members))
    dp.apply_group_delta(name, added_ips=["10.99.0.1"], removed_ips=[])
    assert dp._n_deltas > 0
    _assert_agg_consistent(dp._drs)
    # Recompile fold (install_bundle) rebuilds both levels together.
    dp.install_bundle(cluster.ps)
    _assert_agg_consistent(dp._drs)

    # Mesh word-sharding: the global tables stay consistent AND each
    # rule shard's slice is tiled (W/n_rule % TILE_WORDS == 0: whole
    # superblocks), so per-shard aggregates cover exactly their own
    # incidence words.
    from antrea_tpu.parallel.meshpath import MeshDatapath

    md = MeshDatapath(cluster.ps, n_data=2, n_rule=2, flow_slots=1 << 8,
                      aff_slots=1 << 6, miss_chunk=16, prune_budget=2,
                      canary_probes=0, flightrec_slots=0,
                      realization_slots=0)
    _assert_agg_consistent(md._drs)
    for dd in (md._drs.ingress, md._drs.egress):
        w = dd.at.inc.shape[1]
        s = dd.at.agg.shape[1]
        assert w % (2 * m.TILE_WORDS) == 0  # n_rule=2: a tile a shard
        assert s % 2 == 0 and s * m.AGG_BLOCK == w
        # Shard d's aggregate slice == build_agg of shard d's inc slice.
        inc = np.asarray(dd.at.inc)
        agg = np.asarray(dd.at.agg)
        for d in range(2):
            lo, hi = d * (w // 2), (d + 1) * (w // 2)
            assert np.array_equal(
                agg[:, d * (s // 2):(d + 1) * (s // 2)],
                m.build_agg(inc[:, lo:hi]))


def test_group_delta_pruned_parity_both_engines():
    """Membership deltas must patch the aggregate level too: fresh
    5-tuples touching the added/removed member classify identically on
    the pruned kernel engine and the scalar oracle engine."""
    cluster = gen_cluster(300, seed=8)
    kw = dict(flow_slots=1 << 8, aff_slots=1 << 6, canary_probes=0,
              flightrec_slots=0, realization_slots=0)
    dp = TpuflowDatapath(cluster.ps, miss_chunk=16, prune_budget=2, **kw)
    od = OracleDatapath(cluster.ps, prune_budget=2, **kw)
    name = next(iter(dp._group_members))
    for eng in (dp, od):
        eng.apply_group_delta(name, added_ips=["10.77.3.9"],
                              removed_ips=[])
    assert dp._n_deltas > 0  # the O(1) slot path, not a recompile
    tr = gen_traffic(cluster.pod_ips, batch=64, seed=9)
    # Aim half the probes AT the new member (both directions).
    tr.src_ip[:16] = 0x0A4D0309
    tr.dst_ip[16:32] = 0x0A4D0309
    r, ro = dp.step(tr, now=1), od.step(tr, now=1)
    assert list(r.code) == list(ro.code)
    assert list(r.ingress_rule) == list(ro.ingress_rule)
    assert list(r.egress_rule) == list(ro.egress_rule)
    # Removal exercises the CLEAR slots (stale aggregate bits are legal
    # false positives resolved by the candidate gather's full words).
    for eng in (dp, od):
        eng.apply_group_delta(name, added_ips=[],
                              removed_ips=["10.77.3.9"])
    r2, ro2 = dp.step(tr, now=2), od.step(tr, now=2)
    assert list(r2.code) == list(ro2.code)


# ---------------------------------------------------------------------------
# HLO identity at prune_budget=0 + engine-mode parity
# ---------------------------------------------------------------------------


def test_step_hlo_bit_identical_with_prune_disabled():
    """prune_budget=0 (explicit) must compile the EXACT default program:
    no aggregate tables, no extra outputs, no candidate/fallback ops."""
    cluster = gen_cluster(60, n_nodes=2, pods_per_node=4, seed=5)
    a = TpuflowDatapath(cluster.ps, flow_slots=1 << 8, aff_slots=1 << 6,
                        miss_chunk=16, canary_probes=0,
                        flightrec_slots=0, realization_slots=0)
    b = TpuflowDatapath(cluster.ps, flow_slots=1 << 8, aff_slots=1 << 6,
                        miss_chunk=16, prune_budget=0, canary_probes=0,
                        flightrec_slots=0, realization_slots=0)
    assert b._drs.ingress.at.agg is None

    def lower_text(dp):
        z = jnp.zeros(8, jnp.int32)
        return pl.pipeline_step.lower(
            dp._state, dp._drs, dp._dsvc, z, z, z, z, z,
            jnp.int32(0), jnp.int32(0), meta=dp._meta,
        ).as_text()

    assert lower_text(a) == lower_text(b)
    # And the pruned program is genuinely a different (two-level) one.
    c = TpuflowDatapath(cluster.ps, flow_slots=1 << 8, aff_slots=1 << 6,
                        miss_chunk=16, prune_budget=2, canary_probes=0,
                        flightrec_slots=0, realization_slots=0)
    assert lower_text(c) != lower_text(a)


def test_async_mode_pruned_parity():
    cluster = gen_cluster(300, seed=10)
    kw = dict(flow_slots=1 << 8, aff_slots=1 << 6, async_slowpath=True,
              miss_queue_slots=1 << 10, drain_batch=64, canary_probes=0,
              flightrec_slots=0, realization_slots=0)
    dp = TpuflowDatapath(cluster.ps, miss_chunk=16, prune_budget=2, **kw)
    od = OracleDatapath(cluster.ps, prune_budget=2, **kw)
    tr = gen_traffic(cluster.pod_ips, batch=64, seed=11)
    for eng in (dp, od):
        eng.step(tr, now=1)
        eng.drain_slowpath(now=2)
    r, ro = dp.step(tr, now=3), od.step(tr, now=3)
    assert list(r.code) == list(ro.code)
    assert list(r.est) == list(ro.est)
    assert dp.prune_stats()["classified_total"] > 0  # the drain pruned


def test_rule_sharded_prune_observables_replicated():
    """Under rule sharding the prune observables must be COMBINED over
    the rule axis (skip=AND, fb=OR, cand=per-shard MAX), not one
    arbitrary shard's locals: skip must equal the single-chip mask
    exactly, cand must bound the global count from both sides, and no
    lane the global budget covers may report a fallback."""
    from antrea_tpu.parallel.mesh import make_mesh, make_sharded_classifier

    cluster = gen_cluster(2500, seed=2)
    cps = compile_policy_set(cluster.ps)
    tr = gen_traffic(cluster.pod_ips, batch=64, seed=3)
    drs1, meta1 = m.to_device(cps, prune_budget=2)
    o1 = _classify(drs1, meta1, tr)
    fn, _drs = make_sharded_classifier(cps, make_mesh(1, 2),
                                       prune_budget=2)
    om = fn(m.flip_ips(tr.src_ip), m.flip_ips(tr.dst_ip),
            tr.proto.astype(np.int32), tr.dst_port.astype(np.int32))
    om = {k: np.asarray(v) for k, v in om.items()}
    assert np.array_equal(om["code"], o1["code"])
    assert np.array_equal(om["prune_skip"], o1["prune_skip"])
    cand_s, cand_g = om["prune_cand"], o1["prune_cand"]
    # max-per-shard is sandwiched by [ceil(global/2), global] on 2 shards.
    assert (cand_s <= cand_g).all() and (2 * cand_s >= cand_g).all()
    # A lane the GLOBAL budget covers can never fall back on any shard.
    assert not om["prune_fb"][cand_g <= 2].any()


def test_mesh_mode_pruned_parity():
    from antrea_tpu.parallel.meshpath import MeshDatapath

    cluster = gen_cluster(300, seed=12)
    kw = dict(flow_slots=1 << 8, aff_slots=1 << 6, miss_chunk=16,
              prune_budget=2, canary_probes=0, flightrec_slots=0,
              realization_slots=0)
    md = MeshDatapath(cluster.ps, n_data=2, n_rule=2, **kw)
    sd = TpuflowDatapath(cluster.ps, **kw)
    tr = gen_traffic(cluster.pod_ips, batch=64, seed=13)
    rm, rs = md.step(tr, now=1), sd.step(tr, now=1)
    assert list(rm.code) == list(rs.code)
    assert list(rm.ingress_rule) == list(rs.ingress_rule)
    assert list(rm.egress_rule) == list(rs.egress_rule)
    assert md.prune_stats()["classified_total"] > 0


def test_toservices_svcref_pruned_parity():
    """The egress svc dimension's SECOND (ServiceReference) probe ORs a
    second aggregate row and a second candidate gather — the frontends
    of a referenced Service must still drop, direct-to-endpoint traffic
    must not, bitwise against the scalar engine."""
    import test_toservices as t
    from antrea_tpu.packet import PacketBatch

    dp = TpuflowDatapath(t._ps(), t.SVCS, flow_slots=1 << 10,
                         aff_slots=1 << 4, node_ips=[t.NODE_IP],
                         node_name="n1", miss_chunk=16, prune_budget=2,
                         canary_probes=0, flightrec_slots=0,
                         realization_slots=0)
    od = OracleDatapath(t._ps(), t.SVCS, flow_slots=1 << 10,
                        aff_slots=1 << 4, node_ips=[t.NODE_IP],
                        node_name="n1", canary_probes=0,
                        flightrec_slots=0, realization_slots=0)
    probes = [t._pkt(t.CLIENT, "10.96.0.10", 5432),
              t._pkt(t.CLIENT, t.NODE_IP, 30032),
              t._pkt(t.CLIENT, t.DB_EP, 5432),
              t._pkt(t.CLIENT, "10.96.0.11", 80),
              t._pkt("10.0.8.8", "10.96.0.10", 5432)]
    r = dp.step(PacketBatch.from_packets(probes), now=5)
    ro = od.step(PacketBatch.from_packets(probes), now=5)
    assert list(r.code) == list(ro.code) == [1, 1, 0, 0, 0]
    assert r.egress_rule == ro.egress_rule


# ---------------------------------------------------------------------------
# Planes certify the pruned path; observability; autotuner
# ---------------------------------------------------------------------------


def test_canary_and_audit_certify_pruned_path():
    cluster = gen_cluster(300, seed=14)
    dp = TpuflowDatapath(cluster.ps, flow_slots=1 << 8, aff_slots=1 << 6,
                         miss_chunk=16, prune_budget=2, canary_probes=16)
    assert dp._meta.match.prune_budget == 2  # the canary walks THIS meta
    tr = gen_traffic(cluster.pod_ips, batch=64, seed=15)
    dp.step(tr, now=1)
    gen0 = dp.generation
    dp.install_bundle(cluster.ps)  # canary-gated through the pruned walk
    cp = dp.commit_stats()
    assert dp.generation == gen0 + 1 and not cp["degraded"]
    assert cp["canary_probes_total"] > 0
    assert cp["canary_mismatches_total"] == 0
    dp.audit_scan(now=2, full=True)  # fresh re-proof through the pruned walk
    au = dp.audit_stats()
    assert au["entries_total"] > 0
    assert au["repairs_total"] == 0 and not au["divergences"]


def test_prune_metrics_rendered():
    # Same world/shapes as test_group_delta_pruned_parity_both_engines
    # on purpose (shared jit cache keeps the suite fast).
    cluster = gen_cluster(300, seed=8)
    dp = TpuflowDatapath(cluster.ps, flow_slots=1 << 8, aff_slots=1 << 6,
                         miss_chunk=16, prune_budget=2, canary_probes=0,
                         flightrec_slots=0, realization_slots=0)
    dp.step(gen_traffic(cluster.pod_ips, batch=64, seed=17), now=1)
    txt = render_metrics(dp, node="n")
    for fam in ("antrea_tpu_match_prune_skips_total",
                "antrea_tpu_match_prune_fallbacks_total",
                "antrea_tpu_match_prune_budget",
                "antrea_tpu_match_prune_retunes_total",
                "antrea_tpu_match_prune_candidate_superblocks_bucket"):
        assert fam in txt, fam
    # Off instances expose NO prune families (plane-scoped surface).
    off = TpuflowDatapath(cluster.ps, flow_slots=1 << 8, aff_slots=1 << 6,
                          miss_chunk=16, canary_probes=0,
                          flightrec_slots=0, realization_slots=0)
    assert off.prune_stats() is None
    assert "match_prune" not in render_metrics(off, node="n")


def test_prune_autotuner_unit():
    t = m.PruneAutotuner(4)
    assert t.budget == 4
    # Two consecutive high-fallback windows: one rung up, streak reset.
    assert t.observe(1000, 100) == 4
    assert t.observe(1000, 100) == 8
    assert t.observe(1000, 100) == 8
    # Direction flip resets the streak; two lows walk back down.
    assert t.observe(1000, 0) == 8
    assert t.observe(1000, 0) == 4
    # In-band rates and empty windows hold.
    assert t.observe(1000, 20) == 4
    assert t.observe(0, 0) == 4
    assert t.decisions_up == 1 and t.decisions_down == 1
    # Clamped at the ladder ends.
    t2 = m.PruneAutotuner(m.PRUNE_LADDER[-1])
    for _ in range(6):
        t2.observe(100, 100)
    assert t2.budget == m.PRUNE_LADDER[-1]


def test_autotune_retune_end_to_end():
    """A 100%-fallback world at K=1 presses the controller up the ladder
    within two decision windows; the retune is journaled and subsequent
    steps serve the new rung with unchanged verdicts."""
    ps = _dense_ps(1100)
    dp = TpuflowDatapath(ps, flow_slots=1 << 8, aff_slots=1 << 6,
                         miss_chunk=16, prune_budget=1,
                         autotune_prune=True, canary_probes=0)
    from antrea_tpu.packet import Packet, PacketBatch

    def fresh(n0):
        # 64 lanes on purpose: shares the dense world's compiled step
        # (same meta + shapes as test_dense_world_full_fallback_parity).
        return PacketBatch.from_packets([
            Packet(src_ip=0x0A0A0000 + n0 + i, dst_ip=0x0A0A0007, proto=6,
                   src_port=31000, dst_port=80) for i in range(64)])

    r1 = dp.step(fresh(0), now=1)
    r2 = dp.step(fresh(100), now=2)
    assert dp._prune_budget == 2  # two sticky high-rate signals -> one rung
    assert dp._meta.match.prune_budget == 2
    ev = dp.flightrecorder_events(kind="prune-retune")
    assert ev and ev[-1]["budget_from"] == 1 and ev[-1]["budget_to"] == 2
    assert dp.prune_stats()["retunes_total"] == 1
    r3 = dp.step(fresh(0), now=3)  # same flows: now cache hits, still ALLOW
    assert set(r1.code) == set(r2.code) == set(r3.code) == {0}


def test_prune_config_errors():
    cluster = gen_cluster(60, n_nodes=2, pods_per_node=4, seed=5)
    for eng in (TpuflowDatapath, OracleDatapath):
        with pytest.raises(ConfigError):
            eng(cluster.ps, prune_budget=-1)
        with pytest.raises(ConfigError):
            eng(cluster.ps, autotune_prune=True)


# ---------------------------------------------------------------------------
# Full reachability fixtures through the pruned kernel (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_pruned_kernel_matches_all_reachability_fixtures():
    from fixtures_reachability import SCENARIOS
    from test_reachability_fixtures import _probe_packet
    from antrea_tpu.packet import PacketBatch

    for scenario in SCENARIOS:
        cps = compile_policy_set(scenario.ps)
        batch = PacketBatch.from_packets(
            [_probe_packet(p) for p in scenario.probes])
        for k in (1, 4):
            drs, meta = m.to_device(cps, prune_budget=k)
            out = _classify(drs, meta, batch)
            for i, p in enumerate(scenario.probes):
                assert int(out["code"][i]) == p.expect, (
                    scenario.name, k, p)

"""fused=True over the pruned classify (the Pallas consumer of the
candidate matrices, ops/match._pruned_consumer_call): bitwise
fused-vs-XLA-scan-vs-oracle parity across the fallback, svcref,
delta-slot, mesh and async-drain regimes; no-pallas HLO pinning at
fused=False; canary+audit certification of a fused instance; the
spill-retry prune-accounting dedupe; the second-chance replacement seed;
and per-source admission rate limiting."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from antrea_tpu.compiler.compile import compile_policy_set
from antrea_tpu.config import ConfigError
from antrea_tpu.datapath import OracleDatapath, TpuflowDatapath
from antrea_tpu.models import pipeline as pl
from antrea_tpu.packet import Packet, PacketBatch
from antrea_tpu.simulator import gen_cluster, gen_traffic
from antrea_tpu.utils import ip as iputil

KW = dict(flow_slots=1 << 10, aff_slots=1 << 6, canary_probes=0,
          flightrec_slots=0, realization_slots=0)


def _fused(ps, services=None, prune=2, **kw):
    return TpuflowDatapath(ps, services, fused=True, prune_budget=prune,
                           **{"miss_chunk": 32, **KW, **kw})


def _staged(ps, services=None, prune=2, **kw):
    return TpuflowDatapath(ps, services, prune_budget=prune,
                           **{"miss_chunk": 32, **KW, **kw})


def _oracle(ps, services=None, **kw):
    return OracleDatapath(ps, services, **{**KW, **kw})


def _assert_result_parity(a, b, ctx, est=True):
    assert list(a.code) == list(b.code), ctx
    assert list(a.ingress_rule) == list(b.ingress_rule), ctx
    assert list(a.egress_rule) == list(b.egress_rule), ctx
    assert list(a.svc_idx) == list(b.svc_idx), ctx
    assert list(a.dnat_ip) == list(b.dnat_ip), ctx
    assert list(a.dnat_port) == list(b.dnat_port), ctx
    assert list(a.committed) == list(b.committed), ctx
    assert list(a.snat) == list(b.snat), ctx
    assert list(a.dsr) == list(b.dsr), ctx
    if est:
        assert list(a.est) == list(b.est), ctx
        assert list(a.reply) == list(b.reply), ctx


def _assert_state_parity(a, b, ctx):
    """Commit-row parity: the two engines' flow caches must be bitwise
    identical.  Row N (the dump row, the masked-scatter junk target no
    lookup ever reads) is excluded."""
    for name in ("keys", "meta", "ts"):
        av = np.asarray(getattr(a._state.flow, name))[:-1]
        bv = np.asarray(getattr(b._state.flow, name))[:-1]
        assert np.array_equal(av, bv), (ctx, name)


# ---------------------------------------------------------------------------
# Tentpole: fused vs staged vs oracle, fallback path included
# ---------------------------------------------------------------------------


def test_fused_step_parity_steady_cold_fallback_and_delta():
    """A multi-superblock world at K=1 exercises the candidate path AND
    the pow2-rung fallback; the fused step must be bitwise equal to the
    XLA-scan pruned engine (outputs AND commit rows) and to the scalar
    oracle, cold (all-miss) and steady (all-hit) alike.

    The SAME engines then take pending membership deltas (one world, one
    compile set — the tier-1 wall-clock discipline): SET slots patch the
    aggregate rows conservatively and the candidate words exactly
    (_patch_cand), bitwise on traffic aimed straight at the
    added/removed members."""
    cluster = gen_cluster(2500, seed=12)
    fd = _fused(cluster.ps, prune=1, delta_slots=16)
    sd = _staged(cluster.ps, prune=1, delta_slots=16)
    od = _oracle(cluster.ps, fused=True, prune_budget=1)
    tr = gen_traffic(cluster.pod_ips, batch=160, seed=5)
    for t in range(3):  # t=0 cold, t>0 mostly established
        rf, rs, ro = (fd.step(tr, now=1 + t), sd.step(tr, now=1 + t),
                      od.step(tr, now=1 + t))
        _assert_result_parity(rf, rs, f"staged t={t}")
        _assert_result_parity(rf, ro, f"oracle t={t}")
        _assert_state_parity(fd, sd, f"state t={t}")
    ps = fd.prune_stats()
    assert ps["fallbacks_total"] > 0, "K=1 never exercised the fallback"
    assert ps["skips_total"] > 0 and ps["classified_total"] > 0
    # --- pending-delta phase: O(1) slot patches force the exact fallback.
    g = next(iter(cluster.ps.address_groups))
    members = cluster.ps.address_groups[g].members
    new_ip = "10.200.1.7"
    rm_ip = members[0].ip if members else None
    for dp in (fd, sd, od):
        dp.apply_group_delta(g, added_ips=[new_ip], removed_ips=[])
        if rm_ip:
            dp.apply_group_delta(g, added_ips=[], removed_ips=[rm_ip])
    assert fd._n_deltas >= 1  # the O(1) slot path, not a recompile fold
    # Fresh unique 5-tuples, every lane featuring a delta'd address on
    # one side — padded to the steady batch size so the delta step rides
    # the already-compiled program variant.
    targets = [new_ip] + ([rm_ip] if rm_ip else [])
    pods = [iputil.u32_to_ip(int(p)) for p in cluster.pod_ips[:128]]
    pkts = []
    sport = 31000
    for b_ in pods:
        for a in targets:
            for src, dst in ((a, b_), (b_, a)):
                sport += 1
                pkts.append(Packet(src_ip=iputil.ip_to_u32(src),
                                   dst_ip=iputil.ip_to_u32(dst),
                                   proto=6, src_port=sport, dst_port=80))
        if len(pkts) >= tr.size:
            break
    batch = PacketBatch.from_packets(pkts[:tr.size])
    assert batch.size == tr.size  # shares the steady step's compile
    rf, rs, ro = (fd.step(batch, now=10), sd.step(batch, now=10),
                  od.step(batch, now=10))
    _assert_result_parity(rf, rs, "delta staged")
    _assert_result_parity(rf, ro, "delta oracle")
    _assert_state_parity(fd, sd, "delta state")


def test_fused_churn_and_teardown_parity():
    """Churn shape: fresh flows every step plus FIN teardown of
    established ones — the commit/reclaim/teardown interleavings must
    reproduce bitwise."""
    cluster = gen_cluster(600, seed=3)
    fd = _fused(cluster.ps)
    sd = _staged(cluster.ps)
    for t in range(4):
        tr = gen_traffic(cluster.pod_ips, batch=96, seed=20 + t)
        rf, rs = fd.step(tr, now=10 + t), sd.step(tr, now=10 + t)
        _assert_result_parity(rf, rs, f"churn t={t}")
        _assert_state_parity(fd, sd, f"churn state t={t}")


def test_fused_svcref_parity():
    """toServices (svcref) worlds OR a second aggregate row and a second
    candidate gather — frontends of the referenced Service drop,
    direct-to-endpoint traffic does not, bitwise vs the oracle."""
    import test_toservices as t

    kws = dict(aff_slots=1 << 4, node_ips=[t.NODE_IP], node_name="n1")
    fd = _fused(t._ps(), t.SVCS, **kws)
    sd = _staged(t._ps(), t.SVCS, **kws)
    od = OracleDatapath(t._ps(), t.SVCS, fused=True, prune_budget=2,
                        **{**KW, **kws})
    assert fd._meta.match.svcref
    probes = [t._pkt(t.CLIENT, "10.96.0.10", 5432),
              t._pkt(t.CLIENT, t.NODE_IP, 30032),
              t._pkt(t.CLIENT, t.DB_EP, 5432),
              t._pkt(t.CLIENT, "10.96.0.11", 80),
              t._pkt("10.0.8.8", "10.96.0.10", 5432)]
    b = PacketBatch.from_packets(probes)
    for now in (1, 2):
        rf, rs, ro = fd.step(b, now=now), sd.step(b, now=now), od.step(
            b, now=now)
        _assert_result_parity(rf, rs, f"svcref staged now={now}")
        _assert_result_parity(rf, ro, f"svcref oracle now={now}")
        _assert_state_parity(fd, sd, f"svcref state now={now}")


def test_fused_mesh_parity():
    """The rule-sharded mesh: the kernel emits GLOBAL hits for the pmin
    seam — verdict + attribution
    parity vs the scalar oracle on (data x rule) = (2, 2).  (The oracle
    is the comparator here — fused-vs-staged parity is pinned by the
    single-chip regimes above, and the oracle twin costs no second XLA
    compile.)"""
    from antrea_tpu.parallel.meshpath import MeshDatapath

    cluster = gen_cluster(2500, seed=12)
    md = MeshDatapath(cluster.ps, n_data=2, n_rule=2, miss_chunk=16,
                      fused=True, prune_budget=2, **KW)
    od = _oracle(cluster.ps, fused=True, prune_budget=2)
    tr = gen_traffic(cluster.pod_ips, batch=64, seed=14)
    for now in (1, 2):
        rm, ro = md.step(tr, now=now), od.step(tr, now=now)
        assert list(rm.code) == list(ro.code), now
        assert list(rm.ingress_rule) == list(ro.ingress_rule), now
        assert list(rm.egress_rule) == list(ro.egress_rule), now
        assert list(rm.svc_idx) == list(ro.svc_idx), now
    assert md.prune_stats()["classified_total"] > 0
    # The replica-resolved canary must walk the SERVING (fused) consumer
    # too — its jit key carries the instance's fused meta.
    import antrea_tpu.ops.match as mops

    probes = PacketBatch.from_packets([tr.packet(i) for i in range(8)])
    seen = []
    orig = mops.classify_batch

    def _rec(*a, **k):
        seen.append(bool(k.get("fused", False)))
        return orig(*a, **k)

    mops.classify_batch = _rec
    try:
        got = md._canary_classify(probes, now=3)
    finally:
        mops.classify_batch = orig
    assert seen and all(seen), seen
    assert got.shape == (2, probes.size)


def test_fused_async_drain_parity():
    """The async engine's coalesced drains run the fused consumer
    (miss_chunk == the popped block); verdict + established parity vs
    the oracle twin across admit -> drain -> re-hit."""
    cluster = gen_cluster(600, seed=3)
    fd = _fused(cluster.ps, async_slowpath=True, drain_batch=64)
    od = _oracle(cluster.ps, async_slowpath=True, drain_batch=64)
    tr = gen_traffic(cluster.pod_ips, batch=64, seed=14)
    rf, ro = fd.step(tr, now=1), od.step(tr, now=1)
    assert list(rf.code) == list(ro.code)
    assert list(rf.pending) == list(ro.pending)
    fd.drain_slowpath(now=2)
    od.drain_slowpath(now=2)
    rf, ro = fd.step(tr, now=3), od.step(tr, now=3)
    _assert_result_parity(rf, ro, "post-drain")
    assert int(np.asarray(rf.est).sum()) > 0  # drains established flows


# ---------------------------------------------------------------------------
# HLO pinning at fused=False
# ---------------------------------------------------------------------------


def test_step_hlo_no_pallas_and_identical_with_fused_disabled():
    """fused=False must stay the XLA-scan program: spelling the default
    knobs out lowers BIT-IDENTICALLY to the plain pruned instance, and
    fused=True lowers a different one."""
    cluster = gen_cluster(300, seed=7)
    cps = compile_policy_set(cluster.ps)
    from antrea_tpu.compiler.services import compile_services

    svc = compile_services([])

    def lowered(**kw):
        step, st, (drs, dsvc) = pl.make_pipeline(
            cps, svc, flow_slots=1 << 8, aff_slots=1 << 4, miss_chunk=32,
            **kw)
        cols = (jnp.zeros(128, jnp.int32),) * 5
        return jax.jit(
            pl._pipeline_step, static_argnames=("meta",),
        ).lower(st, drs, dsvc, *cols, jnp.int32(1), jnp.int32(0),
                meta=step.meta).as_text()

    staged = lowered(prune_budget=2)
    assert lowered(prune_budget=2, fused=False) == staged
    assert lowered(prune_budget=2, second_chance=False) == staged
    # On the CPU tier the kernel lowers through interpret mode, so the
    # evidence is program inequality, not a custom-call marker.
    assert lowered(prune_budget=2, fused=True) != staged


# ---------------------------------------------------------------------------
# Canary + audit certification on a fused instance
# ---------------------------------------------------------------------------


def test_canary_and_audit_certify_fused_instance():
    """The eager twin walks carry the fused meta: a fused instance's
    install canary and a full audit sweep certify the serving
    configuration (zero mismatches, zero divergences)."""
    cluster = gen_cluster(600, seed=3)
    dp = TpuflowDatapath(cluster.ps, miss_chunk=32, fused=True,
                         prune_budget=2, flow_slots=1 << 10,
                         aff_slots=1 << 6, canary_probes=16,
                         flightrec_slots=64, realization_slots=16)
    assert dp._meta.fused and dp._meta.match.prune_budget == 2
    tr = gen_traffic(cluster.pod_ips, batch=96, seed=10)
    dp.step(tr, now=1)
    gen0 = dp.generation
    dp.install_bundle(cluster.ps)  # canary-gated (fused trace walk)
    cp = dp.commit_stats()
    assert dp.generation == gen0 + 1 and not cp["degraded"]
    assert cp["canary_probes_total"] > 0
    assert cp["canary_mismatches_total"] == 0
    dp.audit_scan(now=2, full=True)  # fresh re-proof via the fused walk
    au = dp.audit_stats()
    assert au["entries_total"] > 0
    assert au["repairs_total"] == 0 and not au["divergences"]
    # The certification is only worth its name if the probes walked the
    # SERVING consumer: pin that the canary's classify carries the
    # instance's fused meta (a fused=False canary would certify the
    # shadow XLA path and pass all the green checks above regardless).
    seen = []
    orig = pl.classify_batch

    def _rec(*a, **k):
        seen.append(bool(k.get("fused", False)))
        return orig(*a, **k)

    pl.classify_batch = _rec
    try:
        dp._canary_classify(tr, now=3)
    finally:
        pl.classify_batch = orig
    assert seen and all(seen), seen


# ---------------------------------------------------------------------------
# Autotune compatibility (meta-only K swaps)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fused_autotune_retune_is_meta_only():
    """A PruneAutotuner retune under the fused path swaps K in the meta
    (a new jit-cached step variant per rung) — serving stays
    parity-correct across the move."""
    cluster = gen_cluster(2500, seed=2)
    fd = _fused(cluster.ps, prune=1, autotune_prune=True)
    sd = _staged(cluster.ps, prune=1)
    tr = gen_traffic(cluster.pod_ips, batch=160, seed=5)
    k0 = fd._prune_budget
    # The K=1 multi-superblock world produces a high fallback rate; two
    # sticky signals move the rung up.
    for t in range(4):
        tr_t = gen_traffic(cluster.pod_ips, batch=160, seed=40 + t)
        fd.step(tr_t, now=1 + t)
        sd.step(tr_t, now=1 + t)
    # The K=1 fallback pressure retunes UP, and the then-clean K=2 rung
    # retunes back DOWN — both moves serve through jit-cached step
    # variants (every move is a meta-only swap).
    assert fd.prune_stats()["retunes_total"] > 0, (
        "fallback pressure never retuned K")
    assert fd._prune_tuner.decisions_up > 0
    assert fd._meta.match.prune_budget == fd._prune_budget
    del k0
    # Post-retune parity (fresh traffic through the new rung's variant).
    sd2 = _staged(cluster.ps, prune=fd._prune_budget)
    tr2 = gen_traffic(cluster.pod_ips, batch=96, seed=77)
    rf, rs = fd.step(tr2, now=50), sd2.step(tr2, now=50)
    assert list(rf.code) == list(rs.code)
    assert list(rf.ingress_rule) == list(rs.ingress_rule)


# ---------------------------------------------------------------------------
# Config errors
# ---------------------------------------------------------------------------


def test_fused_config_errors():
    cluster = gen_cluster(200, seed=5)
    # fused + pruned has only been proven on v4 worlds: with dual_stack
    # it is rejected, both engines, at construction.
    for cls in (TpuflowDatapath, OracleDatapath):
        with pytest.raises(ConfigError):
            cls(cluster.ps, fused=True, prune_budget=2, dual_stack=True,
                **KW)
    # fused + dual_stack WITHOUT pruning stays legal (staged consumer).
    TpuflowDatapath(cluster.ps, fused=True, dual_stack=True, **KW)
    # Source rate limiting configures the async admission only.
    for cls in (TpuflowDatapath, OracleDatapath):
        with pytest.raises(ConfigError):
            cls(cluster.ps, miss_source_rate=8, **KW)
        with pytest.raises(ConfigError):
            cls(cluster.ps, async_slowpath=True, miss_source_rate=0, **KW)
        with pytest.raises(ConfigError):
            cls(cluster.ps, async_slowpath=True, miss_source_rate=8,
                miss_source_burst=0, **KW)


# ---------------------------------------------------------------------------
# Satellite: spill-retry prune-accounting dedupe (skew batch)
# ---------------------------------------------------------------------------


def test_mesh_spill_retry_prune_evidence_exactly_once():
    """Prune evidence under hash-skew spill: each lane feeds the
    PruneAutotuner band exactly once, from its HOME (serving) walk — the
    mesh's counters must equal a single-chip twin's on the same traffic
    (the main dispatch excludes spilled lanes; their home-routed retry
    accounts them instead)."""
    from antrea_tpu.parallel.meshpath import MeshDatapath

    cluster = gen_cluster(2500, seed=12)
    md = MeshDatapath(cluster.ps, n_data=2, n_rule=1, miss_chunk=16,
                      prune_budget=1, **KW)
    tr = gen_traffic(cluster.pod_ips, batch=64, seed=13)
    spills = 0
    n_miss_sum = 0
    for now in (1, 2, 3):
        r = md.step(tr, now=now)
        n_miss_sum += int(r.n_miss)
        spills = int(md.mesh_stats()["spill_lanes_total"])
    mp = md.prune_stats()
    assert spills > 0, "the batch never spilled — no skew to pin"
    # Exactly-once, home-walk evidence: the merged per-lane miss mask IS
    # the home-walk image (a retried lane that HITS its home cache is
    # not a classification), so the classified meter must equal the
    # summed miss counts bit for bit.  The pre-fix accounting kept the
    # foreign walk's evidence — always-miss for spilled lanes — which
    # inflates classified_total past the home-walk misses from the
    # second step on (established flows re-hit at home).
    assert mp["classified_total"] == n_miss_sum, (
        mp["classified_total"], n_miss_sum)
    assert 0 < mp["fallbacks_total"] <= mp["classified_total"]


# ---------------------------------------------------------------------------
# Satellite: second-chance replacement (thrash resistance)
# ---------------------------------------------------------------------------


def _est_flow_batch(pairs, sport=9000, dport=80):
    return PacketBatch.from_packets([
        Packet(src_ip=iputil.ip_to_u32(s), dst_ip=iputil.ip_to_u32(d),
               proto=6, src_port=sport + i, dst_port=dport)
        for i, (s, d) in enumerate(pairs)])


def _reply_batch(est: PacketBatch) -> PacketBatch:
    """The reverse-direction legs of `est` (no services: dnat == dst)."""
    return PacketBatch.from_packets([
        Packet(src_ip=int(est.dst_ip[i]), dst_ip=int(est.src_ip[i]),
               proto=int(est.proto[i]), src_port=int(est.dst_port[i]),
               dst_port=int(est.src_port[i]))
        for i in range(est.size)])


def _allowed_pairs(cluster, n):
    """Pod pairs the policy world ALLOWS (a denial entry is never
    CONFIRMED-established, so it gets no second chance by design)."""
    from antrea_tpu.oracle import Oracle

    oracle = Oracle(cluster.ps)
    pods = [iputil.u32_to_ip(int(p)) for p in cluster.pod_ips[:64]]
    out = []
    for i, s in enumerate(pods):
        for d in pods[i + 1:]:
            p = Packet(src_ip=iputil.ip_to_u32(s),
                       dst_ip=iputil.ip_to_u32(d), proto=6,
                       src_port=9000, dst_port=80)
            if oracle.classify(p).code == 0:
                out.append((s, d))
                break
        if len(out) >= n:
            break
    assert len(out) >= n, "world has too few allowed pairs"
    return out[:n]


def test_second_chance_pins_established_under_thrash():
    """A gen_cache_thrash storm (universe >> slots) cannot evict an
    ACTIVE established flow: with second_chance=True the established
    table rows survive the storm bitwise on both engines, in full
    oracle parity; with the knob off the same storm evicts some of
    them (the control that proves the mechanism)."""
    from antrea_tpu.simulator.traffic import gen_cache_thrash

    cluster = gen_cluster(600, seed=3)
    est = _est_flow_batch(_allowed_pairs(cluster, 8))
    rep = _reply_batch(est)

    def run(second_chance, with_oracle=True):
        # miss_chunk >= every batch: single-round commit passes, so the
        # device's once-per-pass counter bump matches the oracle's
        # once-per-step bookkeeping exactly (the documented multi-round
        # divergence of the chunked sync path).  The control run (knob
        # off) only has to prove the storm EVICTS — it skips the oracle
        # twin, parity is the ON run's claim.
        dp = TpuflowDatapath(cluster.ps, miss_chunk=256, second_chance=
                             second_chance, flow_slots=1 << 6,
                             aff_slots=1 << 4, canary_probes=0,
                             flightrec_slots=0, realization_slots=0)
        od = OracleDatapath(cluster.ps, second_chance=second_chance,
                            flow_slots=1 << 6, aff_slots=1 << 4,
                            canary_probes=0, flightrec_slots=0,
                            realization_slots=0) if with_oracle else None
        engines = (dp, od) if od is not None else (dp,)
        now = 1
        for e in engines:
            e.step(est, now=now)   # forward leg commits both directions
            e.step(rep, now=now)   # reply leg CONFIRMS the connection
        now += 1
        r = dp.step(est, now=now)
        if od is not None:
            od.step(est, now=now)
        assert list(r.code) == [0] * est.size  # genuinely allowed
        # A self-collision inside the est set itself (direct-mapped) may
        # cost a lane at establishment time; the storm pin covers the
        # rows that DID establish.
        alive = int(np.asarray(r.est).sum())
        assert alive >= 6, "est set mostly self-collided — widen the cache"
        keys0 = np.asarray(dp._state.flow.keys).copy()
        rows0 = {i for i in range(keys0.shape[0] - 1) if keys0[i, 3] != 0}
        # Exactly CHANCE_MAX storm passes between refreshes: a confirmed
        # row's counter reaches at most CHANCE_MAX and never yields.
        for rnd in range(3):
            now += 1
            storm = gen_cache_thrash(cluster.pod_ips, 128,
                                     n_flows=1 << 12, seed=50 + rnd)
            rd = dp.step(storm, now=now)
            if od is not None:
                ro = od.step(storm, now=now)
                assert list(rd.code) == list(ro.code), (second_chance, rnd)
            now += 1
            rd = dp.step(est, now=now)
            if od is not None:
                ro = od.step(est, now=now)
                assert list(rd.code) == list(ro.code)
            # Active connections are TWO-WAY: the reply legs' own hits
            # are what reset THEIR counters (a forward hit refreshes
            # only its own row at this cadence).
            rr = dp.step(rep, now=now)
            if od is not None:
                rro = od.step(rep, now=now)
                assert list(rr.code) == list(rro.code)
            if second_chance:
                # Every established flow still serves from its entry
                # (its own hits keep resetting the collision counter).
                assert int(np.asarray(rd.est).sum()) == alive, rnd
        keys1 = np.asarray(dp._state.flow.keys)
        survived = all(np.array_equal(keys0[i], keys1[i]) for i in rows0)
        return survived, dp, od

    survived_on, dp_on, od_on = run(True)
    assert survived_on, "second_chance failed to pin the established rows"
    assert od_on._oracle.chance_suppressed > 0
    survived_off, _dp, _od = run(False, with_oracle=False)
    assert not survived_off, (
        "the storm never collided with an established row — the control "
        "case proves nothing; shrink flow_slots or grow the storm")


def test_second_chance_yields_after_max_collisions():
    """A SILENT (non-refreshing but confirmed-established) entry yields
    after CHANCE_MAX colliding passes — bounded protection, never a
    wedged slot."""
    from antrea_tpu.models.pipeline import CHANCE_MAX

    cluster = gen_cluster(600, seed=3)
    est = _est_flow_batch(_allowed_pairs(cluster, 2))
    rep = _reply_batch(est)
    # Same shapes as the thrash test's second_chance=True engines: the
    # staged consumer compile is shared; the smaller est set (2 flows in
    # 64 slots) still collides every storm pass.
    dp = TpuflowDatapath(cluster.ps, miss_chunk=256, second_chance=True,
                         flow_slots=1 << 6, aff_slots=1 << 4,
                         canary_probes=0, flightrec_slots=0,
                         realization_slots=0)
    od = OracleDatapath(cluster.ps, second_chance=True, flow_slots=1 << 6,
                        aff_slots=1 << 4, canary_probes=0,
                        flightrec_slots=0, realization_slots=0)
    for e in (dp, od):
        e.step(est, now=1)
        e.step(rep, now=1)  # CONFIRM — unconfirmed entries get no chance
    keys0 = np.asarray(dp._state.flow.keys).copy()
    live0 = (keys0[:, 3] != 0).sum()
    # Storm WITHOUT ever refreshing the established flow: after more
    # than CHANCE_MAX colliding passes every slot is reclaimable.
    from antrea_tpu.simulator.traffic import gen_cache_thrash

    for rnd in range(CHANCE_MAX + 3):
        storm = gen_cache_thrash(cluster.pod_ips, 128, n_flows=1 << 12,
                                 seed=80 + rnd)
        rd, ro = dp.step(storm, now=2 + rnd), od.step(storm, now=2 + rnd)
        assert list(rd.code) == list(ro.code), rnd
    keys1 = np.asarray(dp._state.flow.keys)
    changed = any(
        keys0[i, 3] != 0 and not np.array_equal(keys0[i], keys1[i])
        for i in range(keys0.shape[0] - 1))
    assert changed, (
        f"no established slot was ever reclaimed after "
        f"{CHANCE_MAX + 3} storm passes over {live0} live rows")


# ---------------------------------------------------------------------------
# Satellite: per-source slow-path rate limiting
# ---------------------------------------------------------------------------


def _world_async(**kw):
    cluster = gen_cluster(400, seed=5)
    common = dict(flow_slots=1 << 8, aff_slots=1 << 4,
                  async_slowpath=True, miss_queue_slots=256,
                  drain_batch=32, canary_probes=0, flightrec_slots=0,
                  realization_slots=0, node_name="n1", **kw)
    return (cluster,
            TpuflowDatapath(cluster.ps, miss_chunk=64, **common),
            OracleDatapath(cluster.ps, **common))


def test_source_rate_limit_parity_under_syn_flood():
    """The per-source-/24 bucket clamps a flooding prefix ahead of the
    early-drop ramp, deterministically — full verdict parity every step,
    identical nonzero shed counts on both engines, and an innocent
    source's misses keep admitting while the attacker is clamped."""
    from antrea_tpu.simulator.traffic import gen_syn_flood

    cluster, t, o = _world_async(miss_source_rate=4, miss_source_burst=16)
    dst = [int(cluster.pod_ips[0])]
    seq = 0
    for rnd in range(5):
        flood = gen_syn_flood(dst, 96, start_seq=seq)
        seq += 96
        now = 10 + rnd
        rt, ro = t.step(flood, now=now), o.step(flood, now=now)
        assert list(rt.code) == list(ro.code), rnd
        assert list(rt.pending) == list(ro.pending), rnd
    ts_, os_ = (t._slowpath.source_limited_total,
                o._slowpath.source_limited_total)
    assert ts_ == os_ > 0, (ts_, os_)
    for dp in (t, o):
        assert dp.slowpath_stats()["source_limited_total"] == ts_
    # Metric renders as its registered family.
    from antrea_tpu.observability.metrics import render_metrics

    assert (f'antrea_tpu_miss_queue_source_limited_total{{node="n1"}} {ts_}'
            in render_metrics(t, node="n1"))
    # An innocent source (different /24) still admits at full rate.
    before = t._slowpath.queue.admitted_total
    innocent = PacketBatch.from_packets([
        Packet(src_ip=iputil.ip_to_u32(f"10.77.3.{i + 1}"),
               dst_ip=int(cluster.pod_ips[0]), proto=6,
               src_port=40000 + i, dst_port=80) for i in range(8)])
    t.step(innocent, now=100)
    assert t._slowpath.queue.admitted_total - before == 8


def test_source_rate_limit_refills_on_packet_clock():
    """Token refill is pure clock arithmetic: after the flooding prefix
    goes quiet for rate*dt worth of tokens, its misses admit again."""
    cluster, t, o = _world_async(miss_source_rate=2, miss_source_burst=4)
    src = iputil.ip_to_u32("10.50.0.9")

    def burst(now, n, base):
        b = PacketBatch.from_packets([
            Packet(src_ip=src, dst_ip=int(cluster.pod_ips[0]), proto=6,
                   src_port=base + i, dst_port=80) for i in range(n)])
        return t.step(b, now=now), o.step(b, now=now)

    burst(10, 8, 50000)  # burst of 4 exhausted, 4 shed
    assert t._slowpath.source_limited_total == 4
    assert o._slowpath.source_limited_total == 4
    burst(11, 4, 51000)  # only 2 tokens refilled (rate=2/s, dt=1)
    assert t._slowpath.source_limited_total == 6
    burst(100, 4, 52000)  # long quiet: full burst back
    assert t._slowpath.source_limited_total == 6
    # Out-of-order clock: an OLDER now must neither drive tokens negative
    # (mis-counting sheds) nor rewind the refill stamp (over-refilling the
    # next in-order batch).  Tokens are 0 at stamp 100: the stale batch
    # sheds exactly its 4 lanes, and the next in-order second refills
    # rate*1 = 2 tokens, not a full burst.
    burst(50, 4, 53000)
    assert t._slowpath.source_limited_total == 10
    burst(101, 2, 54000)
    assert t._slowpath.source_limited_total == 10
    assert t._slowpath.source_limited_total == o._slowpath.source_limited_total


def test_source_rate_limit_mesh_replica_independent():
    """On the mesh the limiter runs ONCE per batch ahead of the
    per-replica ramps — shed totals are per-source, not per-replica."""
    from antrea_tpu.parallel.meshpath import MeshDatapath
    from antrea_tpu.simulator.traffic import gen_syn_flood

    cluster = gen_cluster(400, seed=5)
    md = MeshDatapath(cluster.ps, n_data=2, miss_chunk=64,
                      async_slowpath=True, miss_queue_slots=128,
                      drain_batch=32, miss_source_rate=4,
                      miss_source_burst=8, flow_slots=1 << 8,
                      aff_slots=1 << 4, canary_probes=0, flightrec_slots=0,
                      realization_slots=0)
    sd = TpuflowDatapath(cluster.ps, miss_chunk=64, async_slowpath=True,
                         miss_queue_slots=128, drain_batch=32,
                         miss_source_rate=4, miss_source_burst=8,
                         flow_slots=1 << 8, aff_slots=1 << 4,
                         canary_probes=0, flightrec_slots=0,
                         realization_slots=0)
    dst = [int(cluster.pod_ips[0])]
    flood = gen_syn_flood(dst, 64, start_seq=0)
    md.step(flood, now=1)
    sd.step(flood, now=1)
    assert (md._slowpath.source_limited_total
            == sd._slowpath.source_limited_total > 0)

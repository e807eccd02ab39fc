"""Async slow-path engine (ISSUE 3 tentpole): decoupled miss pipeline +
epoch-swapped flow cache, differential tpuflow-vs-oracle throughout.

Probe discipline (the flow-cache-semantics satellite): every
oracle-parity assertion uses FRESH, never-before-seen 5-tuples — an
established flow legitimately survives policy churn, so a reused tuple
would est-bypass the new verdict and mask divergence.  Tuple freshness
comes from a monotonic source-port counter shared by the whole module;
tests that WANT established behavior reuse a tuple explicitly.
"""

import itertools

import numpy as np
import pytest

from antrea_tpu.apis import controlplane as cp
from antrea_tpu.apis.service import Endpoint, ServiceEntry
from antrea_tpu.compiler.ir import PolicySet
from antrea_tpu.datapath import OracleDatapath, TpuflowDatapath
from antrea_tpu.packet import Packet, PacketBatch
from antrea_tpu.utils import ip as iputil

CLIENT, CLIENT2, SRV = "10.0.1.1", "10.0.1.2", "10.0.0.10"
BLOCKED = "10.0.9.9"

# Monotonic clocks: packet time and the fresh-tuple source port.
_NOW = itertools.count(1000)
_SPORT = itertools.count(20000)


def _fresh_pkt(src, dst, dport=80, proto=6):
    """A never-before-seen 5-tuple (unique sport)."""
    return Packet(src_ip=iputil.ip_to_u32(src), dst_ip=iputil.ip_to_u32(dst),
                  proto=proto, src_port=next(_SPORT), dst_port=dport)


def _drop_policy(uid, blocked_ip=BLOCKED, target_ip=SRV):
    """ACNP: drop `blocked_ip` -> `target_ip` ingress."""
    return cp.NetworkPolicy(
        uid=uid, name=uid, type=cp.NetworkPolicyType.ACNP,
        rules=[cp.NetworkPolicyRule(
            direction=cp.Direction.IN,
            from_peer=cp.NetworkPolicyPeer(address_groups=["blocked"]),
            action=cp.RuleAction.DROP, priority=0)],
        applied_to_groups=["web"], tier_priority=250, priority=1.0,
    )


def _world(blocked_ip=BLOCKED):
    ps = PolicySet(
        policies=[_drop_policy("p1")],
        address_groups={"blocked": cp.AddressGroup(
            name="blocked", members=[cp.GroupMember(ip=blocked_ip)])},
        applied_to_groups={"web": cp.AppliedToGroup(
            name="web", members=[cp.GroupMember(ip=SRV)])},
    )
    svcs = [ServiceEntry(cluster_ip="10.96.0.1", port=80, protocol=6,
                         name="web", namespace="default",
                         endpoints=[Endpoint(ip=SRV, port=8080)])]
    return ps, svcs


def _pair(ps, svcs, *, flow_slots=1 << 10, queue=256, admission="forward",
          drain_batch=8, **kw):
    mk = dict(flow_slots=flow_slots, aff_slots=1 << 4,
              async_slowpath=True, miss_queue_slots=queue,
              admission=admission, drain_batch=drain_batch, **kw)
    return (TpuflowDatapath(ps, svcs, miss_chunk=16, **mk),
            OracleDatapath(ps, svcs, **mk))


def _assert_parity(rt, ro, where=""):
    for f in ("code", "est", "pending", "reply", "svc_idx", "dnat_port",
              "committed", "snat", "reject_kind"):
        a, b = getattr(rt, f), getattr(ro, f)
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"{where}: {f} diverged: tpuflow={a} oracle={b}")
    assert np.array_equal(rt.dnat_ip, ro.dnat_ip), where
    assert rt.ingress_rule == ro.ingress_rule, where
    assert rt.egress_rule == ro.egress_rule, where


def _step_both(t, o, pkts, now):
    bt = PacketBatch.from_packets(pkts)
    bo = PacketBatch.from_packets(pkts)
    rt, ro = t.step(bt, now), o.step(bo, now)
    _assert_parity(rt, ro, f"now={now}")
    return rt, ro


def _drain_both(t, o, now):
    st, so = t.drain_slowpath(now), o.drain_slowpath(now)
    assert st["drained"] == so["drained"], (st, so)
    return st


def test_async_parity_and_convergence_to_sync_verdicts():
    """Fresh tuples: provisional on admission, then — after one drain —
    the flows' verdicts equal what a synchronous engine classifies, and
    reply-direction traffic est-bypasses on both engines."""
    ps, svcs = _world()
    t, o = _pair(ps, svcs)
    sync = OracleDatapath(ps, svcs, flow_slots=1 << 10, aff_slots=1 << 4)

    probes = [
        _fresh_pkt(BLOCKED, SRV),       # denied by p1
        _fresh_pkt(CLIENT, SRV),        # plain allow
        _fresh_pkt(CLIENT2, "10.96.0.1"),  # via the service (DNAT)
    ]
    now = next(_NOW)
    rt, _ = _step_both(t, o, probes, now)
    assert list(rt.pending) == [1, 1, 1]
    assert list(rt.code) == [0, 0, 0]  # forward admission: provisional allow
    assert t.slowpath_stats()["depth"] == 3

    _drain_both(t, o, next(_NOW))
    rt2, _ = _step_both(t, o, probes, next(_NOW))
    assert list(rt2.pending) == [0, 0, 0]
    rsync = sync.step(PacketBatch.from_packets(probes), next(_NOW))
    assert list(rt2.code) == list(rsync.code) == [1, 0, 0]
    # The service flow resolved its endpoint through the drain commit.
    assert rt2.dnat_ip[2] == iputil.ip_to_u32(SRV)
    assert rt2.dnat_port[2] == 8080

    # Reply leg of the service connection: est reply-direction hit.
    reply = Packet(src_ip=iputil.ip_to_u32(SRV),
                   dst_ip=probes[2].src_ip, proto=6,
                   src_port=8080, dst_port=probes[2].src_port)
    rt3, _ = _step_both(t, o, [reply], next(_NOW))
    assert list(rt3.reply) == [1] and list(rt3.est) == [1]


def test_oversized_explicit_drain_pop_classifies_whole_block():
    """begin_drain(n) with n > drain_batch pops a block wider than the
    engine chunk; the drain step must pad UP to the block (next pow2
    rung) and classify every popped row — not overflow the
    drain_batch-sized lanes and lose the block (regression)."""
    ps, svcs = _world()
    t, o = _pair(ps, svcs, drain_batch=8, queue=256)
    pkts = [_fresh_pkt(BLOCKED, SRV) for _ in range(24)]
    rt, ro = _step_both(t, o, pkts, now=10)
    assert int(np.asarray(rt.pending).sum()) == 24
    for dp in (t, o):
        sp = dp._slowpath
        assert sp.begin_drain(11, n=24)
        out = sp.finish_drain(11)
        assert out["drained"] == 24, out
    # All 24 flows classified + cached in the one oversized drain; the
    # odd direct-mapped collision victim may legitimately re-miss
    # (parity with the oracle twin is asserted by _step_both either
    # way), but the block as a whole must be live — not lost.
    rt, ro = _step_both(t, o, pkts, now=12)
    pend = np.asarray(rt.pending)
    assert int(pend.sum()) <= 2, pend
    codes = np.asarray(rt.code)
    assert all(c == 1 for c in codes[pend == 0])  # the DROP policy


def test_hold_admission_drops_until_classified():
    ps, svcs = _world()
    t, o = _pair(ps, svcs, admission="hold")
    allowed = _fresh_pkt(CLIENT, SRV)
    rt, _ = _step_both(t, o, [allowed], next(_NOW))
    assert list(rt.code) == [1] and list(rt.pending) == [1]  # held
    assert list(rt.reject_kind) == [0]  # hold is a DROP, never a REJECT
    _drain_both(t, o, next(_NOW))
    rt2, _ = _step_both(t, o, [allowed], next(_NOW))
    assert list(rt2.code) == [0] and list(rt2.pending) == [0]


def test_early_drop_admission_parity_under_syn_flood():
    """admission="drop" (ROADMAP item 4's admission half, round 10):
    under gen_syn_flood pressure — never-repeating tuples, 100%
    admissions — the depth-proportional early-drop sheds admissions
    BEFORE the tail-drop cliff, deterministically (a 5-tuple hash coin),
    so both engines shed the identical lanes and every step keeps full
    oracle parity; the shed volume is metered on both identically."""
    from antrea_tpu.simulator.traffic import gen_syn_flood

    ps, svcs = _world()
    t, o = _pair(ps, svcs, queue=64, admission="drop", drain_batch=8)
    dst = [iputil.ip_to_u32(SRV)]
    seq = 0
    for rnd in range(6):
        flood = gen_syn_flood(dst, 128, start_seq=seq)
        seq += 128
        now = next(_NOW)
        rt, ro = t.step(flood, now=now), o.step(flood, now=now)
        _assert_parity(rt, ro, f"flood round {rnd}")
        if rnd % 2 == 1:
            _drain_both(t, o, next(_NOW))  # asserts drained parity
    te, oe = t._slowpath.early_drops_total, o._slowpath.early_drops_total
    assert te == oe > 0, (te, oe)  # shed, and shed identically
    for dp in (t, o):
        assert dp.slowpath_stats()["early_drops_total"] == te
        # The meter renders as its registered family.
        from antrea_tpu.observability.metrics import render_metrics

        assert (f'antrea_tpu_miss_queue_early_drops_total{{node="n1"}} {te}'
                in render_metrics(dp, node="n1"))
    # Below the floor nothing sheds: a fresh pair's first flood batch
    # admits in full (floor = capacity/2 = 32 > one 24-lane batch).
    t2, o2 = _pair(ps, svcs, queue=64, admission="drop", drain_batch=8)
    small = gen_syn_flood(dst, 24, start_seq=10_000)
    now = next(_NOW)
    _assert_parity(t2.step(small, now=now), o2.step(small, now=now), "calm")
    assert t2._slowpath.early_drops_total == 0
    assert o2._slowpath.early_drops_total == 0
    # And the policy set rejects typos with the full inventory.
    with pytest.raises(ValueError, match="drop"):
        _pair(ps, svcs, admission="shed")


def test_churn_established_survives_fresh_reclassifies():
    """Bundle swap: the established flow keeps flowing (conntrack
    semantics) while a FRESH tuple of the same pair classifies under the
    new policy — asserted with parity on both, plus the revalidation
    plane reclaiming the stale denial slots."""
    ps, svcs = _world()
    t, o = _pair(ps, svcs)

    est = _fresh_pkt(CLIENT, SRV)       # will be established pre-churn
    denied = _fresh_pkt(BLOCKED, SRV)   # cached denial pre-churn
    _step_both(t, o, [est, denied], next(_NOW))
    _drain_both(t, o, next(_NOW))
    rt, _ = _step_both(t, o, [est, denied], next(_NOW))
    assert list(rt.code) == [0, 1] and list(rt.est) == [1, 0]

    # New bundle: now CLIENT is the blocked source.
    ps2, _ = _world(blocked_ip=CLIENT)
    t.install_bundle(ps=ps2)
    o.install_bundle(ps=ps2)
    assert t.slowpath_stats()["epoch_stale"] == 1

    # The ESTABLISHED tuple survives the swap on both engines...
    rt2, _ = _step_both(t, o, [est], next(_NOW))
    assert list(rt2.code) == [0] and list(rt2.est) == [1]
    # ...while a FRESH tuple of the same pair takes the new verdict.
    fresh = _fresh_pkt(CLIENT, SRV)
    _step_both(t, o, [fresh], next(_NOW))
    st = _drain_both(t, o, next(_NOW))
    assert st["revalidated"] >= 1  # the stale BLOCKED denial reclaimed
    rt3, _ = _step_both(t, o, [fresh], next(_NOW))
    assert list(rt3.code) == [1]
    # Old-policy denial is gone from the published epoch; the old blocked
    # source now classifies ALLOW under the new bundle (fresh tuple).
    fresh_old = _fresh_pkt(BLOCKED, SRV)
    _step_both(t, o, [fresh_old], next(_NOW))
    _drain_both(t, o, next(_NOW))
    rt4, _ = _step_both(t, o, [fresh_old], next(_NOW))
    assert list(rt4.code) == [0]


def test_eviction_pressure_with_full_miss_queue():
    """Tiny cache (direct-mapped collisions every drain) + tiny queue
    (admissions tail-drop): overflow accounting matches on both engines,
    overflowed flows stay unclassified until re-admitted, and the
    eviction races stay in exact parity (shared hash discipline)."""
    ps, svcs = _world()
    t, o = _pair(ps, svcs, flow_slots=1 << 4, queue=4, drain_batch=4)

    probes = [_fresh_pkt(CLIENT, SRV) for _ in range(4)] + \
             [_fresh_pkt(CLIENT2, SRV) for _ in range(4)]
    rt, _ = _step_both(t, o, probes, next(_NOW))
    assert list(rt.pending) == [1] * 8
    for dp in (t, o):
        s = dp.slowpath_stats()
        assert (s["depth"], s["overflows_total"]) == (4, 4)

    # 16 slots vs 8 flows x 2 conntrack legs: commits race for slots, so
    # flows can keep re-missing as drains evict each other's entries —
    # the assertion is exact PARITY every round (shared hash/eviction
    # discipline), not convergence.  Overflowed flows re-admit as they
    # re-miss; every non-pending lane reports the true classify verdict.
    for _ in range(5):
        _drain_both(t, o, next(_NOW))
        rti, _ = _step_both(t, o, probes, next(_NOW))
        pend = np.asarray(rti.pending)
        assert np.array_equal(
            np.asarray(rti.code)[pend == 0],
            np.zeros(int((pend == 0).sum()), np.int32),
        )
        ct, co = t.cache_stats(), o.cache_stats()
        # (evictions is excluded: within-batch collision ACCOUNTING is
        # implementation-defined per the oracle's docstring — the
        # resulting cache STATE, below, is the parity surface.)
        for k in ("occupied", "committed", "denials"):
            assert ct[k] == co[k], (k, ct, co)
        st, so = t.slowpath_stats(), o.slowpath_stats()
        for k in ("depth", "admitted_total", "overflows_total",
                  "drained_total", "epoch"):
            assert st[k] == so[k], (k, st, so)


def test_epoch_swap_during_inflight_drain_reclassifies():
    """A bundle swap landing between begin_drain and finish_drain: the
    in-flight batch is re-classified under the NEW tensors (counted in
    stale_reclassified_total), never published stale — asserted against
    the sync oracle compiled from the new bundle."""
    ps, svcs = _world()
    ps2, _ = _world(blocked_ip=CLIENT)  # the swap flips who is blocked
    results = {}
    for dp_cls in (TpuflowDatapath, OracleDatapath):
        kw = {"miss_chunk": 16} if dp_cls is TpuflowDatapath else {}
        dp = dp_cls(ps, svcs, flow_slots=1 << 10, aff_slots=1 << 4,
                    async_slowpath=True, miss_queue_slots=64,
                    drain_batch=8, **kw)
        probe = _fresh_pkt(CLIENT, SRV)
        now = next(_NOW)
        r = dp.step(PacketBatch.from_packets([probe]), now)
        assert list(r.pending) == [1]
        eng = dp._slowpath
        assert eng.begin_drain(next(_NOW))
        dp.install_bundle(ps=ps2)  # mid-drain epoch swap
        st = eng.finish_drain(next(_NOW))
        assert st["stale_reclassified"] == 1
        assert dp.slowpath_stats()["stale_reclassified_total"] == 1
        r2 = dp.step(PacketBatch.from_packets([probe]), next(_NOW))
        results[dp_cls.__name__] = int(r2.code[0])
        # Classified under the NEW bundle: CLIENT -> SRV is now denied...
        sync = OracleDatapath(ps2, svcs, flow_slots=1 << 10,
                              aff_slots=1 << 4)
        rs = sync.step(PacketBatch.from_packets(
            [_fresh_pkt(CLIENT, SRV)]), next(_NOW))
        assert int(r2.code[0]) == int(rs.code[0]) == 1
    assert len(set(results.values())) == 1


def test_age_scan_reclaims_expired_entries_only():
    ps, svcs = _world()
    t, o = _pair(ps, svcs, ct_timeout_s=5)
    young_now = next(_NOW)
    _step_both(t, o, [_fresh_pkt(CLIENT, SRV)], young_now)
    _drain_both(t, o, young_now + 1)
    occ_t = t.cache_stats()["occupied"]
    assert occ_t == o.cache_stats()["occupied"] > 0
    # Well past the idle timeout: the scan physically reclaims both legs.
    late = young_now + 500
    nt = t._slowpath.age_scan(late)
    no = o._slowpath.age_scan(late)
    assert nt == no == occ_t
    assert t.cache_stats()["occupied"] == o.cache_stats()["occupied"] == 0
    assert t.slowpath_stats()["aged_entries_total"] == nt


def test_queue_dump_and_metrics_families():
    from antrea_tpu.observability.metrics import render_metrics

    ps, svcs = _world()
    t, o = _pair(ps, svcs)
    _step_both(t, o, [_fresh_pkt(CLIENT, SRV)], next(_NOW))
    for dp in (t, o):
        [row] = dp.dump_miss_queue()
        assert row["src"] == CLIENT and row["dst"] == SRV
        assert row["epoch"] >= 1 and row["enqueued_at"] >= 1000
        text = render_metrics(dp, node="n1")
        for fam in ("antrea_tpu_miss_queue_depth",
                    "antrea_tpu_miss_queue_capacity",
                    "antrea_tpu_miss_queue_overflows_total",
                    "antrea_tpu_flow_cache_epoch",
                    "antrea_tpu_flow_cache_epoch_age_seconds"):
            assert f'{fam}{{node="n1"}}' in text, fam
        assert 'antrea_tpu_miss_queue_depth{node="n1"} 1' in text
    _drain_both(t, o, next(_NOW))
    for dp in (t, o):
        text = render_metrics(dp, node="n1")
        assert 'antrea_tpu_miss_queue_depth{node="n1"} 0' in text
        # Drain-batch histogram appears once a drain has run.
        assert "antrea_tpu_slowpath_drain_batch_size_bucket" in text
        assert dp.dump_miss_queue() == []
    # Trace overlay cleared after the drain.
    b = PacketBatch.from_packets([_fresh_pkt(CLIENT, SRV)])
    assert t.trace(b, next(_NOW))[0]["queued"] is False


@pytest.mark.chaos
def test_chaos_install_failure_mid_epoch_swap_reconverges():
    """Chaos smoke (satellite): a datapath install failure injected via
    dissemination/faults.py lands MID-epoch-swap (between begin_drain and
    finish_drain); the retry succeeds, the in-flight batch re-classifies
    under the eventually-installed bundle, and the engine reconverges to
    oracle verdict parity on fresh tuples."""
    from antrea_tpu.dissemination.faults import (
        FaultPlan, FlakyDatapath, InjectedInstallError,
    )

    ps, svcs = _world()
    ps2, _ = _world(blocked_ip=CLIENT)
    plan = FaultPlan(seed=3)
    inner = TpuflowDatapath(ps, svcs, flow_slots=1 << 10, aff_slots=1 << 4,
                            miss_chunk=16, async_slowpath=True,
                            miss_queue_slots=64, drain_batch=8)
    dp = FlakyDatapath(inner, plan, "n1")
    oracle = OracleDatapath(ps, svcs, flow_slots=1 << 10, aff_slots=1 << 4,
                            async_slowpath=True, miss_queue_slots=64,
                            drain_batch=8)

    probe = _fresh_pkt(CLIENT, SRV)
    now = next(_NOW)
    dp.step(PacketBatch.from_packets([probe]), now)
    oracle.step(PacketBatch.from_packets([probe]), now)

    # Begin the drain, then fail the FIRST install attempt mid-swap (the
    # reconciler's retry path re-issues it, as in PR 1's agent loop).
    assert inner._slowpath.begin_drain(next(_NOW))
    assert oracle._slowpath.begin_drain(next(_NOW))
    plan.after("n1.install", plan.hits("n1.install"), "fail", times=1)
    with pytest.raises(InjectedInstallError):
        dp.install_bundle(ps=ps2)
    dp.install_bundle(ps=ps2)  # the retry lands
    oracle.install_bundle(ps=ps2)
    assert plan.count("fail") == 1  # the chaos actually happened
    inner._slowpath.finish_drain(next(_NOW))
    oracle._slowpath.finish_drain(next(_NOW))

    # Reconvergence: fresh tuples agree with the oracle twin AND with a
    # clean sync oracle holding the final bundle.
    sync = OracleDatapath(ps2, svcs, flow_slots=1 << 10, aff_slots=1 << 4)
    probes = [_fresh_pkt(CLIENT, SRV), _fresh_pkt(BLOCKED, SRV)]
    now = next(_NOW)
    rt = dp.step(PacketBatch.from_packets(probes), now)
    ro = oracle.step(PacketBatch.from_packets(probes), now)
    inner.drain_slowpath(next(_NOW))
    oracle.drain_slowpath(next(_NOW))
    now = next(_NOW)
    rt = dp.step(PacketBatch.from_packets(probes), now)
    ro = oracle.step(PacketBatch.from_packets(probes), now)
    rs = sync.step(PacketBatch.from_packets(
        [_fresh_pkt(CLIENT, SRV), _fresh_pkt(BLOCKED, SRV)]), next(_NOW))
    assert list(rt.code) == list(ro.code) == list(rs.code) == [1, 0]


@pytest.mark.slow
def test_async_mode_matches_reachability_fixtures():
    """Acceptance: async mode reaches oracle verdict parity on the FULL
    hand-authored reachability suite — every scenario's probes are
    admitted (provisional), drained, and re-probed; post-drain verdicts
    must equal the fixture truth table on both engines."""
    from fixtures_reachability import SCENARIOS, _ip

    for scenario in SCENARIOS:
        t = TpuflowDatapath(scenario.ps, [], flow_slots=1 << 10,
                            aff_slots=1 << 4, miss_chunk=16,
                            async_slowpath=True, drain_batch=64)
        o = OracleDatapath(scenario.ps, [], flow_slots=1 << 10,
                           aff_slots=1 << 4, async_slowpath=True,
                           drain_batch=64)
        pkts = [
            Packet(src_ip=iputil.ip_to_u32(_ip(p.src)),
                   dst_ip=iputil.ip_to_u32(_ip(p.dst)),
                   proto=p.proto, src_port=p.sport, dst_port=p.dport)
            for p in scenario.probes
        ]
        now = next(_NOW)
        rt, _ro = _step_both(t, o, pkts, now)
        assert int(np.asarray(rt.pending).sum()) == len(pkts), scenario.name
        _drain_both(t, o, next(_NOW))
        rt2, _ = _step_both(t, o, pkts, next(_NOW))
        got = [int(c) for c in rt2.code]
        want = [p.expect for p in scenario.probes]
        assert got == want, (scenario.name, scenario.cite,
                             list(zip(scenario.probes, got)))


# ---- round 6: overlapped drain/commit pipeline + autotuner ----------------


def test_overlap_commit_visible_to_next_batch_lost_update_guard():
    """The lost-update guard: with overlap_commits on, the drain of batch
    N is dispatched with its host materialization DEFERRED (two-slot
    staging) — yet batch N+1's lookups must already see N's committed
    entries, because the state pytree swaps at dispatch time (a data
    dependency, not a host barrier).  Verified BEFORE any flush, with
    exact twin parity; the deferred observation settles at flush."""
    ps, svcs = _world()
    t, o = _pair(ps, svcs, overlap_commits=True)

    probes = [
        _fresh_pkt(BLOCKED, SRV),        # denied
        _fresh_pkt(CLIENT, SRV),         # allowed
        _fresh_pkt(CLIENT2, "10.96.0.1"),  # via the service (DNAT)
    ]
    rt, _ = _step_both(t, o, probes, next(_NOW))
    assert list(rt.pending) == [1, 1, 1]
    _drain_both(t, o, next(_NOW))
    for dp in (t, o):
        s = dp.slowpath_stats()
        assert (s["overlap"], s["overlap_depth"],
                s["deferred_commits_total"]) == (1, 1, 1), s
    # Batch N+1, BEFORE flushing the staged commit: verdicts and DNAT
    # resolution must be N's committed values on both engines.
    rt2, _ = _step_both(t, o, probes, next(_NOW))
    assert list(rt2.pending) == [0, 0, 0]
    assert list(rt2.code) == [1, 0, 0]
    assert rt2.dnat_ip[2] == iputil.ip_to_u32(SRV)
    assert rt2.dnat_port[2] == 8080
    # Flush settles the deferred observation; per-rule metrics then agree.
    assert t.flush_slowpath() == o.flush_slowpath() == 1
    st, so = t.stats(), o.stats()
    assert st.ingress == so.ingress and st.egress == so.egress
    for dp in (t, o):
        assert dp.slowpath_stats()["overlap_depth"] == 0


def test_overlap_reenqueue_of_pending_flow_is_idempotent():
    """The re-enqueue arm of the guard: a flow whose packets keep
    arriving while its first classification is staged re-admits and
    re-classifies — idempotent (deterministic endpoint hash -> identical
    entry), with exact twin parity on cache state and queue counters."""
    ps, svcs = _world()
    t, o = _pair(ps, svcs, overlap_commits=True)
    p = _fresh_pkt(CLIENT, "10.96.0.1")
    _step_both(t, o, [p], next(_NOW))            # admitted (pending)
    _step_both(t, o, [p], next(_NOW))            # re-missed: re-admitted
    for dp in (t, o):
        assert dp.slowpath_stats()["depth"] == 2
    _drain_both(t, o, next(_NOW))                # classifies both copies
    rt, _ = _step_both(t, o, [p], next(_NOW))
    assert list(rt.pending) == [0] and list(rt.code) == [0]
    assert rt.dnat_ip[0] == iputil.ip_to_u32(SRV)
    t.flush_slowpath(), o.flush_slowpath()
    ct, co = t.cache_stats(), o.cache_stats()
    for k in ("occupied", "committed", "denials"):
        assert ct[k] == co[k], (k, ct, co)


def test_overlap_epoch_swap_mid_drain_reclassifies():
    """A bundle swap landing mid-overlap (between begin_drain and
    finish_drain, with a commit still staged from an earlier drain): the
    in-flight batch re-classifies under the NEW tensors, the staged
    commit's deferred metrics keep their dispatch-time attribution, and
    both engines converge to the new bundle's verdicts."""
    ps, svcs = _world()
    ps2, _ = _world(blocked_ip=CLIENT)
    t, o = _pair(ps, svcs, overlap_commits=True)

    warm = _fresh_pkt(CLIENT2, SRV)
    probe = _fresh_pkt(CLIENT, SRV)
    _step_both(t, o, [warm], next(_NOW))
    _drain_both(t, o, next(_NOW))      # leaves one staged commit
    _step_both(t, o, [probe], next(_NOW))
    for dp in (t, o):
        assert dp._slowpath.overlap_depth == 1
        assert dp._slowpath.begin_drain(next(_NOW))
        dp.install_bundle(ps=ps2)      # mid-drain, mid-overlap swap
        st = dp._slowpath.finish_drain(next(_NOW))
        assert st["stale_reclassified"] == 1
    rt, _ = _step_both(t, o, [probe], next(_NOW))
    assert list(rt.code) == [1]        # CLIENT now blocked, both engines
    assert t.flush_slowpath() == o.flush_slowpath() == 2
    st, so = t.stats(), o.stats()
    assert st.ingress == so.ingress and st.egress == so.egress


def test_fused_maintain_ages_and_revalidates_in_one_pass():
    """The fused maintenance pass (engine.maintain -> _epoch_maintain):
    one sweep reclaims BOTH idle-expired entries and stale-generation
    denials, with identical counts on both engines and established
    (fresh) entries untouched."""
    ps, svcs = _world()
    t, o = _pair(ps, svcs, ct_timeout_s=5)

    old = _fresh_pkt(CLIENT, SRV)       # will idle out
    early = next(_NOW)
    _step_both(t, o, [old], early)
    _drain_both(t, o, early + 1)        # commits fwd+rev (2 entries)

    late = early + 300                  # far past ct_timeout_s=5
    denied = _fresh_pkt(BLOCKED, SRV)   # fresh denial at `late`
    keep = _fresh_pkt(CLIENT2, SRV)     # fresh established at `late`
    for dp in (t, o):
        dp.step(PacketBatch.from_packets([denied, keep]), late)
    _drain_both(t, o, late + 1)
    # Swap the bundle: the denial's generation goes stale.
    ps2, _ = _world(blocked_ip=CLIENT)
    t.install_bundle(ps=ps2)
    o.install_bundle(ps=ps2)
    for dp in (t, o):
        aged, revalidated = dp._slowpath.maintain(late + 2)
        # 2 idle-expired legs of `old`; 1 stale-generation denial.
        assert (aged, revalidated) == (2, 1), (aged, revalidated)
        assert not dp._slowpath.stale
        s = dp.slowpath_stats()
        assert s["aged_entries_total"] == 2
        assert s["revalidated_entries_total"] == 1
    # The established flow survived the fused sweep on both engines.
    rt, _ = _step_both(t, o, [keep], late + 3)
    assert list(rt.est) == [1] and list(rt.code) == [0]
    ct, co = t.cache_stats(), o.cache_stats()
    assert ct["occupied"] == co["occupied"] == 2  # keep fwd + rev


def test_drain_reclaim_splits_dead_rows_from_evictions():
    """The fused eviction+aging commit pass (meta.drain_reclaim): a drain
    insert over a DEAD row — idle-expired, or a stale-generation denial —
    counts as a reclaim, not an eviction; an insert over a LIVE entry
    still counts as an eviction.  flow_slots=1 forces every flow onto one
    slot so the collisions are deterministic on both engines."""
    ps, svcs = _world()
    t, o = _pair(ps, svcs, flow_slots=1, ct_timeout_s=5, drain_batch=4)

    # Expired-row arm: denial A, then (300s later) denial B over it.
    now0 = next(_NOW)
    for dp in (t, o):
        dp.step(PacketBatch.from_packets([_fresh_pkt(BLOCKED, SRV)]), now0)
        dp.drain_slowpath(now0 + 1)
    late = now0 + 300
    for dp in (t, o):
        dp.step(PacketBatch.from_packets([_fresh_pkt(BLOCKED, SRV)]), late)
        dp.drain_slowpath(late + 1)
    for dp in (t, o):
        c = dp.cache_stats()
        assert c["reclaims"] == 1, c    # expired denial A reclaimed
        assert c["evictions"] == 0, c
    # Live-overwrite arm: a third denial right away evicts the live one.
    for dp in (t, o):
        dp.step(PacketBatch.from_packets([_fresh_pkt(BLOCKED, SRV)]),
                late + 2)
        dp.drain_slowpath(late + 3)
        c = dp.cache_stats()
        assert (c["reclaims"], c["evictions"]) == (1, 1), c
    # Stale-generation arm: swap the bundle, then drain a fresh denial
    # over the now-stale one via begin/finish (bypassing drain()'s
    # maintain pass, which would otherwise clear the slot first).
    ps2, _ = _world(blocked_ip=CLIENT)
    for dp in (t, o):
        dp.install_bundle(ps=ps2)
        dp.step(PacketBatch.from_packets([_fresh_pkt(CLIENT, SRV)]),
                late + 4)
        eng = dp._slowpath
        assert eng.begin_drain(late + 5)
        eng.finish_drain(late + 5)
        dp.flush_slowpath()
        c = dp.cache_stats()
        assert (c["reclaims"], c["evictions"]) == (2, 1), c


def test_autotuner_hysteresis_no_oscillation():
    """DrainAutotuner: a step-function arrival rate walks the rung ladder
    monotonically (one rung per decision, after the hysteresis streak)
    and holds; in-band depth never moves it; alternating (jittery)
    signals reset the streak and never move it."""
    from antrea_tpu.datapath.slowpath import CHUNK_LADDER, DrainAutotuner

    at = DrainAutotuner(4096, 256, 65536)
    assert at.chunk == 4096
    # Step up: sustained backlog -> monotonic walk to the top rung.
    up = [at.observe(depth=10**6, overflow_delta=0) for _ in range(12)]
    assert all(b >= a for a, b in zip(up, up[1:])), up
    assert up[-1] == 65536
    assert at.decisions_up == CHUNK_LADDER.index(65536) - \
        CHUNK_LADDER.index(4096)
    # Step down: idle queue -> monotonic walk to the bottom rung.
    down = [at.observe(depth=0, overflow_delta=0) for _ in range(20)]
    assert all(b <= a for a, b in zip(down, down[1:])), down
    assert down[-1] == 256
    # In-band depth (between chunk/4 and 2*chunk): dead zone, no motion.
    at2 = DrainAutotuner(4096, 256, 65536)
    assert all(at2.observe(depth=4096, overflow_delta=0) == 4096
               for _ in range(10))
    assert (at2.decisions_up, at2.decisions_down) == (0, 0)
    # Alternating pressure (jitter): direction flips reset the streak —
    # the controller never oscillates.
    at3 = DrainAutotuner(4096, 256, 65536)
    jitter = [at3.observe(depth=(10**6 if i % 2 == 0 else 0),
                          overflow_delta=0) for i in range(12)]
    assert set(jitter) == {4096}, jitter
    # Overflow pressure counts as an up signal even at low depth.
    at4 = DrainAutotuner(256, 256, 65536)
    for _ in range(2):
        at4.observe(depth=0, overflow_delta=5)
    assert at4.chunk == 1024
    # Bounds clamp the ladder.
    at5 = DrainAutotuner(4096, 1024, 16384)
    for _ in range(20):
        at5.observe(depth=10**6, overflow_delta=0)
    assert at5.chunk == 16384
    for _ in range(20):
        at5.observe(depth=0, overflow_delta=0)
    assert at5.chunk == 1024


def test_overlap_knobs_require_async_mode():
    """overlap_commits / autotune_drain configure the async engine; on a
    synchronous datapath they would silently do nothing, so both
    constructors reject them without async_slowpath=True."""
    ps, svcs = _world()
    with pytest.raises(ValueError, match="async_slowpath"):
        TpuflowDatapath(ps, svcs, overlap_commits=True)
    with pytest.raises(ValueError, match="async_slowpath"):
        OracleDatapath(ps, svcs, autotune_drain=True)


def test_autotuned_engine_steps_chunk_against_queue_pressure():
    """Engine-level autotuning: the drain chunk follows queue pressure
    through the pre-compiled rung ladder (engine observes once per
    drain() call), on both engines with identical decisions, and drains
    still classify correctly at the retuned chunk."""
    ps, svcs = _world()
    # flow_slots sized so the 600-flow storm (fwd+rev entries) commits
    # without direct-mapped collisions evicting the probed flow.
    t, o = _pair(ps, svcs, flow_slots=1 << 14, queue=2048, drain_batch=8,
                 autotune_drain=True, autotune_bounds=(256, 4096))
    for dp in (t, o):
        assert dp._slowpath.drain_batch == 256  # seeded to nearest rung
    # Sustained backlog: admit far more than 2 rungs' worth, drain with
    # max_batches=0 so only the controller observes (no pops).
    storm = [_fresh_pkt(CLIENT, SRV) for _ in range(600)]
    for _ in range(2):
        now = next(_NOW)
        for dp in (t, o):
            dp.step(PacketBatch.from_packets(storm), now)
            dp.drain_slowpath(now, max_batches=0)
    for dp in (t, o):
        s = dp.slowpath_stats()
        assert s["drain_batch"] == 1024, s   # one rung up after 2 signals
        assert s["autotune_decisions_up"] == 1
    # The retuned chunk actually drains (and classifies) the backlog.
    st = _drain_both(t, o, next(_NOW))
    assert st["drained"] == 1200
    rt, _ = _step_both(t, o, [storm[0]], next(_NOW))
    assert list(rt.pending) == [0] and list(rt.code) == [0]


def test_hold_admission_leaves_punt_and_arp_lanes_alone():
    """Regression: lanes handled BEFORE the pipeline (IGMP punt, ARP)
    are not misses — a hold admission policy must not stamp its
    provisional DROP on them, and they are never queued (parity with the
    oracle's skipped-lane ALLOW image)."""
    ps, svcs = _world()
    t, o = _pair(ps, svcs, admission="hold")
    igmp = Packet(src_ip=iputil.ip_to_u32(CLIENT),
                  dst_ip=iputil.ip_to_u32("224.0.0.22"), proto=2,
                  src_port=0, dst_port=0)
    arp = Packet(src_ip=iputil.ip_to_u32(CLIENT),
                 dst_ip=iputil.ip_to_u32(SRV), proto=0,
                 src_port=0, dst_port=0)
    miss = _fresh_pkt(CLIENT, SRV)
    bt = PacketBatch.from_packets([igmp, arp, miss])
    bt.arp_op = np.array([0, 1, 0], np.int32)
    bo = PacketBatch.from_packets([igmp, arp, miss])
    bo.arp_op = np.array([0, 1, 0], np.int32)
    now = next(_NOW)
    rt, ro = t.step(bt, now), o.step(bo, now)
    _assert_parity(rt, ro, "punt/arp lanes")
    assert list(rt.code) == [0, 0, 1]     # punt/ARP allow; only the real
    assert list(rt.pending) == [0, 0, 1]  # miss is held + queued
    assert t.slowpath_stats()["depth"] == o.slowpath_stats()["depth"] == 1


@pytest.mark.parametrize("admission,code", [("forward", 0), ("hold", 1)])
def test_fast_step_defers_every_miss_and_commits_nothing(admission, code):
    """Catches a fast step that runs any of the slow path: with
    `PipelineMeta.defer_misses` (what the async engine sets for that one
    program) a miss keeps the admission policy's image, the flow cache
    stays bit for bit as it was however often the batch repeats, and only
    the drain step — the same meta without the flag — commits."""
    ps, svcs = _world()
    dp, _od = _pair(ps, svcs, admission=admission)
    assert dp._meta_step.defer_misses and not dp._meta.defer_misses
    assert dp._meta_step._replace(defer_misses=False,
                                  miss_code=dp._meta.miss_code) == dp._meta
    batch = PacketBatch.from_packets(
        [_fresh_pkt(CLIENT, SRV), _fresh_pkt(BLOCKED, SRV)])
    # Row N is the dump row, the masked scatters' junk target no lookup reads.
    before = [np.asarray(x)[:-1].copy() for x in dp._state.flow]
    for _ in range(2):
        r = dp.step(batch, next(_NOW))
        assert r.n_miss == batch.size and list(r.pending) == [1, 1]
        assert list(r.code) == [code, code] and not any(r.committed)
    for was, now in zip(before, dp._state.flow):
        np.testing.assert_array_equal(was, np.asarray(now)[:-1])
    dp.drain_slowpath(next(_NOW))
    r = dp.step(batch, next(_NOW))
    assert r.n_miss == 0 and list(r.code) == [0, 1]

"""Continuous flow-cache revalidator (ISSUE 5 tentpole): audit-and-repair
for stateful device tensors, differential tpuflow-vs-oracle throughout.

The acceptance bar: an injected cached-verdict flip and an injected
rule-tensor word flip are each (a) NOT detected by the existing
fresh-tuple canary — demonstrating the blind spot PR 4 left, (b) detected
by the audit plane within two full sweeps, (c) repaired with zero
post-repair parity mismatches against the scalar oracle, on both engines,
including with the async slow path enabled; plus the audits-racing-drain/
epoch-swap interleavings, the divergence-rate escalation ladder, the
poison-bundle (PolicyCapacityError) no-retry-storm behavior, the /audit
API + antctl surface (the scrub-coverage gate runs as analysis pass
`audit-plane` in tests/test_static_analysis.py).

Probe discipline: every oracle-parity assertion uses FRESH 5-tuples (a
monotonic source-port counter) — an established flow legitimately
survives policy churn; tests that probe a CACHED entry reuse its tuple
explicitly.
"""

import itertools
import json
import random
import sys
from pathlib import Path

import numpy as np
import pytest

from antrea_tpu.apis import controlplane as cp
from antrea_tpu.apis.service import Endpoint, ServiceEntry
from antrea_tpu.compiler.ir import PolicySet
from antrea_tpu.controller.networkpolicy import WatchEvent
from antrea_tpu.datapath import OracleDatapath, TpuflowDatapath
from antrea_tpu.dissemination import FaultPlan
from antrea_tpu.dissemination.faults import FlakyDatapath
from antrea_tpu.models import pipeline as pl
from antrea_tpu.oracle import Oracle
from antrea_tpu.packet import Packet, PacketBatch
from antrea_tpu.utils import ip as iputil

CLIENT, SRV, BLOCKED = "10.0.1.1", "10.0.0.10", "10.0.9.9"
VIP = "10.96.0.1"

_NOW = itertools.count(1000)
_SPORT = itertools.count(20000)

SMALL = dict(flow_slots=1 << 8, aff_slots=1 << 4)


def _world():
    """One policy (drop BLOCKED -> SRV ingress) + one service so every
    entry class exists: committed forward/reply legs, a denial, and
    service tables for the canary-blind tensor-flip case."""
    ps = PolicySet(
        policies=[cp.NetworkPolicy(
            uid="p1", name="p1", type=cp.NetworkPolicyType.ACNP,
            rules=[cp.NetworkPolicyRule(
                direction=cp.Direction.IN,
                from_peer=cp.NetworkPolicyPeer(address_groups=["blocked"]),
                action=cp.RuleAction.DROP, priority=0)],
            applied_to_groups=["web"], tier_priority=250, priority=1.0)],
        address_groups={"blocked": cp.AddressGroup(
            name="blocked", members=[cp.GroupMember(ip=BLOCKED)])},
        applied_to_groups={"web": cp.AppliedToGroup(
            name="web", members=[cp.GroupMember(ip=SRV)])},
    )
    svcs = [ServiceEntry(cluster_ip=VIP, port=80, protocol=6, name="web",
                         namespace="default",
                         endpoints=[Endpoint(ip=SRV, port=8080)])]
    return ps, svcs


def _dp(dp_cls, ps, svcs, **kw):
    if dp_cls is TpuflowDatapath:
        kw.setdefault("miss_chunk", 16)
    return dp_cls(ps, svcs, **SMALL, **kw)


def _fresh(src, dst=SRV, dport=80):
    return Packet(src_ip=iputil.ip_to_u32(src), dst_ip=iputil.ip_to_u32(dst),
                  proto=6, src_port=next(_SPORT), dst_port=dport)


def _fresh_parity(dp, ps, srcs=(BLOCKED, "192.0.2.7", CLIENT)) -> int:
    """Step FRESH probes and diff every verdict vs Oracle(ps) -> mismatches."""
    now = next(_NOW)
    pkts = [_fresh(s) for s in srcs]
    got = dp.step(PacketBatch.from_packets(pkts), now).code
    oracle = Oracle(ps)
    return sum(int(got[i]) != int(oracle.classify(p).code)
               for i, p in enumerate(pkts))


def _warm(dp):
    """Populate every entry class: a committed service connection (fwd +
    reply legs) and a denial entry, on provably DISTINCT cache slots —
    the direct-mapped table would otherwise let a sport-dependent slot
    collision evict one fixture entry under another and make the
    corruption/repair assertions racy.  Returns the cached tuples."""
    from antrea_tpu.ops import hashing

    N = SMALL["flow_slots"]

    def slot(src, dst, sport, dport):
        return int(hashing.flow_hash(
            np.uint32(iputil.ip_to_u32(src)), np.uint32(iputil.ip_to_u32(dst)),
            6, sport, dport)) & (N - 1)

    while True:
        s1, s2 = next(_SPORT), next(_SPORT)
        # est fwd (CLIENT -> VIP), its reply leg (endpoint -> CLIENT,
        # post-DNAT ports), and the denial (BLOCKED -> SRV).
        slots = {slot(CLIENT, VIP, s1, 80), slot(SRV, CLIENT, 8080, s1),
                 slot(BLOCKED, SRV, s2, 80)}
        if len(slots) == 3:
            break
    est = Packet(src_ip=iputil.ip_to_u32(CLIENT),
                 dst_ip=iputil.ip_to_u32(VIP), proto=6,
                 src_port=s1, dst_port=80)
    den = Packet(src_ip=iputil.ip_to_u32(BLOCKED),
                 dst_ip=iputil.ip_to_u32(SRV), proto=6,
                 src_port=s2, dst_port=80)
    now = next(_NOW)
    dp.step(PacketBatch.from_packets([est, den]), now)
    if dp._slowpath is not None:
        dp.drain_slowpath(now)
    return est, den


def _step_codes(dp, pkts):
    return [int(c) for c in
            np.asarray(dp.step(PacketBatch.from_packets(pkts),
                               next(_NOW)).code)]


# ---------------------------------------------------------------------------
# The acceptance differential: blind spot -> detection <= 2 sweeps -> repair
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dp_cls", [OracleDatapath, TpuflowDatapath])
def test_cached_verdict_flip_blind_spot_detect_repair(dp_cls):
    """(a) a flipped cached verdict bit is invisible to the fresh-tuple
    canary AND keeps serving the wrong verdict; (b) the cursor-window
    revalidation finds it within two full sweeps even when the state
    digest cannot help (mutation accounted — the revalidation-bug shape);
    (c) eviction repairs it with zero post-repair parity mismatches."""
    ps, svcs = _world()
    # window = half the slot space: one full sweep == 2 scans.
    dp = _dp(dp_cls, ps, svcs, audit_window=SMALL["flow_slots"] // 2)
    est, den = _warm(dp)
    dp.audit_scan(now=next(_NOW))  # anchor digests on healthy state

    desc = dp._audit_corrupt("verdict")
    assert "verdict" in desc
    # Model a revalidation BUG rather than bit rot: the wrong value was
    # written by an accounted mutation, so the digest re-anchors over it
    # and only the row checks can catch it.
    dp._state_mutations += 1

    # (a) the blind spot: the canary watchdog sees nothing wrong...
    scan = dp.canary_scan(now=next(_NOW))
    assert scan["mismatches"] == 0 and not dp.degraded
    # ...and fresh-tuple traffic keeps full parity while a CACHED tuple
    # serves a wrong verdict (committed ALLOW flipped to DROP, the denial
    # flipped to ALLOW, or the reply leg flipped — whichever live slot the
    # injection hit, it diverges from the oracle).
    assert _fresh_parity(dp, ps) == 0
    oracle = Oracle(ps)
    reply = Packet(src_ip=iputil.ip_to_u32(SRV),
                   dst_ip=iputil.ip_to_u32(CLIENT), proto=6,
                   src_port=8080, dst_port=est.src_port)
    cached = [est, den, reply]
    # Truth: the service flow and its reply leg are ALLOW, the denial is
    # whatever the stateless oracle says for its raw tuple (DROP).
    want = [0, int(oracle.classify(den).code), 0]
    got = _step_codes(dp, cached)
    assert got != want, "the flip must actually serve a wrong verdict"

    # (b) detection within two full sweeps (== 4 scans at window = N/2).
    repaired_at = None
    for i in range(4):
        out = dp.audit_scan(now=next(_NOW))
        if out["repaired"]:
            repaired_at = i
            break
    assert repaired_at is not None, "audit missed the flip within 2 sweeps"
    st = dp.audit_stats()
    assert st["divergences"].get("verdict", 0) >= 1
    assert st["repairs_total"] >= 1

    # (c) zero post-repair parity mismatches: the evicted entry
    # re-classifies to the oracle verdict, fresh traffic stays clean, and
    # further scans are quiet.
    assert _step_codes(dp, cached) == want
    assert _fresh_parity(dp, ps) == 0
    out = dp.audit_scan(now=next(_NOW))
    assert out["divergences"] == 0 and not dp.degraded


@pytest.mark.parametrize("dp_cls", [OracleDatapath, TpuflowDatapath])
def test_rule_tensor_flip_blind_spot_detect_repair(dp_cls):
    """A flipped service-table word (the canary-BLIND tensor class: canary
    probes deliberately avoid service frontends) is (a) invisible to the
    canary, (b) caught by the checksum scrub on the next scan, (c) healed
    by host-mirror re-upload with zero post-repair parity mismatches —
    including the service DNAT resolution the flip corrupted."""
    ps, svcs = _world()
    dp = _dp(dp_cls, ps, svcs)
    _warm(dp)
    dp.audit_scan(now=next(_NOW))  # anchor

    desc = dp._audit_corrupt("tensor")
    assert "flip" in desc

    # (a) canary-blind: probes avoid frontends, so the corrupted service
    # tables certify clean.
    scan = dp.canary_scan(now=next(_NOW))
    assert scan["mismatches"] == 0 and not dp.degraded
    # The corruption is LIVE though: a fresh service flow resolves the
    # wrong endpoint port.
    vip_probe = _fresh("10.0.3.3", dst=VIP)
    r = dp.step(PacketBatch.from_packets([vip_probe]), next(_NOW))
    if dp._slowpath is None:
        assert int(r.dnat_port[0]) != 8080  # serving the flipped port

    # (b) the scrub detects on the next scan and heals by re-upload.
    out = dp.audit_scan(now=next(_NOW))
    assert out.get("healed"), out
    assert dp.audit_stats()["scrub"].get("corrupt", 0) >= 1
    assert dp.audit_stats()["scrub"].get("healed", 0) >= 1

    # (c) post-repair: fresh service traffic resolves the true endpoint
    # (the corrupted-port entry itself was evicted by the forced full
    # revalidation or re-proves clean), and parity holds.
    probe2 = _fresh("10.0.3.4", dst=VIP)
    r2 = dp.step(PacketBatch.from_packets([probe2]), next(_NOW))
    if dp._slowpath is None:
        assert int(r2.dnat_port[0]) == 8080
    assert _fresh_parity(dp, ps) == 0
    out = dp.audit_scan(now=next(_NOW))
    assert out["divergences"] == 0 and "healed" not in out


@pytest.mark.parametrize("dp_cls", [OracleDatapath, TpuflowDatapath])
def test_audit_repair_parity_async_slowpath(dp_cls):
    """The acceptance's async leg: with the background slow-path engine
    enabled, a verdict flip on a drained-and-cached entry is detected and
    repaired, and post-repair verdicts (via admission -> drain -> cached
    re-step) match the scalar oracle exactly."""
    ps, svcs = _world()
    dp = _dp(dp_cls, ps, svcs, async_slowpath=True, miss_queue_slots=64,
             drain_batch=16, audit_window=SMALL["flow_slots"] // 2)
    est, den = _warm(dp)
    dp.audit_scan(now=next(_NOW))

    dp._audit_corrupt("verdict")
    dp._state_mutations += 1  # revalidation-bug shape: digest blind
    for _ in range(4):
        out = dp.audit_scan(now=next(_NOW))
        if out["repaired"]:
            break
    assert dp.audit_stats()["repairs_total"] >= 1

    # Post-repair: each cached tuple re-admits, drains, and re-proves to
    # the oracle verdict.  Per-tuple batches (admission -> drain ->
    # cached re-step) so a direct-mapped slot collision between the two
    # tuples cannot evict one mid-assertion.
    oracle = Oracle(ps)
    for p, expect in ((est, 0), (den, int(oracle.classify(den).code))):
        now = next(_NOW)
        dp.step(PacketBatch.from_packets([p]), now)
        dp.drain_slowpath(now)
        assert _step_codes(dp, [p]) == [expect]
    assert dp.audit_scan(now=next(_NOW))["divergences"] == 0


def test_mode_for_mode_plane_parity():
    """The scalar twin implements identical audit semantics: the same
    traffic + the same corruption sequence produces the same divergence
    kinds, repair counts, and sweep accounting on both engines."""
    ps, svcs = _world()
    planes = []
    for dp_cls in (TpuflowDatapath, OracleDatapath):
        dp = _dp(dp_cls, ps, svcs, audit_window=SMALL["flow_slots"] // 2)
        _warm(dp)
        dp.audit_scan(now=500)
        dp._audit_corrupt("verdict")
        dp._state_mutations += 1
        for _ in range(4):
            dp.audit_scan(now=501)
        dp._audit_corrupt("tensor")
        dp.audit_scan(now=502)
        st = dp.audit_stats()
        planes.append({
            "divergences": st["divergences"],
            "repairs_total": st["repairs_total"],
            "sweeps_total": st["sweeps_total"],
            "entries_min": st["entries_total"] >= 3,
        })
    assert planes[0] == planes[1], planes


# ---------------------------------------------------------------------------
# Interleavings: the unified scheduler racing drains and epoch swaps.
# (PR 7 replaced the hand-enumerated pairwise interleaving cases with the
# scheduler-driven randomized-schedule property test below — the scheduler
# is now the ONLY way the background loops interleave in production, so
# the property is over ALL registered tasks at once, not plane pairs.)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dp_cls", [OracleDatapath, TpuflowDatapath])
def test_scheduler_randomized_schedule_racing_drain_and_epoch_swap(dp_cls):
    """Seeded randomized-schedule property test: interleave maintenance
    ticks (all registered tasks — canary, audit cursor, scrub, fused
    cache-maintain, recompile) with traffic steps, split in-flight drains
    (begin/finish), and bundle installs (epoch swaps).  Invariants held
    at every point: a tick landing inside begin_drain..finish_drain
    defers WHOLE (the serialization point — the pinned block is never
    audited/aged under an in-flight drain); a budgeted tick never spends
    past its budget; and after the storm the engine reconverges — drains
    classify to exact oracle parity, a forced full audit sweep is quiet,
    and nothing is degraded."""
    import copy

    rng = random.Random(0xA11CE)
    ps, svcs = _world()
    dp = _dp(dp_cls, ps, svcs, async_slowpath=True, miss_queue_slots=64,
             drain_batch=16, canary_probes=8, audit_window=32)
    eng = dp._slowpath
    oracle = Oracle(ps)
    inflight = False
    stepped: list = []
    for _op in range(40):
        now = next(_NOW)
        op = rng.choice(["tick", "tick", "budget_tick", "step", "begin",
                         "finish", "install"])
        if op in ("tick", "budget_tick"):
            budget = rng.choice([8, 16, 64]) if op == "budget_tick" else None
            out = dp.maintenance_tick(now=now, budget=budget)
            if inflight:
                assert out["blocked"] == "inflight-drain", out
                assert not out["ran"] and out["spent"] == 0
            else:
                assert out["blocked"] is None
            if budget is not None:
                assert out["spent"] <= budget, out
        elif op == "step":
            pkts = [_fresh(rng.choice([BLOCKED, CLIENT, "192.0.2.7",
                                       "198.51.100.9"]))
                    for _ in range(2)]
            stepped.extend(pkts)
            dp.step(PacketBatch.from_packets(pkts), now)
        elif op == "begin":
            if not inflight:
                inflight = eng.begin_drain(now)
        elif op == "finish":
            if inflight:
                eng.finish_drain(now)
                inflight = False
        elif op == "install":
            # An epoch swap mid-storm (and legitimately mid-drain: the
            # stale-reclassify path) — the scheduler's next unblocked
            # tick promotes the fused heal.
            dp.install_bundle(ps=copy.deepcopy(ps))
    if inflight:
        eng.finish_drain(next(_NOW))
    # Reconvergence: settle the queue (drain() heals any stale epoch with
    # the fused maintenance pass), then every invariant at once.  Parity
    # probes on the async engine go admit -> drain -> cached re-step
    # (fresh misses are provisional until drained).
    dp.drain_slowpath(next(_NOW))
    assert not eng.stale
    probe = stepped[-2:] or [_fresh(BLOCKED)]
    now = next(_NOW)
    dp.step(PacketBatch.from_packets(probe), now)
    dp.drain_slowpath(now)
    got = _step_codes(dp, probe)
    assert got == [int(oracle.classify(p).code) for p in probe]
    quiet = dp.maintenance_force_audit(now=next(_NOW))
    assert quiet["divergences"] == 0, quiet
    assert not dp.degraded
    st = dp.maintenance_stats()
    # The storm exercised both sides of the serialization point.
    assert st["ticks_total"] > 0
    assert all(row["overruns_total"] == 0 for row in st["tasks"].values())


# ---------------------------------------------------------------------------
# Divergence policy: the shared escalation ladder + fault sites
# ---------------------------------------------------------------------------


def test_divergence_rate_trips_degraded_escalation():
    """Findings at/above the trip threshold feed the PR 4 machinery: the
    datapath degrades and the immediate full recompile — itself
    canary-gated — recovers it, exactly like canary_scan."""
    ps, svcs = _world()
    dp = _dp(OracleDatapath, ps, svcs, audit_divergence_trip=1)
    plan = FaultPlan()
    dp.arm_audit_faults(plan, "n1")
    _warm(dp)

    plan.after("n1.audit", plan.hits("n1.audit"), "fail", times=1)
    out = dp.audit_scan(now=next(_NOW))
    assert out["divergences"] == 1  # the forced false positive
    assert out["recovered"] and not dp.degraded  # recompile certified
    assert dp.audit_stats()["divergences"].get("injected") == 1
    assert _fresh_parity(dp, ps) == 0

    # With the recompile ALSO failing (persistent miscompile injection),
    # the trip leaves the datapath safely degraded on LKG verdicts.
    dp.arm_commit_faults(plan, "n1")
    plan.after("n1.audit", plan.hits("n1.audit"), "fail", times=1)
    plan.after("n1.canary", plan.hits("n1.canary"), "fail", times=1)
    out = dp.audit_scan(now=next(_NOW))
    assert not out["recovered"] and dp.degraded
    assert _fresh_parity(dp, ps) == 0  # LKG keeps serving correctly
    dp.install_bundle(ps=ps)  # fault exhausted: agent-style recovery
    assert not dp.degraded


def test_affinity_drift_repairs_without_tripping_degrade():
    """A divergent row on an affinity-bearing program may be DRIFT (the
    fresh walk reads the CURRENT affinity table, which can have expired
    or been overwritten since insert), not corruption: it is repaired by
    eviction but reported as kind 'affinity' and excluded from the
    degrade trip — a burst of expired affinity learns can never
    quarantine a node.  Plane-level test over a stub owner so the drift
    is deterministic."""
    from antrea_tpu.datapath.audit import AuditPlane

    def row(slot, aff, dnat):
        return {"slot": slot, "src": 1, "dst": 2, "proto": 6, "sport": 1000,
                "dport": 80, "code": 1, "svc": 0, "dnat_ip": dnat,
                "dnat_port": 80, "rule_in": "r", "rule_out": None,
                "committed": False, "reply": False, "aff": aff}

    class _Commit:
        def __init__(self):
            self.degraded = False
            self.last_error = ""
            self.recompiles = 0

        def run_bundle(self, ps, services):
            self.recompiles += 1

    class _Stub:
        generation = 0

        def __init__(self):
            self._state_mutations = 0
            self._commit = _Commit()
            self.evicted = []

        def _audit_slots(self):
            return 8

        def _audit_window(self, cursor, k, now):
            # One affinity-bearing row whose service selection drifted,
            # one identical row WITHOUT affinity (proven corruption).
            return [row(1, True, dnat=111), row(2, False, dnat=222)]

        def _audit_fresh(self, rows, now):
            return [{"code": 1, "svc": 0, "dnat_ip": 999, "dnat_port": 80,
                     "rule_in": "r", "rule_out": None} for _ in rows]

        def _audit_evict(self, slots):
            self.evicted.extend(slots)
            self._state_mutations += 1

        def _audit_rule_digests(self):
            return {"rules": 1}

        def _audit_state_digest(self):
            return self._state_mutations  # tracks mutations: never corrupt

    # Mixed scan: both rows repaired; the proven (non-affinity) one trips.
    owner = _Stub()
    plane = AuditPlane(owner, window=8, divergence_trip=1)
    plane.refresh_golden()
    out = plane.scan(now=1)
    assert sorted(owner.evicted) == [1, 2] and out["repaired"] == 2
    assert plane.divergences["affinity"] == 1
    assert plane.divergences["service"] == 1
    assert owner._commit.recompiles == 1  # escalation fired on the proof

    # Affinity-only scan: repaired, metered, but NEVER trips the ladder.
    class _AffOnly(_Stub):
        def _audit_window(self, cursor, k, now):
            return [row(1, True, dnat=111)]

    owner2 = _AffOnly()
    plane2 = AuditPlane(owner2, window=8, divergence_trip=1)
    plane2.refresh_golden()
    out2 = plane2.scan(now=1)
    assert out2["repaired"] == 1 and owner2.evicted == [1]
    assert plane2.divergences == {"affinity": 1}
    assert not owner2._commit.degraded
    assert owner2._commit.recompiles == 0


def test_flaky_wrapper_arms_audit_sites_and_scan_self_detects():
    """FlakyDatapath auto-arms {name}.cache / {name}.audit; a .cache
    firing REALLY corrupts state at scan start and the same scan detects
    and repairs its own injection (state digest anchored pre-scan)."""
    ps, svcs = _world()
    plan = FaultPlan()
    dp = FlakyDatapath(_dp(OracleDatapath, ps, svcs), plan, "nX")
    _warm(dp)
    dp.audit_scan(now=next(_NOW))  # anchor

    plan.after("nX.cache", plan.hits("nX.cache"), "fail", times=1)
    out = dp.audit_scan(now=next(_NOW))
    assert "injected_corruption" in out
    assert out["full"]  # state-digest mismatch forced the full sweep
    assert out["repaired"] >= 1
    assert plan.count("fail") == 1
    assert _fresh_parity(dp, ps) == 0

    # kind "partial" targets the rule-side tensors instead.
    plan.after("nX.cache", plan.hits("nX.cache"), "partial", times=1)
    out = dp.audit_scan(now=next(_NOW))
    assert out.get("healed"), out
    assert _fresh_parity(dp, ps) == 0


# ---------------------------------------------------------------------------
# Hot path unharmed + counters + tooling + typed capacity errors
# ---------------------------------------------------------------------------


def test_step_hlo_bit_identical_with_audit_plane():
    """The audit plane lives entirely off the hot step: the compiled step
    of an audit-configured datapath — before AND after scans — lowers to
    byte-identical HLO vs a default-config twin (the check_phases-style
    bit-identity bar for the plane)."""
    ps, svcs = _world()
    a = _dp(TpuflowDatapath, ps, svcs, audit_window=8,
            audit_divergence_trip=2)
    b = _dp(TpuflowDatapath, ps, svcs)
    assert a._meta_step == b._meta_step

    def lower_text(dp):
        import jax.numpy as jnp

        z = np.zeros(4, np.int32)
        return pl.pipeline_step.lower(
            dp._state, dp._drs, dp._dsvc,
            jnp.asarray(z), jnp.asarray(z), jnp.asarray(z),
            jnp.asarray(z), jnp.asarray(z),
            jnp.int32(0), jnp.int32(0), meta=dp._meta_step,
        ).as_text()

    before = lower_text(a)
    assert before == lower_text(b)
    _warm(a)
    a.audit_scan(now=next(_NOW), full=True)
    assert lower_text(a) == before


def test_audit_scan_leaves_counters_and_census_intact():
    """A clean scan is observable-state-neutral: flow-cache census,
    per-rule stats, and cache contents are untouched (the counter
    interaction proper lives in test_flow_counters.py)."""
    ps, svcs = _world()
    dp = _dp(TpuflowDatapath, ps, svcs)
    _warm(dp)
    before = (dp.cache_stats(), dp.stats().ingress,
              sorted((f["src"], f["sport"])
                     for f in dp.dump_flows(now=next(_NOW))))
    dp.audit_scan(now=next(_NOW), full=True)
    after = (dp.cache_stats(), dp.stats().ingress,
             sorted((f["src"], f["sport"])
                    for f in dp.dump_flows(now=next(_NOW))))
    assert before[0] == after[0] and before[1] == after[1]
    assert before[2] == after[2]


# The scrub-coverage gate (tools/check_audit_plane.py -> analysis pass
# `audit-plane`) runs once for the whole tier-1 suite in
# tests/test_static_analysis.py.


def test_policy_capacity_error_is_typed():
    """rule_split raises the typed PolicyCapacityError (still a
    ValueError for pre-existing callers)."""
    from types import SimpleNamespace

    # the two indices share 32 bits (pl.rule_split): 17 + 17 do not fit
    cps = SimpleNamespace(ingress=SimpleNamespace(n_rules=0xFFFE),
                          egress=SimpleNamespace(n_rules=0xFFFE))
    with pytest.raises(pl.PolicyCapacityError):
        pl.rule_split(cps)
    with pytest.raises(ValueError):
        pl.rule_split(cps)


def test_poison_bundle_reports_failed_and_stops_hot_retrying():
    """A deterministic compile rejection (PolicyCapacityError) is
    classified PERMANENT: one attempt, a Failed realization reported
    upstream with the reason, and NO retry storm — until new upstream
    state arrives, which earns exactly one fresh attempt.  Transient
    errors keep the existing backoff-retry discipline."""
    from antrea_tpu.agent.controller import AgentPolicyController

    class _PoisonDP:
        degraded = False

        def __init__(self, exc):
            self.calls = 0
            self.exc = exc

        def install_bundle(self, ps=None, services=None):
            self.calls += 1
            raise self.exc

    reports = []
    t = [0.0]
    dp = _PoisonDP(pl.PolicyCapacityError("too many rules"))
    agent = AgentPolicyController(
        "n1", dp, clock=lambda: t[0],
        status_reporter=lambda node, realized, failure="": reports.append(
            (node, failure)))
    agent._rules_dirty = True
    for _ in range(8):
        t[0] += 10.0  # far past any backoff window
        agent.sync()
    assert dp.calls == 1, "poison bundle must not hot-retry"
    assert agent.sync_failures_total == 1
    assert "too many rules" in agent.permanent_failure
    assert any("too many rules" in f for _n, f in reports)

    # New upstream state clears the quarantine: exactly one new attempt.
    policy = cp.NetworkPolicy(uid="P9", name="P9",
                              type=cp.NetworkPolicyType.ACNP,
                              applied_to_groups=[], rules=[],
                              tier_priority=250, priority=1.0)
    agent.handle_event(WatchEvent(kind="ADDED", obj_type="NetworkPolicy",
                                  name="P9", obj=policy))
    assert agent.permanent_failure == ""
    t[0] += 10.0
    agent.sync()
    assert dp.calls == 2

    # Contrast: a TRANSIENT error keeps retrying with backoff.
    dp2 = _PoisonDP(RuntimeError("flaky install"))
    agent2 = AgentPolicyController("n2", dp2, clock=lambda: t[0])
    agent2._rules_dirty = True
    for _ in range(4):
        t[0] += 10.0
        agent2.sync()
    assert dp2.calls == 4 and agent2.permanent_failure == ""


# ---------------------------------------------------------------------------
# API + antctl + metrics surface
# ---------------------------------------------------------------------------


def test_audit_api_route_and_forced_sweep_and_antctl(capsys):
    """GET /audit serves the plane's status; ?force=1 runs a synchronous
    full sweep; `antctl audit --server URL --force` drives it end to end;
    the new metric families render and carry the scan counts."""
    import urllib.request

    from antrea_tpu.agent.apiserver import AgentApiServer
    from antrea_tpu.antctl import main as antctl_main
    from antrea_tpu.observability.metrics import render_metrics

    ps, svcs = _world()
    dp = _dp(OracleDatapath, ps, svcs)
    _warm(dp)
    srv = AgentApiServer(dp, node="n1").start()
    try:
        body = json.loads(urllib.request.urlopen(
            srv.address + "/audit").read())
        assert {"cursor", "coverage_ratio", "last_divergence",
                "scans_total"} <= set(body)
        forced = json.loads(urllib.request.urlopen(
            srv.address + "/audit?force=1&now=9").read())
        assert forced["sweeps_total"] >= 1
        assert forced["last_scan"]["full"] is True

        rc = antctl_main(["audit", "--server", srv.address, "--force"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["sweeps_total"] >= 2
    finally:
        srv.close()

    text = render_metrics(dp, node="n1")
    assert "antrea_tpu_cache_audit_scans_total" in text
    assert "antrea_tpu_audit_cursor_coverage_ratio" in text
    assert "antrea_tpu_tensor_scrub_total" in text


# ---------------------------------------------------------------------------
# Full reachability fixtures (the acceptance's fixture sweep; slow tier)
# ---------------------------------------------------------------------------


def _fixture_probe(p):
    from fixtures_reachability import _ip

    return Packet(src_ip=iputil.ip_to_u32(_ip(p.src)),
                  dst_ip=iputil.ip_to_u32(_ip(p.dst)),
                  proto=p.proto, src_port=p.sport + next(_SPORT) % 10000,
                  dst_port=p.dport)


def _fixture_sweep(dp_cls, scenarios):
    for si, scenario in enumerate(scenarios):
        kw = {"miss_chunk": 8} if dp_cls is TpuflowDatapath else {}
        dp = dp_cls(scenario.ps, [], **SMALL, **kw)
        probes = [_fixture_probe(p) for p in scenario.probes]
        L = max(8, len(probes))  # stable lane count: one compile per meta
        dp.step(PacketBatch.from_packets((probes * L)[:L]), next(_NOW))
        dp.audit_scan(now=next(_NOW))  # anchor
        dp._audit_corrupt("verdict" if si % 2 == 0 else "tensor")
        out = dp.audit_scan(now=next(_NOW))  # digest -> forced full sweep
        assert out["full"], (scenario.name, out)
        # Post-repair: fresh-sport probes re-prove the fixture's expected
        # verdicts — zero mismatches vs the hand-authored truth table.
        fresh = [_fixture_probe(p) for p in scenario.probes]
        codes = np.asarray(dp.step(PacketBatch.from_packets(
            (fresh * L)[:L]), next(_NOW)).code)
        bad = [(scenario.name, p.src, p.dst, "expected", p.expect, "got",
                int(codes[i]))
               for i, p in enumerate(scenario.probes)
               if int(codes[i]) != p.expect]
        assert not bad, bad
        quiet = dp.audit_scan(now=next(_NOW), full=True)
        assert quiet["divergences"] == 0, (scenario.name, quiet)


@pytest.mark.slow
def test_fixture_sweep_oracle_engine():
    from fixtures_reachability import SCENARIOS

    _fixture_sweep(OracleDatapath, SCENARIOS)


@pytest.mark.slow
def test_fixture_sweep_tpuflow_engine():
    from fixtures_reachability import SCENARIOS

    _fixture_sweep(TpuflowDatapath, SCENARIOS)

"""Upstream's xLargeScale shape (`networkpolicy_controller_perf_test.go`,
`TestInitXLargeScaleWithSmallNamespaces`: many namespaces of four pods, each
closed on itself by three K8s NetworkPolicies) through the served datapath,
at sizes tier-1 can hold: a few hundred namespaces, and a count past the
simulator's caps (over 4,096 groups and over `match._SS_FLAT` bounds a
dimension, so the blocked interval search runs).

What is held:
  * four steps shaped like the `xlarge75k.churn` cell's (open connections by
    Zipf rank: allowed inside a namespace, pod to external, denied probes;
    a new connection on every 8th lane) answer like the scalar twin
    `OracleDatapath` lane for lane: verdict, both rule ids, `est`,
    `committed`, `n_miss`;
  * an engine that installed half the policy does not;
  * the flow cache's packed rule-attribution column splits its 32 bits by
    the two directions' rule counts (`pipeline.rule_split`: 17/15 for this
    shape's 75,000 ingress rules and no egress rule, 16/16 for every world
    both of whose directions fit 16 bits), and a lopsided split serves,
    reads back and survives a renumbering install;
  * the commit transaction's `upload` sub-span lies inside `compile` and its
    `table_bytes` are the bytes of the rule, isolation and Service tables
    the install placed (observability/tracing.COMMIT_SUBSPANS).
"""

from unittest import mock

import jax
import numpy as np
import pytest

from antrea_tpu.apis import controlplane as cp
from antrea_tpu.compiler.ir import PolicySet
from antrea_tpu.datapath import OracleDatapath, TpuflowDatapath
from antrea_tpu.models import pipeline as pl
from antrea_tpu.observability.tracing import COMMIT_SUBSPANS
from antrea_tpu.ops import match
from antrea_tpu.packet import PacketBatch
from antrea_tpu.simulator import gen_services
from antrea_tpu.utils import ip as iputil

B = 128
KW = dict(flow_slots=1 << 12, aff_slots=1 << 8, canary_probes=8,
          miss_chunk=B)  # one round a step: the twin's bookkeeping exactly
SIZES = (240, 2100)  # namespaces
FIELDS = ("code", "ingress_rule", "egress_rule", "est", "committed", "n_miss")


def _ip(ns: int, pod: int) -> str:
    return f"10.{(ns >> 8) & 255}.{ns & 255}.{pod + 1}"


def _policy_set(n_ns: int, keep=lambda k: True) -> PolicySet:
    """Per namespace what `bench_controller.populate(pods_per_ns=4,
    nps_per_ns=3)` makes: pods 0..3 in the labels app-0 / app-1, policy k
    selecting app-(k % 2) and allowing ingress from app-((k + 1) % 2) on
    TCP/80.  `keep` leaves policies out by their number in the cluster."""
    ps = PolicySet()
    for i in range(n_ns):
        for label in (0, 1):
            members = [cp.GroupMember(ip=_ip(i, j), node=f"node-{j}",
                                      pod_name=f"ns-{i}/pod-{j}")
                       for j in (label, label + 2)]
            name = f"ns-{i}-app-{label}"
            ps.address_groups[name] = cp.AddressGroup(name=name,
                                                      members=members)
            ps.applied_to_groups[name] = cp.AppliedToGroup(name=name,
                                                           members=members)
        for k in range(3):
            if keep(3 * i + k):
                ps.policies.append(cp.NetworkPolicy(
                    uid=f"np-{i}-{k}", name=f"np-{k}", namespace=f"ns-{i}",
                    type=cp.NetworkPolicyType.K8S,
                    rules=[cp.NetworkPolicyRule(
                        direction=cp.Direction.IN,
                        from_peer=cp.NetworkPolicyPeer(
                            address_groups=[f"ns-{i}-app-{(k + 1) % 2}"]),
                        services=[cp.Service(protocol=6, port=80)])],
                    applied_to_groups=[f"ns-{i}-app-{k % 2}"],
                    policy_types=[cp.Direction.IN]))
    return ps


def _batches(n_ns: int, seed: int) -> list:
    """Four batches over 96 open connections (Zipf 1 by rank; 3/4 allowed
    inside a namespace, 1/8 pod to external, 1/8 denied: same label, another
    port, another namespace, external to pod), every 8th lane a connection
    never sent before."""
    rng = np.random.default_rng(seed)

    def flow(kind):
        i, j = rng.integers(n_ns), rng.integers(4)
        src, dst, port = _ip(i, j), _ip(i, (j + 1) % 4), 80
        if kind == "external":
            dst = f"198.51.{rng.integers(256)}.{rng.integers(1, 255)}"
        elif kind == "denied":
            how = rng.integers(4)
            if how == 0:
                dst = _ip(i, (j + 2) % 4)  # the same label
            elif how == 1:
                port = 8080
            elif how == 2:
                dst = _ip((i + 1 + rng.integers(n_ns - 1)) % n_ns, j ^ 1)
            else:
                src = f"203.0.113.{rng.integers(1, 255)}"
        return (iputil.ip_to_u32(src), iputil.ip_to_u32(dst), port)

    kinds = ["allowed"] * 6 + ["external", "denied"]
    hot = [flow(kinds[r % 8]) + (1024 + r,) for r in range(96)]
    weight = 1.0 / np.arange(1, 97)
    out, fresh = [], 0
    for _ in range(4):
        lanes = [hot[r] for r in rng.choice(96, size=B, p=weight
                                            / weight.sum())]
        for at in range(0, B, 8):
            lanes[at] = flow(kinds[fresh % 8]) + (32768 + fresh,)
            fresh += 1
        src, dst, dport, sport = (np.array(c) for c in zip(*lanes))
        out.append(PacketBatch(
            src_ip=src.astype(np.uint32), dst_ip=dst.astype(np.uint32),
            proto=np.full(B, 6, np.int32), src_port=sport.astype(np.int32),
            dst_port=dport.astype(np.int32)))
    return out


@pytest.fixture(scope="module", params=SIZES)
def served(request):
    """(namespaces, [(StepResult, the twin's)] a step, the batches)."""
    n_ns = request.param
    ps = _policy_set(n_ns)
    dp = TpuflowDatapath(ps, [], **KW)
    twin = OracleDatapath(ps, [], **{k: v for k, v in KW.items()
                                     if k != "miss_chunk"})
    batches = _batches(n_ns, seed=n_ns)
    return n_ns, [(dp.step(b, now=10 + i), twin.step(b, now=10 + i))
                  for i, b in enumerate(batches)], batches


def test_the_larger_world_is_past_the_simulators_caps():
    ps = _policy_set(SIZES[1])
    assert len(ps.applied_to_groups) == len(ps.address_groups) > 4096
    assert len(ps.policies) == 3 * SIZES[1]
    dp = TpuflowDatapath(ps, [], **dict(KW, canary_probes=0))
    d = dp._drs.ingress
    for tab in (d.at, d.peer, dp._drs.iso_in):
        assert tab.bounds.shape[0] > match._SS_FLAT  # the blocked search
    w = dp._meta.match.w_in  # 6,300 rules: 197 words, tiled to 256
    assert w == 256 and w % match.TILE_WORDS == 0
    assert d.at.inc.shape == (d.at.bounds.shape[0] + 2, w)
    assert dp._meta.match.in_phases == (0, 3 * SIZES[1], 0)
    assert dp._meta.match.out_phases == (0, 0, 0)  # no egress rule at all


@pytest.mark.parametrize("field", FIELDS)
def test_churn_shaped_steps_answer_like_the_scalar_twin(served, field):
    _, steps, _ = served
    for i, (res, want) in enumerate(steps):
        got, stated = getattr(res, field), getattr(want, field)
        if isinstance(stated, (list, int)):
            assert got == stated, (field, i)
        else:
            np.testing.assert_array_equal(np.asarray(got, np.int64),
                                          np.asarray(stated, np.int64),
                                          err_msg=f"{field} step {i}")
    first, last = steps[0][0], steps[-1][0]
    if field == "code":
        assert {0, 1} == set(np.asarray(first.code).tolist())
        assert 0.05 < np.mean(np.asarray(first.code) != 0) < 0.3
    if field == "ingress_rule":  # the allowing rule is named, a denial by
        named = np.array([r is not None for r in first.ingress_rule])
        assert (named <= (np.asarray(first.code) == 0)).all()  # isolation not
        assert 0.5 < named.mean() < 0.9
    if field == "egress_rule":
        assert all(r is None for r in first.egress_rule)
    if field == "est":  # open connections are established by the last step,
        assert not np.asarray(first.est).any()  # the arrivals never
        assert np.asarray(last.est).mean() > 0.5
        assert not np.asarray(last.est)[::8].any()
    if field == "n_miss":
        assert first.n_miss == B and B // 8 <= last.n_miss < B // 2


def test_half_the_policy_answers_wrongly(served):
    """The planted fault: every second policy left out of the install.  A
    pod whose only selecting policy went is no longer isolated, and a
    conversation whose allowing rules went is denied."""
    n_ns, steps, batches = served
    half = TpuflowDatapath(_policy_set(n_ns, keep=lambda k: k % 2 == 0), [],
                           **dict(KW, canary_probes=0))
    wrong = 0
    for i, (b, (res, _)) in enumerate(zip(batches, steps)):
        got = half.step(b, now=10 + i)
        wrong += int(np.sum(np.asarray(got.code) != np.asarray(res.code)))
    assert wrong > 10


def test_the_upload_lies_inside_compile_and_counts_the_placed_tables():
    ps = _policy_set(SIZES[0])
    services = gen_services(6, [iputil.ip_to_u32(_ip(i, 0))
                                for i in range(8)], seed=2)
    dp = TpuflowDatapath(**KW)
    tracer = dp.realization_tracer
    assert tracer.last_commit() is None  # the boot tables are no transaction
    assert COMMIT_SUBSPANS == (
        ("rules", "compile"), ("tables", "compile"), ("upload", "compile"),
        ("oracle", "canary"), ("walk", "canary"), ("digest", None))

    def placed(*trees):
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(trees))

    dp.install_bundle(ps, services)
    last = tracer.last_commit()
    assert 0.0 < last["upload_s"] <= last["compile_s"]
    assert last["table_bytes"] == placed(dp._drs, dp._dsvc)
    assert last["table_bytes"] > dp._drs.ingress.at.inc.nbytes * 2
    stages = [last[f"{s}_s"] for s in ("compile", "canary", "swap", "settle")]
    assert min(stages) >= 0.0  # the sub-span is in no telescoping sum
    # a Services-only install places the Service tables alone
    dp.install_bundle(None, services[:3])
    again = tracer.last_commit()
    assert again["generation"] == last["generation"] + 1
    assert again["table_bytes"] == placed(dp._dsvc)
    assert 0.0 <= again["upload_s"] <= again["compile_s"]
    # an engine that places nothing records zeros
    twin = OracleDatapath(canary_probes=8)
    twin.install_bundle(ps, services)
    zeros = twin.realization_tracer.last_commit()
    assert (zeros["upload_s"], zeros["table_bytes"]) == (0.0, 0)


@pytest.mark.parametrize("n_in, n_out, bits", [
    (100, 100, 16), (0xFFFD, 0xFFFD, 16),  # every world before this one
    (75000, 1, 17),  # the xLargeScale cluster: 75,000 ingress rules, none out
    (0xFFFE, 3, 17), (3, 0xFFFE, 15), (1 << 20, 1000, 21)])
def test_the_packed_rule_column_splits_by_the_two_counts(n_in, n_out, bits):
    from types import SimpleNamespace as NS

    cps = NS(ingress=NS(n_rules=n_in), egress=NS(n_rules=n_out))
    assert pl.rule_split(cps) == bits
    rule_in = np.array([-1, 0, n_in - 1], np.int32)
    rule_out = np.array([n_out - 1, -1, 0], np.int32)
    packed = pl._pack_rules(rule_in, rule_out, bits)
    for got, want in zip(pl._unpack_rules(packed, bits), (rule_in, rule_out)):
        np.testing.assert_array_equal(got, want)
    assert pl._unpack_rules(int(packed[0]), bits) == (-1, n_out - 1)
    # one bit more than the ingress count leaves: no split holds both
    too_many = 1 << (32 - (n_in + 2).bit_length())
    with pytest.raises(pl.PolicyCapacityError):
        pl.rule_split(NS(ingress=NS(n_rules=n_in),
                         egress=NS(n_rules=too_many)))


def test_a_lopsided_split_serves_and_survives_a_renumbering_install():
    """The 17/15 layout that 75,000 ingress rules select, on a world tier-1
    can hold (the split is forced; `rule_split` itself is held above): the
    steps answer like the twin, the cached attribution reads back through
    `dump_flows`, and an install that moves the split rewrites the cache."""
    n_ns = SIZES[0]
    ps, batches = _policy_set(n_ns), _batches(n_ns, seed=5)
    dp = TpuflowDatapath(ps, [], **KW)  # 16/16
    twin = OracleDatapath(ps, [], **{k: v for k, v in KW.items()
                                     if k != "miss_chunk"})
    assert dp._meta.rule_bits_in == 16
    for i, b in enumerate(batches[:2]):
        res, want = dp.step(b, now=10 + i), twin.step(b, now=10 + i)
    before = {(f["src"], f["dst"], f["sport"]): f["ingress_rule"]
              for f in dp.dump_flows(now=12)}
    assert sum(r is not None for r in before.values()) > 20
    # the same policies in another order, under the lopsided split
    moved = _policy_set(n_ns)
    moved.policies.reverse()
    with mock.patch.object(pl, "rule_split", lambda cps: 17):
        dp.install_bundle(moved, [])
    twin.install_bundle(moved, [])
    assert dp._meta.rule_bits_in == dp._meta_step.rule_bits_in == 17
    after = {(f["src"], f["dst"], f["sport"]): f["ingress_rule"]
             for f in dp.dump_flows(now=12)}
    # rule identity followed the renumbering; what went is the cached
    # denials, which die with their generation and never named a rule
    assert after.items() <= before.items()
    assert all(r is None for k, r in before.items() if k not in after)
    assert sum(r is not None for r in after.values()) > 20
    for i, b in enumerate(batches[2:]):
        res, want = dp.step(b, now=13 + i), twin.step(b, now=13 + i)
        for field in FIELDS:
            got, stated = getattr(res, field), getattr(want, field)
            if isinstance(stated, (list, int)):
                assert got == stated, (field, i)
            else:
                np.testing.assert_array_equal(np.asarray(got, np.int64),
                                              np.asarray(stated, np.int64))
    assert np.asarray(res.est).mean() > 0.4

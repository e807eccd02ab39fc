"""The dual-stack node, fast enough for tier-1 (tests/test_ipv6*.py are
`slow`): a seeded small cluster whose pods have a v4 and a v6 address, both
in every group they are members of, with v4 and v6 ipBlocks, tiers and K8s
isolation, through `TpuflowDatapath(dual_stack=True)`.

What is held:
  * three steps of mixed-family batches (a batch, the same again, a batch of
    new connections beside repeated ones) answer like the scalar twin
    `OracleDatapath(dual_stack=True)` lane for lane: verdict, both rule ids,
    `est`, `committed`, Service and DNAT port, the wide DNAT key, `n_miss`;
  * the wide keys are built when first read, not in `step`: the lists equal
    the per-lane construction they replace on mapped, v6 and masked-out
    lanes, are built once, and `step` itself runs no per-lane Python
    whatever share of the lanes is v6;
  * `v6_lanes` of the step record is the batch's `is6.sum()`, 0 on a narrow
    engine and on a dual-stack engine handed a v4-only batch; the seven
    phases still telescope; the wide engine's transfer counters are the
    narrow one's plus three uploads and two copies;
  * the narrow step program lowers to the same text whether or not the
    dual-stack path was ever built or a `v6_lanes` was recorded: the new
    scope is a name in wide programs only.
"""

import copy
import dataclasses
import ipaddress
import random

import numpy as np
import pytest

import jax.numpy as jnp

from antrea_tpu.apis import controlplane as cp
from antrea_tpu.datapath import OracleDatapath, TpuflowDatapath
from antrea_tpu.datapath.interface import StepResult, WideStepResult
from antrea_tpu.models import forwarding as fwd
from antrea_tpu.observability.tracing import STEP_PHASES, STEP_RECORD
from antrea_tpu.ops import match
from antrea_tpu.packet import PacketBatch
from antrea_tpu.simulator import gen_cluster, gen_services, gen_traffic
from antrea_tpu.utils import ip as iputil

B = 128
KW = dict(flow_slots=1 << 12, aff_slots=1 << 8, canary_probes=8,
          miss_chunk=B)  # one round a step: the twin's bookkeeping exactly
V6_SHARE = 0.4
STAMPS = ["t_start"] + [f"t_{p}" for p in STEP_PHASES] + ["t_done", "t_end"]


def _twin(ip: str) -> str:
    """A pod's v6 address: its node's /64 under fd00:10::/48."""
    a, b, c, d = (int(x) for x in ip.split("."))
    assert a == 10
    return str(ipaddress.IPv6Address(
        (0xFD00_0010 << 96) | ((b * 256 + c) << 64) | d))


def _block6(block: cp.IPBlock) -> cp.IPBlock:
    """A v4 ipBlock's v6 twin under 2001:db8::/32, 32 bits further down."""
    def move(cidr):
        ip, plen = cidr.split("/")
        return (str(ipaddress.IPv6Address(
            (0x2001_0DB8 << 96) | (iputil.ip_to_u32(ip) << 64)))
            + f"/{int(plen) + 32}")
    return cp.IPBlock(cidr=move(block.cidr),
                      excepts=tuple(move(x) for x in block.excepts))


def _dual_stack(ps, seed: int):
    """The policy set with both addresses of every pod in every group and a
    share of the ipBlock peers moved to v6 (a block has one family)."""
    ps = copy.deepcopy(ps)
    rng = random.Random(seed)
    for groups in (ps.address_groups, ps.applied_to_groups):
        for g in groups.values():
            g.members = g.members + [
                dataclasses.replace(m, ip=_twin(m.ip)) for m in g.members]
    for pol in ps.policies:
        for r in pol.rules:
            for peer in (r.from_peer, r.to_peer):
                if peer.ip_blocks and rng.random() < V6_SHARE:
                    peer.ip_blocks = [_block6(b) for b in peer.ip_blocks]
    return ps


def _mixed(batch: PacketBatch, service_ips, seed: int) -> PacketBatch:
    """A v4 batch with a share of its lanes moved to v6, both ends: a pod to
    its twin, an external to 2001:db8:<its 32 bits>::1.  Lanes to a ClusterIP
    stay v4 (the Services are SingleStack)."""
    rng = np.random.default_rng(seed)
    to6 = (rng.random(batch.size) < 0.55) & ~np.isin(batch.dst_ip,
                                                     service_ips)

    def words(col):
        out = np.zeros((batch.size, 4), np.uint32)
        for i in np.nonzero(to6)[0]:
            ip = iputil.u32_to_ip(int(col[i]))
            v = (int(ipaddress.IPv6Address(_twin(ip))) if ip.startswith("10.")
                 else (0x2001_0DB8 << 96) | (int(col[i]) << 64) | 1)
            out[i] = [(v >> s) & 0xFFFFFFFF for s in (96, 64, 32, 0)]
        return out

    return PacketBatch(
        src_ip=np.where(to6, 0, batch.src_ip).astype(np.uint32),
        dst_ip=np.where(to6, 0, batch.dst_ip).astype(np.uint32),
        proto=batch.proto, src_port=batch.src_port, dst_port=batch.dst_port,
        src_ip6=words(batch.src_ip), dst_ip6=words(batch.dst_ip),
        is6=to6.astype(np.int32))


@pytest.fixture(scope="module")
def world():
    """(policy set, services, [batch, batch, new + repeated])."""
    cluster = gen_cluster(240, n_nodes=4, pods_per_node=8, seed=11)
    services = gen_services(8, cluster.pod_ips, seed=2)
    vips = np.array([iputil.ip_to_u32(s.cluster_ip) for s in services],
                    np.uint32)
    ps = _dual_stack(cluster.ps, seed=5)
    first = _mixed(gen_traffic(cluster.pod_ips, B, n_flows=96, seed=3,
                               services=services), vips, seed=1)
    other = _mixed(gen_traffic(cluster.pod_ips, B, n_flows=96, seed=4,
                               services=services), vips, seed=2)
    half = {f.name: np.concatenate([getattr(first, f.name)[:B // 2],
                                    getattr(other, f.name)[:B // 2]])
            for f in dataclasses.fields(first)
            if getattr(first, f.name) is not None}
    return ps, services, [first, first, PacketBatch(**half)]


@pytest.fixture(scope="module")
def served(world):
    """(engine, [(StepResult, the twin's)] a step)."""
    ps, services, batches = world
    dp = TpuflowDatapath(ps, services, dual_stack=True, **KW)
    twin = OracleDatapath(ps, services, dual_stack=True, **{
        k: v for k, v in KW.items() if k != "miss_chunk"})
    return dp, [(dp.step(b, now=10 + i), twin.step(b, now=10 + i))
                for i, b in enumerate(batches)]


def test_the_world_has_both_families_everywhere(world):
    ps, _, batches = world
    for g in list(ps.address_groups.values()) + list(
            ps.applied_to_groups.values()):
        fams = [iputil.is_v6(m.ip) for m in g.members]
        assert sum(fams) * 2 == len(fams) > 0
    blocks = [b.cidr for pol in ps.policies for r in pol.rules
              for peer in (r.from_peer, r.to_peer) for b in peer.ip_blocks]
    n6 = sum(":" in c for c in blocks)
    assert 0 < n6 < len(blocks)
    assert {p.type for p in ps.policies} == {cp.NetworkPolicyType.K8S,
                                             cp.NetworkPolicyType.ACNP}
    assert len({p.tier_priority for p in ps.policies}) > 3
    for b in batches:
        assert 0.25 < b.is6.mean() < 0.6
        assert not b.src_ip[b.is6 != 0].any()
        assert not b.src_ip6[b.is6 == 0].any()


@pytest.mark.parametrize("field", [
    "code", "est", "committed", "svc_idx", "dnat_port", "reject_kind",
    "reply", "snat", "ingress_rule", "egress_rule", "dnat_key", "n_miss"])
def test_three_mixed_steps_answer_like_the_scalar_twin(served, world, field):
    _, steps = served
    for i, (res, want) in enumerate(steps):
        got, stated = getattr(res, field), getattr(want, field)
        if isinstance(stated, (list, int)):
            assert got == stated, (field, i)
        else:
            np.testing.assert_array_equal(np.asarray(got, np.int64),
                                          np.asarray(stated, np.int64),
                                          err_msg=f"{field} step {i}")
    first, again, third = (res for res, _ in steps)
    is6 = world[2][0].is6 != 0
    if field == "code":  # allowed and denied lanes of either family
        for fam in (is6, ~is6):
            assert {0, 1} <= set(np.asarray(first.code)[fam].tolist())
    if field == "est":  # a new flow misses, its repeat is established
        assert not np.asarray(first.est).any()
        assert np.asarray(again.est)[is6].any()
        assert np.asarray(again.est)[~is6].any()
        assert np.asarray(third.est)[:B // 2].sum() > np.asarray(
            third.est)[B // 2:].sum()
    if field == "n_miss":
        assert first.n_miss > again.n_miss and third.n_miss > again.n_miss
    if field == "svc_idx":  # Services answered on v4 lanes only
        assert (np.asarray(first.svc_idx)[~is6] >= 0).any()
        assert (np.asarray(first.svc_idx)[is6] == -1).all()
    if field in ("ingress_rule", "egress_rule"):
        named = np.array([r is not None for r in getattr(first, field)])
        assert named[is6].any() and named[~is6].any()


N_BLOCKS = 2400  # distinct v6 CIDRs of one peer: two boundaries each


def _block_policy(pods) -> tuple:
    """An ACNP over every pod, ahead of every other policy, whose one
    ingress rule drops N_BLOCKS v6 /64s (every second /64 under
    2001:db8:ff00::/40, so no two merge) -> (group, policy)."""
    both = [cp.GroupMember(ip=ip) for p in pods for ip in (p, _twin(p))]
    blocks = [cp.IPBlock(cidr=f"2001:db8:ff{i >> 7:02x}:{(i & 127) * 2:x}::/64")
              for i in range(N_BLOCKS)]
    return cp.AppliedToGroup("every-pod", both), cp.NetworkPolicy(
        uid="drop-v6-blocks", name="drop-v6-blocks",
        type=cp.NetworkPolicyType.ACNP, applied_to_groups=["every-pod"],
        tier_priority=50, priority=1.0, rules=[cp.NetworkPolicyRule(
            direction=cp.Direction.IN, action=cp.RuleAction.DROP, priority=0,
            from_peer=cp.NetworkPolicyPeer(ip_blocks=blocks))])


def _from_the_blocks(batch: PacketBatch, seed: int) -> PacketBatch:
    """The batch with the source of every second v6 lane moved into one of
    the policy's /64s or into the /64 after it, which no block holds."""
    rng = np.random.default_rng(seed)
    src6 = batch.src_ip6.copy()
    lanes = np.nonzero(batch.is6)[0][::2]
    i = rng.integers(0, N_BLOCKS, lanes.size)
    src6[lanes, 0] = 0x2001_0DB8
    src6[lanes, 1] = ((0xFF00 | (i >> 7)) << 16) | (
        (i & 127) * 2 + rng.integers(0, 2, lanes.size))
    src6[lanes, 2:] = rng.integers(0, 2**32, (lanes.size, 2))
    return dataclasses.replace(batch, src_ip6=src6.astype(np.uint32))


def test_a_peer_dimension_over_the_flat_size_serves_like_the_twin(world):
    """The v6 interval search in blocks, inside the step: a world whose
    ingress peer dimension holds more v6 boundaries than `_SS_FLAT`."""
    ps, services, batches = world
    ps = copy.deepcopy(ps)
    pods = sorted({m.ip for g in ps.applied_to_groups.values()
                   for m in g.members if not iputil.is_v6(m.ip)})
    group, policy = _block_policy(pods)
    ps.applied_to_groups[group.name] = group
    ps.policies.append(policy)
    dp = TpuflowDatapath(ps, services, dual_stack=True, **KW)
    n6 = dp._drs.ingress.peer.bounds6.shape[0]
    assert n6 > match._SS_FLAT >= dp._drs.egress.peer.bounds6.shape[0]
    twin = OracleDatapath(ps, services, dual_stack=True, **{
        k: v for k, v in KW.items() if k != "miss_chunk"})
    dropped = 0
    for i, b in enumerate(batches):
        b = _from_the_blocks(b, seed=i)
        res, want = dp.step(b, now=10 + i), twin.step(b, now=10 + i)
        assert res.n_miss == want.n_miss
        assert res.ingress_rule == want.ingress_rule
        assert res.egress_rule == want.egress_rule
        np.testing.assert_array_equal(np.asarray(res.code, np.int64),
                                      np.asarray(want.code, np.int64))
        dropped += sum(r is not None and r.startswith(policy.uid)
                       for r in res.ingress_rule)
    assert dropped > B // 8  # lanes inside a block, denied by its rule


def _per_lane_keys(wide_col, keep):
    """The construction the lazy lists replace, as `attribute` ran it."""
    out = []
    for row, k in zip(iputil.unflip_u32_array(wide_col).tolist(), keep):
        if not k:
            out.append(0)
        elif row[:3] == [0, 0, 0xFFFF]:
            out.append(row[3])
        else:
            out.append(iputil.V6_OFF + ((row[0] << 96) | (row[1] << 64)
                                        | (row[2] << 32) | row[3]))
    return out


def test_the_wide_keys_are_built_on_first_read_and_equal_the_per_lane_ones(
        served, world, monkeypatch):
    dp, steps = served
    calls = []
    real = iputil.words_to_keys
    monkeypatch.setattr(iputil, "words_to_keys",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    batch = world[2][2]
    res = dp.step(batch, now=20)
    assert type(res) is WideStepResult and isinstance(res, StepResult)
    assert calls == []  # `step` built no list
    assert "dnat_key" not in vars(res) and "peer_key" not in vars(res)
    dnat_w_f, peer_w_f, tunnel = res._wide
    assert dnat_w_f.shape == peer_w_f.shape == (B, 4)
    keys = res.dnat_key
    assert type(keys) is list and len(calls) == 1
    assert res.dnat_key is keys and len(calls) == 1  # built once, kept
    assert keys == _per_lane_keys(dnat_w_f, np.ones(B, bool))
    is6 = batch.is6 != 0
    assert all(k >= iputil.V6_OFF for k in np.array(keys, object)[is6])
    assert all(k < iputil.V6_OFF for k in np.array(keys, object)[~is6])
    assert res.dnat_words.dtype == np.uint32
    assert (res.dnat_words[~is6, 2] == 0xFFFF).all()  # v4-mapped rows
    # No topology is installed, so no lane is a deliverable tunnel lane:
    # every peer key is masked out, whatever the device left in the row.
    assert not tunnel.any()
    assert res.peer_key == [0] * B == _per_lane_keys(peer_w_f, tunnel)
    assert not res.peer_words.any()
    # the masked-in branch, on rows of both kinds
    rows = np.array([[0, 0, 0xFFFF, 0x0A000001], [0x20010DB8, 1, 2, 3],
                     [0, 0, 0, 0], [0xFFFFFFFF] * 4], np.uint32)
    keep = np.array([True, True, False, True])
    assert iputil.words_to_keys(rows, keep) == _per_lane_keys(
        iputil.flip_u32(rows), keep) == [
        0x0A000001, iputil.V6_OFF + ((0x20010DB8 << 96) | (1 << 64)
                                     | (2 << 32) | 3), 0,
        iputil.V6_OFF + (1 << 128) - 1]
    # A copy made by the dataclass's own `replace` keeps plain lists.
    twin = dataclasses.replace(res, code=np.asarray(res.code).copy())
    assert twin.dnat_key == keys and twin.peer_key == [0] * B


def test_a_narrow_engines_result_is_the_plain_dataclass(world):
    _, services, _ = world
    cluster = gen_cluster(240, n_nodes=4, pods_per_node=8, seed=11)
    dp = TpuflowDatapath(cluster.ps, services, **KW)
    res = dp.step(gen_traffic(cluster.pod_ips, B, n_flows=96, seed=3), now=1)
    assert type(res) is StepResult
    assert res.dnat_key is None and res.peer_key is None
    assert (dp.step_trace()["records"]["v6_lanes"] == 0).all()
    with pytest.raises(ValueError, match="dual_stack=True"):
        dp.step(world[2][0], now=2)


def test_the_step_record_counts_the_v6_lanes(served, world):
    dp, _ = served
    ps, services, batches = world
    rec = dp.step_trace()["records"]
    # v6_lanes, then the build ledger's two fields (PR 38)
    assert rec.dtype == STEP_RECORD and STEP_RECORD.names[-3:] == (
        "v6_lanes", "xla_builds", "xla_build_ns")
    want = [int(b.is6.sum()) for b in batches]
    assert rec["v6_lanes"][:3].tolist() == want and min(want) > 0
    assert (rec["lanes"] == B).all()
    # the seven phases still telescope to the span
    stamps = np.stack([rec[s] for s in STAMPS], axis=1)
    assert (np.diff(stamps, axis=1) >= 0).all()
    phases = np.diff(stamps[:, 1:-1], axis=1)
    self_ns = (rec["t_stage"] - rec["t_start"]) + (rec["t_end"]
                                                   - rec["t_done"])
    assert phases.shape[1] == 7
    assert (phases.sum(axis=1) + self_ns
            == rec["t_end"] - rec["t_start"]).all()
    # six columns, two scalars and the flags as a narrow engine, and the two
    # address blocks and the family mask: 28 -> 64 B a lane; the record's
    # three blocks and the two wide outputs: 50 -> 82 B a lane
    assert (rec["h2d_transfers"] == 12).all()
    assert (rec["h2d_bytes"] == B * (28 + 36) + 8).all()
    assert (rec["d2h_transfers"] == 5).all()
    assert (rec["d2h_bytes"] == B * (50 + 32) + 16).all()
    # a v4-only batch on the wide engine: the wide lanes are materialised
    # (the key layout is static) and none is counted
    v4 = batches[0]
    only4 = PacketBatch(**{f.name: getattr(v4, f.name)[v4.is6 == 0][:32]
                           for f in dataclasses.fields(v4)
                           if f.name in ("src_ip", "dst_ip", "proto",
                                         "src_port", "dst_port")})
    dp2 = TpuflowDatapath(ps, services, dual_stack=True, **KW)
    dp2.step(only4, now=1)
    last = dp2.step_trace()["records"][-1]
    assert last["v6_lanes"] == 0 and last["h2d_transfers"] == 12


def _narrow_text(dp):
    i32 = jnp.zeros(B, jnp.int32)
    return fwd.pipeline_step_full_packed.lower(
        dp._state, dp._drs, dp._dsvc, dp._dft, i32, i32, i32, i32, i32, i32,
        jnp.int32(1), jnp.int32(1), i32, None, None,
        meta=dp._meta_step).as_text()


def test_the_narrow_step_program_is_untouched(world, served):
    """The narrow engine's lowered step holds nothing of the wide path: the
    same text before and after a dual-stack engine of the same world was
    built and stepped in the process, no v6 interval search, and (with
    debug info) not the new scope's name, which a wide program carries."""
    _, services, _ = world
    cluster = gen_cluster(240, n_nodes=4, pods_per_node=8, seed=11)
    a = TpuflowDatapath(cluster.ps, services, **KW)
    before = _narrow_text(a)
    wide, _ = served
    b = TpuflowDatapath(cluster.ps, services, **KW)
    b.step(gen_traffic(cluster.pod_ips, B, n_flows=96, seed=3), now=1)
    assert _narrow_text(b) == before
    i32, w = jnp.zeros(B, jnp.int32), jnp.zeros((B, 4), jnp.int32)

    def text(dp, v6):
        return fwd.pipeline_step_full_packed.lower(
            dp._state, dp._drs, dp._dsvc, dp._dft, i32, i32, i32, i32, i32,
            i32, jnp.int32(1), jnp.int32(1), i32, None, None,
            meta=dp._meta_step, v6=v6).as_text(debug_info=True)

    assert "classify.index6" not in text(a, None)
    assert "classify.index6" in text(wide, (w, w, i32))

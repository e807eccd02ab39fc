"""The step tracer (observability/tracing.StepTracer): the `step` span, its
seven host phases and the transfer counters of every `Datapath.step` call,
on the single-chip engine and a 4-virtual-device MeshDatapath; the commit
stages readable without a realization span; the device program's scopes.

What is held:
  * the ten stamps of a record are monotonic and the phases telescope to
    the span exactly; step_hist is fed from the SAME clock pair;
  * the ring drops oldest and meters it; a raising `_step` leaves a closed
    record and still feeds step_hist;
  * h2d/d2h counters equal, by hand, what one batch shape uploads/fetches;
  * the mesh's sub-spans (`route` in `stage`, `retry` in `account`) lie
    inside their phases and out of the telescoping sum, its `spill_lanes` /
    `retry_lanes` are `mesh_stats()`'s deltas, and the one-chip engine
    records zeros for all of them;
  * the instrumented `_step` (hoisted staging, `block_until_ready`) answers
    and mutates state exactly like the dispatch it replaced, and its
    vectorised `attribute` phase (rule ids by table gather, masked wide
    keys) equals the per-lane scalar statement, types included;
  * `last_commit()` after a direct install_bundle telescopes to settle -
    start;
  * every STEP_SCOPES name is in the lowered step program, the spans land
    in a profiler trace, and no name is spelled outside the schema tuples.
"""

import ast
import glob
import os
import pathlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import antrea_tpu
from antrea_tpu.compiler.topology import FWD_TUNNEL, NodeRoute, Topology
from antrea_tpu.datapath import OracleDatapath, TpuflowDatapath
from antrea_tpu.datapath.tpuflow import _rid
from antrea_tpu.models import forwarding as fwd
from antrea_tpu.models import pipeline as pl
from antrea_tpu.observability import tracing
from antrea_tpu.observability.tracing import (STEP_PHASES, STEP_RECORD,
                                              STEP_SCOPES, STEP_SUBSPANS,
                                              StepTracer)
from antrea_tpu.packet import Packet, PacketBatch
from antrea_tpu.simulator import gen_cluster, gen_services, gen_traffic
from antrea_tpu.utils import ip as iputil

B = 256
KW = dict(flow_slots=1 << 10, aff_slots=1 << 8, canary_probes=8,
          miss_chunk=64)
STAMPS = ["t_start"] + [f"t_{p}" for p in STEP_PHASES] + ["t_done", "t_end"]
PKG = pathlib.Path(antrea_tpu.__file__).parent


@pytest.fixture(scope="module")
def world():
    cluster = gen_cluster(120, n_nodes=4, pods_per_node=8, seed=7)
    return (cluster, gen_services(8, cluster.pod_ips, seed=2),
            gen_traffic(cluster.pod_ips, B, n_flows=96, seed=3))


def _make(kind, world):
    cluster, services, _ = world
    if kind == "tpuflow":
        return TpuflowDatapath(cluster.ps, services, **KW)
    if len(jax.devices("cpu")) < 4:
        pytest.skip("needs 4 virtual CPU devices")
    from antrea_tpu.parallel import MeshDatapath

    return MeshDatapath(cluster.ps, services, n_data=2, n_rule=2,
                        devices=jax.devices("cpu")[:4], **KW)


@pytest.fixture(scope="module", params=["tpuflow", "mesh"])
def engine(request, world):
    """(kind, engine, results) after three steps of the same batch."""
    dp = _make(request.param, world)
    results = [dp.step(world[2], now=10 + i) for i in range(3)]
    return request.param, dp, results


# -- the records ---------------------------------------------------------------

def test_stamps_are_monotonic_and_phases_telescope(engine):
    _, dp, results = engine
    trace = dp.step_trace()
    rec = trace["records"]
    assert rec.dtype == STEP_RECORD and trace["dropped"] == 0
    assert rec["seq"].tolist() == [1, 2, 3]
    assert (rec["lanes"] == B).all()
    assert rec["n_miss"].tolist() == [r.n_miss for r in results]
    stamps = np.stack([rec[s] for s in STAMPS], axis=1)
    assert (np.diff(stamps, axis=1) >= 0).all()
    assert (rec["t_start"][1:] >= rec["t_end"][:-1]).all()
    # phase p runs from t_<p> to the next stamp: children of `step`, with
    # the span's self time before the first and after the last.
    phases = np.diff(stamps[:, 1:-1], axis=1)
    assert phases.shape[1] == len(STEP_PHASES) == 7
    self_ns = (rec["t_stage"] - rec["t_start"]) + (rec["t_end"]
                                                   - rec["t_done"])
    assert (phases.sum(axis=1) + self_ns
            == rec["t_end"] - rec["t_start"]).all()
    # The device did real work, and the host waited for it in `wait`.
    assert (phases > 0).all()


def test_step_hist_is_fed_from_the_span(engine):
    """One clock pair: the histogram's count and sum ARE the ring's."""
    _, dp, _ = engine
    rec = dp.step_trace()["records"]
    assert dp.step_hist.count == len(rec) == 3
    span_s = [(e - s) * 1e-9 for s, e in zip(rec["t_start"].tolist(),
                                             rec["t_end"].tolist())]
    assert dp.step_hist.sum == pytest.approx(sum(span_s), rel=1e-12)
    src = (PKG / "datapath" / "tpuflow.py").read_text()
    step = src[src.index("    def step(self"):src.index("    def _step(self")]
    assert "perf_counter" not in step and "tr.end()" in step


def test_transfer_counters_by_hand(engine):
    kind, dp, _ = engine
    rec = dp.step_trace()["records"][-1]
    if kind == "tpuflow":
        # src, dst, proto, sport, dport, in_port, flags (i32 columns) and
        # the two scalars now, gen; no ARP lane, no lens (FlowExporter
        # gate off), no v6, no padding mask.
        assert rec["h2d_transfers"] == 9
        assert rec["h2d_bytes"] == 7 * B * 4 + 2 * 4
    else:
        # The mesh walk always carries arp, lens and three bool masks
        # (valid, no_commit=spill, prune_exclude=spill).
        assert rec["h2d_transfers"] >= 14
        assert rec["h2d_bytes"] >= 9 * B * 4 + 3 * B + 2 * 4
    # The egress record, by hand from its schema: one copy a block — nine
    # i32 rows and fourteen i8 rows of B lanes, four i32 scalars (one
    # column a replica on the mesh) — and nothing beside it, since no
    # option with an output of its own is on (tests/test_egress_record.py
    # holds the program's shapes).
    rows = {b: sum(1 for _, blk, *_ in fwd.EGRESS_RECORD if blk == b)
            for b in ("words", "narrow", "scalars")}
    assert rows == {"words": 9, "narrow": 14, "scalars": 4}
    replicas = 1 if kind == "tpuflow" else 2
    lane_bytes, retried = 9 * 4 + 14 * 1, int(rec["retry_lanes"])
    once = lane_bytes * B + 4 * replicas * 4
    if not retried:
        assert (rec["d2h_transfers"], rec["d2h_bytes"]) == (3, once)
    else:  # the mesh's spill retry fetches a second record: a power-of-
        # two rung of lanes a replica, wide enough for what spilled
        assert kind == "mesh" and rec["d2h_transfers"] == 6
        rung, rest = divmod(int(rec["d2h_bytes"]) - once - 4 * replicas * 4,
                            lane_bytes * replicas)
        assert rest == 0 and rung & (rung - 1) == 0
        assert retried <= rung * replicas <= B


def test_sub_spans_are_zero_on_one_chip(engine):
    """`route` and `retry` are the mesh's work: the one-chip engine never
    opens them and places no lane off-home, so its records hold zeros."""
    kind, dp, _ = engine
    rec = dp.step_trace()["records"]
    assert [f"{s}_{e}" for s, _ in STEP_SUBSPANS for e in ("t0", "t1")] + [
        "spill_lanes", "retry_lanes", "v6_lanes"] == list(
            STEP_RECORD.names[-9:-2])
    assert STEP_RECORD.names[-2:] == ("xla_builds", "xla_build_ns")
    assert {p for _, p in STEP_SUBSPANS} <= set(STEP_PHASES)
    if kind == "tpuflow":
        for f in STEP_RECORD.names[-9:-2]:  # a narrow engine: no v6 lane
            assert (rec[f] == 0).all(), f
    else:  # the mesh routes every step; it retries only what spilled
        assert (rec["route_t1"] > rec["route_t0"]).all()
        assert (rec["retry_lanes"] == rec["spill_lanes"]).all()
        assert ((rec["retry_t1"] > rec["retry_t0"])
                == (rec["spill_lanes"] > 0)).all()


def test_mesh_sub_spans_lie_inside_their_phases(world):
    """A batch that all homes to replica 0 of 2: half its lanes spill, and
    the retry re-serves them.  `route` lies inside `stage`, `retry` inside
    `account`, the seven phases still telescope to the span, and the two
    counters are `mesh_stats()`'s deltas, step by step."""
    from antrea_tpu.parallel import mesh as pm

    dp = _make("mesh", world)
    b = world[2]
    shard = pm.shard_of_tuples(b.src_ip, b.dst_ip, b.proto, b.src_port,
                               b.dst_port, 2)
    idx = np.nonzero(shard == 0)[0][:64]
    assert idx.size == 64
    skew = PacketBatch(**{f: getattr(b, f)[idx] for f in (
        "src_ip", "dst_ip", "proto", "src_port", "dst_port")})
    before = dp.mesh_stats()
    totals = []
    for t in range(3):
        dp.step(skew, now=30 + t)
        s = dp.mesh_stats()
        totals.append((s["spill_lanes_total"], s["spill_retried_total"]))
    rec = dp.step_trace()["records"]
    assert (rec["spill_lanes"] == 32).all()  # 64 lanes, 32 a home slice
    start = (before["spill_lanes_total"], before["spill_retried_total"])
    deltas = np.diff(np.array([start] + totals), axis=0)
    assert deltas[:, 0].tolist() == rec["spill_lanes"].tolist()
    assert deltas[:, 1].tolist() == rec["retry_lanes"].tolist()
    assert (rec["t_stage"] <= rec["route_t0"]).all()
    assert (rec["route_t0"] < rec["route_t1"]).all()
    assert (rec["route_t1"] <= rec["t_upload"]).all()
    assert (rec["t_account"] <= rec["retry_t0"]).all()
    assert (rec["retry_t0"] < rec["retry_t1"]).all()
    assert (rec["retry_t1"] <= rec["t_attribute"]).all()
    # the sub-spans are not phases: the seven still fill the span
    stamps = np.stack([rec[s] for s in STAMPS], axis=1)
    assert (np.diff(stamps, axis=1) >= 0).all()
    assert (np.diff(stamps[:, 1:-1], axis=1).sum(axis=1)
            == rec["t_done"] - rec["t_stage"]).all()
    # the retry's transfers are the step's: two dispatches' worth
    assert (rec["d2h_transfers"] % 2 == 0).all()


@pytest.mark.parametrize("kind", ["tpuflow", "mesh"])
def test_round_lanes_are_the_widths_of_the_rounds_that_ran(kind, world):
    """`round_lanes` of a step record is the program's own count: for every
    sharded call (one chip: the one call) and every replica, full rounds
    of `miss_chunk` lanes and then the narrowest rung of the ladder that
    holds what is left — summed over the replicas, and over the step and
    its spill retry on the mesh."""
    from antrea_tpu.parallel import mesh as pm

    dp = _make(kind, world)
    b = world[2]
    blocks = []  # the (4, replicas) scalar block of every sharded call
    if kind == "mesh":
        fold = dp._account_counts
        dp._account_counts = lambda c: (blocks.append(np.array(c)), fold(c))[1]
        shard = pm.shard_of_tuples(b.src_ip, b.dst_ip, b.proto, b.src_port,
                                   b.dst_port, 2)
        idx = np.nonzero(shard == 0)[0][:64]  # all home to one replica
        skew = PacketBatch(**{f: getattr(b, f)[idx] for f in (
            "src_ip", "dst_ip", "proto", "src_port", "dst_port")})
        batches = [b, skew, b]
    else:
        batches = [b, b]
    results = [dp.step(x, now=50 + t) for t, x in enumerate(batches)]
    rec = dp.step_trace()["records"]
    assert "round_lanes" in rec.dtype.names
    assert rec["n_miss"].tolist() == [r.n_miss for r in results]
    M = KW["miss_chunk"]

    def plan(n_miss, lanes):
        ladder = pl.round_ladder(M, lanes)
        full, rest = divmod(n_miss, M)
        return full * M + (min(w for w in ladder if w >= rest) if rest else 0)

    if kind == "tpuflow":
        assert rec["round_lanes"].tolist() == [plan(n, B) for n in
                                               rec["n_miss"].tolist()]
        assert rec["round_lanes"][0] >= M  # a cold batch: whole rounds
    else:
        calls = len(rec) + int((rec["retry_lanes"] > 0).sum())
        assert len(blocks) == calls > len(rec)  # the skewed step retried
        scalars = dict(zip(fwd.EGRESS_SCALARS, np.stack(blocks, axis=1)))
        assert scalars["round_lanes"].shape == (calls, 2)
        for n, got in zip(scalars["n_miss"].ravel().tolist(),
                          scalars["round_lanes"].ravel().tolist()):
            assert n <= got == -(-n // M) * M  # one rung: narrower than 128
        assert rec["round_lanes"].sum() == scalars["round_lanes"].sum() > 0


def test_a_raise_inside_a_sub_span_closes_it():
    tr = StepTracer()
    tr.begin(lanes=4)
    tr.phase(0)
    tr.sub(0)
    assert tr.end() >= 0  # what `step` does when `_step` raised
    (rec,) = tr.records()
    assert rec["t_stage"] <= rec["route_t0"] <= rec["route_t1"] <= rec["t_end"]
    tr.begin(lanes=4)  # the next record starts from zeros
    tr.end()
    assert tr.records()["route_t1"].tolist()[1] == 0


def test_the_ring_drops_oldest_and_meters_it(world, monkeypatch):
    monkeypatch.setattr(tracing, "STEP_RING_SLOTS", 4)
    tr = StepTracer()
    assert len(tr.records()) == 0
    for i in range(6):
        tr.begin(lanes=i)
        for k in range(len(STEP_PHASES) + 1):
            tr.phase(k)
        tr.uploaded(np.zeros(3, np.int32))
        assert tr.end() >= 0
    rec = tr.records()
    assert rec["seq"].tolist() == [3, 4, 5, 6] and tr.dropped == 2
    assert rec["lanes"].tolist() == [2, 3, 4, 5]
    assert (rec["h2d_bytes"] == 12).all() and (rec["d2h_bytes"] == 0).all()
    # On an engine: the same ring behind step_trace().
    dp = _make("tpuflow", world)
    for i in range(6):
        dp.step(world[2], now=20 + i)
    trace = dp.step_trace()
    assert trace["dropped"] == 2
    assert trace["records"]["seq"].tolist() == [3, 4, 5, 6]


@pytest.mark.parametrize("kind", ["tpuflow", "mesh"])
def test_a_raising_step_closes_its_span(kind, world):
    dp = _make(kind, world)
    batch = world[2]
    if kind == "tpuflow":  # a v6 lane on a v4-only engine: raises in `stage`
        bad = PacketBatch(
            src_ip=batch.src_ip, dst_ip=batch.dst_ip, proto=batch.proto,
            src_port=batch.src_port, dst_port=batch.dst_port,
            src_ip6=np.zeros((B, 4), np.uint32),
            dst_ip6=np.zeros((B, 4), np.uint32), is6=np.ones(B, np.int32))
    else:  # 3 lanes over 2 replicas
        bad = PacketBatch(**{f: getattr(batch, f)[:3] for f in (
            "src_ip", "dst_ip", "proto", "src_port", "dst_port")})
    with pytest.raises(ValueError):
        dp.step(bad, now=5)
    rec = dp.step_trace()["records"]
    assert len(rec) == 1 and dp.step_hist.count == 1
    stamps = [int(rec[s][0]) for s in STAMPS]
    assert stamps == sorted(stamps) and stamps[-1] > stamps[0]
    # The unreached boundaries lie on the end stamp: zero-width phases.
    assert stamps[2:] == [stamps[-1]] * (len(STAMPS) - 2)
    assert dp.step_hist.sum == pytest.approx(
        (stamps[-1] - stamps[0]) * 1e-9, rel=1e-12)
    # ... and the next step records normally.
    dp.step(batch, now=6)
    rec = dp.step_trace()["records"]
    assert rec["seq"].tolist() == [1, 2] and rec["n_miss"][1] > 0


def _parent_step(dp, batch, now):
    """The dispatch as it stood before the tracer: every upload an argument
    expression of the call, no `block_until_ready`, a bare fetch."""
    state, out = fwd.pipeline_step_full(
        dp._state, dp._drs, dp._dsvc, dp._dft,
        jnp.asarray(iputil.flip_u32(batch.src_ip)),
        jnp.asarray(iputil.flip_u32(batch.dst_ip)),
        jnp.asarray(batch.proto.astype(np.int32)),
        jnp.asarray(batch.src_port.astype(np.int32)),
        jnp.asarray(batch.dst_port.astype(np.int32)),
        jnp.asarray(batch.in_ports()),
        jnp.int32(now), jnp.int32(dp._gen),
        jnp.asarray(batch.flags()),
        jnp.asarray(batch.arp_ops()) if batch.arp_op is not None else None,
        jnp.asarray(np.maximum(batch.lens(), 0)) if dp._flow_stats else None,
        meta=dp._meta_step, v6=dp._v6_lanes(batch), valid=None)
    dp._state = state
    return {k: np.asarray(v) for k, v in out.items()}


def _scalar_attribution(dp, o):
    """The `attribute` phase lane by lane, as it was written before the
    table gather: `_rid` for the rule ids; for a dual-stack engine the wide
    keys (a v4-mapped row IS its word 3, anything else the 128-bit value
    past V6_OFF) and the peer key only on deliverable tunnel lanes."""
    def key(row):
        w = [int(x) for x in iputil.unflip_u32_array(row)]
        if w[:3] == [0, 0, 0xFFFF]:
            return w[3]
        return iputil.V6_OFF + ((w[0] << 96) | (w[1] << 64) | (w[2] << 32)
                                | w[3])

    want = {
        "ingress_rule": [_rid(dp._cps.ingress.rule_ids, int(i))
                         for i in o["ingress_rule"]],
        "egress_rule": [_rid(dp._cps.egress.rule_ids, int(i))
                        for i in o["egress_rule"]],
        "dnat_key": None, "peer_key": None,
    }
    if dp._dual_stack:
        want["dnat_key"] = [key(row) for row in o["dnat_w_f"]]
        want["peer_key"] = [
            key(row) if (kind == FWD_TUNNEL and port != -1) else 0
            for row, kind, port in zip(o["peer_w"], o["fwd_kind"],
                                       o["out_port"])]
    return want


def _assert_attribution(res, want):
    for field, col in want.items():
        got = getattr(res, field)
        if col is None:
            assert got is None, field
            continue
        assert type(got) is list and got == col, field
        assert [type(g) for g in got] == [type(w) for w in col], field


@pytest.mark.parametrize("dual_stack", [False, True])
def test_answers_and_state_equal_the_parents_path(world, dual_stack):
    cluster, services, batch = world
    kw = dict(KW, dual_stack=dual_stack)
    new = TpuflowDatapath(cluster.ps, services, **kw)
    old = TpuflowDatapath(cluster.ps, services, **kw)
    fresh = gen_traffic(cluster.pod_ips, B, n_flows=64, seed=11)
    for now, b in ((10, batch), (11, batch), (12, fresh), (13, batch)):
        res = new.step(b, now=now)
        o = _parent_step(old, b, now)
        for field, key in (("code", "code"), ("est", "est"),
                           ("committed", "committed"), ("reply", "reply"),
                           ("svc_idx", "svc_idx"), ("dnat_port", "dnat_port"),
                           ("snat", "snat"), ("fwd_kind", "fwd_kind"),
                           ("out_port", "out_port"), ("spoofed", "spoofed")):
            assert (getattr(res, field) == o[key]).all(), field
        assert (res.dnat_ip == iputil.unflip_u32_array(o["dnat_ip_f"])).all()
        assert res.n_miss == int(o["n_miss"])
        _assert_attribution(res, _scalar_attribution(new, o))
        for a, b_ in zip(jax.tree_util.tree_leaves(new._state),
                         jax.tree_util.tree_leaves(old._state)):
            assert (np.asarray(a) == np.asarray(b_)).all()
    assert any(r > 0 for r in new.step_trace()["records"]["n_miss"])


def test_wide_keys_equal_the_scalar_statement():
    """The dual-stack columns on lanes that exercise every branch of them:
    v6 and v4 across the tunnel, over a v4 underlay (a mapped peer key)
    and a v6 one (a wide peer key), v6 and v4 delivered locally, to the
    gateway, dropped."""
    node1, node2, pod_a4, pod_a6, pod_b6 = (
        "192.168.1.2", "fd00:aa::2", "10.10.0.5", "fd00:10::5", "fd00:10::6")
    topo = Topology(
        node_name="n0", gateway_ip="10.10.0.1", gateway_ip6="fd00:10::1",
        pod_cidr="10.10.0.0/24", pod_cidr6="fd00:10:0:0::/64",
        local_pods=[(pod_a4, 3), (pod_a6, 3), (pod_b6, 4)],
        remote_nodes=[NodeRoute("n1", node1, "10.10.1.0/24"),
                      NodeRoute("n1", node1, "fd00:10:0:1::/64"),
                      NodeRoute("n2", node2, "fd00:10:0:2::/64")])
    kw = dict(flow_slots=1 << 10, aff_slots=1 << 6, topology=topo,
              node_ips=[node1, "fd00:10::1"], dual_stack=True, miss_chunk=16)
    new, old = TpuflowDatapath(**kw), TpuflowDatapath(**kw)
    lanes = [(pod_a6, "fd00:10:0:1::9", 3), (pod_a4, "10.10.1.7", 3),
             (pod_a6, "fd00:10:0:2::9", 3),
             (pod_a6, pod_b6, 3), ("fd00:10:0:1::9", pod_a6, 1),
             (pod_a6, "fd00:99::1", 3), (pod_a4, "8.8.8.8", 3),
             (pod_a6, "fd00:10::77", 3), ("fd00:bad::1", pod_b6, 3)]
    batch = PacketBatch.from_packets([
        Packet(src_ip=iputil.ip_to_key(s), dst_ip=iputil.ip_to_key(d),
               proto=6, src_port=40000 + i, dst_port=80)
        for i, (s, d, _) in enumerate(lanes)])
    batch.in_port = np.asarray([p for *_, p in lanes], np.int32)
    for now in (1, 2):
        res = new.step(batch, now=now)
        want = _scalar_attribution(new, _parent_step(old, batch, now))
        _assert_attribution(res, want)
    tunnel = res.fwd_kind == FWD_TUNNEL
    assert tunnel[:3].all() and not tunnel[3:].any()
    assert res.peer_key[:4] == [iputil.ip_to_key(node1)] * 2 + [
        iputil.ip_to_key(node2), 0]
    assert res.dnat_key[0] == iputil.ip_to_key("fd00:10:0:1::9")  # a wide key
    assert res.dnat_key[1] == iputil.ip_to_key("10.10.1.7")


# -- the commit stages ---------------------------------------------------------

def test_last_commit_after_a_direct_install(world):
    cluster, services, _ = world
    dp = TpuflowDatapath(**KW)
    tracer = dp.realization_tracer
    assert tracer.last_commit() is None  # booting is no transaction
    gen = dp.install_bundle(cluster.ps, services)
    last = tracer.last_commit()
    assert last.pop("generation") == gen == dp.generation
    # the sub-spans and counter (COMMIT_SUBSPANS, PR 36 / 38), the
    # constructor's span and the builds are in no telescoping sum
    upload_s, table_bytes = last.pop("upload_s"), last.pop("table_bytes")
    assert 0 < upload_s <= last["compile_s"] and table_bytes > 0
    subs = {f"{name}_s" for name, _ in tracing.COMMIT_SUBSPANS}
    assert all(last.pop(k) >= 0 for k in subs - {"upload_s"})
    assert last.pop("construct_s") > 0 and isinstance(last.pop("builds"),
                                                      dict)
    assert set(last) == {"compile_s", "canary_s", "swap_s", "settle_s"}
    assert all(v >= 0 for v in last.values())
    assert last["compile_s"] > 0 and last["canary_s"] > 0
    _, stamps = tracer._last_commit
    assert sum(last.values()) == pytest.approx(
        stamps["settle"] - stamps["start"], rel=1e-12)
    # No realization span was opened: the stages are readable anyway.
    assert tracer.spans() == []
    dp.install_bundle(None, services[:4])
    assert tracer.last_commit()["generation"] == dp.generation == gen + 1
    assert TpuflowDatapath(realization_slots=0, **KW).realization_tracer \
        is None


def _install_engine(kind):
    if kind == "tpuflow":
        return TpuflowDatapath(**KW)
    from antrea_tpu.parallel import MeshDatapath

    return MeshDatapath(n_data=2, n_rule=1, devices=jax.devices("cpu")[:2],
                        **KW)


@pytest.mark.parametrize("kind", ["tpuflow", "mesh"])
def test_the_install_sub_spans_lie_inside_their_stages(kind, world):
    """COMMIT_SUBSPANS on a small install, on one chip and on the 2-device
    CPU mesh: a stage's sub-spans sum to at most the stage, each is > 0,
    and the builds each caused are builds of its stage."""
    cluster, services, _ = world
    dp = _install_engine(kind)
    dp.install_bundle(cluster.ps, services)
    last = dp.realization_tracer.last_commit()
    sub = {name: last[f"{name}_s"] for name, _ in tracing.COMMIT_SUBSPANS}
    assert min(sub.values()) > 0
    assert sub["rules"] + sub["tables"] + sub["upload"] <= last["compile_s"]
    assert sub["oracle"] + sub["walk"] <= last["canary_s"]
    assert last["construct_s"] > 0 and last["table_bytes"] > 0
    builds = last["builds"]
    assert set(builds) == {"construct", "compile", "canary", "swap",
                           "settle", *sub}
    assert (builds["rules"][0] + builds["tables"][0] + builds["upload"][0]
            <= builds["compile"][0])
    assert builds["oracle"][0] + builds["walk"][0] <= builds["canary"][0]
    # the walk's builds are rows of the ledger filed under the walk
    rows = dp.build_trace()["records"]
    assert (rows["span"] == "commit.canary.walk").sum() == builds["walk"][0]


def test_the_digest_is_a_span_after_settle(world):
    """The audit plane's golden digests run after the settle stamp: a span
    of the transaction's own, which moves no stage stamp."""
    cluster, services, _ = world
    dp = TpuflowDatapath(**KW)
    tracer = dp.realization_tracer
    calls = []
    refresh = dp._audit_refresh_golden

    def spy():
        calls.append(tracer._open_commit is not None)
        refresh()

    dp._audit_refresh_golden = spy
    dp.install_bundle(cluster.ps, services)
    last = tracer.last_commit()
    assert calls == [True] and last["digest_s"] > 0
    _, stamps = tracer._last_commit
    assert stamps["settle"] - stamps["start"] == pytest.approx(
        sum(last[f"{s}_s"] for s in ("compile", "canary", "swap", "settle")),
        rel=1e-12)
    # the oracle engine has no audit plane: its digest span is empty work
    twin = OracleDatapath(canary_probes=8)
    twin.install_bundle(cluster.ps, services)
    assert 0 <= twin.realization_tracer.last_commit()["digest_s"] < \
        last["digest_s"]


def test_a_delta_that_compiles_no_rule_records_zeros(world):
    """An appended delta compiles no rule, builds no table and uploads
    nothing: zeros, while its canary's sub-spans run; a no-op delta settles
    no commit, so the last one stands."""
    cluster, services, _ = world
    dp = TpuflowDatapath(cluster.ps, services, **KW)
    ag = sorted(cluster.ps.address_groups)[0]
    victim = cluster.ps.address_groups[ag].members[0].ip
    gen = dp.apply_group_delta(ag, added_ips=["10.9.9.9"],
                               removed_ips=[victim])
    assert dp._n_deltas > 0  # appended, not recompiled
    last = dp.realization_tracer.last_commit()
    assert last["generation"] == gen
    assert (last["rules_s"], last["tables_s"], last["upload_s"],
            last["table_bytes"]) == (0.0, 0.0, 0.0, 0)
    assert last["oracle_s"] > 0 and last["walk_s"] > 0
    assert dp.apply_group_delta(ag, added_ips=["10.9.9.9"],
                                removed_ips=[]) == gen  # a refcount only
    assert dp.realization_tracer.last_commit() == last


# -- the device scopes and the host annotations --------------------------------

def _lowered_text(dp):
    i32 = jnp.zeros(B, jnp.int32)
    wide = jnp.zeros((B, 4), jnp.int32)
    return fwd.pipeline_step_full_packed.lower(
        dp._state, dp._drs, dp._dsvc, dp._dft, i32, i32, i32, i32, i32, i32,
        jnp.int32(1), jnp.int32(1), i32, None, None, meta=dp._meta_step,
        v6=(wide, wide, i32) if dp._dual_stack else None,
    ).as_text(debug_info=True)


def _mesh_lowered_text(dp):
    """The sharded step as `MeshDatapath._step` calls it, lowered only."""
    from antrea_tpu.parallel import meshpath

    i32, yes = np.zeros(B, np.int32), np.ones(B, bool)
    stepf = meshpath._mesh_step_full_fn(dp._mesh, dp._meta_step, False)
    dsvc, dft = dp._shared_tables()
    return stepf.lower(
        dp._state, dp._drs, dsvc, dft, i32, i32, i32, i32, i32, i32,
        jnp.int32(1), jnp.int32(1), i32, i32, yes, ~yes, i32,
        ~yes).as_text(debug_info=True)


def _drain_lowered_text(dp):
    """The async engine's coalesced drain as `_drain_classify` calls it."""
    D = dp._slowpath.drain_batch
    i32, yes = jnp.zeros(D, jnp.int32), jnp.ones(D, bool)
    return pl.pipeline_step.lower(
        dp._state, dp._drs, dp._dsvc, i32, i32, i32, i32, i32, jnp.int32(1),
        jnp.int32(1), meta=dp._drain_meta(D), valid=yes, no_commit=~yes,
        flags=i32, lens=None).as_text(debug_info=True)


SLOW_PATH = ("miss_detect", "service_lb", "classify", "classify.candidate",
             "classify.scan", "cache_commit", "eviction_scan")
FULL_STEP = ("forwarding", "egress")  # models/forwarding's, not the drain's
# selection -> (engine kind, knobs, lowering, scopes its program leaves out)
V4 = ("classify.index6",)  # the v6 interval search: dual-stack engines only
SELECTIONS = {
    "default": ("tpuflow", {}, _lowered_text, V4 + ("classify.summary",)),
    "dual_stack": ("tpuflow", {"dual_stack": True}, _lowered_text,
                   ("classify.summary",)),
    "fused": ("tpuflow", {"fused": True}, _lowered_text,
              V4 + ("classify.summary",)),
    "prune_budget": ("tpuflow", {"prune_budget": 2}, _lowered_text, V4),
    "fused+prune_budget": ("tpuflow", {"fused": True, "prune_budget": 2},
                           _lowered_text, V4),
    "async_fast_step": ("tpuflow", {"async_slowpath": True,
                                    "drain_batch": 64}, _lowered_text,
                        V4 + SLOW_PATH + ("classify.summary",)),
    "async_drain": ("tpuflow", {"async_slowpath": True, "drain_batch": 64},
                    _drain_lowered_text,
                    V4 + FULL_STEP + ("classify.summary",)),
    "mesh_step": ("mesh", {}, _mesh_lowered_text,
                  V4 + ("classify.summary",)),
}
KERNEL_OF = {"fused": "classify_consumer",
             "fused+prune_budget": "classify_pruned_consumer"}


@pytest.mark.parametrize("selection", list(SELECTIONS))
def test_the_lowered_step_names_every_scope(world, selection):
    """Catches a selection whose program gains or loses a layer: exactly
    the STEP_SCOPES listed lower, nested as tracing.py says; the async fast
    step holds no slow path at all, and no selection holds a kernel but
    its own (`classify_onepass` is gone for good)."""
    cluster, services, _ = world
    kind, knobs, lower, absent = SELECTIONS[selection]
    if kind == "tpuflow":
        dp = TpuflowDatapath(cluster.ps, services, **KW, **knobs)
    else:
        dp = _make("mesh", world)
    text = lower(dp)
    held = [s for s in STEP_SCOPES
            if re.search(rf'[/"]{re.escape(s)}[/"]', text)]
    assert held == [s for s in STEP_SCOPES if s not in absent]
    kernels = set(re.findall(
        r"classify_(?:pruned_)?consumer|classify_onepass", text))
    assert kernels == ({KERNEL_OF[selection]} if selection in KERNEL_OF
                       else set())
    assert "fast_path/probe" in text and "fast_path/refresh" in text
    if selection == "async_fast_step":
        assert not re.search(r"while/body/(service_lb|classify)", text)
        return
    # The round loop's scopes inside miss_detect, the eviction scan
    # inside the commit.
    assert re.search(r"miss_detect/.*while/body/service_lb", text)
    assert re.search(r"miss_detect/.*classify/classify\.scan", text)
    assert re.search(r"miss_detect/.*cache_commit/eviction_scan", text)


def test_an_unknown_scope_is_refused():
    with pytest.raises(ValueError):
        tracing.device_scope("fastpath")


def test_the_names_are_spelled_in_the_schema_only():
    """Device scopes enter through `ops/scopes.device_scope` with a
    STEP_SCOPES literal, host spans through StepTracer; nobody else names
    one, and the kernel layer does not import the observability plane."""
    used = set()
    for path in glob.glob(str(PKG / "**" / "*.py"), recursive=True):
        rel = os.path.relpath(path, PKG)
        if rel.startswith("analysis" + os.sep):
            continue
        src = open(path).read()
        if rel != os.path.join("ops", "scopes.py"):
            assert "named_scope" not in src, rel
        if rel != os.path.join("observability", "tracing.py"):
            assert "TraceAnnotation" not in src, rel
            assert "tpuflow.step" not in src, rel
        if rel.split(os.sep)[0] in ("ops", "models"):
            assert not re.search(r"^\s*(from|import) .*observability", src,
                                 re.M), rel
        for node in ast.walk(ast.parse(src)):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "id", None) == "device_scope"):
                (arg,) = node.args
                assert isinstance(arg, ast.Constant), rel
                used.add(arg.value)
    assert used == set(STEP_SCOPES)
    assert len(set(STEP_SCOPES)) == len(STEP_SCOPES)
    names = [n for n in STEP_RECORD.names if n.startswith("t_")]
    assert names == STAMPS
    # The two Pallas consumers carry stable kernel names.
    match = (PKG / "ops" / "match.py").read_text()
    assert match.count("pl.pallas_call(") == len(
        re.findall(r'name="classify_\w+"', match)) == 2


def test_the_spans_land_in_a_profiler_trace(world, tmp_path):
    dp = _make("tpuflow", world)
    dp.step(world[2], now=1)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        dp.step(world[2], now=2)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    spans = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tpuflow.step"):
                    spans[ev.name] = (ev.start_ns, ev.start_ns
                                      + ev.duration_ns, dict(ev.stats))
    assert set(spans) == {"tpuflow.step"} | {
        f"tpuflow.step.{p}" for p in STEP_PHASES}
    start, end, stats = spans.pop("tpuflow.step")
    assert int(stats["seq"]) == 2
    assert all(start <= s and e <= end for s, e, _ in spans.values())

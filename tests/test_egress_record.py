"""The served step's egress record (models/forwarding.EGRESS_RECORD).

What is held here:
  * pack_egress -> unpack_egress gives every field back by value, at its
    extremes: -1 in the index fields, a rule table's last row, flipped
    addresses with the top bit set, port 65535, every enum constant;
  * the schema: rows contiguous a block, no field twice, and every
    ACT_* / FWD_* / TC_* / REJECT_* constant fits the width its field was
    given — a new enum value the cast would wrap fails HERE;
  * what rides beside the record is exactly the outputs of the options
    that make them (dual-stack's wide columns, the prune and telemetry
    counters), and nothing of the default engine's;
  * both engines: every StepResult field equals, lane for lane and by
    value, what the unpacked dict path (`pipeline_step_full`, one fetch an
    output, the attribution as `_step` wrote it before the record) gives
    and what the scalar twin states — one chip, and the four-replica mesh
    under a Zipf head that overflows one home slice, so the un-permute and
    the retry's merge run on the packed blocks;
  * the transfer counters of a retried mesh step, by hand from the schema.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from antrea_tpu.compiler import compile as cc
from antrea_tpu.compiler import topology as topo_mod
from antrea_tpu.compiler.topology import (FWD_TUNNEL, OFPORT_TUNNEL,
                                          NodeRoute, Topology)
from antrea_tpu.datapath import OracleDatapath, TpuflowDatapath
from antrea_tpu.datapath.interface import StepResult
from antrea_tpu.datapath.tpuflow import _rids
from antrea_tpu.models import forwarding as fwd
from antrea_tpu.models import pipeline as pl
from antrea_tpu.packet import PacketBatch
from antrea_tpu.simulator import gen_cluster, gen_services, gen_traffic
from antrea_tpu.utils import ip as iputil

FIELDS = [f for f, *_ in fwd.EGRESS_RECORD]
SCHEMA = {f: (block, row, bits, signed)
          for f, block, row, bits, signed in fwd.EGRESS_RECORD}
ENUMS = {  # field -> (module, prefix of its constants)
    "code": (cc, "ACT_"), "fwd_kind": (topo_mod, "FWD_"),
    "tc_act": (topo_mod, "TC_"), "reject_kind": (pl, "REJECT_"),
}
LANES = 64


def _constants(field):
    mod, prefix = ENUMS[field]
    return {n: v for n, v in vars(mod).items()
            if n.startswith(prefix) and isinstance(v, int)}


def _fits(value, bits, signed):
    lo, hi = ((-(1 << (bits - 1)), (1 << (bits - 1)) - 1) if signed
              else (0, (1 << bits) - 1))
    return lo <= value <= hi


def _extremes(field):
    block, _row, bits, _signed = SCHEMA[field]
    if block == "scalars":
        return [0, 1, 131072, 2**31 - 1]
    if block == "narrow":  # the flags' two values, every enum constant,
        consts = sorted(_constants(field).values()) if field in ENUMS else []
        return [0, 1, *consts, 2**(bits - 1) - 1]  # the block's own limit
    top_bit = [-2**31, -1, 2**31 - 1, int(iputil.flip_u32(
        np.array([0xFFFFFFFF, 0x80000000, 0], np.uint32))[1])]
    return {
        "svc_idx": [-1, 0, 4999], "mcast_idx": [-1, 0, 2**31 - 1],
        "out_port": [-1, topo_mod.OFPORT_REPLICATE, 65535],
        "dnat_ip_f": top_bit, "peer_f": top_bit,
        "dnat_port": [0, 1, 65535], "tc_port": [0, 65535, 2**30 - 1],
        # no rule, the first, the last row of a 100,000-rule direction
        "ingress_rule": [-1, 0, 99_999], "egress_rule": [-1, 0, 99_999],
    }[field]


@pytest.fixture(scope="module")
def round_trip():
    """Every field at once: lanes 0.. hold its extremes, the rest a draw
    over its whole width -> (what went in, the fetched blocks)."""
    rng = np.random.default_rng(29)
    want = {}
    for field, (block, _row, bits, _signed) in SCHEMA.items():
        ext = _extremes(field)
        if block == "scalars":
            want[field] = np.int32(ext[-1 - len(want) % 2])
            continue
        col = rng.integers(-(1 << (bits - 1)), 1 << (bits - 1), LANES)
        col[:len(ext)] = ext
        want[field] = col.astype(np.int32)
    beside = {"prune_cand_hist": np.arange(5, dtype=np.int32)}
    rec, rest = jax.jit(fwd.pack_egress)(
        {k: jnp.asarray(v) for k, v in {**want, **beside}.items()})
    assert list(rest) == ["prune_cand_hist"]
    return want, tuple(np.asarray(a) for a in rec)


@pytest.mark.parametrize("field", FIELDS)
def test_round_trip_at_the_extremes(round_trip, field):
    want, blocks = round_trip
    block, row, bits, _signed = SCHEMA[field]
    o = fwd.unpack_egress(*blocks)
    assert set(o) == set(FIELDS)
    got = o[field]
    np.testing.assert_array_equal(np.asarray(got, np.int64),
                                  np.asarray(want[field], np.int64))
    for v in _extremes(field):
        assert _fits(v, bits, True), (field, v)
    if block != "scalars":  # a row VIEW of its block: nothing is copied
        src = blocks[fwd.EgressRecord._fields.index(block)]
        assert got.base is src and np.shares_memory(got, src[row])
        assert got.dtype == (np.int32 if block == "words" else np.int8)
        assert got.shape == (LANES,) and got.flags.c_contiguous


# -- the schema ----------------------------------------------------------------

def test_schema_rows_are_contiguous_and_every_field_has_one_place():
    assert len(set(FIELDS)) == len(FIELDS) == 27
    for block, fields, bits in (("words", fwd.EGRESS_WORDS, 32),
                                ("narrow", fwd.EGRESS_NARROW, 8),
                                ("scalars", fwd.EGRESS_SCALARS, 32)):
        rows = [(f, *SCHEMA[f]) for f in fields]
        assert rows == [(f, block, i, bits, True)
                        for i, f in enumerate(fields)]
    assert fwd.EgressRecord._fields == ("words", "narrow", "scalars")
    # 50 B a lane where the columns took 92.
    assert 4 * len(fwd.EGRESS_WORDS) + len(fwd.EGRESS_NARROW) == 50


@pytest.mark.parametrize("field", sorted(ENUMS))
def test_every_enum_constant_fits_its_field(field):
    """The cast to the narrow block would wrap a value its width cannot
    hold: every constant of the field's family has to fit, so a NEW value
    that does not fails here."""
    block, _row, bits, signed = SCHEMA[field]
    consts = _constants(field)
    assert block == "narrow" and len(consts) >= 3, consts
    for name, value in consts.items():
        assert _fits(value, bits, signed), (name, value, bits)
    assert not _fits(1 << (bits - 1), bits, signed)  # what would wrap
    assert fwd.unpack_egress(
        np.zeros((9, 1), np.int32),
        np.asarray(jnp.full((14, 1), max(consts.values())).astype(jnp.int8)),
        np.zeros(4, np.int32))[field][0] == max(consts.values())


def test_the_flags_are_flags():
    """The other ten narrow fields are 0/1 by construction in the step
    (comparisons and masks cast to i32); the provisional verdict of the
    async admission is an ACT_* too."""
    assert set(fwd.EGRESS_NARROW) - set(ENUMS) == {
        "est", "reply", "committed", "miss", "snat", "dsr", "spoofed",
        "l7_redirect", "punt", "dec_ttl"}
    assert pl.PipelineMeta._field_defaults["miss_code"] in _constants(
        "code").values()


# -- engines -------------------------------------------------------------------

KW = dict(flow_slots=1 << 12, aff_slots=1 << 8, canary_probes=16)
B = 256


def _topology():
    return Topology(
        node_name="node-a", gateway_ip="10.10.0.1", pod_cidr="10.10.0.0/24",
        local_pods=[(f"10.10.0.{5 + i}", 3 + i) for i in range(3)],
        remote_nodes=[NodeRoute(name="node-b", node_ip="192.168.1.2",
                                pod_cidr="10.10.1.0/24")])


@pytest.fixture(scope="module")
def world():
    cluster = gen_cluster(120, n_nodes=4, pods_per_node=8, seed=7)
    return cluster, gen_services(8, cluster.pod_ips, seed=2)


@pytest.fixture(scope="module")
def batch(world):
    """248 lanes of 64 connections under a Zipf(1) head (the first takes a
    fifth of the lanes, so one replica of four overflows its home slice)
    and 8 lanes that walk the forwarding tables: local, tunnel, gateway,
    unknown pod, tunnel ingress, a spoofed source."""
    cluster, services = world
    pool = gen_traffic(cluster.pod_ips, 512, n_flows=256, seed=17,
                       services=services, svc_fraction=0.3)
    cols = np.stack([pool.src_ip.astype(np.int64), pool.dst_ip, pool.proto,
                     pool.src_port, pool.dst_port], 1)
    _, first = np.unique(cols, axis=0, return_index=True)
    flows = np.sort(first)[:64]
    w = 1.0 / np.arange(1, 65)
    lane_flow = np.random.default_rng(1).choice(flows, B - 8, p=w / w.sum())
    rows = [("10.10.0.5", "10.10.0.6", 3), ("10.10.0.5", "10.10.1.9", 3),
            ("10.10.0.6", "8.8.8.8", 4), ("10.10.0.5", "10.10.0.99", 3),
            ("10.10.1.9", "10.10.0.5", OFPORT_TUNNEL),
            ("10.10.0.9", "10.10.0.6", 3), ("10.10.0.7", "10.10.0.5", 5),
            ("10.10.0.6", "10.10.0.7", 4)]
    u32 = lambda xs: np.array([iputil.ip_to_u32(x) for x in xs], np.uint32)  # noqa: E731
    tail = lambda col, v: np.concatenate(  # noqa: E731
        [col[lane_flow], np.full(8, v, col.dtype)])
    return PacketBatch(
        src_ip=np.concatenate([pool.src_ip[lane_flow],
                               u32(r[0] for r in rows)]),
        dst_ip=np.concatenate([pool.dst_ip[lane_flow],
                               u32(r[1] for r in rows)]),
        proto=tail(pool.proto, 6), src_port=tail(pool.src_port, 40000),
        dst_port=tail(pool.dst_port, 80),
        in_port=np.concatenate([np.full(B - 8, -1, np.int32),
                                np.array([r[2] for r in rows], np.int32)]))


RESULT_FIELDS = [f.name for f in dataclasses.fields(StepResult)]


def _dict_path_step(dp, batch, now) -> dict:
    """The step as it was served before the record: the program that
    returns the output DICT, one blocking fetch an output, and `_step`'s
    attribution, field for field -> {StepResult field: value}."""
    state, out = fwd.pipeline_step_full(
        dp._state, dp._drs, dp._dsvc, dp._dft,
        jnp.asarray(iputil.flip_u32(batch.src_ip)),
        jnp.asarray(iputil.flip_u32(batch.dst_ip)),
        jnp.asarray(batch.proto.astype(np.int32)),
        jnp.asarray(batch.src_port.astype(np.int32)),
        jnp.asarray(batch.dst_port.astype(np.int32)),
        jnp.asarray(batch.in_ports()), jnp.int32(now), jnp.int32(dp._gen),
        jnp.asarray(batch.flags()), None, None, meta=dp._meta_step)
    dp._state = state
    o = {k: np.asarray(v) for k, v in out.items()}
    assert all(v.dtype == np.int32 for v in o.values())
    unflip = iputil.unflip_u32_array
    tunnel = (o["fwd_kind"] == FWD_TUNNEL) & (o["out_port"] != -1)
    want = {f: o[f] for f in RESULT_FIELDS if f in o}
    want.update(
        n_miss=int(o["n_miss"]), pending=None, dnat_key=None, peer_key=None,
        dnat_ip=unflip(o["dnat_ip_f"]),
        peer_ip=np.where(tunnel, unflip(o["peer_f"]), 0).astype(np.uint32),
        ingress_rule=_rids(dp._cps.ingress, o["ingress_rule"]),
        egress_rule=_rids(dp._cps.egress, o["egress_rule"]))
    assert set(want) == set(RESULT_FIELDS)
    return want


def _assert_field(got, want, field, lanes=slice(None)):
    if want is None or isinstance(want, int):
        assert got == want or (got is None and want is None), field
    elif isinstance(want, list):
        assert type(got) is list and got[lanes] == want[lanes], field
    else:
        np.testing.assert_array_equal(
            np.asarray(got, np.int64)[lanes],
            np.asarray(want, np.int64)[lanes], err_msg=field)


def _engines(world, make):
    cluster, services = world
    dps = (make(), TpuflowDatapath(cluster.ps, services, **KW),
           OracleDatapath(cluster.ps, services, **KW))
    for dp in dps:
        dp.install_topology(_topology())
    return dps


@pytest.fixture(scope="module")
def one_chip(world, batch):
    """Two steps -> [(StepResult, the dict path's fields, the scalar
    twin's StepResult)] a step."""
    cluster, services = world
    dp, ref, oracle = _engines(
        world, lambda: TpuflowDatapath(cluster.ps, services, **KW))
    return [(dp.step(batch, t), _dict_path_step(ref, batch, t),
             oracle.step(batch, t)) for t in (100, 101)]


@pytest.mark.parametrize("field", RESULT_FIELDS)
def test_one_chip_result_is_the_dict_paths_and_the_twins(one_chip, field):
    for res, want, twin in one_chip:
        _assert_field(getattr(res, field), want[field], field)
        stated = getattr(twin, field)
        if stated is not None or field in ("pending", "dnat_key", "peer_key"):
            _assert_field(getattr(res, field), stated, field)
    res, want, _ = one_chip[0]
    assert np.asarray(res.spoofed).sum() == 1 and res.n_miss > 64
    assert len({int(k) for k in res.fwd_kind}) >= 5  # the walk was walked
    assert one_chip[1][0].n_miss < res.n_miss  # ... and the cache served


@pytest.fixture(scope="module")
def mesh4(world, batch):
    """The same two steps through four data replicas -> (results as in
    `one_chip`, the lanes placed off-home, the mesh's step records)."""
    if len(jax.devices("cpu")) < 4:
        pytest.skip("needs 4 virtual CPU devices")
    from antrea_tpu.parallel import MeshDatapath
    from antrea_tpu.parallel import mesh as pm
    from antrea_tpu.parallel.meshpath import _shard_placement

    cluster, services = world
    dp, ref, oracle = _engines(world, lambda: MeshDatapath(
        cluster.ps, services, n_data=4, n_rule=1,
        devices=jax.devices("cpu")[:4], **KW))
    shard = pm.shard_of_tuples(batch.src_ip, batch.dst_ip, batch.proto,
                               batch.src_port, batch.dst_port, 4)
    perm, _inv, spill = _shard_placement(shard, 4)
    steps = [(dp.step(batch, t), _dict_path_step(ref, batch, t),
              oracle.step(batch, t)) for t in (100, 101)]
    return steps, perm[spill], shard, dp.step_trace()["records"]


@pytest.mark.parametrize("field", RESULT_FIELDS)
def test_mesh_result_is_the_dict_paths_and_the_twins(mesh4, field):
    """Un-permuted and merged on the packed blocks, every field is still
    the unsharded dict path's and the twin's.  But for one documented
    case (PERF.md, PR 28): in the FIRST step a retried lane whose flow's
    home lanes were committed a moment earlier in the same call reads
    est=1 / committed=0 where an unsharded batch reads 0 / 1, and is no
    miss — those three fields are held on the home lanes there, and on
    every lane in the second step."""
    steps, spilled, _shard, rec = mesh4
    assert spilled.size > 16 and (rec["retry_lanes"] == spilled.size).all()
    home = np.ones(B, bool)
    home[spilled] = False
    for t, (res, want, twin) in enumerate(steps):
        lanes = home if (t == 0 and field in ("est", "committed")) \
            else slice(None)
        if field == "n_miss":
            # ... and four private 4,096-slot tables collide less than
            # one: the cached denials that one chip re-misses in the
            # second step (3 lanes) are hits here.
            assert (t == 0) <= res.n_miss <= want["n_miss"] == twin.n_miss
            continue
        _assert_field(getattr(res, field), want[field], field, lanes)
        stated = getattr(twin, field)
        if stated is not None or field in ("pending", "dnat_key", "peer_key"):
            _assert_field(getattr(res, field), stated, field, lanes)


def test_mesh_transfer_counters_by_hand(mesh4):
    """A retried mesh step fetches two records: the step's, B lanes wide,
    and the retry's, four replicas at the power-of-two rung of the
    fullest overflow; three copies each, 50 B a lane and four i32
    scalars a replica."""
    _steps, spilled, shard, rec = mesh4
    m = np.bincount(shard[spilled], minlength=4).max()
    rung = min(B // 4, max(16, 1 << (int(m) - 1).bit_length()))
    lane_bytes = 4 * len(fwd.EGRESS_WORDS) + len(fwd.EGRESS_NARROW)
    scalars = 4 * len(fwd.EGRESS_SCALARS) * 4
    assert rec["d2h_transfers"].tolist() == [6, 6]
    assert rec["d2h_bytes"].tolist() == [
        lane_bytes * (B + 4 * rung) + 2 * scalars] * 2


OPTION_OUTPUTS = {  # what rides beside the record, by the option that makes it
    "default": ({}, set()),
    "dual_stack": ({"dual_stack": True}, {"dnat_w_f", "peer_w"}),
    "prune_budget": ({"prune_budget": 2},
                     {"n_prune_skips", "n_prune_fb", "prune_cand_hist"}),
}


@pytest.mark.parametrize("option", sorted(OPTION_OUTPUTS))
def test_only_optional_outputs_ride_beside_the_record(world, option):
    cluster, services = world
    kw, beside = OPTION_OUTPUTS[option]
    dp = TpuflowDatapath(cluster.ps, services, **KW, **kw)
    i32 = jnp.zeros(B, jnp.int32)
    v6 = dp._v6_lanes(PacketBatch(*[np.zeros(B, np.uint32)] * 2,
                                  *[np.zeros(B, np.int32)] * 3))
    _, rec, rest = jax.eval_shape(
        lambda: fwd.pipeline_step_full_packed(
            dp._state, dp._drs, dp._dsvc, dp._dft, i32, i32, i32, i32, i32,
            i32, jnp.int32(1), jnp.int32(1), i32, meta=dp._meta_step, v6=v6))
    assert set(rest) == beside
    assert [(a.shape, a.dtype) for a in rec] == [
        ((9, B), np.int32), ((14, B), np.int8), ((4,), np.int32)]

"""The rule-word axis is tiled (`ops/match.TILE_WORDS`, `_width`).

With default layouts the TPU places a 2-D array in the dimension order that
pads least under its (8, 128) tile.  A (rows, W) incidence table whose W is no
multiple of 128 is therefore laid out COLUMN-major, and a step that gathers
whole rows of it (`classify.candidate`) transposes the whole table first, in
every step that has a miss: 533 MB at 100k rules, 2.34 GB for upstream's
xLargeScale cluster.  A width that is a multiple of 128 pads nothing as the
minor dimension, the table is placed row-major, and the gather reads it in
place.

What is held:
  * every width `to_host` builds is a multiple of 128 words for each rule
    shard, for any rule count, shard count and pruning; every `inc` table of
    both directions has that width, the words past the last rule are zero in
    every row and the actions past it are `ACT_DROP`, so a padding bit can
    never be a first match;
  * the extra words change no answer: a small world steps lane for lane like
    the scalar twin `OracleDatapath` under every classify selection and on a
    2 x 2 mesh (rules sharded two ways: each shard's slice is tiled too);
  * where the TPU's compile-only client can be described: a row gather over a
    table of `to_host`'s width is compiled for a v5e with the table placed
    row-major and no copy of it, and the same rows at the rule count's own
    width are not (the control that shows the probe can tell);
  * the scrub's digest (`models/pipeline.tensor_digest`) folds a table where
    it lies: the value is the flat statement's for every dtype and rank, and
    compiled for the chip the fold of a 1.2 GB table holds no copy of it (it
    flattened and bitcast the table first, two copies beside the tables: the
    peak of the device's memory at install, above any step's).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from antrea_tpu.compiler.compile import compile_policy_set
from antrea_tpu.datapath import OracleDatapath, TpuflowDatapath
from antrea_tpu.models import pipeline as pl
from antrea_tpu.ops import match as m
from antrea_tpu.packet import PacketBatch
from antrea_tpu.simulator import gen_cluster, gen_services, gen_traffic

B = 128
KW = dict(flow_slots=1 << 10, aff_slots=1 << 8, canary_probes=8,
          miss_chunk=B)  # one round a step: the twin's bookkeeping exactly
RULE_COUNTS = (1, 33, 4097, 59_681, 75_000)
FIELDS = ("code", "ingress_rule", "egress_rule", "svc_idx", "dnat_port")


@pytest.fixture(scope="module")
def small():
    cluster = gen_cluster(60, n_nodes=2, pods_per_node=4, seed=3)
    return compile_policy_set(cluster.ps)


def _grown(cps, n_rules: int):
    """The compiled set with `n_rules` ingress rules: the small world's own
    rules over and over, all in the K8s phase.  Same groups, so the tables
    keep a few dozen rows whatever their width."""
    dt = cps.ingress
    pick = np.arange(n_rules) % dt.n_rules
    return dataclasses.replace(cps, ingress=dataclasses.replace(
        dt, at_gid=dt.at_gid[pick], peer_gid=dt.peer_gid[pick],
        svc_gid=dt.svc_gid[pick], action=dt.action[pick], l7=None,
        rule_ids=[], n_phase0=0, n_k8s=n_rules, n_baseline=0))


@pytest.mark.parametrize("prune_budget", [0, 2])
@pytest.mark.parametrize("word_multiple", [1, 2, 4])
@pytest.mark.parametrize("n_rules", RULE_COUNTS)
def test_every_width_is_tiled_and_its_padding_is_inert(
        small, n_rules, word_multiple, prune_budget):
    cps = _grown(small, n_rules)
    drs, meta = m.to_host(cps, word_multiple=word_multiple,
                          prune_budget=prune_budget)
    for dd, dt, w in ((drs.ingress, cps.ingress, meta.w_in),
                      (drs.egress, cps.egress, meta.w_out)):
        assert w % (m.TILE_WORDS * word_multiple) == 0
        assert w * 32 >= dt.n_rules
        # no wider than it has to be: one unit less would not hold the rules
        assert (w - m.TILE_WORDS * word_multiple) * 32 < max(dt.n_rules, 1)
        used = -(-dt.n_rules // 32)
        for tab in (dd.at, dd.peer, dd.svc):
            assert tab.inc.shape[1] == w and tab.inc.dtype == np.uint32
            assert not tab.inc[:, used:].any()
            if dt.n_rules % 32:  # the last used word's bits past the rules
                assert not (tab.inc[:, used - 1] >> (dt.n_rules % 32)).any()
            if prune_budget:
                assert tab.agg.shape == (tab.inc.shape[0], w // m.AGG_BLOCK)
                assert np.array_equal(tab.agg, m.build_agg(tab.inc))
            else:
                assert tab.agg is None
        assert dd.action.shape == dd.l7.shape == (w * 32,)
        assert (dd.action[dt.n_rules:] == m.ACT_DROP).all()
        assert not dd.l7[dt.n_rules:].any()
        assert dd.word_idx.tolist() == list(range(w))
    assert drs.ip_delta.at_in.shape[1] == meta.w_in
    assert drs.ip_delta.at_out.shape[1] == meta.w_out


# -- the padding changes no answer ---------------------------------------------

SELECTIONS = {
    "default": {},
    "fused": dict(fused=True),
    "pruned": dict(prune_budget=2),
    "fused_pruned": dict(fused=True, prune_budget=2),
    "dual_stack": dict(dual_stack=True),
    "mesh2x2": dict(n_data=2, n_rule=2),
}


@pytest.fixture(scope="module")
def world():
    cluster = gen_cluster(120, n_nodes=4, pods_per_node=8, seed=7)
    services = gen_services(8, cluster.pod_ips, seed=2)
    first = gen_traffic(cluster.pod_ips, B, n_flows=96, seed=3,
                        services=services)
    other = gen_traffic(cluster.pod_ips, B, n_flows=96, seed=4,
                        services=services)
    return cluster.ps, services, [first, other]


def _wide(batch: PacketBatch) -> PacketBatch:
    """The v4 batch as a dual-stack engine takes it: no lane is v6."""
    return PacketBatch(
        src_ip=batch.src_ip, dst_ip=batch.dst_ip, proto=batch.proto,
        src_port=batch.src_port, dst_port=batch.dst_port,
        src_ip6=np.zeros((batch.size, 4), np.uint32),
        dst_ip6=np.zeros((batch.size, 4), np.uint32),
        is6=np.zeros(batch.size, np.int32))


@pytest.fixture(scope="module", params=list(SELECTIONS))
def served(request, world):
    """(selection, engine, [(StepResult, the twin's)] a step)."""
    ps, services, batches = world
    kw = SELECTIONS[request.param]
    twin_kw = {k: v for k, v in KW.items() if k != "miss_chunk"}
    if request.param == "mesh2x2":
        if len(jax.devices("cpu")) < 4:
            pytest.skip("needs 4 virtual CPU devices")
        from antrea_tpu.parallel import MeshDatapath

        dp = MeshDatapath(ps, services, devices=jax.devices("cpu")[:4],
                          **kw, **KW)
        twin = OracleDatapath(ps, services, **twin_kw)
    else:
        dp = TpuflowDatapath(ps, services, **kw, **KW)
        twin = OracleDatapath(ps, services, **kw, **twin_kw)
    if kw.get("dual_stack"):
        batches = [_wide(b) for b in batches]
    return request.param, dp, [
        (dp.step(b, now=10 + i), twin.step(b, now=10 + i))
        for i, b in enumerate(batches)]


def test_the_served_tables_are_tiled(served):
    selection, dp, _ = served
    mm = dp._meta.match
    shards = SELECTIONS[selection].get("n_rule", 1)
    for dd, w in ((dp._drs.ingress, mm.w_in), (dp._drs.egress, mm.w_out)):
        assert w % (m.TILE_WORDS * shards) == 0
        for tab in (dd.at, dd.peer, dd.svc):
            assert tab.inc.shape[1] == w


@pytest.mark.parametrize("field", FIELDS)
def test_padded_tables_answer_like_the_scalar_twin(served, field):
    selection, _, steps = served
    for i, (res, want) in enumerate(steps):
        got, stated = getattr(res, field), getattr(want, field)
        if isinstance(stated, (list, int)):
            assert got == stated, (selection, field, i)
        else:
            np.testing.assert_array_equal(
                np.asarray(got, np.int64), np.asarray(stated, np.int64),
                err_msg=f"{selection} {field} step {i}")


# -- what the TPU's compiler does with such a table ------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chip_config_name="default", chips_per_host_bounds=(1, 1, 1),
            num_slices=1)
    except Exception as e:  # no libtpu here, or its lock is taken
        pytest.skip(f"no v5e can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, *shapes):
    """`fn` compiled for the described chip the shapes are placed on."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()  # a compile for a described chip cannot be read back
    try:
        return jax.jit(fn).trace(*shapes).lower(
            lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()


def _row_gather(one_chip, rows: int, w: int):
    """-> (the table's placement is row-major, copies of the table) of a
    row gather over a (rows, w) table compiled for the described chip."""
    tab = jax.ShapeDtypeStruct((rows, w), jnp.uint32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one_chip)
    text = _compiled(lambda t, i: t[i].sum(1), tab, idx).as_text()
    (entry,) = re.findall(
        rf"u32\[{rows},{w}\]\{{([\d,]+):[^}}]*\}} parameter\(0\), sharding",
        text)
    copies = re.findall(rf"= u32\[{rows},{w}\]\S* copy\(", text)
    return entry == "1,0", len(copies)


# rows of np100k's and xlarge75k's largest row tables (PERF.md s7)
@pytest.mark.parametrize("rows,n_rules", [(33_433, 59_681), (24_134, 40_319),
                                          (125_002, 75_000)])
def test_the_tpu_places_a_tiled_table_row_major_and_copies_nothing(
        one_chip, rows, n_rules):
    w = m._width(n_rules, 1)
    assert _row_gather(one_chip, rows, w) == (True, 0)
    own = -(-n_rules // 32)  # the control: the rule count's own words
    if _row_gather(one_chip, rows, own) == (True, 0):
        pytest.skip("this compiler places the untiled table row-major too: "
                    "the probe above cannot tell")


# -- the scrub's digest reads a table where it lies --------------------------------

def _flat_digest(leaves) -> int:
    """`tensor_digest` as its docstring states it, over flat numpy words."""
    h = 0
    for leaf in leaves:
        a = np.asarray(leaf).reshape(-1)
        words = (a.view(np.int32) if a.dtype.itemsize == 4
                 else a.astype(np.int32))
        xor = int(np.bitwise_xor.reduce(words, initial=0)) & 0xFFFFFFFF
        total = int(words.astype(np.int64).sum()) & 0xFFFFFFFF
        for x in (xor, total):
            h = (h * 1000003 + x) & 0xFFFFFFFFFFFFFFFF
    return h


@pytest.mark.parametrize("leaf", [
    np.random.default_rng(1).integers(0, 2**32, (37, 256), dtype=np.uint32),
    np.random.default_rng(2).integers(-2**31, 2**31, (5,), dtype=np.int32),
    np.random.default_rng(3).integers(-128, 127, (3, 4, 5)).astype(np.int8),
    np.random.default_rng(4).integers(0, 2, (17,)).astype(bool),
    np.zeros((0, 4), np.int32),
    np.int32(7),
], ids=["u32_table", "i32_column", "i8_rank3", "bool", "empty", "scalar"])
def test_the_digest_is_the_flat_statements(leaf):
    other = np.arange(12, dtype=np.int32).reshape(3, 4)
    assert pl.tensor_digest([leaf]) == _flat_digest([leaf])
    assert pl.tensor_digest([other, leaf]) == _flat_digest([other, leaf])


def test_the_digest_of_a_table_copies_nothing_on_the_tpu(one_chip):
    rows, w = 125_002, m._width(75_000, 1)
    tab = jax.ShapeDtypeStruct((rows, w), jnp.uint32, sharding=one_chip)
    stats = _compiled(pl._digest_pair, tab).memory_analysis()
    assert stats.argument_size_in_bytes >= rows * w * 4
    assert stats.temp_size_in_bytes < 1 << 20

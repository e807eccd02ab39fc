"""The slow path's round ladder (models/pipeline.round_ladder): the last
round of a step runs at the narrowest rung that holds what is left, and
NOTHING but the `round_lanes` counter may tell — every output, every other
scalar and every slot of the PipelineState equal the program built with the
single rung `(miss_chunk,)`, bit for bit, and the verdicts equal the scalar
oracle's."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from antrea_tpu.compiler.compile import compile_policy_set
from antrea_tpu.compiler.services import compile_services
from antrea_tpu.models import pipeline as pl
from antrea_tpu.observability.tracing import STEP_RECORD
from antrea_tpu.ops import hashing
from antrea_tpu.ops.match import flip_ips
from antrea_tpu.oracle.pipeline import PipelineOracle
from antrea_tpu.simulator import gen_cluster, gen_services, gen_traffic

M = 1024  # ladder (1024, 128): the smallest chunk that has a narrow rung
B = 2304
SLOTS = 1 << 11  # fewer slots than inserts: rounds collide with each other
NOW = 1000
VARIANTS = {
    "plain": {},
    "second_chance": {"second_chance": True},
    "dual_stack": {"dual_stack": True},
    "fused": {"fused": True},
}
N_MISS = (0, 1, 127, 128, 129, M - 1, M, M + 1, 2 * M + 3, 2 * M + 128)
CASES = ([("plain", n) for n in N_MISS]
         + [(v, n) for v in ("second_chance", "dual_stack")
            for n in (129, M + 1, 2 * M + 3)]
         + [("fused", 127), ("fused", M + 1)])


def plan(n_miss: int, ladder=(1024, 128)) -> int:
    """Full rounds at M, then the narrowest rung that holds the rest."""
    full, rest = divmod(n_miss, M)
    return full * M + (min(w for w in ladder if w >= rest) if rest else 0)


@pytest.mark.parametrize("m,b,want", [
    (4096, 131072, (4096, 512)),
    (4096, 513, (4096, 512)),
    (4096, 512, (512,)),  # no rung wider than the batch can fill
    (4096, 64, (512,)),
    (M, B, (1024, 128)),
    (1024, 4096, (1024, 128)),
    (512, 4096, (512,)),  # a rung stays a multiple of the fused tile
    (64, 256, (64,)),
    (32, 64, (32,)),
    (3000, 131072, (3000,)),
])
def test_the_ladder_is_worked_out_from_the_chunk_and_the_batch(m, b, want):
    got = pl.round_ladder(m, b)
    assert got == want
    assert all(w % 128 == 0 for w in got[1:]) and got[0] <= m
    assert all(hi == 8 * lo for hi, lo in zip(got, got[1:]))


@pytest.fixture(scope="module")
def world():
    cluster = gen_cluster(150, seed=5)
    services = gen_services(24, cluster.pod_ips, seed=6, no_ep_fraction=0.1)
    t = gen_traffic(cluster.pod_ips, batch=B, seed=7, services=services,
                    svc_fraction=0.4, one_per_flow=False)
    cols = (np.asarray(flip_ips(t.src_ip)), np.asarray(flip_ips(t.dst_ip)),
            t.proto.astype(np.int32), t.src_port.astype(np.int32),
            t.dst_port.astype(np.int32))
    po = PipelineOracle(cluster.ps, services, flow_slots=SLOTS,
                        aff_slots=1 << 8)
    # From an empty cache every lane is classified fresh, whatever else the
    # batch holds: one scalar walk serves every case.
    scalar = po.step(t, NOW, 0)
    order = np.random.default_rng(8).permutation(B)  # lane -> its rank
    return (compile_policy_set(cluster.ps), compile_services(services), t,
            cols, scalar, order)


_BUILT = {}


def programs(world, variant):
    """-> (laddered, single, state0, drs, dsvc): the same step jitted twice,
    the second traced while round_ladder gives the single rung."""
    if variant not in _BUILT:
        cps, svt = world[:2]
        step, state, (drs, dsvc) = pl.make_pipeline(
            cps, svt, flow_slots=SLOTS, aff_slots=1 << 8, miss_chunk=M,
            **VARIANTS[variant])
        meta = step.meta

        def build():
            def run(state, drs, dsvc, cols, now, valid):
                return pl._pipeline_step(state, drs, dsvc, *cols, now,
                                         jnp.int32(0), meta=meta, valid=valid)
            return jax.jit(run)

        laddered, single = build(), build()
        args = (state, drs, dsvc, world[3], jnp.int32(NOW),
                np.zeros(B, bool))
        text = laddered.lower(*args).as_text()
        with mock.patch.object(pl, "round_ladder", lambda m, b: (m,)):
            text1 = single.lower(*args).as_text()
            single(*args)  # traced (and cached) under the single rung
        # a rung is one more body of the round (its loop and the loops
        # inside it), in the one program
        assert text.count("stablehlo.while") == 2 * text1.count(
            "stablehlo.while")
        _BUILT[variant] = (laddered, single, state, drs, dsvc)
    return _BUILT[variant]


def same(a, b, rows=...):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x)[rows], np.asarray(y)[rows])


@pytest.mark.parametrize("variant,n_miss", CASES)
def test_the_laddered_step_is_the_single_rung_step_bit_for_bit(
        world, variant, n_miss):
    cps, _svt, t, cols, scalar, order = world
    laddered, single, state0, drs, dsvc = programs(world, variant)
    valid = order < n_miss
    lanes = np.nonzero(valid)[0]  # rounds take them in this order
    if n_miss > M:
        # Two lanes of different rounds writing one slot, the later one in
        # the narrowed last round: the last writer has to win there too.
        slot = hashing.flow_hash(
            t.src_ip, t.dst_ip, t.proto, t.src_port, t.dst_port) & (SLOTS - 1)
        tail = lanes[(n_miss - 1) // M * M:]
        assert np.isin(slot[tail], slot[lanes[:M]]).any()
    s_l, s_1 = state0, state0
    for step_no, (now, mask) in enumerate(
            [(NOW, valid), (NOW + 5, np.ones(B, bool))]):
        s_l, out_l = laddered(s_l, drs, dsvc, cols, jnp.int32(now), mask)
        s_1, out_1 = single(s_1, drs, dsvc, cols, jnp.int32(now), mask)
        out_l, out_1 = dict(out_l), dict(out_1)
        got, one_rung = int(out_l.pop("round_lanes")), int(
            out_1.pop("round_lanes"))
        miss = int(out_l["n_miss"])
        assert got == plan(miss) <= one_rung == -(-miss // M) * M
        same(out_l, out_1)
        # Every row of every table but its last: that one is the dump row,
        # where masked scatters (padding lanes among them) leave their junk
        # and which no lookup, scan or census reads (pipeline._live_rows).
        same(s_l, s_1, rows=slice(None, -1))
        if step_no == 0:
            assert miss == n_miss
            if variant in ("plain", "fused"):
                for i in lanes:  # the verdicts, against the scalar oracle
                    so = scalar[i]
                    assert int(out_l["code"][i]) == so.code
                    assert int(out_l["svc_idx"][i]) == so.svc_idx
                    for key, ids, want in (
                            ("ingress_rule", cps.ingress.rule_ids,
                             so.ingress_rule),
                            ("egress_rule", cps.egress.rule_ids,
                             so.egress_rule)):
                        r = int(out_l[key][i])
                        assert (ids[r] if r >= 0 else None) == want
        elif n_miss > 1:  # the second step: hits, re-misses and the rest
            assert 0 < miss < B and int(out_l["est"].sum()) > 0


@pytest.mark.parametrize("variant", ["plain", "fused"])
def test_a_shard_of_a_sharded_step_keeps_the_single_rung(world, variant):
    """With a `hit_combine` seam (the mesh's step, retry rungs and drains)
    the narrow body is not built: the lowered program has the single-rung
    program's loops and no more, and counts whole rounds."""
    cps, svt = world[:2]
    step, state, (drs, dsvc) = pl.make_pipeline(
        cps, svt, flow_slots=SLOTS, aff_slots=1 << 8, miss_chunk=M,
        **VARIANTS[variant])

    def build(seam):
        return jax.jit(lambda state, cols, valid: pl._pipeline_step(
            state, drs, dsvc, *cols, jnp.int32(NOW), jnp.int32(0),
            meta=step.meta, valid=valid, hit_combine=seam))

    valid = world[5] < 100  # 100 misses: the narrow rung, were it built
    sharded, plain = build(lambda hit: hit), build(None)
    whiles = [f.lower(state, world[3], valid).as_text().count(
        "stablehlo.while") for f in (sharded, plain)]
    assert whiles[1] == 2 * whiles[0]
    (s_s, out_s), (s_p, out_p) = (f(state, world[3], valid)
                                  for f in (sharded, plain))
    out_s, out_p = dict(out_s), dict(out_p)
    assert int(out_s.pop("round_lanes")) == M
    assert int(out_p.pop("round_lanes")) == 128
    same(out_s, out_p)
    same(s_s, s_p, rows=slice(None, -1))


def test_the_counter_is_a_field_of_the_step_record():
    names = STEP_RECORD.names
    assert names[names.index("n_miss") + 1] == "round_lanes"

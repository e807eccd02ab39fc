"""Reply legs as data: a cell whose generator listens to every answer and
labels its lanes (`fixtures/reply_flows.py`), whose reference reads the
labels (`fixtures/reply_reference.py`), added to a checkout as files and
manifest entries only (test_harness.py's `tree`, cell `tiny_replies.churn`).
Its two faults of the reply path are cases of
test_harness.py::test_a_broken_timed_path_is_not_correct."""

import os
import time

import numpy as np
import pytest

import control  # noqa: E402
import harness  # noqa: E402
from manifest import Manifest, load_json, load_module  # noqa: E402
from test_harness import FIXTURES, run, tree  # noqa: E402,F401

CELL = "tiny_replies.churn"


def _generator(tree):  # noqa: F811
    return load_module(os.path.join(tree.home, "generators", "reply_flows.py"))


def _world_and_reference():
    config = load_json(os.path.join(FIXTURES, "tiny_replies.json"))
    worlds = load_module(Manifest().world_path(config))
    world = worlds.build_world(config["world"], config["world_seed"])
    ref = load_module(os.path.join(FIXTURES, "reply_reference.py")).Reference(
        world)
    return config, worlds, world, ref


@pytest.mark.parametrize("seed", [5, 2**31 + 17, 914_202_611])
def test_a_reply_cell_is_correct_with_replies_sampled_every_step(tree,
                                                                 seed):
    seen = {}
    r = run(tree, CELL, seed=seed, after_check=lambda ctx: seen.update(ctx))
    assert r["correct"] is True and r["failed"] == 0
    assert r["check"]["wrong_lanes"] == {"value": 0, "limit": 0}
    s = seen["sample"]
    reply = s["leg"] == 1
    # at least one sampled reply lane a step, nearly all of them answered
    # from their connection's reply entry, with the un-DNAT of a Service
    assert reply.sum() >= r["steps"] > 0
    answered = reply & (s["reply"] == 1)
    assert answered.sum() >= 0.95 * reply.sum()
    assert (answered & (s["svc_idx"] >= 0)).any()
    assert ((s["reply"] == 1) <= reply).all()  # no forward leg was marked
    assert r["check"]["lanes_fresh"]["value"] > 0


def test_the_control_of_a_reply_cell_is_not_correct(tree):
    r = run(tree, CELL, seed=7, after_check=control.read)
    assert r["correct"] is True
    assert r["control"]["correct"] is False
    assert r["control"]["wrong_lanes"] > 0


def test_the_reference_holds_on_the_scalar_oracles_answers(tree):  # noqa: F811
    """Every lane of every batch, not a sample: the reference's statements
    on the answers of `make_datapath("oracle")` stepped with the cell's
    batches, warm-up first, as the harness steps them."""
    from antrea_tpu.datapath import make_datapath

    config, worlds, world, ref = _world_and_reference()
    kw = dict(config["engine"]["kwargs"])
    kw.pop("miss_chunk")  # the scalar twin classifies lane by lane
    engine = make_datapath("oracle", **kw)
    engine.install_bundle(*worlds.to_program(world))
    mix = load_json(os.path.join(FIXTURES, "tiny_churn_replies.json"))
    traffic = _generator(tree).Traffic(mix, world, 11, ref)
    t = time.perf_counter()
    harness.warm_up(engine, traffic, t)
    every = np.arange(traffic.batch)
    w = harness.Window()
    for _ in range(24):
        cols, _, _, labels = traffic.next_batch()
        res = engine.step(harness.packet_batch(cols), harness.now_of(t))
        traffic.observe(cols, res)
        w.take(cols, res, every, np.isin(every, traffic.fresh_at), labels)
    s = w.sampled()
    bad = ref.failed_statements(s)
    assert {k: int(v.sum()) for k, v in bad.items()} == dict.fromkeys(bad, 0)
    reply = s["leg"] == 1
    assert reply.sum() == 24 * traffic.reply_lanes
    assert (s["reply"][reply] == 1).mean() > 0.95
    assert (s["svc_idx"][reply & (s["reply"] == 1)] >= 0).any()


def test_a_reply_goes_out_two_batches_after_it_was_heard_and_once(
        tree):  # noqa: F811
    """What the generator sends is what it heard: the reply of each
    committed forward lane, from the address the answer names, to the
    client, in batch n + 2 at the earliest; a lane it did not hear
    committed, and a reply tuple heard for two frontends, get none."""
    _, _, world, ref = _world_and_reference()
    mix = load_json(os.path.join(FIXTURES, "tiny_churn_replies.json"))
    traffic = _generator(tree).Traffic(mix, world, 3, ref)
    for cols in traffic.warmup():  # heard nothing committed
        traffic.observe(cols, _answer(cols, committed=()))
    sent = []
    for k in range(4):
        cols, lanes, fresh, labels = traffic.next_batch()
        sent.append((cols, labels))
        # batch 0 commits lanes 2 and 5 (lane 5 sent to an endpoint); batch
        # 1 commits lane 9 with the reply tuple of batch 0's lane 2 and
        # another frontend
        if k == 0:
            res = _answer(cols, committed=(2, 5), dnat={5: (4242, 8080)})
        elif k == 1:
            cols["src_ip"][9] = sent[0][0]["src_ip"][2]
            cols["src_port"][9] = sent[0][0]["src_port"][2]
            cols["proto"][9] = sent[0][0]["proto"][2]
            res = _answer(cols, committed=(9,),
                          dnat={9: (int(sent[0][0]["dst_ip"][2]),
                                    int(sent[0][0]["dst_port"][2]))})
            cols["dst_ip"][9] += 1  # the frontend it was sent to differs
        else:
            res = _answer(cols, committed=())
        traffic.observe(cols, res)
        assert (labels["leg"] == 1).sum() == (1 if k == 2 else 0), k
    first = sent[0][0]
    cols, labels = sent[2]
    at = traffic.reply_at[0]
    assert labels["leg"][at] == 1
    assert (cols["src_ip"][at], cols["src_port"][at]) == (4242, 8080)
    assert (cols["dst_ip"][at], cols["dst_port"][at]) == (
        first["src_ip"][5], first["src_port"][5])
    assert cols["proto"][at] == first["proto"][5]
    assert (labels["frontend_ip"][at], labels["frontend_port"][at]) == (
        first["dst_ip"][5], first["dst_port"][5])
    # a forward lane is labelled with its own destination
    other = np.nonzero(labels["leg"] == 0)[0]
    assert (labels["frontend_ip"][other] == cols["dst_ip"][other]).all()


def _answer(cols, committed, dnat=None):
    """A result that commits the lanes `committed`, each sent to its own
    destination unless `dnat` names another."""
    from antrea_tpu.datapath.interface import StepResult

    n = len(cols["src_ip"])
    flag = np.zeros(n, np.int8)
    flag[list(committed)] = 1
    ip = np.array(cols["dst_ip"], np.uint32)
    port = np.array(cols["dst_port"], np.int32)
    for lane, (a, p) in (dnat or {}).items():
        ip[lane], port[lane] = a, p
    return StepResult(code=np.zeros(n, np.int8), est=np.zeros(n, np.int8),
                      svc_idx=np.full(n, -1), dnat_ip=ip, dnat_port=port,
                      ingress_rule=[None] * n, egress_rule=[None] * n,
                      committed=flag, n_miss=0, reply=np.zeros(n, np.int8))

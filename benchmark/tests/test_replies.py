"""Reply legs as data: a cell whose generator listens to every answer and
labels its lanes (`fixtures/reply_flows.py`), whose reference reads the
labels (`fixtures/reply_reference.py`), added to a checkout as files and
manifest entries only (test_harness.py's `tree`, cell `tiny_replies.churn`).
Two faults of the reply path are cases of
test_harness.py::test_a_broken_timed_path_is_not_correct; `decide`'s
`reply_unanswered_share`, which the label `leg` turns on, is held here on a
hand-made sample and on the third, the fault it exists for: a program that
keeps no reply entries."""

import os
import time

import numpy as np
import pytest

import control  # noqa: E402
import correct  # noqa: E402
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
    # the reply lanes left unanswered, a share with its limit
    assert r["check"]["lanes_reply"] == {"value": int(reply.sum()),
                                         "limit": None}
    unanswered = r["check"]["reply_unanswered_share"]
    assert unanswered["value"] == pytest.approx(1 - answered.sum()
                                                / reply.sum())
    assert unanswered["value"] <= unanswered["limit"]


def test_the_control_of_a_reply_cell_is_not_correct(tree):
    r = run(tree, CELL, seed=7, after_check=control.read)
    assert r["correct"] is True
    assert r["control"]["correct"] is False
    assert r["control"]["wrong_lanes"] > 0


@pytest.mark.parametrize("seed", [5, 2**31 + 17, 914_202_611])
def test_a_program_with_no_reply_entries_fails_on_the_reply_number_alone(
        tree, seed):
    """The hole `reply_unanswered_share` closes: every reply answered as a
    packet of its own meets what the reference states of a reply whose
    entry the cache lost (`wrong_lanes` 0), and moves `remiss_share` by the
    reply lanes' share of the sent-before lanes only, inside its limit."""
    r = run(tree, "tiny_no_reply_entries.churn", seed=seed)
    assert r["correct"] is False and r["failed"] == 0
    over = [k for k, v in r["check"].items()
            if v["limit"] is not None and v["value"] > v["limit"]]
    assert over == ["reply_unanswered_share"]
    assert r["check"]["reply_unanswered_share"]["value"] == 1.0
    assert r["check"]["wrong_lanes"] == {"value": 0, "limit": 0}
    assert 0 < r["check"]["remiss_share"]["value"] < 0.25


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


# -- `decide` on a hand-made sample --------------------------------------------

class _Stated:
    """A reference whose statements hold on every lane: what `decide` reads
    beside them is its own arithmetic."""

    def failed_statements(self, s):
        s["ref_code"] = np.asarray(s["code"])
        return {"code": np.zeros(len(s["code"]), bool)}


# Eight lanes of two steps: the forward legs 1, 2, 5 and 6 (lane 2 carries a
# reply mark of its own), the reply legs 0, 3, 4 and 7, lane 3 answered as a
# packet of its own.
_SAMPLE = {
    "code": np.array([0, 0, 1, 0, 0, 2, 0, 0]),
    "est": np.array([1, 0, 0, 1, 1, 0, 0, 1]),
    "fresh": np.array([0, 1, 0, 0, 0, 0, 1, 0], bool),
    "svc_idx": np.array([-1, 3, -1, -1, 2, -1, -1, -1]),
    "reply": np.array([1, 0, 1, 0, 1, 0, 0, 1], np.int8),
}
_LEG = np.array([1, 0, 0, 1, 1, 0, 0, 1], np.int8)
_STEPS = {"n_miss": [5, 3], "lanes": [10, 10], "fresh_lanes": [2, 2],
          "allowed": 18, "established": 9}
_LIMITS = {"wrong_lanes": 0, "short_miss_steps": 0, "remiss_share": 0.25,
           "reply_unanswered_share": 0.3}
# The parent's `decide` on `_SAMPLE` without `leg` (taken on 5de7210).
_PARENTS = {
    "wrong_lanes": {"value": 0, "limit": 0},
    "short_miss_steps": {"value": 0, "limit": 0},
    "remiss_share": {"value": 0.25, "limit": 0.25},
    "window_allowed_share": {"value": 0.9, "limit": None},
    "window_established_share": {"value": 0.45, "limit": None},
    "lanes_compared": {"value": 8, "limit": None},
    "lanes_established": {"value": 4, "limit": None},
    "lanes_cached_denial": {"value": 2, "limit": None},
    "lanes_fresh": {"value": 2, "limit": None},
    "lanes_service": {"value": 2, "limit": None},
}


def _decide(leg=None, limits=_LIMITS):
    s = {k: v.copy() for k, v in _SAMPLE.items()}
    if leg is not None:
        s["leg"] = leg
    return correct.decide(_Stated(), s, _STEPS, None, limits)


def test_the_reply_number_is_over_the_reply_lanes_only():
    ok, numbers, notes = _decide(_LEG)
    # 1 of the 4 reply legs, where the sample's reply-0 share is 4 of 8
    assert numbers["reply_unanswered_share"] == {"value": 0.25,
                                                 "limit": 0.3}
    assert numbers["lanes_reply"] == {"value": 4, "limit": None}
    assert ok is True and notes == []
    assert list(numbers)[:4] == ["wrong_lanes", "short_miss_steps",
                                 "remiss_share", "reply_unanswered_share"]
    assert list(numbers)[-1] == "lanes_reply"
    ok, numbers, _ = _decide(_LEG, dict(_LIMITS,
                                        reply_unanswered_share=0.2))
    assert ok is False


def test_a_sample_without_leg_reads_what_the_parent_read():
    ok, numbers, _ = _decide()
    assert ok is True
    assert numbers == _PARENTS and list(numbers) == list(_PARENTS)


def test_a_labelled_sample_without_a_reply_lane_proves_nothing():
    with pytest.raises(ValueError, match="no reply lane sampled"):
        _decide(np.zeros(8, np.int8))


def test_a_labelled_sample_needs_the_reply_numbers_limit():
    limits = {k: v for k, v in _LIMITS.items()
              if k != "reply_unanswered_share"}
    assert _decide(limits=limits)[0] is True
    with pytest.raises(KeyError, match="reply_unanswered_share"):
        _decide(_LEG, limits)


@pytest.mark.parametrize("cell, replay", [("tiny.churn", True),
                                          ("tiny.steady", False)])
def test_the_accepted_mixes_print_the_parents_check(tree, cell, replay):
    """A mix whose generator labels no `leg` prints the parent's `check`,
    key for key and in its order."""
    r = run(tree, cell)
    assert list(r["check"]) == (
        ["wrong_lanes", "short_miss_steps", "remiss_share"]
        + ["replay_unhit_share"] * replay
        + ["window_allowed_share", "window_established_share",
           "lanes_compared", "lanes_established", "lanes_cached_denial",
           "lanes_fresh", "lanes_service"])

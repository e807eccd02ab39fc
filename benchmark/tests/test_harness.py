"""The benchmark's own tests (run by name, not part of tier-1):

    python -m pytest benchmark/tests -q

The harness end to end at a tiny world on the CPU backend (the wanted
platform is passed in; the command line has no such switch), the four-device
mesh rehearsal, the faults and the control that must turn `correct` false,
the trace reduction against a recorded trace, the byte count against hand
arithmetic, and the manifest check against seeded bad manifests.
"""

import copy
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

import check_manifest  # noqa: E402
import control  # noqa: E402
import correct  # noqa: E402
import harness  # noqa: E402
import reduce_trace  # noqa: E402
import work  # noqa: E402
import world as W  # noqa: E402
from manifest import Manifest, load_json  # noqa: E402
from reference import Reference  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY_CELLS = [
    # (cell, config fixture, traffic fixture, chips)
    ("tiny.churn", "tiny", "tiny_churn", 1),
    ("tiny.steady", "tiny", "tiny_steady", 1),
    ("tiny_mesh4.steady", "tiny_mesh4", "tiny_steady", 4),
    ("tiny_flip_code.churn", "tiny_flip_code", "tiny_churn", 1),
    ("tiny_half_batch.churn", "tiny_half_batch", "tiny_churn", 1),
    ("tiny_state_unchanged.churn", "tiny_state_unchanged", "tiny_churn", 1),
    # the same faults under the mix without arrivals (np100k.steady,
    # np100k.steady_b4k): no replay and no fresh lane to lean on
    ("tiny_flip_code.steady", "tiny_flip_code", "tiny_steady", 1),
    ("tiny_half_batch.steady", "tiny_half_batch", "tiny_steady", 1),
    ("tiny_state_unchanged.steady", "tiny_state_unchanged", "tiny_steady", 1),
    # reply legs (test_replies.py): a generator that listens and labels its
    # lanes, a reference that reads the labels, and three faults of the
    # reply path under the same mix (the third in test_replies.py)
    ("tiny_replies.churn", "tiny_replies", "tiny_churn_replies", 1),
    ("tiny_reply_unmarked.churn", "tiny_reply_unmarked", "tiny_churn_replies",
     1),
    ("tiny_reply_at_endpoint.churn", "tiny_reply_at_endpoint",
     "tiny_churn_replies", 1),
    ("tiny_no_reply_entries.churn", "tiny_no_reply_entries",
     "tiny_churn_replies", 1),
]
# Files a tiny cell names beside its configuration and mix: (file, folder).
PLACED = [("reply_flows.py", "generators"),
          ("reply_reference.py", "references")]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A checkout with cells, configurations, traffic mixes and a layer
    reader ADDED as files and manifest entries — no file of the harness is
    edited, which is what a later PR is held to."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, folder in PLACED:
        shutil.copy(os.path.join(FIXTURES, name), root / "benchmark" / folder)
    doc = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for cell, config, traffic, chips in TINY_CELLS:
        shutil.copy(os.path.join(FIXTURES, f"{config}.json"),
                    root / "benchmark" / "configs")
        shutil.copy(os.path.join(FIXTURES, f"{traffic}.json"),
                    root / "benchmark" / "traffic")
        if not any(c["name"] == config for c in doc["configs"]):
            doc["configs"].append({
                "name": config, "source": "benchmark/tests/fixtures",
                "file": f"benchmark/configs/{config}.json", "reduced": [],
                "why": "fixture"})
        doc["workloads"].append({"name": cell, "config": config,
                                 "traffic": traffic, "chips": chips,
                                 "why": "fixture"})
    shutil.copy(os.path.join(FIXTURES, "fixture.steps.py"),
                root / "benchmark" / "layers")
    tiny = [c[0] for c in TINY_CELLS]
    doc["per_layer"].append({
        "name": "fixture.steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "client",
        "moves": "served_pps", "workloads": tiny})
    for m in doc["per_layer"]:
        if "workloads" in m and m["name"] != "fixture.steps":
            m["workloads"] = m["workloads"] + tiny
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(doc, f)
    assert check_manifest.check(doc, str(root)) == []
    return Manifest(str(root / "BENCHMARK.json"))


def run(tree, cell, trace=False, seed=5, seconds=1.5, **kw):
    return harness.run_cell(
        cell, seed, seconds, trace, platform="cpu", manifest=tree,
        peaks=load_json(os.path.join(FIXTURES, "peaks_cpu.json")), **kw)


# -- the yardstick's own parts ------------------------------------------------

def test_world_is_the_programs_generator_seed_for_seed():
    from antrea_tpu.simulator import gen_cluster, gen_services

    params = load_json(os.path.join(FIXTURES, "tiny.json"))["world"]
    w = W.build_world(params, seed=1)
    ps, services = W.to_program(w)
    theirs = gen_cluster(params["n_rules"], n_nodes=params["n_nodes"],
                         pods_per_node=params["pods_per_node"], seed=1)
    assert theirs.ps == ps and theirs.pod_ips == w.pods
    assert gen_services(params["n_services"], theirs.pod_ips,
                        seed=2) == services


@pytest.mark.parametrize("kw", [
    {}, {"acnp_fraction": 1.0, "cidr_fraction": 0.5}])
def test_reference_agrees_with_the_programs_oracle(kw):
    from antrea_tpu.oracle.interpreter import Oracle
    from antrea_tpu.packet import Packet

    w = W.build_world(dict(n_rules=800, n_nodes=4, pods_per_node=8,
                           n_services=0, **kw), seed=3)
    ps, _ = W.to_program(w)
    rng = np.random.default_rng(0)
    pods = np.array(w.pods, np.int64)
    n = 600
    src, dst = rng.choice(pods, n), rng.choice(pods, n)
    src[:60] = rng.integers(0, 1 << 32, 60)
    proto = rng.choice([6, 17], n)
    dport = rng.choice([80, 443, 8080, 53, 5432, 1500], n)
    code, by, rule = Reference(w).classify(src, dst, proto, dport)
    oracle = Oracle(ps)
    for i in range(n):
        v = oracle.classify(Packet(int(src[i]), int(dst[i]), int(proto[i]),
                                   1234, int(dport[i])))
        assert int(v.code) == code[i]
        if v.code != 0:
            want = v.egress.rule if v.egress.code != 0 else v.ingress.rule
            assert rule[i] == want
    assert len(set(code.tolist())) > 1


def test_fresh_flows_are_never_sent_twice():
    mix = load_json(os.path.join(FIXTURES, "tiny_churn.json"))
    w = W.build_world(load_json(os.path.join(FIXTURES, "tiny.json"))["world"],
                      seed=1)
    gen = harness.load_module(os.path.join(BENCH, "generators",
                                           "policy_flows.py"))
    t = gen.Traffic(mix, w, 2**31 + 17, Reference(w))
    seen = set()
    for hot in t.ring:
        seen |= set(zip(*(hot[c].tolist() for c in (
            "src_ip", "dst_ip", "proto", "src_port", "dst_port"))))
    n_hot = len(seen)
    for _ in range(300):  # runs through a refill of the pool
        cols, lanes, fresh = t.next_batch()
        flows = list(zip(*(cols[c][t.fresh_at].tolist() for c in (
            "src_ip", "dst_ip", "proto", "src_port", "dst_port"))))
        assert not seen & set(flows) and len(set(flows)) == len(flows)
        seen |= set(flows)
        assert (fresh == np.isin(lanes, t.fresh_at)).all()
        assert fresh.sum() == len(fresh) // 2
    assert t.refills > 1 and len(seen) == n_hot + 300 * t.fresh_lanes


def test_work_counts_the_bytes_by_hand():
    # 1000 lanes x (20 in + 32 row + 4 stamp + 16 out) + 10 misses x 32.
    assert work.step_bytes(1000, 10) == 1000 * 72 + 320
    assert work.least_seconds(1000, 10, 1e9) == pytest.approx(72320e-9)


def test_reduce_trace_on_the_recorded_trace():
    loaded = load_json(os.path.join(FIXTURES, "trace_small.json"))
    want = loaded.pop("hand_computed")
    r = reduce_trace.reduce(loaded)
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    ms = reduce_trace.step_device_ms(
        r, {"trace": {"step_modules": "pipeline_step_full"}})
    assert len(r["steps"]) == want["steps"]
    assert ms["all"] == pytest.approx(1e3 * want["step_busy_s"] / want["steps"])
    assert ms["while"] == pytest.approx(
        1e3 * want["step_while_s"] / want["steps"])
    assert reduce_trace.step_device_ms(
        r, {"trace": {"step_modules": "no_such_module"}}) is None
    assert 100 * (1 - r["busy_s"] / r["window_s"]) == pytest.approx(
        want["idle_share"])
    assert r["top_gaps"][0][0] == want["longest_gap_in"]


def test_reduce_reads_a_sharded_module_per_chip():
    """Four replicas run the step module at the same time.  Chip 0 runs it
    0-10 ms (its loop 2-6), chip 1 5-15 (5-9), chip 2 0-20 (no loop), chip 3
    10-12 (10-12): 10 + 10 + 20 + 2 over four chips is 10.5 ms a step, 2.5
    of it in a loop.  The union ACROSS chips (what `reduce` took until PR 33)
    is 20 ms, over four chips 5 (and 2.25): under every replica's own time
    but one."""
    ms = 1_000_000
    ops = []
    for chip, (a, b), loop in ((0, (0, 10), (2, 6)), (1, (5, 15), (5, 9)),
                               (2, (0, 20), None), (3, (10, 12), (10, 12))):
        ops.append([chip, "fusion.1", a * ms, (b - a) * ms, "jit_step", False])
        if loop:
            ops.append([chip, "while.2", loop[0] * ms,
                        (loop[1] - loop[0]) * ms, "jit_step", True])
    ops.append([1, "copy.9", 21 * ms, 2 * ms, "jit_other", False])
    r = reduce_trace.reduce({"ops": ops,
                             "spans": [["bench.step", 0, 25 * ms]]})
    assert r["chips"] == 4
    got = reduce_trace.step_device_ms(r, {"trace": {"step_modules": "step"}})
    assert got["all"] == pytest.approx((10 + 10 + 20 + 2) / 4)
    assert got["while"] == pytest.approx((4 + 4 + 0 + 2) / 4)
    assert r["modules"]["jit_other"]["busy_s"] == pytest.approx(2e-3 / 4)
    # the device's busy time was per chip before, and stays: 10, 12, 20, 2
    assert r["busy_s"] == pytest.approx((10 + 12 + 20 + 2) / 4 * 1e-3)
    # a replica's lanes over a replica's time: the roofline reader's count
    reader = harness.load_module(os.path.join(BENCH, "layers",
                                              "step_roofline.py"))
    w = harness.Window()
    w.lanes, w.n_miss = [4000], [40]
    share = reader.read({"reduced": r, "window": w,
                         "config": {"trace": {"step_modules": "step"}},
                         "peak": {"hbm_bytes_per_s": 1e9}})
    assert share == pytest.approx(
        100 * work.least_seconds(1000, 10, 1e9) * 1e3 / 10.5)


# -- the manifest check -------------------------------------------------------

def _bad(doc, edit):
    doc = copy.deepcopy(doc)
    edit(doc)
    return doc


@pytest.mark.parametrize("edit, says", [
    (lambda d: d["per_layer"][2].update(layer="commit plane"), "layer"),
    (lambda d: d["per_layer"][0].update(name="client gen"), "name"),
    (lambda d: d["workloads"][0].update(name="np100k/churn"), "name"),
    (lambda d: d["end_to_end"][0].update(unit="pkt per second"), "unit"),
    (lambda d: d["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda d: d["per_layer"][0].update(why="because"), "extra"),
    (lambda d: d["configs"][0].update(source="x" * 201), "source"),
    (lambda d: d.update(run_seconds=52), "run_seconds"),
    (lambda d: d.update(run_seconds=2.5), "run_seconds"),
    (lambda d: d["end_to_end"][0].update(bound=0.3), "bound"),
    (lambda d: d["workloads"][0].update(traffic="nowhere"), "traffic file"),
    (lambda d: d["per_layer"].append(dict(d["per_layer"][0], name="x.y")),
     "reader"),
    (lambda d: d["configs"][0].update(file="bench.py"), "under paths"),
    (lambda d: d["end_to_end"].pop(2), "setup_s"),
    (lambda d: d["workloads"][0].update(chips=2), "chips"),
])
def test_check_manifest_refuses(edit, says):
    doc = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert check_manifest.check(doc, ROOT) == []
    faults = check_manifest.check(_bad(doc, edit), ROOT)
    assert faults and any(says in f for f in faults), faults


# -- the harness end to end ---------------------------------------------------

@pytest.mark.parametrize("cell", ["tiny.churn", "tiny.steady"])
def test_run_cell_prints_the_contracts_line(tree, cell, capsys):
    r = run(tree, cell)
    line = json.loads(json.dumps(r))  # what run.py prints
    assert RESULT_KEYS <= set(line) and list(line)[-1] == "check"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 256 * line["steps"] > 0
    assert set(line["metrics"]) == {"served_pps", "verdict_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    check = line["check"]
    assert check["wrong_lanes"] == {"value": 0, "limit": 0}
    for kind in ("lanes_established", "lanes_cached_denial", "lanes_service"):
        assert check[kind]["value"] > 0  # the sample covered that path
    assert (check["lanes_fresh"]["value"] > 0) == cell.endswith("churn")
    assert ("replay_unhit_share" in check) == cell.endswith("churn")
    # The numbers compared, each beside its limit, end standard error.
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("[bench] correct = True")
    assert any("wrong_lanes = 0 (limit 0)" in ln for ln in err[-12:])


def test_traced_run_reads_every_layer_and_an_added_reader(tree):
    r = run(tree, "tiny.churn", trace=True, seconds=6.0)
    assert r["correct"] is True
    asked = {m["name"] for m in tree.metrics_of("tiny.churn", "per_layer")}
    # The CPU backend's trace does not nest a loop's body inside its while
    # event, so the slow path's device time has nothing to read there (the
    # reader then returns nothing and the line leaves it out); the recorded
    # TPU trace above holds the split.
    assert "fixture.steps" in asked
    assert asked - set(r["metrics"]) <= {"slowpath.device_ms"}
    assert set(r["metrics"]) <= asked
    assert 0 < r["device"]["busy_s"] < r["device"]["window_s"]
    assert r["metrics"]["slowpath.miss_share"]["value"] > 0
    assert r["metrics"]["step.device_ms"]["value"] > 0
    assert 0 <= r["metrics"]["device.idle_share"]["value"] < 100
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert {g[0] for g in r["breakdown"]["idle_gaps"]} <= {
        "step", "assemble", "between"}
    assert not os.path.exists(os.path.join(tree.root, ".bench_trace"))


def test_mesh_cell_on_four_virtual_devices(tree):
    r = run(tree, "tiny_mesh4.steady")
    assert r["correct"] is True and r["device"]["count"] == 4
    assert r["check"]["wrong_lanes"]["value"] == 0


@pytest.mark.parametrize("fault, mix, number", [
    ("flip_code", "churn", "wrong_lanes"),
    ("half_batch", "churn", "wrong_lanes"),
    ("half_batch", "churn", "short_miss_steps"),
    ("state_unchanged", "churn", "replay_unhit_share"),
    ("flip_code", "steady", "wrong_lanes"),
    ("half_batch", "steady", "wrong_lanes"),
    ("state_unchanged", "steady", "remiss_share"),
    # under tiny_churn_replies (TINY_CELLS)
    ("reply_unmarked", "churn", "wrong_lanes"),
    ("reply_unmarked", "churn", "reply_unanswered_share"),
    ("reply_at_endpoint", "churn", "wrong_lanes"),
])
def test_a_broken_timed_path_is_not_correct(tree, fault, mix, number):
    r = run(tree, f"tiny_{fault}.{mix}")
    assert r["correct"] is False
    n = r["check"][number]
    assert n["value"] > n["limit"]
    if fault == "state_unchanged":
        assert r["check"]["remiss_share"]["value"] == 1.0
        assert r["check"]["lanes_established"]["value"] == 0
        assert n["value"] == 1.0


def test_the_control_is_not_correct(tree):
    r = run(tree, "tiny.churn", after_check=control.read)
    assert r["correct"] is True
    assert r["control"]["correct"] is False
    assert r["control"]["wrong_lanes"] > 0


def test_the_gate(tree):
    with pytest.raises(harness.NoDevice):
        harness.run_cell("tiny.churn", 1, 1.0, False, manifest=tree)
    with pytest.raises(harness.NoDevice):
        harness.device_gate("cpu", 8, {"cpu": {}})
    with pytest.raises(harness.NoDevice):
        harness.device_gate("cpu", 1, {"TPU v5 lite": {}})


def test_command_line_lets_no_cpu_through():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "np100k.steady", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "nothing was run" in p.stderr


def test_the_mix_is_the_stated_one_on_every_seed(tree):
    gen = harness.load_module(os.path.join(BENCH, "generators",
                                           "policy_flows.py"))
    mix = load_json(os.path.join(FIXTURES, "tiny_steady.json"))
    shares = gen.class_shares(mix)
    assert sum(shares.values()) == pytest.approx(1.0)
    weights = np.arange(1, 4097, dtype=float) ** -mix["zipf_s"]
    cls = gen._class_of_rank(weights, shares)
    assert cls[0] == ("pod", True)
    for c, share in shares.items():
        got = sum(w for w, x in zip(weights, cls) if x == c) / weights.sum()
        assert got == pytest.approx(share, abs=0.01)
    # End to end: the window's allowed share is 1 - denied_share whatever
    # the seed, and the comparison agrees with every verdict.
    for seed in (11, 2**31 + 12):
        r = run(tree, "tiny.steady", seed=seed)
        assert r["correct"] is True
        assert r["check"]["window_allowed_share"]["value"] == pytest.approx(
            1 - mix["denied_share"], abs=0.03)
        assert r["check"]["window_established_share"]["value"] > 0.8


def test_a_denied_class_that_nobody_proposed_goes_without():
    gen = harness.load_module(os.path.join(BENCH, "generators",
                                           "policy_flows.py"))
    mix = load_json(os.path.join(FIXTURES, "tiny_churn.json"))
    w = W.build_world(load_json(os.path.join(FIXTURES, "tiny.json"))["world"],
                      seed=1)
    real = gen._classes

    def none_external(*args):
        out = real(*args)
        rows, named = out["ext", False]
        out["ext", False] = rows[:0], named[:0]
        rows, named = out["svc", False]
        out["svc", False] = rows[:1], named[:1]  # and one short class
        return out

    gen._classes = none_external
    t = gen.Traffic(mix, w, 3, Reference(w))
    assert "ext-" not in t.summary and "svc- 1/1" in t.summary
    assert "pod- " in t.summary and "pod+" in t.summary
    full = harness.load_module(os.path.join(
        BENCH, "generators", "policy_flows.py")).Traffic(mix, w, 3,
                                                          Reference(w))
    # The other classes keep their sizes: a short class is short alone.
    assert [x for x in full.summary.split(", ") if "pod" in x] == [
        x for x in t.summary.split(", ") if "pod" in x]


# -- the generator's optional parts: observe and lane labels ------------------

class _Echo:
    """A fake engine: answers every lane from its own columns and keeps each
    result; the `fail_at`-th call raises, the `stop_at`-th sleeps `stop_s`
    (so that a window ends right after it)."""

    def __init__(self, fail_at=None, stop_at=None, stop_s=0.0, step_s=0.0):
        self.results, self.batches, self.calls = [], [], 0
        self.fail_at, self.stop_at, self.stop_s = fail_at, stop_at, stop_s
        self.step_s = step_s

    def step(self, batch, now):
        from antrea_tpu.datapath.interface import StepResult

        self.calls += 1
        if self.calls == self.fail_at:
            raise RuntimeError("a planted failure of the served path")
        self.batches.append({k: np.asarray(v) for k, v in vars(batch).items()
                             if v is not None})
        n = batch.size
        d = np.asarray(batch.dst_port, np.int64)
        res = StepResult(
            code=(d % 3).astype(np.int8), est=(d % 2).astype(np.int8),
            svc_idx=np.full(n, -1), dnat_ip=np.asarray(batch.dst_ip),
            dnat_port=np.asarray(batch.dst_port), ingress_rule=[None] * n,
            egress_rule=[None] * n, committed=np.zeros(n, np.int8),
            n_miss=n // 8, reply=np.zeros(n, np.int8),
            reject_kind=np.zeros(n, np.int8), snat=np.zeros(n, np.int8))
        self.results.append(res)
        time.sleep(self.step_s)
        if self.calls == self.stop_at:
            time.sleep(self.stop_s)
        return res


class _Listener:
    """A fake generator with both optional parts: batch k's source address
    is k on every lane, label `mark` is 1000 k + the lane, and `observe`
    keeps what it heard (and sleeps `listen_s`)."""

    batch = 16
    fresh_lanes = 1
    fresh_at = np.array([0])
    lanes = np.array([0, 3, 7, 15])

    def __init__(self, label="mark", listen_s=0.0, warm=3):
        self.label, self.listen_s, self.warm = label, listen_s, warm
        self.built, self.heard = 0, []

    def _cols(self):
        k, n = self.built, self.batch
        self.built += 1
        return {"src_ip": np.full(n, k, np.uint32),
                "dst_ip": np.arange(n, dtype=np.uint32) + 100,
                "proto": np.full(n, 6, np.int32),
                "src_port": np.full(n, 1024 + k, np.int32),
                "dst_port": np.arange(n, dtype=np.int32) + 80}

    def warmup(self):
        for _ in range(self.warm):
            yield self._cols()

    def next_batch(self):
        k = self.built
        return (self._cols(), self.lanes, self.lanes == 0,
                {self.label: 1000 * k + np.arange(self.batch)})

    def observe(self, cols, res):
        self.heard.append((cols, res))
        time.sleep(self.listen_s)


def test_observe_hears_every_served_step_in_order_and_no_other():
    gen, eng = _Listener(), _Echo(fail_at=3 + 5)
    t = time.perf_counter()
    harness.warm_up(eng, gen, t)
    w = harness.run_window(eng, gen, 30.0, t, None)
    assert w.error and "planted failure" in w.error  # the window's 5th step
    assert len(w.t_verdict) == 4 and len(eng.results) == 3 + 4
    # once a served step, its own result, in order, the warm-up's too; not
    # the step that raised
    assert [res for _, res in gen.heard] == eng.results
    assert [int(cols["src_ip"][0]) for cols, _ in gen.heard] == list(range(7))
    assert all(b["src_ip"][0] == k for k, b in enumerate(eng.batches))
    assert harness.replay_last(eng, w, gen, t) is not None
    assert len(eng.results) == 8 and len(gen.heard) == 7  # not the replay


def test_observe_is_the_clients_time_and_not_the_steps():
    """Listening 20 ms a step against 2 ms steps: the verdict's interval
    never holds it, the client's share of the loop does."""
    gen_share = harness.load_module(
        os.path.join(BENCH, "layers", "client.gen_share.py")).read
    shares = []
    for listen_s in (0.0, 0.02):
        gen, eng = _Listener(listen_s=listen_s), _Echo(step_s=0.002)
        w = harness.run_window(eng, gen, 0.6, time.perf_counter(), None)
        assert len(gen.heard) == len(w.t_verdict) > 5
        waits = np.subtract(w.t_verdict, w.t_handoff)
        assert waits.max() < 0.015
        listened = np.subtract(w.t_done, w.t_verdict)
        assert (listened >= listen_s).all()
        assert harness.end_to_end(w, 1.0)["verdict_p95_ms"] < 15
        shares.append(gen_share({"window": w}))
    assert shares[0] < 60 and shares[1] > 80


def test_labels_reach_the_sample_at_the_sampled_lanes():
    gen, eng = _Listener(), _Echo(stop_at=4, stop_s=1.0)
    w = harness.run_window(eng, gen, 0.8, time.perf_counter(), None)
    assert len(w.t_verdict) == 4
    s = w.sampled()
    want = np.concatenate([1000 * k + gen.lanes for k in range(4)])
    assert (s["mark"] == want).all()
    # beside the columns and the answers of the same lanes
    assert (s["src_ip"] == np.repeat(np.arange(4), len(gen.lanes))).all()
    assert (s["dst_port"] == np.tile(gen.lanes + 80, 4)).all()
    assert (s["fresh"] == np.tile(gen.lanes == 0, 4)).all()
    assert len(s["code"]) == len(want)


@pytest.mark.parametrize("name", [
    "src_ip", "in_port", "pkt_len", "code", "dnat_ip", "fresh", "ref_code",
    "ref_rule"])
def test_a_label_that_takes_a_sample_name_is_refused(name):
    gen, eng = _Listener(label=name), _Echo()
    with pytest.raises(ValueError, match=repr(name)):
        harness.run_window(eng, gen, 5.0, time.perf_counter(), None)
    assert len(eng.results) == 1  # at the first step


# Taken on the parent tree (3f00b88, before observe and lane labels): every
# batch that the warm-up and the first four window steps handed the engine,
# and the window's sample without the rule lists, through a fake engine.
GOLDEN_LOOP = {
    ("tiny", "tiny_churn", 5): ("a69c18e6f2bdfbdb", "9b91df60565504e0"),
    ("tiny", "tiny_steady", 2**31 + 17): ("62df4ca800b195a0",
                                          "95de589b9a3ddb67"),
    ("tiny_dual_stack", "tiny_churn_ds", 5): ("45f01a31ea309ccc",
                                              "7188ff7198eaf51b"),
    ("tiny_xlarge", "tiny_churn_xl", 2**31 + 17): ("7dda55063c4424d5",
                                                   "4d778ea4f71b89c7"),
}


@pytest.mark.parametrize("config, mix, seed", sorted(GOLDEN_LOOP))
def test_the_accepted_generators_send_what_they_sent_through_the_loop(
        config, mix, seed):
    """policy_flows, dual_stack_flows and namespace_flows define neither
    optional part: through warm_up and the window they hand the engine the
    same batches, and the sample holds the same lanes, as before both."""
    from test_seam import _digest

    m = Manifest()
    cfg = load_json(os.path.join(FIXTURES, f"{config}.json"))
    params = load_json(os.path.join(FIXTURES, f"{mix}.json"))
    world = harness.load_module(m.world_path(cfg)).build_world(
        cfg["world"], cfg["world_seed"])
    ref = harness.load_module(m.reference_path(cfg)).Reference(world)
    traffic = harness.load_module(m.generator_path(
        params["generator"])).Traffic(params, world, seed, ref)
    assert not hasattr(traffic, "observe")
    t = time.perf_counter()
    eng = _Echo(stop_s=2.0)
    harness.warm_up(eng, traffic, t)
    eng.stop_at = eng.calls + 4
    w = harness.run_window(eng, traffic, 2.0, t, None)
    assert len(w.t_verdict) == 4
    s = {k: v for k, v in w.sampled().items() if v.dtype != object}
    assert (_digest(*eng.batches), _digest(s)) == GOLDEN_LOOP[
        config, mix, seed]

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
]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A checkout with cells, configurations, traffic mixes and a layer
    reader ADDED as files and manifest entries — no file of the harness is
    edited, which is what a later PR is held to."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
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

"""The xLargeScale cluster as the harness takes it: files found by name
(`worlds/small_namespaces.py`, `references/small_namespaces.py`,
`generators/namespace_flows.py`, the mix, the configuration, two layer
readers) and manifest entries, no file of the harness edited.

  * the world is the source's shape: its three totals at the configuration's
    own size, and per namespace the objects `bench_controller.populate`
    makes;
  * the reference equals `reference.Reference` lane for lane, rule ids and
    named directions included, on this world and on the first deployments'
    (tiers, ipBlocks, Services), and the program's scalar `Oracle` on this
    one;
  * a whole CPU cell run reads `correct` true with no Service lane, the
    control (half the policy) and planted faults read false;
  * both new readers return a value on the change's engine and nothing on
    an engine whose tracer lacks the keys;
  * the generator sends no Service flow, gives every seed the same classes
    at the same ranks and never sends a fresh flow twice;
  * the cell, its configuration and its two metrics pass `check_manifest`.
"""

import json
import os
import shutil

import numpy as np
import pytest

import check_manifest  # noqa: E402
import control  # noqa: E402
import harness  # noqa: E402
import world as W  # noqa: E402
from manifest import Manifest, load_json, load_module  # noqa: E402
from reference import Reference as DenseReference  # noqa: E402
from test_harness import BENCH, FIXTURES, ROOT, run, tree  # noqa: E402,F401

CONFIG, MIX = "tiny_xlarge", "tiny_churn_xl"
FAULTS = {  # cell -> the engine's entry
    "tiny_xl_flip.churn": "broken.flip_code",
    "tiny_xl_unchanged.churn": "broken.state_unchanged",
}
NEW_METRICS = ("commit.upload_s", "commit.table_bytes")


@pytest.fixture(scope="module")
def xl_tree(tree):  # noqa: F811
    """The harness test's checkout plus the tiny configuration, its mix,
    their cell and one cell a planted fault: files and entries."""
    root = tree.root
    doc = load_json(tree.path)
    config = load_json(os.path.join(FIXTURES, f"{CONFIG}.json"))
    shutil.copy(os.path.join(FIXTURES, f"{MIX}.json"),
                os.path.join(root, "benchmark", "traffic"))
    cells = {f"{CONFIG}.churn": config}
    for cell, entry in FAULTS.items():
        cells[cell] = dict(config, engine=dict(config["engine"], entry=entry,
                                               args=[]))
    for cell, cfg in cells.items():
        name = cell.split(".")[0]
        with open(os.path.join(root, "benchmark", "configs",
                               f"{name}.json"), "w") as f:
            json.dump(cfg, f)
        doc["configs"].append({
            "name": name, "source": "benchmark/tests/fixtures",
            "file": f"benchmark/configs/{name}.json", "reduced": [],
            "why": "fixture"})
        doc["workloads"].append({"name": cell, "config": name,
                                 "traffic": MIX, "chips": 1,
                                 "why": "fixture"})
    for m in doc["per_layer"]:
        if "workloads" in m and "tiny.churn" in m["workloads"]:
            m["workloads"] = m["workloads"] + [f"{CONFIG}.churn"]
    with open(tree.path, "w") as f:
        json.dump(doc, f)
    assert check_manifest.check(doc, root) == []
    return Manifest(tree.path)


@pytest.fixture(scope="module")
def tiny():
    """(world, its reference, the generator module, the program's input)."""
    m = Manifest()
    config = load_json(os.path.join(FIXTURES, f"{CONFIG}.json"))
    assert m.world_path(config) == os.path.join(BENCH, "worlds",
                                                "small_namespaces.py")
    worlds = load_module(m.world_path(config))
    w = worlds.build_world(config["world"], config["world_seed"])
    ref = load_module(m.reference_path(config)).Reference(w)
    return w, ref, load_module(m.generator_path("namespace_flows")), \
        worlds.to_program(w)


def _lanes(gen, w, ref, seed, n=2048):
    """Proposals of the generator's two kinds, a fault's neighbours among
    them: (src, dst, proto, dport) columns."""
    rng = np.random.default_rng(seed)
    members = np.array([[W.ip_u32(ip) for ip, _, _ in g] for g in w.groups],
                       np.int64)
    rows = np.concatenate([gen._from_rules(rng, ref, members, n),
                           gen._flows._uniform(rng, ref.pods, n // 2, 0.5)])
    return rows.T


def test_the_world_has_the_sources_shape(tiny):
    w, _, _, (ps, services) = tiny
    config = load_json(os.path.join(BENCH, "configs", "xlarge75k.json"))
    full = load_module(os.path.join(BENCH, "worlds", "small_namespaces.py")
                       ).build_world(config["world"], config["world_seed"])
    assert (len(full.policies), len(full.pods), len(set(full.pods)),
            len(full.groups), len(full.services), len(full.nodes)) == (
        75000, 100000, 100000, 50000, 0, 64)
    assert len({p.namespace for p in full.policies}) == 25000
    # one namespace, object for object, as bench_controller.populate makes it
    i = 300
    assert [W.ip_str(ip) for ip in full.pods[4 * i:4 * i + 4]] == [
        f"10.1.44.{j + 1}" for j in range(4)]
    app0, app1 = full.groups[2 * i], full.groups[2 * i + 1]
    assert [m[0] for m in app0] == ["10.1.44.1", "10.1.44.3"]
    assert [m[0] for m in app1] == ["10.1.44.2", "10.1.44.4"]
    assert [m[1] for m in app0 + app1] == [
        f"node-{(4 * i + j) % 64}" for j in (0, 2, 1, 3)]
    pols = full.policies[3 * i:3 * i + 3]
    assert [p.uid for p in pols] == [f"np-{i}-{k}" for k in range(3)]
    assert [(p.applied_to, p.rules[0].peer) for p in pols] == [
        (2 * i, ("group", 2 * i + 1)), (2 * i + 1, ("group", 2 * i)),
        (2 * i, ("group", 2 * i + 1))]
    for p in pols:
        assert (p.kind, p.namespace, p.policy_types) == (
            "knp", f"ns-{i}", ("In",))
        assert p.rules == (W.Rule("In", p.rules[0].peer, ((6, 80, None),),
                                  "Allow", -1),)
    # the seed draws nothing; the tiny world is the same builder
    assert full.policies[:144] == w.policies and full.groups[:96] == w.groups
    assert services == [] and len(ps.policies) == 144
    assert len(ps.address_groups) == len(ps.applied_to_groups) == 96


def test_the_reference_is_the_first_deployments_lane_for_lane(tiny):
    """Sparse membership states what the dense matrix states: on this world,
    and on the first deployments' own (tiers, Baseline, ipBlocks with
    excepts, port ranges, Services), with half the policy too."""
    w, ref, gen, _ = tiny
    sparse = type(ref)
    first = W.build_world(load_json(os.path.join(FIXTURES, "tiny.json"))[
        "world"], 1)
    rng = np.random.default_rng(7)
    lanes_first = np.concatenate([
        gen._flows._uniform(rng, np.array(sorted(set(first.pods))), 1500,
                            0.8),
        np.stack([rng.choice(first.pods, 500), rng.choice(first.pods, 500),
                  np.full(500, 6), rng.choice([80, 443, 8080], 500)],
                 axis=1)]).T
    for world, lanes in ((w, _lanes(gen, w, ref, 3)), (first, lanes_first)):
        for keep in (None, lambda i: i % 2 == 0):
            a, b = sparse(world, keep), DenseReference(world, keep)
            for x, y in zip(a.classify(*lanes), b.classify(*lanes)):
                assert (x == y).all()
            for x, y in zip(a.classify_named(*lanes),
                            b.classify_named(*lanes)):
                assert (x == y).all()
            for x, y in zip(a.resolve(*lanes[1:]), b.resolve(*lanes[1:])):
                assert (x == y).all()
            for d in ("In", "Out"):
                assert (a.isolated[d] == b.isolated[d]).all()
                for pa, pb in zip(a.phases[d], b.phases[d]):
                    assert (pa.ids == pb.ids).all()
    code, by, rule = sparse(first).classify(*lanes_first)
    assert {0, 1, 2} <= set(code.tolist())  # the three verdicts were met,
    assert sum(r is not None for r in rule) > 50  # and named denials


def test_the_reference_agrees_with_the_programs_oracle(tiny):
    from antrea_tpu.oracle.interpreter import Oracle
    from antrea_tpu.packet import Packet

    w, ref, gen, (ps, _) = tiny
    src, dst, proto, dport = _lanes(gen, w, ref, 11, n=600)[:, :900]
    code, by, rule = ref.classify(src, dst, proto, dport)
    _, named = ref.classify_named(src, dst, proto, dport)
    oracle = Oracle(ps)
    for i in range(len(code)):
        v = oracle.classify(Packet(int(src[i]), int(dst[i]), int(proto[i]),
                                   1234, int(dport[i])))
        assert int(v.code) == code[i]
        # isolation denies and names no rule; an allowing rule is named
        assert rule[i] is None and v.egress.rule is None
        assert (v.ingress.rule is not None) == (named[i] == 1)
    assert (code == 0).sum() > 100 and (code == 1).sum() > 100
    assert set(named.tolist()) == {0, 1}


def test_a_cpu_cell_run_is_correct_and_its_control_is_not(xl_tree, capsys):
    seen = {}

    def look(ctx):
        seen.update(ctx)
        return control.read(ctx)

    r = run(xl_tree, f"{CONFIG}.churn", after_check=look)
    line = json.loads(json.dumps({k: v for k, v in r.items()
                                  if k != "control"}))
    assert line["correct"] is True and line["failed"] == 0
    check = line["check"]
    assert check["wrong_lanes"] == {"value": 0, "limit": 0}
    assert check["short_miss_steps"]["value"] == 0
    for kind in ("lanes_established", "lanes_cached_denial", "lanes_fresh"):
        assert check[kind]["value"] > 0
    assert check["lanes_service"]["value"] == 0
    s = seen["sample"]
    assert (s["svc_idx"] == -1).all() and (s["dnat_ip"] == s["dst_ip"]).all()
    assert (s["ref_code"] != 0).sum() > 10  # denials were compared
    # an allowed lane names the ingress rule that allowed it, no egress one
    allowed_pod = (s["ref_code"] == 0) & np.array(
        [x is not None for x in s["ingress_rule"]])
    assert allowed_pod.sum() > 100
    assert all(x is None for x in s["egress_rule"])
    # the reference is the configuration's own, and so is the control
    assert type(seen["reference"]).__module__ == "bench_small_namespaces"
    assert r["control"]["correct"] is False
    assert r["control"]["wrong_lanes"] > 0
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("[bench] correct = True")


def test_a_traced_run_reads_the_upload_and_the_bytes(xl_tree, monkeypatch):
    import jax

    kept, build = {}, harness.build_engine

    def keep(config, devices):
        kept["engine"] = build(config, devices)
        return kept["engine"]

    monkeypatch.setattr(harness, "build_engine", keep)
    r = run(xl_tree, f"{CONFIG}.churn", trace=True, seconds=6.0)
    assert r["correct"] is True
    asked = {m["name"] for m in xl_tree.metrics_of(f"{CONFIG}.churn",
                                                   "per_layer")}
    assert set(NEW_METRICS) <= asked
    assert asked - set(r["metrics"]) <= {"slowpath.device_ms"}
    upload = r["metrics"]["commit.upload_s"]
    assert upload["unit"] == "s"
    assert 0 <= upload["value"] <= r["metrics"]["commit.compile_s"]["value"]
    engine = kept["engine"]
    assert r["metrics"]["commit.table_bytes"] == {"unit": "B", "value": float(
        sum(x.nbytes for x in jax.tree_util.tree_leaves(
            (engine._drs, engine._dsvc))))}


def test_the_new_readers_return_nothing_without_the_keys():
    """The parent's tracer has the four stages only: both readers say None
    and the line leaves the metrics out."""
    upload, nbytes = (load_module(os.path.join(BENCH, "layers", f"{m}.py"))
                      for m in NEW_METRICS)

    class Tracer:
        def __init__(self, last):
            self.last = last

        def last_commit(self):
            return self.last

    class Engine:
        def __init__(self, last):
            self.realization_tracer = Tracer(last)

    old = {"generation": 1, "compile_s": 2.0, "canary_s": 1.0, "swap_s": 0.0,
           "settle_s": 0.0}
    new = dict(old, upload_s=0.5, table_bytes=2344000000)
    assert upload.read({"engine": Engine(new)}) == 0.5
    assert nbytes.read({"engine": Engine(new)}) == 2344000000.0
    for engine in (Engine(old), Engine(None), object()):
        assert upload.read({"engine": engine}) is None
        assert nbytes.read({"engine": engine}) is None


@pytest.mark.parametrize("cell, number", [
    ("tiny_xl_flip.churn", "wrong_lanes"),
    ("tiny_xl_unchanged.churn", "replay_unhit_share"),
])
def test_a_planted_fault_is_not_correct(xl_tree, cell, number):
    r = run(xl_tree, cell)
    assert r["correct"] is False and r["failed"] == 0
    n = r["check"][number]
    assert n["value"] > n["limit"]


def test_the_generator_sends_the_stated_classes_and_no_service(tiny):
    w, ref, gen, _ = tiny
    mix = dict(load_json(os.path.join(FIXTURES, f"{MIX}.json")),
               universe_flows=4096)
    a = gen.Traffic(mix, w, 5, ref)
    b = gen.Traffic(mix, w, 2**31 + 12, ref)
    assert a.summary.startswith("templates pod+1 ")
    assert "svc" not in a.summary and "ext+0" in a.summary
    pods = set(ref.pods.tolist())
    for t in (a, b):
        lanes = np.concatenate([np.stack(
            [hot[c].astype(np.int64) for c in ("src_ip", "dst_ip", "proto",
                                               "dst_port")]) for hot in
            t.ring], axis=1)
        code, named = ref.classify_named(*lanes)
        both = np.array([s in pods and d in pods
                         for s, d in zip(*lanes[:2].tolist())])
        # the stated shares of the lanes, to the weight of the Zipf head
        assert np.mean(code != 0) == pytest.approx(0.1, abs=0.03)
        assert np.mean(both) == pytest.approx(0.8, abs=0.05)
        assert (named[(code == 0) & both] == 1).all()
        assert (named[~both] == 0).all()
        assert set(ref.resolve(*lanes[1:])[0].tolist()) == {-1}
    assert (a.ring[0]["dst_ip"] != b.ring[0]["dst_ip"]).any()
    with pytest.raises(ValueError, match="svc_fraction 0"):
        gen.Traffic(dict(mix, svc_fraction=0.3), w, 5, ref)


def test_fresh_flows_are_never_sent_twice(tiny):
    w, ref, gen, _ = tiny
    t = gen.Traffic(load_json(os.path.join(FIXTURES, f"{MIX}.json")), w,
                    2**31 + 17, ref)
    names = ("src_ip", "dst_ip", "proto", "src_port", "dst_port")

    def flows(cols, at=slice(None)):
        return set(map(tuple, np.stack(
            [cols[c][at] for c in names], axis=1).astype(np.int64).tolist()))

    seen = set().union(*(flows(hot) for hot in t.ring))
    n_hot = len(seen)
    for _ in range(300):  # runs through a refill of the pool
        cols, lanes, fresh = t.next_batch()
        new = flows(cols, t.fresh_at)
        assert not seen & new and len(new) == t.fresh_lanes
        seen |= new
    assert t.refills > 1 and len(seen) == n_hot + 300 * t.fresh_lanes


def test_the_cell_and_its_files_pass_the_manifest_check():
    doc = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert check_manifest.check(doc, ROOT) == []
    cell = next(c for c in doc["workloads"] if c["name"] == "xlarge75k.churn")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xlarge75k", "churn_xl", 1)
    assert "np100k.churn" in cell["why"]
    entry = next(c for c in doc["configs"] if c["name"] == "xlarge75k")
    for word in ("networkpolicy_controller_perf_test.go",
                 "TestInitXLargeScaleWithSmallNamespaces"):
        assert word in entry["source"]
    config = load_json(os.path.join(ROOT, entry["file"]))
    base = load_json(os.path.join(BENCH, "configs", "np100k.json"))
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    assert config["architecture"] is None
    assert config["world"]["n_services"] == 0
    assert config["engine"] == dict(base["engine"], kwargs={
        "flow_slots": 1 << 24})
    assert config["guarantees"] == base["guarantees"]
    assert config["world_seed"] == base["world_seed"]
    mix = load_json(os.path.join(BENCH, "traffic", "churn_xl.json"))
    churn = load_json(os.path.join(BENCH, "traffic", "churn.json"))
    assert set(mix) == set(churn)
    differ = {k for k in churn if mix[k] != churn[k]}
    assert differ == {"svc_fraction", "templates", "proposals",
                      "universe_flows", "generator", "source", "assumed"}
    assert (mix["svc_fraction"], mix["templates"], mix["proposals"],
            mix["universe_flows"], mix["generator"]) == (
        0, 32768, 131072, 1048576, "namespace_flows")
    for name in NEW_METRICS:
        m = next(m for m in doc["per_layer"] if m["name"] == name)
        assert m["workloads"] == ["xlarge75k.churn"]
        assert (m["layer"], m["moves"]) == ("commit", "setup_s")

"""The dual-stack node as the harness takes it: files found by name
(`worlds/dual_stack.py`, `references/dual_stack.py`,
`generators/dual_stack_flows.py`, the mix, the configuration, two layer
readers) and manifest entries, no file of the harness edited.

  * the reference equals the program's scalar `Oracle` lane for lane, rule
    ids included, on the tiny world, on either family;
  * a whole CPU cell run reads `correct` true, with v6 lanes, v6 denials and
    v4 Service lanes among the compared ones, and the traced run reads
    `entry.v6_lane_share`;
  * faults planted on v6 lanes only, and a state returned unchanged, turn it
    false; so does the control (half the policy);
  * the generator gives every class but the Service ones the stated v6
    share of its weight, the same ranks on every seed, and never sends a
    fresh flow twice;
  * the cell, its configuration and its two metrics pass `check_manifest`.
"""

import ipaddress
import json
import os
import shutil

import numpy as np
import pytest

import check_manifest  # noqa: E402
import control  # noqa: E402
from manifest import Manifest, load_json, load_module  # noqa: E402
from test_harness import BENCH, FIXTURES, ROOT, run, tree  # noqa: E402,F401

CONFIG, MIX = "tiny_dual_stack", "tiny_churn_ds"
FAULTS = {  # cell -> the engine's entry
    "tiny_ds_flip6.churn": "broken_ds.flip_code6",
    "tiny_ds_service6.churn": "broken_ds.service6",
    "tiny_ds_unchanged.churn": "broken.state_unchanged",
}
NEW_METRICS = ("entry.v6_lane_share", "slowpath.width_us")


@pytest.fixture(scope="module")
def ds_tree(tree):  # noqa: F811
    """The harness test's checkout plus the tiny dual-stack configuration,
    its mix, their cell and one cell a planted fault: files and entries."""
    root = tree.root
    doc = load_json(tree.path)
    config = load_json(os.path.join(FIXTURES, f"{CONFIG}.json"))
    shutil.copy(os.path.join(FIXTURES, f"{MIX}.json"),
                os.path.join(root, "benchmark", "traffic"))
    cells = {f"{CONFIG}.churn": config}
    for cell, entry in FAULTS.items():
        engine = dict(config["engine"], entry=entry, args=[])
        cells[cell] = dict(config, engine=engine)
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
        if m["name"] in NEW_METRICS:
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
                                                "dual_stack.py")
    worlds = load_module(m.world_path(config))
    w = worlds.build_world(config["world"], config["world_seed"])
    ref = load_module(m.reference_path(config)).Reference(w)
    return w, ref, load_module(m.generator_path("dual_stack_flows")), \
        worlds.to_program(w)


def test_the_world_is_np100ks_with_two_addresses_a_pod(tiny):
    import world as W

    w, _, _, _ = tiny
    params = load_json(os.path.join(FIXTURES, f"{CONFIG}.json"))["world"]
    share = params.pop("v6_cidr_share")
    plain = W.build_world(params, 1)
    assert (w.pods, w.nodes, w.services) == (plain.pods, plain.nodes,
                                             plain.services)
    assert len(w.pods6) == len(set(w.pods6)) == len(w.pods)
    pods6 = {str(ipaddress.IPv6Address(v)) for v in w.pods6}
    for g, g4 in zip(w.groups, plain.groups):
        assert g[:len(g4)] == g4  # the v4 members, then a twin of each
        assert len(g) == 2 * len(g4) and {m[0] for m in g[len(g4):]} <= pods6
        assert [m[1:] for m in g[len(g4):]] == [m[1:] for m in g4]
    blocks = [(r.peer, r4.peer) for p, p4 in zip(w.policies, plain.policies)
              for r, r4 in zip(p.rules, p4.rules) if r4.peer[0] == "cidr"]
    v6 = [(a, b) for a, b in blocks if ":" in a[1]]
    assert all(a == b for a, b in blocks if ":" not in a[1])
    assert abs(len(v6) / len(blocks) - share) < 0.08
    for (_, cidr, excepts), (_, cidr4, excepts4) in v6:
        net, net4 = (ipaddress.ip_network(c, strict=False)
                     for c in (cidr, cidr4))
        assert net.prefixlen == net4.prefixlen + 32
        assert net.subnet_of(ipaddress.ip_network("2001:db8::/32"))
        assert len(excepts) == len(excepts4)
        assert all(":" in x for x in excepts)  # one family a block


@pytest.mark.parametrize("family", [4, 6])
def test_the_reference_agrees_with_the_programs_oracle(tiny, family):
    from antrea_tpu.oracle.interpreter import Oracle
    from antrea_tpu.packet import Packet
    from antrea_tpu.utils import ip as iputil

    w, ref, gen, (ps, _) = tiny
    rng = np.random.default_rng(family)
    p = {"proposals": 2048, "pod_to_pod_fraction": 0.8}
    if family == 6:
        rows = np.concatenate([r for r, _ in gen._classes6(
            rng, w, ref, p).values()])
        src, dst = rows[:, 0:4], rows[:, 4:8]
        proto, dport = rows[:, 8], rows[:, 9]
    else:
        w4, ref4 = gen._as_v4_reads_it(w, ref)
        members = np.array([[int(ipaddress.ip_address(ip)) for ip, _, _ in g]
                            for g in w4.groups], np.int64)
        rows = np.concatenate([
            gen._flows._from_rules(rng, ref4, members,
                                   gen._flows._Services(w.services), 1024),
            gen._flows._uniform(rng, ref.pods, 512, 0.8)])
        src, dst, proto, dport = rows.T
    pick = rng.permutation(len(rows))[:600]
    src, dst, proto, dport = src[pick], dst[pick], proto[pick], dport[pick]
    code, by, rule = ref.classify(src, dst, proto, dport)

    def key(a):
        if family == 4:
            return int(a)
        return iputil.V6_OFF + int.from_bytes(
            np.asarray(a, ">u4").tobytes(), "big")

    oracle = Oracle(ps)
    for i in range(len(pick)):
        v = oracle.classify(Packet(key(src[i]), key(dst[i]), int(proto[i]),
                                   1234, int(dport[i])))
        assert int(v.code) == code[i]
        if v.code != 0:
            want = v.egress.rule if v.egress.code != 0 else v.ingress.rule
            assert rule[i] == want
    assert len(set(code.tolist())) > 1 and (code != 0).sum() > 50


def test_a_block_of_one_family_matches_no_packet_of_the_other(tiny):
    """A v6 address and the v4 address with the same low bits are two
    addresses: the v4 reading of a rule with a v6 ipBlock is empty, and the
    other way round."""
    _, ref, _, _ = tiny
    for phases in ref.phases.values():
        for ph in phases:
            assert not (ph.block4 & ph.block6).any()
            assert ((ph.block4 | ph.block6) == ~ph.is_group).all()
            assert (ph.block4 == (ph.lo <= ph.hi)).all()
            assert (ph.block6 == ((ph.lo6h < ph.hi6h) | (
                (ph.lo6h == ph.hi6h) & (ph.lo6l <= ph.hi6l)))).all()
    assert sum(ph.block6.sum() for phases in ref.phases.values()
               for ph in phases) > 20


def test_a_cpu_cell_run_is_correct_on_both_families(ds_tree, capsys):
    seen = {}

    def look(ctx):
        seen.update(ctx)
        return control.read(ctx)

    r = run(ds_tree, f"{CONFIG}.churn", after_check=look)
    line = json.loads(json.dumps({k: v for k, v in r.items()
                                  if k != "control"}))
    assert line["correct"] is True and line["failed"] == 0
    check = line["check"]
    assert check["wrong_lanes"] == {"value": 0, "limit": 0}
    assert check["short_miss_steps"]["value"] == 0
    for kind in ("lanes_established", "lanes_cached_denial", "lanes_fresh",
                 "lanes_service"):
        assert check[kind]["value"] > 0
    s = seen["sample"]
    is6 = s["is6"] != 0
    n = check["lanes_compared"]["value"]
    assert len(is6) == n == 32 * line["steps"]
    assert s["src_ip6"].shape == s["dst_ip6"].shape == (n, 4)
    assert 0.3 < is6.mean() < 0.5
    assert (s["ref_code"][is6] != 0).sum() > 10  # v6 denials were compared
    assert (s["svc_idx"][~is6] >= 0).sum() > 0
    assert (s["svc_idx"][is6] == -1).all()  # no Service over v6
    assert not s["src_ip"][is6].any() and not s["src_ip6"][~is6].any()
    assert (s["est"][is6] == 1).sum() > 0  # v6 flows were cached
    # the reference is the configuration's own, and so is the control
    assert type(seen["reference"]).__module__ == "bench_dual_stack"
    assert r["control"]["correct"] is False
    assert r["control"]["wrong_lanes"] > 0
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("[bench] correct = True")


def test_a_traced_run_reads_the_v6_share(ds_tree, monkeypatch):
    import harness
    import reduce_trace
    import step_spans

    seen = {}
    read_layers = harness.read_layers

    def keep(manifest, cell, ctx):
        seen.update(ctx)
        return read_layers(manifest, cell, ctx)

    monkeypatch.setattr(harness, "read_layers", keep)
    r = run(ds_tree, f"{CONFIG}.churn", trace=True, seconds=6.0)
    assert r["correct"] is True
    asked = {m["name"] for m in ds_tree.metrics_of(f"{CONFIG}.churn",
                                                   "per_layer")}
    assert set(NEW_METRICS) <= asked
    assert asked - set(r["metrics"]) <= {"slowpath.device_ms"}
    # The CPU's trace holds the `while` ops of some steps and their bodies
    # in one run and none in the next (0, 0.02, 0.03, and 1.1 on a loaded
    # host), so the width reads the time inside the rounds of the trace
    # that was recorded: a CPU time, or 0 where it holds no `while`.
    rounds = step_spans.window_records(seen)["round_lanes"]
    inside = reduce_trace.step_device_ms(seen["reduced"], seen["config"])
    assert rounds.mean() > 0
    assert r["metrics"]["slowpath.width_us"]["value"] == pytest.approx(
        1e3 * inside["while"] / rounds.mean())
    assert r["metrics"]["entry.v6_lane_share"]["value"] == pytest.approx(
        40.0, abs=4.0)  # 256 lanes a step
    # three more uploads than a narrow engine's nine, 28 -> 64 B a lane; two
    # more copies than its three: the egress record's blocks as
    # models/forwarding lays them out, and the two wide outputs beside them
    assert r["metrics"]["entry.h2d_transfers"]["value"] == 12
    assert r["metrics"]["entry.h2d_bytes"]["value"] == 256 * (28 + 36) + 8
    assert r["metrics"]["entry.d2h_transfers"]["value"] == 5
    assert r["metrics"]["entry.d2h_bytes"]["value"] == _d2h_bytes(256)


def _d2h_bytes(lanes: int) -> int:
    """What a dual-stack step fetches: every lane field of the egress record
    at its width, its scalar counters once, and the wide outputs `dnat_w_f`
    and `peer_w`, an IPv6 address a lane each as four 32-bit words."""
    from antrea_tpu.models import forwarding as fw

    lane_bits = sum(bits for _, block, _, bits, _ in fw.EGRESS_RECORD
                    if block != "scalars")
    scalar_bits = sum(bits for _, block, _, bits, _ in fw.EGRESS_RECORD
                      if block == "scalars")
    wide = 2 * 4 * 4
    return lanes * (lane_bits // 8 + wide) + scalar_bits // 8


def test_the_new_readers_return_nothing_on_a_record_without_the_field():
    """The parent's engine records no `v6_lanes`: the reader says None and
    the line leaves the metric out."""
    import harness
    from numpy.lib import recfunctions

    share = load_module(os.path.join(BENCH, "layers",
                                     "entry.v6_lane_share.py"))
    width = load_module(os.path.join(BENCH, "layers",
                                     "slowpath.width_us.py"))
    dtype = [("seq", "<i8"), ("lanes", "<i8"), ("t_start", "<i8"),
             ("round_lanes", "<i8"), ("v6_lanes", "<i8")]
    rec = np.zeros(3, dtype)
    rec["seq"], rec["lanes"], rec["t_start"] = [1, 2, 3], 100, [10, 20, 30]
    rec["v6_lanes"], rec["round_lanes"] = [39, 40, 42], [4096, 8192, 4096]

    class Engine:
        def __init__(self, records):
            self.records = records

        def step_trace(self):
            return {"records": self.records, "dropped": 0}

    w = harness.Window()
    w.t_handoff, w.t_verdict = [0.0], [1.0]
    reduced = {"modules": {"jit_step": {"busy_s": 0.03, "while_s": 0.012}},
               "steps": [[0, 1]] * 3, "chips": 1}
    ctx = {"window": w, "engine": Engine(rec), "reduced": reduced,
           "config": {"trace": {"step_modules": "step"}}}
    assert share.read(ctx) == pytest.approx(40.0)
    ms = load_module(os.path.join(BENCH, "reduce_trace.py")).step_device_ms(
        reduced, ctx["config"])
    assert width.read(ctx) == pytest.approx(
        1e3 * ms["while"] / np.mean(rec["round_lanes"]))
    rec["round_lanes"] = 0  # no round ran
    assert width.read(ctx) is None
    old = recfunctions.drop_fields(rec, ["v6_lanes", "round_lanes"])
    ctx["engine"] = Engine(old)
    assert share.read(ctx) is None and width.read(ctx) is None
    ctx["engine"] = object()  # a wrapper without a tracer
    assert share.read(ctx) is None and width.read(ctx) is None


@pytest.mark.parametrize("cell, statement", [
    ("tiny_ds_flip6.churn", "code"),
    ("tiny_ds_service6.churn", "service"),
    ("tiny_ds_unchanged.churn", None),
])
def test_a_fault_on_v6_lanes_is_not_correct(ds_tree, cell, statement, capsys):
    r = run(ds_tree, cell)
    assert r["correct"] is False and r["failed"] == 0
    check = r["check"]
    if statement is None:  # the state returned unchanged
        assert check["remiss_share"]["value"] == 1.0
        assert check["replay_unhit_share"]["value"] == 1.0
        assert check["lanes_established"]["value"] == 0
        return
    assert check["wrong_lanes"]["value"] > check["wrong_lanes"]["limit"]
    err = capsys.readouterr().err
    assert f"wrong by statement: {statement} " in err


def test_every_class_but_the_services_has_the_stated_v6_share(tiny):
    w, ref, gen, _ = tiny
    # 4,096 open connections, so that a rank's weight is small against a
    # class's (the fixture's 96 put a fifth of the lanes on the first)
    mix = dict(load_json(os.path.join(FIXTURES, f"{MIX}.json")),
               universe_flows=4096)
    a = gen.Traffic(mix, w, 5, ref)
    b = gen.Traffic(mix, w, 2**31 + 12, ref)
    # the same work on every seed: class and family of every rank
    assert a.rank_class == b.rank_class
    assert (a.rank_v6 == b.rank_v6).all()
    want = mix["v6_lane_share"] / (1 - mix["svc_fraction"])
    assert want == pytest.approx(4 / 7)
    weight, light = a.rank_weight, a.rank_weight.min()
    classes = sorted(set(a.rank_class))
    assert {c[0] for c in classes} == {"pod", "svc", "ext"}
    for c in classes:
        of_c = np.array([x == c for x in a.rank_class])
        got = weight[of_c & a.rank_v6].sum() / weight[of_c].sum()
        if c[0] == "svc":
            assert got == 0.0
        else:  # to the weight of a rank or two of the class's own
            assert got == pytest.approx(want, abs=2 * light
                                        / weight[of_c].sum() + 1e-12)
    assert weight[a.rank_v6].sum() == pytest.approx(mix["v6_lane_share"],
                                                    abs=2e-3)
    assert not a.rank_v6[0] or not a.rank_v6[1]  # the head is of two families
    # the columns: one family a lane, both ends; the arrivals in the same
    # shares; and what differs between two seeds is the draws
    for t in (a, b):
        cols, _, _ = t.next_batch()
        is6 = cols["is6"] != 0
        assert cols["src_ip6"].dtype == cols["dst_ip6"].dtype == np.uint32
        assert cols["src_ip6"].shape == (t.batch, 4)
        assert cols["is6"].dtype == np.int32
        for end in ("src_ip", "dst_ip"):
            assert not cols[end][is6].any()
            assert not cols[end + "6"][~is6].any()
            assert cols[end + "6"][is6].any(axis=1).all()
        table6 = t._table[:, 0] != 0
        assert table6.mean() == pytest.approx(mix["v6_lane_share"], abs=0.03)
    assert (a.ring[0]["dst_ip6"] != b.ring[0]["dst_ip6"]).any()


def test_fresh_flows_are_never_sent_twice_on_either_family(tiny):
    w, ref, gen, _ = tiny
    t = gen.Traffic(load_json(os.path.join(FIXTURES, f"{MIX}.json")), w,
                    2**31 + 17, ref)
    names = ("src_ip", "dst_ip", "proto", "src_port", "dst_port")

    def flows(cols, at=slice(None)):
        wide = [cols[c][at].astype(np.int64) for c in ("src_ip6", "dst_ip6")]
        flat = np.concatenate(
            [np.stack([cols[c][at] for c in names], axis=1).astype(np.int64)]
            + wide, axis=1)
        return set(map(tuple, flat.tolist()))

    seen = set().union(*(flows(hot) for hot in t.ring))
    n_hot, v6 = len(seen), 0
    for _ in range(300):  # runs through a refill of the pool
        cols, lanes, fresh = t.next_batch()
        new = flows(cols, t.fresh_at)
        assert not seen & new and len(new) == t.fresh_lanes
        seen |= new
        v6 += int(cols["is6"][t.fresh_at].sum())
    assert t.refills > 1 and len(seen) == n_hot + 300 * t.fresh_lanes
    assert v6 / (300 * t.fresh_lanes) == pytest.approx(0.4, abs=0.03)


def test_the_cell_and_its_files_pass_the_manifest_check():
    doc = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert check_manifest.check(doc, ROOT) == []
    cell = next(c for c in doc["workloads"]
                if c["name"] == "dualstack100k.churn")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dualstack100k", "churn_ds", 1)
    assert "np100k.churn" in cell["why"]
    entry = next(c for c in doc["configs"] if c["name"] == "dualstack100k")
    assert entry["reduced"] == []
    config = load_json(os.path.join(ROOT, entry["file"]))
    base = load_json(os.path.join(BENCH, "configs", "np100k.json"))
    assert config["source"] == entry["source"]
    assert config["architecture"] is None and config["reduced"] == []
    assert dict(config["world"], v6_cidr_share=None) == dict(
        base["world"], v6_cidr_share=None)  # np100k's, key for key
    assert config["world_seed"] == base["world_seed"]
    assert config["engine"] == dict(base["engine"], kwargs=dict(
        base["engine"]["kwargs"], dual_stack=True))
    assert config["guarantees"][:4] == base["guarantees"]
    mix = load_json(os.path.join(BENCH, "traffic", "churn_ds.json"))
    churn = load_json(os.path.join(BENCH, "traffic", "churn.json"))
    same = set(churn) - {"generator", "source", "assumed"}
    assert {k: mix[k] for k in same} == {k: churn[k] for k in same}
    assert set(mix) - set(churn) == {"v6_lane_share"}
    assert mix["v6_lane_share"] == 0.4
    for name in NEW_METRICS:
        m = next(m for m in doc["per_layer"] if m["name"] == name)
        assert m["workloads"] == ["dualstack100k.churn"]
        assert m["moves"] == "served_pps"

"""The seam a configuration brings its world and reference through
(manifest.py: `worlds/<name>.py`, `references/<name>.py`, named by the
configuration's file): a fixture world builder, reference and generator that
live only under tests/fixtures are copied into a checkout as files plus
manifest entries — no file of the harness is edited — and the cell reads
`correct` through the reference's own statements; what the first deployments
read (world, ring, arrivals, sampled lanes) is held to digests taken on the
tree before the seam; a mix's `flow_seed` pins the hot set."""

import hashlib
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
from reference import Reference  # noqa: E402
from test_harness import BENCH, FIXTURES, run, tree  # noqa: E402,F401

CELLS = [  # (cell, traffic fixture)
    ("tiny_marked.steady_len", "tiny_steady_len"),
    ("tiny_marked.steady_jumbo", "tiny_steady_jumbo"),
]
PLACED = [("marked_world.py", "worlds"), ("marked_reference.py", "references"),
          ("flows_with_len.py", "generators"), ("tiny_marked.json", "configs")]


@pytest.fixture(scope="module")
def seam_tree(tree):  # noqa: F811
    """The harness test's checkout plus a configuration that names a world
    builder and a reference of its own, two mixes from a generator of its
    own, and their two cells: files and manifest entries only."""
    root = tree.root
    for name, folder in PLACED:
        os.makedirs(os.path.join(root, "benchmark", folder), exist_ok=True)
        shutil.copy(os.path.join(FIXTURES, name),
                    os.path.join(root, "benchmark", folder))
    doc = load_json(tree.path)
    doc["configs"].append({
        "name": "tiny_marked", "source": "benchmark/tests/fixtures",
        "file": "benchmark/configs/tiny_marked.json", "reduced": [],
        "why": "fixture"})
    for cell, traffic in CELLS:
        shutil.copy(os.path.join(FIXTURES, f"{traffic}.json"),
                    os.path.join(root, "benchmark", "traffic"))
        doc["workloads"].append({"name": cell, "config": "tiny_marked",
                                 "traffic": traffic, "chips": 1,
                                 "why": "fixture"})
    with open(tree.path, "w") as f:
        json.dump(doc, f)
    assert check_manifest.check(doc, root) == []
    return Manifest(tree.path)


def test_the_harness_names_no_world_and_no_reference():
    """What a later PR is held to: the files it may not edit import neither
    by module name."""
    for name in ("harness.py", "run.py", "correct.py", "manifest.py",
                 os.path.join("tests", "control.py")):
        with open(os.path.join(BENCH, name)) as f:
            text = f.read()
        assert "from reference import" not in text, name
        assert "from world import" not in text, name
        assert "import reference" not in text and "import world" not in text


def test_a_cell_reads_correct_through_what_its_configuration_brought(
        seam_tree):
    seen = {}

    def look(ctx):
        seen.update(ctx)
        return control.read(ctx)

    r = run(seam_tree, "tiny_marked.steady_len", after_check=look)
    assert r["correct"] is True and r["failed"] == 0
    assert r["check"]["wrong_lanes"] == {"value": 0, "limit": 0}
    # the step-level numbers stay the comparison's own, whatever the reference
    assert {"short_miss_steps", "remiss_share"} <= set(r["check"])
    # the world is the builder's, the reference its own class, and the sample
    # carries the column that only this generator sends
    assert all(n.startswith("marked-") for n in seen["world"].nodes)
    assert type(seen["reference"]).__module__ == "bench_marked_reference"
    assert isinstance(seen["reference"], Reference)
    n = r["check"]["lanes_compared"]["value"]
    assert (seen["sample"]["pkt_len"] == 1400).all()
    assert len(seen["sample"]["pkt_len"]) == n == 32 * r["steps"]
    # the control, built from the handed reference's class, still reads false
    assert r["control"]["correct"] is False
    assert r["control"]["wrong_lanes"] > 0


def test_the_references_own_statement_turns_correct_false(seam_tree, capsys):
    r = run(seam_tree, "tiny_marked.steady_jumbo")
    assert r["correct"] is False and r["failed"] == 0
    n = r["check"]["wrong_lanes"]
    assert n["value"] == r["check"]["lanes_compared"]["value"] > n["limit"]
    err = capsys.readouterr().err
    assert f"wrong by statement: pkt_len {n['value']}" in err


def test_the_default_world_is_refused_by_a_reference_that_wants_its_own(
        seam_tree):
    """A reference named without its world builder reads the default world
    and says so, instead of comparing against a world it does not know."""
    config = load_json(os.path.join(FIXTURES, "tiny_marked.json"))
    del config["world_builder"]
    world = load_module(seam_tree.world_path(config)).build_world(
        config["world"], config["world_seed"])
    assert world.nodes[0] == "node-0"
    with pytest.raises(ValueError, match="marked world only"):
        load_module(seam_tree.reference_path(config)).Reference(world)


@pytest.mark.parametrize("edit, says", [
    (lambda c: c.update(world_builder="nowhere"), "which is not there"),
    (lambda c: c.update(reference="nowhere"), "which is not there"),
    (lambda c: c.update(reference="marked_world"), "does not expose"),
    (lambda c: c.update(world_builder="marked_reference"), "does not expose"),
    (lambda c: c.pop("reference"), "no reference"),
    (lambda c: c.update(reference="../reference"), "is not a name"),
])
def test_check_manifest_refuses_a_named_file_that_is_not_there(
        seam_tree, edit, says):
    path = os.path.join(seam_tree.root, "benchmark", "configs",
                        "tiny_marked.json")
    doc = load_json(seam_tree.path)
    good = load_json(path)
    # both kinds of file sit in both directories, so that only the names
    # they expose tell them apart
    for name, folder in (("marked_world.py", "references"),
                         ("marked_reference.py", "worlds")):
        shutil.copy(os.path.join(FIXTURES, name),
                    os.path.join(seam_tree.root, "benchmark", folder))
    bad = dict(good)
    edit(bad)
    try:
        with open(path, "w") as f:
            json.dump(bad, f)
        faults = check_manifest.check(doc, seam_tree.root)
    finally:
        with open(path, "w") as f:
            json.dump(good, f)
    assert faults and all("tiny_marked" in f for f in faults), faults
    assert any(says in f for f in faults), faults
    assert check_manifest.check(doc, seam_tree.root) == []


# -- what the first deployments read, byte for byte ----------------------------

def _digest(*parts) -> str:
    h = hashlib.sha256()
    for part in parts:
        if isinstance(part, dict):
            for k in sorted(part):
                a = np.ascontiguousarray(part[k])
                h.update(k.encode() + str(a.dtype).encode() + a.tobytes())
        else:
            h.update(repr(part).encode())
    return h.hexdigest()[:16]


# Taken on the parent tree (be55208, before the seam and `flow_seed`): the
# world of fixtures/tiny.json; the first ring batch, the first `next_batch`
# and its sampled lanes of the two tiny mixes.
GOLDEN_WORLD = {5: "5ad1a180e8d67d36", 2**31 + 17: "868c6c1a5809cb78"}
GOLDEN_TRAFFIC = {
    ("tiny_steady", 5): (
        "8a1e96a88c149e1f", "8a1e96a88c149e1f", "f3d7ec167fe1da57"),
    ("tiny_steady", 2**31 + 17): (
        "0c35bcec9e3db942", "0c35bcec9e3db942", "7c4fa8633fa2d19e"),
    ("tiny_churn", 5): (
        "8a1e96a88c149e1f", "3635c1a3ac1359c8", "43c0ba1f1b2fb347"),
    ("tiny_churn", 2**31 + 17): (
        "0c35bcec9e3db942", "ccf3d5b6b7c3ceaa", "71d5c0f5c61d47a7"),
}


@pytest.fixture(scope="module")
def default_files():
    """(world module, Reference, generator) as the manifest finds them for a
    configuration that names none."""
    m = Manifest()
    config = load_json(os.path.join(FIXTURES, "tiny.json"))
    assert m.world_path(config) == os.path.join(BENCH, "world.py")
    assert m.reference_path(config) == os.path.join(BENCH, "reference.py")
    return (load_module(m.world_path(config)),
            load_module(m.reference_path(config)).Reference,
            load_module(m.generator_path("policy_flows")))


@pytest.mark.parametrize("seed", sorted(GOLDEN_WORLD))
def test_the_default_world_is_the_parents(default_files, seed):
    worlds, _, _ = default_files
    params = load_json(os.path.join(FIXTURES, "tiny.json"))["world"]
    w = worlds.build_world(params, seed)
    assert _digest((w.pods, w.nodes, w.groups, w.policies,
                    w.services)) == GOLDEN_WORLD[seed]


@pytest.mark.parametrize("mix, seed", sorted(GOLDEN_TRAFFIC))
def test_a_mix_without_flow_seed_draws_as_the_parent_did(default_files, mix,
                                                         seed):
    worlds, reference, gen = default_files
    w = worlds.build_world(
        load_json(os.path.join(FIXTURES, "tiny.json"))["world"], 1)
    t = gen.Traffic(load_json(os.path.join(FIXTURES, f"{mix}.json")), w,
                    seed, reference(w))
    cols, lanes, fresh = t.next_batch()
    assert (_digest(t.ring[0]), _digest(cols),
            _digest({"lanes": lanes, "fresh": fresh})
            ) == GOLDEN_TRAFFIC[mix, seed]


# -- flow_seed ------------------------------------------------------------------

def _flows(t) -> list:
    """Every ring batch as a list of 5-tuples."""
    return [list(zip(*(b[c].tolist() for c in (
        "src_ip", "dst_ip", "proto", "src_port", "dst_port"))))
        for b in t.ring]


def test_flow_seed_pins_the_hot_set_and_leaves_the_lanes_to_the_seed():
    gen = load_module(os.path.join(BENCH, "generators", "policy_flows.py"))
    mix = load_json(os.path.join(FIXTURES, "tiny_steady.json"))
    w = W.build_world(load_json(os.path.join(FIXTURES, "tiny.json"))["world"],
                      seed=1)
    ref = Reference(w)

    def ring(seed, **more):
        t = gen.Traffic(dict(mix, **more), w, seed, ref)
        return t, _flows(t)

    (ta, a), (tb, b) = ring(11, flow_seed=3), ring(2**31 + 12, flow_seed=3)
    hot = set().union(*a, *b)
    # one hot set for every seed: the two rings together show no more
    # connections than the mix has open, the same elephant, the same
    # templates ...
    assert len(hot) <= mix["universe_flows"] < len(hot) * 2
    top = [max(set(x), key=x.count) for x in (sum(a, []), sum(b, []))]
    assert top[0] == top[1]
    assert ta.summary == tb.summary
    # ... in another order of lanes, and other lanes sampled
    assert a != b
    assert (ta.next_batch()[1] != tb.next_batch()[1]).any()
    # another flow_seed is another hot set under the same seed; so is no
    # flow_seed, where the seed draws it (held to the parent's digests above)
    for other in (ring(11, flow_seed=4)[1], ring(11)[1]):
        assert len(hot.union(*other)) > mix["universe_flows"]
    _, again = ring(11, flow_seed=3)
    assert again == a

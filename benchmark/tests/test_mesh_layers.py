"""The four `mesh.*` per-layer metrics (layers/mesh.route_ms, .retry_ms,
.spill_share, .foreign_lanes): a traced run of the four-device tiny mesh
under a mix whose head overflows one replica returns all of them, they agree
with the engine's own record, the one-chip engine reads zeros, and an engine
whose record lacks the fields (the parent's) gives nothing to read.  Also
holds the committed BENCHMARK.json to what the cell np100k.steady_mesh4 is."""

import json
import os
import shutil
import types

import numpy as np
import pytest

import check_manifest  # noqa: E402
import harness  # noqa: E402
import step_spans  # noqa: E402
from manifest import Manifest, load_json, load_module  # noqa: E402
from test_harness import BENCH, FIXTURES, ROOT, run, tree  # noqa: E402,F401

MESH = ("mesh.route_ms", "mesh.retry_ms", "mesh.spill_share",
        "mesh.foreign_lanes")
CELL = "tiny_mesh4.skew"


@pytest.fixture(scope="module")
def skew_tree(tree):  # noqa: F811
    """The harness test's checkout plus one more cell, added as data: the
    four-device tiny mesh under the skewed mix."""
    root = tree.root
    shutil.copy(os.path.join(FIXTURES, "tiny_skew.json"),
                os.path.join(root, "benchmark", "traffic"))
    doc = load_json(tree.path)
    doc["workloads"].append({"name": CELL, "config": "tiny_mesh4",
                             "traffic": "tiny_skew", "chips": 4,
                             "why": "fixture"})
    for m in doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [CELL]
    with open(tree.path, "w") as f:
        json.dump(doc, f)
    assert check_manifest.check(doc, root) == []
    return Manifest(tree.path)


@pytest.fixture(scope="module")
def traced_ctx(skew_tree):
    ctx, read = {}, harness.read_layers

    def keep(manifest, cell, handed):
        ctx.update(handed)
        return read(manifest, cell, handed)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "read_layers", keep)
        return run(skew_tree, CELL, trace=True, seconds=6.0), ctx


def test_a_traced_mesh_run_returns_the_four_metrics(traced_ctx):
    r, ctx = traced_ctx
    got = r["metrics"]
    assert r["correct"] is True and r["device"]["count"] == 4
    assert r["check"]["wrong_lanes"]["value"] == 0
    assert set(MESH) <= set(got)
    assert all(np.isfinite(got[n]["value"]) for n in MESH)
    assert got["mesh.foreign_lanes"]["value"] == 0
    assert got["mesh.spill_share"]["value"] > 0
    assert got["mesh.retry_ms"]["value"] > 0
    # the sub-spans lie inside their phases, so under the phases' medians
    assert 0 < got["mesh.route_ms"]["value"] <= got["entry.stage_ms"]["value"]
    assert got["mesh.retry_ms"]["value"] <= got["entry.account_ms"]["value"]
    # ... and they are the engine's own record, window by window
    rec = step_spans.window_records(ctx)
    assert len(rec) == r["steps"]
    assert got["mesh.spill_share"]["value"] == pytest.approx(
        float(np.median(100.0 * rec["spill_lanes"] / rec["lanes"])))
    assert (rec["retry_lanes"] == rec["spill_lanes"]).all()
    stats = ctx["engine"].mesh_stats()
    assert stats["spill_lanes_total"] == stats["spill_retried_total"] > 0


def test_the_one_chip_engine_reads_zeros(skew_tree):
    r = run(skew_tree, "tiny.steady", trace=True, seconds=4.0)
    for n in MESH:  # the tree asks every tiny cell for them
        assert r["metrics"][n]["value"] == 0


@pytest.mark.parametrize("metric", MESH)
def test_a_record_without_the_fields_gives_nothing_to_read(metric):
    """The parent's engine: a step record of the seven phases and the four
    transfer counters only.  The reader returns None and does not raise."""
    reader = load_module(os.path.join(BENCH, "layers", f"{metric}.py"))
    window = types.SimpleNamespace(t_handoff=[1.0], t_verdict=[5.0])
    assert reader.read({"engine": object(), "window": window}) is None
    rec = np.zeros(3, [("t_start", "<i8"), ("t_stage", "<i8"),
                       ("t_upload", "<i8"), ("lanes", "<i8"),
                       ("h2d_bytes", "<i8")])
    rec["t_start"] = [2_000_000_000, 3_000_000_000, 4_000_000_000]
    engine = types.SimpleNamespace(
        step_trace=lambda: {"records": rec, "dropped": 0})
    ctx = {"engine": engine, "window": window}
    assert len(step_spans.window_records(ctx)) == 3
    assert reader.read(ctx) is None


def test_the_committed_manifest_holds_the_mesh_cell():
    doc = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert check_manifest.check(doc, ROOT) == []
    m = Manifest()
    cell = m.cell("np100k.steady_mesh4")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "np100k_mesh4", "steady_x4", 4)
    config, control = m.config("np100k_mesh4"), m.config("np100k")
    assert config["world"] == control["world"]
    assert config["world_seed"] == control["world_seed"]
    assert config["reduced"] == []
    assert config["guarantees"][:4] == control["guarantees"]
    assert config["engine"] == {
        "entry": "antrea_tpu.parallel.meshpath.MeshDatapath", "args": [],
        "kwargs": {"flow_slots": 4194304},
        "mesh": {"n_data": 4, "n_rule": 1}}
    mix = load_json(m.traffic_path("steady_x4"))
    one = load_json(m.traffic_path("steady"))
    assert (mix["batch"], mix["universe_flows"]) == (524288, 131072)
    differ = {k for k in one if mix[k] != one[k]}
    assert differ == {"batch", "universe_flows", "assumed"}
    # the hot set is the mix's own (PR 33): the seed no longer decides on
    # which replica the Zipf head lands
    assert set(mix) - set(one) == {"flow_seed"}
    small = load_json(m.traffic_path("steady_b4k"))
    assert {k for k in one if small[k] != one[k]} == {
        "batch", "ring", "sample_lanes_per_step", "assumed"}
    assert set(small) == set(one)
    assert small["batch"] * small["ring"] == one["batch"] * one["ring"]
    asked = {x["name"]: x for x in m.metrics_of("np100k.steady_mesh4",
                                                "per_layer")}
    for n in MESH:
        assert asked[n]["workloads"] == ["np100k.steady_mesh4"]
        assert asked[n]["layer"] == "mesh"
    assert not any(n in {x["name"] for x in m.metrics_of(c, "per_layer")}
                   for n in MESH for c in ("np100k.churn", "np100k.steady",
                                           "acnp10k.churn"))

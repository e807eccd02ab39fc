"""The per-layer metric `slowpath.fill_share` (layers/slowpath.fill_share):
the window's misses over the lanes the step program ran its slow-path rounds
at, from the two counters of the engine's step record.  A traced tiny run on
one device returns it (and on the four-device mesh fixture, whose record
carries the summed counter, though the committed manifest does not ask the
mesh cell for it), it is the record's own arithmetic, and a record without the counter (the parent's) gives nothing to
read."""

import os
import types

import numpy as np
import pytest

import harness  # noqa: E402
import step_spans  # noqa: E402
from manifest import Manifest, load_json, load_module  # noqa: E402
from test_harness import BENCH, ROOT, run, tree  # noqa: E402,F401

NAME = "slowpath.fill_share"


def reader():
    return load_module(os.path.join(BENCH, "layers", f"{NAME}.py"))


@pytest.mark.parametrize("cell", ["tiny.churn", "tiny_mesh4.steady"])
def test_a_traced_run_returns_the_records_own_share(tree, cell):  # noqa: F811
    ctx, read = {}, harness.read_layers

    def keep(manifest, name, handed):
        ctx.update(handed)
        return read(manifest, name, handed)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "read_layers", keep)
        r = run(tree, cell, trace=True, seconds=4.0)
    rec = step_spans.window_records(ctx)
    assert len(rec) == r["steps"] and r["correct"] is True
    # every miss was served by a lane of some round
    assert (rec["round_lanes"] >= rec["n_miss"]).all()
    if cell == "tiny.churn":  # one chip: rounds ran exactly where lanes missed
        assert ((rec["round_lanes"] > 0) == (rec["n_miss"] > 0)).all()
        assert (rec["round_lanes"] % 64 == 0).all()  # the fixture's one rung
    if rec["round_lanes"].sum():
        value = r["metrics"][NAME]["value"]
        assert value == pytest.approx(
            100.0 * rec["n_miss"].sum() / rec["round_lanes"].sum())
        # 0 on the mesh fixture: its home lanes all hit, and the rounds are
        # the foreign walks of lanes that skew placed off-home
        assert 0 <= value <= 100 and (value > 0 or cell != "tiny.churn")
        assert r["metrics"][NAME]["unit"] == "%"
    else:
        assert NAME not in r["metrics"]


def _ctx(rec):
    window = types.SimpleNamespace(t_handoff=[1.0], t_verdict=[5.0])
    engine = types.SimpleNamespace(
        step_trace=lambda: {"records": rec, "dropped": 0})
    return {"engine": engine, "window": window}


def test_the_share_is_misses_over_round_lanes_of_the_window():
    rec = np.zeros(4, [("t_start", "<i8"), ("n_miss", "<i8"),
                       ("round_lanes", "<i8")])
    rec["t_start"] = [2_000_000_000, 3_000_000_000, 4_000_000_000,
                      9_000_000_000]  # the last began after the window
    rec["n_miss"] = [226, 0, 4500, 7]
    rec["round_lanes"] = [512, 0, 4096 + 512, 512]
    assert reader().read(_ctx(rec)) == pytest.approx(
        100.0 * (226 + 4500) / (512 + 4096 + 512))
    rec["round_lanes"] = 0  # no round ran: nothing to read
    assert reader().read(_ctx(rec)) is None


def test_a_record_without_the_counter_gives_nothing_to_read():
    """The parent's engine: a step record with `n_miss` and no
    `round_lanes`.  The reader returns None and does not raise."""
    window = types.SimpleNamespace(t_handoff=[1.0], t_verdict=[5.0])
    assert reader().read({"engine": object(), "window": window}) is None
    rec = np.zeros(3, [("t_start", "<i8"), ("lanes", "<i8"),
                       ("n_miss", "<i8")])
    rec["t_start"] = [2_000_000_000, 3_000_000_000, 4_000_000_000]
    rec["n_miss"] = 5
    ctx = _ctx(rec)
    assert len(step_spans.window_records(ctx)) == 3
    assert reader().read(ctx) is None


def test_the_committed_manifest_asks_the_one_chip_cells_for_it():
    """Not the mesh cell: there `n_miss` is a step's merged image and
    `round_lanes` sums the replicas' foreign walks and the spill retry, so
    their quotient is no share of the rounds."""
    m = Manifest()
    doc = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    (entry,) = [x for x in doc["per_layer"] if x["name"] == NAME]
    one_chip = ["np100k.churn", "np100k.steady", "acnp10k.churn",
                "np100k.steady_b4k"]
    listed = entry.pop("workloads")
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "slowpath",
                     "moves": "served_pps"}
    # a later PR's cell may join the list (the test does not pin it), as
    # long as it is a one-chip cell
    assert set(one_chip) <= set(listed)
    assert all(m.cell(c)["chips"] == 1 for c in listed)
    for cell in one_chip:
        assert NAME in {x["name"] for x in m.metrics_of(cell, "per_layer")}
    assert NAME not in {x["name"] for x in m.metrics_of(
        "np100k.steady_mesh4", "per_layer")}

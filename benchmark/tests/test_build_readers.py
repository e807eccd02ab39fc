"""The eight readers of what set-up and builds cost (PR 38), on fake engines:

  * each gives a value where the engine has the program's record (the
    commit's sub-spans and constructor span in `last_commit()`, the build
    ledger's `build_trace()`, `xla_builds` in the step records);
  * each gives None where it has not (the parent's engine), so the line
    leaves the metric out;
  * their manifest entries: no `workloads` list, the layer and the
    end-to-end metric each moves.
"""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from manifest import Manifest, load_module  # noqa: E402
from test_harness import BENCH  # noqa: E402

from antrea_tpu.observability.tracing import BUILD_RECORD, STEP_RECORD

COMMIT = ("construct", "rules", "tables", "oracle", "walk", "digest")
METRICS = tuple(f"commit.{n}_s" for n in COMMIT) + ("build.setup_s",
                                                   "build.window_builds")
OLD_COMMIT = {"generation": 1, "compile_s": 2.0, "canary_s": 1.0,
              "swap_s": 0.0, "settle_s": 0.0, "upload_s": 0.5,
              "table_bytes": 10}
NEW_COMMIT = dict(OLD_COMMIT, construct_s=0.25, rules_s=0.75, tables_s=0.5,
                  oracle_s=0.625, walk_s=0.375, digest_s=0.125)
WINDOW = SimpleNamespace(t_handoff=[10.0, 10.1], t_verdict=[10.05, 10.2],
                         records=[])
PARENT_STEP = np.dtype([(n, "<i8") for n in STEP_RECORD.names
                        if not n.startswith("xla_")])


def _reader(name):
    return load_module(os.path.join(BENCH, "layers", f"{name}.py"))


def _steps(dtype):
    rec = np.zeros(3, dtype)
    rec["seq"] = [1, 2, 3]
    rec["t_start"] = [9_000_000_000, 10_000_000_000, 10_100_000_000]
    if "xla_builds" in dtype.names:
        rec["xla_builds"] = [7, 0, 2]  # the warm-up's, then the window's
    return {"records": rec, "dropped": 0}


def _builds():
    rows = np.zeros(3, BUILD_RECORD)
    rows["seq"] = [1, 2, 3]
    rows["t_end"] = [5_000_000_000, 9_999_000_000, 10_050_000_000]
    rows["trace_ns"], rows["lower_ns"] = 100_000_000, 200_000_000
    rows["backend_ns"] = [700_000_000, 1_700_000_000, 50_000_000]
    return {"records": rows, "dropped": 0}


class Engine:
    """An engine with the program's records (`new`) or the parent's."""

    def __init__(self, new: bool, last=True):
        commit = NEW_COMMIT if new else OLD_COMMIT
        self.realization_tracer = SimpleNamespace(
            last_commit=lambda: commit if last else None)
        self.step_trace = lambda: _steps(STEP_RECORD if new else PARENT_STEP)
        if new:
            self.build_trace = _builds


WANT = {f"commit.{n}_s": NEW_COMMIT[f"{n}_s"] for n in COMMIT}
# builds 1 and 2 end before the first hand-off (10.0 s): 1.0 + 2.0 s
WANT["build.setup_s"] = 3.0
WANT["build.window_builds"] = 2.0  # the steps that began inside the window


@pytest.mark.parametrize("name", METRICS)
def test_a_reader_reads_the_programs_record(name):
    got = _reader(name).read({"engine": Engine(True), "window": WINDOW})
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", METRICS)
def test_a_reader_says_none_on_the_parents_engine(name):
    reader = _reader(name)
    for engine in (Engine(False), Engine(False, last=False), object()):
        assert reader.read({"engine": engine, "window": WINDOW}) is None


def test_build_setup_s_says_none_where_the_ring_lost_rows():
    engine = Engine(True)
    engine.build_trace = lambda: dict(_builds(), dropped=1)
    assert _reader("build.setup_s").read(
        {"engine": engine, "window": WINDOW}) is None


def test_the_manifest_entries():
    per_layer = {m["name"]: m for m in Manifest().doc["per_layer"]}
    for name in METRICS:
        m = per_layer[name]
        assert "workloads" not in m  # every cell, the mesh included
        layer = name.split(".")[0]
        assert m["layer"] == layer and m["better"] == "lower"
        assert m["moves"] == ("served_pps" if name == "build.window_builds"
                              else "setup_s")
        assert m["source"] == ("program_span" if layer == "commit"
                               else "program_counter")

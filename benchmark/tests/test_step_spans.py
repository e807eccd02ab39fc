"""The per-layer metrics that read the program's own step spans, transfer
counters and commit stamps (layers/entry.*_ms, entry.*_bytes, commit.*_s via
step_spans.py): a traced run of the tiny cell on the CPU backend returns every
one of them, they agree with what the client's clock saw, and an engine that
records nothing gives nothing to read."""

import statistics
import types

import numpy as np
import pytest

import harness  # noqa: E402
import step_spans  # noqa: E402
from test_harness import run, tree  # noqa: E402,F401  (the fixture)

PHASES = ("stage", "upload", "dispatch", "wait", "fetch", "account",
          "attribute")
NEW = ([f"entry.{p}_ms" for p in PHASES]
       + ["entry.h2d_bytes", "entry.d2h_bytes", "commit.compile_s",
          "commit.canary_s", "entry.h2d_transfers", "entry.d2h_transfers"])


@pytest.fixture(scope="module")
def traced_ctx(tree):  # noqa: F811
    """(result, ctx): the run's line and what its layer readers were
    handed."""
    ctx, read = {}, harness.read_layers

    def keep(manifest, cell, handed):
        ctx.update(handed)
        return read(manifest, cell, handed)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "read_layers", keep)
        return run(tree, "tiny.churn", trace=True, seconds=6.0), ctx


@pytest.fixture(scope="module")
def traced(traced_ctx):
    return traced_ctx[0]


def test_the_manifest_asks_every_cell_for_the_new_metrics(tree):  # noqa: F811
    asked = {m["name"]: m for m in tree.metrics_of("np100k.steady",
                                                   "per_layer")}
    for name in NEW:
        assert "workloads" not in asked[name]
        assert asked[name]["source"] in ("program_span", "program_counter")
        assert asked[name]["layer"] == name.split(".")[0]


def test_a_traced_run_returns_every_new_metric(traced):
    got = traced["metrics"]
    assert traced["correct"] is True
    assert set(NEW) <= set(got)
    assert all(got[n]["value"] >= 0 for n in NEW)
    # 256 lanes: six i32 columns and the flags, two scalars.
    assert got["entry.h2d_transfers"]["value"] == 7 + 2
    assert got["entry.h2d_bytes"]["value"] == 7 * 256 * 4 + 2 * 4
    # the egress record (PR 29), by its own layout: one copy a block; every
    # lane's rows and the scalars
    from antrea_tpu.models import forwarding as fwd

    lane_bytes = sum(bits // 8 for _, block, _, bits, _ in fwd.EGRESS_RECORD
                     if block != "scalars")
    assert got["entry.d2h_transfers"]["value"] == len(
        {block for _, block, *_ in fwd.EGRESS_RECORD})
    assert got["entry.d2h_bytes"]["value"] == (
        256 * lane_bytes + 4 * len(fwd.EGRESS_SCALARS))


def test_the_phases_sum_to_the_clients_step(traced_ctx):
    """Step by step the seven phases fill the client's wall.  Held on the
    per-step sums: on a busy host a step's delay lands in ONE of its phases,
    so each phase's median stays short and the seven medians under-sum (a
    loaded CPU run read 2.2 of 6.9 ms; a quiet one 95-96 %, the chip 99.4 %)."""
    traced, ctx = traced_ctx
    rec = step_spans.window_records(ctx)
    assert len(rec) == traced["steps"]
    bounds = [f"t_{p}" for p in PHASES] + ["t_done"]
    per_phase = {p: (rec[end] - rec[start]) / 1e6
                 for p, start, end in zip(PHASES, bounds, bounds[1:])}
    wall = statistics.median(traced["step_ms"])
    assert np.median(sum(per_phase.values())) == pytest.approx(wall, rel=0.10)
    got = traced["metrics"]
    for p in PHASES:  # each reader reports its own phase's median
        assert got[f"entry.{p}_ms"]["value"] == pytest.approx(
            float(np.median(per_phase[p])), abs=1e-5)
    # step by step the spans lie inside the client's (the medians need
    # not: a sum of medians may pass the median of the sums)
    assert (sum(per_phase.values()) < np.asarray(traced["step_ms"])).all()


def test_the_commit_stages_lie_inside_the_install(traced):
    got = traced["metrics"]
    stages = got["commit.compile_s"]["value"] + got["commit.canary_s"]["value"]
    assert 0 < stages <= got["commit.install_s"]["value"]


def test_a_ring_that_lost_the_windows_head_gives_nothing_to_read():
    """The ring keeps the last 4,096 steps: once it has dropped steps of
    the window, a median over what is left would pass for the window's."""
    rec = np.zeros(3, [("t_start", "<i8"), ("t_stage", "<i8"),
                       ("t_upload", "<i8"), ("h2d_bytes", "<i8")])
    rec["t_start"] = [2_000_000_000, 3_000_000_000, 4_000_000_000]
    rec["t_stage"], rec["t_upload"] = rec["t_start"], rec["t_start"] + 5_000_000
    rec["h2d_bytes"] = 7
    kept = {"records": rec, "dropped": 0}
    engine = types.SimpleNamespace(step_trace=lambda: kept)
    window = types.SimpleNamespace(t_handoff=[1.5], t_verdict=[4.5])
    ctx = {"engine": engine, "window": window}
    assert len(step_spans.window_records(ctx)) == 3  # nothing dropped yet
    assert step_spans.phase_ms(ctx, "stage") == 5.0
    kept["dropped"] = 9
    window.t_handoff = [2.5]  # a kept step began before the window opened
    assert len(step_spans.window_records(ctx)) == 2
    window.t_handoff = [1.5]  # none did: window steps may be among the 9
    assert step_spans.window_records(ctx) is None
    assert step_spans.phase_ms(ctx, "stage") is None
    assert step_spans.counter_per_step(ctx, "h2d_bytes") is None


def test_a_window_longer_than_the_ring_is_read_from_what_was_put_aside():
    """A traced window of short steps has more of them than the program's
    ring keeps (np100k.steady_b4k: ~3,500 against 4,096 with the warm-up);
    the harness puts the ring aside as it goes and the readers join the
    readings by `seq`.  A stretch that fell between two readings: nothing."""
    slots, all_rec = 8, np.zeros(30, [("seq", "<i8"), ("t_start", "<i8"),
                                      ("t_stage", "<i8"), ("t_upload", "<i8")])
    all_rec["seq"] = np.arange(1, 31)
    all_rec["t_start"] = all_rec["t_stage"] = all_rec["seq"] * 1_000_000_000
    all_rec["t_upload"] = all_rec["t_start"] + all_rec["seq"] * 1_000_000
    done = {"n": 0}
    engine = types.SimpleNamespace(step_trace=lambda: {
        "records": all_rec[max(0, done["n"] - slots):done["n"]],
        "dropped": max(0, done["n"] - slots)})
    w = harness.Window()
    w.t_handoff, w.t_verdict = [4.5], [30.5]  # steps 5..30 are the window's
    ctx = {"engine": engine, "window": w}
    for n in range(1, 31):
        done["n"] = n
        if n % 6 == 0:
            w.keep_records(engine)
    rec = step_spans.window_records(ctx)
    assert rec["seq"].tolist() == list(range(5, 31))
    assert step_spans.phase_ms(ctx, "stage") == float(np.median(range(5, 31)))
    del w.records[2]  # readings at 12 and 24 only: steps 13..16 are lost
    assert step_spans.window_records(ctx) is None
    w.records.clear()  # nothing put aside: the ring alone lost the head
    assert step_spans.window_records(ctx) is None


def test_an_engine_that_records_nothing_gives_nothing_to_read():
    window = types.SimpleNamespace(t_handoff=[1.0], t_verdict=[2.0])
    ctx = {"engine": object(), "window": window}  # the parent's, a wrapper
    assert step_spans.window_records(ctx) is None
    assert step_spans.phase_ms(ctx, "wait") is None
    assert step_spans.counter_per_step(ctx, "h2d_bytes") is None
    assert step_spans.commit_stage_s(ctx, "compile") is None
    quiet = types.SimpleNamespace(step_trace=lambda: None,
                                  realization_tracer=None)
    ctx = {"engine": quiet, "window": window}  # tracing switched off
    assert step_spans.phase_ms(ctx, "wait") is None
    assert step_spans.commit_stage_s(ctx, "canary") is None

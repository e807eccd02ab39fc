"""What the program records about itself, as the layer readers take it from
`ctx["engine"]`: the step tracer's ring (one record per `step` call: the
`step` span, its host phases, the transfer counters — all on
time.perf_counter_ns, the clock of `harness.Window`) and the stage stamps of
the last commit transaction.

An engine without them (a commit before the tracer, a test's wrapper) gives
nothing to read: every function here then returns None, and the result line
leaves the metric out.
"""
import numpy as np


def window_records(ctx):
    """The records whose `step` span began inside the timed window; None
    where they no longer cover the window (the ring keeps the last 4,096
    steps: a median over the window's tail alone would say nothing of it).
    A traced window of more steps than that put its records aside as it went
    (`harness.Window.keep_records`); they are joined here by `seq`."""
    read = getattr(ctx["engine"], "step_trace", None)
    w = ctx["window"]
    trace = read() if read is not None and w.t_verdict else None
    if not trace:
        return None
    rec = trace["records"]
    aside = getattr(w, "records", None)
    if aside:
        rec = np.concatenate(aside + [rec])
        rec = rec[np.unique(rec["seq"], return_index=True)[1]]
    began = rec["t_start"]
    opened = w.t_handoff[0] * 1e9
    if trace["dropped"] and len(rec) and began[0] > opened:
        return None
    rec = rec[(began >= opened) & (began <= w.t_verdict[-1] * 1e9)]
    if aside and len(rec) and np.any(np.diff(rec["seq"]) != 1):
        return None  # a stretch of the window fell between two readings
    return rec if len(rec) else None


def phase_ms(ctx, phase: str):
    """Median over the window's steps of one host phase of `step`.  The
    median, not the mean: the profiler's 3 s stretch and a stall are a few
    steps of some hundred."""
    rec = window_records(ctx)
    if rec is None:
        return None
    names = rec.dtype.names
    ends = names[names.index(f"t_{phase}") + 1]  # where the next one begins
    return float(np.median(rec[ends] - rec[f"t_{phase}"])) / 1e6


def counter_per_step(ctx, counter: str):
    rec = window_records(ctx)
    return None if rec is None else float(np.median(rec[counter]))


def commit_stage_s(ctx, stage: str):
    """Seconds of one stage of the last commit transaction (the cell's one
    `install_bundle`), from the stamps the commit plane takes itself."""
    tracer = getattr(ctx["engine"], "realization_tracer", None)
    read = getattr(tracer, "last_commit", None)
    last = read() if read is not None else None
    return None if not last else float(last[f"{stage}_s"])

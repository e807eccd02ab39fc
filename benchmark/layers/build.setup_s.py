"""Seconds the program spent building executables during set-up: the sum of
trace + lower + backend (compile, or load from the persistent cache) of every
build in the engine's `build_trace()` that ended before the window's first
hand-off (`t_end` and `Window.t_handoff` are both time.perf_counter).  None on
an engine without the build ledger (the parent's) or whose ring lost rows."""


def read(ctx):
    read = getattr(ctx["engine"], "build_trace", None)
    trace = read() if read is not None else None
    w = ctx["window"]
    if not trace or trace["dropped"] or not w.t_handoff:
        return None
    rec = trace["records"]
    rec = rec[rec["t_end"] <= w.t_handoff[0] * 1e9]
    return float((rec["trace_ns"] + rec["lower_ns"]
                  + rec["backend_ns"]).sum()) / 1e9

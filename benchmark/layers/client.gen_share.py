"""Share of the client's loop spent outside `step`: batch assembly and the
sample of answers it puts aside.  A slow generator must not read as a slow
engine.  (Starting and stopping the profiler falls between iterations and is
in neither.)"""


def read(ctx):
    w = ctx["window"]
    if not w.t_done:
        return None
    loop = sum(d - a for a, d in zip(w.t_assemble, w.t_done))
    inside = sum(c - b for b, c in zip(w.t_handoff, w.t_verdict))
    return 100.0 * (1.0 - inside / loop)

"""Lanes that left the fast path: StepResult.n_miss summed over the window
over the lanes offered."""


def read(ctx):
    w = ctx["window"]
    if not w.n_miss:
        return None
    return 100.0 * sum(w.n_miss) / sum(w.lanes[:len(w.n_miss)])

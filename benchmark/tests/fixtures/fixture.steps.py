"""A per-layer metric that a later PR might add: steps in the window.  Here
to show that a reader is found by its metric's name with no edit to the
harness."""


def read(ctx):
    return float(len(ctx["window"].t_verdict))

"""Host phase `stage` of `step`, the program's own span, median over the
window's steps: numpy preparation of the batch's columns (flips, casts,
flags, ports)."""
import step_spans


def read(ctx):
    return step_spans.phase_ms(ctx, "stage")

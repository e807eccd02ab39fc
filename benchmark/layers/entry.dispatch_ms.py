"""Host phase `dispatch` of `step`, the program's own span, median over the
window's steps: the call of the jitted step until it returns (argument
handling, enqueue)."""
import step_spans


def read(ctx):
    return step_spans.phase_ms(ctx, "dispatch")

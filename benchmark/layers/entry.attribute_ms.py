"""Host phase `attribute` of `step`, the program's own span, median over the
window's steps: the per-lane rule-id lists and the StepResult."""
import step_spans


def read(ctx):
    return step_spans.phase_ms(ctx, "attribute")

"""Host phase `account` of `step`, the program's own span, median over the
window's steps: evictions, prune and telemetry folds, admission, the per-
rule stats loop, deny records."""
import step_spans


def read(ctx):
    return step_spans.phase_ms(ctx, "account")

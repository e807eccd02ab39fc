"""Host phase `wait` of `step`, the program's own span, median over the
window's steps: `block_until_ready` on the step's outputs, the host waiting
for the device; should read about `step.device_ms`."""
import step_spans


def read(ctx):
    return step_spans.phase_ms(ctx, "wait")

"""Host phase `fetch` of `step`, the program's own span, median over the
window's steps: `np.asarray` of every output, the device->host copies."""
import step_spans


def read(ctx):
    return step_spans.phase_ms(ctx, "fetch")

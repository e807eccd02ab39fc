"""Host phase `upload` of `step`, the program's own span, median over the
window's steps: the host->device uploads, one `jnp.asarray` a column."""
import step_spans


def read(ctx):
    return step_spans.phase_ms(ctx, "upload")

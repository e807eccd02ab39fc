"""Host->device transfers issued per `step`, counted by the program where
each is issued (median over the window's steps): one per uploaded column or
scalar, each a dispatch of its own on the host."""
import step_spans


def read(ctx):
    return step_spans.counter_per_step(ctx, "h2d_transfers")

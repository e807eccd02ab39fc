"""Device->host copies per `step`, counted by the program where each lands
(median over the window's steps): one per fetched output of the step
program."""
import step_spans


def read(ctx):
    return step_spans.counter_per_step(ctx, "d2h_transfers")

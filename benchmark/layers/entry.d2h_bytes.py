"""Bytes fetched device->host per `step`, counted by the program where each
transfer is issued (median over the window's steps)."""
import step_spans


def read(ctx):
    return step_spans.counter_per_step(ctx, "d2h_bytes")

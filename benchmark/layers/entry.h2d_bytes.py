"""Bytes uploaded host->device per `step`, counted by the program where each
transfer is issued (median over the window's steps)."""
import step_spans


def read(ctx):
    return step_spans.counter_per_step(ctx, "h2d_bytes")

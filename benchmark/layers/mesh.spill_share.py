"""Lanes the mesh placed off their home replica (hash skew past a
replica's B/D lanes) over the lanes offered, per cent, counted by the
program in every step (median over the window's steps).  None where the
engine's record has no such counter (the parent's)."""
import numpy as np

import step_spans


def read(ctx):
    rec = step_spans.window_records(ctx)
    if rec is None or "spill_lanes" not in rec.dtype.names:
        return None
    return float(np.median(100.0 * rec["spill_lanes"] / rec["lanes"]))

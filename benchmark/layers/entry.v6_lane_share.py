"""Lanes of a step whose family mask `is6` is set over the lanes offered, per
cent, counted by the program in `stage` from the batch it was handed (median
over the window's steps).  None where the engine's record has no such
counter (the parent's)."""
import numpy as np

import step_spans


def read(ctx):
    rec = step_spans.window_records(ctx)
    if rec is None or "v6_lanes" not in rec.dtype.names:
        return None
    return float(np.median(100.0 * rec["v6_lanes"] / rec["lanes"]))

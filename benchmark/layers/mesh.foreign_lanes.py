"""Lanes a step left with the verdict of a foreign replica's walk: placed
off-home and NOT re-served from home inside the same call (`spill_lanes` -
`retry_lanes`).  The configuration's guarantee says 0, so this is the
LARGEST over the window's steps, not the median: one step that kept one is
the fault.  None where the engine's record has no such counters (the
parent's)."""
import step_spans


def read(ctx):
    rec = step_spans.window_records(ctx)
    if rec is None or "retry_lanes" not in rec.dtype.names:
        return None
    return float((rec["spill_lanes"] - rec["retry_lanes"]).max())

"""What a slow-path round costs a lane of its WIDTH, in microseconds: device
time inside `while` per traced step (the trace) over the mean, over the
window's steps, of the lanes the rounds were run at (`round_lanes` of the
step record, padding included).  The repo's own unit for a round (PERF.md
s5: 0.92-0.98 us a lane of width on the narrow key, whatever the round
carries).  None where the step module was not traced, the record lacks the
counter or no round ran.  Where rounds ran and the trace nests no op under a
`while` it reads 0: the CPU backend's trace, in the harness's own tests (on
the chip a round that ran is a `while` that took time)."""
import numpy as np

import reduce_trace
import step_spans


def read(ctx):
    ms = reduce_trace.step_device_ms(ctx["reduced"], ctx["config"])
    rec = step_spans.window_records(ctx)
    if not ms or rec is None or "round_lanes" not in rec.dtype.names:
        return None
    width = float(np.mean(rec["round_lanes"]))
    return 1e3 * ms["while"] / width if width else None

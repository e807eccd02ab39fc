"""Sub-span `retry` of `step`'s `account` phase, the program's own stamps,
median over the window's steps: the whole of `_spill_retry` — staging, the
second sharded call, its wait, its fetch, the merge; 0 in a step where no
lane spilled.  None where the engine's record has no such field (the
parent's)."""
import numpy as np

import step_spans


def read(ctx):
    rec = step_spans.window_records(ctx)
    if rec is None or "retry_t1" not in rec.dtype.names:
        return None
    return float(np.median(rec["retry_t1"] - rec["retry_t0"])) / 1e6

"""Sub-span `route` of `step`'s `stage` phase, the program's own stamps,
median over the window's steps: the shard hash of every 5-tuple, the
failover mask, `_shard_placement` and the permutation of the columns into
replica order (parallel/meshpath.MeshDatapath).  None where the engine's
record has no such field (the parent's)."""
import numpy as np

import step_spans


def read(ctx):
    rec = step_spans.window_records(ctx)
    if rec is None or "route_t1" not in rec.dtype.names:
        return None
    return float(np.median(rec["route_t1"] - rec["route_t0"])) / 1e6

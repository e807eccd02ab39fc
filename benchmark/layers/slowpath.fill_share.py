"""How full the slow-path rounds ran: the window's misses over the lanes its
rounds were run at, per cent.  The step program counts both itself: `n_miss`
and `round_lanes` (the sum of the widths of the rounds it ran, padding
included) of the step record.  None where the record has no such counter
(the parent's) or no round ran.  Read in the one-chip cells only
(`workloads` in BENCHMARK.json): the mesh's `n_miss` is the merged image of
a step, its `round_lanes` the sum over the replicas' foreign walks and the
spill retry, so their quotient there is not a share of the rounds."""
import step_spans


def read(ctx):
    rec = step_spans.window_records(ctx)
    if rec is None or "round_lanes" not in rec.dtype.names:
        return None
    lanes = int(rec["round_lanes"].sum())
    return 100.0 * int(rec["n_miss"].sum()) / lanes if lanes else None

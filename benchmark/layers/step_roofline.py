"""The least time the chip could take for a step's unavoidable bytes (work.py,
HBM peak of peaks.json) over the step program's device time.  Bound by
bandwidth: a lookup has no arithmetic to speak of.  On several chips the
device time is a replica's (reduce_trace: the mean over chips), so the bytes
are a replica's too: its share of the lanes and of the misses."""
import reduce_trace
import work


def read(ctx):
    ms = reduce_trace.step_device_ms(ctx["reduced"], ctx["config"])
    w = ctx["window"]
    if not ms:
        return None
    # The window's mean miss count stands for the traced steps'.
    chips = ctx["reduced"]["chips"]
    least = work.least_seconds(w.lanes[0] / chips,
                               sum(w.n_miss) / len(w.n_miss) / chips,
                               ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least * 1e3 / ms["all"]

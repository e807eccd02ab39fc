"""Device time of the step's ops outside any `while` body, per traced step:
hash, cache probe, refresh and the output assembly."""
import reduce_trace


def read(ctx):
    ms = reduce_trace.step_device_ms(ctx["reduced"], ctx["config"])
    return ms and ms["all"] - ms["while"]

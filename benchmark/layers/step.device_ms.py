"""Device-busy time of the step's XLA module(s), per traced step."""
import reduce_trace


def read(ctx):
    ms = reduce_trace.step_device_ms(ctx["reduced"], ctx["config"])
    return ms and ms["all"]

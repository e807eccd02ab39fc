"""Device time of the step's ops inside a `while` body, per traced step: the
synchronous slow-path rounds (ServiceLB, classify, commit scatters,
eviction).  Nothing to read where no round ran."""
import reduce_trace


def read(ctx):
    ms = reduce_trace.step_device_ms(ctx["reduced"], ctx["config"])
    return (ms and ms["while"]) or None

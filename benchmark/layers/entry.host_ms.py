"""Per traced step: wall inside `step` minus the device-busy time that falls
inside it — staging, uploads, dispatch, the fetch of every output and host
post-processing, as far as the device does not hide them."""


def read(ctx):
    steps = ctx["reduced"]["steps"]
    if not steps:
        return None
    return 1e3 * sum(dur - busy for _, dur, busy in steps) / len(steps)

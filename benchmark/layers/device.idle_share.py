"""1 - (union of device-busy intervals / traced window)."""


def read(ctx):
    r = ctx["reduced"]
    if not r["window_s"]:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])

"""Executables built inside the timed window: the sum of `xla_builds` over
the window's step records (the builds the program saw inside each `step`
call).  Should read 0: every shape is warmed up in set-up.  None on a record
without the field (the parent's)."""
import step_spans


def read(ctx):
    rec = step_spans.window_records(ctx)
    if rec is None or "xla_builds" not in rec.dtype.names:
        return None
    return float(rec["xla_builds"].sum())

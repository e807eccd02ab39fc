"""Bytes of rule, isolation and Service tables that the cell's one install
placed on the device, counted by the commit plane from the placed arrays.
None where `last_commit()` has no such key (the parent's)."""


def read(ctx):
    tracer = getattr(ctx["engine"], "realization_tracer", None)
    read_last = getattr(tracer, "last_commit", None)
    last = read_last() if read_last is not None else None
    return float(last["table_bytes"]) if last and "table_bytes" in last else None

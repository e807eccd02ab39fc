"""Sub-span `tables` of the install's `compile` stage, the program's own span:
the host table build (`ops/match.to_host`, `_pad_tables`).  None where
`last_commit()` has no such key (the parent's)."""
import step_spans


def read(ctx):
    try:
        return step_spans.commit_stage_s(ctx, "tables")
    except KeyError:
        return None

"""Sub-span `oracle` of the install's `canary` stage, the program's own span:
the scalar Oracle built over the bundle and the probes' wanted verdicts.
None where `last_commit()` has no such key (the parent's)."""
import step_spans


def read(ctx):
    try:
        return step_spans.commit_stage_s(ctx, "oracle")
    except KeyError:
        return None

"""Sub-span `walk` of the install's `canary` stage, the program's own span:
the candidate's fresh walk of the probes (`_canary_classify`, eager).  None
where `last_commit()` has no such key (the parent's)."""
import step_spans


def read(ctx):
    try:
        return step_spans.commit_stage_s(ctx, "walk")
    except KeyError:
        return None

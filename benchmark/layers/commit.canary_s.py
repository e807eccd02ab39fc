"""Stage `canary` of the install's commit transaction, from the commit plane's
own stamps: the fresh-probe canary gate against the scalar oracle."""
import step_spans


def read(ctx):
    return step_spans.commit_stage_s(ctx, "canary")

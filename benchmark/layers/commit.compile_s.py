"""Stage `compile` of the install's commit transaction, from the commit plane's
own stamps: commit begin -> candidate built and uploaded (snapshot, host
rule compile, upload)."""
import step_spans


def read(ctx):
    return step_spans.commit_stage_s(ctx, "compile")

"""The engine constructor's own span `construct` (`tpuflow.construct`): boot
tables, planes, the audit plane's boot digests, before the install's commit
begins.  Read from `last_commit()`; None where it has no such key (the
parent's)."""
import step_spans


def read(ctx):
    try:
        return step_spans.commit_stage_s(ctx, "construct")
    except KeyError:
        return None

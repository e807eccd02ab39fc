"""Span `digest` of the install's commit, after the settle stamp and in no
stage: the audit plane's golden digests.  None where `last_commit()` has no
such key (the parent's)."""
import step_spans


def read(ctx):
    try:
        return step_spans.commit_stage_s(ctx, "digest")
    except KeyError:
        return None

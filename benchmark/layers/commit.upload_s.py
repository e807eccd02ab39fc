"""Sub-span `upload` of the install's `compile` stage, from the commit plane's
own stamps: the host rule, isolation and Service tables going to the device
(placement and the wait for it; the host build before it is the rest of
`commit.compile_s`).  None where `last_commit()` has no such key (the
parent's)."""
import step_spans


def read(ctx):
    try:
        return step_spans.commit_stage_s(ctx, "upload")
    except KeyError:
        return None

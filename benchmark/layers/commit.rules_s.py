"""Sub-span `rules` of the install's `compile` stage, the program's own span:
the host rule compile (`compile_policy_set`, rung padding, `rule_split`).
None where `last_commit()` has no such key (the parent's)."""
import step_spans


def read(ctx):
    try:
        return step_spans.commit_stage_s(ctx, "rules")
    except KeyError:
        return None

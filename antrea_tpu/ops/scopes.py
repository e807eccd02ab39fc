"""Scope names of the device step program: a leaf module, so the kernel
layer names its sections without depending on the observability plane
(`observability/tracing` re-exports both names beside STEP_PHASES).

`jax.named_scope` names of the step program (models/pipeline,
models/forwarding, ops/match) — how the device's layers are told apart
in a profiler trace: every op of the step lowers under a path of these,
nested as listed (`probe`/`refresh`/`assemble` inside `fast_path`; the round-loop scopes and the `classify.*` stages inside
`miss_detect`, `classify.index6` — the v6 interval search of a dual-stack
engine, ops/match._searchsorted6 — inside `classify.candidate` or
`classify.summary`; `eviction_scan` inside `cache_commit`; `egress`, the
packing of the served step's outputs into one record, after them all).
The ONE place the scope names are declared: call sites go through
`device_scope`, which refuses any other name.
"""

import jax

STEP_SCOPES = (
    "fast_path", "probe", "refresh", "assemble", "forwarding", "miss_detect",
    "service_lb", "classify", "classify.summary", "classify.candidate",
    "classify.index6", "classify.scan", "cache_commit", "eviction_scan",
    "egress",
)


def device_scope(name: str):
    """`jax.named_scope(name)` for a name of STEP_SCOPES — metadata only
    (zero device ops), placed unconditionally so paired lowerings stay
    bit-identical."""
    if name not in STEP_SCOPES:
        raise ValueError(f"{name!r} is not in STEP_SCOPES {STEP_SCOPES}")
    return jax.named_scope(name)

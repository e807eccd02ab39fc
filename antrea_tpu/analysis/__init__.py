"""Unified static-analysis plane: one AST engine, every drift gate.

Public surface:

  run(root=REPO, pass_ids=None) -> RunResult   the whole suite (or a
                                               subset), baseline applied
  run_cli(pass_id, argv) -> int                the legacy tools/check_*
                                               shim entry point
  PASSES                                       id -> (fn, invariant)

The eight legacy `tools/check_*.py` gates live here as passes (the tools
remain as thin CLI shims, verdict-identical — pinned by
tests/test_static_analysis.py), joined by the semantic passes that
pin the hand-caught bug classes: `thread-safety`, `bounded-cache`,
`jit-purity`, `donation-safety`, `bounded-buffer`, `canonical-shape`.
Everything is stdlib-only (ast/re/
json): importing this subpackage never pulls jax, so every gate runs on
any CI image.  See core.py for the engine contract (SourceCache,
Finding, allowlists, BASELINE.analysis.json)."""

from .core import (  # noqa: F401
    BASELINE_NAME,
    Finding,
    PASSES,
    REPO,
    RunResult,
    SourceCache,
    analysis_pass,
    load_baseline,
    run,
    run_cli,
)

# Importing the pass modules registers them (registration order is the
# run order: the eight migrated gates first, then the semantic passes).
from . import (  # noqa: E402,F401
    mesh,
    metrics,
    events,
    commit_plane,
    audit_plane,
    maintenance,
    reshard,
    tenant,
    threads,
    caches,
    jit_purity,
    donation,
    bounded_buffer,
    telemetry,
    canonical_shape,
)

__all__ = [
    "BASELINE_NAME", "Finding", "PASSES", "REPO", "RunResult",
    "SourceCache", "analysis_pass", "load_baseline", "run", "run_cli",
]

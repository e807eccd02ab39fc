"""Persistent XLA compilation cache for the repo's entry scripts.

One helper, called by every script that compiles at deployment size
(chip_smoke.py, dissemination/agent_proc.py) before its first
trace.  Library modules set nothing on import.  The tier-1 CPU cache stays
where tests/conftest.py puts it (outside the checkout, so it is never
copied to a chip machine along with the tree).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """-> the cache directory in use.

    JAX_COMPILATION_CACHE_DIR set: nothing to do — JAX reads the variable
    itself, and no other path is set in code.  Unset: `<checkout>/.jax_cache`
    (git-ignored).  The path is part of how a run finds its entries again,
    so it is fixed — never a temporary name, a pid or a time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # The install canary and the audit walks run EAGERLY: hundreds of
    # sub-second per-op executables that the default 1 s floor would
    # recompile on every start.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path

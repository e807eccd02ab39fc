"""Device timing by in-dispatch loops.

`device_loop_time` runs the kernel K times *inside one dispatch* via
lax.fori_loop (with a carry dependency so iterations cannot be collapsed
or reordered), fetches one scalar of the result to the host — which waits
for the device to finish — and differences two K values, so the fixed
cost of one dispatch plus one fetch cancels out of the per-iteration
time.  What it measures is the device step alone; the host side of a
served step (staging, transfers, fetches) is deliberately outside it.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


def _fetch_scalar(x) -> float:
    return float(np.asarray(x).ravel()[0])


def device_loop_time(
    make_step: Callable,
    init_carry,
    *,
    k_small: int = 2,
    k_big: int = 12,
    repeats: int = 3,
) -> float:
    """Seconds per iteration of make_step, measured on-device.

    make_step(i, carry) -> carry' must be jit-traceable; carry must be a
    pytree of arrays whose first leaf's first element participates in every
    iteration (so the loop cannot be dead-code eliminated).
    """

    def run_k(k):
        @jax.jit
        def f(carry):
            return jax.lax.fori_loop(0, k, make_step, carry)

        # warm (compile) then time.
        _fetch_scalar(jax.tree.leaves(f(init_carry))[0])
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = f(init_carry)
            _fetch_scalar(jax.tree.leaves(out)[0])
            best = min(best, time.perf_counter() - t0)
        return best

    t_small = run_k(k_small)
    t_big = run_k(k_big)
    return max((t_big - t_small) / (k_big - k_small), 1e-9)

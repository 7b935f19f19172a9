"""Device-time measurement by slope.

A host read-back is a synchronisation point with a fixed cost of its
own, so a methodology that fetches full outputs, or runs too few reps,
measures the read-back and not the device. `devtime` dispatches k and
4k dependent-free
calls, drains with a 1-element fetch, and fits the slope; k widens
until the 4k batch costs >= 2x the k batch.
"""

from __future__ import annotations

import time

import numpy as np
import jax


def _force_tiny(out):
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(leaf.ravel()[0:1])


def devtime(fn, *args, k0: int = 8, max_widen: int = 5) -> float:
    """Marginal per-call device seconds of fn(*args)."""
    _force_tiny(fn(*args))  # compile + warm

    def timed(k):
        t0 = time.perf_counter()
        out = None
        for _ in range(k):
            out = fn(*args)
        _force_tiny(out)
        return time.perf_counter() - t0

    k = k0
    for _ in range(max_widen):
        t1 = timed(k)
        t4 = timed(4 * k)
        if t4 >= 2.0 * t1:
            return max((t4 - t1) / (3 * k), 1e-9)
        k *= 4
    # degenerate: op so cheap the RTT dominates even at huge k
    return max((t4 - t1) / (3 * k), 1e-9)

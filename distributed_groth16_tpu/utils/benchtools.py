"""Shared marginal-cost timing for on-chip benchmarks.

Device kernels are timed as the MARGINAL cost between a K=1 and K=3
back-to-back jitted loop (distinct inputs per iteration, checksummed
output) with full host materialisation as the fence, which takes per-call
dispatch latency out of the figure. Used by bench.py and
scripts/profile_msm.py — one implementation so BASELINE numbers stay
methodologically comparable.
"""

from __future__ import annotations

import time

import numpy as np


def marginal_cost(make_fn, args, reps: int = 4) -> float:
    """Seconds per iteration: make_fn(k) must return a jitted callable
    running its workload k times back-to-back; cost = (t3 - t1) / 2 with
    each t the best of `reps` host-synced timings after a warmup call."""

    def timed(k: int) -> float:
        fn = make_fn(k)
        _ = np.asarray(fn(*args))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            _ = np.asarray(fn(*args))  # host sync fence
            best = min(best, time.perf_counter() - t0)
        return best

    t1, t3 = timed(1), timed(3)
    return max((t3 - t1) / 2, 1e-9)

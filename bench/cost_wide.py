"""Operations and bytes of the allocator DP's wide body, from the deployment.

The work is that of the exact Poisson-binomial prefix DP over one row of n
workers with the count axis capped where no threshold can tell counts apart
(``kernels/poisson_binomial``'s wide body caps the same way, but nothing of
the program is imported): after worker i only the counts in
[max(L_i, 0), min(i, T)] are needed, where T is the largest feasible
threshold (every count at or above it passes every tail) and L_i the lowest
count that can still reach a later feasible threshold. It is a function of
(n, K*, ell_g, ell_b, rows) only, never of the implementation's padding or
tiling. Each needed count takes one multiply-add (2 operations); bytes and
the peak the operations are held to are those of ``cost.poisson_binomial``.
"""

from __future__ import annotations

import numpy as np

from bench import cost

_NONE = 2**40            # above any threshold: no feasible prefix remains


def thresholds(n: int, kstar: int, ell_g: int, ell_b: int) -> np.ndarray:
    """w(i) = ceil((K* - (n - i) ell_b) / ell_g) for prefixes i = 1..n."""
    i = np.arange(1, n + 1, dtype=np.int64)
    return -((-(kstar - (n - i) * ell_b)) // ell_g)


def needed_counts(n: int, kstar: int, ell_g: int, ell_b: int) -> int:
    """Count updates one row's capped DP needs, summed over its n workers."""
    i = np.arange(1, n + 1, dtype=np.int64)              # workers seen
    w = thresholds(n, kstar, ell_g, ell_b)
    feasible = w <= i
    if not feasible.any():
        return 0
    v = np.maximum(w, 0)
    top = int(v[feasible].max())
    # a count c after i workers reaches at most c + (j - i) after j
    reach = np.where(feasible, v - i, _NONE)
    floor = i + np.minimum.accumulate(reach[::-1])[::-1]
    lo = np.maximum(floor, 0)
    hi = np.minimum(i, top)
    return int(np.maximum(hi - lo + 1, 0).sum())


def poisson_binomial_wide(rows: float, n: int, kstar: int, ell_g: int,
                          ell_b: int) -> cost.Cost:
    """``rows`` capped DPs over n workers at one (K*, ell_g, ell_b)."""
    return cost.Cost(ops=2.0 * rows * needed_counts(n, kstar, ell_g, ell_b),
                     bytes=3.0 * cost.F32 * rows * n,
                     peak="bf16_flops_per_s")

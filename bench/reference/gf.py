"""Plain reference of the coded round's answer: f(X_j) = X_j w mod p.

The master's decoded output must equal the uncoded product of each data
block with the query vector over GF(p), p = 2^31 - 1. Computed here in
int64 numpy with no coding at all: w is split into 16-bit halves so that a
3000-term dot product of 31-bit residues with 16-bit halves stays below
2^63. The control computes the same product in float64, the precision a
floating-point matrix unit would offer, which cannot hold 62-bit products.
"""

from __future__ import annotations

import numpy as np

FIELD_P = 2**31 - 1
_HALF = 16


def products_modp(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """(m, c) @ (c, q) mod p for residues in [0, p), exact."""
    x = np.asarray(x, np.int64)
    w = np.asarray(w, np.int64)
    if x.shape[1] > 2**(63 - 31 - _HALF):
        raise ValueError("contraction too long for the 16-bit split")
    lo = (x @ (w & (2**_HALF - 1))) % FIELD_P
    hi = (x @ (w >> _HALF)) % FIELD_P
    return (lo + (hi * 2**_HALF) % FIELD_P) % FIELD_P


def products_float64(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """The control: the same product in float64, then reduced mod p."""
    prod = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    return np.mod(prod, float(FIELD_P)).astype(np.int64)

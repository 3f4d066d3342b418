"""Operations and bytes of each kernel call, from its shapes, and the roofline.

The counts are the work the algorithm needs, whatever implements it: no
padding, no recomputation. A kernel's roofline share is the least time the
chip could take for that work (the larger of operations over the peak rate
and bytes over the memory bandwidth) divided by the kernel's device time.
"""

from __future__ import annotations

import dataclasses
import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
F32 = 4
I32 = 4


@dataclasses.dataclass(frozen=True)
class Cost:
    ops: float
    bytes: float
    peak: str          # the key of the compute peak the ops are held to

    def __add__(self, other: "Cost") -> "Cost":
        if self.peak != other.peak:
            raise ValueError(f"cannot add costs held to {self.peak} and {other.peak}")
        return Cost(self.ops + other.ops, self.bytes + other.bytes, self.peak)


def peaks(device_kind: str, path: str = _PEAKS) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def poisson_binomial(rows: int, n: int) -> Cost:
    """Prefix tails of ``rows`` Poisson-binomial DPs over ``n`` workers.

    Each of the n convolution steps updates n + 1 counts with one
    multiply-add (2 operations): 2 * rows * n * (n + 1). Bytes: the
    probabilities and thresholds in and the tails out, (rows, n) float32 or
    int32 each. The VPU has no published peak, so the operations are held to
    the bf16 rate, the highest published floating-point rate.
    """
    return Cost(ops=2.0 * rows * n * (n + 1),
                bytes=3.0 * F32 * rows * n,
                peak="bf16_flops_per_s")


def gf_matmul(m: int, k: int, n: int) -> Cost:
    """Exact (m, k) @ (k, n) over GF(p): 2 * m * k * n modular multiply-add
    operations, held to the int8 rate (the chip's highest integer rate);
    int32 residues in and out."""
    return Cost(ops=2.0 * m * k * n,
                bytes=float(I32 * (m * k + k * n + m * n)),
                peak="int8_ops_per_s")


def least_time(cost: Cost, peak: dict) -> tuple[float, str]:
    """(seconds, bound) -- the least time for ``cost`` and what bounds it."""
    t_ops = cost.ops / peak[cost.peak]
    t_bytes = cost.bytes / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def roofline_pct(cost: Cost, peak: dict, device_seconds: float) -> float:
    """Least time over measured device time, in percent."""
    if device_seconds <= 0:
        raise ValueError("a roofline share needs a device time above 0")
    return 100.0 * least_time(cost, peak)[0] / device_seconds

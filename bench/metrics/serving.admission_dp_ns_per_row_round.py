"""Device nanoseconds under the ``repro.admission_dp`` scope per simulated
row-round: the batched dynamic programme that predicts, before the scan,
each round's success probability for the admission gate.

The trace holds the traced calls alone, and the admission DP is the first
device work of each call, so every one of its operations in the trace
counts, also one that starts before the first ``bench.call`` span opens on
the trace's clock (as a coded round's first products did, by up to 0.8 ms,
on a TPU v5e)."""

import numpy as np

SCOPE = "repro.admission_dp"


def read(view):
    ns = float(np.mean([sum(e.self_ns for e in ops if SCOPE in e.path)
                        for ops in view.device_ops]))
    if ns <= 0:
        return None
    return ns / (view.n_calls * view.info["row_rounds_per_call"])

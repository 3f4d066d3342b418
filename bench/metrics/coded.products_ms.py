"""Device milliseconds under the ``repro.products`` scope per coded round:
every worker's exact GF(p) product of its coded blocks with the query.

The trace holds the traced rounds alone, and the products are the first
device work of each round: on the trace's clock the first round's products
can start before the first ``bench.call`` span opens (by up to 0.8 ms in
traces on a TPU v5e). So every products operation of the trace counts,
not only those that start inside the window."""

import numpy as np

SCOPE = "repro.products"


def read(view):
    ns = float(np.mean([sum(e.self_ns for e in ops if SCOPE in e.path)
                        for ops in view.device_ops]))
    if ns <= 0:
        return None
    return ns / (view.n_calls * view.info["rounds_per_call"]) * 1e-6

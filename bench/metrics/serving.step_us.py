"""Device microseconds of one step of the serving scan (all rows of the
batch together): self time of the operations in the scan's loop body over
the rounds it ran. The arrival sampler's rejection loops, also ``while``
loops, are left out."""

SCAN_BODY = "while/body/"


def read(view):
    ns = view.op_ns(lambda e: SCAN_BODY in e.path and "poisson" not in e.path)
    if ns <= 0:
        return None
    return ns / (view.n_calls * view.info["scan_rounds_per_call"]) * 1e-3

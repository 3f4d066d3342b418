"""Device microseconds of the request queue in one step of the serving scan
(all rows of the batch together): self time of the operations under the
``repro.admit``, ``repro.edf_order`` and ``repro.release`` scopes over the
rounds the scan ran."""

SCOPES = ("repro.admit", "repro.edf_order", "repro.release")


def read(view):
    ns = view.op_ns(lambda e: any(s in e.path for s in SCOPES))
    if ns <= 0:
        return None
    return ns / (view.n_calls * view.info["scan_rounds_per_call"]) * 1e-3

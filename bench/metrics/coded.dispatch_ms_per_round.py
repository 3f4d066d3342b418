"""Host milliseconds inside the ``repro.round`` span per coded round: the
dispatch of the products and the decode, up to the return of the device
arrays."""

SPAN = "repro.round"


def read(view):
    spans = [e for e in view.host if e.name == SPAN and view.lo <= e.start < view.hi]
    if not spans:
        return None
    rounds = view.n_calls * view.info["rounds_per_call"]
    return sum(e.dur for e in spans) / rounds * 1e-6

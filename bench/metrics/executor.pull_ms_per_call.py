"""Host milliseconds inside the executor's ``repro.pull`` spans per
``bench.call``: each group's copy of its success array to the host."""

SPAN = "repro.pull"


def read(view):
    spans = [e for e in view.host if e.name == SPAN and view.lo <= e.start < view.hi]
    if not spans:
        return None
    return sum(e.dur for e in spans) / view.n_calls * 1e-6

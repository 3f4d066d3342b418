"""Host milliseconds inside the ``repro.summarize`` span per ``bench.call``:
the host's fold of the success arrays into per-scenario throughputs,
confidence intervals and regrets."""

SPAN = "repro.summarize"


def read(view):
    spans = [e for e in view.host if e.name == SPAN and view.lo <= e.start < view.hi]
    if not spans:
        return None
    return sum(e.dur for e in spans) / view.n_calls * 1e-6

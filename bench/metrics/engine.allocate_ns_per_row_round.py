"""Device nanoseconds under the engine's ``repro.allocate`` scope per
simulated row-round."""


def read(view):
    ns = view.scope_ns("repro.allocate")
    if ns <= 0:
        return None
    return ns / (view.n_calls * view.info["row_rounds_per_call"])

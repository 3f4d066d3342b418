"""Device nanoseconds under the allocator DP's wide body (``repro.dp_wide``,
inside ``repro.allocate``) per simulated row-round, summed over the chips."""


def read(view):
    ns = view.scope_ns("repro.dp_wide")
    if ns <= 0:
        return None
    chips = len(view.device_ops)
    return ns * chips / (view.n_calls * view.info["row_rounds_per_call"])

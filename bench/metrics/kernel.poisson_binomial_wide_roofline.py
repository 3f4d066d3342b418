"""The wide Poisson-binomial kernel's share of its roofline: the least time
of its calls' work (``cost_wide.poisson_binomial_wide``, the exact DP with
the count axis capped, over the cell's DP rows of each K*) over the
kernel's device time, per chip."""

from bench import cost_wide

SCOPE = "repro.dp_wide"


def read(view):
    ns = view.op_ns(lambda e: SCOPE in e.path and "pallas_call" in e.path)
    if ns <= 0:
        return None
    share = view.n_calls / len(view.device_ops)             # calls' rows per chip
    n = int(view.info["dp_width"])
    work = [cost_wide.poisson_binomial_wide(rows * share, n, kstar, ell_g, ell_b)
            for rows, kstar, ell_g, ell_b in view.info["dp_rows"]]
    return cost_wide.cost.roofline_pct(sum(work[1:], work[0]), view.peak, ns * 1e-9)

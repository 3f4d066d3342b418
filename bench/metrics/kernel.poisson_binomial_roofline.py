"""The Poisson-binomial prefix-tail kernel's share of its roofline: the least
time of its calls' work (``cost.poisson_binomial`` over the driver's DP rows,
bound by bytes at these widths) over the kernel's device time."""

from bench import cost

KERNEL = "success_tails_pallas"   # the jitted wrapper of both prefix-tail kernels


def read(view):
    ns = view.op_ns(lambda e: KERNEL in e.path and "pallas_call" in e.path)
    if ns <= 0:
        return None
    work = cost.poisson_binomial(int(view.n_calls * view.info["dp_rows_per_call"]),
                                 int(view.info["dp_width"]))
    return cost.roofline_pct(work, view.peak, ns * 1e-9)

"""The GF(p) kernel's share of its roofline on the workers' products (the
calls outside the decode): the least time of ``cost.gf_matmul`` at the
products' shape over the kernel's device time there."""

from bench import cost

KERNEL = "matmul_gf_pallas"       # the jitted wrapper of the GF(p) kernel


def read(view):
    ns = view.op_ns(lambda e: KERNEL in e.path and "pallas_call" in e.path
                    and "repro.decode" not in e.path)
    if ns <= 0:
        return None
    m, k, n = view.info["gf_products"]
    work = cost.gf_matmul(m, k, n)
    rounds = view.n_calls * view.info["rounds_per_call"]
    return cost.roofline_pct(work, view.peak, ns * 1e-9 / rounds)

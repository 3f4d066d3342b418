"""The readers of the program's own spans and scopes, on hand-built traces
checked by hand arithmetic, and silent where the span or scope is absent."""

import pytest

from bench import cost, spec
from bench.trace_reduce import Event, TraceView

MS = 1e6                                                    # ns

READERS = {
    "executor.pull_ms_per_call": "ec2_t2micro.sweep",
    "sweeps.summarize_ms_per_call": "ec2_t2micro.sweep",
    "coded.products_ms": "ec2_t2micro.coded_round",
    "coded.dispatch_ms_per_round": "ec2_t2micro.coded_round",
    "serving.queue_us": "sim_t2micro.serve",
    "serving.admission_dp_ns_per_row_round": "sim_t2micro.serve",
}


def reader(name):
    return spec.load_cell(READERS[name]).readers[name]


def test_sweep_host_spans_per_call():
    host = [Event("bench.call", 0, 1000), Event("repro.run_group", 0, 90),
            Event("repro.pull", 100, 130), Event("repro.pull", 200, 220),
            Event("bench.host_reduce", 290, 370),
            Event("repro.summarize", 300, 360),
            Event("bench.call", 1000, 2000), Event("repro.pull", 1100, 1150),
            Event("repro.summarize", 1300, 1340),
            Event("repro.pull", 2500, 2600)]               # after the window
    v = TraceView([[Event("fusion.1", 10, 90)]], host)
    assert reader("executor.pull_ms_per_call").read(v) == pytest.approx(
        (30 + 20 + 50) / 2 * 1e-6)
    assert reader("sweeps.summarize_ms_per_call").read(v) == pytest.approx(
        (60 + 40) / 2 * 1e-6)


def test_coded_round_products_and_dispatch():
    products = "jit(_products_modp)/repro.products"
    decode = "jit(_decode_on_time_modp)/repro.decode"
    ops, host = [], []
    for r in range(2):                                      # two rounds
        t = r * 40 * MS
        ops += [Event("fusion", t + 0.5 * MS, t + 1 * MS,
                      f"{products}/jit(remainder)/rem:"),
                Event("matmul_gf_pallas.1", t + 1 * MS, t + 33 * MS,
                      f"{products}/jit(matmul_gf_pallas)/pallas_call:"),
                Event("matmul_gf_pallas.1", t + 33 * MS, t + 33.1 * MS,
                      f"{decode}/jit(matmul_gf_pallas)/pallas_call:")]
        host += [Event("bench.call", t, t + 40 * MS),
                 Event("repro.round", t, t + 0.4 * MS)]
    info = {"rounds_per_call": 1.0, "gf_products": (3750, 3000, 1)}
    v = TraceView([ops], host, info=info, peak=cost.peaks("TPU v5 lite"))
    assert reader("coded.products_ms").read(v) == pytest.approx(32.5)
    assert reader("coded.dispatch_ms_per_round").read(v) == pytest.approx(0.4)
    # the roofline still finds the kernel under the products' scope
    gf = spec.load_cell("ec2_t2micro.coded_round").readers["kernel.gf_roofline"]
    assert gf.read(v) == pytest.approx(100 * (45_027_000 / 819e9) / 32e-3)
    # the first round's products start before the first call span opens,
    # on the trace's clock: they still count
    early = [Event(e.name, e.start - 1.2 * MS, e.end - 1.2 * MS, e.path)
             if e.start < 34 * MS and "products" in e.path else e for e in ops]
    v = TraceView([early], host, info=info, peak=cost.peaks("TPU v5 lite"))
    assert reader("coded.products_ms").read(v) == pytest.approx(32.5)


def test_serving_queue_and_admission_dp():
    body = "jit(_run_serving_group)/vmap(vmap())/while/body/closed_call"
    dp = "jit(_run_serving_group)/vmap(repro.admission_dp)"
    ops = [Event("fusion.1", 0, 40, f"{dp}/reduce_sum:"),
           Event("pallas.2", 40, 100, f"{dp}/jit(success_tails_pallas_w)/pallas_call:"),
           Event("fusion.3", 100, 110, f"{body}/repro.admit/add:"),
           Event("sort.4", 110, 130,
                 f"{body}/repro.allocate/repro.edf_order/jit(argsort)/sort:"),
           Event("fusion.5", 130, 330, f"{body}/repro.allocate/gt:"),
           Event("fusion.6", 330, 335, f"{body}/repro.release/and:"),
           Event("fusion.7", 335, 400, f"{body}/repro.score/add:")]
    info = {"row_rounds_per_call": 50.0, "scan_rounds_per_call": 2.0}
    v = TraceView([ops], [Event("bench.call", 0, 1000)], info=info)
    assert reader("serving.queue_us").read(v) == pytest.approx(
        (10 + 20 + 5) / 2 * 1e-3)
    assert reader("serving.admission_dp_ns_per_row_round").read(v) == pytest.approx(
        100 / 50)
    # the admission DP opens the call: it counts from before the call span too
    early = [Event(e.name, e.start - 50, e.end - 50, e.path) for e in ops[:2]]
    v2 = TraceView([early + ops[2:]], [Event("bench.call", 0, 1000)], info=info)
    assert reader("serving.admission_dp_ns_per_row_round").read(v2) == pytest.approx(
        100 / 50)
    # the EDF order stays inside the allocation's count
    alloc = spec.load_cell("sim_t2micro.serve").readers[
        "serving.allocate_ns_per_row_round"]
    assert alloc.read(v) == pytest.approx((20 + 200) / 50)


@pytest.mark.parametrize("name", sorted(READERS))
def test_silent_without_its_span_or_scope(name):
    info = {"rounds_per_call": 1.0, "row_rounds_per_call": 50.0,
            "scan_rounds_per_call": 2.0}
    v = TraceView([[Event("fusion.1", 0, 40, "jit(f)/repro.allocate/add:")]],
                  [Event("bench.call", 0, 100), Event("bench.host_reduce", 50, 90)],
                  info=info)
    assert reader(name).read(v) is None

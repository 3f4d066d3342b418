"""The trace reduction on a hand-built trace, checked by hand arithmetic."""

import pytest

from bench import cost
from bench.trace_reduce import Event, TraceView

# two calls on the host; device ops inside them, one under repro.allocate
HOST = [
    Event("bench.call", 0, 100),
    Event("PjitFunction(_run_group)", 0, 20),
    Event("bench.host_reduce", 70, 100),
    Event("bench.call", 100, 200),
    Event("PjitFunction(_run_group)", 100, 115),
    Event("bench.host_reduce", 180, 200),
]
OPS = [
    Event("fusion.1", 10, 40, "fusion.1 tf_op=jit(_run_group)/repro.trajectory/add"),
    Event("_pb_kernel", 40, 60, "_pb_kernel tf_op=jit(_run_group)/repro.allocate/pallas_call"),
    Event("fusion.1", 110, 150, "fusion.1 tf_op=jit(_run_group)/repro.trajectory/add"),
    Event("_pb_kernel", 150, 170, "_pb_kernel tf_op=jit(_run_group)/repro.allocate/pallas_call"),
    Event("fusion.9", 250, 260, "fusion.9 outside the window"),
]


@pytest.fixture
def view():
    return TraceView([OPS], HOST, info={"row_rounds_per_call": 10.0},
                     peak=cost.peaks("TPU v5 lite"))


def test_window_and_busy_time(view):
    assert view.n_calls == 2
    assert view.window_s() == pytest.approx(200e-9)
    # busy: [10, 60] and [110, 170] -> 50 + 60 ns; the op at 250 is outside
    assert view.busy_s() == pytest.approx(110e-9)


def test_time_under_a_scope_and_of_a_kernel(view):
    assert view.scope_ns("repro.allocate") == pytest.approx(20 + 20)
    assert view.scope_ns("repro.trajectory") == pytest.approx(30 + 40)
    assert view.op_ns(lambda e: e.name == "_pb_kernel") == pytest.approx(40)
    assert view.scope_ns("repro.decode") == 0


def test_idle_inside_each_call(view):
    # call 1: 100 - 50 busy; call 2: 100 - 60 busy
    assert view.idle_ns_per_call() == pytest.approx([50.0, 40.0])


def test_idle_gaps_are_named_by_the_host_span(view):
    gaps = view.idle_gaps()
    # [60, 110]: midpoint 85 is in the first call's host reduce
    assert gaps[0] == ("bench.host_reduce", pytest.approx(50e-9))
    assert sorted(s for _, s in gaps) == pytest.approx([10e-9, 30e-9, 50e-9])
    assert ("bench.call / PjitFunction(_run_group)", pytest.approx(10e-9)) in gaps


def test_breakdown_lists_the_heaviest_ops_first(view):
    b = view.breakdown()
    assert b["device_ops"] == [["fusion.1", pytest.approx(70e-9)],
                               ["_pb_kernel", pytest.approx(40e-9)]]
    assert [g[0] for g in b["idle_gaps"]] == ["bench.host_reduce", "bench.host_reduce",
                                            "bench.call / PjitFunction(_run_group)"]


def test_overlapping_ops_count_once_in_busy_time():
    ops = [Event("a", 0, 10), Event("b", 5, 15)]
    v = TraceView([ops], [Event("bench.call", 0, 20)])
    assert v.busy_s() == pytest.approx(15e-9)
    assert v.idle_ns_per_call() == pytest.approx([5.0])


def test_several_chips_average(view):
    two = TraceView([OPS, OPS[:2]], HOST)
    assert two.busy_s() == pytest.approx((110e-9 + 50e-9) / 2)


def test_a_trace_without_device_ops_or_calls_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        TraceView([[]], HOST)
    with pytest.raises(ValueError, match="bench.call"):
        TraceView([OPS], [Event("x", 0, 1)])


def test_metric_readers_on_the_hand_built_trace(view):
    from bench import spec

    cell = spec.load_cell("ec2_t2micro.sweep")
    idle = cell.readers["executor.idle_ms_per_call"].read(view)
    assert idle == pytest.approx(45e-6)
    alloc = cell.readers["engine.allocate_ns_per_row_round"].read(view)
    assert alloc == pytest.approx(40 / (2 * 10.0))


def test_nested_ops_count_their_self_time():
    # a loop op [0, 100] holding two body ops; the loop's own time is 30
    ops = [Event("while.1", 0, 100, "f/while"),
           Event("fusion.2", 10, 50, "f/while/body/repro.allocate/mul"),
           Event("fusion.3", 60, 90, "f/while/body/add")]
    v = TraceView([ops], [Event("bench.call", 0, 100)])
    assert [e.self_ns for e in v.device_ops[0]] == [30.0, 40.0, 30.0]
    assert v.scope_ns("while/body") == pytest.approx(70.0)
    assert v.busy_s() == pytest.approx(100e-9)
    assert v.breakdown()["device_ops"][0] == ["fusion.2", pytest.approx(40e-9)]


def test_an_xplane_file_is_read_with_scopes_and_programs(tmp_path):
    from bench.trace_reduce import read_xplane, xspace_class

    space = xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "program_id")):
        dev.stat_metadata.add(key=key).value.name = name
    mod = dev.event_metadata.add(key=10).value
    mod.name = "jit_step(77)"
    op = dev.event_metadata.add(key=11).value
    op.name, op.display_name = "%fusion.3 = f32[8] fusion(...)", "fusion.3"
    op.stats.add(metadata_id=1, str_value="jit(step)/repro.allocate/mul:")
    op.stats.add(metadata_id=2, uint64_value=77)
    mods = dev.lines.add(name="XLA Modules")
    mods.events.add(metadata_id=10, offset_ps=1_000_000, duration_ps=9_000_000)
    line = dev.lines.add(name="XLA Ops")
    line.events.add(metadata_id=11, offset_ps=2_000_000, duration_ps=3_000_000)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=5).value.name = "bench.call"
    hl = host.lines.add(name="python", timestamp_ns=500)
    hl.events.add(metadata_id=5, offset_ps=0, duration_ps=20_000_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    devices, host_events = read_xplane(str(path))
    (e,) = devices[0]
    assert (e.name, e.start, e.end, e.module) == ("fusion.3", 2000.0, 5000.0, "jit_step")
    assert "repro.allocate" in e.path
    assert host_events[0].name == "bench.call"
    assert (host_events[0].start, host_events[0].end) == (500.0, 20500.0)


def test_serving_and_coded_readers_by_hand():
    from bench import spec

    body = "jit(_run_serving_group)/vmap(vmap())/while/body/closed_call"
    ops = [Event("fusion.1", 0, 300, f"{body}/repro.allocate/gt:"),
           Event("pallas.2", 300, 500,
                 f"{body}/repro.allocate/jit(success_tails_pallas_w)/pallas_call:"),
           Event("fusion.3", 500, 600, f"{body}/repro.score/add:"),
           Event("fusion.4", 600, 700,
                 "jit(_run_serving_group)/vmap(jit(_poisson))/while/body/add:")]
    info = {"row_rounds_per_call": 50.0, "scan_rounds_per_call": 2.0,
            "dp_rows_per_call": 1000.0, "dp_width": 15}
    v = TraceView([ops], [Event("bench.call", 0, 1000)], info=info,
                  peak=cost.peaks("TPU v5 lite"))
    r = spec.load_cell("sim_t2micro.serve").readers
    assert r["serving.step_us"].read(v) == pytest.approx(600 / 2 * 1e-3)
    assert r["serving.allocate_ns_per_row_round"].read(v) == pytest.approx(500 / 50)
    least = 3 * 4 * 1000 * 15 / 819e9                       # bytes bound
    assert r["kernel.poisson_binomial_roofline"].read(v) == pytest.approx(
        100 * least / 200e-9)

    gf = "jit(matmul_gf_pallas)/pallas_call:"
    ms = 1e6                                                # ns
    ops = [Event("matmul_gf_pallas.1", 0, 40 * ms, gf),
           Event("matmul_gf_pallas.1", 40 * ms, 40.1 * ms,
                 "jit(_decode_on_time_modp)/repro.decode/jit(matmul_gf_pallas)/pallas_call:"),
           Event("fusion.9", 40.1 * ms, 40.3 * ms,
                 "jit(_decode_on_time_modp)/repro.decode/gather:")]
    info = {"rounds_per_call": 1.0, "gf_products": (3750, 3000, 1)}
    v = TraceView([ops], [Event("bench.call", 0, 50 * ms)], info=info,
                  peak=cost.peaks("TPU v5 lite"))
    r = spec.load_cell("ec2_t2micro.coded_round").readers
    assert r["coded.decode_ms"].read(v) == pytest.approx(0.3)
    assert r["kernel.gf_roofline"].read(v) == pytest.approx(
        100 * (45_027_000 / 819e9) / 40e-3)                 # about 0.14%

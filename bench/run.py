"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix; their files, the driver of the traffic's
kind and the readers of the cell's per-layer metrics are found by name
(``bench/spec.py``). The run:

1. refuses to measure without a TPU, or with fewer chips than the cell asks
   for, or with a kernel override set: it then exits non-zero and prints no
   result;
2. keeps JAX's persistent compilation cache in ``<checkout>/.jax_cache``;
3. builds the cell's inputs from ``--seed`` and warms up the cell's own
   shapes: all of that, from process start, is ``setup_s``;
4. ``--trace 0``: runs back-to-back units of work for ``--seconds`` and
   reports the cell's end-to-end metrics; ``--trace 1``: traces the traffic's
   ``trace_calls`` units with the profiler and reports the per-layer
   metrics, the device's busy and window seconds and a breakdown;
5. reads the peak device memory, frees the program's state, and compares
   what the timed path produced with the plain reference. Each number
   compared is printed beside its limit as the last lines of standard
   error and, under ``compared``, as the last key of the result line.

The last line of standard output is one JSON object.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
KERNEL_OVERRIDES = ("REPRO_KERNEL_IMPL", "REPRO_KERNEL_INTERPRET")


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def require_chip(devices, chips: int) -> dict:
    """The device as JAX reports it; exits non-zero unless ``chips`` TPUs."""
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform {platform!r}); nothing measured")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found {len(devices)}")
    for var in KERNEL_OVERRIDES:
        if var in os.environ:
            sys.exit(f"bench: {var} is set; the kernels run as the chip runs them")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_cache() -> None:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()


class CompileCounter:
    """Counts backend compiles while open (persistent-cache hits are not
    compiles)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def timed_window(driver, seconds: float) -> dict:
    """Back-to-back units for ``seconds``; the last unit runs to its end."""
    import jax

    calls = []
    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.call"):
            driver.call()
        t1 = time.perf_counter()
        calls.append((t0, t1, driver.work()))
        if t1 - start >= seconds:
            break
    return {"calls": calls, "start": start, "end": calls[-1][1]}


def traced_window(driver, units: int) -> str:
    """Profile ``units`` whole units; returns the trace directory."""
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(TRACE_DIR)
    try:
        for _ in range(units):
            with jax.profiler.TraceAnnotation("bench.call"):
                driver.call()
    finally:
        jax.profiler.stop_trace()
    return TRACE_DIR


def memory_peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def end_to_end(cell, window: dict, setup_s: float) -> dict:
    out = {}
    for m in cell.end_to_end:
        if m["name"] == spec.SETUP:
            value = setup_s
        else:
            value = cell.e2e_readers[m["name"]].value(window)
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def per_layer(cell, view) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cell.readers[m["name"]].read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(cell, seed: int, seconds: float, trace: int, device: dict,
            devices, t0: float = T0) -> dict:
    """Everything of a run after the look for a chip; returns the result."""
    import jax

    from bench import trace_reduce

    breakdown = None
    with CompileCounter() as compiles:
        with jax.profiler.TraceAnnotation("bench.inputs"):
            driver = cell.kind.setup(cell.config, cell.traffic, seed)
        driver.warm()
        setup_s = time.perf_counter() - t0
        compiled_before = compiles.count
        if trace:
            attempted = int(cell.traffic["trace_calls"])
            tdir = traced_window(driver, attempted)
        else:
            window = timed_window(driver, seconds)
            attempted = len(window["calls"])
        in_window = compiles.count - compiled_before
    if trace:
        view = trace_reduce.TraceView.load(tdir, info=driver.layer_info(),
                                           device_kind=device["kind"],
                                           chips=cell.chips)
        shutil.rmtree(tdir, ignore_errors=True)
        metrics = per_layer(cell, view)
        device = dict(device, busy_s=view.busy_s(), window_s=view.window_s())
        breakdown = view.breakdown()
    else:
        metrics = end_to_end(cell, window, setup_s)
    device = dict(device, memory_peak_bytes=memory_peak_bytes(devices[:cell.chips]))
    driver.free()
    check = driver.check()
    limits = cell.traffic["limits"]
    compared = {name: {"value": value, "limit": limits[name]}
                for name, value in check["numbers"].items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    failed = int(check.get("failed", 0 if correct else 1))
    units = ""
    if not trace:
        times = sorted(t1 - t0 for t0, t1, _ in window["calls"])
        units = (f" (unit seconds min {times[0]:.4f}, median "
                 f"{times[len(times) // 2]:.4f}, max {times[-1]:.4f})")
    print(f"bench: {cell.name} seed {seed}: {attempted} units{units}, "
          f"{in_window} compiles inside the window, setup {setup_s:.3f} s, "
          f"check detail {json.dumps(check.get('detail', {}))}", file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result = {"correct": bool(correct and failed == 0), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    args = parse(sys.argv[1:] if argv is None else argv)
    cell = spec.load_cell(args.workload)
    import jax

    devices = jax.devices()
    device = require_chip(devices, cell.chips)
    enable_cache()
    result = execute(cell, args.seed, args.seconds, args.trace, device, devices)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

Usage:
  python -m benchmarks.run                 # run every suite
  python -m benchmarks.run bench_policies  # run the named suite(s) only
  python -m benchmarks.run --list          # print registered targets + blurbs
  python -m benchmarks.run --check ...     # additionally trend-gate: exit 2
                                           # on any HARD trend regression in
                                           # the BENCH_history.jsonl
                                           # trajectory after the suites run
  python -m benchmarks.run --quiet ...     # suppress the stderr progress
                                           # line (CI logs)

Exit code 0 is the CI smoke gate: every requested suite must produce its
rows without raising (exit 1 otherwise); ``--check`` adds exit 2 when the
robust trend detector (``repro.obs.history.trend_report``) flags a hard
regression — the median of the newest history entries leaving the
committed median ± max(tol·|median|, z·MAD) envelope on the worse side
for a perf metric.  Eight targets additionally refresh a manifest at the
repo root (each blurb in ``SUITES`` names its file): ``fig3_sim`` ->
``BENCH_fig3.json`` (rounds/sec, allocator us/call), ``sweep_smoke`` ->
``BENCH_sweep.json`` (with a soft rows/sec regression check against the
committed baseline), ``bench_speed`` -> ``BENCH_speed.json`` (sync vs
async-pipelined executor rows/sec measured in one process, donated-carry
proof, tap overlap accounting and the persistent-compile-cache
cold-vs-warm process row), ``bench_policies`` -> ``BENCH_policies.json``
(per-policy throughput, baseline ratio, final regret + CI vs the oracle),
``bench_gf`` -> ``BENCH_gf.json`` (exact GF(p) device-vs-numpy speedups,
>= 5x acceptance on the exact coded round), ``bench_faults`` ->
``BENCH_faults.json`` (packet-erasure grid: partial-work-conserving decode
vs all-or-nothing under shared fault traces, retry/degrade outcome
accounting), ``bench_serving`` -> ``BENCH_serving.json`` (streaming
serving grid: latency percentiles, served-requests/sec and the
admission-control-vs-admit-all gain at overload) and ``obs_report`` ->
``BENCH_obs.json`` (cross-bench regression summary: metric deltas vs the
committed baselines, collected softgate warnings, provenance audit,
static hlo_cost rows, the trend section over ``BENCH_history.jsonl``,
plus a telemetry+tap serving run exported as the Chrome trace
``benchmarks/artifacts/obs_trace.json``).  Every manifest write appends
its history record (``repro.obs.history``; ``REPRO_BENCH_HISTORY``
redirects the file).

A stderr progress line (suites done, elapsed — ``repro.obs.metrics.
ProgressLine``) tracks the selection unless ``--quiet``; the process-
default metrics registry collects per-suite wall-clock
(``bench.<target>.seconds``) and the executors' compile/phase attribution
either way.

Profiling: set ``REPRO_PROFILE=<dir>`` to wrap the selected suites in a
``jax.profiler`` trace (``repro.obs.profile_trace``); engine phases are
annotated via ``jax.named_scope`` either way.
"""

import os
import sys
import traceback

# (target name, module under benchmarks/, one-line description) — kept as a
# static table so ``--list`` never has to import jax or the suites.
# Convention: a blurb names the BENCH_*.json it refreshes at the repo root
# IF AND ONLY IF the target writes one (audited by tests/test_benchmarks_cli).
SUITES = [
    ("fig3_sim", "fig3_sim",
     "paper Fig. 3 (4 sim scenarios, LEA vs static vs oracle; writes BENCH_fig3.json)"),
    ("fig4_ec2", "fig4_ec2",
     "paper Fig. 4 (6 EC2 scenarios, simulated credit dynamics)"),
    ("table_kstar", "table_kstar",
     "recovery-threshold table (eqs. 15/16)"),
    ("sweep_smoke", "sweep_smoke",
     "repro.sweeps gate: sharded+chunked grid, bit-exact vs engine; writes BENCH_sweep.json"),
    ("bench_speed", "bench_speed",
     "raw-speed gate: sync vs async-pipelined executor, donated carries, "
     "persistent-cache warm restart; writes BENCH_speed.json"),
    ("bench_policies", "bench_policies",
     "scheduling-policy shoot-out with regret columns; writes BENCH_policies.json"),
    ("bench_gf", "bench_gf",
     "exact GF(p) device path vs numpy modp oracle; writes BENCH_gf.json"),
    ("bench_faults", "bench_faults",
     "fault-injection gate: packet erasure grid, conserve vs all-or-nothing, "
     "retry/degrade accounting; writes BENCH_faults.json"),
    ("bench_serving", "bench_serving",
     "streaming serving gate: arrival grid, latency percentiles, admission "
     "control vs admit-all at overload; writes BENCH_serving.json"),
    ("bench_kernels", "bench_kernels",
     "Pallas-kernel + XLA-path microbenchmarks"),
    ("bench_allocator", "bench_allocator",
     "old (sequential seed) vs new (batched) engine + allocator"),
    ("coded_dp", "coded_dp_bench",
     "beyond-paper: LEA-coded microbatch DP in the trainer"),
    ("roofline", "roofline",
     "33-cell dry-run roofline terms (from experiments/dryrun)"),
    ("obs_report", "obs_report",
     "cross-bench regression summary: metric deltas vs committed baselines, "
     "softgate warnings, provenance audit, hlo_cost rows + Chrome trace; "
     "writes BENCH_obs.json"),
]


def list_targets() -> str:
    width = max(len(name) for name, _, _ in SUITES)
    return "\n".join(f"{name:<{width}}  {desc}" for name, _, desc in SUITES)


def main(argv: list[str] | None = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--list" in argv:
        print(list_targets())
        return
    check = "--check" in argv
    quiet = "--quiet" in argv
    argv = [a for a in argv if a not in ("--check", "--quiet")]

    known = {name for name, _, _ in SUITES}
    unknown = [a for a in argv if a not in known]
    if unknown:
        raise SystemExit(
            f"unknown benchmark target(s): {', '.join(unknown)}\n"
            f"registered targets:\n{list_targets()}"
        )
    selected = [row for row in SUITES if not argv or row[0] in argv]

    import importlib

    # persistent XLA compile cache — one-compile-per-family survives process
    # restarts (repro.launch.cache; the hit listener keeps the unified
    # compile counters honest on warm restarts)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    # REPRO_PROFILE=<dir> wraps the whole selection in a jax.profiler trace;
    # each suite is a host span of its own (timed opens one, under the name
    # of its histogram)
    from repro.obs import profile_trace
    from repro.obs.metrics import DEFAULT as _metrics
    from repro.obs.metrics import ProgressLine, timed

    progress = ProgressLine(total=len(selected), enabled=not quiet,
                            label="benchmarks")
    print("name,us_per_call,derived")
    failed = False
    with profile_trace("benchmarks.run"):
        for i, (name, module, _) in enumerate(selected):
            try:
                fn = importlib.import_module(f"benchmarks.{module}").run
                with timed(f"bench.{name}"):
                    rows = fn()
                for row in rows:
                    print(f"{row['name']},{row['us_per_call']:.2f},\"{row['derived']}\"")
            except Exception as e:  # pragma: no cover
                failed = True
                print(f"{name},0,\"SUITE ERROR: {e}\"", file=sys.stdout)
                traceback.print_exc(file=sys.stderr)
            progress.update(i + 1)
    progress.close()
    if failed:
        raise SystemExit(1)
    if check:
        regressions = _trend_check()
        if regressions:
            for r in regressions:
                print(f"TREND REGRESSION: {r['message']}", file=sys.stderr)
            raise SystemExit(2)


def _trend_check() -> list[dict]:
    """Hard trend-regression records over the benchmark history trajectory.

    The history file is ``BENCH_history.jsonl`` next to the repo-root
    manifests (``REPRO_BENCH_HISTORY`` overrides — the hook the tests use
    to doctor a synthetic slowdown)."""
    from repro.obs import history as _history

    anchor = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_obs.json")
    records = _history.read_history(_history.history_path(anchor))
    return _history.hard_regressions(_history.trend_report(records))


if __name__ == "__main__":
    main()

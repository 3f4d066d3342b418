"""Raw-speed gate for the sweep hot path + BENCH_speed.json.

Measures the pipelined executor (``repro.sweeps.run_group(pipeline=True)``
— shard-once batch cache, donated round-chunk carries, async double-
buffered block dispatch) against the sync path IN THE SAME PROCESS on the
committed ``sweep_smoke`` grid, so the before/after comparison is honest on
whatever machine runs it: both numbers are fresh, the committed
``rows_per_sec`` of an older container never inflates the speedup.

Three subprocess children (XLA device flags and persistent-cache config
must precede jax import).  They are sharding rehearsals on 8 virtual CPU
devices, so each is pinned to ``JAX_PLATFORMS=cpu``: on a machine with a
TPU the parent may already hold the chip, and a child that reached for it
would fail or hang.

  * the MAIN child: sync vs async warm rows/sec, phase-seconds
    attribution, the bit-identity hard gate (async == sync, full-width
    rows), donation proof (runtime buffer deletion AND
    ``input_output_alias`` in the compiled block-step HLO) and the tap
    block accounting (``tap.engine_pool.block_seconds`` from a tapped
    pipelined run);
  * a COLD cache child + a WARM cache child sharing one fresh
    ``JAX_COMPILATION_CACHE_DIR`` (fresh, so the cold child really is
    cold): the warm process must re-run the same
    family with ZERO backend compile events through the unified counter
    (``repro.obs.counters.backend_compile_events``) — the cold-vs-warm
    process compile-time row.

Acceptance is the soft-gate convention (``benchmarks._softgate``): the
async path must reach ``SPEEDUP_BAR`` (1.3x) over sync — a miss WARNS and
flags the manifest, the hard gates are the in-child assertions
(bit-identity, donation, warm-restart 0 compiles).  ``BENCH_speed.json``
lands at the repo root and feeds ``BENCH_history.jsonl`` + the trend gate
like every other manifest.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

from benchmarks._softgate import (collect, committed_baseline, warn_slowdown,
                                  warn_speedup_bar)

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
_MANIFEST_PATH = os.path.join(_ROOT, "BENCH_speed.json")

# the committed sweep_smoke grid (benchmarks/sweep_smoke.py) — the speedup
# is measured on exactly the workload the sweep gate tracks
DEVICES = 8
ROUNDS = 192
# both paths run the SAME chunking (sync: lax.map block size; async: the
# dispatched block size).  192/96 = 2 blocks keeps the async loop genuinely
# double-buffered while paying the per-block dispatch tax only twice.
ROUND_CHUNK = 96
SEEDS = 2
KS = (50, 80, 99)
LAMS = (0.2, 0.7)
FAMILY = "hetero_kstar"

SPEEDUP_BAR = 1.3
WARM_REPS = 5

_MARKER = "BENCH_SPEED "


def _child_env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={DEVICES}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _ROOT]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    ).rstrip(os.pathsep)
    if extra:
        env.update(extra)
    return env


def _spawn(flag: str, env: dict) -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), flag],
        capture_output=True, text=True, timeout=900, env=env, cwd=_ROOT,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_speed child {flag} failed:\n{proc.stdout}\n{proc.stderr}")
    if proc.stderr:
        print(proc.stderr, file=sys.stderr, end="")
    for line in proc.stdout.splitlines():
        if line.startswith(_MARKER):
            return json.loads(line[len(_MARKER):])
    raise RuntimeError(f"bench_speed child {flag} printed no payload:\n{proc.stdout}")


def run() -> list[dict]:
    main = _spawn("--child-main", _child_env())
    with tempfile.TemporaryDirectory() as cache_dir:
        env = _child_env({"JAX_COMPILATION_CACHE_DIR": cache_dir})
        cold = _spawn("--child-cache", env)
        warm = _spawn("--child-cache", env)
    # warm restart of an already-cached family: the unified counter must
    # attribute ZERO backend compiles (the persistent-cache acceptance gate)
    assert cold["backend_compiles"] >= 1, cold
    assert warm["backend_compiles"] == 0, warm
    assert warm["persistent_hits"] >= warm["trace_entries"], warm

    speedup = main["async_rows_per_sec"] / main["sync_rows_per_sec"]
    baseline = committed_baseline(_MANIFEST_PATH)
    warnings = collect(
        warn_speedup_bar("bench_speed", speedup, SPEEDUP_BAR,
                         metric="async_vs_sync_rows_per_sec"),
        warn_slowdown("bench_speed", main["async_rows_per_sec"],
                      baseline.get("async_rows_per_sec")),
    )

    from repro.sweeps.results import write_manifest

    doc = {
        "bench": "bench_speed",
        "family": FAMILY,
        "devices": DEVICES,
        "rounds": ROUNDS,
        "round_chunk": ROUND_CHUNK,
        "seeds": SEEDS,
        "batch_rows": main["batch_rows"],
        # before/after, measured in one process on this machine
        "sync_rows_per_sec": main["sync_rows_per_sec"],
        "async_rows_per_sec": main["async_rows_per_sec"],
        "speedup_async_vs_sync": speedup,
        "speedup_bar": SPEEDUP_BAR,
        "speedup_below_bar": bool(speedup < SPEEDUP_BAR),
        "sync_cold_s": main["sync_cold_s"],
        "sync_warm_s": main["sync_warm_s"],
        "async_cold_s": main["async_cold_s"],
        "async_warm_s": main["async_warm_s"],
        "bitexact_async_vs_sync": True,          # hard-asserted in the child
        # donation proof, both layers
        "donated_runtime": main["donated_runtime"],
        "donation_hlo_alias": main["donation_hlo_alias"],
        "pipeline_stats": main["pipeline_stats"],
        # tap accounting (block walls observed DURING the async run)
        "tap_block_seconds_count": main["tap_block_seconds_count"],
        "tap_block_seconds_sum": main["tap_block_seconds_sum"],
        # persistent compile cache: cold vs warm PROCESS on one cache dir
        "cache_cold_compile_s": cold["compile_s"],
        "cache_warm_compile_s": warm["compile_s"],
        "cache_cold_backend_compiles": cold["backend_compiles"],
        "cache_warm_backend_compiles": warm["backend_compiles"],
        "cache_warm_persistent_hits": warm["persistent_hits"],
        "baseline_async_rows_per_sec": baseline.get("async_rows_per_sec"),
        "warnings": warnings,
    }
    write_manifest(_MANIFEST_PATH, doc)

    return [{
        "name": "bench_speed",
        "us_per_call": main["async_warm_s"] * 1e6 / (main["batch_rows"] * ROUNDS),
        "derived": (
            f"sync_rps={main['sync_rows_per_sec']:.0f};"
            f"async_rps={main['async_rows_per_sec']:.0f};"
            f"speedup={speedup:.2f}x;bar={SPEEDUP_BAR}x;"
            f"below_bar={int(speedup < SPEEDUP_BAR)};bitexact=1;"
            f"donated={int(main['donated_runtime'])};"
            f"hlo_alias={int(main['donation_hlo_alias'])};"
            f"warm_restart_compiles={warm['backend_compiles']};"
            f"cache_cold_s={cold['compile_s']:.2f};"
            f"cache_warm_s={warm['compile_s']:.2f}"
        ),
    }]


def _child_main() -> None:
    import numpy as np

    import jax

    from repro import sweeps
    from repro.launch.mesh import make_sweep_mesh
    from repro.obs.metrics import MetricsRegistry, tap_to_registry
    from repro.obs import taps as _taps
    from repro.sweeps import executor

    assert len(jax.devices()) == DEVICES, jax.devices()
    mesh = make_sweep_mesh()
    scenarios = sweeps.expand(FAMILY, ks=KS, lams=LAMS, rounds=ROUNDS)
    (group,) = sweeps.build_groups(scenarios, seeds=SEEDS)
    rows = group.batch.rows

    def _measure(pipeline: bool) -> tuple[float, float, np.ndarray]:
        t0 = time.perf_counter()
        out = executor.run_group(group, mesh=mesh, round_chunk=ROUND_CHUNK,
                                 pipeline=pipeline)
        cold_s = time.perf_counter() - t0
        warm_s = float("inf")
        for _ in range(WARM_REPS):                 # best-of: least contended
            t0 = time.perf_counter()
            out = executor.run_group(group, mesh=mesh, round_chunk=ROUND_CHUNK,
                                     pipeline=pipeline)
            warm_s = min(warm_s, time.perf_counter() - t0)
        return cold_s, warm_s, out

    sync_cold_s, sync_warm_s, sync_out = _measure(pipeline=False)
    async_cold_s, async_warm_s, async_out = _measure(pipeline=True)
    stats = executor.last_pipeline_stats()

    # HARD gate: the async path must be bit-identical to the sync engine
    np.testing.assert_array_equal(async_out, sync_out)
    # HARD gate: the carries were really donated
    assert stats["donated"] is True, stats
    hlo_alias = "input_output_alias" in executor.pipeline_block_hlo(
        group, mesh=mesh, round_chunk=ROUND_CHUNK)
    assert hlo_alias, "block step compiled without input_output_alias"

    # tapped pipelined run: block walls observed at actual completion
    reg = MetricsRegistry()
    _taps.add_tap("bench_speed", tap_to_registry(reg))
    try:
        tapped = executor.run_group(group, mesh=mesh, round_chunk=ROUND_CHUNK,
                                    pipeline=True, tap=True)
    finally:
        _taps.remove_tap("bench_speed")
    np.testing.assert_array_equal(tapped, sync_out)
    blk = reg.get("tap.engine_pool.block_seconds") or {"count": 0, "sum": 0.0}

    print(_MARKER + json.dumps({
        "batch_rows": rows,
        "sync_rows_per_sec": rows * ROUNDS / sync_warm_s,
        "async_rows_per_sec": rows * ROUNDS / async_warm_s,
        "sync_cold_s": sync_cold_s,
        "sync_warm_s": sync_warm_s,
        "async_cold_s": async_cold_s,
        "async_warm_s": async_warm_s,
        "donated_runtime": bool(stats["donated"]),
        "donation_hlo_alias": bool(hlo_alias),
        "pipeline_stats": {k: (bool(v) if isinstance(v, bool) else v)
                           for k, v in stats.items()},
        "tap_block_seconds_count": int(blk["count"]),
        "tap_block_seconds_sum": float(blk["sum"]),
    }))


def _child_cache() -> None:
    # persistent-cache wiring BEFORE jax touches a backend
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    from repro import sweeps
    from repro.launch.mesh import make_sweep_mesh
    from repro.obs import counters
    from repro.sweeps import executor

    mesh = make_sweep_mesh()
    scenarios = sweeps.expand(FAMILY, ks=KS, lams=LAMS, rounds=ROUNDS)
    (group,) = sweeps.build_groups(scenarios, seeds=SEEDS)
    t0 = time.perf_counter()
    executor.run_group(group, mesh=mesh, round_chunk=ROUND_CHUNK)
    compile_s = time.perf_counter() - t0           # first call: compile + run
    print(_MARKER + json.dumps({
        "trace_entries": counters.compile_events("sweeps.run_group"),
        "persistent_hits": counters.persistent_cache_hits(),
        "backend_compiles": counters.backend_compile_events("sweeps.run_group"),
        "compile_s": compile_s,
    }))


if __name__ == "__main__":
    if "--child-main" in sys.argv:
        _child_main()
    elif "--child-cache" in sys.argv:
        _child_cache()
    else:
        for row in run():
            print(row)

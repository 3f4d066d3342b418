"""CI smoke gate for the ``repro.sweeps`` subsystem + BENCH_sweep.json.

Runs a tiny heterogeneous-K* registry grid through the full production path
— 8 forced host devices, a 1-D ``jax.sharding`` batch mesh, ``round_chunk``
blocking, multi-seed rows — in a subprocess (XLA device-count flags must be
set before jax initialises, and the parent harness has already imported
jax), pinned to ``JAX_PLATFORMS=cpu`` because it is a sharding rehearsal on
virtual devices and the parent may hold the machine's TPU, asserts the sharded/chunked output matches per-row static-``LoadParams``
engine runs bit-for-bit (the shape-polymorphic engine's full-width
invariant), and emits ``BENCH_sweep.json`` at the repo root with rows/sec,
per-row allocator time AND the compile count per scenario family so the
perf trajectory covers the sweep subsystem alongside ``BENCH_fig3.json``.

Since the traced-K*/ell engine, the WHOLE hetero-K* grid is ONE compiled
computation (``family_compiles`` asserts it); the compile count per family
is also soft-checked against the committed ``BENCH_sweep.json`` — a family
that starts compiling MORE computations than the baseline prints a WARNING
to stderr and flags the manifest, same convention as the rows/sec check
below (never a hard failure: the hard gate is the in-run assertion).

The warm rows/sec is also soft-checked against the previously committed
``BENCH_sweep.json``: a drop beyond ``SLOWDOWN_WARN_FRACTION`` prints a
WARNING to stderr (and flags the manifest/derived row) but never fails —
shared-CI wall clocks are too noisy for a hard gate.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks._softgate import (SLOWDOWN_WARN_FRACTION, collect,
                                  committed_baseline, warn_compiles,
                                  warn_slowdown)

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
_BASELINE_PATH = os.path.join(_ROOT, "BENCH_sweep.json")

DEVICES = 8
ROUNDS = 192
ROUND_CHUNK = 48
SEEDS = 2
KS = (50, 80, 99)
LAMS = (0.2, 0.7)
FAMILY = "hetero_kstar"

_MARKER = "SWEEP_SMOKE_ROWS "


def run() -> list[dict]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={DEVICES}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _ROOT] + env.get("PYTHONPATH", "").split(os.pathsep)
    ).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        capture_output=True, text=True, timeout=900, env=env, cwd=_ROOT,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"sweep_smoke child failed:\n{proc.stdout}\n{proc.stderr}")
    if proc.stderr:
        print(proc.stderr, file=sys.stderr, end="")
    for line in proc.stdout.splitlines():
        if line.startswith(_MARKER):
            return json.loads(line[len(_MARKER):])
    raise RuntimeError(f"sweep_smoke child produced no rows:\n{proc.stdout}")


def _child_main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import sweeps
    from repro.core import lea as lea_mod
    from repro.core import throughput
    from repro.launch.mesh import make_sweep_mesh

    assert len(jax.devices()) == DEVICES, jax.devices()
    mesh = make_sweep_mesh()

    scenarios = sweeps.expand(FAMILY, ks=KS, lams=LAMS, rounds=ROUNDS)
    groups = sweeps.build_groups(scenarios, seeds=SEEDS)
    # traced K* fuses the whole heterogeneous grid into ONE group
    assert len(groups) == 1, [g.rounds for g in groups]

    c0 = sweeps.compile_cache_size()
    t0 = time.perf_counter()
    succs = sweeps.run_groups(groups, mesh=mesh, round_chunk=ROUND_CHUNK)
    cold_s = time.perf_counter() - t0
    compiles = sweeps.compile_cache_size() - c0
    assert compiles == len(groups) == 1, (compiles, len(groups))
    family_compiles = {FAMILY: compiles}

    # the smoke *gate*: production path == per-row static-LoadParams engine,
    # bit-for-bit (the shape-polymorphic engine's full-width invariant — the
    # strongest reference available now that one group spans many K*s)
    (group,), (succ,) = groups, succs
    for ri, rm in enumerate(group.rows):
        sc = group.scenarios[rm.scenario_index]
        ref = throughput.simulate_strategies(
            group.batch.keys[ri], sc.lp,
            jnp.asarray(sc.p_gg), jnp.asarray(sc.p_bb),
            sc.mu_g, sc.mu_b, sc.deadline, group.rounds,
            strategies=group.strategies,
        )
        np.testing.assert_array_equal(succ[ri], np.asarray(ref))

    # warm steady-state rows/sec (simulated rounds per wall second)
    t0 = time.perf_counter()
    sweeps.run_groups(groups, mesh=mesh, round_chunk=ROUND_CHUNK)
    warm_s = time.perf_counter() - t0
    total_rows = sum(g.batch.rows for g in groups)
    rows_per_sec = total_rows * ROUNDS / warm_s

    # soft regression checks vs the COMMITTED baseline (benchmarks._softgate:
    # git HEAD reference, stderr WARNING + manifest flag, never a hard
    # failure — the hard in-run assertion above is the real gate)
    baseline = committed_baseline(_BASELINE_PATH)
    baseline_rps = baseline.get("rows_per_sec")
    warnings = collect(
        warn_slowdown("sweep_smoke", rows_per_sec, baseline_rps),
        warn_compiles(
            "sweep_smoke", family_compiles, baseline.get("family_compiles", {})
        ),
    )
    slowdown_warned = any(w["kind"] == "slowdown" for w in warnings)
    compile_warned = any(w["kind"] == "compiles" for w in warnings)

    # per-row allocator time inside one batched allocate (the sweep hot path)
    lp = scenarios[0].lp
    p = jnp.asarray(np.random.default_rng(0).uniform(0, 1, (4096, lp.n)), jnp.float32)
    alloc = jax.jit(lambda q: lea_mod.allocate(q, lp)[0])
    alloc(p).block_until_ready()
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        alloc(p).block_until_ready()
    allocator_us_per_row = (time.perf_counter() - t0) / reps / p.shape[0] * 1e6

    results = sweeps.summarize(groups, succs, scenario_order=scenarios)
    doc = sweeps.manifest(
        results,
        bench="sweep_smoke",
        extra={
            "devices": DEVICES,
            "mesh_axes": list(mesh.axis_names),
            "seeds": SEEDS,
            "rounds": ROUNDS,
            "round_chunk": ROUND_CHUNK,
            "groups": len(groups),
            "group_compiles": compiles,
            "family_compiles": family_compiles,
            "compile_warned": compile_warned,
            "batch_rows": total_rows,
            "rows_per_sec": rows_per_sec,
            "baseline_rows_per_sec": baseline_rps,
            "slowdown_warned": slowdown_warned,
            "cold_s": cold_s,
            "warm_s": warm_s,
            "allocator_us_per_row": allocator_us_per_row,
            "warnings": warnings,
        },
    )
    sweeps.write_manifest(_BASELINE_PATH, doc)

    rows = [{
        "name": "sweep_smoke",
        "us_per_call": warm_s * 1e6 / (total_rows * ROUNDS),
        "derived": (
            f"devices={DEVICES};groups={len(groups)};rows={total_rows};"
            f"rounds={ROUNDS};chunk={ROUND_CHUNK};"
            f"rows_per_sec={rows_per_sec:.0f};compiles={compiles};bitexact=1;"
            f"baseline_rps={baseline_rps or 0:.0f};"
            f"slowdown_warned={int(slowdown_warned)};"
            f"compile_warned={int(compile_warned)}"
        ),
    }]
    for r in results:
        rows.append({
            "name": f"sweep_{r.name}",
            "us_per_call": warm_s * 1e6 / (total_rows * ROUNDS),
            "derived": (
                f"Kstar={r.scenario.lp.kstar};"
                + ";".join(f"R_{s}={v:.4f}" for s, v in r.throughput.items())
                + f";ratio={r.baseline_ratio:.2f}x"
            ),
        })
    print(_MARKER + json.dumps(rows))


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child_main()
    else:
        for row in run():
            print(row)

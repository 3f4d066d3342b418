"""Fault-injection gate: the ``repro.faults`` runtime end to end.

Expands the ``packet_erasure`` scenario grid (preemption ramp x iid packet
loss on the Fig. 3 worker pool), turns each cell's meta into TRACED channel
parameters, and scores every cell's rounds under the three decode modes —
all-or-nothing, partial-work conserving, hierarchical layer-1 — on the SAME
trajectories and the SAME fault realisations, fused into ONE compiled
computation (:func:`repro.faults.engine.sweep_faults`; asserted in-run and
soft-checked against the committed baseline like every compile count).

Hard in-run gates (the acceptance criteria, not wall-clock-dependent):

  * containment — no (cell, round, strategy) is AON-recoverable but not
    conserve-recoverable;
  * strict dominance — summed over the faulted cells, the conserving decode
    recovers STRICTLY more rounds than all-or-nothing on the same PRNG keys;
  * executor accounting — a retry/degrade executor run under the same
    channel ends every round in exactly one of {on_time, late, partial,
    dropped} with the counts summing to the round total (never a silent
    drop).

Writes ``BENCH_faults.json`` at the repo root: per-cell recovery rates for
the three modes, the conserve-vs-AON gain, the compile count and the
executor's outcome histogram; rows/sec follows the ``benchmarks._softgate``
soft-regression convention (WARNING + manifest flag, never a failure).
"""

from __future__ import annotations

import os
import time

from benchmarks._softgate import (cold_compile_metric, collect,
                                  committed_baseline, warn_compiles,
                                  warn_slowdown)

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
_MANIFEST_PATH = os.path.join(_ROOT, "BENCH_faults.json")

FAMILY = "packet_erasure"
ROUNDS = 512
STRATEGIES = ("lea", "static")
SEED_BASE = 1000

# the executor accounting demo (small: it is a host loop)
EXEC_ROUNDS = 30
EXEC_P_PREEMPT = 0.35


def _unique_meta(scenarios, key):
    vals = {dict(sc.meta)[key] for sc in scenarios}
    assert len(vals) == 1, (key, vals)
    return vals.pop()


def run() -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import faults, sweeps
    from repro.core.lea import PoolLoad
    from repro.obs import counters
    from repro.runtime.fault_tolerance import (CodedDataParallelExecutor,
                                               CodedDPConfig, OUTCOMES)

    scenarios = sweeps.expand(FAMILY, rounds=ROUNDS)
    b = len(scenarios)
    lp = scenarios[0].lp
    assert all(sc.lp == lp for sc in scenarios)
    n = lp.n
    packets = int(_unique_meta(scenarios, "packets"))
    p1 = int(_unique_meta(scenarios, "p1"))
    r = int(_unique_meta(scenarios, "r"))
    k1star = int(_unique_meta(scenarios, "k1star"))

    keys = jax.vmap(lambda i: jax.random.PRNGKey(SEED_BASE + i))(jnp.arange(b))
    pool = PoolLoad(
        kstar=jnp.full((b,), lp.kstar, jnp.int32),
        ell_g=jnp.full((b,), lp.ell_g, jnp.int32),
        ell_b=jnp.full((b,), lp.ell_b, jnp.int32),
        mask=jnp.ones((b, n), bool),
    )
    p_gg = jnp.asarray([sc.p_gg for sc in scenarios], jnp.float32)
    p_bb = jnp.asarray([sc.p_bb for sc in scenarios], jnp.float32)
    p_pre = jnp.asarray([dict(sc.meta)["p_preempt"] for sc in scenarios],
                        jnp.float32)
    p_drop = jnp.asarray([dict(sc.meta)["p_drop"] for sc in scenarios],
                         jnp.float32)
    channel = faults.make_channel([
        ("preempt", {"p_preempt": p_pre}),
        ("packet_bernoulli", {"p_drop": p_drop}),
    ])

    c0 = faults.fault_compile_cache_size()
    h0 = counters.persistent_cache_hits()
    t0 = time.perf_counter()
    out = faults.sweep_faults(
        keys, pool, p_gg, p_bb,
        scenarios[0].mu_g, scenarios[0].mu_b, scenarios[0].deadline,
        channel, k1star,
        rounds=ROUNDS, strategies=STRATEGIES, r=r, packets=packets, p1=p1,
    )
    jax.block_until_ready(out)
    cold_s = time.perf_counter() - t0
    cold_hits = counters.persistent_cache_hits() - h0
    compiles = faults.fault_compile_cache_size() - c0
    # the whole fault grid — every (p_preempt, p_drop) cell — is ONE compile
    assert compiles == 1, compiles
    family_compiles = {FAMILY: compiles}

    t0 = time.perf_counter()
    jax.block_until_ready(faults.sweep_faults(
        keys, pool, p_gg, p_bb,
        scenarios[0].mu_g, scenarios[0].mu_b, scenarios[0].deadline,
        channel, k1star,
        rounds=ROUNDS, strategies=STRATEGIES, r=r, packets=packets, p1=p1,
    ))
    warm_s = time.perf_counter() - t0
    rows_per_sec = b * ROUNDS / warm_s

    aon = np.asarray(out.full_aon)            # (b, rounds, S) bool
    con = np.asarray(out.full_conserve)
    part = np.asarray(out.partial)
    # containment: a conserving decode can never lose a round AON recovers
    assert not (aon & ~con).any(), "AON-recoverable round lost by conserve"
    assert not (part & con).any(), "partial overlaps full_conserve"
    faulted = np.asarray(p_pre > 0) | np.asarray(p_drop > 0)
    gain_rounds = int(con[faulted].sum()) - int(aon[faulted].sum())
    # strict dominance under faults, on the same keys and the same traces
    assert gain_rounds > 0, "conserve did not strictly beat all-or-nothing"

    # retry/degrade executor under the same channel family: every round ends
    # in exactly one disposition and nothing is silently dropped
    cfg = CodedDPConfig(packets=packets, max_retries=2, allow_partial=True,
                        p1=p1)
    ex = CodedDataParallelExecutor(
        cfg, lambda params, sb: jax.tree.map(jnp.zeros_like, params),
        seed=0,
        channel=faults.make_channel(
            [("preempt", {"p_preempt": EXEC_P_PREEMPT})]
        ),
    )
    params = {"w": jnp.zeros(2)}
    batch = {"x": jnp.zeros((cfg.k, 2))}
    for _ in range(EXEC_ROUNDS):
        grads, info = ex.round(params, batch)
        assert (grads is None) == (info["outcome"] == "dropped")
    assert sum(ex.outcomes.values()) == ex.rounds == EXEC_ROUNDS

    baseline = committed_baseline(_MANIFEST_PATH)
    warnings = collect(
        warn_slowdown("bench_faults", rows_per_sec, baseline.get("rows_per_sec")),
        warn_compiles(
            "bench_faults", family_compiles, baseline.get("family_compiles", {})
        ),
    )
    slowdown_warned = any(w["kind"] == "slowdown" for w in warnings)
    compile_warned = any(w["kind"] == "compiles" for w in warnings)

    li = STRATEGIES.index("lea")
    cells = []
    for i, sc in enumerate(scenarios):
        meta = dict(sc.meta)
        cells.append({
            "name": sc.name,
            "p_preempt": float(meta["p_preempt"]),
            "p_drop": float(meta["p_drop"]),
            "recovered_aon": float(aon[i, :, li].mean()),
            "recovered_conserve": float(con[i, :, li].mean()),
            "recovered_partial_only": float(part[i, :, li].mean()),
            "served_any": float((con[i, :, li] | part[i, :, li]).mean()),
        })

    doc = {
        "bench": "bench_faults",
        "family": FAMILY,
        "cells": b,
        "rounds": ROUNDS,
        "strategies": list(STRATEGIES),
        "packets": packets,
        "p1": p1,
        "kstar": lp.kstar,
        "k1star": k1star,
        "conserve_contains_aon": True,
        "conserve_gain_rounds": gain_rounds,
        "family_compiles": family_compiles,
        "compile_warned": compile_warned,
        "rows_per_sec": rows_per_sec,
        "baseline_rows_per_sec": baseline.get("rows_per_sec"),
        "slowdown_warned": slowdown_warned,
        **cold_compile_metric(cold_s, cold_hits),
        "warm_s": warm_s,
        "executor_rounds": ex.rounds,
        "executor_outcomes": {k: ex.outcomes[k] for k in OUTCOMES},
        "executor_outcomes_sum_ok": True,
        "warnings": warnings,
        "results": cells,
    }
    sweeps.write_manifest(_MANIFEST_PATH, doc)

    rows = [{
        "name": "bench_faults",
        "us_per_call": warm_s * 1e6 / (b * ROUNDS),
        "derived": (
            f"cells={b};rounds={ROUNDS};packets={packets};compiles={compiles};"
            f"gain_rounds={gain_rounds};rows_per_sec={rows_per_sec:.0f};"
            f"slowdown_warned={int(slowdown_warned)};"
            f"compile_warned={int(compile_warned)};"
            + ";".join(f"exec_{k}={ex.outcomes[k]}" for k in OUTCOMES)
        ),
    }]
    for c in cells:
        rows.append({
            "name": f"faults_{c['name']}",
            "us_per_call": warm_s * 1e6 / (b * ROUNDS),
            "derived": (
                f"aon={c['recovered_aon']:.4f};"
                f"conserve={c['recovered_conserve']:.4f};"
                f"partial={c['recovered_partial_only']:.4f};"
                f"served={c['served_any']:.4f}"
            ),
        })
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)

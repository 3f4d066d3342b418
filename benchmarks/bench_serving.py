"""Serving gate: the ``repro.serving`` streaming layer end to end.

Expands the ``arrival_grid`` scenario grid (Poisson arrival rate x request
deadline on the Sec. 6.2 worker pool), turns each cell's meta into TRACED
request-spec / arrival-process parameters, and runs the compiled serving
loop TWICE on the same keys — once admit-all (both admission gates
disabled) and once admission-controlled (the committed
``admit_threshold``/``reserve_cap`` settings).  Both runs share one
compiled computation: admission parameters are traced, so the whole grid
x {admit-all, controlled} fuses into ONE compile (asserted in-run and
soft-checked against the committed baseline like every compile count).

Hard in-run gates (the acceptance criteria, not wall-clock-dependent):

  * one compile — the full serving loop for the family compiles exactly
    once per (rounds, strategies, capacity, grace) signature;
  * conservation — every cell of both runs accounts every request:
    arrivals == admitted + rejected and admitted == served_on_time +
    served_late + expired + in_flight (never a silent drop);
  * admission beats admit-all at overload — summed over the cells whose
    arrival rate exceeds the pool's sustainable service rate
    (pi_g * n / m_min jobs per round), the controlled run serves STRICTLY
    more requests on time than admit-all, on the same keys and the same
    arrival streams.

Writes ``BENCH_serving.json`` at the repo root: per-cell timely
throughput for both admission modes, sojourn-time latency percentiles
(p50/p95/p99) and sustained served-requests/sec at every arrival rate;
rows/sec follows the ``benchmarks._softgate`` soft-regression convention
(WARNING + manifest flag, never a failure).
"""

from __future__ import annotations

import os
import time

from benchmarks._softgate import (cold_compile_metric, collect,
                                  committed_baseline, warn_compiles,
                                  warn_slowdown)

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
_MANIFEST_PATH = os.path.join(_ROOT, "BENCH_serving.json")

FAMILY = "arrival_grid"
ROUNDS = 512
STRATEGIES = ("lea",)
SEED_BASE = 2000


def _percentiles(sojourn, events, served_codes):
    """p50/p95/p99 sojourn (rounds) of the served requests of one cell."""
    import numpy as np

    lat = sojourn[np.isin(events, served_codes)]
    if lat.size == 0:
        return None, None, None
    p50, p95, p99 = np.percentile(lat, [50.0, 95.0, 99.0])
    return float(p50), float(p95), float(p99)


def run() -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import serving, sweeps
    from repro.core import markov
    from repro.obs import counters

    scenarios = sweeps.expand(FAMILY, rounds=ROUNDS)
    b = len(scenarios)
    lp = scenarios[0].lp
    assert all(sc.lp == lp for sc in scenarios)
    n = lp.n
    meta0 = dict(scenarios[0].meta)
    capacity = int(meta0["capacity"])
    grace = int(meta0["grace"])
    assert all(dict(sc.meta)["process"] == "poisson" for sc in scenarios)

    keys = jax.vmap(lambda i: jax.random.PRNGKey(SEED_BASE + i))(jnp.arange(b))
    pool_mask = jnp.ones((b, n), bool)
    p_gg = jnp.asarray([sc.p_gg for sc in scenarios], jnp.float32)
    p_bb = jnp.asarray([sc.p_bb for sc in scenarios], jnp.float32)
    rates = jnp.asarray([dict(sc.meta)["rate"] for sc in scenarios],
                        jnp.float32)
    dl_rel = jnp.asarray([dict(sc.meta)["deadline_rel"] for sc in scenarios],
                         jnp.int32)
    thr = jnp.asarray([dict(sc.meta)["admit_threshold"] for sc in scenarios],
                      jnp.float32)
    cap = jnp.asarray([dict(sc.meta)["reserve_cap"] for sc in scenarios],
                      jnp.float32)
    process = serving.make_process("poisson", rate=rates)

    def spec(admit_threshold, reserve_cap):
        return serving.RequestSpec(
            kstar=jnp.full((b,), lp.kstar, jnp.int32),
            ell_g=jnp.full((b,), lp.ell_g, jnp.int32),
            ell_b=jnp.full((b,), lp.ell_b, jnp.int32),
            deadline_rel=dl_rel,
            admit_threshold=admit_threshold,
            reserve_cap=reserve_cap,
        )

    common = dict(rounds=ROUNDS, strategies=STRATEGIES, capacity=capacity,
                  grace=grace)
    zeros = jnp.zeros((b,), jnp.float32)

    c0 = serving.serving_compile_cache_size()
    h0 = counters.persistent_cache_hits()
    t0 = time.perf_counter()
    out_all = serving.sweep_serving(
        keys, pool_mask, p_gg, p_bb,
        scenarios[0].mu_g, scenarios[0].mu_b, scenarios[0].deadline,
        spec(zeros, jnp.full((b,), serving.ADMIT_ALL_CAP, jnp.float32)),
        process, **common,
    )
    jax.block_until_ready(out_all)
    cold_s = time.perf_counter() - t0
    cold_hits = counters.persistent_cache_hits() - h0
    # the controlled run: same shapes, traced admission knobs -> same compile
    out_ctl = serving.sweep_serving(
        keys, pool_mask, p_gg, p_bb,
        scenarios[0].mu_g, scenarios[0].mu_b, scenarios[0].deadline,
        spec(thr, cap), process, **common,
    )
    jax.block_until_ready(out_ctl)
    compiles = serving.serving_compile_cache_size() - c0
    # the whole grid, admit-all AND admission-controlled, is ONE compile
    assert compiles == 1, compiles
    family_compiles = {FAMILY: compiles}

    t0 = time.perf_counter()
    jax.block_until_ready(serving.sweep_serving(
        keys, pool_mask, p_gg, p_bb,
        scenarios[0].mu_g, scenarios[0].mu_b, scenarios[0].deadline,
        spec(zeros, jnp.full((b,), serving.ADMIT_ALL_CAP, jnp.float32)),
        process, **common,
    ))
    warm_s = time.perf_counter() - t0
    rows_per_sec = b * ROUNDS / warm_s

    # conservation: every request of every cell in exactly one disposition
    def check_conservation(out):
        arr = np.asarray(out.arrivals)
        admitted = np.asarray(out.admitted)
        leave = (np.asarray(out.served_on_time) + np.asarray(out.served_late)
                 + np.asarray(out.expired) + np.asarray(out.in_flight))
        assert (arr == admitted + np.asarray(out.rejected)).all()
        assert (admitted == leave).all()

    check_conservation(out_all)
    check_conservation(out_ctl)

    # overload cells: arrival rate above the sustainable service rate
    pi_g = float(markov.stationary_good_prob(
        jnp.asarray(scenarios[0].p_gg[0]), jnp.asarray(scenarios[0].p_bb[0])))
    m_min = -(-lp.kstar // lp.ell_g)
    sustainable = pi_g * n / m_min          # expected good workers / job size
    overloaded = np.asarray(rates) > sustainable
    assert overloaded.any(), "grid has no overload cell"
    li = STRATEGIES.index("lea")
    served_all = np.asarray(out_all.served_on_time)[:, li]
    served_ctl = np.asarray(out_ctl.served_on_time)[:, li]
    admission_gain = int(served_ctl[overloaded].sum()
                         - served_all[overloaded].sum())
    # admission control must measurably beat admit-all at overload
    assert admission_gain > 0, (
        f"admission control served {admission_gain} fewer requests than "
        f"admit-all on the overloaded cells"
    )

    baseline = committed_baseline(_MANIFEST_PATH)
    warnings = collect(
        warn_slowdown("bench_serving", rows_per_sec, baseline.get("rows_per_sec")),
        warn_compiles(
            "bench_serving", family_compiles, baseline.get("family_compiles", {})
        ),
    )
    slowdown_warned = any(w["kind"] == "slowdown" for w in warnings)
    compile_warned = any(w["kind"] == "compiles" for w in warnings)

    served_codes = (serving.EVENT_ON_TIME, serving.EVENT_LATE)
    deadline_s = float(scenarios[0].deadline)   # one round = d seconds
    cells = []
    for i, sc in enumerate(scenarios):
        meta = dict(sc.meta)
        ev = np.asarray(out_ctl.events)[i, li]
        sj = np.asarray(out_ctl.sojourn)[i, li]
        p50, p95, p99 = _percentiles(sj, ev, served_codes)
        cells.append({
            "name": sc.name,
            "rate": float(meta["rate"]),
            "deadline_rel": int(meta["deadline_rel"]),
            "overloaded": bool(overloaded[i]),
            "arrivals": int(np.asarray(out_ctl.arrivals)[i, li]),
            "served_on_time_admit_all": int(served_all[i]),
            "served_on_time_controlled": int(served_ctl[i]),
            "rejected_controlled": int(np.asarray(out_ctl.rejected)[i, li]),
            "expired_admit_all": int(np.asarray(out_all.expired)[i, li]),
            "expired_controlled": int(np.asarray(out_ctl.expired)[i, li]),
            "served_per_round": float(served_ctl[i] / ROUNDS),
            "served_req_per_sec": float(served_ctl[i] / (ROUNDS * deadline_s)),
            "latency_p50_rounds": p50,
            "latency_p95_rounds": p95,
            "latency_p99_rounds": p99,
        })
        assert served_ctl[i] > 0, sc.name   # percentiles must be real

    doc = {
        "bench": "bench_serving",
        "family": FAMILY,
        "cells": b,
        "rounds": ROUNDS,
        "strategies": list(STRATEGIES),
        "capacity": capacity,
        "grace": grace,
        "kstar": lp.kstar,
        "admit_threshold": float(np.asarray(thr)[0]),
        "reserve_cap": float(np.asarray(cap)[0]),
        "sustainable_rate": sustainable,
        "conservation_ok": True,
        "admission_beats_admit_all": True,
        "admission_gain_requests": admission_gain,
        "family_compiles": family_compiles,
        "compile_warned": compile_warned,
        "rows_per_sec": rows_per_sec,
        "baseline_rows_per_sec": baseline.get("rows_per_sec"),
        "slowdown_warned": slowdown_warned,
        **cold_compile_metric(cold_s, cold_hits),
        "warm_s": warm_s,
        "warnings": warnings,
        "results": cells,
    }
    sweeps.write_manifest(_MANIFEST_PATH, doc)

    rows = [{
        "name": "bench_serving",
        "us_per_call": warm_s * 1e6 / (b * ROUNDS),
        "derived": (
            f"cells={b};rounds={ROUNDS};compiles={compiles};"
            f"admission_gain={admission_gain};"
            f"rows_per_sec={rows_per_sec:.0f};"
            f"slowdown_warned={int(slowdown_warned)};"
            f"compile_warned={int(compile_warned)}"
        ),
    }]
    for c in cells:
        rows.append({
            "name": f"serving_{c['name']}",
            "us_per_call": warm_s * 1e6 / (b * ROUNDS),
            "derived": (
                f"served_all={c['served_on_time_admit_all']};"
                f"served_ctl={c['served_on_time_controlled']};"
                f"req_per_sec={c['served_req_per_sec']:.3f};"
                f"p50={c['latency_p50_rounds']};p95={c['latency_p95_rounds']};"
                f"p99={c['latency_p99_rounds']}"
            ),
        })
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)

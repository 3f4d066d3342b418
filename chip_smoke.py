"""Bring-up check on one TPU: the LEA scheduler, the serving loop and the
coded round, driven through the entry points a user calls, at the paper's
own deployment sizes (Sec. 6, ``repro.configs.paper_lea``).

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # only the sharded sweep, 4 chips vs 1

Phases (each one is fatal: a failed check exits non-zero):

  * scheduler — ``sweeps.run("fig3", rounds=20_000)`` (the paper keys)
    through the sync executor and the pipelined one, bit-identical; LEA
    throughputs within ``PINNED_TOL`` of the pinned paper-key values; the
    compiled engine program holds the Pallas allocator kernel
    (``tpu_custom_call``); the kernel's prefix tails on one engine-sized
    batch match the reference DP; then ``sweeps.run("fig4")``;
  * serving — ``repro.launch.serve.main`` at its defaults (n = 15, 1000
    rounds, shift-exponential arrivals) with its accounting identities;
  * coded round — the EC2 job (n = 15, r = 10, k = 120 so K* = 120, 25 rows
    per chunk, 3000 columns): exact GF(p) encode, worker products and
    decode under an erasure pattern from an engine trajectory, bit-equal
    to the numpy modp oracle; then the float path against float64 numpy.

The script refuses to run without a TPU, or with the kernel overrides of
``repro.kernels.dispatch`` set: there is no CPU fallback.  Times printed
are the first call (compile included) and one warm call of a single chip
run, for orientation only; they are not a benchmark.  The last line of
stdout is one JSON object: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))

# LEA timely throughput of fig3 scenarios 1-4 on the paper keys PRNGKey(1..4)
# at 20,000 rounds, pinned by the reference DP on the host.  The TPU kernel
# agrees with that DP to float32 round-off only, so an argmax near a tie may
# flip a few rounds: hence a tolerance, not equality.
FIG3_ROUNDS = 20_000
PINNED_LEA = (0.1931, 0.4272, 0.7221, 0.9463)
PINNED_TOL = 0.005
TAILS_TOL = 1e-5
KERNEL_BATCH = 4096
# float coded round: max-norm relative error against float64 numpy may be
# at most FLOAT_TOL_ULPS float32 ulps times the condition number of the
# received generator rows.  The decode amplifies input error by that
# condition number; the ulp budget covers the 3000-term worker dot products
# and the 120-term encode and decode sums.  A reduced-precision (bf16)
# matmul pass is 2^15 float32 ulps and cannot meet it.
FLOAT_TOL_ULPS = 1000.0
TRAJECTORY_ROUNDS = 200
FOUR_CHIPS = 4


def _timed(label: str, fn, ready=lambda out: out):
    """Run ``fn`` twice; print first-call and warm seconds; return result.

    ``ready(out)`` picks the arrays to wait for, so a time covers the
    device work and not just its dispatch.
    """
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(ready(fn()))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(ready(out))
    warm = time.perf_counter() - t0
    print(f"[one chip run, not a benchmark] {label}: first call (compile "
          f"included) {first:.3f} s")
    print(f"[one chip run, not a benchmark] {label}: warm call {warm:.3f} s")
    return out


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print(f"  ok: {what}")


def require_tpu(devices) -> dict:
    """The device as JAX reports it; exits non-zero unless it is a TPU."""
    from repro.kernels import dispatch

    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    for var in (dispatch.ENV_IMPL, dispatch.ENV_INTERPRET):
        if var in os.environ:
            sys.exit(f"chip_smoke: {var} is set; refusing to run the kernels "
                     "anywhere but on the chip")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_scheduler(rounds: int = FIG3_ROUNDS, pinned=PINNED_LEA,
                    kernel_batch: int = KERNEL_BATCH,
                    expect_kernel: bool = True) -> dict:
    """fig3 sync vs pipelined, pinned throughputs, kernel presence/tails."""
    import jax.numpy as jnp
    import numpy as np

    from repro import sweeps
    from repro.core import lea
    from repro.kernels.poisson_binomial import success_tails

    print(f"scheduler: fig3, {rounds} rounds, paper keys")
    scenarios = sweeps.expand("fig3", rounds=rounds)
    groups = sweeps.build_groups(scenarios, seeds=1)
    sync = _timed("scheduler fig3 sync",
                  lambda: sweeps.run_groups(groups))
    piped = _timed("scheduler fig3 pipelined",
                   lambda: sweeps.run_groups(groups, pipeline=True))
    _check(all(np.array_equal(a, b) for a, b in zip(sync, piped)),
           "sync == pipelined success streams, bit for bit")
    results = sweeps.summarize(groups, sync, scenario_order=scenarios)
    got = [r.throughput["lea"] for r in results]
    print(f"  LEA throughput keys 1-4: {got}")
    if pinned is not None:
        diff = max(abs(g - p) for g, p in zip(got, pinned))
        _check(diff <= PINNED_TOL,
               f"LEA throughputs within {PINNED_TOL} of pinned {pinned} "
               f"(max |diff| {diff})")

    hlo = sweeps.pipeline_block_hlo(groups[0])
    if expect_kernel:
        _check("tpu_custom_call" in hlo,
               "compiled engine program holds the Pallas allocator kernel")

    rng = np.random.default_rng(0)
    n = scenarios[0].lp.n
    p = jnp.asarray(np.sort(rng.uniform(0, 1, (kernel_batch, n)), axis=-1)
                    [:, ::-1].copy(), jnp.float32)
    w_static = lea.prefix_thresholds(scenarios[0].lp)
    w_traced = jnp.asarray(rng.integers(-2, n + 2, (kernel_batch, n)), jnp.int32)
    for label, w in (("static w", w_static), ("traced w", w_traced)):
        pal = np.asarray(success_tails(p, w, impl="pallas"))
        ref = np.asarray(success_tails(p, w, impl="ref"))
        err = float(np.max(np.abs(pal - ref)))
        _check(err <= TAILS_TOL,
               f"kernel tails ({label}, {kernel_batch}x{n}) match the "
               f"reference DP within {TAILS_TOL} (max |diff| {err})")

    fig4 = _timed("scheduler fig4", lambda: sweeps.run("fig4"))
    for r in fig4:
        _check(all(0.0 <= v <= 1.0 for v in r.throughput.values()),
               f"{r.name} throughputs in [0, 1]: {r.throughput}")
    return {"lea": got}


def phase_serving(argv: list[str] | None = None) -> dict:
    """``repro.launch.serve.main`` at its defaults; accounting identities."""
    from repro.launch import serve

    argv = [] if argv is None else argv
    print(f"serving: repro.launch.serve.main({argv})")
    out = _timed("serving", lambda: serve.main(list(argv)))
    for name, s in out.items():
        _check(s["arrivals"] == s["admitted"] + s["rejected"],
               f"{name}: arrivals == admitted + shed")
        _check(s["admitted"] == s["served_on_time"] + s["served_late"]
               + s["expired"] + s["in_flight"],
               f"{name}: admitted == on time + late + expired + in flight")
        _check(s["arrivals"] > 0, f"{name}: {s['arrivals']} arrivals")
    return out


def _erasure_pattern(spec, seed: int, rounds: int):
    """First engine-trajectory round that decodes yet lost some chunks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import sweeps
    from repro.core import LoadParams, throughput
    from repro.core.coded_ops import chunk_on_time

    sc = sweeps.expand("fig4")[0]          # the EC2 chain and loads
    lp = LoadParams(n=spec.n, kstar=spec.recovery_threshold,
                    ell_g=sc.lp.ell_g, ell_b=sc.lp.ell_b)
    states, loads, _ = throughput.rollout(
        jax.random.PRNGKey(seed), lp, jnp.full((spec.n,), sc.p_gg[0]),
        jnp.full((spec.n,), sc.p_bb[0]), rounds, strategies=("lea",))
    masks = np.asarray(chunk_on_time(states, loads, sc.mu_g, sc.mu_b,
                                     sc.deadline, spec.r))[0]
    got = masks.sum(axis=-1)
    hits = np.nonzero((got >= spec.recovery_threshold) & (got < spec.nr))[0]
    if hits.size == 0:
        raise AssertionError(f"no decodable erasure round in {rounds} rounds")
    m = int(hits[0])
    print(f"  erasure pattern: round {m} of the engine trajectory, "
          f"{int(got[m])}/{spec.nr} chunks on time, K*={spec.recovery_threshold}")
    return masks[m]


def phase_coded_round(n: int = 15, r: int = 10, k: int = 120,
                      rows: int = 25, cols: int = 3000,
                      seed: int = 0) -> dict:
    """The EC2 job's coded round, exact GF(p) and float."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import lagrange as lcc
    from repro.core.coded_ops import (coded_matmul_device, coded_matmul_exact,
                                      encode_dataset, encode_dataset_modp)

    spec = lcc.CodeSpec(n, r, k, deg_f=1)
    kstar = spec.recovery_threshold
    p = lcc.FIELD_P
    print(f"coded round: n={n} r={r} k={k} (K*={kstar}), "
          f"{rows} rows x {cols} columns per chunk")
    on = _erasure_pattern(spec, seed, TRAJECTORY_ROUNDS)
    rng = np.random.default_rng(seed)

    # exact GF(p) round
    x = rng.integers(0, p, size=(k, rows, cols), dtype=np.int64)
    x.reshape(-1)[:2] = [0, p - 1]                   # boundary residues
    w = rng.integers(0, p, size=(cols,), dtype=np.int64)
    x_dev, w_dev, on_dev = (jnp.asarray(x, jnp.int32), jnp.asarray(w, jnp.int32),
                            jnp.asarray(on))
    coded = _timed("coded round exact encode",
                   lambda: encode_dataset_modp(spec, x_dev),
                   ready=lambda c: c.x_tilde)
    out, ok = _timed("coded round exact products + decode",
                     lambda: coded_matmul_exact(coded, w_dev, on_dev))
    _check(bool(ok), "exact round reports ok")
    xt = np.asarray(coded.x_tilde, np.int64)
    gen = lcc.generator_matrix_modp(spec)
    c = min(cols, 8)
    _check(np.array_equal(
        xt[:, :, :c].reshape(spec.nr, -1),
        lcc.matmul_modp(gen, x[:, :, :c].reshape(k, -1))),
        f"encode == numpy matmul_modp(generator) on the first {c} columns")
    received = np.nonzero(on)[0][:kstar]
    results = lcc.matmul_modp(xt.reshape(-1, cols), w.reshape(-1, 1))
    want = lcc.matmul_modp(lcc.decode_matrix_modp(spec, received),
                           results.reshape(spec.nr, rows)[received])
    got = np.asarray(out, np.int64)
    _check(np.array_equal(got, want),
           "decoded round == numpy matmul_modp/decode_matrix_modp pipeline")
    _check(np.array_equal(
        got, lcc.matmul_modp(x.reshape(-1, cols), w.reshape(-1, 1))
        .reshape(k, rows)),
        "decoded round == X_j @ w mod p (MDS round trip)")

    # float round
    xf = rng.standard_normal((k, rows, cols)).astype(np.float32)
    wf = rng.standard_normal((cols,)).astype(np.float32)
    codedf = encode_dataset(spec, jnp.asarray(xf))
    outf, okf = _timed(
        "coded round float products + decode",
        lambda: coded_matmul_device(codedf, jnp.asarray(wf), on_dev))
    _check(bool(okf), "float round reports ok")
    ref = (xf.astype(np.float64).reshape(-1, cols)
           @ wf.astype(np.float64)).reshape(k, rows)
    err = float(np.max(np.abs(np.asarray(outf, np.float64) - ref))
                / np.max(np.abs(ref)))
    g64 = np.asarray(lcc.generator_matrix(spec), np.float64)
    kappa = float(np.linalg.cond(g64[received]))
    tol = FLOAT_TOL_ULPS * float(np.finfo(np.float32).eps) * kappa
    _check(err <= tol,
           f"float round vs float64 X @ w: max rel error {err:.3e} <= "
           f"{FLOAT_TOL_ULPS:g} ulp x cond {kappa:.3e} = {tol:.3e}")
    return {"exact_ok": True, "float_rel_err": err, "cond": kappa}


def phase_four_chips(devices: int = FOUR_CHIPS, seeds: int = 4,
                     rounds: int | None = None) -> None:
    """``sweeps.run("fig4")`` sharded over ``devices`` chips == over one."""
    import numpy as np

    from repro import sweeps
    from repro.launch.mesh import make_sweep_mesh

    kw = {} if rounds is None else {"rounds": rounds}
    scenarios = sweeps.expand("fig4", **kw)
    groups = sweeps.build_groups(scenarios, seeds=seeds)
    print(f"sharded sweep: fig4, seeds={seeds}, batch mesh over {devices} "
          "devices vs 1")
    many = _timed(f"fig4 sweep on {devices} devices",
                  lambda: sweeps.run_groups(groups, mesh=make_sweep_mesh(devices)))
    one = _timed("fig4 sweep on 1 device",
                 lambda: sweeps.run_groups(groups, mesh=make_sweep_mesh(1)))
    _check(all(np.array_equal(a, b) for a, b in zip(many, one)),
           f"{devices}-device and 1-device success streams bit-identical")
    res_many = sweeps.summarize(groups, many, scenario_order=scenarios)
    res_one = sweeps.summarize(groups, one, scenario_order=scenarios)
    _check(all(a.per_seed == b.per_seed for a, b in zip(res_many, res_one)),
           "per-seed throughputs identical")


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    unknown = [a for a in argv if a != "--four-chips"]
    if unknown:
        sys.exit(f"chip_smoke: unknown arguments {unknown}")
    import jax

    device = require_tpu(jax.devices())
    from repro.launch.cache import enable_compile_cache

    cache = enable_compile_cache()
    warm = len(os.listdir(cache))
    print(f"device: {device}; compile cache: {cache} "
          f"({'warm, ' + str(warm) + ' entries' if warm else 'cold'})")
    if "--four-chips" in argv:
        phase_four_chips()
    else:
        phase_scheduler()
        phase_serving()
        phase_coded_round()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

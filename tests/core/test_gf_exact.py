"""Device-resident exact GF(p) coding layer vs the numpy ``*_modp`` oracle.

Every comparison is exact integer equality (array_equal): the device path
(``repro.kernels.gf`` through ``core.lagrange``/``core.coded_ops``) must be
bit-identical to the numpy ``matmul_modp``/``decode_matrix_modp`` pipeline —
including for erasure patterns sampled from engine ``rollout()``
trajectories, the acceptance bar of the subsystem.
"""

import numpy as np
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import lagrange as lcc
from repro.core import throughput
from repro.core.coded_ops import (ModpDecodeCache, chunk_on_time,
                                  coded_linear_gradient_modp,
                                  coded_matmul_exact, encode_dataset_modp)
from repro.core.lea import LoadParams

P = lcc.FIELD_P


def _np_pipeline(spec, xt_np, w_np, on_time):
    """The numpy oracle round: shard matmul -> first-K* gather -> decode."""
    kstar = spec.recovery_threshold
    rows = xt_np.shape[1]
    res = lcc.matmul_modp(xt_np.reshape(spec.nr * rows, -1), w_np.reshape(w_np.shape[0], -1))
    res = res.reshape(spec.nr, rows, -1)
    rec = np.nonzero(on_time)[0][:kstar]
    d = lcc.decode_matrix_modp(spec, rec)
    return lcc.matmul_modp(d, res[rec].reshape(kstar, -1)).reshape(
        (spec.k, rows) + ((w_np.shape[1],) if w_np.ndim == 2 else ())
    )


@settings(max_examples=12, deadline=None)
@given(
    n=st.integers(3, 8),
    r=st.integers(1, 3),
    k=st.integers(2, 6),
    deg_f=st.integers(1, 3),
)
def test_generator_and_decode_matrix_device_bit_equal_numpy(n, r, k, deg_f):
    spec = lcc.CodeSpec(n, r, k, deg_f)
    kstar = spec.recovery_threshold
    if kstar > spec.nr:
        return  # infeasible code
    np.testing.assert_array_equal(
        np.asarray(lcc.generator_matrix_modp_device(spec), np.int64),
        lcc.generator_matrix_modp(spec),
    )
    rng = np.random.default_rng(n * 100 + r * 10 + k + deg_f)
    for _ in range(3):
        received = np.sort(rng.choice(spec.nr, size=kstar, replace=False))
        np.testing.assert_array_equal(
            np.asarray(
                lcc.decode_matrix_modp_device(spec, jnp.asarray(received, jnp.int32)),
                np.int64,
            ),
            lcc.decode_matrix_modp(spec, received),
        )


def test_decode_matrix_device_batched_over_patterns():
    spec = lcc.CodeSpec(5, 2, 4, 1)
    kstar = spec.recovery_threshold
    rng = np.random.default_rng(0)
    received = np.stack(
        [np.sort(rng.choice(spec.nr, size=kstar, replace=False)) for _ in range(6)]
    )
    got = np.asarray(
        lcc.decode_matrix_modp_device(spec, jnp.asarray(received, jnp.int32)),
        np.int64,
    )
    assert got.shape == (6, spec.k, kstar)
    for i in range(6):
        np.testing.assert_array_equal(got[i], lcc.decode_matrix_modp(spec, received[i]))


@settings(max_examples=12, deadline=None)
@given(
    n=st.integers(3, 7),
    r=st.integers(1, 3),
    k=st.integers(2, 6),
    rows=st.integers(1, 4),
    cols=st.integers(1, 5),
    seed=st.integers(0, 2**31 - 1),
)
def test_encode_erase_decode_roundtrip_vs_numpy(n, r, k, rows, cols, seed):
    """encode -> random erasure -> decode == the numpy pipeline AND the raw
    data (deg 1 round-trip), over random shapes and patterns, with the
    0 / p-1 boundary residues spliced into the data."""
    spec = lcc.CodeSpec(n, r, k, deg_f=1)
    kstar = spec.recovery_threshold
    if kstar > spec.nr:
        return
    rng = np.random.default_rng(seed)
    x = rng.integers(0, P, size=(k, rows, cols), dtype=np.int64)
    x.reshape(-1)[: 4] = [0, P - 1, 1, P - 2][:x.size]        # boundary residues
    w = rng.integers(0, P, size=(cols,), dtype=np.int64)
    w[:1] = P - 1

    coded = encode_dataset_modp(spec, jnp.asarray(x, jnp.int32))
    xt_np = lcc.matmul_modp(lcc.generator_matrix_modp(spec), x.reshape(k, -1))
    np.testing.assert_array_equal(
        np.asarray(coded.x_tilde, np.int64).reshape(spec.nr, -1), xt_np)

    # a random K*-subset survives
    on_time = np.zeros(spec.nr, bool)
    on_time[rng.choice(spec.nr, size=kstar, replace=False)] = True
    out, ok = coded_matmul_exact(coded, jnp.asarray(w, jnp.int32), jnp.asarray(on_time))
    assert bool(ok)
    want = _np_pipeline(spec, xt_np.reshape(spec.nr, rows, cols), w, on_time)
    np.testing.assert_array_equal(np.asarray(out, np.int64), want)
    # deg-1 MDS round-trip: the decode recovers f(X_j) = X_j @ w exactly
    np.testing.assert_array_equal(
        np.asarray(out, np.int64),
        lcc.matmul_modp(x.reshape(-1, cols), w.reshape(-1, 1)).reshape(k, rows),
    )


def test_exact_decode_repetition_branch_vs_numpy():
    spec = lcc.CodeSpec(3, 2, 4, 2)        # nr=6 < k*deg-1: repetition, K*=6
    assert spec.mode == "repetition"
    rng = np.random.default_rng(5)
    x = rng.integers(0, P, size=(spec.k, 2, 3), dtype=np.int64)
    w = rng.integers(0, P, size=(3,), dtype=np.int64)
    coded = encode_dataset_modp(spec, jnp.asarray(x, jnp.int32))
    on_time = np.ones(spec.nr, bool)
    out, ok = coded_matmul_exact(coded, jnp.asarray(w, jnp.int32), jnp.asarray(on_time))
    assert bool(ok)
    xt_np = np.asarray(coded.x_tilde, np.int64)
    want = _np_pipeline(spec, xt_np, w, on_time)
    np.testing.assert_array_equal(np.asarray(out, np.int64), want)


def test_exact_round_on_a_block_of_queries_vs_numpy():
    """A (cols, d) block of queries goes through the same jitted products
    and decode as one query, bit-identical to the numpy pipeline."""
    spec = lcc.CodeSpec(5, 2, 4, 1)
    rng = np.random.default_rng(11)
    x = rng.integers(0, P, size=(spec.k, 3, 6), dtype=np.int64)
    w = rng.integers(0, P, size=(6, 3), dtype=np.int64)
    w[0, 0] = P - 1
    coded = encode_dataset_modp(spec, jnp.asarray(x, jnp.int32))
    on_time = np.zeros(spec.nr, bool)
    on_time[rng.choice(spec.nr, size=spec.recovery_threshold, replace=False)] = True
    out, ok = coded_matmul_exact(coded, jnp.asarray(w, jnp.int32), jnp.asarray(on_time))
    assert bool(ok)
    assert out.shape == (spec.k, 3, 3)
    want = _np_pipeline(spec, np.asarray(coded.x_tilde, np.int64), w, on_time)
    np.testing.assert_array_equal(np.asarray(out, np.int64), want)


def test_short_round_reports_not_ok():
    spec = lcc.CodeSpec(5, 2, 4, 1)
    coded = encode_dataset_modp(
        spec, jnp.asarray(np.arange(4 * 2 * 3).reshape(4, 2, 3), jnp.int32))
    on_time = np.zeros(spec.nr, bool)
    on_time[: spec.recovery_threshold - 1] = True          # one short of K*
    _, ok = coded_matmul_exact(
        coded, jnp.asarray(np.ones(3), jnp.int32), jnp.asarray(on_time))
    assert not bool(ok)


def test_modp_cache_from_on_time_raises_on_short_pattern():
    """The eager cache path mirrors coded_matmul's TimeoutError convention
    instead of silently building a truncated decode matrix."""
    spec = lcc.CodeSpec(5, 2, 4, 1)
    cache = ModpDecodeCache(spec)
    on_time = np.zeros(spec.nr, bool)
    on_time[: spec.recovery_threshold - 1] = True          # one short of K*
    try:
        cache.from_on_time(on_time)
    except TimeoutError as e:
        assert f"K*={spec.recovery_threshold}" in str(e)
    else:  # pragma: no cover
        raise AssertionError("expected TimeoutError")
    assert len(cache) == 0 and cache.misses == 0            # nothing memoised
    # exactly K* on time still works
    on_time[spec.recovery_threshold - 1] = True
    received, dmat = cache.from_on_time(on_time)
    np.testing.assert_array_equal(
        np.asarray(dmat, np.int64), lcc.decode_matrix_modp(spec, received))


def test_exact_round_on_engine_rollout_patterns():
    """The acceptance bar: coded_matmul_exact == numpy pipeline for every
    feasible erasure pattern produced by an engine rollout's Markov
    trajectories (both strategy columns), via chunk_on_time."""
    spec = lcc.CodeSpec(6, 3, 5, 1)
    kstar = spec.recovery_threshold
    lp = LoadParams(n=6, kstar=kstar, ell_g=3, ell_b=1)
    mu_g, mu_b, deadline = 3.0, 1.0, 1.0
    rng = np.random.default_rng(2)
    x = rng.integers(0, P, size=(spec.k, 4, 7), dtype=np.int64)
    x[0, 0, 0], x[1, 1, 1] = 0, P - 1
    w = rng.integers(0, P, size=(7,), dtype=np.int64)
    coded = encode_dataset_modp(spec, jnp.asarray(x, jnp.int32))
    xt_np = np.asarray(coded.x_tilde, np.int64)

    states, loads, feasible = throughput.rollout(
        jax.random.PRNGKey(0), lp, jnp.full((6,), 0.8), jnp.full((6,), 0.7),
        30, strategies=("lea", "static"),
    )
    masks = np.asarray(chunk_on_time(states, loads, mu_g, mu_b, deadline, spec.r))
    succ = np.asarray(throughput.score_rollout(
        states, loads, feasible, lp, mu_g, mu_b, deadline))

    fn = jax.jit(lambda m: coded_matmul_exact(coded, jnp.asarray(w, jnp.int32), m))
    cache = ModpDecodeCache(spec)
    checked = 0
    for s in range(masks.shape[0]):
        for m in range(masks.shape[1]):
            on = masks[s, m]
            # chunk masks and engine scoring agree on round success
            assert bool(succ[m, s]) == (on.sum() >= kstar and bool(feasible[s, m]))
            if on.sum() < kstar:
                continue
            out, ok = fn(jnp.asarray(on))
            assert bool(ok)
            want = _np_pipeline(spec, xt_np, w, on)
            np.testing.assert_array_equal(np.asarray(out, np.int64), want)
            # the memoised decode matrix is the same numpy matrix
            received, dmat = cache.from_on_time(on)
            np.testing.assert_array_equal(
                np.asarray(dmat, np.int64), lcc.decode_matrix_modp(spec, received))
            checked += 1
    assert checked > 10
    # discrete worker states ==> patterns recur ==> the cache actually hits
    assert cache.hits > 0 and len(cache) == cache.misses


def test_chunk_on_time_broadcasts_and_prefix_rule():
    # worker 0 good (all 3 chunks), worker 1 bad with load 1 (<= ell_b: first
    # chunk only), worker 2 bad with load 3 (misses deadline: nothing)
    states = jnp.asarray([[1, 0, 0]])
    loads = jnp.asarray([[3, 1, 3]])
    mask = np.asarray(chunk_on_time(states, loads, 3.0, 1.0, 1.0, r=3))
    np.testing.assert_array_equal(
        mask[0], [True, True, True, True, False, False, False, False, False])


# ---------------------------------------------------------------------------
# exact deg-2 gradient: coded_linear_gradient_modp vs the numpy oracle
# ---------------------------------------------------------------------------

def _np_gradient_oracle(spec, xt_np, yt_np, w_np, on_time):
    """Numpy replication: per-chunk X~^T(X~ w - y~), gather, decode, sum."""
    kstar = spec.recovery_threshold
    w2 = w_np.reshape(w_np.shape[0], -1)
    grads = []
    for v in range(spec.nr):
        resid = (lcc.matmul_modp(xt_np[v], w2) - yt_np[v][:, None]) % P
        grads.append(lcc.matmul_modp(xt_np[v].T, resid))
    grads = np.stack(grads)                               # (nr, cols, d)
    rec = np.nonzero(on_time)[0][:kstar]
    d = lcc.decode_matrix_modp(spec, rec)
    per_chunk = lcc.matmul_modp(d, grads[rec].reshape(kstar, -1)).reshape(
        (spec.k,) + grads.shape[1:]
    )
    total = per_chunk.sum(axis=0) % P
    return total[:, 0] if w_np.ndim == 1 else total


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(3, 7),
    r=st.integers(2, 3),
    rows=st.integers(1, 5),
    cols=st.integers(1, 4),
    seed=st.integers(0, 2**31 - 1),
)
def test_coded_linear_gradient_modp_bit_equal_numpy(n, r, rows, cols, seed):
    rng = np.random.default_rng(seed)
    k = max(2, (n * r) // 3)
    spec = lcc.CodeSpec(n, r, k, deg_f=2)
    if spec.mode != "lagrange":
        return
    x = rng.integers(0, P, size=(k, rows, cols), dtype=np.int64)
    y = rng.integers(0, P, size=(k, rows), dtype=np.int64)
    w = rng.integers(0, P, size=(cols,), dtype=np.int64)
    coded = encode_dataset_modp(spec, jnp.asarray(x, jnp.int32),
                                jnp.asarray(y, jnp.int32))
    xt_np = np.asarray(coded.x_tilde, np.int64)
    yt_np = np.asarray(coded.y_tilde, np.int64)
    on_time = np.zeros(spec.nr, bool)
    extra = int(rng.integers(0, spec.nr - spec.recovery_threshold + 1))
    on_time[rng.choice(spec.nr, spec.recovery_threshold + extra,
                       replace=False)] = True
    got, ok = jax.jit(
        lambda m: coded_linear_gradient_modp(coded, jnp.asarray(w, jnp.int32), m)
    )(jnp.asarray(on_time))
    assert bool(ok)
    want = _np_gradient_oracle(spec, xt_np, yt_np, w, on_time)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


def test_coded_linear_gradient_modp_matrix_targets_and_validation():
    import pytest

    rng = np.random.default_rng(3)
    spec = lcc.CodeSpec(5, 3, 4, deg_f=2)
    x = rng.integers(0, P, size=(4, 3, 2), dtype=np.int64)
    y = rng.integers(0, P, size=(4, 3), dtype=np.int64)
    w2 = rng.integers(0, P, size=(2, 3), dtype=np.int64)   # (cols, d) targets
    coded = encode_dataset_modp(spec, jnp.asarray(x, jnp.int32),
                                jnp.asarray(y, jnp.int32))
    on_time = np.ones(spec.nr, bool)
    got, ok = coded_linear_gradient_modp(coded, jnp.asarray(w2, jnp.int32),
                                         jnp.asarray(on_time))
    assert bool(ok) and got.shape == (2, 3)
    want = _np_gradient_oracle(
        spec, np.asarray(coded.x_tilde, np.int64),
        np.asarray(coded.y_tilde, np.int64), w2, on_time,
    )
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)

    # short pattern -> ok False, targetless/odd-degree datasets raise
    short = np.zeros(spec.nr, bool)
    short[: spec.recovery_threshold - 1] = True
    _, ok = coded_linear_gradient_modp(coded, jnp.asarray(w2, jnp.int32),
                                       jnp.asarray(short))
    assert not bool(ok)
    no_targets = encode_dataset_modp(spec, jnp.asarray(x, jnp.int32))
    with pytest.raises(ValueError, match="without targets"):
        coded_linear_gradient_modp(no_targets, jnp.asarray(w2, jnp.int32),
                                   jnp.asarray(on_time))
    spec1 = lcc.CodeSpec(5, 3, 4, deg_f=1)
    coded1 = encode_dataset_modp(spec1, jnp.asarray(x, jnp.int32),
                                 jnp.asarray(y, jnp.int32))
    with pytest.raises(ValueError, match="degree-2"):
        coded_linear_gradient_modp(coded1, jnp.asarray(w2, jnp.int32),
                                   jnp.asarray(on_time))

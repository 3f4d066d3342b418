"""Property tests for the shape-polymorphic (traced-K*/ell, mask-padded)
engine — the PR's load-bearing invariants, per layer:

  * traced integer thresholds == the numpy static thresholds, exactly;
  * the ref DP with per-row threshold arrays == the ref DP with the shared
    static vector, bit-for-bit (the engine's CPU path);
  * the traced-threshold Pallas kernel (interpret) == the ref DP to float32
    round-off (the same tolerance the static kernel always had);
  * ``allocate_masked`` on a full-width pool == ``allocate`` with the
    equivalent static ``LoadParams``, bit-for-bit;
  * masked-allocate edge cases: all-masked rows and K*-infeasible pools set
    the EXPLICIT failure flag and assign zero load — never a silent success;
  * padded-vs-unpadded allocation on random pool sizes: valid workers'
    loads/i* match whenever the success-prob argmax is not within float
    round-off of a tie (the DP tail reduction width is the only difference);
  * the full engine: ``simulate_strategies_pool`` / ``sweep_pool`` on
    full-width pools == the static-``LoadParams`` engine, bit-for-bit,
    including non-stationary chains and round chunking;
  * K*-infeasible pools simulate without crashing and never succeed;
  * masked trajectory sampling freezes masked workers and is inert for
    full-width masks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import lea, markov, throughput
from repro.core.lea import LoadParams, PoolLoad
from repro.kernels.poisson_binomial import (success_tails_pallas_w,
                                            success_tails_ref)


def _random_lp(rng, n) -> LoadParams:
    ell_b = int(rng.integers(1, 4))
    ell_g = ell_b + int(rng.integers(1, 8))
    kstar = int(rng.integers(n * ell_b + 1, n * ell_g + 1))
    return LoadParams(n=n, kstar=kstar, ell_g=ell_g, ell_b=ell_b)


# ---------------------------------------------------------------------------
# thresholds: traced integer ceil-div == numpy float64 ceil
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 40), seed=st.integers(0, 2**31 - 1))
def test_prefix_thresholds_traced_matches_numpy(n, seed):
    rng = np.random.default_rng(seed)
    lp = _random_lp(rng, n)
    want = lea.prefix_thresholds(lp)
    got = lea.prefix_thresholds_traced(
        jnp.asarray(lp.kstar), jnp.asarray(lp.ell_g), jnp.asarray(lp.ell_b),
        jnp.asarray(n), n,
    )
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("kstar", [13_334, 26_667, 39_999])
def test_prefix_thresholds_traced_exact_at_fleet_width(kstar):
    """The four-chip fleet's thresholds (n = 4,000, ell_g = 10, ell_b = 1):
    the int32 ceil-div equals the float64 ceil at every prefix."""
    lp = LoadParams(n=4000, kstar=kstar, ell_g=10, ell_b=1)
    got = lea.prefix_thresholds_traced(kstar, 10, 1, 4000, 4000)
    np.testing.assert_array_equal(np.asarray(got), lea.prefix_thresholds(lp))


def test_prefix_thresholds_traced_pads_infeasible_past_valid_pool():
    got = np.asarray(lea.prefix_thresholds_traced(
        jnp.asarray(9), jnp.asarray(4), jnp.asarray(1), jnp.asarray(3), 6
    ))
    lp = LoadParams(n=3, kstar=9, ell_g=4, ell_b=1)
    np.testing.assert_array_equal(got[:3], lea.prefix_thresholds(lp))
    assert (got[3:] == 7).all()             # sentinel n + 1 > every i~


# ---------------------------------------------------------------------------
# DP layer: per-row thresholds == shared thresholds, bit-for-bit (ref);
# traced-w Pallas kernel == ref to round-off
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 24), b=st.integers(1, 10), seed=st.integers(0, 2**31 - 1))
def test_ref_dp_rowwise_thresholds_bit_equal_shared(n, b, seed):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(
        np.sort(rng.uniform(0, 1, (b, n)), axis=-1)[:, ::-1].copy(), jnp.float32
    )
    w = rng.integers(-2, n + 2, size=n).astype(np.int32)
    shared = success_tails_ref(p, jnp.asarray(w))
    rowwise = success_tails_ref(p, jnp.broadcast_to(jnp.asarray(w), (b, n)))
    np.testing.assert_array_equal(np.asarray(shared), np.asarray(rowwise))


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 24), b=st.integers(1, 10), seed=st.integers(0, 2**31 - 1))
def test_pallas_traced_w_kernel_matches_ref(n, b, seed):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(
        np.sort(rng.uniform(0, 1, (b, n)), axis=-1)[:, ::-1].copy(), jnp.float32
    )
    w = jnp.asarray(rng.integers(-2, n + 2, size=(b, n)), jnp.int32)
    pal = np.asarray(success_tails_pallas_w(p, w, interpret=True))
    ref = np.asarray(success_tails_ref(p, w))
    np.testing.assert_allclose(pal, ref, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# allocate layer
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 20), b=st.integers(1, 8), seed=st.integers(0, 2**31 - 1))
def test_allocate_masked_full_width_bit_identical_to_allocate(n, b, seed):
    rng = np.random.default_rng(seed)
    lp = _random_lp(rng, n)
    p = jnp.asarray(rng.uniform(0, 1, (b, n)), jnp.float32)
    loads_s, istar_s = lea.allocate(p, lp)
    loads_m, istar_m, feasible = lea.allocate_masked(p, lea.pool_load(lp))
    np.testing.assert_array_equal(np.asarray(loads_s), np.asarray(loads_m))
    np.testing.assert_array_equal(np.asarray(istar_s), np.asarray(istar_m))
    assert bool(jnp.all(feasible))          # _random_lp keeps kstar <= n*ell_g


@pytest.mark.parametrize("n", [lea._PAIRWISE_RANK_MAX_N + 8, 300])
def test_allocate_masked_wide_loads_split_at_the_stable_rank(n):
    """Past the pairwise cut the argsort branch ranks the pool: the i*
    workers first in the stable descending order of p (ties by worker
    index) get ell_g."""
    rng = np.random.default_rng(n)
    b = 24
    p = rng.choice([0.2, 0.5, 0.5, 0.7, 0.9], size=(b, n)).astype(np.float32)
    mask = rng.uniform(size=(b, n)) < 0.9
    pool = PoolLoad(kstar=jnp.asarray(rng.integers(n, 8 * n, size=b), jnp.int32),
                    ell_g=jnp.full((b,), 10, jnp.int32),
                    ell_b=jnp.full((b,), 1, jnp.int32), mask=jnp.asarray(mask))
    loads, i_star, _ = lea.allocate_masked(jnp.asarray(p), pool)
    p_eff = np.where(mask, p, np.float32(-1.0))
    ranks = np.argsort(np.argsort(-p_eff, axis=-1, kind="stable"), axis=-1,
                       kind="stable")
    want = np.where(mask, np.where(ranks < np.asarray(i_star)[:, None], 10, 1), 0)
    np.testing.assert_array_equal(np.asarray(loads), want)


@settings(max_examples=20, deadline=None)
@given(n_valid=st.integers(1, 14), pad=st.integers(1, 12),
       seed=st.integers(0, 2**31 - 1))
def test_allocate_masked_padded_vs_unpadded_random_pool_sizes(n_valid, pad, seed):
    """Padded allocation == unpadded allocation for the valid workers.

    The only float difference between the two paths is the DP tail
    reduction width (padded rows sum extra exact zeros), so success probs
    agree to reduction round-off; away from argmax ties the loads and i*
    must match exactly, and masked slots always carry load 0.
    """
    rng = np.random.default_rng(seed)
    lp = _random_lp(rng, n_valid)
    n_max = n_valid + pad
    p_valid = rng.uniform(0, 1, n_valid).astype(np.float32)
    # garbage in the masked slots — must be ignored entirely
    p_pad = np.concatenate([p_valid, rng.uniform(0, 1, pad).astype(np.float32)])
    pool = lea.pool_load(lp, n=n_max)

    loads_u, istar_u = lea.allocate(jnp.asarray(p_valid), lp)
    loads_p, istar_p, feasible = lea.allocate_masked(jnp.asarray(p_pad), pool)
    assert bool(feasible)
    np.testing.assert_array_equal(np.asarray(loads_p)[n_valid:], 0)

    # success probs of both paths (the DP the argmax reads)
    p_sorted = np.sort(p_valid)[::-1].copy()
    probs_u = np.asarray(lea.success_prob_all_prefixes(jnp.asarray(p_sorted), lp))
    p_sorted_pad = np.concatenate([p_sorted, np.zeros(pad, np.float32)])
    probs_p = np.asarray(
        lea.success_prob_all_prefixes(jnp.asarray(p_sorted_pad), pool)
    )
    np.testing.assert_allclose(probs_p[:n_valid], probs_u, rtol=2e-6, atol=1e-7)
    np.testing.assert_array_equal(probs_p[n_valid:], 0.0)

    # exact equality away from reduction-round-off argmax ties
    top = np.max(probs_u)
    runners = probs_u[probs_u < top]
    gap = top - (runners.max() if runners.size else -1.0)
    if gap > 1e-5:
        assert int(istar_p) == int(istar_u)
        np.testing.assert_array_equal(np.asarray(loads_p)[:n_valid],
                                      np.asarray(loads_u))


def test_allocate_masked_all_masked_rows_fail_explicitly():
    rng = np.random.default_rng(0)
    pool = PoolLoad(kstar=jnp.asarray(5, jnp.int32), ell_g=jnp.asarray(4, jnp.int32),
                    ell_b=jnp.asarray(1, jnp.int32), mask=jnp.zeros((8,), bool))
    p = jnp.asarray(rng.uniform(0, 1, (3, 8)), jnp.float32)
    loads, i_star, feasible = lea.allocate_masked(p, pool)
    assert not bool(jnp.any(feasible))
    np.testing.assert_array_equal(np.asarray(loads), 0)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 12), seed=st.integers(0, 2**31 - 1))
def test_allocate_masked_infeasible_kstar_sets_failure_flag(n, seed):
    """kstar beyond the valid pool's capacity must set the explicit failure
    flag — never silently succeed."""
    rng = np.random.default_rng(seed)
    ell_b = int(rng.integers(1, 3))
    ell_g = ell_b + int(rng.integers(1, 5))
    n_valid = int(rng.integers(1, n + 1))
    kstar = n_valid * ell_g + int(rng.integers(1, 10))    # > capacity
    pool = PoolLoad(
        kstar=jnp.asarray(kstar, jnp.int32), ell_g=jnp.asarray(ell_g, jnp.int32),
        ell_b=jnp.asarray(ell_b, jnp.int32), mask=jnp.arange(n) < n_valid,
    )
    p = jnp.asarray(rng.uniform(0, 1, (4, n)), jnp.float32)
    _, _, feasible = lea.allocate_masked(p, pool)
    assert not bool(jnp.any(feasible))


# ---------------------------------------------------------------------------
# engine layer
# ---------------------------------------------------------------------------

ALL_STRATEGIES = ("lea", "static", "static_equal", "static_single", "oracle")


def test_simulate_strategies_pool_full_width_bit_identical():
    lp = LoadParams(n=15, kstar=99, ell_g=10, ell_b=3)
    key = jax.random.PRNGKey(7)
    args = (jnp.full((15,), 0.8), jnp.full((15,), 0.7), 10.0, 3.0, 1.0, 400)
    ref = throughput.simulate_strategies(key, lp, *args, strategies=ALL_STRATEGIES)
    got = throughput.simulate_strategies_pool(
        key, lea.pool_load(lp), *args, strategies=ALL_STRATEGIES
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    # round chunking stays bit-identical on the pool path too
    chunked = throughput.simulate_strategies_pool(
        key, lea.pool_load(lp), *args, strategies=ALL_STRATEGIES, round_chunk=37
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(chunked))


def test_simulate_strategies_pool_time_varying_chain_bit_identical():
    lp = LoadParams(n=6, kstar=24, ell_g=5, ell_b=2)
    key = jax.random.PRNGKey(3)
    rounds = 120
    rng = np.random.default_rng(0)
    p_gg = jnp.asarray(rng.uniform(0.4, 0.95, (rounds, 6)), jnp.float32)
    p_bb = jnp.asarray(rng.uniform(0.3, 0.9, (rounds, 6)), jnp.float32)
    args = (p_gg, p_bb, 5.0, 2.0, 1.0, rounds)
    ref = throughput.simulate_strategies(
        key, lp, *args, strategies=("lea", "static", "oracle")
    )
    got = throughput.simulate_strategies_pool(
        key, lea.pool_load(lp), *args, strategies=("lea", "static", "oracle")
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_sweep_pool_full_width_bit_identical_to_sweep():
    lp = LoadParams(n=15, kstar=99, ell_g=10, ell_b=3)
    b, rounds = 5, 160
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(b)])
    rng = np.random.default_rng(1)
    p_gg = jnp.asarray(rng.uniform(0.6, 0.95, (b, 15)), jnp.float32)
    p_bb = jnp.asarray(rng.uniform(0.4, 0.9, (b, 15)), jnp.float32)
    ref = throughput.sweep(keys, lp, p_gg, p_bb, 10.0, 3.0, 1.0, rounds)
    pool = PoolLoad(
        kstar=jnp.full((b,), lp.kstar, jnp.int32),
        ell_g=jnp.full((b,), lp.ell_g, jnp.int32),
        ell_b=jnp.full((b,), lp.ell_b, jnp.int32),
        mask=jnp.ones((b, 15), bool),
    )
    got = throughput.sweep_pool(keys, pool, p_gg, p_bb, 10.0, 3.0, 1.0, rounds)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_heterogeneous_pool_rows_match_per_row_pool_runs():
    """One fused sweep_pool call over rows with different K*/ell/pool sizes
    == each row run alone through the masked engine (vmap consistency)."""
    n_max, rounds = 12, 96
    rows = [
        (LoadParams(n=12, kstar=30, ell_g=4, ell_b=1), 12),
        (LoadParams(n=8, kstar=20, ell_g=5, ell_b=2), 8),
        (LoadParams(n=5, kstar=9, ell_g=3, ell_b=1), 5),
    ]
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(len(rows))])
    rng = np.random.default_rng(2)
    p_gg = jnp.asarray(rng.uniform(0.5, 0.95, (len(rows), n_max)), jnp.float32)
    p_bb = jnp.asarray(rng.uniform(0.3, 0.9, (len(rows), n_max)), jnp.float32)
    pool = PoolLoad(
        kstar=jnp.asarray([lp.kstar for lp, _ in rows], jnp.int32),
        ell_g=jnp.asarray([lp.ell_g for lp, _ in rows], jnp.int32),
        ell_b=jnp.asarray([lp.ell_b for lp, _ in rows], jnp.int32),
        mask=jnp.stack([jnp.arange(n_max) < nv for _, nv in rows]),
    )
    fused = throughput.sweep_pool(
        keys, pool, p_gg, p_bb, 6.0, 2.0, 1.0, rounds,
        strategies=("lea", "static", "oracle"),
    )
    for ri, (lp, nv) in enumerate(rows):
        one = throughput.simulate_strategies_pool(
            keys[ri], lea.pool_load(lp, n=n_max), p_gg[ri], p_bb[ri],
            6.0, 2.0, 1.0, rounds, strategies=("lea", "static", "oracle"),
        )
        np.testing.assert_array_equal(np.asarray(fused[ri]), np.asarray(one))


def test_infeasible_kstar_pool_simulates_without_silent_success():
    lp = LoadParams(n=4, kstar=9, ell_g=3, ell_b=1)   # capacity 12 >= 9, fine
    pool = PoolLoad(
        kstar=jnp.asarray(50, jnp.int32),             # way past capacity
        ell_g=jnp.asarray(3, jnp.int32), ell_b=jnp.asarray(1, jnp.int32),
        mask=jnp.ones((4,), bool),
    )
    succ = throughput.simulate_strategies_pool(
        jax.random.PRNGKey(0), pool,
        jnp.full((4,), 0.95), jnp.full((4,), 0.1), 3.0, 1.0, 1.0, 64,
        strategies=ALL_STRATEGIES,
    )
    assert not bool(jnp.any(succ))


# ---------------------------------------------------------------------------
# trajectory sampling with masks
# ---------------------------------------------------------------------------

def test_sample_trajectory_mask_freezes_masked_workers():
    key = jax.random.PRNGKey(5)
    p_gg = jnp.full((10,), 0.6)
    p_bb = jnp.full((10,), 0.7)
    mask = jnp.arange(10) < 6
    traj = markov.sample_trajectory(key, p_gg, p_bb, 200, worker_mask=mask)
    assert bool(jnp.all(traj[:, 6:] == 1))            # frozen good
    # full-true mask is value-identical to no mask at all
    ref = markov.sample_trajectory(key, p_gg, p_bb, 200)
    full = markov.sample_trajectory(key, p_gg, p_bb, 200,
                                    worker_mask=jnp.ones((10,), bool))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(full))
    # scan reference agrees under the mask too
    scan = markov.sample_trajectory_scan(key, p_gg, p_bb, 200, worker_mask=mask)
    np.testing.assert_array_equal(np.asarray(traj), np.asarray(scan))


@pytest.mark.parametrize("n", [markov.SEQUENTIAL_N - 1, markov.SEQUENTIAL_N])
def test_sample_trajectory_either_side_of_the_sequential_cut(n):
    """Below the cut the associative scan, from it on the stepped sampler:
    both the stepped sampler's bits, masked workers frozen."""
    key = jax.random.PRNGKey(n)
    p_gg = jnp.linspace(0.5, 0.95, n)
    p_bb = jnp.linspace(0.9, 0.4, n)
    mask = jnp.arange(n) < n - 3
    traj = markov.sample_trajectory(key, p_gg, p_bb, 64, worker_mask=mask)
    scan = markov.sample_trajectory_scan(key, p_gg, p_bb, 64, worker_mask=mask)
    np.testing.assert_array_equal(np.asarray(traj), np.asarray(scan))
    assert bool(jnp.all(traj[:, n - 3:] == 1))


def test_frozen_pad_chain_is_deterministically_good():
    """The sweeps padding convention (p_gg=1, p_bb=0) freezes workers in the
    good state even without a mask — stationary prob exactly 1."""
    key = jax.random.PRNGKey(9)
    p_gg = jnp.concatenate([jnp.full((4,), 0.5), jnp.ones((3,))])
    p_bb = jnp.concatenate([jnp.full((4,), 0.5), jnp.zeros((3,))])
    traj = markov.sample_trajectory(key, p_gg, p_bb, 100)
    assert bool(jnp.all(traj[:, 4:] == 1))

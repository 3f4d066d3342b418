"""The allocator DP's wide body: the same prefix tails as the reference DP,
with the worker loop rolled and the count axis capped and tiled; the
dispatcher's cut between it and the unrolled bodies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lea
from repro.kernels.poisson_binomial import (WIDE_N, success_tails,
                                            success_tails_pallas_w,
                                            success_tails_ref,
                                            success_tails_wide)


def _rows(rng, b, n):
    """Descending probabilities and per-row thresholds: eq. 7/8 at random
    (K*, ell_g, ell_b, pool size), then a row with no feasible prefix, one
    with every w <= 0 and one of arbitrary integers."""
    p = np.sort(rng.uniform(0.0, 1.0, size=(b, n)), axis=-1)[:, ::-1]
    ell_g = rng.integers(2, 11, size=b)
    ell_b = np.maximum(1, ell_g - rng.integers(1, 9, size=b))
    w = np.array(lea.prefix_thresholds_traced(
        rng.integers(1, 10 * n, size=b), ell_g, ell_b,
        rng.integers(1, n + 1, size=b), n))
    w[0] = n + 1
    w[1] = rng.integers(-5, 1, size=n)
    w[2] = rng.integers(-3, n + 3, size=n)
    return jnp.asarray(p, jnp.float32), jnp.asarray(w, jnp.int32)


@pytest.mark.parametrize("n", [128, 300, 1000])
def test_wide_body_matches_the_reference_dp(n):
    p, w = _rows(np.random.default_rng(n), 19, n)
    want = np.asarray(success_tails_ref(p, w))
    for block_b in (8, 32):                  # several batch tiles, and one
        got = np.asarray(success_tails_wide(p, w, block_b=block_b, interpret=True))
        # capping and tiling re-associate the tail sums: float32 round-off
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.all(got[0] == 0.0)             # no feasible prefix
    np.testing.assert_allclose(got[1], 1.0, atol=1e-6)   # every w <= 0


def test_wide_body_under_vmap_as_the_engine_calls_it():
    rng = np.random.default_rng(5)
    p, w = zip(*(_rows(rng, 11, 200) for _ in range(3)))
    p, w = jnp.stack(p), jnp.stack(w)
    got = jax.vmap(lambda x, y: success_tails_wide(x, y, block_b=8,
                                                   interpret=True))(p, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(success_tails_ref(p, w)),
                               rtol=1e-5, atol=1e-6)


def _lowered(n, w_static):
    p = jnp.zeros((16, n), jnp.float32)
    w = (tuple(range(1, n + 1)) if w_static
         else jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.int32), (16, n)))
    fn = lambda x: success_tails(x, w, impl="pallas", interpret=True)
    return jax.jit(fn).lower(p).as_text(debug_info=True)


@pytest.mark.parametrize("w_static", [False, True], ids=["traced_w", "static_w"])
def test_the_dispatcher_cuts_at_wide_n(w_static):
    assert "repro.dp_wide" not in _lowered(15, w_static)
    assert "repro.dp_wide" not in _lowered(WIDE_N - 1, w_static)
    assert "repro.dp_wide" in _lowered(WIDE_N, w_static)


def test_fifteen_workers_keep_the_unrolled_body_bit_for_bit():
    p, w = _rows(np.random.default_rng(15), 40, 15)
    got = success_tails(p, w, impl="pallas", interpret=True)
    want = success_tails_pallas_w(p, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

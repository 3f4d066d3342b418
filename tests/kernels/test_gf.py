"""repro.kernels.gf: exact Mersenne-31 arithmetic, kernel-vs-ref bit-equality.

Residues are exact, so every assertion here is array_equal — never allclose.
The numpy int64 path is the independent oracle for the primitives; the lax
reference is the oracle for the Pallas kernel (interpret mode on CPU) and
the limb-decomposed dot path.
"""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import gf
from repro.kernels.gf import kernel as gf_kernel
from repro.kernels.gf import ref as gf_ref
from repro.kernels.gf.kernel import matmul_gf_pallas

P = gf.FIELD_P

# always-on boundary residues: additive/multiplicative identities and the
# extremes where limb splits and folds are most likely to break
_BOUNDARY = np.array([0, 1, 2, P - 1, P - 2, 2**30, 2**16, 2**15, 0xFFFF],
                     dtype=np.int64)


def _rand_residues(rng, shape):
    vals = rng.integers(0, P, size=shape).astype(np.int64)
    flat = vals.reshape(-1)
    take = min(flat.shape[0], _BOUNDARY.shape[0])
    flat[:take] = _BOUNDARY[:take]          # splice boundary values in
    return flat.reshape(shape)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 257))
def test_mul_gf_matches_numpy_int64(seed, n):
    rng = np.random.default_rng(seed)
    a = _rand_residues(rng, (n,))
    b = _rand_residues(rng, (n,))[::-1].copy()
    got = np.asarray(
        gf.mul_gf(gf.to_gf(a.astype(np.int32)), gf.to_gf(b.astype(np.int32))),
        np.int64,
    )
    np.testing.assert_array_equal(got, (a * b) % P)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_add_sub_inv_gf_match_numpy_int64(seed):
    rng = np.random.default_rng(seed)
    a = _rand_residues(rng, (64,))
    b = _rand_residues(rng, (64,))[::-1].copy()
    ga, gb = gf.to_gf(a.astype(np.int32)), gf.to_gf(b.astype(np.int32))
    np.testing.assert_array_equal(np.asarray(gf.add_gf(ga, gb), np.int64), (a + b) % P)
    np.testing.assert_array_equal(np.asarray(gf.sub_gf(ga, gb), np.int64), (a - b) % P)
    nz = a[a != 0]
    inv = np.asarray(gf.inv_gf(gf.to_gf(nz.astype(np.int32))), np.int64)
    np.testing.assert_array_equal((nz * inv) % P, 1)
    # inv of 0 is defined as 0 (never used by callers, but must not explode)
    assert int(gf.inv_gf(gf.to_gf(np.int32(0)))) == 0


def test_rot_gf_is_power_of_two_multiplication():
    rng = np.random.default_rng(0)
    v = _rand_residues(rng, (128,))
    gv = gf.to_gf(v.astype(np.int32))
    for s in (0, 1, 7, 8, 16, 24, 30, 31, 40, 48, 62):
        got = np.asarray(gf_ref.rot_gf(gv, s), np.int64)
        np.testing.assert_array_equal(got, (v * pow(2, s, P)) % P)


def test_to_gf_reduces_signed_and_unsigned():
    x = np.array([-1, -P, P - 1, 5], dtype=np.int32)
    np.testing.assert_array_equal(
        np.asarray(gf.to_gf(x), np.int64), np.array([P - 1, 0, P - 1, 5]))
    u = np.array([P, P + 1, 2**32 - 1], dtype=np.uint32)
    np.testing.assert_array_equal(
        np.asarray(gf.to_gf(u), np.int64), np.array([0, 1, 1]))


def _np_matmul_gf(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out = np.zeros((a.shape[0], b.shape[1]), np.int64)
    for k in range(a.shape[1]):
        out = (out + a[:, k : k + 1] * b[k : k + 1, :]) % P
    return out


@settings(max_examples=10, deadline=None)
@given(
    m=st.integers(1, 40),
    c=st.integers(1, 300),     # crosses the dot path's 256-wide K-chunk
    n=st.integers(1, 140),
    seed=st.integers(0, 2**31 - 1),
)
def test_matmul_gf_all_impls_bit_equal_numpy(m, c, n, seed):
    rng = np.random.default_rng(seed)
    a = _rand_residues(rng, (m, c))
    b = _rand_residues(rng, (c, n))
    want = _np_matmul_gf(a, b)
    for impl in ("ref", "dot", "pallas"):
        got = np.asarray(
            gf.matmul_gf(a.astype(np.int32), b.astype(np.int32), impl=impl),
            np.int64,
        )
        np.testing.assert_array_equal(got, want, err_msg=f"impl={impl}")


def test_pallas_kernel_multi_tile_grid_accumulation():
    """Small explicit blocks force a (2+, 2+, 2+) grid: the K-innermost
    revisiting accumulation and edge-tile zero padding must stay exact."""
    rng = np.random.default_rng(3)
    a = _rand_residues(rng, (19, 37))
    b = _rand_residues(rng, (37, 150))
    want = _np_matmul_gf(a, b)
    got = matmul_gf_pallas(
        gf.to_gf(a.astype(np.int32)), gf.to_gf(b.astype(np.int32)),
        block_m=8, block_n=128, block_k=16, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


def test_matmul_gf_dot_pins_highest_precision():
    """GPU guard CPU CI can run: every dot_general in the limb GEMM path
    must trace with Precision.HIGHEST, else Ampere+ TF32 (10-bit mantissa)
    silently rounds the limb products and breaks bit-exactness."""
    import jax

    a = jnp.zeros((4, 300), jnp.uint32)       # crosses the 256-wide K-chunk
    b = jnp.zeros((300, 5), jnp.uint32)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    yield from walk(v.jaxpr)

    from repro.kernels.gf.ops import matmul_gf_dot

    dots = list(walk(jax.make_jaxpr(matmul_gf_dot)(a, b).jaxpr))
    assert dots, "expected at least one dot_general in matmul_gf_dot"
    hi = jax.lax.Precision.HIGHEST
    for eqn in dots:
        prec = eqn.params["precision"]
        assert prec in (hi, (hi, hi)), f"dot_general precision {prec!r}"


def test_pallas_rejects_non_lane_blocks_outside_interpret():
    """The Mosaic lane-dim contract (bk, bn multiples of 128) is enforced,
    not just documented: small block_k/block_n only fly in interpret mode."""
    a = gf.to_gf(np.zeros((8, 256), np.int32))
    b = gf.to_gf(np.zeros((256, 256), np.int32))
    for kwargs in ({"block_k": 64}, {"block_n": 64}):
        try:
            matmul_gf_pallas(a, b, interpret=False, **kwargs)
        except ValueError as e:
            assert "128" in str(e)
        else:  # pragma: no cover
            raise AssertionError("expected ValueError")
        # the same blocks are honoured under interpret=True
        out = matmul_gf_pallas(a, b, interpret=True, **kwargs)
        assert out.shape == (8, 256)


def test_matmul_gf_rejects_bad_shapes_and_impl():
    a = np.zeros((2, 3), np.int32)
    b = np.zeros((4, 2), np.int32)
    for fn in (lambda: gf.matmul_gf(a, b), lambda: gf.matmul_gf(a[0], b)):
        try:
            fn()
        except ValueError:
            pass
        else:  # pragma: no cover
            raise AssertionError("expected ValueError")
    try:
        gf.matmul_gf(np.zeros((2, 4), np.int32), b, impl="nope")
    except ValueError as e:
        assert "nope" in str(e)
    else:  # pragma: no cover
        raise AssertionError("expected ValueError")


@settings(max_examples=10, deadline=None)
@given(
    e=st.integers(1, 12),
    j=st.integers(2, 10),
    b=st.integers(1, 4),
    seed=st.integers(0, 2**31 - 1),
)
def test_lagrange_basis_gf_matches_numpy_oracle(e, j, b, seed):
    """Single + batched basis construction == the host `_lagrange_basis_modp`."""
    from repro.core.lagrange import _lagrange_basis_modp

    rng = np.random.default_rng(seed)
    ev = rng.choice(4 * (e + j), size=e, replace=False).astype(np.int64)
    # distinct nodes, disjoint from eval points
    pool = np.setdiff1d(np.arange(4 * (e + j), 8 * (e + j)), ev)
    nodes = np.stack([rng.choice(pool, size=j, replace=False) for _ in range(b)])
    got = np.asarray(
        gf.lagrange_basis_gf(ev.astype(np.int32), nodes.astype(np.int32)),
        np.int64,
    )
    assert got.shape == (b, e, j)
    for i in range(b):
        np.testing.assert_array_equal(got[i], _lagrange_basis_modp(ev, nodes[i]))
    # unbatched call gives the same matrix
    got0 = np.asarray(
        gf.lagrange_basis_gf(ev.astype(np.int32), nodes[0].astype(np.int32)),
        np.int64,
    )
    np.testing.assert_array_equal(got0, got[0])


def test_basis_interpolates_polynomials_exactly():
    """The basis actually interpolates: for data = poly(nodes), basis @ data
    == poly(eval) — exactness of the whole encode pipeline in one identity."""
    rng = np.random.default_rng(7)
    nodes = np.arange(20, 29, dtype=np.int64)        # J = 9 -> deg <= 8
    ev = np.arange(0, 11, dtype=np.int64)
    coeffs = rng.integers(0, P, size=9).astype(np.int64)

    def poly(x):
        acc = np.zeros_like(x)
        for c in reversed(coeffs):
            acc = (acc * x + c) % P
        return acc

    basis = gf.lagrange_basis_gf(ev.astype(np.int32), nodes.astype(np.int32))
    got = np.asarray(
        gf.matmul_gf(gf.from_gf(jnp.asarray(basis)),
                     poly(nodes).reshape(-1, 1).astype(np.int32)),
        np.int64,
    )[:, 0]
    np.testing.assert_array_equal(got, poly(ev))


# ---------------------------------------------------------------------------
# bmm_gf: batched exact matmul (the deg-2 gradient's worker-side op)
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(b=st.integers(1, 6), m=st.integers(1, 9), c=st.integers(1, 17),
       n=st.integers(1, 7), seed=st.integers(0, 2**31 - 1))
def test_bmm_gf_all_impls_bit_equal_numpy(b, m, c, n, seed):
    rng = np.random.default_rng(seed)
    a = _rand_residues(rng, (b, m, c))
    x = _rand_residues(rng, (b, c, n))
    want = np.stack([
        (a[i].astype(object) @ x[i].astype(object) % P).astype(np.int64)
        for i in range(b)
    ])
    for impl in ("dot", "ref"):
        got = np.asarray(
            gf.bmm_gf(jnp.asarray(a, jnp.int32), jnp.asarray(x, jnp.int32),
                      impl=impl),
            np.int64,
        )
        np.testing.assert_array_equal(got, want, err_msg=impl)


def test_bmm_gf_two_dim_falls_through_and_multi_lead_axes():
    rng = np.random.default_rng(0)
    a = _rand_residues(rng, (4, 5))
    x = _rand_residues(rng, (5, 3))
    np.testing.assert_array_equal(
        np.asarray(gf.bmm_gf(jnp.asarray(a, jnp.int32), jnp.asarray(x, jnp.int32))),
        np.asarray(gf.matmul_gf(jnp.asarray(a, jnp.int32), jnp.asarray(x, jnp.int32))),
    )
    a4 = _rand_residues(rng, (2, 3, 4, 5))
    x4 = _rand_residues(rng, (2, 3, 5, 2))
    got = np.asarray(gf.bmm_gf(jnp.asarray(a4, jnp.int32), jnp.asarray(x4, jnp.int32)), np.int64)
    assert got.shape == (2, 3, 4, 2)
    for i in range(2):
        for j in range(3):
            want = (a4[i, j].astype(object) @ x4[i, j].astype(object) % P).astype(np.int64)
            np.testing.assert_array_equal(got[i, j], want)


def test_bmm_gf_rejects_mismatched_shapes():
    import pytest

    a = jnp.zeros((2, 3, 4), jnp.int32)
    with pytest.raises(ValueError):
        gf.bmm_gf(a, jnp.zeros((3, 4, 2), jnp.int32))     # lead mismatch
    with pytest.raises(ValueError):
        gf.bmm_gf(a, jnp.zeros((2, 5, 2), jnp.int32))     # contraction mismatch
    with pytest.raises(ValueError):
        gf.bmm_gf(a, jnp.zeros((4, 2), jnp.int32))        # rank mismatch


def test_bmm_gf_pallas_interpret_bit_equal_dot():
    """The vmapped-pallas_call branch (TPU default) in interpret mode: same
    residues as the dot/ref paths, including multi-tile shapes."""
    rng = np.random.default_rng(7)
    for b, m, c, n in ((3, 4, 9, 5), (2, 17, 33, 6)):
        a = _rand_residues(rng, (b, m, c))
        x = _rand_residues(rng, (b, c, n))
        pal = np.asarray(gf.bmm_gf(jnp.asarray(a, jnp.int32),
                                   jnp.asarray(x, jnp.int32),
                                   impl="pallas", interpret=True))
        dot = np.asarray(gf.bmm_gf(jnp.asarray(a, jnp.int32),
                                   jnp.asarray(x, jnp.int32), impl="dot"))
        np.testing.assert_array_equal(pal, dot)


# ---------------------------------------------------------------------------
# the narrow body: lane contraction for n < 128
# ---------------------------------------------------------------------------

# m not a multiple of 8 and c not of 128; c past one K block of the narrow
# tiles (several K visits, A's last column block ragged)
_MANY_K = gf_kernel._NARROW_BK + 300


@pytest.mark.parametrize("fill", ["random", "p_minus_1"])
@pytest.mark.parametrize("m, c", [(75, 300), (37, _MANY_K), (1, 5)])
@pytest.mark.parametrize("n", [1, 3, 25])
def test_narrow_body_bit_equal_numpy(n, m, c, fill):
    """Every residue at p - 1 stresses the fold and the exact lane reduction
    (128 lanes of p - 1 overflow uint32 if summed whole)."""
    rng = np.random.default_rng(m * 1000 + c + n)
    if fill == "random":
        a, b = _rand_residues(rng, (m, c)), _rand_residues(rng, (c, n))
    else:
        a, b = np.full((m, c), P - 1), np.full((c, n), P - 1)
    got = matmul_gf_pallas(jnp.asarray(a, jnp.uint32), jnp.asarray(b, jnp.uint32),
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.int64), _np_matmul_gf(a, b))


@pytest.mark.parametrize("b, m, c, n", [(3, 75, 300, 1), (2, 40, 25, 3),
                                        (2, 9, _MANY_K, 25)])
def test_narrow_body_through_bmm_gf(b, m, c, n):
    """The vmapped pallas_call lowers the narrow body too."""
    rng = np.random.default_rng(b * m + c)
    a = _rand_residues(rng, (b, m, c))
    x = _rand_residues(rng, (b, c, n))
    got = np.asarray(gf.bmm_gf(jnp.asarray(a, jnp.int32), jnp.asarray(x, jnp.int32),
                               impl="pallas", interpret=True), np.int64)
    for i in range(b):
        np.testing.assert_array_equal(got[i], _np_matmul_gf(a[i], x[i]))


def test_mul_by_zero_is_zero_for_any_uint32():
    """The narrow body reads A's ragged column edge unpadded: on the chip the
    lanes past it hold whatever the buffer held, and meet B's zero padding."""
    x = jnp.asarray(np.array([0, 1, P - 1, P, P + 1, 2**31, 2**32 - 1,
                              0xDEADBEEF], np.uint32))
    np.testing.assert_array_equal(np.asarray(gf.mul_gf(x, jnp.uint32(0))), 0)


@pytest.mark.parametrize("n, narrow", [(1, True), (25, True), (127, True),
                                       (128, False), (200, False)])
def test_width_cut_selects_the_body(n, narrow):
    """n < 128 runs the narrow body, traced under ``repro.gf_matvec``;
    n >= 128 runs the wide body, without it."""
    a = jnp.zeros((16, 40), jnp.uint32)
    b = jnp.zeros((40, n), jnp.uint32)
    hlo = matmul_gf_pallas.lower(a, b, interpret=True).as_text(debug_info=True)
    assert ("repro.gf_matvec" in hlo) == narrow

"""Lagrange Coded Computing (LCC) — the data-encoding layer of LEA.

Implements the coding scheme of Sec. 3.1 of the paper (following Yu et al. 2019):

* ``lagrange`` branch (``nr >= k*deg_f - 1``): the dataset ``X_1..X_k`` is
  interpolated by a degree-(k-1) polynomial ``u`` with ``u(beta_j) = X_j``; the
  encoded chunks are ``X~_v = u(alpha_v)``.  Because ``f`` is a polynomial of
  total degree ``deg_f``, ``h(z) = f(u(z))`` has degree ``(k-1)*deg_f`` and the
  master can interpolate ``h`` from any ``K* = (k-1)*deg_f + 1`` on-time worker
  results, then read off ``f(X_j) = h(beta_j)``.

* ``repetition`` branch (``nr < k*deg_f - 1``): every chunk is replicated
  ``floor(nr/k)`` or ``ceil(nr/k)`` times; ``K* = nr - floor(nr/k) + 1`` results
  always contain at least one copy of each chunk.  (This branch is valid for
  *arbitrary*, non-polynomial ``f`` — it is what the LM-training coded-DP mode
  uses; see DESIGN.md §3/§6.)

Two numeric paths:
  * float32/float64 with Chebyshev interpolation nodes (conditioning-bounded)
    — used by the ML-facing ops and the Pallas kernels;
  * exact arithmetic over the prime field GF(p), p = 2^31 - 1 — mirroring the
    finite field F of the paper.  The numpy ``*_modp`` functions are the host
    oracle; the ``*_modp_device`` functions build the same matrices on device
    through :mod:`repro.kernels.gf` (Mersenne-31 matmul + batched Lagrange
    basis), bit-identically — residues are exact, so host and device agree
    to the last bit.  ``coded_ops.coded_matmul_exact`` runs the whole
    encode -> worker matmul -> erasure-aware decode round on device.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# Mersenne prime 2^31 - 1.  Products of two residues fit in int64 and sums of
# up to ~4e9 residues fit in int64, so exact mod-p linear algebra is safe.
FIELD_P = (1 << 31) - 1


@dataclasses.dataclass(frozen=True)
class CodeSpec:
    """Static description of one coded-computing instance."""

    n: int        # number of workers
    r: int        # encoded chunks stored per worker
    k: int        # number of data chunks
    deg_f: int    # total degree of the polynomial f evaluated each round

    @property
    def nr(self) -> int:
        return self.n * self.r

    @property
    def mode(self) -> str:
        return "lagrange" if self.nr >= self.k * self.deg_f - 1 else "repetition"

    @property
    def recovery_threshold(self) -> int:
        """K*, eq. (15)/(16) of the paper."""
        if self.mode == "lagrange":
            return (self.k - 1) * self.deg_f + 1
        return self.nr - self.nr // self.k + 1

    def chunk_owner(self, v: int) -> int:
        """Worker that stores encoded chunk v (worker i holds [i*r, (i+1)*r))."""
        return v // self.r

    def worker_chunks(self, i: int) -> range:
        return range(i * self.r, (i + 1) * self.r)


def recovery_threshold(n: int, r: int, k: int, deg_f: int) -> int:
    return CodeSpec(n, r, k, deg_f).recovery_threshold


# ---------------------------------------------------------------------------
# Interpolation nodes (float path)
# ---------------------------------------------------------------------------

def beta_points_np(k: int) -> np.ndarray:
    """Chebyshev nodes of the first kind on [-1, 1] — well-conditioned betas."""
    j = np.arange(k)
    return np.cos(np.pi * (2 * j + 1) / (2 * k))


def alpha_points_np(nr: int) -> np.ndarray:
    """nr mutually-distinct evaluation points (Chebyshev grid of size nr)."""
    v = np.arange(nr)
    return np.cos(np.pi * (2 * v + 1) / (2 * nr))


def beta_points(k: int, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.asarray(beta_points_np(k).astype(np.float32), dtype=dtype)


def alpha_points(nr: int, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.asarray(alpha_points_np(nr).astype(np.float32), dtype=dtype)


def _lagrange_basis(eval_pts: np.ndarray, nodes: np.ndarray) -> np.ndarray:
    """Matrix M[e, j] = prod_{l != j} (eval_e - nodes_l) / (nodes_j - nodes_l).

    Computed in float64 regardless of the target dtype (the matrices are tiny —
    (nr, k) / (k, K*) — the data they multiply is what is large).
    """
    eval_pts = np.asarray(eval_pts, dtype=np.float64)
    nodes = np.asarray(nodes, dtype=np.float64)
    e = eval_pts[:, None, None]                    # (E,1,1)
    nj = nodes[None, :, None]                      # (1,J,1)
    nl = nodes[None, None, :]                      # (1,1,J)
    num = e - nl                                   # (E,J,J) broadcast of (e - n_l)
    den = nj - nl                                  # (1,J,J)
    J = nodes.shape[0]
    eye = np.eye(J, dtype=bool)[None]
    num = np.where(eye, 1.0, np.broadcast_to(num, (eval_pts.shape[0], J, J)))
    den = np.where(eye, 1.0, np.broadcast_to(den, (1, J, J)))
    return np.prod(num / den, axis=-1)             # (E, J)


def chunk_alpha_indices(spec: CodeSpec) -> np.ndarray:
    """Chunk v -> index into the alpha grid, STRIDED across workers.

    Worker i stores chunks [i*r, (i+1)*r) and always evaluates a *prefix* of
    them (two-level loads, Lemma 4.4).  Mapping worker i's j-th chunk to grid
    position j*n + i spreads any union of per-worker prefixes uniformly over
    the Chebyshev grid, keeping the real-valued decode well-conditioned.
    (Irrelevant over the paper's finite field F; essential for the float
    adaptation — DESIGN §9.)
    """
    v = np.arange(spec.nr)
    worker, j = v // spec.r, v % spec.r
    return j * spec.n + worker


def generator_matrix(spec: CodeSpec, dtype=jnp.float32) -> jnp.ndarray:
    """(nr, k) encoding matrix G with X~ = G @ X (rows = encoded chunks).

    Lagrange branch: G[v, j] = Lagrange basis at alpha_{idx(v)}.
    Repetition branch: 0/1 replication matrix, chunk v holds X_{v mod k}.
    """
    if spec.mode == "lagrange":
        alphas = alpha_points_np(spec.nr)[chunk_alpha_indices(spec)]
        g = _lagrange_basis(alphas, beta_points_np(spec.k))
        return jnp.asarray(g.astype(np.float32), dtype=dtype)
    g = np.zeros((spec.nr, spec.k))
    g[np.arange(spec.nr), np.arange(spec.nr) % spec.k] = 1.0
    return jnp.asarray(g, dtype=dtype)


# Precision of the float coded round's encode, worker products and decode.
# The decode multiplies the error of every encoded result by the condition
# number of the received generator rows, and a TPU's default float32 matmul
# is one bfloat16 pass, 2^15 float32 ulps.  HIGH (three bfloat16 passes)
# meets chip_smoke's bound of 1000 ulps x cond at the EC2 size; on a CPU
# every float32 matmul is full precision anyway.
FLOAT_PRECISION = jax.lax.Precision.HIGH


@partial(jax.jit, static_argnames=())
def encode(generator: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """Encode stacked data chunks: (k, *dims) -> (nr, *dims)."""
    return jnp.tensordot(generator, data, axes=1, precision=FLOAT_PRECISION)


def decode_matrix(
    spec: CodeSpec, received: Sequence[int] | np.ndarray, dtype=jnp.float32
) -> jnp.ndarray:
    """(k, K*) decode matrix D for a given set of received chunk indices.

    Lagrange branch: interpolate h(z)=f(u(z)) (degree (k-1)*deg_f) through the
    received alphas and evaluate at the betas:  f(X) = D @ f(X~)[received].
    Requires len(received) == K* and h-degree + 1 <= K*.

    Repetition branch: 0/1 selection of the first on-time copy of each chunk.
    """
    received = np.asarray(received, dtype=np.int64)
    kstar = spec.recovery_threshold
    if received.shape[0] != kstar:
        raise ValueError(f"need exactly K*={kstar} received indices, got {received.shape[0]}")
    if len(np.unique(received)) != kstar:
        raise ValueError("received chunk indices must be distinct")
    if spec.mode == "lagrange":
        alphas = alpha_points_np(spec.nr)[chunk_alpha_indices(spec)[received]]
        betas = beta_points_np(spec.k)
        return jnp.asarray(_lagrange_basis(betas, alphas).astype(np.float32), dtype=dtype)
    d = np.zeros((spec.k, kstar))
    src = received % spec.k
    for j in range(spec.k):
        hits = np.nonzero(src == j)[0]
        if hits.size == 0:
            raise ValueError(
                f"received set misses every copy of chunk {j} — violates K* guarantee"
            )
        d[j, hits[0]] = 1.0
    return jnp.asarray(d, dtype=dtype)


@partial(jax.jit, static_argnames=())
def decode(decode_mat: jnp.ndarray, results: jnp.ndarray) -> jnp.ndarray:
    """Decode: (k, K*) @ (K*, *dims) -> (k, *dims)."""
    return jnp.tensordot(decode_mat, results, axes=1)


# ---------------------------------------------------------------------------
# Device-resident decode path (traced received set — no host round-trip)
# ---------------------------------------------------------------------------

def _lagrange_basis_jax(eval_pts: jnp.ndarray, nodes: jnp.ndarray) -> jnp.ndarray:
    """Traced counterpart of :func:`_lagrange_basis`: M[e, j] =
    prod_{l != j} (eval_e - nodes_l) / (nodes_j - nodes_l).

    ``nodes`` may be a traced gather of alpha points (the received set), so
    this runs fully on device in float32 (the host path uses float64; the
    Chebyshev grids keep the products conditioned — DESIGN §9).
    """
    eval_pts = jnp.asarray(eval_pts, jnp.float32)
    nodes = jnp.asarray(nodes, jnp.float32)
    j = nodes.shape[0]
    e = eval_pts[:, None, None]                    # (E,1,1)
    nj = nodes[None, :, None]                      # (1,J,1)
    nl = nodes[None, None, :]                      # (1,1,J)
    eye = jnp.eye(j, dtype=bool)[None]
    num = jnp.where(eye, 1.0, e - nl)              # (E,J,J)
    den = jnp.where(eye, 1.0, nj - nl)
    return jnp.prod(num / den, axis=-1)            # (E, J)


def decode_matrix_jax(spec: CodeSpec, received: jnp.ndarray) -> jnp.ndarray:
    """(k, K*) decode matrix from a TRACED (K*,) received-index vector.

    Fully jittable (``spec`` is static): a static-shape gather picks the
    received alpha points and the Lagrange basis is evaluated on device —
    no ``np.nonzero`` / host construction per round.  Validity (distinct
    indices, repetition coverage) is the caller's contract, exactly the K*
    guarantee of Defn. 4.1; rows that would be unrecoverable come back as
    zeros rather than raising (jit cannot raise data-dependently).
    """
    received = jnp.asarray(received, jnp.int32)
    kstar = spec.recovery_threshold
    assert received.shape == (kstar,), (received.shape, kstar)
    if spec.mode == "lagrange":
        alpha_grid = jnp.asarray(
            alpha_points_np(spec.nr)[chunk_alpha_indices(spec)], jnp.float32
        )
        alphas = jnp.take(alpha_grid, received)    # (K*,) traced gather
        betas = jnp.asarray(beta_points_np(spec.k), jnp.float32)
        return _lagrange_basis_jax(betas, alphas)
    # repetition: select the first received copy of each chunk j (j = v mod k)
    src = received % spec.k                        # (K*,)
    pos = jnp.arange(kstar)
    hit = src[None, :] == jnp.arange(spec.k)[:, None]          # (k, K*)
    first = jnp.min(jnp.where(hit, pos[None, :], kstar), axis=1)  # (k,)
    return (pos[None, :] == first[:, None]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Exact GF(p) path (mirrors the paper's finite field F; used by property tests)
# ---------------------------------------------------------------------------

def _mod_inv(a: np.ndarray, p: int = FIELD_P) -> np.ndarray:
    """Vectorized modular inverse via Fermat: a^(p-2) mod p."""
    a = np.asarray(a, dtype=np.int64) % p
    result = np.ones_like(a)
    base = a.copy()
    e = p - 2
    while e:
        if e & 1:
            result = (result * base) % p
        base = (base * base) % p
        e >>= 1
    return result


def _lagrange_basis_modp(eval_pts: np.ndarray, nodes: np.ndarray, p: int = FIELD_P) -> np.ndarray:
    eval_pts = np.asarray(eval_pts, dtype=np.int64) % p
    nodes = np.asarray(nodes, dtype=np.int64) % p
    E, J = eval_pts.shape[0], nodes.shape[0]
    out = np.ones((E, J), dtype=np.int64)
    for l in range(J):
        num = (eval_pts[:, None] - nodes[l]) % p          # (E,1)
        den = (nodes[None, :] - nodes[l]) % p             # (1,J)
        num = np.broadcast_to(num, (E, J)).copy()
        den = np.broadcast_to(den, (E, J)).copy()
        skip = np.zeros((E, J), dtype=bool)
        skip[:, l] = True
        num[skip] = 1
        den[skip] = 1
        out = (out * ((num * _mod_inv(den, p)) % p)) % p
    return out


def generator_matrix_modp(spec: CodeSpec, p: int = FIELD_P) -> np.ndarray:
    """Exact (nr, k) generator over GF(p); alphas/betas = 0..nr-1 / nr..nr+k-1."""
    if spec.mode != "lagrange":
        return np.asarray(generator_matrix(spec, jnp.float64), dtype=np.int64)
    alphas = np.arange(spec.nr, dtype=np.int64)[chunk_alpha_indices(spec)]
    betas = np.arange(spec.nr, spec.nr + spec.k, dtype=np.int64)
    return _lagrange_basis_modp(alphas, betas, p)


def decode_matrix_modp(
    spec: CodeSpec, received: Sequence[int] | np.ndarray, p: int = FIELD_P
) -> np.ndarray:
    received = np.asarray(received, dtype=np.int64)
    if spec.mode != "lagrange":
        return np.asarray(decode_matrix(spec, received, jnp.float64), dtype=np.int64)
    alphas = np.arange(spec.nr, dtype=np.int64)[chunk_alpha_indices(spec)[received]]
    betas = np.arange(spec.nr, spec.nr + spec.k, dtype=np.int64)
    return _lagrange_basis_modp(betas, alphas, p)


def matmul_modp(a: np.ndarray, b: np.ndarray, p: int = FIELD_P) -> np.ndarray:
    """Exact (m, c) @ (c, *dims) mod p.  Products of residues stay < 2^63."""
    a = np.asarray(a, dtype=np.int64) % p
    b = np.asarray(b, dtype=np.int64) % p
    trailing = b.shape[1:]
    b2 = b.reshape(b.shape[0], -1)
    # per-term product mod p (each < p), then sum over the contraction axis
    # (< 2^32 terms each < 2^31 fits int64), then one final mod.
    terms = (a[:, :, None] * b2[None, :, :]) % p      # (m, c, flat)
    out = np.sum(terms, axis=1) % p
    return out.reshape((a.shape[0],) + trailing)


# ---------------------------------------------------------------------------
# Device-resident exact GF(p) path (repro.kernels.gf) — no host round-trip
# ---------------------------------------------------------------------------

def _gf():
    # local import: repro.kernels.gf is a leaf package, but keeping the core
    # import graph lazy mirrors the policies/throughput convention
    from repro.kernels import gf as gf_mod

    return gf_mod


def _alpha_grid_modp(spec: CodeSpec) -> np.ndarray:
    """The strided integer alpha grid of the exact path: chunk v -> idx(v)."""
    return np.arange(spec.nr, dtype=np.int32)[chunk_alpha_indices(spec)]


def generator_matrix_modp_device(spec: CodeSpec) -> jnp.ndarray:
    """Device-built exact (nr, k) generator over GF(p) — int32 residues.

    Bit-identical (as integers) to the numpy :func:`generator_matrix_modp`:
    same integer alphas/betas (0..nr-1 strided / nr..nr+k-1), same field —
    residues are exact, so the only difference is where the matrix lives.
    """
    gf = _gf()
    if spec.mode != "lagrange":
        return jnp.asarray(generator_matrix_modp(spec), jnp.int32)
    alphas = jnp.asarray(_alpha_grid_modp(spec))
    betas = jnp.arange(spec.nr, spec.nr + spec.k, dtype=jnp.int32)
    return gf.from_gf(gf.lagrange_basis_gf(alphas, betas))


def decode_matrix_modp_device(spec: CodeSpec, received: jnp.ndarray) -> jnp.ndarray:
    """Exact (..., k, K*) decode matrices from TRACED (..., K*) received rows.

    The erasure-pattern-aware device decode: a static-shape gather picks the
    surviving alpha points and the GF(p) Lagrange basis is inverted on
    device (Fermat), so erasure patterns straight from the engine's Markov
    trajectories decode with no host sync.  Leading axes batch over
    patterns (one call builds a whole trajectory's decode matrices).
    Validity (distinct indices, repetition coverage) is the caller's
    contract, exactly as for :func:`decode_matrix_jax`.
    """
    gf = _gf()
    received = jnp.asarray(received, jnp.int32)
    kstar = spec.recovery_threshold
    assert received.shape[-1] == kstar, (received.shape, kstar)
    if spec.mode == "lagrange":
        alpha_grid = jnp.asarray(_alpha_grid_modp(spec))
        alphas = jnp.take(alpha_grid, received)            # (..., K*) gather
        betas = jnp.arange(spec.nr, spec.nr + spec.k, dtype=jnp.int32)
        return gf.from_gf(gf.lagrange_basis_gf(betas, alphas))
    # repetition: 0/1 selection of the first received copy of each chunk
    src = received % spec.k                                # (..., K*)
    pos = jnp.arange(kstar)
    hit = src[..., None, :] == jnp.arange(spec.k)[:, None]           # (..., k, K*)
    first = jnp.min(jnp.where(hit, pos, kstar), axis=-1)             # (..., k)
    return (pos == first[..., None]).astype(jnp.int32)

"""Dispatcher for device-resident exact GF(p) linear algebra, p = 2^31 - 1.

Two entry points the coding layer uses:

  * :func:`matmul_gf`          — exact (m, c) @ (c, n) mod p
  * :func:`lagrange_basis_gf`  — batched Lagrange basis matrices over GF(p)
                                 (generator / erasure-pattern decode builder)

``matmul_gf`` impls:

  * ``impl="pallas"`` — the blocked VMEM kernels (TPU; ``interpret=True``
    on CPU for testing): a lane contraction along A's rows for a narrow
    right-hand side (n < 128: a round's products, the decode), outer
    products for a wide one (the encode); see :mod:`.kernel`.
  * ``impl="dot"``    — the XLA fast path used on CPU/GPU: residues are
    decomposed into four 8-bit limbs and contracted with SIXTEEN float32
    GEMMs per K-chunk of 256 (256 * 255^2 < 2^24, so every float32 partial
    sum is an exactly-representable integer regardless of reduction order),
    then the limb planes are recombined with Mersenne rotations
    (2^31 === 1).  This rides the platform's optimised sgemm instead of an
    elementwise modular loop — where the >= 5x-over-numpy speedup in
    BENCH_gf.json comes from.  The GEMMs are pinned to
    ``Precision.HIGHEST``: JAX's default precision allows TF32 on Ampere+
    GPUs, whose 10-bit mantissa would round the limb products.
  * ``impl="ref"``    — the lax fori_loop fold path, the kernel's
    interpret-mode oracle.
  * ``impl=None``     — pallas on TPU, dot elsewhere.

Residues are exact, so ALL impls return bit-identical uint32 arrays — the
tests assert pairwise equality (not allclose) across every path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import default_interpret, resolve_impl

from .kernel import matmul_gf_pallas
from .ref import (FIELD_P, add_gf, lagrange_basis_gf_ref, matmul_gf_ref,
                  rot_gf, to_gf)

# K-chunk bound for the float32 limb dot: 256 terms of (2^8-1)^2 products
# sum to 16_646_400 < 2^24, the largest integer float32 represents exactly.
_DOT_CHUNK = 256
_LIMBS = 4          # 31 bits as 8+8+8+7

_IMPLS = ("pallas", "dot", "ref")


def _limbs_f32(x: jnp.ndarray) -> jnp.ndarray:
    """(..., ) uint32 residues -> (4, ...) float32 8-bit limb planes (exact)."""
    return jnp.stack(
        [((x >> jnp.uint32(8 * i)) & jnp.uint32(0xFF)).astype(jnp.float32)
         for i in range(_LIMBS)]
    )


@jax.jit
def matmul_gf_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Exact mod-p matmul on canonical uint32 residues via limb float32 GEMMs.

    The 16 limb-pair products are laid out as ONE (4m, kc) @ (kc, 4n) block
    GEMM per K-chunk — a single large sgemm the platform BLAS runs at full
    rate, instead of 16 skinny ones — then the (i, j) blocks are recombined
    with Mersenne rotations.
    """
    m, c = a.shape
    n = b.shape[1]
    a_l = _limbs_f32(a).reshape(_LIMBS * m, c)             # (4m, c) stacked rows
    b_l = jnp.moveaxis(_limbs_f32(b), 0, 1).reshape(c, _LIMBS * n)  # (c, 4n)
    acc = jnp.zeros((m, n), jnp.uint32)
    for k0 in range(0, c, _DOT_CHUNK):
        k1 = min(k0 + _DOT_CHUNK, c)
        # Precision.HIGHEST is load-bearing: JAX's default matmul precision
        # permits TF32 on Ampere+ GPUs (10-bit mantissa), which would round
        # the 16-bit limb products and the < 2^24 partial sums — silently
        # wrong residues.  HIGHEST guarantees a true float32 GEMM everywhere.
        part = jnp.dot(
            a_l[:, k0:k1], b_l[k0:k1, :],
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )                                                  # (4m, 4n), exact ints
        part_u = part.astype(jnp.uint32)                   # < 2^24, exact
        part_u = part_u.reshape(_LIMBS, m, _LIMBS, n)
        for i in range(_LIMBS):
            for j in range(_LIMBS):
                acc = add_gf(acc, rot_gf(part_u[i, :, j, :], 8 * (i + j)))
    return acc


def matmul_gf(
    a,
    b,
    *,
    impl: str | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Exact (m, c) @ (c, n) mod p.  Any int dtype in, uint32 residues out."""
    a = to_gf(a)
    b = to_gf(b)
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"matmul_gf: bad shapes {a.shape} @ {b.shape}")
    impl = resolve_impl(impl, allowed=_IMPLS, host_impl="dot")
    if impl == "ref":
        return _matmul_gf_ref_jit(a, b)
    if impl == "dot":
        return matmul_gf_dot(a, b)
    return matmul_gf_pallas(a, b, interpret=default_interpret(interpret))


_matmul_gf_ref_jit = jax.jit(matmul_gf_ref)


def bmm_gf(
    a,
    b,
    *,
    impl: str | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Exact batched (..., m, c) @ (..., c, n) mod p — vmapped 2-D matmuls.

    Leading axes must match exactly (no broadcasting — the coded-computing
    callers batch over worker chunks, which both operands carry).  Same impl
    set as :func:`matmul_gf`; residues are exact, so all impls agree bit for
    bit.  2-D inputs fall through to :func:`matmul_gf` unchanged.
    """
    a = to_gf(a)
    b = to_gf(b)
    if a.ndim < 2 or b.ndim < 2 or a.ndim != b.ndim:
        raise ValueError(f"bmm_gf: bad ranks {a.shape} @ {b.shape}")
    if a.shape[:-2] != b.shape[:-2] or a.shape[-1] != b.shape[-2]:
        raise ValueError(f"bmm_gf: bad shapes {a.shape} @ {b.shape}")
    if a.ndim == 2:
        return matmul_gf(a, b, impl=impl, interpret=interpret)
    impl = resolve_impl(impl, allowed=_IMPLS, host_impl="dot")
    if impl == "ref":
        core = _matmul_gf_ref_jit
    elif impl == "dot":
        core = matmul_gf_dot
    else:
        core = partial(matmul_gf_pallas, interpret=default_interpret(interpret))
    lead = a.shape[:-2]
    a3 = a.reshape((-1,) + a.shape[-2:])
    b3 = b.reshape((-1,) + b.shape[-2:])
    out = jax.vmap(core)(a3, b3)
    return out.reshape(lead + out.shape[-2:])


@jax.jit
def lagrange_basis_gf(eval_pts, nodes) -> jnp.ndarray:
    """Batched exact Lagrange basis M[..., e, j] over GF(p).

    ``eval_pts`` (E,), ``nodes`` (..., J) — leading axes of ``nodes`` batch
    over node sets, so a (B, K*) batch of erasure patterns builds all B
    decode matrices in one call.  ``nodes`` may be a traced gather (the
    received alpha points): fully jittable, no host round-trip.
    """
    return lagrange_basis_gf_ref(eval_pts, nodes)


__all__ = [
    "FIELD_P", "bmm_gf", "lagrange_basis_gf", "matmul_gf", "matmul_gf_dot",
    "matmul_gf_pallas", "matmul_gf_ref",
]

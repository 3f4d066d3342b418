"""Pallas TPU kernel: blocked exact GF(p) matmul, p = 2^31 - 1 (Mersenne-31).

C = A @ B over the prime field, on int32/uint32 residues, bit-identical to
the numpy int64 host path — the device half of the paper's finite field F.

Design (mirrors the float matmul revisiting pattern, VPU-only):

  * grid (M/bm, N/bn, K/bk) with the contraction axis INNERMOST: each (i, j)
    output tile stays resident in VMEM across its K/bk visits, initialised at
    the first visit (``pl.when(pl.program_id(2) == 0)``) and accumulated
    in-place after that — tiled accumulation, never a partial sum > 32 bits;
  * inside one visit, a ``fori_loop`` over the bk contraction steps does a
    broadcast (bm, 1) x (1, bn) multiply-fold-add per step: the B row is a
    dynamic sublane read of the ref, the A column a masked lane sum.
    Products of two 31-bit residues are formed as four 16-bit-limb uint32
    partial products and reduced with the Mersenne fold 2^31 === 1
    (shift-adds, no division, no int64) — see :mod:`repro.kernels.gf.ref`
    for the arithmetic;
  * the MXU is never touched: exact integer dots don't fit a float systolic
    array, so this is a pure VPU kernel with lanes padded to 128.  Zero
    padding is harmless (0 is the additive identity).

``ref.matmul_gf_ref`` is the interpret-mode oracle; on CPU the ops
dispatcher routes to the XLA paths and this kernel is exercised with
``interpret=True`` in tests (exactness makes every path bit-equal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import add_gf, mul_gf

_LANES = 128


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _gf_matmul_kernel(a_ref, b_ref, out_ref, *, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # residues are < 2^31, so the int32 view is exact; Mosaic reduces int32
    # lanes but not uint32 ones
    a = a_ref[...].astype(jnp.int32)  # (bm, bk)
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)

    def body(i, acc):
        # column i of A as a masked lane sum (exact: one nonzero term) —
        # Mosaic cannot slice a value at a traced lane offset
        col = jnp.sum(jnp.where(lane == i, a, 0), axis=1, keepdims=True)
        row = b_ref[pl.ds(i, 1), :]                            # (1, bn)
        return add_gf(acc, mul_gf(col.astype(jnp.uint32), row))

    out_ref[...] = jax.lax.fori_loop(0, bk, body, out_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def matmul_gf_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_m: int = 64,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """(m, c) uint32 @ (c, n) uint32 -> (m, n) canonical residues mod p.

    Inputs must already be canonical residues in [0, p) (the ops dispatcher
    guarantees this); blocks are padded to the (8, 128) float32-class tile
    grid with zeros.
    """
    m, c = a.shape
    n = b.shape[1]
    bm = min(block_m, _round_up(m, 8))
    bn = min(block_n, _round_up(n, _LANES))
    # bk is A's minormost (lane) dim, so like bn it must stay a multiple of
    # 128 for Mosaic tiling — small K is padded up, never shrunk below a lane
    # tile (c=50 pads to bk=128; an explicit non-128-multiple block_k is
    # honoured only in interpret mode, for small-grid tests).
    bk = min(block_k, _round_up(c, _LANES))
    if not interpret and (bk % _LANES or bn % _LANES):
        raise ValueError(
            f"matmul_gf_pallas: lane-dim blocks (bk={bk}, bn={bn}) must be "
            f"multiples of {_LANES} on real hardware; pass a conforming "
            "block_k/block_n or interpret=True"
        )
    m_pad, c_pad, n_pad = _round_up(m, bm), _round_up(c, bk), _round_up(n, bn)
    a_p = jnp.pad(a.astype(jnp.uint32), ((0, m_pad - m), (0, c_pad - c)))
    b_p = jnp.pad(b.astype(jnp.uint32), ((0, c_pad - c), (0, n_pad - n)))

    out = pl.pallas_call(
        functools.partial(_gf_matmul_kernel, bk=bk),
        grid=(m_pad // bm, n_pad // bn, c_pad // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((bk, bn), lambda i, j, kb: (kb, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.uint32),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]

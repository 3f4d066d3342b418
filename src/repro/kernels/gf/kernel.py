"""Pallas TPU kernels: blocked exact GF(p) matmul, p = 2^31 - 1 (Mersenne-31).

C = A @ B over the prime field, on uint32 residues, bit-identical to the
numpy int64 host path — the device half of the paper's finite field F.
Both bodies are VPU-only: exact integer dots don't fit a float systolic
array, so the MXU is never touched.  Products of two 31-bit residues are
formed as four 16-bit-limb uint32 partial products and reduced with the
Mersenne fold 2^31 === 1 (shift-adds, no division, no int64) — see
:mod:`repro.kernels.gf.ref` for the arithmetic.

:func:`matmul_gf_pallas` picks one of two bodies on the static width
n = ``b.shape[1]``:

  * **narrow, n < 128** (a coded round's products against one query, the
    decode, the gradient's per-chunk products) — a lane contraction along
    A's rows, traced under the ``repro.gf_matvec`` device scope.  B goes in
    transposed, as n lane-dense rows.  Grid (M/bm, K/bk), K innermost; in
    each visit every 128-lane chunk of a (rows, 128) strip of A is
    multiplied elementwise (``mul_gf``) by the same chunk of one B row,
    broadcast over sublanes, and folded into a (rows, 128) lane-wise
    accumulator (``add_gf``): no cross-lane work per step.  At the end of
    the visit the 128 lanes are reduced exactly to one residue per row —
    a lane sum of residues would overflow uint32, so the 16-bit low halves
    and the 15-bit high halves are summed apart as int32 (each sum
    < 2^23) and recombined as hi * 2^16 + lo by a Mersenne rotation — and
    added into the (bm, n) output block, which stays resident across the
    K visits.  A is read as it lies, never padded: only B is padded, with
    zero columns up to the K grid, so whatever lies past A's ragged column
    edge meets a zero of B, and ``mul_gf(x, 0) = 0`` exactly for any
    uint32 x (the interpreter pads that edge with zeros; the chip leaves
    whatever its buffer held).  Ragged rows of A only feed output rows
    that are never written back.
  * **wide, n >= 128** (the encode) — outer products.  Grid (M/bm, N/bn,
    K/bk) with K innermost: each (i, j) output tile stays resident in VMEM
    across its K/bk visits, initialised at the first visit and
    accumulated in place after that.  Inside one visit a ``fori_loop``
    over the bk contraction steps does a broadcast (bm, 1) x (1, bn)
    multiply-fold-add per step: the B row is a dynamic sublane read of the
    ref, the A column a masked lane sum.  Operands are zero-padded to the
    tile grid (0 is the additive identity).

``ref.matmul_gf_ref`` is the interpret-mode oracle; on CPU the ops
dispatcher routes to the XLA paths and these bodies are exercised with
``interpret=True`` in tests (exactness makes every path bit-equal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.obs.profiling import phase as _phase

from .ref import add_gf, mul_gf, rot_gf

_LANES = 128

# Narrow body: widths below this take the lane contraction.  On a v5e it
# beat the wide body at every n measured below the cut: 0.134 against
# 32.6 ms at (3750, 3000) @ (3000, 1), 7.2 against 32.6 ms at n = 64, 0.019
# against 0.050 ms at the decode's (120, 120) @ (120, 25).
_NARROW_N = _LANES
# Its tiles: (bm, bk) blocks of A, at most _NARROW_BK columns wide, the K
# axis split into equal 128-lane multiples so little of B's zero padding is
# multiplied; worked in strips of _STRIP rows, so a strip's accumulator and
# the products' temporaries stay in vregs.  A block of (256, 3072) words is
# 3 MiB, double-buffered under the default scoped VMEM.
_NARROW_BM = 256
_NARROW_BK = 3072
_STRIP = 32


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


def _gf_matvec_kernel(a_ref, bt_ref, out_ref, *, rows: int):
    bm, bk = a_ref.shape
    n = bt_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)

    def query(j, carry):
        b_j = bt_ref[pl.ds(j, 1), :]                              # (1, bk)
        b_row = [b_j[:, l * _LANES:(l + 1) * _LANES] for l in range(bk // _LANES)]

        def strip(s, carry):
            r = pl.ds(pl.multiple_of(s * rows, rows), rows)
            acc = jnp.zeros((rows, _LANES), jnp.uint32)
            for l, b in enumerate(b_row):
                acc = add_gf(acc, mul_gf(a_ref[r, pl.ds(l * _LANES, _LANES)], b))
            # exact lane reduction: residues < 2^31 split into 16-bit lows
            # and 15-bit highs, whose 128-lane int32 sums stay below 2^23
            lo = jnp.sum((acc & 0xFFFF).astype(jnp.int32), axis=1, keepdims=True)
            hi = jnp.sum((acc >> 16).astype(jnp.int32), axis=1, keepdims=True)
            col = add_gf(rot_gf(hi.astype(jnp.uint32), 16), lo.astype(jnp.uint32))
            # column j of the block through a lane mask: Mosaic cannot
            # store at a traced lane offset, and adding 0 elsewhere is exact
            out_ref[r, :] = add_gf(out_ref[r, :], jnp.where(lane == j, col, 0))
            return carry

        return jax.lax.fori_loop(0, bm // rows, strip, carry)

    jax.lax.fori_loop(0, n, query, 0)


def _matvec_gf(a: jnp.ndarray, b: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """The narrow body: (m, c) @ (c, n) for n < 128, A read unpadded."""
    m, c = a.shape
    n = b.shape[1]
    bm = min(_NARROW_BM, _round_up(m, _STRIP))
    k_visits = pl.cdiv(c, _NARROW_BK)
    bk = _round_up(pl.cdiv(c, k_visits), _LANES)
    c_pad = k_visits * bk
    bt = jnp.pad(b.astype(jnp.uint32).T, ((0, 0), (0, c_pad - c)))   # (n, c_pad)
    return pl.pallas_call(
        functools.partial(_gf_matvec_kernel, rows=_STRIP),
        grid=(pl.cdiv(m, bm), k_visits),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, kb: (i, kb)),
            pl.BlockSpec((n, bk), lambda i, kb: (0, kb)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i, kb: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.uint32),
        interpret=interpret,
    )(a.astype(jnp.uint32), bt)


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
    guarantees this).  n < 128 takes the narrow body, whose tiles are its
    own; ``block_*`` shape the wide body, whose blocks are padded to the
    (8, 128) float32-class tile grid with zeros.
    """
    m, c = a.shape
    n = b.shape[1]
    if n < _NARROW_N:
        with _phase("gf_matvec"):
            return _matvec_gf(a, b, interpret)
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

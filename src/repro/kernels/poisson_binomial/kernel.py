"""Pallas TPU kernel: batched Poisson-binomial prefix tails in one VMEM pass.

The EA allocator (eq. 7/8) needs, for every prefix i~ of a descending-sorted
probability vector, the tail P[count >= w(i~)] of the Poisson-binomial pmf of
the first i~ Bernoullis.  The seed computed this with an O(n^2) ``lax.scan``
per vector; here the whole DP runs for a *batch* of vectors at once:

  * grid over batch tiles only — each kernel instance owns a (bb, n_pad)
    probability tile and keeps the full (bb, c_pad) pmf resident in VMEM
    registers for all n convolution steps (n <= a few hundred in every
    deployed config, so the working set is a few hundred KB);
  * the worker loop is unrolled at trace time (n is static), so each step is
    a pure VPU shift-multiply-add over the batch tile — no scalar control
    flow on the device;
  * lanes are padded to 128 (pmf counts axis and prefix axis), MXU is never
    touched — this is a pure VPU kernel.

Two threshold conventions, two entry points:

  * :func:`success_tails_pallas` — the classic static path: ``w`` is a
    Python tuple baked in as trace-time constants (no SMEM traffic;
    feasibility ``w > i~`` and the ``max(w, 0)`` clamp resolve at trace
    time).  One kernel per distinct ``w`` — one compile per ``LoadParams``.
  * :func:`success_tails_pallas_w` — the shape-polymorphic path: ``w`` is a
    TRACED (B, n) int32 input riding the same VMEM tiling as the
    probabilities, so heterogeneous-K*/ell batches (and mask-padded pools,
    whose padded prefixes carry an infeasible threshold) run in ONE compiled
    kernel.  Feasibility and the clamp become per-row selects.  Both kernels
    are validated against the ref DP in interpret mode; static-vs-traced
    agreement is to float32 round-off only (XLA constant-folds the static
    kernel's baked-in tail masks into re-associated reductions), exactly the
    tolerance the static kernel always had against the ref scan.

``ref.success_tails_ref`` (the seed ``lax.scan`` DP) is the interpret-mode
oracle; on CPU the ops dispatcher routes to the ref path and the Pallas
kernels are exercised with ``interpret=True`` in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _shift_counts(pmf, counts):
    """pmf[:, c] -> pmf[:, c - 1] along the counts lanes, 0 into count 0.

    A lane rotate plus a select: Mosaic refuses unaligned lane slices of a
    multi-row tile (the ``concatenate([0, pmf[:, :-1]])`` form aborts the TPU
    compiler for any batch block above 8 rows).  The wrapped-around lane is
    the last padded count, which is always 0 (counts above i are impossible
    after i Bernoullis), and is masked anyway.
    """
    return jnp.where(counts == 0, 0.0, pltpu.roll(pmf, 1, 1))


def _pb_kernel(probs_ref, out_ref, *, n: int, w: tuple[int, ...]):
    probs = probs_ref[...].astype(jnp.float32)          # (bb, n_pad)
    bb, n_pad = probs.shape
    c_pad = _round_up(n + 1, _LANES)

    counts = jax.lax.broadcasted_iota(jnp.int32, (bb, c_pad), 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bb, n_pad), 1)
    pmf = (counts == 0).astype(jnp.float32)             # point mass at count 0
    out = jnp.zeros((bb, n_pad), jnp.float32)

    for i in range(n):
        p_i = probs[:, i : i + 1]                       # (bb, 1), static slice
        pmf = pmf * (1.0 - p_i) + _shift_counts(pmf, counts) * p_i
        if w[i] > i + 1:                                # infeasible prefix
            continue                                    # (out stays 0)
        tail = jnp.sum(
            jnp.where(counts >= max(w[i], 0), pmf, 0.0), axis=1, keepdims=True,
        )                                               # (bb, 1)
        out = jnp.where(cols == i, tail, out)

    out_ref[...] = out


def _pb_kernel_w(probs_ref, w_ref, out_ref, *, n: int):
    """Traced-threshold body: identical DP, per-row w from a VMEM tile.

    The static kernel's trace-time branches become selects over the same
    expressions — an infeasible prefix writes the literal 0.0 the static
    kernel left in place, a feasible one the same masked tail sum (equal to
    the static kernel's to float32 round-off; XLA folds the static kernel's
    constant masks into re-associated reductions).
    """
    probs = probs_ref[...].astype(jnp.float32)          # (bb, n_pad)
    w = w_ref[...]                                      # (bb, n_pad) int32
    bb, n_pad = probs.shape
    c_pad = _round_up(n + 1, _LANES)

    counts = jax.lax.broadcasted_iota(jnp.int32, (bb, c_pad), 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bb, n_pad), 1)
    pmf = (counts == 0).astype(jnp.float32)             # point mass at count 0
    out = jnp.zeros((bb, n_pad), jnp.float32)

    for i in range(n):
        p_i = probs[:, i : i + 1]                       # (bb, 1), static slice
        pmf = pmf * (1.0 - p_i) + _shift_counts(pmf, counts) * p_i
        w_i = w[:, i : i + 1]                           # (bb, 1), static slice
        tail = jnp.sum(
            jnp.where(counts >= jnp.maximum(w_i, 0), pmf, 0.0),
            axis=1, keepdims=True,
        )                                               # (bb, 1)
        tail = jnp.where(w_i > i + 1, 0.0, tail)        # infeasible prefix
        out = jnp.where(cols == i, tail, out)

    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("w", "block_b", "interpret"))
def success_tails_pallas(
    probs: jnp.ndarray,
    w: tuple[int, ...],
    *,
    block_b: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """(B, n) descending-sorted probabilities -> (B, n) prefix tails.

    ``w`` must be a static tuple of n ints (from ``lea.prefix_thresholds``).
    """
    b, n = probs.shape
    assert len(w) == n, (len(w), n)
    bb = min(block_b, _round_up(b, 8))
    b_pad = _round_up(b, bb)
    n_pad = _round_up(n, _LANES)
    probs_p = jnp.pad(probs.astype(jnp.float32), ((0, b_pad - b), (0, n_pad - n)))

    out = pl.pallas_call(
        functools.partial(_pb_kernel, n=n, w=tuple(int(v) for v in w)),
        grid=(b_pad // bb,),
        in_specs=[pl.BlockSpec((bb, n_pad), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bb, n_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, n_pad), jnp.float32),
        interpret=interpret,
    )(probs_p)
    return out[:b, :n]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def success_tails_pallas_w(
    probs: jnp.ndarray,
    w: jnp.ndarray,
    *,
    block_b: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """(B, n) probabilities + (B, n) TRACED int32 thresholds -> (B, n) tails.

    The shape-polymorphic kernel: one compile serves every per-row
    (K*, ell) combination and every mask padding (padded prefixes carry
    ``w > i~`` and probability 0.0, so they score exactly 0).
    """
    b, n = probs.shape
    assert w.shape == (b, n), (w.shape, (b, n))
    bb = min(block_b, _round_up(b, 8))
    b_pad = _round_up(b, bb)
    n_pad = _round_up(n, _LANES)
    probs_p = jnp.pad(probs.astype(jnp.float32), ((0, b_pad - b), (0, n_pad - n)))
    # pad thresholds with n + 1 (> any i~): pad rows/cols are infeasible by
    # construction, not just sliced off — belt and braces for the batch pad.
    w_p = jnp.pad(w.astype(jnp.int32), ((0, b_pad - b), (0, n_pad - n)),
                  constant_values=n + 1)

    out = pl.pallas_call(
        functools.partial(_pb_kernel_w, n=n),
        grid=(b_pad // bb,),
        in_specs=[pl.BlockSpec((bb, n_pad), lambda i: (i, 0)),
                  pl.BlockSpec((bb, n_pad), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bb, n_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, n_pad), jnp.float32),
        interpret=interpret,
    )(probs_p, w_p)
    return out[:b, :n]


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m

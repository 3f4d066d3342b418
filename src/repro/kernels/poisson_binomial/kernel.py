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

from repro.obs.profiling import phase as _phase

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


# -- the wide body: fleets of hundreds to thousands of workers ----------------

# A batch tile of the wide body: rows on sublanes, so each step's update of a
# 128-count tile is bb / 8 vregs; its pmf, probability, threshold and output
# blocks are (n_pad / 128, bb, 128) words each, 4 MiB at n = 4,000.  On a
# v5e a tile step cost about 115 ns at every bb from 8 to 64 rows (61 us a
# row at 64, 4,000 workers): the step's overhead, not its vregs, set the
# pace, so the tile is as tall as an engine block of rounds.
_WIDE_BB = 256
# No threshold of any row reaches this: the floor offset of a row with no
# feasible prefix, so that it never lowers its tile's floor.
_NO_FLOOR = 2**30


def _pb_wide_kernel(bounds_ref, probs_ref, w_ref, top_ref, out_ref, pmf_ref,
                    *, n: int):
    """The same DP with the worker loop rolled and the count axis capped.

    Blocks: ``probs_ref``, ``w_ref``, ``out_ref`` are (n_chunks, bb, 128),
    chunk k holding workers / prefixes 128k .. 128k + 127 of bb rows;
    ``top_ref`` (bb, 128) is each row's top bin t, repeated along the lanes;
    ``bounds_ref`` (SMEM) holds the tile's floor offset s and largest top
    bin.  ``pmf_ref`` holds count c of every row in slot c // 128 + 1,
    lane c % 128; slot 0 stays zero, the counts below 0.

    Capping, exact for every tail the kernel writes: counts at or above t
    fold into count t (absorbing: it keeps its mass and takes the inflow
    from t - 1), and after worker i only counts in [i + s, min(i + 1, t)]
    are updated.  A count below i + s cannot reach any later feasible
    threshold, so its value never reaches a count that is summed: it is
    left stale.
    """
    bb = top_ref.shape[0]
    floor_off = bounds_ref[0, 0]
    top_max = bounds_ref[0, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bb, _LANES), 1)
    top = top_ref[...]
    zeros = jnp.zeros((bb, _LANES), jnp.float32)

    def clear(t, carry):
        pmf_ref[t] = zeros
        return carry

    jax.lax.fori_loop(0, pmf_ref.shape[0], clear, 0)
    pmf_ref[1] = (lane == 0).astype(jnp.float32)        # point mass at count 0

    def chunk(k, carry):
        p_chunk = probs_ref[k]
        w_chunk = w_ref[k]

        def step(j, out_chunk):
            i = k * _LANES + j                          # worker i, prefix i + 1
            pick = lane == j
            p = jnp.sum(jnp.where(pick, p_chunk, 0.0), axis=1, keepdims=True)
            w = jnp.sum(jnp.where(pick, w_chunk, 0), axis=1, keepdims=True)
            keep = 1.0 - p
            lo = jnp.maximum(i + floor_off, 0) // _LANES
            hi = jnp.minimum(i + 1, top_max) // _LANES

            def tile(u, carry):
                # tiles from the highest down, so the tile below is still old
                cur, rolled, acc = carry
                t = hi - u
                below = pmf_ref[t]
                rolled_below = pltpu.roll(below, 1, 1)
                shifted = jnp.where(lane == 0, rolled_below, rolled)
                c = lane + t * _LANES
                new = (cur * jnp.where(c < top, keep, 1.0)
                       + shifted * jnp.where(c <= top, p, 0.0))
                pmf_ref[t + 1] = new
                acc = acc + jnp.where(c >= w, new, 0.0)
                return below, rolled_below, acc

            cur = pmf_ref[hi + 1]
            _, _, acc = jax.lax.fori_loop(
                0, jnp.maximum(hi - lo + 1, 0), tile,
                (cur, pltpu.roll(cur, 1, 1), zeros))
            tail = jnp.sum(acc, axis=1, keepdims=True)
            tail = jnp.where(w > i + 1, 0.0, tail)      # infeasible prefix
            return jnp.where(pick, tail, out_chunk)

        steps = jnp.minimum(_LANES, n - k * _LANES)
        out_ref[k] = jax.lax.fori_loop(0, steps, step, zeros)
        return carry

    jax.lax.fori_loop(0, probs_ref.shape[0], chunk, 0)


def _wide_vmem_bytes(n: int, bb: int) -> int:
    """VMEM the wide body asks for: its three (n_pad / 128, bb, 128) word
    blocks and the top bins, double-buffered, the pmf, and 8 MiB to spare."""
    tile = bb * _LANES * 4
    blocks = 3 * (_round_up(n, _LANES) // _LANES) * tile + tile
    return 2 * blocks + (n // _LANES + 2) * tile + (8 << 20)


def _wide_caps(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(floor offset s, top bin t) of each row of (B, n) thresholds.

    Prefix j + 1 scores only when feasible (w_j <= j + 1), and then reads
    the counts >= v_j = max(w_j, 0).  A count c after worker i reaches at
    most c + j - i after worker j, so it can still count only if c >= i +
    min over feasible j >= i of (v_j - j); s, the minimum over all feasible
    j, is a floor under that at every i.  t = max feasible v_j: every count
    at or above it passes every feasible tail.  A row with no feasible
    prefix gets (_NO_FLOOR, 0) and writes zeros.
    """
    j = jnp.arange(w.shape[1], dtype=jnp.int32)
    feasible = w <= j + 1
    v = jnp.maximum(w, 0)
    floor_off = jnp.min(jnp.where(feasible, v - j, _NO_FLOOR), axis=1)
    top = jnp.max(jnp.where(feasible, v, 0), axis=1)
    return floor_off, top


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def success_tails_wide(
    probs: jnp.ndarray,
    w: jnp.ndarray,
    *,
    block_b: int = _WIDE_BB,
    interpret: bool = False,
) -> jnp.ndarray:
    """(B, n) probabilities + (B, n) traced int32 thresholds -> (B, n) tails.

    The wide body: the worker loop is a ``fori_loop`` and the count axis is
    capped per row (:func:`_wide_caps`) and worked in 128-lane tiles, so
    neither the compile nor VMEM grows with n beyond the (n_pad / 128, bb,
    128) blocks.  Traced under the ``repro.dp_wide`` device scope.
    """
    b, n = probs.shape
    assert w.shape == (b, n), (w.shape, (b, n))
    with _phase("dp_wide"):
        bb = min(block_b, _round_up(b, 8))
        b_pad = _round_up(b, bb)
        n_pad = _round_up(n, _LANES)
        chunks = n_pad // _LANES
        w = w.astype(jnp.int32)
        floor_off, top = _wide_caps(w)
        floor_off = jnp.pad(floor_off, (0, b_pad - b), constant_values=_NO_FLOOR)
        top = jnp.pad(top, (0, b_pad - b))
        bounds = jnp.stack([jnp.min(floor_off.reshape(-1, bb), axis=1),
                            jnp.max(top.reshape(-1, bb), axis=1)], axis=1)[:, None]

        def by_chunk(x, fill):
            x = jnp.pad(x, ((0, b_pad - b), (0, n_pad - n)), constant_values=fill)
            return x.reshape(b_pad, chunks, _LANES).transpose(1, 0, 2)

        out = pl.pallas_call(
            functools.partial(_pb_wide_kernel, n=n),
            grid=(b_pad // bb,),
            in_specs=[
                pl.BlockSpec((None, 1, 2), lambda i: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((chunks, bb, _LANES), lambda i: (0, i, 0)),
                pl.BlockSpec((chunks, bb, _LANES), lambda i: (0, i, 0)),
                pl.BlockSpec((bb, _LANES), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((chunks, bb, _LANES), lambda i: (0, i, 0)),
            out_shape=jax.ShapeDtypeStruct((chunks, b_pad, _LANES), jnp.float32),
            scratch_shapes=[pltpu.VMEM((n // _LANES + 2, bb, _LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_wide_vmem_bytes(n, bb)),
            interpret=interpret,
        )(bounds, by_chunk(probs.astype(jnp.float32), 0.0), by_chunk(w, n + 1),
          jnp.broadcast_to(top[:, None], (b_pad, _LANES)))
        return out.transpose(1, 0, 2).reshape(b_pad, n_pad)[:b, :n]


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m

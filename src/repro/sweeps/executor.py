"""Sharded, grouped, chunked execution of scenario batches.

One :class:`~repro.sweeps.registry.SweepGroup` = one compiled computation:
:func:`_run_group` is the single jitted entry point, with only ``(rounds,
strategies, round_chunk)`` static.  Load parameters (K*, ell_g, ell_b) and
the worker-pool mask are TRACED batch leaves fed to the shape-polymorphic
engine (:func:`repro.core.throughput.simulate_strategies_pool`), so a
heterogeneous-K* grid, a deadline/load sweep or an elastic pool ramp is ONE
compile for the whole family regardless of how many scenarios and seeds it
spans (:func:`compile_cache_size` exposes the cache counter the tests
assert on).

Sharding: sweep rows are embarrassingly parallel, so the executor lays the
flat (scenarios x seeds) batch over the ``"batch"`` axis of a 1-D
``jax.sharding`` mesh (:func:`repro.launch.mesh.make_sweep_mesh`) by
device_put-ing every batch leaf with ``NamedSharding(mesh, P("batch"))``,
and each device runs the per-row program on its own rows
(:func:`_rows_per_shard`, a ``shard_map``: GSPMD cannot partition the
Pallas allocator kernel the engine calls on a TPU).  Batches are
padded (by repeating the last row) to a multiple of the mesh size; padded
rows are sliced off the result, so sharded output is bit-identical to the
unsharded :func:`repro.core.throughput.sweep` on the same keys.

Memory: ``round_chunk`` is forwarded to the engine's ``lax.map``-over-round-
blocks path so paper-scale M = 1e5 grids hold peak memory at one block.
"""

from __future__ import annotations

import collections
import math
import time
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import throughput
from repro.core.lea import PoolLoad
from repro.obs import counters as _obs_counters
from repro.obs import metrics as _metrics
from repro.obs.profiling import annotate

from .registry import ScenarioBatch, SweepGroup


def _rows_per_shard(fn, mesh: Mesh | None):
    """``fn`` over batch rows, run shard by shard on ``mesh`` (None: as is).

    Rows are independent, so each device running ``fn`` on its own rows is
    bit-identical to the whole batch at once.  Every argument and output is
    split along its leading (row) axis; values ``fn`` closes over are
    replicated.
    """
    if mesh is None:
        return fn
    rows = PartitionSpec("batch")

    def per_shard(*args):
        return jax.shard_map(fn, mesh=mesh, in_specs=(rows,) * len(args),
                             out_specs=rows, check_vma=False)(*args)

    return per_shard


@partial(jax.jit,
         static_argnames=("rounds", "strategies", "round_chunk", "telemetry",
                          "tap", "tap_stride", "mesh"))
def _run_group(
    keys: jnp.ndarray,
    p_gg: jnp.ndarray,
    p_bb: jnp.ndarray,
    mu_g: jnp.ndarray,
    mu_b: jnp.ndarray,
    deadline: jnp.ndarray,
    pool: PoolLoad,
    *,
    rounds: int,
    strategies: tuple[str, ...],
    round_chunk: int | None,
    telemetry: bool = False,
    tap: bool = False,
    tap_stride: int | None = None,
    mesh: Mesh | None = None,
):
    """(B,) rows -> (B, rounds, S) success indicators, one XLA computation."""
    fn = partial(
        throughput.simulate_strategies_pool,
        rounds=rounds, strategies=strategies, round_chunk=round_chunk,
        telemetry=telemetry, tap=tap, tap_stride=tap_stride,
    )
    if tap:
        rows = jnp.arange(keys.shape[0], dtype=jnp.int32)
        return _rows_per_shard(jax.vmap(
            lambda k, pg, pb, mg, mb, d, pl, ri: fn(
                k, pool=pl, p_gg=pg, p_bb=pb, mu_g=mg, mu_b=mb, deadline=d,
                tap_row=ri,
            )
        ), mesh)(keys, p_gg, p_bb, mu_g, mu_b, deadline, pool, rows)
    return _rows_per_shard(jax.vmap(
        lambda k, pg, pb, mg, mb, d, pl: fn(
            k, pool=pl, p_gg=pg, p_bb=pb, mu_g=mg, mu_b=mb, deadline=d
        )
    ), mesh)(keys, p_gg, p_bb, mu_g, mu_b, deadline, pool)


_obs_counters.register_compiled("sweeps.run_group", _run_group)


def compile_cache_size() -> int:
    """Number of distinct group computations compiled so far.

    Thin alias over the unified obs counter
    (``obs.compile_events("sweeps.run_group")``) — kept for the pre-obs
    tests and benchmarks."""
    return _obs_counters.compile_events("sweeps.run_group")


def _pad_batch(batch: ScenarioBatch, multiple: int) -> tuple[ScenarioBatch, int]:
    """Pad rows to a multiple of the mesh size by repeating the last row.

    Rows are vmapped independently, so pad rows cannot perturb real rows;
    they are sliced off the result.
    """
    b = batch.rows
    pad = (-b) % multiple
    if pad == 0:
        return batch, b
    rep = jax.tree.map(
        lambda x: jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)]), batch
    )
    return rep, b


def _shard_batch(batch: ScenarioBatch, mesh: Mesh) -> ScenarioBatch:
    sh = NamedSharding(mesh, PartitionSpec("batch"))
    return jax.tree.map(lambda x: jax.device_put(x, sh), batch)


# ---------------------------------------------------------------------------
# pipelined (async, donated-carry) execution path
# ---------------------------------------------------------------------------
#
# The sync path runs one fused computation per group: preamble + a blocking
# ``lax.map`` over round blocks, re-padding and re-device_put-ing the batch
# on every call.  The pipelined path rebuilds the hot loop host-side:
#
#   * the padded/sharded batch is CACHED per (group identity, mesh) — shard
#     once, dispatch many (the steady-state sweep driver pattern);
#   * ``_prepare_group`` computes the engine preamble for every row ONCE
#     (:func:`repro.core.throughput.engine_preamble` — the identical traced
#     ops the sync engine runs, so per-round values are bit-identical);
#   * ``_block_step`` scores ONE round block for every row with the
#     cumulative aggregates (success counts, estimator-error sums, tap
#     tokens) as DONATED carries — XLA aliases them in place instead of
#     double-buffering (verified: donated buffers are deleted after the
#     first step, and the compiled HLO carries ``input_output_alias``);
#   * the host loop dispatches block b+1 while folding block b's device
#     result into host memory (JAX dispatch is async) — at most
#     ``PIPELINE_DEPTH`` blocks in flight, one final ``block_until_ready``
#     drain.  With ``tap=True`` each block emits the same ``engine.pool``
#     events as the sync scan, timed at actual block completion, so
#     ``tap.engine_pool.block_seconds`` measures real overlap.
#
# Blocks are independent per-round work, so any dispatch partition is
# bit-identical to the sync path on the same keys (property-tested).

PIPELINE_DEPTH = 2          # max blocks in flight (double-buffered)

_SHARD_CACHE_MAX = 4
_shard_cache: "collections.OrderedDict[tuple, tuple]" = collections.OrderedDict()

_PIPELINE_STATS: dict = {}


def last_pipeline_stats() -> dict:
    """Host-loop accounting of the most recent pipelined run_group call.

    Keys: ``blocks``, ``round_chunk``, ``donated`` (runtime proof: the
    donated carry buffer was consumed by the first block step),
    ``shard_cached`` (the padded/sharded batch came from the shard-once
    cache).  The loop keeps no clock of its own: each block's copy to the
    host is a ``repro.pull`` span of a profiler trace
    (:mod:`repro.obs.profiling`).
    """
    return dict(_PIPELINE_STATS)


def _cached_shard(group: SweepGroup, mesh: Mesh | None):
    """Padded + device_put batch for ``group`` on ``mesh``, cached by identity.

    The cache key holds a strong reference to the group and is verified
    with ``is`` — id() reuse after garbage collection can never alias two
    distinct groups.  Bounded FIFO (the executor is typically driven with a
    handful of live groups)."""
    if mesh is None:
        return group.batch, group.batch.rows, False
    key = (id(group), tuple(mesh.axis_names), tuple(d.id for d in mesh.devices.flat))
    hit = _shard_cache.get(key)
    if hit is not None and hit[0] is group:
        _shard_cache.move_to_end(key)
        return hit[1], hit[2], True
    batch, b = _pad_batch(group.batch, mesh.devices.size)
    batch = _shard_batch(batch, mesh)
    _shard_cache[key] = (group, batch, b)
    while len(_shard_cache) > _SHARD_CACHE_MAX:
        _shard_cache.popitem(last=False)
    return batch, b, False


@partial(jax.jit,
         static_argnames=("rounds", "strategies", "n_blocks", "round_chunk",
                          "tap"))
def _prepare_group(
    keys: jnp.ndarray,
    p_gg: jnp.ndarray,
    p_bb: jnp.ndarray,
    mu_g: jnp.ndarray,
    mu_b: jnp.ndarray,
    deadline: jnp.ndarray,
    pool: PoolLoad,
    *,
    rounds: int,
    strategies: tuple[str, ...],
    n_blocks: int,
    round_chunk: int,
    tap: bool,
):
    """Per-row engine preamble, round-padded to ``n_blocks * round_chunk``.

    Returns ``(states (B, Mp, n), round_keys (B, Mp, 2), p_alloc
    (B, A, Mp, n), est, pack_f (B, n + 3), pack_i (B, 3), mask (B, n),
    succ0, err0, tok0)`` — exactly the arrays the sync engine computes
    before its block loop (same PRNG discipline, same edge-round padding),
    materialised once so the block steps only slice.

    The calling convention is deliberately PACKED: per-row invariants that
    the block steps only read — ``pi_g``/``mu_g``/``mu_b``/``deadline``
    into ``pack_f``, the integer load params into ``pack_i`` — plus the
    zero carries, built HERE (sharding-tied to the batch so donation still
    aliases).  Dispatching a multi-device jit costs ~50us PER SHARDED
    ARGUMENT on this backend, and the block step is dispatched once per
    block per group: every leaf trimmed off its signature is wall-clock
    the async loop keeps.  ``est`` (the estimator-error stream), the error
    carry and the tap token are ``None`` when ``tap=False`` — zero leaves
    instead of dead arrays (``tap`` is already a static compile key).
    """

    def row(k, pg, pb, pl):
        states, round_keys, p_alloc, pi_g = throughput.engine_preamble(
            k, pl, pg, pb, rounds, strategies
        )
        est = (throughput.estimator_error_rounds(
            states, p_alloc, pg, pb, pi_g, pl.mask
        ) if tap else None)
        return states, round_keys, p_alloc, est, pi_g

    states, round_keys, p_alloc, est, pi_g = jax.vmap(row)(keys, p_gg, p_bb, pool)
    pad = n_blocks * round_chunk - rounds
    if pad:
        # edge-round padding, exactly the sync chunked path's convention:
        # blocks are independent, so pad rounds cannot perturb real rounds
        states = jnp.concatenate([states, states[:, -pad:]], axis=1)
        round_keys = jnp.concatenate([round_keys, round_keys[:, -pad:]], axis=1)
        p_alloc = jnp.concatenate([p_alloc, p_alloc[:, :, -pad:]], axis=2)
        if tap:
            est = jnp.concatenate([est, est[:, -pad:]], axis=1)
    pack_f = jnp.concatenate(
        [pi_g.astype(jnp.float32), mu_g[:, None].astype(jnp.float32),
         mu_b[:, None].astype(jnp.float32),
         deadline[:, None].astype(jnp.float32)], axis=1)
    pack_i = jnp.stack([pool.kstar, pool.ell_g, pool.ell_b], axis=1)
    # zero carries, arithmetic-tied to a batch-sharded operand so GSPMD
    # lays them out exactly like the block step's outputs (donation aliases)
    zero = pack_i[:, 0] * 0                                     # (B,) int32
    succ0 = zero[:, None] + jnp.zeros((1, len(strategies)), jnp.int32)
    err0 = (zero[:, None].astype(jnp.float32)
            + jnp.zeros((1, p_alloc.shape[1]), jnp.float32)) if tap else None
    tok0 = zero if tap else None
    return (states, round_keys, p_alloc, est, pack_f, pack_i, pool.mask,
            succ0, err0, tok0)


@partial(jax.jit,
         static_argnames=("rounds", "strategies", "round_chunk", "tap",
                          "mesh"),
         donate_argnums=(0, 1, 2))
def _block_step(
    succ_cum: jnp.ndarray,     # (B, S) int32 — DONATED
    err_cum,                   # (B, A) float32 — DONATED; None when tap=False
    token,                     # (B,) int32 tap token — DONATED; None w/o tap
    block_i: jnp.ndarray,      # traced scalar int32
    states: jnp.ndarray,       # (B, Mp, n)
    round_keys: jnp.ndarray,   # (B, Mp, 2)
    p_alloc: jnp.ndarray,      # (B, A, Mp, n)
    est,                       # (B, Mp, A) — None when tap=False
    pack_f: jnp.ndarray,       # (B, n + 3) f32: pi_g | mu_g | mu_b | deadline
    pack_i: jnp.ndarray,       # (B, 3) int32: kstar | ell_g | ell_b
    mask: jnp.ndarray,         # (B, n) bool worker mask
    *,
    rounds: int,
    strategies: tuple[str, ...],
    round_chunk: int,
    tap: bool,
    mesh: Mesh | None = None,
):
    """Round block ``block_i`` for every row: donated carries + (B, m, S) succ.

    One compile serves every block (``block_i`` is traced; slicing is
    ``dynamic_slice``).  The block body is
    :func:`repro.core.throughput.engine_block` — the identical per-round
    ops the sync chunked ``lax.map`` runs — so dispatch order cannot change
    a single bit of the success stream.  Unpacking ``pack_f``/``pack_i``
    is free slicing inside the trace; what it buys is a short argument
    list, i.e. cheap per-block dispatch (see ``_prepare_group``).
    """
    m = round_chunk
    start = block_i * m
    in_round = jnp.arange(m, dtype=jnp.int32)
    valid = (start + in_round) < rounds                        # (m,)
    n = mask.shape[-1]
    rows_idx = jnp.arange(succ_cum.shape[0], dtype=jnp.int32)  # tap row labels

    def row(succ_c, err_c, tok, states_r, keys_r, p_alloc_r, est_r, pf, pi,
            mk, ri):
        pl = PoolLoad(kstar=pi[0], ell_g=pi[1], ell_b=pi[2], mask=mk)
        states_b = jax.lax.dynamic_slice_in_dim(states_r, start, m, axis=0)
        keys_b = jax.lax.dynamic_slice_in_dim(keys_r, start, m, axis=0)
        p_alloc_b = jax.lax.dynamic_slice_in_dim(p_alloc_r, start, m, axis=1)
        succ_b = throughput.engine_block(
            states_b, keys_b, p_alloc_b, pf[:n], pl, strategies,
            pf[n], pf[n + 1], pf[n + 2]
        )                                                      # (m, S)
        succ_c = succ_c + jnp.sum(
            jnp.where(valid[:, None], succ_b.astype(jnp.int32), 0), axis=0
        )
        if tap:
            from repro.obs import taps as _taps

            est_b = jax.lax.dynamic_slice_in_dim(est_r, start, m, axis=0)
            err_c = err_c + jnp.sum(jnp.where(valid[:, None], est_b, 0.0),
                                    axis=0)
            rounds_done = jnp.minimum((block_i + 1) * m, rounds)
            done_f = jnp.maximum(rounds_done.astype(jnp.float32), 1.0)
            tok = _taps.emit(
                "engine.pool", token=tok,
                block=jnp.asarray(block_i, jnp.int32),
                row=jnp.asarray(ri, jnp.int32),
                rounds_done=jnp.asarray(rounds_done, jnp.int32),
                succ_so_far=succ_c,
                throughput_so_far=succ_c.astype(jnp.float32) / done_f,
                est_err_so_far=err_c / done_f,
            )
        return succ_c, err_c, tok, succ_b

    return _rows_per_shard(jax.vmap(row), mesh)(
        succ_cum, err_cum, token, states, round_keys, p_alloc, est, pack_f,
        pack_i, mask, rows_idx)


_obs_counters.register_compiled("sweeps.prepare_group", _prepare_group)
_obs_counters.register_compiled("sweeps.block_step", _block_step)


def _pipeline_geometry(rounds: int, round_chunk: int | None) -> tuple[int, int]:
    """(chunk, n_blocks) for the pipelined loop — whole run = one block."""
    if round_chunk is not None and round_chunk <= 0:
        raise ValueError("round_chunk must be positive")
    chunk = rounds if round_chunk is None or round_chunk >= rounds else round_chunk
    return chunk, -(-rounds // chunk)


def _run_group_pipelined(
    group: SweepGroup,
    batch: ScenarioBatch,
    b: int,
    *,
    mesh: Mesh | None,
    round_chunk: int | None,
    tap: bool,
) -> np.ndarray:
    chunk, n_blocks = _pipeline_geometry(group.rounds, round_chunk)
    rounds, strategies = group.rounds, group.strategies
    (states, round_keys, p_alloc, est, pack_f, pack_i, mask,
     succ_cum, err_cum, token) = _prepare_group(
        batch.keys, batch.p_gg, batch.p_bb, batch.mu_g, batch.mu_b,
        batch.deadline, batch.pool,
        rounds=rounds, strategies=strategies, n_blocks=n_blocks,
        round_chunk=chunk, tap=tap,
    )

    first_carry = succ_cum
    host_blocks: list[np.ndarray | None] = [None] * n_blocks
    inflight: collections.deque = collections.deque()

    def fold_oldest():
        j, sb = inflight.popleft()
        with annotate("repro.pull"):
            host_blocks[j] = np.asarray(sb)  # waits for block j only

    for bi in range(n_blocks):
        succ_cum, err_cum, token, succ_b = _block_step(
            succ_cum, err_cum, token, jnp.asarray(bi, jnp.int32),
            states, round_keys, p_alloc, est, pack_f, pack_i, mask,
            rounds=rounds, strategies=strategies, round_chunk=chunk, tap=tap,
            mesh=mesh,
        )
        inflight.append((bi, succ_b))
        if len(inflight) >= PIPELINE_DEPTH:
            fold_oldest()
    while inflight:
        fold_oldest()
    jax.block_until_ready((succ_cum, err_cum, token))

    _PIPELINE_STATS.update(
        blocks=n_blocks, round_chunk=chunk, donated=bool(first_carry.is_deleted()),
    )
    succ = (host_blocks[0] if n_blocks == 1
            else np.concatenate(host_blocks, axis=1))
    return succ[:b, :rounds]


def pipeline_block_hlo(
    group: SweepGroup,
    *,
    mesh: Mesh | None = None,
    round_chunk: int | None = None,
    tap: bool = False,
) -> str:
    """Compiled HLO text of ``_block_step`` on this group's shapes.

    The donation introspection hook: the text carries
    ``input_output_alias`` entries iff XLA actually aliased the donated
    carries — what the tests and ``bench_speed`` assert instead of hoping.
    """
    batch, _, _ = _cached_shard(group, mesh)
    chunk, n_blocks = _pipeline_geometry(group.rounds, round_chunk)
    (states, round_keys, p_alloc, est, pack_f, pack_i, mask,
     succ0, err0, tok0) = _prepare_group(
        batch.keys, batch.p_gg, batch.p_bb, batch.mu_g, batch.mu_b,
        batch.deadline, batch.pool,
        rounds=group.rounds, strategies=group.strategies, n_blocks=n_blocks,
        round_chunk=chunk, tap=tap,
    )
    lowered = _block_step.lower(
        succ0, err0, tok0, jnp.asarray(0, jnp.int32),
        states, round_keys, p_alloc, est, pack_f, pack_i, mask,
        rounds=group.rounds, strategies=group.strategies, round_chunk=chunk,
        tap=tap, mesh=mesh,
    )
    return lowered.compile().as_text()


def run_group(
    group: SweepGroup,
    *,
    mesh: Mesh | None = None,
    round_chunk: int | None = None,
    telemetry: bool = False,
    tap: bool = False,
    tap_stride: int | None = None,
    pipeline: bool = False,
):
    """Execute one group; returns host (B, rounds, S) bool success array.

    With ``telemetry=True`` returns ``(succ, TelemetryFrame)`` — the frame
    leaves are host arrays with the same leading (B,) slicing as ``succ``
    (see :mod:`repro.obs.telemetry`); the group still compiles once.  With
    ``tap=True`` the engine streams per-row block aggregates to the
    registered tap handlers DURING the run (:mod:`repro.obs.taps`) — same
    bit-identity and one-compile contract.  Every call attributes its
    wall-clock up to the device result (``repro.run_group.seconds``, or
    ``repro.run_pipelined.seconds``) and any compile events it triggered
    (``compile.sweeps_run_group.*``) to the default metrics registry
    (:mod:`repro.obs.metrics`); in a profiler trace the same intervals are
    host spans of those names, and the copy of the result to the host is a
    ``repro.pull`` span.

    ``pipeline=True`` selects the async double-buffered path: shard-once
    batch cache, donated-carry block steps, host folds overlapped with the
    in-flight block (see the pipelined section above) — bit-identical
    output, :func:`last_pipeline_stats` for the loop accounting.  Telemetry
    frames are a whole-run artifact and incompatible with per-block
    dispatch; tap events stream per block (``tap_stride`` is the sync
    path's knob and is ignored — the pipeline's block size IS
    ``round_chunk``).
    """
    if group.rounds < 1:
        names = ", ".join(sc.name for sc in group.scenarios[:3])
        raise ValueError(
            f"group [{names}, ...] has rounds={group.rounds}; catalogue-only "
            "scenario families (e.g. kstar_table) cannot be simulated"
        )
    if mesh is not None and tuple(mesh.axis_names) != ("batch",):
        raise ValueError(f'sweep mesh must have axes ("batch",), got {mesh.axis_names}')
    if pipeline:
        if telemetry:
            raise ValueError(
                "pipeline=True is incompatible with telemetry=True: telemetry "
                "frames are whole-run artifacts (use tap= for live streams)"
            )
        batch, b, cached = _cached_shard(group, mesh)
        c0 = _obs_counters.compile_events("sweeps.block_step") \
            + _obs_counters.compile_events("sweeps.prepare_group")
        h0 = _obs_counters.persistent_cache_hits()
        t0 = time.perf_counter()
        with _metrics.timed("repro.run_pipelined"):
            succ = _run_group_pipelined(
                group, batch, b, mesh=mesh, round_chunk=round_chunk, tap=tap,
            )
        _PIPELINE_STATS["shard_cached"] = cached
        _metrics.record_compile(
            "sweeps.pipeline",
            max(_obs_counters.compile_events("sweeps.block_step")
                + _obs_counters.compile_events("sweeps.prepare_group") - c0
                - (_obs_counters.persistent_cache_hits() - h0), 0),
            time.perf_counter() - t0,
        )
        return succ
    batch, b = (group.batch, group.batch.rows)
    if mesh is not None:
        batch, b = _pad_batch(batch, mesh.devices.size)
        batch = _shard_batch(batch, mesh)
    c0 = _obs_counters.compile_events("sweeps.run_group")
    h0 = _obs_counters.persistent_cache_hits()
    t0 = time.perf_counter()
    with _metrics.timed("repro.run_group"):
        out = _run_group(
            batch.keys, batch.p_gg, batch.p_bb, batch.mu_g, batch.mu_b,
            batch.deadline, batch.pool,
            rounds=group.rounds, strategies=group.strategies,
            round_chunk=round_chunk, telemetry=telemetry,
            tap=tap, tap_stride=tap_stride, mesh=mesh,
        )
        out = jax.block_until_ready(out)
    # a trace-cache entry served from the persistent compilation cache
    # (repro.launch.cache) is not a compile — subtract the hit delta so warm
    # restarts attribute 0 compile events
    _metrics.record_compile(
        "sweeps.run_group",
        max(_obs_counters.compile_events("sweeps.run_group") - c0
            - (_obs_counters.persistent_cache_hits() - h0), 0),
        time.perf_counter() - t0,
    )
    with annotate("repro.pull"):
        if not telemetry:
            return np.asarray(out[:b])
        succ, frame = out
        return np.asarray(succ[:b]), jax.tree.map(lambda x: np.asarray(x[:b]), frame)


def run_groups(
    groups: Sequence[SweepGroup],
    *,
    mesh: Mesh | None = None,
    round_chunk: int | None = None,
    tap: bool = False,
    tap_stride: int | None = None,
    pipeline: bool = False,
) -> list[np.ndarray]:
    """Execute every group (one compile each); list aligned with ``groups``."""
    return [run_group(g, mesh=mesh, round_chunk=round_chunk,
                      tap=tap, tap_stride=tap_stride, pipeline=pipeline)
            for g in groups]


def suggest_round_chunk(
    group: SweepGroup,
    *,
    mesh: Mesh | None = None,
    budget_bytes: int = 1 << 30,
    pipeline: bool = False,
) -> int | None:
    """A round_chunk that keeps one group's per-device block under ``budget``.

    Per-block intermediates per (strategy, round) row: the O(n) DP/score
    arrays (~(S + A) * chunk * n floats with ~8x temporary headroom) PLUS the
    allocator's pairwise-rank elimination, whose unrolled compares
    materialise O(A * chunk * n^2) floats for n <= ``_PAIRWISE_RANK_MAX_N``
    — the term that dominates as n grows, exactly the memory-constrained
    case this knob exists for.  Returns None when the whole run already fits.

    ``pipeline=True`` halves the budget: the async path keeps up to
    ``PIPELINE_DEPTH`` (= 2) block results live at once (the in-flight block
    plus the one being folded), so a chunk sized for the full budget would
    double peak memory under overlap.
    """
    from repro.core.lea import _PAIRWISE_RANK_MAX_N

    if pipeline:
        budget_bytes //= PIPELINE_DEPTH
    b = group.batch.rows
    if mesh is not None:
        b = math.ceil(b / mesh.devices.size)
    n = group.n_max
    s = len(group.strategies)
    a = len(throughput.allocator_strategies(group.strategies))
    per_round = 4 * b * (8 * (s + 2) * n)
    if n <= _PAIRWISE_RANK_MAX_N:
        per_round += 4 * b * (a * n * n)
    chunk = max(1, budget_bytes // max(per_round, 1))
    return None if chunk >= group.rounds else int(chunk)


def run(
    family_or_scenarios,
    *,
    seeds: int = 1,
    mesh: Mesh | None = None,
    round_chunk: int | None = None,
    tap: bool = False,
    tap_stride: int | None = None,
    pipeline: bool = False,
    **params,
):
    """The one-liner: expand -> group -> execute -> summarize.

    ``family_or_scenarios`` is a registered family name (with ``**params``
    forwarded to its expansion) or an iterable of
    :class:`~repro.sweeps.registry.Scenario`.  Returns a list of
    :class:`~repro.sweeps.results.ScenarioResult` in scenario order.
    """
    from . import results as results_mod
    from .registry import build_groups, expand

    if isinstance(family_or_scenarios, str):
        scenarios = expand(family_or_scenarios, **params)
    else:
        if params:
            raise TypeError("family params only apply to a named family")
        scenarios = tuple(family_or_scenarios)
    groups = build_groups(scenarios, seeds=seeds)
    succs = run_groups(groups, mesh=mesh, round_chunk=round_chunk,
                       tap=tap, tap_stride=tap_stride, pipeline=pipeline)
    return results_mod.summarize(groups, succs, scenario_order=scenarios)


def _slice_group_rows(group: SweepGroup, process_id: int,
                      num_processes: int) -> SweepGroup:
    """The sub-group of rows ``r`` with ``r % num_processes == process_id``.

    Rows are vmapped independently by the engine, so computing a row subset
    yields the SAME bits per row as the full batch (the padding argument in
    the module docstring, applied to interleaved selection instead) — the
    merged multi-host result is bit-identical to single-host.  Interleaving
    (not contiguous split) balances seeds/scenarios across hosts.
    """
    import dataclasses as _dc

    batch = jax.tree.map(lambda x: x[process_id::num_processes], group.batch)
    rows = tuple(group.rows[process_id::num_processes])
    return _dc.replace(group, batch=batch, rows=rows)


def run_multihost(
    family_or_scenarios,
    *,
    spool_dir,
    seeds: int = 1,
    mesh: Mesh | None = None,
    round_chunk: int | None = None,
    pipeline: bool = False,
    timeout_s: float = 600.0,
    **params,
):
    """:func:`run` over a ``jax.distributed`` grid: per-host row shards,
    host-0 merge.

    Every process expands the same deterministic scenario list and group
    composition, computes the interleaved row shard ``rows[pid::P]`` of
    every group ON ITS LOCAL DEVICES (same engine, same executor path —
    ``pipeline=`` selects the async loop per host), and publishes it to
    ``spool_dir`` via atomic renames
    (:func:`repro.sweeps.results.write_row_shard`).  Process 0 merges the
    shards back into full row order, summarizes, and returns the scenario
    results; every other process returns ``None``.

    World size comes from :func:`repro.launch.mesh.world`; at world=1 this
    IS :func:`run` (no spool, no merge — the degeneration the tests pin to
    bit-identical manifests).
    """
    from repro.launch import mesh as mesh_mod

    from . import results as results_mod
    from .registry import build_groups, expand

    pid, nprocs = mesh_mod.world()
    if nprocs == 1:
        return run(family_or_scenarios, seeds=seeds, mesh=mesh,
                   round_chunk=round_chunk, pipeline=pipeline, **params)

    if isinstance(family_or_scenarios, str):
        scenarios = expand(family_or_scenarios, **params)
    else:
        if params:
            raise TypeError("family params only apply to a named family")
        scenarios = tuple(family_or_scenarios)
    groups = build_groups(scenarios, seeds=seeds)
    for gi, group in enumerate(groups):
        sub = _slice_group_rows(group, pid, nprocs)
        if sub.batch.rows == 0:      # more hosts than rows: empty shard
            succ = np.zeros((0, group.rounds, len(group.strategies)), bool)
        else:
            succ = run_group(sub, mesh=mesh, round_chunk=round_chunk,
                             pipeline=pipeline)
        results_mod.write_row_shard(spool_dir, gi, pid, nprocs, succ)
    if pid != 0:
        return None
    succs = [
        results_mod.merge_row_shards(spool_dir, gi, nprocs, timeout_s=timeout_s)
        for gi in range(len(groups))
    ]
    return results_mod.summarize(groups, succs, scenario_order=scenarios)

"""The streaming serving engine: one compiled ``lax.scan`` over rounds.

Turns the offline sweep engine into an online service simulator: a
continuous arrival process feeds a fixed-capacity device-resident request
queue; every round the worker pool is split across the active queue slots
by greedy EDF water-filling (:func:`repro.core.lea.allocate_queue`), slots
are scored with the engine's on-time rule, and completed / expired
requests leave with full accounting (:class:`ServingOutcomes`).

Engine discipline (all inherited, none re-invented):

  * PRNG — the preamble is :func:`repro.core.throughput.serve_rollout`:
    the same ``split(key)``, masked trajectory and policy-stream
    ``fold_in`` as the offline engine, with arrivals on their own
    :func:`repro.serving.arrivals.arrival_key` stream and faults on
    :func:`repro.faults.channels.fault_key` — so a single-slot queue fed
    one always-admitted request per round with ``deadline_rel = 0``
    reproduces :func:`~repro.core.throughput.simulate_strategies_pool`
    BIT-IDENTICALLY on the same key, and a zero-arrival run leaves every
    engine stream untouched (both property-tested);
  * scoring — ``loads/speed <= t_cut + 1e-9`` per slot, the engine rule
    verbatim; ``t_cut`` is the deadline unless a ``repro.faults`` channel
    degrades it (time-axis injectors only: ``crash_restart``/``preempt``;
    packet-axis injectors are REJECTED loudly, never silently ignored);
  * accounting — every request ends in exactly one disposition:

        arrivals == admitted + rejected
        admitted == served_on_time + served_late + expired + in_flight

    (the never-silently-drop convention; asserted in tests/serving/).

Round order inside the scan body: (1) admit this round's arrivals (they
may be served the same round, like the offline engine's one-round jobs);
(2) allocate over active slots in EDF order; (3) score; (4) retire —
completions by ``deadline_abs`` are on time, completions within ``grace``
extra rounds are late, uncompleted requests past ``deadline_abs + grace``
expire; freed slots are recycled immediately.

:func:`sweep_serving` vmaps the whole thing over (B,) rows — keys, chains,
request specs, arrival-process and channel parameters are all traced — so
an arrival-rate x deadline grid (the ``arrival_grid`` family), admit-all
AND admission-controlled variants included, compiles ONCE per static
``(rounds, strategies, capacity, grace)`` signature
(:func:`serving_compile_cache_size` is the counter the tests and
``benchmarks/bench_serving.py`` assert on).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import lea as lea_mod
from repro.core import throughput
from repro.obs import counters as _obs_counters
from repro.obs.profiling import phase as _phase
from repro.obs.telemetry import ServingTelemetry

from . import admission
from . import arrivals as arrivals_mod
from . import queue as rqueue

# event codes emitted per (round, slot)
EVENT_NONE, EVENT_ON_TIME, EVENT_LATE, EVENT_EXPIRED = 0, 1, 2, 3

# fault injectors that act on the time axis (t_cut) — the only ones the
# serving scorer consumes; packet-axis injectors would be silently inert
_TIME_INJECTORS = frozenset({"crash_restart", "preempt"})


class ServingOutcomes(NamedTuple):
    """Per-strategy serving accounting over one simulation.

    Counters are (S,) int32 (leading batch axes under :func:`sweep_serving`);
    ``events`` / ``sojourn`` are (S, rounds, Q) per-slot streams: the event
    code (EVENT_*) of any request LEAVING that slot that round, and its
    sojourn time ``t - arrival + 1`` in rounds (0 where no event) — the raw
    material for latency percentiles.

    Conservation (every request in exactly one disposition):
    ``arrivals == admitted + rejected`` and
    ``admitted == served_on_time + served_late + expired + in_flight``.
    """

    arrivals: jnp.ndarray
    admitted: jnp.ndarray
    served_on_time: jnp.ndarray
    served_late: jnp.ndarray
    rejected: jnp.ndarray
    expired: jnp.ndarray
    in_flight: jnp.ndarray
    events: jnp.ndarray
    sojourn: jnp.ndarray


class _Counters(NamedTuple):
    admitted: jnp.ndarray
    served_on_time: jnp.ndarray
    served_late: jnp.ndarray
    rejected: jnp.ndarray
    expired: jnp.ndarray


def _check_channel(channel) -> None:
    for inj in channel:
        name = getattr(type(inj), "injector_name", type(inj).__name__)
        if name not in _TIME_INJECTORS:
            raise ValueError(
                f"serving consumes the time axis (t_cut) of a fault trace "
                f"only; injector {name!r} acts on the packet axis and would "
                f"be silently ignored — use one of "
                f"{sorted(_TIME_INJECTORS)} or score packets through "
                f"repro.faults.engine instead"
            )


def _ceil_div(num, den):
    return -((-jnp.asarray(num, jnp.int32)) // jnp.maximum(
        jnp.asarray(den, jnp.int32), 1
    ))


def _simulate_serving_impl(
    key, pool_mask, p_gg, p_bb, mu_g, mu_b, deadline, spec, process, channel,
    rounds, strategies, capacity, grace, telemetry=False,
    tap=False, tap_stride=None, tap_row=None,
):
    states, p_alloc = throughput.serve_rollout(
        key, pool_mask, p_gg, p_bb, rounds, strategies
    )                                             # (M, n), (A, M, n)
    n = states.shape[-1]

    # -- per-round request spec rows (traced; scalars broadcast)
    as_rounds = lambda v, dt: jnp.broadcast_to(jnp.asarray(v, dt), (rounds,))
    ks_m = as_rounds(spec.kstar, jnp.int32)
    eg_m = as_rounds(spec.ell_g, jnp.int32)
    eb_m = as_rounds(spec.ell_b, jnp.int32)
    dl_m = as_rounds(spec.deadline_rel, jnp.int32)
    thr_m = as_rounds(spec.admit_threshold, jnp.float32)
    cap_m = as_rounds(spec.reserve_cap, jnp.float32)

    # -- arrival stream (dedicated key tag; never perturbs engine streams)
    counts = arrivals_mod.sample_arrivals(key, process, rounds)    # (M,)

    # -- compute-cutoff times: the deadline, optionally degraded by a
    #    time-axis fault channel on the dedicated fault stream
    _check_channel(channel)
    if len(channel):
        from repro.faults.channels import apply_channel, base_trace, fault_key

        trace = base_trace(rounds, n, 1, 1, deadline)
        t_cut = apply_channel(fault_key(key), channel, trace).t_cut
    else:
        t_cut = jnp.full((rounds, n), deadline, jnp.float32)       # (M, n)

    # -- admission prediction gate, ONE batched DP over (A, M) rows
    with _phase("admission_dp"):
        p_succ = admission.predicted_success(
            p_alloc, pool_mask, ks_m, eg_m, eb_m
        )                                         # (A, M)

    n_valid = jnp.sum(pool_mask.astype(jnp.int32))
    t_idx = jnp.arange(rounds, dtype=jnp.int32)

    def body(carry, xs):
        q, cnt = carry
        (t, states_t, p_t, p_succ_t, count_t, ks_t, eg_t, eb_t, dl_t,
         thr_t, cap_t, tcut_t) = xs
        # (1) admission: prediction gate x capacity gate x free slots
        with _phase("admit"):
            m_active = admission.minimal_demand(q.occupied, q.kstar, q.ell_g)
            room = admission.admission_room(
                m_active, _ceil_div(ks_t, eg_t), n_valid, cap_t
            )
            want = jnp.where(
                p_succ_t >= thr_t, jnp.minimum(count_t, room), 0
            )
            q, n_admit = rqueue.admit(q, t, want, ks_t, eg_t, eb_t, dl_t)
        # (2) multi-job allocation: greedy EDF water-filling
        with _phase("allocate"):
            with _phase("edf_order"):
                order = rqueue.edf_order(q)
            loads, _i_star, feas = lea_mod.allocate_queue(
                p_t, pool_mask, q.occupied, q.kstar, q.ell_g, q.ell_b, order,
            )                                     # (Q, n), (Q,), (Q,)
        # (3) score: the engine's on-time rule, per slot
        with _phase("score"):
            speeds = jnp.where(states_t == 1, mu_g, mu_b)          # (n,)
            on_time = loads.astype(jnp.float32) / speeds <= tcut_t + 1e-9
            received = jnp.sum(jnp.where(on_time, loads, 0), axis=-1)  # (Q,)
        complete = q.occupied & feas & (received >= q.kstar)
        # (4) disposition
        done_on_time = complete & (t <= q.deadline_abs)
        done_late = complete & (t > q.deadline_abs)
        overdue = q.occupied & ~complete & (t >= q.deadline_abs + grace)
        leave = complete | overdue
        event_t = (
            jnp.where(done_on_time, EVENT_ON_TIME, 0)
            + jnp.where(done_late, EVENT_LATE, 0)
            + jnp.where(overdue, EVENT_EXPIRED, 0)
        ).astype(jnp.int32)
        sojourn_t = jnp.where(leave, t - q.arrival + 1, 0)
        with _phase("release"):
            q = rqueue.release(q, leave)
        count_i = lambda m: jnp.sum(m.astype(jnp.int32))
        cnt = _Counters(
            admitted=cnt.admitted + n_admit,
            served_on_time=cnt.served_on_time + count_i(done_on_time),
            served_late=cnt.served_late + count_i(done_late),
            rejected=cnt.rejected + (count_t - n_admit),
            expired=cnt.expired + count_i(overdue),
        )
        if not telemetry:
            return (q, cnt), (event_t, sojourn_t)
        # extra per-round scan outputs: queue occupancy after departures
        # and the round's admission decisions (same traced values, so the
        # primary streams above are untouched)
        occ_t = jnp.sum(q.occupied.astype(jnp.int32))
        return (q, cnt), (event_t, sojourn_t, occ_t, n_admit,
                          count_t - n_admit)

    def run_one(p_a, p_succ_a, strat_i):
        zero = jnp.int32(0)
        carry0 = (
            rqueue.empty_queue(capacity),
            _Counters(zero, zero, zero, zero, zero),
        )
        xs = (t_idx, states, p_a, p_succ_a, counts, ks_m, eg_m, eb_m,
              dl_m, thr_m, cap_m, t_cut)
        if not tap:
            (q_f, cnt), ys = jax.lax.scan(body, carry0, xs=xs)
            return cnt, jnp.sum(q_f.occupied.astype(jnp.int32)), ys
        # tap=True: the ONE scan becomes a trace-time chain of per-block
        # scans of the SAME body over a partition of the same xs — the
        # carry threads through unchanged and the ys concatenate, so every
        # output is bit-identical — with a block-aggregate emit between
        # segments.  (io_callback cannot be cond-gated here: the body runs
        # under vmap — over strategies and sweep rows — and jax rejects IO
        # effects in vmap-of-cond; segmenting needs no cond at all.)
        from repro.obs import taps as _taps

        stride = _taps.resolve_stride(rounds, tap_stride)
        row = (jnp.int32(-1) if tap_row is None
               else jnp.asarray(tap_row, jnp.int32))
        carry, token, ys_blocks, done = carry0, None, [], 0
        for bi, bound in enumerate(_taps.stride_boundaries(rounds, stride)):
            xs_b = jax.tree.map(lambda x: x[done:bound], xs)
            carry, ys_b = jax.lax.scan(body, carry, xs=xs_b)
            ys_blocks.append(ys_b)
            q_b, cnt_b = carry
            token = _taps.emit(
                "serving", token=token,
                block=jnp.int32(bi), row=row,
                strategy=jnp.asarray(strat_i, jnp.int32),
                rounds_done=jnp.int32(bound),
                admitted_so_far=cnt_b.admitted,
                served_on_time_so_far=cnt_b.served_on_time,
                served_late_so_far=cnt_b.served_late,
                rejected_so_far=cnt_b.rejected,
                expired_so_far=cnt_b.expired,
                occupancy=jnp.sum(q_b.occupied.astype(jnp.int32)),
            )
            done = bound
        q_f, cnt = carry
        ys = jax.tree.map(
            lambda *bs: jnp.concatenate(bs, axis=0), *ys_blocks
        )
        return cnt, jnp.sum(q_f.occupied.astype(jnp.int32)), ys

    strat_idx = jnp.arange(len(strategies), dtype=jnp.int32)
    cnt, in_flight, ys = jax.vmap(run_one)(p_alloc, p_succ, strat_idx)
    events, sojourn = ys[0], ys[1]
    n_strat = len(strategies)
    outcomes = ServingOutcomes(
        arrivals=jnp.broadcast_to(jnp.sum(counts), (n_strat,)),
        admitted=cnt.admitted,
        served_on_time=cnt.served_on_time,
        served_late=cnt.served_late,
        rejected=cnt.rejected,
        expired=cnt.expired,
        in_flight=in_flight,
        events=events,
        sojourn=sojourn,
    )
    if not telemetry:
        return outcomes
    occ, admit_t, rej_t = ys[2], ys[3], ys[4]
    return outcomes, ServingTelemetry(
        arrivals_t=counts,
        occupancy=occ,
        admitted_t=admit_t,
        rejected_t=rej_t,
    )


@partial(jax.jit, static_argnames=("rounds", "strategies", "capacity",
                                   "grace", "telemetry", "tap", "tap_stride"))
def simulate_serving(
    key: jax.Array,
    pool_mask: jnp.ndarray,
    p_gg: jnp.ndarray,
    p_bb: jnp.ndarray,
    mu_g,
    mu_b,
    deadline,
    spec: rqueue.RequestSpec,
    process,
    *,
    rounds: int,
    strategies: tuple[str, ...] = ("lea",),
    capacity: int = 4,
    grace: int = 0,
    channel: tuple = (),
    telemetry: bool = False,
    tap: bool = False,
    tap_stride: int | None = None,
):
    """One serving simulation (see module docstring).

    ``pool_mask`` (n,) bool marks real workers; ``spec`` is a
    :class:`~repro.serving.queue.RequestSpec` of traced scalars or
    (rounds,) rows; ``process`` a registered arrival process
    (:mod:`repro.serving.arrivals`); ``strategies`` unique policy names
    (static draws are rejected — serving allocates from predictions);
    ``channel`` an optional time-axis ``repro.faults`` channel.
    ``capacity`` (queue slots) and ``grace`` (late-completion window in
    rounds) are static.

    ``telemetry`` (static): True returns ``(ServingOutcomes,
    ServingTelemetry)`` — per-round arrivals, queue occupancy and
    admission decisions out of the same compiled scan; False (default) is
    the pre-existing path, bit-identical.

    ``tap`` (static): True streams per-(strategy) block aggregates —
    admissions, served-on-time/late, rejections, expiries, occupancy so
    far — to the host every ``tap_stride`` rounds WHILE the scan runs
    (:mod:`repro.obs.taps`): the round scan is segmented at trace time
    into equivalent per-block scans with an ``io_callback`` emit between
    segments, so outputs stay bit-identical and ``tap=False`` traces zero
    callbacks (one compile per static signature either way).
    """
    return _simulate_serving_impl(
        key, pool_mask, p_gg, p_bb, mu_g, mu_b, deadline, spec, process,
        channel, rounds, tuple(strategies), capacity, grace, telemetry,
        tap, tap_stride,
    )


@partial(jax.jit, static_argnames=("rounds", "strategies", "capacity",
                                   "grace", "telemetry", "tap", "tap_stride"))
def _run_serving_group(
    keys, pool_mask, p_gg, p_bb, mu_g, mu_b, deadline, spec, process, channel,
    *, rounds, strategies, capacity, grace, telemetry=False,
    tap=False, tap_stride=None,
):
    """(B,) rows -> ServingOutcomes of (B, S, ...) leaves, ONE computation."""
    fn = lambda k, m, pg, pb, mg, mb, d, sp, pr, ri: _simulate_serving_impl(
        k, m, pg, pb, mg, mb, d, sp, pr, channel,
        rounds, strategies, capacity, grace, telemetry, tap, tap_stride, ri,
    )
    if tap:
        rows = jnp.arange(keys.shape[0], dtype=jnp.int32)
        return jax.vmap(fn)(keys, pool_mask, p_gg, p_bb, mu_g, mu_b,
                            deadline, spec, process, rows)
    return jax.vmap(
        lambda k, m, pg, pb, mg, mb, d, sp, pr: fn(
            k, m, pg, pb, mg, mb, d, sp, pr, None
        )
    )(keys, pool_mask, p_gg, p_bb, mu_g, mu_b, deadline, spec, process)


_obs_counters.register_compiled("serving.sweep", _run_serving_group)
_obs_counters.register_compiled("serving.simulate", simulate_serving)


def serving_compile_cache_size() -> int:
    """Distinct serving-group computations compiled so far.

    Thin alias over the unified obs counter
    (``obs.compile_events("serving.sweep")``) — kept for the pre-obs tests
    and benchmarks."""
    return _obs_counters.compile_events("serving.sweep")


def sweep_serving(
    keys: jnp.ndarray,
    pool_mask: jnp.ndarray,
    p_gg: jnp.ndarray,
    p_bb: jnp.ndarray,
    mu_g,
    mu_b,
    deadline,
    spec: rqueue.RequestSpec,
    process,
    *,
    rounds: int,
    strategies: tuple[str, ...] = ("lea",),
    capacity: int = 4,
    grace: int = 0,
    channel: tuple = (),
    telemetry: bool = False,
    tap: bool = False,
    tap_stride: int | None = None,
):
    """Batched :func:`simulate_serving`: every leaf carries a leading (B,).

    ``spec`` leaves and ``process`` parameters are (B,) traced rows (scalars
    broadcast), so a whole arrival-rate x deadline x admission grid fuses
    into ONE compile per static (rounds, strategies, capacity, grace)
    signature.  The fault ``channel`` (if any) is shared across rows with
    scalar parameters (per-row channel grids belong to
    :func:`repro.faults.engine.sweep_faults`).  ``telemetry=True`` returns
    ``(ServingOutcomes, ServingTelemetry)`` with a leading (B,) on every
    telemetry leaf — still ONE compile for the whole grid.  ``tap=True``
    streams per-(row, strategy) block aggregates mid-scan (see
    :func:`simulate_serving`) — same one-compile contract, outputs
    bit-identical.
    """
    strategies = tuple(strategies)
    b = p_gg.shape[0]
    as_b = lambda x, dt: jnp.broadcast_to(jnp.asarray(x, dt), (b,))
    spec = rqueue.RequestSpec(
        kstar=as_b(spec.kstar, jnp.int32),
        ell_g=as_b(spec.ell_g, jnp.int32),
        ell_b=as_b(spec.ell_b, jnp.int32),
        deadline_rel=as_b(spec.deadline_rel, jnp.int32),
        admit_threshold=as_b(spec.admit_threshold, jnp.float32),
        reserve_cap=as_b(spec.reserve_cap, jnp.float32),
    )
    process = jax.tree.map(lambda x: as_b(x, jnp.float32), process)
    return _run_serving_group(
        keys, pool_mask, p_gg, p_bb,
        as_b(mu_g, jnp.float32), as_b(mu_b, jnp.float32),
        as_b(deadline, jnp.float32), spec, process, channel,
        rounds=rounds, strategies=strategies, capacity=capacity, grace=grace,
        telemetry=telemetry, tap=tap, tap_stride=tap_stride,
    )

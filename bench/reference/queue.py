"""Plain reference of the streaming service: one request queue, round by round.

Each round, in this order: (1) this round's arrivals are admitted while the
fleet's predicted success for a fresh request clears the admission
threshold, the queue's summed minimal worker demand stays within
``reserve_cap`` of the pool, and slots are free (newcomers take the lowest
free slots); (2) the live requests, most urgent first (deadline, then
arrival, then slot), each get a contiguous run of the workers sorted by
predicted good probability: at least their minimal demand ceil(K* / ell_g),
and the most urgent one every worker the later ones do not need; each run
gets its own LEA two-level loads; (3) a request completes when the
evaluations that meet the deadline reach its K*; (4) completed requests
leave, on time or late, and a request past its deadline plus ``grace``
expires. Counted: arrivals, admitted, rejected, on time, late, expired.

Imports nothing of the program. Worker chains and the LEA estimator come
from :mod:`reference.fleet`; arrivals are ``jax.random.poisson`` draws on
the arrival stream of the row key (``fold_in`` with the arrival tag).
"""

from __future__ import annotations

import jax
import numpy as np

from . import fleet

ARRIVAL_TAG = 0x5BD1E995 % (2**31)
EVENT_ON_TIME, EVENT_LATE, EVENT_EXPIRED = 1, 2, 3


def arrivals(key: np.ndarray, rate: float, rounds: int) -> np.ndarray:
    k = jax.random.fold_in(key, ARRIVAL_TAG)
    return np.asarray(jax.random.poisson(k, np.float32(rate), (rounds,)), np.int64)


def best_prefix_success(p_good: np.ndarray, kstar, ell_g, ell_b,
                        dtype=np.float64) -> np.ndarray:
    """Max over prefixes of the success probability on the whole pool."""
    rows, n = p_good.shape
    order = np.argsort(-p_good, axis=-1, kind="stable")
    p_sorted = np.take_along_axis(p_good, order, axis=-1)
    w = fleet.prefix_thresholds(np.full(rows, kstar), np.full(rows, ell_g),
                                np.full(rows, ell_b), np.full(rows, n), n)
    return fleet.prefix_tails(p_sorted, w, dtype).max(axis=-1)


def serve_row(key: np.ndarray, p_gg, p_bb, mu_g, mu_b, deadline, kstar: int,
              ell_g: int, ell_b: int, rate: float, deadline_rel: int,
              admit_threshold: float, reserve_cap: float, capacity: int,
              grace: int, rounds: int, dtype=np.float64,
              est_dtype=np.float32) -> dict:
    """One row of the service: its counters and the (rounds, capacity) event
    and sojourn streams."""
    k_traj, _ = fleet.split(key)
    states = fleet.trajectory(k_traj, p_gg, p_bb, rounds)
    n = states.shape[1]
    p_alloc = fleet.lea_p_good(states, est_dtype)
    counts = arrivals(key, rate, rounds)
    p_succ = best_prefix_success(p_alloc, kstar, ell_g, ell_b, dtype)
    thr = np.float32(admit_threshold)
    m_new = -(-kstar // ell_g)
    budget = int(np.clip(np.float32(reserve_cap) * np.float32(n), 0.0, 2.0**30))

    occupied = np.zeros(capacity, bool)
    dl_abs = np.zeros(capacity, np.int64)
    arrived = np.zeros(capacity, np.int64)
    events = np.zeros((rounds, capacity), np.int64)
    sojourn = np.zeros((rounds, capacity), np.int64)
    tally = dict(arrivals=0, admitted=0, rejected=0, on_time=0, late=0, expired=0)
    slots = np.arange(capacity)
    for t in range(rounds):
        # (1) admission
        room = max(budget - m_new * int(occupied.sum()), 0) // m_new
        want = min(int(counts[t]), room) if p_succ[t] >= thr else 0
        free = np.flatnonzero(~occupied)
        take = free[:min(want, free.size)]
        occupied[take] = True
        dl_abs[take] = t + deadline_rel
        arrived[take] = t
        tally["arrivals"] += int(counts[t])
        tally["admitted"] += take.size
        tally["rejected"] += int(counts[t]) - take.size
        # (2) allocation: EDF over the live slots, one run of ranks each
        live = slots[occupied]
        order = live[np.lexsort((live, arrived[live], dl_abs[live]))]
        ranks = np.argsort(np.argsort(-p_alloc[t], kind="stable"), kind="stable")
        received = np.zeros(capacity, np.int64)
        feasible = np.zeros(capacity, bool)
        remaining = n
        for pos, j in enumerate(order):
            reserve_after = m_new * (len(order) - pos - 1)
            size = min(max(m_new, remaining - reserve_after), remaining)
            start = n - remaining
            remaining -= size
            seg = (ranks >= start) & (ranks < start + size)
            loads, _, feas = fleet.allocate(p_alloc[t][None], kstar, ell_g, ell_b,
                                            seg[None], dtype)
            feasible[j] = feas[0]
            received[j] = fleet.on_time_received(loads, states[t][None], mu_g,
                                                 mu_b, deadline)[0]
        # (3) completion and (4) disposition
        complete = occupied & feasible & (received >= kstar)
        on_time = complete & (t <= dl_abs)
        late = complete & (t > dl_abs)
        overdue = occupied & ~complete & (t >= dl_abs + grace)
        leave = complete | overdue
        events[t] = (np.where(on_time, EVENT_ON_TIME, 0) + np.where(late, EVENT_LATE, 0)
                     + np.where(overdue, EVENT_EXPIRED, 0))
        sojourn[t] = np.where(leave, t - arrived + 1, 0)
        tally["on_time"] += int(on_time.sum())
        tally["late"] += int(late.sum())
        tally["expired"] += int(overdue.sum())
        occupied &= ~leave
    tally["in_flight"] = int(occupied.sum())
    return {"counters": tally, "events": events, "sojourn": sojourn}

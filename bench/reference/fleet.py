"""Plain reference of the scheduler, one simulated row at a time, in numpy.

What the paper (arXiv 1904.05522, Sec. 3-4) defines, written out directly:
two-state Markov worker chains started from their stationary law, the LEA
estimator (add-one smoothed transition counts, Sec. 3.2 phase 4), the
Poisson-binomial success probability of every prefix of the workers sorted
by predicted good probability (eq. 7/8), the argmax prefix getting the good
load and the rest the bad load (Lemma 4.5), and the on-time rule
``load / speed <= deadline``.

It imports nothing of the program under test. The random draws follow the
simulation's documented key discipline on ``jax.random`` threefry keys (a
row key splits into a trajectory key and a round key; the trajectory key
into the initial draw and one key per transition; the round key into one key
per round), so the reference sees the same worker states and the same
static draws as the program. ``dtype`` sets the precision of the estimator
and of the dynamic programme: the reference runs them in float32 and
float64; the control of the correctness check runs both in bfloat16.
"""

from __future__ import annotations

import jax
import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16
STRATEGIES = ("lea", "static_single")


# -- keys and draws ---------------------------------------------------------

def seed_key(seed: int) -> np.ndarray:
    return np.asarray(jax.random.PRNGKey(seed))


def row_key(base: np.ndarray, repeat: int) -> np.ndarray:
    """Repeat 0 is the scenario's own key; later repeats fold the index in."""
    if repeat == 0:
        return base
    return np.asarray(jax.random.fold_in(base, repeat))


def split(key: np.ndarray, num: int = 2) -> np.ndarray:
    return np.asarray(jax.random.split(key, num))


def uniforms(keys: np.ndarray, n: int) -> np.ndarray:
    """One (n,) float32 uniform draw per key: (len(keys), n)."""
    return np.asarray(jax.vmap(lambda k: jax.random.uniform(k, (n,)))(keys))


# -- worker chains and the LEA estimator -----------------------------------

def stationary_good(p_gg: np.ndarray, p_bb: np.ndarray) -> np.ndarray:
    """pi_g = (1 - p_bb) / (2 - p_gg - p_bb), float32."""
    one = np.float32(1.0)
    return (one - p_bb) / (np.float32(2.0) - p_gg - p_bb)


def t_step_chain(p_gg: float, p_bb: float, t: int) -> tuple[float, float]:
    """(p_gg, p_bb) of the t-step chain, float32, in closed form."""
    p_gg, p_bb = np.float32(p_gg), np.float32(p_bb)
    lam_t = (p_gg + p_bb - np.float32(1.0)) ** np.float32(t)
    pi_g = stationary_good(p_gg, p_bb)
    return (float(pi_g + (np.float32(1.0) - pi_g) * lam_t),
            float((np.float32(1.0) - pi_g) + pi_g * lam_t))


def ec2_scenario(config: dict, index: int) -> dict:
    """One (rows, k, lambda, d) scenario of the paper's Sec. 6.2 EC2 fleet.

    One request a round: a good worker clears its whole store of r chunks by
    the deadline, a bad one ``mu_b / mu_g`` of it, and the worker chain
    between two requests is the gap-step chain, gap = round((30 + lambda) /
    (10 d)) transitions.
    """
    rows, k, lam, d = config["scenarios"][index]
    n, r = config["n"], config["r"]
    ell_b = max(1, int(r * config["mu_b"] / config["mu_g"]))
    gap = max(1, int(round((30.0 + lam) / (10 * d))))
    p_gg, p_bb = t_step_chain(config["chain"]["p_gg"], config["chain"]["p_bb"], gap)
    return dict(n=n, kstar=(k - 1) * config["deg_f"] + 1, ell_g=r, ell_b=ell_b,
                p_gg=p_gg, p_bb=p_bb, mu_g=float(r), mu_b=float(ell_b), deadline=1.0)


def trajectory(key: np.ndarray, p_gg: np.ndarray, p_bb: np.ndarray,
               rounds: int) -> np.ndarray:
    """(rounds, n) int32 states, 1 = good, stepped one round at a time."""
    p_gg = np.asarray(p_gg, np.float32)
    p_bb = np.asarray(p_bb, np.float32)
    n = p_gg.shape[0]
    k0, k1 = split(key)
    s = (uniforms(k0[None], n)[0] < stationary_good(p_gg, p_bb)).astype(np.int32)
    out = np.empty((rounds, n), np.int32)
    out[0] = s
    if rounds > 1:
        u = uniforms(split(k1, rounds - 1), n)
        leave_bad = np.float32(1.0) - p_bb
        for t in range(1, rounds):
            s = np.where(s == 1, u[t - 1] < p_gg, u[t - 1] < leave_bad)
            out[t] = s
    return out


def lea_p_good(states: np.ndarray, dtype=np.float32) -> np.ndarray:
    """(M, n) predicted good probability entering each round.

    Round m uses the transitions seen among rounds 0..m-1 and the state of
    round m-1; round 0 has seen nothing and predicts 1/2.
    """
    m_rounds, n = states.shape
    prev, cur = states[:-1], states[1:]
    inc = np.stack([(prev == 1) & (cur == 1), (prev == 1) & (cur == 0),
                    (prev == 0) & (cur == 1), (prev == 0) & (cur == 0)],
                   axis=-1).astype(np.float64)
    seen = np.zeros((m_rounds, n, 4), np.float64)   # integer counts
    if m_rounds > 2:
        seen[2:] = np.cumsum(inc, axis=0)[:-1]
    c = seen.astype(dtype)
    one, two = dtype(1.0), dtype(2.0)
    p_gg = (c[..., 0] + one) / (c[..., 0] + c[..., 1] + two)
    p_bb = (c[..., 3] + one) / (c[..., 2] + c[..., 3] + two)
    last = np.concatenate([states[:1], states[:-1]], axis=0)
    p = np.where(last == 1, p_gg, one - p_bb).astype(dtype)
    p[0] = dtype(0.5)
    return p


# -- allocation --------------------------------------------------------------

def prefix_thresholds(kstar, ell_g, ell_b, n_valid, n: int) -> np.ndarray:
    """w(i) = ceil((K* - (n_valid - i) ell_b) / ell_g) for i = 1..n; prefixes
    past the valid pool get the impossible n + 1. Leading axes broadcast."""
    kstar, ell_g, ell_b, n_valid = (np.asarray(v, np.int64)[..., None]
                                    for v in (kstar, ell_g, ell_b, n_valid))
    i = np.arange(1, n + 1)
    w = -((-(kstar - (n_valid - i) * ell_b)) // ell_g)
    return np.where(i > n_valid, n + 1, w)


def prefix_tails(p_sorted: np.ndarray, w: np.ndarray, dtype=np.float64) -> np.ndarray:
    """P[count >= w(i)] over the first i sorted workers, for every prefix i;
    0 where w(i) > i (the prefix cannot reach K*)."""
    p = np.asarray(p_sorted).astype(dtype)
    rows, n = p.shape
    one, zero = dtype(1.0), dtype(0.0)
    counts = np.arange(n + 1)
    pmf = np.zeros((rows, n + 1), dtype)
    pmf[:, 0] = one
    out = np.zeros((rows, n), dtype)
    for i in range(n):
        p_i = p[:, i:i + 1]
        shifted = np.concatenate([np.zeros((rows, 1), dtype), pmf[:, :-1]], axis=1)
        pmf = pmf * (one - p_i) + shifted * p_i
        w_i = w[:, i:i + 1]
        tail = np.sum(np.where(counts >= np.maximum(w_i, 0), pmf, zero), axis=1)
        out[:, i] = np.where(w_i[:, 0] > i + 1, zero, tail)
    return out


def allocate(p_good: np.ndarray, kstar, ell_g, ell_b, mask: np.ndarray,
             dtype=np.float64) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """LEA loads over a (masked) pool: ``(loads (R, n), i_star (R,), feasible (R,))``.

    Masked workers sort last, take part in no prefix and get no load. Ties in
    the predicted probability keep worker order.
    """
    p_good = np.asarray(p_good)
    rows, n = p_good.shape
    mask = np.broadcast_to(mask, (rows, n))
    kstar, ell_g, ell_b = (np.broadcast_to(np.asarray(v, np.int64), (rows,))
                           for v in (kstar, ell_g, ell_b))
    p_eff = np.where(mask, p_good, p_good.dtype.type(-1.0))
    order = np.argsort(-p_eff, axis=-1, kind="stable")
    ranks = np.argsort(order, axis=-1, kind="stable")
    n_valid = mask.sum(axis=-1)
    i = np.arange(1, n + 1)
    p_sorted = np.take_along_axis(p_eff, order, axis=-1)
    p_dp = np.where(i <= n_valid[:, None], p_sorted, 0.0)
    w = prefix_thresholds(kstar, ell_g, ell_b, n_valid, n)
    tails = prefix_tails(p_dp, w, dtype)
    i_star = np.argmax(tails, axis=-1) + 1
    feasible = np.any((w <= i) & (i <= n_valid[:, None]), axis=-1)
    loads = np.where(ranks < i_star[:, None], ell_g[:, None], ell_b[:, None])
    return np.where(mask, loads, 0), i_star, feasible


def on_time_received(loads: np.ndarray, states: np.ndarray, mu_g, mu_b,
                     t_cut) -> np.ndarray:
    """Evaluations that arrive by the cut-off: a worker's whole load or none."""
    speeds = np.where(states == 1, np.float32(mu_g), np.float32(mu_b))
    cut = np.float32(t_cut) + np.float32(1e-9)
    on_time = loads.astype(np.float32) / speeds <= cut
    return np.sum(np.where(on_time, loads, 0), axis=-1)


# -- one sweep row -----------------------------------------------------------

def sweep_row(key: np.ndarray, p_gg, p_bb, mu_g, mu_b, deadline, kstar: int,
              ell_g: int, ell_b: int, rounds: int, strategies=STRATEGIES,
              dtype=np.float64, est_dtype=np.float32) -> np.ndarray:
    """(rounds, S) success indicators of one (scenario, seed) row."""
    k_traj, k_rounds = split(key)
    states = trajectory(k_traj, p_gg, p_bb, rounds)
    n = states.shape[1]
    mask = np.ones((n,), bool)
    out = []
    for s in strategies:
        if s == "lea":
            p = lea_p_good(states, est_dtype)
            loads, _, feasible = allocate(p, kstar, ell_g, ell_b, mask, dtype)
        elif s == "static_single":
            draw = uniforms(split(k_rounds, rounds), n)
            loads = np.where(draw < np.float32(0.5), ell_g, ell_b)
            feasible = np.ones((rounds,), bool)
        else:
            raise ValueError(f"no reference for strategy {s!r}")
        received = on_time_received(loads, states, mu_g, mu_b, deadline)
        out.append((received >= kstar) & feasible)
    return np.stack(out, axis=-1)


def lea_erasures(key: np.ndarray, p_gg, p_bb, mu_g, mu_b, deadline, kstar: int,
                 ell_g: int, ell_b: int, r: int, rounds: int) -> np.ndarray:
    """(rounds, n * r) chunk masks of a LEA rollout: worker i returns the
    first ``loads_i`` of its r coded chunks when its load meets the
    deadline, else none."""
    k_traj, _ = split(key)
    states = trajectory(k_traj, p_gg, p_bb, rounds)
    n = states.shape[1]
    loads, _, _ = allocate(lea_p_good(states), kstar, ell_g, ell_b,
                           np.ones((n,), bool))
    speeds = np.where(states == 1, np.float32(mu_g), np.float32(mu_b))
    done = np.where(loads.astype(np.float32) / speeds
                    <= np.float32(deadline) + np.float32(1e-9), loads, 0)
    return (np.arange(n * r) % r) < np.repeat(done, r, axis=-1)

"""Two-state Markov worker-speed model (Sec. 2.2 of the paper).

State convention throughout the codebase: ``1 = good``, ``0 = bad``.
Each worker i has transition probs ``p_gg[i] = P[good -> good]`` and
``p_bb[i] = P[bad -> bad]``; chains are mutually independent and initialized
from their stationary distribution (as in the paper).

Non-stationary chains (beyond the paper): the trajectory samplers also
accept ``p_gg``/``p_bb`` of shape (rounds, n) — row t governs the
transition INTO round t (t >= 1) and row 0 the initial distribution.  The
associative-scan sampler composes per-round transition maps anyway, so a
time-varying chain is the same parallel prefix with per-row thresholds;
stationary (n,) inputs take the exact original code path, bit-for-bit.

Mask-padded pools (the shape-polymorphic engine): the samplers accept an
optional ``worker_mask`` (n,) bool.  Masked (padding) workers are FROZEN —
pinned to the good state every round — so a padded pool is simulated at its
padded width with deterministic, inert extras.  The mask does not change
the PRNG geometry: draws are shaped (n,) over the padded width, exactly as
an unpadded width-n pool draws (``worker_mask=None`` and an all-True mask
are value-identical; a row padded from a NARROWER pool keeps the padded
width's stream — pool width has always been part of the stream geometry).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


# Pools at least this wide step their trajectory sequentially: for a v5e
# the associative scan's program compiled in time that grows with rounds x n
# (390 s for 32 rows of 2,000 rounds x 4,000 workers, against 20 s stepped),
# while a step over thousands of workers is work enough to hide the loop.
SEQUENTIAL_N = 128


def stationary_good_prob(p_gg: jnp.ndarray, p_bb: jnp.ndarray) -> jnp.ndarray:
    """pi_g = (1 - p_bb) / (2 - p_gg - p_bb) for an irreducible 2-state chain."""
    return (1.0 - p_bb) / (2.0 - p_gg - p_bb)


def initial_states(
    key: jax.Array,
    p_gg: jnp.ndarray,
    p_bb: jnp.ndarray,
    worker_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Sample worker states (n,) int32 from the stationary distribution.

    A (rounds, n) schedule initializes from its round-0 chain.  Masked
    workers (``worker_mask`` False) are pinned to the good state.
    """
    if p_gg.ndim == 2:
        p_gg, p_bb = p_gg[0], p_bb[0]
    pi_g = stationary_good_prob(p_gg, p_bb)
    s0 = (jax.random.uniform(key, p_gg.shape) < pi_g).astype(jnp.int32)
    if worker_mask is None:
        return s0
    return jnp.where(worker_mask, s0, 1)


def step_states(
    key: jax.Array, states: jnp.ndarray, p_gg: jnp.ndarray, p_bb: jnp.ndarray
) -> jnp.ndarray:
    """One Markov transition for all n workers."""
    u = jax.random.uniform(key, states.shape)
    stay_good = u < p_gg
    leave_bad = u < (1.0 - p_bb)
    return jnp.where(states == 1, stay_good, leave_bad).astype(jnp.int32)


@partial(jax.jit, static_argnames=("rounds",))
def sample_trajectory_scan(
    key: jax.Array,
    p_gg: jnp.ndarray,
    p_bb: jnp.ndarray,
    rounds: int,
    worker_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Sequential reference: (rounds, n) trajectory via ``lax.scan``.

    Kept as the oracle for :func:`sample_trajectory` (the associative-scan
    path), which must reproduce it bit-for-bit.  Accepts a (rounds, n)
    time-varying schedule like the parallel sampler, and an optional
    ``worker_mask`` freezing masked workers in the good state.
    """
    k0, k1 = jax.random.split(key)
    s0 = initial_states(k0, p_gg, p_bb)
    keys = jax.random.split(k1, rounds - 1)

    if p_gg.ndim == 2:
        def body_tv(carry, xs):
            k, pg, pb = xs
            s = step_states(k, carry, pg, pb)
            return s, s

        _, tail = jax.lax.scan(body_tv, s0, (keys, p_gg[1:], p_bb[1:]))
        traj = jnp.concatenate([s0[None], tail], axis=0)
    else:
        def body(carry, k):
            s = step_states(k, carry, p_gg, p_bb)
            return s, s

        _, tail = jax.lax.scan(body, s0, keys)
        traj = jnp.concatenate([s0[None], tail], axis=0)
    if worker_mask is None:
        return traj
    return jnp.where(worker_mask, traj, 1)


@partial(jax.jit, static_argnames=("rounds",))
def sample_trajectory(
    key: jax.Array,
    p_gg: jnp.ndarray,
    p_bb: jnp.ndarray,
    rounds: int,
    worker_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """(rounds, n) int32 state trajectory, initial state from stationary dist.

    ``worker_mask`` (n,) bool freezes masked workers in the good state
    (``None`` and an all-True mask are value-identical; the mask never
    changes the PRNG draw geometry — see the module docstring).

    Parallel-prefix formulation: round t's transition is a map {0,1} -> {0,1}
    fully determined by its uniform draw ``u_t`` —

        f_t(s) = [u_t < p_gg]  if s == 1  else  [u_t < 1 - p_bb]

    i.e. the pair ``(to1_if_bad, to1_if_good) = ([u_t < 1-p_bb], [u_t < p_gg])``
    (exactly :func:`step_states` on both possible inputs).  Function
    composition of such maps is associative, so the prefix compositions
    ``f_t ∘ ... ∘ f_1`` come from one ``lax.associative_scan`` (O(log M)
    depth instead of the M-step scan — the last sequential computation in the
    batched Monte-Carlo engine).  Applying prefix t to the stationary draw s0
    gives state t.  Every round consumes the same per-key uniform draw and the
    composition is pure boolean selection, so trajectories are bit-identical
    to :func:`sample_trajectory_scan` on the same key.  Pools of
    ``SEQUENTIAL_N`` workers or more take that sequential sampler itself.
    """
    if p_gg.shape[-1] >= SEQUENTIAL_N:
        return sample_trajectory_scan(key, p_gg, p_bb, rounds, worker_mask)
    k0, k1 = jax.random.split(key)
    s0 = initial_states(k0, p_gg, p_bb)
    if rounds == 1:
        traj = s0[None]
        return traj if worker_mask is None else jnp.where(worker_mask, traj, 1)

    # per-step thresholds: a (rounds, n) schedule contributes rows 1..M-1
    # (row t is the chain in force for the transition into round t); the
    # stationary (n,) case broadcasts one row over all steps as before.
    n_shape = p_gg.shape[-1:]
    p_step_gg = p_gg[1:] if p_gg.ndim == 2 else p_gg
    p_step_bb = p_bb[1:] if p_bb.ndim == 2 else p_bb
    keys = jax.random.split(k1, rounds - 1)
    u = jax.vmap(lambda k: jax.random.uniform(k, n_shape))(keys)  # (M-1, n)
    # f_t as a value table: out1[t] = f_t(good), out0[t] = f_t(bad)
    out1 = (u < p_step_gg).astype(jnp.int32)
    out0 = (u < (1.0 - p_step_bb)).astype(jnp.int32)

    pref0, pref1 = jax.lax.associative_scan(_compose_maps, (out0, out1), axis=0)
    tail = jnp.where(s0[None] == 1, pref1, pref0)
    traj = jnp.concatenate([s0[None], tail], axis=0)
    if worker_mask is None:
        return traj
    return jnp.where(worker_mask, traj, 1)


def _compose_maps(f, g):
    """(g ∘ f) for {0,1} -> {0,1} maps as (f(0), f(1)) value tables."""
    f0, f1 = f
    g0, g1 = g
    return (jnp.where(f0 == 1, g1, g0), jnp.where(f1 == 1, g1, g0))


@partial(jax.jit, static_argnames=("rounds",))
def sample_trajectory_from(
    key: jax.Array,
    p_stay1: jnp.ndarray,
    p_stay0: jnp.ndarray,
    rounds: int,
    init: jnp.ndarray,
) -> jnp.ndarray:
    """(rounds, n) trajectory of a 2-state chain from an EXPLICIT initial state.

    The fault-process twin of :func:`sample_trajectory`: ``init`` (n,) int32
    IS round 0 (no stationary draw — a fleet starts alive, a channel starts
    clear), and ``p_stay1``/``p_stay0`` are the stay probabilities
    P[1 -> 1] / P[0 -> 0], broadcastable against ``init``.  Same
    parallel-prefix composition as :func:`sample_trajectory` (per-round
    transition maps composed with ``lax.associative_scan``), so it is
    equally batched-engine-friendly; the whole key feeds the transition
    draws (there is no initial-state draw to split it with).
    """
    init = jnp.asarray(init, jnp.int32)
    if rounds == 1:
        return init[None]
    keys = jax.random.split(key, rounds - 1)
    u = jax.vmap(lambda k: jax.random.uniform(k, init.shape))(keys)
    out1 = (u < p_stay1).astype(jnp.int32)
    out0 = (u < (1.0 - p_stay0)).astype(jnp.int32)
    pref0, pref1 = jax.lax.associative_scan(_compose_maps, (out0, out1), axis=0)
    tail = jnp.where(init[None] == 1, pref1, pref0)
    return jnp.concatenate([init[None], tail], axis=0)


def speeds_from_states(states: jnp.ndarray, mu_g: float, mu_b: float) -> jnp.ndarray:
    """Map 0/1 states to evaluations-per-second speeds."""
    return jnp.where(states == 1, mu_g, mu_b)


def t_step_transitions(p_gg, p_bb, t: int):
    """Effective (p_gg, p_bb) of the t-step chain: P^t in closed form.

    For a 2-state chain with eigenvalue lam = p_gg + p_bb - 1,
    ``P^t[g,g] = pi_g + (1 - pi_g) lam^t`` (and symmetrically for b).  Used by
    the Fig. 4 EC2 replay: applying ``t`` Markov transitions between requests
    is equivalent to one transition of the t-step chain, which lets the
    arrival-gap simulation run on the batched one-transition-per-round engine.
    """
    p_gg = jnp.asarray(p_gg, jnp.float32)
    p_bb = jnp.asarray(p_bb, jnp.float32)
    lam = p_gg + p_bb - 1.0
    pi_g = stationary_good_prob(p_gg, p_bb)
    lam_t = lam ** t
    return pi_g + (1.0 - pi_g) * lam_t, (1.0 - pi_g) + pi_g * lam_t

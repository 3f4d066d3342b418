"""Offline Monte-Carlo sweep of a fleet of thousands of workers, over every
chip of the host.

One unit of work is one call a user makes to evaluate the fleet's scenarios:
``sweeps.run_groups`` over every (scenario, seed) row, laid over the
``("batch",)`` mesh of the traffic's ``chips`` (``launch.mesh.make_sweep_mesh``),
then ``sweeps.summarize`` on the host. Work is counted in simulated
row-rounds. The scenarios are the program's own ``fleet4k`` family, built
and checked against the configuration file before anything compiles; the
plain reference builds its inputs from the file alone.

At this width the reference's DP costs some 10^10 element-steps a row, so the
check samples rows and, in each, rounds: the trajectory and the estimator
are computed for every round, the DP and the allocation at the sampled
rounds only. Rows are sampled in every chip's share of the batch, so a fault
on one chip shows; scenarios nearer the fleet's capacity, where LEA's argmax
decides the outcome, can take more sampled rounds.

Traffic keys: ``rounds`` per row, ``seeds`` per scenario, ``strategies``,
``chips`` (the mesh spans that many local devices), ``round_chunk`` (the
executor's memory bound), ``check_rows_per_chip`` (per group),
``check_rounds`` (sampled rounds of a checked row, one entry per scenario of
the configuration), ``trace_calls`` and ``limits``.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.deploy import seeds_from
from bench.reference import fleet

# sampled rounds of one row whose float64 DP runs as one task: rows split into
# such blocks keep every core of the host busy
DP_BLOCK = 16


def fleet_scenarios(config: dict, rounds: int) -> tuple:
    """The configuration's scenarios as the program's ``fleet4k`` builds them."""
    from repro.sweeps import expand

    built = expand("fleet4k", n=config["n"], ks=tuple(config["k_of_150"]),
                   rounds=rounds)
    want = reference_specs(config)
    for sc, s in zip(built, want, strict=True):
        found = dict(n=sc.lp.n, kstar=sc.lp.kstar, ell_g=sc.lp.ell_g,
                     ell_b=sc.lp.ell_b, mu_g=sc.mu_g, mu_b=sc.mu_b,
                     deadline=sc.deadline)
        stated = {key: s[key] for key in found}
        chains_equal = (np.array_equal(np.asarray(sc.p_gg, np.float32), s["p_gg"])
                        and np.array_equal(np.asarray(sc.p_bb, np.float32), s["p_bb"]))
        if found != stated or not chains_equal:
            raise ValueError(f"the configuration states {stated} and its chains, "
                             f"the program's fleet4k scenario {sc.name} {found}")
    return built


def reference_specs(config: dict) -> list[dict]:
    """Each scenario of the configuration, from its file alone: worker i on
    chain i mod len(chains), loads ell_g = min(mu_g d, r), ell_b = mu_b d."""
    n, r, d = config["n"], config["r"], config["deadline"]
    chains = np.asarray(config["chains"], np.float32)[np.arange(n) % len(config["chains"])]
    return [dict(n=n, kstar=int(kstar), ell_g=int(min(config["mu_g"] * d, r)),
                 ell_b=int(config["mu_b"] * d), mu_g=float(config["mu_g"]),
                 mu_b=float(config["mu_b"]), deadline=float(d),
                 p_gg=chains[:, 0], p_bb=chains[:, 1])
            for kstar in config["kstar"]]


# LEA's argmax between prefixes whose float64 tails lie closer than this share
# of the best is a tie to a float32 scheduler: at n = 4,000 its tails err by
# up to 1.2e-6 of the best (the numpy float32 DP, both K*) and the program's
# by 1.9e-6 more (the wide body against the float32 scan DP, on a v5e), while
# a bfloat16 scheduler's argmax loses 3e-3 to 1.6e-2 of the best. Where even
# the best tail lies below float32's range (K* = 26,667 on the even
# predictions of rounds 0 and 1: 8.8e-101), every tail reads 0 and all
# prefixes tie.
TIE = 3e-5


def lea_outcomes(p: np.ndarray, states: np.ndarray, s: dict) -> tuple:
    """(can_succeed, can_fail) of LEA at each of (R, n) predicted rounds: the
    success indicators of every prefix whose float64 tail lies within ``TIE``
    of the best, the reference's own argmax among them."""
    rows, n = p.shape
    order = np.argsort(-p, axis=-1, kind="stable")
    w = fleet.prefix_thresholds(np.full(rows, s["kstar"]), s["ell_g"], s["ell_b"], n, n)
    tails = fleet.prefix_tails(np.take_along_axis(p, order, axis=-1), w)
    best = tails.max(axis=-1, keepdims=True)
    near = (tails >= best - TIE * best) | (best < np.finfo(np.float32).tiny)
    # prefix i loads its i sorted workers with ell_g and the rest with ell_b:
    # each worker's on-time evaluations under either load, summed by prefix
    st = np.take_along_axis(states, order, axis=-1)[..., None]

    def on_time(load):
        return fleet.on_time_received(np.full(st.shape, load), st, s["mu_g"],
                                      s["mu_b"], s["deadline"])

    good, bad = on_time(s["ell_g"]), on_time(s["ell_b"])
    received = np.cumsum(good, -1) + bad.sum(-1, keepdims=True) - np.cumsum(bad, -1)
    feasible = np.any(w <= np.arange(1, n + 1), axis=-1, keepdims=True)
    success = (received >= s["kstar"]) & feasible
    return np.any(near & success, axis=-1), np.any(near & ~success, axis=-1)


def _in_blocks(fn, p: np.ndarray, *args, pool=None) -> list:
    """``fn`` over blocks of ``DP_BLOCK`` rows of ``p`` (on ``pool``, if given)."""
    starts = range(0, len(p), DP_BLOCK)
    return list((pool.map if pool else map)(
        lambda i: fn(p[i:i + DP_BLOCK], *(a[i:i + DP_BLOCK] for a in args)), starts))


def sampled_row(key: np.ndarray, s: dict, rounds: int, picked: np.ndarray,
                strategies, dtype=None, est_dtype=np.float32,
                pool: ThreadPoolExecutor | None = None) -> tuple:
    """(can_succeed, can_fail), each (len(picked), S), of one row at its
    ``picked`` rounds: ``fleet.sweep_row`` with the DP run at those rounds
    only, in blocks of ``DP_BLOCK`` rounds (on ``pool``, if given). LEA may
    take either outcome where prefixes tie (:func:`lea_outcomes`); with a
    ``dtype`` its DP runs in that precision and takes its argmax alone."""
    k_traj, k_rounds = fleet.split(key)
    states = fleet.trajectory(k_traj, s["p_gg"], s["p_bb"], rounds)
    n = states.shape[1]
    yes, no = [], []
    for name in strategies:
        if name == "lea" and dtype is None:
            p = fleet.lea_p_good(states, est_dtype)[picked]
            parts = _in_blocks(lambda b, st: lea_outcomes(b, st, s), p, states[picked],
                               pool=pool)
            yes.append(np.concatenate([part[0] for part in parts]))
            no.append(np.concatenate([part[1] for part in parts]))
            continue
        if name == "lea":
            p = fleet.lea_p_good(states, est_dtype)[picked]
            parts = _in_blocks(lambda b: fleet.allocate(
                b, s["kstar"], s["ell_g"], s["ell_b"], np.ones((n,), bool), dtype),
                p, pool=pool)
            loads = np.concatenate([part[0] for part in parts])
            feasible = np.concatenate([part[2] for part in parts])
        elif name == "static_single":
            draw = fleet.uniforms(fleet.split(k_rounds, rounds)[picked], n)
            loads = np.where(draw < np.float32(0.5), s["ell_g"], s["ell_b"])
            feasible = np.ones((len(picked),), bool)
        else:
            raise ValueError(f"no reference for strategy {name!r}")
        received = fleet.on_time_received(loads, states[picked], s["mu_g"],
                                          s["mu_b"], s["deadline"])
        success = (received >= s["kstar"]) & feasible
        yes.append(success)
        no.append(~success)
    return np.stack(yes, axis=-1), np.stack(no, axis=-1)


class FleetSweep:
    def __init__(self, config: dict, traffic: dict, seed: int):
        import dataclasses

        self.traffic = traffic
        self.seed = seed
        self.rounds = int(traffic["rounds"])
        built = fleet_scenarios(config, self.rounds)
        self.specs = reference_specs(config)

        from repro import sweeps
        from repro.launch.mesh import make_sweep_mesh

        self.sweeps = sweeps
        self.chips = int(traffic["chips"])
        self.mesh = make_sweep_mesh(self.chips)
        strategies = tuple(traffic["strategies"])
        seeds = seeds_from(seed, 0, len(built))
        self.scenarios = tuple(
            dataclasses.replace(sc, seed=sd, strategies=strategies,
                                baseline=strategies[-1])
            for sc, sd in zip(built, seeds))
        self.groups = sweeps.build_groups(self.scenarios, seeds=int(traffic["seeds"]))
        self.rows = sum(g.batch.rows for g in self.groups)
        self.last = None
        # (group, row) of every (scenario, repeat)
        self.where = {}
        for gi, g in enumerate(self.groups):
            for r, m in enumerate(g.rows):
                si = self.scenarios.index(g.scenarios[m.scenario_index])
                self.where[(si, m.seed_index)] = (gi, r)
        self.picks = self.sample()

    # -- the timed path ------------------------------------------------------

    def call(self):
        import jax

        succ = self.sweeps.run_groups(self.groups, mesh=self.mesh,
                                      round_chunk=self.traffic["round_chunk"])
        with jax.profiler.TraceAnnotation("bench.host_reduce"):
            results = self.sweeps.summarize(self.groups, succ,
                                            scenario_order=self.scenarios)
        self.last = succ
        return results

    def work(self) -> float:
        return float(self.rows * self.rounds)

    def warm(self) -> None:
        self.call()

    def layer_info(self) -> dict:
        """Per-call counts the per-layer readers divide by: row-rounds, and
        the DP rows of each (K*, ell_g, ell_b) at the fleet's width."""
        allocators = [s for s in self.traffic["strategies"] if s in ("lea",)]
        dp_rows = [(float(int(self.traffic["seeds"]) * self.rounds * len(allocators)),
                    s["kstar"], s["ell_g"], s["ell_b"]) for s in self.specs]
        return {"row_rounds_per_call": self.work(), "dp_rows": dp_rows,
                "dp_width": self.specs[0]["n"]}

    def free(self) -> None:
        self.groups = None

    # -- the check -------------------------------------------------------------

    def sample(self) -> list[tuple[int, int, np.ndarray]]:
        """(scenario, repeat, sorted rounds) the check compares, from the seed:
        ``check_rows_per_chip`` rows in each chip's share of each group's
        batch (the executor pads the batch to a multiple of the chips and
        gives each chip one contiguous block)."""
        rng = np.random.default_rng([self.seed, 1])
        per = int(self.traffic["check_rows_per_chip"])
        chips = self.chips
        picks = []
        for g in self.groups:
            rows = len(g.rows)
            block = -(-rows // chips)
            for chip in range(chips):
                real = np.arange(chip * block, min((chip + 1) * block, rows))
                for r in sorted(rng.choice(real, size=min(per, real.size),
                                           replace=False)):
                    m = g.rows[r]
                    si = self.scenarios.index(g.scenarios[m.scenario_index])
                    count = min(int(self.traffic["check_rounds"][si]), self.rounds)
                    rounds = np.sort(rng.choice(self.rounds, size=count,
                                                replace=False))
                    picks.append((si, m.seed_index, rounds))
        return picks

    def check(self, control: bool = False) -> dict:
        """Success indicators at sampled rounds of sampled rows against the
        plain reference: an indicator is unlike it where the reference's
        outcomes at that round (either one at a tie of prefixes) exclude it.
        With ``control`` the reference computed in bfloat16 (estimator and
        dynamic programme) stands in the program's place."""
        strategies = tuple(self.traffic["strategies"])
        picks = self.picks

        def compare(pick):
            si, rep, picked = pick
            s = self.specs[si]
            key = fleet.row_key(fleet.seed_key(self.scenarios[si].seed), rep)
            can_succeed, can_fail = sampled_row(key, s, self.rounds, picked,
                                                strategies, pool=blocks)
            if control:
                got, _ = sampled_row(key, s, self.rounds, picked, strategies,
                                     dtype=fleet.BF16, est_dtype=fleet.BF16, pool=blocks)
            else:
                gi, r = self.where[(si, rep)]
                got = self.last[gi][r][picked]
            unlike = np.where(got, ~can_succeed, ~can_fail)
            return int(np.sum(unlike)), got.size, int(np.sum(can_succeed & can_fail))

        # rows and their DP blocks apart: numpy's loops release the
        # interpreter lock
        with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as blocks, \
                ThreadPoolExecutor(max_workers=len(picks)) as rows:
            counts = list(rows.map(compare, picks))
        mismatched = sum(c[0] for c in counts)
        total = sum(c[1] for c in counts)
        return {"numbers": {"success_mismatch_share": mismatched / total},
                "detail": {"indicators": total, "mismatched": mismatched,
                           "ties": sum(c[2] for c in counts)}}


def setup(config: dict, traffic: dict, seed: int) -> FleetSweep:
    return FleetSweep(config, traffic, seed)

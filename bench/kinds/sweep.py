"""Offline Monte-Carlo sweep: the scenario grid of a fleet, many seeds each.

One unit of work is one call a user makes to evaluate the grid:
``sweeps.run_groups`` over every (scenario, seed) row, then
``sweeps.summarize`` on the host. Work is counted in simulated row-rounds
(one row is one scenario under one seed; strategies do not multiply it).

Traffic keys: ``rounds`` per row, ``seeds`` per scenario, ``strategies``,
``round_chunk`` (the executor's memory bound), ``check_rows_per_scenario``,
``trace_calls`` and ``limits``.
"""

from __future__ import annotations

import numpy as np

from bench.deploy import ec2_scenarios, seeds_from
from bench.reference import fleet


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        import dataclasses

        from repro import sweeps

        self.sweeps = sweeps
        self.traffic = traffic
        self.seed = seed
        rounds = int(traffic["rounds"])
        built = ec2_scenarios(config, rounds)
        self.specs = [fleet.ec2_scenario(config, i) for i in range(len(built))]
        seeds = seeds_from(seed, 0, len(built))
        strategies = tuple(traffic["strategies"])
        self.scenarios = tuple(
            dataclasses.replace(sc, seed=sd, strategies=strategies,
                                baseline=strategies[-1])
            for sc, sd in zip(built, seeds))
        self.groups = sweeps.build_groups(self.scenarios, seeds=int(traffic["seeds"]))
        self.rows = sum(g.batch.rows for g in self.groups)
        self.rounds = rounds
        self.last = None
        # (group, row) of every (scenario, repeat)
        self.where = {}
        for gi, g in enumerate(self.groups):
            for r, m in enumerate(g.rows):
                si = self.scenarios.index(g.scenarios[m.scenario_index])
                self.where[(si, m.seed_index)] = (gi, r)

    # -- the timed path ------------------------------------------------------

    def call(self):
        import jax

        succ = self.sweeps.run_groups(self.groups,
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
        """Per-call counts the per-layer readers divide by."""
        allocators = [s for s in self.traffic["strategies"] if s in ("lea",)]
        n = self.specs[0]["n"]
        return {"row_rounds_per_call": self.work(),
                "dp_rows_per_call": float(self.rows * self.rounds * len(allocators)),
                "dp_width": n}

    def free(self) -> None:
        self.groups = None

    # -- the check -------------------------------------------------------------

    def sample(self) -> list[tuple[int, int]]:
        """(scenario, repeat) of the rows the check compares, drawn from the seed."""
        rng = np.random.default_rng([self.seed, 1])
        per = int(self.traffic["check_rows_per_scenario"])
        seeds = int(self.traffic["seeds"])
        picks = []
        for si in range(len(self.scenarios)):
            for rep in sorted(rng.choice(seeds, size=per, replace=False)):
                picks.append((si, int(rep)))
        return picks

    def check(self, control: bool = False) -> dict:
        """Success indicators of sampled rows against the plain reference.

        With ``control`` the reference computed in bfloat16 (estimator and
        dynamic programme) stands in the program's place.
        """
        mismatched = total = 0
        for si, rep in self.sample():
            s = self.specs[si]
            key = fleet.row_key(fleet.seed_key(self.scenarios[si].seed), rep)
            args = (key, np.full(s["n"], s["p_gg"], np.float32),
                    np.full(s["n"], s["p_bb"], np.float32), s["mu_g"], s["mu_b"],
                    s["deadline"], s["kstar"], s["ell_g"], s["ell_b"], self.rounds,
                    tuple(self.traffic["strategies"]))
            want = fleet.sweep_row(*args)
            if control:
                got = fleet.sweep_row(*args, dtype=fleet.BF16, est_dtype=fleet.BF16)
            else:
                gi, r = self.where[(si, rep)]
                got = self.last[gi][r]
            mismatched += int(np.sum(got != want))
            total += want.size
        return {"numbers": {"success_mismatch_share": mismatched / total},
                "detail": {"indicators": total, "mismatched": mismatched}}


def setup(config: dict, traffic: dict, seed: int) -> Driver:
    return Driver(config, traffic, seed)

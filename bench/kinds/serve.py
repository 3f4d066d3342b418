"""The streaming service simulator over an arrival-rate x deadline grid.

Requests arrive as a Poisson stream per round and compete for one worker
pool through an admission-controlled EDF queue (``serving.sweep_serving``).
One unit of work is one call over every (grid cell, seed) row, with the
outcomes pulled to the host and the served-on-time share of each grid cell
reduced there. Work is counted in simulated row-rounds.

Traffic keys: ``k`` and ``deg_f`` of every request, ``chain`` (index into
the configuration's chains), ``rates``, ``deadline_rels``, ``capacity``,
``grace``, ``admit_threshold``, ``reserve_cap``, ``rounds``, ``seeds`` per
grid cell, ``strategies``, ``check_rows_per_cell``, ``trace_calls`` and
``limits``.
"""

from __future__ import annotations

import numpy as np

from bench.deploy import seeds_from
from bench.reference import fleet
from bench.reference import queue as ref_queue


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from repro import serving

        self.serving = serving
        self.traffic = traffic
        self.seed = seed
        n, d = int(config["n"]), float(config["deadline"])
        self.n = n
        self.kstar = (int(traffic["k"]) - 1) * int(traffic["deg_f"]) + 1
        self.ell_g = int(min(config["mu_g"] * d, config["r"]))
        self.ell_b = int(config["mu_b"] * d)
        self.mu_g, self.mu_b, self.deadline = float(config["mu_g"]), float(config["mu_b"]), d
        self.p_gg, self.p_bb = config["chains"][int(traffic["chain"])]
        self.cells = [(float(rate), int(dl)) for rate in traffic["rates"]
                      for dl in traffic["deadline_rels"]]
        self.seeds = int(traffic["seeds"])
        self.rounds = int(traffic["rounds"])
        self.cell_seeds = seeds_from(seed, 3, len(self.cells))
        rows = [(c, rep) for c in range(len(self.cells)) for rep in range(self.seeds)]
        self.rows = len(rows)
        self.keys = np.stack([fleet.row_key(fleet.seed_key(self.cell_seeds[c]), rep)
                              for c, rep in rows])
        b = self.rows
        col = lambda i: np.asarray([self.cells[c][i] for c, _ in rows])
        self.args = dict(
            keys=jnp.asarray(self.keys), pool_mask=jnp.ones((b, n), bool),
            p_gg=jnp.full((b, n), self.p_gg, jnp.float32),
            p_bb=jnp.full((b, n), self.p_bb, jnp.float32),
            mu_g=self.mu_g, mu_b=self.mu_b, deadline=self.deadline,
            spec=serving.RequestSpec(
                kstar=jnp.full((b,), self.kstar, jnp.int32),
                ell_g=jnp.full((b,), self.ell_g, jnp.int32),
                ell_b=jnp.full((b,), self.ell_b, jnp.int32),
                deadline_rel=jnp.asarray(col(1), jnp.int32),
                admit_threshold=jnp.full((b,), traffic["admit_threshold"], jnp.float32),
                reserve_cap=jnp.full((b,), traffic["reserve_cap"], jnp.float32)),
            process=serving.make_process("poisson",
                                         rate=jnp.asarray(col(0), jnp.float32)))
        jax.block_until_ready(self.args["keys"])
        self.cell_of_row = np.asarray([c for c, _ in rows])
        self.last = None

    def call(self):
        import jax

        a = self.args
        out = self.serving.sweep_serving(
            a["keys"], a["pool_mask"], a["p_gg"], a["p_bb"], a["mu_g"], a["mu_b"],
            a["deadline"], a["spec"], a["process"], rounds=self.rounds,
            strategies=tuple(self.traffic["strategies"]),
            capacity=int(self.traffic["capacity"]), grace=int(self.traffic["grace"]))
        with jax.profiler.TraceAnnotation("bench.host_reduce"):
            host = jax.tree.map(np.asarray, out)
            on_time = np.bincount(self.cell_of_row, host.served_on_time[:, 0],
                                  minlength=len(self.cells))
            arrived = np.bincount(self.cell_of_row, host.arrivals[:, 0],
                                  minlength=len(self.cells))
        self.last = host
        return on_time / np.maximum(arrived, 1)

    def work(self) -> float:
        return float(self.rows * self.rounds)

    def warm(self) -> None:
        self.call()

    def layer_info(self) -> dict:
        a = len(self.traffic["strategies"])
        q = int(self.traffic["capacity"])
        return {"row_rounds_per_call": self.work(), "scan_rounds_per_call": float(self.rounds),
                # one admission DP per (row, round, policy) and one
                # allocation DP per (row, round, policy, queue slot)
                "dp_rows_per_call": float(self.rows * self.rounds * a * (1 + q)),
                "dp_width": self.n}

    def free(self) -> None:
        self.args = None

    def sample(self) -> list[int]:
        """Rows the check compares, ``check_rows_per_cell`` per grid cell,
        drawn from the seed."""
        rng = np.random.default_rng([self.seed, 4])
        per = int(self.traffic["check_rows_per_cell"])
        return [c * self.seeds + int(rep) for c in range(len(self.cells))
                for rep in sorted(rng.choice(self.seeds, size=per, replace=False))]

    def check(self, control: bool = False) -> dict:
        """Accounting identities on every row; events and sojourn times of
        sampled rows over all their rounds against the plain queue. With
        ``control`` the plain queue computed in bfloat16 (estimator and
        dynamic programmes) stands in the program's place."""
        o = self.last
        leave = o.served_on_time + o.served_late + o.expired + o.in_flight
        broken = int(np.sum(o.arrivals != o.admitted + o.rejected)
                     + np.sum(o.admitted != leave))
        differ = total = 0
        for row in self.sample():
            rate, dl = self.cells[self.cell_of_row[row]]
            args = (self.keys[row], np.full(self.n, self.p_gg, np.float32),
                    np.full(self.n, self.p_bb, np.float32), self.mu_g, self.mu_b,
                    self.deadline, self.kstar, self.ell_g, self.ell_b, rate, dl,
                    self.traffic["admit_threshold"], self.traffic["reserve_cap"],
                    int(self.traffic["capacity"]), int(self.traffic["grace"]),
                    self.rounds)
            want = ref_queue.serve_row(*args)
            if control:
                got = ref_queue.serve_row(*args, dtype=fleet.BF16, est_dtype=fleet.BF16)
                got_ev, got_sj = got["events"], got["sojourn"]
            else:
                got_ev = o.events[row, 0]
                got_sj = o.sojourn[row, 0]
            differ += int(np.sum((got_ev != want["events"]) | (got_sj != want["sojourn"])))
            total += want["events"].size
        return {"numbers": {"accounting_breaks": float(broken),
                            "event_mismatch_share": differ / total},
                "detail": {"rows": self.rows, "sampled_slot_rounds": total,
                           "differing": differ}}


def setup(config: dict, traffic: dict, seed: int) -> Driver:
    return Driver(config, traffic, seed)

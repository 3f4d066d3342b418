"""The coded data plane: one exact coded round after another, closed loop.

The data X (k blocks of ``rows`` x ``cols`` residues over GF(2^31 - 1)) is
encoded once at set-up, as it is stored at the workers. One unit of work is
one round: the workers' products of their coded blocks with a query vector
w and the master's decode from the chunks that arrived by the deadline
(``coded_matmul_exact``), with the answer pulled to the host. Queries cycle
through a pool drawn from the seed; erasure patterns cycle through the
decodable rounds of a LEA rollout of the scenario's worker chain.

Traffic keys: ``scenario`` (index into the configuration's scenarios),
``w_pool``, ``patterns``, ``pattern_rounds``, ``trace_calls`` and
``limits``.
"""

from __future__ import annotations

import numpy as np

from bench.deploy import seeds_from
from bench.reference import fleet, gf


def erasure_patterns(config: dict, traffic: dict, seed: int) -> np.ndarray:
    """(P, n r) decodable chunk masks of a LEA rollout, from the seed."""
    s = fleet.ec2_scenario(config, int(traffic["scenario"]))
    key = fleet.seed_key(seeds_from(seed, 2, 1)[0])
    masks = fleet.lea_erasures(
        key, np.full(s["n"], s["p_gg"], np.float32),
        np.full(s["n"], s["p_bb"], np.float32), s["mu_g"], s["mu_b"],
        s["deadline"], s["kstar"], s["ell_g"], s["ell_b"], config["r"],
        int(traffic["pattern_rounds"]))
    decodable = masks[masks.sum(axis=-1) >= s["kstar"]]
    want = int(traffic["patterns"])
    if len(decodable) < want:
        raise ValueError(f"only {len(decodable)} decodable rounds of "
                         f"{traffic['pattern_rounds']}; need {want}")
    return decodable[:want]


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from repro.core import lagrange
        from repro.core.coded_ops import coded_matmul_exact, encode_dataset_modp

        rows, k = config["scenarios"][int(traffic["scenario"])][:2]
        self.spec = lagrange.CodeSpec(config["n"], config["r"], k, config["deg_f"])
        cols = int(config["cols"])
        rng = np.random.default_rng([seed, 0])
        self.x = rng.integers(0, gf.FIELD_P, size=(k, rows, cols), dtype=np.int64)
        self.w = rng.integers(0, gf.FIELD_P, size=(int(traffic["w_pool"]), cols),
                              dtype=np.int64)
        self.patterns = erasure_patterns(config, traffic, seed)
        self.coded = encode_dataset_modp(self.spec, jnp.asarray(self.x, jnp.int32))
        self.w_dev = [jax.device_put(jnp.asarray(v, jnp.int32)) for v in self.w]
        self.on_dev = [jax.device_put(jnp.asarray(m)) for m in self.patterns]
        jax.block_until_ready((self.coded.x_tilde, self.w_dev, self.on_dev))
        self.round_fn = coded_matmul_exact
        self.rounds = []          # (query, pattern, answer, ok) per round

    def call(self):
        import jax

        i = len(self.rounds)
        j, q = i % len(self.w_dev), i % len(self.on_dev)
        out, ok = self.round_fn(self.coded, self.w_dev[j], self.on_dev[q])
        with jax.profiler.TraceAnnotation("bench.host_reduce"):
            answer, ok = np.asarray(out), bool(ok)
        self.rounds.append((j, q, answer, ok))

    def work(self) -> float:
        return 1.0

    def warm(self) -> None:
        for _ in range(3):
            self.call()
        self.rounds = []

    def layer_info(self) -> dict:
        k, rows, cols = self.x.shape
        return {"rounds_per_call": 1.0,
                "gf_products": (self.spec.nr * rows, cols, 1),
                "gf_decode": (k, self.spec.recovery_threshold, rows)}

    def free(self) -> None:
        self.coded = self.w_dev = self.on_dev = None

    def check(self, control: bool = False) -> dict:
        """Every round's answer against X_j w mod p; the control puts the
        float64 product in the program's place."""
        k, rows, cols = self.x.shape
        flat = self.x.reshape(k * rows, cols)
        want = gf.products_modp(flat, self.w.T).reshape(k, rows, -1)
        if control:
            got_all = gf.products_float64(flat, self.w.T).reshape(k, rows, -1)
        wrong = not_ok = bad_rounds = 0
        for j, _q, answer, ok in self.rounds:
            got = got_all[:, :, j] if control else answer
            miss = int(np.sum(got != want[:, :, j]))
            wrong += miss
            not_ok += int(not ok)
            bad_rounds += int(miss > 0 or not ok)
        return {"numbers": {"wrong_residues": float(wrong),
                            "rounds_not_ok": float(not_ok)},
                "failed": bad_rounds,
                "detail": {"rounds": len(self.rounds),
                           "residues": len(self.rounds) * k * rows}}


def setup(config: dict, traffic: dict, seed: int) -> Driver:
    return Driver(config, traffic, seed)

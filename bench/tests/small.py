"""The benchmark's cells cut to sizes a CPU test holds, and a run of one."""

from __future__ import annotations

import jax

from bench import run, spec

SMALL = {
    "ec2_t2micro.sweep": dict(rounds=240, seeds=3, round_chunk=96,
                              check_rows_per_scenario=2),
    "ec2_t2micro.coded_round": dict(pattern_rounds=300, patterns=6, w_pool=4),
    "sim_t2micro.serve": dict(rounds=160, seeds=2),
}
SMALL_COLS = 256
SEED = 2**31 + 4242          # larger than 32 signed bits hold
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def small_cell(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    cell.traffic = dict(cell.traffic, **SMALL[name])
    if name.endswith("coded_round"):
        cell.config = dict(cell.config, cols=SMALL_COLS)
    return cell


def run_small(name: str, seconds: float = 0.3, seed: int = SEED) -> dict:
    """Everything of a run after the look for a chip, at the small size."""
    return run.execute(small_cell(name), seed, seconds, 0, CPU, jax.devices())

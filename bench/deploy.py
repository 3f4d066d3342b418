"""A configuration's deployment turned into the program's inputs.

The EC2 scenarios are the program's own Sec. 6.2 replay,
``repro.sweeps.scenarios.fig4``, so the benchmark runs the scenarios its
users run. The plain reference builds the same scenarios from the
configuration's file alone (``reference.fleet.ec2_scenario``), so a replay
that drifts from the file reads as not correct.
"""

from __future__ import annotations

import numpy as np


def ec2_scenarios(config: dict, rounds: int) -> tuple:
    """The configuration's scenarios as ``fig4`` builds them, ``rounds`` each."""
    from repro.sweeps.scenarios import fig4

    built = fig4(rounds)
    found = [[dict(sc.meta)[key] for key in ("rows", "k", "lam", "d")] for sc in built]
    if found != config["scenarios"]:
        raise ValueError(f"the configuration states the scenarios "
                         f"{config['scenarios']}, the program's fig4 replay {found}")
    return built


def seeds_from(seed: int, stream: int, count: int) -> list[int]:
    """``count`` 31-bit PRNG seeds drawn from the run's seed, on its own
    stream so that inputs of different kinds never share draws."""
    rng = np.random.default_rng([seed, stream])
    return [int(v) for v in rng.integers(1, 2**31 - 1, size=count)]

"""Readings that set a cell's correctness limits: the program's and the
control's numbers over many seeds, in one process.

    python3 bench/readings.py --workload <cell> --seeds <n> [<n> ...]

For each seed it builds the cell's inputs, runs one unit of the timed path
(the traffic's ``trace_calls`` units for a cell whose unit is one coded
round), and prints one JSON line with the compared numbers of the program
and of the control: the plain reference computed in the nearest lower
precision (bfloat16 for the float32 scheduler, float64 products for the
exact field arithmetic), put in the program's place. A limit lies above
the program's largest reading and below the control's smallest. The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run, spec  # noqa: E402


def readings(cell, seed: int) -> dict:
    driver = cell.kind.setup(cell.config, cell.traffic, seed)
    for _ in range(int(cell.traffic["trace_calls"])):
        driver.call()
    driver.free()
    return {"seed": seed, "program": driver.check()["numbers"],
            "control": driver.check(control=True)["numbers"]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax

    device = run.require_chip(jax.devices(), cell.chips)
    run.enable_cache()
    for seed in args.seeds:
        print(json.dumps(dict(readings(cell, seed), workload=cell.name,
                              device=device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Each cell's control comes out as not correct against the cell's limits.

The control is the plain reference computed in the nearest lower precision
and put in the program's place: bfloat16 for the scheduler's float32
estimator and dynamic programmes, float64 products for the exact GF(p)
round. At the cells' own sizes it is read on the chip
(``bench/readings.py``); here it runs at a size a CPU test holds, on seeds
on which the lower precision flips a decision within that size.
"""

import pytest

from bench.tests.small import small_cell

CASES = [
    ("ec2_t2micro.sweep", (12, 14, 2**31 + 5)),
    ("ec2_t2micro.coded_round", (12, 13, 2**31 + 99)),
    ("sim_t2micro.serve", (11, 14, 2**31 + 6)),
]


@pytest.mark.parametrize("name,seeds", CASES, ids=[c[0] for c in CASES])
def test_the_control_fails_the_limits(name, seeds):
    cell = small_cell(name)
    limits = cell.traffic["limits"]
    for seed in seeds:
        driver = cell.kind.setup(cell.config, cell.traffic, seed)
        driver.call()
        sound = driver.check()["numbers"]
        control = driver.check(control=True)["numbers"]
        assert all(sound[k] <= limits[k] for k in limits), (seed, sound)
        assert any(control[k] > limits[k] for k in limits), (seed, control)

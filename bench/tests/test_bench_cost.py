"""Kernel operations and bytes at the cells' shapes, by hand arithmetic; the
table of peaks; the harness refusing to run without a TPU."""

import os
import subprocess
import sys

import pytest

from bench import cost

V5E = cost.peaks("TPU v5 lite")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_gf_worker_products_at_the_ec2_round():
    # 150 coded chunks x 25 rows against 3000 columns, one query column
    c = cost.gf_matmul(150 * 25, 3000, 1)
    assert c.ops == 22_500_000
    assert c.bytes == 4 * (3750 * 3000 + 3000 + 3750) == 45_027_000
    seconds, bound = cost.least_time(c, V5E)
    assert bound == "bytes"
    assert seconds == pytest.approx(45_027_000 / 819e9)      # about 55 us


def test_gf_decode_at_the_ec2_round():
    c = cost.gf_matmul(120, 120, 25)                        # D (k, K*) @ (K*, rows)
    assert c.ops == 720_000
    assert c.bytes == 4 * (120 * 120 + 120 * 25 + 120 * 25) == 81_600


def test_poisson_binomial_at_the_sweep_call():
    rows = 384 * 20_000                                     # row-rounds, one policy
    c = cost.poisson_binomial(rows, 15)
    assert c.ops == 2 * rows * 15 * 16 == 3_686_400_000
    assert c.bytes == 3 * 4 * rows * 15 == 1_382_400_000
    seconds, bound = cost.least_time(c, V5E)
    assert bound == "bytes"
    assert seconds == pytest.approx(1_382_400_000 / 819e9)   # about 1.69 ms
    assert 3_686_400_000 / 197e12 < seconds


def test_roofline_share():
    c = cost.gf_matmul(150 * 25, 3000, 1)
    least = 45_027_000 / 819e9
    assert cost.roofline_pct(c, V5E, 2 * least) == pytest.approx(50.0)
    with pytest.raises(ValueError):
        cost.roofline_pct(c, V5E, 0.0)


def test_costs_add_only_under_one_peak():
    a = cost.poisson_binomial(10, 15)
    assert (a + a).ops == 2 * a.ops
    with pytest.raises(ValueError):
        a + cost.gf_matmul(1, 1, 1)


def test_an_unknown_device_kind_is_an_error():
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="no published peaks"):
        cost.peaks("TPU v9 imaginary")


def _run_harness(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join("bench", "run.py"), *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=120)


def test_the_harness_exits_non_zero_and_prints_nothing_without_a_tpu():
    proc = _run_harness("--workload", "ec2_t2micro.sweep", "--seed", str(2**31 + 7),
                        "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_the_harness_refuses_an_unknown_workload():
    proc = _run_harness("--workload", "no_such.cell", "--seed", "1",
                        "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout == ""

"""The plain references against the program's entry points, on the CPU at
small sizes, with the Pallas kernels in interpret mode."""

import numpy as np
import pytest

from bench.reference import fleet, gf
from bench.tests.small import SEED, small_cell

INTERPRET = {"REPRO_KERNEL_IMPL": "pallas", "REPRO_KERNEL_INTERPRET": "1"}


@pytest.fixture
def pallas_interpret(monkeypatch):
    from repro.kernels import dispatch

    for k, v in INTERPRET.items():
        monkeypatch.setenv(k, v)
    assert dispatch.resolve_impl(None, allowed=("pallas", "ref")) == "pallas"
    assert dispatch.default_interpret() is True


def _driven(name, seed=SEED):
    cell = small_cell(name)
    driver = cell.kind.setup(cell.config, cell.traffic, seed)
    driver.call()
    return driver


def test_sweep_reference_matches_run_groups(pallas_interpret):
    d = _driven("ec2_t2micro.sweep")
    check = d.check()
    assert check["detail"]["indicators"] == 6 * 2 * 240 * 2
    assert check["numbers"]["success_mismatch_share"] == 0.0


def test_gf_reference_matches_coded_matmul_exact(pallas_interpret):
    d = _driven("ec2_t2micro.coded_round")
    for _ in range(3):
        d.call()
    check = d.check()
    assert check["detail"]["rounds"] == 4
    assert check["numbers"] == {"wrong_residues": 0.0, "rounds_not_ok": 0.0}
    assert check["failed"] == 0


def test_serve_reference_matches_sweep_serving(pallas_interpret):
    d = _driven("sim_t2micro.serve")
    check = d.check()
    assert check["numbers"] == {"accounting_breaks": 0.0, "event_mismatch_share": 0.0}
    assert check["detail"]["sampled_slot_rounds"] == 6 * 160 * 6


def test_gf_products_are_exact_against_python_integers():
    rng = np.random.default_rng(7)
    x = rng.integers(0, gf.FIELD_P, size=(5, 40))
    w = rng.integers(0, gf.FIELD_P, size=(40, 3))
    x[0, :] = gf.FIELD_P - 1                                  # largest residues
    want = [[sum(int(a) * int(b) for a, b in zip(row, col)) % gf.FIELD_P
             for col in w.T] for row in x]
    assert gf.products_modp(x, w).tolist() == want
    assert (gf.products_float64(x, w) != np.asarray(want)).any()


def test_prefix_tails_match_enumeration():
    rng = np.random.default_rng(3)
    p = np.sort(rng.uniform(size=(4, 6)), axis=-1)[:, ::-1]
    w = fleet.prefix_thresholds(np.full(4, 20), np.full(4, 5), np.full(4, 1),
                                np.full(4, 6), 6)
    got = fleet.prefix_tails(p, w)
    for r in range(4):
        for i in range(6):
            want = 0.0
            if w[r, i] <= i + 1:
                for bits in range(2 ** (i + 1)):
                    on = [(bits >> j) & 1 for j in range(i + 1)]
                    if sum(on) >= w[r, i]:
                        want += np.prod([p[r, j] if b else 1 - p[r, j]
                                         for j, b in enumerate(on)])
            assert got[r, i] == pytest.approx(want, abs=1e-12)


def test_trajectory_is_the_chain_it_claims():
    key = fleet.seed_key(2**31 - 3)
    s = fleet.trajectory(key, np.full(8, 0.9, np.float32),
                         np.full(8, 0.6, np.float32), 20_000)
    stay_good = (s[1:] == 1) & (s[:-1] == 1)
    stay_bad = (s[1:] == 0) & (s[:-1] == 0)
    assert stay_good.sum() / (s[:-1] == 1).sum() == pytest.approx(0.9, abs=0.01)
    assert stay_bad.sum() / (s[:-1] == 0).sum() == pytest.approx(0.6, abs=0.02)

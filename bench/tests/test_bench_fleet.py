"""The four-chip fleet sweep (``ec2_fleet4k.sweep``) at CPU sizes: the
program's ``fleet4k`` family against the plain reference, row for row; the
cell's check, sound and with faults planted; the readers of its two
per-layer metrics on a four-chip trace; the wide DP's cost function."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cost, cost_wide, run, spec
from bench.reference import fleet
from bench.trace_reduce import Event, TraceView

CELL = "ec2_fleet4k.sweep"
SEED = 2**31 + 4242          # larger than 32 signed bits hold
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
V5E = cost.peaks("TPU v5 lite")


def small_cell(n: int = 40, **traffic) -> spec.Cell:
    """The cell at n workers, K* at the configuration's two ratios."""
    cell = spec.load_cell(CELL)
    c = cell.config
    kstar = [-(-k * n * c["r"] // 150) for k in c["k_of_150"]]
    cell.config = dict(c, n=n, kstar=kstar)
    cell.traffic = dict(cell.traffic, **dict(
        dict(rounds=96, seeds=3, chips=1, round_chunk=48, check_rounds=[32, 32]),
        **traffic))
    return cell


def run_small(cell, seed: int = SEED) -> dict:
    return run.execute(cell, seed, 0.3, 0, CPU, jax.devices())


# -- the program's family against the plain reference --------------------------

def test_fleet4k_rows_equal_the_reference_bit_for_bit():
    from repro import sweeps

    scenarios = sweeps.expand("fleet4k", n=40, rounds=160)
    specs = fleet_specs(small_cell(40).config)
    groups = sweeps.build_groups(scenarios, seeds=2)
    (succ,) = sweeps.run_groups(groups)
    (group,) = groups
    for r, row in enumerate(group.rows):
        sc = group.scenarios[row.scenario_index]
        s = specs[scenarios.index(sc)]
        want = fleet.sweep_row(
            fleet.row_key(fleet.seed_key(sc.seed), row.seed_index), s["p_gg"],
            s["p_bb"], s["mu_g"], s["mu_b"], s["deadline"], s["kstar"],
            s["ell_g"], s["ell_b"], 160, sc.strategies)
        np.testing.assert_array_equal(succ[r], want)


def fleet_specs(config):
    return spec.load_cell(CELL).kind.reference_specs(config)


def test_wide_allocation_picks_the_float64_prefix():
    """``allocate_masked`` over 300 workers through the wide body against the
    reference's float64 DP, on every row whose two best prefixes differ by
    more than float32 resolves (1e-5 of the best): a closer pair is a tie to
    float32, as on the plateau near 1 of an easy K*."""
    from repro.core import lea

    n, rows = 300, 128
    rng = np.random.default_rng(300)
    checked = 0
    for kstar in (1400, 1600, 1800):           # around the pool's capacity
        p = rng.uniform(0.05, 0.95, size=(rows, n)).astype(np.float32)
        pool = lea.pool_load(lea.LoadParams(n=n, kstar=kstar, ell_g=10, ell_b=1))
        loads, i_star, feasible = lea.allocate_masked(jnp.asarray(p), pool,
                                                      impl="pallas")
        mask = np.ones((n,), bool)
        want_loads, want_i, want_f = fleet.allocate(p, kstar, 10, 1, mask)
        order = np.argsort(-p, axis=-1, kind="stable")
        tails = fleet.prefix_tails(np.take_along_axis(p, order, axis=-1),
                                   fleet.prefix_thresholds(np.full(rows, kstar), 10, 1, n, n))
        top2 = np.sort(tails, axis=-1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > 1e-5 * top2[:, 1]
        checked += int(clear.sum())
        np.testing.assert_array_equal(np.asarray(i_star)[clear], want_i[clear])
        np.testing.assert_array_equal(np.asarray(loads)[clear], want_loads[clear])
        np.testing.assert_array_equal(np.asarray(feasible), want_f)
    assert checked >= 0.9 * 3 * rows


# -- the cell's check ---------------------------------------------------------------

def test_tied_prefixes_allow_either_outcome():
    """Six workers, K* = 13: every prefix's tail is exactly 1, and prefixes
    of three or fewer good-loaded workers succeed while longer ones do not.
    Then a clear best prefix, and tails all below float32's range."""
    kind = spec.load_cell(CELL).kind
    s = dict(kstar=13, ell_g=10, ell_b=1, mu_g=10.0, mu_b=1.0, deadline=1.0)
    p = np.array([[1, 1, 1, .5, .5, .5]], np.float32)
    states = np.array([[1, 0, 0, 0, 0, 0]])
    assert [v.tolist() for v in kind.lea_outcomes(p, states, s)] == [[True], [True]]
    # one clear best prefix: its outcome alone
    p = np.array([[.99, .9, .6, .2, .1, .1]], np.float32)
    s["kstar"] = 30
    assert [v.tolist() for v in kind.lea_outcomes(p, states, s)] == [[False], [True]]
    # 400 even predictions and K* = 3,950: the best tail, about 1e-110, is
    # below float32's range, so every prefix ties; all 400 good-loaded
    # workers succeed with 396 of them good, one does not
    p = np.full((1, 400), 0.5, np.float32)
    states = (np.arange(400) < 396)[None].astype(np.int32)
    s["kstar"] = 3950
    assert [v.tolist() for v in kind.lea_outcomes(p, states, s)] == [[True], [True]]


@pytest.mark.parametrize("n", [40, 300])
def test_the_reference_argmax_is_always_allowed(n):
    """The outcome of the float64 reference's own argmax is among those the
    check allows, and nearly every sampled round allows only it."""
    cell = small_cell(n)
    kind = cell.kind
    for s in kind.reference_specs(cell.config):
        key = fleet.row_key(fleet.seed_key(11), 1)
        picked = np.arange(0, 400, 7)
        yes, no = kind.sampled_row(key, s, 400, picked, ("lea", "static_single"))
        exact, _ = kind.sampled_row(key, s, 400, picked, ("lea", "static_single"),
                                    dtype=np.float64)
        assert np.all(np.where(exact, yes, no))
        assert np.sum(yes & no) <= 0.05 * yes.size


def test_sound_runs_are_correct():
    result = run_small(small_cell(40))
    assert result["correct"] is True, result["compared"]
    assert list(result)[-1] == "compared"


def test_sound_run_through_the_wide_body(monkeypatch):
    """Past the cut the whole timed path runs what the chip runs: the wide
    DP (interpreted) and the stepped trajectory sampler."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    result = run_small(small_cell(130))
    assert result["correct"] is True, result["compared"]


def test_a_configuration_unlike_the_program_is_refused():
    cell = small_cell(40)
    chains = [list(c) for c in cell.config["chains"]]
    chains[0][0] = 0.75
    cell.config = dict(cell.config, chains=chains)
    with pytest.raises(ValueError, match="fleet4k"):
        run_small(cell)


def _all_bad_loads(real):
    def allocate_masked(p_good, pool, *, impl=None):
        loads, i_star, feasible = real(p_good, pool, impl=impl)
        return jnp.where(pool.mask, pool.ell_b[..., None], 0) + 0 * loads, i_star, feasible
    return allocate_masked


def _half_rows(run_groups):
    def broken(groups, **kw):
        out = []
        for s in run_groups(groups, **kw):
            s = np.array(s)
            half = s.shape[0] // 2
            s[half:] = s[:s.shape[0] - half]
            out.append(s)
        return out
    return broken


def test_an_altered_allocation_fails_the_check(monkeypatch):
    from repro.core import lea

    monkeypatch.setattr(lea, "allocate_masked", _all_bad_loads(lea.allocate_masked))
    jax.clear_caches()
    try:
        result = run_small(small_cell(40, seeds=2))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert result["correct"] is False
    assert result["compared"]["success_mismatch_share"]["value"] > 0


def test_half_the_rows_left_out_fails_the_check(monkeypatch):
    from repro import sweeps

    monkeypatch.setattr(sweeps, "run_groups", _half_rows(sweeps.run_groups))
    result = run_small(small_cell(40))
    assert result["correct"] is False


def _one_chip_of_four_wrong(run_groups):
    """The last quarter of each group's rows, one chip's share on four
    chips, replaced by the first quarter."""
    def broken(groups, **kw):
        out = []
        for s in run_groups(groups, **kw):
            s = np.array(s)
            q = s.shape[0] // 4
            s[-q:] = s[:q]
            out.append(s)
        return out
    return broken


def _four_chip_shares_on_one_device(monkeypatch):
    """The cell's sampling laid out for four chips, its run on one device."""
    from repro.launch import mesh

    real = mesh.make_sweep_mesh
    monkeypatch.setattr(mesh, "make_sweep_mesh", lambda num_devices=None: real(1))


def test_every_chips_share_of_the_batch_is_checked(monkeypatch):
    _four_chip_shares_on_one_device(monkeypatch)
    cell = small_cell(40, seeds=8, chips=4)
    sweep = cell.kind.setup(cell.config, cell.traffic, SEED)
    (group,) = sweep.groups
    block = len(group.rows) // 4
    chips = sorted(sweep.where[(si, rep)][1] // block for si, rep, _ in sweep.picks)
    assert chips == [0, 1, 2, 3]
    # the rounds each scenario's checked rows sample, as the traffic gives them
    for si, _, rounds in sweep.picks:
        assert len(rounds) == cell.traffic["check_rounds"][si]


def test_one_chips_rows_wrong_fails_the_check(monkeypatch):
    from repro import sweeps

    _four_chip_shares_on_one_device(monkeypatch)
    monkeypatch.setattr(sweeps, "run_groups", _one_chip_of_four_wrong(sweeps.run_groups))
    result = run_small(small_cell(40, seeds=8, chips=4))
    assert result["correct"] is False


def test_the_bfloat16_control_fails_the_check(monkeypatch):
    _four_chip_shares_on_one_device(monkeypatch)
    cell = small_cell(1000, rounds=200, seeds=2, chips=4, round_chunk=200,
                      check_rounds=[64, 64])
    sweep = cell.kind.setup(cell.config, cell.traffic, SEED)
    share = sweep.check(control=True)["numbers"]["success_mismatch_share"]
    assert share > cell.traffic["limits"]["success_mismatch_share"]


# -- the readers, on a four-chip trace -----------------------------------------------

WIDE = "jit(_run_group)/shard_map/vmap(jit(simulate_strategies_pool))/repro.allocate/jit(success_tails_wide)/repro.dp_wide"
INFO = {"row_rounds_per_call": 1000.0, "dp_width": 4000,
        "dp_rows": [(500.0, 13334, 10, 1), (500.0, 26667, 10, 1)]}


def _four_chip_view(kernel_ns):
    devices, host = [], []
    for chip in range(4):
        ops = []
        for call in range(2):
            t = call * 10_000_000
            ops += [Event("sort.1", t, t + 1000, "jit(_run_group)/repro.allocate/sort:"),
                    Event("copy.2", t + 1000, t + 1500 + chip, f"{WIDE}/transpose:"),
                    Event("repro.dp_wide.3", t + 2000, t + 2000 + kernel_ns,
                          f"{WIDE}/pallas_call:")]
        devices.append(ops)
    for call in range(2):
        host.append(Event("bench.call", call * 10_000_000, call * 10_000_000 + 9_000_000))
    return TraceView(devices, host, info=INFO, peak=V5E)


def _reader(name):
    return spec.load_cell(CELL).readers[name]


def test_dp_wide_ns_per_row_round_sums_the_chips():
    v = _four_chip_view(kernel_ns=4_000_000)
    per_chip = [2 * (500 + chip + 4_000_000) for chip in range(4)]
    assert _reader("engine.dp_wide_ns_per_row_round").read(v) == pytest.approx(
        sum(per_chip) / (2 * 1000))


def test_wide_roofline_is_the_least_time_over_the_kernel_time():
    work = [cost_wide.poisson_binomial_wide(rows * 2 / 4, 4000, k, 10, 1)
            for rows, k, _, _ in INFO["dp_rows"]]
    least, bound = cost.least_time(work[0] + work[1], V5E)
    assert bound == "bytes"
    # a kernel that took exactly the least time reads 100%
    v = _four_chip_view(kernel_ns=least / 2 * 1e9)
    assert _reader("kernel.poisson_binomial_wide_roofline").read(v) == pytest.approx(100.0)
    v = _four_chip_view(kernel_ns=least / 2 * 1e9 * 40)
    assert _reader("kernel.poisson_binomial_wide_roofline").read(v) == pytest.approx(2.5)


def test_the_readers_are_silent_without_the_wide_body():
    v = TraceView([[Event("fusion", 0, 10, "jit(_run_group)/repro.allocate/add:")]] * 4,
                  [Event("bench.call", 0, 20)], info=INFO, peak=V5E)
    for name in ("engine.dp_wide_ns_per_row_round", "kernel.poisson_binomial_wide_roofline"):
        assert _reader(name).read(v) is None


# -- the cost function ---------------------------------------------------------------

def _brute_force_counts(n, kstar, ell_g, ell_b):
    """Count the (worker, count) cells of the DP that a tail can read:
    count c after i workers, c <= i, below the top bin T or at it, and able
    to reach some later feasible threshold."""
    w = cost_wide.thresholds(n, kstar, ell_g, ell_b)
    feasible = [j for j in range(1, n + 1) if w[j - 1] <= j]
    if not feasible:
        return 0
    v = {j: max(int(w[j - 1]), 0) for j in feasible}
    top = max(v.values())
    needed = 0
    for i in range(1, n + 1):
        for c in range(0, min(i, top) + 1):
            if any(c + (j - i) >= v[j] for j in feasible if j >= i):
                needed += 1
    return needed


@pytest.mark.parametrize("n,kstar,ell_g,ell_b", [
    (12, 40, 10, 1), (12, 80, 10, 1), (30, 100, 10, 1), (30, 250, 10, 1),
    (25, 60, 4, 3), (25, 10, 5, 1), (40, 500, 10, 1), (15, 99, 10, 3)])
def test_needed_counts_match_a_brute_force_count(n, kstar, ell_g, ell_b):
    got = cost_wide.needed_counts(n, kstar, ell_g, ell_b)
    assert got == _brute_force_counts(n, kstar, ell_g, ell_b)
    assert got <= n * (n + 1)                  # never more than the uncapped DP
    wide = cost_wide.poisson_binomial_wide(100, n, kstar, ell_g, ell_b)
    assert wide.ops <= cost.poisson_binomial(100, n).ops
    assert wide.bytes == cost.poisson_binomial(100, n).bytes


def test_the_fleet_cells_work_at_4000_workers():
    # about 890 of the 4,001 counts a worker, at both of the cell's K*
    for kstar in (13334, 26667):
        got = cost_wide.needed_counts(4000, kstar, 10, 1)
        assert 3_550_000 < got < 3_570_000

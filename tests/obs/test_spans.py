"""The span catalogue of ``repro.obs.profiling``: every device scope reaches
the HLO of the program that opens it, host spans reach a profiler trace, and
no ``repro.*`` name hides inside another (trace readers match by substring).
"""

import itertools
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serving, sweeps
from repro.core.coded_ops import (_decode_on_time_modp, _products_modp,
                                  coded_matmul_exact, encode_dataset_modp)
from repro.core.lagrange import CodeSpec
from repro.kernels import dispatch
from repro.kernels.gf.kernel import matmul_gf_pallas
from repro.kernels.poisson_binomial import WIDE_N
from repro.obs.profiling import DEVICE_SCOPES, HOST_SPANS, phase
from repro.serving.engine import _run_serving_group
from repro.sweeps import executor

SRC = pathlib.Path(__file__).resolve().parents[2] / "src" / "repro"
NAMES = [f"repro.{s}" for s in DEVICE_SCOPES] + list(HOST_SPANS)


def test_no_name_is_a_substring_of_another():
    assert len(set(NAMES)) == len(NAMES)
    for a, b in itertools.permutations(NAMES, 2):
        assert a not in b, (a, b)


def test_phase_refuses_a_scope_outside_the_catalogue():
    with pytest.raises(ValueError, match="DEVICE_SCOPES"):
        phase("nowhere")


def _literals(pattern: str) -> set[str]:
    found: set[str] = set()
    for path in SRC.rglob("*.py"):
        found |= set(re.findall(pattern, path.read_text()))
    return found


def test_the_catalogue_is_what_the_program_opens():
    assert _literals(r'_phase\("(\w+)"\)') == set(DEVICE_SCOPES)
    assert _literals(r'(?:annotate|timed)\("(repro\.\w+)"\)') == set(HOST_SPANS)


# -- each scope in the lowered HLO of its program ------------------------------

def _sweep_group_hlo() -> str:
    scenarios = sweeps.expand("hetero_kstar", ks=(50, 99), lams=(0.5,), rounds=8)
    (group,) = sweeps.build_groups(scenarios, seeds=1)
    b = group.batch
    return executor._run_group.lower(
        b.keys, b.p_gg, b.p_bb, b.mu_g, b.mu_b, b.deadline, b.pool,
        rounds=group.rounds, strategies=group.strategies, round_chunk=None,
        telemetry=False, tap=False, tap_stride=None, mesh=None,
    ).as_text(debug_info=True)


def _serving_group_hlo() -> str:
    b, n = 2, 15
    full = lambda v, dt=jnp.int32: jnp.full((b,), v, dt)
    spec = serving.RequestSpec(
        kstar=full(50), ell_g=full(10), ell_b=full(3), deadline_rel=full(1),
        admit_threshold=full(0.5, jnp.float32),
        reserve_cap=full(0.7, jnp.float32))
    process = serving.make_process("poisson", rate=full(1.2, jnp.float32))
    return _run_serving_group.lower(
        jax.random.split(jax.random.PRNGKey(0), b), jnp.ones((b, n), bool),
        jnp.full((b, n), 0.8), jnp.full((b, n), 0.7), full(10.0, jnp.float32),
        full(3.0, jnp.float32), full(1.0, jnp.float32), spec, process, (),
        rounds=4, strategies=("lea",), capacity=3, grace=0,
    ).as_text(debug_info=True)


def _coded_round_hlo() -> str:
    spec = CodeSpec(5, 2, 4, 1)
    coded = encode_dataset_modp(spec, jnp.arange(4 * 2 * 3, dtype=jnp.int32)
                                .reshape(4, 2, 3))
    results = _products_modp.lower(coded.x_tilde, jnp.ones((3,), jnp.int32))
    decode = _decode_on_time_modp.lower(
        spec, jnp.zeros((spec.nr, 2, 1), jnp.int32), jnp.ones((spec.nr,), bool))
    return (results.as_text(debug_info=True)
            + decode.as_text(debug_info=True))


def _gf_matvec_hlo() -> str:
    a = jnp.zeros((16, 40), jnp.uint32)
    b = jnp.zeros((40, 1), jnp.uint32)
    return matmul_gf_pallas.lower(a, b, interpret=True).as_text(debug_info=True)


def _wide_sweep_group_hlo() -> str:
    """A sweep group at the allocator DP's cut, with the kernels the chip
    runs (interpreted): compiled, so the wide body's scope shows nested in
    the allocation."""
    forced = {dispatch.ENV_IMPL: "pallas", dispatch.ENV_INTERPRET: "1"}
    was = {k: os.environ.get(k) for k in forced}
    os.environ.update(forced)
    try:
        scenarios = sweeps.expand("fleet4k", n=WIDE_N, rounds=4)
        (group,) = sweeps.build_groups(scenarios, seeds=1)
        b = group.batch
        return executor._run_group.lower(
            b.keys, b.p_gg, b.p_bb, b.mu_g, b.mu_b, b.deadline, b.pool,
            rounds=group.rounds, strategies=group.strategies, round_chunk=None,
            telemetry=False, tap=False, tap_stride=None, mesh=None,
        ).compile().as_text()
    finally:
        for k, v in was.items():
            if v is None:
                os.environ.pop(k)
            else:
                os.environ[k] = v


PROGRAMS = {
    "sweep_group": (_sweep_group_hlo,
                    ("trajectory", "policy_replay", "allocate", "score")),
    "serving_group": (_serving_group_hlo,
                      ("admission_dp", "admit", "allocate", "edf_order",
                       "score", "release")),
    "coded_round": (_coded_round_hlo, ("products", "decode")),
    "gf_matvec": (_gf_matvec_hlo, ("gf_matvec",)),
    "wide_sweep_group": (_wide_sweep_group_hlo, ("allocate", "dp_wide")),
}


def test_every_scope_has_a_program():
    covered = {s for _, scopes in PROGRAMS.values() for s in scopes}
    assert covered == set(DEVICE_SCOPES)


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_scopes_reach_the_lowered_hlo(program):
    build, scopes = PROGRAMS[program]
    hlo = build()
    for scope in scopes:
        assert f"repro.{scope}" in hlo, (program, scope)
    if "edf_order" in scopes:            # counted inside the allocation too
        assert "repro.allocate/repro.edf_order" in hlo
    if "dp_wide" in scopes:
        assert re.search(r"repro\.allocate/[^\"]*repro\.dp_wide/", hlo)


# -- host spans in a profiler trace ---------------------------------------------

def _host_event_names(trace_dir) -> set[str]:
    from jax.profiler import ProfileData

    (path,) = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return {e.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_program_host_spans_appear_in_a_trace(tmp_path):
    scenarios = sweeps.expand("hetero_kstar", ks=(50,), lams=(0.5,), rounds=8)
    groups = sweeps.build_groups(scenarios, seeds=1)
    spec = CodeSpec(5, 2, 4, 1)
    coded = encode_dataset_modp(spec, jnp.arange(24, dtype=jnp.int32)
                                .reshape(4, 2, 3))
    w, on_time = jnp.ones((3,), jnp.int32), jnp.ones((spec.nr,), bool)
    jax.profiler.start_trace(str(tmp_path))
    try:
        succ = sweeps.run_groups(groups)
        sweeps.run_groups(groups, round_chunk=4, pipeline=True)
        sweeps.summarize(groups, succ)
        np.asarray(coded_matmul_exact(coded, w, on_time)[0])
    finally:
        jax.profiler.stop_trace()
    assert set(HOST_SPANS) <= _host_event_names(tmp_path)

"""The benchmarks/run.py CLI: --list target discovery and target selection."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(*args: str, timeout: int = 120):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _ROOT]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    ).rstrip(os.pathsep)
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=_ROOT,
    )


def test_list_prints_every_registered_target_with_description():
    proc = _run_cli("--list")
    assert proc.returncode == 0, proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    from benchmarks.run import SUITES

    assert len(lines) == len(SUITES)
    for (name, _, desc), line in zip(SUITES, lines):
        assert line.startswith(name) and desc in line
    # --list must not print the CSV header (it runs nothing)
    assert "us_per_call" not in proc.stdout


def test_unknown_target_fails_with_target_listing():
    proc = _run_cli("no_such_bench")
    assert proc.returncode != 0
    assert "no_such_bench" in proc.stderr
    assert "bench_policies" in proc.stderr      # the listing helps recovery


def test_bench_policies_is_a_registered_target():
    from benchmarks.run import SUITES

    names = [name for name, _, _ in SUITES]
    assert "bench_policies" in names and "sweep_smoke" in names


def test_bench_gf_is_a_registered_target_and_listed():
    from benchmarks.run import SUITES

    names = [name for name, _, _ in SUITES]
    assert "bench_gf" in names
    proc = _run_cli("--list")
    assert proc.returncode == 0, proc.stderr
    assert "bench_gf" in proc.stdout and "BENCH_gf.json" in proc.stdout


def test_suite_blurbs_name_exactly_the_manifests_they_write():
    """The SUITES table is the manifest contract: a blurb names a
    BENCH_*.json iff the target writes it, and every named file is
    committed at the repo root."""
    import re

    from benchmarks.run import SUITES

    writers = {
        "fig3_sim": "BENCH_fig3.json",
        "sweep_smoke": "BENCH_sweep.json",
        "bench_speed": "BENCH_speed.json",
        "bench_policies": "BENCH_policies.json",
        "bench_gf": "BENCH_gf.json",
        "bench_faults": "BENCH_faults.json",
        "bench_serving": "BENCH_serving.json",
        "obs_report": "BENCH_obs.json",
    }
    for name, _, desc in SUITES:
        named = re.findall(r"BENCH_\w+\.json", desc)
        if name in writers:
            assert named == [writers[name]], (name, desc)
            assert os.path.exists(os.path.join(_ROOT, writers[name])), name
        else:
            assert not named, f"{name} blurb names a manifest it never writes"


def test_every_committed_manifest_is_provenance_stamped():
    """The manifest contract: every BENCH_*.json writer stamps run
    provenance (repro.obs.provenance via results.manifest/write_manifest)
    and carries the structured ``warnings`` list the softgate records
    append to."""
    import glob
    import json

    from repro.obs.provenance import has_required_fields

    paths = sorted(glob.glob(os.path.join(_ROOT, "BENCH_*.json")))
    assert len(paths) >= 8, paths        # all eight writers are committed
    for path in paths:
        with open(path) as f:
            doc = json.load(f)
        name = os.path.basename(path)
        assert "provenance" in doc, f"{name} missing provenance stamp"
        assert has_required_fields(doc["provenance"]), name
        assert doc["provenance"]["git_sha"], name
        assert isinstance(doc.get("warnings"), list), name
        for w in doc["warnings"]:
            assert {"kind", "bench", "metric", "message"} <= set(w), (name, w)


def test_bench_speed_is_a_registered_target_and_listed():
    from benchmarks.run import SUITES

    names = [name for name, _, _ in SUITES]
    assert "bench_speed" in names
    proc = _run_cli("--list")
    assert proc.returncode == 0, proc.stderr
    assert "bench_speed" in proc.stdout and "BENCH_speed.json" in proc.stdout


def test_committed_bench_speed_manifest_shape_and_invariants():
    """BENCH_speed.json is a committed artifact: the bit-identity, donation
    and warm-restart-0-compiles acceptance results must hold in the
    committed numbers.  rows/sec and the speedup itself are
    machine-dependent and follow the soft-gate convention (a miss is a
    recorded warning, never a hidden one), so only their presence, the
    honest before/after pairing and the structural flags are pinned."""
    import json

    with open(os.path.join(_ROOT, "BENCH_speed.json")) as f:
        doc = json.load(f)
    assert doc["bench"] == "bench_speed"
    assert doc["family"] == "hetero_kstar"
    # hard in-run gates, recorded
    assert doc["bitexact_async_vs_sync"] is True
    assert doc["donated_runtime"] is True
    assert doc["donation_hlo_alias"] is True
    # warm restart of the cached family attributed ZERO backend compiles
    assert doc["cache_warm_backend_compiles"] == 0
    assert doc["cache_cold_backend_compiles"] >= 1
    assert doc["cache_warm_persistent_hits"] >= 1
    # before/after measured in one process: both sides present and positive
    assert doc["sync_rows_per_sec"] > 0 and doc["async_rows_per_sec"] > 0
    assert doc["speedup_async_vs_sync"] == (
        doc["async_rows_per_sec"] / doc["sync_rows_per_sec"])
    assert doc["speedup_bar"] == 1.3
    # a below-bar committed run must carry the structured warning
    if doc["speedup_below_bar"]:
        assert any(w["kind"] == "speedup_bar" for w in doc["warnings"])
    # tap overlap accounting rode along (count > 0 iff events streamed)
    assert doc["tap_block_seconds_count"] > 0
    assert doc["pipeline_stats"]["blocks"] >= 1


def test_bench_faults_is_a_registered_target_and_listed():
    from benchmarks.run import SUITES

    names = [name for name, _, _ in SUITES]
    assert "bench_faults" in names
    proc = _run_cli("--list")
    assert proc.returncode == 0, proc.stderr
    assert "bench_faults" in proc.stdout and "BENCH_faults.json" in proc.stdout


def test_committed_bench_faults_manifest_shape_and_invariants():
    """BENCH_faults.json is a committed artifact: the decode-mode dominance
    and executor-accounting acceptance results must hold in the committed
    numbers, not just in a fresh run.  rows/sec is machine-dependent and
    follows the soft-gate convention, so only its presence is pinned."""
    import json

    with open(os.path.join(_ROOT, "BENCH_faults.json")) as f:
        doc = json.load(f)
    assert doc["bench"] == "bench_faults"
    assert doc["family"] == "packet_erasure"
    assert doc["conserve_contains_aon"] is True
    assert doc["conserve_gain_rounds"] > 0
    # the whole fault grid fuses into one compiled computation
    assert doc["family_compiles"] == {"packet_erasure": 1}
    assert doc["rows_per_sec"] > 0
    # executor accounting: every round in exactly one disposition
    outcomes = doc["executor_outcomes"]
    assert set(outcomes) == {"on_time", "late", "partial", "dropped"}
    assert sum(outcomes.values()) == doc["executor_rounds"]
    assert doc["executor_outcomes_sum_ok"] is True
    for cell in doc["results"]:
        # containment, cell by cell, in the committed rates
        assert cell["recovered_conserve"] >= cell["recovered_aon"]
        assert 0.0 <= cell["served_any"] <= 1.0


@pytest.mark.parametrize("hits,key", [(0, "cold_s"), (3, "cold_cached_s")])
def test_cold_call_served_by_the_compile_cache_stays_out_of_the_cold_trend(
        hits, key):
    """bench_faults/bench_serving run in the benchmarks.run process, where
    the persistent compile cache is on: a first call that loaded its family
    from the cache is recorded apart, so the ``cold_s`` trajectory in
    BENCH_history.jsonl only holds real compiles."""
    sys.path.insert(0, _ROOT)
    from benchmarks._softgate import cold_compile_metric
    from repro.obs import history

    rec = {"bench": "bench_faults", "metrics": cold_compile_metric(1.5, hits)}
    assert rec["metrics"] == {key: 1.5, "cold_cache_hits": hits}
    series = history._series([rec])["bench_faults"]
    assert series == {key: [1.5]}


def test_bench_serving_is_a_registered_target_and_listed():
    from benchmarks.run import SUITES

    names = [name for name, _, _ in SUITES]
    assert "bench_serving" in names
    proc = _run_cli("--list")
    assert proc.returncode == 0, proc.stderr
    assert "bench_serving" in proc.stdout
    assert "BENCH_serving.json" in proc.stdout


def test_committed_bench_serving_manifest_shape_and_invariants():
    """BENCH_serving.json is a committed artifact: the admission-beats-
    admit-all acceptance result, the one-compile contract and the
    conservation flag must hold in the committed numbers, not just in a
    fresh run.  rows/sec is machine-dependent and follows the soft-gate
    convention, so only its presence is pinned."""
    import json

    with open(os.path.join(_ROOT, "BENCH_serving.json")) as f:
        doc = json.load(f)
    assert doc["bench"] == "bench_serving"
    assert doc["family"] == "arrival_grid"
    assert doc["conservation_ok"] is True
    # the acceptance criterion: controlled admission strictly beats
    # admit-all timely throughput on the overloaded cells
    assert doc["admission_beats_admit_all"] is True
    assert doc["admission_gain_requests"] > 0
    # the whole grid, admit-all AND controlled, is one compiled computation
    assert doc["family_compiles"] == {"arrival_grid": 1}
    assert doc["rows_per_sec"] > 0
    rates = set()
    overloaded_gain = 0
    for cell in doc["results"]:
        rates.add(cell["rate"])
        assert cell["served_on_time_controlled"] > 0
        assert cell["served_req_per_sec"] > 0
        # percentiles are real and ordered
        assert (cell["latency_p50_rounds"] <= cell["latency_p95_rounds"]
                <= cell["latency_p99_rounds"])
        assert cell["latency_p50_rounds"] >= 1.0
        if cell["overloaded"]:
            overloaded_gain += (cell["served_on_time_controlled"]
                                - cell["served_on_time_admit_all"])
    # latency + req/sec at >= 3 arrival rates, at least one overloaded
    assert len(rates) >= 3
    assert overloaded_gain == doc["admission_gain_requests"]


def test_obs_report_is_a_registered_target_and_listed():
    from benchmarks.run import SUITES

    names = [name for name, _, _ in SUITES]
    assert "obs_report" in names
    proc = _run_cli("--list")
    assert proc.returncode == 0, proc.stderr
    assert "obs_report" in proc.stdout and "BENCH_obs.json" in proc.stdout


def test_committed_obs_report_manifest_and_trace():
    """BENCH_obs.json is a committed artifact: the telemetry run compiled
    exactly once, the committed Chrome trace is structurally valid and its
    request dispositions reconcile (the flag the bench hard-gates in-run),
    and the cost model covers every hlo_cost entry point."""
    import json

    from repro.launch import hlo_cost
    from repro.obs import validate_trace

    with open(os.path.join(_ROOT, "BENCH_obs.json")) as f:
        doc = json.load(f)
    assert doc["bench"] == "obs_report"
    assert doc["telemetry_compiles"] == 1
    assert doc["trace_dispositions_ok"] is True
    assert doc["trace_complete"] > 0
    targets = {row["target"] for row in doc["cost_model"]}
    assert targets == set(hlo_cost.entry_point_names())
    for row in doc["cost_model"]:
        assert row["flops"] > 0 and row["hbm_bytes"] > 0, row["target"]
    # every sibling manifest was aggregated
    assert set(doc["manifests"]) >= {
        "BENCH_fig3.json", "BENCH_sweep.json", "BENCH_policies.json",
        "BENCH_gf.json", "BENCH_faults.json", "BENCH_serving.json",
    }
    assert doc["missing_provenance"] == []
    # the live tier rode along: tap events streamed during the demo run and
    # the trend section spans a real history trajectory
    assert doc["tap_events"] > 0
    assert doc["trend"]["entries"] >= 2
    assert "regressions" in doc["trend"] and "series" in doc["trend"]
    # the committed trace itself must be a valid trace-event document, and
    # it lives under benchmarks/artifacts/ (the root stays manifest-only)
    assert doc["trace_path"].replace(os.sep, "/").startswith(
        "benchmarks/artifacts/"
    )
    with open(os.path.join(_ROOT, doc["trace_path"])) as f:
        trace = json.load(f)
    stats = validate_trace(trace)
    assert stats["complete"] == doc["trace_complete"]
    assert stats["dispositions"] == doc["trace_dispositions"]


def test_committed_bench_gf_manifest_shape_and_flags():
    """BENCH_gf.json is a committed artifact: it must carry the exact-path
    speedup fields and the bit-exactness flag.  Speedups themselves follow
    the repo's soft-perf convention (sweep_smoke): the bench WARNS below
    the 5x bar and records ``speedup_below_bar``, but wall-clock numbers
    are machine-dependent so the unit test only pins the structure and the
    algorithmic floor (device beats numpy at all)."""
    import json

    with open(os.path.join(_ROOT, "BENCH_gf.json")) as f:
        doc = json.load(f)
    assert doc["bench"] == "bench_gf"
    assert doc["bit_exact_vs_numpy"] is True
    assert doc["field_p"] == (1 << 31) - 1
    assert doc["speedup_bar"] == 5.0
    for key in ("speedup_encode_gemm", "speedup_decode_matrix",
                "speedup_exact_round"):
        assert doc[key] > 1.0, key
    # the committed manifest (this container, idle) must meet the bar
    assert doc["speedup_below_bar"] is False
    names = [r["name"] for r in doc["results"]]
    assert names == ["gf_encode_gemm", "gf_decode_matrix", "gf_exact_round"]

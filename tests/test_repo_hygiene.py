"""Repo hygiene: generated artifacts must never be tracked (and the ones
that ARE deliberately committed — the BENCH manifests and their history —
must keep their contracts: root placement, append-only history, schema)."""

import json
import os
import subprocess

import pytest

from repro.obs import history as obs_history

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _git(*args):
    try:
        proc = subprocess.run(
            ["git", *args], capture_output=True, text=True,
            timeout=30, cwd=_ROOT,
        )
    except (OSError, subprocess.SubprocessError):
        pytest.skip("git unavailable")
    if proc.returncode != 0:
        pytest.skip(f"not a usable git checkout: {proc.stderr.strip()}")
    return proc.stdout


def _tracked_files():
    return _git("ls-files").splitlines()


def test_no_bytecode_or_pycache_is_tracked():
    offenders = [
        f for f in _tracked_files()
        if f.endswith((".pyc", ".pyo")) or "__pycache__" in f.split("/")
    ]
    assert not offenders, f"generated bytecode tracked in git: {offenders}"


def test_gitignore_covers_bytecode():
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        lines = [ln.strip() for ln in f]
    assert "__pycache__/" in lines
    assert any(ln in ("*.pyc", "*.py[cod]") for ln in lines)


def test_repo_root_is_free_of_generated_artifacts():
    """The root keeps ONLY the deliberate bench surface: ``BENCH_*.json``
    manifests, ``BENCH_history.jsonl``, the chip benchmark's description
    ``BENCHMARK.json`` and its ledger ``PERF_LEDGER.jsonl``.  Traces, logs
    and other generated files live under ``benchmarks/artifacts/``."""
    rooted = [f for f in _tracked_files() if "/" not in f]
    offenders = [
        f for f in rooted
        if f.endswith((".jsonl", ".log", ".csv", ".prof", ".trace"))
        and f not in ("BENCH_history.jsonl", "PERF_LEDGER.jsonl")
    ]
    offenders += [
        f for f in rooted
        if f.endswith(".json") and not f.startswith("BENCH_")
        and f != "BENCHMARK.json"
    ]
    assert not offenders, f"generated artifacts tracked at repo root: {offenders}"
    # the trace moved out of the root for good
    assert "obs_trace.json" not in rooted


def _committed_history_lines():
    text = _git("show", f"HEAD:{obs_history.HISTORY_BASENAME}")
    return [ln for ln in text.splitlines() if ln.strip()]


def test_bench_history_is_append_only_vs_head():
    """The committed history may only GROW: every line at HEAD must appear,
    in order, as a prefix of the working-tree file."""
    head_lines = _committed_history_lines()
    path = os.path.join(_ROOT, obs_history.HISTORY_BASENAME)
    with open(path) as f:
        work_lines = [ln.rstrip("\n") for ln in f if ln.strip()]
    assert len(work_lines) >= len(head_lines), (
        "BENCH_history.jsonl lost committed entries (history is append-only)"
    )
    assert work_lines[:len(head_lines)] == head_lines, (
        "BENCH_history.jsonl rewrote committed entries (history is "
        "append-only; regenerate manifests instead of editing the past)"
    )


def test_bench_history_schema_and_provenance():
    path = os.path.join(_ROOT, obs_history.HISTORY_BASENAME)
    records = obs_history.read_history(path)
    assert len(records) >= 2, "history needs a trajectory (>= 2 entries)"
    for rec in records:
        assert obs_history.valid_record(rec), rec
        assert rec["schema"] == obs_history.SCHEMA_VERSION
        assert rec["provenance"]["git_sha"], rec["manifest"]
        assert isinstance(rec["written_at"], float)
        assert rec["metrics"], rec["manifest"]


def test_bench_history_covers_every_committed_manifest():
    manifests = sorted(
        f for f in _tracked_files()
        if "/" not in f and f.startswith("BENCH_") and f.endswith(".json")
    )
    assert manifests, "no committed BENCH manifests?"
    records = obs_history.read_history(
        os.path.join(_ROOT, obs_history.HISTORY_BASENAME)
    )
    seen = {rec["manifest"] for rec in records}
    missing = [m for m in manifests if m not in seen]
    assert not missing, (
        f"committed manifests with no history record: {missing}; "
        "regenerate them via benchmarks/run.py so write_manifest appends"
    )

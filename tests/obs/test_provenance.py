"""Run provenance, manifest stamping, compile-counter registry and the
REPRO_PROFILE gating of the profiling layer."""

import json
import os
import subprocess

import pytest

from repro.obs import (PROFILE_ENV, compile_events, counter_names, phase,
                       profile_dir, provenance, register_compiled)
from repro.obs.provenance import has_required_fields

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_provenance_has_every_schema_field_and_caller_timestamp():
    doc = provenance(1234.5)
    assert has_required_fields(doc)
    assert doc["timestamp"] == 1234.5
    assert doc["python"] and doc["platform"]
    # in-repo: the sha is the checkout's HEAD
    head = subprocess.run(
        ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
        cwd=_ROOT, timeout=30,
    ).stdout.strip()
    assert doc["git_sha"] == head
    assert isinstance(doc["git_dirty"], bool)
    # jax metadata is live in this environment
    assert doc["jax"] and doc["jaxlib"] and doc["backend"]
    json.dumps(doc, allow_nan=False)


def test_provenance_never_raises_outside_a_checkout(tmp_path):
    doc = provenance(0.0, root=str(tmp_path))
    assert doc["git_sha"] is None and doc["git_dirty"] is None
    assert has_required_fields(doc)


def test_manifest_and_write_manifest_stamp_provenance(tmp_path):
    from repro.sweeps import results as rmod

    doc = rmod.manifest([], bench="t", timestamp=99.0)
    assert doc["provenance"]["timestamp"] == 99.0
    assert doc["warnings"] == []
    # hand-assembled docs are stamped by the writer backstop
    path = tmp_path / "BENCH_x.json"
    rmod.write_manifest(path, {"bench": "x"})
    back = json.loads(path.read_text())
    assert has_required_fields(back["provenance"])
    assert back["warnings"] == []
    # an existing stamp is never overwritten
    rmod.write_manifest(path, {"bench": "x", "provenance": {"timestamp": 7.0}})
    assert json.loads(path.read_text())["provenance"] == {"timestamp": 7.0}


def test_counter_registry_names_and_totals():
    # the subsystems register their compiled entry points when imported;
    # import them here so the test does not hang on what ran before it
    from repro import faults, serving, sweeps  # noqa: F401

    names = counter_names()
    for expected in ("engine.simulate_strategies_pool", "sweeps.run_group",
                     "faults.sweep", "serving.sweep"):
        assert expected in names, names
    assert names == tuple(sorted(names))
    total = compile_events()
    assert total == sum(compile_events(n) for n in names)
    # counters are monotonic within a process
    assert total >= 0


def test_register_compiled_rejects_uncallable_hooks():
    with pytest.raises(TypeError):
        register_compiled("bad.hook", object())


def test_profile_gating_and_phase_scope():
    import jax.numpy as jnp

    old = os.environ.pop(PROFILE_ENV, None)
    try:
        assert profile_dir() is None
        # the named scope is trace-time metadata: values are untouched
        with phase("allocate"):
            x = jnp.arange(3) * 2
        assert list(x) == [0, 2, 4]
    finally:
        if old is not None:
            os.environ[PROFILE_ENV] = old


def test_profile_trace_writes_a_trace_when_enabled(tmp_path):
    import pathlib

    from jax.profiler import ProfileData

    from repro.obs import MetricsRegistry, annotate, profile_trace, timed

    reg = MetricsRegistry()
    old = os.environ.get(PROFILE_ENV)
    os.environ[PROFILE_ENV] = str(tmp_path)
    try:
        import jax.numpy as jnp

        with profile_trace("test") as out:
            assert out == str(tmp_path)
            with annotate("span"):
                jnp.arange(4).sum().block_until_ready()
            with timed("phase.demo", reg):
                jnp.arange(8).sum().block_until_ready()
    finally:
        if old is None:
            os.environ.pop(PROFILE_ENV, None)
        else:
            os.environ[PROFILE_ENV] = old
    # jax.profiler drops its dump under plugins/profile/<run>/
    dumped = [p for p, _, files in os.walk(tmp_path) if files]
    assert dumped, "REPRO_PROFILE produced no trace files"
    # both host spans are in the trace under their names, and timed still
    # fills its histogram from the interval it spans
    (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    host = {e.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert {"test", "span", "phase.demo"} <= host
    snap = reg.get("phase.demo.seconds")
    assert snap["count"] == 1 and snap["sum"] > 0

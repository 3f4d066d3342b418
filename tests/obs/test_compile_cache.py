"""Persistent compile cache (repro.launch.cache): warm restarts compile 0.

Two child processes share one ``JAX_COMPILATION_CACHE_DIR`` directory and run the
SAME sweep family.  The cold child populates the cache (real backend
compiles, 0 hits); the warm child must serve every computation from the
persistent cache — 0 backend compile events through the unified counter
(``counters.backend_compile_events``), and ``record_compile`` attributes
nothing to the metrics registry.
"""

import json
import os
import subprocess
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))


def _child(cache_dir: str) -> dict:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_HERE, "..", "..", "src")
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    proc = subprocess.run(
        [sys.executable, os.path.join(_HERE, "_cache_child.py"), cache_dir],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_warm_process_records_zero_compile_events(tmp_path):
    cache_dir = str(tmp_path / "xla_cache")

    cold = _child(cache_dir)
    assert cold["trace_entries"] >= 1
    assert cold["persistent_hits"] == 0
    assert cold["persistent_misses"] >= 1          # cache was really on
    assert cold["backend_compiles"] == cold["trace_entries"]
    assert cold["recorded_compile_metric"] == cold["trace_entries"]
    assert os.listdir(cache_dir)                   # entries persisted

    warm = _child(cache_dir)
    assert warm["trace_entries"] == cold["trace_entries"]  # same tracing
    assert warm["persistent_hits"] >= warm["trace_entries"]
    assert warm["backend_compiles"] == 0           # THE warm-restart contract
    assert warm["recorded_compile_metric"] is None  # nothing attributed


def test_enable_is_idempotent_but_rejects_redirect(tmp_path, monkeypatch):
    from repro.launch import cache as cache_mod

    monkeypatch.setattr(cache_mod, "_STATE",
                        {"enabled_dir": None, "listener": True, "misses": 0})
    d1 = str(tmp_path / "a")
    monkeypatch.setenv(cache_mod.CACHE_ENV, d1)
    try:
        assert cache_mod.enable_compile_cache() == os.path.abspath(d1)
        assert cache_mod.enable_compile_cache() == os.path.abspath(d1)
        assert os.path.isdir(d1)
        monkeypatch.setenv(cache_mod.CACHE_ENV, str(tmp_path / "b"))
        with pytest.raises(RuntimeError, match="already enabled"):
            cache_mod.enable_compile_cache()
    finally:
        # tmp_path dies with the test; leaving the global cache dir pointed
        # at it would make every later compile in this process write there
        import jax

        jax.config.update("jax_compilation_cache_dir", None)


def test_disabled_when_env_unset(monkeypatch):
    """Nothing is enabled until asked; with the variable unset the cache
    goes to the fixed ``<checkout>/.jax_cache``, never a per-run path."""
    from repro.launch import cache as cache_mod

    monkeypatch.delenv(cache_mod.CACHE_ENV, raising=False)
    monkeypatch.setattr(cache_mod, "_STATE",
                        {"enabled_dir": None, "listener": True, "misses": 0})
    assert cache_mod.cache_dir() is None
    root = os.path.abspath(os.path.join(_HERE, "..", ".."))
    assert cache_mod.resolve_cache_dir() == os.path.join(root, ".jax_cache")
    assert cache_mod.resolve_cache_dir() == cache_mod.DEFAULT_DIR

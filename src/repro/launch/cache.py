"""Persistent XLA compilation cache wiring (on by default).

The engine's one-compile-per-family story (``repro.sweeps`` traced-K*
grouping) holds within a process; every restart still pays the full XLA
compile for each family signature.  JAX ships a persistent compilation
cache (supported on cpu/gpu/tpu backends) keyed by the computation
fingerprint; pointing every entry process at one directory makes the
per-family compile a one-time cost per machine.

:func:`enable_compile_cache` is the single switch:

  * the directory is JAX's own ``JAX_COMPILATION_CACHE_DIR`` when that is
    set, so whoever runs the program places the cache; otherwise the fixed
    ``<checkout>/.jax_cache`` (:data:`DEFAULT_DIR`, git-ignored).  The path
    is part of every entry's key, so it never depends on a temporary
    directory, a pid or a clock;
  * sets ``jax_compilation_cache_dir`` plus the two thresholds that
    default to skipping fast-compiling modules
    (``jax_persistent_cache_min_compile_time_secs`` and
    ``..._min_entry_size_bytes`` both to 0 — the sweep families compile in
    O(seconds) but the unit-test families compile in milliseconds, and a
    cache that silently skips them cannot back the warm-restart tests);
  * installs a ``jax.monitoring`` listener feeding persistent-cache HIT
    events into :func:`repro.obs.counters.note_persistent_cache_hits`, so
    the unified compile counter can tell "compiled" from "served from
    cache" (the warm-restart-records-0-compile-events contract).

Callers: ``benchmarks/run.py``, ``chip_smoke.py`` and the launch CLIs
(``repro.launch.serve``, ``repro.launch.train``) call this before any
jitted work — JAX decides once per process, at its first compile, whether
the cache is in use.  It is idempotent per process.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_STATE = {"enabled_dir": None, "listener": False, "misses": 0}


def _listener(event: str, **kwargs) -> None:
    if event == _HIT_EVENT:
        from repro.obs import counters as _counters

        _counters.note_persistent_cache_hits(1)
    elif event == _MISS_EVENT:
        _STATE["misses"] += 1


def persistent_cache_misses() -> int:
    """Persistent-cache misses observed this process (0 until enabled)."""
    return int(_STATE["misses"])


def cache_dir() -> str | None:
    """The directory the cache was enabled with, or None."""
    return _STATE["enabled_dir"]


def resolve_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else :data:`DEFAULT_DIR`."""
    return os.path.abspath(os.environ.get(CACHE_ENV) or DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory.

    Safe to call repeatedly; re-enabling after ``JAX_COMPILATION_CACHE_DIR``
    moved raises — a process mixing cache directories would double-count its
    own compiles.
    """
    target = resolve_cache_dir()
    if _STATE["enabled_dir"] is not None:
        if _STATE["enabled_dir"] != target:
            raise RuntimeError(
                f"compile cache already enabled at {_STATE['enabled_dir']!r}; "
                f"cannot re-enable at {target!r}"
            )
        return target
    os.makedirs(target, exist_ok=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", target)
    # the defaults skip computations compiling faster than 1s / smaller than
    # a floor — useless for test-scale families; cache everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not _STATE["listener"]:
        from jax import monitoring as _monitoring

        _monitoring.register_event_listener(_listener)
        _STATE["listener"] = True
    _STATE["enabled_dir"] = target
    return target

"""The unified compile-event counter.

Before ``repro.obs`` each engine carried its own copy-pasted hook over its
jitted group entry point (``sweeps.compile_cache_size``,
``faults.fault_compile_cache_size``, ``serving.serving_compile_cache_size``
— all three were ``<jitted>._cache_size()`` one-liners).  They now register
here once at import time and the old names are thin aliases over
:func:`compile_events`, so "did this sweep add a compile?" is a single
question with a single answer no matter which engine ran:

    before = obs.compile_events()
    ... run any mix of sweep families ...
    assert obs.compile_events() - before == expected_new_computations

Counters are monotonic per process (they read jit caches, which only
grow); deltas, not absolutes, are the meaningful quantity.  Registration
is idempotent by name — re-importing an engine module re-registers the
same hook.

Persistent-compilation-cache awareness: a jit trace-cache entry appears
whether XLA actually compiled or the persistent cache
(see :mod:`repro.launch.cache`) served the
executable — so a warm-restart process would otherwise look like it
recompiled everything.  :func:`note_persistent_cache_hits` is fed by the
``jax.monitoring`` listener the cache layer installs; executors subtract
the hit delta from the trace-cache delta
(``max(trace_delta - hit_delta, 0)``) before attributing compile events,
so "0 new compile events on a warm restart" is a real, measurable claim.
With the cache not enabled in a process the hit counter stays 0 and every
delta reduces to the plain trace-cache delta.
"""

from __future__ import annotations

import threading
from typing import Callable

_REGISTRY: dict[str, Callable[[], int]] = {}

_PERSISTENT_LOCK = threading.Lock()
_PERSISTENT_HITS = 0


def note_persistent_cache_hits(n: int = 1) -> None:
    """Record ``n`` persistent-compilation-cache hits (listener callback)."""
    global _PERSISTENT_HITS
    if n < 0:
        raise ValueError(f"persistent cache hits increment must be >= 0: {n}")
    with _PERSISTENT_LOCK:
        _PERSISTENT_HITS += int(n)


def persistent_cache_hits() -> int:
    """Monotonic count of persistent-compilation-cache hits this process."""
    with _PERSISTENT_LOCK:
        return _PERSISTENT_HITS


def backend_compile_events(name: str | None = None) -> int:
    """:func:`compile_events` minus process-wide persistent-cache hits.

    The "did XLA actually compile?" view: clamped at 0 because hits are
    counted process-wide (op-by-op dispatches hit the cache too) while the
    trace-cache counters are per entry point.  Meaningful as a delta
    around a call window, exactly like :func:`compile_events`.
    """
    return max(compile_events(name) - persistent_cache_hits(), 0)


def register_compiled(name: str, jitted) -> None:
    """Register a jitted entry point's compile-cache counter under ``name``.

    ``jitted`` is anything with a ``_cache_size()`` hook (a ``jax.jit``
    wrapper) or a plain zero-arg callable returning an int.
    """
    hook = getattr(jitted, "_cache_size", jitted)
    if not callable(hook):
        raise TypeError(f"{name!r}: {jitted!r} has no _cache_size and is not callable")
    _REGISTRY[name] = hook


def counter_names() -> tuple[str, ...]:
    """Registered counter names, sorted (only imported engines appear)."""
    return tuple(sorted(_REGISTRY))


def compile_events(name: str | None = None) -> int:
    """Compiled computations so far: one named counter, or the sum of all.

    With ``name=None`` the value sums every registered engine — the number
    the acceptance tests diff around a sweep to assert "this run added
    exactly N compiles" (N=1 per new family signature, and telemetry=on
    must add zero beyond that).
    """
    if name is None:
        return sum(int(hook()) for hook in _REGISTRY.values())
    if name not in _REGISTRY:
        raise KeyError(
            f"no compile counter {name!r}; registered: {counter_names()}"
        )
    return int(_REGISTRY[name]())

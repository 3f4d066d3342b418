"""Profiling hooks: named device scopes, host spans, REPRO_PROFILE traces.

Three layers, all zero-cost when unused:

  * :func:`phase` — ``jax.named_scope`` ``repro.<name>`` around a step of a
    traced program.  Pure trace-time metadata: the names land in the HLO
    (and therefore on the device operations of a profiler trace) and add
    NOTHING at runtime, so the programs open them unconditionally.  A
    scope reaches the device only when it is opened while the program is
    traced: around an eager call of a jitted function it names nothing.
    :data:`DEVICE_SCOPES` is the catalogue of every scope the programs
    open; :func:`phase` refuses any other name.
  * :func:`annotate` — a host-side ``jax.profiler.TraceAnnotation`` span,
    the program's one host span primitive.  :func:`repro.obs.metrics.timed`
    opens one under its own name.  :data:`HOST_SPANS` is the catalogue of
    the spans the program opens.  No-op unless a profiler trace is being
    collected.
  * :func:`profile_trace` — the collection gate: when the
    ``REPRO_PROFILE`` env var names a directory, the context manager wraps
    its body in ``jax.profiler.start_trace``/``stop_trace`` and dumps a
    trace viewable in Perfetto / TensorBoard there; unset, it is a no-op.
    ``benchmarks/run.py`` wraps every selected suite in it, so

        REPRO_PROFILE=/tmp/trace python -m benchmarks.run bench_serving

    profiles a whole target with the scopes and spans labelled.

Host spans and device scopes share the trace's clock, and a trace reader
finds them by substring, so no ``repro.*`` name is a substring of another.

jax is imported lazily so ``--list``-style cold paths never pay for it.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator

PROFILE_ENV = "REPRO_PROFILE"

# every device scope, ``repro.<name>`` in the HLO, and where it is opened
DEVICE_SCOPES = (
    "trajectory",     # core/throughput.py: worker-state trajectory sample
    "policy_replay",  # core/throughput.py: the policy's estimator replay
    "allocate",       # core/throughput.py, serving/engine.py: LEA allocation
    "score",          # core/throughput.py, serving/engine.py: on-time rule
    "decode",         # core/coded_ops.py, faults/engine.py: master decode
    "products",       # core/coded_ops.py: the workers' exact GF(p) products
    "admit",          # serving/engine.py: admission gates + queue admit
    "edf_order",      # serving/engine.py, inside allocate: EDF queue order
    "release",        # serving/engine.py: slot recycling
    "admission_dp",   # serving/engine.py: batched admission prediction
    "gf_matvec",      # kernels/gf/kernel.py: the GF(p) narrow-width body
    "dp_wide",        # kernels/poisson_binomial/kernel.py, inside allocate:
                      # the allocator DP's wide body
)

# every host span the program opens (through annotate, or timed)
HOST_SPANS = (
    "repro.run_group",      # sweeps/executor.py: one sync group, to ready
    "repro.run_pipelined",  # sweeps/executor.py: one pipelined group
    "repro.pull",           # sweeps/executor.py: a group's result to host
    "repro.summarize",      # sweeps/results.py: the host fold of a sweep
    "repro.round",          # core/coded_ops.py: dispatch of one exact round
)


def profile_dir() -> str | None:
    """The REPRO_PROFILE trace directory, or None when profiling is off."""
    return os.environ.get(PROFILE_ENV) or None


def phase(name: str):
    """``jax.named_scope`` ``repro.<name>`` for one catalogued device scope."""
    import jax

    if name not in DEVICE_SCOPES:
        raise ValueError(f"{name!r} is not in DEVICE_SCOPES {DEVICE_SCOPES}")
    return jax.named_scope(f"repro.{name}")


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Host-side profiler span; inert when no trace is being collected."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def profile_trace(label: str = "repro") -> Iterator[str | None]:
    """Collect a jax profiler trace into $REPRO_PROFILE, if set.

    Yields the trace directory (or None when profiling is off).  The
    directory is created if missing; ``stop_trace`` runs even when the
    body raises, so a crashing benchmark still leaves a usable trace.
    """
    out = profile_dir()
    if out is None:
        yield None
        return
    import jax

    os.makedirs(out, exist_ok=True)
    jax.profiler.start_trace(out)
    try:
        with annotate(label):
            yield out
    finally:
        jax.profiler.stop_trace()

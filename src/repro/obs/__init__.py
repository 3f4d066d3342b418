"""repro.obs — observability: telemetry streams, provenance, profiling.

The engines (:mod:`repro.core.throughput`, :mod:`repro.faults.engine`,
:mod:`repro.serving.engine`) expose an optional ``telemetry=`` static flag
that threads extra per-round streams out of the SAME compiled computation
— estimator error vs. the genie's true p_good, allocated-load totals,
allocator prefix sizes, queue occupancy, admission decisions, fault-event
counts.  ``telemetry=False`` (the default) is literally the pre-existing
code path: bit-identical outputs, zero cost, and a telemetry-on batch
still compiles exactly once per sweep family (asserted through the
unified compile counter below).  This package owns everything that sits
on top of those streams:

  * :mod:`~repro.obs.counters`   — the ONE compile-event counter registry
    behind ``sweeps.compile_cache_size`` /
    ``faults.fault_compile_cache_size`` /
    ``serving.serving_compile_cache_size`` (all three are now thin
    aliases over :func:`compile_events`);
  * :mod:`~repro.obs.telemetry`  — :class:`TelemetryFrame` /
    :class:`FaultTelemetry` / :class:`ServingTelemetry` pytrees plus
    host-side exporters: flat metric tables (:func:`metric_streams`,
    :func:`metric_table`) and Chrome trace-event JSON
    (:func:`serving_trace`, viewable in Perfetto / ``chrome://tracing``);
  * :mod:`~repro.obs.provenance` — :func:`provenance`: git sha + dirty
    flag, jax/jaxlib versions, backend/device, caller-supplied timestamp
    — stamped into every ``BENCH_*.json`` by
    :func:`repro.sweeps.results.write_manifest`;
  * :mod:`~repro.obs.profiling`  — the span catalogue and its two
    primitives: :func:`phase`, a ``jax.named_scope`` ``repro.<name>``
    device scope inside a traced program (``DEVICE_SCOPES``: trajectory
    sample -> policy replay -> allocate -> score -> decode in the engines,
    the coded round's products, the serving queue's admit / edf_order /
    release and its admission DP), and :func:`annotate`, the one host-side
    ``jax.profiler.TraceAnnotation`` span (``HOST_SPANS``: the executor's
    run_group / run_pipelined / pull, the sweep summarize, the coded
    round's dispatch); plus a ``REPRO_PROFILE=<dir>``-gated
    profiler-trace context manager.

The LIVE tier (PR 9) sits next to the post-hoc ``telemetry=`` streams:

  * :mod:`~repro.obs.taps`       — the ``tap=`` static engine flag's host
    side: ``io_callback``-backed block-aggregate events streamed DURING
    compiled scans, handler registry (:func:`add_tap` /
    :func:`capture_taps`), event schema validation; tap-off is
    bit-identical and zero-callback, tap-on still compiles once per
    family signature (same contract as ``telemetry=``);
  * :mod:`~repro.obs.metrics`    — host metrics registry (named counters /
    gauges / histograms under a strict naming convention) with JSONL,
    Prometheus-exposition and stderr progress-line sinks, plus per-phase
    wall-clock / compile-time attribution (:func:`timed`, which opens an
    :func:`annotate` span of the same name, and :func:`record_compile`);
  * :mod:`~repro.obs.history`    — ``BENCH_history.jsonl``: every
    :func:`repro.sweeps.results.write_manifest` appends a compact
    provenance-stamped record, and :func:`~repro.obs.history.trend_report`
    flags robust (median-vs-MAD-envelope) slowdowns across the trajectory
    — the softgate's "vs HEAD" widened to "vs trajectory"
    (``benchmarks/run.py --check`` gates on it).

``benchmarks/run.py obs_report`` is the consumer: it aggregates every
committed ``BENCH_*.json`` into one provenance-stamped regression summary
(metric deltas vs. the committed baselines, softgate warnings collected,
trend section over the history) and renders a serving run as a
request-timeline trace.
"""

from .counters import compile_events, counter_names, register_compiled
from .history import (HISTORY_BASENAME, HISTORY_ENV, append_record,
                      history_path, read_history, record_from_manifest,
                      trend_report)
from .metrics import (DEFAULT as default_metrics, JsonlSink, MetricsRegistry,
                      ProgressLine, record_compile, tap_to_registry, timed)
from .profiling import (DEVICE_SCOPES, HOST_SPANS, PROFILE_ENV, annotate,
                        phase, profile_dir, profile_trace)
from .provenance import provenance
from .taps import (EVENT_STREAMS, TAP_ENGINES, add_tap, capture_taps,
                   remove_tap, tap_names, validate_event)
from .telemetry import (FaultTelemetry, ServingTelemetry, TelemetryFrame,
                        metric_streams, metric_table, serving_trace,
                        validate_trace, write_trace)

__all__ = [
    "DEVICE_SCOPES", "EVENT_STREAMS", "FaultTelemetry", "HISTORY_BASENAME",
    "HISTORY_ENV", "HOST_SPANS", "JsonlSink", "MetricsRegistry",
    "PROFILE_ENV", "ProgressLine", "ServingTelemetry", "TAP_ENGINES",
    "TelemetryFrame", "add_tap", "annotate", "append_record",
    "capture_taps", "compile_events", "counter_names", "default_metrics",
    "history_path", "metric_streams", "metric_table", "phase",
    "profile_dir", "profile_trace", "provenance", "read_history",
    "record_compile", "record_from_manifest", "register_compiled",
    "remove_tap", "serving_trace", "tap_names", "tap_to_registry", "timed",
    "trend_report", "validate_event", "validate_trace", "write_trace",
]

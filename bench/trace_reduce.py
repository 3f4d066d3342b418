"""Reduce a profiler trace to the numbers the per-layer metrics read.

A trace is JAX's ``.xplane.pb``: an ``XSpace`` protobuf of planes, lines and
events with a start and a duration, host and device on one clock. It is
read here with a minimal schema of its own (only the fields used below), so
that each device operation keeps what ``jax.profiler.ProfileData`` does not
show: its ``tf_op`` (the ``jax.named_scope`` path it was traced under) and
the program it ran in. Kept from it:

* device operations: the ``XLA Ops`` line of every ``/device:TPU:<i>``
  plane, each with its short name, its scope path, its program, and its
  self time (its duration less that of the operations nested in it, as the
  body of a ``while`` is nested in the loop);
* host events: every event on the host's threads (the benchmark's own
  ``bench.*`` spans among them).

From those: device busy time (the union of operation intervals), time under
a named scope, time of a kernel, idle time inside each ``bench.call`` span,
and idle gaps named by what the host was doing in them.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

import numpy as np

CALL = "bench.call"
BENCH_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float      # ns
    end: float        # ns
    path: str = ""    # device ops: the scope path (``tf_op``) and the name
    module: str = ""  # device ops: the program it ran in
    self_ns: float | None = None

    @property
    def dur(self) -> float:
        return self.end - self.start


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _clipped_total(merged, lo: float, hi: float) -> float:
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def set_self_times(ops: list[Event]) -> None:
    """Each op's duration less its directly nested ops' (ops sorted by start)."""
    stack: list[Event] = []
    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        e.self_ns = e.dur
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack and e.end <= stack[-1].end:
            stack[-1].self_ns -= e.dur
        stack.append(e)


# -- the XSpace protobuf, as much of it as is read here ----------------------

_SCHEMA = {
    "XStat": [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
              ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
              ("str_value", 5, "string"), ("bytes_value", 6, "bytes"),
              ("ref_value", 7, "uint64")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64"), ("stats", 4, "*XStat")],
    "XLine": [("id", 1, "int64"), ("name", 2, "string"), ("timestamp_ns", 3, "int64"),
              ("events", 4, "*XEvent")],
    "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                       ("display_name", 4, "string"), ("stats", 5, "*XStat")],
    "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
    "EventMetadataEntry": [("key", 1, "int64"), ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XPlane": [("id", 1, "int64"), ("name", 2, "string"), ("lines", 3, "*XLine"),
               ("event_metadata", 4, "*EventMetadataEntry"),
               ("stat_metadata", 5, "*StatMetadataEntry"), ("stats", 6, "*XStat")],
    "XSpace": [("planes", 1, "*XPlane")],
}


def xspace_class():
    """A protobuf message class for ``XSpace``, built from ``_SCHEMA``."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    field = descriptor_pb2.FieldDescriptorProto
    for msg, fields in _SCHEMA.items():
        m = fdp.message_type.add(name=msg)
        for name, number, kind in fields:
            f = m.field.add(name=name, number=number,
                            label=field.LABEL_REPEATED if kind[0] == "*"
                            else field.LABEL_OPTIONAL)
            kind = kind.lstrip("*")
            if kind in _SCHEMA:
                f.type, f.type_name = field.TYPE_MESSAGE, f".bench_xplane.{kind}"
            else:
                f.type = getattr(field, "TYPE_" + kind.upper())
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stat_value(stat, stat_names: dict) -> str:
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    return stat.str_value or str(stat.uint64_value or stat.int64_value)


def read_xplane(path: str) -> tuple[list[list[Event]], list[Event]]:
    """(device ops per chip, host events) of one ``.xplane.pb`` file."""
    space = xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices, host = [], []
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        is_device = (plane.name.startswith(DEVICE_PREFIX)
                     and plane.name[len(DEVICE_PREFIX):].isdigit())
        if not (is_device or plane.name.startswith("/host:")):
            continue
        lines = {line.name: line for line in plane.lines}
        if not is_device:
            for line in plane.lines:
                base = line.timestamp_ns
                host.extend(Event(meta[e.metadata_id].name,
                                  base + e.offset_ps * 1e-3,
                                  base + (e.offset_ps + e.duration_ps) * 1e-3)
                            for e in line.events)
            continue
        modules = {}
        if MODULES_LINE in lines:
            for e in lines[MODULES_LINE].events:
                name = meta[e.metadata_id].name              # jit_f(<program id>)
                modules[name[name.rfind("(") + 1:-1]] = name[:name.rfind("(")]
        info = {}
        for key, md in meta.items():
            stats = {stat_names.get(s.metadata_id): _stat_value(s, stat_names)
                     for s in md.stats}
            info[key] = (md.display_name or md.name, stats.get("tf_op", ""),
                         modules.get(stats.get("program_id", ""), ""))
        ops = []
        if OPS_LINE in lines:
            line = lines[OPS_LINE]
            for e in line.events:
                name, tf_op, module = info[e.metadata_id]
                start = line.timestamp_ns + e.offset_ps * 1e-3
                ops.append(Event(name, start, start + e.duration_ps * 1e-3,
                                 f"{tf_op} {name}", module))
        set_self_times(ops)
        devices.append(ops)
    return devices, host


class TraceView:
    """One traced window: ``info`` holds the driver's per-call counts and
    ``peak`` the published peaks of the device the trace ran on."""

    def __init__(self, device_ops: list[list[Event]], host: list[Event],
                 info: dict | None = None, peak: dict | None = None):
        if not any(device_ops):
            raise ValueError("the trace holds no device operation")
        for ops in device_ops:
            if any(e.self_ns is None for e in ops):
                set_self_times(ops)
        self.device_ops = [sorted(ops, key=lambda e: e.start) for ops in device_ops]
        self.host = host
        self.calls = sorted((e for e in host if e.name == CALL), key=lambda e: e.start)
        if not self.calls:
            raise ValueError(f"the trace holds no {CALL} span")
        self.info = info or {}
        self.peak = peak
        self.lo, self.hi = self.calls[0].start, self.calls[-1].end
        self._busy = [_merge([(e.start, e.end) for e in ops]) for ops in self.device_ops]

    @classmethod
    def load(cls, trace_dir: str, info: dict, device_kind: str,
             chips: int = 1) -> "TraceView":
        from bench import cost

        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        if len(paths) != 1:
            raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                    f"found {len(paths)}")
        devices, host = read_xplane(paths[0])
        return cls(devices[:chips], host, info, cost.peaks(device_kind))

    # -- totals over the window ------------------------------------------------

    @property
    def n_calls(self) -> int:
        return len(self.calls)

    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the chips traced."""
        per_chip = [_clipped_total(m, self.lo, self.hi) for m in self._busy]
        return float(np.mean(per_chip)) * 1e-9

    def op_ns(self, match) -> float:
        """Self time of the operations ``match(event)`` accepts, summed on each
        chip and averaged over the chips."""
        per_chip = [sum(e.self_ns for e in ops if self.lo <= e.start < self.hi and match(e))
                    for ops in self.device_ops]
        return float(np.mean(per_chip))

    def scope_ns(self, scope: str) -> float:
        """Device time of the operations traced under ``scope``."""
        return self.op_ns(lambda e: scope in e.path)

    # -- inside each call -------------------------------------------------------

    def idle_ns_per_call(self) -> list[float]:
        """Device-idle nanoseconds inside each ``bench.call`` span (chip 0)."""
        merged = self._busy[0]
        return [c.dur - _clipped_total(merged, c.start, c.end) for c in self.calls]

    # -- breakdown ---------------------------------------------------------------

    def _host_label(self, t: float) -> str:
        covering = [e for e in self.host if e.start <= t < e.end]
        bench = [e for e in covering if e.name.startswith(BENCH_PREFIX)]
        other = [e for e in covering if not e.name.startswith(BENCH_PREFIX)]
        parts = []
        if bench:
            parts.append(min(bench, key=lambda e: e.dur).name)
        if other:
            parts.append(min(other, key=lambda e: e.dur).name)
        return " / ".join(parts) if parts else "no host span"

    def idle_gaps(self, top: int = 10) -> list[tuple[str, float]]:
        """The ``top`` longest idle stretches of chip 0 inside the window,
        longest first, each named by the host span it fell in."""
        edges = [self.lo]
        for lo, hi in self._busy[0]:
            if hi <= self.lo or lo >= self.hi:
                continue
            edges += [max(lo, self.lo), min(hi, self.hi)]
        edges.append(self.hi)
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self._host_label((a + b) / 2), (b - a) * 1e-9) for a, b in gaps[:top]]

    def top_ops(self) -> list[tuple[str, float]]:
        totals: dict[str, float] = collections.defaultdict(float)
        for e in self.device_ops[0]:
            if self.lo <= e.start < self.hi:
                totals[f"{e.module}/{e.name}" if e.module else e.name] += e.self_ns
        top = sorted(totals.items(), key=lambda kv: -kv[1])
        return [(name, ns * 1e-9) for name, ns in top]

    def breakdown(self, top: int = 10) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops()[:top]],
                "idle_gaps": [list(x) for x in self.idle_gaps(top)]}

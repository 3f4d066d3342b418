"""The benchmark's description, read from ``BENCHMARK.json`` and found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix. Each
lives in a file of its own, found by its name alone, so that a new cell is
new files plus new entries and never an edit:

* ``bench/configs/<config>.json`` -- the deployment: its sizes, ``assumed``
  and ``reduced``;
* ``bench/traffic/<traffic>.json`` -- the mix: its parameters, the ``kind``
  of driver that runs it and the limits of its correctness comparison;
* ``bench/kinds/<kind>.py`` -- one driver per kind of work (set-up, one unit
  of work, the check against the plain reference);
* ``bench/metrics/<metric>.py`` -- one reader per per-layer metric;
* ``bench/end_to_end/<metric>.py`` -- one reader per end-to-end metric
  other than ``setup_s``, which the harness takes itself.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
SETUP = "setup_s"


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> types.ModuleType:
    """Import the file at ``path`` (its name may hold dots) as ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One workload with everything found for it by name."""

    name: str
    chips: int
    config: dict
    traffic: dict
    kind: types.ModuleType
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict[str, types.ModuleType]
    e2e_readers: dict[str, types.ModuleType]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, bench_dir: str = BENCH_DIR,
              benchmark: dict | None = None) -> Cell:
    """The cell named ``workload``, with its files found by name."""
    if benchmark is None:
        benchmark = _load_json(os.path.join(os.path.dirname(bench_dir),
                                            "BENCHMARK.json"))
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    config = _load_json(os.path.join(bench_dir, "configs", w["config"] + ".json"))
    traffic = _load_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    kind = load_module(os.path.join(bench_dir, "kinds", traffic["kind"] + ".py"),
                       f"bench_kind_{traffic['kind']}")
    end_to_end = [m for m in benchmark["end_to_end"] if _applies(m, workload)]
    per_layer = [m for m in benchmark["per_layer"] if _applies(m, workload)]
    readers = {
        m["name"]: load_module(os.path.join(bench_dir, "metrics", m["name"] + ".py"),
                               "bench_metric_" + m["name"].replace(".", "_"))
        for m in per_layer
    }
    e2e_readers = {
        m["name"]: load_module(os.path.join(bench_dir, "end_to_end", m["name"] + ".py"),
                               "bench_e2e_" + m["name"])
        for m in end_to_end if m["name"] != SETUP
    }
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, kind=kind, end_to_end=end_to_end,
                per_layer=per_layer, readers=readers, e2e_readers=e2e_readers)

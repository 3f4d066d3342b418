"""A configuration, a traffic kind and metrics added as new files are found
by their names, and run, with no edit to any file that is there."""

import hashlib
import json
import os
import shutil

import jax
import pytest

from bench import run, spec
from bench.trace_reduce import Event, TraceView

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
PARTS = ("configs", "traffic", "kinds", "metrics", "end_to_end")

TOY_KIND = '''"""A toy kind: one jitted product per unit of work."""
import numpy as np


class Driver:
    def __init__(self, config, traffic, seed):
        import jax.numpy as jnp

        self.x = jnp.asarray(np.random.default_rng(seed).standard_normal(
            (config["width"], config["width"])), jnp.float32)
        self.last = None

    def call(self):
        self.last = np.asarray(self.x @ self.x)

    def work(self):
        return float(self.x.shape[0] ** 3)

    def warm(self):
        self.call()

    def layer_info(self):
        return {"products_per_call": 1.0}

    def free(self):
        self.x = None

    def check(self, control=False):
        return {"numbers": {"nan_count": float(np.isnan(self.last).sum())}}


def setup(config, traffic, seed):
    return Driver(config, traffic, seed)
'''
TOY_E2E = '''def value(window):
    return sum(w for _, _, w in window["calls"]) / (window["end"] - window["start"])
'''
TOY_METRIC = '''def read(view):
    return 100.0 * view.busy_s() / view.window_s()
'''


def _digest(root):
    out = {}
    for part in PARTS:
        for name in sorted(os.listdir(os.path.join(root, part))):
            with open(os.path.join(root, part, name), "rb") as f:
                out[(part, name)] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture
def extended(tmp_path):
    """A copy of the benchmark's files with one new cell added as new files."""
    for part in PARTS:
        shutil.copytree(os.path.join(BENCH, part), tmp_path / part)
    before = _digest(tmp_path)
    (tmp_path / "configs" / "toy_fleet.json").write_text(json.dumps({"width": 64}))
    (tmp_path / "traffic" / "toy_mix.json").write_text(json.dumps(
        {"kind": "toy", "trace_calls": 2, "limits": {"nan_count": 0.0}}))
    (tmp_path / "kinds" / "toy.py").write_text(TOY_KIND)
    (tmp_path / "end_to_end" / "toy_flops_per_s.py").write_text(TOY_E2E)
    (tmp_path / "metrics" / "toy.busy_pct.py").write_text(TOY_METRIC)
    after = _digest(tmp_path)
    assert {k: after[k] for k in before} == before        # nothing edited
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["configs"].append({"name": "toy_fleet", "source": "a test",
                                 "file": "bench/configs/toy_fleet.json",
                                 "reduced": [], "why": "a test"})
    benchmark["workloads"].append({"name": "toy_fleet.toy_mix", "config": "toy_fleet",
                                   "traffic": "toy_mix", "chips": 1, "why": "a test"})
    benchmark["end_to_end"].insert(0, {
        "name": "toy_flops_per_s", "unit": "flop/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["toy_fleet.toy_mix"]})
    benchmark["per_layer"].append({
        "name": "toy.busy_pct", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "toy", "moves": "toy_flops_per_s",
        "workloads": ["toy_fleet.toy_mix"]})
    return str(tmp_path), benchmark


def test_new_files_are_found_by_name(extended):
    bench_dir, benchmark = extended
    cell = spec.load_cell("toy_fleet.toy_mix", bench_dir=bench_dir, benchmark=benchmark)
    assert cell.config == {"width": 64}
    assert cell.kind.__file__ == os.path.join(bench_dir, "kinds", "toy.py")
    assert [m["name"] for m in cell.end_to_end] == ["toy_flops_per_s", "setup_s"]
    assert list(cell.readers) == ["toy.busy_pct"]
    view = TraceView([[Event("op", 0, 30)]], [Event("bench.call", 0, 100)])
    assert cell.readers["toy.busy_pct"].read(view) == pytest.approx(30.0)
    # the cells that were there do not see the new metrics
    old = spec.load_cell("ec2_t2micro.sweep", bench_dir=bench_dir, benchmark=benchmark)
    assert "toy.busy_pct" not in old.readers
    assert "toy_flops_per_s" not in old.e2e_readers


def test_a_new_cell_runs_through_the_harness(extended):
    bench_dir, benchmark = extended
    cell = spec.load_cell("toy_fleet.toy_mix", bench_dir=bench_dir, benchmark=benchmark)
    result = run.execute(cell, 2**31 + 1, 0.2, 0,
                         {"platform": "cpu", "kind": "cpu", "count": 1}, jax.devices())
    assert result["correct"] is True
    assert set(result["metrics"]) == {"toy_flops_per_s", "setup_s"}
    assert result["metrics"]["toy_flops_per_s"]["value"] > 0
    assert result["compared"] == {"nan_count": {"value": 0.0, "limit": 0.0}}

"""chip_smoke.py off the chip: it refuses to run, and its phases are right.

The script itself runs only on a TPU; here its phase functions run at tiny
sizes on the CPU (same control flow and the same correctness checks, minus
the pinned full-size throughputs and the kernel-presence check, which need
the chip), and the script is shown to exit non-zero without a TPU, with a
kernel override set, and outside the repository.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import types

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cwd: str, script: str, **env_extra) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    return subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=300, env=env, cwd=cwd)


def _printed_result(stdout: str) -> bool:
    return any('"ok"' in line for line in stdout.splitlines())


def test_exits_nonzero_without_a_tpu():
    proc = _run(_ROOT, _SCRIPT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not _printed_result(proc.stdout)


def test_exits_nonzero_alone_in_a_directory(tmp_path):
    shutil.copy(_SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert not _printed_result(proc.stdout)


@pytest.mark.parametrize("var", ["REPRO_KERNEL_INTERPRET", "REPRO_KERNEL_IMPL"])
def test_refuses_kernel_overrides_on_a_tpu(monkeypatch, var):
    cs = _load()
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert cs.require_tpu([tpu]) == {"platform": "tpu",
                                     "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setenv(var, "1")
    with pytest.raises(SystemExit):
        cs.require_tpu([tpu])


def test_phases_at_tiny_size(capsys):
    cs = _load()
    sched = cs.phase_scheduler(rounds=64, pinned=None, kernel_batch=16,
                               expect_kernel=False)
    assert len(sched["lea"]) == 4
    serving = cs.phase_serving(["--smoke"])
    assert serving["lea"]["arrivals"] > 0
    coded = cs.phase_coded_round(n=6, r=3, k=5, rows=3, cols=7)
    assert coded["exact_ok"]
    out = capsys.readouterr().out
    assert "sync == pipelined" in out
    assert "decoded round == X_j @ w mod p" in out


def test_four_chip_phase_on_virtual_cpu_devices():
    """The --four-chips comparison, rehearsed on 4 virtual CPU devices."""
    code = ("import chip_smoke as cs; "
            "cs.phase_four_chips(devices=4, seeds=4, rounds=32)")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env, cwd=_ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "bit-identical" in proc.stdout

"""A run with the timed path broken underneath reads ``correct: false``.

Each test drives the whole of a run but the look for a chip (the cells cut
to CPU size), with one fault planted where the program produces its
answer: an answer altered, half of the batch left out and the rest
repeated in its place, or a state that never advances. (The cells run on
one chip, so no exchange between chips can be left out.)
"""

import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests.small import run_small


def test_sound_runs_are_correct():
    for name in ("ec2_t2micro.sweep", "ec2_t2micro.coded_round", "sim_t2micro.serve"):
        result = run_small(name)
        assert result["correct"] is True, (name, result["compared"])
        assert result["failed"] == 0
        assert list(result)[-1] == "compared"


# -- the sweep -----------------------------------------------------------------

def _broken_sweep(monkeypatch, fault):
    from repro import sweeps

    real = sweeps.run_groups

    def broken(groups, **kw):
        return [fault(np.array(s)) for s in real(groups, **kw)]

    monkeypatch.setattr(sweeps, "run_groups", broken)


def _flip_last_round(s):
    s[:, -1, 0] = ~s[:, -1, 0]
    return s


def _half_batch(s):
    half = s.shape[0] // 2
    s[half:] = s[:s.shape[0] - half]
    return s


def _state_unchanged(s):
    return np.broadcast_to(s[:, :1], s.shape).copy()


@pytest.mark.parametrize("fault", [_flip_last_round, _half_batch, _state_unchanged],
                         ids=["answer_altered", "half_batch", "state_unchanged"])
def test_sweep_faults_fail_the_check(monkeypatch, fault):
    _broken_sweep(monkeypatch, fault)
    result = run_small("ec2_t2micro.sweep")
    assert result["correct"] is False
    assert result["compared"]["success_mismatch_share"]["value"] > 0


# -- the coded round -------------------------------------------------------------

def _broken_round(monkeypatch, fault):
    from repro.core import coded_ops

    real = coded_ops.coded_matmul_exact

    def broken(coded, w, on_time):
        out, ok = real(coded, w, on_time)
        return fault(out), ok

    monkeypatch.setattr(coded_ops, "coded_matmul_exact", broken)


def _alter_one_residue(out):
    return out.at[0, 0].set((out[0, 0] + 1) % (2**31 - 1))


def _half_blocks(out):
    half = out.shape[0] // 2
    return out.at[half:].set(out[:out.shape[0] - half])


@pytest.mark.parametrize("fault", [_alter_one_residue, _half_blocks],
                         ids=["answer_altered", "half_batch"])
def test_coded_round_faults_fail_the_check(monkeypatch, fault):
    _broken_round(monkeypatch, fault)
    result = run_small("ec2_t2micro.coded_round")
    assert result["correct"] is False
    assert result["compared"]["wrong_residues"]["value"] > 0
    assert result["failed"] == result["attempted"]


# -- the service -------------------------------------------------------------------

def _broken_service(monkeypatch, fault):
    from repro import serving

    real = serving.sweep_serving

    def broken(*args, **kw):
        return fault(real(*args, **kw))

    monkeypatch.setattr(serving, "sweep_serving", broken)


def _late_for_on_time(o):
    return o._replace(events=jnp.where(o.events == 1, 2, o.events))


def _half_rows(o):
    def half(x):
        h = x.shape[0] // 2
        return x.at[h:].set(x[:x.shape[0] - h])
    return type(o)(*(half(x) for x in o))


def _queue_never_moves(o):
    zero = jnp.zeros_like
    return o._replace(events=zero(o.events), sojourn=zero(o.sojourn),
                      served_on_time=zero(o.served_on_time),
                      served_late=zero(o.served_late), expired=zero(o.expired),
                      in_flight=o.admitted)


@pytest.mark.parametrize("fault", [_late_for_on_time, _half_rows, _queue_never_moves],
                         ids=["answer_altered", "half_batch", "state_unchanged"])
def test_service_faults_fail_the_check(monkeypatch, fault):
    _broken_service(monkeypatch, fault)
    result = run_small("sim_t2micro.serve")
    assert result["correct"] is False
    assert result["compared"]["event_mismatch_share"]["value"] > 0

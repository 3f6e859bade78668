"""The check that decides `correct`, driven through the rest of a run (the
look for a chip skipped) at a size the Pallas interpreter holds.

The program passes in every cell; the lower-precision control (the
program's own bfloat16 path in its place) fails; and so does each fault
that a one-chip cell can have, planted underneath the timed path: a step
that returns its state unchanged, half of the batch left out, and an
answer altered where it is produced.  The limits are the cells' own
(`bench/limits/`)."""

import dataclasses
import time

import jax
import pytest

import bench_testing as bt
import harness
from repro.serve.forecast import ForecastEngine
from repro.weather.program import ExecutionPlan

BATCH = ["dycore-e11-batch", "chain-e10-batch"]
SERVE = ["dycore-e11-serve"]
SEED = 2**33 + 12345


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bt.small_root(tmp_path_factory.mktemp("bench_root"))


def _run(root, workload, dtype=None):
    return harness.run_cell(workload, SEED, 0.5, False, time.perf_counter(),
                            root=root, dtype=dtype)


def _numbers(line):
    return {k: c["value"] for k, c in line["checks"].items()}


@pytest.mark.parametrize("workload", BATCH + SERVE)
def test_program_is_correct(root, workload):
    line = _run(root, workload)
    assert line["correct"], _numbers(line)
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload", BATCH + SERVE)
def test_bfloat16_control_is_not_correct(root, workload):
    line = _run(root, workload, dtype="bfloat16")
    assert not line["correct"], _numbers(line)


def _keep_upper_half(new, old):
    e = new.wcon.shape[0]
    return jax.tree_util.tree_map(
        lambda a, b: a.at[e // 2:].set(b[e // 2:]), new, old)


def _swap_members(state):
    u = state.fields["u"]
    return dataclasses.replace(
        state, fields=dict(state.fields, u=u.at[0].set(u[1])))


BATCH_FAULTS = {
    "state_unchanged": lambda run: lambda self, state, steps: state,
    "half_the_batch": lambda run: lambda self, state, steps:
        _keep_upper_half(run(self, state, steps), state),
    "answer_altered": lambda run: lambda self, state, steps:
        _swap_members(run(self, state, steps)),
}


@pytest.mark.parametrize("fault", sorted(BATCH_FAULTS))
@pytest.mark.parametrize("workload", BATCH)
def test_batch_fault_is_not_correct(root, workload, fault, monkeypatch):
    monkeypatch.setattr(ExecutionPlan, "run",
                        BATCH_FAULTS[fault](ExecutionPlan.run))
    line = _run(root, workload)
    assert not line["correct"], _numbers(line)


def _alter_answer(finish):
    def altered(self, rid, prog, state, **kw):
        u = state.fields["u"].copy()
        u[0, 0, 0, 0] += 1.0
        state = dataclasses.replace(state, fields=dict(state.fields, u=u))
        return finish(self, rid, prog, state, **kw)
    return altered


def test_serve_state_unchanged_is_not_correct(root, monkeypatch):
    monkeypatch.setattr(ExecutionPlan, "step", lambda self, state: state)
    assert not _run(root, SERVE[0])["correct"]


def test_serve_half_the_batch_is_not_correct(root, monkeypatch):
    step = ExecutionPlan.step
    monkeypatch.setattr(ExecutionPlan, "step", lambda self, state:
                        _keep_upper_half(step(self, state), state))
    assert not _run(root, SERVE[0])["correct"]


def test_serve_answer_altered_is_not_correct(root, monkeypatch):
    monkeypatch.setattr(ForecastEngine, "_finish",
                        _alter_answer(ForecastEngine._finish))
    assert not _run(root, SERVE[0])["correct"]

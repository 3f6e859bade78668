"""The program's own spans on the profiler's clock: a served engine's
`forecast.*` spans nest pump > round > step/guard, each retired request has
one `forecast.retire` carrying its rid, `plan.run` names the Pallas kernels
its plan reports, and every round module is named after its op."""

import glob
import warnings

import jax
import numpy as np
import pytest

from repro.serve.forecast import ForecastEngine, ForecastRequest
from repro.weather.fields import initial_state, zeros_state
from repro.weather.pipeline import PipelineProgram, PipelineStage
from repro.weather.program import StencilProgram, compile

GRID = (4, 16, 16)
STEPS = (3, 1, 2)


def _spans(logdir):
    """(start_ns, end_ns, name, metadata) of each program span."""
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    with warnings.catch_warnings():
        # The profile reader's event-stats type warns as it is built.
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                out.extend((ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
                           for ev in line.events
                           if ev.name.startswith(("forecast.", "plan.")))
    return sorted(out, key=lambda s: s[:2])


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def _chain():
    return PipelineProgram(grid_shape=GRID, stages=(
        PipelineStage("hadv_upwind"), PipelineStage("vadvc_update"),
        PipelineStage("hdiff")))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    prog = StencilProgram(grid_shape=GRID, op="dycore")
    states = [initial_state(jax.random.PRNGKey(i), GRID, ensemble=1)
              for i in range(len(STEPS))]
    eng = ForecastEngine(slots=2)
    logdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(logdir)):
        rids = [eng.submit(ForecastRequest(program=prog, state=s, steps=n))
                for s, n in zip(states, STEPS)]
        results = eng.drain()
        plan = compile(prog)
        solo = [plan.run(s, n) for s, n in zip(states, STEPS)]
        chain = compile(_chain())
        jax.block_until_ready(chain.run(zeros_state(GRID), 1))
    return {"spans": _spans(logdir), "rids": rids, "results": results,
            "stats": eng.stats(), "plan": plan, "solo": solo,
            "chain": chain}


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def test_rounds_nest_in_pumps_and_steps_and_guards_in_rounds(served):
    spans = served["spans"]
    pumps, rounds = _named(spans, "forecast.pump"), _named(spans,
                                                           "forecast.round")
    assert len(rounds) == served["stats"]["rounds"] > 0
    assert len(pumps) >= len(rounds)
    for r in rounds:
        assert any(_inside(r, p) for p in pumps)
    for name in ("forecast.step", "forecast.guard"):
        inner = _named(spans, name)
        assert len(inner) == len(rounds)
        for s in inner:
            (owner,) = [r for r in rounds if _inside(s, r)]
            assert s[3]["round"] == owner[3]["round"]
    assert [r[3]["round"] for r in rounds] == list(range(len(rounds)))
    assert {s[3]["attempt"] for s in _named(spans, "forecast.step")} == {0}
    assert sum(a[3]["slots"] for a in _named(spans, "forecast.admit")) \
        == len(STEPS)


def test_each_retired_request_has_one_retire_span_with_its_rid(served):
    retires = _named(served["spans"], "forecast.retire")
    assert sorted(s[3]["rid"] for s in retires) == sorted(served["rids"])
    state = zeros_state(GRID, ensemble=1)
    nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(state))
    assert {s[3]["bytes"] for s in retires} == {nbytes}
    rounds = _named(served["spans"], "forecast.round")
    for s in retires:
        assert any(_inside(s, r) for r in rounds)
    for rid, want in zip(served["rids"], served["solo"]):
        got = served["results"][rid]
        assert got.ok
        np.testing.assert_array_equal(got.state.wcon, np.asarray(want.wcon))


def test_a_plan_compiles_once_inside_the_engines_compile_span(served):
    spans = served["spans"]
    (outer,) = _named(spans, "forecast.compile")
    inside = [s for s in _named(spans, "plan.compile") if _inside(s, outer)]
    assert [s[3]["op"] for s in inside] == ["dycore"]
    assert inside[0][3]["ensemble"] == 2


def test_plan_run_names_the_kernels_the_plan_reports(served):
    runs = _named(served["spans"], "plan.run")
    assert [s[3]["steps"] for s in runs] == list(STEPS) + [1]
    kernels = served["plan"].report()["kernels"]
    assert kernels == ["nero_dycore_whole_state"]
    chain = served["chain"].report()["kernels"]
    assert len(chain) == 3
    assert [s[3]["kernels"] for s in runs] == [kernels[0]] * len(STEPS) + [
        ";".join(chain)]


@pytest.mark.parametrize("make,kernels", [
    (lambda: StencilProgram(grid_shape=GRID, op="dycore"),
     ["nero_dycore_whole_state"]),
    (lambda: StencilProgram(grid_shape=GRID, op="dycore", k_steps=2),
     ["nero_dycore_kstep"]),
    (lambda: StencilProgram(grid_shape=GRID, op="dycore",
                            variant="per_field"), ["nero_dycore_fused"]),
    (lambda: StencilProgram(grid_shape=GRID, op="dycore", variant="unfused"),
     []),
    (lambda: StencilProgram(grid_shape=GRID, op="hdiff"), ["nero_hdiff"]),
    (lambda: StencilProgram(grid_shape=GRID, op="vadvc"), ["nero_vadvc"]),
    (_chain, ["nero_hadv_upwind", "nero_vadvc", "nero_hdiff"]),
], ids=["dycore", "dycore-kstep", "dycore-per-field", "dycore-unfused",
        "hdiff", "vadvc", "chain"])
def test_report_lists_the_kernels_a_round_launches(make, kernels):
    plan = compile(make())
    assert plan.report()["kernels"] == kernels
    assert list(plan.kernels()) == kernels


def test_round_modules_are_named_after_the_op():
    plan = compile(_chain())
    state = zeros_state(GRID, ensemble=1)
    prefix = "module @jit_pipeline_hadv_upwind_vadvc_update_hdiff_round"
    assert plan._step_fn().lower(state).as_text().startswith(prefix + " ")
    assert plan._rounds_fn(2).lower(state).as_text().startswith(
        prefix + "s ")
    dycore = compile(StencilProgram(grid_shape=GRID, op="dycore"))
    assert dycore._step_fn().lower(state).as_text().startswith(
        "module @jit_dycore_round ")

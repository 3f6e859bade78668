"""Declarative dycore programs: `compile` planner coverage (dycore op).

This module exercises the plan API on the dycore op (per-op hdiff/vadvc
coverage lives in tests/test_stencil_program.py).  The legacy flag-soup
shims were RETIRED this PR; `test_legacy_shims_removed` pins that down,
and CI still runs the module under `python -W error::DeprecationWarning`
to prove no production path warns."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.weather import fields
from repro.weather.program import (DycoreProgram, ExchangeSchedule,
                                   ExecutionPlan, compile_dycore)


def _max_err(a, b, name):
    return np.abs(np.asarray(a.fields[name]) - np.asarray(b.fields[name]))


def test_program_validation():
    with pytest.raises(ValueError):
        DycoreProgram(grid_shape=(4, 8))                 # not a triple
    with pytest.raises(ValueError):
        DycoreProgram(grid_shape=(4, 8, 8), variant="bogus")
    with pytest.raises(ValueError):
        DycoreProgram(grid_shape=(4, 8, 8), boundary="dirichlet")
    with pytest.raises(ValueError):
        DycoreProgram(grid_shape=(4, 8, 8), k_steps=0)
    with pytest.raises(ValueError):
        DycoreProgram(grid_shape=(4, 8, 8), k_steps=2, variant="per_field")
    with pytest.raises(ValueError):
        DycoreProgram(grid_shape=(4, 8, 8), k_steps=1, variant="kstep")
    with pytest.raises(ValueError):
        DycoreProgram(grid_shape=(4, 8, 8), halo=3)
    with pytest.raises(ValueError):
        DycoreProgram(grid_shape=(4, 8, 8), fields=())
    with pytest.raises(ValueError):
        DycoreProgram(grid_shape=(4, 8, 8), op="not-a-registered-op")
    with pytest.raises(TypeError):
        compile_dycore({"grid_shape": (4, 8, 8)})
    # programs are immutable specs
    with pytest.raises(dataclasses.FrozenInstanceError):
        DycoreProgram(grid_shape=(4, 8, 8)).ensemble = 2


def test_plan_selection_deterministic():
    """The planner is a pure function of (program, mesh): compiling the
    same spec twice yields identical plans/reports across (grid, dtype,
    k) combos — no hidden state, no ordering effects."""
    combos = (((4, 8, 8), "float32", "auto"),
              ((4, 16, 16), "float32", 2),
              ((8, 32, 16), "bfloat16", 1),
              ((4, 12, 16), "float32", 4))
    for grid, dtype, k in combos:
        prog = DycoreProgram(grid_shape=grid, dtype=dtype, k_steps=k)
        p1, p2 = compile_dycore(prog), compile_dycore(prog)
        assert p1.report() == p2.report(), (grid, dtype, k)
        assert p1.variant == p2.variant and p1.tile_ty == p2.tile_ty
        want = "kstep" if isinstance(k, int) and k > 1 else "whole_state"
        assert p1.variant == want          # single-chip auto resolves k=1
        assert isinstance(p1, ExecutionPlan)


def test_plan_resolution_and_structure():
    """Variant/k/tile resolution: auto -> whole-state on a single chip,
    explicit kstep keeps its k, and the structural counts are the
    single-chip ones (no collectives; 1 launch per round except the
    per-field/unfused oracles)."""
    grid = (4, 16, 16)
    auto = compile_dycore(DycoreProgram(grid_shape=grid))
    assert (auto.variant, auto.k_steps) == ("whole_state", 1)
    assert auto.collectives_per_round == 0
    assert auto.pallas_calls_per_round == 1
    assert auto.exchange is None and auto.state_spec is None

    k = compile_dycore(DycoreProgram(grid_shape=grid, variant="kstep",
                                     k_steps=2))
    assert (k.variant, k.k_steps) == ("kstep", 2)
    assert k.pallas_calls_per_round == 1
    assert k.tile_ty >= 2 * k.program.halo     # the validity-front bound

    pf = compile_dycore(DycoreProgram(grid_shape=grid, variant="per_field",
                                      k_steps=1))
    assert pf.pallas_calls_per_round == len(fields.PROGNOSTIC)
    un = compile_dycore(DycoreProgram(grid_shape=grid, variant="unfused"))
    assert un.pallas_calls_per_round == 0 and un.tile_ty is None


def test_plan_report_is_machine_readable():
    """report() is plain JSON (benchmarks embed it verbatim in
    BENCH_dycore.json) and carries the full strategy: variant, tile,
    k_steps, exchange, structural counts, modeled traffic."""
    plan = compile_dycore(DycoreProgram(grid_shape=(4, 16, 16),
                                        variant="kstep", k_steps=2))
    rep = plan.report()
    rep2 = json.loads(json.dumps(rep))
    assert rep2 == rep                          # round-trips losslessly
    assert rep["variant"] == "kstep" and rep["k_steps"] == 2
    assert rep["tile"]["op"] == "dycore_kstep"
    assert rep["tile"]["ty"] == rep["tile"]["tile"][1]
    assert rep["tile"]["vmem_bytes"] > 0
    assert rep["pallas_calls_per_round"] == 1
    assert rep["traffic"]["fused_kstep"]["total"] > 0
    assert rep["exchange"] is None              # single chip
    assert rep["program"]["fields"] == list(fields.PROGNOSTIC)


def test_plan_step_checks_state():
    st = fields.initial_state(jax.random.PRNGKey(0), (4, 8, 8))
    plan = compile_dycore(DycoreProgram(grid_shape=(4, 16, 16)))
    with pytest.raises(ValueError, match="grid"):
        plan.step(st)
    bf = compile_dycore(DycoreProgram(grid_shape=(4, 8, 8),
                                      dtype="bfloat16"))
    with pytest.raises(ValueError, match="precision"):
        bf.step(st)
    with pytest.raises(ValueError):
        compile_dycore(DycoreProgram(grid_shape=(4, 8, 8))).run(st, -1)


def test_plan_run_ragged_tail_matches_sequential():
    """plan.run(steps) with steps % k_steps != 0 executes a shorter TAIL
    round (k' = steps mod k) instead of raising — equivalent to the
    sequential whole-state trajectory within the limiter-fragile
    tolerance (ISSUE 4 satellite)."""
    grid = (4, 12, 16)
    st = fields.initial_state(jax.random.PRNGKey(3), grid, ensemble=2)
    seq = compile_dycore(DycoreProgram(grid_shape=grid, ensemble=2,
                                       k_steps=1, variant="whole_state"))
    want = seq.run(st, 5)
    for k in (2, 3):
        kplan = compile_dycore(DycoreProgram(grid_shape=grid, ensemble=2,
                                             variant="kstep", k_steps=k))
        got = kplan.run(st, 5)                  # full rounds + ragged tail
        for name in fields.PROGNOSTIC:
            err = _max_err(got, want, name)
            bad = int((err > 1e-5).sum())
            assert bad <= 4 and err.max() < 0.05, (k, name, bad, err.max())
    # steps == 0 is a no-op, steps < k is ONE tail round
    same = kplan.run(st, 0)
    assert np.array_equal(np.asarray(same.fields["t"]),
                          np.asarray(st.fields["t"]))
    one = kplan.run(st, 1)
    err = _max_err(one, seq.run(st, 1), name="t")
    assert err.max() < 1e-6


def test_legacy_shims_removed():
    """The flag-soup era is over (retired ROADMAP item): the deprecated
    `dycore_step`/`run`/`make_distributed_step` shims are gone — plans are
    the only execution surface — while the first-class helpers the plan
    lowerings build on remain."""
    from repro.weather import domain, dycore
    for mod, name in ((dycore, "dycore_step"), (dycore, "run"),
                      (domain, "make_distributed_step")):
        assert not hasattr(mod, name), f"{name} should be retired"
    for mod, name in ((dycore, "hdiff_periodic"), (dycore, "vadvc_field"),
                      (dycore, "stack_state"), (domain, "_exchange_packed"),
                      (domain, "shard_state")):
        assert hasattr(mod, name), f"{name} should remain first-class"


# ---------------------------------------------------------------------------
# Distributed plans: report() must equal the traced structure
# ---------------------------------------------------------------------------

_DIST_PLAN_SNIPPET = r"""
import jax, numpy as np
from repro.core import trace_stats
from repro.weather import domain, fields
from repro.weather.program import DycoreProgram, compile_dycore
kw = {"axis_types": (jax.sharding.AxisType.Auto,) * 2}
mesh = jax.make_mesh((2, 2), ("data", "model"), **kw)
grid = (4, 16, 16)
st = fields.initial_state(jax.random.PRNGKey(0), grid, ensemble=2)

# report() == traced structure, for EVERY variant: the plan's modeled
# pallas_calls_per_round / collectives_per_round are the program text's
# actual primitive counts.
plans = {}
for variant, k in (("kstep", 2), ("whole_state", 1), ("per_field", 1),
                   ("unfused", 1)):
    plan = compile_dycore(DycoreProgram(grid_shape=grid, ensemble=2,
                                        variant=variant, k_steps=k),
                          mesh=mesh)
    rep = plan.report()
    j = jax.make_jaxpr(plan.step)(st)
    trace_stats.assert_plan_structure(j, rep)
    plans[variant] = plan

# the distributed k-step plan's contract (ISSUE 4 acceptance criterion)
assert plans["kstep"].report()["collectives_per_round"] == 4
assert plans["kstep"].report()["pallas_calls_per_round"] == 1
assert plans["whole_state"].report()["collectives_per_round"] == 4

# the ragged exchange schedule: wcon's +1 staggering column is RIGHT-only
sched = plans["kstep"].report()["exchange"]
assert sched["mode"] == "packed"
assert sched["wcon_depth_x"] == [sched["depth_x"], sched["depth_x"] + 1]

# distributed ragged tail: 3 steps on a k=2 plan == 3 sequential rounds
sst = domain.shard_state(st, mesh, plans["kstep"].state_spec)
got = plans["kstep"].run(sst, 3)
want = sst
for _ in range(3):
    want = plans["whole_state"].step(want)
for name in fields.PROGNOSTIC:
    err = np.abs(np.asarray(got.fields[name]) - np.asarray(want.fields[name]))
    bad = int((err > 1e-5).sum())
    assert bad <= 2 and err.max() < 0.05, (name, bad, err.max())

# bf16 wire policy resolves into the schedule (and still 4 collectives)
bplan = compile_dycore(DycoreProgram(grid_shape=grid, ensemble=2,
                                     variant="kstep", k_steps=2,
                                     exchange_dtype="bfloat16"), mesh=mesh)
assert bplan.report()["exchange"]["wire_dtype"] == "bfloat16"
trace_stats.assert_plan_structure(jax.make_jaxpr(bplan.step)(st),
                                  bplan.report())

# k_steps="auto": resolved at compile time, deterministically
a1 = compile_dycore(DycoreProgram(grid_shape=grid, ensemble=2), mesh=mesh)
a2 = compile_dycore(DycoreProgram(grid_shape=grid, ensemble=2), mesh=mesh)
assert a1.k_steps == a2.k_steps >= 1 and a1.variant == a2.variant

# a variant pinned to one step per round + the default k_steps="auto"
# must resolve k=1 on a mesh, not crash on the auto-resolved deep k
ws = compile_dycore(DycoreProgram(grid_shape=grid, ensemble=2,
                                  variant="whole_state"), mesh=mesh)
assert (ws.variant, ws.k_steps) == ("whole_state", 1)

# too-deep halo refuses loudly at compile time
try:
    compile_dycore(DycoreProgram(grid_shape=(4, 8, 8), variant="kstep",
                                 k_steps=4), mesh=mesh)
except ValueError as e:
    assert "halo" in str(e), e
else:
    raise AssertionError("k_steps=4 on a 4-row slab should refuse")
print("PLAN_DIST_OK")
"""


def _run_forced_device_snippet(snippet: str, marker: str):
    """Run `snippet` in a subprocess with 4 forced host CPU devices."""
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    r = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert marker in r.stdout, r.stderr[-2000:]


def test_distributed_plan_report_matches_trace():
    """Forced-4-device subprocess: for every variant the plan's report()
    equals the traced launch/collective counts, the distributed k-step
    plan reports collectives_per_round == 4, the ragged tail round is
    equivalent to sequential stepping, and compile-time validation
    refuses a halo deeper than the local slab."""
    _run_forced_device_snippet(_DIST_PLAN_SNIPPET, "PLAN_DIST_OK")


def test_exchange_schedule_describe():
    """The schedule is rides-first (per-operand (lo, hi) depths straight
    from the registry) but keeps the legacy depth_y/depth_x/wcon_depth_x
    summary keys the CI plan-block check and cross-PR diffs read."""
    s = ExchangeSchedule(mode="packed", shards=(2, 2),
                         rides=(("fields", (4, 4), (4, 4)),
                                ("wcon", (4, 4), (4, 5))),
                         wire_dtype="bfloat16")
    assert (s.depth_y, s.depth_x, s.wcon_depth_x) == (4, 4, (4, 5))
    d = s.describe()
    assert d["mode"] == "packed" and d["shards"] == [2, 2]
    assert d["rides"]["wcon"] == {"depth_y": [4, 4], "depth_x": [4, 5]}
    assert d["wcon_depth_x"] == [4, 5] and d["depth_y"] == 4
    assert d["wire_dtype"] == "bfloat16"
    # an op with no wcon ride (hdiff) simply omits the wcon summary key
    h = ExchangeSchedule(mode="packed", shards=(2, 2),
                         rides=(("fields", (2, 2), (2, 2)),),
                         wire_dtype=None)
    assert "wcon_depth_x" not in h.describe()
    assert h.wcon_depth_x is None


def test_plan_cache_key_and_json_roundtrip():
    """The frozen program IS the plan-cache key: ensemble rebinding is the
    only transform, the spec survives a JSON round-trip bit-for-bit, and
    rebound keys hash/compare like the directly-constructed spec (the
    serving engine keys its plan cache on exactly this)."""
    import jax.numpy as jnp

    from repro.weather.program import StencilProgram, plan_cache_key
    p = StencilProgram(grid_shape=(4, 8, 8), op="hdiff",
                       dtype=jnp.bfloat16)   # non-canonical spelling
    assert plan_cache_key(p) is p                   # no rebind, no copy
    assert plan_cache_key(p, ensemble=1) is p       # ensemble already 1
    k4 = plan_cache_key(p, ensemble=4)
    assert k4.ensemble == 4 and k4.dtype == "bfloat16"    # normalized
    assert k4 == StencilProgram(grid_shape=(4, 8, 8), op="hdiff",
                                dtype="bfloat16", ensemble=4)
    assert {k4: "plan"}[plan_cache_key(p, ensemble=4)] == "plan"
    # JSON round-trip: to_json is plain-serializable, from_json rebuilds
    # an equal (hence same-cache-slot) spec
    d = json.loads(json.dumps(k4.to_json()))
    back = StencilProgram.from_json(d)
    assert back == k4 and hash(back) == hash(k4)


def test_ensemble_slot_helpers():
    """Slot view/assign/select are the engine's admission/retire/rollback
    primitives: a view keeps the leading axis, assign scatters member
    states into batch slots, select mixes per-slot old/new."""
    from repro.weather.program import (ensemble_slot_assign,
                                       ensemble_slot_select,
                                       ensemble_slot_view)
    grid = (3, 8, 8)
    batch = fields.initial_state(jax.random.PRNGKey(0), grid, ensemble=3)
    one = fields.initial_state(jax.random.PRNGKey(1), grid, ensemble=1)
    v = ensemble_slot_view(batch, 1)
    for name in fields.PROGNOSTIC:
        assert v.fields[name].shape[0] == 1
        assert np.array_equal(np.asarray(v.fields[name]),
                              np.asarray(batch.fields[name][1:2]))
    put = ensemble_slot_assign(batch, np.asarray([2]), one)
    for name in fields.PROGNOSTIC:
        assert np.array_equal(np.asarray(put.fields[name][2]),
                              np.asarray(one.fields[name][0]))
        assert np.array_equal(np.asarray(put.fields[name][:2]),
                              np.asarray(batch.fields[name][:2]))
    mask = np.asarray([True, False, True])
    mixed = ensemble_slot_select(mask, put, batch)
    for name in fields.PROGNOSTIC:
        got = np.asarray(mixed.fields[name])
        assert np.array_equal(got[0], np.asarray(put.fields[name][0]))
        assert np.array_equal(got[1], np.asarray(batch.fields[name][1]))


def test_round_plan_depths_and_validation():
    """round_plan(k) is run()'s ragged-tail machinery made public: the
    full-depth round is `self` (no recompilation), shallower rounds are
    derived plans with the same strategy at k' steps, and out-of-range
    depths fail loudly."""
    plan = compile_dycore(DycoreProgram(grid_shape=(4, 12, 16),
                                        variant="kstep", k_steps=3))
    assert plan.round_plan(3) is plan
    two = plan.round_plan(2)
    assert two.k_steps == 2 and two.variant == plan.variant
    assert two is plan.round_plan(2)                # derived plans cached
    for bad in (0, 4, -1, "2", 2.0):
        with pytest.raises(ValueError, match="round_plan"):
            plan.round_plan(bad)


# ---------------------------------------------------------------------------
# run()'s round scan: the stacked carry
# ---------------------------------------------------------------------------

_CARRY_GRID = (4, 16, 16)


def _carry_program(variant, k_steps=1):
    if variant == "chain":
        from repro.weather.pipeline import PipelineProgram, PipelineStage
        return PipelineProgram(grid_shape=_CARRY_GRID, ensemble=2, stages=(
            PipelineStage("hadv_upwind"), PipelineStage("vadvc_update"),
            PipelineStage("hdiff")))
    return DycoreProgram(grid_shape=_CARRY_GRID, ensemble=2,
                         variant=variant, k_steps=k_steps)


@pytest.mark.parametrize("variant,k,steps,carry", [
    ("whole_state", 1, 3, "stacked"),      # odd: the unroll's remainder
    ("whole_state", 1, 4, "stacked"),
    ("kstep", 2, 5, "stacked"),            # two rounds and a ragged tail
    ("per_field", 1, 3, "dict"),
    ("unfused", 1, 3, "dict"),
    ("chain", 1, 3, "dict"),
], ids=["whole_state-3", "whole_state-4", "kstep-5", "per_field",
        "unfused", "chain"])
def test_run_carry_is_bitwise_repeated_steps(variant, k, steps, carry):
    """run(state, n) equals its rounds as repeated step() calls (and the
    tail round's plan) bit for bit, whichever carry the round scan takes;
    report()["rounds"] names that carry."""
    from repro.weather.program import compile
    plan = compile(_carry_program(variant, k))
    unroll = 2 if carry == "stacked" else 1
    assert plan.report()["rounds"] == {"carry": carry, "unroll": unroll}
    st = fields.initial_state(jax.random.PRNGKey(5), _CARRY_GRID,
                              ensemble=2)
    want = st
    for _ in range(steps // plan.k_steps):
        want = plan.step(want)
    if steps % plan.k_steps:
        want = plan.round_plan(steps % plan.k_steps).step(want)
    got = plan.run(st, steps)
    for name in fields.PROGNOSTIC:
        for part in ("fields", "stage_tens"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, part)[name]),
                np.asarray(getattr(want, part)[name]), err_msg=(part, name))


def test_stacked_round_scan_body_is_the_kernel_alone():
    """The dycore's round scan carries the kernel's stacked operands: its
    body is the kernel launch (with the free reshapes that fold the field
    axis into the kernel's batch), no stack, slice or gather of the state,
    unrolled by 2.  The chain keeps the dict scan: its body is exactly one
    step()."""
    from repro.core import trace_stats
    from repro.weather.program import compile
    st = fields.initial_state(jax.random.PRNGKey(0), _CARRY_GRID, ensemble=2)
    dycore = compile(_carry_program("whole_state"))
    (unroll, body), = trace_stats.scan_bodies(
        jax.make_jaxpr(dycore._rounds_fn(4))(st))
    prims = trace_stats.primitive_counts(body, kernels=False)
    assert unroll == 2
    assert prims.pop("pallas_call") == 1
    assert set(prims) <= {"reshape"}, prims

    chain = compile(_carry_program("chain"))
    (unroll, body), = trace_stats.scan_bodies(
        jax.make_jaxpr(chain._rounds_fn(4))(st))
    assert unroll == 1
    assert (trace_stats.primitive_counts(body, kernels=False)
            == trace_stats.primitive_counts(
                jax.make_jaxpr(chain._step_fn())(st), kernels=False))

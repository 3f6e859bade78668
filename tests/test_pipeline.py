"""Pipeline programs: fused exchange, ordered launches, bit-identity.

The contract of `repro.weather.pipeline` (ISSUE 10): a chain of
registered stages compiles to ONE execution plan whose single packed
exchange pair per direction carries every stage's operand footprint at
the chain's back-propagated depths, whose stage launches run in order on
resident operands (no HBM round trip between stages), and whose output
is BIT-IDENTICAL to running the same stages as sequential solo programs
— on one chip and on a forced-4-device mesh alike.  The property sweep
uses `hypothesis` when the dev extra is installed and a seeded
deterministic sweep of the same property otherwise.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import memmodel
from repro.serve.forecast import ForecastEngine, ForecastRequest
from repro.weather import fields
from repro.weather import program as wprog
from repro.weather.pipeline import (PipelineProgram, PipelineStage,
                                    pipeline_op_name)
from repro.weather.program import StencilProgram, compile, plan_cache_key

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                              # pragma: no cover
    HAVE_HYPOTHESIS = False

_GRID = (3, 8, 8)
_FLAGSHIP = ("hadv_upwind", "vadvc_update", "hdiff")
# Chainable zoo: every op with an apply_stage lowering.
_CHAINABLE = ("hadv_upwind", "vadvc_update", "hdiff", "vadvc", "asselin")


def _state(grid=_GRID, ensemble=1, seed=0):
    return fields.initial_state(jax.random.PRNGKey(seed), grid,
                                ensemble=ensemble)


def _pipe(stages, grid=_GRID, ensemble=1, **kw):
    kw.setdefault("variant", "whole_state")
    kw.setdefault("k_steps", 1)
    return PipelineProgram(grid_shape=grid, ensemble=ensemble, coeff=0.05,
                           stages=tuple(stages), **kw)


def _solo_chain(stages, state, grid=_GRID, ensemble=1):
    """Reference: the same stages as sequential solo programs."""
    for op in stages:
        p = compile(StencilProgram(grid_shape=grid, ensemble=ensemble,
                                   coeff=0.05, op=op, variant="whole_state",
                                   k_steps=1))
        state = p.step(state)
    return state


def _assert_state_equal(a, b, names=fields.PROGNOSTIC):
    for n in names:
        np.testing.assert_array_equal(np.asarray(a.fields[n]),
                                      np.asarray(b.fields[n]), err_msg=n)
        np.testing.assert_array_equal(np.asarray(a.stage_tens[n]),
                                      np.asarray(b.stage_tens[n]), err_msg=n)


# ---------------------------------------------------------------------------
# Single-chip bit-identity
# ---------------------------------------------------------------------------

def test_flagship_chain_matches_sequential_solos():
    """hadv_upwind -> vadvc_update -> hdiff as ONE plan is bitwise equal
    to the three solo programs run back to back, and launches exactly one
    pallas call per stage per round."""
    st_ = _state(ensemble=2)
    plan = compile(_pipe(_FLAGSHIP, ensemble=2))
    rep = plan.report()
    assert rep["pallas_calls_per_round"] == len(_FLAGSHIP)
    assert rep["collectives_per_round"] == 0        # single chip
    _assert_state_equal(plan.step(st_),
                        _solo_chain(_FLAGSHIP, st_, ensemble=2))


def test_pinned_kstep_round_matches_two_chain_rounds():
    """A k=2 pipeline round reuses ONE (deeper) fused exchange and is
    bitwise equal to two k=1 rounds."""
    st_ = _state()
    p1 = compile(_pipe(_FLAGSHIP))
    p2 = compile(_pipe(_FLAGSHIP, variant="kstep", k_steps=2))
    assert p2.report()["pallas_calls_per_round"] == 2 * len(_FLAGSHIP)
    _assert_state_equal(p2.step(st_), p1.step(p1.step(st_)))


def test_run_ragged_tail_matches_sequential_rounds():
    """run(state, 3) on a k=2 chain (one full round + ragged tail) equals
    three sequential chain rounds."""
    st_ = _state()
    p1 = compile(_pipe(_FLAGSHIP))
    p2 = compile(_pipe(_FLAGSHIP, variant="kstep", k_steps=2))
    ref = st_
    for _ in range(3):
        ref = p1.step(ref)
    _assert_state_equal(p2.run(st_, 3), ref)


def test_subset_binding_applies_stage_to_bound_fields_only():
    """pipeline(hadv_upwind -> hdiff[u,v]) diffuses only u and v; t and
    pp pass through the hdiff stage untouched (bitwise)."""
    st_ = _state()
    plan = compile(PipelineProgram(
        grid_shape=_GRID, ensemble=1, coeff=0.05,
        variant="whole_state", k_steps=1,
        stages=(PipelineStage(op="hadv_upwind"),
                PipelineStage(op="hdiff", fields=("u", "v")))))
    out = plan.step(st_)
    adv = _solo_chain(("hadv_upwind",), st_)
    full = _solo_chain(("hadv_upwind", "hdiff"), st_)
    for n in ("u", "v"):
        np.testing.assert_array_equal(np.asarray(out.fields[n]),
                                      np.asarray(full.fields[n]), err_msg=n)
    for n in ("t", "pp"):
        np.testing.assert_array_equal(np.asarray(out.fields[n]),
                                      np.asarray(adv.fields[n]), err_msg=n)


def test_asselin_chain_elides_every_exchange():
    """A zero-ride chain declares no rides and costs zero collectives on
    any mesh shape (checked here via the generic model), while staying
    bitwise equal to the solo filter."""
    st_ = _state()
    prog = _pipe(("asselin",))
    opdef = __import__("repro.weather.stencil_ops",
                       fromlist=["get_stencil_op"]).get_stencil_op(prog.op)
    assert opdef.resolved_rides(1) == ()
    assert opdef.halo == 0
    assert opdef.generic_collectives(2, 2, 1) == 0
    plan = compile(prog)
    assert plan.report()["collectives_per_round"] == 0
    _assert_state_equal(plan.step(st_), _solo_chain(("asselin",), st_))


def test_chain_rides_match_backpropagated_depths():
    """The flagship chain's registered rides are the hand-derived
    backward-validity depths: fields (3,2)/(3,2), wcon (2,2)y (2,3)x,
    tens and stage_tens (2,2)/(2,2) — and they deepen linearly in k."""
    prog = _pipe(_FLAGSHIP)
    fp = compile(prog).report()["footprint"]
    got = {r["operand"]: (tuple(r["depth_y"]), tuple(r["depth_x"]))
           for r in fp["rides"]}
    assert got == {"fields": ((3, 2), (3, 2)),
                   "stage_tens": ((2, 2), (2, 2)),
                   "tens": ((2, 2), (2, 2)),
                   "wcon": ((2, 2), (2, 3))}
    assert fp["halo"] == 3 and "kstep" in fp["variants"]


# ---------------------------------------------------------------------------
# Property: any chainable subset/ordering == sequential solos, bitwise
# ---------------------------------------------------------------------------

def _check_chain_property(stages, seed):
    st_ = _state(seed=seed)
    plan = compile(_pipe(stages))
    _assert_state_equal(plan.step(st_), _solo_chain(stages, st_))


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.lists(st.sampled_from(_CHAINABLE), min_size=1, max_size=3,
                    unique=True),
           st.integers(min_value=0, max_value=2 ** 16))
    def test_random_chains_match_sequential(stages, seed):
        _check_chain_property(tuple(stages), seed)
else:
    def test_random_chains_match_sequential():
        rng = np.random.default_rng(1234)
        for i in range(6):
            size = int(rng.integers(1, 4))
            stages = tuple(rng.choice(_CHAINABLE, size=size, replace=False))
            _check_chain_property(stages, seed=int(rng.integers(2 ** 16)))


# ---------------------------------------------------------------------------
# Serialization + serving
# ---------------------------------------------------------------------------

def test_json_roundtrip_and_cache_key():
    """to_json/from_json round-trips through the BASE class dispatch (a
    serving checkpoint only knows `StencilProgram.from_json`), report()'s
    embedded program block does too, and the plan-cache key is distinct
    from every constituent solo program's."""
    prog = _pipe(_FLAGSHIP, ensemble=2)
    back = StencilProgram.from_json(prog.to_json())
    assert isinstance(back, PipelineProgram)
    assert back == prog
    rep_prog = StencilProgram.from_json(compile(prog).report()["program"])
    assert rep_prog == prog
    keys = {plan_cache_key(prog, ensemble=2)}
    for op in _FLAGSHIP:
        keys.add(plan_cache_key(
            StencilProgram(grid_shape=_GRID, ensemble=2, coeff=0.05, op=op),
            ensemble=2))
    assert len(keys) == 1 + len(_FLAGSHIP)
    assert hash(prog) is not None


def test_engine_caches_pipeline_plans(monkeypatch):
    """Six requests over {solo hdiff, pipeline-with-hdiff} compile exactly
    TWO plans: the chain's cache key never collides with the solo op's."""
    calls = []
    real_compile = wprog.compile

    def spy(program, *a, **kw):
        calls.append(program)
        return real_compile(program, *a, **kw)

    monkeypatch.setattr(wprog, "compile", spy)
    progs = [StencilProgram(grid_shape=_GRID, ensemble=1, coeff=0.05,
                            op="hdiff"),
             _pipe(("hadv_upwind", "hdiff"))]
    eng = ForecastEngine(slots=2)
    rids = []
    for i in range(6):
        rids.append(eng.submit(ForecastRequest(
            program=progs[i % 2], state=_state(seed=30 + i),
            steps=1 + i % 2)))
    results = eng.drain()
    assert sorted(results) == sorted(rids)
    assert len(calls) == 2, [p.op for p in calls]
    assert {p.op for p in calls} == {"hdiff",
                                     pipeline_op_name(progs[1].stages)}
    s = eng.stats()
    assert s["plan_cache_misses"] == 2 and s["plan_cache_hits"] == 4
    assert plan_cache_key(progs[1], ensemble=2) in eng._plans


# ---------------------------------------------------------------------------
# Traffic model
# ---------------------------------------------------------------------------

def test_chained_traffic_beats_sequential_on_realistic_grids():
    """On a production-shaped grid the fused chain's HBM stream per round
    undercuts the summed solo stages (intermediates stay resident); the
    report carries both sides and their ratio."""
    prog = _pipe(_FLAGSHIP, grid=(8, 128, 128))
    t = compile(prog).report()["traffic"]
    assert t["chained_per_round"] < t["sequential_per_round"]
    assert t["chained_reduction_x"] > 1.0
    assert set(t["sequential_by_stage"]) == set(_FLAGSHIP)
    assert sum(t["sequential_by_stage"].values()) == t["sequential_per_round"]


# ---------------------------------------------------------------------------
# Validation
# ---------------------------------------------------------------------------

def test_chain_validation_refuses_bad_programs():
    with pytest.raises(ValueError, match="at least one stage"):
        PipelineProgram(grid_shape=_GRID, stages=())
    with pytest.raises(KeyError, match="unknown stencil op"):
        PipelineProgram(grid_shape=_GRID, stages=("no_such_op",))
    with pytest.raises(ValueError, match="apply_stage"):
        PipelineProgram(grid_shape=_GRID, stages=("dycore",))
    with pytest.raises(ValueError, match="unknown fields"):
        PipelineProgram(grid_shape=_GRID,
                        stages=(PipelineStage(op="hdiff",
                                              fields=("bogus",)),))
    with pytest.raises(ValueError, match="derives"):
        PipelineProgram(grid_shape=_GRID, op="hdiff", stages=("hdiff",))
    with pytest.raises(TypeError, match="expected a PipelineStage"):
        PipelineProgram(grid_shape=_GRID, stages=(42,))


# ---------------------------------------------------------------------------
# Forced-4-device distributed behaviour (subprocess)
# ---------------------------------------------------------------------------

_DIST_PIPELINE_SNIPPET = """
import jax, numpy as np
from repro.core import trace_stats
from repro.weather import domain, fields
from repro.weather.program import StencilProgram, compile
from repro.weather.pipeline import PipelineProgram

kw = {"axis_types": (jax.sharding.AxisType.Auto,) * 2}
mesh = jax.make_mesh((2, 2), ("data", "model"), **kw)
grid = (4, 16, 16)
st = fields.initial_state(jax.random.PRNGKey(0), grid, ensemble=2)
FLAG = ("hadv_upwind", "vadvc_update", "hdiff")

def pipe(**kw):
    kw.setdefault("variant", "whole_state")
    kw.setdefault("k_steps", 1)
    kw.setdefault("stages", FLAG)
    return PipelineProgram(grid_shape=grid, ensemble=2, coeff=0.05, **kw)

plan = compile(pipe(), mesh=mesh)
rep = plan.report()
# ONE packed exchange pair per direction, regardless of chain length.
assert rep["collectives_per_round"] == 4, rep["collectives_per_round"]
assert rep["pallas_calls_per_round"] == 3
trace_stats.assert_plan_structure(jax.make_jaxpr(plan.step)(st), rep)

sh = domain.shard_state(st, mesh, plan.state_spec)
out = plan.step(sh)
seq = sh
for op in FLAG:
    p = compile(StencilProgram(grid_shape=grid, ensemble=2, coeff=0.05,
                               op=op, variant="whole_state", k_steps=1),
                mesh=mesh)
    seq = p.step(seq)
for n in fields.PROGNOSTIC:
    assert np.array_equal(np.asarray(out.fields[n]),
                          np.asarray(seq.fields[n])), n
    assert np.array_equal(np.asarray(out.stage_tens[n]),
                          np.asarray(seq.stage_tens[n])), n

# k=2 reuses ONE deeper exchange pair per direction and matches two rounds.
kplan = compile(pipe(variant="kstep", k_steps=2), mesh=mesh)
krep = kplan.report()
assert krep["collectives_per_round"] == 4, krep["collectives_per_round"]
trace_stats.assert_plan_structure(jax.make_jaxpr(kplan.step)(st), krep)
a = plan.step(plan.step(sh))
b = kplan.step(sh)
for n in fields.PROGNOSTIC:
    assert np.array_equal(np.asarray(a.fields[n]), np.asarray(b.fields[n])), n

# Zero-ride chain: every direction's exchange is elided on the mesh.
ap = compile(PipelineProgram(grid_shape=grid, ensemble=2,
                             stages=("asselin",)), mesh=mesh)
arep = ap.report()
assert arep["collectives_per_round"] == 0, arep["collectives_per_round"]
trace_stats.assert_plan_structure(jax.make_jaxpr(ap.step)(st), arep)

# bf16 wire: still one pair per direction; error bounded, not bit-equal.
bp = compile(pipe(exchange_dtype="bfloat16"), mesh=mesh)
brep = bp.report()
assert brep["collectives_per_round"] == 4
trace_stats.assert_plan_structure(jax.make_jaxpr(bp.step)(st), brep)
outb = bp.step(sh)
errs = [float(np.abs(np.asarray(outb.fields[n]) -
                     np.asarray(out.fields[n])).max())
        for n in fields.PROGNOSTIC]
assert 0.0 < max(errs) < 0.1, errs

print("PIPELINE_DIST_OK")
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


def test_distributed_pipeline_fused_exchange_and_bit_identity():
    """Forced-4-device subprocess: the flagship chain compiles to ONE
    packed ppermute pair per direction per round (4 collectives on a 2x2
    mesh, traced == reported), its sharded step is bitwise equal to the
    sequential solo plans on the same mesh, a k=2 round still costs 4
    collectives and matches two k=1 rounds, an asselin-only chain elides
    every exchange, and a bfloat16 wire keeps the cast confined to the
    halo."""
    _run_forced_device_snippet(_DIST_PIPELINE_SNIPPET, "PIPELINE_DIST_OK")

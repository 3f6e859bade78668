"""Per-op compiled programs: the StencilOp registry end-to-end.

ISSUE 5 tentpole coverage: `compile()` works over REGISTERED stencil ops —
hdiff-only and vadvc-only programs are first-class, their plans carry the
op's declared footprint, `trace_stats.assert_plan_structure` verifies the
traced round for all three ops, and the per-op outputs match their
`ref.py` oracles (hdiff BIT-exactly — the Pallas variants and the stacked
oracle lower to identical arithmetic; vadvc to 1 ulp, its kernel runs the
step-by-step COSMO sweep while the jnp oracle runs the vectorized one —
plus the solver-independent tridiagonal-residual property).

Runs clean under `python -W error::DeprecationWarning` (no legacy shims
left to warn)."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import autotune, memmodel, tiling, trace_stats
from repro.kernels.hdiff import ops as hdiff_ops
from repro.kernels.vadvc import ops as vadvc_ops
from repro.kernels.vadvc import ref as vadvc_ref
from repro.weather import dycore, fields
from repro.weather.program import (StencilProgram, compile,
                                   get_stencil_op, register_stencil_op,
                                   registered_stencil_ops)

GRID = (4, 12, 16)


def _plan(op, variant="auto", k_steps=1, grid=GRID, ensemble=2, **kw):
    return compile(StencilProgram(grid_shape=grid, ensemble=ensemble,
                                  op=op, variant=variant, k_steps=k_steps),
                   **kw)


def _state(grid=GRID, ensemble=2, seed=0):
    return fields.initial_state(jax.random.PRNGKey(seed), grid,
                                ensemble=ensemble)


# ---------------------------------------------------------------------------
# Registry + validation
# ---------------------------------------------------------------------------


def test_registry_has_the_papers_ops():
    """The three first-class workloads are registered; compile() accepts
    each (the acceptance criterion's 'at least three registered ops')."""
    assert {"dycore", "hdiff", "vadvc"} <= set(registered_stencil_ops())
    for op in ("dycore", "hdiff", "vadvc"):
        plan = _plan(op)
        assert plan.pallas_calls_per_round == 1      # whole_state default
        rep = plan.report()
        assert rep["op"] == op
        fp = rep["footprint"]
        assert fp["op"] == op and fp["rides"], op
    with pytest.raises(KeyError):
        get_stencil_op("not-registered")


def test_footprint_declarations_match_the_math():
    """The registry declares the paper's footprints: hdiff a symmetric
    (2,2)/(2,2) per-field ride, vadvc ONLY wcon's right staggering column
    (the asymmetric (0,1) x-ride), the dycore all three field operands
    plus wcon's k-scaled ragged ride."""
    h = get_stencil_op("hdiff")
    assert h.halo == 2 and h.writes == ("fields",)
    assert h.resolved_rides(1) == (("fields", (2, 2), (2, 2)),)
    assert h.resolved_rides(3) == (("fields", (6, 6), (6, 6)),)

    v = get_stencil_op("vadvc")
    assert v.halo == 0 and v.writes == ("stage_tens",)
    assert v.resolved_rides(1) == (("wcon", (0, 0), (0, 1)),)

    d = get_stencil_op("dycore")
    rides = dict((r[0], r[1:]) for r in d.resolved_rides(2))
    assert rides["fields"] == ((4, 4), (4, 4))
    assert rides["wcon"] == ((4, 4), (4, 5))     # right-only +1, k-scaled
    # flops thread through to the k resolver / models
    assert (h.flops_per_point, v.flops_per_point, d.flops_per_point) == (
        21.0, 38.0, 61.0)


def test_per_op_validation():
    with pytest.raises(ValueError, match="k-step"):
        # vadvc's footprint does not deepen with k: no k-step round
        StencilProgram(grid_shape=GRID, op="vadvc", k_steps=2)
    with pytest.raises(ValueError, match="variant"):
        StencilProgram(grid_shape=GRID, op="vadvc", variant="kstep")
    with pytest.raises(ValueError, match="reach"):
        StencilProgram(grid_shape=GRID, op="vadvc", halo=2)
    # hdiff DOES have a k-step round (k launches on a deep halo)
    assert _plan("hdiff", variant="kstep", k_steps=2).k_steps == 2
    # ...but a halo deeper than the grid refuses at COMPILE time even on a
    # single chip (the wrap pad cannot span more than one period)
    with pytest.raises(ValueError, match="halo"):
        _plan("hdiff", variant="kstep", k_steps=5, grid=(4, 8, 8))


def test_unfused_per_op_reports_model_legal_tiles():
    """report() on oracle (unfused) per-op plans models traffic at a tile
    that is a LEGAL window of the physical grid — not of the padded or
    ensemble-folded compute grid the kernels tile over."""
    for op in ("hdiff", "vadvc"):
        rep = _plan(op, variant="unfused", grid=(16, 64, 64)).report()
        assert rep["tile"] is None
        assert 1 <= rep["traffic_model_ty"] <= 64
        assert 64 % rep["traffic_model_ty"] == 0
        assert rep["traffic"]["stream"] >= rep["traffic"]["ideal"] > 0


def test_registered_tile_spaces_and_snap_drift():
    """Satellite: the standalone hdiff/vadvc OpSpecs live in the autotune
    registry and their ops.plan_tile paths use the unified
    `tiling.snap_to_divisor` rule (the largest TPU-aligned divisor below
    the tuned extent, else the whole extent) — no more private halving
    loops that drifted from `resolve_tile`."""
    assert autotune.get_op("hdiff") is tiling.HDIFF
    assert autotune.get_op("vadvc") is tiling.VADVC
    for ny in (8, 12, 14, 32, 96):
        ty = hdiff_ops.plan_tile((8, ny, 16), "float32")
        assert ny % ty == 0 and ty >= 2, (ny, ty)
        assert ty % 8 == 0 or ty == ny, (ny, ty)
    for ny, nx in ((8, 16), (12, 24), (6, 14), (32, 512)):
        tj, ti = vadvc_ops.plan_tile((8, ny, nx), "float32")
        assert ny % tj == 0 and nx % ti == 0, (ny, nx, tj, ti)
        assert tj % 8 == 0 or tj == ny, (ny, tj)
        assert ti % 128 == 0 or ti == nx, (nx, ti)
    assert tiling.snap_to_divisor(5, 16, align=8, lo=2) == 8
    assert tiling.snap_to_divisor(7, 12, align=8, lo=2) == 12
    assert tiling.snap_to_divisor(6, 7, align=8, lo=2) == 7  # whole extent
    assert tiling.snap_to_divisor(24, 32, align=8) == 16
    assert tiling.snap_to_divisor(300, 512, align=128) == 256
    assert tiling.sublanes("float32") == 8
    assert tiling.sublanes("bfloat16") == 16


# ---------------------------------------------------------------------------
# hdiff-only programs vs the ref.py oracle
# ---------------------------------------------------------------------------


def test_hdiff_plans_bit_match_reference():
    """Acceptance: hdiff-only plans match the reference kernel BIT-exactly
    — the unfused variant IS the ref.py composition, and the Pallas
    per-field/whole-state/kstep variants compute identical arithmetic on
    identically-assembled windows."""
    st = _state()
    ref = _plan("hdiff", variant="unfused").step(st)
    # the oracle variant against the hand-written periodic composition
    want = {n: dycore.hdiff_periodic(st.fields[n], 0.025)
            for n in fields.PROGNOSTIC}
    for n in fields.PROGNOSTIC:
        np.testing.assert_allclose(np.asarray(ref.fields[n]),
                                   np.asarray(want[n]), atol=1e-6)
        # tendencies pass through untouched (hdiff writes fields only)
        assert np.array_equal(np.asarray(ref.stage_tens[n]),
                              np.asarray(st.stage_tens[n]))
    for variant in ("per_field", "whole_state"):
        got = _plan("hdiff", variant=variant).step(st)
        for n in fields.PROGNOSTIC:
            assert np.array_equal(np.asarray(got.fields[n]),
                                  np.asarray(ref.fields[n])), (variant, n)


def test_hdiff_kstep_and_ragged_tail():
    """hdiff k-step rounds (ONE in-kernel launch on a k·2-deep wrap halo)
    equal k sequential whole-state steps bit-for-bit, including the ragged
    tail (5 steps on a k=2 plan = 2 rounds + a 1-step tail)."""
    st = _state(seed=3)
    seq = _plan("hdiff", variant="whole_state")
    kplan = _plan("hdiff", variant="kstep", k_steps=2)
    assert kplan.pallas_calls_per_round == 1         # in-kernel k-step round
    want = seq.run(st, 5)
    got = kplan.run(st, 5)
    for n in fields.PROGNOSTIC:
        assert np.array_equal(np.asarray(got.fields[n]),
                              np.asarray(want.fields[n])), n
    # steps == 0 is a no-op
    same = kplan.run(st, 0)
    assert np.array_equal(np.asarray(same.fields["t"]),
                          np.asarray(st.fields["t"]))


# ---------------------------------------------------------------------------
# vadvc-only programs vs the ref.py oracle
# ---------------------------------------------------------------------------


def test_vadvc_plans_match_reference():
    """vadvc-only plans update ONLY the stage tendencies: every variant
    matches the jnp oracle to 1 ulp (the Pallas kernel runs the
    step-by-step COSMO sweep, the oracle the vectorized formulation; even
    the oracle variant differs from the hand-vmapped helper only in XLA
    fusion order), and every variant leaves fields/tens untouched."""
    st = _state(seed=1)
    want = {n: dycore.vadvc_field(st.fields[n], st.wcon, st.fields[n],
                                  st.tens[n], st.stage_tens[n])
            for n in fields.PROGNOSTIC}
    ref = _plan("vadvc", variant="unfused").step(st)
    for n in fields.PROGNOSTIC:
        np.testing.assert_allclose(np.asarray(ref.stage_tens[n]),
                                   np.asarray(want[n]), atol=1e-6,
                                   err_msg=n)
    for variant in ("per_field", "whole_state"):
        got = _plan("vadvc", variant=variant).step(st)
        for n in fields.PROGNOSTIC:
            np.testing.assert_allclose(
                np.asarray(got.stage_tens[n]), np.asarray(want[n]),
                atol=1e-6, err_msg=f"{variant}/{n}")
            assert np.array_equal(np.asarray(got.fields[n]),
                                  np.asarray(st.fields[n])), (variant, n)


def test_vadvc_pallas_plan_solves_the_system():
    """Solver-independent property: the whole-state vadvc plan's output
    reconstructs x with A x = d (the implicit vertical discretization) —
    bit-level oracle agreement is not assumed, the algebra is checked."""
    st = _state(ensemble=1, seed=2)
    out = _plan("vadvc", variant="whole_state", ensemble=1).step(st)
    wcon_s = np.concatenate([np.asarray(st.wcon[0]),
                             np.asarray(st.wcon[0][..., :1])], axis=-1)
    for n in fields.PROGNOSTIC:
        res = vadvc_ref.tridiagonal_residual(
            np.asarray(st.fields[n][0]), wcon_s,
            np.asarray(st.fields[n][0]), np.asarray(st.tens[n][0]),
            np.asarray(st.stage_tens[n][0]),
            np.asarray(out.stage_tens[n][0], np.float64))
        assert res < 1e-4, (n, res)


# ---------------------------------------------------------------------------
# Footprint-driven models (memmodel satellites)
# ---------------------------------------------------------------------------


def test_packed_exchange_model_reproduces_dycore_cases():
    """The generic footprint-driven byte model IS the old hand-written
    dycore accounting: `kstep_exchange_model` (now a footprint wrapper)
    still produces the exact bytes, and the per-operand split is exposed."""
    for k in (1, 2, 4):
        m = memmodel.kstep_exchange_model((64, 256, 256), "float32",
                                          n_fields=4, k=k, shards=(2, 2))
        assert m["bytes_wcon"] == m["bytes_by_operand"]["wcon"]
        assert (m["bytes_by_operand"]["fields"] + m["bytes_wcon"]
                == m["bytes_kstep"])
        assert m["rounds_kstep"] == 2


def test_packed_exchange_model_vadvc_footprint():
    """vadvc's declared footprint — one right-only wcon column, nothing in
    y — models to a SINGLE active exchange round and exactly one column of
    wire bytes per shard."""
    op = get_stencil_op("vadvc")
    nz, ny, nx = 64, 256, 256
    m = memmodel.packed_exchange_model((nz, ny, nx), "float32",
                                       rides=op.memmodel_rides(4),
                                       k=1, shards=(2, 2),
                                       compute_halo=(0, 0))
    ly = ny // 2
    assert m["rounds_kstep"] == 1                    # x only, one side
    assert m["bytes_kstep"] == nz * 1 * ly * 4       # one fp32 column
    assert m["redundant_flops_frac"] == 0.0          # no halo-ring compute


def test_stencil_op_traffic_per_op_bounds():
    """Per-op traffic bounds derive from the registered OpSpecs: vadvc
    streams 8 field-sized arrays per field (7 in + 1 out), hdiff 2 plus
    its y/x halo re-reads — the per-kernel contrast the paper's table
    shows."""
    grid = (64, 256, 256)
    h = memmodel.stencil_op_traffic(autotune.get_op("hdiff"), grid,
                                    "float32", n_fields=4, tile=(1, 32, 256))
    v = memmodel.stencil_op_traffic(autotune.get_op("vadvc"), grid,
                                    "float32", n_fields=4,
                                    tile=(64, 32, 256))
    fb = 4 * int(np.prod(grid)) * 4                  # 4 fields, fp32
    assert v["ideal"] == 8 * fb
    assert h["ideal"] == 2 * fb
    assert h["stream"] >= h["ideal"]                 # halo re-reads
    assert v["stream"] >= v["ideal"]
    assert h["halo_overhead"] > 0.0
    assert v["flops_per_step"] < h["flops_per_step"] * 4  # 38 vs 21 per pt


# ---------------------------------------------------------------------------
# Distributed: report() == traced structure for ALL registered ops
# ---------------------------------------------------------------------------

_DIST_OPS_SNIPPET = r"""
import jax, numpy as np
from repro.core import trace_stats
from repro.weather import domain, fields
from repro.weather.program import StencilProgram, compile
kw = {"axis_types": (jax.sharding.AxisType.Auto,) * 2}
mesh = jax.make_mesh((2, 2), ("data", "model"), **kw)
grid = (4, 16, 16)
st = fields.initial_state(jax.random.PRNGKey(0), grid, ensemble=2)

def dist_plan(op, variant, k=1, **kwargs):
    return compile(StencilProgram(grid_shape=grid, ensemble=2, op=op,
                                  variant=variant, k_steps=k, **kwargs),
                   mesh=mesh)

# report() == traced structure for every variant of every registered op —
# the acceptance criterion: assert_plan_structure passes for all three.
cases = [("dycore", "kstep", 2), ("dycore", "whole_state", 1),
         ("hdiff", "whole_state", 1), ("hdiff", "per_field", 1),
         ("hdiff", "unfused", 1), ("hdiff", "kstep", 2),
         ("vadvc", "whole_state", 1), ("vadvc", "per_field", 1),
         ("vadvc", "unfused", 1)]
plans = {}
for op, variant, k in cases:
    plan = dist_plan(op, variant, k)
    trace_stats.assert_plan_structure(jax.make_jaxpr(plan.step)(st),
                                      plan.report())
    plans[(op, variant)] = plan

# vadvc's asymmetric wcon footprint: ONE collective (the right staggering
# column rides backward; the forward direction ships nothing and is
# elided), declared via the registry, visible in the schedule.
vrep = plans[("vadvc", "whole_state")].report()
assert vrep["collectives_per_round"] == 1, vrep["collectives_per_round"]
assert vrep["exchange"]["rides"]["wcon"]["depth_x"] == [0, 1]
assert vrep["exchange_model"]["rounds_kstep"] == 1

# hdiff rides all four collectives at the k-scaled symmetric depth
hrep = plans[("hdiff", "kstep")].report()
assert hrep["collectives_per_round"] == 4
assert hrep["exchange"]["rides"]["fields"]["depth_y"] == [4, 4]
assert hrep["pallas_calls_per_round"] == 1     # ONE launch, ONE exchange

# per-op distributed results == single-chip oracles
single = {op: compile(StencilProgram(grid_shape=grid, ensemble=2, op=op,
                                     variant="unfused"))
          for op in ("hdiff", "vadvc")}
sst = {}
for op, tgt in (("hdiff", "fields"), ("vadvc", "stage_tens")):
    want = single[op].step(st)
    for variant in ("whole_state", "per_field", "unfused"):
        plan = plans[(op, variant)]
        s = domain.shard_state(st, mesh, plan.state_spec)
        out = plan.step(s)
        for n in fields.PROGNOSTIC:
            err = np.abs(np.asarray(getattr(out, tgt)[n])
                         - np.asarray(getattr(want, tgt)[n])).max()
            assert err < 1e-6, (op, variant, n, err)
    sst[op] = domain.shard_state(st, mesh, plans[(op, "whole_state")]
                                 .state_spec)

# hdiff k-step round == 2 sequential exchanged rounds, and the ragged
# tail (3 steps on the k=2 plan) == 3 sequential rounds — bit-for-bit
seq = sst["hdiff"]
for _ in range(3):
    seq = plans[("hdiff", "whole_state")].step(seq)
got = plans[("hdiff", "kstep")].run(sst["hdiff"], 3)
for n in fields.PROGNOSTIC:
    assert np.array_equal(np.asarray(got.fields[n]),
                          np.asarray(seq.fields[n])), n

# bf16 wire policy works on per-op programs too (hdiff packs all variants)
bplan = dist_plan("hdiff", "whole_state", exchange_dtype="bfloat16")
assert bplan.report()["exchange"]["wire_dtype"] == "bfloat16"
trace_stats.assert_plan_structure(jax.make_jaxpr(bplan.step)(st),
                                  bplan.report())
outB = bplan.step(sst["hdiff"])
outF = plans[("hdiff", "whole_state")].step(sst["hdiff"])
errs = [np.abs(np.asarray(outB.fields[n]) - np.asarray(outF.fields[n])).max()
        for n in fields.PROGNOSTIC]
assert max(errs) < 0.1 and max(errs) > 0.0, errs   # cast confined to halo

# a too-deep hdiff k-step refuses loudly at compile time
try:
    dist_plan("hdiff", "kstep", 5)
except ValueError as e:
    assert "halo" in str(e), e
else:
    raise AssertionError("k=5 needs a 10-deep halo on an 8-row slab")
print("STENCIL_DIST_OK")
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


def test_distributed_per_op_plans_match_trace_and_oracles():
    """Forced-4-device subprocess: for every registered op and variant the
    plan's report() equals the traced launch/collective counts, vadvc's
    registry-declared (0,1) wcon ride costs exactly ONE collective, hdiff
    k-step rounds (and their ragged tails) are bit-equal to sequential
    exchanged rounds, and bf16 wire + compile-time halo validation work on
    per-op programs."""
    _run_forced_device_snippet(_DIST_OPS_SNIPPET, "STENCIL_DIST_OK")


def test_register_custom_op_compiles():
    """`register_stencil_op` admits a new operator without planner changes:
    a trivial copy op reusing the hdiff lowering hooks compiles, reports,
    and steps."""
    import dataclasses
    base = get_stencil_op("hdiff")
    op = dataclasses.replace(base, name="hdiff_copy",
                             title="registry smoke (hdiff clone)")
    register_stencil_op(op)
    try:
        st = _state()
        plan = _plan("hdiff_copy")
        out = plan.step(st)
        ref = _plan("hdiff").step(st)
        for n in fields.PROGNOSTIC:
            assert np.array_equal(np.asarray(out.fields[n]),
                                  np.asarray(ref.fields[n])), n
        assert plan.report()["op"] == "hdiff_copy"
    finally:
        from repro.weather.stencil_ops import STENCIL_OPS
        STENCIL_OPS.pop("hdiff_copy", None)

"""Weather dycore: single-device correctness + distributed equivalence.

Everything goes through the plan API (`repro.weather.program.compile`) —
the legacy `dycore_step`/`run`/`make_distributed_step` shims are gone
(retired ROADMAP item)."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.weather import dycore, fields
from repro.weather.program import DycoreProgram, compile_dycore


def _plan(grid, ensemble=1, variant="auto", k_steps=1, **kw):
    return compile_dycore(DycoreProgram(grid_shape=grid, ensemble=ensemble,
                                        variant=variant, k_steps=k_steps),
                          **kw)


def test_dycore_step_finite_and_shaped():
    st = fields.initial_state(jax.random.PRNGKey(0), (8, 16, 16),
                              ensemble=2)
    out = _plan((8, 16, 16), ensemble=2).step(st)
    for name in fields.PROGNOSTIC:
        f = np.asarray(out.fields[name])
        assert f.shape == (2, 8, 16, 16)
        assert np.isfinite(f).all()


def test_dycore_run_scan():
    st = fields.initial_state(jax.random.PRNGKey(1), (4, 8, 8))
    out = _plan((4, 8, 8)).run(st, 3)
    f = np.asarray(out.fields["t"])
    assert np.isfinite(f).all()


def _roughness(f):
    return float(jnp.abs(jnp.diff(f, axis=-1)).sum()
                 + jnp.abs(jnp.diff(f, axis=-2)).sum())


def test_diffusion_damps_checkerboard_and_conserves():
    """hdiff is 4th-order hyperdiffusion: it damps the 2Δx (checkerboard)
    mode hardest — amplification factor g = 1 - 64c at the spectrum peak —
    and, being in flux form on a periodic domain, conserves the mean.
    (It is NOT total-variation-diminishing: ∇⁴ overshoots at plateau
    edges, which is correct physics, so we don't assert on TV.)"""
    z, ny, nx = 4, 32, 32
    yy, xx = jnp.meshgrid(jnp.arange(ny), jnp.arange(nx), indexing="ij")
    checker = ((-1.0) ** (yy + xx)).astype(jnp.float32)
    base = jnp.sin(2 * jnp.pi * xx / nx).astype(jnp.float32)
    f0 = jnp.broadcast_to(base + 0.5 * checker, (z, ny, nx))
    f1 = dycore.hdiff_periodic(f0, coeff=0.02)
    amp0 = float(jnp.abs((f0 * checker).mean()))
    amp1 = float(jnp.abs((f1 * checker).mean()))
    assert amp1 < amp0 * 0.7, (amp0, amp1)
    assert abs(float(f1.mean() - f0.mean())) < 1e-5


def test_diffusion_unstable_above_cfl():
    """Above the stability bound the explicit step amplifies noise — the
    documented reason programs default to coeff=0.025."""
    st = fields.initial_state(jax.random.PRNGKey(2), (4, 32, 32))
    f0 = st.fields["t"]
    f = f0
    for _ in range(8):
        f = dycore.hdiff_periodic(f, coeff=0.12)
    assert _roughness(f) > _roughness(f0)


_DIST_SNIPPET = r"""
import jax, numpy as np
from repro.weather import fields, domain
from repro.weather.program import DycoreProgram, compile_dycore
key = jax.random.PRNGKey(0)
st = fields.initial_state(key, (6, 8, 8), ensemble=2)
kw = {"axis_types": (jax.sharding.AxisType.Auto,) * 2}
mesh = jax.make_mesh((2, 2), ("data", "model"), **kw)
outs = {}
for variant in ("whole_state", "per_field", "unfused"):
    # like-for-like: distributed vs single-device on the SAME path.  Even
    # so the graphs differ (pad/crop vs wrap, shard shapes), so a handful
    # of flux-limiter branch flips are legitimate (see
    # kernels/dycore_fused/ref.py::limiter_fragile_mask); tolerate <=2
    # flipped points per field under a loose physical bound.
    prog = DycoreProgram(grid_shape=(6, 8, 8), ensemble=2, variant=variant,
                         k_steps=1)
    ref = compile_dycore(prog).step(st)
    plan = compile_dycore(prog, mesh=mesh)
    out = plan.step(domain.shard_state(st, mesh, plan.state_spec))
    outs[variant] = out
    for name in fields.PROGNOSTIC:
        err = np.abs(np.asarray(ref.fields[name])
                     - np.asarray(out.fields[name]))
        bad = int((err > 1e-5).sum())
        assert bad <= 2 and err.max() < 0.05, (variant, name, bad, err.max())
        errs = np.abs(np.asarray(ref.stage_tens[name])
                      - np.asarray(out.stage_tens[name])).max()
        assert errs < 1e-5, (variant, name, errs)  # stage: no limiter upstream
# stacked exchange vs per-field exchange, head-to-head on the same shards
for name in fields.PROGNOSTIC:
    a = np.asarray(outs["whole_state"].fields[name])
    b = np.asarray(outs["per_field"].fields[name])
    bad = int((np.abs(a - b) > 1e-5).sum())
    assert bad <= 2 and np.abs(a - b).max() < 0.05, (name, bad)
    sa = np.asarray(outs["whole_state"].stage_tens[name])
    sb = np.asarray(outs["per_field"].stage_tens[name])
    assert np.abs(sa - sb).max() < 1e-5, name
print("DIST_OK")
"""


_KSTEP_SNIPPET = r"""
import jax, numpy as np
from repro.core import trace_stats
from repro.weather import fields, domain
from repro.weather.program import DycoreProgram, compile_dycore
K = 2
grid = (4, 8, 16)
st = fields.initial_state(jax.random.PRNGKey(1), grid, ensemble=2)
kw = {"axis_types": (jax.sharding.AxisType.Auto,) * 2}
mesh = jax.make_mesh((2, 2), ("data", "model"), **kw)
def plan_for(variant="auto", k=1, **kwargs):
    return compile_dycore(DycoreProgram(grid_shape=grid, ensemble=2,
                                        variant=variant, k_steps=k,
                                        **kwargs), mesh=mesh)
planK = plan_for("kstep", K)
plan1 = plan_for("whole_state", 1)

# structural win of the k-step round, asserted via trace_stats: exactly
# ONE pallas_call (the in-kernel k-step scan — not one launch per local
# step) and ONE ppermute pair per mesh direction (4 collectives) per round
j = jax.make_jaxpr(planK.step)(st)
trace_stats.assert_kstep_structure(j)
j1 = jax.make_jaxpr(plan1.step)(st)
assert trace_stats.count_primitive(j1, "ppermute") == 4
jpf = jax.make_jaxpr(plan_for("per_field").step)(st)
n_pf = trace_stats.count_primitive(jpf, "ppermute")
assert n_pf >= 4 * len(fields.PROGNOSTIC), n_pf   # per-field/per-input cost

# K-step deep halo == K sequential exchanged steps (tolerance: fp32 round)
sst = domain.shard_state(st, mesh, planK.state_spec)
outK = planK.step(sst)
seq = sst
for _ in range(K):
    seq = plan1.step(seq)
for name in fields.PROGNOSTIC:
    err = np.abs(np.asarray(outK.fields[name])
                 - np.asarray(seq.fields[name]))
    bad = int((err > 1e-5).sum())
    assert bad <= 2 and err.max() < 0.05, (name, bad, err.max())
    errs = np.abs(np.asarray(outK.stage_tens[name])
                  - np.asarray(seq.stage_tens[name])).max()
    assert errs < 1e-5, (name, errs)

# the deep halo cannot exceed the local slab: loud error at COMPILE time
try:
    plan_for("kstep", 3)
except ValueError as e:
    assert "halo" in str(e), e
else:
    raise AssertionError("k_steps=3 on a 4-row slab should refuse")

# bf16 stacked exchange: same 4-collective structure, results within bf16
# halo rounding of the fp32-wire round
planB = plan_for("kstep", K, exchange_dtype="bfloat16")
trace_stats.assert_kstep_structure(jax.make_jaxpr(planB.step)(st))
outB = planB.step(sst)
for name in fields.PROGNOSTIC:
    err = np.abs(np.asarray(outB.fields[name])
                 - np.asarray(outK.fields[name]))
    assert np.isfinite(np.asarray(outB.fields[name])).all(), name
    assert err.max() < 0.1, (name, err.max())   # halo-ring bf16 rounding
    assert err.max() > 0.0, name                # the cast actually happened

# k_steps="auto": resolved by the planner at compile time
planA = plan_for("auto", "auto")
outA = planA.step(domain.shard_state(st, mesh, planA.state_spec))
kA = planA.k_steps
assert isinstance(kA, int) and kA >= 1, kA
ref = sst
for _ in range(kA):
    ref = plan1.step(ref)
for name in fields.PROGNOSTIC:
    err = np.abs(np.asarray(outA.fields[name])
                 - np.asarray(ref.fields[name]))
    bad = int((err > 1e-5).sum())
    assert bad <= 2 and err.max() < 0.05, (name, kA, bad, err.max())
print("KSTEP_OK")
"""


def _run_forced_device_snippet(snippet: str, marker: str):
    """Run `snippet` in a subprocess with 4 forced host CPU devices."""
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    r = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert marker in r.stdout, r.stderr[-2000:]


def test_distributed_matches_single_device():
    """Halo-exchange domain decomposition == single-device periodic step on
    all three local-compute paths, and stacked-exchange == per-field
    exchange head-to-head (subprocess with 4 forced host devices)."""
    _run_forced_device_snippet(_DIST_SNIPPET, "DIST_OK")


def test_kstep_communication_avoiding():
    """K-step deep-halo mode: one ppermute pair per direction per K steps,
    ONE pallas_call per round, equivalent to K sequential exchanged
    steps, and a loud error when the halo outgrows the local slab."""
    _run_forced_device_snippet(_KSTEP_SNIPPET, "KSTEP_OK")


def test_run_whole_state_matches_per_field():
    """Whole-state and per-field plans agree over multi-step trajectories."""
    st = fields.initial_state(jax.random.PRNGKey(5), (4, 8, 8))
    out_w = _plan((4, 8, 8), variant="whole_state").run(st, 3)
    out_p = _plan((4, 8, 8), variant="per_field").run(st, 3)
    for name in fields.PROGNOSTIC:
        err = np.abs(np.asarray(out_w.fields[name])
                     - np.asarray(out_p.fields[name]))
        bad = int((err > 1e-5).sum())
        assert bad <= 2 and err.max() < 0.05, (name, bad, err.max())


def test_run_kstep_matches_sequential():
    """Single-chip k-step mode: plan.run(steps) on a k-step plan — steps/k
    rounds of ONE in-kernel-scan launch each — matches the step-by-step
    trajectory to fp32 rounding (limiter-fragile flips tolerated)."""
    grid = (4, 12, 16)
    st = fields.initial_state(jax.random.PRNGKey(6), grid, ensemble=2)
    out_seq = _plan(grid, ensemble=2).run(st, 4)
    out_k = _plan(grid, ensemble=2, variant="kstep", k_steps=2).run(st, 4)
    for name in fields.PROGNOSTIC:
        err = np.abs(np.asarray(out_k.fields[name])
                     - np.asarray(out_seq.fields[name]))
        bad = int((err > 1e-5).sum())
        assert bad <= 4 and err.max() < 0.05, (name, bad, err.max())
    with pytest.raises(ValueError):
        # k_steps > 1 is the k-step strategy; a one-step variant refuses
        DycoreProgram(grid_shape=grid, variant="per_field", k_steps=2)


def test_run_kstep_ragged_tail():
    """steps % k_steps != 0 is not an error: the plan runs the full k-step
    rounds and finishes with one shorter TAIL round at k' = steps mod k
    (ISSUE 4 satellite) — equivalent to sequential stepping within the
    usual limiter-fragile tolerance."""
    grid = (4, 12, 16)
    st = fields.initial_state(jax.random.PRNGKey(7), grid, ensemble=2)
    out_seq = _plan(grid, ensemble=2).run(st, 5)     # 5 sequential steps
    out_k = _plan(grid, ensemble=2, variant="kstep",
                  k_steps=2).run(st, 5)              # 2 rounds + k'=1 tail
    out_k3 = _plan(grid, ensemble=2, variant="kstep",
                   k_steps=3).run(st, 5)             # 1 round + k'=2 tail
    for out in (out_k, out_k3):
        for name in fields.PROGNOSTIC:
            err = np.abs(np.asarray(out.fields[name])
                         - np.asarray(out_seq.fields[name]))
            bad = int((err > 1e-5).sum())
            assert bad <= 4 and err.max() < 0.05, (name, bad, err.max())

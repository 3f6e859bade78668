"""Quickstart: the paper's two kernels through the NERO engine layers.

1. Run hdiff + vadvc oracles on the paper's 256x256x64 domain.
2. Auto-tune the 3-D window (paper Fig. 6) and show the chosen plan.
3. Validate the Pallas TPU kernels (interpret mode) against the oracles.
4. Compile declarative programs — hdiff-only, vadvc-only, and the fused
   dycore, each a registered StencilOp — into ExecutionPlans
   (`repro.weather.program.compile`) and advance them.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import hierarchy, perfmodel, tiling
from repro.core.autotune import tune
from repro.kernels.hdiff import ref as href
from repro.kernels.hdiff.hdiff import hdiff_pallas
from repro.kernels.vadvc import ref as vref
from repro.kernels.vadvc.vadvc import vadvc_pallas
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache()
    rng = np.random.default_rng(0)
    nz, ny, nx = grid = (64, 256, 256)
    print(f"== NERO quickstart on the paper's {nx}x{ny}x{nz} domain ==")

    src = jnp.asarray(rng.normal(size=grid).astype(np.float32))
    out = jax.jit(href.hdiff)(src)
    print(f"hdiff: out[2,2,2]={float(out[2, 2, 2]):+.4f} "
          f"finite={bool(jnp.isfinite(out).all())}")

    us, up, ut, uts = (jnp.asarray(rng.normal(size=grid).astype(np.float32))
                       for _ in range(4))
    wcon = jnp.asarray(rng.uniform(-0.2, 0.2, size=(nz, ny, nx + 1))
                       .astype(np.float32))
    adv = jax.jit(vref.vadvc)(us, wcon, up, ut, uts)
    res = vref.tridiagonal_residual(us, wcon, up, ut, uts, np.asarray(adv))
    print(f"vadvc: tridiagonal residual {res:.2e} (solves the system)")

    for op, dtype in ((tiling.VADVC, "float32"), (tiling.VADVC, "bfloat16")):
        t = tune(op, grid, dtype)
        pct = 100 * t.plan.vmem_bytes / hierarchy.tpu_v5e().vmem.capacity_bytes
        print(f"autotuned {op.name}/{dtype}: tile={t.plan.tile} "
              f"vmem={pct:.0f}% model_gflops={t.est.gflops:.0f}")

    # Pallas kernels, interpret mode (CPU container; TPU is the target)
    small = (8, 32, 32)
    s2 = jnp.asarray(rng.normal(size=small).astype(np.float32))
    pe = np.asarray(hdiff_pallas(s2, ty=8, interpret=True))
    err = np.abs(pe - np.asarray(href.hdiff(s2))).max()
    print(f"pallas hdiff vs oracle: max err {err:.2e}")

    f = [jnp.asarray(rng.normal(size=small).astype(np.float32))
         for _ in range(4)]
    w2 = jnp.asarray(rng.uniform(-0.2, 0.2, size=(8, 32, 33))
                     .astype(np.float32))
    pv = np.asarray(vadvc_pallas(f[0], w2, f[1], f[2], f[3], tj=8, ti=16,
                                 interpret=True))
    err = np.abs(pv - vref.vadvc_np(f[0], w2, f[1], f[2], f[3])).max()
    print(f"pallas vadvc vs oracle: max err {err:.2e}")

    # Declarative programs over REGISTERED stencil ops: the spec says WHAT
    # (op, grid, fields, k-step policy); compile resolves HOW (variant,
    # auto-tuned tile, footprint-derived exchange, launches per round)
    # once.  The paper's two kernels are first-class programs.
    from repro.weather import fields as wfields
    from repro.weather.program import (DycoreProgram, StencilProgram,
                                       compile)
    st = wfields.initial_state(jax.random.PRNGKey(0), small)
    hplan = compile(StencilProgram(grid_shape=small, op="hdiff"))
    hrep = hplan.report()
    print(f"compile(op=hdiff): variant={hrep['variant']} "
          f"launches/round={hrep['pallas_calls_per_round']} "
          f"footprint={hrep['footprint']['rides'][0]['depth_y']} "
          f"model_gflops={hrep['model']['gflops']:.0f}")
    st = hplan.step(st)
    vplan = compile(StencilProgram(grid_shape=small, op="vadvc"))
    vrep = vplan.report()
    print(f"compile(op=vadvc): variant={vrep['variant']} "
          f"wcon ride={vrep['footprint']['rides'][0]['depth_x']} "
          f"model_gflops={vrep['model']['gflops']:.0f}")
    st = vplan.step(st)
    plan = compile(DycoreProgram(grid_shape=small, variant="kstep",
                                 k_steps=2))
    rep = plan.report()
    print(f"compile(op=dycore): variant={rep['variant']} "
          f"k_steps={rep['k_steps']} tile={rep['tile']['tile']} "
          f"launches/round={rep['pallas_calls_per_round']}")
    st = plan.run(st, 3)   # 1 k-step round + a ragged 1-step tail round
    ok = bool(jnp.isfinite(st.fields["t"]).all())
    print(f"plan.run(3 steps): finite={ok}")

    # Chain registered ops into ONE plan: the planner back-propagates the
    # stages' reach into a single fused exchange and runs the launches in
    # order on resident operands — bit-identical to the solo programs.
    from repro.weather.pipeline import PipelineProgram
    pplan = compile(PipelineProgram(
        grid_shape=small, coeff=0.05,
        stages=("hadv_upwind", "vadvc_update", "hdiff")))
    prep = pplan.report()
    print(f"compile(pipeline): stages=3 "
          f"launches/round={prep['pallas_calls_per_round']} "
          f"merged fields ride="
          f"{prep['footprint']['rides'][0]['depth_y']} "
          f"hbm_reduction={prep['traffic']['chained_reduction_x']:.2f}x")
    st = pplan.step(st)
    print("quickstart OK")


if __name__ == "__main__":
    main()

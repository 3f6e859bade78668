"""NeroEngine: plan caching, dispatch, and oracle agreement."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import NeroEngine
from repro.kernels.hdiff import ref as href
from repro.kernels.vadvc import ref as vref


def test_plan_is_cached_and_fits():
    eng = NeroEngine()
    t1 = eng.plan("hdiff", (8, 64, 64), jnp.float32)
    t2 = eng.plan("hdiff", (8, 64, 64), jnp.float32)
    assert t1 is t2
    assert t1.plan.fits(eng.hier)
    assert t1.est.time_s > 0


def test_precision_changes_pareto_choice():
    eng = NeroEngine()
    p32 = eng.plan("hdiff", (64, 256, 256), jnp.float32).plan
    p16 = eng.plan("hdiff", (64, 256, 256), jnp.bfloat16).plan
    # paper Fig. 6: the chosen window depends on dtype (bf16 fits more)
    assert p16.vmem_bytes <= p32.vmem_bytes * 2
    assert p16.tile != p32.tile or p16.dtype != p32.dtype


def test_run_hdiff_matches_oracle():
    eng = NeroEngine()
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.normal(size=(4, 16, 128)).astype(np.float32))
    tuned = eng.plan("hdiff", src.shape, src.dtype)
    out = eng.run(tuned, src)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(href.hdiff(src)),
                               atol=1e-5)


def test_run_vadvc_matches_oracle():
    eng = NeroEngine()
    rng = np.random.default_rng(1)
    shp = (8, 8, 128)
    f = lambda: jnp.asarray(rng.normal(size=shp).astype(np.float32))
    wcon = jnp.asarray(rng.normal(size=(8, 8, 129)).astype(np.float32))
    u, up, ut, us = f(), f(), f(), f()
    tuned = eng.plan("vadvc", shp, jnp.float32)
    out = eng.run(tuned, u, wcon, up, ut, us)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(vref.vadvc(u, wcon, up, ut, us)),
                               atol=2e-4, rtol=2e-4)


def test_precision_dependent_pareto_under_bram_budget():
    """Paper Fig. 6: the Pareto-optimal window depends on precision when
    the near-memory resource binds (FPGA ~1 MiB BRAM per PE).  At v5e's
    128 MiB VMEM the 256x256x64 domain never binds — also asserted, since
    that hardware-adaptation finding is recorded in EXPERIMENTS.md."""
    from repro.core import hierarchy as hw
    from repro.core.autotune import tune
    from repro.core import tiling

    hier = hw.tpu_v5e()
    small = hw.Hierarchy(
        hbm=hier.hbm,
        vmem=hw.MemoryLevel("vmem", 2**20,
                            hier.vmem.bandwidth_bytes_per_s,
                            hier.vmem.energy_pj_per_byte),
        vreg=hier.vreg)
    grid = (64, 256, 256)
    # The paper's PE streams each window once and keeps no compiler
    # temporaries: model it without the Pallas kernels' aliased halo
    # windows and live values (fp32 whatever the storage precision).
    pe = lambda op: dataclasses.replace(op, input_windows=1, live_values=0)
    for op in (pe(tiling.VADVC), pe(tiling.HDIFF)):
        c32 = tune(op, grid, "float32", small).plan
        c16 = tune(op, grid, "bfloat16", small).plan
        assert c32.tile != c16.tile, op.name
        assert c16.tile_points > c32.tile_points, op.name
        v32 = tune(op, grid, "float32", hier).plan
        v16 = tune(op, grid, "bfloat16", hier).plan
        assert v32.tile == v16.tile, op.name

"""Jitted public entry points for vadvc (planner-aware dispatch)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import autotune, tiling
from repro.kernels.vadvc import ref as _ref
from repro.kernels.vadvc.vadvc import vadvc_pallas


def plan_tile(grid_shape, dtype):
    """Auto-tuned (tj, ti) horizontal window (paper's 64x2 fp32 analogue),
    snapped by `tiling.snap_to_divisor`: tj to sublane-aligned divisors of
    y, ti to 128-lane divisors of x (or the whole extents)."""
    tuned = autotune.tune_named("vadvc", grid_shape, dtype)
    _, tj, ti = tuned.plan.tile
    nz, ny, nx = grid_shape
    return (tiling.snap_to_divisor(tj, ny, align=tiling.sublanes(dtype)),
            tiling.snap_to_divisor(ti, nx, align=tiling.LANES))


def resolve_tile(grid_shape, dtype) -> tiling.TilePlan:
    """Planner entry (`weather/program.py::compile`): the auto-tuned,
    snapped (tj, ti) window as a full `TilePlan` over the vadvc tile space
    (z stays whole — the Thomas solve is sequential in z)."""
    tj, ti = plan_tile(grid_shape, dtype)
    return tiling.TilePlan(op=autotune.get_op("vadvc"),
                           grid_shape=tuple(int(g) for g in grid_shape),
                           tile=(int(grid_shape[0]), tj, ti),
                           dtype=str(jnp.dtype(dtype)))


@functools.partial(jax.jit, static_argnames=("use_pallas", "tj", "ti",
                                             "interpret"))
def vadvc(u_stage, wcon, u_pos, utens, utens_stage,
          use_pallas: bool = False, tj: int = 0, ti: int = 0,
          interpret: bool = True):
    if use_pallas:
        if not (tj and ti):
            tj, ti = plan_tile(u_stage.shape, u_stage.dtype)
        return vadvc_pallas(u_stage, wcon, u_pos, utens, utens_stage,
                            tj=tj, ti=ti, interpret=interpret)
    return _ref.vadvc(u_stage, wcon, u_pos, utens, utens_stage)

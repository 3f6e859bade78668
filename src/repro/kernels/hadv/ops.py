"""Jitted public entry points for hadv_upwind (planner-aware dispatch)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import autotune, tiling
from repro.kernels.hadv import ref as _ref
from repro.kernels.hadv.hadv import hadv_pallas

HALO = 1   # one-sided (low-side) reach in y and x


def plan_tile(grid_shape, dtype) -> int:
    """Auto-tuned y-window for the Pallas kernel, snapped to a
    sublane-aligned divisor (or the whole extent)."""
    tuned = autotune.tune_named("hadv_upwind", grid_shape, dtype)
    return tiling.snap_to_divisor(tuned.plan.tile[1], grid_shape[1],
                                  align=tiling.sublanes(dtype))


def resolve_tile(grid_shape, dtype) -> tiling.TilePlan:
    """Planner entry (`weather/program.py::compile`): the auto-tuned,
    snapped y-window as a full `TilePlan` over the hadv tile space."""
    ty = plan_tile(grid_shape, dtype)
    return tiling.TilePlan(op=autotune.get_op("hadv_upwind"),
                           grid_shape=tuple(int(g) for g in grid_shape),
                           tile=(1, ty, int(grid_shape[2])),
                           dtype=str(jnp.dtype(dtype)))


@functools.partial(jax.jit, static_argnames=("cfl", "use_pallas", "ty",
                                             "interpret"))
def hadv_upwind(src: jnp.ndarray, cfl: float = _ref.DEFAULT_CFL,
                use_pallas: bool = False, ty: int = 0,
                interpret: bool = True) -> jnp.ndarray:
    if use_pallas:
        ty = ty or plan_tile(src.shape, src.dtype)
        return hadv_pallas(src, cfl=cfl, ty=ty, interpret=interpret)
    return _ref.hadv_upwind(src, cfl=cfl)

"""Jitted public entry points for hdiff (planner-aware dispatch).

`hdiff(...)` picks the implementation: the Pallas kernel on TPU (or when
`interpret=True` is forced for validation), else the pure-jnp oracle — the
differentiable path used by the weather dycore during training.
Tile sizes come from the NERO autotuner unless overridden.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import autotune, tiling
from repro.kernels.hdiff import ref as _ref
from repro.kernels.hdiff.hdiff import hdiff_pallas

HALO = 2   # the compound stencil's one-sided reach in y and x


def plan_tile(grid_shape, dtype) -> int:
    """Auto-tuned y-window for the Pallas kernel (paper Fig. 6 stage),
    snapped by `tiling.snap_to_divisor` to a sublane-aligned divisor of
    the y extent (or the whole extent)."""
    tuned = autotune.tune_named("hdiff", grid_shape, dtype)
    return tiling.snap_to_divisor(tuned.plan.tile[1], grid_shape[1],
                                  align=tiling.sublanes(dtype), lo=2)


def resolve_tile(grid_shape, dtype) -> tiling.TilePlan:
    """Planner entry (`weather/program.py::compile`): the auto-tuned,
    snapped y-window as a full `TilePlan` over the hdiff tile space."""
    ty = plan_tile(grid_shape, dtype)
    # The kernel's grid is (nz, ny/ty): one z-plane and the whole x extent
    # per cell, so the staged window is (1, ty, nx).
    return tiling.TilePlan(op=autotune.get_op("hdiff"),
                           grid_shape=tuple(int(g) for g in grid_shape),
                           tile=(1, ty, int(grid_shape[2])),
                           dtype=str(jnp.dtype(dtype)))


@functools.partial(jax.jit, static_argnames=("coeff", "use_pallas", "ty",
                                             "interpret"))
def hdiff(src: jnp.ndarray, coeff: float = _ref.DEFAULT_COEFF,
          use_pallas: bool = False, ty: int = 0,
          interpret: bool = True) -> jnp.ndarray:
    if use_pallas:
        ty = ty or plan_tile(src.shape, src.dtype)
        return hdiff_pallas(src, coeff=coeff, ty=ty, interpret=interpret)
    return _ref.hdiff(src, coeff=coeff)

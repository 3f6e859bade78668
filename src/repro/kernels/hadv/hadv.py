"""Pallas TPU kernel for first-order upwind horizontal advection.

Same shape as the hdiff kernel, with a 1-row low-side halo instead of a
symmetric 2-row one: grid = (nz, ny/ty), the y-halo realized with an
aliased prev-window ref (clamped at the global low edge — those rows are
passthrough anyway), x whole per window on the lane dimension.  Compute
is fp32 internally; bf16 in/out supported.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import VMEM_LIMIT_BYTES
from repro.kernels.hadv.ref import DEFAULT_CFL


def _hadv_kernel(prev_ref, cur_ref, out_ref, *, cfl: float,
                 ny: int, ty: int):
    j = pl.program_id(1)
    nx = cur_ref.shape[2]

    prev = prev_ref[0].astype(jnp.float32)     # (ty, nx)
    cur = cur_ref[0].astype(jnp.float32)
    # Working window with a 1-row halo on the low side only.
    work = jnp.concatenate([prev[-1:], cur], axis=0)   # (ty+1, nx)

    c = work[1: 1 + ty, 1:]         # (ty, nx-1)
    ym = work[0: ty, 1:]
    xm = work[1: 1 + ty, : nx - 1]
    interior = c - cfl * ((c - ym) + (c - xm))

    # Global row 0 passes through (low-side ring); column 0 is never
    # written.  Clamped prev at j == 0 only feeds that invalid row.
    row_ids = j * ty + jax.lax.broadcasted_iota(jnp.int32, (ty, 1), 0)
    valid = row_ids >= 1
    center = work[1: 1 + ty, :]
    out_ref[0] = center.astype(out_ref.dtype)
    out_ref[0, :, 1:] = jnp.where(valid, interior,
                                  center[:, 1:]).astype(out_ref.dtype)


def hadv_pallas(src: jnp.ndarray, cfl: float = DEFAULT_CFL,
                ty: int = 8, interpret: bool = False) -> jnp.ndarray:
    """Tiled upwind advection.  src: (nz, ny, nx), ny % ty == 0, ty >= 1."""
    nz, ny, nx = src.shape
    if ny % ty or ty < 1:
        raise ValueError(f"ny={ny} must be divisible by ty={ty} >= 1")
    nyb = ny // ty

    spec = functools.partial(pl.BlockSpec, (1, ty, nx))
    in_specs = [
        spec(lambda k, j: (k, jnp.maximum(j - 1, 0), 0)),   # prev
        spec(lambda k, j: (k, j, 0)),                       # cur
    ]
    out_spec = spec(lambda k, j: (k, j, 0))

    kernel = functools.partial(_hadv_kernel, cfl=cfl, ny=ny, ty=ty)
    fn = pl.pallas_call(
        kernel,
        grid=(nz, nyb),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(src.shape, src.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="nero_hadv_upwind",
    )
    return fn(src, src)

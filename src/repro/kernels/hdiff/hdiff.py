"""Pallas TPU kernel for the COSMO horizontal diffusion compound stencil.

NERO's hdiff PE streams a 3-D window from a dedicated HBM channel through
BRAM/URAM line buffers and computes laplace -> limited flux -> output as a
dataflow pipeline.  The TPU formulation:

  * grid = (nz, ny/ty): z is fully parallel (paper: "hdiff can be fully
    parallelized in the vertical dimension"); y is tiled into windows.
  * The y-halo (2 points) is realized with three aliased input refs
    (prev / cur / next window) — the Pallas idiom for overlapping windows;
    HBM->VMEM block transfers are double-buffered by the Pallas pipeline,
    which is exactly the paper's load/compute/store dataflow overlap.
  * x stays whole inside a window (the paper's windows also keep one axis
    whole per PE); lane dimension = x for VPU alignment.

Compute is fp32 internally; bf16 in/out supported (paper's half-precision
mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import VMEM_LIMIT_BYTES
from repro.kernels.hdiff.ref import DEFAULT_COEFF


def _hdiff_kernel(prev_ref, cur_ref, next_ref, out_ref, *, coeff: float,
                  ny: int, ty: int):
    j = pl.program_id(1)
    nx = cur_ref.shape[2]

    prev = prev_ref[0].astype(jnp.float32)     # (ty, nx)
    cur = cur_ref[0].astype(jnp.float32)
    nxt = next_ref[0].astype(jnp.float32)
    # Assemble the VMEM working window with a 2-row halo on each side.
    work = jnp.concatenate([prev[-2:], cur, nxt[:2]], axis=0)  # (ty+4, nx)

    def s(dj: int, di: int) -> jnp.ndarray:
        """Window shifted by (dj, di), cropped to the x-interior (halo 2)."""
        return work[2 + dj: 2 + dj + ty, 2 + di: nx - 2 + di]

    def lap(dj: int, di: int) -> jnp.ndarray:
        # true-Laplacian sign (see ref.py): Σ neighbors - 4·center
        return ((s(dj, di - 1) + s(dj, di + 1)
                 + s(dj - 1, di) + s(dj + 1, di))
                - 4.0 * s(dj, di))

    lap_c, lap_xp, lap_xm = lap(0, 0), lap(0, 1), lap(0, -1)
    lap_yp, lap_ym = lap(1, 0), lap(-1, 0)

    flx = lap_xp - lap_c
    flx_m = lap_c - lap_xm
    fly = lap_yp - lap_c
    fly_m = lap_c - lap_ym
    # COSMO flux limiter.
    flx = jnp.where(flx * (s(0, 1) - s(0, 0)) > 0.0, 0.0, flx)
    flx_m = jnp.where(flx_m * (s(0, 0) - s(0, -1)) > 0.0, 0.0, flx_m)
    fly = jnp.where(fly * (s(1, 0) - s(0, 0)) > 0.0, 0.0, fly)
    fly_m = jnp.where(fly_m * (s(0, 0) - s(-1, 0)) > 0.0, 0.0, fly_m)

    interior = s(0, 0) - coeff * ((flx - flx_m) + (fly - fly_m))

    # Rows outside [2, ny-2) pass through (global-boundary ring).
    row_ids = j * ty + jax.lax.broadcasted_iota(jnp.int32, (ty, 1), 0)
    valid = (row_ids >= 2) & (row_ids < ny - 2)
    center = work[2: 2 + ty, :]
    out_ref[0] = center.astype(out_ref.dtype)
    out_ref[0, :, 2: nx - 2] = jnp.where(
        valid, interior, center[:, 2: nx - 2]).astype(out_ref.dtype)


def _hdiff_kstep_kernel(prev_ref, cur_ref, next_ref, out_ref, w_ref, *,
                        coeff: float, ny: int, ty: int, k_steps: int):
    j = pl.program_id(1)
    nx = cur_ref.shape[2]
    out_dtype = out_ref.dtype
    h = 3 * ty   # slab height: prev + cur + next windows

    # The (3*ty, nx) fp32 slab lives in VMEM scratch; each step rewrites
    # its interior in place.
    w_ref[...] = jnp.concatenate([prev_ref[0], cur_ref[0], next_ref[0]],
                                 axis=0).astype(jnp.float32)
    # Global row id of every slab row *as if* the neighbor windows were
    # not edge-clamped.  Clamp duplicates then get out-of-range ids, so
    # they are never recomputed, and the global passthrough ring (rows
    # 0,1 and ny-2,ny-1 — also never recomputed) keeps their stale values
    # from ever reaching a valid output row.
    row_ids = ((j - 1) * ty
               + jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0))
    valid = (row_ids >= 2) & (row_ids < ny - 2)

    def step(_, carry):
        w = w_ref[...]

        def s(dj: int, di: int) -> jnp.ndarray:
            return w[2 + dj: h - 2 + dj, 2 + di: nx - 2 + di]

        def lap(dj: int, di: int) -> jnp.ndarray:
            return ((s(dj, di - 1) + s(dj, di + 1)
                     + s(dj - 1, di) + s(dj + 1, di))
                    - 4.0 * s(dj, di))

        lap_c, lap_xp, lap_xm = lap(0, 0), lap(0, 1), lap(0, -1)
        lap_yp, lap_ym = lap(1, 0), lap(-1, 0)
        flx = lap_xp - lap_c
        flx_m = lap_c - lap_xm
        fly = lap_yp - lap_c
        fly_m = lap_c - lap_ym
        flx = jnp.where(flx * (s(0, 1) - s(0, 0)) > 0.0, 0.0, flx)
        flx_m = jnp.where(flx_m * (s(0, 0) - s(0, -1)) > 0.0, 0.0, flx_m)
        fly = jnp.where(fly * (s(1, 0) - s(0, 0)) > 0.0, 0.0, fly)
        fly_m = jnp.where(fly_m * (s(0, 0) - s(-1, 0)) > 0.0, 0.0, fly_m)
        interior = s(0, 0) - coeff * ((flx - flx_m) + (fly - fly_m))

        upd = jnp.where(valid[2: h - 2], interior, s(0, 0))
        # Round-trip through the storage dtype so each in-kernel step
        # rounds exactly like a separate launch (bit-equal ragged tails);
        # the untouched ring already holds storage-dtype values.
        w_ref[2: h - 2, 2: nx - 2] = upd.astype(out_dtype).astype(
            jnp.float32)
        return carry

    jax.lax.fori_loop(0, k_steps, step, 0)
    out_ref[0] = w_ref[ty: 2 * ty].astype(out_dtype)


def hdiff_kstep_pallas(src: jnp.ndarray, coeff: float = DEFAULT_COEFF,
                       ty: int = 8, k_steps: int = 1,
                       interpret: bool = False) -> jnp.ndarray:
    """In-kernel k-step hdiff: ONE launch applies `k_steps` rounds.

    src: (nz, ny, nx), ny % ty == 0, ty >= max(2, 2*k_steps) — each step
    shrinks the slab's valid interior by 2 rows per side, so the written
    center window (rows [ty, 2*ty)) stays step-correct through all k.
    """
    nz, ny, nx = src.shape
    k_steps = int(k_steps)
    if k_steps < 1:
        raise ValueError(f"k_steps={k_steps} must be >= 1")
    lo = max(2, 2 * k_steps)
    if ny % ty or ty < lo:
        raise ValueError(
            f"ny={ny} must be divisible by ty={ty} >= max(2, 2*k)={lo}")
    nyb = ny // ty

    spec = functools.partial(pl.BlockSpec, (1, ty, nx))
    in_specs = [
        spec(lambda k, j: (k, jnp.maximum(j - 1, 0), 0)),          # prev
        spec(lambda k, j: (k, j, 0)),                              # cur
        spec(lambda k, j: (k, jnp.minimum(j + 1, nyb - 1), 0)),    # next
    ]
    out_spec = spec(lambda k, j: (k, j, 0))

    kernel = functools.partial(_hdiff_kstep_kernel, coeff=coeff, ny=ny,
                               ty=ty, k_steps=k_steps)
    fn = pl.pallas_call(
        kernel,
        grid=(nz, nyb),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(src.shape, src.dtype),
        scratch_shapes=[pltpu.VMEM((3 * ty, nx), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="nero_hdiff_kstep",
    )
    return fn(src, src, src)


def hdiff_pallas(src: jnp.ndarray, coeff: float = DEFAULT_COEFF,
                 ty: int = 8, interpret: bool = False) -> jnp.ndarray:
    """Tiled compound hdiff.  src: (nz, ny, nx), ny % ty == 0, ty >= 2."""
    nz, ny, nx = src.shape
    if ny % ty or ty < 2:
        raise ValueError(f"ny={ny} must be divisible by ty={ty} >= 2")
    nyb = ny // ty

    spec = functools.partial(pl.BlockSpec, (1, ty, nx))
    in_specs = [
        spec(lambda k, j: (k, jnp.maximum(j - 1, 0), 0)),          # prev
        spec(lambda k, j: (k, j, 0)),                              # cur
        spec(lambda k, j: (k, jnp.minimum(j + 1, nyb - 1), 0)),    # next
    ]
    out_spec = spec(lambda k, j: (k, j, 0))

    kernel = functools.partial(_hdiff_kernel, coeff=coeff, ny=ny, ty=ty)
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
        name="nero_hdiff",
    )
    return fn(src, src, src)

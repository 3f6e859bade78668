"""Pallas TPU kernel for COSMO vertical advection (Thomas solver).

This is the paper's vadvc PE design mapped to VMEM:

  * grid = (ny/tj, nx/ti): the horizontal plane is tiled into windows — the
    paper's auto-tuned x/y tiles (z is never tiled: "vadvc has dependencies
    in the z-dimension; therefore, it cannot be parallelized in z").
  * Each window stages full z-columns of all 7 fields in VMEM (the paper's
    URAM/BRAM column buffers), runs the forward sweep storing (ccol, dcol)
    in fp32 VMEM scratch — the paper's "intermediate buffer to allow for
    backward sweep calculation" — then back-substitutes and streams the
    tendency out.
  * The i+1-staggered wcon access is materialized as two pre-sliced inputs
    (wl = wcon[..., :-1], wr = wcon[..., 1:]) so every block transfer stays
    a clean rectangular HBM->VMEM DMA (no overlapping windows needed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import VMEM_LIMIT_BYTES
from repro.kernels.vadvc.ref import BET_M, BET_P, DTR_STAGE


def _vadvc_kernel(ustage_ref, wl_ref, wr_ref, upos_ref, utens_ref,
                  ustagetens_ref, out_ref, ccol_ref, dcol_ref, *, nz: int):
    f32 = jnp.float32

    def ld(ref, k):
        return ref[pl.ds(k, 1), :, :][0].astype(f32)

    # ---- forward sweep, k = 0 ---------------------------------------------
    w1 = ld(wl_ref, 1) + ld(wr_ref, 1)
    gcv = 0.25 * w1
    cs = gcv * BET_M
    ccol0 = gcv * BET_P
    bcol = DTR_STAGE - ccol0
    u0 = ld(ustage_ref, 0)
    u1 = ld(ustage_ref, 1)
    corr = -cs * (u1 - u0)
    dcol0 = (DTR_STAGE * ld(upos_ref, 0) + ld(utens_ref, 0)
             + ld(ustagetens_ref, 0) + corr)
    divided = 1.0 / bcol
    ccol_ref[pl.ds(0, 1)] = (ccol0 * divided)[None]
    dcol_ref[pl.ds(0, 1)] = (dcol0 * divided)[None]

    # ---- forward sweep, 0 < k < nz-1 ---------------------------------------
    def fwd_body(k, _):
        wk = ld(wl_ref, k) + ld(wr_ref, k)
        wk1 = ld(wl_ref, k + 1) + ld(wr_ref, k + 1)
        gav = -0.25 * wk
        gcv = 0.25 * wk1
        as_ = gav * BET_M
        cs = gcv * BET_M
        acol = gav * BET_P
        ccol = gcv * BET_P
        bcol = DTR_STAGE - acol - ccol
        ukm, uk, ukp = (ld(ustage_ref, k - 1), ld(ustage_ref, k),
                        ld(ustage_ref, k + 1))
        corr = -as_ * (ukm - uk) - cs * (ukp - uk)
        dcol = (DTR_STAGE * ld(upos_ref, k) + ld(utens_ref, k)
                + ld(ustagetens_ref, k) + corr)
        cprev = ccol_ref[pl.ds(k - 1, 1)][0]
        dprev = dcol_ref[pl.ds(k - 1, 1)][0]
        divided = 1.0 / (bcol - cprev * acol)
        ccol_ref[pl.ds(k, 1)] = (ccol * divided)[None]
        dcol_ref[pl.ds(k, 1)] = ((dcol - dprev * acol) * divided)[None]
        return 0

    jax.lax.fori_loop(1, nz - 1, fwd_body, 0)

    # ---- forward sweep, k = nz-1 -------------------------------------------
    k = nz - 1
    wk = ld(wl_ref, k) + ld(wr_ref, k)
    gav = -0.25 * wk
    as_ = gav * BET_M
    acol = gav * BET_P
    bcol = DTR_STAGE - acol
    corr = -as_ * (ld(ustage_ref, k - 1) - ld(ustage_ref, k))
    dcol = (DTR_STAGE * ld(upos_ref, k) + ld(utens_ref, k)
            + ld(ustagetens_ref, k) + corr)
    cprev = ccol_ref[pl.ds(k - 1, 1)][0]
    dprev = dcol_ref[pl.ds(k - 1, 1)][0]
    divided = 1.0 / (bcol - cprev * acol)
    dlast = (dcol - dprev * acol) * divided
    dcol_ref[pl.ds(k, 1)] = dlast[None]

    # ---- backward sweep ------------------------------------------------------
    out_ref[pl.ds(nz - 1, 1)] = (
        DTR_STAGE * (dlast - ld(upos_ref, nz - 1)))[None].astype(out_ref.dtype)

    def bwd_body(m, datac):
        k = nz - 2 - m
        dk = dcol_ref[pl.ds(k, 1)][0]
        ck = ccol_ref[pl.ds(k, 1)][0]
        datac = dk - ck * datac
        out_ref[pl.ds(k, 1)] = (
            DTR_STAGE * (datac - ld(upos_ref, k)))[None].astype(out_ref.dtype)
        return datac

    jax.lax.fori_loop(0, nz - 1, bwd_body, dlast)


def vadvc_pallas(u_stage: jnp.ndarray, wcon: jnp.ndarray, u_pos: jnp.ndarray,
                 utens: jnp.ndarray, utens_stage: jnp.ndarray,
                 tj: int = 8, ti: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """Tiled vadvc.  Fields (nz, ny, nx); wcon (nz, ny, nx+1); ny%tj==nx%ti==0."""
    nz, ny, nx = u_stage.shape
    if ny % tj or nx % ti:
        raise ValueError(f"(ny={ny}, nx={nx}) must tile by (tj={tj}, ti={ti})")
    wl = wcon[:, :, :nx]
    wr = wcon[:, :, 1:nx + 1]

    spec = pl.BlockSpec((nz, tj, ti), lambda j, i: (0, j, i))
    kernel = functools.partial(_vadvc_kernel, nz=nz)
    fn = pl.pallas_call(
        kernel,
        grid=(ny // tj, nx // ti),
        in_specs=[spec] * 6,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(u_stage.shape, u_stage.dtype),
        scratch_shapes=[
            pltpu.VMEM((nz, tj, ti), jnp.float32),   # ccol
            pltpu.VMEM((nz, tj, ti), jnp.float32),   # dcol
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="nero_vadvc",
    )
    return fn(u_stage, wl, wr, u_pos, utens, utens_stage)

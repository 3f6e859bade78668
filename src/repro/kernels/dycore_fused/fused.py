"""Pallas TPU compound kernel: one fused dycore field step per grid cell.

This is the NERO dataflow argument (arxiv 2107.08716 §3) applied to the whole
dycore step instead of a single stencil: the CPU/GPU baseline writes every
stage's result back to main memory (vadvc tendency, explicitly-updated field,
padded halo copy), while the FPGA PE streams a window once and pipelines
laplace -> flux-limit -> output plus the vertical Thomas solve entirely in
near-memory (BRAM/URAM).  The TPU formulation of that PE:

  * grid = (batch, ny/ty): each grid cell owns a full z-slab of one y-window
    (vadvc is sequential in z, so z is never tiled — the paper's PE design);
    batch rides the ensemble axis.
  * The 2-deep periodic y-halo is realized with three aliased input refs
    (prev / cur / next window) whose index maps wrap modulo the window count
    — the overlapping-window idiom from kernels/hdiff/hdiff.py, made
    periodic.  x stays whole inside the window; the periodic x-halo is a
    lane roll in VMEM.
  * Stages chain through VMEM scratch only: the forward Thomas sweep stores
    (ccol, dcol) in fp32 scratch (the paper's "intermediate buffer to allow
    for backward sweep calculation"), backward substitution writes the stage
    tendency into scratch, the point-wise update and the compound hdiff read
    it straight from VMEM, and only (f_new, stage) for the *cur* window ever
    travel back to HBM.
  * Compute is fp32 internally; bf16 I/O supported (the paper's
    half-precision mode trades HBM traffic for accuracy).

The staggered vertical velocity enters pre-combined: callers pass
w = wcon_i + wcon_{i+1} (periodic next column), which is the only combination
the solve ever uses — this keeps every block transfer a clean rectangular
HBM->VMEM DMA, the same trick vadvc.py uses with its wl/wr pre-slices.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import VMEM_LIMIT_BYTES
from repro.kernels.hdiff.ref import DEFAULT_COEFF
from repro.kernels.vadvc.ref import BET_M, BET_P, DTR_STAGE

HALO = 2   # y/x halo depth of the compound hdiff stage


def _window_step(fwork, wwork, rhs, ccol, dcol, stage,
                 *, nz: int, dt: float, coeff: float):
    """One full dycore step on the (nz, R, nx) fp32 working window held in
    VMEM scratch refs: Thomas solve -> stage tendency (written into `stage`),
    point-wise update, compound hdiff with periodic in-window wrap in both y
    and x.  Returns the diffused field as a (nz, R, nx) array; rows within
    HALO of a window edge whose wrap is not truly periodic come out garbage
    (callers crop / shrink validity accordingly)."""

    def ld(ref, k):
        return ref[pl.ds(k, 1)][0]

    # ---- vadvc forward sweep, k = 0 ---------------------------------------
    gcv = 0.25 * ld(wwork, 1)
    cs = gcv * BET_M
    ccol0 = gcv * BET_P
    bcol = DTR_STAGE - ccol0
    corr = -cs * (ld(fwork, 1) - ld(fwork, 0))
    divided = 1.0 / bcol
    ccol[pl.ds(0, 1)] = (ccol0 * divided)[None]
    dcol[pl.ds(0, 1)] = ((ld(rhs, 0) + corr) * divided)[None]

    # ---- forward sweep, 0 < k < nz-1 --------------------------------------
    def fwd_body(k, _):
        gav = -0.25 * ld(wwork, k)
        gcv = 0.25 * ld(wwork, k + 1)
        as_ = gav * BET_M
        cs = gcv * BET_M
        acol = gav * BET_P
        ccol_k = gcv * BET_P
        bcol = DTR_STAGE - acol - ccol_k
        fk = ld(fwork, k)
        corr = (-as_ * (ld(fwork, k - 1) - fk)
                - cs * (ld(fwork, k + 1) - fk))
        cprev = ccol[pl.ds(k - 1, 1)][0]
        dprev = dcol[pl.ds(k - 1, 1)][0]
        divided = 1.0 / (bcol - cprev * acol)
        ccol[pl.ds(k, 1)] = (ccol_k * divided)[None]
        dcol[pl.ds(k, 1)] = (((ld(rhs, k) + corr) - dprev * acol)
                             * divided)[None]
        return 0

    jax.lax.fori_loop(1, nz - 1, fwd_body, 0)

    # ---- forward sweep, k = nz-1 ------------------------------------------
    k = nz - 1
    gav = -0.25 * ld(wwork, k)
    as_ = gav * BET_M
    acol = gav * BET_P
    bcol = DTR_STAGE - acol
    corr = -as_ * (ld(fwork, k - 1) - ld(fwork, k))
    cprev = ccol[pl.ds(k - 1, 1)][0]
    dprev = dcol[pl.ds(k - 1, 1)][0]
    divided = 1.0 / (bcol - cprev * acol)
    dlast = ((ld(rhs, k) + corr) - dprev * acol) * divided
    dcol[pl.ds(k, 1)] = dlast[None]

    # ---- backward substitution -> stage tendency, never leaving VMEM -------
    stage[pl.ds(nz - 1, 1)] = (DTR_STAGE * (dlast - ld(fwork, nz - 1)))[None]

    def bwd_body(m, datac):
        k = nz - 2 - m
        datac = dcol[pl.ds(k, 1)][0] - ccol[pl.ds(k, 1)][0] * datac
        stage[pl.ds(k, 1)] = (DTR_STAGE * (datac - ld(fwork, k)))[None]
        return datac

    jax.lax.fori_loop(0, nz - 1, bwd_body, dlast)

    # ---- point-wise explicit update (still in VMEM) ------------------------
    fup = fwork[...] + dt * stage[...]

    # ---- compound hdiff on the updated field -------------------------------
    # Both y and x shifts are periodic VMEM rolls over the working window;
    # at window edges whose wrap is not truly periodic this writes garbage
    # that stays within HALO rows/cols of the edge.
    def s(dj: int, di: int) -> jnp.ndarray:
        win = jnp.roll(fup, -dj, axis=1) if dj else fup
        return jnp.roll(win, -di, axis=2) if di else win

    def lap(dj: int, di: int) -> jnp.ndarray:
        # true-Laplacian sign (see kernels/hdiff/ref.py)
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

    return s(0, 0) - coeff * ((flx - flx_m) + (fly - fly_m))


def _fused_kernel(f_prev, f_cur, f_next,
                  w_prev, w_cur, w_next,
                  t_prev, t_cur, t_next,
                  s_prev, s_cur, s_next,
                  outf_ref, outs_ref,
                  fwork, wwork, rhs, ccol, dcol, stage,
                  *, nz: int, ty: int, dt: float, coeff: float):
    f32 = jnp.float32

    def asm(prev, cur, nxt):
        """Assemble the (nz, ty+4, nx) fp32 working window: cur plus a 2-row
        halo taken from the periodic prev/next windows."""
        return jnp.concatenate(
            [prev[0][:, -HALO:], cur[0], nxt[0][:, :HALO]],
            axis=1).astype(f32)

    fwork[...] = asm(f_prev, f_cur, f_next)
    wwork[...] = asm(w_prev, w_cur, w_next)
    # u_pos == u_stage == f in the dycore step, so the static part of the
    # tridiagonal RHS is precomputed once per window.
    rhs[...] = (DTR_STAGE * fwork[...] + asm(t_prev, t_cur, t_next)
                + asm(s_prev, s_cur, s_next))

    out = _window_step(fwork, wwork, rhs, ccol, dcol, stage,
                       nz=nz, dt=dt, coeff=coeff)
    outf_ref[0] = out[:, HALO:HALO + ty, :].astype(outf_ref.dtype)
    outs_ref[0] = stage[:, HALO:HALO + ty, :].astype(outs_ref.dtype)


def fused_dycore_pallas(f: jnp.ndarray, w: jnp.ndarray, utens: jnp.ndarray,
                        utens_stage: jnp.ndarray, *,
                        coeff: float = DEFAULT_COEFF, dt: float = 0.1,
                        ty: int = 8, interpret: bool = False):
    """Fused dycore field step.  All inputs (..., nz, ny, nx), doubly
    periodic in (y, x); `w` is the pre-combined staggered vertical velocity
    wcon_i + wcon_{i+1} (see module docstring).  ny % ty == 0, ty >= 2,
    nz >= 2.  Returns (f_new, stage) shaped/typed like `f`.
    """
    shape = f.shape
    nz, ny, nx = shape[-3:]
    if ny % ty or ty < 2:
        raise ValueError(f"ny={ny} must be divisible by ty={ty} >= 2")
    if nz < 2:
        raise ValueError(f"nz={nz} must be >= 2 (staggered vertical sweep)")
    nyb = ny // ty
    batch = math.prod(shape[:-3]) if len(shape) > 3 else 1

    spec = functools.partial(pl.BlockSpec, (1, nz, ty, nx))
    # Periodic overlapping windows: prev/next wrap modulo the window count.
    window = [
        spec(lambda b, j: (b, 0, (j + nyb - 1) % nyb, 0)),   # prev
        spec(lambda b, j: (b, 0, j, 0)),                     # cur
        spec(lambda b, j: (b, 0, (j + 1) % nyb, 0)),         # next
    ]
    out_spec = spec(lambda b, j: (b, 0, j, 0))

    kernel = functools.partial(_fused_kernel, nz=nz, ty=ty, dt=dt,
                               coeff=coeff)
    bshape = (batch, nz, ny, nx)
    scratch = pltpu.VMEM((nz, ty + 2 * HALO, nx), jnp.float32)
    fn = pl.pallas_call(
        kernel,
        grid=(batch, nyb),
        in_specs=window * 4,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct(bshape, f.dtype)] * 2,
        scratch_shapes=[scratch] * 6,   # fwork, wwork, rhs, ccol, dcol, stage
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="nero_dycore_fused",
    )
    args = []
    for a in (f, w, utens, utens_stage):
        a = a.reshape(bshape)
        args += [a, a, a]
    f_new, stage = fn(*args)
    return f_new.reshape(shape), stage.reshape(shape)


class _StackedLayout:
    """Validated geometry + BlockSpec pieces of the (batch, ny/ty, field)
    grid shared by the whole-state and k-step wrappers: per-field operands
    flattened to `batch*nf` with periodic prev/cur/next y-windows, the
    shared `w` keeping its un-stacked layout and a field-collapsing index
    map."""

    def __init__(self, fs: jnp.ndarray, w: jnp.ndarray, ty: int):
        shape = fs.shape
        if len(shape) < 4:
            raise ValueError(f"fs must be (..., nf, nz, ny, nx), got {shape}")
        nf, nz, ny, nx = shape[-4:]
        if ny % ty or ty < 2:
            raise ValueError(f"ny={ny} must be divisible by ty={ty} >= 2")
        if nz < 2:
            raise ValueError(f"nz={nz} must be >= 2 (staggered vertical "
                             f"sweep)")
        if w.shape[-3:] != (nz, ny, nx):
            raise ValueError(f"w shape {w.shape} != fields grid "
                             f"{(nz, ny, nx)}")
        self.nf, self.nz, self.ny, self.nx = nf, nz, ny, nx
        self.nyb = ny // ty
        batch = math.prod(shape[:-4]) if len(shape) > 4 else 1
        self.batch = batch
        self.grid = (batch, self.nyb, nf)
        self.fshape = (batch * nf, nz, ny, nx)
        self.wshape = (batch, nz, ny, nx)
        spec = functools.partial(pl.BlockSpec, (1, nz, ty, nx))
        nyb = self.nyb

        def fmap(dj: int):
            return lambda b, j, k: (b * nf + k, 0, (j + dj) % nyb, 0)

        def wmap(dj: int):
            # Shared operand: the field grid index k is collapsed — the
            # block index repeats across the nf innermost iterations, so
            # the slab is fetched once per (b, j).
            return lambda b, j, k: (b, 0, (j + dj) % nyb, 0)

        self.fwin = [spec(fmap(nyb - 1)), spec(fmap(0)), spec(fmap(1))]
        self.wwin = [spec(wmap(nyb - 1)), spec(wmap(0)), spec(wmap(1))]
        self.out_spec = spec(lambda b, j, k: (b * nf + k, 0, j, 0))


def fused_dycore_whole_state_pallas(fs: jnp.ndarray, w: jnp.ndarray,
                                    utens: jnp.ndarray,
                                    utens_stage: jnp.ndarray, *,
                                    coeff: float = DEFAULT_COEFF,
                                    dt: float = 0.1, ty: int = 8,
                                    interpret: bool = False):
    """Whole-state fused dycore step: ONE `pallas_call` for every prognostic
    field, sharing the staggered-velocity slab across fields.

    `fs`, `utens`, `utens_stage` are field-stacked `(..., nf, nz, ny, nx)`;
    `w` is the pre-combined staggered vertical velocity `(..., nz, ny, nx)`,
    identical for every field.  The grid is `(batch, ny/ty, nf)` with the
    field axis innermost and the per-field operands flattened to
    `batch*nf` so their index maps read `b*nf + k` — while `w` keeps its
    un-stacked layout and an index map that *ignores* `k`.  Consecutive
    field iterations therefore revisit the same `w` block index, and Pallas
    elides the re-fetch: each (ensemble, y-window) slab of `w` is DMA'd
    from HBM once per step instead of once per field (~1/(3+1/nf) of input
    traffic saved, 25% at nf→∞) on top of the nf× launch amortization.

    Returns `(f_new, stage)` shaped/typed like `fs`.
    """
    shape = fs.shape
    lay = _StackedLayout(fs, w, ty)

    kernel = functools.partial(_fused_kernel, nz=lay.nz, ty=ty, dt=dt,
                               coeff=coeff)
    scratch = pltpu.VMEM((lay.nz, ty + 2 * HALO, lay.nx), jnp.float32)
    fn = pl.pallas_call(
        kernel,
        grid=lay.grid,
        in_specs=lay.fwin + lay.wwin + lay.fwin + lay.fwin,
        out_specs=[lay.out_spec, lay.out_spec],
        out_shape=[jax.ShapeDtypeStruct(lay.fshape, fs.dtype)] * 2,
        scratch_shapes=[scratch] * 6,   # fwork, wwork, rhs, ccol, dcol, stage
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="nero_dycore_whole_state",
    )
    args = []
    for a, s in ((fs, lay.fshape), (w, lay.wshape), (utens, lay.fshape),
                 (utens_stage, lay.fshape)):
        a = a.reshape(s)
        args += [a, a, a]
    f_new, stage = fn(*args)
    return f_new.reshape(shape), stage.reshape(shape)


# ---------------------------------------------------------------------------
# k-step kernel: the whole communication-avoiding round in ONE launch.
# ---------------------------------------------------------------------------

def _kstep_body(outf_ref, outs_ref,
                fwork, wwork, twork, swork, rhs, ccol, dcol, stage,
                *, nz: int, ty: int, k_steps: int, dt: float, coeff: float):
    """Run the k-step time loop on the (nz, 3*ty, nx) working window already
    assembled into scratch.  Prognostic state (field + stage tendency) lives
    in `fwork`/`swork` between local steps — it never round-trips HBM.  Each
    step's in-window wrap garbage advances HALO rows per step from the window
    edges; `ty >= k_steps*HALO` keeps the central `ty` rows valid."""

    def body(_, carry):
        # u_pos == u_stage == f; the tridiagonal RHS is rebuilt each step
        # from the carried state and the constant slow tendency.
        rhs[...] = DTR_STAGE * fwork[...] + twork[...] + swork[...]
        out = _window_step(fwork, wwork, rhs, ccol, dcol, stage,
                           nz=nz, dt=dt, coeff=coeff)
        fwork[...] = out
        swork[...] = stage[...]
        return carry

    jax.lax.fori_loop(0, k_steps, body, 0)
    outf_ref[0] = fwork[:, ty:2 * ty, :].astype(outf_ref.dtype)
    outs_ref[0] = swork[:, ty:2 * ty, :].astype(outs_ref.dtype)


def _asm_full(prev, cur, nxt, dtype=jnp.float32):
    """Assemble the full (nz, 3*ty, nx) working window from three whole
    aliased windows (the k-step halo is up to ty deep per side)."""
    return jnp.concatenate([prev[0], cur[0], nxt[0]], axis=1).astype(dtype)


def _kstep_kernel_windows(f_prev, f_cur, f_next,
                          w_prev, w_cur, w_next,
                          t_prev, t_cur, t_next,
                          s_prev, s_cur, s_next,
                          outf_ref, outs_ref,
                          fwork, wwork, twork, swork, rhs, ccol, dcol, stage,
                          *, nz: int, ty: int, k_steps: int, dt: float,
                          coeff: float):
    """Interpreter-safe k-step kernel: `w` arrives as three aliased BlockSpec
    windows (index map collapses the field axis, so Pallas elides the
    re-fetch across the nf innermost iterations)."""
    fwork[...] = _asm_full(f_prev, f_cur, f_next)
    wwork[...] = _asm_full(w_prev, w_cur, w_next)
    twork[...] = _asm_full(t_prev, t_cur, t_next)
    swork[...] = _asm_full(s_prev, s_cur, s_next)
    _kstep_body(outf_ref, outs_ref, fwork, wwork, twork, swork, rhs, ccol,
                dcol, stage, nz=nz, ty=ty, k_steps=k_steps, dt=dt,
                coeff=coeff)


def _kstep_kernel_prefetch(f_prev, f_cur, f_next,
                           w_hbm,
                           t_prev, t_cur, t_next,
                           s_prev, s_cur, s_next,
                           outf_ref, outs_ref,
                           fwork, wwork, twork, swork, rhs, ccol, dcol,
                           stage, wbuf, wsem,
                           *, nz: int, ty: int, k_steps: int, dt: float,
                           coeff: float, nyb: int):
    """k-step kernel with explicit double-buffered `w` prefetch: `w` stays in
    HBM (`memory_space=ANY`) and is DMA'd by hand with `make_async_copy`.
    While window j iterates its nf fields and k local steps, window j+1's
    three `w` sections are already in flight into the other buffer slot, so
    the shared-slab fetch overlaps compute instead of serializing at the
    window boundary."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    kf = pl.program_id(2)

    def dma(slot, jj, sec):
        # Section `sec` (0/1/2 = prev/cur/next) of window jj, periodic in y.
        row = jax.lax.rem(jj + (sec - 1) + nyb, nyb) * ty
        return pltpu.make_async_copy(
            w_hbm.at[b, :, pl.ds(row, ty), :],
            wbuf.at[slot, :, pl.ds(sec * ty, ty), :],
            wsem.at[slot, sec])

    slot = jax.lax.rem(j, 2)

    @pl.when(kf == 0)
    def _fetch():
        # Warm-up: the first window of each batch row starts its own copies
        # (nothing was in flight for it).
        @pl.when(j == 0)
        def _warm():
            for sec in range(3):
                dma(0, 0, sec).start()
        for sec in range(3):
            dma(slot, j, sec).wait()
        # Prefetch the NEXT window's w into the other slot while this
        # window's nf fields x k steps compute.
        @pl.when(j + 1 < nyb)
        def _ahead():
            for sec in range(3):
                dma(jax.lax.rem(j + 1, 2), j + 1, sec).start()
        wwork[...] = wbuf[slot].astype(jnp.float32)

    fwork[...] = _asm_full(f_prev, f_cur, f_next)
    twork[...] = _asm_full(t_prev, t_cur, t_next)
    swork[...] = _asm_full(s_prev, s_cur, s_next)
    _kstep_body(outf_ref, outs_ref, fwork, wwork, twork, swork, rhs, ccol,
                dcol, stage, nz=nz, ty=ty, k_steps=k_steps, dt=dt,
                coeff=coeff)


def fused_dycore_kstep_pallas(fs: jnp.ndarray, w: jnp.ndarray,
                              utens: jnp.ndarray, utens_stage: jnp.ndarray,
                              *, k_steps: int, coeff: float = DEFAULT_COEFF,
                              dt: float = 0.1, ty: int = 8,
                              interpret: bool = False,
                              prefetch_w: bool | None = None):
    """The whole communication-avoiding round in ONE `pallas_call`: grid
    `(ensemble, ny/ty, field)`, and the kernel body runs the `k_steps` time
    loop internally (`lax.fori_loop` over Thomas solve + update + hdiff),
    so the prognostic state between local steps lives in VMEM scratch
    instead of round-tripping HBM k times.

    Shapes as `fused_dycore_whole_state_pallas`: `fs`/`utens`/`utens_stage`
    field-stacked `(..., nf, nz, ny, nx)`, shared staggered velocity `w`
    `(..., nz, ny, nx)`, doubly periodic in (y, x).  Each grid cell stages a
    3-window (`3*ty`-row) y-slab and shrinks its valid region by HALO per
    local step, so `ty >= k_steps * HALO` is required (the redundant
    halo-ring flops are the communication-avoiding price).

    `prefetch_w=True` (default outside interpret mode) streams the shared
    `w` slab with an explicit double-buffered `pltpu.make_async_copy`
    pipeline: window j+1's slab is DMA'd while window j computes.
    `prefetch_w=False` is the interpreter-safe fallback (three aliased
    BlockSpec windows with a field-collapsing index map, fetch elided
    across the field axis).  Both paths are bit-identical.

    Returns `(f_new, stage)` shaped/typed like `fs` — the state after
    `k_steps` timesteps and the last step's stage tendency.
    """
    shape = fs.shape
    if k_steps < 1:
        raise ValueError(f"k_steps={k_steps} must be >= 1")
    lay = _StackedLayout(fs, w, ty)
    if ty < k_steps * HALO:
        raise ValueError(
            f"ty={ty} must be >= k_steps*HALO={k_steps * HALO}: each local "
            f"step consumes a {HALO}-row ring of window validity")
    if prefetch_w is None:
        prefetch_w = not interpret
    nz, nx = lay.nz, lay.nx

    window = pltpu.VMEM((nz, 3 * ty, nx), jnp.float32)
    # fwork, wwork, twork, swork, rhs, ccol, dcol, stage
    scratch = [window] * 8
    if prefetch_w:
        kernel = functools.partial(_kstep_kernel_prefetch, nz=nz, ty=ty,
                                   k_steps=k_steps, dt=dt, coeff=coeff,
                                   nyb=lay.nyb)
        wspec = [pl.BlockSpec(memory_space=pl.ANY)]
        scratch = scratch + [pltpu.VMEM((2, nz, 3 * ty, nx), w.dtype),
                             pltpu.SemaphoreType.DMA((2, 3))]
    else:
        kernel = functools.partial(_kstep_kernel_windows, nz=nz, ty=ty,
                                   k_steps=k_steps, dt=dt, coeff=coeff)
        wspec = lay.wwin

    fn = pl.pallas_call(
        kernel,
        grid=lay.grid,
        in_specs=lay.fwin + wspec + lay.fwin + lay.fwin,
        out_specs=[lay.out_spec, lay.out_spec],
        out_shape=[jax.ShapeDtypeStruct(lay.fshape, fs.dtype)] * 2,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="nero_dycore_kstep",
    )
    args = [a.reshape(lay.fshape) for a in (fs, fs, fs)]
    wa = w.reshape(lay.wshape)
    args += [wa] if prefetch_w else [wa, wa, wa]
    for a in (utens, utens_stage):
        a = a.reshape(lay.fshape)
        args += [a, a, a]
    f_new, stage = fn(*args)
    return f_new.reshape(shape), stage.reshape(shape)

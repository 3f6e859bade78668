"""Jitted public entry points for the fused dycore step (planner-aware).

Two granularities:

* `fused_step(...)` — one prognostic field per call: builds the pre-combined
  staggered vertical velocity, picks the auto-tuned y-window (NERO's
  OpenTuner stage via core/autotune.py), and dispatches to the Pallas
  compound kernel — or to the unfused oracle composition when
  `use_pallas=False` (the differentiable fallback path).
* `fused_step_whole_state(...)` — ALL prognostic fields in ONE `pallas_call`:
  fields are stacked on a leading `nf` axis, the shared staggered-velocity
  slab is DMA'd once per (ensemble, y-window) instead of once per field, and
  the launch cost is amortized nf×.  Compiled `whole_state` plans launch
  the same kernel on their own stacked operands
  (`weather/stencil_ops.py::_dycore_stacked_round`).
* `fused_step_kstep(...)` — the whole k-step round in ONE `pallas_call`: the
  kernel body runs the k local steps internally, prognostic state between
  steps lives in VMEM scratch, and the shared `w` slab is double-buffer
  prefetched across y-windows (`kernels/dycore_fused/fused.py::
  fused_dycore_kstep_pallas`).  Compiled `kstep` plans, single-chip and
  distributed (the communication-avoiding mode), launch that kernel on
  their own stacked operands (`weather/stencil_ops.py`).

Both default `interpret=None`, resolved via `_auto_interpret()`: native
Pallas on TPU, interpreter everywhere else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import autotune, hierarchy as hw, tiling
from repro.kernels.dycore_fused import ref as _ref
from repro.kernels.dycore_fused.fused import (HALO,
                                              fused_dycore_kstep_pallas,
                                              fused_dycore_pallas,
                                              fused_dycore_whole_state_pallas)

DEFAULT_COEFF = _ref.DEFAULT_COEFF
DEFAULT_DT = _ref.DEFAULT_DT


def _auto_interpret() -> bool:
    """Pallas runs natively on TPU, in interpreter mode everywhere else."""
    return jax.default_backend() != "tpu"


def staggered_w(wcon: jnp.ndarray) -> jnp.ndarray:
    """The kernels' staggered vertical velocity `wcon_i + wcon_{i+1}`,
    periodic in x, from the unstaggered `wcon`."""
    return wcon + jnp.roll(wcon, -1, axis=-1)


def snap_ty(ty: int, ny: int, dtype=jnp.float32) -> int:
    """Legal y-window near `ty`: a sublane-aligned divisor of ny, or the
    whole extent (`tiling.snap_to_divisor`)."""
    return tiling.snap_to_divisor(ty, ny, align=tiling.sublanes(dtype),
                                  lo=2)


def plan_tile(grid_shape, dtype) -> int:
    """Auto-tuned y-window for the fused kernel (paper Fig. 6 stage)."""
    tuned = autotune.tune_named("dycore_fused", grid_shape, dtype)
    return snap_ty(tuned.plan.tile[1], grid_shape[1], dtype)


def snap_ty_kstep(ty: int, ny: int, k_steps: int, dtype=jnp.float32) -> int:
    """Legal k-step y-window: a sublane-aligned divisor of `ny` (or ny
    itself) that is at least `k_steps * HALO` (each local step consumes a
    HALO-deep ring of window validity) — `tiling.snap_to_divisor` with
    that floor."""
    lo = max(2, k_steps * HALO)
    if ny < lo:
        raise ValueError(
            f"ny={ny} < k_steps*HALO={lo}: no window can hold the k-step "
            f"validity front; use a bigger grid or a smaller k_steps")
    return tiling.snap_to_divisor(ty, ny, align=tiling.sublanes(dtype),
                                  lo=lo)


def plan_tile_kstep(grid_shape, dtype, n_fields: int, k_steps: int,
                    hier=None) -> int:
    """Auto-tuned y-window for the k-step kernel.

    The k-step tile space (`tiling.dycore_kstep_spec`) is far tighter than
    the whole-state one: every grid cell stages a 3-window working slab, all
    8 pipeline temporaries span it, and the double-buffered `w` prefetch
    adds two more padded buffers.  After the Pareto pick the window is
    snapped to a divisor of ny that clears the `ty >= k_steps*HALO`
    validity-front bound, and the snapped plan is re-checked against the
    VMEM budget — plans that do not fit the double buffer are rejected
    loudly instead of silently spilling."""
    hier = hier or hw.tpu_v5e()
    spec = tiling.dycore_kstep_spec(n_fields, k_steps)
    tuned = autotune.tune(spec, grid_shape, dtype, hier=hier)
    ty = snap_ty_kstep(tuned.plan.tile[1], grid_shape[1], k_steps, dtype)
    plan = tiling.TilePlan(op=spec, grid_shape=tuple(grid_shape),
                           tile=(grid_shape[0], ty, grid_shape[2]),
                           dtype=str(jnp.dtype(dtype)))
    if not plan.fits(hier):
        raise ValueError(
            f"k-step tile plan ty={ty} for grid={tuple(grid_shape)} "
            f"k_steps={k_steps} needs {plan.vmem_bytes / 2**20:.1f} MiB of "
            f"VMEM (3-window scratch + double-buffered w prefetch) but only "
            f"{hier.vmem.capacity_bytes / 2**20:.1f} MiB fit; use a smaller "
            f"k_steps or grid")
    return ty


def resolve_tile(variant: str, grid_shape, dtype, n_fields: int,
                 k_steps: int = 1, hier=None):
    """ONE tile resolver for every fused-dycore execution variant — the
    planner entry `weather/program.py::compile_dycore` calls instead of
    picking among the three `plan_tile*` paths itself.  Returns the
    auto-tuned, snapped y-window, or None for the unfused oracle (which
    has no Pallas tile to plan)."""
    if variant == "unfused":
        return None
    if variant == "per_field":
        return plan_tile(grid_shape, dtype)
    if variant == "whole_state":
        return plan_tile_whole_state(grid_shape, dtype, n_fields)
    if variant == "kstep":
        return plan_tile_kstep(grid_shape, dtype, n_fields, k_steps,
                               hier=hier)
    raise ValueError(f"unknown dycore variant {variant!r}")


def plan_tile_whole_state(grid_shape, dtype, n_fields: int) -> int:
    """Auto-tuned y-window for the whole-state kernel.

    The whole-state tile space differs from the per-field one: the shared
    `w` slab amortizes to 1/n_fields of input *traffic* but stays fully
    resident in VMEM alongside the per-field windows, so the legal tile set
    (and the Pareto pick) shifts with the field count.  The default
    (4-field) space lives in the autotune registry as
    "dycore_whole_state"; here the spec for the *actual* `n_fields` is
    built and tuned directly, leaving the registry untouched.
    """
    spec = tiling.dycore_whole_state_spec(n_fields)
    tuned = autotune.tune(spec, grid_shape, dtype)
    return snap_ty(tuned.plan.tile[1], grid_shape[1], dtype)


@functools.partial(jax.jit, static_argnames=("coeff", "dt", "use_pallas",
                                             "ty", "interpret"))
def fused_step(f: jnp.ndarray, wcon: jnp.ndarray, utens: jnp.ndarray,
               utens_stage: jnp.ndarray, coeff: float = DEFAULT_COEFF,
               dt: float = DEFAULT_DT, use_pallas: bool = True, ty: int = 0,
               interpret: bool | None = None):
    """One fused dycore field step on a doubly-periodic (..., nz, ny, nx)
    domain.  `wcon` is the unstaggered vertical velocity; the kernel's
    staggered neighbor is the periodic next x-column.  Returns
    (f_new, stage)."""
    if not use_pallas:
        return _ref.fused_step_ref_batched(f, wcon, utens, utens_stage,
                                           coeff=coeff, dt=dt)
    if interpret is None:
        interpret = _auto_interpret()
    ny = f.shape[-2]
    ty = snap_ty(ty, ny, f.dtype) if ty else plan_tile(f.shape[-3:], f.dtype)
    w = staggered_w(wcon)
    return fused_dycore_pallas(f, w, utens, utens_stage, coeff=coeff, dt=dt,
                               ty=ty, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("coeff", "dt", "use_pallas",
                                             "ty", "interpret"))
def fused_step_whole_state(fs: jnp.ndarray, wcon: jnp.ndarray,
                           utens: jnp.ndarray, utens_stage: jnp.ndarray,
                           coeff: float = DEFAULT_COEFF,
                           dt: float = DEFAULT_DT, use_pallas: bool = True,
                           ty: int = 0, interpret: bool | None = None):
    """Whole-state fused dycore step: `fs`/`utens`/`utens_stage` are
    field-stacked (..., nf, nz, ny, nx); `wcon` is the shared unstaggered
    vertical velocity (..., nz, ny, nx).  One `pallas_call` covers every
    field; see `fused_dycore_whole_state_pallas`.  Returns (f_new, stage)
    shaped like `fs`."""
    if not use_pallas:
        wb = jnp.broadcast_to(jnp.expand_dims(wcon, -4), fs.shape)
        return _ref.fused_step_ref_batched(fs, wb, utens, utens_stage,
                                           coeff=coeff, dt=dt)
    if interpret is None:
        interpret = _auto_interpret()
    nf, _, ny, _ = fs.shape[-4:]
    ty = (snap_ty(ty, ny, fs.dtype) if ty
          else plan_tile_whole_state(fs.shape[-3:], fs.dtype, nf))
    w = staggered_w(wcon)
    return fused_dycore_whole_state_pallas(fs, w, utens, utens_stage,
                                           coeff=coeff, dt=dt, ty=ty,
                                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k_steps", "coeff", "dt", "ty",
                                             "interpret", "prefetch_w"))
def fused_step_kstep(fs: jnp.ndarray, wcon: jnp.ndarray,
                     utens: jnp.ndarray, utens_stage: jnp.ndarray,
                     k_steps: int = 2, coeff: float = DEFAULT_COEFF,
                     dt: float = DEFAULT_DT, ty: int = 0,
                     interpret: bool | None = None,
                     prefetch_w: bool | None = None):
    """Advance the whole stacked state `k_steps` timesteps in ONE
    `pallas_call` (`fused_dycore_kstep_pallas`): the k-step time loop runs
    inside the kernel, state between local steps stays in VMEM, and the
    shared staggered-velocity slab is double-buffer-prefetched across
    y-windows (`prefetch_w`, default on outside interpret mode).

    Shapes as `fused_step_whole_state`; doubly periodic domain.  Returns
    `(f_new, stage)` after `k_steps` steps."""
    if interpret is None:
        interpret = _auto_interpret()
    nf, _, ny, _ = fs.shape[-4:]
    ty = (snap_ty_kstep(ty, ny, k_steps, fs.dtype) if ty
          else plan_tile_kstep(fs.shape[-3:], fs.dtype, nf, k_steps))
    w = staggered_w(wcon)
    return fused_dycore_kstep_pallas(fs, w, utens, utens_stage,
                                     k_steps=k_steps, coeff=coeff, dt=dt,
                                     ty=ty, interpret=interpret,
                                     prefetch_w=prefetch_w)

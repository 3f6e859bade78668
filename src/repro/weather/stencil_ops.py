"""The StencilOp registry: declared operators the planner compiles.

NERO evaluates its two compound kernels SEPARATELY — vadvc (5.3x, 1.61
GFLOPS/W) and hdiff (12.7x, 21.01 GFLOPS/W) — and the per-kernel contrast
(hdiff's star footprint vs vadvc's tridiagonal z-sweep) is the paper's core
result.  The PR-4 plan API was hardwired to the single fused vadvc+hdiff
dycore; this module turns it into a platform: each operator is a
`StencilOpDef` declaring

* which state operands it streams (`reads`/`writes`),
* its per-operand, PER-SIDE halo footprint (`OperandRide`: `(lo, hi)`
  depths in y and x per local step, plus k-independent fixed columns like
  wcon's right-only staggering `+1`),
* its stencil reach (`halo`, the per-step validity shrink), flop count,
  supported execution variants, and tile search spaces (names in the
  `core/autotune` registry),
* its lowerings: tile resolution, the single-chip step, and the
  shard-local compute the distributed round wraps.

`weather/program.py::compile` consumes ONLY this declaration: the exchange
schedule, collective/launch counts, traffic and k-step models are all
derived from the footprint — no op-specific branches in the planner.
Registered out of the box:

  "dycore"       — the fused compound step (vadvc + point-wise + hdiff),
                   with the in-kernel k-step round;
  "hdiff"        — compound horizontal diffusion alone (fields only,
                   (2,2)/(2,2) footprint; the k-step round is ONE
                   `hdiff_kstep_pallas` launch on a k·2-deep halo);
  "vadvc"        — vertical advection alone (updates the stage tendencies;
                   the only exchanged operand is wcon's RIGHT staggering
                   column, a `(0, 1)` x-ride that lowers to ONE ppermute);
  "vadvc_update" — the paper's ablation composition: vadvc fused with the
                   point-wise leapfrog update (writes fields AND
                   stage_tens; no hdiff);
  "hadv_upwind"  — first-order upwind horizontal advection; its donor-cell
                   stencil reaches BACKWARD only, so its rides are
                   asymmetric ((1,0) in y and x);
  "asselin"      — point-wise leapfrog time filter from the stored
                   tendencies: zero rides, zero collectives (exercises the
                   empty-direction elision path end to end).

`register_stencil_op` admits new operators without touching the planner.
Ops that additionally provide `apply_stage` can ride inside a
`weather/pipeline.py::PipelineProgram`: the hook returns the op's
FULL-SLAB stage function (no exchange, no crop — the pipeline planner owns
both), which is how a chain keeps intermediates resident between stages.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import autotune, memmodel, tiling
from repro.kernels.dycore_fused import ops as fused_ops
from repro.kernels.dycore_fused.fused import (fused_dycore_kstep_pallas,
                                              fused_dycore_pallas,
                                              fused_dycore_whole_state_pallas)
from repro.kernels.hadv import ops as hadv_ops
from repro.kernels.hadv import ref as hadv_ref
from repro.kernels.hadv.hadv import hadv_pallas
from repro.kernels.hdiff import ops as hdiff_ops
from repro.kernels.hdiff import ref as hdiff_ref
from repro.kernels.hdiff.hdiff import hdiff_kstep_pallas, hdiff_pallas
from repro.kernels.vadvc import ops as vadvc_ops
from repro.kernels.vadvc import ref as vadvc_ref
from repro.kernels.vadvc.vadvc import vadvc_pallas
from repro.weather import domain as _domain
from repro.weather import dycore as _dycore
from repro.weather.dycore import HALO
from repro.weather.fields import WeatherState

VARIANTS = ("auto", "unfused", "per_field", "whole_state", "kstep")


def jit_round(op: str, suffix: str = "round"):
    """`jax.jit` as a decorator that names the compiled module
    `jit_<op>_<suffix>` (the op's name with its punctuation folded to
    `_`), so a profile's XLA Modules line tells a plan's rounds apart from
    the serving engine's guard and admission modules."""
    name = re.sub(r"\W+", "_", op).strip("_") + "_" + suffix

    def wrap(fn):
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn)
    return wrap


@dataclasses.dataclass(frozen=True)
class OperandRide:
    """One operand's declared halo footprint on the packed exchange wire.

    Per mesh direction the resolved per-side depth at steps-per-round k is
    `k * base + fixed`: `y`/`x` are the `(lo, hi)` PER-STEP reaches that
    deepen with the communication-avoiding k, `y_fixed`/`x_fixed` the
    k-independent extra rows/columns (e.g. wcon's right-only staggering
    column `x_fixed=(0, 1)`).  `per_field` operands ride once per program
    field; others (wcon) once per state."""

    operand: str
    y: Tuple[int, int] = (0, 0)
    x: Tuple[int, int] = (0, 0)
    y_fixed: Tuple[int, int] = (0, 0)
    x_fixed: Tuple[int, int] = (0, 0)
    per_field: bool = False

    def depths(self, k: int):
        """Resolved ((y_lo, y_hi), (x_lo, x_hi)) at steps-per-round `k`."""
        return ((k * self.y[0] + self.y_fixed[0],
                 k * self.y[1] + self.y_fixed[1]),
                (k * self.x[0] + self.x_fixed[0],
                 k * self.x[1] + self.x_fixed[1]))

    def describe(self, k: int) -> Dict[str, Any]:
        dy, dx = self.depths(k)
        return {"operand": self.operand, "per_field": self.per_field,
                "depth_y": list(dy), "depth_x": list(dx)}


@dataclasses.dataclass(frozen=True)
class StackedRound:
    """A single-chip round split at the kernel's own operand layout.

    `pack(state) -> (carry, consts)` builds the kernel's operands: the
    carry it rewrites every round and the loop invariants it only reads;
    `step(carry, consts) -> carry` is the bare kernel launch;
    `unpack(carry, state) -> WeatherState` lays the carry back out as the
    state.  `pack`, then `step`, then `unpack` is exactly one `plan.step`."""

    pack: Callable
    step: Callable
    unpack: Callable


@dataclasses.dataclass(frozen=True)
class StencilOpDef:
    """A registered stencil operator: footprint declaration + lowerings.

    The declaration part (`reads`/`writes`/`halo`/`flops_per_point`/
    `rides`/`variants`/`tile_spaces`) is what the planner and the models
    consume; the callables are the op's lowerings:

    * `resolve_tile(variant, compute_grid, dtype, n_fields, ensemble, k)`
      -> Optional[tiling.TilePlan] (None for the oracle variants);
    * `build_shard_local(plan)` -> `(fields, wcon, tens, stage) ->
      (new_fields, new_stage)`, the chip-local round the distributed step
      shard_maps (and, for ops with `pads_single_chip`, the single-chip
      step too — the packed exchange degenerates to wrap padding);
    * `build_local_step(plan)` -> jitted `state -> state`, or None to
      derive it from `build_shard_local` on a 1x1 "mesh";
    * `collectives(variant, n_fields, py, px, k)` -> ppermutes per round,
      or None to derive generically from the rides (a collective per mesh
      direction and side anything rides);
    * `traffic(plan)` / `exchange_model(plan)` -> the report()'s modeled
      HBM / wire-byte blocks;
    * `apply_stage(prog, names, interpret, use_ref)` -> the op's FULL-SLAB
      stage function `(fields, wconp, tens, stage_tens) -> (new_fields,
      new_stage_tens)` for pipeline chaining (`weather/pipeline.py`): all
      dict values are padded slabs, `names` the stage's bound fields, and
      the op must neither exchange nor crop — the pipeline planner owns
      the fused exchange and the final interior crop.  None => the op
      cannot ride in a pipeline;
    * `kstep_vmem_check(program, shards)` -> per-k legality callable for
      `autotune.resolve_k_steps` — ops with their OWN in-kernel k-step
      round (not the fused dycore's) declare how a candidate k's working
      slab is checked;
    * `stacked_round(plan)` -> a `StackedRound` for a single-chip plan
      whose kernel takes field-stacked operands, or None: `run()`'s round
      scan then carries the state in the kernel's layout and stacks once
      per call instead of twice per round.
    """

    name: str
    title: str
    reads: Tuple[str, ...]
    writes: Tuple[str, ...]
    halo: int                                # per-step stencil reach (y, x)
    flops_per_point: float                   # per field per step
    rides: Tuple[OperandRide, ...]
    variants: Tuple[str, ...]
    tile_spaces: Tuple[Tuple[str, str], ...]  # (variant, autotune op name)
    inkernel_kstep: bool = False             # k-step round is ONE launch
    pads_single_chip: bool = False           # single chip wrap-pads + crops
    packed_variants: Tuple[str, ...] = ()    # variants on the packed wire
    resolve_tile: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False)
    build_shard_local: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False)
    build_local_step: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False)
    pallas_calls: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False)
    collectives: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False)
    traffic: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False)
    exchange_model: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False)
    apply_stage: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False)
    kstep_vmem_check: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False)
    stacked_round: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False)

    # -- footprint-derived accounting ---------------------------------------
    def resolved_rides(self, k: int):
        """((operand, (y_lo, y_hi), (x_lo, x_hi)), ...) at depth k."""
        return tuple((r.operand,) + r.depths(k) for r in self.rides)

    def memmodel_rides(self, n_fields: int):
        """The rides in `memmodel.packed_exchange_model` form."""
        return tuple((r.operand, n_fields if r.per_field else 1,
                      r.y, r.x, r.y_fixed, r.x_fixed) for r in self.rides)

    def generic_collectives(self, py: int, px: int, k: int) -> int:
        """Collectives per packed round, derived from the footprint: one
        ppermute per mesh direction and SIDE any operand rides (a side
        nothing rides is elided by `domain._exchange_packed`)."""
        total = 0
        for axis, n in (("y", py), ("x", px)):
            if n <= 1:
                continue
            lo = hi = False
            for r in self.rides:
                dy, dx = r.depths(k)
                d = dy if axis == "y" else dx
                lo |= d[0] > 0
                hi |= d[1] > 0
            total += int(lo) + int(hi)
        return total

    def describe(self, n_fields: int = 4, k: int = 1) -> Dict[str, Any]:
        """JSON footprint declaration — `plan.report()["footprint"]` and
        the docs/kernels.md StencilOpDef table."""
        return {"op": self.name,
                "reads": list(self.reads),
                "writes": list(self.writes),
                "halo": self.halo,
                "flops_per_point": self.flops_per_point,
                "rides": [r.describe(k) for r in self.rides],
                "variants": list(self.variants),
                "inkernel_kstep": self.inkernel_kstep}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

STENCIL_OPS: Dict[str, StencilOpDef] = {}


def register_stencil_op(op: StencilOpDef) -> StencilOpDef:
    """Add (or replace) a stencil operator; returns it for chaining."""
    STENCIL_OPS[op.name] = op
    return op


def get_stencil_op(name: str) -> StencilOpDef:
    try:
        return STENCIL_OPS[name]
    except KeyError:
        raise KeyError(f"unknown stencil op {name!r}; registered: "
                       f"{sorted(STENCIL_OPS)}") from None


def registered_stencil_ops() -> Tuple[str, ...]:
    return tuple(sorted(STENCIL_OPS))


# ---------------------------------------------------------------------------
# "dycore" — the fused compound step (the PR-1..4 tentpole kernels)
# ---------------------------------------------------------------------------


def _dycore_resolve_tile(variant, compute_grid, dtype, n_fields, ensemble,
                         k):
    ty = fused_ops.resolve_tile(variant, compute_grid, dtype, n_fields, k)
    if ty is None:
        return None
    spec = {"per_field": tiling.DYCORE_FUSED,
            "whole_state": tiling.dycore_whole_state_spec(n_fields),
            "kstep": tiling.dycore_kstep_spec(n_fields, k)}[variant]
    return tiling.TilePlan(op=spec, grid_shape=tuple(compute_grid),
                           tile=(compute_grid[0], ty, compute_grid[2]),
                           dtype=str(jnp.dtype(dtype)))


def _dycore_local_step(plan):
    """Single-chip lowering: the periodic-domain kernels at the plan's
    resolved tile/precision/interpret settings.  Every variant is wrapped
    in ONE jax.jit so a round is a single dispatch (the per-field loop, or
    the stacked round's pack, launch and unpack, trace into the same
    computation)."""
    prog = plan.program
    names, coeff, dt = prog.fields, prog.coeff, prog.dt
    variant, interp = plan.variant, plan.interpret
    ty = plan.tile_ty

    if variant == "unfused":
        @jit_round(prog.op)
        def step(state: WeatherState) -> WeatherState:
            new_fields, new_stage = {}, {}
            for name in names:
                f = state.fields[name]
                stage = _dycore.vadvc_field(
                    u_stage=f, wcon=state.wcon, u_pos=f,
                    utens=state.tens[name],
                    utens_stage=state.stage_tens[name])
                f = f + dt * stage
                f = _dycore.hdiff_periodic(f, coeff)
                new_fields[name] = f
                new_stage[name] = stage
            return WeatherState(fields=new_fields, wcon=state.wcon,
                                tens=state.tens, stage_tens=new_stage)
        return step

    if variant == "per_field":
        @jit_round(prog.op)
        def step(state: WeatherState) -> WeatherState:
            new_fields, new_stage = {}, {}
            for name in names:
                f_new, stage = fused_ops.fused_step(
                    state.fields[name], state.wcon, state.tens[name],
                    state.stage_tens[name], coeff=coeff, dt=dt, ty=ty,
                    interpret=interp)
                new_fields[name] = f_new
                new_stage[name] = stage
            return WeatherState(fields=new_fields, wcon=state.wcon,
                                tens=state.tens, stage_tens=new_stage)
        return step

    split = _dycore_stacked_round(plan)

    @jit_round(prog.op)
    def step(state: WeatherState) -> WeatherState:
        carry, consts = split.pack(state)
        return split.unpack(split.step(carry, consts), state)
    return step


def _dycore_stacked_round(plan):
    """The whole-state and k-step rounds split at the kernel's layout: the
    carry is the field-stacked `(fields, stage_tens)` pair, the invariants
    the stacked slow tendencies and the staggered velocity
    `w = wcon + wcon[x+1]` (periodic).  None for the variants that launch
    per field or run the oracle."""
    prog = plan.program
    if plan.variant not in ("whole_state", "kstep"):
        return None
    names, coeff, dt = prog.fields, prog.coeff, prog.dt
    interp, k = plan.interpret, plan.k_steps
    ny, dtype = prog.grid_shape[1], jnp.dtype(prog.dtype)
    stack = lambda d: _dycore.stack_state(d, names)
    unstack = lambda a: _dycore.unstack_state(a, names)

    def pack(state: WeatherState):
        w = fused_ops.staggered_w(state.wcon)
        return ((stack(state.fields), stack(state.stage_tens)),
                (stack(state.tens), w))

    if plan.variant == "whole_state":
        launch = functools.partial(
            fused_dycore_whole_state_pallas,
            ty=fused_ops.snap_ty(plan.tile_ty, ny, dtype))
    else:
        launch = functools.partial(
            fused_dycore_kstep_pallas, k_steps=k,
            ty=fused_ops.snap_ty_kstep(plan.tile_ty, ny, k, dtype),
            prefetch_w=plan.prefetch_w)

    def step(carry, consts):
        (fs, ss), (ts, w) = carry, consts
        return launch(fs, w, ts, ss, coeff=coeff, dt=dt, interpret=interp)

    def unpack(carry, state: WeatherState) -> WeatherState:
        fs, ss = carry
        return WeatherState(fields=unstack(fs), wcon=state.wcon,
                            tens=state.tens, stage_tens=unstack(ss))
    return StackedRound(pack=pack, step=step, unpack=unpack)


def _dycore_shard_local(plan):
    """Chip-local round of the distributed dycore: exchange (per the
    plan's schedule) + local kernel + interior crop — the function
    `program._build_distributed_step` shard_maps.  See `weather/domain.py`
    for the exchange primitives and the design rationale."""
    prog = plan.program
    ax_e, ax_y, ax_x = plan.mesh_axes
    names, nf = prog.fields, prog.n_fields
    coeff, dt, halo = prog.coeff, prog.dt, HALO
    k, ty, interp = plan.k_steps, plan.tile_ty, plan.interpret
    py, px = plan.shards

    def local_step_unfused(fields, wcon, tens, stage_tens):
        new_fields, new_stage = {}, {}
        for name in names:
            f = fields[name]
            stage = _domain._local_vadvc(f, wcon, f, tens[name],
                                         stage_tens[name], ax_x, px)
            f = f + dt * stage
            f = _domain._local_hdiff(f, coeff, ax_y, ax_x, py, px)
            new_fields[name] = f
            new_stage[name] = stage
        return new_fields, new_stage

    def local_step_per_field(fields, wcon, tens, stage_tens):
        e, nz, ly, lx = wcon.shape

        def pad(a):
            a = _domain._exchange(a, ax_y, py, halo, dim=2)
            return _domain._exchange(a, ax_x, px, halo, dim=3)

        # One exchange of the pre-combined staggered velocity serves all
        # fields; the per-field inputs are exchanged so the halo ring's
        # vadvc tendency is recomputed locally.
        wp = pad(_domain._staggered_w(wcon, ax_x, px))
        crop = lambda a: a[:, :, halo:halo + ly, halo:halo + lx]
        new_fields, new_stage = {}, {}
        for name in names:
            f_new, stage = fused_dycore_pallas(
                pad(fields[name]), wp, pad(tens[name]),
                pad(stage_tens[name]), coeff=coeff, dt=dt, ty=ty,
                interpret=interp)
            new_fields[name] = crop(f_new)
            new_stage[name] = crop(stage)
        return new_fields, new_stage

    def local_step_packed(fields, wcon, tens, stage_tens):
        e, nz, ly, lx = wcon.shape
        sched = plan.exchange
        hy, hx = sched.depth_y, sched.depth_x
        # ONE packed exchange per direction covers every operand: fields,
        # slow tendencies, stage tendencies at the k-step stencil reach and
        # raw wcon at its own RAGGED depth — the +1 staggering column
        # (w[c] = wcon[c] + wcon[c+1]) comes from the RIGHT neighbor only,
        # so wcon's x-ride is (hx, hx+1), not a symmetric hx+1.
        stacked = jnp.stack(
            [fields[n] for n in names]
            + [tens[n] for n in names]
            + [stage_tens[n] for n in names], axis=1)
        stacked, wconp = _domain._exchange_packed(
            [(stacked, hy), (wcon, hy)], ax_y, py, dim=-2,
            wire_dtype=sched.wire_dtype)
        stacked, wconp = _domain._exchange_packed(
            [(stacked, hx), (wconp, sched.wcon_depth_x)], ax_x, px, dim=-1,
            wire_dtype=sched.wire_dtype)
        # Whole 128-lane tiles: the k-step kernel's `w` prefetch DMAs
        # whole rows, which Mosaic only slices lane-aligned.  The extra
        # high-x columns are edge copies the crop below never reads.
        xw = -(-stacked.shape[-1] // tiling.LANES) * tiling.LANES
        lane = lambda a, w: jnp.pad(
            a, [(0, 0)] * (a.ndim - 1) + [(0, w - a.shape[-1])], mode="edge")
        stacked, wconp = lane(stacked, xw), lane(wconp, xw + 1)
        fs, ts, ss = (stacked[:, :nf], stacked[:, nf:2 * nf],
                      stacked[:, 2 * nf:])
        # Staggered velocity on the padded slab — valid everywhere: the
        # right-only extra wcon column supplies the outermost neighbor.
        w = wconp[..., :-1] + wconp[..., 1:]

        if k == 1:
            fs, ss = fused_dycore_whole_state_pallas(
                fs, w, ts, ss, coeff=coeff, dt=dt, ty=ty, interpret=interp)
        else:
            # The WHOLE round in one launch: the kernel iterates the k
            # local steps with state held in VMEM (no scan of launches,
            # no HBM state round-trips between steps).
            fs, ss = fused_dycore_kstep_pallas(
                fs, w, ts, ss, k_steps=k, coeff=coeff, dt=dt, ty=ty,
                interpret=interp, prefetch_w=plan.prefetch_w)
        crop = lambda a: a[..., hy:hy + ly, hx:hx + lx]
        new_fields = {n: crop(fs[:, i]) for i, n in enumerate(names)}
        new_stage = {n: crop(ss[:, i]) for i, n in enumerate(names)}
        return new_fields, new_stage

    return {"unfused": local_step_unfused,
            "per_field": local_step_per_field,
            "whole_state": local_step_packed,
            "kstep": local_step_packed}[plan.variant]


def _dycore_collectives(variant, n_fields, py, px, k):
    if variant in ("whole_state", "kstep"):
        return None          # derive from the rides (one pair per direction)
    ey = 2 if py > 1 else 0  # one ppermute pair per active direction
    ex = 2 if px > 1 else 0
    rc = 1 if px > 1 else 0  # wcon's right-column fetch
    if variant == "per_field":
        # shared staggered-w pad + 3 per-operand pads per field
        return rc + (ey + ex) + n_fields * 3 * (ey + ex)
    # unfused: per-field vadvc + hdiff pads
    return n_fields * (rc + ey + ex)


def _dycore_traffic(plan, model_ty):
    prog = plan.program
    return memmodel.dycore_step_traffic(
        prog.grid_shape, prog.dtype, n_fields=prog.n_fields, ty=model_ty,
        k_steps=plan.k_steps)


def _dycore_exchange_model(plan):
    prog = plan.program
    return memmodel.kstep_exchange_model(
        prog.grid_shape, prog.dtype, n_fields=prog.n_fields,
        k=plan.k_steps, shards=plan.exchange.shards, halo=HALO,
        exchange_dtype=prog.exchange_dtype)


register_stencil_op(StencilOpDef(
    name="dycore",
    title="fused compound dycore step (vadvc + point-wise + hdiff)",
    reads=("fields", "wcon", "tens", "stage_tens"),
    writes=("fields", "stage_tens"),
    halo=HALO,
    flops_per_point=tiling.DYCORE_FUSED.flops_per_point,
    rides=(OperandRide("fields", y=(HALO, HALO), x=(HALO, HALO),
                       per_field=True),
           OperandRide("tens", y=(HALO, HALO), x=(HALO, HALO),
                       per_field=True),
           OperandRide("stage_tens", y=(HALO, HALO), x=(HALO, HALO),
                       per_field=True),
           OperandRide("wcon", y=(HALO, HALO), x=(HALO, HALO),
                       x_fixed=(0, 1))),
    variants=("unfused", "per_field", "whole_state", "kstep"),
    tile_spaces=(("per_field", "dycore_fused"),
                 ("whole_state", "dycore_whole_state"),
                 ("kstep", "dycore_kstep")),
    inkernel_kstep=True,
    pads_single_chip=False,
    packed_variants=("whole_state", "kstep"),
    resolve_tile=_dycore_resolve_tile,
    build_shard_local=_dycore_shard_local,
    build_local_step=_dycore_local_step,
    pallas_calls=lambda variant, nf, k: {"unfused": 0, "per_field": nf,
                                         "whole_state": 1, "kstep": 1}[
                                             variant],
    collectives=_dycore_collectives,
    traffic=_dycore_traffic,
    exchange_model=_dycore_exchange_model,
    stacked_round=_dycore_stacked_round,
))


# ---------------------------------------------------------------------------
# "hdiff" — compound horizontal diffusion alone (paper: 12.7x, 21.01 GF/W)
# ---------------------------------------------------------------------------


def _hdiff_resolve_tile(variant, compute_grid, dtype, n_fields, ensemble,
                        k):
    if variant == "unfused":
        return None
    return hdiff_ops.resolve_tile(compute_grid, dtype)


def _hdiff_kstep_ty(Y: int, ty: int, k: int, dtype) -> int:
    """The in-kernel k-step window: a legal window of the slab height `Y`
    (`tiling.snap_to_divisor`) with at least `max(2, 2k)` rows — each
    in-slab step shrinks the window's valid interior by 2 rows per side,
    so smaller windows would self-corrupt before the round ends.  `Y` is
    always a legal fallback (the deep-ride compile check keeps
    `Y = ly + 4k > 2k`)."""
    return tiling.snap_to_divisor(ty, Y, align=tiling.sublanes(dtype),
                                  lo=max(2, 2 * k))


def _hdiff_kstep_vmem_check(program, shards):
    """Per-k legality for `autotune.resolve_k_steps`: the k-step round
    must find a legal tuned window on the k·2-padded local slab."""
    nz, ny, nx = program.grid_shape
    py, px = shards

    def check(kk):
        hdiff_ops.resolve_tile(
            (nz, ny // py + 4 * kk, nx // px + 4 * kk), program.dtype)
    return check


def _hdiff_shard_local(plan):
    """Chip-local hdiff round, ALL variants: ONE packed exchange per
    direction at the k-scaled footprint depth, then the local compute —
    oracle / one launch per field / one launch for the whole state (the
    fully-z-parallel stencil folds (ensemble, field, z) into the kernel's
    batch axis) / ONE `hdiff_kstep_pallas` launch that iterates the k
    local steps with the slab held in VMEM (validity shrinks HALO per
    in-slab step; the crop keeps the k-step-valid interior) — and the
    interior crop.  With 1 shard the exchange degenerates to periodic
    wrap-padding, so this same lowering IS the single-chip step."""
    prog = plan.program
    names = prog.fields
    coeff, variant, interp = prog.coeff, plan.variant, plan.interpret
    k = plan.k_steps
    ty = plan.tile_ty
    _, ax_y, ax_x = plan.mesh_axes
    py, px = plan.shards
    (_, (hy_lo, hy_hi), (hx_lo, hx_hi)), = plan.rides
    wire = prog.exchange_dtype

    def local(fields, wcon, tens, stage_tens):
        fs = _dycore.stack_state(fields, names)   # (e, nf, nz, ly, lx)
        e, nf, nz, ly, lx = fs.shape
        (fs,) = _domain._exchange_packed([(fs, (hy_lo, hy_hi))], ax_y, py,
                                         dim=-2, wire_dtype=wire)
        (fs,) = _domain._exchange_packed([(fs, (hx_lo, hx_hi))], ax_x, px,
                                         dim=-1, wire_dtype=wire)
        Y, X = fs.shape[-2:]

        def one_launch(a):
            """One hdiff_pallas launch over a (..., nz, Y, X) stack."""
            out = hdiff_pallas(a.reshape(-1, Y, X), coeff=coeff, ty=ty,
                               interpret=interp)
            return out.reshape(a.shape)

        if variant == "unfused":
            fs = hdiff_ref.hdiff(fs.reshape(-1, Y, X),
                                 coeff=coeff).reshape(fs.shape)
        elif variant == "per_field":
            fs = jnp.concatenate([one_launch(fs[:, i:i + 1])
                                  for i in range(nf)], axis=1)
        elif k == 1:   # whole_state
            fs = one_launch(fs)
        else:
            # kstep: the WHOLE round in ONE launch (ROADMAP item 2) — the
            # kernel iterates the k local steps with each window's slab
            # held in VMEM, matching the dycore's one-launch-per-round
            # contract.  Bit-equal to k sequential launches: every step
            # round-trips through the storage dtype in-kernel.
            out = hdiff_kstep_pallas(fs.reshape(-1, Y, X), coeff=coeff,
                                     ty=_hdiff_kstep_ty(Y, ty, k, fs.dtype),
                                     k_steps=k, interpret=interp)
            fs = out.reshape(fs.shape)
        out = fs[..., hy_lo:hy_lo + ly, hx_lo:hx_lo + lx]
        new_fields = {n: out[:, i] for i, n in enumerate(names)}
        return new_fields, dict(stage_tens)
    return local


def _hdiff_traffic(plan, model_ty):
    prog = plan.program
    nz, ny, nx = prog.grid_shape
    # model_ty may have been resolved on a padded/folded grid (distributed
    # or unfused plans); the traffic model runs on the physical grid, so
    # snap to a legal window of it.
    tile = (1, tiling.snap_to_divisor(model_ty, ny,
                                      align=tiling.sublanes(prog.dtype)),
            nx)
    return memmodel.stencil_op_traffic(
        autotune.get_op("hdiff"), prog.grid_shape, prog.dtype,
        n_fields=prog.n_fields, tile=tile, k_steps=plan.k_steps)


def _hdiff_apply_stage(prog, names, interpret, use_ref):
    """Full-slab hdiff stage for pipeline chaining: the bound fields fold
    into the kernel's batch axis; the window is re-tuned on the ACTUAL
    slab (merged pipeline rides make it wider than the solo compute grid
    — harmless, the kernel is bitwise tile-invariant)."""
    coeff = prog.coeff

    def fn(fields, wconp, tens, stage_tens):
        fs = jnp.stack([fields[n] for n in names], axis=1)
        e, nb, nz, Y, X = fs.shape
        if use_ref:
            out = hdiff_ref.hdiff(fs.reshape(-1, Y, X), coeff=coeff)
        else:
            ty = hdiff_ops.plan_tile((e * nb * nz, Y, X), fs.dtype)
            out = hdiff_pallas(fs.reshape(-1, Y, X), coeff=coeff, ty=ty,
                               interpret=interpret)
        out = out.reshape(fs.shape)
        new_fields = dict(fields)
        for i, n in enumerate(names):
            new_fields[n] = out[:, i]
        return new_fields, dict(stage_tens)
    return fn


# ---------------------------------------------------------------------------
# "vadvc" — vertical advection alone (paper: 5.3x, 1.61 GF/W)
# ---------------------------------------------------------------------------


def _vadvc_fold_grid(variant, local_grid, n_fields, ensemble):
    """The grid the vadvc kernel actually tiles: the horizontally-parallel
    sweep folds (ensemble [, field]) into y."""
    nz, ly, lx = local_grid
    fold = ensemble * (n_fields if variant == "whole_state" else 1)
    return (nz, fold * ly, lx)


def _vadvc_resolve_tile(variant, compute_grid, dtype, n_fields, ensemble,
                        k):
    if variant == "unfused":
        return None
    return vadvc_ops.resolve_tile(
        _vadvc_fold_grid(variant, compute_grid, n_fields, ensemble), dtype)


def _vadvc_launch_whole_state(fs, wconp, ts, ss, tile, interp):
    """ONE vadvc launch over stacked (e, nf, nz, ly, lx) operands —
    (ensemble, field) folded into the kernel's y axis, the shared wcon
    (already carrying its +1 staggering column) replicated across the
    field fold.  Returns the stage-tendency stack.  Shared by the solo
    whole-state lowering and the `vadvc`/`vadvc_update` pipeline stages;
    `tile` extents are re-snapped to the actual fold (the Thomas sweep is
    bitwise tile-invariant, so snapping never changes results).  The fold
    is padded to whole sublane tiles so that a legal row window always
    exists (the columns are independent; the padding rows are dropped)."""
    e, nf, nz, ly, lx = fs.shape
    rows = e * nf * ly
    sub = tiling.sublanes(fs.dtype)
    padded = -(-rows // sub) * sub
    _, tj, ti = tile
    ti = tiling.snap_to_divisor(ti, lx, align=tiling.LANES)
    tj = tiling.snap_to_divisor(tj, padded, align=sub)

    def foldf(a):            # (e, nf, nz, ly, lx') -> (nz, padded, lx')
        a = a.transpose(2, 0, 1, 3, 4).reshape(nz, rows, a.shape[-1])
        return jnp.pad(a, ((0, 0), (0, padded - rows), (0, 0)), mode="edge")

    wrep = jnp.broadcast_to(wconp[:, None], (e, nf) + wconp.shape[1:])
    out = vadvc_pallas(foldf(fs), foldf(wrep), foldf(fs), foldf(ts),
                       foldf(ss), tj=tj, ti=ti, interpret=interp)
    return out[:, :rows].reshape(nz, e, nf, ly, lx).transpose(1, 2, 0, 3, 4)


def _vadvc_shard_local(plan):
    """Chip-local vadvc round: the ONLY exchanged operand is wcon's RIGHT
    staggering column — the `(0, 1)` x-ride declared in the registry, ONE
    ppermute (the forward direction ships nothing and is elided).  Fields/
    tendencies have a zero footprint (the z-sweep is pointwise in the
    horizontal), so there is no pad-and-crop: the updated stage tendencies
    are full-slab valid.  per_field folds the ensemble into the kernel's
    y axis; whole_state folds (ensemble, field) and replicates the shared
    wcon across the field fold."""
    prog = plan.program
    names = prog.fields
    variant, interp = plan.variant, plan.interpret
    _, _, ax_x = plan.mesh_axes
    py, px = plan.shards
    (_, _ydepth, (wx_lo, wx_hi)), = plan.rides
    wire = prog.exchange_dtype
    tile = plan.tile_plan.tile if plan.tile_plan is not None else None

    def local(fields, wcon, tens, stage_tens):
        e, nz, ly, lx = wcon.shape
        (wconp,) = _domain._exchange_packed([(wcon, (wx_lo, wx_hi))], ax_x,
                                            px, dim=-1, wire_dtype=wire)
        if variant == "unfused":
            new_stage = {
                n: jax.vmap(vadvc_ref.vadvc)(fields[n], wconp, fields[n],
                                             tens[n], stage_tens[n])
                for n in names}
            return dict(fields), new_stage

        # The planner resolved (tj, ti) against the GLOBAL ensemble fold;
        # under an ensemble-sharded ("pod") mesh the local fold is
        # smaller, so re-snap to the shard's actual extents (static at
        # trace time; a no-op when they already divide).
        _, tj, ti = tile
        ti = tiling.snap_to_divisor(ti, lx, align=tiling.LANES)
        if variant == "per_field":
            tj_l = tiling.snap_to_divisor(tj, e * ly,
                                          align=tiling.sublanes(wcon.dtype))

            def fold(a):         # (e, nz, ly, lx') -> (nz, e*ly, lx')
                return a.transpose(1, 0, 2, 3).reshape(nz, e * ly,
                                                       a.shape[-1])
            wf = fold(wconp)
            new_stage = {}
            for n in names:
                out = vadvc_pallas(fold(fields[n]), wf, fold(fields[n]),
                                   fold(tens[n]), fold(stage_tens[n]),
                                   tj=tj_l, ti=ti, interpret=interp)
                new_stage[n] = out.reshape(nz, e, ly, lx).transpose(
                    1, 0, 2, 3)
            return dict(fields), new_stage

        # whole_state: ONE launch — (ensemble, field) folded into y, the
        # shared wcon replicated across the field fold.
        stk = lambda d: _dycore.stack_state(d, names)  # (e,nf,nz,ly,lx)
        out = _vadvc_launch_whole_state(stk(fields), wconp, stk(tens),
                                        stk(stage_tens), tile, interp)
        new_stage = {n: out[:, i] for i, n in enumerate(names)}
        return dict(fields), new_stage
    return local


def _vadvc_apply_stage(prog, names, interpret, use_ref):
    """Full-slab vadvc stage: updates the bound stage tendencies only
    (fields pass through).  `wconp` is the pipeline's wcon slab — one
    column wider on the high-x side than the field slabs, exactly the
    solo lowering's staggering contract."""
    def fn(fields, wconp, tens, stage_tens):
        new_stage = dict(stage_tens)
        if use_ref:
            for n in names:
                new_stage[n] = jax.vmap(vadvc_ref.vadvc)(
                    fields[n], wconp, fields[n], tens[n], stage_tens[n])
            return dict(fields), new_stage
        stk = lambda d: jnp.stack([d[n] for n in names], axis=1)
        fs = stk(fields)
        e, nb, nz, Y, X = fs.shape
        tile = vadvc_ops.resolve_tile((nz, e * nb * Y, X), fs.dtype).tile
        out = _vadvc_launch_whole_state(fs, wconp, stk(tens),
                                        stk(stage_tens), tile, interpret)
        for i, n in enumerate(names):
            new_stage[n] = out[:, i]
        return dict(fields), new_stage
    return fn


def _vadvc_traffic(plan, model_ty):
    prog = plan.program
    nz, ny, nx = prog.grid_shape
    # The resolved tile lives on the ensemble/field-FOLDED grid; the
    # traffic model runs on the physical grid, so snap its (tj, ti) to
    # legal extents of (ny, nx) (z stays whole — the sweep is sequential).
    if plan.tile_plan is not None:
        _, tj, ti = plan.tile_plan.tile
    else:
        tj, ti = model_ty, nx
    tile = (nz, tiling.snap_to_divisor(tj, ny,
                                       align=tiling.sublanes(prog.dtype)),
            tiling.snap_to_divisor(ti, nx, align=tiling.LANES))
    return memmodel.stencil_op_traffic(
        autotune.get_op("vadvc"), prog.grid_shape, prog.dtype,
        n_fields=prog.n_fields, tile=tile, k_steps=plan.k_steps)


def _generic_exchange_model(op: StencilOpDef):
    def model(plan):
        prog = plan.program
        return memmodel.packed_exchange_model(
            prog.grid_shape, prog.dtype, rides=op.memmodel_rides(
                prog.n_fields),
            k=plan.k_steps, shards=plan.exchange.shards,
            compute_halo=(plan.k_steps * op.halo, plan.k_steps * op.halo),
            exchange_dtype=prog.exchange_dtype)
    return model


_HDIFF_OP = register_stencil_op(StencilOpDef(
    name="hdiff",
    title="compound horizontal diffusion (laplace -> limited flux -> out)",
    reads=("fields",),
    writes=("fields",),
    halo=hdiff_ops.HALO,
    flops_per_point=tiling.HDIFF.flops_per_point,
    rides=(OperandRide("fields", y=(hdiff_ops.HALO, hdiff_ops.HALO),
                       x=(hdiff_ops.HALO, hdiff_ops.HALO), per_field=True),),
    variants=("unfused", "per_field", "whole_state", "kstep"),
    tile_spaces=(("per_field", "hdiff"), ("whole_state", "hdiff"),
                 ("kstep", "hdiff")),
    inkernel_kstep=True,
    pads_single_chip=True,
    packed_variants=("unfused", "per_field", "whole_state", "kstep"),
    resolve_tile=_hdiff_resolve_tile,
    build_shard_local=_hdiff_shard_local,
    pallas_calls=lambda variant, nf, k: {"unfused": 0, "per_field": nf,
                                         "whole_state": 1, "kstep": 1}[
                                             variant],
    traffic=_hdiff_traffic,
    kstep_vmem_check=_hdiff_kstep_vmem_check,
))
_HDIFF_OP = dataclasses.replace(
    _HDIFF_OP, exchange_model=_generic_exchange_model(_HDIFF_OP),
    apply_stage=_hdiff_apply_stage)
register_stencil_op(_HDIFF_OP)

_VADVC_OP = register_stencil_op(StencilOpDef(
    name="vadvc",
    title="vertical advection (implicit Thomas solve; updates stage_tens)",
    reads=("fields", "wcon", "tens", "stage_tens"),
    writes=("stage_tens",),
    halo=0,
    flops_per_point=tiling.VADVC.flops_per_point,
    rides=(OperandRide("wcon", x_fixed=(0, 1)),),
    variants=("unfused", "per_field", "whole_state"),
    tile_spaces=(("per_field", "vadvc"), ("whole_state", "vadvc")),
    inkernel_kstep=False,
    pads_single_chip=True,
    packed_variants=("unfused", "per_field", "whole_state"),
    resolve_tile=_vadvc_resolve_tile,
    build_shard_local=_vadvc_shard_local,
    pallas_calls=lambda variant, nf, k: {"unfused": 0, "per_field": nf,
                                         "whole_state": 1}[variant],
    traffic=_vadvc_traffic,
))
_VADVC_OP = dataclasses.replace(
    _VADVC_OP, exchange_model=_generic_exchange_model(_VADVC_OP),
    apply_stage=_vadvc_apply_stage)
register_stencil_op(_VADVC_OP)


# ---------------------------------------------------------------------------
# "vadvc_update" — the paper's ablation composition: vadvc + point-wise
# leapfrog update (no hdiff)
# ---------------------------------------------------------------------------


def _vadvc_update_resolve_tile(variant, compute_grid, dtype, n_fields,
                               ensemble, k):
    if variant == "unfused":
        return None
    tj, ti = vadvc_ops.plan_tile(
        _vadvc_fold_grid("whole_state", compute_grid, n_fields, ensemble),
        dtype)
    return tiling.TilePlan(op=autotune.get_op("vadvc_update"),
                           grid_shape=tuple(int(g) for g in compute_grid),
                           tile=(int(compute_grid[0]), tj, ti),
                           dtype=str(jnp.dtype(dtype)))


def _vadvc_update_shard_local(plan):
    """Chip-local vadvc_update round: the vadvc lowering (ONE wcon
    right-column ppermute, full-slab-valid stage tendencies) followed by
    the resident point-wise update `f += dt * stage` — the composition
    never round-trips the stage tendency through HBM between the solve
    and the update."""
    prog = plan.program
    names, dt = prog.fields, prog.dt
    variant, interp = plan.variant, plan.interpret
    _, _, ax_x = plan.mesh_axes
    py, px = plan.shards
    (_, _ydepth, (wx_lo, wx_hi)), = plan.rides
    wire = prog.exchange_dtype
    tile = plan.tile_plan.tile if plan.tile_plan is not None else None

    def local(fields, wcon, tens, stage_tens):
        (wconp,) = _domain._exchange_packed([(wcon, (wx_lo, wx_hi))], ax_x,
                                            px, dim=-1, wire_dtype=wire)
        if variant == "unfused":
            new_fields, new_stage = {}, {}
            for n in names:
                stage = jax.vmap(vadvc_ref.vadvc)(
                    fields[n], wconp, fields[n], tens[n], stage_tens[n])
                new_fields[n] = fields[n] + dt * stage
                new_stage[n] = stage
            return new_fields, new_stage
        stk = lambda d: _dycore.stack_state(d, names)
        fs = stk(fields)
        ss = _vadvc_launch_whole_state(fs, wconp, stk(tens),
                                       stk(stage_tens), tile, interp)
        fs = fs + dt * ss
        new_fields = {n: fs[:, i] for i, n in enumerate(names)}
        new_stage = {n: ss[:, i] for i, n in enumerate(names)}
        return new_fields, new_stage
    return local


def _vadvc_update_apply_stage(prog, names, interpret, use_ref):
    """Full-slab vadvc_update stage: solve + resident point-wise update of
    the bound fields; writes fields AND stage tendencies."""
    dt = prog.dt

    def fn(fields, wconp, tens, stage_tens):
        new_fields, new_stage = dict(fields), dict(stage_tens)
        if use_ref:
            for n in names:
                stage = jax.vmap(vadvc_ref.vadvc)(
                    fields[n], wconp, fields[n], tens[n], stage_tens[n])
                new_fields[n] = fields[n] + dt * stage
                new_stage[n] = stage
            return new_fields, new_stage
        stk = lambda d: jnp.stack([d[n] for n in names], axis=1)
        fs = stk(fields)
        e, nb, nz, Y, X = fs.shape
        tile = vadvc_ops.resolve_tile((nz, e * nb * Y, X), fs.dtype).tile
        ss = _vadvc_launch_whole_state(fs, wconp, stk(tens),
                                       stk(stage_tens), tile, interpret)
        fs = fs + dt * ss
        for i, n in enumerate(names):
            new_fields[n] = fs[:, i]
            new_stage[n] = ss[:, i]
        return new_fields, new_stage
    return fn


def _vadvc_update_traffic(plan, model_ty):
    prog = plan.program
    nz, ny, nx = prog.grid_shape
    if plan.tile_plan is not None:
        _, tj, ti = plan.tile_plan.tile
    else:
        tj, ti = model_ty, nx
    tile = (nz, tiling.snap_to_divisor(tj, ny,
                                       align=tiling.sublanes(prog.dtype)),
            tiling.snap_to_divisor(ti, nx, align=tiling.LANES))
    return memmodel.stencil_op_traffic(
        autotune.get_op("vadvc_update"), prog.grid_shape, prog.dtype,
        n_fields=prog.n_fields, tile=tile, k_steps=plan.k_steps)


_VADVC_UPDATE_OP = register_stencil_op(StencilOpDef(
    name="vadvc_update",
    title="vertical advection + fused point-wise update (no hdiff)",
    reads=("fields", "wcon", "tens", "stage_tens"),
    writes=("fields", "stage_tens"),
    halo=0,
    flops_per_point=tiling.VADVC_UPDATE.flops_per_point,
    rides=(OperandRide("wcon", x_fixed=(0, 1)),),
    variants=("unfused", "whole_state"),
    tile_spaces=(("whole_state", "vadvc_update"),),
    inkernel_kstep=False,
    pads_single_chip=True,
    packed_variants=("unfused", "whole_state"),
    resolve_tile=_vadvc_update_resolve_tile,
    build_shard_local=_vadvc_update_shard_local,
    pallas_calls=lambda variant, nf, k: {"unfused": 0,
                                         "whole_state": 1}[variant],
    traffic=_vadvc_update_traffic,
))
_VADVC_UPDATE_OP = dataclasses.replace(
    _VADVC_UPDATE_OP,
    exchange_model=_generic_exchange_model(_VADVC_UPDATE_OP),
    apply_stage=_vadvc_update_apply_stage)
register_stencil_op(_VADVC_UPDATE_OP)


# ---------------------------------------------------------------------------
# "hadv_upwind" — first-order upwind horizontal advection (backward-only
# reach: the registry's asymmetric-ride op)
# ---------------------------------------------------------------------------


def _hadv_resolve_tile(variant, compute_grid, dtype, n_fields, ensemble, k):
    if variant == "unfused":
        return None
    return hadv_ops.resolve_tile(compute_grid, dtype)


def _hadv_shard_local(plan):
    """Chip-local hadv round: ONE packed exchange per direction at the
    asymmetric (1, 0) depth — the donor cell only looks backward, so the
    high sides ship NOTHING and `domain._exchange_packed` elides those
    halves of the wire buffer.  With 1 shard the exchange degenerates to
    periodic wrap-padding (the op is periodic, like hdiff programs)."""
    prog = plan.program
    names = prog.fields
    cfl, variant, interp = prog.coeff, plan.variant, plan.interpret
    ty = plan.tile_ty
    _, ax_y, ax_x = plan.mesh_axes
    py, px = plan.shards
    (_, (hy_lo, hy_hi), (hx_lo, hx_hi)), = plan.rides
    wire = prog.exchange_dtype

    def local(fields, wcon, tens, stage_tens):
        fs = _dycore.stack_state(fields, names)   # (e, nf, nz, ly, lx)
        e, nf, nz, ly, lx = fs.shape
        (fs,) = _domain._exchange_packed([(fs, (hy_lo, hy_hi))], ax_y, py,
                                         dim=-2, wire_dtype=wire)
        (fs,) = _domain._exchange_packed([(fs, (hx_lo, hx_hi))], ax_x, px,
                                         dim=-1, wire_dtype=wire)
        Y, X = fs.shape[-2:]
        if variant == "unfused":
            fs = hadv_ref.hadv_upwind(fs.reshape(-1, Y, X),
                                      cfl=cfl).reshape(fs.shape)
        else:
            # The compute grid the planner tuned on is symmetrically
            # padded; the actual slab only grows on the low sides — snap
            # the window to it (the kernel is bitwise tile-invariant).
            ty_l = tiling.snap_to_divisor(ty, Y,
                                          align=tiling.sublanes(fs.dtype))
            fs = hadv_pallas(fs.reshape(-1, Y, X), cfl=cfl, ty=ty_l,
                             interpret=interp).reshape(fs.shape)
        out = fs[..., hy_lo:hy_lo + ly, hx_lo:hx_lo + lx]
        new_fields = {n: out[:, i] for i, n in enumerate(names)}
        return new_fields, dict(stage_tens)
    return local


def _hadv_apply_stage(prog, names, interpret, use_ref):
    """Full-slab upwind-advection stage for pipeline chaining."""
    cfl = prog.coeff

    def fn(fields, wconp, tens, stage_tens):
        fs = jnp.stack([fields[n] for n in names], axis=1)
        e, nb, nz, Y, X = fs.shape
        if use_ref:
            out = hadv_ref.hadv_upwind(fs.reshape(-1, Y, X), cfl=cfl)
        else:
            ty = hadv_ops.plan_tile((e * nb * nz, Y, X), fs.dtype)
            out = hadv_pallas(fs.reshape(-1, Y, X), cfl=cfl, ty=ty,
                              interpret=interpret)
        out = out.reshape(fs.shape)
        new_fields = dict(fields)
        for i, n in enumerate(names):
            new_fields[n] = out[:, i]
        return new_fields, dict(stage_tens)
    return fn


def _hadv_traffic(plan, model_ty):
    prog = plan.program
    nz, ny, nx = prog.grid_shape
    tile = (1, tiling.snap_to_divisor(model_ty, ny,
                                      align=tiling.sublanes(prog.dtype)),
            nx)
    return memmodel.stencil_op_traffic(
        autotune.get_op("hadv_upwind"), prog.grid_shape, prog.dtype,
        n_fields=prog.n_fields, tile=tile, k_steps=plan.k_steps)


_HADV_OP = register_stencil_op(StencilOpDef(
    name="hadv_upwind",
    title="upwind horizontal advection (donor cell, backward-only reach)",
    reads=("fields",),
    writes=("fields",),
    halo=hadv_ops.HALO,
    flops_per_point=tiling.HADV_UPWIND.flops_per_point,
    rides=(OperandRide("fields", y=(hadv_ops.HALO, 0),
                       x=(hadv_ops.HALO, 0), per_field=True),),
    variants=("unfused", "whole_state"),
    tile_spaces=(("whole_state", "hadv_upwind"),),
    inkernel_kstep=False,
    pads_single_chip=True,
    packed_variants=("unfused", "whole_state"),
    resolve_tile=_hadv_resolve_tile,
    build_shard_local=_hadv_shard_local,
    pallas_calls=lambda variant, nf, k: {"unfused": 0,
                                         "whole_state": 1}[variant],
    traffic=_hadv_traffic,
))
_HADV_OP = dataclasses.replace(
    _HADV_OP, exchange_model=_generic_exchange_model(_HADV_OP),
    apply_stage=_hadv_apply_stage)
register_stencil_op(_HADV_OP)


# ---------------------------------------------------------------------------
# "asselin" — point-wise leapfrog time filter (zero rides, zero exchange)
# ---------------------------------------------------------------------------


def _asselin_shard_local(plan):
    """Chip-local asselin round: pure point-wise jnp — no exchange at all
    (the registry's zero-ride op; every direction is elided), no Pallas
    launch (XLA fuses the three-operand FMA fine on its own)."""
    prog = plan.program
    names, coeff, dt = prog.fields, prog.coeff, prog.dt

    def local(fields, wcon, tens, stage_tens):
        new_fields = {n: fields[n] + coeff * dt * (tens[n] - stage_tens[n])
                      for n in names}
        return new_fields, dict(stage_tens)
    return local


def _asselin_apply_stage(prog, names, interpret, use_ref):
    """Full-slab asselin stage: the same point-wise filter the solo
    lowering runs (there is no kernel to dispatch either way)."""
    coeff, dt = prog.coeff, prog.dt

    def fn(fields, wconp, tens, stage_tens):
        new_fields = dict(fields)
        for n in names:
            new_fields[n] = (fields[n]
                             + coeff * dt * (tens[n] - stage_tens[n]))
        return new_fields, dict(stage_tens)
    return fn


def _asselin_traffic(plan, model_ty):
    prog = plan.program
    nz, ny, nx = prog.grid_shape
    tile = (1, tiling.snap_to_divisor(model_ty, ny,
                                      align=tiling.sublanes(prog.dtype)),
            nx)
    return memmodel.stencil_op_traffic(
        autotune.get_op("asselin"), prog.grid_shape, prog.dtype,
        n_fields=prog.n_fields, tile=tile, k_steps=plan.k_steps)


_ASSELIN_OP = register_stencil_op(StencilOpDef(
    name="asselin",
    title="leapfrog time filter from stored tendencies (point-wise)",
    reads=("fields", "tens", "stage_tens"),
    writes=("fields",),
    halo=0,
    flops_per_point=tiling.ASSELIN.flops_per_point,
    rides=(),
    variants=("unfused", "whole_state"),
    tile_spaces=(),
    inkernel_kstep=False,
    pads_single_chip=False,
    packed_variants=("unfused", "whole_state"),
    resolve_tile=lambda variant, compute_grid, dtype, nf, e, k: None,
    build_shard_local=_asselin_shard_local,
    pallas_calls=lambda variant, nf, k: 0,
    traffic=_asselin_traffic,
))
_ASSELIN_OP = dataclasses.replace(
    _ASSELIN_OP, exchange_model=_generic_exchange_model(_ASSELIN_OP),
    apply_stage=_asselin_apply_stage)
register_stencil_op(_ASSELIN_OP)

"""Pipelined stencil programs: a stage chain compiled to ONE fused plan.

NERO's near-memory argument is about *chains*, not single kernels: the
paper's dycore wins because vadvc's tendencies never round-trip main
memory before the point-wise update and hdiff consume them (§3 — the
baseline's intermediates bounce through DRAM between kernels).  The
registry (`weather/stencil_ops.py`) gave every operator a solo program;
this module gives chains the same one-plan treatment WITHOUT writing a
fused mega-kernel per combination:

* `PipelineProgram` is a `StencilProgram` whose op is an ordered list of
  registered stages (`PipelineStage`: op name + optional field binding).
  Constructing one synthesizes and registers a chain `StencilOpDef` — so
  `program.compile` plans it like any other op, with NO pipeline branches
  in the planner.
* **One fused exchange.**  A backward validity analysis walks the stages
  in reverse, accumulating how far beyond the interior each operand must
  be valid BEFORE the chain runs (stage reach = the stage's own declared
  k=1 ride; written operands reset the requirement).  The merged
  per-operand `(lo, hi)` depths become the chain op's `OperandRide`s:
  the whole round is ONE packed ppermute pair per mesh direction —
  max-over-stages depth per operand side, ragged per operand — instead of
  one exchange per stage.  The analysis runs at k=1 and k=2 and the
  depths are encoded as `k*base + fixed` (verified linear at k=3), so the
  chain inherits the communication-avoiding k-step round for free.
* **Ordered resident launches.**  The lowering exchanges once, edge-pads
  every operand to the common slab target, then launches the stages IN
  ORDER via their `apply_stage` hooks on the shared padded slabs: an
  operand written by stage i is stage i+1's input WITHOUT an intermediate
  HBM round trip or re-exchange (validity shrinks stage by stage, exactly
  as the analysis accounted).  One interior crop ends the round.
* **Traffic model.**  `core/memmodel.pipeline_step_traffic` prices the
  chained single-pass against the sum of solo stages; the chain's tile
  space is `core/tiling.pipeline_spec` (flops sum, streams union,
  sequential axes union), registered in `core/autotune` under the chain
  name.

Stage semantics: stages share the program's `coeff`/`dt` scalars and may
write only `fields` / `stage_tens` (the round contract — `wcon` and the
slow tendencies are read-only).  A stage binding (`fields=("u",)`)
restricts the stage to a subset of the program's fields; unbound fields
pass through bitwise.  Zero-ride chains (e.g. a lone `asselin`) compile
to ZERO collectives — the packed exchange elides every direction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.core import autotune, memmodel, tiling
from repro.weather import domain as _domain
from repro.weather import stencil_ops as _sops
from repro.weather.program import StencilProgram
from repro.weather.stencil_ops import (OperandRide, StencilOpDef,
                                       get_stencil_op, register_stencil_op)

__all__ = ["PipelineStage", "PipelineProgram", "pipeline_op_name"]

# Operand slots a stage may write (the round returns (fields, stage_tens);
# wcon and the slow tendencies pass through every registered lowering).
_WRITABLE = ("fields", "stage_tens")
_PER_FIELD = ("fields", "tens", "stage_tens")
_ZERO = ((0, 0), (0, 0))


@dataclasses.dataclass(frozen=True)
class PipelineStage:
    """One chain link: a registered op plus an optional field binding.

    `fields=None` binds the stage to every program field; a tuple
    restricts it (unbound fields pass through that stage bitwise)."""

    op: str
    fields: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        if self.fields is not None:
            object.__setattr__(self, "fields", tuple(self.fields))

    def describe(self) -> Dict[str, Any]:
        return {"op": self.op,
                "fields": None if self.fields is None else list(self.fields)}


def pipeline_op_name(stages) -> str:
    """Canonical synthesized op name: the chain signature.  Bindings are
    part of the name because the merged rides depend on them — two
    pipelines with the same signature share one registry entry."""
    sig = []
    for st in stages:
        s = st.op
        if st.fields is not None:
            s += "[" + ",".join(st.fields) + "]"
        sig.append(s)
    return "pipeline(" + "->".join(sig) + ")"


# ---------------------------------------------------------------------------
# Backward validity analysis -> merged OperandRides
# ---------------------------------------------------------------------------


def _req_add(a, b):
    return ((a[0][0] + b[0][0], a[0][1] + b[0][1]),
            (a[1][0] + b[1][0], a[1][1] + b[1][1]))


def _req_max(a, b):
    return ((max(a[0][0], b[0][0]), max(a[0][1], b[0][1])),
            (max(a[1][0], b[1][0]), max(a[1][1], b[1][1])))


def _chain_requirements(stages, field_names, k: int):
    """Walk `k` chain repetitions BACKWARD, accumulating per-(operand,
    field) validity requirements: how far beyond the interior each slot
    must be valid before the round runs so the final interior crop is
    exact.  A stage's reads need (max requirement over its written slots)
    + the stage's own declared per-operand reach; writing a slot RESETS
    its requirement to what the stage itself reads it at."""
    req: Dict[Tuple[str, Optional[str]], Any] = {}

    def get(key):
        return req.get(key, _ZERO)

    for _ in range(k):
        for st in reversed(stages):
            od = get_stencil_op(st.op)
            bound = st.fields if st.fields is not None else field_names
            reach = {r.operand: r.depths(1) for r in od.rides}
            needed = _ZERO
            for w in od.writes:
                for f in bound:
                    needed = _req_max(needed, get((w, f)))
            new_read: Dict[Tuple[str, Optional[str]], Any] = {}
            for o in od.reads:
                cand = _req_add(needed, reach.get(o, _ZERO))
                if o in _PER_FIELD:
                    for f in bound:
                        new_read[(o, f)] = cand
                else:
                    new_read[(o, None)] = cand
            written = {(w, f) for w in od.writes for f in bound}
            for key, cand in new_read.items():
                if key not in written:
                    req[key] = _req_max(get(key), cand)
            for key in written:
                req[key] = new_read.get(key, _ZERO)
    merged: Dict[str, Any] = {}
    for (o, _f), r in req.items():
        merged[o] = _req_max(merged.get(o, _ZERO), r)
    return merged


def _chain_rides(stages, field_names):
    """Merged per-operand rides in `k*base + fixed` form, plus whether the
    footprint is LINEAR in k (the k-step precondition: the analysis at
    k=3 must match the extrapolation from k=1 and k=2)."""
    r1 = _chain_requirements(stages, field_names, 1)
    r2 = _chain_requirements(stages, field_names, 2)
    r3 = _chain_requirements(stages, field_names, 3)
    operands = sorted(set(r1) | set(r2) | set(r3))
    rides, linear, deepens = [], True, False
    for o in operands:
        a = r1.get(o, _ZERO)
        b = r2.get(o, _ZERO)
        c = r3.get(o, _ZERO)
        base = ((b[0][0] - a[0][0], b[0][1] - a[0][1]),
                (b[1][0] - a[1][0], b[1][1] - a[1][1]))
        if (min(base[0] + base[1]) < 0
                or _req_add(b, base) != c):
            linear = False
        if any(d > 0 for d in base[0] + base[1]):
            deepens = True
        fixed = ((a[0][0] - base[0][0], a[0][1] - base[0][1]),
                 (a[1][0] - base[1][0], a[1][1] - base[1][1]))
        if not any(d > 0 for d in a[0] + a[1] + base[0] + base[1]):
            continue              # never rides: zero at every k
        rides.append(OperandRide(o, y=base[0], x=base[1],
                                 y_fixed=fixed[0], x_fixed=fixed[1],
                                 per_field=o in _PER_FIELD))
    return tuple(rides), linear, deepens


# ---------------------------------------------------------------------------
# Synthesized chain op: tile space, lowering, traffic
# ---------------------------------------------------------------------------


def _stage_tile_spec(st: PipelineStage) -> tiling.OpSpec:
    """The autotune OpSpec a stage models as: its op's whole-state tile
    space when it registers one, else the op's own registered spec."""
    od = get_stencil_op(st.op)
    name = dict(od.tile_spaces).get("whole_state", st.op)
    return autotune.get_op(name)


def _make_chain_spec(name, stages, field_names) -> tiling.OpSpec:
    reads = set()
    writes = set()
    for st in stages:
        od = get_stencil_op(st.op)
        reads.update(od.reads)
        writes.update(od.writes)
    nf = max(1, len(field_names))
    fields_in = (sum(1 for o in _PER_FIELD if o in reads)
                 + (1.0 / nf if "wcon" in reads else 0.0))
    fields_out = sum(1 for o in _PER_FIELD if o in writes)
    halo = sum(get_stencil_op(st.op).halo for st in stages)
    return tiling.pipeline_spec(
        name, [_stage_tile_spec(st) for st in stages],
        fields_in=fields_in, fields_out=fields_out, halo=(0, halo, halo))


def _pipeline_resolve_tile(spec: tiling.OpSpec):
    def resolve(variant, compute_grid, dtype, n_fields, ensemble, k):
        if variant == "unfused":
            return None
        grid = tuple(int(g) for g in compute_grid)
        tuned = autotune.tune(spec, grid, dtype)
        tz, ty, tx = tuned.plan.tile
        ty = tiling.snap_to_divisor(ty, grid[1],
                                    align=tiling.sublanes(dtype))
        return tiling.TilePlan(op=spec, grid_shape=grid, tile=(tz, ty, tx),
                               dtype=str(jnp.dtype(dtype)))
    return resolve


def _pipeline_traffic(spec: tiling.OpSpec, stages):
    def traffic(plan, model_ty):
        prog = plan.program
        nz, ny, nx = prog.grid_shape
        tile = (nz if 0 in spec.seq_axes else 1,
                tiling.snap_to_divisor(model_ty, ny,
                                       align=tiling.sublanes(prog.dtype)),
                nx)
        pairs = [(_stage_tile_spec(st),
                  len(st.fields) if st.fields is not None
                  else prog.n_fields) for st in stages]
        return memmodel.pipeline_step_traffic(
            spec, pairs, prog.grid_shape, prog.dtype, tile=tile,
            k_steps=plan.k_steps)
    return traffic


def _pipeline_pallas_calls(stages):
    def calls(variant, nf, k):
        if variant == "unfused":
            return 0
        per_chain = sum(
            get_stencil_op(st.op).pallas_calls(
                "whole_state",
                len(st.fields) if st.fields is not None else nf, 1)
            for st in stages)
        return k * per_chain
    return calls


def _pipeline_shard_local(stages):
    """The chain round the distributed step shard_maps (and, via
    `pads_single_chip`, the single-chip step): ONE packed exchange per
    direction at the merged ragged depths, edge-pad to the common slab
    target, then the stages IN ORDER on the resident slabs, one crop."""

    def build(plan):
        prog = plan.program
        names = prog.fields
        variant, interp, k = plan.variant, plan.interpret, plan.k_steps
        use_ref = variant == "unfused"
        _, ax_y, ax_x = plan.mesh_axes
        py, px = plan.shards
        wire = prog.exchange_dtype
        rides = {name: (dy, dx) for name, dy, dx in plan.rides}

        def depth(o):
            return rides.get(o, _ZERO)

        reads = set()
        for st in stages:
            reads.update(get_stencil_op(st.op).reads)
        writes = set()
        for st in stages:
            writes.update(get_stencil_op(st.op).writes)
        # Per-field operands every stage sees on the slab; canonical order.
        slab_ops = tuple(o for o in _PER_FIELD if o in reads)
        wcon_read = "wcon" in reads
        # Common slab target: per-side max over the per-field operands —
        # every operand a stage stacks together must share one geometry.
        ty_lo = max([depth(o)[0][0] for o in slab_ops] or [0])
        ty_hi = max([depth(o)[0][1] for o in slab_ops] or [0])
        tx_lo = max([depth(o)[1][0] for o in slab_ops] or [0])
        tx_hi = max([depth(o)[1][1] for o in slab_ops] or [0])
        stage_fns = [
            (get_stencil_op(st.op).apply_stage(
                prog, st.fields if st.fields is not None else names,
                interp, use_ref), st)
            for st in stages]

        def pad_to(a, have, want_lo, want_hi, dim):
            d_lo, d_hi = want_lo - have[0], want_hi - have[1]
            if d_lo == 0 and d_hi == 0:
                return a
            pw = [(0, 0)] * a.ndim
            pw[dim] = (d_lo, d_hi)
            # Edge values, not zeros: finite garbage the validity analysis
            # already bounds away from the interior (a NaN would poison the
            # stencil windows that straddle the pad ring).
            return jnp.pad(a, pw, mode="edge")

        def local(fields, wcon, tens, stage_tens):
            e, nz, ly, lx = wcon.shape
            src = {"fields": fields, "tens": tens,
                   "stage_tens": stage_tens}
            stacked = {o: jnp.stack([src[o][n] for n in names], axis=1)
                       for o in slab_ops}
            # ONE packed ppermute pair per direction for the WHOLE chain:
            # every operand rides at its own merged depth (ragged; zero
            # sides ship nothing, all-zero directions are elided).
            parts = [(stacked[o], depth(o)[0]) for o in slab_ops]
            if wcon_read:
                parts.append((wcon, depth("wcon")[0]))
            parts = _domain._exchange_packed(parts, ax_y, py, dim=-2,
                                             wire_dtype=wire)
            parts = _domain._exchange_packed(
                [(p, depth(o)[1]) for p, o in
                 zip(parts, slab_ops + (("wcon",) if wcon_read else ()))],
                ax_x, px, dim=-1, wire_dtype=wire)
            slabs = dict(zip(slab_ops, parts))
            # Edge-pad every operand to the common target so the stages
            # share one slab geometry; wcon keeps its one-wider-on-high-x
            # staggering contract.
            for o in slab_ops:
                dy, dx = depth(o)
                a = pad_to(slabs[o], dy, ty_lo, ty_hi, dim=-2)
                slabs[o] = pad_to(a, dx, tx_lo, tx_hi, dim=-1)
            if wcon_read:
                dy, dx = depth("wcon")
                wconp = pad_to(parts[-1], dy, ty_lo, ty_hi, dim=-2)
                wconp = pad_to(wconp, dx, tx_lo, tx_hi + 1, dim=-1)
            else:
                wconp = wcon
            un = {o: {n: slabs[o][:, i] for i, n in enumerate(names)}
                  for o in slab_ops}
            fdict = un.get("fields", dict(fields))
            tdict = un.get("tens", dict(tens))
            sdict = un.get("stage_tens", dict(stage_tens))
            # The chain: stages in order on the RESIDENT slabs — stage i's
            # writes are stage i+1's inputs with no exchange and no HBM
            # round trip in between; k chain repetitions on one deep
            # exchange (validity shrinks exactly as the rides account).
            for _ in range(k):
                for fn, _st in stage_fns:
                    fdict, sdict = fn(fdict, wconp, tdict, sdict)
            crop = lambda a: a[..., ty_lo:ty_lo + ly, tx_lo:tx_lo + lx]
            new_fields = ({n: crop(fdict[n]) for n in names}
                          if "fields" in writes else dict(fields))
            new_stage = ({n: crop(sdict[n]) for n in names}
                         if "stage_tens" in writes else dict(stage_tens))
            return new_fields, new_stage
        return local
    return build


def _ensure_registered(name: str, stages: Tuple[PipelineStage, ...],
                       field_names: Tuple[str, ...]) -> StencilOpDef:
    """Synthesize + register the chain's StencilOpDef and tile space
    (idempotent: the name encodes the signature AND bindings, so a second
    program with the same chain reuses the entry)."""
    if name in _sops.STENCIL_OPS:
        return get_stencil_op(name)
    rides, linear, deepens = _chain_rides(stages, field_names)
    halo = sum(get_stencil_op(st.op).halo for st in stages)
    variants = ("unfused", "whole_state")
    if linear and deepens and halo > 0:
        variants = variants + ("kstep",)
    spec = _make_chain_spec(name, stages, field_names)
    autotune.register_op(spec)
    flops = sum(
        get_stencil_op(st.op).flops_per_point for st in stages)
    reads, writes = [], []
    for o in ("fields", "wcon", "tens", "stage_tens"):
        if any(o in get_stencil_op(st.op).reads for st in stages):
            reads.append(o)
        if any(o in get_stencil_op(st.op).writes for st in stages):
            writes.append(o)
    op = StencilOpDef(
        name=name,
        title="fused stage chain: " + " -> ".join(st.op for st in stages),
        reads=tuple(reads),
        writes=tuple(writes),
        halo=halo,
        flops_per_point=flops,
        rides=rides,
        variants=variants,
        tile_spaces=tuple((v, name) for v in variants if v != "unfused"),
        inkernel_kstep=False,
        pads_single_chip=True,
        packed_variants=variants,
        resolve_tile=_pipeline_resolve_tile(spec),
        build_shard_local=_pipeline_shard_local(stages),
        pallas_calls=_pipeline_pallas_calls(stages),
        traffic=_pipeline_traffic(spec, stages),
    )
    op = dataclasses.replace(
        op, exchange_model=_sops._generic_exchange_model(op))
    return register_stencil_op(op)


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PipelineProgram(StencilProgram):
    """A `StencilProgram` whose op is an ordered stage chain.

    Construction synthesizes and registers the chain's `StencilOpDef`
    (merged rides, fused-exchange lowering, chained traffic model) under
    the canonical signature name, then validates like any program —
    `program.compile` needs no pipeline awareness.  `op` is derived; do
    not set it."""

    stages: Tuple[PipelineStage, ...] = ()

    def __post_init__(self):
        stages = []
        for st in self.stages:
            if isinstance(st, PipelineStage):
                stages.append(st)
            elif isinstance(st, str):
                stages.append(PipelineStage(op=st))
            elif isinstance(st, dict):
                f = st.get("fields")
                stages.append(PipelineStage(
                    op=st["op"], fields=None if f is None else tuple(f)))
            else:
                raise TypeError(f"stage {st!r}: expected a PipelineStage, "
                                f"op name, or {{'op': ...}} dict")
        stages = tuple(stages)
        object.__setattr__(self, "stages", stages)
        if not stages:
            raise ValueError("a PipelineProgram needs at least one stage")
        names = tuple(self.fields)
        for st in stages:
            od = get_stencil_op(st.op)      # raises on unknown ops
            if od.apply_stage is None:
                raise ValueError(
                    f"op {st.op!r} cannot ride in a pipeline (no "
                    f"apply_stage lowering)")
            bad = set(od.writes) - set(_WRITABLE)
            if bad:
                raise ValueError(
                    f"stage {st.op!r} writes {sorted(bad)}: a pipeline "
                    f"round may only write {list(_WRITABLE)}")
            if st.fields is not None:
                missing = [f for f in st.fields if f not in names]
                if missing:
                    raise ValueError(
                        f"stage {st.op!r} binds unknown fields {missing} "
                        f"(program fields: {list(names)})")
                if not st.fields:
                    raise ValueError(f"stage {st.op!r}: an explicit "
                                    f"binding needs at least one field")
        name = pipeline_op_name(stages)
        if self.op not in ("dycore", name):
            raise ValueError(f"op={self.op!r}: a PipelineProgram derives "
                             f"its op from the stages ({name!r}); leave "
                             f"it unset")
        object.__setattr__(self, "op", name)
        opdef = _ensure_registered(name, stages, names)
        if self.halo is not None and self.halo != opdef.halo:
            raise ValueError(f"halo={self.halo}: chain {name!r} reaches "
                             f"{opdef.halo} per step")
        super().__post_init__()

    def to_json(self) -> Dict[str, Any]:
        d = super().to_json()
        d["stages"] = [st.describe() for st in self.stages]
        return d

"""3-D window (tile) planner — NERO's "precision-optimized tiling".

The paper streams a 3-D window of the grid per PE through the on-chip
hierarchy.  Here a `TilePlan` describes exactly that: the window (block)
shape per field, its halo, the VMEM footprint including the double-buffered
pipeline stage, and which hierarchy level it lands in.  The autotuner
(`core/autotune.py`) searches over TilePlans; the Pallas kernels consume the
chosen plan as their BlockSpec shapes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterable, List, Sequence, Tuple

import jax.numpy as jnp

from repro.core import hierarchy as hw


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """Abstract description of a memory-bound operator for planning purposes.

    `fields_in` / `fields_out`: number of same-shaped 3-D input/output fields
    the op streams (vadvc: 7 in / 1 out; hdiff: 1 in / 1 out).  May be
    fractional when a stream is shared/amortized across an outer batch axis
    (dycore_whole_state: the `w` slab is read once per field group).
    `halo`: per-axis one-sided halo the stencil needs (hdiff: (0,2,2)).
    `halo_tiles`: additional per-axis one-sided halo measured in multiples
    of the tile extent itself (dycore_kstep: (0,1,0) — the working window
    is the tile plus a whole aliased window per side).
    `seq_axes`: axes that must stay whole inside a tile because the op is
    sequential along them (vadvc: z; lru_scan: t).
    `flops_per_point`: useful FLOPs per output grid point.
    `scratch_fields`: number of tile-shaped temporaries (vadvc: ccol,dcol);
    sized to the padded window when `scratch_padded` (dycore_kstep carries
    the whole working window per temporary).
    `extra_vmem_buffers`: padded-window-sized dtype-width buffers the kernel
    allocates beyond the streamed fields and fp32 scratch (dycore_kstep: 2,
    the double-buffered `w` prefetch slots).
    `input_windows`: when > 1, each input stream is staged as that many
    whole tile windows (the aliased prev/cur/next refs: 3) rather than one
    halo-padded tile.
    `live_values`: padded-window-sized fp32 values the kernel body keeps
    live besides its scratch refs (the TPU compiler places them in VMEM
    too) — read off the compiler's scoped-VMEM reports at the paper's
    domain, so that the plans the model admits are the plans that compile.
    """

    name: str
    fields_in: float
    fields_out: int
    halo: Tuple[int, int, int]
    seq_axes: Tuple[int, ...]
    flops_per_point: float
    scratch_fields: int = 0
    parallel_axes: Tuple[int, ...] = ()
    halo_tiles: Tuple[int, int, int] = (0, 0, 0)
    scratch_padded: bool = False
    extra_vmem_buffers: float = 0.0
    input_windows: int = 1
    live_values: float = 0.0

    @property
    def bytes_moved_per_point(self) -> float:
        """Ideal HBM traffic per point per dtype-byte (reads + writes)."""
        return float(self.fields_in + self.fields_out)

    def arithmetic_intensity(self, dtype) -> float:
        return self.flops_per_point / (
            self.bytes_moved_per_point * hw.dtype_bytes(dtype))


# Canonical op specs for the paper's kernels -------------------------------

# hdiff: per output point the compound stencil does ~21 flops (4 laplacians
# reused across neighbors amortize; we count the gridtools fused-op count).
HDIFF = OpSpec(
    name="hdiff", fields_in=1, fields_out=1, halo=(0, 2, 2),
    seq_axes=(), parallel_axes=(0, 1, 2), flops_per_point=21.0,
    input_windows=3, live_values=14)

# vadvc: 7 input fields (ccol,dcol,wcon,ustage,upos,utens,utensstage),
# 1 output; forward+backward sweep ~ 38 flops/point; sequential in z (axis 0
# in our (z, y, x) layout); scratch ccol/dcol tiles.
VADVC = OpSpec(
    name="vadvc", fields_in=7, fields_out=1, halo=(0, 0, 1),
    seq_axes=(0,), parallel_axes=(1, 2), flops_per_point=38.0,
    scratch_fields=3)

COPY = OpSpec(
    name="copy", fields_in=1, fields_out=1, halo=(0, 0, 0),
    seq_axes=(), parallel_axes=(0, 1, 2), flops_per_point=0.0)

# lru_scan (RG-LRU / SSM sweep): layout (channels, time) folded to 3-D as
# (time, batch*channels, 1); sequential in time; 9 flops/point (gates+fma).
LRU_SCAN = OpSpec(
    name="lru_scan", fields_in=3, fields_out=1, halo=(0, 0, 0),
    seq_axes=(0,), parallel_axes=(1,), flops_per_point=9.0,
    scratch_fields=1)

# dycore_fused: the whole-field dycore step fused into one dataflow pipeline
# (kernels/dycore_fused) — vadvc Thomas solve + point-wise update + compound
# hdiff.  4 streamed inputs (f, w, utens, utens_stage), 2 outputs (f_new,
# stage); z stays whole (the solve is sequential) and so does x (the kernel
# realizes the periodic x-halo as a VMEM lane roll, so only y is tiled and
# only the 2-deep y-halo is re-read from HBM); every input arrives as three
# aliased windows, 6 padded-window fp32 VMEM temporaries (fwork/wwork/rhs/
# ccol/dcol/stage), and the hdiff stage keeps ~18 window-sized values live.
# flops/point = vadvc(38) + update(2) + hdiff(21).
DYCORE_FUSED = OpSpec(
    name="dycore_fused", fields_in=4, fields_out=2, halo=(0, 2, 0),
    seq_axes=(0, 2), parallel_axes=(1,), flops_per_point=61.0,
    scratch_fields=6, scratch_padded=True, input_windows=3,
    live_values=18)


# hadv_upwind: first-order donor-cell horizontal advection.  The stencil
# reaches ONE point backward in y and x only (the rides in the registry are
# asymmetric); the tile model keeps the symmetric one-sided halo convention.
HADV_UPWIND = OpSpec(
    name="hadv_upwind", fields_in=1, fields_out=1, halo=(0, 1, 1),
    seq_axes=(), parallel_axes=(0, 1, 2), flops_per_point=5.0,
    input_windows=2, live_values=6)

# vadvc_update: the paper's ablation composition — the vadvc Thomas solve
# fused with the point-wise leapfrog update (no hdiff).  Same 7 input
# streams and z-sequential geometry as vadvc, but two outputs (new field +
# stage tendency) and the +2 update flops.
VADVC_UPDATE = OpSpec(
    name="vadvc_update", fields_in=7, fields_out=2, halo=(0, 0, 1),
    seq_axes=(0,), parallel_axes=(1, 2), flops_per_point=40.0,
    scratch_fields=3)

# asselin: point-wise leapfrog time filter from stored tendencies —
# f' = f + coeff * (tens - stage_tens).  Three input streams, one output,
# zero halo (the registry's zero-exchange op).
ASSELIN = OpSpec(
    name="asselin", fields_in=3, fields_out=1, halo=(0, 0, 0),
    seq_axes=(), parallel_axes=(0, 1, 2), flops_per_point=3.0)


def pipeline_spec(name: str, stage_specs: Sequence[OpSpec], *,
                  fields_in: float, fields_out: int,
                  halo: Tuple[int, int, int]) -> OpSpec:
    """Synthesize the tile space of a fused stage chain (`weather/
    pipeline.py`): ONE pass streams the union of the stages' operands
    (`fields_in`/`fields_out`, computed by the pipeline planner from its
    operand bindings) while intermediates stay resident, so flops are the
    SUM over stages but the byte streams are not.  Sequential axes union
    (one z-sequential stage pins the whole chain's z), scratch takes the
    max simultaneous working set, and `halo` is the chain's accumulated
    one-sided reach."""
    if not stage_specs:
        raise ValueError("pipeline needs at least one stage spec")
    seq = tuple(sorted({a for s in stage_specs for a in s.seq_axes}))
    par = tuple(sorted(set(range(3)) - set(seq)))
    return OpSpec(
        name=name, fields_in=float(fields_in), fields_out=int(fields_out),
        halo=tuple(int(h) for h in halo), seq_axes=seq, parallel_axes=par,
        flops_per_point=float(sum(s.flops_per_point for s in stage_specs)),
        scratch_fields=max(s.scratch_fields for s in stage_specs))


LANES = int(hw.VPU_LANES[1])          # minor-most block dim: 128 lanes

# The scoped-VMEM limit every `pallas_call` passes to the TPU compiler IS
# the planner's VMEM budget (`TilePlan.fits`): one number, the spec's
# usable near-memory capacity.
VMEM_LIMIT_BYTES = int(hw.VMEM_USABLE)


def sublanes(dtype) -> int:
    """Second-minor block alignment for `dtype`: 8 rows of 32-bit, 16 of
    16-bit (packed sublanes), 32 of 8-bit."""
    return int(hw.VPU_LANES[0]) * max(1, 4 // hw.dtype_bytes(dtype))


def snap_to_divisor(t: int, n: int, *, align: int, lo: int = 1) -> int:
    """The ONE tile-snapping rule: the largest divisor of `n` that is a
    multiple of `align`, `>= lo` and `<= t`; else the smallest such
    divisor above `t`; else `n` itself (a block equal to the whole extent
    is always legal).

    `align` is the TPU block rule for the dimension: `sublanes(dtype)` for
    the second-minor one, `LANES` for the minor one — Mosaic refuses any
    other block extent, and the interpreter would not notice."""
    first = -(-max(lo, 1) // align) * align
    legal = [d for d in range(first, n + 1, align) if n % d == 0]
    below = [d for d in legal if d <= t]
    if below:
        return below[-1]
    return legal[0] if legal else n


def dycore_whole_state_spec(n_fields: int = 4) -> OpSpec:
    """Tile space of the whole-state fused dycore step (one `pallas_call`
    for all `n_fields` prognostic fields, shared staggered velocity `w`).

    Per-field HBM traffic: 3 private input streams (f, utens, utens_stage)
    plus the shared `w` slab amortized over the field axis — `fields_in =
    3 + 1/n_fields` (the planner's byte accounting tolerates a fractional
    stream).  VMEM is a different story: `w` amortizes in *traffic* but
    its three aliased windows stay double-buffered next to the per-field
    ones (the VMEM model rounds `fields_in` up to whole streams).  That is
    why the whole-state space is registered separately — its traffic, and
    hence the Pareto pick, depends on the field count.
    """
    if n_fields < 1:
        raise ValueError(f"n_fields={n_fields} must be >= 1")
    return OpSpec(
        name="dycore_whole_state", fields_in=3 + 1.0 / n_fields,
        fields_out=2, halo=(0, 2, 0), seq_axes=(0, 2), parallel_axes=(1,),
        flops_per_point=61.0, scratch_fields=6, scratch_padded=True,
        input_windows=3, live_values=18)


DYCORE_WHOLE_STATE = dycore_whole_state_spec()


def dycore_kstep_spec(n_fields: int = 4, k_steps: int = 2) -> OpSpec:
    """Tile space of the k-step fused dycore round (one `pallas_call` runs
    the whole communication-avoiding round: `k_steps` local steps per grid
    cell with the prognostic state held in VMEM between steps).

    Geometry: each grid cell stages a THREE-window working slab (the k-step
    halo is up to a whole `ty` per side — `halo_tiles=(0,1,0)`), and every
    one of the 8 pipeline temporaries (fwork/wwork/twork/swork/rhs/ccol/
    dcol/stage) spans that padded window (`scratch_padded`).  The explicit
    double-buffered `w` prefetch adds 2 padded dtype-width buffers on top
    (`extra_vmem_buffers=2`) — the VMEM budget must clear ALL of that, which
    is why the k-step space is registered separately: its legal-tile set is
    much tighter than the whole-state one.

    HBM traffic per ROUND: the same `3 + 1/n_fields` input streams as the
    whole-state step (state+tendencies once, shared `w` amortized over the
    field axis) and 2 output streams — but the round advances `k_steps`
    timesteps, so `flops_per_point` scales with k while the byte terms do
    not: arithmetic intensity grows ~k-fold (NERO's keep-it-on-fabric
    argument applied across time).
    """
    if n_fields < 1:
        raise ValueError(f"n_fields={n_fields} must be >= 1")
    if k_steps < 1:
        raise ValueError(f"k_steps={k_steps} must be >= 1")
    return OpSpec(
        name="dycore_kstep", fields_in=3 + 1.0 / n_fields, fields_out=2,
        halo=(0, 0, 0), halo_tiles=(0, 1, 0), seq_axes=(0, 2),
        parallel_axes=(1,), flops_per_point=61.0 * k_steps,
        scratch_fields=8, scratch_padded=True, extra_vmem_buffers=2.0,
        live_values=14)


DYCORE_KSTEP = dycore_kstep_spec()


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """A concrete 3-D window choice for an OpSpec on a grid."""

    op: OpSpec
    grid_shape: Tuple[int, int, int]     # full (z, y, x) domain
    tile: Tuple[int, int, int]           # window shape (z, y, x)
    dtype: str
    pipeline_depth: int = 2              # double buffering (dataflow overlap)

    # -- geometry ----------------------------------------------------------
    @property
    def tile_points(self) -> int:
        return int(self.tile[0] * self.tile[1] * self.tile[2])

    @property
    def padded_tile(self) -> Tuple[int, int, int]:
        """Window + halos actually staged into VMEM (tile-multiple halos,
        e.g. the k-step kernel's whole aliased window per side, included)."""
        return tuple(t + 2 * h + 2 * ht * t for t, h, ht in
                     zip(self.tile, self.op.halo, self.op.halo_tiles))

    @property
    def num_tiles(self) -> int:
        return int(math.prod(
            math.ceil(g / t) for g, t in zip(self.grid_shape, self.tile)))

    # -- resources ----------------------------------------------------------
    @property
    def vmem_bytes(self) -> int:
        """NERO's "resource utilization" axis: bytes of near-memory the plan
        claims — pipeline double-buffering on the streamed fields (each input as
        `input_windows` whole windows when the kernel aliases its halo
        windows), the op's explicit extra buffers (e.g. the k-step kernel's
        double-buffered `w` prefetch slots), padded-window scratch where
        the op keeps whole working windows as temporaries, and the values
        its body keeps live.  `TilePlan.fits` holds this against the same
        `VMEM_LIMIT_BYTES` every kernel passes to the compiler."""
        b = hw.dtype_bytes(self.dtype)
        pt = math.prod(self.padded_tile)
        tp = self.tile_points
        win = self.op.input_windows
        in_pts = win * tp if win > 1 else pt
        streamed = (math.ceil(self.op.fields_in) * in_pts
                    + self.op.fields_out * pt) * b
        scratch_pts = pt if self.op.scratch_padded else tp
        scratch = ((self.op.scratch_fields + self.op.live_values)
                   * scratch_pts * max(b, 4))
        extra = self.op.extra_vmem_buffers * pt * b
        return int(streamed * self.pipeline_depth + scratch + extra)

    def fits(self, hier: hw.Hierarchy) -> bool:
        return self.vmem_bytes <= hier.vmem.capacity_bytes

    # -- alignment ----------------------------------------------------------
    @property
    def lane_aligned(self) -> bool:
        """Minor-most dim multiple of 128 lanes, next of 8 sublanes — the MXU
        /VPU alignment the paper's BRAM-width matching corresponds to."""
        z, y, x = self.padded_tile
        return (x % LANES == 0) and (y % sublanes(self.dtype) == 0)

    # -- traffic ------------------------------------------------------------
    @property
    def hbm_bytes_per_tile(self) -> int:
        b = hw.dtype_bytes(self.dtype)
        pt = math.prod(self.padded_tile)
        return int((self.op.fields_in * pt + self.op.fields_out *
                    self.tile_points) * b)

    @property
    def hbm_bytes_total(self) -> int:
        return self.hbm_bytes_per_tile * self.num_tiles

    @property
    def halo_overhead(self) -> float:
        """Fraction of HBM traffic that is redundant halo re-reads."""
        ideal = (self.op.bytes_moved_per_point *
                 hw.dtype_bytes(self.dtype) * math.prod(self.grid_shape))
        return self.hbm_bytes_total / max(ideal, 1.0) - 1.0

    @property
    def flops_total(self) -> float:
        return self.op.flops_per_point * math.prod(self.grid_shape)

    def describe(self) -> dict:
        """JSON-serializable summary — embedded by
        `weather/program.py::ExecutionPlan.report()` and hence by the
        `BENCH_dycore.json` plan block."""
        return {"op": self.op.name,
                "grid": list(self.grid_shape),
                "tile": list(self.tile),
                "padded_tile": list(self.padded_tile),
                "dtype": self.dtype,
                "vmem_bytes": int(self.vmem_bytes),
                "lane_aligned": bool(self.lane_aligned),
                "hbm_bytes_total": int(self.hbm_bytes_total),
                "halo_overhead": float(self.halo_overhead)}


def candidate_tiles(op: OpSpec,
                    grid_shape: Sequence[int],
                    dtype,
                    hier: hw.Hierarchy | None = None,
                    max_candidates: int = 512) -> List[TilePlan]:
    """Enumerate the legal tile space (the autotuner's search domain).

    Sequential axes are never split (vadvc needs the whole z column in VMEM —
    exactly the paper's design, which tiles x/y only for vadvc).  Other axes
    take power-of-two sizes, lane-aligned on the minor axis where possible.
    """
    hier = hier or hw.tpu_v5e()
    grid_shape = tuple(int(g) for g in grid_shape)

    def axis_options(ax: int) -> List[int]:
        g = grid_shape[ax]
        if ax in op.seq_axes:
            return [g]
        opts = []
        s = 1
        while s <= g:
            opts.append(s)
            s *= 2
        if g not in opts:
            opts.append(g)
        return opts

    plans: List[TilePlan] = []
    for tz in axis_options(0):
        for ty in axis_options(1):
            for tx in axis_options(2):
                plan = TilePlan(op=op, grid_shape=grid_shape,
                                tile=(tz, ty, tx), dtype=str(jnp.dtype(dtype)))
                if plan.fits(hier):
                    plans.append(plan)
    # Prefer bigger, aligned tiles first so truncation keeps the useful region.
    plans.sort(key=lambda p: (-int(p.lane_aligned), -p.tile_points))
    return plans[:max_candidates]

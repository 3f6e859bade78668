"""Declarative stencil programs: spec → plan → launch, over registered ops.

NERO's key design move (paper §4) is separating the *what* — a compound
stencil over a field set — from the *how* — a synthesized dataflow: tiling,
line buffers, burst schedule — so the host calls ONE compiled accelerator
action.  Since this PR the *what* names a REGISTERED STENCIL OPERATOR
(`weather/stencil_ops.py`), not just the fused dycore:

* `StencilProgram` is the *what*: the op (`"dycore"`, `"hdiff"`,
  `"vadvc"`, or anything `register_stencil_op` admitted), grid shape,
  ensemble, field set, precision policy (state dtype + exchange wire
  dtype), boundary, and the steps-per-round policy (`k_steps`, possibly
  `"auto"`).  `DycoreProgram` is the dycore spec's thin alias.
* `compile(program, mesh=None, ...)` is the planner: it resolves the whole
  execution strategy ONCE — execution variant, the tile plan via the op's
  declared tile spaces (`resolve_tile` hooks over `core/tiling` /
  `core/autotune`), the communication-avoiding depth
  (`core/autotune.resolve_k_steps` fed the op's declared flops and reach,
  VMEM-clamped), and the packed-exchange schedule derived ENTIRELY from
  the op's per-operand `(lo, hi)` footprint (`OperandRide`) — wcon's
  right-only staggering column and vadvc's single-ppermute wcon ride fall
  out of the declaration, not out of planner special cases.
  `compile_dycore` is the historical alias.
* `ExecutionPlan` is the *how*, immutable: `plan.step(state)` advances one
  round (`k_steps` timesteps), `plan.run(state, steps)` advances any step
  count (a shorter ragged TAIL round `k' = steps mod k` is compiled on
  demand), and `plan.report()` returns the machine-readable strategy —
  the op's declared footprint, modeled HBM traffic and per-op wire bytes
  (`core/memmodel`, footprint-driven), modeled GFLOPS
  (`core/perfmodel`), and the structural launch/collective counts that
  `core/trace_stats.assert_plan_structure` verifies against the traced
  jaxpr — which benchmarks embed verbatim in `BENCH_dycore.json`
  (`per_kernel` blocks: hdiff vs vadvc vs fused, the paper's table).

The legacy flag-soup entry points (`dycore_step`/`run`/
`make_distributed_step`) are GONE — retired ROADMAP item; every caller
builds a program and compiles it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map as _shard_map
from repro.core import (autotune, hwspec, memmodel, perfmodel, tiling,
                        trace_stats)
from repro.weather import stencil_ops as _sops
from repro.weather.fields import PROGNOSTIC, WeatherState, zeros_state
from repro.weather.stencil_ops import (StencilOpDef, get_stencil_op,
                                       register_stencil_op,
                                       registered_stencil_ops)

VARIANTS = _sops.VARIANTS

__all__ = ["StencilProgram", "DycoreProgram", "ExchangeSchedule",
           "ExecutionPlan", "compile", "compile_dycore",
           "compile_with_fallback", "reference_program", "StencilOpDef",
           "get_stencil_op", "register_stencil_op",
           "registered_stencil_ops", "VARIANTS", "plan_cache_key",
           "ensemble_slot_view", "ensemble_slot_assign",
           "ensemble_slot_select", "slot_validity", "slot_guard"]


@dataclasses.dataclass(frozen=True)
class StencilProgram:
    """The *what* of a stencil run: op + field set + grid + policies.

    `op` names a registered `StencilOpDef` (`"dycore"`, `"hdiff"`,
    `"vadvc"`, ...).  `variant` names the execution strategy, `"auto"`
    lets the planner pick (the op's k-step round when `k_steps > 1`
    resolves, else whole-state).  `k_steps` is the steps-per-round policy:
    a positive int, or `"auto"` to let the planner resolve it from the
    op's footprint-driven exchange model (distributed; single-chip
    `"auto"` resolves to 1 — there are no collectives to amortize).
    `dtype` is the state/compute precision policy; `exchange_dtype` the
    wire precision of the packed halo exchange (e.g. `"bfloat16"`).
    `halo` defaults to the op's declared stencil reach and only exists so
    a mismatched expectation fails loudly.  `hardware` names the
    `hwspec` spec the plan's MODELED numbers target (`"tpu_v5e"`,
    `"power9"`, `"nero_ad9h7"`; None = the session default spec) — it
    changes the model, never the lowering."""

    grid_shape: Tuple[int, int, int]            # (nz, ny, nx)
    ensemble: int = 1
    fields: Tuple[str, ...] = PROGNOSTIC        # field set (fields.py)
    halo: Optional[int] = None                  # op's reach; checked if given
    dtype: str = "float32"
    boundary: str = "periodic"
    coeff: float = 0.025
    dt: float = 0.1
    variant: str = "auto"
    k_steps: Any = "auto"                       # int or "auto"
    exchange_dtype: Optional[str] = None
    op: str = "dycore"
    hardware: Optional[str] = None              # hwspec spec name, or default

    def __post_init__(self):
        object.__setattr__(self, "grid_shape",
                           tuple(int(g) for g in self.grid_shape))
        object.__setattr__(self, "fields", tuple(self.fields))
        # Normalize dtype spellings (jnp.float32, np.dtype, "float32") to
        # the canonical string so plan comparison, _check_state, and
        # report()'s JSON stay consistent.
        object.__setattr__(self, "dtype", str(jnp.dtype(self.dtype)))
        if self.exchange_dtype is not None:
            object.__setattr__(self, "exchange_dtype",
                               str(jnp.dtype(self.exchange_dtype)))
        try:
            opdef = get_stencil_op(self.op)
        except KeyError as e:
            raise ValueError(str(e)) from None
        if self.halo is None:
            object.__setattr__(self, "halo", opdef.halo)
        if len(self.grid_shape) != 3 or min(self.grid_shape) < 1:
            raise ValueError(f"grid_shape={self.grid_shape} must be a "
                             f"positive (nz, ny, nx) triple")
        if not self.fields:
            raise ValueError("a StencilProgram needs at least one field")
        if self.ensemble < 1:
            raise ValueError(f"ensemble={self.ensemble} must be >= 1")
        if self.boundary != "periodic":
            raise ValueError(f"boundary={self.boundary!r}: only 'periodic' "
                             f"is implemented (the paper's dycore test "
                             f"setup; halo exchange supplies shard edges)")
        if self.halo != opdef.halo:
            raise ValueError(f"halo={self.halo}: op {self.op!r} declares a "
                             f"fixed stencil reach of {opdef.halo}")
        if self.variant != "auto" and self.variant not in opdef.variants:
            raise ValueError(f"variant={self.variant!r} not supported by "
                             f"op {self.op!r} (supported: "
                             f"{('auto',) + opdef.variants})")
        if self.k_steps != "auto" and (not isinstance(self.k_steps, int)
                                       or self.k_steps < 1):
            raise ValueError(f"k_steps={self.k_steps!r} must be a positive "
                             f"int or 'auto'")
        if (isinstance(self.k_steps, int) and self.k_steps > 1
                and "kstep" not in opdef.variants):
            raise ValueError(f"k_steps={self.k_steps}: op {self.op!r} has "
                             f"no k-step round (its footprint does not "
                             f"deepen with k)")
        if (self.variant in ("unfused", "per_field", "whole_state")
                and self.k_steps not in ("auto", 1)):
            raise ValueError(f"variant={self.variant!r} with "
                             f"k_steps={self.k_steps}: k_steps > 1 is the "
                             f"k-step strategy — use variant='kstep' (or "
                             f"'auto')")
        if self.variant == "kstep" and self.k_steps == 1:
            raise ValueError("variant='kstep' needs k_steps >= 2 (or "
                             "'auto'); k_steps=1 IS the whole-state step")
        if self.hardware is not None:
            try:
                hwspec.load_spec(self.hardware)
            except KeyError as e:
                raise ValueError(str(e)) from None

    @property
    def n_fields(self) -> int:
        return len(self.fields)

    def to_json(self) -> Dict[str, Any]:
        """Plain-JSON spec (the `report()["program"]` block); round-trips
        through `from_json` — serving checkpoints persist programs this
        way so a restarted engine rebuilds its plan cache from keys."""
        d = dataclasses.asdict(self)
        d["grid_shape"] = list(self.grid_shape)
        d["fields"] = list(self.fields)
        return d

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "StencilProgram":
        d = dict(d)
        if "stages" in d and cls is StencilProgram:
            # A serialized PipelineProgram: dispatch to the subclass (late
            # import — pipeline.py builds on this module).
            from repro.weather.pipeline import PipelineProgram
            return PipelineProgram.from_json(d)
        d["grid_shape"] = tuple(d["grid_shape"])
        d["fields"] = tuple(d["fields"])
        return cls(**d)


# The dycore spec is a thin alias: `op` already defaults to "dycore".
DycoreProgram = StencilProgram


def plan_cache_key(program: StencilProgram,
                   ensemble: Optional[int] = None) -> StencilProgram:
    """The canonical compile-once-serve-forever cache key for `program`.

    `StencilProgram.__post_init__` already normalizes every field (dtype
    spellings, tuple-ization), and the spec is frozen and hashable — so
    the program itself IS the key.  `ensemble` rebinds the batch axis:
    a serving engine folds single-member requests into the ensemble axis
    of one shared plan, so requests that differ ONLY in ensemble share a
    compiled plan keyed at the engine's slot count."""
    if ensemble is not None and ensemble != program.ensemble:
        program = dataclasses.replace(program, ensemble=ensemble)
    return program


# --- ensemble-slot views: requests <-> the (e, ...) batch axis -------------
# Every WeatherState leaf is (E, nz, ny, nx); a serving slot is one member.


def ensemble_slot_view(state: WeatherState, e: int) -> WeatherState:
    """Member `e` of a batched state as an ensemble-1 state (a view — no
    copy until the caller materializes it)."""
    return jax.tree_util.tree_map(lambda a: a[e:e + 1], state)


def ensemble_slot_assign(batch: WeatherState, indices,
                         sub: WeatherState) -> WeatherState:
    """Functionally write `sub` (leading dim = len(indices)) into the given
    ensemble slots of `batch`."""
    idx = jnp.asarray(indices, jnp.int32)
    return jax.tree_util.tree_map(lambda b, s: b.at[idx].set(s), batch, sub)


def ensemble_slot_select(mask, new: WeatherState,
                         old: WeatherState) -> WeatherState:
    """Per-slot select: slots where `mask` (shape (E,)) is True take `new`,
    the rest keep `old` — how a serving engine rolls back slots that sat
    out a shorter-than-their-next-part round."""
    def sel(n, o):
        m = jnp.reshape(jnp.asarray(mask), (-1,) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree_util.tree_map(sel, new, old)


@jax.jit
def slot_validity(state: WeatherState, limit) -> jnp.ndarray:
    """Per-slot physics validity: a fused NaN/Inf + magnitude-bound
    reduction over every leaf, returning a ``(E,)`` bool — True where the
    member is entirely finite and within ``|x| <= limit``.  One cheap
    jitted reduction per round boundary is the serving engine's guard; it
    reads every leaf once and writes E booleans, so it cannot perturb any
    slot's bits."""
    def per_leaf(a):
        axes = tuple(range(1, a.ndim))      # no reshape: stays shardable
        finite = jnp.all(jnp.isfinite(a), axis=axes)
        mag = jnp.max(jnp.where(jnp.isfinite(a), jnp.abs(a), 0.0),
                      axis=axes)
        return finite & (mag <= limit)
    per = [per_leaf(leaf) for leaf in jax.tree_util.tree_leaves(state)]
    return jnp.all(jnp.stack(per), axis=0)


# Odd 32-bit mixing constants (Knuth/FNV lineage) for the fingerprint.
_FP_MIX = np.uint32(0x9E3779B1)
_FP_LEAF = np.uint32(0x01000193)
_FP_AXIS = (np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35),
            np.uint32(0x27D4EB2F), np.uint32(0x165667B1))


@jax.jit
def slot_guard(state: WeatherState, limit):
    """`slot_validity` plus a per-slot content FINGERPRINT, one fused
    jitted pass: returns ``(ok, fp)`` with `ok` the ``(E,)`` validity
    bool and `fp` an ``(E,)`` uint32 digest of every leaf's exact bits.

    The fingerprint is the cross-device divergence guard the validity
    reduction cannot be: finite, in-bounds corruption (a bad halo wire
    buffer, a flipped mantissa bit on one shard) passes every NaN/Inf/
    magnitude test, but it changes the digest.  The serving engine
    records each slot's digest at round boundaries and demands that slots
    which did NOT advance a round (rolled-back and idle slots) keep it
    bit-for-bit — so per-shard divergence is caught at the boundary where
    it occurs, not steps later when it blows up.

    Construction: element bits (bitcast, never rounded) are mixed with a
    position hash (per-axis `broadcasted_iota` — no reshape, so the
    reduction stays shardable and the digest is a function of GLOBAL
    positions, invariant to how the array is sharded) and XOR-folded over
    every non-ensemble axis; leaves combine order-sensitively.  XOR makes
    the fold order-independent, so per-shard partial folds under jit
    compose to the same digest on ANY mesh — the property the elastic
    failover relies on when it compares digests across a reshard."""
    def leaf_ok(a):
        axes = tuple(range(1, a.ndim))      # no reshape: stays shardable
        finite = jnp.all(jnp.isfinite(a), axis=axes)
        mag = jnp.max(jnp.where(jnp.isfinite(a), jnp.abs(a), 0.0),
                      axis=axes)
        return finite & (mag <= limit)

    def leaf_fp(a):
        u = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}.get(
            a.dtype.itemsize)
        if u is not None:
            bits = jax.lax.bitcast_convert_type(a, u).astype(jnp.uint32)
        else:                               # 8-byte leaves: (..., 2) u32
            bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        pos = jnp.zeros((), jnp.uint32)
        for d in range(1, bits.ndim):
            iota = jax.lax.broadcasted_iota(jnp.uint32, bits.shape, d)
            pos = pos + iota * _FP_AXIS[d % len(_FP_AXIS)]
        v = (bits + pos) * _FP_MIX
        v = v ^ (v >> 16)                   # element swaps don't cancel
        # XOR-fold every non-ensemble axis by repeated halving (XLA has
        # no built-in xor reduction on every backend; a log-n cascade of
        # elementwise XORs lowers everywhere and computes the same fold).
        for axis in range(v.ndim - 1, 0, -1):
            while v.shape[axis] > 1:
                n = v.shape[axis]
                h = n // 2
                r = (jax.lax.slice_in_dim(v, 0, h, axis=axis)
                     ^ jax.lax.slice_in_dim(v, h, 2 * h, axis=axis))
                if n % 2:
                    r = jnp.concatenate(
                        [r, jax.lax.slice_in_dim(v, 2 * h, n, axis=axis)],
                        axis=axis)
                v = r
        return v.reshape(v.shape[0])

    oks, fp = [], None
    for leaf in jax.tree_util.tree_leaves(state):
        oks.append(leaf_ok(leaf))
        f = leaf_fp(leaf)
        fp = f if fp is None else (fp * _FP_LEAF) ^ f
    return jnp.all(jnp.stack(oks), axis=0), fp


@dataclasses.dataclass(frozen=True)
class ExchangeSchedule:
    """Resolved halo-exchange strategy of a distributed plan.

    `mode="packed"` is the stacked ragged exchange: every operand shares
    one flattened wire buffer per direction (at most one `ppermute` pair
    each; a side nothing rides is elided).  `rides` are the RESOLVED
    per-operand `(lo, hi)` depths straight from the op's registry
    declaration — e.g. the dycore's `wcon` at `(k·HALO, k·HALO + 1)` in x
    (the `+1` staggering column comes from the RIGHT neighbor only), or
    vadvc's lone `("wcon", (0, 0), (0, 1))` single-ppermute ride.
    `mode="per_operand"` is the legacy per-field exchange of the dycore's
    per-field/unfused variants."""

    mode: str                                   # "packed" | "per_operand"
    shards: Tuple[int, int]                     # (py, px)
    rides: Tuple[Tuple[str, Tuple[int, int], Tuple[int, int]], ...]
    wire_dtype: Optional[str]

    def _ride(self, operand: str):
        for name, dy, dx in self.rides:
            if name == operand:
                return dy, dx
        return None

    @property
    def depth_y(self) -> int:
        r = self._ride("fields")
        return r[0][1] if r else 0

    @property
    def depth_x(self) -> int:
        r = self._ride("fields")
        return r[1][0] if r else 0

    @property
    def wcon_depth_x(self) -> Optional[Tuple[int, int]]:
        r = self._ride("wcon")
        return r[1] if r else None

    def describe(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "mode": self.mode, "shards": list(self.shards),
            "rides": {name: {"depth_y": list(dy), "depth_x": list(dx)}
                      for name, dy, dx in self.rides},
            "depth_y": self.depth_y, "depth_x": self.depth_x,
            "wire_dtype": self.wire_dtype}
        if self.wcon_depth_x is not None:
            d["wcon_depth_x"] = list(self.wcon_depth_x)
        return d


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """The *how*: an immutable, fully-resolved execution strategy.

    Produced by `compile`; exposes `step(state)` (one round = `k_steps`
    timesteps), `run(state, steps)` (any step count; a shorter tail round
    is compiled for `steps % k_steps`), and `report()` (the
    machine-readable strategy benchmarks embed verbatim)."""

    program: StencilProgram
    variant: str                                # resolved, never "auto"
    k_steps: int                                # resolved int
    tile_ty: Optional[int]                      # None for unfused
    tile_plan: Optional[Any]                    # tiling.TilePlan
    local_grid: Tuple[int, int, int]            # per-shard (nz, ly, lx)
    compute_grid: Tuple[int, int, int]          # grid the kernel tiles over
    rides: Tuple[Tuple[str, Tuple[int, int], Tuple[int, int]], ...]
    interpret: bool
    prefetch_w: bool
    exchange: Optional[ExchangeSchedule]        # None on a single chip
    pallas_calls_per_round: int
    collectives_per_round: int
    mesh: Optional[Mesh] = dataclasses.field(default=None, repr=False,
                                             compare=False)
    mesh_axes: Tuple[Optional[str], str, str] = ("pod", "data", "model")
    _cache: dict = dataclasses.field(default_factory=dict, repr=False,
                                     compare=False)

    # -- public API ---------------------------------------------------------
    @property
    def op_def(self) -> StencilOpDef:
        return get_stencil_op(self.program.op)

    @property
    def hardware(self) -> str:
        """Spec name the plan's modeled numbers target (never None)."""
        return self.program.hardware or hwspec.default_spec_name()

    def hardware_spec(self) -> hwspec.HardwareSpec:
        return hwspec.load_spec(self.hardware)

    @property
    def distributed(self) -> bool:
        return self.mesh is not None

    @property
    def shards(self) -> Tuple[int, int]:
        return self.exchange.shards if self.exchange is not None else (1, 1)

    @property
    def state_spec(self) -> Optional[P]:
        """PartitionSpec for `domain.shard_state`; None on a single chip."""
        if self.mesh is None:
            return None
        ax_e, ax_y, ax_x = self.mesh_axes
        have_e = ax_e is not None and ax_e in self.mesh.axis_names
        return P(ax_e if have_e else None, None, ax_y, ax_x)

    def step(self, state: WeatherState) -> WeatherState:
        """Advance ONE round: `k_steps` timesteps in the plan's strategy."""
        self._check_state(state)
        return self._step_fn()(state)

    def run(self, state: WeatherState, steps: int) -> WeatherState:
        """Advance `steps` timesteps: `steps // k_steps` full rounds plus,
        when `steps % k_steps != 0`, one shorter TAIL round at
        `k' = steps mod k_steps` (a derived plan, compiled on demand) —
        no step count is rejected.  On a single chip the full rounds are
        one jitted scan whose carry `_round_carry` picks."""
        if not isinstance(steps, int) or steps < 0:
            raise ValueError(f"steps={steps!r} must be a non-negative int")
        self._check_state(state)
        self.kernels()
        with jax.profiler.TraceAnnotation(
                "plan.run", steps=steps, kernels=self._cache["kernel_names"],
                carry=self._round_carry()[0]):
            rounds, tail = divmod(steps, self.k_steps)
            if rounds:
                if self.mesh is None:
                    state = self._rounds_fn(rounds)(state)
                else:
                    # Deliberately a Python loop, not a scan: each round is
                    # one jitted shard_map program, which keeps run()
                    # composable with host-side work between rounds
                    # (checkpoints, I/O) and keeps the traced round — what
                    # the structural tests and report() describe — the
                    # unit of execution.
                    step = self._step_fn()
                    for _ in range(rounds):
                        state = step(state)
            if tail:
                state = self.round_plan(tail).step(state)
        return state

    def round_plan(self, k: int) -> "ExecutionPlan":
        """The plan that advances a round of exactly `k` timesteps: `self`
        when `k == k_steps`, else a derived plan for the shorter round
        (cached — this is `run()`'s ragged-TAIL machinery, public so a
        serving engine can retire ragged step counts at round boundaries
        through the exact same lowering a solo `run()` would use)."""
        if not isinstance(k, int) or not 1 <= k <= self.k_steps:
            raise ValueError(f"round_plan(k={k!r}): k must be an int in "
                             f"[1, k_steps={self.k_steps}]")
        if k == self.k_steps:
            return self
        return self._tail_plan(k)

    def kernels(self) -> Tuple[str, ...]:
        """The `name` of each Pallas kernel one round launches, in launch
        order, read off the traced round (`trace_stats.pallas_call_names`):
        the names the kernels carry in a device profile.  Traced once per
        plan, on its first `run` or `report()`."""
        names = self._cache.get("kernels")
        if names is None:
            p = self.program
            state = jax.eval_shape(lambda: zeros_state(
                p.grid_shape, p.ensemble, p.dtype, names=p.fields))
            names = trace_stats.pallas_call_names(
                jax.make_jaxpr(self._step_fn())(state))
            self._cache["kernels"] = names
            # `,` and `=` delimit a span's metadata entries in a profile.
            self._cache["kernel_names"] = ";".join(names)
        return names

    def report(self) -> Dict[str, Any]:
        """Machine-readable strategy: the resolved op + variant + tile + k
        + exchange, the op's declared footprint, the Pallas kernels a round
        launches (`kernels()`), the structural launch/collective counts per
        round (verifiable against a traced jaxpr via
        `trace_stats.assert_plan_structure`), and the modeled
        HBM-traffic / wire-byte / GFLOPS numbers.  Plain JSON-serializable
        types only — benchmarks embed it verbatim."""
        prog = self.program
        opdef = self.op_def
        rep: Dict[str, Any] = {
            "op": prog.op,
            "program": {
                "op": prog.op,
                "grid_shape": list(prog.grid_shape),
                "ensemble": prog.ensemble,
                "fields": list(prog.fields),
                "halo": prog.halo,
                "dtype": prog.dtype,
                "boundary": prog.boundary,
                "coeff": prog.coeff,
                "dt": prog.dt,
                "variant": prog.variant,
                "k_steps": prog.k_steps,
                "exchange_dtype": prog.exchange_dtype,
                "hardware": prog.hardware,
                # A PipelineProgram's chain: report()["program"] must
                # round-trip through StencilProgram.from_json like
                # to_json() does (serving checkpoints persist it).
                **({"stages": [st.describe()
                               for st in getattr(prog, "stages")]}
                   if getattr(prog, "stages", None) else {}),
            },
            "variant": self.variant,
            "k_steps": self.k_steps,
            "footprint": opdef.describe(prog.n_fields, self.k_steps),
            "tile": (None if self.tile_plan is None
                     else {"ty": self.tile_ty, **self.tile_plan.describe()}),
            "interpret": self.interpret,
            "prefetch_w": self.prefetch_w,
            "distributed": self.distributed,
            "mesh_axes": list(self.mesh_axes),
            "local_grid": list(self.local_grid),
            "compute_grid": list(self.compute_grid),
            "exchange": (None if self.exchange is None
                         else self.exchange.describe()),
            "kernels": list(self.kernels()),
            "rounds": dict(zip(("carry", "unroll"), self._round_carry())),
            "pallas_calls_per_round": self.pallas_calls_per_round,
            "collectives_per_round": self.collectives_per_round,
        }
        # The traffic model needs a tile; unfused plans have none, so model
        # at the tile the default variant WOULD resolve (recorded as
        # traffic_model_ty so the artifact is self-describing; cached — it
        # is an autotune sweep and report() is advertised as cheap).
        model_ty = self.tile_ty
        if model_ty is None:
            model_ty = self._cache.get("traffic_model_ty")
            if model_ty is None:
                # Resolve over the PHYSICAL grid (not the padded/folded
                # compute grid): the traffic model below is evaluated on
                # the physical grid, so the modeled tile must be a legal
                # window of it.
                tp = opdef.resolve_tile("whole_state", prog.grid_shape,
                                        prog.dtype, prog.n_fields,
                                        prog.ensemble, 1)
                model_ty = tp.tile[1]
                self._cache["traffic_model_ty"] = model_ty
        rep["traffic_model_ty"] = model_ty
        rep["traffic"] = opdef.traffic(self, model_ty)
        if (self.exchange is not None and self.exchange.mode == "packed"
                and opdef.exchange_model is not None):
            rep["exchange_model"] = opdef.exchange_model(self)
        else:
            rep["exchange_model"] = None
        # Modeled performance of the resolved tile plan on the program's
        # target hardware spec — the per-op GFLOPS / GFLOPS-per-watt axis
        # of the paper's two-kernel table.
        if self.tile_plan is not None:
            est = self._cache.get("perf_est")
            if est is None:
                est = perfmodel.estimate(self.tile_plan,
                                         spec=self.hardware_spec())
                self._cache["perf_est"] = est
            rep["model"] = {"time_us": est.time_s * 1e6,
                            "gflops": est.gflops,
                            "gflops_per_watt": est.gflops_per_watt,
                            "bottleneck": est.bottleneck,
                            "hardware": est.hardware,
                            "kernel_class": est.kernel_class,
                            "spec_fingerprint":
                                self.hardware_spec().fingerprint}
        else:
            rep["model"] = None
        rep["model_by_hardware"] = self.model_by_hardware()
        return rep

    def model_by_hardware(self, grid_shape: Optional[Tuple[int, int, int]]
                          = None) -> Dict[str, Any]:
        """The paper's cross-machine two-kernel table, modeled: for hdiff
        and vadvc (the paper's kernels) and every shipped hardware spec,
        re-tune the tile window FOR that machine's hierarchy and model
        time / GFLOPS / GFLOPS-per-watt under its spec, plus the modeled
        speedup over the POWER9 baseline.  `grid_shape` defaults to the
        program's grid (benchmarks evaluate it at the paper's domain);
        cached per grid — it is a handful of analytic autotune sweeps."""
        grid = tuple(int(g) for g in (grid_shape or self.program.grid_shape))
        cached = self._cache.get(("model_by_hardware", grid))
        if cached is not None:
            return cached
        spec_names = hwspec.available_specs()
        out: Dict[str, Any] = {
            "grid_shape": list(grid),
            "dtype": self.program.dtype,
            "baseline": "power9",
            "specs": {n: hwspec.load_spec(n).describe() for n in spec_names},
            "kernels": {},
        }
        for kname in ("hdiff", "vadvc"):
            try:
                ests = perfmodel.estimate_by_hardware(
                    autotune.get_op(kname), grid, self.program.dtype,
                    specs=spec_names)
            except ValueError:
                # No legal tile at this grid for this kernel (tiny smoke
                # grids): the table row is simply absent, never a crash.
                continue
            t_p9 = ests["power9"].time_s if "power9" in ests else 0.0
            row: Dict[str, Any] = {}
            for name, est in ests.items():
                row[name] = {
                    "time_us": est.time_s * 1e6,
                    "gflops": est.gflops,
                    "gflops_per_watt": est.gflops_per_watt,
                    "bottleneck": est.bottleneck,
                    "kernel_class": est.kernel_class,
                    "speedup_vs_power9": (t_p9 / est.time_s
                                          if est.time_s > 0 else 0.0),
                }
            out["kernels"][kname] = row
        self._cache[("model_by_hardware", grid)] = out
        return out

    # -- internals ----------------------------------------------------------
    def _check_state(self, state: WeatherState) -> None:
        if state.grid_shape != self.program.grid_shape:
            raise ValueError(
                f"state grid {state.grid_shape} does not match the "
                f"program's {self.program.grid_shape}; compile a plan for "
                f"this grid")
        if str(state.wcon.dtype) != self.program.dtype:
            raise ValueError(
                f"state dtype {state.wcon.dtype} does not match the "
                f"program's precision policy {self.program.dtype!r}")
        if (state.wcon.ndim == 4
                and int(state.wcon.shape[0]) != self.program.ensemble):
            raise ValueError(
                f"state ensemble {int(state.wcon.shape[0])} does not match "
                f"the program's ensemble={self.program.ensemble} (the "
                f"report() must describe what actually runs)")
        missing = [n for n in self.program.fields if n not in state.fields]
        if missing:
            raise ValueError(f"state is missing program fields {missing}")

    def _step_fn(self):
        fn = self._cache.get("step")
        if fn is None:
            fn = (_build_distributed_step(self) if self.mesh is not None
                  else _build_local_step(self))
            self._cache["step"] = fn
        return fn

    def _round_carry(self) -> Tuple[str, int, _sops.StackedRound]:
        """How `run()`'s single-chip round scan carries the state:
        `("stacked", 2, split)` where the op declares a stacked round for
        the plan's variant — the scan carries the kernel's own operands,
        stacked once per call, and unrolls by 2 so consecutive rounds
        ping-pong between two buffers instead of copying the kernel's
        output back into the carry — else `("dict", 1, split)`, the
        `WeatherState` itself around `plan.step`."""
        rc = self._cache.get("round_carry")
        if rc is None:
            opdef = self.op_def
            split = (opdef.stacked_round(self)
                     if self.mesh is None and opdef.stacked_round is not None
                     else None)
            if split is not None:
                rc = ("stacked", 2, split)
            else:
                step = self._step_fn()
                rc = ("dict", 1, _sops.StackedRound(
                    pack=lambda state: (state, ()),
                    step=lambda state, _: step(state),
                    unpack=lambda state, _: state))
            self._cache["round_carry"] = rc
        return rc

    def _rounds_fn(self, rounds: int):
        """Jitted scan of `rounds` full rounds (single-chip), cached per
        round count so repeated `run` calls don't re-trace the scan."""
        fn = self._cache.get(("rounds", rounds))
        if fn is None:
            _, unroll, split = self._round_carry()

            @_sops.jit_round(self.program.op, "rounds")
            def fn(state):
                carry, consts = split.pack(state)

                def body(c, _):
                    return split.step(c, consts), ()
                carry, _ = jax.lax.scan(body, carry, (), length=rounds,
                                        unroll=unroll)
                return split.unpack(carry, state)
            self._cache[("rounds", rounds)] = fn
        return fn

    def _tail_plan(self, k_tail: int) -> "ExecutionPlan":
        plan = self._cache.get(("tail", k_tail))
        if plan is None:
            prog = dataclasses.replace(self.program, variant="auto",
                                       k_steps=k_tail)
            ax_e, ax_y, ax_x = self.mesh_axes
            plan = compile(prog, mesh=self.mesh, ax_e=ax_e,
                           ax_y=ax_y, ax_x=ax_x,
                           interpret=self.interpret,
                           prefetch_w=self.prefetch_w)
            self._cache[("tail", k_tail)] = plan
        return plan


# ---------------------------------------------------------------------------
# The planner
# ---------------------------------------------------------------------------


def compile(program: StencilProgram, mesh: Optional[Mesh] = None, *,
            ax_e: Optional[str] = "pod", ax_y: str = "data",
            ax_x: str = "model", interpret: Optional[bool] = None,
            prefetch_w: Optional[bool] = None,
            tune: Optional[str] = None,
            _tile_ty: Optional[int] = None) -> ExecutionPlan:
    """Resolve `program`'s whole execution strategy once; return the plan.

    Works over any REGISTERED stencil op: the exchange schedule, the
    structural launch/collective counts, the k-step resolution, and the
    tile plan are all derived from the op's `StencilOpDef` declaration
    (footprint rides, flops, tile spaces, lowering hooks) — the planner
    has no per-op branches.

    With `mesh`, the plan shards y over `ax_y`, x over `ax_x`, the
    ensemble over `ax_e` when present (z always chip-local), and its step
    runs the distributed round: the op's packed halo exchange + the
    chip-local kernel + interior crop.  Overrides: `interpret` (default:
    auto — native Pallas on TPU, interpreter elsewhere) and `prefetch_w`
    (the dycore k-step kernel's double-buffered `w` DMA pipeline; default:
    on outside interpret mode).

    `tune` picks the tuning mode: None / `"model"` resolve the tile from
    the analytic model (the paper's "model-guided" mode); `"measure"`
    re-tunes the y-window EMPIRICALLY — each candidate plan is compiled
    and wall-clock timed on THIS process's jax backend (the paper's
    "auto-tuned" mode, `autotune.tune(measure=...)`) and the winner is
    persisted to an on-disk cache keyed on (program, hardware-spec
    fingerprint, backend), so a plan is measured once and every later
    process reuses the pick.  `_tile_ty` is the internal pin the measured
    path re-enters with."""
    if not isinstance(program, StencilProgram):
        raise TypeError(f"compile wants a StencilProgram, got "
                        f"{type(program).__name__}")
    if tune not in (None, "model", "measure"):
        raise ValueError(f"tune={tune!r}: expected None, 'model', or "
                         f"'measure'")
    with jax.profiler.TraceAnnotation("plan.compile", op=program.op,
                                      ensemble=program.ensemble):
        return _resolve(program, mesh, ax_e=ax_e, ax_y=ax_y, ax_x=ax_x,
                        interpret=interpret, prefetch_w=prefetch_w,
                        tune=tune, _tile_ty=_tile_ty)


def _resolve(program: StencilProgram, mesh: Optional[Mesh], *,
             ax_e: Optional[str], ax_y: str, ax_x: str,
             interpret: Optional[bool], prefetch_w: Optional[bool],
             tune: Optional[str], _tile_ty: Optional[int]) -> ExecutionPlan:
    """`compile`'s planner, after its argument checks."""
    opdef = get_stencil_op(program.op)
    nz, ny, nx = program.grid_shape
    nf = program.n_fields
    halo = opdef.halo
    if interpret is None:
        interpret = not on_tpu()

    if mesh is not None:
        for ax in (ax_y, ax_x):
            if ax not in mesh.axis_names:
                raise ValueError(f"mesh {dict(mesh.shape)} has no axis "
                                 f"{ax!r}")
        py, px = int(mesh.shape[ax_y]), int(mesh.shape[ax_x])
        if ny % py or nx % px:
            raise ValueError(f"grid (ny={ny}, nx={nx}) does not divide over "
                             f"(py={py}, px={px}) shards")
    else:
        py = px = 1
    ly, lx = ny // py, nx // px

    # --- steps-per-round: the communication-avoiding k (one resolver,
    # fed the OP'S declared flops/reach and footprint-driven wire model) ---
    k = program.k_steps
    if k == "auto":
        if ("kstep" not in opdef.variants
                or program.variant not in ("auto", "kstep") or mesh is None):
            # The op (or pinned variant) steps once per round, or there
            # are no collectives at all: nothing to amortize.
            k = 1
        else:
            def exchange_model(kk):
                return memmodel.packed_exchange_model(
                    program.grid_shape, program.dtype,
                    rides=opdef.memmodel_rides(nf), k=kk, shards=(py, px),
                    compute_halo=(kk * halo, kk * halo))
            if opdef.kstep_vmem_check is not None:
                # The op declares its OWN in-kernel k-step legality.
                vmem_check = opdef.kstep_vmem_check(program, (py, px))
            elif opdef.inkernel_kstep:
                vmem_check = None     # the fused dycore's default check
            else:
                vmem_check = lambda kk: None
            k = autotune.resolve_k_steps(
                program.grid_shape, program.dtype, (py, px), n_fields=nf,
                halo=halo, flops_per_point=opdef.flops_per_point,
                exchange_model=exchange_model, vmem_check=vmem_check)

    # --- execution variant ---
    variant = program.variant
    if variant == "auto":
        variant = "kstep" if k > 1 else "whole_state"
    if variant == "kstep" and k == 1:
        variant = "whole_state"    # k resolved to 1: same round, one step
    if k > 1 and variant != "kstep":
        raise ValueError(f"k_steps={k} requires the k-step round "
                         f"(variant {variant!r} steps one at a time)")
    if (program.exchange_dtype is not None
            and variant not in opdef.packed_variants):
        raise ValueError("exchange_dtype requires a packed (stacked) "
                         "exchange variant of op "
                         f"{program.op!r} ({opdef.packed_variants})")

    # --- exchange schedule + the grid the kernel actually tiles over,
    # both derived from the op's declared footprint ---
    rides = opdef.resolved_rides(k)
    hy = hx = k * halo
    pads = (mesh is not None) or opdef.pads_single_chip
    compute_grid = ((nz, ly + 2 * hy, lx + 2 * hx) if pads
                    else program.grid_shape)
    if pads:
        # A ride deeper than the local slab would need data from beyond
        # the adjacent neighbor (or, single-chip, wrap more than one
        # period) — refuse at compile time, loudly.
        for name, dy, dx in rides:
            if max(dy) > ly or max(dx) > lx:
                raise ValueError(
                    f"k_steps={k} needs a ({max(dy)}, {max(dx)})-deep halo "
                    f"for {name!r} but the local slab is only ({ly}, {lx}); "
                    f"use fewer shards, a bigger grid, or a smaller "
                    f"k_steps")
    exchange = None
    if mesh is not None:
        if variant in opdef.packed_variants:
            exchange = ExchangeSchedule(mode="packed", shards=(py, px),
                                        rides=rides,
                                        wire_dtype=program.exchange_dtype)
        else:
            # Legacy per-operand exchange (dycore per_field/unfused): one
            # exchange per operand at the per-step reach.
            exchange = ExchangeSchedule(mode="per_operand", shards=(py, px),
                                        rides=opdef.resolved_rides(1),
                                        wire_dtype=None)
            compute_grid = (nz, ly + 2 * halo, lx + 2 * halo)

    # --- tile plan: the op's own resolver over its registered spaces ---
    tile_plan = opdef.resolve_tile(variant, compute_grid, program.dtype,
                                   nf, program.ensemble, k)
    if _tile_ty is not None and tile_plan is not None:
        # The measured-tuning pin: same plan, y-window overridden by the
        # empirical winner (always a candidate of the same tile space).
        tile_plan = dataclasses.replace(
            tile_plan, tile=(tile_plan.tile[0], int(_tile_ty),
                             tile_plan.tile[2]))
    ty = tile_plan.tile[1] if tile_plan is not None else None

    # --- structural costs per round (trace-verifiable, see trace_stats) ---
    pallas_calls = opdef.pallas_calls(variant, nf, k)
    if mesh is None:
        collectives = 0
    else:
        collectives = (opdef.collectives(variant, nf, py, px, k)
                       if opdef.collectives is not None else None)
        if collectives is None:
            collectives = opdef.generic_collectives(py, px, k)

    if prefetch_w is None:
        # The `w` prefetch DMAs whole rows; Mosaic slices them only
        # lane-aligned.  Distributed slabs are padded to whole lanes; a
        # single-chip periodic domain is not, so it needs nx % 128 == 0.
        prefetch_w = not interpret and (mesh is not None
                                        or nx % tiling.LANES == 0)

    plan = ExecutionPlan(
        program=program, variant=variant, k_steps=k, tile_ty=ty,
        tile_plan=tile_plan, local_grid=(nz, ly, lx),
        compute_grid=compute_grid, rides=rides, interpret=interpret,
        prefetch_w=prefetch_w, exchange=exchange,
        pallas_calls_per_round=pallas_calls,
        collectives_per_round=collectives, mesh=mesh,
        mesh_axes=(ax_e, ax_y, ax_x))
    if tune == "measure" and _tile_ty is None:
        plan = _measured_retune(plan, program, mesh, ax_e=ax_e, ax_y=ax_y,
                                ax_x=ax_x, interpret=interpret,
                                prefetch_w=prefetch_w)
    return plan


def _measured_retune(plan: ExecutionPlan, program: StencilProgram,
                     mesh: Optional[Mesh], *, ax_e, ax_y, ax_x,
                     interpret, prefetch_w) -> ExecutionPlan:
    """The `tune="measure"` path: empirically pick the y-window.

    The candidate set is the analytic tuner's own (the op's tile space at
    the plan's compute grid), scored by `autotune.tune(measure=...)` with
    a wall-clock measure callable: a candidate that keeps the kernel's
    streamed axes whole (same z/x window as the resolved plan — the
    y-window is the lowering's one pinnable knob) is compiled with its
    `ty` pinned and its round timed on this process's backend; any other
    candidate scores `inf`.  The winning ty is persisted keyed on
    (program cache key + shards, spec fingerprint, backend) — a second
    process compiles the winner directly, measuring nothing."""
    if plan.tile_plan is None:
        return plan   # oracle variant: no tile to tune
    spec = plan.hardware_spec()
    backend = jax.default_backend()
    shards = plan.shards
    cache_key = autotune.tune_cache_key(
        (plan_cache_key(program), shards), spec, backend)
    entry = autotune.tune_cache_load(cache_key)
    if entry is None:
        entry = _measure_tile_candidates(plan, program, mesh, ax_e=ax_e,
                                         ax_y=ax_y, ax_x=ax_x,
                                         interpret=interpret,
                                         prefetch_w=prefetch_w)
        entry.update({"backend": backend, "spec": spec.name,
                      "spec_fingerprint": spec.fingerprint,
                      "k_steps": plan.k_steps})
        autotune.tune_cache_store(cache_key, entry)
    ty = entry.get("tile_ty")
    if ty is None or int(ty) == plan.tile_ty:
        return plan
    return compile(program, mesh=mesh, ax_e=ax_e, ax_y=ax_y, ax_x=ax_x,
                   interpret=interpret, prefetch_w=prefetch_w,
                   _tile_ty=int(ty))


def _measure_tile_candidates(plan: ExecutionPlan, program: StencilProgram,
                             mesh: Optional[Mesh], *, ax_e, ax_y, ax_x,
                             interpret, prefetch_w,
                             max_measured: int = 8) -> Dict[str, Any]:
    """Wall-clock-score the tile candidates; returns the cache entry."""
    base = plan.tile_plan
    state = zeros_state(program.grid_shape, program.ensemble,
                        program.dtype, names=program.fields)
    timed: Dict[int, float] = {}
    # Distinct measurable ty values, analytically ordered; cap how many we
    # actually time (each costs a compile + a few steps).
    cands = tiling.candidate_tiles(base.op, base.grid_shape, program.dtype,
                                   plan.hardware_spec().hierarchy())
    ny = base.grid_shape[1]
    align = tiling.sublanes(program.dtype)
    ty_pool = sorted({p.tile[1] for p in cands
                      if p.tile[0] == base.tile[0]
                      and p.tile[2] == base.tile[2]
                      and tiling.snap_to_divisor(p.tile[1], ny,
                                                 align=align) == p.tile[1]})
    if len(ty_pool) > max_measured:
        stride = len(ty_pool) / max_measured
        ty_pool = sorted({ty_pool[int(i * stride)]
                          for i in range(max_measured)})
    allowed = set(ty_pool)

    def measure(cand: tiling.TilePlan) -> float:
        ty = cand.tile[1]
        if (cand.tile[0] != base.tile[0] or cand.tile[2] != base.tile[2]
                or ty not in allowed):
            return math.inf
        if ty not in timed:
            try:
                cp = compile(program, mesh=mesh, ax_e=ax_e, ax_y=ax_y,
                             ax_x=ax_x, interpret=interpret,
                             prefetch_w=prefetch_w, _tile_ty=ty)

                def run_once():
                    jax.block_until_ready(cp.step(state))
                timed[ty] = autotune.measure_walltime(run_once)
            except Exception:   # noqa: BLE001 — kernel rejects this window
                timed[ty] = math.inf
        return timed[ty]

    try:
        tuned = autotune.tune(base.op, base.grid_shape, program.dtype,
                              spec=plan.hardware_spec(), measure=measure)
        best_ty = int(tuned.plan.tile[1])
        best_s = timed.get(best_ty)
    except ValueError:
        best_ty, best_s = None, None
    if best_s is None or not math.isfinite(best_s):
        best_ty, best_s = None, None      # nothing ran: keep analytic pick
    return {"tile_ty": plan.tile_ty if best_ty is None else best_ty,
            "measured_s": best_s,
            "measured": {str(k): v for k, v in sorted(timed.items())}}


# The historical dycore entry point: same planner, op defaults to "dycore".
compile_dycore = compile


def reference_program(program: StencilProgram) -> StencilProgram:
    """`program` rebound to its op's REFERENCE lowering: the unfused
    (oracle) variant when the op declares one, step-at-a-time rounds, no
    wire compression — the maximally-conservative availability fallback.
    Numerics are the same physics but NOT guaranteed bitwise-equal to the
    fused variants (different loop structure); callers that degrade this
    far must surface it (see `compile_with_fallback`)."""
    opdef = get_stencil_op(program.op)
    ref = "unfused" if "unfused" in opdef.variants else opdef.variants[0]
    return dataclasses.replace(program, variant=ref, k_steps=1,
                               exchange_dtype=None)


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU: Pallas runs natively and
    no path may fall back to the interpreter."""
    return jax.default_backend() == "tpu"


def compile_with_fallback(program: StencilProgram,
                          mesh: Optional[Mesh] = None, *,
                          ax_e: Optional[str] = "pod", ax_y: str = "data",
                          ax_x: str = "model",
                          interpret: Optional[bool] = None,
                          prefetch_w: Optional[bool] = None,
                          attempt_hook=None
                          ) -> Tuple[ExecutionPlan, Optional[str], list]:
    """`compile` with graceful degradation: a retry chain over

      1. ``native``    — the program exactly as asked (Pallas lowering,
         `interpret` as given / auto),
      2. ``interpret`` — the SAME plan forced through the Pallas
         interpreter (survives backend codegen/lowering failures; on a
         backend where auto-interpret already resolves True this is the
         identical plan, so results stay bit-identical).  Skipped on a
         TPU: there a kernel the chip refuses is a defect to surface, not
         one to hide behind the interpreter,
      3. ``reference`` — `reference_program(program)`: the op's unfused
         oracle lowering, one step per round (availability over
         bit-identity — the last resort; callers count it).

    Returns ``(plan, fallback, errors)``: `fallback` is None when the
    native attempt won, else the winning stage name; `errors` lists
    ``(stage, repr(exc))`` for every failed attempt.  Raises the LAST
    error only if every stage fails.  `attempt_hook(program, stage)` is
    the fault-injection seam — `testing.faults.FaultInjector.on_compile`
    plugs in here to rehearse lowering failures deterministically."""
    attempts = [("native", program, {"interpret": interpret})]
    if not on_tpu():
        attempts.append(("interpret", program, {"interpret": True}))
    attempts.append(("reference", reference_program(program),
                     {"interpret": interpret}))
    errors: list = []
    for stage, prog, kw in attempts:
        try:
            if attempt_hook is not None:
                attempt_hook(prog, stage)
            plan = compile(prog, mesh=mesh, ax_e=ax_e, ax_y=ax_y, ax_x=ax_x,
                           prefetch_w=prefetch_w, **kw)
            return plan, (None if stage == "native" else stage), errors
        except Exception as e:  # noqa: BLE001 — any lowering failure degrades
            errors.append((stage, repr(e)))
            last = e
    raise RuntimeError(
        f"compile fallback chain exhausted for op={program.op!r}: "
        f"{errors}") from last


# ---------------------------------------------------------------------------
# Lowering: plan -> step callable (shared shard_map/jit scaffolding; the
# per-op compute comes from the registry's lowering hooks)
# ---------------------------------------------------------------------------


def _build_local_step(plan: ExecutionPlan):
    """Single-chip lowering.  Ops with a dedicated periodic-domain path
    (the dycore's kernels wrap in-kernel) supply `build_local_step`;
    otherwise the op's shard-local round runs directly — its packed
    exchange degenerates to wrap padding on one shard.  Either way the
    round is ONE jax.jit dispatch."""
    opdef = plan.op_def
    if opdef.build_local_step is not None:
        return opdef.build_local_step(plan)
    local = opdef.build_shard_local(plan)

    @_sops.jit_round(plan.program.op)
    def step(state: WeatherState) -> WeatherState:
        new_fields, new_stage = local(state.fields, state.wcon,
                                      state.tens, state.stage_tens)
        return WeatherState(fields=new_fields, wcon=state.wcon,
                            tens=state.tens, stage_tens=new_stage)
    return step


def _build_distributed_step(plan: ExecutionPlan):
    """Distributed lowering: the op's chip-local round (halo exchange per
    the plan's footprint-derived schedule + local kernel + interior crop),
    shard_mapped over the mesh.

    See `weather/domain.py` for the exchange primitives and the design
    rationale (NERO's scale-out story)."""
    local_step = plan.op_def.build_shard_local(plan)
    spec = plan.state_spec
    sharded = _shard_map(local_step, plan.mesh,
                         in_specs=(spec, spec, spec, spec),
                         out_specs=(spec, spec))

    @_sops.jit_round(plan.program.op)
    def step(state: WeatherState) -> WeatherState:
        new_fields, new_stage = sharded(state.fields, state.wcon,
                                        state.tens, state.stage_tens)
        return WeatherState(fields=new_fields, wcon=state.wcon,
                            tens=state.tens, stage_tens=new_stage)

    return step

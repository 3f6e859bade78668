"""Forecast-as-a-service: a continuous-batching ensemble serving engine.

An operational forecast service runs the SAME compiled stencil programs
for many concurrent consumers — requests differ only in initial state and
step count, over a handful of plans.  This engine is that service layer
over the plan API (`weather/program.py`):

* **Plan cache, compile once / serve forever.**  Every request names a
  `StencilProgram` (ensemble 1 — one forecast).  The engine canonicalizes
  it with `program.plan_cache_key(prog, ensemble=slots)` and compiles at
  most ONE `ExecutionPlan` per distinct program, shared by every request
  that ever arrives for it.

* **Continuous batching into the ensemble axis.**  The `(e, ...)` fold is
  already the batch dimension of every kernel, so admission is a slot
  scatter (`ensemble_slot_assign`) into a zero-initialized batch state,
  and each engine round is ONE `plan.step` launch for up to `slots`
  concurrent forecasts.  Finished slots retire at round boundaries and
  are backfilled from the queue — the batch never drains to serve a
  straggler.

* **Bit-identical to solo runs.**  The correctness contract (verified by
  `tests/test_forecast_engine.py`'s property harness) is that serving a
  request batched is bit-identical to `compile(program).run(state,
  steps)` solo.  Two facts make that hold: ensemble members are computed
  independently (no cross-slot arithmetic, tile resolution per-member
  invariant), and the engine advances every request through EXACTLY the
  round sequence a solo `run()` would — `floor(steps/k)` full rounds plus
  one ragged tail of `steps mod k`, via the plan's own
  `round_plan(k')` tail machinery.  When ragged step counts force a
  shorter round than some co-batched slot's next canonical part, that
  slot runs the round anyway (slots advance together) but is ROLLED BACK
  (`ensemble_slot_select`) and not credited, so its realized sequence
  never deviates.  With `k_steps == 1` (every single-chip auto plan)
  rounds are single steps and no rollback ever happens.

* **Host I/O overlaps device compute.**  `submit` stages request arrays
  onto the device immediately (`jax.device_put` is async), so by the time
  a slot frees the admission wave's data is already resident; the slot
  scatter donates the old batch buffer on backends that support donation.
  Retirement reads back exactly one slot.

* **Warm restarts, on ANY mesh.**  `checkpoint()` persists the whole
  engine — batched in-flight state (gathered unsharded-logical), queue,
  finished results, per-request bookkeeping, and each lane's RESOLVED
  round strategy — through `ckpt.save_tree`; `ForecastEngine.restore()`
  resumes mid-forecast in a fresh process on whatever mesh it is given:
  a checkpoint written single-chip restores onto 4 devices and vice
  versa (lane batches reshard through the new plan's `state_spec`, plans
  recompile through the plan cache — still compile-once per mesh shape).
  The persisted (variant, k_steps) pin keeps every in-flight request's
  canonical round sequence intact across the transition; see
  docs/robustness.md for the mesh-compatibility matrix of which
  transitions additionally preserve exact bits.  When the newest
  checkpoint is corrupt, restore-from-latest falls back to the previous
  valid one instead of dying.

* **Supervised, safe to run unattended.**  One shared batch means one
  poisoned request could take down every co-scheduled forecast — so the
  engine supervises itself (docs/robustness.md):

  - *Validity guards*: at every round boundary a cheap fused NaN/Inf +
    bounds reduction (`program.slot_validity`) checks every slot; an
    invalid slot is QUARANTINED — its request returns `status="failed"`
    with a per-field diagnosis, the slot is re-zeroed (zeros are a
    stencil fixed point) and backfills from the queue — while every
    healthy slot keeps its exact bits (the guard only reads).
  - *Fingerprint guards*: the same fused pass (`program.slot_guard`)
    digests every slot's exact bits into a sharding-invariant uint32.
    Slots that did NOT advance a round — rolled-back and idle slots —
    must keep their digest bit-for-bit; a mismatch is cross-device/shard
    divergence (a corrupted halo wire buffer, silent per-shard rot) that
    NaN/magnitude checks can never see, caught at the round boundary
    where it occurred.  Divergent in-flight slots quarantine with a
    `fingerprint_divergence` diagnosis; divergent idle slots are
    scrubbed.
  - *Mesh failover*: on a persistent device loss, instead of failing the
    lane the engine rebuilds a mesh from the surviving devices
    (`domain.failover_meshes`, preferring shapes that keep every
    sharded axis sharded — the bitwise-safe transitions), recompiles the
    plans (pinned round depth), reshards every lane's pre-round state,
    and RERUNS the interrupted round — every in-flight request resumes
    from the last round boundary; `stats()` records `mesh_failovers`,
    `recovery_rounds`, `requests_preserved`, and a per-failover detail
    list.
  - *Round deadline watchdog*: `round_deadline_s` bounds each round
    attempt's wall clock; a straggling/hung collective counts as a
    failed attempt and goes through the same retry/degrade/failover
    escalation instead of wedging the engine.
  - *Graceful degradation*: plan compilation goes through
    `program.compile_with_fallback` (native → interpret → reference
    lowering); a failed round retries with exponential backoff, then
    degrades the plan, then fails only that lane's in-flight requests
    with a diagnosis — never the whole engine.
  - *Backpressure + deadlines*: `max_queue` bounds the queue (`submit`
    raises `QueueFullError` instead of accepting unbounded work);
    per-request `deadline_s` expires stale work at round boundaries.
  - *Watchdog*: `ckpt_every_rounds=N` auto-checkpoints every N rounds so
    a crash resumes from the last round boundary bitwise-equal to an
    uninterrupted run.
  - *Rehearsed in CI*: every one of these paths is driven
    deterministically by `repro.testing.faults.FaultInjector` (the
    engine's `fault_injector` hook) in the chaos test suite.

See docs/serving.md for the lifecycle diagrams and BENCH_serve.json for
the latency/occupancy numbers under synthetic load.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ckpt
from repro.weather import domain as _domain
from repro.weather import fields as _fields
from repro.weather import program as _wprog
from repro.weather.fields import WeatherState

__all__ = ["ForecastRequest", "ForecastResult", "ForecastEngine",
           "QueueFullError", "RoundDeadlineError", "STATUSES"]

# Result statuses (see docs/serving.md for the full table):
#   ok       — served; state is bit-identical to the solo run
#   failed   — quarantined by the validity guard or a persistent round
#              failure; `diagnosis` says why, `state` is the last state
#   expired  — per-request deadline passed before completion
STATUSES = ("ok", "failed", "expired")


class QueueFullError(RuntimeError):
    """`submit()` refused a request: the bounded queue is full.  This is
    explicit backpressure — retry later or raise `max_queue`; silently
    buffering unbounded work is how a service dies of memory instead."""


class RoundDeadlineError(RuntimeError):
    """A round attempt exceeded `round_deadline_s` — a straggling or hung
    collective.  Raised inside the supervised retry scope so it escalates
    through the same retry → degrade → failover ladder as any other round
    failure instead of wedging the engine."""


@dataclasses.dataclass
class ForecastRequest:
    """One forecast: a program (the *what*, ensemble 1), its initial
    state ((1, nz, ny, nx) leaves), and how many timesteps to advance."""

    program: _wprog.StencilProgram
    state: WeatherState
    steps: int
    rid: Optional[int] = None                   # assigned by submit()
    deadline_s: Optional[float] = None          # wall-clock budget from submit

    def validate(self) -> None:
        if self.deadline_s is not None and not self.deadline_s > 0:
            raise ValueError(f"deadline_s={self.deadline_s!r} must be a "
                             f"positive number of seconds (or None)")
        if self.program.ensemble != 1:
            raise ValueError(f"a request is ONE forecast: program.ensemble "
                             f"must be 1, got {self.program.ensemble}")
        if not isinstance(self.steps, int) or self.steps < 0:
            raise ValueError(f"steps={self.steps!r} must be a "
                             f"non-negative int")
        if self.state.grid_shape != self.program.grid_shape:
            raise ValueError(f"state grid {self.state.grid_shape} != "
                             f"program grid {self.program.grid_shape}")
        if str(self.state.wcon.dtype) != self.program.dtype:
            raise ValueError(f"state dtype {self.state.wcon.dtype} != "
                             f"program dtype {self.program.dtype}")
        if set(self.state.fields) != set(self.program.fields):
            raise ValueError(f"state fields {sorted(self.state.fields)} != "
                             f"program fields {sorted(self.program.fields)}")
        if int(self.state.wcon.shape[0]) != 1:
            raise ValueError("request state must have a leading ensemble "
                             "dim of 1")


@dataclasses.dataclass
class ForecastResult:
    """A finished forecast: the final state plus honest per-request
    accounting — `latency_s` is THIS request's admit-to-finish wall time
    (not its wave's), `queue_wait_s` the time it sat unadmitted."""

    rid: int
    program: _wprog.StencilProgram
    state: WeatherState                         # (1, ...) leaves, host-side
    steps: int
    latency_s: float
    queue_wait_s: float
    rounds: int
    status: str = "ok"                          # one of STATUSES
    steps_done: Optional[int] = None            # == steps when status=="ok"
    diagnosis: Optional[Dict[str, Any]] = None  # why, when status != "ok"

    @property
    def ok(self) -> bool:
        return self.status == "ok"


@dataclasses.dataclass
class _Slot:
    rid: int
    remaining: int
    steps: int
    admit_t: float
    queue_wait_s: float
    rounds: int = 0
    deadline_s: Optional[float] = None

    @property
    def submit_t(self) -> float:
        return self.admit_t - self.queue_wait_s


@dataclasses.dataclass
class _Lane:
    """One plan's batch: all slots share the lane's compiled plan."""

    key: _wprog.StencilProgram                  # canonical, ensemble=slots
    batch: WeatherState                         # (slots, nz, ny, nx) leaves
    slots: List[Optional[_Slot]]
    # Per-slot content digests recorded at round boundaries (slot index ->
    # uint32 as int).  Sharding-invariant, so they survive a failover
    # reshard and keep guarding across it.  Entries are dropped whenever a
    # slot's bits legitimately get new content (admit, scrub).
    fps: Dict[int, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _Pending:
    request: ForecastRequest
    submit_t: float
    counted: bool = False       # plan-cache hit/miss recorded once only


class ForecastEngine:
    """Continuous-batching forecast service over cached ExecutionPlans.

    `submit()` enqueues (and stages arrays onto the device), `pump()`
    admits + advances every busy lane one round, `drain()` pumps until
    idle and returns `{rid: ForecastResult}`.  `checkpoint()` /
    `ForecastEngine.restore()` persist and resume the warm engine."""

    def __init__(self, slots: int = 4, mesh=None,
                 interpret: Optional[bool] = None, ax_e: str = "pod",
                 ax_y: str = "data", ax_x: str = "model",
                 ckpt_dir: Optional[str] = None, ckpt_keep: int = 3,
                 max_queue: Optional[int] = None, guard: bool = True,
                 guard_limit: float = 1e6,
                 ckpt_every_rounds: Optional[int] = None,
                 max_round_retries: int = 2, retry_backoff_s: float = 0.05,
                 fault_injector=None, failover: bool = True,
                 round_deadline_s: Optional[float] = None):
        if slots < 1:
            raise ValueError(f"slots={slots} must be >= 1")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue={max_queue} must be >= 1 (or None "
                             f"for unbounded)")
        self.slots = slots
        self.mesh = mesh
        self.interpret = interpret
        self.mesh_axes = (ax_e, ax_y, ax_x)
        self.ckpt_dir = ckpt_dir
        self.ckpt_keep = ckpt_keep
        self.max_queue = max_queue
        self.guard = guard
        self.guard_limit = float(guard_limit)
        self.ckpt_every_rounds = ckpt_every_rounds
        self.max_round_retries = max_round_retries
        self.retry_backoff_s = retry_backoff_s
        self.fault_injector = fault_injector
        self.failover = failover
        self.round_deadline_s = round_deadline_s

        self._queue: collections.deque[_Pending] = collections.deque()
        self._lanes: Dict[_wprog.StencilProgram, _Lane] = {}
        self._plans: Dict[_wprog.StencilProgram, _wprog.ExecutionPlan] = {}
        self._fallbacks: Dict[_wprog.StencilProgram, Dict[str, Any]] = {}
        # First-resolution (variant, k_steps) per program key.  A lane's
        # canonical round sequence is fixed the moment its plan first
        # compiles; recompiles on a DIFFERENT mesh (failover, elastic
        # restore) re-pin the same round depth so every in-flight
        # request's realized [k, ..., k, tail] sequence — and therefore
        # its bit-identity contract — survives the mesh change.
        self._pinned: Dict[_wprog.StencilProgram, Dict[str, Any]] = {}
        self._failovers: List[Dict[str, Any]] = []
        self._results: Dict[int, ForecastResult] = {}
        self._next_rid = 0
        self._ckpt_step = 0
        self._last_ckpt_round = 0
        self._stats = {"plan_cache_hits": 0, "plan_cache_misses": 0,
                       "rounds": 0, "admitted": 0, "completed": 0,
                       "rolled_back_slot_rounds": 0,
                       "occupancy_sum": 0.0, "occupancy_samples": 0,
                       "quarantined": 0, "scrubbed_idle_slots": 0,
                       "round_retries": 0, "lane_failures": 0,
                       "fallback_compiles": 0, "rejected": 0,
                       "deadline_expired": 0, "watchdog_checkpoints": 0,
                       "mesh_failovers": 0, "recovery_rounds": 0,
                       "requests_preserved": 0, "fingerprint_divergence": 0,
                       "round_deadline_hits": 0, "plan_repins": 0}
        # Donating the pre-admission batch buffer lets XLA reuse it for
        # the scattered batch; CPU has no donation (it would only warn).
        donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
        self._assign = jax.jit(_wprog.ensemble_slot_assign,
                               donate_argnums=donate)

    # -- public API ---------------------------------------------------------
    def submit(self, request: ForecastRequest) -> int:
        """Enqueue one forecast; returns its rid.  The initial state is
        device_put NOW (async) so admission later is a device-side
        scatter — staging hides behind whatever round is running.

        Raises `QueueFullError` when `max_queue` is set and the queue is
        at capacity — explicit backpressure, not silent buffering."""
        request.validate()
        if (self.max_queue is not None
                and len(self._queue) >= self.max_queue):
            self._stats["rejected"] += 1
            raise QueueFullError(
                f"queue is full ({len(self._queue)}/{self.max_queue} "
                f"pending, slots={self.slots}): the engine is saturated — "
                f"retry after a pump()/drain(), shed load upstream, or "
                f"raise max_queue")
        if request.rid is None:
            request.rid = self._next_rid
        self._next_rid = max(self._next_rid, request.rid) + 1
        request.state = jax.device_put(request.state)
        self._queue.append(_Pending(request, time.perf_counter()))
        return request.rid

    def has_work(self) -> bool:
        return bool(self._queue) or any(
            any(s is not None for s in lane.slots)
            for lane in self._lanes.values())

    def pump(self) -> bool:
        """Admit whatever fits, advance every busy lane ONE round, retire
        finished slots.  Returns `has_work()`.  With `ckpt_every_rounds`
        set (and a ckpt_dir), the watchdog auto-checkpoints at the pump
        boundary — every lane sits at a round boundary there, so a crash
        resumes bitwise-equal to an uninterrupted run."""
        self._admit()
        for lane in self._lanes.values():
            if any(s is not None for s in lane.slots):
                self._round(lane)
        if (self.ckpt_every_rounds and self.ckpt_dir is not None
                and self._stats["rounds"] - self._last_ckpt_round
                >= self.ckpt_every_rounds):
            self.checkpoint()
            self._last_ckpt_round = self._stats["rounds"]
            self._stats["watchdog_checkpoints"] += 1
        return self.has_work()

    def drain(self) -> Dict[int, ForecastResult]:
        """Pump until idle; returns ALL results finished so far."""
        while self.pump():
            pass
        return dict(self._results)

    @property
    def results(self) -> Dict[int, ForecastResult]:
        return dict(self._results)

    def stats(self) -> Dict[str, Any]:
        """Service counters: plan-cache hit rate, mean batch occupancy
        (active slots / slots over lane-rounds), rounds/admissions."""
        s = dict(self._stats)
        lookups = s["plan_cache_hits"] + s["plan_cache_misses"]
        s["plan_cache_hit_rate"] = (
            s["plan_cache_hits"] / lookups if lookups else None)
        s["occupancy"] = (s["occupancy_sum"] / s["occupancy_samples"]
                          if s["occupancy_samples"] else 0.0)
        s["plans_cached"] = len(self._plans)
        s["queued"] = len(self._queue)
        s["active"] = sum(sum(sl is not None for sl in lane.slots)
                          for lane in self._lanes.values())
        s["failed"] = sum(1 for r in self._results.values()
                          if r.status == "failed")
        s["expired"] = sum(1 for r in self._results.values()
                           if r.status == "expired")
        s["plan_fallbacks"] = {k.op: v["stage"]
                               for k, v in self._fallbacks.items()}
        s["failovers"] = [dict(f) for f in self._failovers]
        s["mesh_devices"] = (None if self.mesh is None
                             else [int(d.id) for d in
                                   self.mesh.devices.flat])
        return s

    # -- scheduling ---------------------------------------------------------
    def _plan_for(self, key: _wprog.StencilProgram) -> _wprog.ExecutionPlan:
        plan = self._plans.get(key)
        if plan is None:
            ax_e, ax_y, ax_x = self.mesh_axes
            inj = self.fault_injector
            prog = key
            pinned = self._pinned.get(key)
            if pinned is not None:
                # Recompiling an already-served program (failover/elastic
                # restore): pin the FIRST resolution's round strategy so
                # in-flight canonical round sequences stay intact.  If the
                # pinned depth cannot compile on this mesh (e.g. a deep k
                # on a tiny shard), fall back to re-resolving — requests
                # still complete, bit-identity becomes best-effort, and
                # `plan_repins` records that it happened.
                prog = dataclasses.replace(key, variant=pinned["variant"],
                                           k_steps=pinned["k_steps"])
                try:
                    _wprog.compile(prog, mesh=self.mesh, ax_e=ax_e,
                                   ax_y=ax_y, ax_x=ax_x,
                                   interpret=self.interpret)
                except Exception:  # noqa: BLE001 — planner rejection
                    self._stats["plan_repins"] += 1
                    prog = key
            # Compile through the fallback chain (native -> interpret ->
            # reference lowering), via the module so a test spy on
            # repro.weather.program.compile observes every compilation.
            plan, fallback, errors = _wprog.compile_with_fallback(
                prog, mesh=self.mesh, ax_e=ax_e, ax_y=ax_y, ax_x=ax_x,
                interpret=self.interpret,
                attempt_hook=inj.on_compile if inj is not None else None)
            if fallback is not None:
                self._stats["fallback_compiles"] += 1
                self._fallbacks[key] = {"stage": fallback, "errors": errors}
            self._plans[key] = plan
            self._pinned.setdefault(
                key, {"variant": plan.variant, "k_steps": plan.k_steps})
        return plan

    def _lane_for(self, key: _wprog.StencilProgram) -> _Lane:
        lane = self._lanes.get(key)
        if lane is None:
            batch = _fields.zeros_state(key.grid_shape, ensemble=self.slots,
                                        dtype=key.dtype, names=key.fields)
            if self.mesh is not None:
                batch = _domain.shard_state(
                    batch, self.mesh, self._plan_for(key).state_spec)
            lane = _Lane(key=key, batch=batch,
                         slots=[None] * self.slots)
            self._lanes[key] = lane
        return lane

    def _admit(self) -> None:
        """FIFO admission: fill free slots per lane; a lane with no free
        slot does not block requests bound for other lanes.  All slots
        admitted to one lane this wave go in as ONE scatter."""
        now = time.perf_counter()
        waves: Dict[_wprog.StencilProgram,
                    List[Tuple[int, _Pending]]] = {}
        keep: collections.deque[_Pending] = collections.deque()
        free: Dict[_wprog.StencilProgram, List[int]] = {}
        for pend in self._queue:
            req = pend.request
            if (req.deadline_s is not None
                    and now - pend.submit_t > req.deadline_s):
                # Expired while queued: serving it now would waste a slot
                # on an answer nobody is waiting for.
                self._stats["deadline_expired"] += 1
                self._finish(req.rid, req.program,
                             jax.tree_util.tree_map(np.asarray, req.state),
                             steps=req.steps, admit_t=now,
                             queue_wait_s=now - pend.submit_t, rounds=0,
                             status="expired", steps_done=0,
                             diagnosis={"reason": "deadline_exceeded",
                                        "deadline_s": req.deadline_s,
                                        "waited_s": now - pend.submit_t,
                                        "where": "queue"})
                continue
            if req.steps == 0:
                # A 0-step forecast is its own answer (solo run(state, 0)
                # is the identity) — finish without occupying a slot.
                self._finish(req.rid, req.program,
                             jax.tree_util.tree_map(np.asarray, req.state),
                             steps=0, admit_t=now,
                             queue_wait_s=now - pend.submit_t, rounds=0)
                continue
            key = _wprog.plan_cache_key(req.program, ensemble=self.slots)
            # Request-level cache accounting (once per request): hit-rate
            # == the fraction of requests served by an already-compiled
            # plan, so N requests over M programs miss exactly M times.
            if not pend.counted:
                pend.counted = True
                if key in self._plans:
                    self._stats["plan_cache_hits"] += 1
                else:
                    self._stats["plan_cache_misses"] += 1
                    self._plan_for(key)
            lane = self._lane_for(key)
            if key not in free:
                free[key] = [i for i, s in enumerate(lane.slots)
                             if s is None]
            if free[key]:
                waves.setdefault(key, []).append((free[key].pop(0), pend))
            else:
                keep.append(pend)
        self._queue = keep
        for key, wave in waves.items():
            lane = self._lanes[key]
            idx = [i for i, _ in wave]
            sub = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, axis=0),
                *[p.request.state for _, p in wave])
            lane.batch = self._assign(lane.batch, jnp.asarray(idx), sub)
            admit_t = time.perf_counter()
            for i, pend in wave:
                lane.fps.pop(i, None)   # fresh content in this slot
                req = pend.request
                lane.slots[i] = _Slot(rid=req.rid, remaining=req.steps,
                                      steps=req.steps, admit_t=admit_t,
                                      queue_wait_s=admit_t - pend.submit_t,
                                      deadline_s=req.deadline_s)
                self._stats["admitted"] += 1

    def _round(self, lane: _Lane) -> None:
        """One SUPERVISED lane round.

        Scheduling is unchanged from the unsupervised engine: the shortest
        next canonical part among active slots picks the round depth;
        slots whose next part is deeper run along but are rolled back
        (uncredited) so every request's realized round sequence equals its
        solo `run()` sequence.  Around that, supervision: the step retries
        with exponential backoff on runtime failure (degrading the plan,
        then failing only this lane's in-flight requests), the fault
        injector's poison hook fires post-step, the validity guard
        quarantines invalid slots pre-credit, and per-request deadlines
        expire at the boundary."""
        plan = self._plan_for(lane.key)
        k = plan.k_steps
        parts = {i: min(s.remaining, k)
                 for i, s in enumerate(lane.slots) if s is not None}
        kk = min(parts.values())
        participants = [i for i, p in parts.items() if p == kk]
        rnd = self._stats["rounds"]
        prev = lane.batch if len(participants) < len(parts) else None
        new_batch = self._step_with_retry(lane, plan, kk, rnd)
        if new_batch is None:                    # escalation exhausted
            if self._try_failover(lane, rnd):
                return          # round re-ran on the rebuilt mesh
            self._fail_lane(lane, rnd)
            return
        lane.batch = new_batch
        if prev is not None:
            mask = np.zeros(self.slots, bool)
            mask[participants] = True
            lane.batch = _wprog.ensemble_slot_select(mask, lane.batch, prev)
            self._stats["rolled_back_slot_rounds"] += (
                len(parts) - len(participants))
        self._stats["rounds"] += 1
        self._stats["occupancy_sum"] += len(parts) / self.slots
        self._stats["occupancy_samples"] += 1
        inj = self.fault_injector
        if inj is not None:
            nonparts = tuple(i for i in range(self.slots)
                             if i not in set(participants))
            lane.batch = inj.poison(lane.batch, lane.key.op, rnd,
                                    tuple(parts), nonparticipants=nonparts,
                                    shards=plan.shards)
        bad = (self._guard_check(lane, parts, participants, rnd)
               if self.guard else {})
        for i, (diag, state) in bad.items():
            self._quarantine(lane, i, diag, state)
        for i in participants:
            if i in bad:
                continue
            slot = lane.slots[i]
            slot.remaining -= kk
            slot.rounds += 1
            if slot.remaining == 0:
                self._retire(lane, i)
        now = time.perf_counter()
        for i, slot in enumerate(lane.slots):
            if (slot is not None and slot.deadline_s is not None
                    and now - slot.submit_t > slot.deadline_s):
                self._expire_slot(lane, i, now)

    def _step_with_retry(self, lane: _Lane, plan, kk: int, rnd: int):
        """Run one round, retrying transient failures with exponential
        backoff; after `max_round_retries`, degrade the plan (force the
        interpreter) and try once more.  Returns the new batch, or None
        when every recourse failed (the caller escalates to mesh failover,
        then fails the lane).  With `round_deadline_s` set, an attempt
        whose wall clock exceeds the deadline counts as a failed attempt —
        a straggling collective goes through the same ladder instead of
        being waited on forever."""
        inj = self.fault_injector
        delay = self.retry_backoff_s
        last = None
        for attempt in range(self.max_round_retries + 1):
            try:
                t0 = time.perf_counter()
                if inj is not None:
                    inj.on_round(lane.key.op, rnd,
                                 device_ids=self._device_ids())
                out = plan.round_plan(kk).step(lane.batch)
                if (self.guard or inj is not None
                        or self.round_deadline_s is not None):
                    # Surface async runtime failures HERE, inside the
                    # retry scope, rather than at some later readback
                    # (the guard reads the batch right after anyway).
                    jax.block_until_ready(out)
                if (self.round_deadline_s is not None
                        and time.perf_counter() - t0
                        > self.round_deadline_s):
                    self._stats["round_deadline_hits"] += 1
                    raise RoundDeadlineError(
                        f"round {rnd} attempt took "
                        f"{time.perf_counter() - t0:.3f}s > "
                        f"round_deadline_s={self.round_deadline_s}")
                return out
            except Exception as e:  # noqa: BLE001 — supervised boundary
                self._stats["round_retries"] += 1
                last = e
                if attempt < self.max_round_retries:
                    time.sleep(delay)
                    delay *= 2
        # Retries exhausted: degrade to the interpreter lowering once —
        # unless the failure names a lost device (degradation cannot
        # resurrect hardware; that case belongs to mesh failover), or the
        # backend is a TPU (a round served by the interpreter would hide
        # the device's failure behind an "ok").
        if (not plan.interpret and not _wprog.on_tpu()
                and getattr(last, "lost_device", None) is None):
            try:
                ax_e, ax_y, ax_x = self.mesh_axes
                plan2 = _wprog.compile(lane.key, mesh=self.mesh, ax_e=ax_e,
                                       ax_y=ax_y, ax_x=ax_x, interpret=True)
                out = plan2.round_plan(kk).step(lane.batch)
                jax.block_until_ready(out)
                self._plans[lane.key] = plan2
                self._fallbacks[lane.key] = {
                    "stage": "interpret", "errors": [("runtime", repr(last))]}
                self._stats["fallback_compiles"] += 1
                return out
            except Exception as e:  # noqa: BLE001
                last = e
        self._last_round_error = repr(last)
        self._last_round_exc = last
        return None

    def _fail_lane(self, lane: _Lane, rnd: int) -> None:
        """A round failed beyond retry and degradation: fail ONLY this
        lane's in-flight requests (each gets a diagnosis and its pre-round
        state) and reset the lane so the rest of the engine keeps
        serving."""
        self._stats["lane_failures"] += 1
        err = getattr(self, "_last_round_error", "unknown")
        for i, slot in enumerate(lane.slots):
            if slot is None:
                continue
            lane.slots[i] = None
            state = jax.tree_util.tree_map(
                np.asarray, _wprog.ensemble_slot_view(lane.batch, i))
            self._finish(slot.rid,
                         dataclasses.replace(lane.key, ensemble=1), state,
                         steps=slot.steps, admit_t=slot.admit_t,
                         queue_wait_s=slot.queue_wait_s, rounds=slot.rounds,
                         status="failed",
                         steps_done=slot.steps - slot.remaining,
                         diagnosis={"reason": "round_failure", "round": rnd,
                                    "error": err})
        lane.batch = jax.device_put(_fields.zeros_state(
            lane.key.grid_shape, ensemble=self.slots, dtype=lane.key.dtype,
            names=lane.key.fields))
        if self.mesh is not None:
            lane.batch = _domain.shard_state(
                lane.batch, self.mesh, self._plan_for(lane.key).state_spec)
        lane.fps.clear()

    # -- mesh failover ------------------------------------------------------
    def _device_ids(self) -> Optional[List[int]]:
        if self.mesh is None:
            return None
        return [int(d.id) for d in self.mesh.devices.flat]

    def _probe_devices(self, devs) -> List[Any]:
        """The devices among `devs` that still answer a trivial
        transfer + compute + readback (the failure-agnostic way to find
        survivors when the round error did not name the lost device)."""
        alive = []
        for d in devs:
            try:
                jax.block_until_ready(jax.device_put(jnp.zeros(()), d) + 1)
                alive.append(d)
            except Exception:  # noqa: BLE001 — that IS the probe result
                pass
        return alive

    def _try_failover(self, lane: _Lane, rnd: int) -> bool:
        """The escalation step past retry + degrade: rebuild the mesh from
        surviving devices and resume EVERY in-flight request from the last
        round boundary.  Returns True when the interrupted round re-ran on
        the new mesh (nothing was failed), False when failover is off,
        no device is identifiably lost, or no surviving shape carries the
        lanes (the caller then fails the lane as before).

        Sequence: identify the lost device (the raised error's
        `lost_device`, else a probe of every mesh device); gather every
        lane's pre-round batch to host (the reshard pivot — `_round` has
        not credited anything yet, so this IS the last round boundary);
        walk `domain.failover_meshes` best-first until one shape compiles
        every lane's plan (pinned round depth, so canonical round
        sequences survive); reshard; re-run the interrupted round.  Slot
        fingerprints are sharding-invariant and keep guarding across the
        transition."""
        if not self.failover or self.mesh is None:
            return False
        devs = list(self.mesh.devices.flat)
        lost = getattr(getattr(self, "_last_round_exc", None),
                       "lost_device", None)
        if lost is not None:
            survivors = [d for d in devs if int(d.id) != int(lost)]
        else:
            survivors = self._probe_devices(devs)
        if not survivors or len(survivors) == len(devs):
            return False        # nothing identifiably lost: not a mesh fault
        t0 = time.perf_counter()
        host = {key: _domain.gather_state(ln.batch)
                for key, ln in self._lanes.items()}
        old_mesh, old_plans, old_fb = self.mesh, self._plans, self._fallbacks
        like = (self._plans[lane.key].shards
                if lane.key in self._plans else None)
        ax_e, ax_y, ax_x = self.mesh_axes
        grids = [ln.key.grid_shape for ln in self._lanes.values()]
        chosen = None
        for mesh2 in _domain.failover_meshes(survivors, grids,
                                             axes=(ax_y, ax_x), like=like):
            self.mesh, self._plans, self._fallbacks = mesh2, {}, {}
            try:
                for key in self._lanes:
                    self._plan_for(key)
                chosen = mesh2
                break
            except Exception:  # noqa: BLE001 — try the next shape
                continue
        if chosen is None:
            self.mesh, self._plans, self._fallbacks = (
                old_mesh, old_plans, old_fb)
            return False
        for key, ln in self._lanes.items():
            ln.batch = _domain.shard_state(
                host[key], self.mesh, self._plan_for(key).state_spec)
        active = sum(sum(s is not None for s in ln.slots)
                     for ln in self._lanes.values())
        self._stats["mesh_failovers"] += 1
        self._stats["recovery_rounds"] += 1
        self._stats["requests_preserved"] += active
        self._failovers.append({
            "round": rnd,
            "lost_device": None if lost is None else int(lost),
            "from_devices": [int(d.id) for d in devs],
            "to_devices": [int(d.id) for d in self.mesh.devices.flat],
            "from_shape": None if like is None else list(like),
            "to_shape": list(self._plan_for(lane.key).shards),
            "reshard_ms": (time.perf_counter() - t0) * 1e3,
            "requests_preserved": active,
        })
        self._round(lane)       # re-run the interrupted round
        return True

    # -- validity guard / quarantine ---------------------------------------
    def _guard_check(self, lane: _Lane, parts: Dict[int, int],
                     participants: List[int],
                     rnd: int) -> Dict[int, Tuple[Dict[str, Any],
                                                  WeatherState]]:
        """The per-slot supervision pass: ONE fused reduction over the
        whole lane batch at the round boundary computing both the physics
        validity bit (NaN/Inf + bounds) and a content fingerprint per slot
        (`program.slot_guard`).  Active invalid slots are diagnosed (host
        readback of just that slot); idle slots that rot are scrubbed back
        to zeros.  Then the fingerprint check: slots that did NOT advance
        this round — rolled-back and idle slots — must keep their digest
        bit-for-bit; a mismatch is cross-device/shard divergence (e.g. a
        corrupted halo wire buffer) that magnitude checks cannot see.
        Divergent in-flight slots quarantine, divergent idle slots scrub.
        Healthy slots are only READ — their bits cannot change."""
        ok_d, fp_d = _wprog.slot_guard(lane.batch, self.guard_limit)
        ok, fp = np.asarray(ok_d), np.asarray(fp_d)
        bad: Dict[int, Tuple[Dict[str, Any], WeatherState]] = {}
        for i in parts:
            if not bool(ok[i]):
                bad[i] = self._diagnose(lane, i, rnd)
        for i, slot in enumerate(lane.slots):
            if slot is None and not bool(ok[i]):
                self._scrub(lane, i)
                self._stats["scrubbed_idle_slots"] += 1
        advanced = set(participants)
        for i in range(self.slots):
            if i in bad or not bool(ok[i]):
                continue        # already handled by the validity pass
            got = int(fp[i])
            if i in advanced or i not in lane.fps:
                # The slot legitimately has new bits (it advanced a round)
                # or has no recorded digest yet: (re)record.
                lane.fps[i] = got
                continue
            want = lane.fps[i]
            if want == got:
                continue
            self._stats["fingerprint_divergence"] += 1
            if lane.slots[i] is not None:
                bad[i] = self._diagnose_fp(lane, i, rnd, want, got)
            else:
                self._scrub(lane, i)
                self._stats["scrubbed_idle_slots"] += 1
        return bad

    def _diagnose_fp(self, lane: _Lane, i: int, rnd: int, want: int,
                     got: int) -> Tuple[Dict[str, Any], WeatherState]:
        state = jax.tree_util.tree_map(
            np.asarray, _wprog.ensemble_slot_view(lane.batch, i))
        diag = {"reason": "fingerprint_divergence", "round": rnd,
                "expected_fp": want, "observed_fp": got,
                "note": "slot did not advance this round but its bits "
                        "changed: cross-shard/device divergence (e.g. a "
                        "corrupted halo wire buffer), invisible to "
                        "NaN/magnitude validity checks"}
        return diag, state

    def _diagnose(self, lane: _Lane, i: int,
                  rnd: int) -> Tuple[Dict[str, Any], WeatherState]:
        """Host-side diagnosis of one invalid slot (the slow path — it
        only runs on quarantine): per-leaf NaN/Inf/out-of-bounds counts."""
        state = jax.tree_util.tree_map(
            np.asarray, _wprog.ensemble_slot_view(lane.batch, i))
        leaves = {}
        for name, a in sorted(state.fields.items()):
            leaves[f"fields/{name}"] = a
        leaves["wcon"] = np.asarray(state.wcon)
        for name, a in sorted(state.tens.items()):
            leaves[f"tens/{name}"] = a
        for name, a in sorted(state.stage_tens.items()):
            leaves[f"stage_tens/{name}"] = a
        per_leaf = {}
        for key, a in leaves.items():
            a = np.asarray(a, np.float64)
            nan = int(np.isnan(a).sum())
            inf = int(np.isinf(a).sum())
            finite = a[np.isfinite(a)]
            oob = int((np.abs(finite) > self.guard_limit).sum())
            if nan or inf or oob:
                per_leaf[key] = {"nan": nan, "inf": inf,
                                 "out_of_bounds": oob}
        diag = {"reason": "validity_guard", "round": rnd,
                "limit": self.guard_limit, "bad_leaves": per_leaf,
                "first_bad": next(iter(per_leaf), None)}
        return diag, state

    def _quarantine(self, lane: _Lane, i: int, diag: Dict[str, Any],
                    state: WeatherState) -> None:
        """Remove ONE offending slot: its request finishes `failed` with
        the diagnosis (and the offending state, for forensics), the slot
        is re-zeroed so the lane stays healthy, and the freed slot
        backfills from the queue at the next admit."""
        slot = lane.slots[i]
        lane.slots[i] = None
        self._stats["quarantined"] += 1
        self._scrub(lane, i)
        self._finish(slot.rid, dataclasses.replace(lane.key, ensemble=1),
                     state, steps=slot.steps, admit_t=slot.admit_t,
                     queue_wait_s=slot.queue_wait_s, rounds=slot.rounds,
                     status="failed",
                     steps_done=slot.steps - slot.remaining, diagnosis=diag)

    def _scrub(self, lane: _Lane, i: int) -> None:
        zero = _fields.zeros_state(lane.key.grid_shape, ensemble=1,
                                   dtype=lane.key.dtype,
                                   names=lane.key.fields)
        lane.batch = self._assign(lane.batch, jnp.asarray([i]), zero)
        lane.fps.pop(i, None)   # the slot's bits were legitimately replaced

    def _expire_slot(self, lane: _Lane, i: int, now: float) -> None:
        slot = lane.slots[i]
        lane.slots[i] = None
        self._stats["deadline_expired"] += 1
        state = jax.tree_util.tree_map(
            np.asarray, _wprog.ensemble_slot_view(lane.batch, i))
        self._scrub(lane, i)
        self._finish(slot.rid, dataclasses.replace(lane.key, ensemble=1),
                     state, steps=slot.steps, admit_t=slot.admit_t,
                     queue_wait_s=slot.queue_wait_s, rounds=slot.rounds,
                     status="expired",
                     steps_done=slot.steps - slot.remaining,
                     diagnosis={"reason": "deadline_exceeded",
                                "deadline_s": slot.deadline_s,
                                "elapsed_s": now - slot.submit_t,
                                "where": "in_flight"})

    def _retire(self, lane: _Lane, i: int) -> None:
        slot = lane.slots[i]
        lane.slots[i] = None
        # Read back exactly this slot; blocking here IS the finish time.
        state = jax.tree_util.tree_map(
            np.asarray, _wprog.ensemble_slot_view(lane.batch, i))
        prog = dataclasses.replace(lane.key, ensemble=1)
        self._finish(slot.rid, prog, state, steps=slot.steps,
                     admit_t=slot.admit_t, queue_wait_s=slot.queue_wait_s,
                     rounds=slot.rounds)

    def _finish(self, rid: int, prog, state, *, steps: int, admit_t: float,
                queue_wait_s: float, rounds: int, status: str = "ok",
                steps_done: Optional[int] = None,
                diagnosis: Optional[Dict[str, Any]] = None) -> None:
        self._results[rid] = ForecastResult(
            rid=rid, program=prog, state=state, steps=steps,
            latency_s=time.perf_counter() - admit_t,
            queue_wait_s=queue_wait_s, rounds=rounds, status=status,
            steps_done=steps if steps_done is None else steps_done,
            diagnosis=diagnosis)
        self._stats["completed"] += 1

    # -- warm-state checkpointing ------------------------------------------
    def checkpoint(self, ckpt_dir: Optional[str] = None,
                   step: Optional[int] = None) -> int:
        """Persist the warm engine (in-flight batches, queue, results,
        bookkeeping) atomically via `ckpt.save_tree`.  Returns the
        checkpoint step.  In-flight latency clocks are stored as
        elapsed-so-far and resume ticking on restore."""
        ckpt_dir = ckpt_dir or self.ckpt_dir
        if ckpt_dir is None:
            raise ValueError("no ckpt_dir: pass one here or at __init__")
        if step is None:
            step = self._ckpt_step
        self._ckpt_step = step + 1
        now = time.perf_counter()
        lanes = list(self._lanes.values())
        tree = {
            "lanes": [lane.batch for lane in lanes],
            "queue": [p.request.state for p in self._queue],
            "results": {str(rid): r.state
                        for rid, r in self._results.items()},
        }
        extra = {
            "slots": self.slots,
            "next_rid": self._next_rid,
            "ckpt_step": self._ckpt_step,
            "stats": {k: v for k, v in self._stats.items()},
            "mesh_devices": (None if self.mesh is None
                             else int(self.mesh.devices.size)),
            "config": {
                "max_queue": self.max_queue, "guard": self.guard,
                "guard_limit": self.guard_limit,
                "ckpt_every_rounds": self.ckpt_every_rounds,
                "max_round_retries": self.max_round_retries,
                "retry_backoff_s": self.retry_backoff_s,
                "last_ckpt_round": self._last_ckpt_round,
            },
            "lanes": [{
                "program": lane.key.to_json(),
                # The resolved round strategy: restore re-pins it so the
                # canonical round sequence survives a mesh change.
                "plan": self._pinned.get(lane.key),
                "slots": [None if s is None else {
                    "rid": s.rid, "remaining": s.remaining,
                    "steps": s.steps, "rounds": s.rounds,
                    "elapsed_s": now - s.admit_t,
                    "queue_wait_s": s.queue_wait_s,
                    "deadline_s": s.deadline_s,
                } for s in lane.slots],
            } for lane in lanes],
            "queue": [{
                "rid": p.request.rid,
                "steps": p.request.steps,
                "program": p.request.program.to_json(),
                "waited_s": now - p.submit_t,
                "deadline_s": p.request.deadline_s,
            } for p in self._queue],
            "results": [{
                "rid": r.rid, "steps": r.steps, "rounds": r.rounds,
                "latency_s": r.latency_s, "queue_wait_s": r.queue_wait_s,
                "program": r.program.to_json(),
                "status": r.status, "steps_done": r.steps_done,
                "diagnosis": r.diagnosis,
            } for r in self._results.values()],
        }
        ckpt.save_tree(ckpt_dir, step, tree, extra=extra,
                       keep=self.ckpt_keep)
        return step

    @classmethod
    def restore(cls, ckpt_dir: str, step: Optional[int] = None, *,
                mesh=None, interpret: Optional[bool] = None,
                ax_e: str = "pod", ax_y: str = "data", ax_x: str = "model",
                ckpt_keep: int = 3, fault_injector=None) -> "ForecastEngine":
        """Resume a checkpointed engine — on ANY mesh.

        In-flight forecasts continue from their persisted round boundary
        (no respin), queued requests stay queued, finished results are
        preserved.  The checkpoint is mesh-elastic: lane batches are
        persisted unsharded-logical and reshard through the NEW plan's
        `state_spec`, so a checkpoint written single-chip restores onto 4
        devices and vice versa.  Plans are NOT serialized — they
        recompile through the plan cache (compile-once per mesh shape)
        with the persisted (variant, k_steps) pin, keeping every
        in-flight request's canonical round sequence intact across the
        transition; docs/robustness.md has the matrix of which
        transitions additionally preserve exact bits.  Supervision config
        (max_queue, guard, watchdog cadence, retry policy) is restored
        from the checkpoint.

        With `step=None` the newest checkpoint is used; when it is
        corrupt (`ckpt.CheckpointCorruptError`), restore falls back to
        the next-older valid one instead of dying, and raises an
        aggregated error only when every retained checkpoint is
        unreadable."""
        if step is not None:
            return cls._restore_step(
                ckpt_dir, step, mesh=mesh, interpret=interpret, ax_e=ax_e,
                ax_y=ax_y, ax_x=ax_x, ckpt_keep=ckpt_keep,
                fault_injector=fault_injector)
        steps = sorted(ckpt.all_steps(ckpt_dir), reverse=True)
        if not steps:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir!r}")
        failures = []
        for s in steps:
            try:
                return cls._restore_step(
                    ckpt_dir, s, mesh=mesh, interpret=interpret, ax_e=ax_e,
                    ax_y=ax_y, ax_x=ax_x, ckpt_keep=ckpt_keep,
                    fault_injector=fault_injector)
            except ckpt.CheckpointCorruptError as e:
                failures.append((s, e))
        raise ckpt.CheckpointCorruptError(
            f"every checkpoint in {ckpt_dir!r} is unreadable — "
            + "; ".join(f"step {s}: {e}" for s, e in failures))

    @classmethod
    def _restore_step(cls, ckpt_dir: str, step: int, *, mesh, interpret,
                      ax_e: str, ax_y: str, ax_x: str, ckpt_keep: int,
                      fault_injector) -> "ForecastEngine":
        def prog_of(d):
            return _wprog.StencilProgram.from_json(d)

        def template(prog, ensemble):
            return _fields.zeros_state(prog.grid_shape, ensemble=ensemble,
                                       dtype=prog.dtype, names=prog.fields)

        meta = ckpt.read_meta(ckpt_dir, step)
        try:
            extra = meta["extra"]
            slots = extra["slots"]
            tmpl = {
                "lanes": [template(prog_of(ln["program"]), slots)
                          for ln in extra["lanes"]],
                "queue": [template(prog_of(q["program"]), 1)
                          for q in extra["queue"]],
                "results": {str(r["rid"]): template(prog_of(r["program"]), 1)
                            for r in extra["results"]},
            }
        except (KeyError, TypeError) as e:
            raise ckpt.CheckpointCorruptError(
                f"checkpoint {ckpt_dir!r} step {step}: the engine sidecar "
                f"is missing or malformed at {e!r} — written by an "
                f"incompatible engine version or truncated.  Restore from "
                f"another step, or re-checkpoint with this engine."
            ) from e
        tree, _ = ckpt.restore_tree(ckpt_dir, step, tmpl)

        cfg = extra.get("config", {})
        eng = cls(slots=slots, mesh=mesh, interpret=interpret, ax_e=ax_e,
                  ax_y=ax_y, ax_x=ax_x, ckpt_dir=ckpt_dir,
                  ckpt_keep=ckpt_keep,
                  max_queue=cfg.get("max_queue"),
                  guard=cfg.get("guard", True),
                  guard_limit=cfg.get("guard_limit", 1e6),
                  ckpt_every_rounds=cfg.get("ckpt_every_rounds"),
                  max_round_retries=cfg.get("max_round_retries", 2),
                  retry_backoff_s=cfg.get("retry_backoff_s", 0.05),
                  fault_injector=fault_injector)
        eng._next_rid = extra["next_rid"]
        eng._ckpt_step = extra["ckpt_step"]
        eng._last_ckpt_round = cfg.get("last_ckpt_round", 0)
        eng._stats.update(extra["stats"])
        now = time.perf_counter()
        for ln, batch in zip(extra["lanes"], tree["lanes"]):
            key = _wprog.plan_cache_key(prog_of(ln["program"]),
                                        ensemble=slots)
            pin = ln.get("plan")
            if pin is not None:
                # Seed the round-strategy pin BEFORE the first compile so
                # the recompiled plan replays the writer's [k,...,k,tail]
                # sequences even on a different mesh shape.
                eng._pinned[key] = dict(pin)
            if mesh is not None:
                batch = _domain.shard_state(batch, mesh,
                                            eng._plan_for(key).state_spec)
            else:
                batch = jax.device_put(batch)
            eng._lanes[key] = _Lane(
                key=key, batch=batch,
                slots=[None if s is None else _Slot(
                    rid=s["rid"], remaining=s["remaining"],
                    steps=s["steps"], rounds=s["rounds"],
                    admit_t=now - s["elapsed_s"],
                    queue_wait_s=s["queue_wait_s"],
                    deadline_s=s.get("deadline_s"))
                    for s in ln["slots"]])
        for q, state in zip(extra["queue"], tree["queue"]):
            req = ForecastRequest(program=prog_of(q["program"]),
                                  state=jax.device_put(state),
                                  steps=q["steps"], rid=q["rid"],
                                  deadline_s=q.get("deadline_s"))
            eng._queue.append(_Pending(req, now - q["waited_s"]))
        for r in extra["results"]:
            eng._results[r["rid"]] = ForecastResult(
                rid=r["rid"], program=prog_of(r["program"]),
                state=jax.tree_util.tree_map(np.asarray,
                                             tree["results"][str(r["rid"])]),
                steps=r["steps"], latency_s=r["latency_s"],
                queue_wait_s=r["queue_wait_s"], rounds=r["rounds"],
                status=r.get("status", "ok"),
                steps_done=r.get("steps_done", r["steps"]),
                diagnosis=r.get("diagnosis"))
        return eng

"""The measured windows: set-up, the timed loop and the check of what the
timed path produced, for each traffic kind.

The program under test is used only through its public entry points:
`StencilProgram.from_json` / `compile` / `ExecutionPlan.run` for a batch
forecast, `ForecastEngine.submit` / `pump` for served forecasts.  Every
call into it sits in one of the benchmark's own host spans
(`jax.profiler.TraceAnnotation`), which a traced run lays over the device
trace: `bench.window`, `bench.run_call`, `bench.submit`, `bench.pump`,
`bench.poll`, and `bench.wait` while the host waits for the next arrival.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import math
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import traffic as _traffic
import yardstick

LEAF_GROUPS = ("fields", "tens", "stage_tens")


@contextlib.contextmanager
def span(name: str):
    with jax.profiler.TraceAnnotation(name):
        yield


def _weather_state(d: Dict):
    from repro.weather.fields import WeatherState
    return WeatherState(fields=dict(d["fields"]), wcon=d["wcon"],
                        tens=dict(d["tens"]),
                        stage_tens=dict(d["stage_tens"]))


def _as_dict(state) -> Dict:
    return {"fields": dict(state.fields), "wcon": state.wcon,
            "tens": dict(state.tens), "stage_tens": dict(state.stage_tens)}


def _leaves(d: Dict, groups=LEAF_GROUPS + ("wcon",)) -> Dict[str, Any]:
    out = {}
    for g in groups:
        if g == "wcon":
            out["wcon"] = d["wcon"]
        else:
            out.update({f"{g}/{n}": a for n, a in d[g].items()})
    return out


@functools.partial(jax.jit, static_argnums=2)
def _member_of(d: Dict, m, sharding) -> Dict:
    out = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, m, keepdims=False), d)
    if sharding is not None:
        out = jax.lax.with_sharding_constraint(out, sharding)
    return out


def _member(d: Dict, m: int, sharding=None) -> Dict:
    """Member `m`'s leaves; on a mesh, laid out as `sharding` (the
    ensemble's without its member axis), so no chip holds more than its
    share of the member."""
    return _member_of(d, jnp.int32(m), sharding)


@functools.partial(jax.jit, static_argnums=1)
def _split_members(d: Dict, members: int) -> List[Dict]:
    return [jax.tree_util.tree_map(lambda a, m=m: a[m:m + 1], d)
            for m in range(members)]


def _cast(d: Dict, dtype) -> Dict:
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), d)


# -- the reference -----------------------------------------------------------

def load_reference(bench_dir: str, spec: str) -> Callable:
    """`module.step` of a reference module kept beside the configurations
    (`bench/configs/<module>.py`)."""
    module, step = spec.rsplit(".", 1)
    path = os.path.join(bench_dir, "configs", f"{module}.py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_ref_{module}",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return getattr(mod, step)


class Reference:
    """Advances one member (leaves without the ensemble axis) by `n` steps
    of the configuration's plain reference, in float32: one compiled
    program for every step count.  With `sharding` (a member's on the
    mesh) XLA partitions the reference and its result stays there."""

    def __init__(self, bench_dir: str, config: Dict, sharding=None):
        step = load_reference(bench_dir, config["reference"])
        coeff, dt = config["program"]["coeff"], config["program"]["dt"]

        def run(state, n):
            return jax.lax.fori_loop(0, n, lambda _, s: step(s, coeff, dt),
                                     state)
        self._run = (jax.jit(run) if sharding is None
                     else jax.jit(run, out_shardings=sharding))

    def __call__(self, member: Dict, n: int) -> Dict:
        return self._run(_cast(member, jnp.float32), jnp.int32(n))


@jax.jit
def _norms(got: Dict, want: Dict):
    diff = {k: jnp.sqrt(jnp.sum(jnp.square(got[k].astype(jnp.float32)
                                           - want[k])))
            for k in want}
    ref = {k: jnp.sqrt(jnp.sum(jnp.square(want[k]))) for k in want}
    return diff, ref


def gap(got: Dict, want: Dict, groups) -> float:
    """`yardstick.rel_gap` of one member's leaves in `groups`."""
    g, w = _leaves(got, groups), _leaves(want, groups)
    diff, ref = jax.device_get(_norms({k: jnp.asarray(g[k]) for k in w}, w))
    return yardstick.rel_gap(diff, ref)


# -- the runs ----------------------------------------------------------------

@dataclasses.dataclass
class Outcome:
    """What a window measured and what its check compared."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    counters: Dict[str, Any]
    checks: Dict[str, float]
    report: Dict[str, Any] = dataclasses.field(default_factory=dict)


def program_for(config: Dict, ensemble: int, dtype: Optional[str] = None):
    from repro.weather.program import StencilProgram
    d = dict(config["program"], ensemble=ensemble)
    if dtype is not None:
        d["dtype"] = dtype
    return StencilProgram.from_json(d)


def mesh_for(config: Dict, devices: Sequence[Any]) -> Optional[Mesh]:
    """The configuration's decomposition over the cell's chips, laid on
    the physical torus; None for a cell on one chip."""
    spec = config.get("mesh")
    if spec is None:
        return None
    from jax.experimental import mesh_utils
    return Mesh(mesh_utils.create_device_mesh(spec["shape"], devices),
                tuple(spec["axes"]))


class Batch:
    """Back-to-back forecasts of `forecast_steps` steps, all members
    together, each advanced by `run(state, steps_per_call)` calls that
    continue from the last state, each call timed to `block_until_ready`.
    Every forecast starts from the initial state made from the seed.

    With a `mesh` in the configuration the plan is the program's
    distributed one on that mesh (y and x sharded, z and the ensemble
    whole on each chip), and the initial state and the check's arrays
    live sharded as its `state_spec` says."""

    def __init__(self, cell, seed: int, devices: Sequence[Any],
                 dtype: Optional[str] = None):
        from repro.weather.program import compile
        self.cell, self.seed = cell, seed
        self.cfg, self.n = cell.config, cell.traffic["steps_per_call"]
        self.calls_per_forecast, rest = divmod(
            cell.traffic["forecast_steps"], self.n)
        if rest or not self.calls_per_forecast:
            raise ValueError(f"forecast_steps must be a positive multiple "
                             f"of steps_per_call: {cell.traffic}")
        self.dtype = dtype or self.cfg["program"]["dtype"]
        prog = program_for(self.cfg, self.cfg["members"], self.dtype)
        mesh = mesh_for(self.cfg, devices)
        if mesh is None:
            self.plan = compile(prog)
            self.sharding = self.member_sharding = None
        else:
            ax_y, ax_x = mesh.axis_names
            self.plan = compile(prog, mesh=mesh, ax_e=None, ax_y=ax_y,
                                ax_x=ax_x)
            spec = self.plan.state_spec
            self.sharding = NamedSharding(mesh, spec)
            self.member_sharding = NamedSharding(mesh, P(*spec[1:]))
        self.x0 = _weather_state(_cast(
            _traffic.make_state(seed, self.cfg["program"],
                                self.cfg["members"], self.sharding),
            self.dtype))
        jax.block_until_ready(self.plan.run(self.x0, self.n))

    def window(self, seconds: float) -> None:
        run, n = self.plan.run, self.n
        x = None
        first, calls = None, 0
        with span("bench.window"):
            t0 = end = time.perf_counter()
            while True:
                if calls % self.calls_per_forecast == 0:
                    x = self.x0  # a new forecast
                last_in = x      # the previous input is dropped here
                with span("bench.run_call"):
                    x = jax.block_until_ready(run(x, n))
                end = time.perf_counter()
                calls += 1
                if first is None:
                    # Only what the step writes; the rest is the input's.
                    first = {"fields": dict(x.fields),
                             "stage_tens": dict(x.stage_tens)}
                if end - t0 >= seconds:
                    break
        self.window_s, self.calls = end - t0, calls
        self.first_out, self.last_in, self.last_out = first, last_in, x

    def outcome(self, bench_dir: str) -> Outcome:
        """Frees the plan, then compares the window's first and last calls
        with the reference, member by member, each from its own input."""
        cfg, members = self.cfg, self.cfg["members"]
        self.plan = None
        on = self.member_sharding
        ref = Reference(bench_dir, cfg, on)
        x0 = _as_dict(self.x0)
        last_in, last_out = _as_dict(self.last_in), _as_dict(self.last_out)
        first_gap = last_gap = 0.0
        for m in range(members):
            want = ref(_member(x0, m, on), self.n)
            first_gap = _worse(first_gap, gap(_member(self.first_out, m, on),
                                              want, ("fields", "stage_tens")))
            del want    # one member's reference at a time on the chips
            want = ref(_member(last_in, m, on), self.n)
            last_gap = _worse(last_gap, gap(_member(last_out, m, on), want,
                                            LEAF_GROUPS + ("wcon",)))
            del want
        steps = self.calls * self.n
        work = steps * yardstick.gridpoints(cfg)
        return Outcome(
            attempted=self.calls, failed=0,
            end_to_end={"gridpoint_steps_per_s":
                        yardstick.rate(work, self.window_s) / 1e9},
            counters={"gridpoint_steps": work, "steps": steps,
                      "window_s": self.window_s},
            checks={"first_call_rel_err": first_gap,
                    "last_call_rel_err": last_gap})


def _worse(a: float, b: float) -> float:
    return b if math.isnan(b) or b > a else a


class Serve:
    """An open loop of ensemble bursts into a `ForecastEngine` with the
    configuration's `slots` (default: one per member).  Latency runs from
    each request's due time to the moment the host sees its result.  The
    engine runs on JAX's default device, the cell's one chip."""

    def __init__(self, cell, seed: int, devices: Sequence[Any],
                 dtype: Optional[str] = None):
        from repro.serve.forecast import ForecastEngine
        if "mesh" in cell.config:
            raise ValueError(f"{cell.name}: the served window runs one chip; "
                             f"a configuration with a 'mesh' is refused, "
                             f"not run on one chip")
        self.cell, self.seed = cell, seed
        self.cfg, self.traffic = cell.config, cell.traffic
        self.dtype = dtype or self.cfg["program"]["dtype"]
        members = self.cfg["members"]
        self.prog = program_for(self.cfg, 1, self.dtype)
        pool = _cast(_traffic.make_state(seed, self.cfg["program"], members),
                     self.dtype)
        self.pool = [_weather_state(p)
                     for p in _split_members(pool, members)]
        del pool
        slots = self.cfg.get("slots", members)
        self.engine = ForecastEngine(slots=slots)
        # Admission waves of every size and a retire from every slot: the
        # shapes a burst can meet, compiled now and not in the window.
        for w in [slots] + list(range(1, slots)):
            for m in range(w):
                self._submit(m, 1)
            while self.engine.pump():
                pass
            self._take_results()
        jax.block_until_ready([p.wcon for p in self.pool])

    def _submit(self, member: int, steps: int) -> int:
        from repro.serve.forecast import ForecastRequest
        return self.engine.submit(ForecastRequest(
            program=self.prog, state=self.pool[member], steps=steps))

    def _take_results(self) -> Dict[int, Any]:
        # The engine keeps every result for its lifetime; a service hands
        # them on, and 200 states of 218 MB would not fit the host.
        got = self.engine._results
        return {rid: got.pop(rid) for rid in list(got)}

    def window(self, seconds: float) -> None:
        eng = self.engine
        sched = _traffic.serve_schedule(self.traffic, self.cfg["members"],
                                        self.seed, seconds)
        sample = set(_traffic.check_sample(self.traffic, sched, self.seed))
        self.sched, self.kept = sched, {}
        rid_of: Dict[int, int] = {}
        done: Dict[int, float] = {}
        self.status: Dict[int, str] = {}
        self.queue_wait: Dict[int, float] = {}
        late: List[float] = []
        pump_s = 0.0
        rounds0 = rounds = eng.stats()["rounds"]
        i = 0
        t0 = time.perf_counter()

        def submit_due(now):
            nonlocal i
            while i < len(sched) and sched[i].due_s <= now:
                with span("bench.submit"):
                    rid_of[self._submit(sched[i].member, sched[i].steps)] = i
                late.append(time.perf_counter() - t0 - sched[i].due_s)
                i += 1

        def pump():
            nonlocal pump_s, rounds
            tp = time.perf_counter()
            with span("bench.pump"):
                eng.pump()
            dt = time.perf_counter() - tp
            r = eng.stats()["rounds"]
            if r > rounds:
                pump_s, rounds = pump_s + dt, r
            with span("bench.poll"):
                for rid, res in self._take_results().items():
                    j = rid_of[rid]
                    done[j] = time.perf_counter() - t0
                    self.status[j] = res.status
                    self.queue_wait[j] = res.queue_wait_s
                    if j in sample:
                        self.kept[j] = res.state

        with span("bench.window"):
            while True:
                now = time.perf_counter() - t0
                if now >= seconds:
                    break
                submit_due(now)
                if eng.has_work():
                    pump()
                elif i < len(sched):
                    with span("bench.wait"):
                        time.sleep(max(0.0, min(sched[i].due_s, seconds)
                                       - (time.perf_counter() - t0)))
        self.window_s = time.perf_counter() - t0
        submit_due(seconds)             # every request due in the window
        close = time.perf_counter()
        while eng.has_work() and time.perf_counter() - close < 60.0:
            pump()
        self.done = done
        self.late = late
        self.pump_s, self.rounds = pump_s, rounds - rounds0

    def outcome(self, bench_dir: str) -> Outcome:
        """Frees the engine, then compares the sampled requests' results
        with the reference run from the same initial states."""
        self.engine = None
        sched = self.sched
        ok = {j for j, s in self.status.items() if s == "ok"}
        lat = yardstick.latencies(
            {j: r.due_s for j, r in enumerate(sched)},
            {j: t for j, t in self.done.items() if j in ok})
        lats = list(lat.values())
        ref = Reference(bench_dir, self.cfg)
        worst = 0.0
        for j, state in sorted(self.kept.items()):
            r = sched[j]
            want = ref(_member(_as_dict(self.pool[r.member]), 0), r.steps)
            got = _member(_as_dict(state), 0)
            worst = _worse(worst, gap(got, want, LEAF_GROUPS + ("wcon",)))
        missing = len(set(_traffic.check_sample(self.traffic, sched,
                                                self.seed)) - set(self.kept))
        unanswered = len(sched) - len(ok)
        return Outcome(
            attempted=len(sched), failed=unanswered,
            end_to_end={f"latency_p{q}_s": yardstick.percentile(lats, q)
                        for q in (50, 90, 95)},
            counters={"rounds": self.rounds, "round_pump_s": self.pump_s,
                      "queue_wait_s": [self.queue_wait[j] for j in ok],
                      "window_s": self.window_s},
            checks={"sample_rel_err": worst if not missing else math.inf,
                    "unanswered": float(unanswered)},
            report={"generator_late_max_s": max(self.late, default=0.0),
                    "requests": len(sched)})


DRIVERS = {"batch": Batch, "serve": Serve}

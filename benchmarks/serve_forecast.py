"""Forecast-as-a-service under synthetic open-loop load.

Drives `repro.serve.forecast.ForecastEngine` the way a deployment would:
requests for a small catalog of stencil programs arrive on a seeded
Poisson clock (exponential interarrivals, open-loop — arrivals do NOT
wait for completions), each carrying its own initial conditions and step
count; the engine folds them into the ensemble axis of per-program cached
plans and retires them at round boundaries.

Reported metrics (docs/benchmarks.md, "BENCH_serve.json"):
  serve_forecast/latency_p50        us, admit -> result on host, p50
  serve_forecast/latency_p99        us, ditto p99 (tail = queueing)
  serve_forecast/steps_per_s_mean   per-request forecast throughput
  serve_forecast/occupancy          mean busy-slot fraction per round
  serve_forecast/cache_hit_rate     plan-cache hits / requests

Plus the supervision recovery numbers:
  serve_forecast/recovery_rounds    rounds the chaos engine kept serving
                                    after its first injected fault

Also writes BENCH_serve.json: the latency distribution, per-request
steps/s, batch occupancy, plan-cache hit statistics, the program catalog,
the load spec, a `robustness` block (a deterministic chaos segment: one
poisoned request, one device loss, one forced lowering fallback), and a
`failover` block (a kill-a-device run on a forced-4-device subprocess —
recovery rounds, requests preserved across the mesh rebuild, reshard wall
time, and whether every preserved request stayed bit-identical to a solo
run on the original mesh) — everything the CI smoke job asserts on.
BENCH_SMOKE=1 shrinks the request count and slot pool.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import emit, smoke_mode, write_json
from repro.serve.forecast import ForecastEngine, ForecastRequest
from repro.testing.faults import FaultInjector, FaultSpec
from repro.weather import fields
from repro.weather.program import StencilProgram

# The served catalog: three programs a real mesoscale service would mix —
# the fused compound step at two precisions plus a diffusion-only product.
_CATALOG = (
    StencilProgram(grid_shape=(4, 16, 16), op="dycore"),
    StencilProgram(grid_shape=(4, 16, 16), op="dycore", dtype="bfloat16"),
    StencilProgram(grid_shape=(3, 8, 8), op="hdiff"),
)


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def _drive(eng: ForecastEngine, requests, arrivals):
    """Open-loop load: submit each request at its scheduled arrival time
    (whether or not the engine kept up), pump between arrivals."""
    t0 = time.perf_counter()
    pending = list(zip(arrivals, requests))
    while pending or eng.has_work():
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            eng.submit(pending[0][1])
            pending.pop(0)
        busy = eng.pump()
        if not busy and pending:
            # idle until the next arrival; open-loop clients don't block
            time.sleep(max(0.0, pending[0][0]
                           - (time.perf_counter() - t0)))
    return eng.drain()


def _chaos_segment(slots: int) -> dict:
    """A deterministic supervised run: one poisoned request, one injected
    device loss, one forced lowering fallback — reports what the engine
    absorbed and how many rounds it kept serving past the first fault."""
    inj = FaultInjector([
        FaultSpec(kind="compile_fail", op="hdiff", attempt="native"),
        FaultSpec(kind="poison_nan", round=1),
        FaultSpec(kind="device_loss", round=2),
    ], seed=7)
    eng = ForecastEngine(slots=slots, retry_backoff_s=0.0,
                         fault_injector=inj)
    n = 6
    for i in range(n):
        prog = _CATALOG[i % len(_CATALOG)]
        state = fields.initial_state(jax.random.PRNGKey(2000 + i),
                                     prog.grid_shape, ensemble=1,
                                     dtype=prog.dtype)
        eng.submit(ForecastRequest(program=prog, state=state, steps=4))
    results = eng.drain()
    assert len(results) == n and not eng.has_work()
    stats = eng.stats()
    fault_rounds = [e["round"] for e in inj.log if "round" in e]
    recovery = (stats["rounds"] - min(fault_rounds)) if fault_rounds else 0
    return {"requests": n,
            "statuses": {s: sum(1 for r in results.values()
                                if r.status == s)
                         for s in ("ok", "failed", "expired")},
            "quarantined": stats["quarantined"],
            "round_retries": stats["round_retries"],
            "fallback_compiles": stats["fallback_compiles"],
            "lane_failures": stats["lane_failures"],
            "recovery_rounds": recovery,
            "faults_fired": inj.fired()}


_FAILOVER_SNIPPET = r"""
import json, time
import numpy as np, jax
from repro.serve.forecast import ForecastEngine, ForecastRequest
from repro.testing.faults import FaultInjector, FaultSpec
from repro.weather import domain, fields
from repro.weather import program as wprog
from repro.weather.program import StencilProgram

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
grid = (4, 16, 16)
prog = StencilProgram(grid_shape=grid, ensemble=1)
states = [fields.initial_state(jax.random.PRNGKey(s), grid, ensemble=1)
          for s in (0, 1, 2)]
steps = (5, 3, 4)
solo = wprog.compile(prog, mesh=mesh)
refs = [solo.run(domain.shard_state(s, mesh, solo.state_spec), n)
        for s, n in zip(states, steps)]

inj = FaultInjector([FaultSpec(kind="device_loss", round=1, device=3,
                               once=False)])
eng = ForecastEngine(slots=2, mesh=mesh, fault_injector=inj,
                     max_round_retries=1, retry_backoff_s=0.01)
t0 = time.perf_counter()
rids = [eng.submit(ForecastRequest(program=prog, state=s, steps=n))
        for s, n in zip(states, steps)]
res = eng.drain()
wall = time.perf_counter() - t0
st = eng.stats()
bitwise = all(
    res[rid].status == "ok"
    and all(np.array_equal(np.asarray(res[rid].state.fields[n]),
                           np.asarray(ref.fields[n]))
            for n in prog.fields)
    for rid, ref in zip(rids, refs))
fo = st["failovers"][0] if st["failovers"] else {}
print("FAILOVER_JSON " + json.dumps({
    "mesh_failovers": st["mesh_failovers"],
    "recovery_rounds": st["recovery_rounds"],
    "requests_preserved": st["requests_preserved"],
    "lane_failures": st["lane_failures"],
    "reshard_ms": fo.get("reshard_ms"),
    "lost_device": fo.get("lost_device"),
    "from_shape": fo.get("from_shape"),
    "to_shape": fo.get("to_shape"),
    "drain_wall_s": wall,
    "all_ok": all(res[r].status == "ok" for r in rids),
    "bitwise_vs_original_mesh": bool(bitwise),
}))
"""


def _failover_segment() -> dict:
    """Kill-a-device chaos on a forced-4-device subprocess (the main
    bench process pins a single CPU device, so the mesh run needs its own
    interpreter): device 3 dies persistently at round 1, the engine
    rebuilds 2x2 -> 2x1 and preserves every in-flight request.  Reports
    the recovery accounting BENCH_serve.json's `failover` block carries
    and CI asserts on."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _FAILOVER_SNIPPET], env=env,
                       capture_output=True, text=True, timeout=600)
    for line in r.stdout.splitlines():
        if line.startswith("FAILOVER_JSON "):
            return json.loads(line[len("FAILOVER_JSON "):])
    raise RuntimeError(f"failover segment produced no report: "
                       f"{r.stderr[-2000:]}")


def run() -> None:
    smoke = smoke_mode()
    slots = 2 if smoke else 4
    n_requests = 8 if smoke else 32
    mean_interarrival_s = 0.05 if smoke else 0.1

    rng = np.random.default_rng(42)
    arrivals = np.cumsum(rng.exponential(mean_interarrival_s,
                                         size=n_requests))
    steps = rng.integers(1, 5 if smoke else 13, size=n_requests)
    progs = [_CATALOG[i % len(_CATALOG)] for i in range(n_requests)]
    requests = []
    for i, (prog, s) in enumerate(zip(progs, steps)):
        state = fields.initial_state(jax.random.PRNGKey(1000 + i),
                                     prog.grid_shape, ensemble=1,
                                     dtype=prog.dtype)
        requests.append(ForecastRequest(program=prog, state=state,
                                        steps=int(s)))

    eng = ForecastEngine(slots=slots)
    results = _drive(eng, requests, arrivals)
    assert len(results) == n_requests, (len(results), n_requests)
    stats = eng.stats()

    lat = [r.latency_s for r in results.values()]
    sps = [r.steps / r.latency_s for r in results.values()
           if r.latency_s > 0]
    p50, p99 = _percentile(lat, 50), _percentile(lat, 99)
    emit("serve_forecast/latency_p50", p50 * 1e6,
         f"n={n_requests} slots={slots}")
    emit("serve_forecast/latency_p99", p99 * 1e6, "tail=queueing")
    emit("serve_forecast/steps_per_s_mean", float(np.mean(sps)),
         "per-request forecast throughput")
    emit("serve_forecast/occupancy", stats["occupancy"],
         "busy-slot fraction per lane-round")
    cache = {"hits": stats["plan_cache_hits"],
             "misses": stats["plan_cache_misses"],
             "hit_rate": stats["plan_cache_hit_rate"]}
    emit("serve_forecast/cache_hit_rate", cache["hit_rate"],
         f"{len(_CATALOG)} programs, {cache['misses']} compiles")

    chaos = _chaos_segment(slots)
    failover = _failover_segment()
    emit("serve_forecast/recovery_rounds", chaos["recovery_rounds"],
         f"{chaos['faults_fired']} faults, "
         f"{chaos['quarantined']} quarantined")
    emit("serve_forecast/failover_reshard_ms", failover["reshard_ms"],
         f"{failover['from_shape']}->{failover['to_shape']}, "
         f"{failover['requests_preserved']} requests preserved")

    write_json("BENCH_serve.json", {
        "slots": slots,
        "n_requests": n_requests,
        "n_programs": len(_CATALOG),
        "latency_s": {"p50": p50, "p99": p99,
                      "mean": float(np.mean(lat)),
                      "max": float(np.max(lat))},
        "steps_per_s_per_request": {"mean": float(np.mean(sps)),
                                    "p50": _percentile(sps, 50),
                                    "min": float(np.min(sps))},
        "occupancy": stats["occupancy"],
        "plan_cache": cache,
        "robustness": chaos,
        "failover": failover,
        "programs": [p.to_json() for p in _CATALOG],
        "load": {"model": "open-loop poisson", "seed": 42,
                 "mean_interarrival_s": mean_interarrival_s,
                 "steps_min": 1,
                 "steps_max": int(steps.max())},
    })


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()

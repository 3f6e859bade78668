"""Fused vs unfused dycore step — the NERO fusion claim, measured + modeled.

Paper §3 (arxiv 2107.08716): the CPU/GPU baseline round-trips every
intermediate through main memory; the in-fabric pipeline streams each field
once.  This benchmark reports that claim three ways for one full dycore step
(4 prognostic fields), going EXCLUSIVELY through the declarative plan API
(`weather/program.py::compile_dycore`) — exactly ONE `ExecutionPlan` per
measured configuration, and any use of a deprecated flag-soup entry point
fails the run (DeprecationWarnings from our shims are promoted to errors):

  * measured wall-clock of the four execution variants — unfused oracle,
    per-field fused (4 Pallas launches), whole-state fused (ONE launch),
    and the k-step round (K timesteps in ONE launch).  (CPU note: without
    a TPU the fused kernels run in the Pallas *interpreter*, so their
    wall-clock here validates the pipelines, it does not demonstrate the
    speedup — the modeled rows do);
  * modeled HBM traffic per step from the model-grid plan's `report()`
    (`core/memmodel.dycore_step_traffic` with the plan's auto-tuned tile);
  * modeled TPU time/energy for the fused plan from core/perfmodel, and the
    k-step communication-avoiding exchange model
    (core/memmodel.kstep_exchange_model).

Emitted metric names (docs/benchmarks.md):
  dycore_fused/walltime_{unfused,fused,whole_state}  us per step (measured)
  dycore_fused/traffic_{unfused,fused,whole_state}_* modeled MB per step
  dycore_fused/model_{fused}                         modeled TPU time
  dycore_fused/kstep_k<k>                            k-step exchange model

Since the StencilOp registry landed, the benchmark also reproduces the
paper's PER-KERNEL table: hdiff-only and vadvc-only programs are compiled
through the same `compile()` planner and measured side-by-side with the
fused compound step — `BENCH_dycore.json["per_kernel"]` carries, for each
of (hdiff, vadvc, fused), the measured walltime, the plan report (op +
declared footprint + tile), and the modeled GFLOPS / GFLOPS-per-watt from
`core/perfmodel` (the paper's 21.01 vs 1.61 GFLOPS/W axis).

Also writes BENCH_dycore.json (walltime, modeled HBM bytes, steps/s, and
the distributed k-step plan's `report()` embedded verbatim as "plan") for
cross-PR perf tracking.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import warnings

import jax

from benchmarks.common import emit, smoke_mode, time_fn, write_json
from benchmarks.energy import energy_block
from benchmarks.roofline_kernels import roofline_block
from repro.core import hierarchy as hw
from repro.core import memmodel, perfmodel, tiling, trace_stats
from repro.weather import fields
from repro.weather import stencil_ops
from repro.weather.pipeline import PipelineProgram
from repro.weather.program import (DycoreProgram, StencilProgram,
                                   compile_dycore)

# Measured grid: deliberately small.  The Pallas interpreter's grid loop
# carries the full output state per iteration (O(grid_steps x state) copy
# overhead that real hardware does not have), which at large grids swamps —
# and inverts — the launch-amortization effect the whole-state step
# targets.  At this size the per-`pallas_call` dispatch cost is the visible
# term, which is exactly the 4-launches-vs-1 comparison; HBM-traffic
# effects are covered by the modeled rows at the paper's domain.
GRID = (4, 16, 16)
ENSEMBLE = 1
MODEL_GRID = (64, 256, 256)  # the paper's domain, for the modeled rows
SMOKE_GRID = (4, 16, 16)     # CI smoke job (tiny, interpret mode)
KSTEP_K = 2                  # depth of the measured/traced k-step round


# Structural counts + plan report of the distributed k-step round need >1
# shard per mesh axis, so they are produced in a subprocess with forced
# host devices (same trick as tests/test_program.py) and read back as JSON.
# They are counts, not times: the child runs on the CPU, so it never asks
# for the chip this process already holds.
_STRUCT_SNIPPET = r"""
import json, jax
from repro.core import trace_stats
from repro.weather import fields
from repro.weather.program import DycoreProgram, compile_dycore
st = fields.initial_state(jax.random.PRNGKey(0), (4, 16, 16), ensemble=1)
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = compile_dycore(DycoreProgram(grid_shape=(4, 16, 16),
                                    variant="kstep", k_steps=%d), mesh=mesh)
rep = plan.report()
j = jax.make_jaxpr(plan.step)(st)
counts = trace_stats.assert_plan_structure(j, rep)   # report == trace
print("STRUCT=" + json.dumps(counts))
print("PLAN=" + json.dumps(rep))
"""


def _kstep_round_structure(k: int) -> tuple:
    """Trace the distributed k-step plan on a forced 4-device CPU mesh and
    return ({"pallas_call": ..., "ppermute": ...}, plan.report())."""
    env = {k_: v for k_, v in os.environ.items() if k_ != "XLA_FLAGS"}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", "src")
    r = subprocess.run([sys.executable, "-c", _STRUCT_SNIPPET % k], env=env,
                       capture_output=True, text=True, timeout=600)
    struct = plan_rep = None
    for line in r.stdout.splitlines():
        if line.startswith("STRUCT="):
            struct = json.loads(line[len("STRUCT="):])
        elif line.startswith("PLAN="):
            plan_rep = json.loads(line[len("PLAN="):])
    if struct is None or plan_rep is None:
        raise RuntimeError(f"k-step structure trace failed: "
                           f"{r.stderr[-2000:]}")
    return struct, plan_rep


# Measured-autotuning round trip: compile(tune="measure") in a subprocess
# with a spy on autotune.measure_walltime, twice against the same cache
# dir.  The first process must MEASURE (cache miss -> store); the second
# must compile the cached winner measuring NOTHING (cache hit) — the
# persistent (program, spec fingerprint, backend) cache proven end-to-end.
# Both children run on the CPU (the parent may hold the chip), so the block
# is labelled backend "cpu": it proves the cache, not a chip tile.
_TUNE_SNIPPET = r"""
import json, jax
from repro.core import autotune
calls = {"n": 0}
_real = autotune.measure_walltime
def _spy(fn, repeats=3):
    calls["n"] += 1
    return _real(fn, repeats=1)
autotune.measure_walltime = _spy
from repro.weather import program as P
plan = P.compile(P.StencilProgram(grid_shape=(4, 16, 16)), tune="measure")
print("TUNE=" + json.dumps({"tile_ty": plan.tile_ty,
                            "measure_calls": calls["n"],
                            "stats": autotune.TUNE_CACHE_STATS}))
"""


def _measured_autotune_roundtrip() -> dict:
    """Run the two-process measured-tuning check; returns the JSON block
    (including per-process spy counts and the cache-hit verdict)."""
    def one(cache_dir: str) -> dict:
        env = dict(os.environ)
        env["REPRO_TUNE_CACHE"] = cache_dir
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("PYTHONPATH", "src")
        r = subprocess.run([sys.executable, "-c", _TUNE_SNIPPET], env=env,
                           capture_output=True, text=True, timeout=600)
        for line in r.stdout.splitlines():
            if line.startswith("TUNE="):
                return json.loads(line[len("TUNE="):])
        raise RuntimeError(f"measured-autotune subprocess failed: "
                           f"{r.stderr[-2000:]}")
    with tempfile.TemporaryDirectory(prefix="repro-tune-") as cache_dir:
        first = one(cache_dir)
        second = one(cache_dir)
    round_trip = (first["measure_calls"] > 0
                  and first["stats"]["stores"] == 1
                  and second["measure_calls"] == 0
                  and second["stats"]["hits"] == 1
                  and second["tile_ty"] == first["tile_ty"])
    return {"backend": "cpu", "first": first, "second": second,
            "cache_round_trip": bool(round_trip)}


def run():
    # Every entry point below goes through an ExecutionPlan; the legacy
    # flag-soup shims are gone, and any stray DeprecationWarning from our
    # own modules still fails the benchmark loudly.
    with warnings.catch_warnings():
        warnings.filterwarnings("error", category=DeprecationWarning,
                                module=r"repro\..*")
        _run()


def _run():
    smoke = smoke_mode()
    grid = SMOKE_GRID if smoke else GRID
    iters, warmup = (1, 1) if smoke else (7, 2)
    st = fields.initial_state(jax.random.PRNGKey(0), grid,
                              ensemble=ENSEMBLE)
    n_fields = len(fields.PROGNOSTIC)
    backend = jax.default_backend()
    interp_note = ("" if backend == "tpu"
                   else " (Pallas interpreter — validates, not representative)")

    # ONE ExecutionPlan per measured configuration.
    def plan_for(variant, k=1):
        return compile_dycore(DycoreProgram(
            grid_shape=grid, ensemble=ENSEMBLE, variant=variant, k_steps=k))

    plans = {"unfused": plan_for("unfused"),
             "fused_per_field": plan_for("per_field"),
             "fused_whole_state": plan_for("whole_state"),
             "kstep_round": plan_for("kstep", k=KSTEP_K)}

    walltime = {}
    t_unfused = time_fn(plans["unfused"].step, st, iters=iters,
                        warmup=warmup)
    walltime["unfused"] = t_unfused
    emit("dycore_fused/walltime_unfused", t_unfused,
         f"grid={grid} ensemble={ENSEMBLE}")
    t_fused = time_fn(plans["fused_per_field"].step, st, iters=iters,
                      warmup=warmup)
    walltime["fused_per_field"] = t_fused
    emit("dycore_fused/walltime_fused", t_fused,
         f"grid={grid} ensemble={ENSEMBLE} backend={backend}"
         f" 4 launches{interp_note}")
    t_whole = time_fn(plans["fused_whole_state"].step, st, iters=iters,
                      warmup=warmup)
    walltime["fused_whole_state"] = t_whole
    emit("dycore_fused/walltime_whole_state", t_whole,
         f"grid={grid} ensemble={ENSEMBLE} backend={backend}"
         f" 1 launch, shared w{interp_note} "
         f"vs_per_field={t_fused / max(t_whole, 1e-9):.2f}x")
    # The k-step round: KSTEP_K timesteps in ONE launch (in-kernel scan,
    # state in VMEM between local steps) vs KSTEP_K whole-state launches.
    t_kstep = time_fn(plans["kstep_round"].step, st, iters=iters,
                      warmup=warmup)
    t_kseq = time_fn(
        lambda s: plans["fused_whole_state"].run(s, KSTEP_K), st,
        iters=iters, warmup=warmup)
    walltime["kstep_round"] = t_kstep
    walltime["kstep_scan_of_launches"] = t_kseq
    emit("dycore_fused/walltime_kstep", t_kstep,
         f"grid={grid} k={KSTEP_K} backend={backend} 1 launch/round"
         f"{interp_note} vs_scan={t_kseq / max(t_kstep, 1e-9):.2f}x")

    # --- the paper's PER-KERNEL table (ISSUE 5): hdiff-only, vadvc-only
    # and the fused compound step, side by side through the SAME planner.
    # Measured walltime at the bench grid; modeled GFLOPS / GFLOPS-per-watt
    # (core/perfmodel over the plan's auto-tuned tile) at the paper's
    # domain — the 12.7x/21.01-GF/W (hdiff) vs 5.3x/1.61-GF/W (vadvc) axis.
    # Modeled rows always use the paper's domain — modeling is analytic, so
    # smoke mode keeps the full-size numbers (CI asserts against them).
    model_grid = MODEL_GRID
    per_kernel = {}
    for key, op in (("hdiff", "hdiff"), ("vadvc", "vadvc"),
                    ("vadvc_update", "vadvc_update"),
                    ("hadv_upwind", "hadv_upwind"),
                    ("fused", "dycore")):
        plan = compile_dycore(StencilProgram(
            grid_shape=grid, ensemble=ENSEMBLE, op=op,
            variant="whole_state"))
        t = time_fn(plan.step, st, iters=iters, warmup=warmup)
        rep = plan.report()
        mrep = compile_dycore(StencilProgram(
            grid_shape=model_grid, ensemble=ENSEMBLE, op=op,
            variant="whole_state")).report()
        per_kernel[key] = {
            "op": op,
            "walltime_us": t,
            "modeled_gflops": mrep["model"]["gflops"],
            "modeled_gflops_per_watt": mrep["model"]["gflops_per_watt"],
            "modeled_time_us": mrep["model"]["time_us"],
            "flops_per_point": rep["footprint"]["flops_per_point"],
            "pallas_calls_per_round": rep["pallas_calls_per_round"],
            "plan": rep,
            "model_plan": mrep,
        }
        emit(f"dycore_fused/per_kernel_{key}", t,
             f"grid={grid} op={op} "
             f"model_gflops={mrep['model']['gflops']:.0f} "
             f"model_gflops_per_watt={mrep['model']['gflops_per_watt']:.2f}"
             f"{interp_note}")

    # --- flagship CHAINED pipeline (ISSUE 10): the three stages as ONE
    # plan — one fused exchange pair per direction, launches in order on
    # resident operands.  Measured walltime vs the three solo rows above;
    # modeled rows at the paper's domain carry the chained-vs-sequential
    # HBM stream (intermediates stay out of HBM) and the packed-wire model
    # (2 exchange rounds regardless of chain length vs one round set PER
    # STAGE sequentially — the chain ships deeper footprints, so its win
    # is ROUND COUNT/latency, not bytes; both sides are reported).
    pipe_stages = ("hadv_upwind", "vadvc_update", "hdiff")
    pipe_plan = compile_dycore(PipelineProgram(
        grid_shape=grid, ensemble=ENSEMBLE, coeff=0.05,
        variant="whole_state", k_steps=1, stages=pipe_stages))
    t_pipe = time_fn(pipe_plan.step, st, iters=iters, warmup=warmup)
    t_solo_sum = sum(per_kernel[k_]["walltime_us"] for k_ in pipe_stages)
    rep = pipe_plan.report()
    mrep = compile_dycore(PipelineProgram(
        grid_shape=model_grid, ensemble=ENSEMBLE, coeff=0.05,
        variant="whole_state", k_steps=1, stages=pipe_stages)).report()
    mt = mrep["traffic"]

    def _wire(opdef):
        return memmodel.packed_exchange_model(
            model_grid, "float32", rides=opdef.memmodel_rides(n_fields),
            k=1, shards=(2, 2), compute_halo=(opdef.halo, opdef.halo))

    w_chain = _wire(stencil_ops.get_stencil_op(rep["op"]))
    w_stage = {op: _wire(stencil_ops.get_stencil_op(op))
               for op in pipe_stages}
    wire = {
        "chained_bytes": w_chain["bytes_kstep"],
        "sequential_bytes": sum(w["bytes_kstep"]
                                for w in w_stage.values()),
        "chained_rounds": w_chain["rounds_kstep"],
        "sequential_rounds": sum(w["rounds_kstep"]
                                 for w in w_stage.values()),
        "by_stage_bytes": {op: w["bytes_kstep"]
                           for op, w in w_stage.items()},
    }
    per_kernel["pipeline"] = {
        "op": rep["op"],
        "stages": list(pipe_stages),
        "walltime_us": t_pipe,
        "walltime_sequential_us": t_solo_sum,
        "modeled_gflops": mrep["model"]["gflops"],
        "modeled_gflops_per_watt": mrep["model"]["gflops_per_watt"],
        "modeled_time_us": mrep["model"]["time_us"],
        "flops_per_point": rep["footprint"]["flops_per_point"],
        "pallas_calls_per_round": rep["pallas_calls_per_round"],
        "hbm_chained_per_round": mt["chained_per_round"],
        "hbm_sequential_per_round": mt["sequential_per_round"],
        "hbm_chained_reduction_x": mt["chained_reduction_x"],
        "hbm_sequential_by_stage": mt["sequential_by_stage"],
        "wire": wire,
        "plan": rep,
        "model_plan": mrep,
    }
    emit("dycore_fused/per_kernel_pipeline", t_pipe,
         f"grid={grid} stages={'->'.join(pipe_stages)} "
         f"vs_sequential={t_solo_sum / max(t_pipe, 1e-9):.2f}x "
         f"hbm_reduction={mt['chained_reduction_x']:.2f}x "
         f"wire_rounds={wire['chained_rounds']}v{wire['sequential_rounds']}"
         f"{interp_note}")

    # Modeled HBM traffic at the paper's domain: ONE model-grid plan per
    # dtype; its report() embeds the memmodel accounting at the plan's own
    # auto-tuned tile.
    # Where no k-step window fits the VMEM budget (bf16 at the paper's
    # domain: the kernel's fp32 working set leaves no 16-row window), the
    # rows model the k-step traffic at the whole-state plan's window.
    traffic = {}
    for dtype in ("float32", "bfloat16"):
        prog = DycoreProgram(grid_shape=model_grid, ensemble=ENSEMBLE,
                             dtype=dtype, variant="kstep", k_steps=KSTEP_K)
        try:
            rep = compile_dycore(prog).report()
            t = rep["traffic"]
            kstep_refused = None
        except ValueError as e:
            kstep_refused = str(e)
            rep = compile_dycore(dataclasses.replace(
                prog, variant="whole_state", k_steps=1)).report()
            t = memmodel.dycore_step_traffic(
                model_grid, dtype, n_fields=n_fields, ty=rep["tile"]["ty"],
                k_steps=KSTEP_K)
        ty = rep["tile"]["ty"]
        traffic[dtype] = {
            "unfused": t["unfused"]["total"],
            "fused_per_field": t["fused"]["total"],
            "fused_whole_state": t["fused_whole"]["total"],
            "fused_kstep": t["fused_kstep"]["total"],
            "fused_kstep_scan": t["fused_kstep"]["scan_total"],
            "interstep_state": t["fused_kstep"]["interstep_state"],
            "interstep_state_scan": t["fused_kstep"]["interstep_state_scan"],
            "reduction_x_whole": t["reduction_x_whole"],
            "interstep_reduction_x": t["interstep_reduction_x"],
            "kstep_refused": kstep_refused,
        }
        mb = 1.0 / 2**20
        emit(f"dycore_fused/traffic_unfused_{dtype}", 0.0,
             f"MB={t['unfused']['total'] * mb:.0f} "
             f"vadvc={t['unfused']['vadvc'] * mb:.0f} "
             f"pointwise={t['unfused']['pointwise'] * mb:.0f} "
             f"hdiff={(t['unfused']['hdiff'] + t['unfused']['hdiff_pad']) * mb:.0f}")
        emit(f"dycore_fused/traffic_fused_{dtype}", 0.0,
             f"MB={t['fused']['total'] * mb:.0f} ty={ty} "
             f"halo_overhead={t['halo_overhead'] * 100:.1f}% "
             f"reduction={t['reduction_x']:.2f}x "
             f"(aliased-window pessimistic bound: "
             f"MB={t['fused']['stream_window_reads'] * mb:.0f}, "
             f"{t['reduction_x_window_reads']:.2f}x)")
        emit(f"dycore_fused/traffic_whole_state_{dtype}", 0.0,
             f"MB={t['fused_whole']['total'] * mb:.0f} ty={ty} "
             f"reduction={t['reduction_x_whole']:.2f}x "
             f"vs_per_field="
             f"{t['fused']['total'] / max(t['fused_whole']['total'], 1):.3f}x "
             f"(pessimistic bound: "
             f"MB={t['fused_whole']['stream_window_reads'] * mb:.0f}, "
             f"{t['reduction_x_whole_window_reads']:.2f}x)")
        emit(f"dycore_fused/traffic_kstep_{dtype}", 0.0,
             f"MB={t['fused_kstep']['total'] * mb:.0f}/round k={KSTEP_K} "
             f"vs_scan={t['reduction_x_kstep_vs_scan']:.2f}x "
             f"interstep_state_MB={t['fused_kstep']['interstep_state'] * mb:.0f}"
             f" vs {t['fused_kstep']['interstep_state_scan'] * mb:.0f} "
             f"({t['interstep_reduction_x']:.0f}x fewer HBM state "
             f"round-trips)")

        # Modeled TPU time for the fused plan (per field pipeline pass).
        plan = tiling.TilePlan(op=tiling.DYCORE_FUSED, grid_shape=model_grid,
                               tile=(model_grid[0], ty, model_grid[2]),
                               dtype=dtype)
        est = perfmodel.estimate(plan)
        emit(f"dycore_fused/model_fused_{dtype}",
             est.time_s * n_fields * 1e6,
             f"bottleneck={est.bottleneck} gflops={est.gflops:.0f} "
             f"vmem={100.0 * plan.vmem_bytes / hw.tpu_v5e().vmem.capacity_bytes:.0f}%")

    # Communication-avoiding k-step exchange model (weather/program.py).
    kstep = {}
    for k in (1, 2, 4):
        try:
            m = memmodel.kstep_exchange_model(model_grid, "float32",
                                              n_fields=n_fields, k=k)
        except ValueError:
            continue
        kstep[str(k)] = m
        emit(f"dycore_fused/kstep_k{k}", 0.0,
             f"rounds={m['rounds_kstep']}v{m['rounds_sequential']} "
             f"bytes_ratio={m['bytes_ratio']:.2f} "
             f"redundant_flops={m['redundant_flops_frac'] * 100:.0f}%")

    # Structural counts of the k-step round — the regression guard that is
    # immune to interpreter-walltime noise: the single-chip round must be
    # ONE pallas_call; the distributed round additionally one ppermute pair
    # per mesh direction — and the plan's own report() must agree with the
    # trace (asserted in the subprocess via assert_plan_structure).
    local_kplan = compile_dycore(DycoreProgram(
        grid_shape=SMOKE_GRID, variant="kstep", k_steps=KSTEP_K),
        interpret=True)
    st_small = fields.initial_state(jax.random.PRNGKey(0), SMOKE_GRID)
    j = jax.make_jaxpr(lambda s: local_kplan.run(s, KSTEP_K))(st_small)
    calls_local = trace_stats.count_primitive(j, "pallas_call")
    struct, plan_rep = _kstep_round_structure(KSTEP_K)
    calls_round = max(calls_local, struct["pallas_call"])
    emit("dycore_fused/kstep_structure", 0.0,
         f"pallas_calls_per_round={calls_round} "
         f"collectives_per_round={struct['ppermute']} k={KSTEP_K}")

    # Cross-machine model blocks at the paper's domain (all analytic), and
    # the measured-autotune persistent-cache round trip (two subprocesses
    # sharing one REPRO_TUNE_CACHE dir; CI asserts cache_round_trip).
    model_by_hardware = per_kernel["fused"]["model_plan"]["model_by_hardware"]
    measured_autotune = _measured_autotune_roundtrip()
    if not measured_autotune["cache_round_trip"]:
        raise SystemExit(f"measured-autotune cache round trip failed: "
                         f"{measured_autotune}")
    emit("dycore_fused/measured_autotune", 0.0,
         f"cache_round_trip={measured_autotune['cache_round_trip']} "
         f"tile_ty={measured_autotune.get('first', {}).get('tile_ty')}")

    write_json("BENCH_dycore.json", {
        "grid": list(grid),
        "model_grid": list(model_grid),
        "ensemble": ENSEMBLE,
        "n_fields": n_fields,
        "k_steps": KSTEP_K,
        "pallas_calls_per_round": calls_round,
        "collectives_per_round": struct["ppermute"],
        # The distributed k-step plan's full report(), embedded VERBATIM —
        # variant, tile, k_steps, exchange schedule (incl. wire dtype),
        # structural counts, modeled traffic (forced-4-device CPU trace).
        "plan": plan_rep,
        # One report per measured single-chip configuration.
        "plans": {name: p.report() for name, p in plans.items()},
        # The paper's two-kernel table: hdiff vs vadvc vs fused, each with
        # measured walltime + modeled GFLOPS from its own compiled plan.
        "per_kernel": per_kernel,
        "walltime_us": walltime,
        # steps_per_s counts SIMULATED timesteps: the kstep entries' walltime
        # covers a whole KSTEP_K-step round, the others a single step.
        "steps_per_s": {
            k: (KSTEP_K if k.startswith("kstep") else 1) * 1e6
            / max(v, 1e-9) for k, v in walltime.items()},
        "modeled_hbm_bytes": traffic,
        "kstep_exchange": kstep,
        # The paper's cross-machine table (NERO vs POWER9 vs v5e) at the
        # paper's domain, from the fused model-grid plan's report, plus the
        # spec-derived energy/roofline blocks and the measured-autotune
        # persistent-cache proof.  bench-smoke asserts all four.
        "model_by_hardware": model_by_hardware,
        "energy_by_hardware": energy_block(MODEL_GRID),
        "roofline_by_hardware": roofline_block(MODEL_GRID),
        "measured_autotune": measured_autotune,
    })

    if calls_round > 1:
        # Structural regression: the k-step round fragmented into multiple
        # launches.  Fail the bench (and the CI smoke job) loudly.
        raise SystemExit(
            f"k-step structural regression: {calls_round} pallas_calls per "
            f"round (expected 1)")


if __name__ == "__main__":
    run()

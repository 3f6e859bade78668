"""One run of one benchmark cell, found by name.

`BENCHMARK.json` names each cell's configuration, traffic mix and metrics.
Everything that belongs to one of them is a file of its own under the
benchmark's directory, found by that name:

* `configs/<file>.json`: the program as `StencilProgram.to_json()` gives
  it, the member count, the operands a step must read and write, the
  plain reference (`<module>.<step>` of a module beside it), and, for a
  cell on more than one chip, its decomposition: `"mesh": {"shape": [py,
  px], "axes": [ax_y, ax_x]}`, y and x over the mesh's two axes, z and the
  ensemble whole on every chip, `py * px` the workload's `chips`;
* `traffic/<name>.json`: the parameters of the mix (`traffic.py`);
* `metrics/<name>.py`: a per-layer metric's reader, `read(run)`, which
  returns a number or None where it finds nothing to read;
* `limits/<workload>.json`: the limit of each number the cell's check
  compares, with the readings it was set from.

Adding a cell, mix or metric is adding files and entries; no file that is
there changes.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

import jax

import trace_reduce
import traffic
import window
import yardstick

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WORK = os.path.join(BENCH_DIR, "_work")   # cache and traces; not committed


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, Dict[str, Any]]
    bench_dir: str


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _reports(metric: Dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def _check_mesh(config: Dict, chips: int, workload: str) -> None:
    """A cell on several chips says how it uses them: its configuration's
    `mesh` has two axes, y and x, whose sizes multiply to `chips`."""
    mesh = config.get("mesh")
    if mesh is None:
        if chips != 1:
            raise ValueError(f"{workload}: a cell on {chips} chips needs a "
                             f"'mesh' in its configuration, the "
                             f"decomposition it runs")
        return
    shape, axes = mesh.get("shape"), mesh.get("axes")
    if (not isinstance(shape, list) or len(shape) != 2
            or not all(isinstance(n, int) and n > 0 for n in shape)
            or not isinstance(axes, list) or len(axes) != 2
            or not all(isinstance(a, str) and a for a in axes)
            or axes[0] == axes[1]):
        raise ValueError(f"{workload}: mesh {mesh!r} is not {{'shape': "
                         f"[py, px], 'axes': [ax_y, ax_x]}}")
    if math.prod(shape) != chips:
        raise ValueError(f"{workload}: mesh shape {shape} holds "
                         f"{math.prod(shape)} chips, the cell asks for "
                         f"{chips}")


def load_cell(workload: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    bench_dir = os.path.join(root, spec["paths"][0])
    w = _by_name(spec["workloads"], workload, "workload")
    c = _by_name(spec["configs"], w["config"], "configuration")
    with open(os.path.join(root, c["file"])) as f:
        config = json.load(f)
    _check_mesh(config, w["chips"], workload)
    mix = traffic.load(os.path.join(bench_dir, "traffic",
                                    f"{w['traffic']}.json"))
    with open(os.path.join(bench_dir, "limits", f"{workload}.json")) as f:
        limits = json.load(f)["checks"]
    return Cell(name=workload, chips=w["chips"], config=config, traffic=mix,
                end_to_end=[m for m in spec["end_to_end"]
                            if _reports(m, workload)],
                per_layer=[m for m in spec["per_layer"]
                           if _reports(m, workload)],
                limits=limits, bench_dir=bench_dir)


def load_reader(bench_dir: str, metric: str):
    path = os.path.join(bench_dir, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a metric reader may read: the cell, the window's counters, the
    reduced trace (None without one), and the device's peaks."""
    cell: Cell
    counters: Dict[str, Any]
    trace: Optional[Any]
    device_kind: str

    @property
    def config(self) -> Dict[str, Any]:
        return self.cell.config

    @property
    def peaks(self) -> Dict[str, Any]:
        return yardstick.peaks_for(self.device_kind)


def per_layer_metrics(run: Run) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric of the cell that its reader finds."""
    out = {}
    for m in run.cell.per_layer:
        value = load_reader(run.cell.bench_dir, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def require_devices(chips: int) -> List[Any]:
    """The chips the cell runs on; exits without a result where JAX finds
    no TPU or fewer chips than the cell asks for."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX's devices are {devs[0].platform!r}, "
                         f"not 'tpu'; the benchmark measures only on a TPU")
    if len(devs) < chips:
        raise SystemExit(f"bench: {len(devs)} chip(s) visible, the cell "
                         f"asks for {chips}")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached, so that only a checkout's first run compiles."""
    path = os.path.join(WORK, "jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts what JAX traces or compiles while it listens: a window that
    warmed up every shape it uses counts none."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event in self.EVENTS:
            self.n += 1


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: str = ROOT, devices=None,
             dtype: Optional[str] = None) -> Dict[str, Any]:
    """Set up, measure, check; returns the result line as a dict.

    `devices` are the chips the cell runs on and reports (None: the run is
    off a chip, as in the tests, runs on JAX's first `chips` devices and
    reports no device numbers); `dtype` replaces the configuration's
    precision, for the lower-precision control."""
    cell = load_cell(workload, root)
    kind = devices[0].device_kind if devices else "none"
    if devices:
        yardstick.peaks_for(kind)
    run = window.DRIVERS[cell.traffic["kind"]](
        cell, seed, devices or jax.devices()[:cell.chips], dtype=dtype)
    setup_s = time.perf_counter() - t_start
    trace_dir = os.path.join(WORK, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(
            trace_dir, profiler_options=trace_reduce.profile_options())
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        run.window(seconds)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
        if trace:
            jax.profiler.stop_trace()
    device = {"platform": devices[0].platform if devices else "none",
              "kind": kind, "count": len(jax.devices()),
              "mesh": cell.config.get("mesh", {}).get("shape")}
    if devices:
        device["memory_peak_bytes"] = max(
            d.memory_stats()["peak_bytes_in_use"] for d in devices)
    out = run.outcome(cell.bench_dir)
    if trace:
        reduced = trace_reduce.reduce(_newest_trace(trace_dir),
                                      n_devices=cell.chips)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        metrics = per_layer_metrics(Run(cell=cell, counters=out.counters,
                                        trace=reduced, device_kind=kind))
    else:
        reduced = None
        values = dict(out.end_to_end, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    checks = {}
    for name, value in out.checks.items():
        if name not in cell.limits:
            raise KeyError(f"{workload}: no limit for check {name!r}")
        checks[name] = {"value": value, "limit": cell.limits[name]["limit"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        line["breakdown"] = {"device_ops": reduced.top_ops[:10],
                             "idle_gaps": reduced.idle_gaps[:10]}
    line.update(out.report, compiles_in_window=compiles.n)
    line["checks"] = checks
    return line


def _newest_trace(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def print_result(line: Dict[str, Any]) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, and the result as the last line of standard output."""
    print(f"compiles_in_window = {line['compiles_in_window']}",
          file=sys.stderr, flush=True)
    for name, c in line["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(line, allow_nan=True), flush=True)

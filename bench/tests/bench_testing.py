"""Helpers for the benchmark's CPU tests: a benchmark root of small cells
in a temporary directory, built from the real files of the benchmark
(reference, metric readers) and small configurations of the same
deployments."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

GRID = [4, 16, 16]
MEMBERS = 3
MESH_CELL = "dycore-2x2-batch"


def small_config(name: str, members: int = MEMBERS) -> dict:
    """A real configuration file with the grid and the member count cut to
    a size the Pallas interpreter runs in a second."""
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg["program"]["grid_shape"] = list(GRID)
    cfg["program"]["ensemble"] = members
    cfg["members"] = members
    if "slots" in cfg:
        cfg["slots"] = members - 1
    return cfg


def small_root(tmp) -> str:
    """A benchmark root with the real cells at a small size: the real
    BENCHMARK.json, reference, metric readers and limits, with small
    configurations and traffic."""
    root = str(tmp)
    bench = os.path.join(root, "bench")
    for d in ("configs", "traffic"):
        os.makedirs(os.path.join(bench, d), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bench, "metrics"), dirs_exist_ok=True)
    shutil.copy(os.path.join(BENCH, "configs", "stencil_reference.py"),
                os.path.join(bench, "configs"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        cfg = small_config(c["name"])
        with open(os.path.join(root, c["file"]), "w") as f:
            json.dump(cfg, f)
    write(bench, "traffic/batch_forecast100.json", {
        "kind": "batch", "steps_per_call": 2, "forecast_steps": 4})
    write(bench, "traffic/serve_bursts.json", {
        "kind": "serve", "burst_interval_s": 0.3, "steps": [1, 4],
        "rotate": 1, "check_sample": 3})
    shutil.copytree(os.path.join(BENCH, "limits"),
                    os.path.join(bench, "limits"), dirs_exist_ok=True)
    write(root, "BENCHMARK.json", spec)
    return root


def write(base: str, rel: str, obj) -> None:
    with open(os.path.join(base, rel), "w") as f:
        json.dump(obj, f)


def add_mesh_cell(root: str, shape=(2, 2), chips: int = 4,
                  name: str = MESH_CELL) -> None:
    """Adds to a small root a batch cell of the real dycore configuration
    decomposed over a `shape` mesh (None: no `mesh` key) on `chips` chips,
    with the limits of `dycore-e11-batch`: files and entries only."""
    bench = os.path.join(root, "bench")
    cfg = small_config("nero_dycore_256x256x64")
    cfg["name"] = "nero_dycore_mesh"
    if shape is not None:
        cfg["mesh"] = {"shape": list(shape), "axes": ["data", "model"]}
    write(bench, "configs/nero_dycore_mesh.json", cfg)
    shutil.copy(os.path.join(bench, "limits", "dycore-e11-batch.json"),
                os.path.join(bench, "limits", f"{name}.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "nero_dycore_mesh", "source": "test",
                            "file": "bench/configs/nero_dycore_mesh.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": name, "config": "nero_dycore_mesh",
                              "traffic": "batch_forecast100",
                              "chips": chips, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "dycore-e11-batch" in m.get("workloads", ()):
            m["workloads"].append(name)
    write(root, "BENCHMARK.json", spec)


def on_four_devices(code: str, *args: str, timeout: float = 900) -> dict:
    """Runs `code` with `args` in a fresh process whose JAX has four CPU
    devices, with the benchmark's modules importable, and returns the JSON
    object it prints as its last line of standard output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([TESTS, BENCH,
                                           os.path.join(ROOT, "src")]))
    r = subprocess.run([sys.executable, "-c", code, *args], env=env,
                       cwd=ROOT,
                       capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0 or not r.stdout.strip():
        raise AssertionError(f"exit {r.returncode}\n{r.stdout[-2000:]}\n"
                             f"{r.stderr[-4000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])

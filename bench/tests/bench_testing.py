"""Helpers for the benchmark's CPU tests: a benchmark root of small cells
in a temporary directory, built from the real files of the benchmark
(reference, metric readers) and small configurations of the same
deployments."""

from __future__ import annotations

import json
import os
import shutil
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

GRID = [4, 16, 16]
MEMBERS = 3


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

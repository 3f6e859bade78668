"""Cells, mixes and metrics are found by name: adding one is adding files
and BENCHMARK.json entries, with no edit to a file that is there."""

import json
import os
import shutil
import time

import pytest

import bench_testing as bt
import harness


def _snapshot(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_a_new_cell_mix_and_metric_need_no_edit(tmp_path):
    root = bt.small_root(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    before = _snapshot(root)
    bench = os.path.join(root, "bench")
    del before["BENCHMARK.json"]      # entries are added, none changed

    cfg = bt.small_config("nero_dycore_256x256x64", members=2)
    cfg["name"] = "tiny_dycore"
    bt.write(bench, "configs/tiny_dycore.json", cfg)
    bt.write(bench, "traffic/batch_run3.json",
             {"kind": "batch", "steps_per_call": 3, "forecast_steps": 9})
    bt.write(bench, "limits/tiny-batch.json", {"checks": {
        "first_call_rel_err": {"limit": 1e-4},
        "last_call_rel_err": {"limit": 1e-4}}})
    with open(os.path.join(bench, "metrics", "steps_done.tiny.py"),
              "w") as f:
        f.write("def read(run):\n    return run.counters['steps']\n")
    spec["configs"].append({"name": "tiny_dycore", "source": "test",
                            "file": "bench/configs/tiny_dycore.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-batch", "config": "tiny_dycore",
                              "traffic": "batch_run3", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "steps_done.tiny", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves":
                              "gridpoint_steps_per_s",
                              "workloads": ["tiny-batch"]})
    spec["end_to_end"][0]["workloads"].append("tiny-batch")
    bt.write(root, "BENCHMARK.json", spec)

    line = harness.run_cell("tiny-batch", 7, 0.3, False, time.perf_counter(),
                            root=root)
    assert line["correct"]
    assert set(line["metrics"]) == {"gridpoint_steps_per_s", "setup_s"}
    cell = harness.load_cell("tiny-batch", root)
    assert cell.config["members"] == 2 and cell.traffic["steps_per_call"] == 3
    assert [m["name"] for m in cell.per_layer] == ["steps_done.tiny"]
    run = harness.Run(cell=cell, counters={"steps": 3 * line["attempted"]},
                      trace=None, device_kind="none")
    assert harness.per_layer_metrics(run) == {
        "steps_done.tiny": {"value": 3 * line["attempted"],
                            "unit": "steps"}}
    after = _snapshot(root)
    assert all(after[k] == v for k, v in before.items())


# `bench/run.py` in a checkout of the benchmark, with the look for a chip
# skipped: the run is on JAX's first `chips` devices and reports none.
RUN_PY = r"""
import os, sys
root = sys.argv[1]
sys.path.insert(0, os.path.join(root, "bench"))
import harness, run
harness.require_devices = lambda chips: None
sys.exit(run.main(["--workload", sys.argv[2], "--seed", str(2**33 + 99),
                   "--seconds", "0.5", "--trace", "0"]))
"""


def test_a_cell_on_a_2x2_mesh_needs_no_edit(tmp_path):
    """A configuration with a `mesh` and a 4-chip workload entry run
    through `bench/run.py` by files and entries alone."""
    root = bt.small_root(tmp_path)
    for f in os.listdir(bt.BENCH):
        if f.endswith(".py"):
            shutil.copy(os.path.join(bt.BENCH, f),
                        os.path.join(root, "bench"))
    before = _snapshot(root)
    del before["BENCHMARK.json"]      # entries are added, none changed
    bt.add_mesh_cell(root)
    line = bt.on_four_devices(RUN_PY, root, bt.MESH_CELL)
    assert line["correct"], line["checks"]
    assert line["device"]["mesh"] == [2, 2]
    assert set(line["metrics"]) == {"gridpoint_steps_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    after = _snapshot(root)
    assert all(after[k] == v for k, v in before.items())


def test_a_metric_that_finds_nothing_is_left_out(tmp_path):
    root = bt.small_root(tmp_path)
    cell = harness.load_cell("dycore-e11-serve", root)
    run = harness.Run(cell=cell, counters={}, trace=None, device_kind="none")
    assert harness.per_layer_metrics(run) == {}


def test_unknown_names_are_errors(tmp_path):
    root = bt.small_root(tmp_path)
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", root)
    with pytest.raises(FileNotFoundError):
        harness.load_reader(os.path.join(root, "bench"), "no_such_metric")


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(bt.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.load_reader(cell.bench_dir, m["name"]))

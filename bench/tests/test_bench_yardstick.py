"""The benchmark's arithmetic: compulsory bytes, rates, tails, latency from
the due time, the gap to the reference, and the table of peaks."""

import json
import math
import os

import pytest

import bench_testing as bt
import yardstick


def _config(name):
    with open(os.path.join(bt.BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["nero_dycore_256x256x64",
                                  "nero_chain_256x256x64"])
def test_compulsory_bytes_are_21_fp32_arrays(name):
    """Reads: 4 fields, wcon, 4 tens, 4 stage_tens; writes: 4 fields and
    4 stage_tens.  84 B per grid-point-step, whatever implements it."""
    cfg = _config(name)
    assert yardstick.compulsory_arrays(cfg) == 21
    assert yardstick.compulsory_bytes_per_point_step(cfg) == 84


def test_compulsory_bytes_follow_dtype_and_fields():
    cfg = _config("nero_dycore_256x256x64")
    cfg["program"]["dtype"] = "bfloat16"
    assert yardstick.compulsory_bytes_per_point_step(cfg) == 42
    cfg["program"]["fields"] = ["u", "v"]
    assert yardstick.compulsory_arrays(cfg) == 2 * 3 + 1 + 2 * 2
    cfg["compulsory"]["reads"].append("halo")
    with pytest.raises(ValueError):
        yardstick.compulsory_arrays(cfg)


def test_gridpoints_count_every_member():
    cfg = _config("nero_dycore_256x256x64")
    assert yardstick.gridpoints(cfg) == 11 * 64 * 256 * 256


def test_rate_is_all_the_work_over_the_whole_window():
    assert yardstick.rate(30.0, 4.0) == 7.5
    with pytest.raises(ValueError):
        yardstick.rate(1.0, 0.0)


def test_percentile_is_over_all_values_with_failures_infinite():
    lat = yardstick.latencies({i: 0.0 for i in range(20)},
                              {i: float(i + 1) for i in range(18)})
    values = list(lat.values())
    assert yardstick.percentile(values, 50) == 10.0
    assert yardstick.percentile(values, 90) == 18.0
    assert yardstick.percentile(values, 95) == math.inf
    assert yardstick.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        yardstick.percentile([], 50)


def test_latency_runs_from_the_due_time():
    lat = yardstick.latencies({7: 2.0, 8: 2.0}, {7: 5.5, 8: None})
    assert lat == {7: 3.5, 8: math.inf}


def test_rel_gap_takes_the_worst_leaf_over_its_norm_or_the_median():
    ref = {"a": 10.0, "b": 1.0, "c": 4.0}
    assert yardstick.rel_gap({"a": 0.1, "b": 0.0, "c": 0.0}, ref) == 0.01
    # A leaf with a small norm is measured against the median leaf's.
    assert yardstick.rel_gap({"a": 0.0, "b": 0.2, "c": 0.0}, ref) == 0.05
    assert math.isnan(yardstick.rel_gap({"a": math.nan, "b": 0.0,
                                         "c": 0.0}, ref))


def test_peaks_table_has_the_v5e_with_its_source():
    v5e = yardstick.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert "TPU v5e" in v5e["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v99"):
        yardstick.peaks_for("TPU v99")

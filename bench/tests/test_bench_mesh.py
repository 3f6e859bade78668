"""A cell on a 2x2 mesh: the configuration's `mesh` makes the harness
compile the program's distributed plan, make the ensemble sharded and
check it on the mesh; the roofline counts every chip.

The runs need four devices: one process with four CPU devices runs them
all (`bench_testing.on_four_devices`) on the real dycore configuration at
the tests' small grid, 2x2 over (y, x), and the tests read what it
recorded.  The limits are `dycore-e11-batch`'s."""

import types

import pytest

import bench_testing as bt
import harness
import yardstick

FAULTS = ("state_unchanged", "half_the_batch", "answer_altered",
          "quadrant_unchanged", "exchange_left_out")

RUNS = r"""
import json, sys, time
import jax
import numpy as np
import bench_testing as bt, harness, traffic, window
from repro.weather.program import ExecutionPlan

root = sys.argv[1]
bt.small_root(root)
bt.add_mesh_cell(root)
SEED = 2**33 + 12345


def placed(tree):
    # (global shape, one chip's shard shape, chips) of every leaf
    return sorted({(a.shape, a.sharding.shard_shape(a.shape),
                    len(a.sharding.device_set))
                   for a in jax.tree_util.tree_leaves(tree)})


seen = {"x0": set(), "out": set(), "check": set()}
plan_of = {}
real_window, real_gap = window.Batch.window, window.gap


def recording_window(self, seconds):
    seen["x0"].update(placed(window._as_dict(self.x0)))
    plan_of.update(distributed=self.plan.distributed,
                   collectives=self.plan.collectives_per_round,
                   shards=list(self.plan.shards))
    real_window(self, seconds)
    seen["out"].update(placed(window._as_dict(self.last_out)))


def recording_gap(got, want, groups):
    seen["check"].update(placed(got) + placed(want))
    return real_gap(got, want, groups)


def run(dtype=None):
    line = harness.run_cell(bt.MESH_CELL, SEED, 0.5, False,
                            time.perf_counter(), root=root, dtype=dtype)
    return {"correct": line["correct"], "attempted": line["attempted"],
            "device": line["device"],
            "checks": {k: c["value"] for k, c in line["checks"].items()}}


window.Batch.window, window.gap = recording_window, recording_gap
out = {"program": run()}
out["placed"] = {k: sorted(v) for k, v in seen.items()}
out["plan"] = plan_of
out["bfloat16"] = run("bfloat16")
window.Batch.window, window.gap = real_window, real_gap

step = ExecutionPlan.run


def keep(new, old, where):
    return jax.tree_util.tree_map(
        lambda a, b: a.at[where].set(b[where]), new, old)


def quadrant(self, state, steps):
    ny, nx = state.wcon.shape[-2:]
    return keep(step(self, state, steps), state,
                (Ellipsis, slice(0, ny // 2), slice(0, nx // 2)))


def half(self, state, steps):
    e = state.wcon.shape[0]
    return keep(step(self, state, steps), state, slice(e // 2, None))


def altered(self, state, steps):
    new = step(self, state, steps)
    u = new.fields["u"]
    return type(new)(fields=dict(new.fields, u=u.at[0].set(u[1])),
                     wcon=new.wcon, tens=new.tens,
                     stage_tens=new.stage_tens)


FAULTS = {
    "state_unchanged": (ExecutionPlan, "run",
                        lambda self, state, steps: state),
    "half_the_batch": (ExecutionPlan, "run", half),
    "answer_altered": (ExecutionPlan, "run", altered),
    "quadrant_unchanged": (ExecutionPlan, "run", quadrant),
    # Each shard takes its own edge for its neighbour's halo.
    "exchange_left_out": (jax.lax, "ppermute",
                          lambda x, axis_name, perm: x),
}
for name, (owner, attr, fault) in FAULTS.items():
    real = getattr(owner, attr)
    setattr(owner, attr, fault)
    try:
        out[name] = run()
    finally:
        setattr(owner, attr, real)

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental import mesh_utils
mesh = Mesh(mesh_utils.create_device_mesh((2, 2), jax.devices()),
            ("data", "model"))
sharding = NamedSharding(mesh, P(None, None, "data", "model"))
equal = {}
for grid, members in ((bt.GRID, 3), ([8, 32, 64], 2)):
    program = dict(bt.small_config("nero_dycore_256x256x64")["program"],
                   grid_shape=grid)
    one = traffic.make_state(SEED, program, members)
    four = traffic.make_state(SEED, program, members, sharding)
    equal[str(grid)] = {
        "bitwise": all(np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(jax.tree_util.tree_leaves(one),
                                       jax.tree_util.tree_leaves(four))),
        "placed": placed(four)}
out["make_state"] = equal
out["threefry_partitionable"] = bool(jax.config.jax_threefry_partitionable)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    return bt.on_four_devices(RUNS, str(tmp_path_factory.mktemp("mesh")))


def _share(entry):
    (shape, shard, chips) = entry
    return shard[-2:] == [shape[-2] // 2, shape[-1] // 2] and chips == 4


def test_mesh_cell_is_correct_and_runs_sharded(four):
    line = four["program"]
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0
    assert line["device"]["mesh"] == [2, 2]
    assert four["plan"] == {"distributed": True, "collectives": 4,
                            "shards": [2, 2]}
    # Every array of set-up, window and check is a quarter on each chip.
    for phase in ("x0", "out", "check"):
        assert four["placed"][phase], phase
        assert all(_share(e) for e in four["placed"][phase]), phase


def test_mesh_bfloat16_control_is_not_correct(four):
    line = four["bfloat16"]
    assert not line["correct"], line["checks"]
    assert line["device"]["mesh"] == [2, 2]


@pytest.mark.parametrize("fault", FAULTS)
def test_mesh_fault_is_not_correct(four, fault):
    assert not four[fault]["correct"], four[fault]["checks"]


def test_sharded_initial_state_is_the_one_device_state_bitwise(four):
    assert four["threefry_partitionable"]
    for grid, got in four["make_state"].items():
        assert got["bitwise"], grid
        assert all(_share(e) for e in got["placed"]), grid


def _run(chips):
    cell = types.SimpleNamespace(chips=chips, config=bt.small_config(
        "nero_dycore_256x256x64"))
    trace = types.SimpleNamespace(busy_s=2.0, idle_share=0.25)
    return types.SimpleNamespace(cell=cell, config=cell.config,
                                 counters={"gridpoint_steps": 1e10},
                                 trace=trace,
                                 peaks=yardstick.peaks_for("TPU v5 lite"))


def test_roofline_on_four_chips_is_a_quarter_of_one_chips():
    one, four = (yardstick.step_roofline(_run(n)) for n in (1, 4))
    assert one == 100.0 * 84 * 1e10 / 2.0 / 819e9
    assert four == pytest.approx(one / 4, rel=1e-15)
    # Busy time is already the chips' mean: the idle share is its own.
    assert yardstick.idle_share(_run(4)) == yardstick.idle_share(_run(1))


@pytest.mark.parametrize("shape, chips, match", [
    (None, 4, "needs a 'mesh'"), ((2, 1), 4, "holds 2 chips"),
    ((2, 2), 1, "holds 4 chips"), ((2, 2), 2, "holds 4 chips"),
    ((2, 2, 1), 4, r"is not \{'shape'")])
def test_load_cell_refuses_chips_the_mesh_does_not_hold(tmp_path, shape,
                                                        chips, match):
    root = bt.small_root(tmp_path)
    bt.add_mesh_cell(root, shape=shape, chips=chips)
    with pytest.raises(ValueError, match=match):
        harness.load_cell(bt.MESH_CELL, root)


def test_a_one_chip_cell_has_no_mesh(tmp_path):
    root = bt.small_root(tmp_path)
    for w in ("dycore-e11-batch", "dycore-e11-serve", "chain-e10-batch"):
        assert "mesh" not in harness.load_cell(w, root).config


def test_serve_refuses_a_mesh(tmp_path):
    root = bt.small_root(tmp_path)
    path = tmp_path / "bench" / "configs" / \
        "nero_dycore_served_256x256x64.json"
    cfg = bt.small_config("nero_dycore_served_256x256x64")
    cfg["mesh"] = {"shape": [1, 1], "axes": ["data", "model"]}
    bt.write(str(path.parent), path.name, cfg)
    with pytest.raises(ValueError, match="mesh"):
        harness.run_cell("dycore-e11-serve", 7, 0.3, False, 0.0, root=root)

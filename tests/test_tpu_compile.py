"""Compile-only rehearsal of the weather path for a TPU v5e.

Each test compiles one plan's step at the paper's domain (64, 256, 256)
for a described — not attached — v5e chip, with `interpret=False`, and
checks that the Pallas kernels really lowered (`tpu_custom_call` in the
compiled HLO).  The TPU compiler refuses here what the Pallas interpreter
would accept: unaligned blocks, in-kernel scatters, kernels that exceed the
scoped VMEM limit.  Nothing runs, so nothing here says anything about
results or times.

The topology is described inside a module fixture (never at import, so
every xdist worker collects the same tests) and the fixture skips where it
cannot be described.  JAX's persistent compilation cache is off around the
compiles: an entry written for a described chip cannot be read back here.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.weather.fields import PROGNOSTIC, WeatherState
from repro.weather.pipeline import PipelineProgram
from repro.weather.program import StencilProgram, compile

GRID = (64, 256, 256)
FLAGSHIP = ("hadv_upwind", "vadvc_update", "hdiff")
ONE_CHIP = {
    "hdiff": dict(op="hdiff"),
    "hadv_upwind": dict(op="hadv_upwind"),
    "vadvc": dict(op="vadvc"),
    "vadvc_update": dict(op="vadvc_update"),
    "dycore_whole_state": dict(op="dycore", variant="whole_state"),
    "dycore_kstep": dict(op="dycore", variant="kstep", k_steps=2),
    "pipeline": dict(stages=FLAGSHIP),
}
MESH_2X2 = {
    "dycore_kstep": dict(op="dycore", variant="kstep", k_steps=2),
    "pipeline": dict(stages=FLAGSHIP),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _program(ensemble, spec):
    if "stages" in spec:
        return PipelineProgram(grid_shape=GRID, ensemble=ensemble, **spec)
    return StencilProgram(grid_shape=GRID, ensemble=ensemble, **spec)


def _state_shapes(sharding, ensemble):
    leaf = jax.ShapeDtypeStruct((ensemble,) + GRID, jnp.float32,
                                sharding=sharding)
    per = {n: leaf for n in PROGNOSTIC}
    return WeatherState(fields=per, wcon=leaf, tens=dict(per),
                        stage_tens=dict(per))


def _compiled_text(plan, shapes):
    assert plan.interpret is False
    return jax.jit(plan.step).lower(shapes).compile().as_text()


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_one_chip_plan_compiles_native(name, topo, no_compile_cache):
    """Every op step of the weather path compiles for one v5e chip."""
    plan = compile(_program(2, ONE_CHIP[name]), interpret=False)
    if plan.variant == "kstep":
        assert plan.prefetch_w      # the DMA path, default off-interpreter
    text = _compiled_text(plan, _state_shapes(
        SingleDeviceSharding(topo.devices[0]), 2))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", sorted(MESH_2X2))
def test_2x2_plan_compiles_native(name, topo, no_compile_cache):
    """The domain-decomposed plans compile on a 2x2 mesh of v5e chips:
    Pallas kernels plus the packed halo exchange's collective permutes.
    One member (the ensemble is a batch axis; it only scales the compile
    time here)."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = compile(_program(1, MESH_2X2[name]), mesh=mesh, interpret=False)
    text = _compiled_text(plan, _state_shapes(
        NamedSharding(mesh, plan.state_spec), 1))
    assert "tpu_custom_call" in text
    assert plan.collectives_per_round > 0
    assert "collective-permute" in text


def _computation(text, name):
    """The instruction lines of HLO computation `name` in `text`."""
    block = re.search(r"^%" + re.escape(name) + r" .*?\n(.*?)^}", text,
                      re.S | re.M)
    assert block, name
    return block.group(1).splitlines()


def test_batch_rounds_loop_holds_only_the_kernels(topo, no_compile_cache):
    """The batch cell's `run(state, 10)`: 11 members at (64, 256, 256),
    whole-state.  The round loop carries the stacked state, so each trip
    of the compiled while loop is the two unrolled kernel launches: no
    stack, relayout copy or unstack of the state inside it."""
    plan = compile(_program(11, ONE_CHIP["dycore_whole_state"]),
                   interpret=False)
    shapes = _state_shapes(SingleDeviceSharding(topo.devices[0]), 11)
    text = plan._rounds_fn(10).lower(shapes).compile().as_text()
    (body,) = re.findall(r" while\(.*?body=%([\w.-]+)", text)
    lines = _computation(text, body)
    ops = [m.group(2) for m in (re.match(r"\s*(ROOT )?%\S+ = .*? "
                                         r"([a-z][a-z0-9-]*)\(", line)
                                for line in lines) if m]
    kernels = [line for line in lines if "custom-call(" in line]
    assert len(kernels) == 2
    assert all("%nero_dycore_whole_state" in line for line in kernels)
    assert not {"copy", "fusion", "concatenate"} & set(ops), ops

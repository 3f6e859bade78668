"""Elastic mesh failover + mesh-elastic engine restore (ISSUE 8).

Two recovery paths share one mechanism (gather to unsharded-logical,
recompile with the pinned round strategy, reshard through the new plan's
`state_spec`):

* **In-place failover** — a device falls out of the fabric mid-round; the
  engine rebuilds a mesh from the survivors and resumes every in-flight
  request from the last round boundary.  The kill-a-device test asserts
  the strongest form of the contract: every request completes
  ``status=="ok"`` BIT-identical to a solo run compiled on the ORIGINAL
  mesh, with ``lane_failures == 0``.
* **Elastic restore** — `ForecastEngine.restore(mesh=...)` accepts a
  checkpoint written on ANY device count.  The transition sweep
  (1→4, 4→1, 4→2) asserts bitwise identity to an uninterrupted run.

Bitwise caveat the sweep encodes (see docs/robustness.md for the full
matrix): collapsing a SHARDED mesh axis to one shard switches that axis
from halo-exchange to wrap-padding lowering and changes result bits for
ops that are not sharding-transparent (dycore, vadvc) — while shrinking a
sharded axis (2x2 → 2x1) keeps bits, and hdiff is bitwise mesh-invariant
everywhere.  So the 1↔4 legs run hdiff and the 4→2 leg adds dycore.

Mesh-level chaos (wire corruption caught by the fingerprint guard,
stragglers caught by the round-deadline watchdog) runs in-process below.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.serve.forecast import ForecastEngine, ForecastRequest
from repro.testing.faults import FaultInjector, FaultSpec
from repro.weather import domain, fields
from repro.weather import program as wprog
from repro.weather.program import StencilProgram

GRID = (3, 8, 8)
PROG = StencilProgram(grid_shape=GRID, ensemble=1)


def _state(seed, grid=GRID):
    return fields.initial_state(jax.random.PRNGKey(seed), grid, ensemble=1)


def _assert_bits(result, state, prog=None):
    prog = prog or result.program
    want = wprog.compile(prog).run(state, result.steps)
    for name in prog.fields:
        np.testing.assert_array_equal(np.asarray(result.state.fields[name]),
                                      np.asarray(want.fields[name]),
                                      err_msg=name)


def _run_snippet(snippet, marker, extra_env=None):
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu"}
    env.update(extra_env or {})
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    r = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert marker in r.stdout, (r.stdout[-1000:], r.stderr[-2000:])


_FORCE4 = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}

_COMMON = r"""
import os, numpy as np, jax
from repro.serve.forecast import ForecastEngine, ForecastRequest
from repro.testing.faults import FaultInjector, FaultSpec
from repro.weather import domain, fields
from repro.weather import program as wprog
from repro.weather.program import StencilProgram

def make_mesh(py, px):
    kw = {"axis_types": (jax.sharding.AxisType.Auto,) * 2}
    return jax.make_mesh((py, px), ("data", "model"), **kw)
"""


# ---------------------------------------------------------------------------
# Kill a device: in-place failover, in-flight work preserved bit-for-bit
# ---------------------------------------------------------------------------

_KILL_DEVICE_SNIPPET = _COMMON + r"""
assert len(jax.devices()) == 4
mesh = make_mesh(2, 2)
grid = (4, 16, 16)
prog = StencilProgram(grid_shape=grid, ensemble=1)
states = [fields.initial_state(jax.random.PRNGKey(s), grid, ensemble=1)
          for s in (0, 1, 2)]
steps = (5, 3, 4)

# The reference: solo runs compiled on the ORIGINAL (pre-failure) mesh.
solo = wprog.compile(prog, mesh=mesh)
refs = [solo.run(domain.shard_state(s, mesh, solo.state_spec), n)
        for s, n in zip(states, steps)]

# Device 3 falls out of the fabric at round 1 and STAYS dead: the spec
# fires on every round while device 3 is part of the mesh the engine
# steps on, so only an actual failover clears it.
inj = FaultInjector([FaultSpec(kind="device_loss", round=1, device=3,
                               once=False)])
eng = ForecastEngine(slots=2, mesh=mesh, fault_injector=inj,
                     max_round_retries=1, retry_backoff_s=0.01)
rids = [eng.submit(ForecastRequest(program=prog, state=s, steps=n))
        for s, n in zip(states, steps)]
res = eng.drain()
st = eng.stats()

assert st["mesh_failovers"] >= 1, st
assert st["lane_failures"] == 0, st
assert st["recovery_rounds"] >= 1 and st["requests_preserved"] >= 1, st
fo = st["failovers"][0]
assert fo["lost_device"] == 3
assert 3 not in fo["to_devices"]
# 3 survivors cannot carry a 16x16 grid (16 % 3 != 0); the chosen shape
# must keep the y axis sharded (the bitwise-safe direction): 2x2 -> 2x1.
assert fo["from_shape"] == [2, 2] and fo["to_shape"] == [2, 1], fo
assert st["mesh_devices"] is not None and len(st["mesh_devices"]) == 2

for rid, ref in zip(rids, refs):
    assert res[rid].status == "ok", res[rid].diagnosis
    for name in prog.fields:
        assert np.array_equal(np.asarray(res[rid].state.fields[name]),
                              np.asarray(ref.fields[name])), (rid, name)
print("FAILOVER_KILL_OK")
"""


def test_kill_device_failover_preserves_inflight_forced_4dev():
    """A persistent device loss on a forced-4-device 2x2 mesh: every
    in-flight request completes ok, bit-identical to a solo run on the
    ORIGINAL mesh, without a single lane failure."""
    _run_snippet(_KILL_DEVICE_SNIPPET, "FAILOVER_KILL_OK", _FORCE4)


# ---------------------------------------------------------------------------
# Elastic restore: checkpoint written on one mesh, resumed on another
# ---------------------------------------------------------------------------

# Phase A runs under WRITE_MESH (or single-chip), pumps a couple of rounds
# and checkpoints mid-flight; phase B restores under READ_MESH and asserts
# every drained result is bit-identical to an uninterrupted solo run
# compiled on REF_MESH (empty = single-chip).
_RESTORE_WRITE_SNIPPET = _COMMON + r"""
def mesh_of(env):
    v = os.environ.get(env, "")
    return make_mesh(*map(int, v.split("x"))) if v else None

grid = (4, 16, 16)
ops = os.environ["RESTORE_OPS"].split(",")
eng = ForecastEngine(slots=2, mesh=mesh_of("WRITE_MESH"),
                     ckpt_dir=os.environ["RESTORE_CKPT"])
for i, op in enumerate(ops * 2):
    st = fields.initial_state(jax.random.PRNGKey(i), grid, ensemble=1)
    prog = StencilProgram(grid_shape=grid, ensemble=1, op=op)
    eng.submit(ForecastRequest(program=prog, state=st, steps=6 + i))
eng.pump()
eng.pump()
eng.checkpoint()
assert eng.has_work(), "checkpoint must land mid-flight"
print("RESTORE_WRITE_OK")
"""

_RESTORE_READ_SNIPPET = _COMMON + r"""
def mesh_of(env):
    v = os.environ.get(env, "")
    return make_mesh(*map(int, v.split("x"))) if v else None

grid = (4, 16, 16)
ops = os.environ["RESTORE_OPS"].split(",")
eng = ForecastEngine.restore(os.environ["RESTORE_CKPT"],
                             mesh=mesh_of("READ_MESH"))
res = eng.drain()
ref_mesh = mesh_of("REF_MESH")
for i, op in enumerate(ops * 2):
    st = fields.initial_state(jax.random.PRNGKey(i), grid, ensemble=1)
    prog = StencilProgram(grid_shape=grid, ensemble=1, op=op)
    solo = wprog.compile(prog, mesh=ref_mesh)
    if ref_mesh is not None:
        st = domain.shard_state(st, ref_mesh, solo.state_spec)
    want = solo.run(st, 6 + i)
    assert res[i].status == "ok", res[i].diagnosis
    for name in prog.fields:
        assert np.array_equal(np.asarray(res[i].state.fields[name]),
                              np.asarray(want.fields[name])), (i, op, name)
print("RESTORE_READ_OK")
"""


@pytest.mark.parametrize(
    "write,read,ref,ops",
    [
        ("", "2x2", "", "hdiff"),          # 1 -> 4: scale up
        ("2x2", "", "", "hdiff"),          # 4 -> 1: scale down to a chip
        ("2x2", "2x1", "2x2", "dycore,hdiff"),   # 4 -> 2: lose a node
    ],
    ids=["1to4", "4to1", "4to2"])
def test_elastic_restore_transition_bitwise(tmp_path, write, read, ref, ops):
    """The mesh-transition restore sweep: a checkpoint written on one
    mesh shape resumes on another and drains bit-identical to an
    uninterrupted solo run.  The 1↔4 legs use hdiff (bitwise
    mesh-invariant everywhere); 4→2 shrinks a sharded axis — the
    bitwise-safe direction — so dycore rides too."""
    env = dict(_FORCE4)
    env.update({"RESTORE_CKPT": str(tmp_path), "RESTORE_OPS": ops,
                "WRITE_MESH": write, "READ_MESH": read, "REF_MESH": ref})
    _run_snippet(_RESTORE_WRITE_SNIPPET, "RESTORE_WRITE_OK", env)
    _run_snippet(_RESTORE_READ_SNIPPET, "RESTORE_READ_OK", env)


# ---------------------------------------------------------------------------
# The fingerprint guard is sharding-invariant (the property failover and
# the wire-corruption detector both lean on)
# ---------------------------------------------------------------------------

_FP_INVARIANT_SNIPPET = _COMMON + r"""
from jax.sharding import NamedSharding, PartitionSpec as P
grid = (4, 16, 16)
batch = fields.initial_state(jax.random.PRNGKey(7), grid, ensemble=2)
ok_solo, fp_solo = map(np.asarray, wprog.slot_guard(batch, 1e6))
mesh = make_mesh(2, 2)
sharded = jax.tree.map(
    lambda a: jax.device_put(a, NamedSharding(mesh,
                                              P(None, None, "data",
                                                "model"))), batch)
ok_sh, fp_sh = map(np.asarray, wprog.slot_guard(sharded, 1e6))
assert np.array_equal(ok_solo, ok_sh)
assert np.array_equal(fp_solo, fp_sh), (fp_solo, fp_sh)
print("FP_INVARIANT_OK")
"""


def test_slot_guard_fingerprint_is_sharding_invariant_forced_4dev():
    _run_snippet(_FP_INVARIANT_SNIPPET, "FP_INVARIANT_OK", _FORCE4)


def test_slot_guard_detects_inplace_corruption():
    """The digest sees what magnitude checks cannot: finite, in-bounds
    damage to one slot changes ONLY that slot's fingerprint, and element
    swaps (which preserve every per-element statistic) change it too."""
    batch = fields.initial_state(jax.random.PRNGKey(3), GRID, ensemble=3)
    ok0, fp0 = map(np.asarray, wprog.slot_guard(batch, 1e6))
    assert ok0.all()

    inj = FaultInjector([FaultSpec(kind="wire_corrupt", round=0, slot=1)])
    poisoned = inj.poison(batch, "dycore", 0, (0, 1, 2),
                          nonparticipants=(1,))
    ok1, fp1 = map(np.asarray, wprog.slot_guard(poisoned, 1e6))
    assert ok1.all(), "wire corruption must PASS the validity guard"
    assert fp1[1] != fp0[1], "corrupted slot's digest must change"
    assert fp1[0] == fp0[0] and fp1[2] == fp0[2], \
        "healthy slots' digests must not change"

    u = np.array(batch.fields["u"])
    a, b = u[1, 0, 1, 1].copy(), u[1, 2, 5, 3].copy()
    assert a != b
    u[1, 0, 1, 1], u[1, 2, 5, 3] = b, a
    swapped = jax.tree_util.tree_map(lambda x: x, batch)
    swapped.fields = dict(swapped.fields)
    swapped.fields["u"] = u
    _, fp2 = map(np.asarray, wprog.slot_guard(swapped, 1e6))
    assert fp2[1] != fp0[1], "position-blind digests would miss swaps"


# ---------------------------------------------------------------------------
# Wire corruption: caught by the fingerprint at the boundary it occurs
# ---------------------------------------------------------------------------


def test_wire_corrupt_idle_slot_scrubbed_not_served():
    """Corruption landing in an IDLE slot (stale bits a dead wire buffer
    would scribble on) is scrubbed at the next round boundary and counted
    — the in-flight request is untouched, bit-for-bit."""
    inj = FaultInjector([FaultSpec(kind="wire_corrupt", round=1)])
    eng = ForecastEngine(slots=2, fault_injector=inj)
    s = _state(10)
    rid = eng.submit(ForecastRequest(program=PROG, state=s, steps=3))
    res = eng.drain()
    st = eng.stats()
    assert inj.fired("wire_corrupt") == 1
    assert st["fingerprint_divergence"] == 1
    assert st["scrubbed_idle_slots"] == 1
    assert st["quarantined"] == 0
    assert res[rid].status == "ok"
    _assert_bits(res[rid], s)


def test_wire_corrupt_rolled_back_slot_quarantines():
    """A rolled-back slot's bits provably must not change across the
    round — corruption there quarantines that request with a
    `fingerprint_divergence` diagnosis while its lane-mate completes
    bit-identically.  (k_steps=2 with steps 4 vs 3 forces the deep slot
    to be rolled back on the ragged round — the corruption target.)"""
    prog = StencilProgram(grid_shape=GRID, ensemble=1, variant="kstep",
                          k_steps=2)
    inj = FaultInjector([FaultSpec(kind="wire_corrupt", round=1, slot=0)])
    eng = ForecastEngine(slots=2, fault_injector=inj)
    s0, s1 = _state(11), _state(12)
    r0 = eng.submit(ForecastRequest(program=prog, state=s0, steps=4))
    r1 = eng.submit(ForecastRequest(program=prog, state=s1, steps=3))
    res = eng.drain()
    st = eng.stats()
    assert st["fingerprint_divergence"] == 1
    assert res[r0].status == "failed"
    d = res[r0].diagnosis
    assert d["reason"] == "fingerprint_divergence"
    assert d["expected_fp"] != d["observed_fp"]
    assert res[r1].status == "ok"
    _assert_bits(res[r1], s1, prog)


def test_guard_off_lets_wire_corruption_through():
    """guard=False documents what the fingerprint buys: the same
    corruption flows into an `ok` result."""
    inj = FaultInjector([FaultSpec(kind="wire_corrupt", round=1, slot=0)])
    prog = StencilProgram(grid_shape=GRID, ensemble=1, variant="kstep",
                          k_steps=2)
    eng = ForecastEngine(slots=2, guard=False, fault_injector=inj)
    r0 = eng.submit(ForecastRequest(program=prog, state=_state(13), steps=4))
    eng.submit(ForecastRequest(program=prog, state=_state(14), steps=3))
    res = eng.drain()
    assert res[r0].status == "ok"
    assert eng.stats()["fingerprint_divergence"] == 0


# ---------------------------------------------------------------------------
# Straggler: the round-deadline watchdog
# ---------------------------------------------------------------------------


def test_straggler_hits_round_deadline_and_recovers():
    """A hung collective (straggler sleep > round_deadline_s) counts as a
    failed attempt: the watchdog records the overrun, the retry serves
    the round, nothing is lost.  The deadline is armed only after a
    warm-up request so plan compile time never counts against it."""
    inj = FaultInjector([FaultSpec(kind="straggler", round=2, delay_s=0.3)])
    eng = ForecastEngine(slots=1, fault_injector=inj, retry_backoff_s=0.0)
    warm = eng.submit(ForecastRequest(program=PROG, state=_state(20),
                                      steps=2))
    eng.drain()                             # rounds 0..1 compile the plan
    eng.round_deadline_s = 0.05
    s = _state(21)
    rid = eng.submit(ForecastRequest(program=PROG, state=s, steps=3))
    res = eng.drain()
    st = eng.stats()
    assert inj.fired("straggler") == 1
    assert st["round_deadline_hits"] == 1
    assert st["round_retries"] == 1
    assert st["lane_failures"] == 0
    assert res[warm].status == "ok" and res[rid].status == "ok"
    _assert_bits(res[rid], s)


def test_straggler_under_deadline_is_not_flagged():
    inj = FaultInjector([FaultSpec(kind="straggler", round=0,
                                   delay_s=0.01)])
    eng = ForecastEngine(slots=1, fault_injector=inj, round_deadline_s=30.0)
    rid = eng.submit(ForecastRequest(program=PROG, state=_state(22),
                                     steps=2))
    res = eng.drain()
    assert res[rid].status == "ok"
    assert eng.stats()["round_deadline_hits"] == 0


# ---------------------------------------------------------------------------
# Failover mesh candidates (the shape-selection policy)
# ---------------------------------------------------------------------------


def test_failover_meshes_prefers_pattern_preserving_shapes():
    """Survivor shapes are ordered: most devices first, then shapes whose
    sharded-axis pattern matches the dying mesh (the bitwise-safe
    transitions), then taller-y.  With one real device only (1, 1) is
    offered — the policy is exercised at scale in the subprocess tests,
    via the failover detail's to_shape."""
    dev = jax.devices()[:1]
    meshes = domain.failover_meshes(dev, [(4, 16, 16)], like=(2, 2))
    assert [m.devices.shape for m in meshes] == [(1, 1)]
    # no survivors -> no candidates rather than a broken mesh
    assert domain.failover_meshes([], [(4, 16, 16)]) == []


def test_failover_disabled_fails_lane_as_before():
    """failover=False restores the pre-ISSUE-8 contract: a persistent
    loss fails the lane (diagnosed), never silently reshapes the mesh."""
    inj = FaultInjector([FaultSpec(kind="device_loss", round=1,
                                   once=False)])
    eng = ForecastEngine(slots=2, failover=False, max_round_retries=1,
                         retry_backoff_s=0.0, fault_injector=inj)
    rid = eng.submit(ForecastRequest(program=PROG, state=_state(30),
                                     steps=3))
    res = eng.drain()
    st = eng.stats()
    assert st["lane_failures"] == 1 and st["mesh_failovers"] == 0
    assert res[rid].status == "failed"
    assert res[rid].diagnosis["reason"] == "round_failure"

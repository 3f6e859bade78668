"""Forecast-as-a-service engine: batching invariance, plan cache, restarts.

The core correctness contract of admission batching (ISSUE 6): every
request served through `ForecastEngine` — batched into the ensemble axis
of a shared plan, retired raggedly at round boundaries, backfilled from
the queue — is BIT-IDENTICAL to the same request run solo through
`compile(program).run()`.  The property harness below drives that over
random mixes of grids / ops / step counts / precisions; it uses
`hypothesis` when the dev extra is installed and a seeded deterministic
sweep of the same property otherwise (so the module never skips).
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.serve.forecast import (ForecastEngine, ForecastRequest,
                                  ForecastResult)
from repro.testing.faults import FaultInjector, FaultSpec
from repro.weather import fields
from repro.weather import program as wprog
from repro.weather.program import StencilProgram, plan_cache_key

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                              # pragma: no cover
    HAVE_HYPOTHESIS = False

# Small grids keep interpret-mode Pallas fast; two shapes + two dtypes +
# three ops + a pinned-k program span the scenario axes.
_GRIDS = ((3, 8, 8), (4, 12, 16))
_OPS = ("dycore", "hdiff", "vadvc")
_DTYPES = ("float32", "bfloat16")

_SOLO_PLANS = {}


def _solo_plan(prog):
    plan = _SOLO_PLANS.get(prog)
    if plan is None:
        plan = _SOLO_PLANS.setdefault(prog, wprog.compile(prog))
    return plan


def _mk_request(seed, grid_i, op_i, dtype_i, steps, pinned_k=False):
    grid = _GRIDS[grid_i % len(_GRIDS)]
    op = _OPS[op_i % len(_OPS)]
    dtype = _DTYPES[dtype_i % len(_DTYPES)]
    kw = {}
    if pinned_k and op == "dycore":
        kw = {"variant": "kstep", "k_steps": 2}
    prog = StencilProgram(grid_shape=grid, ensemble=1, op=op, dtype=dtype,
                          **kw)
    state = fields.initial_state(jax.random.PRNGKey(seed), grid,
                                 ensemble=1, dtype=dtype)
    return ForecastRequest(program=prog, state=state, steps=steps)


def _assert_bit_identical(result: ForecastResult, request_state):
    """result == compile(program).run(state, steps), every field, bitwise."""
    want = _solo_plan(result.program).run(request_state, result.steps)
    for name in result.program.fields:
        np.testing.assert_array_equal(
            np.asarray(result.state.fields[name]),
            np.asarray(want.fields[name]),
            err_msg=f"fields[{name}] steps={result.steps} "
                    f"op={result.program.op}")
        np.testing.assert_array_equal(
            np.asarray(result.state.stage_tens[name]),
            np.asarray(want.stage_tens[name]),
            err_msg=f"stage_tens[{name}] steps={result.steps} "
                    f"op={result.program.op}")


# One engine for the whole property run: its plan cache persists across
# examples exactly like a long-lived service's would.
_ENGINE = ForecastEngine(slots=2)


def _check_mix(mix):
    """Serve `mix` (list of request descriptors) and compare every result
    to its solo run, bitwise."""
    reqs = []
    for seed, (grid_i, op_i, dtype_i, steps, pinned) in enumerate(mix):
        req = _mk_request(seed, grid_i, op_i, dtype_i, steps, pinned)
        state = req.state        # keep a handle: engine may donate/stage
        rid = _ENGINE.submit(req)
        reqs.append((rid, state))
    results = _ENGINE.drain()
    for rid, state in reqs:
        _assert_bit_identical(results[rid], state)


_CASE = st.tuples(st.integers(0, 1), st.integers(0, 2), st.integers(0, 1),
                  st.integers(0, 4),
                  st.booleans()) if HAVE_HYPOTHESIS else None

if HAVE_HYPOTHESIS:
    @settings(max_examples=4, deadline=None, derandomize=True)
    @given(st.lists(_CASE, min_size=2, max_size=4))
    def test_batching_invariance_property(mix):
        _check_mix(mix)
else:
    def test_batching_invariance_property():
        """Seeded fallback: the same property over deterministic random
        mixes (hypothesis drives this when the dev extra is present)."""
        rng = np.random.default_rng(0)
        for _ in range(3):
            n = int(rng.integers(2, 5))
            mix = [(int(rng.integers(0, 2)), int(rng.integers(0, 3)),
                    int(rng.integers(0, 2)), int(rng.integers(0, 5)),
                    bool(rng.integers(0, 2))) for _ in range(n)]
            _check_mix(mix)


def test_ragged_pinned_k_rollback_bit_identical():
    """Mixed step counts on a pinned k_steps=2 program force the rollback
    scheduler (slots whose next canonical part is deeper than the round
    sit it out uncredited) — results must still be solo-bit-identical and
    the engine must report the rollbacks it performed."""
    grid = (3, 8, 8)
    prog = StencilProgram(grid_shape=grid, ensemble=1, variant="kstep",
                          k_steps=2)
    eng = ForecastEngine(slots=3)
    reqs = []
    for i, steps in enumerate([7, 10, 3, 4, 1]):
        st_ = fields.initial_state(jax.random.PRNGKey(10 + i), grid,
                                   ensemble=1)
        rid = eng.submit(ForecastRequest(program=prog, state=st_,
                                         steps=steps))
        reqs.append((rid, st_))
    results = eng.drain()
    for rid, st_ in reqs:
        _assert_bit_identical(results[rid], st_)
    assert eng.stats()["rolled_back_slot_rounds"] > 0


def test_request_validation_and_zero_steps():
    grid = (3, 8, 8)
    st_ = fields.initial_state(jax.random.PRNGKey(0), grid, ensemble=1)
    prog = StencilProgram(grid_shape=grid, ensemble=1)
    with pytest.raises(ValueError, match="ensemble"):
        ForecastRequest(program=StencilProgram(grid_shape=grid, ensemble=2),
                        state=st_, steps=1).validate()
    with pytest.raises(ValueError, match="steps"):
        ForecastRequest(program=prog, state=st_, steps=-1).validate()
    with pytest.raises(ValueError, match="dtype"):
        ForecastRequest(program=StencilProgram(grid_shape=grid,
                                               dtype="bfloat16"),
                        state=st_, steps=1).validate()
    with pytest.raises(ValueError, match="grid"):
        ForecastRequest(program=StencilProgram(grid_shape=(4, 12, 16)),
                        state=st_, steps=1).validate()
    # steps == 0 finishes immediately (no slot) and returns the input
    eng = ForecastEngine(slots=1)
    rid = eng.submit(ForecastRequest(program=prog, state=st_, steps=0))
    res = eng.drain()[rid]
    assert res.rounds == 0
    for name in prog.fields:
        np.testing.assert_array_equal(np.asarray(res.state.fields[name]),
                                      np.asarray(st_.fields[name]))


def test_plan_cache_exactly_m_compiles(monkeypatch):
    """N requests over M distinct programs compile exactly M plans (the
    compile-once-serve-forever contract), observed by a spy on
    `repro.weather.program.compile`, and the engine's own cache counters
    agree: M misses, N-M hits."""
    calls = []
    real_compile = wprog.compile

    def spy(program, *a, **kw):
        calls.append(program)
        return real_compile(program, *a, **kw)

    monkeypatch.setattr(wprog, "compile", spy)
    progs = [StencilProgram(grid_shape=(3, 8, 8), ensemble=1),
             StencilProgram(grid_shape=(3, 8, 8), ensemble=1, op="hdiff")]
    eng = ForecastEngine(slots=2)
    reqs = []
    for i in range(6):
        prog = progs[i % 2]
        st_ = fields.initial_state(jax.random.PRNGKey(20 + i),
                                   prog.grid_shape, ensemble=1)
        rid = eng.submit(ForecastRequest(program=prog, state=st_,
                                         steps=1 + i % 3))
        reqs.append((rid, st_))
    results = eng.drain()
    assert sorted(results) == sorted(r for r, _ in reqs)
    assert len(calls) == 2, [p.op for p in calls]
    assert {p.ensemble for p in calls} == {eng.slots}
    s = eng.stats()
    assert s["plan_cache_misses"] == 2 and s["plan_cache_hits"] == 4
    assert s["plan_cache_hit_rate"] == pytest.approx(4 / 6)
    # the cache key canonicalizes the request program onto the slot count
    assert plan_cache_key(progs[0], ensemble=2) in eng._plans


def test_per_request_latency_accounting():
    """Each result carries ITS OWN admit->finish latency (the seed
    `ServeEngine` bug gave every request the whole-wave wall time): a
    request that queues behind a full engine records a strictly larger
    queue wait, and a longer forecast a larger latency than a short one
    admitted together."""
    grid = (3, 8, 8)
    prog = StencilProgram(grid_shape=grid, ensemble=1)
    eng = ForecastEngine(slots=2)
    rids = []
    for i, steps in enumerate([1, 6, 4]):
        st_ = fields.initial_state(jax.random.PRNGKey(30 + i), grid,
                                   ensemble=1)
        rids.append(eng.submit(ForecastRequest(program=prog, state=st_,
                                               steps=steps)))
    res = eng.drain()
    short, long_, queued = (res[r] for r in rids)
    assert short.latency_s > 0 and long_.latency_s > 0
    # same admission wave: the 6-step forecast finishes after the 1-step
    assert long_.latency_s > short.latency_s
    assert long_.rounds == 6 and short.rounds == 1
    # the third request waited for a slot: strictly positive queue wait
    assert queued.queue_wait_s > short.queue_wait_s
    occ = eng.stats()["occupancy"]
    assert 0 < occ <= 1


# ---------------------------------------------------------------------------
# Subprocess variants: forced-4-device batching + fresh-process restart
# ---------------------------------------------------------------------------

_WORKLOAD_SNIPPET = r"""
import jax, numpy as np
from repro.serve.forecast import ForecastEngine, ForecastRequest
from repro.weather import fields
from repro.weather.program import StencilProgram, compile as pcompile

def workload():
    progs = [StencilProgram(grid_shape=(3, 8, 8), ensemble=1),
             StencilProgram(grid_shape=(3, 8, 8), ensemble=1, op="hdiff"),
             StencilProgram(grid_shape=(4, 12, 16), ensemble=1,
                            dtype="bfloat16")]
    reqs = []
    for i, steps in enumerate([3, 5, 2, 4, 1]):
        prog = progs[i % 3]
        st = fields.initial_state(jax.random.PRNGKey(100 + i),
                                  prog.grid_shape, ensemble=1,
                                  dtype=prog.dtype)
        reqs.append(ForecastRequest(program=prog, state=st, steps=steps,
                                    rid=i))
    return reqs

def save_results(path, results):
    arrays = {}
    for rid, r in results.items():
        for name in r.program.fields:
            arrays[f"{rid}/{name}"] = np.asarray(r.state.fields[name],
                                                 np.float32)
    np.savez(path, **arrays)
"""

_DIST_SERVE_SNIPPET = _WORKLOAD_SNIPPET + r"""
from repro.weather import domain
from repro.weather.program import plan_cache_key
kw = {"axis_types": (jax.sharding.AxisType.Auto,) * 2}
mesh = jax.make_mesh((2, 2), ("data", "model"), **kw)
grid = (4, 16, 16)
prog = StencilProgram(grid_shape=grid, ensemble=1)
eng = ForecastEngine(slots=2, mesh=mesh)
reqs = []
for i, steps in enumerate([3, 2, 4]):
    st = fields.initial_state(jax.random.PRNGKey(i), grid, ensemble=1)
    rid = eng.submit(ForecastRequest(program=prog, state=st, steps=steps))
    reqs.append((rid, st, steps))
res = eng.drain()

# Batch-folding requests into the ensemble axis must NOT change the
# round's structure: same collectives, same single launch as solo.
solo = pcompile(prog, mesh=mesh)
batched = eng._plans[plan_cache_key(prog, ensemble=2)]
srep, brep = solo.report(), batched.report()
assert brep["collectives_per_round"] == srep["collectives_per_round"] == 4
assert brep["pallas_calls_per_round"] == srep["pallas_calls_per_round"] == 1

# ... and every batched result is bit-identical to its solo run.
for rid, st, steps in reqs:
    sst = domain.shard_state(st, mesh, solo.state_spec)
    want = solo.run(sst, steps)
    got = res[rid].state
    for name in prog.fields:
        assert np.array_equal(np.asarray(got.fields[name]),
                              np.asarray(want.fields[name])), (rid, name)
print("SERVE_DIST_OK")
"""

_CKPT_PHASE_A = _WORKLOAD_SNIPPET + r"""
import os
eng = ForecastEngine(slots=2, ckpt_dir=os.environ["FORECAST_CKPT"])
for r in workload():
    eng.submit(r)
eng.pump()
eng.pump()
eng.checkpoint()
assert eng.has_work(), "checkpoint must land mid-queue, not after drain"
print("SERVE_CKPT_A_OK")
"""

_CKPT_PHASE_B = _WORKLOAD_SNIPPET + r"""
import os
eng = ForecastEngine.restore(os.environ["FORECAST_CKPT"])
assert eng.has_work()
results = eng.drain()
assert sorted(results) == [0, 1, 2, 3, 4]
save_results(os.path.join(os.environ["FORECAST_CKPT"], "restored.npz"),
             results)
print("SERVE_CKPT_B_OK")
"""


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
    assert marker in r.stdout, r.stderr[-2000:]


def test_batched_serving_keeps_plan_structure_forced_4dev():
    """Forced-4-device subprocess: admission batching into the ensemble
    axis leaves `collectives_per_round` (and the single launch) unchanged
    vs the solo plan, and distributed batched results stay bit-identical
    to solo distributed runs."""
    _run_snippet(
        _DIST_SERVE_SNIPPET, "SERVE_DIST_OK",
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})


def test_checkpoint_restart_matches_uninterrupted(tmp_path):
    """Crash/restart equivalence: checkpoint the engine mid-queue, restart
    in a FRESH process, drain — the results must be bit-identical to an
    uninterrupted run of the same workload."""
    ckpt_dir = str(tmp_path / "engine_ckpt")
    env = {"FORECAST_CKPT": ckpt_dir}
    _run_snippet(_CKPT_PHASE_A, "SERVE_CKPT_A_OK", env)
    _run_snippet(_CKPT_PHASE_B, "SERVE_CKPT_B_OK", env)

    # Uninterrupted reference, in-process (deterministic same workload).
    ns = {}
    exec(compile(_WORKLOAD_SNIPPET, "<workload>", "exec"), ns)
    eng = ForecastEngine(slots=2)
    for r in ns["workload"]():
        eng.submit(r)
    want = eng.drain()
    got = np.load(os.path.join(ckpt_dir, "restored.npz"))
    for rid, res in want.items():
        for name in res.program.fields:
            np.testing.assert_array_equal(
                got[f"{rid}/{name}"],
                np.asarray(res.state.fields[name], np.float32),
                err_msg=f"rid={rid} field={name}")


# ---------------------------------------------------------------------------
# Supervision (ISSUE 7): poisoned-slot isolation, crash/restore sweep,
# and the combined acceptance scenario
# ---------------------------------------------------------------------------


def _check_poison_isolation(mix, poison_round, seed):
    """Serve `mix` with a NaN poison injected at `poison_round` into a
    seeded-random busy slot: AT MOST the poisoned request fails (with a
    validity-guard diagnosis) and every other result is bitwise equal to
    its solo run — the quarantine never perturbs a healthy slot."""
    inj = FaultInjector([FaultSpec(kind="poison_nan", round=poison_round)],
                        seed=seed)
    eng = ForecastEngine(slots=2, fault_injector=inj)
    reqs = []
    for s, (grid_i, op_i, dtype_i, steps, pinned) in enumerate(mix):
        req = _mk_request(200 + 17 * seed + s, grid_i, op_i, dtype_i,
                          steps, pinned)
        state = req.state
        rid = eng.submit(req)
        reqs.append((rid, state))
    results = eng.drain()
    failed = [r for r in results.values() if r.status == "failed"]
    assert len(failed) == inj.fired("poison_nan") <= 1
    assert eng.stats()["quarantined"] == len(failed)
    for r in failed:
        assert r.diagnosis["reason"] == "validity_guard"
        assert r.diagnosis["bad_leaves"]
    for rid, state in reqs:
        if results[rid].status == "ok":
            _assert_bit_identical(results[rid], state)


_POISON_CASE = st.tuples(
    st.integers(0, 1), st.integers(0, 2), st.integers(0, 1),
    st.integers(1, 4), st.booleans()) if HAVE_HYPOTHESIS else None

if HAVE_HYPOTHESIS:
    @settings(max_examples=4, deadline=None, derandomize=True)
    @given(st.lists(_POISON_CASE, min_size=2, max_size=4),
           st.integers(0, 1), st.integers(0, 5))
    def test_poisoned_slot_isolation_property(mix, poison_round, seed):
        _check_poison_isolation(mix, poison_round, seed)
else:
    def test_poisoned_slot_isolation_property():
        """Seeded fallback: same property over deterministic mixes."""
        rng = np.random.default_rng(7)
        for case in range(3):
            n = int(rng.integers(2, 5))
            mix = [(int(rng.integers(0, 2)), int(rng.integers(0, 3)),
                    int(rng.integers(0, 2)), int(rng.integers(1, 5)),
                    bool(rng.integers(0, 2))) for _ in range(n)]
            _check_poison_isolation(mix, int(rng.integers(0, 2)), case)


def test_crash_restore_at_every_round_boundary(tmp_path):
    """Kill-at-every-round-boundary sweep: run a workload under the
    watchdog (`ckpt_every_rounds=1`, keep everything), then for EVERY
    saved checkpoint simulate a crash there — restore, drain — and assert
    the full result set is bit-identical to the uninterrupted run."""
    grid = (3, 8, 8)
    prog = StencilProgram(grid_shape=grid, ensemble=1)

    def submit_all(eng):
        rids = []
        for i, steps in enumerate([3, 1, 2, 4]):
            st_ = fields.initial_state(jax.random.PRNGKey(60 + i), grid,
                                       ensemble=1)
            rids.append(eng.submit(ForecastRequest(program=prog, state=st_,
                                                   steps=steps)))
        return rids

    ref_eng = ForecastEngine(slots=2)
    rids = submit_all(ref_eng)
    want = ref_eng.drain()

    d = str(tmp_path)
    wd_eng = ForecastEngine(slots=2, ckpt_dir=d, ckpt_every_rounds=1,
                            ckpt_keep=0)
    assert submit_all(wd_eng) == rids
    wd_eng.drain()
    saved = sorted(int(p.split("_")[1]) for p in os.listdir(d)
                   if p.startswith("step_"))
    assert len(saved) == wd_eng.stats()["watchdog_checkpoints"] >= 3

    for step in saved:
        eng = ForecastEngine.restore(d, step)
        # the resumed engine inherits the watchdog config; mute it so the
        # sweep's remaining checkpoints aren't overwritten/GC'd mid-sweep
        eng.ckpt_every_rounds = None
        res = eng.drain()
        assert sorted(res) == sorted(rids), f"crash at checkpoint {step}"
        for rid in rids:
            for name in prog.fields:
                np.testing.assert_array_equal(
                    np.asarray(res[rid].state.fields[name]),
                    np.asarray(want[rid].state.fields[name]),
                    err_msg=f"crash at checkpoint {step}, rid={rid}, "
                            f"field={name}")


def test_supervised_acceptance_combo(tmp_path):
    """The ISSUE 7 acceptance scenario in one run: a poisoned request, an
    injected mid-round device loss, a forced lowering fallback, AND a hard
    crash resumed from the watchdog's checkpoint — every healthy request
    bit-identical to its solo run, the poisoned request `failed` with a
    diagnosis, and the engine drains the full queue without intervention."""
    grid = (3, 8, 8)
    prog = StencilProgram(grid_shape=grid, ensemble=1)
    inj = FaultInjector([
        FaultSpec(kind="compile_fail", op="dycore", attempt="native"),
        FaultSpec(kind="poison_nan", round=1),
        FaultSpec(kind="device_loss", round=2),
    ], seed=3)
    eng = ForecastEngine(slots=2, ckpt_dir=str(tmp_path),
                         ckpt_every_rounds=1, ckpt_keep=0,
                         retry_backoff_s=0.0, fault_injector=inj)
    sts = [fields.initial_state(jax.random.PRNGKey(300 + i), grid,
                                ensemble=1) for i in range(4)]
    rids = [eng.submit(ForecastRequest(program=prog, state=s, steps=5))
            for s in sts]
    while eng.stats()["rounds"] < 3 and eng.has_work():
        eng.pump()
    assert inj.fired() == 3, inj.log   # all three faults hit pre-crash

    # Hard crash: abandon the warm engine, resume from the watchdog's
    # last auto-checkpoint in a fresh one (no injector — faults are over).
    eng2 = ForecastEngine.restore(str(tmp_path))
    res = eng2.drain()
    assert not eng2.has_work()
    assert sorted(res) == sorted(rids)

    failed = [rid for rid in rids if res[rid].status == "failed"]
    assert len(failed) == 1
    diag = res[failed[0]].diagnosis
    assert diag["reason"] == "validity_guard" and diag["bad_leaves"]
    for rid, s in zip(rids, sts):
        if rid != failed[0]:
            assert res[rid].status == "ok"
            _assert_bit_identical(res[rid], s)
    st2 = eng2.stats()
    assert st2["quarantined"] == 1
    assert st2["fallback_compiles"] >= 1
    assert st2["round_retries"] >= 1
    assert st2["watchdog_checkpoints"] >= 3


def test_checkpoint_restore_in_process(tmp_path):
    """Same-process restore: the cheap API-level path (no subprocess) —
    queue, in-flight slots, finished results and counters all survive."""
    grid = (3, 8, 8)
    prog = StencilProgram(grid_shape=grid, ensemble=1)
    eng = ForecastEngine(slots=1, ckpt_dir=str(tmp_path))
    sts = [fields.initial_state(jax.random.PRNGKey(40 + i), grid,
                                ensemble=1) for i in range(3)]
    rids = [eng.submit(ForecastRequest(program=prog, state=st_, steps=2))
            for st_ in sts]
    eng.pump()                                   # rid0 in flight, rest queued
    step = eng.checkpoint()
    eng2 = ForecastEngine.restore(str(tmp_path), step)
    assert eng2.slots == 1 and eng2.has_work()
    res = eng2.drain()
    assert sorted(res) == sorted(rids)
    for rid, st_ in zip(rids, sts):
        _assert_bit_identical(res[rid], st_)

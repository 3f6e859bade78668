"""Fused dycore Pallas kernel vs the unfused oracle composition.

The fused pipeline (vadvc Thomas solve -> point-wise update -> compound
hdiff, all in VMEM) must match the unfused reference that materializes every
intermediate — over shape sweeps, tile sizes (including non-divisible
requests that snap), bf16 I/O, batching, periodicity, and the halo-mode
(pad/crop) trick the distributed domain uses.

Comparison policy: the stage tendency (no limiter upstream) must match to
1e-5 everywhere.  The diffused field must match to 1e-5 at every point whose
flux-limiter branch decision is numerically stable; at the measure-zero set
of fragile points (limiter product within fp32 noise of zero —
`ref.limiter_fragile_mask`) two evaluation orders of the same scheme may
legitimately take different branches, so only a loose physical bound
(coeff-scaled flux magnitude) applies there.
"""

import inspect

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import autotune, trace_stats
from repro.kernels.dycore_fused import ops, ref
from repro.kernels.dycore_fused.fused import fused_dycore_pallas
from repro.weather import fields
from repro.weather.program import DycoreProgram, compile_dycore


def _plan(grid, ensemble=1, variant="auto", k_steps=1, **kw):
    return compile_dycore(DycoreProgram(grid_shape=grid, ensemble=ensemble,
                                        variant=variant, k_steps=k_steps),
                          **kw)

SHAPES = [(4, 8, 16), (6, 12, 8), (5, 16, 32), (3, 10, 14), (2, 6, 6)]
DT = ref.DEFAULT_DT
LOOSE = 0.05   # |coeff * flux| scale at a flipped limiter branch


def _inputs(rng, shape, dtype=np.float32):
    mk = lambda s: jnp.asarray((s * rng.normal(size=shape)).astype(dtype))
    return mk(1.0), mk(0.15), mk(0.01), mk(0.01)   # f, wcon, utens, ustage


def _assert_field_close(got, want, f2, atol=1e-5, msg=""):
    """Field comparison aware of limiter-fragile points (module docstring)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(got - want)
    fragile = np.asarray(ref.limiter_fragile_mask(f2))
    stable = err[~fragile]
    assert stable.size == 0 or stable.max() <= atol, \
        f"{msg}: stable-point err {stable.max()}"
    assert err.max() <= LOOSE, f"{msg}: fragile-point err {err.max()}"


def _ref_with_f2(f, wcon, ut, us):
    """Unfused reference plus the updated field the limiter consumes."""
    want_f, want_s = ref.fused_step_ref_batched(f, wcon, ut, us)
    return want_f, want_s, f + DT * want_s


@pytest.mark.parametrize("shape", SHAPES)
def test_fused_matches_unfused_ref(shape, rng):
    f, wcon, ut, us = _inputs(rng, shape)
    want_f, want_s, f2 = _ref_with_f2(f, wcon, ut, us)
    ny = shape[1]
    for ty in {2, 3, 5, ny // 2 or 2, ny}:
        ty = ops.snap_ty(ty, ny)
        got_f, got_s = ops.fused_step(f, wcon, ut, us, ty=ty,
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                                   atol=1e-5, err_msg=f"ty={ty} s {shape}")
        _assert_field_close(got_f, want_f, f2, msg=f"ty={ty} f {shape}")


def test_nondivisible_tile_request_snaps(rng):
    """A requested y-window that does not divide ny must snap to a legal
    window instead of erroring (non-divisible tile sizes): a
    sublane-aligned divisor (8 rows fp32, 16 bf16) or the whole extent —
    the only blocks the TPU compiler accepts."""
    assert ops.snap_ty(5, 16) == 8     # no aligned divisor <= 5: next up
    assert ops.snap_ty(20, 48) == 16
    assert ops.snap_ty(20, 48, "bfloat16") == 16
    assert ops.snap_ty(8, 32, "bfloat16") == 16
    assert ops.snap_ty(7, 12) == 12    # no aligned divisor -> whole-y
    assert ops.snap_ty(6, 7) == 7      # prime ny -> whole-y window
    f, wcon, ut, us = _inputs(rng, (3, 14, 8))
    want_f, want_s, f2 = _ref_with_f2(f, wcon, ut, us)
    got_f, got_s = ops.fused_step(f, wcon, ut, us, ty=5, interpret=True)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5)
    _assert_field_close(got_f, want_f, f2)


def test_bf16_io(rng):
    """bf16 in/out (the paper's half-precision mode): fp32 internal compute
    keeps the error at bf16 quantization level, not accumulation level."""
    shape = (4, 8, 16)
    f, wcon, ut, us = _inputs(rng, shape)
    want_f, want_s = ref.fused_step_ref(f, wcon, ut, us)
    b = lambda a: a.astype(jnp.bfloat16)
    got_f, got_s = ops.fused_step(b(f), b(wcon), b(ut), b(us), ty=4,
                                  interpret=True)
    assert got_f.dtype == jnp.bfloat16 and got_s.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got_f, np.float32),
                               np.asarray(want_f), atol=0.25)
    np.testing.assert_allclose(np.asarray(got_s, np.float32),
                               np.asarray(want_s), atol=0.25)


def test_batched_matches_per_member(rng):
    shape = (2, 3, 4, 8, 16)   # two leading batch dims
    f, wcon, ut, us = _inputs(rng, shape)
    got_f, got_s = ops.fused_step(f, wcon, ut, us, ty=4, interpret=True)
    assert got_f.shape == shape and got_s.shape == shape
    want_f, want_s, f2 = _ref_with_f2(f, wcon, ut, us)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5)
    _assert_field_close(got_f, want_f, f2)


def test_periodicity(rng):
    """Doubly-periodic domain: shifting every input cyclically shifts the
    output by the same amount (no hidden boundary treatment)."""
    shape = (3, 8, 12)
    f, wcon, ut, us = _inputs(rng, shape)
    out_f, out_s = ops.fused_step(f, wcon, ut, us, ty=4, interpret=True)
    _, ref_s, f2 = _ref_with_f2(f, wcon, ut, us)
    for sy, sx in [(3, 0), (0, 5), (2, 7)]:
        r = lambda a: jnp.roll(jnp.roll(a, sy, axis=-2), sx, axis=-1)
        rf, rs = ops.fused_step(r(f), r(wcon), r(ut), r(us), ty=4,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(rs), np.asarray(r(out_s)),
                                   atol=1e-5, err_msg=f"shift=({sy},{sx})")
        _assert_field_close(rf, r(out_f), r(f2), msg=f"shift=({sy},{sx})")


def test_halo_mode_pad_crop(rng):
    """The distributed domain runs the periodic kernel on a halo-exchanged
    slab and crops the interior; wrap-around garbage must stay inside the
    cropped 2-ring (weather/domain.py `local_step_fused`)."""
    shape = (4, 8, 12)
    H = ref.HALO
    ny, nx = shape[-2:]
    f, wcon, ut, us = _inputs(rng, shape)
    want_f, want_s, f2 = _ref_with_f2(f, wcon, ut, us)
    w = wcon + jnp.roll(wcon, -1, axis=-1)
    pad = ref.pad_periodic
    got_f, got_s = fused_dycore_pallas(pad(f), pad(w), pad(ut), pad(us),
                                       ty=4, interpret=True)
    crop = lambda a: a[..., H:H + ny, H:H + nx]
    np.testing.assert_allclose(np.asarray(crop(got_s)), np.asarray(want_s),
                               atol=1e-5)
    _assert_field_close(crop(got_f), want_f, f2)


def test_dycore_step_fused_matches_unfused():
    """End-to-end: the fused dycore plan vs the unfused-oracle plan, all
    four prognostic fields + stage tendencies."""
    st = fields.initial_state(jax.random.PRNGKey(3), (6, 12, 16), ensemble=2)
    out_f = _plan((6, 12, 16), ensemble=2).step(st)
    out_u = _plan((6, 12, 16), ensemble=2, variant="unfused").step(st)
    for name in fields.PROGNOSTIC:
        np.testing.assert_allclose(
            np.asarray(out_f.stage_tens[name]),
            np.asarray(out_u.stage_tens[name]), atol=1e-5, err_msg=name)
        f2 = st.fields[name] + 0.1 * out_u.stage_tens[name]
        _assert_field_close(out_f.fields[name], out_u.fields[name], f2,
                            msg=name)


def test_autotuned_plan_is_legal():
    for grid in [(8, 16, 32), (64, 256, 256), (4, 10, 14)]:
        ty = ops.plan_tile(grid, jnp.float32)
        assert grid[1] % ty == 0 and 2 <= ty <= grid[1], (grid, ty)


# ---- whole-state fused step (one pallas_call for every field) -------------


def _whole_inputs(rng, shape, dtype=np.float32):
    """shape = (..., nf, nz, ny, nx); wcon drops the field axis."""
    mk = lambda s, sh: jnp.asarray((s * rng.normal(size=sh)).astype(dtype))
    wshape = shape[:-4] + shape[-3:]
    return (mk(1.0, shape), mk(0.15, wshape), mk(0.01, shape),
            mk(0.01, shape))


def _whole_ref(fs, wcon, ut, us):
    wb = jnp.broadcast_to(jnp.expand_dims(wcon, -4), fs.shape)
    want_f, want_s = ref.fused_step_ref_batched(fs, wb, ut, us)
    return want_f, want_s, fs + DT * want_s


@pytest.mark.parametrize("shape", [(4, 5, 12, 16), (2, 3, 8, 8),
                                   (3, 4, 10, 14)])   # incl. non-div. ny
def test_whole_state_matches_oracle(shape, rng):
    """Whole-state fused == per-field fused == unfused oracle, including a
    prime-factor ny that forces the y-window to snap."""
    fs, wcon, ut, us = _whole_inputs(rng, shape)
    want_f, want_s, f2 = _whole_ref(fs, wcon, ut, us)
    got_f, got_s = ops.fused_step_whole_state(fs, wcon, ut, us, ty=5,
                                              interpret=True)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5, err_msg=f"{shape}")
    _assert_field_close(got_f, want_f, f2, msg=f"{shape}")
    # cross-check against the per-field fused kernel, field by field
    for i in range(shape[0]):
        pf_f, pf_s = ops.fused_step(fs[i], wcon, ut[i], us[i], ty=5,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(got_s[i]), np.asarray(pf_s),
                                   atol=1e-5, err_msg=f"field {i}")
        _assert_field_close(got_f[i], pf_f, f2[i], msg=f"field {i}")


def test_whole_state_batched_and_bf16(rng):
    shape = (2, 4, 4, 8, 16)   # (E, nf, nz, ny, nx)
    fs, wcon, ut, us = _whole_inputs(rng, shape)
    want_f, want_s, f2 = _whole_ref(fs, wcon, ut, us)
    got_f, got_s = ops.fused_step_whole_state(fs, wcon, ut, us, ty=4,
                                              interpret=True)
    assert got_f.shape == shape and got_s.shape == shape
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5)
    _assert_field_close(got_f, want_f, f2)
    b = lambda a: a.astype(jnp.bfloat16)
    bf, bs = ops.fused_step_whole_state(b(fs), b(wcon), b(ut), b(us), ty=4,
                                        interpret=True)
    assert bf.dtype == jnp.bfloat16 and bs.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(bf, np.float32),
                               np.asarray(want_f), atol=0.25)
    np.testing.assert_allclose(np.asarray(bs, np.float32),
                               np.asarray(want_s), atol=0.25)


def test_whole_state_use_pallas_false_oracle(rng):
    fs, wcon, ut, us = _whole_inputs(rng, (4, 3, 8, 8))
    want_f, want_s, _ = _whole_ref(fs, wcon, ut, us)
    got_f, got_s = ops.fused_step_whole_state(fs, wcon, ut, us,
                                              use_pallas=False)
    np.testing.assert_allclose(np.asarray(got_f), np.asarray(want_f),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-6)


def test_dycore_step_single_pallas_call():
    """The whole-state step must launch exactly ONE Pallas kernel for all
    prognostic fields; the per-field path launches one per field (the
    launch-granularity oracle this PR's tentpole collapses)."""
    st = fields.initial_state(jax.random.PRNGKey(0), (3, 8, 8))
    j = jax.make_jaxpr(_plan((3, 8, 8), interpret=True).step)(st)
    assert trace_stats.count_primitive(j, "pallas_call") == 1
    j = jax.make_jaxpr(_plan((3, 8, 8), variant="per_field",
                             interpret=True).step)(st)
    assert trace_stats.count_primitive(j, "pallas_call") == \
        len(fields.PROGNOSTIC)


def test_dycore_step_whole_state_matches_per_field():
    st = fields.initial_state(jax.random.PRNGKey(4), (5, 12, 16), ensemble=2)
    out_w = _plan((5, 12, 16), ensemble=2, variant="whole_state").step(st)
    out_p = _plan((5, 12, 16), ensemble=2, variant="per_field").step(st)
    out_u = _plan((5, 12, 16), ensemble=2, variant="unfused").step(st)
    for name in fields.PROGNOSTIC:
        np.testing.assert_allclose(
            np.asarray(out_w.stage_tens[name]),
            np.asarray(out_u.stage_tens[name]), atol=1e-5, err_msg=name)
        f2 = st.fields[name] + 0.1 * out_u.stage_tens[name]
        _assert_field_close(out_w.fields[name], out_u.fields[name], f2,
                            msg=name)
        _assert_field_close(out_w.fields[name], out_p.fields[name], f2,
                            msg=name)


def test_interpret_defaults_to_auto():
    """ISSUE 2 satellite: `fused_step`'s interpret default was a hard-coded
    True (TPU callers silently got the interpreter); both entry points must
    now default to None -> `_auto_interpret()`."""
    for fn in (ops.fused_step, ops.fused_step_whole_state):
        assert inspect.signature(fn).parameters["interpret"].default is None
    assert ops._auto_interpret() == (jax.default_backend() != "tpu")


# ---- k-step kernel (the whole round in one pallas_call) -------------------


def _seq_whole_state(fs, wcon, ut, us, k, ty):
    """Oracle: k sequential whole-state launches (the PR 2 scan path)."""
    f, s = fs, us
    for _ in range(k):
        f, s = ops.fused_step_whole_state(f, wcon, ut, s, ty=ty,
                                          interpret=True)
    return f, s


@pytest.mark.parametrize("k", [2, 3])
def test_kstep_matches_sequential_steps(k, rng):
    """ONE k-step launch == k sequential whole-state launches to fp32
    rounding (the k local steps run in-kernel on VMEM state; only
    limiter-fragile points may flip branches across the k-step chain)."""
    shape = (3, 4, 12, 16)   # (nf, nz, ny, nx)
    fs, wcon, ut, us = _whole_inputs(rng, shape)
    ty = 2 * k               # ty >= k*HALO
    want_f, want_s = _seq_whole_state(fs, wcon, ut, us, k, ty)
    got_f, got_s = ops.fused_step_kstep(fs, wcon, ut, us, k_steps=k, ty=ty,
                                        interpret=True)
    for got, want, name in ((got_f, want_f, "f"), (got_s, want_s, "s")):
        err = np.abs(np.asarray(got) - np.asarray(want))
        bad = int((err > 1e-5).sum())
        assert bad <= 2 and err.max() < LOOSE, (name, k, bad, err.max())


def test_kstep_prefetch_matches_windows_path(rng):
    """The double-buffered make_async_copy w prefetch and the aliased-
    BlockSpec fallback are the same arithmetic — bit-identical outputs."""
    shape = (2, 3, 4, 16, 16)   # batched (E, nf, nz, ny, nx)
    fs, wcon, ut, us = _whole_inputs(rng, shape)
    out_pf = ops.fused_step_kstep(fs, wcon, ut, us, k_steps=2, ty=4,
                                  interpret=True, prefetch_w=True)
    out_win = ops.fused_step_kstep(fs, wcon, ut, us, k_steps=2, ty=4,
                                   interpret=True, prefetch_w=False)
    for a, b in zip(out_pf, out_win):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kstep_k1_matches_whole_state(rng):
    """k_steps=1 degenerates to one whole-state step (same round)."""
    fs, wcon, ut, us = _whole_inputs(rng, (4, 3, 8, 16))
    want_f, want_s, f2 = _whole_ref(fs, wcon, ut, us)
    got_f, got_s = ops.fused_step_kstep(fs, wcon, ut, us, k_steps=1, ty=4,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5)
    _assert_field_close(got_f, want_f, f2)


def test_kstep_bf16_io(rng):
    shape = (3, 4, 8, 16)
    fs, wcon, ut, us = _whole_inputs(rng, shape)
    want_f, want_s = _seq_whole_state(fs, wcon, ut, us, 2, 4)
    b = lambda a: a.astype(jnp.bfloat16)
    got_f, got_s = ops.fused_step_kstep(b(fs), b(wcon), b(ut), b(us),
                                        k_steps=2, ty=4, interpret=True)
    assert got_f.dtype == jnp.bfloat16 and got_s.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got_f, np.float32),
                               np.asarray(want_f, np.float32), atol=0.5)


def test_kstep_single_launch_trace():
    """The whole k-step round must trace to exactly ONE pallas_call — the
    structural claim the PR's tentpole makes (no launch per local step)."""
    st = fields.initial_state(jax.random.PRNGKey(0), (3, 8, 8))
    kplan = _plan((3, 8, 8), variant="kstep", k_steps=2, interpret=True)
    j = jax.make_jaxpr(lambda s: kplan.run(s, 2))(st)
    assert trace_stats.count_primitive(j, "pallas_call") == 1
    # and the non-kstep trajectory of the same length also launches once
    # per step (scan body), so the k-step mode strictly halves launches
    # per simulated step at k=2.
    plan1 = _plan((3, 8, 8), interpret=True)
    j1 = jax.make_jaxpr(lambda s: plan1.run(s, 2))(st)
    assert trace_stats.count_primitive(j1, "pallas_call") == 1  # scan body


def test_kstep_ty_snapping_and_validity_bound():
    """snap_ty_kstep: a sublane-aligned divisor of ny (or ny) respecting
    ty >= k*HALO; too-small requests snap UP (the validity front needs the
    room), impossible grids refuse loudly."""
    assert ops.snap_ty_kstep(8, 16, 2) == 8
    assert ops.snap_ty_kstep(5, 16, 2) == 8      # no aligned window <= 5
    assert ops.snap_ty_kstep(2, 16, 3) == 8      # snaps UP past k*HALO=6
    assert ops.snap_ty_kstep(24, 48, 5) == 24    # aligned, >= k*HALO=10
    assert ops.snap_ty_kstep(2, 14, 3) == 14     # no aligned divisor: ny
    with pytest.raises(ValueError):
        ops.snap_ty_kstep(4, 4, 3)               # ny < k*HALO: no window
    with pytest.raises(ValueError):
        # kernel-level guard: ty below the validity bound
        from repro.kernels.dycore_fused.fused import fused_dycore_kstep_pallas
        fused_dycore_kstep_pallas(jnp.zeros((2, 3, 8, 8)),
                                  jnp.zeros((3, 8, 8)),
                                  jnp.zeros((2, 3, 8, 8)),
                                  jnp.zeros((2, 3, 8, 8)),
                                  k_steps=3, ty=4, interpret=True)


def test_kstep_vmem_budget_rejection():
    """Tile plans that cannot hold the 3-window scratch + double-buffered w
    prefetch must be rejected loudly, not silently spilled: a huge-x grid
    with a deep k forces ty up to the validity bound and past the VMEM
    budget."""
    with pytest.raises(ValueError, match="VMEM|vmem|fit|legal"):
        ops.plan_tile_kstep((128, 8, 1024), jnp.float32, 4, 4)
    # a k=1 window at the paper's z and x extents is plannable
    assert ops.plan_tile((64, 8, 256), jnp.float32) >= 2


def test_kstep_tile_space_registered():
    """The k-step tile space lives in the autotune registry; its VMEM
    accounting covers the double buffer (extra_vmem_buffers) so the legal
    window set is tighter than the whole-state space's."""
    spec = autotune.get_op("dycore_kstep")
    assert spec.scratch_fields == 8 and spec.scratch_padded
    assert spec.extra_vmem_buffers == 2.0
    ty = ops.plan_tile_kstep((8, 16, 32), jnp.float32, 4, 2)
    assert 16 % ty == 0 and ty >= 4


def test_whole_state_tile_space_registered():
    """The whole-state tile space is registered with the autotuner and its
    VMEM accounting depends on the field count (shared-w residency)."""
    ty = ops.plan_tile_whole_state((8, 16, 32), jnp.float32, 4)
    assert 16 % ty == 0 and 2 <= ty <= 16
    spec = autotune.get_op("dycore_whole_state")
    # 6 padded temporaries; the shared w is a 4th three-window stream
    assert spec.scratch_fields == 6 and spec.input_windows == 3
    assert abs(spec.fields_in - (3 + 1 / 4)) < 1e-9
    # planning for another field count tunes its own space without
    # clobbering the registered default
    ty8 = ops.plan_tile_whole_state((8, 16, 32), jnp.float32, 8)
    assert 16 % ty8 == 0 and 2 <= ty8 <= 16
    assert autotune.get_op("dycore_whole_state") == spec

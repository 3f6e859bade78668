"""Whole-state dycore traffic + k-step exchange accounting (memmodel)."""

import pytest

from repro.core import hierarchy, memmodel, tiling

def test_dycore_traffic_whole_state_beats_per_field():
    """Whole-state fused step: shared-w batching must strictly reduce
    modeled HBM traffic vs the per-field fused step, in both bounds."""
    for dtype in ("float32", "bfloat16"):
        t = memmodel.dycore_step_traffic((64, 256, 256), dtype,
                                         n_fields=4, ty=32)
        assert t["fused_whole"]["total"] < t["fused"]["total"]
        assert (t["fused_whole"]["stream_window_reads"]
                < t["fused"]["stream_window_reads"])
        assert t["reduction_x_whole"] > t["reduction_x"] > 1.0
        # shared w saves ~the per-field w stream: bounded by 1/4 of inputs
        saving = t["fused"]["total"] / t["fused_whole"]["total"]
        assert 1.05 < saving < 1.25, saving


def test_kstep_exchange_model():
    """Communication-avoiding k-step: collective rounds drop k-fold; bytes
    stay within ~1x of sequential (deep halo ~= k shallow halos, plus a
    mildly growing corner-region overhead); the redundant-flops tax grows
    monotonically with k."""
    prev_tax = -1.0
    for k in (1, 2, 4):
        m = memmodel.kstep_exchange_model((64, 256, 256), "float32",
                                          n_fields=4, k=k, shards=(2, 2))
        assert m["rounds_kstep"] == 2
        assert m["rounds_sequential"] == 2 * k
        assert 0.5 < m["bytes_ratio"] < 1.1
        assert m["redundant_flops_frac"] > prev_tax
        prev_tax = m["redundant_flops_frac"]
    with pytest.raises(ValueError):
        memmodel.kstep_exchange_model((8, 16, 16), "float32", k=4,
                                      shards=(2, 2))


def test_kstep_exchange_model_wire_dtype():
    """bf16 stacked exchange (the paper's half-precision mode on the wire):
    exactly half the ppermuted bytes of fp32 at every k, same rounds, same
    redundant-flops tax — the cast changes wire width only."""
    for k in (1, 2, 4):
        f32 = memmodel.kstep_exchange_model((64, 256, 256), "float32", k=k)
        bf = memmodel.kstep_exchange_model((64, 256, 256), "float32", k=k,
                                           exchange_dtype="bfloat16")
        assert bf["bytes_kstep"] * 2 == f32["bytes_kstep"]
        assert bf["bytes_sequential"] * 2 == f32["bytes_sequential"]
        assert bf["rounds_kstep"] == f32["rounds_kstep"]
        assert bf["redundant_flops_frac"] == f32["redundant_flops_frac"]
    # a bf16 *state* exchanged without a wire cast already ships 2-byte halos
    b16 = memmodel.kstep_exchange_model((64, 256, 256), "bfloat16", k=2)
    bfw = memmodel.kstep_exchange_model((64, 256, 256), "float32", k=2,
                                        exchange_dtype="bfloat16")
    assert b16["bytes_kstep"] == bfw["bytes_kstep"]


def test_kstep_exchange_model_wcon_ragged_depth():
    """Only wcon ships the +1 staggering column, and only to the RIGHT
    side (the left pad's extra column is never read by
    `w[c] = wcon[c] + wcon[c+1]`): its x-ride is `(k*HALO, k*HALO+1)`, so
    the x legs carry `2*k*HALO + 1` columns.  The packed total is strictly
    below both the old symmetric-wcon geometry (one spare column per
    round) and the uniform-depth whole-stack over-shipping."""
    nz, ny, nx = 64, 256, 256
    for k in (1, 2):
        m = memmodel.kstep_exchange_model((nz, ny, nx), "float32",
                                          n_fields=4, k=k, shards=(2, 2))
        ly, lx = ny // 2, nx // 2
        hy = hx = k * 2
        b = 4
        # wcon alone: symmetric hy in y, ragged (hx, hx+1) in x.
        want_wcon = nz * b * (2 * hy * lx + (2 * hx + 1) * (ly + 2 * hy))
        assert m["bytes_wcon"] == want_wcon
        # the pre-fix symmetric ride at (hy, hx+1) both ways: exactly one
        # spare (ly + 2*hy)-column per round more than the ragged ride.
        symmetric = 2 * nz * b * (hy * lx + (hx + 1) * (ly + 2 * hy))
        assert symmetric - m["bytes_wcon"] == nz * b * (ly + 2 * hy)
        # uniform-depth stack at (hy, hx+1) for all 13 operands (the old
        # over-shipping): strictly more than the ragged pack.
        uniform = 13 * symmetric
        assert m["bytes_kstep"] < uniform


def test_kstep_traffic_interstep_reduction():
    """The in-kernel k-step scan keeps prognostic state in VMEM between
    local steps: modeled inter-step state traffic (field + stage, read and
    written at HBM) drops exactly k-fold vs the scan-of-launches path, and
    the round's total stream bound beats k whole-state launches."""
    for k in (2, 4):
        t = memmodel.dycore_step_traffic((64, 256, 256), "float32",
                                         n_fields=4, ty=32, k_steps=k)
        ks = t["fused_kstep"]
        assert t["interstep_reduction_x"] == k
        assert ks["interstep_state_scan"] == k * ks["interstep_state"]
        assert t["reduction_x_kstep_vs_scan"] > 1.0
        assert ks["total"] < ks["scan_total"]
    # k_steps=1: no kstep entry (the whole-state step IS the round)
    t1 = memmodel.dycore_step_traffic((64, 256, 256), "float32", ty=32)
    assert "fused_kstep" not in t1


def test_kstep_opspec_vmem_accounting():
    """The k-step tile space stages a 3-window working slab: padded tile is
    3x the y-window, all 8 temporaries span it, and the double-buffered w
    prefetch claims 2 more padded buffers — so the same tile costs strictly
    more VMEM than in the whole-state space."""
    spec = tiling.dycore_kstep_spec(4, 2)
    assert spec.halo_tiles == (0, 1, 0) and spec.scratch_padded
    assert spec.extra_vmem_buffers == 2.0
    kplan = tiling.TilePlan(op=spec, grid_shape=(64, 256, 256),
                            tile=(64, 32, 256), dtype="float32")
    assert kplan.padded_tile == (64, 96, 256)
    wplan = tiling.TilePlan(op=tiling.dycore_whole_state_spec(4),
                            grid_shape=(64, 256, 256), tile=(64, 32, 256),
                            dtype="float32")
    assert kplan.vmem_bytes > wplan.vmem_bytes
    # What the TPU compiler accepts at the paper's domain under the 64 MiB
    # scoped limit every kernel passes: whole-state ty=16 but not 32,
    # k-step ty=8 but not 16.
    hier = hierarchy.tpu_v5e()
    assert hier.vmem.capacity_bytes == tiling.VMEM_LIMIT_BYTES

    def fits(spec, ty):
        return tiling.TilePlan(op=spec, grid_shape=(64, 256, 256),
                               tile=(64, ty, 256), dtype="float32").fits(hier)
    ws = tiling.dycore_whole_state_spec(4)
    assert fits(ws, 16) and not fits(ws, 32)
    assert fits(spec, 8) and not fits(spec, 16)
    with pytest.raises(ValueError):
        tiling.dycore_kstep_spec(4, 0)


def test_whole_state_opspec_field_count_dependence():
    """More fields amortize the shared-w stream further (fields_in -> 3) but
    never change the resident VMEM accounting (w's windows stay resident)."""
    s2 = tiling.dycore_whole_state_spec(2)
    s8 = tiling.dycore_whole_state_spec(8)
    assert s8.fields_in < s2.fields_in
    assert s2.scratch_fields == s8.scratch_fields == 6
    plan = lambda s: tiling.TilePlan(op=s, grid_shape=(64, 256, 256),
                                     tile=(64, 16, 256), dtype="float32")
    assert plan(s2).vmem_bytes == plan(s8).vmem_bytes
    with pytest.raises(ValueError):
        tiling.dycore_whole_state_spec(0)

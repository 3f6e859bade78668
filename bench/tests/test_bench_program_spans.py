"""The program's spans over the device trace (`program_spans`), on hand-made
intervals and on a trace recorded here with the spans' own mechanism."""

import types

import jax
import pytest

import bench_testing as bt
import harness
import program_spans as ps

# Two engine rounds in a 1000 ns window; a third round falls outside it.
SPANS = [
    (0, 1000, "bench.window", {}),
    (100, 400, "forecast.pump", {"round": 0}),
    (105, 120, "forecast.admit", {"slots": 2, "queued": 0}),
    (120, 390, "forecast.round", {"round": 0}),
    (125, 200, "forecast.step", {"round": 0, "attempt": 0}),
    (200, 260, "forecast.guard", {"round": 0}),
    (260, 380, "forecast.retire", {"rid": 7, "bytes": 64}),
    (500, 700, "forecast.pump", {"round": 1}),
    (510, 690, "forecast.round", {"round": 1}),
    (510, 600, "forecast.step", {"round": 1, "attempt": 0}),
    (600, 650, "forecast.guard", {"round": 1}),
    (1100, 1200, "forecast.round", {"round": 2}),
]
OPS = {"/device:TPU:0": [(0, 90, "%a = f()"), (130, 195, "%k.1 = f()"),
                         (210, 240, "%g = f()"), (300, 320, "%x = f()"),
                         (520, 590, "%k.2 = f()"), (605, 640, "%g.1 = f()")]}


def test_host_gaps_are_named_by_the_innermost_span():
    s = ps.summarize(SPANS, OPS)
    assert s.rounds == 2 and s.retires == 1
    # Idle in pump 1: [100,130] [195,210] [240,300] [320,400]; in pump 2:
    # [500,520] [590,605] [640,700]; each cut where a span begins or ends.
    want = {"forecast.pump": 35, "forecast.admit": 15, "forecast.round": 55,
            "forecast.step": 30, "forecast.guard": 45, "forecast.retire": 100}
    assert s.idle_in_pump_s == pytest.approx(
        {n: t * 1e-9 for n, t in want.items()})
    assert s.host_gap_ms == pytest.approx(280e-6 / 2)


def test_span_time_per_round_and_per_retire():
    s = ps.summarize(SPANS, OPS)
    assert s.per_round_ms("forecast.step") == pytest.approx(165e-6 / 2)
    assert s.per_round_ms("forecast.guard") == pytest.approx(110e-6 / 2)
    assert s.per_round_ms("forecast.admit") == pytest.approx(15e-6 / 2)
    assert s.per_round_ms("forecast.round") == pytest.approx(450e-6 / 2)
    assert s.readback_ms == pytest.approx(120e-6)


def test_kernels_match_by_name_whatever_their_instance_suffix():
    spans = [(0, 100, "bench.window", {}),
             (0, 50, "plan.run", {"steps": 10,
                                  "kernels": "nero_vadvc;nero_hdiff"}),
             (50, 100, "plan.run", {"steps": 10, "kernels": "nero_hdiff"})]
    ops = {"/device:TPU:0": [
        (0, 100, "%while.1 = (...) while()"),
        (10, 30, "%nero_vadvc.5 = f32[2] custom-call(%p)"),
        (40, 50, "%nero_hdiff.12 = f32[2] custom-call(%p)"),
        (50, 60, "%nero_hdiff_kstep.3 = f32[2] custom-call(%p)"),
        (60, 90, "%copy.20 = f32[2] copy(%p)")]}
    s = ps.summarize(spans, ops)
    assert s.kernels == ("nero_vadvc", "nero_hdiff")
    assert s.kernel_s == pytest.approx(30e-9)
    assert s.busy_s == pytest.approx(100e-9)
    assert s.lowering_share == pytest.approx(70.0)
    assert ps.kernel_of("%nero_vadvc.5 = f32[2] custom-call()") \
        == "nero_vadvc"
    assert ps.kernel_of("nero_hdiff_kstep") == "nero_hdiff_kstep"


def test_a_program_without_spans_gives_nothing():
    s = ps.summarize([(0, 100, "bench.window", {})],
                     {"/device:TPU:0": [(0, 50, "%nero_hdiff.1 = f()")]})
    assert s.rounds == 0 and s.kernels == ()
    assert s.host_gap_ms is None and s.readback_ms is None
    assert s.per_round_ms("forecast.step") is None
    assert s.lowering_share is None


def test_spans_outside_the_window_do_not_count():
    s = ps.summarize(SPANS[:1] + SPANS[-1:], OPS)
    assert s.rounds == 0


def test_a_recorded_trace_is_read_once_per_run(tmp_path, monkeypatch):
    """Spans written through `jax.profiler.TraceAnnotation`, as the program
    writes them, read back with their metadata by the metric readers."""
    bench = tmp_path / "bench"
    with jax.profiler.trace(str(bench / "_work" / "trace")):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("forecast.pump", round=0):
                with jax.profiler.TraceAnnotation("forecast.round", round=0):
                    with jax.profiler.TraceAnnotation("forecast.step",
                                                      round=0, attempt=0):
                        jax.block_until_ready(jax.numpy.ones(8) + 1)
                    with jax.profiler.TraceAnnotation(
                            "forecast.retire", rid=3, bytes=64):
                        pass
    spans, _ = ps.read(ps.newest_trace(str(bench)))
    assert [(n, m) for _, _, n, m in sorted(spans, key=lambda x: x[0])] == [
        ("bench.window", {}), ("forecast.pump", {"round": 0}),
        ("forecast.round", {"round": 0}),
        ("forecast.step", {"round": 0, "attempt": 0}),
        ("forecast.retire", {"rid": 3, "bytes": 64})]
    reads = []
    real = ps.read
    monkeypatch.setattr(ps, "read", lambda p: reads.append(p) or real(p))
    cell = types.SimpleNamespace(bench_dir=str(bench), chips=1)
    run = types.SimpleNamespace(cell=cell, trace=object())
    values = {}
    for name in ("engine_step_ms.serve", "engine_readback_ms.serve",
                 "engine_host_gap_ms.serve", "engine_guard_ms.serve"):
        values[name] = harness.load_reader(bt.BENCH, name)(run)
    assert len(reads) == 1
    # No device plane on the CPU: no idle time to name, no guard span.
    assert values["engine_host_gap_ms.serve"] is None
    assert values["engine_guard_ms.serve"] is None
    assert values["engine_step_ms.serve"] > 0
    assert values["engine_readback_ms.serve"] is not None
    assert run._program_spans.rounds == 1
    assert harness.load_reader(bt.BENCH, "lowering_share.batch")(run) is None


def test_an_untraced_run_reads_nothing():
    run = types.SimpleNamespace(cell=None, trace=None)
    for name in ("engine_step_ms.serve", "engine_guard_ms.serve",
                 "engine_readback_ms.serve", "engine_host_gap_ms.serve",
                 "lowering_share.batch"):
        assert harness.load_reader(bt.BENCH, name)(run) is None

"""The trace reduction, on hand-made intervals and on a trace recorded on a
TPU v5e: the dycore batch cell's 3-second window (`bench/testdata`)."""

import os

import pytest

import bench_testing as bt
import trace_reduce as tr

TRACE = os.path.join(bt.BENCH, "testdata", "dycore_batch_window3s.xplane.pb")


def test_busy_idle_and_gaps_on_hand_made_intervals():
    spans = [(0, 100, "bench.window"), (10, 35, "bench.run_call"),
             (60, 90, "bench.pump"), (70, 80, "bench.poll")]
    ops = {"/device:TPU:0": [(5, 20, "%a.1 = f32[] add()"),
                             (15, 30, "%b = f32[] mul()"),
                             (50, 72, "%a.1 = f32[] add()")]}
    s = tr.summarize(spans, ops, n_devices=1)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(47e-9)          # [5,30] + [50,72]
    assert s.idle_share == pytest.approx(0.53)
    # Gaps [0,5] [30,50] [72,100]; each named by its innermost span.
    assert s.idle_gaps == [["bench.pump", pytest.approx(28e-9)],
                           ["bench.window", pytest.approx(20e-9)],
                           ["bench.window", pytest.approx(5e-9)]]
    assert s.top_ops[0] == ["a.1", pytest.approx(37e-9)]


def test_nested_operations_count_their_own_time_only():
    ops = [(0, 100, "%while.1 = (...) while()"), (10, 30, "%k = f()"),
           (30, 90, "%k = f()")]
    assert tr.self_times(ops) == [20, 20, 60]
    s = tr.summarize([(0, 100, "bench.window")], {"/device:TPU:0": ops}, 1)
    assert s.top_ops == [["k", pytest.approx(80e-9)],
                         ["while.1", pytest.approx(20e-9)]]
    assert s.busy_s == pytest.approx(100e-9)


def test_busy_time_is_averaged_over_the_chips_used():
    spans = [(0, 100, "bench.window")]
    ops = {"/device:TPU:0": [(0, 100, "%x = f()")],
           "/device:TPU:1": [(0, 50, "%x = f()")],
           "/device:TPU:2": []}
    assert tr.summarize(spans, ops, 2).busy_s == pytest.approx(75e-9)
    assert tr.summarize(spans, ops, 1).busy_s == pytest.approx(100e-9)
    # So is each operation's time: on the chips used they sum to busy_s.
    assert tr.summarize(spans, ops, 2).top_ops == [["x",
                                                    pytest.approx(75e-9)]]


def test_a_window_with_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize([(0, 10, "bench.window")], {"/device:TPU:0": []}, 1)


def test_op_names_drop_the_instruction_text():
    assert tr.op_name("%fusion.3 = f32[2] fusion(%p), kind=kLoop") \
        == "fusion.3"
    assert tr.op_name("jit_fn(123)") == "jit_fn(123)"


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce(TRACE)


def test_recorded_trace_window_and_busy_time(recorded):
    spans, ops = tr.read(TRACE)
    (window,) = [(s, e) for s, e, n in spans if n == "bench.window"]
    assert recorded.window_s == pytest.approx((window[1] - window[0]) * 1e-9)
    assert recorded.window_s == pytest.approx(3.0118, abs=1e-3)
    assert recorded.busy_s == pytest.approx(2.9881, abs=1e-3)
    assert 0 < recorded.idle_share < 0.02
    assert list(ops) == ["/device:TPU:0"]
    assert sum(n == "bench.run_call" for _, _, n in spans) == 11


def test_recorded_trace_top_ops_and_gaps(recorded):
    name, seconds = recorded.top_ops[0]
    assert name == "nero_dycore_whole_state.5"
    assert seconds == pytest.approx(1.3866, abs=1e-3)
    # The scan's while holds every step: its own time is the loop's.
    assert all(not n.startswith("while") for n, _ in recorded.top_ops[:8])
    total = sum(t for _, t in recorded.top_ops)
    assert total == pytest.approx(recorded.busy_s, rel=1e-3)
    assert len(recorded.idle_gaps) == 10
    assert {n for n, _ in recorded.idle_gaps} <= {"bench.run_call",
                                                 "bench.window"}
    assert recorded.idle_gaps[0][1] >= recorded.idle_gaps[-1][1]

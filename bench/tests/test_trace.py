"""The trace reduction, on a small trace recorded on one TPU v5e chip by
``record_trace.py`` (three annotated steps of a 1024 x 1024 bf16 matmul,
each followed by an annotated request), and on intervals made by hand."""
import types
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "small_tpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace.reduce_file(str(DATA))


def test_device_ops_and_host_spans_are_found(red):
    assert sorted(red.ops) == [0]
    names = [n for _, _, n in red.ops[0]]
    assert names == ["copy-start", "copy-done", "fusion"] * 3
    assert [s[0] for s in red.spans] == ["bench.step",
                                         "bench.request.test"] * 3
    assert all(e > s for _, s, e in red.spans)


def test_busy_time_is_the_sum_of_the_recorded_ops(red):
    # copy-start, copy-done and fusion of each step do not overlap:
    # (13 + 3 + 11878) + (14 + 3 + 11877) + (14 + 3 + 11878) ns
    lo, hi = red.ops[0][0][0], red.ops[0][-1][1]
    assert trace.union(red.ops[0], lo, hi) == 35683
    gaps = trace.idle_gaps(red.ops[0], lo, hi)
    # eight boundaries between nine ops, less the first step's copy-done,
    # which ends where its fusion starts
    assert len(gaps) == 7
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - 35683)


def test_align_and_busy_window(red):
    starts = [10.0, 10.001, 10.002]       # host seconds of the three steps
    trace.align(red, starts)
    spans = red.step_spans()
    lo, hi = red.host_to_trace((starts[0], starts[2]))
    assert abs(lo - spans[0][1]) < 2e6 and abs(hi - spans[2][1]) < 2e6
    run = types.SimpleNamespace(reduced=red, held=[(0.0, (0,))])
    wlo, whi = red.ops[0][0][0], red.ops[0][-1][1]
    busy, window = trace.busy_and_window(run, wlo, whi)
    assert busy == pytest.approx(35683e-9)
    assert window == pytest.approx((whi - wlo) * 1e-9)
    out = trace.breakdown(run, wlo, whi)
    assert out["device_ops"][0][0] == "fusion"
    assert out["device_ops"][0][1] == pytest.approx(35633e-9)
    assert len(out["idle_gaps"]) <= 10


def test_union_of_overlapping_and_nested_intervals():
    ops = [(0, 10, "a"), (5, 20, "b"), (6, 7, "c"), (30, 40, "d")]
    assert trace.union(ops, 0, 100) == 30
    assert trace.union(ops, 8, 35) == 17
    assert trace.idle_gaps(ops, 0, 50) == [(20, 30), (40, 50)]


def test_a_released_chip_is_not_held_until_granted():
    red = trace.Reduced(ops={0: [], 1: []}, spans=[], offset_ns=0.0)
    held = [(0.0, (0, 1)), (2e-6, (0,)), (5e-6, (0, 1))]
    run = types.SimpleNamespace(reduced=red, held=held)
    assert trace.held_intervals(run, 0, 0, 8000, red) == [(0, 8000)]
    assert trace.held_intervals(run, 1, 0, 8000, red) == [(0, 2000),
                                                          (5000, 8000)]
    busy, window = trace.busy_and_window(run, 0, 8000)
    assert busy == 0 and window == pytest.approx((8000 + 5000) / 2 * 1e-9)


def test_op_name_drops_the_hlo_text():
    assert trace.op_name("%fusion.12 = bf16[2]{0} fusion(x)") == "fusion.12"


def test_breakdown_counts_exclusive_time_and_names_gaps_by_program_span():
    from bench.scopes import Profile, Span
    ops = [(0, 100, "while.1"), (10, 30, "a"), (40, 90, "b"),
           (130, 150, "c")]
    spans = [("bench.step", 0, 200)]
    program = [Span("edl.step", 0, 200, {}, "t"),
               Span("edl.step.post", 100, 125, {}, "t"),
               Span("edl.adjust.prep", 105, 160, {"adj": 1}, "worker")]
    red = trace.Reduced(ops={0: ops}, spans=spans,
                        program=Profile({0: ops}, {}, program))
    run = types.SimpleNamespace(reduced=red, held=[(0.0, (0,))])
    out = trace.breakdown(run, 0, 200)
    # the while's own time is 100 less its body's 20 + 50
    assert out["device_ops"] == [["b", 50e-9], ["while.1", 30e-9],
                                 ["a", 20e-9], ["c", 20e-9]]
    # gaps [150, 200), [100, 130): the innermost program span in the
    # middle of each, else the benchmark's
    assert out["idle_gaps"] == [["chip 0: edl.step", 50e-9],
                                ["chip 0: edl.adjust.prep", 30e-9]]
    red.program = None
    assert trace.breakdown(run, 0, 200)["idle_gaps"][0] == \
        ["chip 0: bench.step", 50e-9]

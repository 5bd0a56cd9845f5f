"""The reduction from a profiler trace to busy time, operations and idle gaps."""

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def test_merge_unions_overlaps():
    assert trace.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_summarize_by_hand():
    spans = [("bench.product", 0.0, 4.0), ("bench.sync", 4.0, 10.0),
             ("bench.product", 10.0, 11.0)]
    ops = {
        "/device:TPU:0": [("gather", 1.0, 3.0), ("fusion", 2.0, 5.0), ("fusion", 6.0, 7.0)],
        "/device:TPU:1": [("gather", -1.0, 2.0), ("fusion", 9.0, 12.0)],
    }
    s = trace.summarize(ops, spans, (0.0, 10.0))
    assert s.window_s == 10.0
    assert s.busy_s == {"/device:TPU:0": 5.0, "/device:TPU:1": 3.0}
    assert s.mean_busy_s() == 4.0
    assert s.mean_idle_share() == pytest.approx(0.6)
    # TPU:0's gather overlaps the fusion after it, so it counts as holding
    # it: its time is left to the fusion
    assert s.op_s == pytest.approx({"gather": 2.0, "fusion": 5.0})
    # TPU:0 idle 0-1 (product), 5-6 and 7-10 (sync); TPU:1 idle 2-9, all
    # of it labelled by its middle, 5.5, which falls in sync
    assert s.gap_s == pytest.approx({"bench.product": 1.0, "bench.sync": 1.0 + 3.0 + 7.0})
    b = s.breakdown()
    assert b["device_ops"] == [["fusion", 2.5], ["gather", 1.0]]
    assert b["idle_gaps"][0] == ["bench.sync", pytest.approx(5.5)]


def test_gap_outside_spans_and_innermost_label():
    spans = [("bench.solve", 0.0, 10.0), ("bench.rhs", 2.0, 3.0)]
    ops = {"/device:TPU:0": [("op", 0.0, 2.4), ("op", 2.6, 8.0), ("op", 9.0, 10.5)]}
    s = trace.summarize(ops, spans, (0.0, 12.0))
    assert s.gap_s == pytest.approx({"bench.rhs": 0.2, "bench.solve": 1.0,
                                     trace.NO_SPAN: 1.5})


def test_empty_window_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize({}, [], (1.0, 1.0))


def test_recorded_v5e_trace():
    """Three calls of a small gather program on one v5e, each dispatched in a
    ``bench.product`` span and waited for in a ``bench.sync`` span."""
    path = str(DATA / "v5e_gather.xplane.pb")
    ops, spans = trace.read_xspace(path)
    assert list(ops) == ["/device:TPU:0"]
    events = ops["/device:TPU:0"]
    assert len(events) == 15
    assert {e[0] for e in events} == {"copy-start", "copy-done", "fusion", "fusion.1",
                                      "multiply_reduce_fusion"}
    assert [s[0] for s in spans] == ["bench.product", "bench.sync"] * 3
    s = trace.reduce_trace(path, ("bench.product", "bench.sync"), ["/device:TPU:0"])
    assert s.window_s == pytest.approx(spans[-1][2] - spans[0][1])
    # the program's operations run one after another: busy is their sum
    assert s.busy_s["/device:TPU:0"] == pytest.approx(sum(e - b for _, b, e in events))
    assert s.busy_s["/device:TPU:0"] == pytest.approx(13.093e-6, rel=1e-3)
    assert sum(s.gap_s.values()) == pytest.approx(s.window_s - s.busy_s["/device:TPU:0"])
    assert s.breakdown()["device_ops"][0][0] == "fusion"  # the gather
    assert trace.reduce_trace(path, ("bench.solve",), ["/device:TPU:0"]) is None
    assert trace.reduce_trace(path, ("bench.product",), ["/device:TPU:1"]) is None


def test_op_name():
    assert trace.op_name("%fusion.10 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop") == "fusion.10"
    assert trace.op_name("while.42") == "while.42"


def test_nested_operation_leaves_its_time_to_its_body():
    ops = {"/device:TPU:0": [("while.1", 0.0, 10.0), ("fusion.2", 1.0, 6.0),
                             ("fusion.3", 6.0, 9.0)]}
    s = trace.summarize(ops, [("bench.solve", 0.0, 10.0)], (0.0, 10.0))
    assert s.busy_s["/device:TPU:0"] == 10.0
    assert s.op_s == {"fusion.2": 5.0, "fusion.3": 3.0}

"""The trace reduction on plain lists and on a small trace recorded on a
TPU v5e (data/tpu_v5e_small.xplane.pb: three ``benchmark.call`` spans of
four executions of one jitted scan each, python tracer off)."""
import os

import pytest

from benchmark.harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_clip_gaps():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert merged == [(0, 3), (5, 8), (10, 11)]
    assert tr.total(merged) == 7
    assert tr.clip(merged, 2, 10) == [(2, 3), (5, 8)]
    assert tr.gaps(merged, -1, 12) == [(-1, 0), (3, 5), (8, 10), (11, 12)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_self_times_sum_to_busy():
    events = [("while", 0, 100), ("a", 0, 30), ("b", 40, 50),
              ("b.inner", 50, 10), ("c", 120, 5)]
    own = tr.self_times(events)
    assert own == {"while": 20, "a": 30, "b": 40, "b.inner": 10, "c": 5}
    assert sum(own.values()) == tr.total(
        tr.union([(s, s + d) for _, s, d in events]))


def test_short_op_name():
    assert tr.short_op_name(
        "%fusion.9 = (f32[]{:T(128)}) fusion(f32[2] %x), kind=kOutput"
    ) == "fusion.9"


def test_label_gaps():
    spans = [("benchmark.call", 0.0, 100.0), ("benchmark.call", 150.0, 50.0)]
    host = spans + [("decode", 10.0, 30.0), ("write", 60.0, 39.0),
                    ("tick", 0.0, 1000.0)]
    out = dict(tr.label_gaps([(12.0, 38.0), (100.0, 150.0), (70.0, 71.0)],
                             spans, host, "benchmark.call", labelled=2))
    assert out["between calls: tick"] == pytest.approx(50e-9)
    assert out["benchmark.call: decode"] == pytest.approx(26e-9)
    assert out["short gaps"] == pytest.approx(1e-9)


@pytest.fixture(scope="module")
def recorded():
    return tr.load(os.path.join(DATA, "tpu_v5e_small.xplane.pb"))


def test_recorded_trace_planes(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    assert len(recorded.spans("benchmark.call")) == 3
    assert len(recorded.devices["/device:TPU:0"]["XLA Modules"]) == 12


def test_recorded_trace_busy_and_idle(recorded):
    r = tr.reduce(recorded, "benchmark.call", chips=1)
    modules = recorded.devices["/device:TPU:0"]["XLA Modules"]
    lo, hi = r["window_ns"]
    inside = [d for _, s, d in modules if s >= lo and s + d <= hi]
    # every op lies inside its module's execution, and the ops leave
    # little of it idle: busy is most of the modules' time, never more
    assert 0.9 * sum(inside) / 1e9 <= r["busy_s"] <= sum(
        d for _, _, d in modules) / 1e9
    assert r["calls"] == 3
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert r["idle_share_worst"] == pytest.approx(
        1 - r["busy_s"] / r["window_s"])
    # the op self times account for all busy time inside the window (to
    # the nanosecond rounding of nested events' ends)
    assert sum(s for _, s in r["device_ops"]) == pytest.approx(
        r["busy_s"], rel=1e-3)
    # each span slept 5 ms with the device idle
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["window_s"] - r["busy_s"] > 3 * 0.005


def test_wrong_chip_count_is_an_error(recorded):
    with pytest.raises(ValueError):
        tr.reduce(recorded, "benchmark.call", chips=4)

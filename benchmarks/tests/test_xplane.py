"""The trace reducer: its interval arithmetic on made-up intervals, and
the whole reduction on a small trace recorded on a TPU v5e
(``record_trace.py`` says how)."""

import os

import pytest

import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "recorded.xplane.pb.gz")


def test_union_intersect_subtract():
    a = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert a == [(0, 3), (5, 8)]
    b = [(2, 6), (7.5, 9)]
    assert xplane.intersect(a, b) == [(2, 3), (5, 6), (7.5, 8)]
    assert xplane.subtract(a, b) == [(0, 2), (6, 7.5)]
    assert xplane.subtract(a, []) == a


def test_self_time_takes_the_children_out():
    evs = [("while", 0.0, 10.0), ("fusion.1", 1.0, 4.0),
           ("fusion.2", 4.0, 9.0), ("copy", 12.0, 13.0),
           ("fusion.1", 13.0, 14.0)]
    own = xplane.self_times(evs)
    assert own == pytest.approx({"while": 2.0, "fusion.1": 4.0,
                                 "fusion.2": 5.0, "copy": 1.0})
    assert sum(own.values()) == pytest.approx(
        sum(e - s for s, e in xplane.union([(s, e) for _, s, e in evs])))


def test_recorded_tpu_trace():
    red = xplane.reduce_trace(RECORDED)
    assert red["on_cpu"] is False
    dev = red["per_device"]
    assert [d["device"] for d in dev] == ["/device:TPU:0"]
    assert red["annotation_counts"]["reflow.window"] == 6
    assert red["annotation_counts"]["bench.dispatch_staged"] == 6
    # pinned from the recording (see RECORDED_READINGS below)
    assert dev[0]["ops"] == RECORDED_READINGS["ops"]
    assert red["busy_s"] == pytest.approx(RECORDED_READINGS["busy_s"],
                                          rel=1e-9)
    assert red["window_s"] == pytest.approx(RECORDED_READINGS["window_s"],
                                            rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    # busy + idle, split by label, tile the span
    idle = sum(s for _, s in red["idle_gaps"])
    assert red["busy_s"] + idle == pytest.approx(red["window_s"], rel=1e-6)
    # self times add up to the busy time: nothing is counted twice
    total_self = sum(s for _, s in red["device_ops"])
    assert total_self <= red["busy_s"] * (1 + 1e-9)
    assert red["device_ops"][0][0].startswith(RECORDED_READINGS["top_op"])


RECORDED_READINGS = {"ops": 120, "busy_s": 9.734899999998964e-05,
                     "window_s": 0.018501986000000005,
                     "top_op": "%fusion.8 = f32[512,512]"}

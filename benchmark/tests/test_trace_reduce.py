"""The reduction from a profiler trace to busy time, per-operation time and
attributed gaps: its arithmetic on hand-made intervals, and the whole of it
on a small trace recorded on the chip (``data/q6_v5e.xplane.pb``: the first
two of the ten traced q6 queries of a ``tpch_sf1_cached.q6`` run on one "TPU
v5 lite", PR 25, cut out of the profiler's file with event names and times
kept and event statistics dropped)."""

import os

import numpy as np
import pytest

import trace_reduce as TR

RECORDED = os.path.join(os.path.dirname(__file__), "data", "q6_v5e.xplane.pb")


def test_union_merges_overlaps_and_nesting():
    s = np.array([10.0, 0.0, 2.0, 20.0, 21.0])
    e = np.array([12.0, 5.0, 3.0, 30.0, 25.0])
    us, ue = TR.union(s, e)
    assert us.tolist() == [0.0, 10.0, 20.0] and ue.tolist() == [5.0, 12.0, 30.0]
    assert (ue - us).sum() == 17.0


def test_union_of_nothing():
    us, ue = TR.union(np.zeros(0), np.zeros(0))
    assert len(us) == 0 and len(ue) == 0


def test_self_time_subtracts_children():
    # while [0,100) holds body ops [10,30) and [40,90), the last holds [50,60)
    names = ["while", "fusion.1", "fusion.2", "copy", "fusion.1"]   # as labelled
    s = np.array([0.0, 10.0, 40.0, 50.0, 200.0])
    e = np.array([100.0, 30.0, 90.0, 60.0, 210.0])
    t = TR.self_times(names, s, e)
    assert t == {"while": 30.0, "fusion.1": 30.0, "fusion.2": 40.0,
                 "copy": 10.0}
    assert sum(t.values()) == (TR.union(s, e)[1] - TR.union(s, e)[0]).sum()


def test_labels():
    assert TR._label("12:stage3") == "#:stage#"
    assert TR.op_label("%fusion.7 = pred[1048576]{0:T(1024)S(1)} fusion(pred[8]"
                       "{0} %k), kind=kCustom") == "fusion pred[1048576]"
    assert TR.op_label("%f.1 = (f32[8]{0}, s32[8]{0}) fusion(") == "f f32[8]"
    assert TR.op_label("while.3") == "while"


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    r = TR.reduce_trace(RECORDED)
    assert r["device_planes"] == ["/device:TPU:0"]
    assert r["queries"] == EXPECTED["queries"]
    assert r["device_events"] == EXPECTED["device_events"]
    assert r["window_s"] == pytest.approx(EXPECTED["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(EXPECTED["busy_s"], rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"][0][0] == EXPECTED["top_op"]
    # self times add up to the busy union; gaps and busy add up to the window
    assert sum(s for _, s in TR.reduce_trace(RECORDED, top=10**6)["device_ops"]) \
        == pytest.approx(r["busy_s"], rel=1e-6)
    assert sum(s for _, s in TR.reduce_trace(RECORDED, top=10**6)["idle_gaps"]) \
        + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-6)


#: read once from the recorded file with this code and looked at by hand
EXPECTED = {"queries": 2, "device_events": 1696, "window_s": 0.663191594,
            "busy_s": 0.636770506, "top_op": "fusion pred[1048576]"}

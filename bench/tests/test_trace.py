"""The trace reduction, on small traces recorded on the chip
(``record_trace.py``): a few closed-loop windows of a tiny store."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACES = sorted(f for f in os.listdir(DATA) if f.endswith(".xplane.pb"))


def test_merge_unions_overlaps():
    assert trace._merge([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
    assert trace._length(trace._merge([(2, 4), (3, 4)])) == 2


def test_self_times_subtract_nested_ops():
    t = trace._self_times([(0, 10, "while.1"), (1, 3, "fusion.2"),
                           (4, 5, "copy.3"), (4.5, 5, "bitcast.4"),
                           (12, 13, "fusion.2")])
    assert t == {"while.1": 7, "fusion.2": 3, "copy.3": 0.5, "bitcast.4": 0.5}
    assert trace.op_name("%copy.107 = s32[8]{0} copy(s32[8]{0} %p)") == "copy.107"


def test_recorded_traces_exist():
    assert any("p8" in f for f in TRACES)


@pytest.mark.parametrize("name", TRACES)
def test_reduce_recorded_trace(name):
    r = trace.reduce(os.path.join(DATA, name))
    assert r is not None
    assert r["devices"] == (4 if "mesh4" in name else 1)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert all(0 < b <= r["window_s"] for b in r["busy_s_per_device"])
    assert r["host_spans"] == 3 * 4          # 4 windows, 3 spans each
    assert r["device_ops"] and all(t > 0 for _, t in r["device_ops"])
    assert {g[0] for g in r["idle_gaps"]} <= set(trace.HOST_SPANS) | {"other"}
    gaps = sum(g for _, g in r["idle_gaps"])
    assert gaps <= r["window_s"] - r["busy_s_per_device"][0] + 1e-9
    if "mesh4" in name:
        assert 0 < r["collective_s"] < r["busy_s"]
    else:
        assert r["collective_s"] == 0

"""The trace reduction on a small recorded trace with known busy intervals.

Device ops (ns): while.1 [100,400) holding fusion.2 [120,220) and
custom-call.3 [250,350); copy.4 [380,440) overlaps the loop's end;
fusion.5 [600,800); fusion.2 [900,950). The busy union is
[100,440) + [600,800) + [900,950) = 590 of the 1000 traced.
"""
import json
import os

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def _planes():
    return json.load(open(os.path.join(HERE, "data", "trace_small.json")))


def test_busy_is_the_union_not_the_sum():
    r = trace_reduce.reduce(_planes())
    assert r["window_s"] == 1000e-9
    assert abs(r["busy_s"] - 590e-9) < 1e-15
    assert len(r["devices"]) == 1 and r["devices"][0]["events"] == 6


def test_time_by_operation_is_self_time():
    ops = dict(trace_reduce.reduce(_planes())["device_ops"])
    assert abs(ops["fusion.5"] - 200e-9) < 1e-15
    assert abs(ops["fusion.2"] - 150e-9) < 1e-15       # two events
    assert abs(ops["custom-call.3"] - 100e-9) < 1e-15
    # the loop without its body; the overlapping copy clipped to the loop
    assert abs(ops["while.1"] - (300 - 100 - 100 - 20) * 1e-9) < 1e-15
    assert abs(ops["copy.4"] - 20e-9) < 1e-15


def test_idle_gaps_go_to_what_the_host_was_doing():
    gaps = dict(trace_reduce.reduce(_planes())["idle_gaps"])
    # [0,100) in the first bench/step; [440,600) mid 520 in PjitFunction
    # inside the second bench/step (innermost wins); [800,900) mid 850 in
    # TransferFromDevice on another thread; [950,1000) in bench/traced only
    assert abs(gaps["bench/step"] - 100e-9) < 1e-15
    assert abs(gaps["PjitFunction(step)"] - 160e-9) < 1e-15
    assert abs(gaps["TransferFromDevice"] - 100e-9) < 1e-15
    assert abs(gaps["bench/traced"] - 50e-9) < 1e-15
    assert abs(sum(gaps.values()) - 410e-9) < 1e-15


def test_events_are_clipped_to_the_traced_span():
    planes = _planes()
    planes[1]["lines"][0]["events"][0] = ["bench/traced", 200, 500, {}]
    r = trace_reduce.reduce(planes)
    assert r["window_s"] == 500e-9
    # [200,440) + [600,700)
    assert abs(r["busy_s"] - 340e-9) < 1e-15


def test_a_trace_without_a_device_plane_is_an_error():
    import pytest
    with pytest.raises(ValueError, match="no /device:TPU:"):
        trace_reduce.reduce(_planes()[1:])


def test_events_matching_by_shape():
    evs = trace_reduce.events_matching(
        _planes(), lambda n, st: "[2,2,8,4]" in str(st.get("long_name")))
    assert [e[0] for e in evs] == ["custom-call.3"]

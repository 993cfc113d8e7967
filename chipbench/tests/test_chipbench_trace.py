"""``trace_reduce`` on a trace recorded on a TPU v5e: three calls of a small
jitted function (a 512x512 matmul, tanh, matmul), each annotated as a call,
with a 2 ms host sleep inside each call and 3 ms between calls."""
import os

import pytest

from benchlib import ROOT

from chipbench import trace_reduce

PROBE = os.path.join(ROOT, "chipbench", "testdata", "probe.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(PROBE)


def test_finds_the_device_and_the_calls(reduced):
    assert reduced["devices"] == 1
    assert reduced["calls"] == 3


def test_busy_is_the_union_of_the_ops(reduced):
    # three ~4 us program runs on the device
    assert reduced["busy_s"] == pytest.approx(1.1876e-05, rel=1e-6)
    # from the first call's start to the last call's end, host clock
    assert reduced["window_s"] == pytest.approx(0.017119891, rel=1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_ops_are_charged_self_time_under_short_names(reduced):
    ops = dict(reduced["device_ops"])
    assert set(ops) == {"%fusion", "%copy-start", "%copy-done"}
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"], rel=1e-3)


def test_idle_gaps_are_the_holes_between_programs(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) == 3
    assert all(name in ("inside call", "between calls") for name, _ in gaps)
    # each hole holds sleeps (2 ms in a call, 3 ms between) and dispatch;
    # the last runs from the last op to the end of the last call
    assert all(0.004 < s < 0.008 for _, s in gaps)
    # host and device clocks differ by about a millisecond
    assert sum(s for _, s in gaps) < reduced["window_s"] + 0.002


def test_self_time_of_nested_ops():
    events = [("%while.1 = (...)", 0.0, 100.0), ("%fusion.2 = f32", 10.0, 30.0),
              ("%fusion.3 = f32", 40.0, 50.0), ("%copy.4 = f32", 120.0, 130.0)]
    got = trace_reduce._self_time(events)
    assert got == {"%while.1": 70.0, "%fusion.2": 20.0, "%fusion.3": 10.0,
                   "%copy.4": 10.0}

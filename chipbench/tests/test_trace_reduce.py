"""The reducer's arithmetic on hand-written events, and the adapter on the
recorded traces of the two cells."""
import glob
import json
import os

import pytest

import trace_reduce as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1_000_000


def ev(line, name, start_ms, dur_ms, plane=DEV):
    return (plane, line, name, int(start_ms * MS), int(dur_ms * MS))


def hand_written():
    """Three runs of jit_step at 0, 10 and 20 ms (the window is 0..20 ms,
    the third run only closes it), each 8 ms of module time; a small other
    module; overlapping ops; host spans around each period."""
    events = []
    for i, t in enumerate((0, 10, 20)):
        events.append(ev(tr.MODULES_LINE, "jit_step(%d)" % (7 + i), t, 8))
        events.append(ev(tr.OPS_LINE, "fusion.1", t, 5))
        events.append(ev(tr.OPS_LINE, "copy.2", t + 4, 2))     # overlaps 1 ms
        events.append(ev(tr.OPS_LINE, "dot.3", t + 7, 1))      # gap 6..7
        events.append(ev("cb:unused", "cb:step", t - 0.5, 9.0, HOST))
        events.append(ev("cb:unused", "cb:client", t + 8.5, 1.0, HOST))
    events.append(ev(tr.MODULES_LINE, "jit_other(1)", 8.2, 0.1))
    events.append(ev(tr.OPS_LINE, "tiny.9", 8.2, 0.1))
    events.append(ev("Steps", "ignored", 0, 30))
    return events


def test_window_busy_and_step_time():
    out = tr.reduce(hand_written())
    assert out["step_module"] == "jit_step"
    assert out["steps"] == 2
    assert out["window_s"] == pytest.approx(0.020)
    # per period: [0,6) merged from fusion+copy, [7,8) dot; plus tiny.9 once
    assert out["busy_s"] == pytest.approx((2 * 7 + 0.1) / 1e3, rel=1e-4)
    assert out["step_device_ms"] == pytest.approx(8.0)


def test_top_ops_and_gaps():
    out = tr.reduce(hand_written())
    ops = dict(out["device_ops"])
    assert ops["fusion.1 x1"] == pytest.approx(0.010)
    assert ops["copy.2 x1"] == pytest.approx(0.004)
    gaps = dict(out["idle_gaps"])
    # 6..7 ms lies inside a run of the step program
    assert gaps["step:in_program"] == pytest.approx(0.002)
    # 8..8.2 between jit_step and jit_other, 8.3..8.5 and 18..18.5 after
    # the last program of their cb:step
    assert gaps["step:between_programs"] == pytest.approx(0.0002, rel=1e-4)
    assert gaps["step:after_device"] == pytest.approx(0.0007, rel=1e-4)
    # 8.5..9.5 is the client's; 9.5..10 belongs to the next cb:step,
    # before its program starts
    assert gaps["client"] == pytest.approx(0.002)
    assert gaps["step:before_device"] == pytest.approx(0.001)
    assert sum(gaps.values()) == pytest.approx(out["window_s"]
                                               - out["busy_s"])


def test_two_devices_average_and_no_device():
    one = hand_written()
    two = one + [(("/device:TPU:1",) + e[1:]) for e in one if e[0] == DEV]
    a, b = tr.reduce(one), tr.reduce(two)
    assert b["busy_s"] == pytest.approx(a["busy_s"])
    assert b["window_s"] == pytest.approx(a["window_s"])
    assert tr.reduce([e for e in one if e[0] == HOST]) is None


def test_merge():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [[0, 3], [5, 8]]


RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "recorded")


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(RECORDED, "*.xplane.pb"))) or [None])
def test_adapter_on_recorded_trace(path):
    """A real .xplane.pb of each cell, cut short, with the reducer's output
    as it was on the day it was recorded."""
    if path is None:
        pytest.skip("no recorded trace")
    with open(path[:-len(".xplane.pb")] + ".reduced.json") as f:
        want = json.load(f)
    got = tr.reduce(tr.read_xplane(path))
    assert got["step_module"] == want["step_module"]
    assert got["steps"] == want["steps"]
    for key in ("window_s", "busy_s", "step_device_ms"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    assert [k for k, _ in got["device_ops"]] == \
        [k for k, _ in want["device_ops"]]
    assert [k for k, _ in got["idle_gaps"]] == \
        [k for k, _ in want["idle_gaps"]]


def test_op_kind():
    name = ("%copy.39 = bf16[160,32,16,16,128]{4,2,1,3,0:T(8,128)(2,1)} "
            "copy(bf16[160,32,16,16,128]{4,3,2,1,0:T(8,128)(2,1)} %bitcast.44)")
    assert tr.op_kind(name) == "copy bf16[160,32,16,16,128]"
    name = ("%fusion.100 = (f32[30522,768]{1,0:T(8,128)S(1)}, f32[30522,768]"
            "{1,0:T(8,128)}) fusion(f32[30522,768]{1,0} %p), kind=kLoop, "
            "calls=%fused_computation.1")
    assert tr.op_kind(name) == "fusion f32[30522,768]"
    assert tr.op_kind("fusion.1") == "fusion.1"
    events = [(DEV, tr.OPS_LINE,
               "%%copy.%d = bf16[4]{0} copy(bf16[4]{0} %%p)" % i, i * MS,
               MS // 2) for i in range(3)]
    assert tr.reduce(events)["device_ops"] == [["copy bf16[4] x3", 0.0015]]

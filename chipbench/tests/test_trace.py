"""The trace reduction, on intervals written by hand and on a small trace
recorded on a TPU v5e by ``record_trace.py``."""
from __future__ import annotations

import os

import pytest

import smoke  # noqa: F401

from chipbench import trace as tr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traces", "small.xplane.pb")


def ev(name, start, end):
    return tr.Event(name, start, end - start)


def test_busy_union_exposed_collectives_and_gap_labels():
    t = tr.Trace(
        devices={
            0: [ev("fusion.1", 10, 30), ev("fusion.2", 30, 40),
                ev("all-reduce.1", 35, 60), ev("fusion.1", 70, 80)],
            1: [ev("fusion.1", 0, 100)],
        },
        host=[ev("chipbench:window", 10, 90), ev("chipbench:loss", 40, 68),
              ev("chipbench:feed", 80, 95)])
    s = tr.reduce(t)
    assert s.window_s == pytest.approx(80e-9)
    # device 0: [10,60] + [70,80] = 60; device 1 clipped to the window: 80
    assert s.busy_s == pytest.approx(70e-9)
    # the all-reduce runs alone on device 0 from 40 to 60
    assert s.collective_exposed_s == pytest.approx(10e-9)
    assert s.n_devices == 2
    # fusion.1: 20 + 10 on device 0, 80 on device 1, over two devices
    assert s.device_ops[0] == ("fusion.1", pytest.approx(55e-9))
    assert s.idle_gaps == [("loss", pytest.approx(10e-9)),
                           ("feed", pytest.approx(10e-9))]


def test_one_window_span_is_required():
    with pytest.raises(ValueError, match="one 'chipbench:window'"):
        tr.reduce(tr.Trace(devices={0: [ev("f", 0, 1)]}, host=[]))
    with pytest.raises(ValueError, match="no TPU device"):
        tr.reduce(tr.Trace(host=[ev("chipbench:window", 0, 1)]))


def test_recorded_chip_trace():
    """Three 4096-square bfloat16 products and tanh, each followed by a
    20 ms host sleep inside ``chipbench:feed``."""
    s = tr.reduce(tr.load(RECORDED))
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.collective_exposed_s == 0
    labels = [label for label, _ in s.idle_gaps]
    assert labels[:3] == ["feed"] * 3
    assert all(g >= 0.015 for _, g in s.idle_gaps[:3])


def test_nested_operations_count_their_self_time():
    t = tr.Trace(
        devices={0: [ev("%while.3 = (s32[], f32[8]{0}) while(...)", 0, 100),
                     ev("%fusion.1 = f32[16,2048]{1,0} fusion(...)", 10, 40),
                     ev("%fusion.1 = f32[16,2048]{1,0} fusion(...)", 50, 70)]},
        host=[ev("chipbench:window", 0, 100)])
    s = tr.reduce(t)
    assert s.device_ops == [("fusion.1 f32[16,2048]", pytest.approx(50e-9)),
                            ("while.3 s32[]", pytest.approx(50e-9))]
    assert s.busy_s == pytest.approx(100e-9)

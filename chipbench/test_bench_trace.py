"""The trace reduction on a small synthetic trace."""
from types import SimpleNamespace as NS

import pytest

from chipbench import trace
from chipbench.hooks import SPANS


def _ev(name, a, b):
    return NS(name=name, start_ns=a, duration_ns=b - a)


def _planes():
    ops = [_ev("fusion.1", 0, 10), _ev("fusion.2", 5, 20),
           _ev("convolution.3", 30, 40), _ev("fusion.1", 60, 100),
           _ev("fusion.1", 100, 110)]
    mods = [_ev("jit_train_step(7)", 0, 45), _ev("jit_train_step(7)", 55, 100)]
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=mods),
                                             NS(name="XLA Ops", events=ops)])
    spans = [_ev("window", 0, 100), _ev("train_step", 15, 25),
             _ev("checkpoint", 38, 70), _ev("vote", 45, 58),
             _ev("unrelated", 0, 200)]
    host = NS(name="/host:CPU", lines=[NS(name="python", events=spans)])
    return [NS(name="/host:metadata", lines=[]), host, device]


def test_busy_union_idle_share_and_gaps():
    r = trace.reduce_planes(_planes(), SPANS)
    assert r.window_s == pytest.approx(100e-9)
    # Ops merge to [0,20] [30,40] [60,100]; the op past the window is cut.
    assert r.busy_s == pytest.approx(70e-9)
    assert r.idle_share == pytest.approx(0.3)
    # Gap [20,30]: train_step to 25, nothing after; gap [40,60]: the
    # checkpoint, with the vote inside it as the innermost span.
    assert dict(r.idle_gaps) == pytest.approx(
        {"vote": 13e-9, "checkpoint": 7e-9, "train_step": 5e-9,
         "other": 5e-9})
    assert r.idle_gaps[0][0] == "vote"
    assert dict(r.device_ops) == pytest.approx(
        {"fusion.1": 50e-9, "fusion.2": 15e-9, "convolution.3": 10e-9})
    assert r.modules == {"jit_train_step(7)": pytest.approx([45e-9, 45e-9])}


def test_two_devices_average_and_explicit_window():
    planes = _planes()
    other = NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[
        _ev("fusion.9", 0, 50)])])
    r = trace.reduce_planes(planes + [other], SPANS, window=(0, 50))
    assert r.devices == 2
    # TPU:0 is busy 20 + 10 of [0,50], TPU:1 all 50.
    assert r.busy_s == pytest.approx(40e-9)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace.reduce_planes(_planes()[:2], SPANS)

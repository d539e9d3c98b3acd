"""The trace reduction, on hand-made events and on a small recorded TPU
trace (``data/tiny.xplane.pb``, made by ``record_trace.py`` on one v5e)."""

import os

import pytest

from bench import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny.xplane.pb")
DEV = "/device:TPU:0"


def _made():
    ops = [
        T.Event("fusion.1", 0, 10),
        T.Event("gather_distance.2", 5, 15, {"long_name": "f32[4096,1,60]{2,1,0} custom-call(...)"}),
        T.Event("fusion.3", 30, 10),
        T.Event("late.4", 150, 10),  # after the window
    ]
    modules = [T.Event("jit_wave_core(7)", 0, 20), T.Event("jit_wave_core(7)", 30, 10),
               T.Event("jit_search(9)", 41, 1)]
    host = [
        T.Event(T.WINDOW_SPAN, 0, 100),
        T.Event("bench.step", 15, 20),
        T.Event("bench.wait", 40, 60),
        T.Event("PjitFunction(search)", 41, 40),
    ]
    return T.Trace(ops={DEV: ops}, modules={DEV: modules}, host=host)


def test_busy_share_is_the_union_inside_the_window():
    busy, window = T.busy_share(_made())
    assert busy == pytest.approx(30e-9)  # [0, 20] and [30, 40]
    assert window == pytest.approx(100e-9)


def test_idle_gaps_are_attributed_to_the_innermost_host_span():
    gaps = T.idle_gaps(_made())
    assert gaps[0][0] == "bench.wait: PjitFunction(search)"
    assert gaps[0][1] == pytest.approx(60e-9)
    assert gaps[1][0] == "bench.step" and gaps[1][1] == pytest.approx(10e-9)


def test_kernel_and_module_time_by_name():
    tr = _made()
    assert T.kernel_seconds(tr, "gather_distance") == pytest.approx(15e-9)
    assert T.kernel_shape(T.kernel_events(tr, "gather_distance")[0]) == (4096, 60)
    runs = T.module_events(tr, "jit_wave_core")
    assert [e.dur for e in runs] == [20, 10]
    assert T.top_ops(tr)[0] == ["gather_distance.2", pytest.approx(15e-9)]


def test_self_time_nets_out_nested_ops():
    ops = [T.Event("%while.6 = (s32[8,40]{1,0}, f32[8]) while(...)", 0, 100),
           T.Event("%fusion.1 = f32[8,60]{1,0:T(8,128)} fusion(...)", 10, 30),
           T.Event("%fusion.1 = f32[8,60]{1,0:T(8,128)} fusion(...)", 50, 30),
           T.Event("%copy.2 = f32[8]{0} copy(...)", 120, 5)]
    assert T.self_times(ops) == {"while.6 s32[8,40]": 40, "fusion.1 f32[8,60]": 60, "copy.2 f32[8]": 5}


def test_a_name_that_is_not_there_is_an_error():
    tr = _made()
    with pytest.raises(KeyError):
        T.kernel_events(tr, "pairwise_distance")
    with pytest.raises(KeyError):
        T.module_events(tr, "jit_commit_wave")
    with pytest.raises(KeyError):
        T.busy_share(T.Trace(ops={}, modules={}, host=[]))
    with pytest.raises(KeyError):
        T.window(T.Trace(ops={DEV: []}, modules={}, host=[]))


def test_recorded_tpu_trace():
    tr = T.load(DATA)
    assert tr.devices == [DEV]
    busy, window = T.busy_share(tr)
    assert 0 < busy < window
    kernel = T.kernel_events(tr, "gather_distance")
    assert len(kernel) == 2
    assert T.kernel_shape(kernel[0]) == (8, 16)
    assert len(T.module_events(tr, "jit_tiny_step")) == 4
    name, secs = T.idle_gaps(tr)[0]
    assert name.startswith("bench.wait") and 0.04 < secs < 0.1
    with pytest.raises(KeyError):
        T.module_events(tr, "jit_wave_core")

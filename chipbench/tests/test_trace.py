"""The trace reducer on a small trace recorded on a TPU v5e (three calls of
one jitted function, each in a ``walk.run`` span followed by a 10 ms sleep
in a ``host.next`` span, all inside ``window``), and on hand-made
intervals."""
import os

import pytest

from chipbench.core import trace as tr

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
SPANS = {"window", "walk.run", "host.next"}


def test_union_and_self_times():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    # a while op 0..10 holding ops 1..4 and 6..9: self time 10 - 3 - 3
    st = tr._self_times([(0, 10), (1, 4), (6, 9), (11, 12)])
    assert st == {0: 4, 1: 3, 2: 3, 3: 1}


def test_names():
    assert tr._op_name("%fusion.12 = f32[8]{0} fusion(...)") == "fusion.12"
    assert tr._module_name("jit__simulate(2354710543457823928)") == \
        "jit__simulate"


def test_small_recorded_trace():
    s = tr.reduce(SMALL, [0], SPANS)
    # the window span, and the two ops that start inside it (11,097 ns and
    # 11,323 ns); the first op lies before the window on the trace's clock
    assert s.window_s == pytest.approx(0.034664067, abs=1e-9)
    assert s.busy_s == pytest.approx((11097 + 11323) * 1e-9, abs=1e-12)
    assert s.top_ops == [["jit__lambda/fusion", pytest.approx(s.busy_s)]]
    idle = tr.idle_by_span(s)
    assert [name for name, _ in idle] == ["host.next"]
    assert idle[0][1] == pytest.approx(s.window_s - s.busy_s)


def test_no_device_plane_reads_nothing():
    s = tr.reduce(SMALL, [7], SPANS)
    assert s.busy_s == 0.0 and s.chips == 0 and s.top_ops == []

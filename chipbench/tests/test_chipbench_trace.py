"""The trace reduction, on a small trace recorded on the CPU
(``record_cpu_trace.py``): the CPU backend's worker thread stands in
for the device's operation line, and its ``dot_general`` events for the
program's executions. Expected values are worked out here from the raw
events by a sweep over their end points, apart from trace.py."""

import os

import pytest

from chipbench import trace

PATH = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")
CPU = trace.Layout(device_plane="/host:CPU", ops_line="tf_XLAPjRtCpuClient",
                   modules_line="tf_XLAPjRtCpuClient", host_plane="/host:CPU",
                   host_thread="python")


@pytest.fixture(scope="module")
def tl():
    return trace.load(PATH, CPU)


def covered(intervals, lo, hi):
    """Seconds of [lo, hi] inside at least one interval: end-point sweep."""
    pts = sorted([(max(s, lo), 1) for s, e in intervals if e > lo and s < hi]
                 + [(min(e, hi), -1) for s, e in intervals if e > lo and s < hi])
    depth, last, total = 0, None, 0.0
    for t, d in pts:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_window_and_busy_time(tl):
    win = tl.window
    assert 0.006 < win[1] - win[0] < 0.02          # three ticks and sleeps
    (plane, evs), = tl.ops.items()
    want = covered([(e.start, e.end) for e in evs], *win)
    assert trace.busy_s(tl) == pytest.approx(want, rel=1e-9)
    assert 0 < want < win[1] - win[0]


def test_per_tick_program_time(tl):
    per = trace.per_span_device_s(tl, "chipbench.tick", "dot_general")
    assert sorted(per) == [0, 1, 2]
    (plane, evs), = tl.modules.items()
    for ev in (e for e in tl.host if e.name == "chipbench.tick"):
        want = sum(e.end - e.start for e in evs if "dot_general" in e.name
                   and ev.start <= e.start <= ev.end)
        assert per[int(ev.stats["tick"])] == pytest.approx(want, abs=1e-12)
        assert want > 0


def test_idle_gaps_go_to_the_host_span(tl):
    gaps = dict(trace.idle_gaps(tl))
    idle = (tl.window[1] - tl.window[0]) - trace.busy_s(tl)
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-9)
    # the sleeps between ticks are the longest idle stretches
    assert max(gaps, key=gaps.get) == "$time sleep"
    assert gaps["$time sleep"] > 0.005


def test_top_ops(tl):
    ops = dict(trace.top_ops(tl))
    assert max(ops, key=ops.get) == "dot_general.1"


def test_interval_arithmetic():
    assert trace.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert trace.union([(0, 10)], clip=(2, 3)) == [(2, 3)]
    assert trace.subtract([(0, 10)], [(1, 2), (5, 6)]) == [(0, 1), (2, 5), (6, 10)]
    assert trace.subtract([(0, 1), (2, 3)], [(0.5, 2.5)]) == [(0, 0.5), (2.5, 3)]


def test_exposed_collectives():
    ev = trace.Event
    tl = trace.Timeline(
        window=(0.0, 10.0),
        ops={"/device:TPU:0": [ev("fusion.1", 0, 4), ev("all-reduce.2", 3, 6),
                               ev("all-gather.3", 8, 9)],
             "/device:TPU:1": [ev("all-reduce.2", 0, 2)]},
        modules={}, host=[])
    # device 0: all-reduce 4..6 and all-gather 8..9 run alone -> 3 s;
    # device 1: 2 s; averaged over the two devices
    assert trace.exposed_collective_s(tl) == pytest.approx(2.5)

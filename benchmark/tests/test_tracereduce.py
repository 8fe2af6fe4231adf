"""The trace reduction, checked by hand on made-up events and against a
brute-force count on ``recorded.xplane.pb`` (1.2 s of a trace recorded on the
v5e, PR 25 call A: the end of one 255-leaf iteration at 41,943,040 rows and
the start of the next; ``cut_trace.py`` says how it was cut).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import tracereduce as tr

RECORDED = HERE / "recorded.xplane.pb"
KERNELS = {"hist": ["wave_histogram_pallas", "fused_partition_histogram_pallas"]}


def test_self_time_charges_a_while_only_what_its_body_leaves():
    ev = [("while.1", 0.0, 100.0), ("fusion.a", 10.0, 30.0),
          ("kernel.b", 30.0, 90.0), ("copy.c", 120.0, 130.0)]
    selfs = tr.self_times(ev)
    assert dict(selfs) == {"while.1": 20.0, "fusion.a": 20.0,
                           "kernel.b": 60.0, "copy.c": 10.0}
    # the self times add up to the union of the intervals: 0..100, 120..130
    assert tr.union([(s, e) for _, s, e in ev]) == [[0.0, 100.0], [120.0, 130.0]]
    assert sum(t for _, t in selfs) == 110.0


def test_union_clips_and_merges():
    assert tr.union([(5, 8), (0, 3), (2, 4), (8, 9)], lo=1, hi=8.5) == \
        [[1, 4], [5, 8.5]]
    assert tr.union([(0, 1)], lo=2, hi=3) == []


def test_gaps_are_what_the_union_leaves_of_the_window():
    assert tr.gaps([[1, 4], [5, 8]], 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_a_gap_goes_to_the_innermost_host_span_open_when_it_began():
    spans = [("bench/window", 0, 100), ("bench/update_issue", 10, 50),
             ("bench/fence_wait", 20, 30)]
    assert tr.attribute((25, 26), spans) == "fence_wait"
    assert tr.attribute((40, 60), spans) == "update_issue"
    assert tr.attribute((70, 80), spans) == "nothing"


def test_short_name_is_the_instruction_not_its_hlo_text():
    assert tr.short_name("%fusion.3 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop") \
        == "fusion.3"
    assert tr.short_name("bench/window") == "bench/window"


@pytest.fixture(scope="module")
def recorded():
    pd = tr.load(RECORDED)
    return pd, tr.reduce(pd, KERNELS)


def _device_events(pd):
    (plane,) = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    (line,) = [l for l in plane.lines if l.name == tr.OPS_LINE]
    return tr._events(line)


def test_recorded_busy_union_against_a_brute_force_grid(recorded):
    """Busy time and idle share, counted again the slow way: a 10 us grid over
    the window, a tick busy when some operation covers it."""
    pd, red = recorded
    ev = _device_events(pd)
    (win,) = [(s, e) for n, s, e in tr.host_spans(pd) if n == tr.WINDOW_SPAN]
    inner = [(s, e) for _, s, e in ev]
    step = 10_000.0
    ticks = int((win[1] - win[0]) // step)
    busy = [False] * ticks
    for s, e in inner:
        for i in range(max(0, int((s - win[0]) // step)),
                       min(ticks, int((e - win[0]) // step) + 1)):
            mid = win[0] + (i + 0.5) * step
            if s <= mid < e:
                busy[i] = True
    brute = sum(busy) * step / 1e9
    assert red["planes"] == 1
    assert red["window_s"] == pytest.approx((win[1] - win[0]) / 1e9, rel=1e-9)
    assert red["busy_s"] == pytest.approx(brute, abs=2e-3)
    assert 0.0 < red["busy_s"] <= red["window_s"]
    idle = 1.0 - red["busy_s"] / red["window_s"]
    assert idle == pytest.approx(1.0 - brute / red["window_s"], abs=2e-3)


def test_recorded_kernel_sums_and_self_times(recorded):
    pd, red = recorded
    ev = _device_events(pd)
    # the two histogram kernels enclose nothing, so their self time is their
    # own duration, summed straight off the events
    direct = sum(e - s for n, s, e in ev
                 if "histogram_pallas" in n) / 1e9
    assert red["kernel_s"]["hist"] == pytest.approx(direct, rel=1e-9)
    assert {n.split(".")[0] for n, _ in red["device_ops"][:2]} == {
        "fused_partition_histogram_pallas", "wave_histogram_pallas"}
    # self times add up to the busy union: no instant is charged twice
    assert sum(red["op_seconds"].values()) == pytest.approx(red["busy_s"],
                                                            rel=1e-6)
    # both kernels are in the slice (the root pass of the next iteration)
    assert red["kernel_s"]["hist"] / red["busy_s"] > 0.9


def test_recorded_gaps_name_the_host_span(recorded):
    _pd, red = recorded
    names = {n for n, _ in red["idle_gaps"]}
    assert names <= {"fence_wait", "update_issue", "update_issue+stop_check",
                     "nothing"}
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6, abs=1e-9)

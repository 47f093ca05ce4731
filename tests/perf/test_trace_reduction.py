"""The reduction from a profiler trace to busy time, kernel time and the
host's part in each idle gap: on a hand-made event list whose answers are
known, and on an excerpt of the first real trace."""

import json
import os

import pytest

from perf.harness.trace import (Event, TraceSummary, gaps_ns, short_name,
                                union_ns)

DEV = "/device:TPU:0"
MS = 1_000_000


def _ev(plane, name, start_ms, dur_ms, line="XLA Ops"):
    return Event(plane, line, name, int(start_ms * MS), int(dur_ms * MS))


def _handmade():
    """A 100 ms window. The device runs [10,30) and [20,40) (overlapping:
    busy 30 ms), [60,70) (10 ms) and an operation [95,110) that the window
    cuts at 100 (5 ms): 45 ms busy, 55 idle. Gaps: [0,10) and [40,60) fall
    under the host's ``perf.dispatch`` spans, [70,95) under
    ``perf.wait``."""
    return [
        _ev("/host:CPU", "perf.window", 0, 100, line="python3"),
        _ev("/host:CPU", "perf.dispatch", 0, 12, line="python3"),
        _ev("/host:CPU", "perf.dispatch", 38, 22, line="python3"),
        _ev("/host:CPU", "perf.wait", 70, 30, line="python3"),
        _ev(DEV, "%fusion.1 = bf16[8,8]{1,0} fusion(...)", 10, 20),
        _ev(DEV, "%_ln_fwd_impl.3 = (bf16[8,8]{1,0}) custom-call(...)", 20, 20),
        _ev(DEV, "%fusion.1 = bf16[8,8]{1,0} fusion(...)", 60, 10),
        _ev(DEV, "%_ln_fwd_impl.4 = (bf16[8,8]{1,0}) custom-call(...)", 95, 15),
        # a second chip, busy all the time, and a line that is no operation
        _ev("/device:TPU:1", "%fusion.9 = f32[2]{0} fusion()", 0, 100),
    ]


@pytest.mark.parametrize("intervals,total", [
    ([], 0), ([(0, 10)], 10), ([(0, 10), (5, 20)], 20),
    ([(0, 10), (10, 20)], 20), ([(0, 10), (30, 40), (2, 4)], 20),
])
def test_union_of_overlapping_intervals(intervals, total):
    assert union_ns(intervals) == total


def test_gaps_are_what_no_interval_covers():
    assert gaps_ns([(10, 30), (20, 40), (60, 70)], 0, 100) == \
        [(0, 10), (40, 60), (70, 100)]
    assert gaps_ns([], 5, 9) == [(5, 9)]
    assert gaps_ns([(0, 100)], 10, 20) == []


def test_busy_idle_and_window_on_one_chip():
    t = TraceSummary(_handmade(), chips=1)
    assert t.devices == [0]
    assert t.window_s == pytest.approx(0.100)
    assert t.busy_s == pytest.approx(0.045)
    assert t.idle_share == pytest.approx(0.55)


def test_busy_is_averaged_over_the_chips_used():
    t = TraceSummary(_handmade(), chips=4)
    assert t.devices == [0, 1]
    assert t.busy_s == pytest.approx((0.045 + 0.100) / 2)


def test_kernel_time_by_name_pattern():
    t = TraceSummary(_handmade(), chips=1)
    seconds, calls = t.op_seconds(r"^%_ln_fwd_impl(\.\d+)? = ")
    assert calls == 2
    assert seconds == pytest.approx(0.020 + 0.005)  # the second is cut
    assert t.op_seconds(r"no_such_kernel") == (0.0, 0)
    assert t.top_ops(1) == [["%fusion.1 = bf16[8,8]", pytest.approx(0.030)]]


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    t = TraceSummary(_handmade(), chips=1)
    gaps = dict(map(tuple, t.idle_gaps()))
    assert gaps == {"perf.dispatch": pytest.approx(0.030),
                    "perf.wait": pytest.approx(0.025)}
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s)


def test_a_gap_no_span_covers_is_named_so():
    events = [e for e in _handmade() if e.name != "perf.wait"]
    gaps = dict(map(tuple, TraceSummary(events, chips=1).idle_gaps()))
    assert gaps["host:no_benchmark_span"] == pytest.approx(0.025)


def test_short_name_keeps_the_operation_and_its_shape():
    assert short_name("%fusion.21 = bf16[30522,768]{1,0:T(8,128)} fusion("
                      "bf16[8192,768]{1,0} %x)") == "%fusion.21 = bf16[30522,768]"
    assert short_name("%while.5 = (s32[]{:T(128)}, s32[64]) while()") == \
        "%while.5 ="


def test_excerpt_of_the_first_real_trace():
    """600 device operations of one BERT-base step as the chip's profiler
    named them: the reduction finds the fused LayerNorm kernels by their
    names, and a training step leaves the chip all but never idle."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "bert_step_excerpt.json")
    with open(path) as f:
        events = [Event(*e) for e in json.load(f)["events"]]
    t = TraceSummary(events, chips=1)
    ops = [e for e in events if e.plane == DEV]
    assert len(ops) == 600
    assert t.window_s == pytest.approx(0.01160536)
    assert 0 < t.busy_s <= t.window_s
    # answers taken by a slow, obvious method: a timeline of nanoseconds
    lo = min(e.start_ns for e in ops)
    covered = bytearray(t.hi - lo)
    for e in ops:
        covered[e.start_ns - lo:e.start_ns + e.dur_ns - lo] = \
            b"\x01" * e.dur_ns
    assert t.busy_s == pytest.approx(sum(covered) / 1e9, rel=1e-9)
    assert t.idle_share < 0.01
    seconds, calls = t.op_seconds(r"^%_ln_(fwd|bwd)_impl(\.\d+)? = ")
    assert calls == 19
    assert seconds == pytest.approx(
        sum(e.dur_ns for e in ops if e.name.startswith("%_ln_")) / 1e9)
    assert sum(s for _, s in t.idle_gaps()) == \
        pytest.approx(t.window_s - t.busy_s)

"""The per-layer metric PR 37 added for the sparse attention's indexer
kernel: ``dsa_select_time_share`` on a hand-made event list (the share with
the kernel's events, nothing and no error without them: the parent
commit's program), and the manifest as it was before it."""

import json
import os
import types

import pytest

from perf.harness.loader import Benchmark
from perf.harness.trace import Event, TraceSummary

from _runs import REPO

CELL = "keye-vl2-30b-a3b.longctx-closed"
NAME = "dsa_select_time_share"
MS = 1_000_000
DEV = "/device:TPU:0"
# names as the chip's compiler writes them (PR 37's chunk program, and the
# parent's: the radix select's loop and its body's fusion)
SELECT = ("%dsa_index_select.3 = s8[1,2048,16640]{2,1,0:T(8,128)(4,1)S(1)} "
          "custom-call(s32[1]{0:T(128)S(6)}")
WINDOW = "%dsa_selected_window.11 = bf16[1,8,4,2048,128]{4,3,2,1,0:T(8,128)"
LOOP = "%while.126 = (s32[]{:T(128)}, u32[1,2048,1]{2,1,0:T(8,128)}, u32[1,"
BODY = "%convert_reduce_fusion.42 = s32[1,2048]{1,0:T(8,128)} fusion(u32[1,"


@pytest.fixture(scope="module")
def bench():
    return Benchmark(REPO)


def _ev(name, start_ms, dur_ms):
    return Event(DEV, "XLA Ops", name, int(start_ms * MS), int(dur_ms * MS))


def _run(events):
    trace = None if events is None else TraceSummary(events, chips=1)
    return types.SimpleNamespace(obs={}, window_s=2.0, e2e={}, trace=trace)


def test_the_share_of_busy_time_in_the_kernels_events(bench):
    read = bench.layer_metric(NAME).read
    # six calls of 1.5 ms and one unnamed, 100 ms busy of a 120 ms stretch
    events = [_ev(SELECT.replace(".3 ", f".{n} "), 10 * n, 1.5)
              for n in range(1, 6)]
    events += [_ev("%dsa_index_select = s8[1,2048,16640]", 60, 1.5),
               _ev(WINDOW, 70, 21), _ev(LOOP, 91, 30), _ev(BODY, 91, 29.9),
               _ev("%fusion.7 = bf16[2048,2048]", 121, 40)]
    run = _run(events)
    assert run.trace.busy_s_of(0) == pytest.approx(0.100)
    assert read(run) == pytest.approx(100 * 9.0 / 100.0)
    # the window's kernel is another metric's, and is not counted here
    assert bench.layer_metric("dsa_time_share").read(run) == \
        pytest.approx(21.0)


@pytest.mark.parametrize("events", [
    None, [], [_ev(WINDOW, 0, 12), _ev(LOOP, 12, 30), _ev(BODY, 12, 29.9)]],
    ids=["untraced", "no-device-events", "the-parents-program"])
def test_without_the_kernels_events_nothing_is_read(bench, events):
    assert bench.layer_metric(NAME).read(_run(events)) is None


def test_the_manifest_before_this_metric_is_still_there(bench):
    """Every entry the manifest held before PR 37 is there, in place, in
    order, with the content it had; the one new metric follows them and
    lists keye's cell. Nothing is said of what comes after it."""
    with open(os.path.join(REPO, "tests", "perf", "data",
                           "manifest_before_index_select.json")) as f:
        before = json.load(f)
    now = bench.manifest
    for key in ("command", "paths", "run_seconds"):
        assert now[key] == before[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert now[group][:len(before[group])] == before[group], group
    for group in ("configs", "workloads", "end_to_end"):
        assert len(now[group]) >= len(before[group])
    assert now["per_layer"][len(before["per_layer"])] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert NAME in {n for n, _ in bench.per_layer(bench.cell(CELL))}
    assert "serve_tokens_per_s" in {
        m["name"] for m in bench.end_to_end(bench.cell(CELL))}
    for cell in bench.manifest["workloads"]:
        if cell["name"] != CELL:
            assert NAME not in {
                n for n, _ in bench.per_layer(bench.cell(cell["name"]))}

"""The per-layer metric PR 39 added for the sparse attention's two
decode-step kernels: ``dsa_decode_time_share`` on a hand-made event list
(the share with the kernels' events, nothing and no error without them:
the parent commit's program), and its own entry of the manifest."""

import types

import pytest

from perf.harness.loader import Benchmark
from perf.harness.trace import Event, TraceSummary

from _runs import REPO

CELL = "keye-vl2-30b-a3b.longctx-closed"
NAME = "dsa_decode_time_share"
NEW = (NAME,)
MS = 1_000_000
DEV = "/device:TPU:0"
# names as the chip's compiler writes them: the decode burst's loop with
# this PR's two Mosaic calls inside it, and the chunk program's kernels
BURST = "%while.327 = (s32[]{:T(128)}, s32[16]{0:T(128)S(1)}, pred[16]{0:T(5"
SELECT = ("%dsa_decode_select.90 = (s8[16,136,128]{2,1,0:T(8,128)(4,1)S(1)}, "
          "bf16[2081,64,128]{2,1,0:T(8,128)(2,1)}) custom-call(s32[16,130]")
WINDOW = ("%dsa_decode_window.93 = bf16[16,32,128]{2,1,0:T(8,128)(2,1)S(1)} "
          "custom-call(s32[16,130]{1,0:T(8,128)S(6)}")
CHUNK_WINDOW = "%dsa_selected_window.11 = bf16[1,8,4,2048,128]{4,3,2,1,0:T(8"
CHUNK_SELECT = "%dsa_index_select.3 = s8[1,2048,16640]{2,1,0:T(8,128)(4,1)S("
SORT = "%sort.12 = (f32[16,16640]{1,0:T(8,128)}, s32[16,16640]{1,0:T(8,128)"


@pytest.fixture(scope="module")
def bench():
    return Benchmark(REPO)


def _ev(name, start_ms, dur_ms):
    return Event(DEV, "XLA Ops", name, int(start_ms * MS), int(dur_ms * MS))


def _run(events):
    trace = None if events is None else TraceSummary(events, chips=1)
    return types.SimpleNamespace(obs={}, window_s=2.0, e2e={}, trace=trace)


def test_the_share_of_busy_time_in_the_two_kernels_events(bench):
    read = bench.layer_metric(NAME).read
    # a burst of 24 ms with two steps of six layers inside it: twelve calls
    # of each kernel, 0.1 and 0.4 ms (one of each unnumbered); then a chunk
    # of 56 ms: 80 ms busy of a 100 ms stretch
    events = [_ev(BURST, 0, 24)]
    for n in range(12):
        at = 2.0 * n
        events += [
            _ev(SELECT.replace(".90 ", f".{90 + n % 6} ") if n else
                "%dsa_decode_select = (s8[16,136,128]", at, 0.1),
            _ev(WINDOW.replace(".93 ", f".{90 + n % 6} ") if n else
                "%dsa_decode_window = bf16[16,32,128]", at + 0.1, 0.4)]
    events += [_ev(CHUNK_SELECT, 44, 0.65), _ev(CHUNK_WINDOW, 45, 1.87),
               _ev("%fusion.7 = bf16[2048,2048]", 44, 56)]
    run = _run(events)
    assert run.trace.busy_s_of(0) == pytest.approx(0.080)
    assert read(run) == pytest.approx(100 * 12 * 0.5 / 80.0)
    # the chunk's kernels are other metrics', and do not meet these events
    assert bench.layer_metric("dsa_time_share").read(run) == \
        pytest.approx(100 * 1.87 / 80.0)
    assert bench.layer_metric("dsa_select_time_share").read(run) == \
        pytest.approx(100 * 0.65 / 80.0)


@pytest.mark.parametrize("events", [
    None, [],
    [_ev(BURST, 0, 32), _ev(SORT, 1, 0.4), _ev(CHUNK_SELECT, 44, 0.65),
     _ev(CHUNK_WINDOW, 45, 1.87)]],
    ids=["untraced", "no-device-events", "the-parents-program"])
def test_without_the_kernels_events_nothing_is_read(bench, events):
    assert bench.layer_metric(NAME).read(_run(events)) is None


def test_the_manifest_lists_the_metric_for_keyes_cell(bench):
    """What this PR says of ITS OWN entry, and nothing of the others (a
    later entry must not turn this test red: ``PERF.md`` 7 (p))."""
    entry, = [m for m in bench.manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "tpot_p95_ms", "workloads": [CELL]}
    got = {n for n, _ in bench.per_layer(bench.cell(CELL))}
    assert set(NEW) <= got
    assert "tpot_p95_ms" in {
        m["name"] for m in bench.end_to_end(bench.cell(CELL))}
    for cell in bench.manifest["workloads"]:
        if cell["name"] != CELL:
            assert NAME not in {
                n for n, _ in bench.per_layer(bench.cell(cell["name"]))}

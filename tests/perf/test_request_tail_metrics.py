"""The six per-layer metrics PR 36 added: 95th percentiles, over a window,
of the parts of a first token's latency and of the scheduler's passes, read
from the window histograms the program keeps in ``ContinuousBatcher.stats``
(``h_*``). Each reader on hand-made ``stats0`` / ``stats1``; nothing on a
program without the keys; a traced rehearsal of each serving cell lists the
six and the training cell none; and the manifest before them is in place."""

import json
import os
import types

import pytest

from mxnet_tpu.telemetry import metrics
from perf.harness.clock import percentile
from perf.harness.loader import Benchmark

from _runs import REPO, child

SERVING = ["transformer-big.translate-closed",
           "keye-vl2-30b-a3b.longctx-closed",
           "granite-4.0-h-micro.chat-closed",
           "joyai-llm-flash.longgen-closed"]
TRAINING = "bert-base.pretrain-s128"
# metric -> (the histogram it reads, its layer, what it moves)
NEW = {
    "queue_wait_p95_ms": ("h_queue_ms", "scheduler", "ttft_p95_ms"),
    "seat_wait_p95_ms": ("h_seat_ms", "scheduler", "ttft_p95_ms"),
    "prefill_service_p95_ms": ("h_service_ms", "engine, serving",
                               "ttft_p95_ms"),
    "first_token_deliver_p95_ms": ("h_deliver_ms", "scheduler",
                                   "ttft_p95_ms"),
    "pass_wall_p95_ms": ("h_pass_ms", "engine, serving", "tpot_p95_ms"),
    "decode_wait_p95_ms": ("h_burst_ms", "engine, serving", "tpot_p95_ms"),
}
# what the ramp left in every histogram, and what each window observed
BEFORE = [3.0, 70000.0, 0.5]
WINDOW = {
    "h_queue_ms": [41.0 + 0.37 * i for i in range(90)],
    "h_seat_ms": [0.0] * 40,                          # the cold path
    "h_service_ms": [13.0, 14.5, 12.25, 90.0, 13.7],
    "h_deliver_ms": [0.021 * (i + 1) for i in range(300)],
    "h_pass_ms": [45.1] * 50 + [88.0] * 5,
    "h_burst_ms": [18.7],                             # one observation
}


@pytest.fixture(scope="module")
def bench():
    return Benchmark(REPO)


def _hist(values):
    block = metrics.BucketBlock(["k"])
    for ms in values:
        block.observe(("k", ms))
    return block.flush()["k"]


def _run(stats0, stats1):
    return types.SimpleNamespace(obs={"stats0": stats0, "stats1": stats1},
                                 window_s=50.0, e2e={}, trace=None)


@pytest.fixture(scope="module")
def stats():
    stats0 = {k: _hist(BEFORE) for k in WINDOW}
    stats1 = {k: stats0[k] + _hist(v) for k, v in WINDOW.items()}
    return dict(stats0, iterations=10), dict(stats1, iterations=65)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_hand_made_histograms(bench, stats, name):
    key, layer, moves = NEW[name]
    reader = bench.layer_metric(name)
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == \
        (name, "ms", layer, moves)
    stats0, stats1 = stats
    got = reader.read(_run(stats0, stats1))
    want = percentile(WINDOW[key], 95)
    # the window's own observations, not the ramp's 70 s
    assert got == pytest.approx(want, rel=0.015, abs=1e-9), (got, want)
    # a program without the key (the parent commit): nothing, no error
    for short in (0, 1):
        pair = [dict(stats0), dict(stats1)]
        del pair[short][key]
        assert reader.read(_run(*pair)) is None
    assert reader.read(types.SimpleNamespace(obs={}, e2e={})) is None
    # a window that observed nothing
    assert reader.read(_run(stats0, stats0)) is None


def test_known_answers(bench, stats):
    read = {n: bench.layer_metric(n).read(_run(*stats)) for n in NEW}
    assert read["seat_wait_p95_ms"] == 0.0            # every wait was 0
    assert read["decode_wait_p95_ms"] == pytest.approx(18.7, abs=1e-6)
    assert read["pass_wall_p95_ms"] == pytest.approx(88.0, rel=0.015)
    assert 45.1 < read["pass_wall_p95_ms"]            # the tail, not the mean
    assert read["prefill_service_p95_ms"] == pytest.approx(
        0.8 * 90.0 + 0.2 * 14.5, rel=0.015)           # between two ranks


def test_the_manifest_before_these_metrics_is_still_there(bench):
    """Every entry the manifest held before PR 36 is there, in place, in
    order, with the content it had, and the six new metrics list the four
    serving cells. Nothing is said of what comes after them."""
    with open(os.path.join(REPO, "tests", "perf", "data",
                           "manifest_before_request_tails.json")) as f:
        before = json.load(f)
    now = bench.manifest
    for key in ("command", "paths", "run_seconds"):
        assert now[key] == before[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert now[group][:len(before[group])] == before[group], group
    listed = {m["name"]: m for m in now["per_layer"]}
    for name, (_, layer, moves) in NEW.items():
        assert listed[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_counter", "layer": layer, "moves": moves,
            "workloads": SERVING}
    names = [m["name"] for m in now["per_layer"]]
    at = len(before["per_layer"])
    assert names[at:at + len(NEW)] == list(NEW)
    # the cells report what the six are said to move
    for cell in SERVING:
        got = {n for n, _ in bench.per_layer(bench.cell(cell))}
        assert set(NEW) <= got
        assert {"ttft_p95_ms", "tpot_p95_ms"} <= {
            m["name"] for m in bench.end_to_end(bench.cell(cell))}
    assert not set(NEW) & {
        n for n, _ in bench.per_layer(bench.cell(TRAINING))}


@pytest.mark.parametrize("cell", SERVING + [TRAINING])
def test_a_traced_rehearsal_lists_the_six_in_a_serving_cell(cell):
    proc = child("--workload", cell, "--seed", str(2**31 + 36), "--seconds",
                 "2", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    got = set(last["metrics_reported"])
    if cell == TRAINING:
        assert not got & set(NEW)
        return
    assert set(NEW) <= got
    # the older readers of the same copies of ``stats`` are not disturbed
    assert {"queue_wait_p50_ms", "iter_wall_ms", "decode_wait_ms",
            "sched_iter_busy_ms", "batch_occupancy"} <= got

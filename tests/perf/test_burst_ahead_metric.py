"""The per-layer metric PR 42 added for the scheduler's burst dispatched
ahead: ``burst_ahead_share`` on hand-made ``stats`` copies (the window's
``bursts_ahead`` over its ``iterations``; nothing, and no error, where the
program keeps no such count: the parent commit's), and the six serving
cells it is listed for."""

import types

import pytest

from perf.harness.loader import Benchmark

from _runs import REPO

NAME = "burst_ahead_share"
SERVING = ["transformer-big.translate-closed",
           "keye-vl2-30b-a3b.longctx-closed",
           "granite-4.0-h-micro.chat-closed",
           "joyai-llm-flash.longgen-closed",
           "ouro-2.6b.reason-closed",
           "zaya1-8b.think-closed"]


@pytest.fixture(scope="module")
def bench():
    return Benchmark(REPO)


def _run(stats0, stats1):
    obs = {} if stats0 is None else {"stats0": stats0, "stats1": stats1}
    return types.SimpleNamespace(obs=obs, window_s=50.0, e2e={}, trace=None)


@pytest.mark.parametrize("ahead0,ahead1,iters0,iters1,want", [
    (0, 0, 100, 1300, 0.0),             # a slot always free or entering
    (40, 961, 100, 1300, 76.75),        # two host turns in the open a reply
    (7, 1207, 0, 1200, 100.0),          # every burst of the window
    (120, 120, 300, 700, 0.0),          # ahead in the ramp, not the window
])
def test_the_windows_bursts_ahead_over_its_iterations(bench, ahead0, ahead1,
                                                      iters0, iters1, want):
    read = bench.layer_metric(NAME).read
    run = _run({"iterations": iters0, "bursts_ahead": ahead0, "tokens": 1},
               {"iterations": iters1, "bursts_ahead": ahead1, "tokens": 9})
    assert read(run) == pytest.approx(want)


@pytest.mark.parametrize("stats0,stats1", [
    (None, None),
    ({}, {}),
    # the parent's program: a scheduler that counts no such thing
    ({"iterations": 100, "occupancy_sum": 99.0},
     {"iterations": 1300, "occupancy_sum": 1290.0}),
    # no iteration in the window: no share of them
    ({"iterations": 100, "bursts_ahead": 3},
     {"iterations": 100, "bursts_ahead": 3}),
], ids=["no-stats", "empty", "the-parents-program", "no-iteration"])
def test_without_the_count_nothing_is_read(bench, stats0, stats1):
    assert bench.layer_metric(NAME).read(_run(stats0, stats1)) is None


def test_a_count_that_began_inside_the_window_counts_from_zero(bench):
    """``stats0`` taken from a program before the count existed in it (a
    reader must not raise on it)."""
    read = bench.layer_metric(NAME).read
    run = _run({"iterations": 0}, {"iterations": 10, "bursts_ahead": 5})
    assert read(run) == pytest.approx(50.0)


@pytest.mark.parametrize("cell", SERVING)
def test_the_metric_is_listed_for_the_serving_cell(bench, cell):
    """Its entry says what its reader says, lists the six serving cells
    (and whatever a later PR appends), moves the metric every one of them
    reports, and is read in a traced run of each; the training cell does
    not read it."""
    entry, = [m for m in bench.manifest["per_layer"] if m["name"] == NAME]
    reader = bench.layer_metric(NAME)
    assert (entry["unit"], entry["layer"], entry["moves"]) == \
        (reader.UNIT, reader.LAYER, reader.MOVES) == \
        ("%", "scheduler", "serve_tokens_per_s")
    assert entry["better"] == "higher"
    assert entry["source"] == "program_counter"
    assert set(SERVING) <= set(entry["workloads"])
    listed = {w["name"]: w for w in bench.manifest["workloads"]}
    assert cell in listed
    found = dict(bench.per_layer(bench.cell(cell)))
    assert found[NAME] == "%"
    assert any(m["name"] == entry["moves"]
               for m in bench.end_to_end(bench.cell(cell)))
    assert NAME not in dict(bench.per_layer(
        bench.cell("bert-base.pretrain-s128")))

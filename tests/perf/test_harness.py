"""The harness: files found by name with no edit, names and units held to
the contract, clock arithmetic, rehearsals that end in one well-formed line,
and runs that must come out as not correct or not run at all."""

import json
import os
import shutil
import statistics

import pytest

from perf.harness import clock
from perf.harness import traffic as gen
from perf.harness.loader import Benchmark, BenchmarkError, check_name, \
    check_unit

from _runs import REPO, child, in_process, lines

CELLS = [w["name"] for w in
         json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]]
FOUR_CHIP_CELL = os.path.join(REPO, "tests", "perf", "cells",
                              "bert-base.pretrain-s128-dp4.json")


# ------------------------------------------------------------- the loader
@pytest.fixture
def copy(tmp_path):
    """The benchmark's own files in a directory of their own."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "perf"), tmp_path / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_new_files_are_found_with_no_edit(copy):
    """What a later PR does: a configuration, a mix, a cell and a per-layer
    metric as files of their own and entries in ``BENCHMARK.json``."""
    perf = copy / "perf"
    (perf / "configs" / "new-model.json").write_text(json.dumps(
        {"driver": "train", "hidden_size": 8}))
    (perf / "traffic" / "new-mix.json").write_text(json.dumps(
        {"kind": "train_stream", "seq_len": 4}))
    # what the manifest has no key for (mesh, sharding) is the cell's own file
    (perf / "cells").mkdir()
    (perf / "cells" / "new-model.new-mix.json").write_text(json.dumps(
        {"chips": 4, "mesh": {"data": 4}, "sharding": "fsdp"}))
    (perf / "layer_metrics" / "new_metric.py").write_text(
        'NAME = "new_metric"\nUNIT = "ms"\nLAYER = "engine, training"\n'
        'MOVES = "train_tokens_per_s"\n\n\ndef read(run):\n    return 1.5\n')
    m = json.loads((copy / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "new-model", "source": "a paper",
                         "file": "perf/configs/new-model.json",
                         "reduced": [], "why": "x"})
    m["workloads"].append({"name": "new-model.new-mix", "config": "new-model",
                           "traffic": "new-mix", "chips": 4, "why": "x"})
    m["end_to_end"][0]["workloads"].append("new-model.new-mix")
    m["per_layer"].append({"name": "new_metric", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "engine, training",
                           "moves": "train_tokens_per_s",
                           "workloads": ["new-model.new-mix"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(m))

    b = Benchmark(str(copy))
    cell = b.cell("new-model.new-mix")
    assert (cell["chips"], cell["mesh"], cell["sharding"]) == \
        (4, {"data": 4}, "fsdp")
    assert b.config(cell["config"])["hidden_size"] == 8
    assert b.traffic(cell["traffic"])["seq_len"] == 4
    assert b.driver("train").run
    assert b.layer_metric("new_metric").read(None) == 1.5
    assert ("new_metric", "ms") in b.per_layer(cell)
    assert [m["name"] for m in b.end_to_end(cell)] == \
        ["train_tokens_per_s", "setup_s"]
    # and the cells that were there read what they read before
    old = b.cell(CELLS[0])
    assert ("new_metric", "ms") not in b.per_layer(old)


def test_every_listed_cell_resolves_to_its_files():
    b = Benchmark(REPO)
    for name in CELLS:
        cell = b.cell(name)
        cfg = b.config(cell["config"])
        assert b.traffic(cell["traffic"])["kind"]
        assert b.driver(cfg["driver"]).run
        assert b.reference(cell["config"]).init_params
        assert b.end_to_end(cell) and b.per_layer(cell)
        for metric, unit in b.per_layer(cell):
            reader = b.layer_metric(metric)
            assert (reader.NAME, reader.UNIT) == (metric, unit)
            entry = [m for m in b.manifest["per_layer"]
                     if m["name"] == metric][0]
            assert (reader.LAYER, reader.MOVES) == \
                (entry["layer"], entry["moves"])


def test_a_cell_file_may_not_contradict_the_manifest(copy):
    (copy / "perf" / "cells").mkdir()
    (copy / "perf" / "cells" / (CELLS[0] + ".json")).write_text(
        json.dumps({"chips": 4}))
    with pytest.raises(BenchmarkError, match="chips"):
        Benchmark(str(copy)).cell(CELLS[0])


@pytest.mark.parametrize("name", [
    "has space", "comma,name", "slash/name", "", "µs", "x" * 65, "-lead",
    ".lead", None])
def test_a_name_outside_the_allowed_set_is_refused(name):
    with pytest.raises(BenchmarkError):
        check_name(name)


@pytest.mark.parametrize("name", ["bert-base.pretrain-s128", "a", "_x",
                                  "9lives", "x" * 64, "device_idle.train"])
def test_a_name_inside_the_allowed_set_passes(name):
    assert check_name(name) == name


@pytest.mark.parametrize("unit", ["tokens per s", "µs", "", "x" * 17,
                                  "ms,", None])
def test_a_unit_outside_the_allowed_set_is_refused(unit):
    with pytest.raises(BenchmarkError):
        check_unit(unit)


@pytest.mark.parametrize("unit", ["tokens/s", "%", "ms", "s", "GB/s"])
def test_a_unit_inside_the_allowed_set_passes(unit):
    assert check_unit(unit) == unit


def test_a_manifest_with_a_bad_name_is_refused(copy):
    m = json.loads((copy / "BENCHMARK.json").read_text())
    m["per_layer"][0]["name"] = "two words"
    (copy / "BENCHMARK.json").write_text(json.dumps(m))
    with pytest.raises(BenchmarkError, match="two words"):
        Benchmark(str(copy))


# ------------------------------------------------------- clock arithmetic
@pytest.mark.parametrize("values,p,want", [
    ([5.0], 95, 5.0), ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05), ([4, 1, 3, 2], 0, 1.0),
    ([4, 1, 3, 2], 100, 4.0)])
def test_percentile_on_hand_made_samples(values, p, want):
    assert clock.percentile(values, p) == pytest.approx(want)
    assert clock.percentile([], p) is None


def test_spread_is_the_quartile_distance_over_the_median():
    values = [100, 101, 102, 103, 104, 105]
    q = statistics.quantiles(values, n=4)
    assert clock.spread(values) == pytest.approx((q[2] - q[0]) / 102.5)


def test_open_loop_latency_counts_from_the_due_instant():
    due = clock.due_times([0.5, 0.5, 1.0], start=10.0)
    assert due == [10.5, 11.0, 12.0]
    # the generator stalled: the second request left 0.7 s late, and a
    # first token at 12.0 is 1.0 s after it was due, not 0.3 s after it left
    sent = [10.5, 11.7, 12.0]
    assert sent[1] - due[1] == pytest.approx(0.7)
    assert 12.0 - due[1] == pytest.approx(1.0)


def test_per_token_gap():
    assert clock.per_token_gap(1.0, 2.0, 5) == pytest.approx(0.25)
    assert clock.per_token_gap(1.0, 1.0, 1) is None


# ------------------------------------------------------------------ memory
class _Chip:
    def __init__(self, *samples):
        self.samples = list(samples)

    def memory_stats(self):
        return self.samples.pop(0) if len(self.samples) > 1 \
            else self.samples[0]


class _Jax:
    def __init__(self, *chips):
        self.chips = chips

    def devices(self):
        return list(self.chips)


@pytest.mark.parametrize("samples,allocator_peak,want", [
    # arrays and the programs' reservation are added within one sample, and
    # the largest sample stands, not the sum of two instants' largest
    ([(30, 5), (10, 20)], 31, 35),
    # set-up held more arrays than any sample saw: the allocator's peak
    ([(30, 5), (10, 20)], 50, 50),
    # a backend that keeps no counters (the CPU): nothing to report
    ([None], 0, 0)])
def test_memory_peak_adds_only_within_one_sample(samples, allocator_peak,
                                                 want):
    from perf.harness.main import Memory

    def stats(s):
        return None if s is None else {
            "bytes_in_use": s[0], "bytes_reserved": s[1],
            "peak_bytes_in_use": allocator_peak, "pool": "x"}

    said = []
    chip = _Chip(*[stats(s) for s in samples], stats(samples[-1]))
    idle = _Chip(stats(None if samples[0] is None else (1, 1)))
    mem = Memory(_Jax(chip, idle), 2, lambda note, **kw: said.append(kw))
    for i, _ in enumerate(samples):
        mem.sample(f"instant {i}")
    assert mem.peak() == want
    assert [kw["at"] for kw in said] == [f"instant {i}"
                                         for i in range(len(samples))]
    if samples[0] is not None:     # each counter under its own name
        assert said[0]["bytes_in_use"] == 30 and said[0]["held_bytes"] == 35
        assert "pool" not in said[0]


# ---------------------------------------------------------------- traffic
MIX = {"kind": "closed_loop", "population": 200, "population_seed": 3,
       "source_length": {"median": 24, "sigma": 0.6, "min": 4, "max": 128},
       "output_length": {"ratio_mean": 1.05, "ratio_sd": 0.15, "min": 4,
                         "max": 128}}


def test_a_seed_changes_the_inputs_and_not_the_work():
    a = gen.RequestStream(MIX, 2**31 + 7, 32768)
    b = gen.RequestStream(MIX, 11, 32768)
    again = gen.RequestStream(MIX, 2**31 + 7, 32768)
    n = MIX["population"]
    sizes = lambda s: sorted((len(s.request(i)[0]), s.request(i)[1])  # noqa: E731
                             for i in range(n))
    assert sizes(a) == sizes(b)                       # the same set of sizes
    assert [len(a.request(i)[0]) for i in range(n)] != \
        [len(b.request(i)[0]) for i in range(n)]      # in another order
    assert all((a.request(i)[0] == again.request(i)[0]).all()
               for i in range(20))                    # same seed, same inputs
    assert [len(a.request(i)[0]) for i in range(n)] != \
        [len(a.request(n + i)[0]) for i in range(n)]  # each pass reshuffled
    toks = a.request(0)[0]
    assert toks.min() >= gen.SPECIAL_IDS and toks.max() < 32768


def test_every_row_of_a_training_pool_differs():
    pool = gen.train_pool({"per_chip_batch": 4,
                           "seq_len": 16, "pool_dispatches": 3}, 5, 512, 2)
    rows = [tuple(r) for ids, _ in pool for r in ids]
    assert len(pool) == 3 and pool[0][0].shape == (8, 16)
    assert len(set(rows)) == len(rows)


# -------------------------------------------------------------- whole runs
def _last_line_is_well_formed(proc, chips):
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is True
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["metrics_reported"]
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == chips
    return last


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_each_cell_ends_in_one_well_formed_line(cell):
    last = _last_line_is_well_formed(
        child("--workload", cell, "--seed", str(2**31 + 17), "--seconds", "2",
              "--trace", "0", "--rehearse"), chips=1)
    assert "setup_s" in last["metrics_reported"]
    assert "breakdown" not in last


def test_a_traced_rehearsal_reads_per_layer_metrics():
    last = _last_line_is_well_formed(
        child("--workload", CELLS[-1], "--seed", "4", "--seconds", "2",
              "--trace", "1", "--rehearse"), chips=1)
    assert "setup_s" not in last["metrics_reported"]
    assert {"busy_s", "window_s"} <= set(last["device"])


def test_rehearsal_of_the_four_chip_cell_file_needs_no_edit():
    """The first Open question of PERF.md is one data file: the harness
    takes it on four virtual devices as it stands."""
    last = _last_line_is_well_formed(
        child("--workload", FOUR_CHIP_CELL, "--seed", "3", "--seconds", "2",
              "--trace", "0", "--rehearse"), chips=4)
    assert "train_tokens_per_s" in last["metrics_reported"]


def test_a_run_off_the_chip_exits_nonzero_and_prints_no_result():
    proc = child("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0")
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "no accelerator" in proc.stderr


def test_the_benchmark_alone_in_a_directory_exits_nonzero(copy):
    proc = child("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0", "--rehearse", root=str(copy))
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


# ----------------------------- the timed path broken underneath the harness
def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    """The look for a chip is skipped (``--rehearse``), the rest of a run is
    driven, and the program's step does nothing to its state."""
    from mxnet_tpu.parallel import TrainStep

    real_load, real_state = TrainStep.load_state_dict, TrainStep.state_dict
    frozen = {}

    def load(self, sd):
        real_load(self, sd)
        frozen[id(self)] = real_state(self)

    monkeypatch.setattr(TrainStep, "load_state_dict", load)
    monkeypatch.setattr(
        TrainStep, "state_dict",
        lambda self: frozen.get(id(self)) or real_state(self))
    code, out = in_process(capsys, "--workload", "bert-base.pretrain-s128",
                           "--seed", "5", "--seconds", "1", "--rehearse")
    assert code == 0 and out[-1]["correct"] is False
    outside = {r["number"] for r in out
               if r.get("note") == "compared" and not r["inside"]}
    assert "param_change_norm_worst_leaf_gap" in outside
    assert "first_grad_norm_worst_leaf_gap" in outside


def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from mxnet_tpu.serving.batcher import GenerationResult

    real = GenerationResult._resolve

    def resolve(self, tokens):
        real(self, [3 + (int(t) * 7 + 11) % 1000 for t in tokens])

    monkeypatch.setattr(GenerationResult, "_resolve", resolve)
    code, out = in_process(
        capsys, "--workload", "transformer-big.translate-closed",
        "--seed", "5", "--seconds", "1", "--rehearse")
    assert code == 0 and out[-1]["correct"] is False
    gap = [r for r in out if r.get("number") == "widest_logit_gap"][0]
    assert not gap["inside"] and gap["value"] > 10 * gap["limit"]

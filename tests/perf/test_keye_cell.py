"""The cells PR 27 added, rehearsed on the CPU: the decoder-only serving cell
agrees with its plain reference, its float8 control does not, a broken
timed path reads not correct, and the new per-layer readers give known
answers on hand-made counters and a hand-made event list."""

import json
import types

import numpy as np
import pytest

from perf.harness import lm_counts, traffic_lm
from perf.harness.loader import Benchmark
from perf.harness.trace import Event, TraceSummary

from _runs import REPO, child, in_process

CELL = "keye-vl2-30b-a3b.longctx-closed"
MS = 1_000_000
DEV = "/device:TPU:0"
NEW = ["decode_step_roofline_share", "moe_time_share", "moe_roofline_share",
       "dsa_time_share", "dsa_roofline_share", "expert_load_imbalance",
       "dsa_selected_share", "prefill_chunk_ms", "prompt_tokens_per_s"]


@pytest.fixture(scope="module")
def bench():
    return Benchmark(REPO)


def _compared(out, of=None):
    return {r["number"]: r for r in out
            if r.get("note") == "compared" and r.get("of") == of}


# -------------------------------------------------------------- whole runs
def test_the_system_agrees_with_its_reference(capsys):
    code, out = in_process(capsys, "--workload", CELL, "--seed", "21",
                           "--seconds", "1", "--rehearse")
    assert code == 0
    numbers = _compared(out)
    assert len(numbers) >= 5
    assert all(r["inside"] for r in numbers.values()), numbers
    assert out[-1]["correct"] is True and out[-1]["failed"] == 0
    assert {"serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"} \
        <= set(out[-1]["metrics_reported"])
    counts = [r for r in out if r.get("note") == "window_counts"][0]
    assert counts["prompt_chunks"] > 0 and counts["prompt_tokens"] > 0
    assert counts["prefill_keys_selected"] <= counts["prefill_keys_seen"]


@pytest.mark.parametrize("seed", [41, 2**31 + 43])
def test_the_float8_control_fails_the_check(capsys, seed):
    code, out = in_process(capsys, "--workload", CELL, "--seed", str(seed),
                           "--seconds", "1", "--rehearse", "--control")
    assert code == 0 and out[-1]["found_not_correct"] is True
    program = _compared(out)["widest_logit_gap"]
    control = _compared(out, of="control")["widest_logit_gap"]
    assert program["inside"] and not control["inside"]
    assert control["value"] > 3 * program["value"]
    assert program["positions"] == control["positions"] > 8


def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from mxnet_tpu.serving.batcher import GenerationResult

    real = GenerationResult._resolve

    def resolve(self, tokens):
        real(self, [3 + (int(t) * 7 + 11) % 100 for t in tokens])

    monkeypatch.setattr(GenerationResult, "_resolve", resolve)
    code, out = in_process(capsys, "--workload", CELL, "--seed", "5",
                           "--seconds", "1", "--rehearse")
    assert code == 0 and out[-1]["correct"] is False
    gap = [r for r in out if r.get("number") == "widest_logit_gap"][0]
    assert not gap["inside"] and gap["value"] > 3 * gap["limit"]


def test_a_traced_rehearsal_reads_the_new_counters():
    proc = child("--workload", CELL, "--seed", str(2**31 + 17), "--seconds",
                 "2", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    # the device metrics need a device's timeline; the counters do not
    assert {"expert_load_imbalance", "dsa_selected_share",
            "prefill_chunk_ms", "prompt_tokens_per_s"} \
        <= set(last["metrics_reported"])


# ------------------------------------------------------------ the manifest
def test_the_cell_is_listed_as_the_issue_names_it(bench):
    keye = bench.cell(CELL)
    assert (keye["config"], keye["traffic"], keye["chips"]) == \
        ("keye-vl2-30b-a3b", "longctx-closed", 1)
    assert {m["name"] for m in bench.end_to_end(keye)} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    # the new readers, and every accepted serving metric beside them: a
    # metric with no list of cells is every cell's that reports its moves
    got = {n for n, _ in bench.per_layer(keye)}
    assert set(NEW) <= got and len(got) == len(NEW) + 13
    assert "device_idle_share.train" not in got


def test_new_metrics_list_the_new_cell_and_old_entries_are_as_they_were(
        bench):
    for m in bench.manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            reader = bench.layer_metric(m["name"])
            assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) \
                == (m["name"], m["unit"], m["layer"], m["moves"])
        else:
            assert "workloads" not in m
    old = {n for n, _ in bench.per_layer(
        bench.cell("transformer-big.translate-closed"))}
    assert len(old) == 13 and not old & set(NEW)
    assert len(bench.per_layer(bench.cell("bert-base.pretrain-s128"))) == 5


def test_the_configuration_keeps_every_published_width(bench):
    cfg = bench.config("keye-vl2-30b-a3b")
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 6
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2048, 32, 4, 128)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["vocab_size"]) == \
        (128, 8, 768, 151936)
    assert cfg["sa_config"]["topk"] == 2048
    srv = cfg["serving"]
    assert srv["prefill_chunk"] % 512 == 0 and srv["prefill_chunk"] <= 2048
    assert srv["slots"] == 16 and srv["prefix_cache"] is False
    ref = bench.reference("keye-vl2-30b-a3b")
    specs = ref.tensor_specs(cfg)
    n = sum(int(np.prod(s)) for s in specs.values())
    assert 4.36e9 < n < 4.38e9               # 8.75 GB in bfloat16
    ops = bench.ops_counts("keye-vl2-30b-a3b")
    assert ops.expert_bytes(cfg) == 3 * 2048 * 768 * 2


# ---------------------------------------------------------------- traffic
def test_a_seed_changes_the_inputs_and_not_the_work(bench):
    mix = bench.traffic("longctx-closed")
    a = traffic_lm.RequestStream(mix, 2**31 + 7, 151936)
    b = traffic_lm.RequestStream(mix, 11, 151936)
    n = mix["population"]
    sizes = lambda s: sorted((len(s.request(i)[0]), s.request(i)[1])  # noqa: E731
                             for i in range(n))
    assert sizes(a) == sizes(b)
    assert not (a.request(5)[0] == b.request(5)[0]).all()
    lengths = [len(a.request(i)[0]) for i in range(n)]
    assert min(lengths) >= 3072 and max(lengths) <= 16384
    assert min(lengths) > 2048               # always past topk
    assert all(32 <= a.request(i)[1] <= 256 for i in range(n))
    assert (a.request(5)[0] == traffic_lm.RequestStream(
        mix, 2**31 + 7, 151936).request(5)[0]).all()


def test_every_seed_offers_the_lengths_in_the_same_order(bench):
    """A window finishes about one pass of the 64 pairs, so an order of the
    run's own would change the work in it (the driver's check of PR 27
    refused the cell for that): the order is the mix's, new each pass."""
    mix = bench.traffic("longctx-closed")
    a = traffic_lm.RequestStream(mix, 2**31 + 7, 151936)
    b = traffic_lm.RequestStream(mix, 11, 151936)
    n = mix["population"]
    shape = lambda s, at: [(len(s.request(i)[0]), s.request(i)[1])  # noqa: E731
                           for i in range(at, at + n)]
    assert shape(a, 0) == shape(b, 0) and shape(a, n) == shape(b, n)
    assert shape(a, 0) != shape(a, n)            # a new order each pass
    assert sorted(shape(a, 0)) == sorted(shape(a, n))
    assert shape(a, 0) != sorted(shape(a, 0))
    other = traffic_lm.RequestStream(dict(mix, population_seed=1), 11, 151936)
    assert shape(other, 0) != shape(a, 0)


# ------------------------------------------ readers on hand-made readings
CFG = {"name": "keye-vl2-30b-a3b", "num_hidden_layers": 2}
E = 4


def _stats(scale):
    tokens = np.array([4, 0, 2, 2, 1, 1, 5, 1], np.int64) * scale  # 2 x E
    return {"iterations": 10 * scale,
            "prefill_expert_tokens": tokens * 100,
            "prefill_experts_touched": 6 * scale,
            "prefill_expert_layers": 2 * scale,
            "prefill_keys_seen": 4000 * scale,
            "prefill_keys_selected": 1000 * scale,
            "decode_expert_tokens": tokens,
            "decode_experts_touched": 12 * scale,
            "decode_expert_layers": 8 * scale,
            "decode_keys_seen": 1000 * scale,
            "decode_keys_selected": 250 * scale,
            "prompt_chunks": 5 * scale, "prompt_tokens": 900 * scale,
            "prefill_chunk_s": 0.75 * scale}


def _ev(name, start_ms, dur_ms):
    return Event(DEV, "XLA Ops", name, int(start_ms * MS), int(dur_ms * MS))


# names as the chip's compiler writes them (PR 27's programs)
BURST = ("%while.326 = (s32[]{:T(128)}, s32[16]{0:T(128)S(1)}, s32[772]{0:T("
         "1024)}, bf16[2081,128,64]{2,1,0:T(8,128)(2,1)S(1)}")
CHUNK_LOOP = "%while.42 = (s32[]{:T(128)}, f32[1,2048,16640]{2,1,0:T(8,128)}"
GROUPING = "%while.54 = (s32[]{:T(128)}, s32[255]{0:T(256)S(1)}, s32[255]{0"
KERNEL = "%moe_grouped_swiglu.6 = bf16[32640,2048]{1,0:T(8,128)(2,1)} custo"
WINDOW = "%dsa_selected_window.11 = bf16[1,8,4,2048,128]{4,3,2,1,0:T(8,128)"


def _run(bench, stats1=None, events=None):
    cfg = dict(bench.config("keye-vl2-30b-a3b"), **CFG)
    trace = None if events is None else TraceSummary(events, chips=1)
    ctx = types.SimpleNamespace(
        bench=bench, peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    return types.SimpleNamespace(
        obs={"stats0": _stats(1), "stats1": stats1 or _stats(3),
             "config": cfg, "iter_tokens": 4, "slots": 16},
        window_s=2.0, e2e={}, trace=trace, ctx=ctx)


def test_counter_readers_on_hand_made_counters(bench):
    run = _run(bench)
    read = lambda n: bench.layer_metric(n).read(run)  # noqa: E731
    assert read("dsa_selected_share") == pytest.approx(25.0)
    assert read("prefill_chunk_ms") == pytest.approx(150.0)
    assert read("prompt_tokens_per_s") == pytest.approx(900.0)
    # layer 0: busiest 4 of mean 2; layer 1: busiest 5 of mean 2
    assert read("expert_load_imbalance") == pytest.approx((2.0 + 2.5) / 2)
    # a program without the counters (the parent commit): nothing, no error
    short = {k: v for k, v in _stats(3).items()
             if k != "decode_expert_tokens"}
    for name in NEW:
        assert bench.layer_metric(name).read(_run(bench, stats1=short)) \
            is None
        assert bench.layer_metric(name).read(
            types.SimpleNamespace(obs={}, e2e={}, trace=None)) is None


def test_device_readers_on_a_hand_made_event_list(bench):
    events = [_ev(BURST, 0, 40), _ev(CHUNK_LOOP, 40, 6), _ev(GROUPING, 46, 4),
              _ev(KERNEL, 50, 4), _ev(KERNEL, 54, 6), _ev(WINDOW, 60, 12),
              _ev("%fusion.1 = bf16[2048,2048]", 72, 8), _ev(BURST, 100, 40)]
    run = _run(bench, events=events)
    ops = bench.ops_counts("keye-vl2-30b-a3b")
    cfg = run.obs["config"]
    counts = lm_counts.window_counts(run)
    # 16 expert layers of 2 layers: 8 steps in the window
    step_bytes = ops.decode_step_bytes(cfg, counts)
    assert step_bytes == pytest.approx(
        (24 * ops.expert_bytes(cfg) + 16 * ops.other_layer_bytes(cfg)
         + 8 * ops.head_bytes(cfg) + 500 * 2 * 4 * 128 * 2
         + 2000 * 64 * 2) / 8)
    # two bursts of four steps in 80 ms: 10 ms a step
    assert bench.layer_metric("decode_step_roofline_share").read(run) == \
        pytest.approx(100 * step_bytes / 819e9 / 0.010)
    assert bench.layer_metric("moe_time_share").read(run) == \
        pytest.approx(100 * 10 / 120)
    call_ops, call_bytes = ops.moe_call(cfg, counts)
    assert call_ops == pytest.approx(3200 * 3 * 2 * 2048 * 768 / 4)
    least = max(call_ops / 197e12, call_bytes / 819e9)
    assert bench.layer_metric("moe_roofline_share").read(run) == \
        pytest.approx(100 * least / 0.005)
    assert bench.layer_metric("dsa_time_share").read(run) == \
        pytest.approx(100 * 12 / 120)
    win_ops, win_bytes = ops.selected_window_call(cfg, counts)
    assert win_ops == pytest.approx(2000 * 2 * 2 * 32 * 128 / 4)
    assert bench.layer_metric("dsa_roofline_share").read(run) == \
        pytest.approx(100 * max(win_ops / 197e12, win_bytes / 819e9) / 0.012)
    # the chunk program's own loops are not a decode burst
    assert bench.layer_metric("decode_step_roofline_share").read(
        _run(bench, events=[_ev(CHUNK_LOOP, 0, 10),
                            _ev(GROUPING, 10, 4)])) is None

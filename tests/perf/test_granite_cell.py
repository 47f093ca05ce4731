"""The cell PR 31 added, rehearsed on the CPU: the hybrid state-space
serving cell agrees with its plain reference in its served tokens and in
the recurrent state its slots are left with, its float8 control does not,
a program that drops the carried state between chunks, advances it over
padding or carries it in a lower precision than the configuration states
reads not correct, and the new per-layer readers and counts give known
answers on hand-made counters and a hand-made event list."""

import json
import types

import numpy as np
import pytest

from perf.harness import hybrid_counts, traffic_lm
from perf.harness.loader import Benchmark
from perf.harness.trace import Event, TraceSummary

from _runs import REPO, child, in_process

CELL = "granite-4.0-h-micro.chat-closed"
CONFIG = "granite-4.0-h-micro"
MS = 1_000_000
DEV = "/device:TPU:0"
NEW = ["ssm_decode_step_roofline_share", "ssm_state_bytes_share",
       "scan_padding_share", "hybrid_prefill_chunk_ms",
       "hybrid_prompt_tokens_per_s"]
KEYE = ["decode_step_roofline_share", "moe_time_share", "moe_roofline_share",
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
    assert len(numbers) >= 6
    assert all(r["inside"] for r in numbers.values()), numbers
    assert numbers["widest_logit_gap"]["positions"] > 8
    # the slots' recurrent state itself: a float32 program differs from the
    # token-by-token reference by its sums' order alone
    state = numbers["mean_state_gap"]
    assert state["requests"] >= 1 and state["heads"] >= 32
    assert state["value"] <= state["widest_head"] < 0.05 * state["limit"]
    replies = [r for r in out if r.get("note") == "replies"][0]
    assert replies["replies_ended_early"] == 0 and replies["finished"] > 20
    assert out[-1]["correct"] is True and out[-1]["failed"] == 0
    assert {"serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"} \
        <= set(out[-1]["metrics_reported"])
    counts = [r for r in out if r.get("note") == "window_counts"][0]
    assert counts["prompt_chunks"] > 0 and counts["prompt_tokens"] > 0
    assert counts["decode_row_steps"] > 0 and counts["decode_calls"] > 0


@pytest.mark.parametrize("seed", [41, 2**31 + 43])
def test_the_float8_control_fails_the_check(capsys, seed):
    code, out = in_process(capsys, "--workload", CELL, "--seed", str(seed),
                           "--seconds", "1", "--rehearse", "--control")
    assert code == 0 and out[-1]["found_not_correct"] is True
    for number in ("widest_logit_gap", "mean_logit_gap"):
        program = _compared(out)[number]
        control = _compared(out, of="control")[number]
        assert program["inside"] and not control["inside"]
        assert control["value"] > 3 * program["value"]
    assert program["positions"] == control["positions"] > 8


def _broken_scan(monkeypatch, how):
    from mxnet_tpu.ops import ssm

    real = ssm.ssd_chunk_scan

    def scan(x, dt, a, b, c, state, *rest):
        if how == "drops the carried state":
            state = state * 0
        else:                           # advances the state over padding
            dt = dt.at[:, 1:].set(
                (dt[:, 1:] == 0) * dt[:, :1] + dt[:, 1:])
        return real(x, dt, a, b, c, state, *rest)

    monkeypatch.setattr(ssm, "ssd_chunk_scan", scan)


@pytest.mark.parametrize("how", ["drops the carried state",
                                 "advances the state over padding"])
def test_a_program_that_mishandles_the_state_is_not_correct(
        capsys, monkeypatch, how):
    """A chunk program that starts every chunk from zero, or that lets a
    chunk's padding advance the state, serves tokens the reference's one
    forward pass does not put first: the run is not ``correct``."""
    _broken_scan(monkeypatch, how)
    code, out = in_process(capsys, "--workload", CELL, "--seed", "5",
                           "--seconds", "1", "--rehearse")
    assert code == 0 and out[-1]["correct"] is False
    # a float32 program reads 0.0 exactly; here served tokens are not the
    # reference's best, at a few positions of some fifty
    widest = _compared(out)["widest_logit_gap"]
    assert not widest["inside"]
    assert widest["off_best"] >= 1 and widest["positions"] > 20
    # the state the slots are left with says so outright: 0.12 to 0.7 by
    # which requests the window's end leaves to the sample (the clock's
    # choice), where a sound program reads 0.000001
    state = _compared(out)["mean_state_gap"]
    assert not state["inside"] and state["value"] > 100 * state["limit"]
    # nothing else is at fault: no recompile, every page back
    assert _compared(out)["steady_state_recompiles"]["inside"]
    assert _compared(out)["pages_not_back_after_stop"]["inside"]


@pytest.mark.parametrize("seed", [5, 2**31 + 52])
def test_a_state_carried_in_a_lower_precision_is_not_correct(
        capsys, monkeypatch, seed):
    """The configuration states a float32 recurrent state. A program that
    carries it in bfloat16 serves the very tokens the reference puts first
    (the logits do not see it) and is still not ``correct``: its slots'
    state lies a thousand times farther from the reference's."""
    from mxnet_tpu.gluon.model_zoo.granite_hybrid import GraniteHybridLM

    real = GraniteHybridLM.__init__

    def init(self, *args, **kw):
        assert kw["state_dtype"] == "float32"
        real(self, *args, **dict(kw, state_dtype="bfloat16"))

    monkeypatch.setattr(GraniteHybridLM, "__init__", init)
    code, out = in_process(capsys, "--workload", CELL, "--seed", str(seed),
                           "--seconds", "1", "--rehearse")
    assert code == 0 and out[-1]["correct"] is False
    numbers = _compared(out)
    assert numbers["widest_logit_gap"]["inside"]
    assert numbers["mean_logit_gap"]["inside"]
    state = numbers["mean_state_gap"]
    assert not state["inside"]
    assert 10 * state["limit"] < state["value"] < state["widest_head"] < 0.05


def _record(index, first, last, prompt=8, tokens=4, max_new=4, error=None):
    return types.SimpleNamespace(
        index=index, prompt=[3] * prompt, tokens=[5] * tokens,
        max_new=max_new, first=first, last=last, error=error)


def test_the_state_sample_is_what_no_later_request_overwrote(bench):
    """A slot is zeroed by the prompt that takes it next: only a request
    that ended after the last admission surely left its state behind; the
    longest of those are taken, and none that an end token cut short."""
    pick = bench.driver("serve-hybrid-lm")._state_sample
    records = [
        _record(0, first=1.0, last=2.0, prompt=30),      # slot taken again
        _record(1, first=1.5, last=5.0, prompt=12),
        _record(2, first=3.0, last=4.0),                 # the last admission
        _record(3, first=2.0, last=6.0, prompt=20),
        _record(4, first=2.5, last=7.0, prompt=40, tokens=3),  # ended early
        _record(5, first=2.6, last=None, error="late"),
        _record(6, first=2.7, last=2.9, prompt=9)]
    assert [r.index for r in pick(records, 2)] == [3, 1]
    assert [r.index for r in pick(records, 9)] == [3, 1, 2]
    assert pick([records[5]], 2) == []


def test_a_traced_rehearsal_reads_the_new_counters():
    proc = child("--workload", CELL, "--seed", str(2**31 + 17), "--seconds",
                 "2", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    # the device metrics need a device's timeline; the counters do not
    got = set(last["metrics_reported"])
    assert {"ssm_state_bytes_share", "scan_padding_share",
            "hybrid_prefill_chunk_ms", "hybrid_prompt_tokens_per_s",
            "batch_occupancy", "iter_wall_ms", "decode_wait_ms",
            "prefill_wait_ms"} <= got
    assert not got & set(KEYE)          # another model's, another cell's


# ------------------------------------------------------------ the manifest
def test_the_cell_is_listed_as_the_issue_names_it(bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "chat-closed", 1)
    assert {m["name"] for m in bench.end_to_end(cell)} == {
        "serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    # the five new readers, and every accepted serving metric with no list
    # of cells beside them; keye's nine list keye's cell alone
    got = {n for n, _ in bench.per_layer(cell)}
    assert set(NEW) <= got and len(got) == len(NEW) + 13
    assert not got & set(KEYE) and "device_idle_share.train" not in got
    assert bench.config(CONFIG)["driver"] == "serve-hybrid-lm"
    assert bench.driver("serve-hybrid-lm").run


def test_each_prs_metrics_list_its_cell_and_older_entries_are_as_they_were(
        bench):
    keye = "keye-vl2-30b-a3b.longctx-closed"
    names = [m["name"] for m in bench.manifest["per_layer"]]
    assert names[-len(NEW):] == NEW            # appended, nothing between
    for m in bench.manifest["per_layer"]:
        if m["name"] in NEW or m["name"] in KEYE:
            assert m["workloads"] == [CELL if m["name"] in NEW else keye]
            reader = bench.layer_metric(m["name"])
            assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) \
                == (m["name"], m["unit"], m["layer"], m["moves"])
        else:
            assert "workloads" not in m
    assert len(names) == 18 + len(KEYE) + len(NEW)
    for m in bench.manifest["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms"):
            assert m["workloads"] == ["transformer-big.translate-closed",
                                      keye, CELL]


@pytest.mark.parametrize("cell,n", [
    ("transformer-big.translate-closed", 13),
    ("keye-vl2-30b-a3b.longctx-closed", 22),
    ("bert-base.pretrain-s128", 5)])
def test_an_older_cell_reads_the_per_layer_metrics_it_read(bench, cell, n):
    """What ``test_keye_cell.py::test_new_metrics_list_the_new_cell_and_
    old_entries_are_as_they_were`` holds of the older cells, under a name
    of its own (that test also asserts that no metric but PR 27's nine
    lists its cells, which this configuration's five must: it fails until
    a ``benchmark`` issue rewrites its ``else`` branch, PERF.md 7 (p)):
    each older cell reads as many per-layer metrics as before this
    configuration, none of them new."""
    got = {m for m, _ in bench.per_layer(bench.cell(cell))}
    assert len(got) == n and not got & set(NEW)
    if cell != "keye-vl2-30b-a3b.longctx-closed":
        assert not got & set(KEYE)


def test_the_configuration_carries_the_catalog_entry_whole(bench):
    cfg = bench.config(CONFIG)
    assert cfg["reduced"] == [] and cfg["num_hidden_layers"] == 40
    kinds = cfg["layer_types"]
    assert len(kinds) == 40 and kinds.count("attention") == 4
    assert [i for i, k in enumerate(kinds) if k == "attention"] == \
        [5, 15, 25, 35]
    assert (cfg["hidden_size"], cfg["vocab_size"], cfg["mamba_n_heads"],
            cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["shared_intermediate_size"]) == \
        (2048, 100352, 64, 64, 128, 4, 8192)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["attention_multiplier"], cfg["embedding_multiplier"],
            cfg["residual_multiplier"], cfg["logits_scaling"]) == \
        (32, 8, 0.015625, 12, 0.22, 8)
    assert cfg["precision"]["state"] == "float32"
    srv = cfg["serving"]
    assert srv["slots"] == 64 and srv["page_size"] == 128
    assert srv["prefill_chunk"] % cfg["mamba_chunk_size"] == 0
    assert srv["prompt_buckets"][-1] + srv["max_new_tokens"] == 1536
    assert srv["prefix_cache"] is False and srv["max_prefix_tokens"] == 0
    assert bench.driver("serve-hybrid-lm").NO_END_TOKEN == -1
    assert cfg["check"]["pad_to"] == 1536
    ref = bench.reference(CONFIG)
    n = sum(int(np.prod(s)) for s in ref.tensor_specs(cfg).values())
    assert n == 3_191_396_096                    # 6.38 GB in bfloat16
    ops = bench.ops_counts(CONFIG)
    assert ops.weight_bytes(cfg) == \
        2 * (n - 0)                              # every tensor once, bf16
    assert ops.state_bytes_row(cfg) == 36 * 64 * 64 * 128 * 4
    assert ops.tail_bytes_row(cfg) == 36 * 3 * 4352 * 2
    assert ops.kv_bytes_position(cfg) == 4 * 2 * 8 * 64 * 2
    # the program's constructor takes the same sizes
    kw = bench.driver("serve-hybrid-lm")._model_kwargs(cfg)
    assert kw["layer_types"] == tuple(kinds) and kw["state_dtype"] == \
        "float32"


def test_the_catalogs_keys_are_all_there_unchanged(bench):
    """Every number of the catalog's ``config`` under the same key (the
    values below are the catalog's: ``architectures.jsonl``, row
    ``granite-4.0-h-micro``)."""
    cfg = bench.config(CONFIG)
    catalog = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    for key, value in catalog.items():
        assert cfg[key] == value, key
    entry = [c for c in bench.manifest["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]


# ---------------------------------------------------------------- traffic
def test_the_mix_is_as_the_issue_gives_it(bench):
    mix = bench.traffic("chat-closed")
    assert (mix["kind"], mix["clients"], mix["population"],
            mix["population_seed"], mix["drain_s"]) == \
        ("closed_loop_lm", 64, 256, 20260929, 60)
    a = traffic_lm.RequestStream(mix, 2**31 + 7, 100352)
    b = traffic_lm.RequestStream(mix, 11, 100352)
    n = mix["population"]
    shape = lambda s, at: [(len(s.request(i)[0]), s.request(i)[1])  # noqa: E731
                           for i in range(at, at + n)]
    assert shape(a, 0) == shape(b, 0) and shape(a, n) == shape(b, n)
    assert shape(a, 0) != shape(a, n)            # a new order each pass
    assert not (a.request(5)[0] == b.request(5)[0]).all()
    prompts = np.array([p for p, _ in shape(a, 0)])
    replies = np.array([r for _, r in shape(a, 0)])
    assert 32 <= prompts.min() and prompts.max() <= 1024
    assert 64 <= replies.min() and replies.max() <= 512
    assert 340 < np.median(prompts) < 430 and 230 < np.median(replies) < 290
    ids = a.request(3)[0]
    assert ids.min() >= 3 and ids.max() < 100352
    # the chunk seat of the configuration's own arithmetic
    cfg = bench.config(CONFIG)["serving"]
    chunks = np.ceil(prompts / cfg["prefill_chunk"]).mean()
    seat = 64 * cfg["iter_tokens"] / replies.mean() * chunks
    assert seat < 0.7


# ------------------------------------------ readers on hand-made readings
def _stats(scale):
    return {"iterations": 10 * scale,
            "prefill_scan_tokens": 900 * scale,
            "prefill_scan_padded": 300 * scale,
            "prefill_chunks_from_zero": 3 * scale,
            "prefill_row_steps": 0, "prefill_attn_keys": 5000 * scale,
            "prefill_calls": 4 * scale,
            "decode_scan_tokens": 0, "decode_scan_padded": 0,
            "decode_chunks_from_zero": 0,
            "decode_row_steps": 48 * scale, "decode_attn_keys": 30000 * scale,
            "decode_calls": scale,
            "prompt_chunks": 4 * scale, "prompt_tokens": 900 * scale,
            "prefill_chunk_s": 0.5 * scale}


def _ev(name, start_ms, dur_ms):
    return Event(DEV, "XLA Ops", name, int(start_ms * MS), int(dur_ms * MS))


BURST = ("%while.91 = (s32[]{:T(128)}, s32[64]{0:T(128)S(1)}, s32[6]{0:T("
         "128)}, bf16[769,128,8,64]{3,2,1,0:T(8,128)(2,1)}")
OTHER_LOOP = "%while.12 = (s32[]{:T(128)}, f32[1,8,2,64,32,128]{5,4,3,2,1,0"
WINDOW = "%dsa_selected_window.3 = bf16[1,2,8,1024,64]{4,3,2,1,0:T(8,128)(2"


def _run(bench, stats1=None, events=None):
    cfg = bench.config(CONFIG)
    trace = None if events is None else TraceSummary(events, chips=1)
    ctx = types.SimpleNamespace(
        bench=bench, peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    return types.SimpleNamespace(
        obs={"stats0": _stats(1), "stats1": stats1 or _stats(3),
             "config": cfg, "iter_tokens": 2, "slots": 64},
        window_s=2.0, e2e={}, trace=trace, ctx=ctx)


def test_counts_on_hand_made_numbers(bench):
    """Two steps in the window, 48 live rows a step, 30,000 cached
    positions a step: the bytes a step must move, by part."""
    cfg = bench.config(CONFIG)
    ops = bench.ops_counts(CONFIG)
    counts = hybrid_counts.window_counts(_run(bench))
    assert counts["decode_calls"] == 2 and counts["decode_row_steps"] == 96
    parts = ops.decode_step_parts(cfg, counts)
    assert parts["weights"] == 2 * 3_191_396_096
    assert parts["state"] == 48 * 2 * 36 * 64 * 64 * 128 * 4
    assert parts["tails"] == 48 * 2 * 36 * 3 * 4352 * 2
    assert parts["kv"] == 30000 * 4 * 2 * 8 * 64 * 2
    assert ops.decode_step_bytes(cfg, counts) == sum(parts.values())
    # the state is read once and written once a live row: with every one of
    # 64 rows live it is 9.66 GB of a step's 16.6, under three fifths
    full = dict(counts, decode_row_steps=64 * 2, decode_attn_keys=2 * 64000)
    p = ops.decode_step_parts(cfg, full)
    assert p["state"] == 64 * 2 * 75_497_472
    assert 0.55 < p["state"] / sum(p.values()) < 0.6
    assert ops.decode_step_parts(cfg, dict(counts, decode_calls=0)) is None


def test_counter_readers_on_hand_made_counters(bench):
    run = _run(bench)
    read = lambda n: bench.layer_metric(n).read(run)  # noqa: E731
    assert read("scan_padding_share") == pytest.approx(25.0)
    # 8 chunks of 1,800 prompt tokens took 1.0 s of a window of 2.0 s
    assert read("hybrid_prefill_chunk_ms") == pytest.approx(125.0)
    assert read("hybrid_prompt_tokens_per_s") == pytest.approx(900.0)
    cfg = bench.config(CONFIG)
    parts = bench.ops_counts(CONFIG).decode_step_parts(
        cfg, hybrid_counts.window_counts(run))
    assert read("ssm_state_bytes_share") == pytest.approx(
        100 * parts["state"] / sum(parts.values()))
    assert 0 < read("ssm_state_bytes_share") < 100
    # a program without the counters (the parent commit, another model):
    # nothing, no error
    short = {k: v for k, v in _stats(3).items() if k != "decode_row_steps"}
    for name in NEW:
        assert bench.layer_metric(name).read(_run(bench, stats1=short)) \
            is None
        assert bench.layer_metric(name).read(
            types.SimpleNamespace(obs={}, e2e={}, trace=None)) is None


def test_device_readers_on_a_hand_made_event_list(bench):
    events = [_ev(BURST, 0, 50), _ev(OTHER_LOOP, 50, 5), _ev(WINDOW, 61, 3),
              _ev("%fusion.1 = bf16[512,16384]", 64, 16), _ev(BURST, 100, 50)]
    run = _run(bench, events=events)
    ops = bench.ops_counts(CONFIG)
    cfg = run.obs["config"]
    counts = hybrid_counts.window_counts(run)
    # two bursts of two steps in 100 ms: 25 ms a step
    step_bytes = ops.decode_step_bytes(cfg, counts)
    assert bench.layer_metric("ssm_decode_step_roofline_share").read(run) \
        == pytest.approx(100 * step_bytes / 819e9 / 0.025)
    # neither the chunk program's own loops nor another slot count's burst
    none = _run(bench, events=[_ev(OTHER_LOOP, 0, 10), _ev(WINDOW, 10, 4)])
    assert bench.layer_metric("ssm_decode_step_roofline_share").read(none) \
        is None

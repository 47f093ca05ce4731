"""The cell PR 48 added, rehearsed on the CPU: the gated delta-rule hybrid
serving cell agrees with its plain reference in its served tokens and in
the matrix state its slots are left with, over a FIXED set of requests (not
a second of traffic: PERF.md 7 (ba)) and through the whole harness; its
float8 control, a state carried in bfloat16, a state dropped between chunks
and a state advanced over padding each read not correct; the cell, the mix
and the configuration are as the issue gives them; and the new counts and
readers give known answers on hand-made counters and a hand-made event
list, and nothing in another model's cell. Of the manifest these tests say
only what is true of THIS PR's entries, so that the next cell turns none of
them red (PERF.md 7 (p))."""

import json
import types

import numpy as np
import pytest

from perf.harness import hybrid_counts, traffic_lm
from perf.harness.loader import Benchmark
from perf.harness.trace import Event, TraceSummary

from _runs import REPO, child, in_process

CELL = "olmo-hybrid-7b.rag-closed"
CONFIG = "olmo-hybrid-7b"
MS = 1_000_000
DEV = "/device:TPU:0"
NEW = ["delta_step_time_share", "delta_step_roofline_share"]
# granite's five read the same quantities of this net's counts as they
# stand; an older test pins their lists of cells (PERF.md 7 (bn)), so the
# manifest does not list this cell with them yet
GRANITE = ["ssm_decode_step_roofline_share", "ssm_state_bytes_share",
           "scan_padding_share", "hybrid_prefill_chunk_ms",
           "hybrid_prompt_tokens_per_s"]
LISTED = ["pass_wall_p95_ms", "decode_wait_p95_ms", "burst_ahead_share"]
GAPS = ("widest_logit_gap", "mean_logit_gap", "mean_state_gap",
        "state_cut_gap")
E2E = ("serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms")
# PR 36's four readers of the client's TTFT
TAILS = ["queue_wait_p95_ms", "seat_wait_p95_ms", "prefill_service_p95_ms",
         "first_token_deliver_p95_ms"]


@pytest.fixture(scope="module")
def bench():
    return Benchmark(REPO)


def _compared(out, of=None):
    return {r["number"]: r for r in out
            if r.get("note") == "compared" and r.get("of") == of}


# ------------------------------------------------- a fixed set of requests
# (prompt, reply): two waves through three slots; prompts of one to five
# chunks of 8, cut at offsets that are no multiple of the block of 4
FIRST = ((5, 5), (23, 6), (9, 2))
LAST = ((38, 6), (16, 4), (31, 5))


def _fixed(bench, seed=5, state="float32"):
    """The rehearsal's program over SIX fixed requests, through the
    driver's own builder and the scheduler, and the three numbers the
    driver's comparison reads of them with the driver's own functions: the
    logits of all six, the final state of the three that took the slots
    last."""
    cfg = bench.config(CONFIG)
    cfg = dict(cfg, **cfg["rehearse"]["config"])
    cfg["precision"] = dict(cfg["precision"], state=state)
    ref = bench.reference(CONFIG)
    hybrid, lm = bench.driver("serve-hybrid-lm"), bench.driver("serve-lm")
    delta_lm = bench.driver("serve-delta-lm")
    hybrid._model_kwargs = delta_lm._model_kwargs
    _, eng, bat = hybrid._build_program(cfg, ref, seed)
    rng = np.random.default_rng(seed)
    done = []
    try:
        for wave in (FIRST, LAST):
            prompts = [rng.integers(3, cfg["vocab_size"], n).astype(np.int32)
                       for n, _ in wave]
            futs = [bat.submit(p, max_new_tokens=n)
                    for p, (_, n) in zip(prompts, wave)]
            done += [types.SimpleNamespace(
                prompt=p, tokens=[int(t) for t in f.result(timeout=300)])
                for p, f in zip(prompts, futs)]
    finally:
        bat.stop()
    assert [len(r.tokens) for r in done] == [n for _, n in FIRST + LAST]
    delta = np.stack([np.asarray(a, np.float32)
                      for a in bat.slot_arrays()["delta"]])
    widest, mean, positions, _ = lm.logit_gaps(ref, seed, cfg, done)
    gaps = delta_lm.state_gaps(hybrid, ref, seed, cfg, done[3:], delta)
    assert positions == sum(n for _, n in FIRST + LAST)
    assert gaps.shape == (3, 4, 4)          # requests, layers, heads
    state_gap, beside = delta_lm.state_numbers(gaps)
    assert len(beside["by_layer"]) == 4
    cut = delta_lm.cut_gaps(hybrid, eng, bat.paged_state(), cfg, seed)
    assert cut.shape == (4, 4)              # layers, heads
    return {"widest_logit_gap": widest, "mean_logit_gap": mean,
            "mean_state_gap": state_gap,
            "state_cut_gap": float(cut.mean())}, cfg["tolerance"]


def _alter(monkeypatch, how):
    from mxnet_tpu.ops import delta_rule

    real = delta_rule.delta_rule_chunk

    def chunk(q, k, v, g, beta, state, *rest):
        if how == "drops the carried state":
            state = state * 0
        else:                           # advances the state over padding
            pad = (g == 0) & (beta == 0)
            g, beta = g + pad * g[:, :1], beta + pad * beta[:, :1]
        return real(q, k, v, g, beta, state, *rest)

    monkeypatch.setattr(delta_rule, "delta_rule_chunk", chunk)


def test_the_fixed_requests_agree(bench):
    numbers, limits = _fixed(bench)
    assert all(numbers[k] <= limits[k] for k in GAPS), numbers
    # a float32 program puts first what the float32 reference puts first,
    # and its state differs by the sums' order alone
    assert numbers["mean_logit_gap"] == 0.0
    assert 0 < numbers["mean_state_gap"] < 0.05 * limits["mean_state_gap"]
    # the same operands through the same blocks: the cuts change nothing
    assert numbers["state_cut_gap"] < 0.05 * limits["state_cut_gap"]


@pytest.mark.parametrize("how", ["drops the carried state",
                                 "advances the state over padding"])
def test_a_program_that_mishandles_the_state_is_not_correct(
        bench, monkeypatch, how):
    """A chunk program that starts every chunk from zero, or that lets a
    chunk's padding advance the state, leaves its slots a state far from
    the reference's and serves tokens the reference does not put first."""
    _alter(monkeypatch, how)
    numbers, limits = _fixed(bench)
    for k in GAPS[2:]:
        assert numbers[k] > 100 * limits[k]
    assert numbers["mean_logit_gap"] > limits["mean_logit_gap"]


@pytest.mark.parametrize("seed", [5, 2**31 + 52])
def test_a_state_carried_in_a_lower_precision_is_not_correct(bench, seed):
    """The configuration states a float32 state. A program that carries it
    in bfloat16 is not ``correct`` by the state's own limit, whatever its
    tokens say."""
    numbers, limits = _fixed(bench, seed=seed, state="bfloat16")
    assert numbers["mean_state_gap"] > 10 * limits["mean_state_gap"]
    assert numbers["mean_state_gap"] < 0.05


# -------------------------------------------------------------- whole runs
def test_the_system_agrees_with_its_reference(capsys):
    code, out = in_process(capsys, "--workload", CELL, "--seed", "21",
                           "--seconds", "1", "--rehearse")
    assert code == 0
    numbers = _compared(out)
    assert set(GAPS) <= set(numbers) and len(numbers) >= 8
    assert all(r["inside"] for r in numbers.values()), numbers
    # chosen by index: each caller's first two requests, the six longest
    assert numbers["widest_logit_gap"]["requests"] == 6
    assert numbers["widest_logit_gap"]["positions"] > 8
    state = numbers["mean_state_gap"]
    assert state["requests"] >= 1 and state["heads"] >= 16
    # which requests ended last is the clock's choice: the widest single
    # head of theirs is held to the limit itself, not to a tenth of it
    assert state["value"] <= state["widest_head"] < state["limit"]
    assert len(state["by_layer"]) == 4
    cut = numbers["state_cut_gap"]
    assert cut["heads"] == 16 and cut["new_programs"] == 0
    assert cut["value"] <= cut["widest_head"] < 0.1 * cut["limit"]
    replies = [r for r in out if r.get("note") == "replies"][0]
    assert replies["replies_ended_early"] == 0 and replies["finished"] > 20
    assert out[-1]["correct"] is True and out[-1]["failed"] == 0
    assert set(E2E) | {"setup_s"} <= set(out[-1]["metrics_reported"])
    counts = [r for r in out if r.get("note") == "window_counts"][0]
    assert counts["prompt_chunks"] > 0 and counts["prompt_tokens"] > 0
    assert counts["decode_row_steps"] > 0 and counts["decode_calls"] > 0
    said = [r for r in out if r.get("note") == "state_bytes"][0]
    assert said["slot_arrays"] == 3 * 4 * (4 * 8 * 16 + 3 * 128) * 4


@pytest.mark.parametrize("seed", [41, 2**31 + 43])
def test_the_float8_control_fails_the_check(capsys, seed):
    code, out = in_process(capsys, "--workload", CELL, "--seed", str(seed),
                           "--seconds", "1", "--rehearse", "--control")
    assert code == 0 and out[-1]["found_not_correct"] is True
    for number in ("widest_logit_gap", "mean_logit_gap"):
        program = _compared(out)[number]
        control = _compared(out, of="control")[number]
        assert program["inside"] and not control["inside"]
        assert control["value"] > 10 * control["limit"]
    assert program["positions"] == control["positions"] > 8


def _record(index, first, last, prompt=8, tokens=5, max_new=5, error=None):
    return types.SimpleNamespace(
        index=index, prompt=[3] * prompt, tokens=[5] * tokens,
        max_new=max_new, first=first, last=last, error=error)


def test_the_samples_are_chosen_by_rule_and_not_by_the_clock(bench):
    """The logits' sample is the longest among each caller's first two
    requests, whatever ended when; the state's is what no later request
    overwrote, no end token cut short, and whose every fed token the caller
    holds (a burst of 4 runs whole: 5 or 8 served tokens were fed 4 or 8,
    6 were fed 8)."""
    drv = bench.driver("serve-delta-lm")
    records = [
        _record(0, 1.0, 2.0, prompt=30), _record(1, 1.5, 5.0, prompt=12),
        _record(2, 3.0, 4.0, prompt=20), _record(3, 2.0, 6.0, prompt=25),
        _record(4, 2.5, 7.0, prompt=40), _record(5, 2.6, None, error="late"),
        _record(9, 2.7, 2.9, prompt=90)]
    pick = [r.index for r in drv._check_sample(records, 3, clients=2)]
    assert pick == [0, 3, 2]                 # of 0..3: the longest three
    assert drv._check_sample(list(reversed(records)), 3, 2)[0].index == 0
    state = [
        _record(0, first=1.0, last=2.0, prompt=30),      # slot taken again
        _record(1, first=1.5, last=5.0, prompt=12),
        _record(2, first=3.0, last=4.0),                 # the last admission
        _record(3, first=2.0, last=6.0, prompt=20, tokens=6, max_new=6),
        _record(4, first=2.5, last=7.0, prompt=40, tokens=3),  # ended early
        _record(5, first=2.6, last=6.5, prompt=9, tokens=8, max_new=8)]
    intact = bench.driver("serve-hybrid-lm")._state_sample(state, 9)
    assert [r.index for r in intact] == [3, 1, 5, 2]
    assert [r.index for r in drv._settled(intact, 2, 4)] == [1, 5]
    assert [r.index for r in drv._settled(intact, 9, 2)] == [3, 1, 5, 2]
    assert drv._settled([], 2, 4) == []


def test_a_traced_rehearsal_reads_the_counters():
    proc = child("--workload", CELL, "--seed", str(2**31 + 17), "--seconds",
                 "2", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    # the device metrics need a device's timeline; the counters do not
    got = set(last["metrics_reported"])
    assert {"batch_occupancy", "iter_wall_ms", "burst_ahead_share",
            "pass_wall_p95_ms", "decode_wait_p95_ms"} <= got
    assert not got & (set(NEW) | {
        "mla_cache_bytes_share", "prefill_chunk_ms", "cca_cache_bytes_share"})


# ------------------------------------------------------------ the manifest
def test_the_cell_is_listed_as_the_issue_names_it(bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "rag-closed", 1)
    assert set(E2E) | {"setup_s"} <= \
        {m["name"] for m in bench.end_to_end(cell)}
    got = {n for n, _ in bench.per_layer(cell)}
    assert set(NEW) | set(LISTED) | set(TAILS) <= got
    assert not got & {"mhc_time_share", "cca_cache_bytes_share",
                      "moe_time_share", "device_idle_share.train"}
    assert bench.config(CONFIG)["driver"] == "serve-delta-lm"
    assert bench.driver("serve-delta-lm").run
    entry = [c for c in bench.manifest["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert entry["file"] == "perf/configs/olmo-hybrid-7b.json"
    assert entry["source"] == bench.config(CONFIG)["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert "16 callers" in cell["why"] and "16 of 32 layers, so host and " \
        "idle share exceed the deployment's" in cell["why"]


def test_this_prs_metrics_list_its_cell(bench):
    by = {m["name"]: m for m in bench.manifest["per_layer"]}
    assert set(NEW) <= set(by)
    for name in NEW:
        m = by[name]
        assert m["workloads"] == [CELL]
        assert (m["source"] == "device_trace") == (m["layer"] == "kernels")
        reader = bench.layer_metric(name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for name in LISTED + TAILS:
        assert CELL in by[name]["workloads"]
        assert by[name]["moves"] in E2E
    for m in bench.manifest["end_to_end"]:
        if m["name"] in E2E:
            assert CELL in m["workloads"]


def test_the_configuration_carries_the_catalogs_keys(bench):
    """Every key of the catalog's ``config`` (``architectures.jsonl``, row
    ``Olmo-Hybrid-7B``; the values below are the catalog's) unchanged but
    the two that say the depth, every width the published one, the
    deployment, each ``assumed``, and the issue's arithmetic."""
    cfg = bench.config(CONFIG)
    period = ["linear_attention"] * 3 + ["full_attention"]
    catalog = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": period * 8, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in catalog.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 16
    assert cfg["layer_types"] == period * 4          # four whole periods
    for word in ("two pipeline stages", "first stage", "the head"):
        assert word in cfg["deployment"], word
    assert len(cfg["assumed"]) >= 8
    said = " ".join(cfg["assumed"])
    for word in ("family's convention", "rope_theta null", "separate "
                 "projections and convolutions", "gate's silu", "1e-6",
                 "A uniform in 1..16", "dropped or stale state"):
        assert word in said, word
    for line in cfg["assumed"]:
        assert "\n" not in line
    assert cfg["precision"]["state"] == "float32"
    assert cfg["precision"]["conv_tail"] == "bfloat16"
    assert cfg["control"] == "fp8"
    assert set(cfg["memory"]) == {"reckoned", "read"}
    srv = cfg["serving"]
    assert (srv["slots"], srv["page_size"], srv["prefill_chunk"],
            srv["iter_tokens"], srv["max_new_tokens"]) == \
        (16, 128, 2048, 2, 1024)
    assert srv["prompt_buckets"][-1] + srv["max_new_tokens"] == 40 * 128
    assert srv["prefill_chunk"] % cfg["delta_block"] == 0
    assert srv["prefix_cache"] is False and srv["max_prefix_tokens"] == 0
    assert set(GAPS) <= set(cfg["tolerance"])
    assert cfg["check"]["pad_to"] == 5120
    kw = bench.driver("serve-delta-lm")._model_kwargs(cfg)
    assert kw["layer_types"] == tuple(period * 4)
    assert (kw["linear_key_dim"], kw["linear_value_dim"],
            kw["state_dtype"]) == (96, 192, "float32")
    # the issue's arithmetic: parameters a layer, bytes a slot and a token
    ref, ops = bench.reference(CONFIG), bench.ops_counts(CONFIG)
    n = sum(int(np.prod(s)) for s in ref.tensor_specs(cfg).values())
    assert ops.weight_bytes(cfg) == 2 * (n - 100352 * 3840)  # but the rows
    assert 8.19e9 < 2 * n < 8.21e9                      # 8.20 GB in bfloat16
    layer = {k: int(np.prod(s)) for k, s in ref.layer_specs(cfg, 0).items()}
    mlp = 3 * 3840 * 11008
    assert sum(layer.values()) - mlp - 2 * 3840 == \
        2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 + 4 * 11520 \
        + 2 * 30 + 192                                  # 88.75 M
    full = {k: int(np.prod(s)) for k, s in ref.layer_specs(cfg, 3).items()}
    assert sum(full.values()) - mlp - 2 * 3840 == 4 * 3840 * 3840 + 2 * 3840
    assert ops.state_bytes_row(cfg) == 12 * 30 * 96 * 192 * 4   # 26.5 MB
    assert ops.tail_bytes_row(cfg) == 12 * 3 * 11520 * 2
    assert ops.kv_bytes_position(cfg) == 4 * 2 * 3840 * 2       # 61.4 KB
    # 16 slots x 40 pages and the trash page: 5.04 GB of K/V
    assert 5.03e9 < 641 * 128 * ops.kv_bytes_position(cfg) < 5.05e9


# ---------------------------------------------------------------- traffic
def test_the_mix_is_as_the_issue_gives_it(bench):
    mix = bench.traffic("rag-closed")
    assert (mix["kind"], mix["clients"], mix["population"],
            mix["sampling"], mix["drain_s"]) == \
        ("closed_loop_lm", 16, 256, "greedy", 60)
    assert mix["prompt_length"] == {"median": 2048, "sigma": 0.6,
                                    "min": 512, "max": 4096}
    assert mix["reply_length"] == {"median": 384, "sigma": 0.5,
                                   "min": 96, "max": 1024}
    seeds = {json.load(open(f"{REPO}/perf/traffic/{f}"))["population_seed"]
             for f in ("chat-closed.json", "think-closed.json",
                       "longdoc-closed.json")}
    assert mix["population_seed"] not in seeds          # a new one
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
    assert 512 <= prompts.min() and prompts.max() <= 4096
    assert 96 <= replies.min() and replies.max() <= 1024
    assert 1900 < np.median(prompts) < 2200 and 2100 < prompts.mean() < 2500
    assert 340 < np.median(replies) < 420 and 400 < replies.mean() < 460
    # half the prompts enter in one chunk, the rest carry the state over a
    # boundary (1 to 4 chunks at the issue's first settings, 1,024 and
    # bursts of 4, whose TTFT p95 spread too widely: PERF.md 7 (bq)); the
    # chunk seat of the configuration's own arithmetic
    cfg = bench.config(CONFIG)["serving"]
    chunks = np.ceil(prompts / cfg["prefill_chunk"])
    assert chunks.min() == 1 and chunks.max() == 2
    assert 0.45 < (chunks == 2).mean() < 0.55
    assert np.ceil(prompts / 1024).max() == 4
    seat = 16 * cfg["iter_tokens"] / replies.mean() * chunks.mean()
    assert 0.1 < seat < 0.12
    # the logits' sample is a fixed set: the four longest of the first 32
    first = sorted(shape(a, 0)[:32], key=lambda pr: -(pr[0] + pr[1]))[:4]
    assert all(p + r <= 5120 for p, r in first) and first[0][0] == 4096


# ------------------------------------------ readers on hand-made readings
def _stats(scale):
    return {"iterations": 10 * scale,
            "prefill_scan_tokens": 1700 * scale,
            "prefill_scan_padded": 348 * scale,
            "prefill_chunks_from_zero": scale,
            "prefill_row_steps": 0, "prefill_attn_keys": 5000 * scale,
            "prefill_calls": 2 * scale,
            "decode_scan_tokens": 0, "decode_scan_padded": 0,
            "decode_chunks_from_zero": 0,
            "decode_row_steps": 56 * scale, "decode_attn_keys": 40000 * scale,
            "decode_calls": 4 * scale,
            "prompt_chunks": 2 * scale, "prompt_tokens": 1700 * scale,
            "prefill_chunk_s": 0.2 * scale}


def _ev(name, start_ms, dur_ms):
    return Event(DEV, "XLA Ops", name, int(start_ms * MS), int(dur_ms * MS))


BURST = ("%while.91 = (s32[]{:T(128)}, s32[16]{0:T(128)S(1)}, s32[6]{0:T("
         "128)}, bf16[641,128,3840]{2,1,0:T(8,128)(2,1)}")
STEP = "%gated_delta_step.2 = (f32[16,30,192]{2,1,0:T(8,128)}, f32[16,30,9"
OTHER = "%paged_window.3 = bf16[8,3840,128]{2,1,0:T(8,128)(2,1)} custom-c"


def _run(bench, config=CONFIG, stats1=None, events=None):
    cfg = bench.config(config)
    trace = None if events is None else TraceSummary(events, chips=1)
    ctx = types.SimpleNamespace(
        bench=bench, peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    return types.SimpleNamespace(
        obs={"stats0": _stats(1), "stats1": stats1 or _stats(3),
             "config": cfg, "iter_tokens": 4, "slots": 16},
        window_s=2.0, e2e={}, trace=trace, ctx=ctx)


def test_counts_on_hand_made_numbers(bench):
    """Eight steps in the window, 14 live rows a step, 10,000 cached
    positions a step; four chunks of 850 real tokens."""
    cfg = bench.config(CONFIG)
    ops = bench.ops_counts(CONFIG)
    counts = hybrid_counts.window_counts(_run(bench))
    assert counts["decode_calls"] == 8 and counts["decode_row_steps"] == 112
    parts = ops.decode_step_parts(cfg, counts)
    assert parts["weights"] == ops.weight_bytes(cfg)
    assert parts["state"] == 14 * 2 * 12 * 30 * 96 * 192 * 4
    assert parts["tails"] == 14 * 2 * 12 * 3 * 11520 * 2
    assert parts["kv"] == 10000 * 4 * 2 * 3840 * 2
    assert ops.decode_step_bytes(cfg, counts) == sum(parts.values())
    assert ops.decode_step_parts(cfg, dict(counts, decode_calls=0)) is None
    # one call of the step kernel: 14 live rows' states in and out, and the
    # step's vectors beside them; seven operations a state entry
    step_ops, step_bytes = ops.delta_step_call(cfg, counts)
    assert step_bytes == 14 * (2 * 30 * 96 * 192 * 4
                               + 30 * (2 * 96 + 4 * 192) * 4)
    assert step_ops == 14 * 30 * 7 * 96 * 192
    assert not hasattr(ops, "delta_chunk_call")    # that kernel went
    assert ops.delta_step_call(cfg, dict(counts, decode_calls=0)) is None


def test_device_readers_on_a_hand_made_event_list(bench):
    events = [_ev(BURST, 0, 80), _ev(STEP, 1, 0.2), _ev(STEP, 21, 0.2),
              _ev(OTHER, 30, 1),
              _ev("%fusion.1 = bf16[1024,22016]", 100, 19.8)]
    run = _run(bench, events=events)
    read = lambda n: bench.layer_metric(n).read(run)  # noqa: E731
    busy = run.trace.busy_s_of(run.trace.devices[0])
    assert busy == pytest.approx(0.0998)     # the steps lie inside the burst
    assert read("delta_step_time_share") == pytest.approx(
        100 * 0.0004 / busy)
    ops, cfg = bench.ops_counts(CONFIG), run.obs["config"]
    counts = hybrid_counts.window_counts(run)
    o, b = ops.delta_step_call(cfg, counts)
    assert read("delta_step_roofline_share") == pytest.approx(
        100 * 2 * (b / 819e9) / 0.0004)
    assert 0 < read("delta_step_roofline_share") < 100


GRANITES_OWN = {
    # the burst's one event (80 ms over four steps) against a step's bytes
    "ssm_decode_step_roofline_share":
        lambda ops, cfg, c: 100 * ops.decode_step_bytes(cfg, c) / 819e9
        / 0.020,
    "ssm_state_bytes_share":
        lambda ops, cfg, c: 100 * ops.decode_step_parts(cfg, c)["state"]
        / sum(ops.decode_step_parts(cfg, c).values()),
    "scan_padding_share": lambda *_: 100 * 696 / (3400 + 696),
    "hybrid_prefill_chunk_ms": lambda *_: 100.0,
    "hybrid_prompt_tokens_per_s": lambda *_: 1700.0}


@pytest.mark.parametrize("name", GRANITE)
def test_granites_readers_read_this_cell_as_they_stand(bench, name):
    """The net declares granite's six count names and its ``ops_counts``
    bring the functions granite's five readers call, so a ``benchmark`` PR
    that loosens the older pin only appends this cell to their lists
    (PERF.md 7 (bn)): on the hand-made window each reads this net's own
    arithmetic."""
    run = _run(bench, events=[_ev(BURST, 0, 80), _ev(STEP, 1, 0.2)])
    ops, cfg = bench.ops_counts(CONFIG), run.obs["config"]
    counts = hybrid_counts.window_counts(run)
    assert bench.layer_metric(name).read(run) == pytest.approx(
        GRANITES_OWN[name](ops, cfg, counts))


@pytest.mark.parametrize("config", ["granite-4.0-h-micro", "zaya1-8b"])
def test_the_new_readers_find_nothing_in_another_models_cell(bench, config):
    """granite's cell keeps the same counts and has no such kernel; zaya's
    keeps other counts: nothing, no error, with or without a trace."""
    events = [_ev(BURST, 0, 50), _ev(OTHER, 61, 3)]
    for run in (_run(bench, config, events=events), _run(bench, config),
                types.SimpleNamespace(obs={}, e2e={}, trace=None)):
        for name in NEW:
            assert bench.layer_metric(name).read(run) is None
    # and in this cell on a program without the kernel (the CPU's forms,
    # the parent's checkout): no event, no metric
    own = _run(bench, events=events)
    for name in NEW:
        assert bench.layer_metric(name).read(own) is None

"""The cell PR 33 added, rehearsed on the CPU: the latent-attention serving
cell agrees with its plain reference in its served tokens, in its module's
drafts and in the latents it caches; both controls (float8 weights, a
float8 latent cache) do not; a token, a draft or a count a row altered where
it is produced reads not correct; and the new per-layer readers and counts
give known answers on hand-made counters and a hand-made event list."""

import json
import os
import types

import numpy as np
import pytest

from perf.harness import mla_counts, traffic_lm
from perf.harness.loader import Benchmark
from perf.harness.trace import Event, TraceSummary

from _runs import REPO, child, in_process

CELL = "joyai-llm-flash.longgen-closed"
CONFIG = "joyai-llm-flash"
MS = 1_000_000
DEV = "/device:TPU:0"
NEW = ["mla_decode_step_roofline_share", "mla_latent_time_share",
       "mla_latent_roofline_share", "mla_cache_bytes_share",
       "mtp_accept_rate", "held_expert_pair_share"]
GAPS = ("widest_logit_gap", "mean_logit_gap", "mtp_logit_gap", "latent_gap")
E2E = ("serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms")


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
    assert set(GAPS) <= set(numbers) and len(numbers) >= 8
    assert all(r["inside"] for r in numbers.values()), numbers
    assert numbers["widest_logit_gap"]["positions"] > 8
    # the module's drafts and the cached latents: a float32 program differs
    # from the reference by its sums' order alone
    assert numbers["mtp_logit_gap"]["positions"] > 8
    assert numbers["mtp_logit_gap"]["value"] == 0.0
    latent = numbers["latent_gap"]
    assert latent["positions"] >= 3 and latent["value"] < 1e-5
    assert out[-1]["correct"] is True and out[-1]["failed"] == 0
    assert set(E2E) | {"setup_s"} <= set(out[-1]["metrics_reported"])
    counts = [r for r in out if r.get("note") == "window_counts"][0]
    assert counts["prompt_chunks"] > 0 and counts["prompt_tokens"] > 0
    assert counts["decode_row_steps"] > 0 and counts["decode_calls"] > 0
    assert counts["decode_mtp_drafts"] == counts["decode_row_steps"]
    assert 0 < counts["decode_pairs_held"] < counts["decode_pairs_all"]
    # ``tokens`` counts emitted tokens, a step's one or two (less what
    # ``max_new_tokens`` cut off a burst's end), not steps
    assert 0 < counts["tokens"] <= counts["decode_row_steps"] \
        + counts["decode_mtp_accepted"]
    state = [r for r in out if r.get("note") == "state_bytes"][0]
    assert state["pages"] > 0 and state["slot_arrays"] > 0


@pytest.mark.parametrize("seed", [41, 2**31 + 43])
def test_both_controls_fail_the_check(capsys, seed):
    """The float8-weights reference falls outside the limits of the served
    tokens and of the drafts; the program with a float8 latent cache serves
    nearly the same tokens and falls outside the limit of the latents."""
    code, out = in_process(capsys, "--workload", CELL, "--seed", str(seed),
                           "--seconds", "1", "--rehearse", "--control")
    assert code == 0 and out[-1]["found_not_correct"] is True
    program, control = _compared(out), _compared(out, of="control")
    for number in GAPS[:3]:
        assert program[number]["inside"] and program[number]["value"] == 0
    # some fifty positions: which requests the window leaves to the sample
    # is the clock's choice, so the widest gap is held to a margin and the
    # two means to their limits alone
    assert control["widest_logit_gap"]["value"] > 5 * \
        control["widest_logit_gap"]["limit"]
    assert not control["mean_logit_gap"]["inside"]
    assert not control["mtp_logit_gap"]["inside"]
    assert program["latent_gap"]["inside"]
    cache = _compared(out, of="control_cache")
    assert not cache["latent_gap"]["inside"]
    assert cache["latent_gap"]["value"] > 10 * cache["latent_gap"]["limit"]
    said = [r for r in out if r.get("note") == "control_cache"][0]
    assert said["found_not_correct"] and "latent_gap" in said["outside"]
    assert said["latent_dtype"] == "float8_e4m3fn"


def _alter(monkeypatch, what):
    from mxnet_tpu.serving.batcher import ContinuousBatcher, GenerationResult

    scramble = lambda t: 3 + (int(t) * 7 + 11) % 100  # noqa: E731
    if what == "count":
        real = ContinuousBatcher._take_steps

        def take(self, s, row, eos):
            row = np.array(row)
            row[2::4] = 2                  # every draft kept, whatever it is
            return real(self, s, row, eos)

        monkeypatch.setattr(ContinuousBatcher, "_take_steps", take)
        return
    real = GenerationResult._resolve

    def resolve(self, tokens):
        if what == "token":
            tokens = [scramble(t) for t in tokens]
        elif self.drafts:
            self.drafts = [(j, scramble(d)) for j, d in self.drafts]
        real(self, tokens)

    monkeypatch.setattr(GenerationResult, "_resolve", resolve)


@pytest.mark.parametrize("what,number", [
    ("token", "widest_logit_gap"), ("draft", "mtp_logit_gap"),
    ("count", "widest_logit_gap")])
def test_what_is_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch, what, number):
    """A served token, a draft, or the count a row that says how many of a
    step's two tokens stand: each altered on its way out reads not
    ``correct`` by the number that looks at it."""
    _alter(monkeypatch, what)
    code, out = in_process(capsys, "--workload", CELL, "--seed", "5",
                           "--seconds", "1", "--rehearse")
    assert code == 0 and out[-1]["correct"] is False
    gap = _compared(out)[number]
    assert not gap["inside"] and gap["value"] > 3 * gap["limit"]
    if what == "draft":        # the tokens themselves were the model's own
        assert _compared(out)["widest_logit_gap"]["inside"]
    assert _compared(out)["latent_gap"]["inside"]


def test_a_traced_rehearsal_reads_the_new_counters():
    proc = child("--workload", CELL, "--seed", str(2**31 + 17), "--seconds",
                 "2", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    # the device metrics need a device's timeline; the counters do not
    got = set(last["metrics_reported"])
    assert {"mla_cache_bytes_share", "mtp_accept_rate",
            "held_expert_pair_share", "batch_occupancy", "iter_wall_ms",
            "decode_wait_ms", "prefill_wait_ms"} <= got
    assert not got & {"expert_load_imbalance", "ssm_state_bytes_share",
                      "prefill_chunk_ms", "hybrid_prefill_chunk_ms"}


# ------------------------------------------------------------ the manifest
def test_the_cell_is_listed_as_the_issue_names_it(bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longgen-closed", 1)
    assert {m["name"] for m in bench.end_to_end(cell)} == set(E2E) | \
        {"setup_s"}
    got = {n for n, _ in bench.per_layer(cell)}
    assert set(NEW) <= got
    assert bench.config(CONFIG)["driver"] == "serve-mla-lm"
    assert bench.driver("serve-mla-lm").run
    entry = [c for c in bench.manifest["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert entry["file"] == "perf/configs/joyai-llm-flash.json"


def test_the_manifest_before_this_cell_is_still_there(bench):
    """Every entry the manifest held before this configuration is there
    with the content it had (the three end-to-end lists longer by this cell
    at their end), and this configuration's six metrics list its cell.
    Nothing is said of what comes after them."""
    with open(os.path.join(REPO, "tests", "perf", "data",
                           "manifest_before_joyai.json")) as f:
        before = json.load(f)
    now = bench.manifest
    for key in ("command", "paths", "run_seconds"):
        assert now[key] == before[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in now[group]]
        old = [e["name"] for e in before[group]]
        assert names[:len(old)] == old          # in place, in order
        for was, entry in zip(before[group], now[group]):
            if group == "end_to_end" and was["name"] in E2E:
                cells = entry["workloads"]
                assert cells[:len(was["workloads"])] == was["workloads"]
                assert CELL in cells[len(was["workloads"]):]
                entry = dict(entry, workloads=was["workloads"])
            assert entry == was
    for m in now["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["unit"] == "%"
            reader = bench.layer_metric(m["name"])
            assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) \
                == (m["name"], m["unit"], m["layer"], m["moves"])
    assert set(NEW) <= {m["name"] for m in now["per_layer"]}


def test_the_configuration_carries_the_catalogs_keys(bench):
    """Every number of the catalog's ``config`` under the same key but the
    two ``reduced`` (the values below are the catalog's:
    ``architectures.jsonl``, row ``JoyAI-LLM-Flash``), the published values
    of those two beside them, the deployment and each ``assumed``."""
    cfg = bench.config(CONFIG)
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    for key, value in catalog.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"]) == (10, 16)
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "n_routed_experts": 256}
    assert cfg["router_width"] == 256 and cfg["experts_held"] == [0, 16]
    for word in ("sixteen chips", "four pipeline stages", "16 a chip",
                 "WITHOUT its exchange"):
        assert word in cfg["deployment"], word
    assert len(cfg["assumed"]) >= 6 and "normal(0, 0.1)" in cfg["assumed"][1]
    srv = cfg["serving"]
    assert (srv["slots"], srv["page_size"], srv["prefill_chunk"]) == \
        (40, 128, 2048)
    assert srv["prompt_buckets"][-1] + srv["max_new_tokens"] == 130 * 128
    assert srv["prefix_cache"] is False and srv["max_prefix_tokens"] == 0
    assert set(GAPS) <= set(cfg["tolerance"])
    assert cfg["control"] == "fp8" and \
        cfg["control_cache"] == "float8_e4m3fn"
    drv = bench.driver("serve-mla-lm")
    assert drv.NO_END_TOKEN == -1
    kw = drv._model_kwargs(cfg)
    assert (kw["num_layers"], kw["num_experts"], kw["experts_held"]) == \
        (10, 256, (0, 16))
    # the issue's arithmetic: parameters held, bytes a cached position
    ref, ops = bench.reference(CONFIG), bench.ops_counts(CONFIG)
    n = sum(int(np.prod(s)) for s in ref.tensor_specs(cfg).values())
    assert n == ops.weight_params(cfg)
    assert 3.35e9 < 2 * n < 3.38e9                      # 3.36 GB in bfloat16
    assert ops.attention_params(cfg) - 2 * 2048 - 1536 - 512 == \
        26_345_472                                      # 26.35 M a layer
    assert ops.expert_params(cfg) == 4_718_592
    assert ops.latent_bytes_position(cfg) == 11 * 1152 == 12_672


# ---------------------------------------------------------------- traffic
def test_the_mix_is_as_the_issue_gives_it(bench):
    mix = bench.traffic("longgen-closed")
    assert (mix["kind"], mix["clients"], mix["population"],
            mix["sampling"]) == ("closed_loop_lm", 40, 128, "greedy")
    assert mix["prompt_length"] == {"median": 8192, "sigma": 0.5,
                                    "min": 2048, "max": 14336}
    assert mix["reply_length"] == {"median": 1024, "sigma": 0.5,
                                   "min": 256, "max": 2048}
    a = traffic_lm.RequestStream(mix, 2**31 + 7, 129280)
    b = traffic_lm.RequestStream(mix, 11, 129280)
    n = mix["population"]
    shape = lambda s, at: [(len(s.request(i)[0]), s.request(i)[1])  # noqa: E731
                           for i in range(at, at + n)]
    assert shape(a, 0) == shape(b, 0) and shape(a, n) == shape(b, n)
    assert shape(a, 0) != shape(a, n)            # a new order each pass
    assert not (a.request(5)[0] == b.request(5)[0]).all()
    prompts = np.array([p for p, _ in shape(a, 0)])
    replies = np.array([r for _, r in shape(a, 0)])
    assert 2048 <= prompts.min() and prompts.max() <= 14336
    assert 256 <= replies.min() and replies.max() <= 2048
    assert 7000 < np.median(prompts) < 9000 and 900 < np.median(replies) < 1150
    ids = a.request(3)[0]
    assert ids.min() >= 3 and ids.max() < 129280
    # the configuration's own reckoning: the prompt side (one chunk a pass)
    # and the slot side (every row a burst a pass) bind together at 6.1
    # steps a burst, and the burst is the next whole number past it: the
    # seat is taken in every pass and the tails are the schedule's
    srv = bench.config(CONFIG)["serving"]
    chunks = np.ceil(prompts / srv["prefill_chunk"]).mean()
    binds = replies.mean() / (40 * chunks)
    assert 6.0 < binds < 6.3 and srv["iter_tokens"] == 7
    assert (prompts + replies).max() <= 130 * 128


# ------------------------------------------ readers on hand-made readings
def _stats(scale):
    return {"iterations": 10 * scale,
            "prefill_latent_keys": 9000 * scale,
            "prefill_pairs_all": 16000 * scale,
            "prefill_pairs_held": 1000 * scale, "prefill_calls": 4 * scale,
            "decode_latent_keys": 360_000 * scale,
            "decode_row_steps": 38 * scale, "decode_calls": scale,
            "decode_pairs_all": 6400 * scale,
            "decode_pairs_held": 400 * scale,
            "decode_experts_touched": 150 * scale,
            "decode_expert_layers": 10 * scale,
            "decode_mtp_drafts": 38 * scale,
            "decode_mtp_accepted": 19 * scale,
            "prompt_chunks": 4 * scale, "prompt_tokens": 8000 * scale,
            "prefill_chunk_s": 0.5 * scale}


def _ev(name, start_ms, dur_ms):
    return Event(DEV, "XLA Ops", name, int(start_ms * MS), int(dur_ms * MS))


BURST = ("%while.91 = (s32[]{:T(128)}, s32[40]{0:T(128)S(1)}, s32[40]{0:T("
         "128)}, bf16[5201,128,640]{2,1,0:T(8,128)(2,1)}")
OTHER_LOOP = "%while.12 = (s32[]{:T(128)}, bf16[1,16896,8192]{2,1,0"
KERNEL = "%mla_latent_decode.7 = bf16[40,64,512]{2,1,0:T(8,128)(2,1)}"
PREFILL = "%mla_prefill.3 = bf16[1,2048,4096]{2,1,0:T(8,128)(2,1)}"


def _run(bench, stats1=None, events=None):
    cfg = bench.config(CONFIG)
    trace = None if events is None else TraceSummary(events, chips=1)
    ctx = types.SimpleNamespace(
        bench=bench, peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    return types.SimpleNamespace(
        obs={"stats0": _stats(1), "stats1": stats1 or _stats(3),
             "config": cfg, "iter_tokens": 4, "slots": 40},
        window_s=2.0, e2e={}, trace=trace, ctx=ctx)


def test_counts_on_hand_made_numbers(bench):
    """Two steps in the window, 38 live rows a step, 360,000 cached
    positions a step: the bytes and the operations a step needs."""
    cfg = bench.config(CONFIG)
    ops = bench.ops_counts(CONFIG)
    counts = mla_counts.window_counts(_run(bench))
    assert counts["decode_calls"] == 2 and counts["decode_row_steps"] == 76
    parts = ops.decode_step_parts(cfg, counts)
    assert parts["latent"] == 360_000 * 11 * 576 * 2
    assert parts["experts"] == 150 * 4_718_592 * 2
    assert parts["head"] == 2 * (2048 * 129280 + 2048) * 2
    fixed = ops.fixed_expert_block_params(cfg)
    assert fixed == ops.attention_params(cfg) + 2048 * 256 + 256 + 4_718_592
    assert parts["weights"] == 2 * (
        ops.dense_block_params(cfg) + 10 * fixed + 2 * 2048 * 2048 + 3 * 2048)
    assert ops.decode_step_bytes(cfg, counts) == sum(parts.values())
    # the issue's step: 40 rows at 9,300 positions read 4.7 GB of latents
    # against some 3.3 GB of weights, three fifths of the bytes
    full = dict(counts, decode_latent_keys=2 * 40 * 9300,
                decode_experts_touched=2 * 147)
    p = ops.decode_step_parts(cfg, full)
    assert 4.6e9 < p["latent"] < 4.8e9
    assert 0.55 < p["latent"] / sum(p.values()) < 0.62
    assert 9.0e-3 < sum(p.values()) / 819e9 < 10.5e-3   # 9.7 ms a step
    assert 0.5e12 < ops.decode_step_ops(cfg, full) < 0.8e12
    assert ops.decode_step_parts(cfg, dict(counts, decode_calls=0)) is None
    work, moved = ops.latent_call(cfg, counts)
    assert moved == 360_000 * 576 * 2
    assert work == 2 * 2 * 360_000 * 32 * (2 * 512 + 64)
    assert ops.latent_call(cfg, dict(counts, decode_calls=0)) is None


def test_counter_readers_on_hand_made_counters(bench):
    run = _run(bench)
    read = lambda n: bench.layer_metric(n).read(run)  # noqa: E731
    assert read("mtp_accept_rate") == pytest.approx(50.0)
    assert read("held_expert_pair_share") == pytest.approx(6.25)
    cfg = bench.config(CONFIG)
    parts = bench.ops_counts(CONFIG).decode_step_parts(
        cfg, mla_counts.window_counts(run))
    assert read("mla_cache_bytes_share") == pytest.approx(
        100 * parts["latent"] / sum(parts.values()))
    assert 50 < read("mla_cache_bytes_share") < 100
    # no device timeline: the three device metrics say nothing
    for name in NEW[:3]:
        assert read(name) is None
    # a program without the counters (the parent commit, another model):
    # nothing, no error
    short = {k: v for k, v in _stats(3).items() if k != "decode_latent_keys"}
    for name in NEW:
        assert bench.layer_metric(name).read(_run(bench, stats1=short)) \
            is None
        assert bench.layer_metric(name).read(
            types.SimpleNamespace(obs={}, e2e={}, trace=None)) is None
    # no draft verified, no pair routed: nothing to divide by
    idle = dict(_stats(3), decode_mtp_drafts=_stats(1)["decode_mtp_drafts"],
                decode_pairs_all=_stats(1)["decode_pairs_all"])
    assert bench.layer_metric("mtp_accept_rate").read(
        _run(bench, stats1=idle)) is None
    assert bench.layer_metric("held_expert_pair_share").read(
        _run(bench, stats1=idle)) is None


def test_device_readers_on_a_hand_made_event_list(bench):
    events = [_ev(BURST, 0, 80), _ev(OTHER_LOOP, 80, 5), _ev(PREFILL, 85, 3),
              _ev(KERNEL, 88, 1), _ev(KERNEL.replace(".7", ".8"), 89, 1),
              _ev("%fusion.1 = bf16[80,129280]", 90, 10), _ev(BURST, 100, 80)]
    run = _run(bench, events=events)
    ops = bench.ops_counts(CONFIG)
    cfg = run.obs["config"]
    counts = mla_counts.window_counts(run)
    # two bursts of four steps in 160 ms: 20 ms a step
    least = max(ops.decode_step_bytes(cfg, counts) / 819e9,
                ops.decode_step_ops(cfg, counts) / 197e12)
    assert bench.layer_metric("mla_decode_step_roofline_share").read(run) \
        == pytest.approx(100 * least / 0.020)
    # two events of the kernel, 1 ms each, of 180 ms busy
    assert bench.layer_metric("mla_latent_time_share").read(run) \
        == pytest.approx(100 * 0.002 / 0.180)
    work, moved = ops.latent_call(cfg, counts)
    assert bench.layer_metric("mla_latent_roofline_share").read(run) \
        == pytest.approx(100 * max(work / 197e12, moved / 819e9) / 0.001)
    # neither the chunk program's own loops nor another slot count's burst,
    # and no event of the kernel: nothing
    none = _run(bench, events=[_ev(OTHER_LOOP, 0, 10), _ev(PREFILL, 10, 4)])
    for name in NEW[:3]:
        assert bench.layer_metric(name).read(none) is None

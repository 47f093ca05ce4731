"""The cell PR 44 added, rehearsed on the CPU: the hyper-connected
latent-attention serving cell agrees with its plain reference in its served
tokens, in its module's drafts and in the latents it caches; both controls
(float8 weights, a float8 latent cache) do not; a program altered where the
stream is mixed or the queries are scaled reads not correct over a FIXED
set of requests (not a second of traffic: PERF.md 7 (ba)); the cell, the
mix and the configuration are as the issue gives them; and the new counts
and readers give known answers on hand-made counters and a hand-made event
list. Of the manifest these tests say only what is true of THIS PR's
entries, so that the next cell turns none of them red (PERF.md 7 (p))."""

import json
import types

import numpy as np
import pytest

from perf.harness import mhc_counts, traffic_lm
from perf.harness.loader import Benchmark
from perf.harness.trace import Event, TraceSummary

from _runs import REPO, child, in_process

CELL = "xing4.0-29b-a4b.longdoc-closed"
CONFIG = "xing4.0-29b-a4b"
MS = 1_000_000
DEV = "/device:TPU:0"
NEW = ["mhc_time_share", "mhc_roofline_share", "mhc_stream_bytes_share",
       "mla_prefill_time_share", "mla_prefill_roofline_share"]
LISTED = ["queue_wait_p95_ms", "seat_wait_p95_ms", "prefill_service_p95_ms",
          "first_token_deliver_p95_ms", "pass_wall_p95_ms",
          "decode_wait_p95_ms", "burst_ahead_share"]
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
    # a float32 program differs from the reference by its sums' order alone
    assert numbers["mtp_logit_gap"]["positions"] > 8
    assert numbers["mtp_logit_gap"]["value"] == 0.0
    latent = numbers["latent_gap"]
    assert latent["positions"] >= 3 and latent["value"] < 1e-5
    assert out[-1]["correct"] is True and out[-1]["failed"] == 0
    assert set(E2E) | {"setup_s"} <= set(out[-1]["metrics_reported"])
    counts = [r for r in out if r.get("note") == "window_counts"][0]
    assert counts["prompt_chunks"] > 0 and counts["prompt_tokens"] > 0
    assert counts["decode_mtp_drafts"] == counts["decode_row_steps"] > 0
    # every expert is held: no pair falls on another chip
    assert counts["decode_pairs_held"] == counts["decode_pairs_all"] > 0
    # two mixers in each of three blocks and two in the module a live
    # token (the module's first position of a prompt lies before 0)
    assert 0 < counts["prefill_mhc_pairs"] <= 8 * counts["prompt_tokens"]
    assert counts["prefill_mhc_pairs"] > 6 * counts["prompt_tokens"]
    assert counts["decode_mhc_pairs"] <= 8 * 2 * counts["decode_row_steps"]
    assert counts["prefill_scored_pairs"] >= counts["prompt_tokens"]
    assert counts["decode_scored_pairs"] == 0


def test_both_controls_fail_the_check(capsys):
    """The float8-weights reference falls outside the limits of the served
    tokens and of the drafts; the program with a float8 latent cache falls
    outside the limit of the latents."""
    code, out = in_process(capsys, "--workload", CELL, "--seed",
                           str(2**31 + 43), "--seconds", "1", "--rehearse",
                           "--control")
    assert code == 0 and out[-1]["found_not_correct"] is True
    program, control = _compared(out), _compared(out, of="control")
    for number in GAPS[:3]:
        assert program[number]["inside"] and program[number]["value"] == 0
    assert not control["mean_logit_gap"]["inside"]
    assert not control["mtp_logit_gap"]["inside"]
    assert program["latent_gap"]["inside"]
    cache = _compared(out, of="control_cache")
    assert not cache["latent_gap"]["inside"]
    assert cache["latent_gap"]["value"] > 10 * cache["latent_gap"]["limit"]
    said = [r for r in out if r.get("note") == "control_cache"][0]
    assert said["found_not_correct"] and "latent_gap" in said["outside"]


# ------------------------------------- a fixed set of requests, altered
LENGTHS = ((5, 5), (23, 6), (9, 2), (38, 6), (16, 4), (31, 6))


def _served_gaps(bench, seed=5):
    """The rehearsal's program over SIX fixed requests, through the
    driver's own builder and the scheduler, and the numbers the driver's
    comparison reads of them: ``ref.served_gaps`` a request."""
    cfg = bench.config(CONFIG)
    cfg = dict(cfg, **cfg["rehearse"]["config"])
    ref = bench.reference(CONFIG)
    mla = bench.driver("serve-mla-lm")
    mla._model_kwargs = bench.driver("serve-mhc-lm")._model_kwargs
    _, _, bat = mla._build_program(cfg, ref, seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(3, cfg["vocab_size"], n).astype(np.int32)
               for n, _ in LENGTHS]
    try:
        futs = [bat.submit(p, max_new_tokens=n)
                for p, (_, n) in zip(prompts, LENGTHS)]
        replies = [f.result(timeout=300) for f in futs]
    finally:
        bat.stop()
    served, drafted = [], []
    for p, f, toks in zip(prompts, futs, replies):
        a, b = ref.served_gaps(seed, cfg, p, toks, list(f.drafts or []),
                               pad_to=cfg["check"]["pad_to"])
        served.append(a), drafted.append(b)
    served, drafted = np.concatenate(served), np.concatenate(drafted)
    assert len(served) == sum(n for _, n in LENGTHS) and len(drafted) > 8
    return {"widest_logit_gap": float(served.max()),
            "mean_logit_gap": float(served.mean()),
            "mtp_logit_gap": float(drafted.mean())}, cfg["tolerance"]


def _alter(monkeypatch, what):
    from mxnet_tpu.ops import hyper_connection as hc
    from mxnet_tpu.ops import mla

    if what == "one_iteration":
        real = hc.sinkhorn
        monkeypatch.setattr(hc, "sinkhorn",
                            lambda M, iters, eps: real(M, 1, eps))
    elif what == "post_without_its_2":
        real = hc.maps

        def maps(tilde, cfg):
            pre, post, res = real(tilde, cfg)
            return pre, post / 2, res

        monkeypatch.setattr(hc, "maps", maps)
    elif what == "mscale_dropped":
        monkeypatch.setattr(mla, "yarn_mscale", lambda factor, m=1.0: 1.0)
    else:                       # "first_stream_for_the_sum"
        def leave(s, y, cfg):
            X = hc.mix(s["X"], y, s["hres"], s["hpost"], cfg.n)
            return X[:, :X.shape[1] // cfg.n]

        monkeypatch.setattr(hc, "leave", leave)


def test_the_fixed_requests_agree_unaltered(bench):
    numbers, limits = _served_gaps(bench)
    assert all(numbers[k] <= limits[k] for k in numbers), numbers
    assert numbers["mean_logit_gap"] == 0.0


@pytest.mark.parametrize("what", [
    "one_iteration", "post_without_its_2", "mscale_dropped",
    "first_stream_for_the_sum"])
def test_what_is_altered_where_it_is_produced_is_not_correct(
        bench, monkeypatch, what):
    """One Sinkhorn pass for twenty, ``H_post`` without its 2, YaRN's
    ``mscale`` dropped from the softmax scale, the
    stream's end its first stream alone: each reads outside a limit, of
    the served tokens or of the drafts, by three times and more. (The issue's "a mean for a sum" at
    the stream's end is the sum to a scale of 4, which the final norm and
    the module's two norms take out to 1e-7: no comparison on logits can
    see it, so the test alters the end in a way that one can.)"""
    _alter(monkeypatch, what)
    numbers, limits = _served_gaps(bench)
    assert max(numbers[k] / limits[k] for k in (
        "mean_logit_gap", "mtp_logit_gap")) > 3, (what, numbers)


def test_a_traced_rehearsal_reads_the_new_counters():
    proc = child("--workload", CELL, "--seed", str(2**31 + 17), "--seconds",
                 "2", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    # the device metrics need a device's timeline; the counters do not
    got = set(last["metrics_reported"])
    assert {"mhc_stream_bytes_share", "batch_occupancy", "iter_wall_ms",
            "burst_ahead_share", "pass_wall_p95_ms"} <= got
    assert not got & {"mla_cache_bytes_share", "mtp_accept_rate",
                      "expert_load_imbalance", "prefill_chunk_ms"}


# ------------------------------------------------------------ the manifest
def test_the_cell_is_listed_as_the_issue_names_it(bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longdoc-closed", 1)
    assert {m["name"] for m in bench.end_to_end(cell)} == set(E2E) | \
        {"setup_s"}
    got = {n for n, _ in bench.per_layer(cell)}
    assert set(NEW) | set(LISTED) <= got
    # joyai's six stay joyai's: the decode kernel is guarded there
    assert not got & {"mla_latent_time_share", "mla_cache_bytes_share",
                      "mtp_accept_rate", "held_expert_pair_share"}
    assert bench.config(CONFIG)["driver"] == "serve-mhc-lm"
    assert bench.driver("serve-mhc-lm").run
    entry = [c for c in bench.manifest["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "perf/configs/xing4.0-29b-a4b.json"
    assert entry["source"] == bench.config(CONFIG)["source"] == \
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/" \
        "config.json"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_this_prs_metrics_list_its_cell(bench):
    by = {m["name"]: m for m in bench.manifest["per_layer"]}
    assert set(NEW) <= set(by)
    for name in NEW:
        m = by[name]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        reader = bench.layer_metric(name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for name in LISTED:
        assert CELL in by[name]["workloads"]
    for m in bench.manifest["end_to_end"]:
        if m["name"] in E2E:
            assert CELL in m["workloads"]


def test_the_configuration_carries_the_catalogs_keys(bench):
    """Every key of the catalog's ``config`` (``architectures.jsonl``, row
    ``Xing4.0-29B-A4B``; the values below are the catalog's) unchanged but
    ``num_hidden_layers``, with the published value beside it, the
    deployment, each ``assumed``, and the issue's arithmetic."""
    cfg = bench.config(CONFIG)
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "max_position_embeddings": 262144,
        "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in catalog.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 6
    assert cfg["published"] == {"num_hidden_layers": 40}
    for word in ("ep_size 1", "seven pipeline stages", "the whole stream"):
        assert word in cfg["deployment"], word
    assert len(cfg["assumed"]) >= 8
    for line in cfg["assumed"]:
        assert "\n" not in line
    assert "accumulated_in_float32" in cfg["precision"] and \
        "Sinkhorn" in cfg["precision"]["accumulated_in_float32"]
    srv = cfg["serving"]
    assert (srv["slots"], srv["page_size"], srv["prefill_chunk"],
            srv["max_new_tokens"]) == (12, 128, 2048, 256)
    assert srv["prompt_buckets"][-1] + srv["max_new_tokens"] == 258 * 128
    assert srv["prefix_cache"] is False and srv["max_prefix_tokens"] == 0
    assert set(GAPS) <= set(cfg["tolerance"])
    assert cfg["control"] == "fp8" and \
        cfg["control_cache"] == "float8_e4m3fn"
    assert cfg["check"]["pad_to"] >= 258 * 128
    kw = bench.driver("serve-mhc-lm")._model_kwargs(cfg)
    assert (kw["num_layers"], kw["num_experts"], kw["hc_mult"],
            kw["hc_clamp"]) == (6, 64, 4, (-30, 30))
    assert kw["rope_scaling"]["factor"] == 64
    # the issue's arithmetic: parameters held, bytes a cached position
    ref, ops = bench.reference(CONFIG), bench.ops_counts(CONFIG)
    n = sum(int(np.prod(s)) for s in ref.tensor_specs(cfg).values())
    assert n == ops.weight_params(cfg)
    assert 9.88e9 < 2 * n < 9.91e9                      # 9.90 GB in bfloat16
    assert ops.attention_params(cfg) - 2 * 3584 - 768 - 512 == \
        3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
    assert ops.expert_params(cfg) == 3 * 3584 * 1024
    assert 2 * (ops.mixer_params(cfg) - 27) == 2 * 14336 * 24   # 0.69 M
    assert ops.caches(cfg) * 640 * 2 == 8960
    # 12 slots x 258 pages of 128 positions in seven pools: 3.55 GB
    assert 3.54e9 < 12 * 258 * 128 * 8960 < 3.56e9
    assert ops.mhc_pair(cfg) == (2 * 14336 * 24, 10 * 3584 * 2)


# ---------------------------------------------------------------- traffic
def test_the_mix_is_as_the_issue_gives_it(bench):
    mix = bench.traffic("longdoc-closed")
    assert (mix["kind"], mix["clients"], mix["population"],
            mix["sampling"], mix["drain_s"]) == \
        ("closed_loop_lm", 12, 48, "greedy", 60)
    assert mix["prompt_length"] == {"median": 16384, "sigma": 0.4,
                                    "min": 8192, "max": 32768}
    assert mix["reply_length"] == {"median": 128, "sigma": 0.5,
                                   "min": 32, "max": 256}
    a = traffic_lm.RequestStream(mix, 2**31 + 7, 131072)
    b = traffic_lm.RequestStream(mix, 11, 131072)
    n = mix["population"]
    shape = lambda s, at: [(len(s.request(i)[0]), s.request(i)[1])  # noqa: E731
                           for i in range(at, at + n)]
    assert shape(a, 0) == shape(b, 0) and shape(a, n) == shape(b, n)
    assert shape(a, 0) != shape(a, n)            # a new order each pass
    assert not (a.request(5)[0] == b.request(5)[0]).all()
    prompts = np.array([p for p, _ in shape(a, 0)])
    replies = np.array([r for _, r in shape(a, 0)])
    assert 8192 <= prompts.min() and prompts.max() <= 32768
    assert 32 <= replies.min() and replies.max() <= 256
    assert 15000 < np.median(prompts) < 18000
    assert 100 < np.median(replies) < 160
    assert prompts.max() > 2 * 14336             # rows twice joyai's longest
    ids = a.request(3)[0]
    assert ids.min() >= 3 and ids.max() < 131072
    srv = bench.config(CONFIG)["serving"]
    assert (prompts + replies).max() <= 258 * 128
    chunks = np.ceil(prompts / srv["prefill_chunk"]).mean()
    assert 9.0 < chunks < 9.5                    # "about nine chunks"
    # prompt side and slot side bind at 1.5 steps a burst
    assert 1.4 < replies.mean() / (12 * chunks) < 1.6


# ------------------------------------------ readers on hand-made readings
def _stats(scale):
    return {"iterations": 10 * scale,
            "prefill_latent_keys": 40_000 * scale,
            "prefill_pairs_all": 4 * 8000 * 5 * scale,
            "prefill_experts_touched": 4 * 5 * 64 * scale,
            "prefill_calls": 4 * scale,
            "prefill_mhc_pairs": 14 * 8000 * scale,
            "prefill_scored_pairs": 60_000_000 * scale,
            "decode_mhc_pairs": 14 * 2 * 11 * 8 * scale,
            "decode_scored_pairs": 0, "decode_calls": 8 * scale,
            "decode_latent_keys": 8 * 200_000 * scale,
            "prompt_chunks": 4 * scale, "prompt_tokens": 8000 * scale,
            "prefill_chunk_s": 0.4 * scale}


def _ev(name, start_ms, dur_ms):
    return Event(DEV, "XLA Ops", name, int(start_ms * MS), int(dur_ms * MS))


T = "{1,0:T(8,128)(2,1)}"


def _mix(site, rows):
    return (f"%mhc_mix.{site} = (bf16[{rows},14336]{T}, bf16[{rows},3584]{T}"
            f", f32[{rows},128]{{1,0:T(8,128)}}) custom-call(")


LEAVE_C = "%mhc_leave.2 = bf16[2048,3584]" + T + " custom-call("
PREFILL = "%mla_prefill.{} = bf16[1,2048,4096]{{2,1,0:T(8,128)(2,1)}}"
BURST = "%while.91 = (s32[]{:T(128)}, s32[12]{0:T(128)S(1)}"


def _run(bench, stats1=None, events=None):
    cfg = bench.config(CONFIG)
    trace = None if events is None else TraceSummary(events, chips=1)
    ctx = types.SimpleNamespace(
        bench=bench, peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    return types.SimpleNamespace(
        obs={"stats0": _stats(1), "stats1": stats1 or _stats(3),
             "config": cfg, "iter_tokens": 2, "slots": 12},
        window_s=2.0, e2e={}, trace=trace, ctx=ctx)


def test_counts_on_hand_made_numbers(bench):
    """Eight chunks in the window, 2,000 live tokens a chunk, 14 mixers a
    token; sixteen decode steps of 11 live rows."""
    cfg = bench.config(CONFIG)
    ops = bench.ops_counts(CONFIG)
    counts = mhc_counts.window_counts(_run(bench))
    assert counts["prefill_calls"] == 8 and counts["decode_calls"] == 16
    work, moved = ops.mhc_call(cfg, counts, "prefill")
    assert moved == 14 * 2000 * 10 * 3584 * 2           # 2.0 GB a chunk
    assert work == 14 * 2000 * 2 * 14336 * 24
    work_d, moved_d = ops.mhc_call(cfg, counts, "decode")
    assert moved_d == 14 * 22 * 10 * 3584 * 2
    assert ops.mhc_call(cfg, dict(counts, decode_calls=0), "decode") is None
    # the issue's chunk: 2,048 tokens, 14 mixers: 2.06 GB, 2.5 ms at the peak
    full = dict(counts, prefill_mhc_pairs=8 * 14 * 2048)
    assert 2.05e9 < ops.mhc_call(cfg, full, "prefill")[1] < 2.06e9
    # one call of the chunk's attention: 7 caches share 7 x 15 M - 2,000
    # scored pairs a chunk, every head through 192 + 128 a pair
    work, moved = ops.prefill_call(cfg, counts)
    pairs = (7 * 15_000_000 - 2000) / 7
    assert work == pytest.approx(2 * pairs * 32 * 320)
    assert moved == pytest.approx((10_000 * (32 * 256 + 64)
                                   + 2000 * 32 * 320) * 2)
    assert ops.prefill_call(cfg, dict(counts, prefill_calls=0)) is None
    parts = ops.chunk_parts(cfg, counts)
    assert parts["stream"] == 14 * 2000 * 10 * 3584 * 2
    # five expert blocks were counted, the module's among them: four read
    assert parts["experts"] == 4 * 64 * ops.expert_params(cfg) * 2
    assert parts["head"] == (3584 * 131072 + 3584) * 2
    assert parts["latents"] == 7 * 12_000 * 576 * 2
    assert parts["expansion"] == 7 * 2 * 10_000 * 8192 * 2
    # every weight is counted once somewhere: but for the embedding's other
    # rows and the head's norm nothing of weight_params is left out
    fixed = parts["weights"] / 2 - 2000 * 3584
    assert fixed + 5 * 64 * ops.expert_params(cfg) + 2 * 3584 * 131072 \
        + 3584 == ops.weight_params(cfg)        # (the module's 64 too)
    assert ops.chunk_parts(cfg, dict(counts, prefill_calls=0)) is None


def test_counter_readers_on_hand_made_counters(bench):
    run = _run(bench)
    cfg = bench.config(CONFIG)
    parts = bench.ops_counts(CONFIG).chunk_parts(
        cfg, mhc_counts.window_counts(run))
    share = bench.layer_metric("mhc_stream_bytes_share").read(run)
    assert share == pytest.approx(100 * parts["stream"]
                                  / sum(parts.values()))
    assert 5 < share < 40
    # no device timeline: the four device metrics say nothing
    for name in NEW:
        if name != "mhc_stream_bytes_share":
            assert bench.layer_metric(name).read(run) is None
    # a program without the counts (the parent commit, another model):
    # nothing, no error
    short = {k: v for k, v in _stats(3).items() if k != "prefill_mhc_pairs"}
    for name in NEW:
        assert bench.layer_metric(name).read(_run(bench, stats1=short)) \
            is None
        assert bench.layer_metric(name).read(
            types.SimpleNamespace(obs={}, e2e={}, trace=None)) is None


def test_device_readers_on_a_hand_made_event_list(bench):
    """Two chunks and three decode steps in the stretch: the chunk program
    names two mixing calls and a closing one (3 ms, 3 ms, 1 ms a chunk),
    the burst's step two (0.02 ms each), and a chunk seven attention calls
    of which two are listed."""
    events = []
    for k, at in enumerate((0, 100)):
        events += [_ev(_mix(5, 2048), at, 3), _ev(_mix(6, 2048), at + 3, 3),
                   _ev(LEAVE_C, at + 6, 1),
                   _ev(PREFILL.format(3), at + 7, 10),
                   _ev(PREFILL.format(4), at + 17, 14),
                   _ev("%fusion.9 = bf16[2048,3584]", at + 31, 29)]
    for k in range(3):
        events += [_ev(_mix(5, 32), 60 + 10 * k, 0.02),
                   _ev(_mix(8, 32), 61 + 10 * k, 0.02)]
    events.append(_ev(BURST, 200, 40))
    run = _run(bench)
    run.trace = TraceSummary(events, chips=1)
    found = mhc_counts.dispatches(run.trace, mhc_counts.MHC_KERNEL, 2048)
    assert found["prefill"] == (pytest.approx(0.014), 2)
    assert found["decode"] == (pytest.approx(0.00012), 3)
    busy = 2 * (7 + 24 + 29) / 1e3 + 0.00012 + 0.040
    assert run.trace.busy_s_of(0) == pytest.approx(busy)
    assert bench.layer_metric("mhc_time_share").read(run) == \
        pytest.approx(100 * 0.01412 / busy)
    ops, cfg = bench.ops_counts(CONFIG), run.obs["config"]
    counts = mhc_counts.window_counts(run)
    least = 2 * ops.mhc_call(cfg, counts, "prefill")[1] / 819e9 \
        + 3 * ops.mhc_call(cfg, counts, "decode")[1] / 819e9
    assert bench.layer_metric("mhc_roofline_share").read(run) == \
        pytest.approx(100 * least / 0.01412)
    assert bench.layer_metric("mla_prefill_time_share").read(run) == \
        pytest.approx(100 * 0.048 / busy)
    work, moved = ops.prefill_call(cfg, counts)
    assert bench.layer_metric("mla_prefill_roofline_share").read(run) == \
        pytest.approx(100 * max(work / 197e12, moved / 819e9) / 0.012)
    # a trace without such events (another model's, the parent's): nothing
    none = _run(bench)
    none.trace = TraceSummary([_ev(BURST, 0, 10)], chips=1)
    for name in NEW:
        if name != "mhc_stream_bytes_share":
            assert bench.layer_metric(name).read(none) is None

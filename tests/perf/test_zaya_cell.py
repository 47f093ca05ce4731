"""The cell PR 40 added, rehearsed on the CPU: the compressed-latent model's
serving cell agrees with its plain reference in its served tokens, in the
keys and values its first layer caches and in the tail and value half every
layer's slot keeps; both controls (float8 weights, a float8 K/V cache) do
not; a token altered where it is produced, or a tail taken from the wrong
position at a chunk boundary, reads not correct; the configuration carries
the catalog's keys; and the new per-layer readers and counts give known
answers on hand-made counters and a hand-made event list."""

import json
import types

import numpy as np
import pytest

from perf.harness import cca_counts, traffic_lm
from perf.harness.loader import Benchmark
from perf.harness.trace import Event, TraceSummary

from _runs import REPO, child, in_process

CELL = "zaya1-8b.think-closed"
CONFIG = "zaya1-8b"
MS = 1_000_000
DEV = "/device:TPU:0"
NEW = ["cca_decode_step_roofline_share", "cca_cache_bytes_share",
       "top1_experts_touched_share", "top1_expert_load_imbalance",
       "cca_moe_roofline_share"]
DEVICE = (NEW[0], NEW[4])
TAILS = ["queue_wait_p95_ms", "seat_wait_p95_ms", "prefill_service_p95_ms",
         "first_token_deliver_p95_ms", "pass_wall_p95_ms",
         "decode_wait_p95_ms"]
GAPS = ("widest_logit_gap", "mean_logit_gap", "page_gap", "page_gap_widest",
        "tail_gap")
E2E = ("serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms")

# the catalog's row ``ZAYA1-8B`` (architectures.jsonl beside the
# model-configs guide), its ``config`` copied here: every number under the
# same key in the configuration's file, but the depth
CATALOG = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "layer_types": ["hybrid"] * 40,
    "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}
SOURCE = "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"


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
    assert set(GAPS) <= set(numbers) and len(numbers) >= 9
    assert all(r["inside"] for r in numbers.values()), numbers
    assert numbers["widest_logit_gap"]["positions"] > 8
    assert numbers["widest_logit_gap"]["value"] == 0.0
    # the pages and the tail: a float32 program differs from the reference
    # by its sums' order alone
    assert numbers["page_gap"]["positions"] >= 4
    assert numbers["page_gap"]["prompt"] >= 3
    for number in GAPS[2:]:
        assert numbers[number]["value"] < 1e-5
    routing = [r for r in out if r.get("note") == "routing"][0]
    assert 0.0 <= routing["near_tie_share"] < 0.5
    assert out[-1]["correct"] is True and out[-1]["failed"] == 0
    assert set(E2E) | {"setup_s"} <= set(out[-1]["metrics_reported"])
    counts = [r for r in out if r.get("note") == "window_counts"][0]
    assert counts["prompt_chunks"] > 0 and counts["decode_calls"] > 0
    assert counts["prefill_chunk_tokens"] == counts["prompt_tokens"]
    assert counts["prefill_calls"] == counts["prompt_chunks"]
    # at most every expert of every layer in every step, at least one
    assert counts["decode_calls"] * 3 <= counts["decode_experts_touched"] \
        <= counts["decode_calls"] * 3 * 4
    state = [r for r in out if r.get("note") == "state_bytes"][0]
    # 3 slots x 12 pages and the trash page, K and V, 3 layers: a page of
    # 4 positions x 2 heads x 16; a tail of 2 x 96 and a value half of 16
    assert state == {"note": "state_bytes", "encoder_memory": 0,
                     "pages": 37 * (4 * 2 * 16 * 4) * 2 * 3,
                     "slot_arrays": 3 * 3 * (2 * 96 + 16) * 4}


@pytest.mark.parametrize("seed", [2**31 + 43])
def test_both_controls_fail_the_check(capsys, seed):
    """The float8-weights reference falls outside the limits of the served
    tokens; the program with a float8 K/V cache falls outside the limits
    of the pages."""
    code, out = in_process(capsys, "--workload", CELL, "--seed", str(seed),
                           "--seconds", "1", "--rehearse", "--control")
    assert code == 0 and out[-1]["found_not_correct"] is True
    program, control = _compared(out), _compared(out, of="control")
    for number in GAPS:
        assert program[number]["inside"]
    assert not control["widest_logit_gap"]["inside"]
    assert not control["mean_logit_gap"]["inside"]
    cache = _compared(out, of="control_cache")
    assert not cache["page_gap"]["inside"]
    assert cache["page_gap"]["value"] > 10 * cache["page_gap"]["limit"]
    assert not cache["page_gap_widest"]["inside"]
    said = [r for r in out if r.get("note") == "control_cache"][0]
    assert said["found_not_correct"] and "page_gap" in said["outside"]
    assert said["cache_dtype"] == "float8_e4m3fn"


def _alter(monkeypatch, what):
    from mxnet_tpu.gluon.model_zoo.zaya import ZayaLM

    if what == "token":
        from mxnet_tpu.serving.batcher import GenerationResult

        real = GenerationResult._resolve
        monkeypatch.setattr(
            GenerationResult, "_resolve", lambda self, tokens: real(
                self, [3 + (int(t) * 7 + 11) % 100 for t in tokens]))
    elif what == "tail_off_by_one":
        # the tail of the position BEFORE the row's last real one
        import jax.numpy as jnp

        real = ZayaLM._kept
        monkeypatch.setattr(ZayaLM, "_kept", lambda self, x, at: real(
            self, x, jnp.maximum(at - 1, 0)))
    else:                               # "merge": gains and biases dropped
        real = ZayaLM._w
        monkeypatch.setattr(ZayaLM, "_w", lambda self, name: (
            real(self, name) * 0 + (1 if name.endswith("gain") else 0)
            if "_res_" in name else real(self, name)))


@pytest.mark.parametrize("what,number", [
    ("token", "widest_logit_gap"), ("tail_off_by_one", "page_gap_widest"),
    ("merge", "tail_gap")])
def test_what_is_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch, what, number):
    """A served token altered on its way out, a chunk program that takes
    the tail from the wrong position (off by one: whichever request ended
    last, the key after its prompt is wrong; the chunk's end in place of
    the last real token is ``tests/test_zaya_lm.py``'s, where the prompt is
    chosen), a merge without its gains and biases: each reads not
    ``correct`` by the number that looks at it."""
    _alter(monkeypatch, what)
    code, out = in_process(capsys, "--workload", CELL, "--seed", "5",
                           "--seconds", "1", "--rehearse")
    assert code == 0 and out[-1]["correct"] is False
    gap = _compared(out)[number]
    assert not gap["inside"] and gap["value"] > 3 * gap["limit"]
    if what == "merge":
        # the first layer's tail is made before any merge: the number sees
        # one where a layer past the first is held (no near tie upstream)
        assert gap["tail_layers_held"] >= 2
    if what.startswith("tail"):
        # a wrong key at a chunk's boundary, and the pages for good
        assert not _compared(out)["page_gap"]["inside"]
        assert gap["value"] > 0.05


def _margins(near):
    """``(5, 8)`` router margins, wide everywhere but at the ``(layer,
    position)`` pairs of ``near``."""
    margins = np.full((5, 8), 0.3)
    for layer, position in near:
        margins[layer, position] = 0.001
    return margins


@pytest.mark.parametrize("near,at,held", [
    ((), 7, [1, 1, 1, 1, 1]),                  # no near tie: every layer
    (((1, 7),), 7, [1, 1, 0, 0, 0]),           # the layers past it are not
    (((0, 6),), 7, [1, 0, 0, 0, 0]),           # the position before counts
    (((2, 6), (3, 7)), 7, [1, 1, 1, 0, 0]),    # the first one decides
    (((0, 5), (1, 3), (2, 0)), 7, [1, 1, 1, 1, 1]),   # other positions do not
    (((4, 7),), 7, [1, 1, 1, 1, 1]),           # nothing lies past the last
    (((0, 0),), 0, [1, 0, 0, 0, 0]),           # a sequence of one position
    (((0, 1),), 0, [1, 1, 1, 1, 1]),           # and what comes after it
])
def test_the_tail_is_held_as_far_as_the_routing_upstream_is_settled(
        bench, near, at, held):
    """A layer's tail at a position is made of the residual there and at
    the position before: it is held against the reference's while no layer
    below it routed either of the two near a tie; the first always is."""
    driver = bench.driver("serve-cca-lm")
    got = driver.settled_layers(_margins(near), at, 0.02)
    assert got.dtype == bool and got.tolist() == [bool(h) for h in held]


def test_a_flipped_expert_at_the_last_position_is_not_a_wrong_tail(bench):
    """What the stopped scheduler left, hand-made: pages and a first
    layer's tail as the reference has them, and from the third layer on a
    tail a whole expert term off, where the reference's router stood near
    a tie in the second layer at the request's last position. The judged
    number reads the held layers (0: they agree); the mean over every
    layer is said beside it. Without the near tie the same tails are
    judged whole and read not correct."""
    driver = bench.driver("serve-cca-lm")
    cfg = bench.config(CONFIG)
    cfg = dict(cfg, num_key_value_heads=2,
               serving=dict(cfg["serving"], page_size=4), check={})
    rng = np.random.default_rng(3)
    n, layers, ch, d = 6, 5, 24, 4
    want = {"k": rng.normal(size=(n, 2, d)), "v": rng.normal(size=(n, 2, d)),
            "tails": rng.normal(size=(layers, 2, ch)),
            "halves": rng.normal(size=(layers, d))}
    request = types.SimpleNamespace(prompt=[5, 6, 7], tokens=[8, 9, 10, 11],
                                    index=0)     # three steps fed: 6

    def reference(margins):
        def hidden(seed, cfg, seq, tap=None, pad_to=None):
            assert len(seq) == n and tap["tail_at"] == n - 1
            tap.update(k={0: want["k"]}, v={0: want["v"]},
                       tails=want["tails"], halves=want["halves"],
                       margins=margins)
        return types.SimpleNamespace(hidden=hidden, ROUTE_MARGIN=0.02)

    pools = {name: np.zeros((3, 4 * 2, d)) for name in "kv"}
    for name in "kv":
        flat = want[name].reshape(n * 2, d)
        pools[name][2, :8], pools[name][1, :4] = flat[:8], flat[8:]
    tail = 10 * rng.normal(size=(layers, 2, 2, ch))    # slot 0: another's
    half = 10 * rng.normal(size=(layers, 2, d))
    tail[:, 1], half[:, 1] = want["tails"], want["halves"]
    tail[2:, 1] *= 1.15                     # a whole expert term off
    half[2:, 1] *= 1.15
    read = {"k": pools["k"], "v": pools["v"], "tail": tail, "half": half,
            "last": request, "settled": request, "iter_tokens": 1}
    numbers, more = driver.cache_and_tail_gaps(
        reference(_margins([(1, n - 1)])), 7, cfg, read)
    assert numbers["page_gap"] == 0.0 and numbers["page_gap_widest"] == 0.0
    assert numbers["tail_gap"] == 0.0 and more["tail_layers_held"] == 2
    assert more["tail_gap_every_layer"] == pytest.approx(0.15 * 3 / 5)
    numbers, more = driver.cache_and_tail_gaps(
        reference(_margins([])), 7, cfg, read)
    assert more["tail_layers_held"] == 5
    assert numbers["tail_gap"] == pytest.approx(0.15 * 3 / 5) \
        and numbers["tail_gap"] > cfg["tolerance"]["tail_gap"]
    # a tail from a wrong place is wrong in the first layer too, which is
    # held whatever the router did
    tail[0, 1] = rng.normal(size=(2, ch))
    numbers, more = driver.cache_and_tail_gaps(
        reference(_margins([(0, n - 1)])), 7, cfg, read)
    assert more["tail_layers_held"] == 1
    assert numbers["tail_gap"] > 3 * cfg["tolerance"]["tail_gap"]


def test_a_traced_rehearsal_reads_the_new_counters():
    proc = child("--workload", CELL, "--seed", str(2**31 + 17), "--seconds",
                 "2", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    # the device metrics need a device's timeline; the counters do not
    got = set(last["metrics_reported"])
    assert {"cca_cache_bytes_share", "top1_experts_touched_share",
            "top1_expert_load_imbalance", "batch_occupancy", "iter_wall_ms",
            "decode_wait_ms", "prefill_wait_ms"} | set(TAILS) <= got
    assert not got & {"expert_load_imbalance", "ssm_state_bytes_share",
                      "mla_cache_bytes_share", "loop_weight_bytes_share",
                      "prefill_chunk_ms", "hybrid_prefill_chunk_ms"}


# ------------------------------------------------------------ the manifest
def test_the_cell_is_listed_as_the_issue_names_it(bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "think-closed", 1)
    assert len(cell["why"]) <= 200
    assert {m["name"] for m in bench.end_to_end(cell)} == set(E2E) | \
        {"setup_s"}
    got = {n for n, _ in bench.per_layer(cell)}
    assert set(NEW) | set(TAILS) <= got
    listed = [c for c in bench.manifest["configs"] if c["name"] == CONFIG][0]
    assert listed["source"] == SOURCE
    assert listed["file"] == "perf/configs/zaya1-8b.json"
    assert listed["reduced"] == ["num_hidden_layers"]
    assert len(listed["why"]) <= 200


def test_this_prs_own_entries_are_where_they_were_appended(bench):
    """Only of this PR's own entries: later PRs append behind them."""
    m = bench.manifest
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
    assert {by_name[n]["layer"] for n in NEW} == \
        {"kernels", "attention", "expert layer"}
    assert {by_name[n]["moves"] for n in NEW} == \
        {"tpot_p95_ms", "serve_tokens_per_s"}
    assert [by_name[n]["source"] for n in NEW] == [
        "device_trace", "program_counter", "program_counter",
        "program_counter", "device_trace"]
    for group, names in (("end_to_end", E2E), ("per_layer", TAILS)):
        for e in m[group]:
            if e["name"] in names:
                assert CELL in e["workloads"]
    assert "setup_s" in {e["name"] for e in m["end_to_end"]
                         if "workloads" not in e}


def test_the_configuration_carries_the_catalogs_keys(bench):
    cfg = bench.config(CONFIG)
    assert cfg["source"] == SOURCE and cfg["driver"] == "serve-cca-lm"
    differs = [k for k, v in CATALOG.items() if cfg.get(k) != v]
    assert differs == ["num_hidden_layers"] == cfg["reduced"]
    assert cfg["num_hidden_layers"] == 20
    assert cfg["published"] == {"num_hidden_layers": 40}
    for key in ("reduced_why", "deployment", "not_built", "assumed",
                "precision", "memory", "tolerance", "check", "rehearse"):
        assert cfg[key], key
    assert cfg["control"] == "fp8"
    assert cfg["control_cache"] == "float8_e4m3fn"
    assert cfg["precision"]["weights"] == cfg["precision"]["cache"] == \
        cfg["precision"]["tails"] == "bfloat16"
    assert set(GAPS) <= set(cfg["tolerance"])
    srv = cfg["serving"]
    assert (srv["slots"], srv["page_size"], srv["prompt_buckets"],
            srv["max_new_tokens"]) == (64, 128, [2048], 2048)
    assert srv["prefix_cache"] is False
    for key in ("prefill_chunk_why", "iter_tokens_why", "page_size_why"):
        assert len(srv[key]) > 40 and "TO BE SET" not in srv[key]
    assert "TO BE SET" not in json.dumps(cfg)
    # the reckoning of the cut
    ops = bench.ops_counts(CONFIG)
    assert ops.expert_params(cfg) == 3 * 2048 * 2048
    assert ops.layer_params(cfg) == 207_583_506
    assert ops.weight_params(cfg) == 4_688_805_224          # 9.38 GB
    assert ops.weight_params(dict(cfg, num_hidden_layers=40)) \
        == 8_840_475_344                                    # 17.7 GB
    assert ops.kv_bytes_position(cfg) == 1024
    assert ops.tail_bytes_row(cfg) == (2 * 1280 + 128) * 2
    ref = bench.reference(CONFIG)
    assert sum(int(np.prod(s)) for s in ref.tensor_specs(cfg).values()) \
        == ops.weight_params(cfg)


def test_the_mix_is_as_the_issue_gives_it(bench):
    mix = bench.traffic("think-closed")
    assert (mix["kind"], mix["clients"], mix["population"],
            mix["population_seed"], mix["drain_s"]) == \
        ("closed_loop_lm", 64, 256, 20261001, 240)
    assert mix["prompt_length"] == {"median": 512, "sigma": 0.8,
                                    "min": 64, "max": 2048}
    assert mix["reply_length"] == {"median": 1024, "sigma": 0.5,
                                   "min": 256, "max": 2048}
    assert mix["sampling"] == "greedy"
    pairs = np.asarray(traffic_lm.length_population(mix))
    assert pairs.shape == (256, 2)
    prompts, replies = pairs[:, 0], pairs[:, 1]
    assert 64 <= prompts.min() and prompts.max() <= 2048
    assert 256 <= replies.min() and replies.max() <= 2048
    assert 450 < np.median(prompts) < 650 and 900 < np.median(replies) < 1150
    srv = bench.config(CONFIG)["serving"]
    assert prompts.max() <= srv["prompt_buckets"][-1]
    assert replies.max() <= srv["max_new_tokens"]
    assert (prompts.max() + replies.max()) <= 32 * srv["page_size"]


# ------------------------------------------ readers on hand-made readings
LAYERS, EXPERTS = 20, 16


def _stats(scale, skew=1):
    """100 decode steps (x scale) of 60 live rows at 1,100 cached
    positions, 15 experts read a layer a step; the experts' tokens level
    but for expert 0 of every layer, which gets ``skew`` times a share."""
    steps, rows = 100 * scale, 60
    tokens = np.full((LAYERS, EXPERTS), steps * rows // 20)
    tokens[:, 0] *= skew
    return {"iterations": 25 * scale, "tokens": 6000 * scale,
            "admitted": 5 * scale,
            "prefill_row_steps": 0, "prefill_attn_keys": 700_000 * scale,
            "prefill_expert_tokens": tokens.ravel() // 10,
            "prefill_experts_touched": 5 * LAYERS * 16 * scale,
            "prefill_chunk_tokens": 3500 * scale,
            "prefill_chunk_padded": 1620 * scale,
            "prefill_chunks_from_zero": 4 * scale,
            "prefill_calls": 5 * scale,
            "decode_row_steps": steps * rows,
            "decode_attn_keys": steps * rows * 1100,
            "decode_expert_tokens": tokens.ravel(),
            "decode_experts_touched": steps * LAYERS * 15,
            "decode_calls": steps}


def _ev(name, start_ms, dur_ms):
    return Event(DEV, "XLA Ops", name, int(start_ms * MS), int(dur_ms * MS))


BURST = ("%while.91 = (s32[]{:T(128)}, s32[64]{0:T(128)S(1)}, pred[64]{0:T("
         "128)}, bf16[2049,256,128]{2,1,0:T(8,128)(2,1)}")
DECODE_MOE = "%moe_grouped_swiglu.7 = bf16[304,2048]{1,0:T(8,128)(2,1)}"
CHUNK_MOE = "%moe_grouped_swiglu.3 = bf16[3072,2048]{1,0:T(8,128)(2,1)}"


def _run(bench, stats1=None, events=None):
    cfg = bench.config(CONFIG)
    trace = None if events is None else TraceSummary(events, chips=1)
    ctx = types.SimpleNamespace(
        bench=bench, peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    return types.SimpleNamespace(
        obs={"stats0": _stats(1), "stats1": stats1 or _stats(3),
             "config": cfg, "iter_tokens": 4, "slots": 64},
        window_s=2.0, e2e={}, trace=trace, ctx=ctx)


def test_counts_on_hand_made_numbers(bench):
    """200 steps in the window, 60 live rows a step at 1,100 cached
    positions, 15 of 16 experts read a layer: the bytes and the operations
    a step needs."""
    cfg = bench.config(CONFIG)
    ops = bench.ops_counts(CONFIG)
    counts = cca_counts.window_counts(_run(bench))
    assert counts["decode_calls"] == 200
    assert counts["decode_row_steps"] == 12000
    parts = ops.decode_step_parts(cfg, counts)
    expert = 3 * 2048 * 2048
    assert parts["experts"] == 20 * 15 * expert * 2
    assert parts["other_weights"] == 20 * 6_256_914 * 2
    assert parts["head"] == 2048 * 262272 * 2
    assert parts["pages"] == 20 * 60 * 1100 * 1024
    assert parts["tails"] == 2 * 20 * 60 * 5376
    assert ops.decode_step_bytes(cfg, counts) == sum(parts.values())
    # the issue's step: ~8 GB of experts, 0.25 of other weights, 1.07 of
    # head, ~1.5 of pages: some 11 GB, 13 ms at the peak bandwidth
    assert 7.5e9 < parts["experts"] < 7.6e9
    assert 0.24e9 < parts["other_weights"] < 0.26e9
    assert 1.3e9 < parts["pages"] < 1.4e9 and parts["tails"] < 0.02e9
    assert 12e-3 < sum(parts.values()) / 819e9 < 13e-3
    work = ops.decode_step_ops(cfg, counts)
    assert work == 2 * 60 * (20 * (expert + 6_256_914) + 2048 * 262272) \
        + 4 * 20 * 60 * 1100 * 8 * 128
    assert work / 197e12 < 1e-3                  # bandwidth bounds the step
    assert ops.decode_step_parts(cfg, dict(counts, decode_calls=0)) is None
    call_ops, moved = ops.decode_moe_call(cfg, counts)
    assert moved == 15 * expert * 2 + 2 * 60 * 2048 * 2
    assert call_ops == 2 * 60 * expert
    assert ops.decode_moe_call(cfg, dict(counts, decode_calls=0)) is None
    # 64 pairs on 16 experts, every run padded to a tile of 16: the rows
    # that name the decode step's event
    assert ops.decode_moe_rows(cfg, 64) == 304
    from mxnet_tpu.ops.pallas import grouped_swiglu as moe
    assert moe.row_tile(64, 16) == ops.ROW_TILE


def test_counter_readers_on_hand_made_counters(bench):
    run = _run(bench)
    read = lambda n: bench.layer_metric(n).read(run)  # noqa: E731
    assert read("top1_experts_touched_share") == pytest.approx(100 * 15 / 16)
    assert read("top1_expert_load_imbalance") == pytest.approx(1.0)
    skewed = _run(bench, stats1={**_stats(3), "decode_expert_tokens":
                                 _stats(3, skew=4)["decode_expert_tokens"]})
    # expert 0 of every layer took 11 shares of 25 where the mean is 25/16
    by_layer = (_stats(3, skew=4)["decode_expert_tokens"]
                - _stats(1)["decode_expert_tokens"]).reshape(20, 16)
    assert bench.layer_metric("top1_expert_load_imbalance").read(skewed) \
        == pytest.approx(float((by_layer.max(1) / by_layer.mean(1)).mean()))
    assert bench.layer_metric("top1_expert_load_imbalance").read(skewed) > 4
    cfg = bench.config(CONFIG)
    parts = bench.ops_counts(CONFIG).decode_step_parts(
        cfg, cca_counts.window_counts(run))
    assert read("cca_cache_bytes_share") == pytest.approx(
        100 * (parts["pages"] + parts["tails"]) / sum(parts.values()))
    assert 11 < read("cca_cache_bytes_share") < 15
    # no device timeline: the two device metrics say nothing
    for name in DEVICE:
        assert read(name) is None
    # a program without the counters (the parent commit, another model):
    # nothing, no error
    short = {k: v for k, v in _stats(3).items()
             if k != "decode_experts_touched"}
    events = [_ev(BURST, 0, 80), _ev(DECODE_MOE, 88, 1)]
    for name in NEW:
        assert bench.layer_metric(name).read(
            _run(bench, stats1=short, events=events)) is None
        assert bench.layer_metric(name).read(
            types.SimpleNamespace(obs={}, e2e={}, trace=None)) is None
    # no decode step in the window: nothing to divide by
    idle = dict(_stats(3), decode_calls=_stats(1)["decode_calls"])
    for name in NEW[:3]:
        assert bench.layer_metric(name).read(
            _run(bench, stats1=idle, events=events)) is None


def test_device_readers_on_a_hand_made_event_list(bench):
    events = [_ev(BURST, 0, 72), _ev(CHUNK_MOE, 72, 3),
              _ev(DECODE_MOE, 75, 0.5),
              _ev(DECODE_MOE.replace(".7", ".8"), 76, 0.5),
              _ev("%fusion.1 = f32[64,262272]", 77, 3), _ev(BURST, 80, 72)]
    run = _run(bench, events=events)
    ops = bench.ops_counts(CONFIG)
    cfg = run.obs["config"]
    counts = cca_counts.window_counts(run)
    # two bursts of four steps in 144 ms: 18 ms a step
    least = max(ops.decode_step_bytes(cfg, counts) / 819e9,
                ops.decode_step_ops(cfg, counts) / 197e12)
    share = bench.layer_metric(NEW[0]).read(run)
    assert share == pytest.approx(100 * least / 0.018) and 65 < share < 75
    # the decode step's two calls, 0.5 ms each; the chunk's is another's
    work, moved = ops.decode_moe_call(cfg, counts)
    share = bench.layer_metric(NEW[4]).read(run)
    assert share == pytest.approx(
        100 * max(work / 197e12, moved / 819e9) / 0.0005)
    assert 85 < share < 100
    # another slot count's burst, the chunk's calls alone: nothing
    other = BURST.replace("s32[64]", "s32[10]")
    none = _run(bench, events=[_ev(CHUNK_MOE, 0, 10), _ev(other, 10, 4)])
    for name in DEVICE:
        assert bench.layer_metric(name).read(none) is None

"""The cell PR 38 added, rehearsed on the CPU: the looped model's serving
cell agrees with its plain reference in its served tokens, in the keys its
first layer caches at the first and the last plane and in the exit
distribution it counts; both controls (float8 weights, a float8 K/V cache)
do not; a token altered where it is produced, or passes made to share a
plane, read not correct; and the new per-layer readers and counts give
known answers on hand-made counters and a hand-made event list."""

import json
import types

import numpy as np
import pytest

from perf.harness import loop_counts, traffic_lm
from perf.harness.loader import Benchmark
from perf.harness.trace import Event, TraceSummary

from _runs import REPO, child, in_process

CELL = "ouro-2.6b.reason-closed"
CONFIG = "ouro-2.6b"
MS = 1_000_000
DEV = "/device:TPU:0"
NEW = ["loop_decode_step_roofline_share", "loop_weight_bytes_share",
       "loop_passes_per_token", "loop_attention_time_share",
       "loop_attention_roofline_share"]
TAILS = ["queue_wait_p95_ms", "seat_wait_p95_ms", "prefill_service_p95_ms",
         "first_token_deliver_p95_ms", "pass_wall_p95_ms",
         "decode_wait_p95_ms"]
GAPS = ("widest_logit_gap", "mean_logit_gap", "plane_gap", "plane_gap_last",
        "gate_gap")
E2E = ("serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms")
OLDER = ["transformer-big.translate-closed",
         "keye-vl2-30b-a3b.longctx-closed",
         "granite-4.0-h-micro.chat-closed",
         "joyai-llm-flash.longgen-closed"]


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
    # the planes and the gate: a float32 program differs from the
    # reference by its sums' order alone
    assert numbers["plane_gap"]["positions"] >= 8
    for number in GAPS[2:]:
        assert numbers[number]["value"] < 1e-5
    assert out[-1]["correct"] is True and out[-1]["failed"] == 0
    assert set(E2E) | {"setup_s"} <= set(out[-1]["metrics_reported"])
    counts = [r for r in out if r.get("note") == "window_counts"][0]
    assert counts["prompt_chunks"] > 0 and counts["decode_calls"] > 0
    rows = counts["prefill_row_steps"] + counts["decode_row_steps"]
    assert counts["prefill_stack_passes"] + counts["decode_stack_passes"] \
        == 4 * rows
    assert counts["decode_attn_calls"] == counts["decode_calls"] * 4 * 3
    mass = np.array(counts["prefill_exit_mass"]) \
        + np.array(counts["decode_exit_mass"])
    assert mass.shape == (4,) and abs(mass.sum() / 1e6 - rows) < 1e-3 * rows
    state = [r for r in out if r.get("note") == "state_bytes"][0]
    # 3 slots x 12 pages and the trash page, K and V, 3 layers, 4 planes
    assert state == {"note": "state_bytes", "slot_arrays": 0,
                     "encoder_memory": 0,
                     "pages": 37 * (4 * 4 * 16 * 4) * 2 * 3 * 4}


@pytest.mark.parametrize("seed", [2**31 + 43])
def test_both_controls_fail_the_check(capsys, seed):
    """The float8-weights reference falls outside the limits of the served
    tokens; the program with a float8 K/V cache falls outside the limit of
    the planes."""
    code, out = in_process(capsys, "--workload", CELL, "--seed", str(seed),
                           "--seconds", "1", "--rehearse", "--control")
    assert code == 0 and out[-1]["found_not_correct"] is True
    program, control = _compared(out), _compared(out, of="control")
    for number in GAPS:
        assert program[number]["inside"]
    assert control["widest_logit_gap"]["value"] > 5 * \
        control["widest_logit_gap"]["limit"]
    assert not control["mean_logit_gap"]["inside"]
    cache = _compared(out, of="control_cache")
    assert not cache["plane_gap"]["inside"]
    assert cache["plane_gap"]["value"] > 10 * cache["plane_gap"]["limit"]
    assert not cache["plane_gap_last"]["inside"]
    said = [r for r in out if r.get("note") == "control_cache"][0]
    assert said["found_not_correct"] and "plane_gap" in said["outside"]
    assert said["cache_dtype"] == "float8_e4m3fn"


def _alter(monkeypatch, what):
    if what == "token":
        from mxnet_tpu.serving.batcher import GenerationResult

        real = GenerationResult._resolve
        monkeypatch.setattr(
            GenerationResult, "_resolve", lambda self, tokens: real(
                self, [3 + (int(t) * 7 + 11) % 100 for t in tokens]))
        return
    if what == "gate":
        from mxnet_tpu.gluon.model_zoo import ouro

        monkeypatch.setattr(ouro, "PPM", 1.1e6)
        return
    if what == "gate_bias":
        from mxnet_tpu.gluon.model_zoo.ouro import OuroLM

        real = OuroLM._w
        monkeypatch.setattr(OuroLM, "_w", lambda self, name: real(
            self, name) * (0 if name == "exit_b" else 1))
        return
    from mxnet_tpu.gluon.model_zoo.ouro import OuroLM

    if what == "shared_plane":
        monkeypatch.setattr(OuroLM, "_plane_start",
                            lambda self, t, num_pages: 0 * t)
        return
    real = OuroLM.decode_step_paged         # "decode_plane"

    def last_plane(self, *args, **kw):
        self._plane_start = lambda t, num_pages: 0 * t + 3 * num_pages
        try:
            return real(self, *args, **kw)
        finally:
            del self._plane_start

    monkeypatch.setattr(OuroLM, "decode_step_paged", last_plane)


@pytest.mark.parametrize("what,number", [
    ("token", "widest_logit_gap"), ("shared_plane", "plane_gap"),
    ("decode_plane", "plane_gap"), ("gate", "gate_gap"),
    ("gate_bias", "gate_gap")])
def test_what_is_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch, what, number):
    """A served token altered on its way out, every pass made to write and
    read plane 0, the decode step alone made to write and read plane 3 in
    every pass (the chunk program sound), an exit distribution counted a
    tenth too high, a gate without its bias: each reads not ``correct`` by
    the number that looks at it."""
    _alter(monkeypatch, what)
    code, out = in_process(capsys, "--workload", CELL, "--seed", "5",
                           "--seconds", "1", "--rehearse")
    assert code == 0 and out[-1]["correct"] is False
    gap = _compared(out)[number]
    assert not gap["inside"] and gap["value"] > 3 * gap["limit"]
    if what == "shared_plane":
        # plane 0 ends up holding the LAST pass's keys and plane 3 none
        assert gap["value"] > 0.3
        assert _compared(out)["plane_gap_last"]["value"] > 0.9
    if what == "decode_plane":
        # the reply's positions (one at least, of 41 at most, whichever
        # request ended last) hold no key of the first pass at plane 0;
        # the prompt's are sound
        assert gap["value"] > 0.02
        assert not _compared(out)["plane_gap_last"]["inside"]
    if what.startswith("gate"):
        assert _compared(out)["widest_logit_gap"]["inside"]
        assert _compared(out)["plane_gap"]["inside"]


def test_a_traced_rehearsal_reads_the_new_counters():
    proc = child("--workload", CELL, "--seed", str(2**31 + 17), "--seconds",
                 "2", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    # the device metrics need a device's timeline; the counters do not
    got = set(last["metrics_reported"])
    assert {"loop_weight_bytes_share", "loop_passes_per_token",
            "batch_occupancy", "iter_wall_ms", "decode_wait_ms",
            "prefill_wait_ms"} | set(TAILS) <= got
    assert not got & {"expert_load_imbalance", "ssm_state_bytes_share",
                      "mla_cache_bytes_share", "prefill_chunk_ms",
                      "hybrid_prefill_chunk_ms"}


# ------------------------------------------------------------ the manifest
def test_the_cell_is_listed_as_the_issue_names_it(bench):
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "reason-closed", 1)
    assert len(cell["why"]) <= 200
    assert {m["name"] for m in bench.end_to_end(cell)} == set(E2E) | \
        {"setup_s"}
    got = {n for n, _ in bench.per_layer(cell)}
    assert set(NEW) | set(TAILS) <= got
    assert bench.config(CONFIG)["driver"] == "serve-loop-lm"
    assert bench.driver("serve-loop-lm").run
    entry = [c for c in bench.manifest["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == []
    assert entry["file"] == "perf/configs/ouro-2.6b.json"
    assert entry["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"


def test_this_prs_own_entries_are_where_they_were_appended(bench):
    """Of ITS OWN entries only: the cell stands behind the four serving
    cells that were there, in the lists it was appended to, and its five
    metrics list it alone. Nothing is said of what comes after it, nor of
    how many metrics an older cell reads."""
    now = bench.manifest
    lists = {m["name"]: m["workloads"]
             for m in now["end_to_end"] + now["per_layer"]
             if m["name"] in E2E or m["name"] in TAILS}
    assert set(lists) == set(E2E) | set(TAILS)
    for name, cells in lists.items():
        assert cells[:5] == OLDER + [CELL], name
    names = [w["name"] for w in now["workloads"]]
    assert names.index(CELL) == 1 + names.index(OLDER[-1])
    for m in now["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            reader = bench.layer_metric(m["name"])
            assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) \
                == (m["name"], m["unit"], m["layer"], m["moves"])
    assert set(NEW) <= {m["name"] for m in now["per_layer"]}
    by = {m["name"]: m for m in now["per_layer"]}
    assert by["loop_passes_per_token"]["unit"] == "passes"
    assert by["loop_passes_per_token"]["better"] == "lower"
    assert {by[n]["layer"] for n in NEW} == {"kernels", "looped stack"}


def test_the_configuration_carries_the_catalogs_keys(bench):
    """Every key of the catalog's ``config`` at its published value (the
    values below are the catalog's: ``architectures.jsonl``, row
    ``Ouro-2.6B``), ``reduced`` empty, the deployment, what is not built
    and each ``assumed``."""
    cfg = bench.config(CONFIG)
    catalog = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    assert cfg["reduced"] == []
    for key, value in catalog.items():
        assert cfg[key] == value, key
    for key, value in cfg["published"].items():
        assert cfg[key] == value, key        # what is run is what is published
    for word in ("one chip serves the whole model", "one replica of many",
                 "nothing shared between chips"):
        assert word in cfg["deployment"], word
    assert len(cfg["assumed"]) >= 6 and len(cfg["not_built"]) >= 3
    assert "modeling_ouro.py" in cfg["assumed"][0]
    assert "NONZERO" in cfg["assumed"][-1]
    srv = cfg["serving"]
    assert (srv["slots"], srv["page_size"], srv["prefill_chunk"]) == \
        (10, 128, 256)
    assert srv["prompt_buckets"] == [128, 256]
    assert srv["prompt_buckets"][-1] + srv["max_new_tokens"] == 4 * 128
    assert srv["prefix_cache"] is False and srv["max_prefix_tokens"] == 0
    assert "iter_tokens_why" in srv and srv["iter_tokens"] >= 1
    assert set(GAPS) <= set(cfg["tolerance"]) and cfg["tolerance"]["why"]
    assert cfg["control"] == "fp8" and \
        cfg["control_cache"] == "float8_e4m3fn"
    assert cfg["precision"]["weights"] == cfg["precision"]["cache"] == \
        "bfloat16"
    drv = bench.driver("serve-loop-lm")
    assert drv.NO_END_TOKEN == -1
    kw = drv._model_kwargs(cfg)
    assert (kw["num_layers"], kw["total_ut_steps"], kw["num_heads"]) == \
        (48, 4, 16)
    # the issue's arithmetic: parameters, bytes a position, bytes held
    ref, ops = bench.reference(CONFIG), bench.ops_counts(CONFIG)
    n = sum(int(np.prod(s)) for s in ref.tensor_specs(cfg).values())
    assert n == ops.weight_params(cfg) == 2_667_974_657
    assert ops.layer_params(cfg) == 51_388_416
    assert ops.plane_bytes_position(cfg) == 1_572_864
    assert ops.kv_bytes_position(cfg) == 8192
    assert 4.93e9 < ops.stack_params(cfg) * 2 < 4.94e9
    pools = (srv["slots"] * 4 + 1) * srv["page_size"] \
        * ops.plane_bytes_position(cfg)
    assert 8.2e9 < pools < 8.3e9 and 13.5e9 < pools + 2 * n < 13.7e9


# ---------------------------------------------------------------- traffic
def test_the_mix_is_as_the_issue_gives_it(bench):
    mix = bench.traffic("reason-closed")
    assert (mix["kind"], mix["clients"], mix["population"],
            mix["sampling"], mix["population_seed"], mix["drain_s"]) == \
        ("closed_loop_lm", 10, 128, "greedy", 20260930, 120)
    assert mix["prompt_length"] == {"median": 128, "sigma": 0.5,
                                    "min": 32, "max": 256}
    assert mix["reply_length"] == {"median": 160, "sigma": 0.5,
                                   "min": 48, "max": 256}
    a = traffic_lm.RequestStream(mix, 2**31 + 7, 49152)
    b = traffic_lm.RequestStream(mix, 11, 49152)
    n = mix["population"]
    shape = lambda s, at: [(len(s.request(i)[0]), s.request(i)[1])  # noqa: E731
                           for i in range(at, at + n)]
    assert shape(a, 0) == shape(b, 0) and shape(a, n) == shape(b, n)
    assert shape(a, 0) != shape(a, n)            # a new order each pass
    assert not (a.request(5)[0] == b.request(5)[0]).all()
    prompts = np.array([p for p, _ in shape(a, 0)])
    replies = np.array([r for _, r in shape(a, 0)])
    assert 32 <= prompts.min() and prompts.max() <= 256
    assert 48 <= replies.min() and replies.max() <= 256
    assert 110 < np.median(prompts) < 145 and 140 < np.median(replies) < 180
    ids = a.request(3)[0]
    assert ids.min() >= 3 and ids.max() < 49152
    # a slot's four pages hold the longest prompt and reply; a prompt is
    # one chunk
    srv = bench.config(CONFIG)["serving"]
    assert (prompts + replies).max() <= 4 * srv["page_size"]
    assert prompts.max() <= srv["prefill_chunk"]


# ------------------------------------------ readers on hand-made readings
def _stats(scale):
    return {"iterations": 10 * scale, "tokens": 960 * scale,
            "admitted": 40 * scale,
            "prefill_stack_passes": 160 * scale,
            "prefill_row_steps": 40 * scale,
            "prefill_attn_keys": 40 * 140 * 192 * scale,
            "prefill_attn_calls": 40 * 192 * scale,
            "prefill_calls": 40 * scale,
            "prefill_exit_mass": np.array([10, 10, 10, 10]) * 1_000_000
            * scale,
            "decode_stack_passes": 4000 * scale,
            "decode_row_steps": 1000 * scale,
            "decode_attn_keys": 100 * 2100 * 192 * scale,
            "decode_attn_calls": 100 * 192 * scale,
            "decode_calls": 100 * scale,
            "decode_exit_mass": np.array([400, 300, 200, 100]) * 1_000_000
            * scale}


def _ev(name, start_ms, dur_ms):
    return Event(DEV, "XLA Ops", name, int(start_ms * MS), int(dur_ms * MS))


BURST = ("%while.91 = (s32[]{:T(128)}, s32[10]{0:T(128)S(1)}, pred[10]{0:T("
         "128)}, bf16[4,41,128,16,128]{4,3,2,1,0:T(8,128)(2,1)}")
PASS_LOOP = "%while.12 = (s32[]{:T(128)}, bf16[10,2048]{1,0"
KERNEL = "%paged_window.7 = bf16[10,16,128]{2,1,0:T(8,128)(2,1)}"
CHUNK = "%dsa_selected_window.3 = bf16[1,1,16,256,128]{4,3,2,1,0}"


def _run(bench, stats1=None, events=None):
    cfg = bench.config(CONFIG)
    trace = None if events is None else TraceSummary(events, chips=1)
    ctx = types.SimpleNamespace(
        bench=bench, peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    return types.SimpleNamespace(
        obs={"stats0": _stats(1), "stats1": stats1 or _stats(3),
             "config": cfg, "iter_tokens": 4, "slots": 10},
        window_s=2.0, e2e={}, trace=trace, ctx=ctx)


def test_counts_on_hand_made_numbers(bench):
    """200 steps in the window, 10 live rows a step at 210 cached
    positions: the bytes and the operations a step needs."""
    cfg = bench.config(CONFIG)
    ops = bench.ops_counts(CONFIG)
    counts = loop_counts.window_counts(_run(bench))
    assert counts["decode_calls"] == 200 and counts["decode_row_steps"] == 2000
    parts = ops.decode_step_parts(cfg, counts)
    stack = 48 * 51_388_416 + 2 * 2048 + 1
    assert parts["weights"] == 4 * stack * 2
    assert parts["head"] == 2048 * 49152 * 2
    assert parts["planes"] == 2100 * 192 * 8192
    assert ops.decode_step_bytes(cfg, counts) == sum(parts.values())
    # the issue's step: 4 x 4.93 GB of stack, 0.2 of head, 3.3 of planes:
    # 23.3 GB, 28 ms at the peak bandwidth, the weights over half
    assert 19.7e9 < parts["weights"] < 19.8e9
    assert 3.2e9 < parts["planes"] < 3.4e9
    assert 23.1e9 < sum(parts.values()) < 23.5e9
    assert 28e-3 < sum(parts.values()) / 819e9 < 29e-3
    work = ops.decode_step_ops(cfg, counts)
    assert work == 2 * 10 * (4 * stack + 2048 * 49152) \
        + 4 * 2100 * 192 * 16 * 128
    assert work / 197e12 < 2e-3                  # bandwidth bounds the step
    assert ops.decode_step_parts(cfg, dict(counts, decode_calls=0)) is None
    call_ops, moved = ops.attention_call(cfg, counts)
    assert moved == 2100 * 8192 and call_ops == 4 * 2100 * 16 * 128
    assert ops.attention_call(cfg, dict(counts, decode_attn_calls=0)) is None


def test_counter_readers_on_hand_made_counters(bench):
    run = _run(bench)
    read = lambda n: bench.layer_metric(n).read(run)  # noqa: E731
    # 8,320 passes for 2,000 tokens: a burst ran 4 % of its rows past
    # their last token
    assert read("loop_passes_per_token") == pytest.approx(
        (320 + 8000) / (1920 + 80))
    cfg = bench.config(CONFIG)
    parts = bench.ops_counts(CONFIG).decode_step_parts(
        cfg, loop_counts.window_counts(run))
    assert read("loop_weight_bytes_share") == pytest.approx(
        100 * parts["weights"] / sum(parts.values()))
    assert 80 < read("loop_weight_bytes_share") < 90
    # no device timeline: the three device metrics say nothing
    for name in (NEW[0], NEW[3], NEW[4]):
        assert read(name) is None
    # a program without the counters (the parent commit, another model):
    # nothing, no error
    short = {k: v for k, v in _stats(3).items() if k != "decode_attn_keys"}
    events = [_ev(BURST, 0, 80), _ev(KERNEL, 88, 1)]
    for name in NEW:
        assert bench.layer_metric(name).read(
            _run(bench, stats1=short, events=events)) is None
        assert bench.layer_metric(name).read(
            types.SimpleNamespace(obs={}, e2e={}, trace=None)) is None
    # no token in the window: nothing to divide by
    idle = dict(_stats(3), tokens=_stats(1)["tokens"],
                admitted=_stats(1)["admitted"])
    assert bench.layer_metric("loop_passes_per_token").read(
        _run(bench, stats1=idle)) is None


def test_device_readers_on_a_hand_made_event_list(bench):
    events = [_ev(BURST, 0, 140), _ev(PASS_LOOP, 140, 5), _ev(CHUNK, 145, 3),
              _ev(KERNEL, 148, 1), _ev(KERNEL.replace(".7", ".8"), 149, 1),
              _ev("%fusion.1 = bf16[10,49152]", 150, 10),
              _ev(BURST, 160, 140)]
    run = _run(bench, events=events)
    ops = bench.ops_counts(CONFIG)
    cfg = run.obs["config"]
    counts = loop_counts.window_counts(run)
    # two bursts of four steps in 280 ms: 35 ms a step
    least = max(ops.decode_step_bytes(cfg, counts) / 819e9,
                ops.decode_step_ops(cfg, counts) / 197e12)
    share = bench.layer_metric("loop_decode_step_roofline_share").read(run)
    assert share == pytest.approx(100 * least / 0.035) and 75 < share < 85
    # the chunk's call and two of the step's, 5 ms of 300 ms busy
    assert bench.layer_metric("loop_attention_time_share").read(run) \
        == pytest.approx(100 * 0.005 / 0.300)
    work, moved = ops.attention_call(cfg, counts)
    assert bench.layer_metric("loop_attention_roofline_share").read(run) \
        == pytest.approx(100 * max(work / 197e12, moved / 819e9) / 0.001)
    # neither the loop over passes nor another slot count's burst, and no
    # event of a kernel: nothing
    none = _run(bench, events=[_ev(PASS_LOOP, 0, 10),
                               _ev(BURST.replace("s32[10]", "s32[64]"), 10, 4)])
    for name in (NEW[0], NEW[3], NEW[4]):
        assert bench.layer_metric(name).read(none) is None

"""Driver of serving cells of a hybrid language model whose recurrent layers
keep a MATRIX state a head written by the gated delta rule: what
``serve-hybrid-lm.py`` does (the zoo's model behind ``InferStep`` and the
default batcher, a closed loop of callers timed from the client's side, the
served tokens held against the plain reference's logits and the slots'
recurrent state against the reference's token-by-token state), for a model
whose constructor takes the ``olmo_hybrid`` keys. Building the program and
the state's comparison are ``serve-hybrid-lm.py``'s, taken as it takes
``serve-lm.py``'s; that driver reads Granite's keys where it builds and the
slot array ``ssm`` where it reads back, so this one brings its own
``_model_kwargs`` and reads ``delta``.

Three things differ in what decides ``correct``:

- The requests whose logits are compared are chosen by their INDEX, not by
  the clock: the ``check.sample_requests`` longest (prompt and reply
  together) among each caller's first two requests. The lengths' order is
  the mix's own (``traffic_lm.py``), so every run of a seed, whatever its
  timing, holds the same prompts against the reference (PERF.md 7 (ba): a
  sample the window's end chose made two rehearsals hang on the clock).
- A decode burst runs its ``iter_tokens`` steps whole, so a request of ``n``
  served tokens was fed ``iter_tokens x ceil((n - 1) / iter_tokens)`` of
  them; at bursts of more than two that can be more than the caller was
  handed. Only a request whose every fed token the caller holds has a state
  the reference can follow (``_settled``, over ``serve-hybrid-lm.py``'s
  ``_state_sample``).
- The state has TWO numbers. ``mean_state_gap`` is granite's: the slots'
  state against the reference's token-by-token state, the mean over every
  layer's heads. It tells a state that was dropped, stale or advanced over
  padding; at sixteen layers served in bfloat16 it reads the weights' and
  activations' rounding (3 % on the chip), and the precision the state is
  CARRIED in hides under that (a bfloat16 state adds 0.5 % in quadrature,
  in the first layer too: the gates' rounded pre-activations alone put
  0.7 % there). ``state_cut_gap`` reads the carry by itself: once the
  scheduler has stopped, one seeded prompt of two chunks goes through the
  engine's chunk program twice, into two slots: in whole chunks, and in
  half chunks (four dispatches, each padded to the chunk, each cut on a
  block of the rule). The reference has no chunks: its state is a function
  of the tokens alone, so whatever the cuts change is the program's error.
  Both feeds push the same operands through the same blocks, so a state
  carried exactly reads about 0, and one rounded where it is stored reads
  that rounding.

What it takes from the configuration beside ``serve-lm.py``'s keys:
``precision.state``, ``check.state_requests``. The engine is given an end
token no vocabulary holds, as ``serve-hybrid-lm.py`` gives it.
"""

import gc
import math
import time

import numpy as np


def _model_kwargs(cfg):
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        linear_key_heads=cfg["linear_num_key_heads"],
        linear_value_heads=cfg["linear_num_value_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        linear_conv=cfg["linear_conv_kernel_dim"],
        allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        delta_block=cfg["delta_block"],
        rms_eps=cfg["rms_norm_eps"],
        state_dtype=cfg["precision"]["state"],
        dtype=cfg["precision"]["weights"])


def _check_sample(records, n, clients):
    """The ``n`` longest (prompt and reply together) finished requests among
    each caller's first two, by index: the same requests in every run of a
    seed."""
    first = [r for r in records if r.index < 2 * clients
             and r.error is None and r.tokens]
    first.sort(key=lambda r: (-(len(r.prompt) + len(r.tokens)), r.index))
    return first[:n]


def _settled(intact, n, iter_tokens):
    """Of ``intact`` (``serve-hybrid-lm.py``'s ``_state_sample``: the
    requests that ended after the last admission and that no end token cut
    short, the longest first) the first ``n`` whose every fed token the
    caller holds: only their final state can the reference follow."""
    return [r for r in intact
            if iter_tokens * math.ceil((len(r.tokens) - 1) / iter_tokens)
            <= len(r.tokens)][:n]


def state_gaps(hybrid, ref, seed, cfg, sample, delta):
    """``(requests, layers, heads)`` of ``|S - S_ref| / |S_ref|``: each
    sampled request's slot (``serve-hybrid-lm.py``'s rule: the one whose
    first layer lies nearest the reference's) against the reference's
    token-by-token state after the prompt and the served tokens the bursts
    fed, head by head of every delta-rule layer."""
    it = int(cfg["serving"]["iter_tokens"])
    gaps = []
    for r in sample:
        fed = it * math.ceil((len(r.tokens) - 1) / it)
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.tokens[:fed], np.int32)])
        want = ref.final_states(seed, cfg, seq, [len(seq)],
                                pad_to=cfg["check"].get("pad_to"))[:, 0]
        slot = int(np.argmin(hybrid._head_gaps(delta[0], want[0]).mean(-1)))
        gaps.append(hybrid._head_gaps(delta[:, slot], want))
    return np.stack(gaps) if gaps else np.zeros((0, len(delta), 0))


def state_numbers(gaps):
    """``mean_state_gap`` and what is said beside it: the widest single
    head (it swings from seed to seed) and each layer's mean, first layer
    first."""
    if not gaps.size or not np.isfinite(gaps).all():
        return float("nan"), {"widest_head": float("nan"), "by_layer": []}
    return float(gaps.mean()), {
        "widest_head": float(gaps.max()),
        "by_layer": [round(float(g), 6) for g in gaps.mean((0, 2))]}


def cut_gaps(hybrid, eng, state, cfg, seed):
    """``(layers, heads)`` of ``|S_cut - S_whole| / |S_whole|``: one seeded
    prompt of two chunks fed through the engine's chunk program into slot 0
    in whole chunks and into slot 1 in half chunks, every dispatch of the
    shapes the scheduler's own had (nothing compiles). ``state`` is a
    stopped batcher's ``paged_state()``; the program donates it."""
    srv = cfg["serving"]
    chunk, page = int(srv["prefill_chunk"]), int(srv["page_size"])
    assert (chunk // 2) % int(cfg["delta_block"]) == 0
    pages = -(-(max(srv["prompt_buckets"]) + srv["max_new_tokens"]) // page)
    prompt = np.random.default_rng([seed, chunk]).integers(
        0, cfg["vocab_size"], 2 * chunk).astype(np.int32)
    for slot, piece in ((0, chunk), (1, chunk // 2)):
        # the slot's own pages of a fully provisioned pool; page 0 is the
        # trash page
        table = 1 + slot * pages + np.arange(pages, dtype=np.int32)[None]
        for at in range(0, len(prompt), piece):
            tokens = np.zeros((1, chunk), np.int32)
            tokens[0, :piece] = prompt[at:at + piece]
            _, state = eng.prefill_suffix_paged(
                state, tokens, np.full((1,), piece, np.int32),
                np.full((1,), at, np.int32), table,
                np.full((1,), slot, np.int32), np.ones((1,), bool),
                wide=True)
    whole, cut = (np.stack([np.asarray(a[slot], np.float32)
                            for a in state["delta"]]) for slot in (0, 1))
    return hybrid._head_gaps(cut, whole)


def _serve_lm(ctx, hybrid, kept):
    """``serve-lm.py`` with this model's program in place of Keye's, built
    by ``serve-hybrid-lm.py``'s builder from this file's keys. The
    program's class is imported first: a program that lacks it ends the run
    here, in seconds, before any weight is made. ``kept`` receives the
    run's records and, read once the scheduler has stopped, every slot's
    state on the host."""
    lm = ctx.bench.driver("serve-lm")
    lm._program_class(ctx.config)
    hybrid._model_kwargs = _model_kwargs
    serve_ = lm._serve
    clients = int(ctx.traffic["clients"])

    def build(cfg, ref, seed):
        kept["program"] = program = hybrid._build_program(cfg, ref, seed)
        ctx.say("state_bytes", **program[2].state_bytes)
        return program

    def serve(*args):
        records = serve_(*args)
        _, eng, bat = kept.pop("program")
        # (layers, slots, heads, d_k, d_v): what each slot's last occupant
        # left
        arrays = bat.slot_arrays()["delta"]
        kept["delta"] = delta = np.empty(
            (len(arrays),) + arrays[0].shape, np.float32)
        for i, a in enumerate(arrays):
            delta[i] = np.asarray(a, np.float32)
        del arrays
        before = eng.compile_guard.signatures
        kept["cut"] = cut_gaps(hybrid, eng, bat.paged_state(), ctx.config,
                               ctx.seed)
        kept["cut_programs"] = eng.compile_guard.signatures - before
        del eng, bat
        kept["records"] = records
        gc.collect()                # the device is the reference's now
        return records

    lm._build_program, lm._serve = build, serve
    lm._check_sample = lambda finished, cfg, seed: _check_sample(
        kept["records"], int(cfg["check"]["sample_requests"]), clients)
    return lm


def run(ctx, with_control=False):
    cfg, kept = ctx.config, {}
    hybrid = ctx.bench.driver("serve-hybrid-lm")
    lm = _serve_lm(ctx, hybrid, kept)
    run = lm.run(ctx, with_control)
    records = kept["records"]
    ctx.say("replies", finished=sum(r.error is None and bool(r.tokens)
                                    for r in records),
            replies_ended_early=sum(
                r.error is None and bool(r.tokens)
                and len(r.tokens) < r.max_new for r in records))
    # ---- the slots' state against the plain reference's
    t = time.perf_counter()
    ref = ctx.bench.reference(cfg["name"])
    sample = _settled(hybrid._state_sample(records, len(records)),
                      int(cfg["check"]["state_requests"]),
                      int(cfg["serving"]["iter_tokens"]))
    gaps = state_gaps(hybrid, ref, ctx.seed, cfg, sample, kept.pop("delta"))
    mean, beside = state_numbers(gaps)
    inside = lm._compare(
        ctx, cfg, {"mean_state_gap": mean}, requests=len(sample),
        heads=int(gaps.size),
        positions=[len(r.prompt) + len(r.tokens) for r in sample],
        reference_s=time.perf_counter() - t, **beside)
    # ---- the carry by itself: the same prompt cut two ways
    cut = kept.pop("cut")
    inside = lm._compare(
        ctx, cfg, {"state_cut_gap": float(cut.mean())},
        widest_head=float(cut.max()), heads=int(cut.size),
        new_programs=kept.pop("cut_programs"),
        by_layer=[round(float(g), 7) for g in cut.mean(-1)]) and inside
    run.correct = run.correct and inside and gaps.size > 0
    return run


def control(ctx):
    """The control: the reference in float8 in the program's place, at the
    positions of the program's own served tokens; it has to fall outside
    the limits."""
    return not run(ctx, with_control=True).control_inside

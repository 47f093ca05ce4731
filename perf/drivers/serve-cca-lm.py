"""Driver of serving cells of a language model with attention in a
compressed latent (a convolution over the last positions and a shifted
value, so that a page holds a function of THREE positions and every slot a
tail beside its pages) and a top-1 expert layer behind a router MLP: what
``serve-lm.py`` does (the zoo's model behind ``InferStep`` and the default
batcher, a closed loop of callers timed from the client's side, the served
tokens held against the plain reference's logits), for a model whose
constructor takes the ``zaya`` keys. Everything but building the program is
``serve-lm.py``'s, taken as it takes ``serve.py``'s; that driver reads
Keye's keys where it builds (``_model_kwargs`` inside ``_build_program``),
so this one brings its own two and hands them over, as
``serve-loop-lm.py`` does.

What it adds to the comparison that decides ``correct``, read once the
scheduler has stopped and before anything else touches its state, as the
window's own programs left it. The PAGES are those of the request that
ended LAST: a request's pages go back to the pool when it ends and a row
that is still decoding takes a page as its context grows, so only after
the last one was nobody left to take them. The TAIL is that of the request
that ended last among those whose every fed token the caller holds
(``_settled``; no request was admitted after the window closed, so a slot
keeps what its last request left):

- ``page_gap`` and ``page_gap_widest``, the cache itself: the first
  layer's K and V pages at the prompt's positions (the chunk program
  wrote them) and at the reply's (the decode steps did), against the keys
  and values the reference makes there, position by position, ``|k -
  k_ref| / |k_ref|``: the mean over all of them, the two kinds also said
  apart, and the widest single position. The page table went back with
  the pages, so a page of the request is the pool page that lies nearest
  the reference's keys of those positions. A served token only says which
  logit stood first, and the logits of a bfloat16 program hide a cache
  held in a lower precision among their own rounding (PERF.md section 6,
  three times over); in the first layer nothing upstream of the keys but
  the embedding and two products rounds, so a float8 cache stands out. A
  key is made of three positions: a chunk that takes its tail from a
  wrong position leaves ONE or two wrong keys at its boundary, which the
  mean over a thousand positions would hide and the widest does not.
- ``tail_gap``: the slot's tail (both rows) and value half against the
  reference's at the settled request's last position, ``|t - t_ref| /
  |t_ref|`` a layer, in the mean over the layers whose routing UPSTREAM is
  settled (``settled_layers``). A layer's tail is made of the residual at
  that position and the one before, which holds the expert term of every
  layer below at those two positions; where the reference's router stands
  near a tie there (its margin under ``ROUTE_MARGIN``), a bfloat16 program
  may take the other expert, and from the next layer on its tail differs by
  a whole expert term (0.09 to 0.17, where bfloat16 alone reads 0.004 to
  0.05): that is a different, equally sound routing and not a tail from a
  wrong place, and ONE position has no mean to drown it in as the logits'
  thousands have. So the layers after the first near tie are said
  (``tail_gap_every_layer``, ``tail_layers_held``) and not judged; the
  first layer, which nothing routes into, always is. The slot is the one
  whose first layer's tail lies nearest the reference's.

The logit gaps are ``serve-lm.py``'s; beside them goes the share of
(served position, layer) pairs at which the reference's router margin is
under the rounding (``routing``), so that a wide gap can be put down to a
flipped expert or not.

The second control, a float8 K/V cache (``control_cache``): the program
served once more with its keys and values rounded to float8 at the write,
which has to fall outside a limit as the float8-weights reference does.

The engine is given an end token no vocabulary holds (``NO_END_TOKEN``):
the mix gives a reply's length as ``max_new_tokens``, and a real end token
would end that reply early and shift every later request of the closed
loop (PERF.md 7 (f)).
"""

import gc
import math
import time

import numpy as np

GAPS = ("widest_logit_gap", "mean_logit_gap", "page_gap", "page_gap_widest",
        "tail_gap")

NO_END_TOKEN = -1


def _model_kwargs(cfg, cache_dtype=None):
    rope = cfg["rope_parameters"]["hybrid"]
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        router_hidden=cfg["router_hidden_size"],
        cca_time0=cfg["cca_time0"], cca_time1=cfg["cca_time1"],
        partial_rotary_factor=rope["partial_rotary_factor"],
        rope_theta=rope["rope_theta"], rms_eps=cfg["rms_norm_eps"],
        cache_dtype=cache_dtype, dtype=cfg["precision"]["weights"])


def _build_program(cfg, ref, seed, cache_dtype=None):
    """The system under test: the zoo's model, given the seeded weights one
    tensor at a time, behind ``InferStep`` and ``make_batcher`` with
    default gates; no ``MXTPU_*`` variable is set."""
    import importlib

    mod, cls = cfg["program"]["model"].split(":")
    model = getattr(importlib.import_module(mod), cls)
    from mxnet_tpu import nd
    from mxnet_tpu.parallel import InferStep
    from mxnet_tpu.serving import make_batcher

    srv, dtype = cfg["serving"], cfg["precision"]["weights"]
    net = model(**_model_kwargs(cfg, cache_dtype))
    net.collect_params().setattr("grad_req", "null")   # served, not trained
    params = net._collect_params_with_prefix()
    specs = ref.tensor_specs(cfg)
    if set(params) != set(specs):
        raise SystemExit("perf: the program's parameters and the reference's "
                         f"differ: {sorted(set(params) ^ set(specs))[:6]}")
    for name, tensor in ref.init_params(seed, cfg):
        params[name].set_data(nd.NDArray(tensor.astype(dtype)))
        del tensor
    eng = InferStep(net, amp=None if dtype == "float32" else dtype,
                    eos_id=NO_END_TOKEN)
    bat = make_batcher(eng, srv["prompt_buckets"], slots=srv["slots"],
                       max_new_tokens=srv["max_new_tokens"],
                       page_size=srv["page_size"],
                       prefill_chunk=srv["prefill_chunk"],
                       iter_tokens=srv["iter_tokens"],
                       max_prefix_tokens=srv["max_prefix_tokens"],
                       prefix_cache=srv["prefix_cache"],
                       warmup=True, name="perf")
    return net, eng, bat


def steps_fed(n_tokens, iter_tokens):
    """Decode steps a request of ``n_tokens`` served tokens has run when it
    ends: a burst runs its ``iter_tokens`` steps whole, so the ``n - 1``
    steps it needs are rounded up, and step ``j`` feeds served token ``j``
    at position ``len(prompt) + j``."""
    return iter_tokens * math.ceil((n_tokens - 1) / iter_tokens)


def _ended(records):
    """The finished requests, the one that ended last first."""
    return sorted((r for r in records if r.error is None and r.tokens
                   and r.last is not None), key=lambda r: -r.last)


def _settled(records, iter_tokens):
    """The request that ended last among those whose every fed token the
    caller holds (the steps a burst runs past a request's last token feed
    tokens nobody was handed: one more than the served ones is not known).
    After the window closed no request was admitted, so its slot is as it
    left it."""
    for r in _ended(records):
        if steps_fed(len(r.tokens), iter_tokens) <= len(r.tokens):
            return r
    return None


def _serve_lm(ctx, kept, cache_dtype=None):
    """``serve-lm.py`` with this model's program in place of Keye's. The
    program's class is imported first: a program that lacks it ends the run
    here, in seconds, before any weight is made. ``kept`` receives, read
    once the scheduler has stopped, the first layer's pools and every
    layer's slot arrays as the window left them, and the request to hold
    them against."""
    lm = ctx.bench.driver("serve-lm")
    lm._program_class(ctx.config)
    serve_ = lm._serve

    def build(cfg, ref, seed):
        kept["program"] = program = _build_program(cfg, ref, seed,
                                                   cache_dtype)
        ctx.say("state_bytes", **program[2].state_bytes)
        return program

    def serve(*args):
        records = serve_(*args)
        net, eng, bat = kept.pop("program")
        state, arrays = bat.paged_state(), bat.slot_arrays()
        kept["read"] = {
            "k": np.asarray(state["k_pools"][0], np.float32),
            "v": np.asarray(state["v_pools"][0], np.float32),
            "tail": np.stack([np.asarray(a, np.float32)
                              for a in arrays["tail"]]),
            "half": np.stack([np.asarray(a, np.float32)
                              for a in arrays["value_half"]]),
            "last": next(iter(_ended(records)), None),
            "settled": _settled(records, bat.iter_tokens),
            "iter_tokens": bat.iter_tokens}
        del net, eng, bat, state, arrays
        gc.collect()                # the device is the reference's now
        return records

    def logit_gaps(ref, seed, cfg, sample, quant=None):
        """``serve-lm.py``'s four numbers from one forward a request, and
        beside them how often the reference's router stood near a tie at
        a served position."""
        got = [ref.served_gaps(seed, cfg, r.prompt, r.tokens, quant,
                               pad_to=cfg["check"].get("pad_to"))
               for r in sample]
        gaps = np.concatenate([g for g, _ in got]) if got else np.zeros((0,))
        if not len(gaps) or not np.isfinite(gaps).all():
            return float("nan"), float("nan"), len(gaps), 0
        ctx.say("routing", near_tie_share=float(np.mean([n for _, n in got])),
                margin=ref.ROUTE_MARGIN, requests=len(got))
        return float(gaps.max()), float(gaps.mean()), len(gaps), \
            int((gaps > 0).sum())

    lm._build_program, lm._serve, lm.logit_gaps = build, serve, logit_gaps
    return lm


def _relative(got, want):
    """``|got - want| / |want|`` over the last two axes."""
    return np.sqrt(((got - want) ** 2).sum((-2, -1))
                   / np.maximum((want ** 2).sum((-2, -1)), 1e-60))


def _forward(ref, seed, cfg, r, iter_tokens):
    """The reference's forward of what the program took in of request
    ``r``: its prompt and the served tokens its decode steps were fed, as
    far as the caller holds them. Returns ``(positions, prompt length,
    tap)``: the first layer's keys and values at every position and every
    layer's tail at the last."""
    fed = min(steps_fed(len(r.tokens), iter_tokens), len(r.tokens))
    seq = np.concatenate([np.asarray(r.prompt, np.int32),
                          np.asarray(r.tokens[:fed], np.int32)])
    tap = {"kv_layers": (0,), "tail_at": len(seq) - 1}
    ref.hidden(seed, cfg, seq, tap=tap, pad_to=cfg["check"].get("pad_to"))
    return len(seq), len(r.prompt), tap


def settled_layers(margins, at, margin):
    """``(L,)`` bool: the layers whose tail at position ``at`` no near tie
    lies upstream of. ``margins (L, S)`` is the reference's router margin
    between its two best; layer ``l``'s tail is made of the residual that
    enters it at ``at`` and ``at - 1``, so it is held while in every layer
    below it both positions' margins are ``margin`` or more. The first
    layer always is."""
    near = (margins[:, max(at - 1, 0):at + 1] < margin).any(1)
    return np.concatenate([[True], np.cumsum(near)[:-1] == 0])


def cache_and_tail_gaps(ref, seed, cfg, read):
    """``({page_gap, page_gap_widest, tail_gap}, more)`` of what the
    stopped scheduler left (``read``) against the reference's forwards of
    the request that ended last (``read["last"]``: its pages) and of the
    settled one (``read["settled"]``: its slot's tail, over
    ``settled_layers``); one forward where they are the same request."""
    nan = float("nan")
    last, settled = read["last"], read["settled"]
    if last is None or settled is None:
        return dict.fromkeys(GAPS[2:], nan), {"positions": 0}
    kv = cfg["num_key_value_heads"]
    page = cfg["serving"]["page_size"]
    positions, prompt, tap = _forward(ref, seed, cfg, last,
                                      read["iter_tokens"])
    # ---- the pages: (num_pages, page x heads, D) as (num_pages, page,
    # heads, D); a page of the request is the pool page nearest its keys
    pools = {n: read[n].reshape(read[n].shape[0], page, kv, -1)
             for n in ("k", "v")}
    flat = pools["k"].reshape(len(pools["k"]), page, -1)
    gaps = []
    for j in range(0, positions, page):
        want = {n: tap[n][0][j:j + page] for n in pools}
        held = len(want["k"])
        near = flat[:, :held].reshape(len(flat), -1)
        key = want["k"].reshape(-1)
        at = int(np.argmin((near * near).sum(1) - 2.0 * near @ key))
        gaps.append(np.stack([_relative(pools[n][at, :held], want[n])
                              for n in pools], 1))       # (held, 2)
    gaps = np.concatenate(gaps)
    near_ties = float((tap["margins"] < ref.ROUTE_MARGIN).mean())
    # ---- the slot: the one whose first layer's tail lies nearest
    end = positions
    if settled is not last:
        end, _, tap = _forward(ref, seed, cfg, settled, read["iter_tokens"])
    slot = int(np.argmin(_relative(read["tail"][0], tap["tails"][0])))
    tail = _relative(read["tail"][:, slot], tap["tails"])
    half = _relative(read["half"][:, slot][:, None], tap["halves"][:, None])
    held = settled_layers(tap["margins"], end - 1, ref.ROUTE_MARGIN)
    return {"page_gap": float(gaps.mean()),
            "page_gap_widest": float(gaps.max()),
            "tail_gap": float(np.concatenate([tail[held],
                                              half[held]]).mean())}, {
        "positions": positions, "prompt": prompt,
        "page_gap_prompt": float(gaps[:prompt].mean()),
        "page_gap_reply": float(gaps[prompt:].mean())
        if positions > prompt else nan,
        "tail_of_the_last": settled is last,
        "tail_layers_held": int(held.sum()),
        "tail_gap_every_layer": float(np.concatenate([tail, half]).mean()),
        "tail_gap_first_layer": float(tail[0]),
        "tail_gap_last_layer": float(tail[-1]),
        "near_tie_share": near_ties}


def run(ctx, with_control=False, cache_dtype=None):
    cfg, kept = ctx.config, {}
    lm = _serve_lm(ctx, kept, cache_dtype)
    run = lm.run(ctx, with_control)
    # ---- what the window left in the first layer's pages and in every
    # layer's tail, against the reference's
    t = time.perf_counter()
    numbers, more = cache_and_tail_gaps(
        ctx.bench.reference(cfg["name"]), ctx.seed, cfg, kept.pop("read"))
    inside = lm._compare(ctx, cfg, numbers,
                         reference_s=time.perf_counter() - t, **more)
    run.correct = run.correct and inside and more["positions"] > 0
    gc.collect()
    return run


def control(ctx):
    """Two controls, and each has to fall outside a limit: the reference in
    float8 in the program's place, at the positions of the program's own
    served tokens; then the program itself once more with its K/V cache
    rounded to float8 (its comparisons are marked ``of: control_cache``)."""
    weights = not run(ctx, with_control=True).control_inside
    say, outside, t = ctx.say, [], time.perf_counter()

    def marked(note, **fields):
        if note == "compared":
            fields["of"] = "control_cache"
            if fields["number"] in GAPS and not fields["inside"]:
                outside.append(fields["number"])
        say(note, **fields)

    ctx.say = marked
    try:
        run(ctx, cache_dtype=ctx.config["control_cache"])
    finally:
        ctx.say = say
    ctx.say("control_cache", cache_dtype=ctx.config["control_cache"],
            found_not_correct=bool(outside), outside=outside,
            seconds=time.perf_counter() - t)
    return weights and bool(outside)

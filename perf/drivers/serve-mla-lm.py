"""Driver of serving cells of a latent-attention language model with its own
draft module: what ``serve-lm.py`` does (the zoo's model behind
``InferStep`` and the default batcher, a closed loop of callers timed from
the client's side, the served tokens held against the plain reference's
logits), for a model whose constructor takes the ``joyai_llm_flash`` keys.
Everything but building the program is ``serve-lm.py``'s, taken as it takes
``serve.py``'s; that driver reads Keye's keys where it builds
(``_model_kwargs`` inside ``_build_program``), so this one brings its own
two and hands them over, as ``serve-hybrid-lm.py`` does.

What it adds to the comparison that decides ``correct``:

- ``mtp_logit_gap``, the draft module itself. With seeded weights no draft
  is the model's own token, so a module that computed nonsense would serve
  the very same tokens. The scheduler hands each request's drafts back with
  its tokens (``GenerationResult.drafts``); the last ``check.
  draft_positions`` of each sampled request are held against the
  reference module's logits at the position they were made from, as the
  served tokens are held against the model's: how far the drafted token's
  logit lies below the reference module's best, in the mean.
- ``latent_gap``, the cache itself. A served token only says which logit
  stood first, and the logits of a bfloat16 program hide a cache held in a
  lower precision than the configuration states among their own rounding
  (as they hid granite's state, PERF.md section 6, PR 31). So once the
  scheduler has stopped, the engine's own chunk program writes the first
  chunk of the longest sampled prompt into the pages the scheduler left,
  and what the first layer cached is held against the reference's latents,
  position by position: ``|l - l_ref| / |l_ref|`` in the mean.
- the second control, a float8 latent cache (``control_cache``): the
  program served once more with its latents rounded to float8 at the write,
  which has to fall outside a limit as the float8-weights reference does.

The engine is given an end token no vocabulary holds (``NO_END_TOKEN``):
the mix gives a reply's length as ``max_new_tokens``, and a real end token,
emitted about once in the 100,000 tokens of a run, would end that reply
early and shift every later request of the closed loop (PERF.md 7 (f)).
"""

import gc
import time

import numpy as np

GAPS = ("widest_logit_gap", "mean_logit_gap", "mtp_logit_gap", "latent_gap")

NO_END_TOKEN = -1


def _model_kwargs(cfg, latent_dtype=None):
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        first_dense=cfg["first_k_dense_replace"],
        num_experts=cfg["router_width"],
        experts_held=tuple(cfg["experts_held"]),
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"],
        routed_scaling=cfg["routed_scaling_factor"],
        rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
        latent_dtype=latent_dtype, dtype=cfg["precision"]["weights"])


def _build_program(cfg, ref, seed, latent_dtype=None):
    """The system under test: the zoo's model, given the seeded weights one
    tensor at a time, behind ``InferStep`` and ``make_batcher`` with
    default gates; no ``MXTPU_*`` variable is set, and nothing here turns
    the drafting on: the net declares it."""
    import importlib

    mod, cls = cfg["program"]["model"].split(":")
    model = getattr(importlib.import_module(mod), cls)
    from mxnet_tpu import nd
    from mxnet_tpu.parallel import InferStep
    from mxnet_tpu.serving import make_batcher

    srv, dtype = cfg["serving"], cfg["precision"]["weights"]
    net = model(**_model_kwargs(cfg, latent_dtype))
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


def _serve_lm(ctx, kept, latent_dtype=None):
    """``serve-lm.py`` with this model's program in place of Keye's and
    its comparison reading the drafts beside the tokens. The program's
    class is imported first: a program that lacks it ends the run here, in
    seconds, before any weight is made. ``kept`` receives the drafts of
    every request by its prompt, and the draft gaps of each comparison."""
    lm = ctx.bench.driver("serve-lm")
    lm._program_class(ctx.config)
    kept["futures"], kept["draft_gaps"] = {}, {}

    serve_ = lm._serve

    def build(cfg, ref, seed):
        kept["program"] = net, eng, bat = _build_program(
            cfg, ref, seed, latent_dtype)
        ctx.say("state_bytes", **bat.state_bytes)
        submit = bat.submit

        def keep(prompt, **kw):
            kept["futures"][id(prompt)] = fut = submit(prompt, **kw)
            return fut

        bat.submit = keep
        return net, eng, bat

    def logit_gaps(ref, seed, cfg, sample, quant=None):
        """``serve-lm.py``'s numbers, from ONE forward of the reference a
        request that scores the drafts too."""
        last = int(cfg["check"]["draft_positions"])
        served, drafted = [], []
        for r in sample:
            fut = kept["futures"].get(id(r.prompt))
            drafts = list(getattr(fut, "drafts", None) or [])[-last:]
            a, b = ref.served_gaps(seed, cfg, r.prompt, r.tokens, drafts,
                                   quant, pad_to=cfg["check"].get("pad_to"))
            served.append(a), drafted.append(b)
        kept["draft_gaps"][quant] = np.concatenate(drafted) if drafted \
            else np.zeros((0,))
        gaps = np.concatenate(served) if served else np.zeros((0,))
        if not len(gaps) or not np.isfinite(gaps).all():
            return float("nan"), float("nan"), len(gaps), 0
        return float(gaps.max()), float(gaps.mean()), len(gaps), \
            int((gaps > 0).sum())

    def serve(*args):
        records = serve_(*args)
        net, eng, bat = kept.pop("program")
        kept["latents"] = _first_chunk_latents(eng, bat, records)
        del net, eng, bat
        gc.collect()                # the device is the reference's now
        return records

    lm._build_program, lm.logit_gaps, lm._serve = build, logit_gaps, serve
    return lm


def _first_chunk_latents(eng, bat, records):
    """``(tokens, latents (n, rank + rope))``: the first chunk of the
    longest finished prompt, written once more by the engine's chunk program
    (the shapes the scheduler dispatched: nothing compiles) into the first
    pages of the state the stopped scheduler left, and what the first layer
    cached for it."""
    ok = [r for r in records if r.error is None and r.tokens]
    if not ok:
        return None
    r = max(ok, key=lambda r: (len(r.prompt), -r.index))
    chunk, page = bat.chunk, bat.page_size
    part = np.asarray(r.prompt[:chunk], np.int32)
    toks = np.zeros((1, chunk), np.int32)
    toks[0, :len(part)] = part
    table = np.zeros((1, bat.pages_per_slot), np.int32)
    pages = -(-len(part) // page)
    table[0, :pages] = 1 + np.arange(pages)
    _, state = eng.prefill_suffix_paged(
        bat.paged_state(), toks, [len(part)], [0], table, [0], [True],
        wide=True)
    pool = state["latent_pools"][0]
    got = np.asarray(pool[1:1 + pages], np.float32)
    return part, got.reshape(-1, got.shape[-1])[:len(part)]


def latent_gap(ref, seed, cfg, latents):
    """Mean over the chunk's positions of ``|l - l_ref| / |l_ref|``."""
    if latents is None:
        return float("nan"), 0
    tokens, got = latents
    want = ref.first_latents(seed, cfg, tokens)
    got = got[:, :want.shape[1]]        # the row's lanes past it are zero
    gap = np.sqrt(((got - want) ** 2).sum(-1)
                  / np.maximum((want ** 2).sum(-1), 1e-60))
    return float(gap.mean()) if np.isfinite(gap).all() else float("nan"), \
        len(gap)


def _draft_gap(gaps):
    if gaps is None or not len(gaps) or not np.isfinite(gaps).all():
        return float("nan"), 0
    return float(np.mean(gaps)), int((gaps > 0).sum())


def run(ctx, with_control=False, latent_dtype=None):
    cfg, kept = ctx.config, {}
    lm = _serve_lm(ctx, kept, latent_dtype)
    run = lm.run(ctx, with_control)
    # ---- the module's drafts against the reference module's logits
    mean, off = _draft_gap(kept["draft_gaps"].get(None))
    positions = len(kept["draft_gaps"].get(None, ()))
    inside = lm._compare(ctx, cfg, {"mtp_logit_gap": mean},
                         positions=positions, off_best=off)
    run.correct = run.correct and inside and positions > 0
    # ---- the first layer's cached latents against the reference's
    gap, n = latent_gap(ctx.bench.reference(cfg["name"]), ctx.seed, cfg,
                        kept.get("latents"))
    inside = lm._compare(ctx, cfg, {"latent_gap": gap}, positions=n)
    run.correct = run.correct and inside and n > 0
    if with_control:
        mean, off = _draft_gap(kept["draft_gaps"].get(cfg["control"]))
        run.control_inside = lm._compare(
            ctx, cfg, {"mtp_logit_gap": mean}, of="control",
            off_best=off) and run.control_inside
    kept.clear()
    gc.collect()
    return run


def control(ctx):
    """Two controls, and each has to fall outside a limit: the reference in
    float8 in the program's place, at the positions of the program's own
    served tokens and drafts; then the program itself once more with its
    latent cache rounded to float8 (its comparisons are marked ``of:
    control_cache``)."""
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
        run(ctx, latent_dtype=ctx.config["control_cache"])
    finally:
        ctx.say = say
    ctx.say("control_cache", latent_dtype=ctx.config["control_cache"],
            found_not_correct=bool(outside), outside=outside,
            seconds=time.perf_counter() - t)
    return weights and bool(outside)

"""Driver of serving cells of a LOOPED language model (one stack of layers
run several times over the same weights, a K/V plane a pass): what
``serve-lm.py`` does (the zoo's model behind ``InferStep`` and the default
batcher, a closed loop of callers timed from the client's side, the served
tokens held against the plain reference's logits), for a model whose
constructor takes the ``ouro`` keys. Everything but building the program is
``serve-lm.py``'s, taken as it takes ``serve.py``'s; that driver reads
Keye's keys where it builds (``_model_kwargs`` inside ``_build_program``),
so this one brings its own two and hands them over, as
``serve-hybrid-lm.py`` and ``serve-mla-lm.py`` do.

What it adds to the comparison that decides ``correct``, read once the
scheduler has stopped and before anything else touches its state:

- ``plane_gap`` and ``plane_gap_last``, the cache itself, AS THE WINDOW'S OWN
  PROGRAMS LEFT IT: what the first layer holds at plane 0 and at plane ``T
  - 1`` for the request that ended last (as its pages went back nobody was
  left to take them), the prompt's positions that the chunk program wrote
  and the reply's that the decode bursts wrote, against the keys the
  reference makes in its first and its last pass, position by position,
  ``|k - k_ref| / |k_ref|`` in the mean, each plane against its own limit.
  The page table went back with the pages, so a page of the request is the
  pool page whose plane 0 lies nearest the reference's keys of those
  positions (the first layer's keys of the first pass are a function of
  token and position alone, and no two pages hold the same tokens): a
  program that wrote another pass's keys there has no near page and reads
  a gap of the order of 1. A served token only says which logit stood
  first, and the logits of a bfloat16 program hide a cache held in a lower
  precision than the configuration states among their own rounding (as
  they hid granite's state and joyai's latents, PERF.md section 6): at
  plane 0 nothing upstream of the keys but the embedding and one product
  rounds, so a float8 cache stands out there. At plane ``T - 1`` the keys
  come after ``T - 1`` passes of the whole stack in bfloat16, whose
  rounding a stack of seeded weights amplifies, so that reading is loose
  and is there for the PLANES: a chunk or a decode step that shares a
  plane between passes, or writes another pass's, leaves at one of the two
  planes keys that are a whole pass away from the reference's.
- ``gate_gap``, the exit gate: the program counts the exit distribution
  ``p_t`` of every position whose logits it hands back (``exit_mass``,
  parts per million a pass). Afterwards the engine's own chunk program
  takes ``check.plane_prompts`` finished prompts once more, one a call,
  into the first pages of the state the scheduler left; a call hands back
  one position, the prompt's last, and its ``p_t`` is held against the
  reference's there: the largest ``|p_t - p_t_ref|`` over the passes, in
  the mean over the prompts. At threshold 1 the gate decides no token, so
  nothing else sees it; its bias is drawn away from zero
  (``perf/reference/ouro-2.6b.py``), so a gate without it moves ``p_t`` by
  several times the limit.

The second control, a float8 K/V cache (``control_cache``): the program
served once more with its keys and values rounded to float8 at the write,
which has to fall outside a limit as the float8-weights reference does.

The engine is given an end token no vocabulary holds (``NO_END_TOKEN``):
the mix gives a reply's length as ``max_new_tokens``, and a real end token,
emitted about once in the 50,000 tokens of a run, would end that reply
early and shift every later request of the closed loop (PERF.md 7 (f)).
"""

import gc
import time

import numpy as np

GAPS = ("widest_logit_gap", "mean_logit_gap", "plane_gap", "plane_gap_last",
        "gate_gap")

NO_END_TOKEN = -1


def _model_kwargs(cfg, cache_dtype=None):
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        total_ut_steps=cfg["total_ut_steps"],
        early_exit_threshold=cfg["early_exit_threshold"],
        rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
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


def _serve_lm(ctx, kept, cache_dtype=None):
    """``serve-lm.py`` with this model's program in place of Keye's. The
    program's class is imported first: a program that lacks it ends the run
    here, in seconds, before any weight is made. ``kept`` receives, read
    once the scheduler has stopped, the first layer's planes as the window
    left them and what the chunk program counted for some of the finished
    prompts (``_planes``, ``_gates``)."""
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
        cfg = ctx.config
        n = int(cfg["check"]["plane_prompts"])
        ended = [r for r in records if r.error is None and r.tokens
                 and r.last is not None]
        # the request that ended last, and finished prompts beside it
        last = max(ended, key=lambda r: r.last) if ended else None
        sample = [r for r in lm._check_sample(
            records, dict(cfg, check={"sample_requests": n}), ctx.seed)
            if r is not last][:n - 1]
        planes = _planes(bat, cfg["total_ut_steps"])
        kept["read"] = planes, last, _gates(
            eng, bat, ([last] if last else []) + sample,
            cfg["total_ut_steps"])
        del net, eng, bat
        gc.collect()                # the device is the reference's now
        return records

    lm._build_program, lm._serve = build, serve
    return lm


def _planes(bat, passes):
    """``{plane: (num_pages, page, heads, D) float32}``: the first layer's
    key pool at planes 0 and ``passes - 1`` as the stopped scheduler left
    it."""
    pool = bat.paged_state()["k_pools"][0]
    return {t: np.asarray(pool[t], np.float32)
            for t in sorted({0, passes - 1})}


def _gates(eng, bat, requests, passes):
    """``[(prompt's first chunk, p (passes,))]`` a request: the prompt
    written once more by the engine's chunk program into the first pages
    of the state the stopped scheduler left (the shapes the scheduler
    dispatched), and the exit distribution the call counted at the
    prompt's last position. The calls donate the state: nothing reads the
    pools after them."""
    chunk, page = bat.chunk, bat.page_size
    state, out = bat.paged_state(), []
    at = 1                                  # behind the one token
    for name, length in eng.slot_state["counts"]:
        if name == "exit_mass":
            break
        at += length
    for r in requests:
        part = np.asarray(r.prompt[:chunk], np.int32)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(part)] = part
        table = np.zeros((1, bat.pages_per_slot), np.int32)
        pages = -(-len(part) // page)
        table[0, :pages] = 1 + np.arange(pages)
        got, state = eng.prefill_suffix_paged(
            state, toks, [len(part)], [0], table, [0], [True], wide=True)
        out.append((part, got.asnumpy()[at:at + passes] / 1e6))
    return out


def _key_gaps(got, want):
    """``|k - k_ref| / |k_ref|`` of each position's keys ``(n, heads,
    D)``."""
    return np.sqrt(((got - want) ** 2).sum((-2, -1))
                   / np.maximum((want ** 2).sum((-2, -1)), 1e-60))


def plane_and_gate_gaps(ref, seed, cfg, planes, last, gates):
    """``(plane_gap, plane_gap_last, gate_gap, positions)``: the mean over
    the cached positions of the request ``last`` (its prompt and all but
    the last of its served tokens) of ``|k - k_ref| / |k_ref|`` at plane 0
    and at plane ``T - 1`` of ``planes``, each page of the request taken
    from the pool page that lies nearest at plane 0; and the mean over
    ``gates`` (the first is ``last``'s) of the largest ``|p_t -
    p_t_ref|`` at a prompt's last position."""
    final = cfg["total_ut_steps"] - 1
    page, pad_to = cfg["serving"]["page_size"], cfg["check"].get("pad_to")
    gaps, moved = {0: [], final: []}, []
    for n, (part, mass) in enumerate(gates):
        seq, tap = part, None
        if n == 0:          # one forward gives this request's keys too
            seq = np.concatenate([np.asarray(last.prompt, np.int32),
                                  np.asarray(last.tokens[:-1], np.int32)])
            tap = {"planes": ((0, 0), (final, 0))}
        _, lam = ref.forward(seed, cfg, seq, want=[len(part) - 1], tap=tap,
                             pad_to=pad_to)
        p = ref.exit_distribution(np.asarray(lam)[:, len(part) - 1])
        moved.append(np.abs(mass - p).max())
        for j in range(0, len(seq) if tap else 0, page):
            want = {t: tap["keys"][(t, 0)][j:j + page] for t in gaps}
            held = len(want[0])
            at = int(np.argmin(((planes[0][:, :held] - want[0]) ** 2).sum(
                (1, 2, 3))))
            for t in gaps:
                gaps[t].append(_key_gaps(planes[t][at, :held], want[t]))
    positions = sum(len(g) for g in gaps[0])
    if not positions:
        return float("nan"), float("nan"), float("nan"), 0
    first, last_ = (float(np.concatenate(gaps[t]).mean())
                    for t in (0, final))
    return first, last_, float(np.mean(moved)), positions


def run(ctx, with_control=False, cache_dtype=None):
    cfg, kept = ctx.config, {}
    lm = _serve_lm(ctx, kept, cache_dtype)
    run = lm.run(ctx, with_control)
    # ---- what the window left in the first layer's first and last plane,
    # and the exit distribution the program counted, against the reference's
    t = time.perf_counter()
    planes, last, gates = kept.pop("read")
    first, final, gate, positions = plane_and_gate_gaps(
        ctx.bench.reference(cfg["name"]), ctx.seed, cfg, planes, last, gates)
    inside = lm._compare(
        ctx, cfg, {"plane_gap": first, "plane_gap_last": final,
                   "gate_gap": gate},
        positions=positions, prompt=len(last.prompt) if last else 0,
        gates=len(gates), reference_s=time.perf_counter() - t)
    run.correct = run.correct and inside and positions > 0
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

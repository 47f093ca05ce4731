"""Driver of serving cells of a hybrid state-space language model: what
``serve-lm.py`` does (the zoo's model behind ``InferStep`` and the default
batcher, a closed loop of callers timed from the client's side, the served
tokens held against the plain reference's logits), for a model whose
constructor takes the ``granitemoehybrid`` keys. Everything but building
the program is ``serve-lm.py``'s, taken as it takes ``serve.py``'s; that
driver reads Keye's keys where it builds (``_model_kwargs`` inside
``_build_program``), so this one brings its own two and hands them over.

What it adds to the comparison that decides ``correct``: the recurrent
state itself. A served token only says which logit stood first, and the
logits of a bfloat16 program hide a state carried in a lower precision than
the configuration states among their own rounding (PERF.md section 6, PR
31). So once the scheduler has stopped, the slots' recurrent state is read
as the last requests left it, and the longest of them are held against the
reference's token-by-token state at the same position, head by head
(``mean_state_gap``).

What it takes from the configuration beside ``serve-lm.py``'s keys:
``precision.state``, the dtype a slot's recurrent state is carried in, and
``check.state_requests``, how many requests' final states are compared.

The engine is given an end token no vocabulary holds (``NO_END_TOKEN``). The
mix gives a reply's length as ``max_new_tokens``; with random weights every
id is as likely as any other, so a real end token is emitted about once in
the 100,000 tokens of a run, that reply ends early, and the schedule of
every later request of the closed loop shifts (``replies_ended_early`` in
the output counts them; PERF.md section 6, PR 31).
"""

import gc
import math
import time

import numpy as np

NO_END_TOKEN = -1


def _model_kwargs(cfg):
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["shared_intermediate_size"],
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_state=cfg["mamba_d_state"], mamba_groups=cfg["mamba_n_groups"],
        mamba_conv=cfg["mamba_d_conv"], mamba_expand=cfg["mamba_expand"],
        mamba_chunk=cfg["mamba_chunk_size"],
        attention_multiplier=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], rms_eps=cfg["rms_norm_eps"],
        state_dtype=cfg["precision"]["state"],
        dtype=cfg["precision"]["weights"])


def _build_program(cfg, ref, seed):
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
    net = model(**_model_kwargs(cfg))
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


def _serve_lm(ctx, kept):
    """``serve-lm.py`` with this model's program in place of Keye's. The
    program's class is imported first: a program that lacks it ends the run
    here, in seconds, before any weight is made. ``kept`` receives the
    run's records and, read once the scheduler has stopped, every slot's
    recurrent state on the host."""
    lm = ctx.bench.driver("serve-lm")
    lm._program_class(ctx.config)
    serve_ = lm._serve

    def build(cfg, ref, seed):
        kept["program"] = program = _build_program(cfg, ref, seed)
        return program

    def serve(*args):
        records = serve_(*args)
        bat = kept.pop("program")[2]
        # (layers, slots, heads, d_head, d_state): what each slot's last
        # occupant left
        arrays = bat.slot_arrays()["ssm"]
        kept["ssm"] = ssm = np.empty((len(arrays),) + arrays[0].shape,
                                     np.float32)
        for i, a in enumerate(arrays):
            ssm[i] = np.asarray(a)
        del arrays
        kept["records"] = records
        del bat
        gc.collect()                # the device is the reference's now
        return records

    lm._build_program, lm._serve = build, serve
    return lm


def _state_sample(records, n):
    """The ``n`` longest requests whose final state no later request can
    have overwritten: those that ended after the last admission (a slot
    is zeroed by the first chunk of the prompt that takes it, before that
    prompt's first token). A reply that an end token cut short is left
    out: the burst stopped its row where the host cannot see."""
    ok = [r for r in records if r.error is None and r.tokens
          and r.last is not None]
    if not ok:
        return []
    last_admission = max(r.first for r in ok)
    intact = [r for r in ok if len(r.tokens) == r.max_new
              and (r.last > last_admission or r.first == last_admission)]
    intact.sort(key=lambda r: (-(len(r.prompt) + len(r.tokens)), r.index))
    return intact[:n]


def _head_gaps(got, want):
    """``|got - want| / |want|`` of each head's ``(d_head, d_state)``
    state (the last two axes)."""
    return np.sqrt(((got - want) ** 2).sum((-2, -1))
                   / np.maximum((want ** 2).sum((-2, -1)), 1e-60))


def state_gaps(ref, seed, cfg, sample, ssm):
    """``(widest, mean, heads)`` of the relative gap between a slot's
    recurrent state and the reference's, over every head of every
    state-space layer of every request of the sample. A decode burst runs
    its ``iter_tokens`` steps whole, so a request of ``n`` served tokens
    has taken its prompt and ``iter_tokens x ceil((n - 1) / iter_tokens)``
    of them in. The slot is the one whose first state-space layer lies
    nearest the reference's; a program that dropped or overwrote the state
    has no near one and reads a gap of the order of 1."""
    it = int(cfg["serving"]["iter_tokens"])
    gaps = []
    for r in sample:
        fed = it * math.ceil((len(r.tokens) - 1) / it)
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.tokens[:fed], np.int32)])
        want = ref.final_states(seed, cfg, seq, [len(seq)],
                                pad_to=cfg["check"].get("pad_to"))[:, 0]
        slot = int(np.argmin(_head_gaps(ssm[0], want[0]).mean(-1)))
        gaps.append(_head_gaps(ssm[:, slot], want).ravel())
    gaps = np.concatenate(gaps) if gaps else np.zeros((0,))
    if not len(gaps) or not np.isfinite(gaps).all():
        return float("nan"), float("nan"), len(gaps)
    return float(gaps.max()), float(gaps.mean()), len(gaps)


def run(ctx, with_control=False):
    cfg, kept = ctx.config, {}
    lm = _serve_lm(ctx, kept)
    run = lm.run(ctx, with_control)
    records = kept["records"]
    ctx.say("replies", finished=sum(r.error is None and bool(r.tokens)
                                    for r in records),
            replies_ended_early=sum(
                r.error is None and bool(r.tokens)
                and len(r.tokens) < r.max_new for r in records))
    # ---- the slots' recurrent state against the plain reference's
    t = time.perf_counter()
    ref = ctx.bench.reference(cfg["name"])
    sample = _state_sample(records, int(cfg["check"]["state_requests"]))
    widest, mean, heads = state_gaps(ref, ctx.seed, cfg, sample,
                                     kept.pop("ssm"))
    # the mean over some thousands of heads carries the precision; the
    # widest head swings from seed to seed and is said beside it
    inside = lm._compare(
        ctx, cfg, {"mean_state_gap": mean}, widest_head=widest,
        requests=len(sample), heads=heads,
        positions=[len(r.prompt) + len(r.tokens) for r in sample],
        reference_s=time.perf_counter() - t)
    run.correct = run.correct and inside and heads > 0
    return run


def control(ctx):
    """The control: the reference in float8 in the program's place, at the
    positions of the program's own served tokens; it has to fall outside
    the limits."""
    return not run(ctx, with_control=True).control_inside

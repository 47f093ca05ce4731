"""Driver of serving cells of a decoder-only language model: the zoo's model
behind ``InferStep`` and the default batcher, offered a closed loop of
callers, timed from the client's side (``serve.py`` builds encoder-decoder
inputs; its callers, its records and its arithmetic are reused as they
are).

What it takes from the configuration: ``program``, ``precision``,
``serving`` (slots, pages, chunk, buckets, limits), ``check``,
``tolerance``. From the traffic mix: ``clients``, the two length
distributions, ``drain_s``.

Order of a run: the program's class is imported and built (a program that
lacks it ends the run here, before any weight is made) -> seeded weights,
tensor by tensor from the reference's generator, cast to the serving dtype
and handed to the program as each is made -> engine and batcher built and
warmed -> the callers start and each finishes one request (the ramp,
set-up) -> window -> drain -> the program is stopped and freed -> the plain
reference runs over a seeded sample of the requests the window finished,
the longest among them, one sequence at a time and layer by layer, and the
served tokens are held against its logits at every served position.
"""

import gc
import importlib
import time

import numpy as np

from perf.harness import traffic_lm as gen
from perf.harness.main import Run


def _program_class(cfg):
    mod, cls = cfg["program"]["model"].split(":")
    try:
        return getattr(importlib.import_module(mod), cls)
    except (ImportError, AttributeError) as e:
        raise SystemExit(f"perf: the program cannot run configuration "
                         f"{cfg['name']!r}: {e}")


def _model_kwargs(cfg):
    sa = cfg["sa_config"]
    return dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        kv_chunk=sa["kv_chunk_size"], rope_theta=cfg["rope_theta"],
        mrope_section=cfg["rope_scaling"]["mrope_section"],
        rms_eps=cfg["rms_norm_eps"], dtype=cfg["precision"]["weights"])


def _build_program(cfg, ref, seed):
    """The system under test: the zoo's model, given the seeded weights one
    tensor at a time, behind ``InferStep`` and ``make_batcher`` with
    default gates; no ``MXTPU_*`` variable is set."""
    model = _program_class(cfg)          # before any weight is made
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
    eng = InferStep(net, amp=None if dtype == "float32" else dtype)
    bat = make_batcher(eng, srv["prompt_buckets"], slots=srv["slots"],
                       max_new_tokens=srv["max_new_tokens"],
                       page_size=srv["page_size"],
                       prefill_chunk=srv["prefill_chunk"],
                       iter_tokens=srv["iter_tokens"],
                       max_prefix_tokens=srv["max_prefix_tokens"],
                       prefix_cache=srv["prefix_cache"],
                       warmup=True, name="perf")
    return net, eng, bat


def _check_sample(records, cfg, seed):
    """A seeded sample of finished requests with the longest (prompt and
    reply together) in it."""
    ok = [r for r in records if r.error is None and r.tokens]
    if not ok:
        return []
    n = min(int(cfg["check"]["sample_requests"]), len(ok))
    longest = max(ok, key=lambda r: (len(r.prompt) + len(r.tokens), -r.index))
    rest = [r for r in ok if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, 7])
    return [longest] + [rest[j] for j in rng.permutation(len(rest))[:n - 1]]


def logit_gaps(ref, seed, cfg, sample, quant=None):
    """``(widest, mean, positions, off_best)`` of the gap, over every served position
    of the sample, by which the served token's logit lies below the
    reference's best; with ``quant`` the control's tokens stand in for the
    served ones. Selection is discrete (a bfloat16 program may pick a
    ninth-ranked expert or a 2,049th-ranked key where float32 picks the
    other), so the widest gap swings with single flips and only bounds
    gross faults; the mean over all positions carries the precision.
    ``off_best`` counts the positions whose served token is not the
    reference's best: how often a flip or a rounding decided a token."""
    gaps = np.concatenate([
        ref.served_token_gaps(seed, cfg, r.prompt, r.tokens, quant,
                              pad_to=cfg["check"].get("pad_to"))
        for r in sample]) if sample else np.zeros((0,))
    if not len(gaps) or not np.isfinite(gaps).all():
        return float("nan"), float("nan"), len(gaps), 0
    return float(gaps.max()), float(gaps.mean()), len(gaps), \
        int((gaps > 0).sum())


def _compare(ctx, cfg, numbers, of=None, **more):
    """Says each number beside its limit; True when all are inside."""
    inside = True
    for name, value in numbers.items():
        limit = cfg["tolerance"][name]
        ok = bool(value <= limit)
        inside = inside and ok
        ctx.say("compared", number=name, value=value, limit=limit,
                inside=ok, **({"of": of} if of else {}), **more)
    return inside


def _serve(ctx, cfg, mix, run, ref, serve):
    """Build, warm, ramp, window, drain, stop. Returns the records."""
    import jax

    t_build = time.perf_counter()
    net, eng, bat = _build_program(cfg, ref, ctx.seed)
    t_ramp = time.perf_counter()
    ctx.memory.sample("built_and_warm")
    stream = gen.RequestStream(mix, ctx.seed, cfg["vocab_size"])
    drain_s = float(mix["drain_s"])
    loop = serve.ClosedLoop(bat, stream, int(mix["clients"]), drain_s,
                            ctx.tracer.span)
    loop.start()
    ramp_deadline = time.perf_counter() + drain_s
    while min(loop.done_by_client) < 1:   # the ramp: every slot refilled once
        if time.perf_counter() > ramp_deadline:
            raise SystemExit("perf: the ramp did not finish in drain_s")
        time.sleep(0.01)
    ctx.say("setup_parts", before_build=t_build - ctx.process_start,
            build_and_warm_up=t_ramp - t_build,
            ramp=time.perf_counter() - t_ramp,
            warmup_programs=eng.compile_guard.signatures)
    run.compiles_before_window = ctx.compiles.count
    stats0 = dict(bat.stats)
    run.window_start = t0 = time.perf_counter()
    ctx.memory.sample("window_open")
    if ctx.trace:
        after, length = ctx.tracer.stretch(ctx.seconds)
        time.sleep(after)
        ctx.tracer.start()
        time.sleep(length)
        ctx.tracer.stop()
    time.sleep(max(0.0, t0 + ctx.seconds / 2 - time.perf_counter()))
    ctx.memory.sample("mid_window")
    time.sleep(max(0.0, t0 + ctx.seconds - time.perf_counter()))
    loop.close()
    t1 = time.perf_counter()
    stats1 = dict(bat.stats)
    ctx.memory.sample("window_close")
    run.compiles_in_window = ctx.compiles.count - run.compiles_before_window
    run.window_s = t1 - t0
    all_ended = loop.join(drain_s)
    # the scheduler retires on its own thread: give it a moment to hand the
    # last pages back before they are counted
    pool = bat.pool
    deadline = time.perf_counter() + 10
    while pool.free_pages != pool.num_pages \
            and time.perf_counter() < deadline:
        time.sleep(0.01)
    unaccounted = pool.num_pages - pool.free_pages
    bat.stop()
    recompiles = eng.compile_guard.steady_state_recompiles
    pages_back = pool.free_pages == pool.num_pages
    pool.check_invariants(set())
    ctx.say("compared", number="steady_state_recompiles", value=recompiles,
            limit=0, inside=recompiles == 0)
    ctx.say("compared", number="pages_unaccounted_after_drain",
            value=unaccounted, limit=0, inside=unaccounted == 0)
    ctx.say("compared", number="pages_not_back_after_stop",
            value=pool.num_pages - pool.free_pages, limit=0, inside=pages_back)
    ctx.say("compared", number="callers_ended_in_drain", value=all_ended,
            inside=all_ended)
    run.correct = (recompiles == 0 and unaccounted == 0 and pages_back
                   and all_ended)
    run.obs.update(stats0=stats0, stats1=stats1, t0=t0, t1=t1,
                   slots=cfg["serving"]["slots"],
                   iter_tokens=bat.iter_tokens, config=cfg)
    window = {k: (stats1[k] - stats0[k]) for k in stats1}
    ctx.say("window_counts", **{
        k: (v.tolist() if hasattr(v, "tolist") else v)
        for k, v in window.items() if not k.endswith("_expert_tokens")})
    records = list(loop.records)
    # free the program before the reference runs, so that the device's peak
    # stays the program's
    del loop, bat, eng, net
    gc.collect()
    jax.clear_caches()
    return records


def run(ctx, with_control=False):
    import jax

    cfg, mix = ctx.config, ctx.traffic
    if mix["kind"] != "closed_loop_lm":
        raise SystemExit(f"perf: no sender for traffic kind {mix['kind']!r} "
                         "in this driver")
    _program_class(cfg)   # a program without the class ends here, in seconds
    import mxnet_tpu as mx

    mx.telemetry.disable()
    serve = ctx.bench.driver("serve")   # its callers, records, arithmetic
    run = Run()
    ref = ctx.bench.reference(cfg["name"])
    records = _serve(ctx, cfg, mix, run, ref, serve)
    finished = serve._measure(ctx, mix, run, records)

    # ---- the served tokens against the plain reference
    t = time.perf_counter()
    sample = _check_sample(finished, cfg, ctx.seed)
    widest, mean, positions, off = logit_gaps(ref, ctx.seed, cfg, sample)
    inside = _compare(
        ctx, cfg, {"widest_logit_gap": widest, "mean_logit_gap": mean},
        requests=len(sample), positions=positions, off_best=off,
        longest=(len(sample[0].prompt) + len(sample[0].tokens))
        if sample else 0, reference_s=time.perf_counter() - t)
    run.correct = run.correct and inside and run.failed == 0 and positions > 0
    if with_control:
        widest, mean, positions, off = logit_gaps(
            ref, ctx.seed, cfg, sample, quant=cfg["control"])
        run.control_inside = _compare(
            ctx, cfg, {"widest_logit_gap": widest, "mean_logit_gap": mean},
            of="control", positions=positions, off_best=off)
    jax.clear_caches()
    return run


def control(ctx):
    """The control: a short run of the program at the cell's own load, and
    then, at each position of the same prompts and served tokens, the token
    that the reference computed in float8 puts first, held against the
    float32 reference. It has to fall outside the limit; the program's own
    reading is printed beside it."""
    return not run(ctx, with_control=True).control_inside

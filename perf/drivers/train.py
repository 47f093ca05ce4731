"""Driver of training cells: one compiled step with its state, checked
against the plain reference on its first steps and then handed, the same
object, to the measured window.

What it takes from the configuration: ``program`` (the model class and how
its arguments are named after the config's keys), ``optimizer``,
``precision``, ``tolerance``. From the traffic mix: ``per_chip_batch``,
``seq_len``, ``pool_dispatches``. From the cell:
``chips``, ``mesh``, ``sharding``.

Order of a run: seeded weights (the reference's generator, one jitted call)
-> the reference follows the first steps in float32 and is freed -> the
program is built, given the same weights and driven through the same steps
by the window's own call and feed -> comparison -> window.
"""

import importlib
import math
import statistics
import time

import numpy as np

from perf.harness import traffic as gen
from perf.harness.clock import percentile
from perf.harness.main import Run

GROUP_SECONDS = 0.3   # a host-clock reading spans 250 ms or more


def _build_program(cfg, mix, cell):
    """The system under test: model + loss + optimizer behind one
    ``TrainStep``, as ``bench._build`` has it. Returns the step and the
    names of the parameters it trains."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, optimizer as opt
    from mxnet_tpu.parallel import TrainStep

    prog = cfg["program"]
    mod, cls = prog["model"].split(":")
    kwargs = {k: cfg[v] for k, v in prog["kwargs"].items()}
    net = getattr(importlib.import_module(mod), cls)(**kwargs)
    net.initialize()
    net._probe_shapes(mx.nd.zeros((2, 8), dtype="int32"))
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    tied = getattr(net, prog["tied_embedding"]).weight

    class TiedEmbeddingCE:
        """Cross-entropy of every position against the tied embedding."""

        def __call__(self, seq_out, pooled, label):
            logits = seq_out.reshape(-1, seq_out.shape[-1]).dot(tied.data().T)
            return ce(logits, label.reshape(-1))

    o = cfg["optimizer"]
    optimizer = opt.AdamW(learning_rate=o["learning_rate"], beta1=o["beta1"],
                          beta2=o["beta2"], epsilon=o["epsilon"], wd=o["wd"])
    mesh = None
    if cell.get("mesh"):
        from mxnet_tpu.parallel.sharding import make_global_mesh

        mesh = make_global_mesh(dict(cell["mesh"]))
    pr = cfg["precision"]
    step = TrainStep(net, TiedEmbeddingCE(), optimizer, mesh=mesh,
                     sharding=cell.get("sharding"),
                     compute_dtype=pr["compute"],
                     state_dtype=pr["optimizer_state"])
    trainable = [n for n, p in net._collect_params_with_prefix().items()
                 if p.grad_req != "null"]
    return step, trainable


def _leaf_norms(jax, tree):
    """Norm of every leaf, one small transfer."""
    import jax.numpy as jnp

    names = sorted(tree)
    out = jax.jit(lambda t: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(t[n].astype(jnp.float32))))
        for n in names]))(tree)
    return dict(zip(names, map(float, np.asarray(out))))


def worst_leaf_diff(jax, got, want, want_norm):
    """The largest norm of the difference between the program's leaf and
    the reference's, against the reference's norm of that leaf or of the
    median leaf. The gaps between norms hardly feel the precision (rounding
    errors cancel in a norm); this one does."""
    import jax.numpy as jnp

    diff = _leaf_norms(jax, {n: got[n].astype(jnp.float32)
                             - want[n].astype(jnp.float32) for n in want})
    return _worst(diff, want_norm)


def _worst(error, want_norm):
    """The largest error of a leaf against the reference's norm of that leaf
    or of the median leaf, whichever is larger (some gradients are all but
    zero), and the leaf it is on."""
    floor = statistics.median(want_norm.values())
    worst, where = 0.0, None
    for n, e in error.items():
        rel = e / max(want_norm[n], floor, 1e-30)
        if not rel <= worst:  # a NaN is the worst there is
            worst, where = rel, n
    return worst, where


def worst_leaf_gap(got, want):
    """The largest gap between the program's norm of a leaf and the
    reference's (not the norm of their difference)."""
    return _worst({n: abs(got[n] - w) for n, w in want.items()}, want)


def compare(program, reference, tol, vocab, say):
    """Each number compared beside its limit; True when all are inside."""
    rows = []
    for i, (lp, lr) in enumerate(zip(program["losses"],
                                     reference["losses"]), start=1):
        rows.append((f"loss_step{i}_rel_gap", abs(lp - lr) / abs(lr),
                     tol["loss_rel_gap"], None))
    g, where = worst_leaf_gap(program["grad_norm"], reference["grad_norm"])
    rows.append(("first_grad_norm_worst_leaf_gap", g,
                 tol["grad_norm_worst_leaf_gap"], where))
    d, where = program["grad_rel_diff"]
    rows.append(("first_grad_worst_leaf_rel_diff", d,
                 tol["grad_worst_leaf_rel_diff"], where))
    c, where = worst_leaf_gap(program["change_norm"],
                              reference["change_norm"])
    rows.append(("param_change_norm_worst_leaf_gap", c,
                 tol["change_norm_worst_leaf_gap"], where))
    first = program["losses"][0]
    rows.append(("first_loss_over_ln_vocab", first / math.log(vocab),
                 tol["first_loss_over_ln_vocab_max"], None))
    ok = True
    for name, value, limit, where in rows:
        inside = bool(value <= limit)  # False for a NaN
        ok = ok and inside
        say("compared", number=name, value=value, limit=limit,
            inside=inside, worst_leaf=where)
    return ok


def control(ctx):
    """The control of "How correct is decided": the reference in the
    program's place, computed in float8, at the cell's own size. Prints
    what the comparison would read of it; it has to fall outside a limit."""
    import jax

    cfg, mix = ctx.config, ctx.traffic
    pool = gen.train_pool(mix, ctx.seed, cfg["vocab_size"], ctx.chips)
    check_steps = int(mix.get("check_steps", 3))
    ref = ctx.bench.reference(cfg["name"])
    w0 = ref.init_params(ctx.seed, cfg)
    rows = int(mix.get("reference_rows", 8))
    want = ref.train_check(w0, pool[:check_steps], cfg, cfg["optimizer"],
                           rows_per_block=rows)
    got = ref.train_check(w0, pool[:check_steps], cfg, cfg["optimizer"],
                          rows_per_block=rows, quant=cfg["control"])
    got["grad_rel_diff"] = worst_leaf_diff(
        jax, got.pop("first_grad"), want.pop("first_grad"),
        want["grad_norm"])
    return not compare(got, want, cfg["tolerance"], cfg["vocab_size"],
                       ctx.say)


def run(ctx):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx

    mx.telemetry.disable()  # telemetry/events.jsonl is a tracked file
    cfg, mix = ctx.config, ctx.traffic
    run = Run()
    took = {"imports": time.perf_counter() - ctx.process_start}
    lap = time.perf_counter()

    def mark(name):
        nonlocal lap
        took[name] = time.perf_counter() - lap
        lap = time.perf_counter()

    vocab = cfg["vocab_size"]
    check_steps = int(mix.get("check_steps", 3))
    pool = gen.train_pool(mix, ctx.seed, vocab, ctx.chips)
    rows, seq = pool[0][0].shape
    tokens_per_dispatch = rows * seq

    # ---- seeded weights, then the reference, which is freed again
    ref = ctx.bench.reference(cfg["name"])
    w0 = ref.init_params(ctx.seed, cfg)
    jax.block_until_ready(w0)
    mark("weights_from_seed")
    t = time.perf_counter()
    want = ref.train_check(w0, pool[:check_steps], cfg, cfg["optimizer"],
                           rows_per_block=int(mix.get("reference_rows", 8)))
    # the first gradient waits on the host, so that the device's peak
    # stays the program's
    ref_grad = {n: np.asarray(g.astype(jnp.bfloat16))
                for n, g in want.pop("first_grad").items()}
    run.reference_s_in_setup = time.perf_counter() - t
    ctx.say("reference", seconds=run.reference_s_in_setup,
            losses=want["losses"])

    # ---- the program, given the same weights
    mark("reference_not_counted")
    step, trainable = _build_program(cfg, mix, ctx.cell)
    mark("build_program")
    state_dtype = jnp.dtype(cfg["precision"]["optimizer_state"])
    step.load_state_dict({
        "values": {n: jnp.copy(v) for n, v in w0.items()},
        "opt_state": {n: (jnp.zeros(w0[n].shape, state_dtype),
                          jnp.zeros(w0[n].shape, state_dtype))
                      for n in trainable},
        "t_host": 0})
    del w0  # made again from the seed when the change is measured
    mark("give_weights")

    # ---- its first steps, through the window's own call and feed
    got = {"losses": []}
    dispatch_s = []
    for i in range(check_steps):
        t = time.perf_counter()
        loss = step(*pool[i])
        got["losses"].append(float(loss.asscalar()))
        dispatch_s.append(time.perf_counter() - t)
        if i == 0:
            m = {n: st[0] for n, st in step.state_dict()["opt_state"].items()}
            b1 = cfg["optimizer"]["beta1"]
            got["grad_norm"] = {n: v / (1 - b1)
                                for n, v in _leaf_norms(jax, m).items()}
            got["grad_rel_diff"] = worst_leaf_diff(
                jax, {n: m[n].astype(jnp.float32) / (1 - b1) for n in m},
                {n: jnp.asarray(ref_grad[n]) for n in m}, want["grad_norm"])
            del m, ref_grad
    now = step.state_dict()["values"]
    w0 = ref.init_params(ctx.seed, cfg)
    got["change_norm"] = _leaf_norms(
        jax, {n: now[n].astype(jnp.float32) - w0[n] for n in w0})
    # leaves the optimizer never sees (no gradient) stand at zero
    for n in w0:
        got["grad_norm"].setdefault(n, 0.0)
    del now, w0
    mark("checked_first_steps")
    run.correct = compare(got, want, cfg["tolerance"], vocab, ctx.say)
    step.compile_guard.mark_steady()

    # ---- the window
    est = dispatch_s[-1]
    group = max(1, math.ceil(GROUP_SECONDS / est))
    ctx.say("setup_parts", **took)
    ctx.say("window", dispatch_s_estimate=est, dispatches_per_group=group,
            tokens_per_dispatch=tokens_per_dispatch,
            first_dispatch_s=dispatch_s[0])
    span = ctx.tracer.span
    run.compiles_before_window = ctx.compiles.count
    run.window_start = t_start = time.perf_counter()
    deadline = t_start + ctx.seconds
    # the traced stretch: whole groups, inside the window
    trace_on, trace_len = ctx.tracer.stretch(ctx.seconds)
    trace_on = t_start + trace_on if ctx.trace else None
    trace_off = None
    sent, prev, marks = 0, None, []
    while True:
        for _ in range(group):
            with span("upload_and_dispatch"):
                loss = step(*pool[sent % len(pool)])
            sent += 1
        if prev is None:
            ctx.memory.sample("first_group_in_flight")
        else:
            with span("wait_for_device"):
                jax.block_until_ready(prev.data)
            marks.append(time.perf_counter())
        prev = loss
        now = time.perf_counter()
        if trace_on is not None and now >= trace_on:
            jax.block_until_ready(prev.data)
            ctx.tracer.start()
            trace_on, trace_off = None, time.perf_counter() + trace_len
            marks = []
        elif trace_off is not None and now >= trace_off:
            jax.block_until_ready(prev.data)
            ctx.tracer.stop()
            trace_off, marks = None, []
        if now >= deadline:
            break
    ctx.tracer.stop()  # a window too short to end the stretch itself
    jax.block_until_ready(prev.data)
    t_end = time.perf_counter()
    run.window_s = t_end - t_start
    ctx.memory.sample("window_close")
    run.compiles_in_window = ctx.compiles.count - run.compiles_before_window
    last = float(prev.asscalar())
    finite = math.isfinite(last)
    recompiles = step.compile_guard.steady_state_recompiles
    ctx.say("compared", number="last_loss_finite", value=last, inside=finite)
    ctx.say("compared", number="steady_state_recompiles", value=recompiles,
            limit=0, inside=recompiles == 0)
    run.correct = run.correct and finite and recompiles == 0
    run.attempted, run.failed = sent, 0
    tokens = sent * tokens_per_dispatch
    run.e2e = {"train_tokens_per_s": (tokens / run.window_s, "tokens/s")}
    # time of one dispatch in the steady state: the gap between the ends of
    # successive groups, over the dispatches of a group
    gaps = [(b - a) / group for a, b in zip(marks, marks[1:])]
    run.obs = {"dispatches": sent, "tokens": tokens,
               "tokens_per_dispatch": tokens_per_dispatch,
               "dispatch_gap_s": gaps,
               "config": cfg, "traffic": mix}
    ctx.say("measured", dispatches=sent, window_s=run.window_s,
            tokens=tokens, group_gaps=len(gaps),
            dispatch_gap_ms={p: 1e3 * percentile(gaps, p)
                             for p in (0, 50, 95, 100)} if gaps else None,
            last_loss=last,
            losses=got["losses"])
    return run

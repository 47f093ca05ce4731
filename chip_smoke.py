"""Chip smoke: the quickest proof that the system still starts on the TPU.

One process drives the main path once through the entry points a user
calls, at the full width of the two models the repo's records are about:

- *device*: ``jax.devices()[0].platform`` must be ``tpu`` — anything else
  exits nonzero before any other work;
- *train*: BERT-base exactly as ``bench.py`` builds it (bf16 compute,
  AdamW, ``TrainStep``), batch 64 x seq 128, a few dispatches. Loss finite
  on every dispatch, the fused LayerNorm in the compiled step as a Mosaic
  kernel and within tolerance of the jnp composition, one steady dispatch
  timed twice (ended by ``block_until_ready``, ended by a scalar fetch);
- *serve*: Transformer-base behind ``InferStep`` + ``make_batcher`` (the
  default ``ContinuousBatcher``, paged KV, default kernel gates), a few
  requests of mixed prompt length, one carrying a forced prefix. Every
  request resolves, zero steady-state recompiles, every page returned,
  both paged attention entry points compiled (not interpreted) and within
  tolerance of their ``paged_*_reference``.

``--chips 4`` runs ONLY the mesh path: BERT-base ``TrainStep`` under the
``fsdp`` rules on a 4-device mesh against the same seed and global batch
on a 1-device mesh in the same process.

``--rehearse`` runs the same phases at a tiny size on whatever backend is
there (the sandbox, one tier-1 test). It never prints the success line.

Every phase fails the run: no ``try`` that carries on, no smaller batch,
no other backend. The LAST line of a real run is the success line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
everything else worth knowing is on earlier lines. Nothing printed here
is a benchmark figure: the timings are smoke figures of one cold run.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

# published widths (depth and width are never what the smoke cuts) and
# the rehearsal's tiny stand-ins; C % 128 == 0 and head dim 64 keep the
# rehearsal on the same kernels
REAL = dict(
    bert={},  # bench.BERT_BASE as is
    batch=64, seq=128,
    xf_zoo="transformer_base",
    xf=dict(src_vocab=32768, tgt_vocab=32768, max_length=512),
)
TINY = dict(
    bert=dict(vocab_size=512, units=128, hidden_size=256, num_layers=2,
              num_heads=2, max_length=64),
    batch=8, seq=16,
    xf_zoo="TransformerModel",
    xf=dict(src_vocab=256, tgt_vocab=256, units=128, hidden_size=256,
            num_layers=1, num_heads=2, max_length=64),
)
STEPS_PER_CALL = 2
DISPATCHES = 4          # 1 compiles, 1 warms, 2 are timed
BUCKETS = (16, 32)      # prompt-length menu
SLOTS = 4
MAX_NEW = 16
MAX_PREFIX = 4          # forced-prefix budget: drives the window kernel
# stated tolerances: max abs error against the reference, relative to the
# reference's scale. The first chip run (PR 22) read 2.7e-3 / 2.8e-7 for
# the LayerNorm, 1.8e-6 for the paged kernels and 4.6e-4 across meshes
LN_TOL = {"bfloat16": 2e-2, "float32": 1e-5}
PAGED_TOL = 1e-4
MESH_LOSS_RTOL = 1e-2


def say(phase, **kv):
    print(json.dumps({"phase": phase, **kv}), flush=True)


def check(cond, msg):
    if not cond:
        print(f"chip_smoke: FAILED: {msg}", flush=True)
        sys.exit(1)


# ------------------------------------------------------------------ device
def phase_device(rehearse):
    import jax
    import jaxlib
    from importlib import metadata

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearse:
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{dev.platform!r} (use --rehearse off the chip)", flush=True)
        sys.exit(2)
    import mxnet_tpu as mx

    mx.telemetry.disable()  # a smoke run writes no events.jsonl
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=metadata.version("libtpu"),
        compile_cache_dir=mx.compile_cache.cache_dir(),
        jax_compilation_cache_dir=jax.config.jax_compilation_cache_dir)
    return device


# ------------------------------------------------------------------- train
def _layer_norm_vs_reference(rows, C, on_tpu):
    """Fused kernel against the jnp composition, forward and backward."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas import layer_norm as ln

    def ref(x, g, b):
        xf = x.astype(jnp.float32)
        mean = xf.mean(-1, keepdims=True)
        var = ((xf - mean) ** 2).mean(-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + 1e-5)
        return (y * g.astype(jnp.float32) + b.astype(jnp.float32)
                ).astype(x.dtype)

    def fused(x, g, b):
        return ln.layer_norm_fused(x, g, b, 1e-5)

    out = {}
    for dt in ("bfloat16", "float32"):
        k = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(k[0], (rows, C), jnp.float32).astype(dt)
        g = (1 + 0.1 * jax.random.normal(k[1], (C,))).astype(dt)
        b = (0.1 * jax.random.normal(k[2], (C,))).astype(dt)
        dy = jax.random.normal(k[3], (rows, C), jnp.float32).astype(dt)
        outs = {}
        for name, f in (("fused", fused), ("ref", ref)):
            y, vjp = jax.vjp(f, x, g, b)
            outs[name] = (y,) + vjp(dy)
        names = ("y", "dx", "dgamma", "dbeta")
        worst = {}
        for n, a, r in zip(names, outs["fused"], outs["ref"]):
            a, r = a.astype(jnp.float32), r.astype(jnp.float32)
            # gradients of gamma/beta sum over rows: compare relative to
            # the reference's scale
            scale = max(1.0, float(jnp.abs(r).max()))
            worst[n] = float(jnp.abs(a - r).max()) / scale
        check(all(math.isfinite(v) and v <= LN_TOL[dt]
                  for v in worst.values()),
              f"layer_norm_fused {dt} ({rows},{C}) off its reference: "
              f"{worst} > {LN_TOL[dt]}")
        out[dt] = worst
    if on_tpu:
        txt = jax.jit(fused).lower(x, g, b).as_text()
        check("tpu_custom_call" in txt,
              "layer_norm_fused lowered without a Mosaic kernel")
    return out


def phase_train(size, on_tpu):
    import jax
    import numpy as np

    import bench

    batch, seq = size["batch"], size["seq"]
    t_phase = time.perf_counter()
    step, ids, labels = bench._build(batch, seq,
                                     steps_per_call=STEPS_PER_CALL,
                                     **size["bert"])
    build_s = time.perf_counter() - t_phase
    losses = []
    t0 = time.perf_counter()
    loss = step(ids, labels)
    losses.append(float(loss.asscalar()))
    compile_s = time.perf_counter() - t0
    loss = step(ids, labels)
    losses.append(float(loss.asscalar()))
    # one steady dispatch, timed twice: the two ways to end it
    t0 = time.perf_counter()
    loss = step(ids, labels)
    enqueue_s = time.perf_counter() - t0
    jax.block_until_ready(loss.data)
    bur_s = time.perf_counter() - t0
    losses.append(float(loss.asscalar()))
    t0 = time.perf_counter()
    loss = step(ids, labels)
    losses.append(float(loss.asscalar()))
    fetch_s = time.perf_counter() - t0
    check(len(losses) == DISPATCHES and all(np.isfinite(losses)),
          f"BERT loss not finite: {losses}")
    cfg = dict(bench.BERT_BASE, **size["bert"])
    check(losses[0] < 2 * math.log(cfg["vocab_size"]),
          f"BERT first loss {losses[0]} is not near ln(vocab)")
    text = step.compiled_text()
    n_kernels = text.count("tpu_custom_call")
    if on_tpu:
        check(n_kernels > 0, "the compiled BERT step holds no "
              "tpu_custom_call: the fused LayerNorm was not compiled")
    ln_err = _layer_norm_vs_reference(batch * seq, cfg["units"], on_tpu)
    say("train", model="BERT", units=cfg["units"],
        layers=cfg["num_layers"],
        batch=batch, seq=seq, steps_per_call=STEPS_PER_CALL,
        losses=losses, build_s=round(build_s, 3),
        compile_and_first_dispatch_s=round(compile_s, 3),
        steady_dispatch_enqueue_s=round(enqueue_s, 5),
        steady_dispatch_block_until_ready_s=round(bur_s, 5),
        steady_dispatch_scalar_fetch_s=round(fetch_s, 5),
        tpu_custom_calls_in_step=n_kernels,
        layer_norm_max_err=ln_err,
        phase_s=round(time.perf_counter() - t_phase, 3))


# ------------------------------------------------------------------- serve
def _paged_vs_reference(state, heads, slots, pages_per_slot, on_tpu):
    """Both paged entry points against their references at the serving
    shapes: this engine's pools (``heads`` heads a position, however the
    pool declares them), slot count and suffix-window menu."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.pallas import paged_flash_attention as pfa

    pool = state["k_pools"][0]
    n_pool, ps = pool.shape[:2]
    H, D = heads, math.prod(pool.shape[2:]) // heads
    dt = pool.dtype
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    k_pool = jax.random.normal(k[0], pool.shape, jnp.float32).astype(dt)
    v_pool = jax.random.normal(k[1], pool.shape, jnp.float32).astype(dt)
    rng = np.random.RandomState(1)
    table = rng.permutation(np.arange(1, n_pool))[:slots * pages_per_slot]
    table = jnp.asarray(table.reshape(slots, pages_per_slot), jnp.int32)
    cap = pages_per_slot * ps
    pos = jnp.asarray(rng.randint(0, cap, (slots,)), jnp.int32)
    sm = 1.0 / math.sqrt(D)
    errs = {}
    with jax.default_matmul_precision("highest"):
        q = jax.random.normal(k[2], (slots, H, D), jnp.float32).astype(dt)
        dec = jax.jit(lambda *a: pfa.paged_decode_attention(*a, sm_scale=sm))
        got = dec(q, k_pool, v_pool, table, pos)
        want = pfa.paged_decode_reference(q, k_pool, v_pool, table, pos,
                                          sm_scale=sm)
        errs["decode"] = float(jnp.abs(got.astype(jnp.float32)
                                       - want.astype(jnp.float32)).max())
        lowered = [dec.lower(q, k_pool, v_pool, table, pos).as_text()]
        for S in (1, 2, MAX_PREFIX):
            qw = jax.random.normal(jax.random.PRNGKey(10 + S),
                                   (slots, S, H, D), jnp.float32).astype(dt)
            off = jnp.minimum(pos, cap - S)
            vl = jnp.asarray(rng.randint(1, S + 1, (slots,)), jnp.int32)
            win = jax.jit(
                lambda *a: pfa.paged_window_attention(*a, sm_scale=sm))
            got = win(qw, k_pool, v_pool, table, off, vl)
            want = pfa.paged_window_reference(qw, k_pool, v_pool, table,
                                              off, vl, sm_scale=sm)
            errs[f"window_S{S}"] = float(
                jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())
            lowered.append(win.lower(qw, k_pool, v_pool, table, off,
                                     vl).as_text())
    check(all(math.isfinite(e) and e <= PAGED_TOL for e in errs.values()),
          f"paged attention off its reference: {errs} > {PAGED_TOL}")
    if on_tpu:
        check(all("tpu_custom_call" in t for t in lowered),
              "a paged attention entry point lowered without a Mosaic "
              "kernel")
    return {"shape": {"slots": slots, "heads": H, "head_dim": D,
                      "page_size": ps, "pages_per_slot": pages_per_slot,
                      "dtype": str(dt)},
            "max_abs_err": errs}


def phase_serve(size, on_tpu, seed):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import transformer as zoo
    from mxnet_tpu.ops import paged as paged_ops
    from mxnet_tpu.ops.pallas import _use_interpret
    from mxnet_tpu.parallel import InferStep
    from mxnet_tpu.serving import make_batcher

    t_phase = time.perf_counter()
    mx.random.seed(seed)
    np.random.seed(seed)
    net = getattr(zoo, size["xf_zoo"])(dropout=0.0, **size["xf"])
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    eng = InferStep(net, max_len=BUCKETS[-1] + MAX_PREFIX + MAX_NEW + 8)
    kernels_on = paged_ops.kernels_on()
    if on_tpu:
        check(kernels_on and not _use_interpret(),
              "the default gates left the paged kernels off or interpreted "
              f"on a TPU (enabled={kernels_on}, "
              f"interpret={_use_interpret()})")
    t0 = time.perf_counter()
    bat = make_batcher(eng, BUCKETS, slots=SLOTS, max_new_tokens=MAX_NEW,
                       max_prefix_tokens=MAX_PREFIX, suffix_wide=True,
                       warmup=True, name="smoke")
    warmup_s = time.perf_counter() - t0
    check(type(bat).__name__ == "ContinuousBatcher",
          f"make_batcher built a {type(bat).__name__}")
    programs = eng.compile_guard.signatures
    vocab = size["xf"]["tgt_vocab"]
    rng = np.random.RandomState(seed)
    lens = [5, 16, 9, 30, 23, 12, 32, 3]
    prompts = [rng.randint(3, vocab, (n,)).astype("int32") for n in lens]
    t0 = time.perf_counter()
    caps = [MAX_NEW if i % 2 else MAX_NEW // 2 for i in range(len(lens))]
    futs = [bat.submit(p, max_new_tokens=c) for p, c in zip(prompts, caps)]
    outs = [f.result(timeout=600) for f in futs]
    # a follow-up turn carrying forced target-side history: admission
    # replays it through the q_offset-aware window program
    prefix = [int(t) for t in outs[0][:MAX_PREFIX]]
    caps.append(MAX_NEW // 2)
    outs.append(bat.submit(prompts[0], max_new_tokens=caps[-1],
                           prefix_ids=prefix).result(timeout=600))
    serve_s = time.perf_counter() - t0
    # warm-up dispatched every window program once; the forced prefix must
    # have gone through one of them a second time
    replays = max(e["count"] for e in eng.cache_info()["entries"]
                  if "paged_suffix" in e["signature"])
    check(replays >= 2, "no request was served through the paged window "
          "program (forced-prefix replay)")
    for i, (out, cap) in enumerate(zip(outs, caps)):
        check(1 <= len(out) <= cap
              and all(0 <= int(t) < vocab for t in out),
              f"request {i} resolved with bad tokens: {list(out)}")
    # the drain: once the scheduler has retired every slot, the only
    # pages still referenced are the full pages the prefix trie (default
    # on) keeps for reuse; stop() then returns the whole pool
    pool = bat.pool
    deadline = time.perf_counter() + 30
    while pool.free_pages + bat.cache.total_pages != pool.num_pages \
            and time.perf_counter() < deadline:
        time.sleep(0.01)
    cached_pages = bat.cache.total_pages
    check(pool.free_pages + cached_pages == pool.num_pages,
          f"pages leaked after the drain: {pool.free_pages} free + "
          f"{cached_pages} in the prefix trie of {pool.num_pages}")
    stats = dict(bat.stats)
    bat.stop()
    state = bat._state  # read after the scheduler thread is gone
    check(eng.compile_guard.steady_state_recompiles == 0,
          f"{eng.compile_guard.steady_state_recompiles} steady-state "
          "recompiles while serving")
    check(pool.free_pages == pool.num_pages,
          f"{pool.free_pages} pages free of {pool.num_pages} after stop()")
    pool.check_invariants(set())
    # the decode-iteration program the scheduler dispatched, lowered again
    # from the engine's own jitted function: the kernel must be IN it
    idle = np.zeros((SLOTS,), np.int32)
    decode_text = eng._get_decode_iter_fn(bat.iter_tokens, "greedy", 0).lower(
        eng._values, state, np.asarray(pool.table, np.int32), idle, idle,
        idle.astype(bool), np.int32(0), np.float32(1.0)).as_text()
    kernels_in_decode = decode_text.count("tpu_custom_call")
    if on_tpu:
        check(kernels_in_decode > 0, "the serving decode program holds no "
              "Mosaic kernel: paged attention was replaced or interpreted")
    paged = _paged_vs_reference(state, size["xf"]["num_heads"], SLOTS,
                                bat.pages_per_slot, on_tpu)
    say("serve", model=size["xf_zoo"], units=net._units,
        batcher=type(bat).__name__, buckets=list(BUCKETS), slots=SLOTS,
        max_new_tokens=MAX_NEW, max_prefix_tokens=MAX_PREFIX,
        flash_paged_enabled=kernels_on, interpret=_use_interpret(),
        tpu_custom_calls_in_decode_program=kernels_in_decode,
        warmup_programs=programs, warmup_s=round(warmup_s, 3),
        requests=len(outs), tokens=sum(len(o) for o in outs),
        serve_s=round(serve_s, 3), iterations=stats["iterations"],
        steady_state_recompiles=eng.compile_guard.steady_state_recompiles,
        free_pages=pool.free_pages, num_pages=pool.num_pages,
        pages_kept_by_prefix_trie_before_stop=cached_pages,
        prefix_hits=stats["prefix_hits"],
        paged_attention=paged,
        phase_s=round(time.perf_counter() - t_phase, 3))


# -------------------------------------------------------------------- mesh
def phase_mesh(size, seed):
    """BERT-base under the ``fsdp`` rules on a 4-device mesh against the
    same seed and global batch on a 1-device mesh, same process."""
    import jax
    import numpy as np

    import bench
    import mxnet_tpu as mx
    from mxnet_tpu.parallel.sharding import make_global_mesh

    t_phase = time.perf_counter()
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, jax sees {len(jax.devices())}")
    batch, seq = size["batch"], size["seq"]
    losses, steps = {}, {}
    for n in (1, 4):
        # same seed, same init, no dropout: the two meshes differ only in
        # where the arrays live and how the reductions are ordered
        mx.random.seed(seed)
        np.random.seed(seed)
        t0 = time.perf_counter()
        step, ids, labels = bench._build(
            batch, seq, steps_per_call=1,
            mesh=make_global_mesh({"data": n}), sharding="fsdp",
            **dict(size["bert"], dropout=0.0))
        losses[n] = [float(step(ids, labels).asscalar())
                     for _ in range(DISPATCHES)]
        steps[n] = step
        say("mesh.run", devices=n, losses=losses[n],
            seconds=round(time.perf_counter() - t0, 3))
    check(all(np.isfinite(losses[1] + losses[4])),
          f"mesh losses not finite: {losses}")
    rel = max(abs(a - b) / abs(a) for a, b in zip(losses[1], losses[4]))
    check(rel <= MESH_LOSS_RTOL,
          f"4-device losses {losses[4]} differ from 1-device {losses[1]} "
          f"by {rel:.2e} > {MESH_LOSS_RTOL}")
    text = steps[4].compiled_text()
    collectives = {c: text.count(c) for c in
                   ("all-gather", "reduce-scatter", "all-reduce")}
    check(collectives["all-gather"] > 0
          and collectives["reduce-scatter"] + collectives["all-reduce"] > 0,
          f"the 4-device step holds no fsdp collectives: {collectives}")
    steps[4].sync_params()
    w = steps[4]._net.word_embed.weight.data().data
    shard_devs = sorted({s.device.id for s in w.addressable_shards})
    shard_shapes = sorted({tuple(s.data.shape)
                           for s in w.addressable_shards})
    check(len(shard_devs) == 4 and shard_shapes != [tuple(w.shape)],
          f"word embedding {w.shape} is not sharded over 4 devices: "
          f"devices {shard_devs}, shard shapes {shard_shapes}")
    say("mesh", losses_1=losses[1], losses_4=losses[4],
        max_rel_diff=rel, tolerance=MESH_LOSS_RTOL,
        collectives=collectives, word_embed_shape=list(w.shape),
        shard_shapes=[list(s) for s in shard_shapes],
        shard_devices=shard_devs,
        phase_s=round(time.perf_counter() - t_phase, 3))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend is there; never "
                    "prints the success line")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the 4-device mesh path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.rehearse and args.chips == 4:
        # four virtual CPU devices when the rehearsal has no chips; must
        # be in place before jax starts its backends
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    t0 = time.perf_counter()
    device = phase_device(args.rehearse)
    on_tpu = device["platform"] == "tpu"
    size = TINY if args.rehearse else REAL
    if args.chips == 4:
        phase_mesh(size, args.seed)
    else:
        phase_train(size, on_tpu)
        phase_serve(size, on_tpu, args.seed)
    import mxnet_tpu as mx

    say("total", seconds=round(time.perf_counter() - t0, 3),
        compile_cache=mx.compile_cache.cache_stats())
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}),
              flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

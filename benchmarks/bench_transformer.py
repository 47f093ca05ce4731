"""BASELINE config 4: Transformer-base WMT En-De train step (the config
that exercises graph fusion: encoder+decoder+tied-logits in one XLA
program via TrainStep, bf16 + AdamW).

``--variable-length`` instead runs the shape-stability ablation (CPU-
sized by default): the same variable-length token stream fed (a)
unbucketed — every batch padded to its own max length, one compiled
program per distinct length — and (b) bucketed through
``FixedBucketSampler`` + ``PadToBucket`` with ``TrainStep.warmup`` over
the bucket signatures, which must hold compiles to <= n_buckets with
ZERO steady-state recompiles (counter-verified via the step's
``compile_guard``). With ``MXTPU_COMPILE_CACHE_DIR`` set, a second
process run also reports persistent-cache hits.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from .common import run_bench, run_varlen_mode

BATCH, SRC_LEN, TGT_LEN = 64, 64, 64
STEPS_PER_CALL = 40
VOCAB = 32768
# derived ceiling (BASELINE.md arithmetic style): ~61M non-embedding params
# => ~0.37 GFLOPs/token train cost; 45% of v4 peak 275T => ~3.3e5 tok/s.
CEILING = 3.3e5


def fixed_main(amp=None, remat=None, mesh=None, sharding=None):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, optimizer as opt
    from mxnet_tpu.gluon.model_zoo.transformer import transformer_base
    from mxnet_tpu.parallel import TrainStep

    mesh_obj = None
    if mesh:
        from mxnet_tpu.parallel import sharding as _shard

        # --mesh NxM: in-graph SPMD over the first N*M visible devices;
        # --sharding picks the placement rules (default fsdp: params +
        # moments sharded so the per-device bytes drop mesh.size-fold)
        mesh_obj = _shard.make_global_mesh(mesh)
        if sharding is None:
            sharding = "fsdp"

    net = transformer_base(src_vocab=VOCAB, tgt_vocab=VOCAB, max_length=512,
                           dropout=0.1)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    class _Loss:
        def __call__(self, logits, label):
            return ce(logits.reshape(-1, VOCAB), label.reshape(-1))

    # steps_per_call: STEPS_PER_CALL full optimizer steps on as many
    # DISTINCT microbatches per dispatch (device-side scan,
    # parallel/step.py) — one host dispatch feeds the device for many
    # steps, like a real input pipeline. Default precision is the legacy cast-everything
    # bf16; --amp switches to the lists-driven AMP pass, --remat arms
    # whole-graph rematerialization.
    precision = ({"amp": amp} if amp else
                 {"compute_dtype": "bfloat16", "state_dtype": "bfloat16"})
    step_fn = TrainStep(net, _Loss(), opt.AdamW(learning_rate=1e-4),
                        steps_per_call=STEPS_PER_CALL, remat=remat,
                        mesh=mesh_obj, sharding=sharding, **precision)
    rng = np.random.RandomState(0)
    n = BATCH * STEPS_PER_CALL
    src = nd.array(rng.randint(0, VOCAB, (n, SRC_LEN)), dtype="int32")
    tgt = nd.array(rng.randint(0, VOCAB, (n, TGT_LEN)), dtype="int32")
    labels = nd.array(rng.randint(0, VOCAB, (n, TGT_LEN)), dtype="int32")

    run_bench(
        "transformer_wmt_tokens_per_sec_per_chip", "tokens/sec", CEILING,
        lambda: step_fn(src, tgt, labels),
        lambda loss: float(loss.asscalar()),
        STEPS_PER_CALL * BATCH * TGT_LEN,
        warmup=2, steps=16,
    )


# ------------------------------------------------------ variable-length mode
def variable_length_main(args):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache, gluon, nd, optimizer as opt
    from mxnet_tpu.gluon.data import (DataLoader, FixedBucketSampler,
                                      PadToBucket)
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import TrainStep

    V = args.vocab
    rng = np.random.RandomState(args.seed)
    lengths = rng.randint(args.min_len, args.max_len + 1,
                          size=args.samples).tolist()
    dataset = []
    for length in lengths:
        s = rng.randint(1, V, size=length).astype("int32")
        t = rng.randint(1, V, size=length).astype("int32")
        dataset.append((s, t, t))  # label = tgt; pad with -1 for the mask
    tokens_per_epoch = int(sum(lengths))

    class MaskedCE:
        """Per-token CE averaged over VALID (label != -1) tokens only.
        Reduced per row THEN across rows: appending pad columns only adds
        exact zeros to each row's reduction, so padded and unpadded
        batches of the same sentences are bit-identical (asserted in
        tests/test_bucketing.py)."""

        def __call__(self, logits, label):
            x = logits.data.astype(jnp.float32)
            y = label.data
            mask = y >= 0
            safe = jnp.where(mask, y, 0).astype(jnp.int32)
            logp = jax.nn.log_softmax(x, axis=-1)
            nll = -jnp.take_along_axis(logp, safe[..., None],
                                       axis=-1)[..., 0]
            row = jnp.where(mask, nll, 0.0).sum(axis=-1)
            return NDArray(row.sum() / mask.sum())

    def make_step():
        net = TransformerModel(
            src_vocab=V, tgt_vocab=V, units=args.units,
            hidden_size=args.units * 2, num_layers=args.layers, num_heads=2,
            max_length=args.max_len + 8, dropout=0.0)
        net.initialize(mx.initializer.Xavier())
        net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                          nd.zeros((2, 8), dtype="int32"))
        return TrainStep(net, MaskedCE(), opt.AdamW(learning_rate=1e-4))

    # ---- unbucketed: shuffled fixed-size batches, each padded to its own
    # max length — the classic one-compile-per-distinct-length feed
    def pad_batch(idxs):
        ml = max(lengths[i] for i in idxs)
        s = np.zeros((len(idxs), ml), "int32")
        t = np.zeros((len(idxs), ml), "int32")
        lab = np.full((len(idxs), ml), -1, "int32")
        for r, i in enumerate(idxs):
            s[r, : lengths[i]] = dataset[i][0]
            t[r, : lengths[i]] = dataset[i][1]
            lab[r, : lengths[i]] = dataset[i][2]
        return nd.array(s), nd.array(t), nd.array(lab)

    def unbucketed_epochs(ep):
        order = np.random.RandomState(args.seed + 1 + ep).permutation(
            len(dataset))
        for i in range(0, len(order) - args.batch_size + 1,
                       args.batch_size):
            yield pad_batch(order[i: i + args.batch_size].tolist())

    step_u = make_step()
    unbucketed = run_varlen_mode(step_u, unbucketed_epochs,
                                 tokens_per_epoch, epochs=args.epochs)

    # ---- bucketed: FixedBucketSampler + PadToBucket, every bucket
    # signature compiled up front by TrainStep.warmup
    sampler = FixedBucketSampler(
        lengths, args.batch_size, num_buckets=args.buckets,
        ratio=args.ratio, shuffle=True, last_batch="pad")
    batchify = PadToBucket(sampler.bucket_keys, pad_val=0,
                           label_pad_val=[0, -1], valid_length=False)
    loader = DataLoader(dataset, batch_sampler=sampler,
                        batchify_fn=batchify)
    step_b = make_step()
    warm_sigs = [
        (((bs, key), "int32"), ((bs, key), "int32"), ((bs, key), "int32"))
        for bs, key in sampler.signatures()
    ]
    t0 = time.perf_counter()
    warm_compiles = step_b.warmup(warm_sigs)
    warmup_s = time.perf_counter() - t0

    def bucketed_epochs(ep):
        np.random.seed(args.seed + 100 + ep)  # sampler shuffle per epoch
        yield from iter(loader)

    bucketed = run_varlen_mode(step_b, bucketed_epochs, tokens_per_epoch,
                               epochs=args.epochs)
    bucketed["warmup_compiles"] = warm_compiles
    bucketed["warmup_s"] = round(warmup_s, 3)
    bucketed["n_buckets"] = len(sampler.bucket_keys)

    row = {
        "metric": "transformer_varlen_bucketed_tokens_per_sec",
        "value": bucketed["steady_tokens_per_sec"],
        "unit": "tokens/sec",
        "unbucketed": unbucketed,
        "bucketed": bucketed,
        "compile_cache": compile_cache.cache_stats(),
    }
    print(json.dumps(row))
    print(f"unbucketed: {unbucketed['signatures_total']} compiled programs "
          f"({unbucketed['signatures_per_epoch']} per epoch), "
          f"{unbucketed['steady_tokens_per_sec']} tok/s steady")
    print(f"bucketed:   {bucketed['signatures_total']} compiled programs "
          f"(warmup {warm_compiles} <= {bucketed['n_buckets']} buckets), "
          f"{bucketed['steady_state_recompiles']} steady-state recompiles, "
          f"{bucketed['steady_tokens_per_sec']} tok/s steady")
    cache = compile_cache.cache_stats()
    if cache["enabled"]:
        print(f"persistent cache: dir={cache['dir']} hits={cache['hits']} "
              f"misses={cache['misses']}")
    ok = (bucketed["steady_state_recompiles"] == 0
          and bucketed["signatures_total"] <= len(warm_sigs))
    if not ok:
        print("FAIL: bucketed mode recompiled in steady state",
              file=sys.stderr)
    return 0 if ok else 1


# ------------------------------------------------------------- decode mode
def decode_main(args):
    """Inference ablation (CPU-sized): KV-cached incremental decode vs
    naive re-forward generation on the same model and prompts.

    Naive = the pre-engine reality: every emitted token re-runs the full
    forward over the whole prefix (one jitted program PER emitted length,
    O(T²) total compute, one host round trip per token for the argmax).
    KV = ``InferStep``: bucketed prefill + one ``lax.while_loop`` decode
    program, warmed over the prompt-bucket menu — the acceptance gate is
    >= 5x naive tokens/sec with ZERO steady-state recompiles."""
    import warnings

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
    from mxnet_tpu.parallel import InferStep
    from .common import infer_fields

    V, B, T = args.vocab, args.batch_size, args.decode_tokens
    rng = np.random.RandomState(args.seed)
    net = TransformerModel(
        src_vocab=V, tgt_vocab=V, units=args.units,
        hidden_size=args.units * 2, num_layers=args.layers, num_heads=2,
        max_length=args.max_len + T + 8, dropout=0.0)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))

    # one prompt batch padded to the largest bucket (both paths see the
    # same (B, bucket) prompt + valid_length contract)
    bucket = args.max_len
    lens = rng.randint(args.min_len, args.max_len + 1, size=B)
    src_np = np.zeros((B, bucket), "int32")
    for i, n in enumerate(lens):
        src_np[i, :n] = rng.randint(3, V, size=n)
    vl_np = lens.astype("int32")

    # ---- naive: hybridized full re-forward per emitted token (programs
    # compile on pass 0; pass 1 is the steady-state figure). The per-step
    # argmax host read is PART of the baseline being replaced.
    net.hybridize()

    def naive_generate():
        tgt = np.full((B, 1), 1, "int32")  # BOS
        for _ in range(T):
            logits = net(nd.array(src_np), nd.array(tgt),
                         nd.array(vl_np, dtype="int32"))
            nxt = logits.asnumpy()[:, -1].argmax(-1).astype("int32")
            tgt = np.concatenate([tgt, nxt[:, None]], axis=1)
        return tgt[:, 1:]

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # sig-count alarm
        naive_generate()  # compile pass: T programs
        t0 = time.perf_counter()
        naive_tokens = naive_generate()
        naive_s = time.perf_counter() - t0
    net.hybridize(False)
    naive_tps = B * T / naive_s

    # ---- KV-cached: warmed InferStep, one prefill + one decode dispatch
    eng = InferStep(net, max_len=bucket + T + 4)
    warm = eng.warmup([(B, bucket)], max_new_tokens=T)
    eng.decode_n(src_np, vl_np, max_new_tokens=T)  # dispatch-cache hot
    t0 = time.perf_counter()
    toks, lengths = eng.decode_n(src_np, vl_np, max_new_tokens=T)
    kv_tokens = toks.asnumpy()
    kv_s = time.perf_counter() - t0
    kv_tps = B * T / kv_s

    parity = bool(np.array_equal(kv_tokens, naive_tokens))
    recompiles = eng.compile_guard.steady_state_recompiles
    row = {
        "metric": "transformer_decode_tokens_per_sec",
        "value": round(kv_tps, 1),
        "unit": "tokens/sec",
        "naive_tokens_per_sec": round(naive_tps, 1),
        "speedup": round(kv_tps / naive_tps, 2),
        "greedy_tokens_match_naive": parity,
        "warmup_compiles": warm,
        "steady_state_recompiles": recompiles,
        "batch": B, "prompt_bucket": bucket, "decode_tokens": T,
    }
    row.update(infer_fields())
    row["steady_state_recompiles"] = recompiles
    print(json.dumps(row))
    print(f"naive re-forward: {naive_tps:.1f} tok/s ({T} programs, "
          f"O(T^2) recompute); kv-cached: {kv_tps:.1f} tok/s "
          f"({row['speedup']}x, {recompiles} steady recompiles, greedy "
          f"tokens match naive: {parity})")
    ok = kv_tps >= 5 * naive_tps and recompiles == 0
    if not ok:
        print("FAIL: kv-cached decode must be >= 5x naive with zero "
              "steady-state recompiles", file=sys.stderr)
    return 0 if ok else 1


# --------------------------------------------------------- open-loop mode
def open_loop_main(args):
    """Continuous-vs-fixed batching under Poisson open-loop load (the
    ISSUE-8 acceptance ablation, CPU-sized).

    One seeded request stream — exponential inter-arrival gaps at
    ``--open-loop RATE`` req/s, uniform prompt lengths, and a 50/50 mix
    of short (``T // 4``) and long (``T``) ``max_new_tokens`` — is
    replayed against (a) the PR-5 fixed-dispatch ``DynamicBatcher``
    (every batch decodes the full ``T`` and a finished row idles its slot
    until the batch drains) and (b) the paged-KV ``ContinuousBatcher``
    (iteration-level retire/admit). Gates: sustained decode-batch
    occupancy >= 0.9 for the continuous engine and >= 1.5x the fixed
    batcher's decode tokens/sec, with zero steady-state recompiles."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
    from mxnet_tpu.parallel import InferStep
    from mxnet_tpu.serving import ContinuousBatcher, DynamicBatcher
    from .common import infer_fields

    V, B, T = args.vocab, args.batch_size, args.decode_tokens
    bucket = args.max_len
    rate = args.open_loop
    n_requests = args.samples
    # scheduling quality only shows when MODEL COMPUTE is the scheduled
    # resource: at the other modes' micro sizes a decode step costs less
    # than its dispatch and every scheduler measures python overhead, so
    # this mode floors the model at a small-but-real serving size
    units = max(args.units, 128)
    layers = max(args.layers, 2)
    iter_tokens = args.iter_tokens if args.iter_tokens is not None else 8

    net = TransformerModel(
        src_vocab=V, tgt_vocab=V, units=units,
        hidden_size=units * 2, num_layers=layers, num_heads=2,
        max_length=bucket + T + 8, dropout=0.0)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))

    # one seeded workload, replayed identically against both schedulers.
    # The max_new mix mirrors real serving traffic: mostly short
    # responses with a long tail (the regime Orca/PagedAttention target —
    # the fixed batcher decodes EVERY batch to the full T while its short
    # rows idle their slots)
    short = max(T // 8, 2)
    rng = np.random.RandomState(args.seed)
    stream = []
    for _ in range(n_requests):
        n = rng.randint(args.min_len, bucket + 1)
        stream.append({
            "gap": rng.exponential(1.0 / rate) if rate > 0 else 0.0,
            "prompt": rng.randint(3, V, (n,)).astype("int32"),
            "max_new": short if rng.rand() < 0.8 else T,
        })
    total_requested = sum(r["max_new"] for r in stream)

    def drive(batcher):
        futs = []
        t0 = time.perf_counter()
        for r in stream:
            if r["gap"]:
                time.sleep(r["gap"])
            futs.append(batcher.submit(r["prompt"],
                                       max_new_tokens=r["max_new"]))
        tokens = ttfts = 0
        ttft_list, lat_list = [], []
        for f in futs:
            out = f.result(timeout=600)
            tokens += len(out)
            done = time.perf_counter()
            lat_list.append((done - f.enqueued_at) * 1e3 / max(len(out), 1))
            if f.first_token_at is not None:
                ttft_list.append((f.first_token_at - f.enqueued_at) * 1e3)
                ttfts += 1
        wall = time.perf_counter() - t0
        ttft_list.sort()
        lat_list.sort()
        return {
            "tokens": tokens,
            "tokens_per_sec": round(tokens / wall, 1),
            "wall_s": round(wall, 3),
            "ttft_ms_p50": round(_q(ttft_list, 50), 1) if ttft_list
            else None,
            "ttft_ms_p95": round(_q(ttft_list, 95), 1) if ttft_list
            else None,
            "token_latency_ms_p50": round(_q(lat_list, 50), 2),
            "token_latency_ms_p95": round(_q(lat_list, 95), 2),
        }

    # ---- fixed (PR-5): whole-batch dispatches at the batcher's max_new
    eng_f = InferStep(net, max_len=bucket + T + 4)
    fixed_bat = DynamicBatcher(eng_f, bucket_keys=(bucket,), slots=B,
                               timeout_ms=2.0, max_new_tokens=T,
                               warmup=True, name="fixed")
    fixed = drive(fixed_bat)
    fixed_bat.stop()
    fixed["steady_state_recompiles"] = \
        eng_f.compile_guard.steady_state_recompiles

    # ---- continuous: iteration-level retire/admit over the paged pool
    eng_c = InferStep(net, max_len=bucket + T + 4)
    cont_bat = ContinuousBatcher(
        eng_c, bucket_keys=(bucket,), slots=B, max_new_tokens=T,
        page_size=args.page_size, iter_tokens=iter_tokens,
        warmup=True, name="continuous")
    cont = drive(cont_bat)
    occupancy = round(cont_bat.sustained_occupancy, 4)
    stats = dict(cont_bat.stats)
    pool = cont_bat.pool
    cont_bat.stop()
    cont["steady_state_recompiles"] = \
        eng_c.compile_guard.steady_state_recompiles
    cont["sustained_occupancy"] = occupancy
    cont["iterations"] = stats["iterations"]
    cont["preempted"] = stats["preempted"]

    speedup = round(cont["tokens_per_sec"] / max(fixed["tokens_per_sec"],
                                                 1e-9), 2)
    row = {
        "metric": "transformer_open_loop_tokens_per_sec",
        "value": cont["tokens_per_sec"],
        "unit": "tokens/sec",
        "open_loop_rate": rate,
        "requests": n_requests,
        "tokens_requested": total_requested,
        "sustained_occupancy": occupancy,
        "speedup_vs_fixed": speedup,
        "fixed": fixed,
        "continuous": cont,
        "slots": B, "prompt_bucket": bucket, "decode_tokens": T,
        "page_size": pool.page_size, "num_pages": pool.num_pages,
        "iter_tokens": cont_bat.iter_tokens,
    }
    row.update(infer_fields())
    print(json.dumps(row))
    print(f"open loop @ {rate}/s, {n_requests} req (max_new {short}|{T} "
          f"mix): fixed {fixed['tokens_per_sec']} tok/s "
          f"(ttft p50 {fixed['ttft_ms_p50']} ms) vs continuous "
          f"{cont['tokens_per_sec']} tok/s ({speedup}x, occupancy "
          f"{occupancy}, ttft p50 {cont['ttft_ms_p50']} ms, "
          f"{stats['preempted']} preemptions, "
          f"{cont['steady_state_recompiles']} steady recompiles)")
    ok = (occupancy >= 0.9 and speedup >= 1.5
          and cont["steady_state_recompiles"] == 0)
    if not ok:
        print("FAIL: continuous batching must sustain >= 90% occupancy "
              "and >= 1.5x fixed-batcher tokens/sec with zero steady "
              "recompiles", file=sys.stderr)
    return 0 if ok else 1


# -------------------------------------------------------- prefix-mix mode
def prefix_mix_main(args):
    """Prefix caching ablation (the ISSUE-13 acceptance run, CPU-sized).

    One seeded multi-turn chat workload — half the conversations share
    one system prompt (their histories diverge at per-conversation user
    tokens: the COW/branching regime), half carry distinct prompts (one
    linear trie chain each) — is replayed TWICE through the same warmed
    engine: once through a ``ContinuousBatcher`` with the prefix trie on
    and once with it off (every turn re-prefills its full forced
    history). Turn 1 is cold for both; turns >= 2 re-send the
    accumulated history as ``prefix_ids``.

    Gates: >= 3x TTFT p50 improvement on the prefix-carrying turns,
    BIT-identical greedy transcripts between the two runs, a
    refcount-exact pool/trie audit after the cached run, and zero
    steady-state recompiles."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
    from mxnet_tpu.parallel import InferStep
    from mxnet_tpu.serving import ContinuousBatcher
    from .common import infer_fields

    V = args.vocab
    bucket = 16          # prompt bucket (system prompts are short)
    T = 16               # new tokens per turn
    turns = 4            # 1 cold + 3 prefix-carrying
    max_prefix = 96      # >= turns' accumulated history
    convs = max(args.batch_size, 6)
    # prefix savings only show when the replayed HISTORY costs real
    # compute (same floor rationale as the open-loop mode): the hit
    # path's adoption overhead is O(1) in history length, the cold
    # replay O(len) — at micro sizes both drown in dispatch overhead
    units = max(args.units, 128)
    layers = max(args.layers, 2)

    np.random.seed(args.seed)
    mx.random.seed(args.seed)
    net = TransformerModel(
        src_vocab=V, tgt_vocab=V, units=units, hidden_size=units * 2,
        num_layers=layers, num_heads=2,
        max_length=max_prefix + T + 8, dropout=0.0)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    eng = InferStep(net, max_len=max_prefix + T + 8)

    rng = np.random.RandomState(args.seed)
    system = rng.randint(3, V, (12,)).astype("int32")
    prompts = [system if i < convs // 2
               else rng.randint(3, V, (rng.randint(8, 13),))
               .astype("int32") for i in range(convs)]
    # the user's reply tokens per conversation+turn: what makes shared-
    # prompt histories diverge (and exercises the COW tail)
    user = [[rng.randint(3, V, (2,)).tolist() for _ in range(turns)]
            for _ in range(convs)]

    def drive(cache_on, tag):
        # every conversation gets a slot (TTFT measures the cache, not
        # queueing) and the pool holds the whole working set — eviction
        # thrash would bill the cached run for pool pressure instead
        bat = ContinuousBatcher(
            eng, bucket_keys=(bucket,), slots=convs, max_new_tokens=T,
            page_size=args.page_size if args.page_size is not None else 8,
            num_pages=convs * 2 * ((max_prefix + T) // 8 + 2),
            iter_tokens=args.iter_tokens
            if args.iter_tokens is not None else 4,
            max_prefix_tokens=max_prefix, prefix_cache=cache_on,
            warmup=True, name=tag)
        hist = [[] for _ in range(convs)]
        transcript = []
        ttfts = []
        t0 = time.perf_counter()
        for turn in range(turns):
            futs = []
            for c in range(convs):
                futs.append(bat.submit(
                    prompts[c], max_new_tokens=T,
                    prefix_ids=hist[c] if turn else None))
            for c, f in enumerate(futs):
                out = f.result(timeout=600)
                transcript.append(list(out))
                if turn and f.first_token_at is not None:
                    ttfts.append((f.first_token_at - f.enqueued_at) * 1e3)
                hist[c] = hist[c] + list(out) + user[c][turn]
        wall = time.perf_counter() - t0
        stats = bat.prefix_stats()
        audit_ok = True
        try:
            bat.cache.check_invariants()
            bat.pool.check_invariants(cache_pages=bat.cache.pages())
        except Exception as e:  # noqa: BLE001 - report, don't crash
            audit_ok = False
            print(f"AUDIT FAIL ({tag}): {e}", file=sys.stderr)
        bat.stop()
        ttfts.sort()
        return transcript, {
            "wall_s": round(wall, 3),
            "prefix_ttft_ms_p50": round(_q(ttfts, 50), 1),
            "prefix_ttft_ms_p95": round(_q(ttfts, 95), 1),
            "hits": stats["hits"],
            "hit_rate": round(stats["hit_rate"], 4),
            "tokens_saved": stats["tokens_saved"],
            "cow_copies": stats["cow_copies"],
            "cached_pages": stats["pages"],
            "evicted_pages": stats["evicted_pages"],
            "audit_ok": audit_ok,
        }

    cached_transcript, cached = drive(True, "prefix-cached")
    cold_transcript, cold = drive(False, "prefix-off")

    identical = cached_transcript == cold_transcript
    speedup = round(cold["prefix_ttft_ms_p50"]
                    / max(cached["prefix_ttft_ms_p50"], 1e-9), 2)
    recompiles = eng.compile_guard.steady_state_recompiles
    row = {
        "metric": "transformer_prefix_mix_ttft_speedup",
        "value": speedup,
        "unit": "x",
        "conversations": convs,
        "turns": turns,
        "max_prefix_tokens": max_prefix,
        "bit_identical": identical,
        "steady_state_recompiles": recompiles,
        "cached": cached,
        "uncached": cold,
    }
    row.update(infer_fields())
    print(json.dumps(row))
    print(f"prefix mix, {convs} convs x {turns} turns: cached ttft p50 "
          f"{cached['prefix_ttft_ms_p50']} ms (hit rate "
          f"{cached['hit_rate']}, {cached['cow_copies']} COW copies) vs "
          f"uncached {cold['prefix_ttft_ms_p50']} ms -> {speedup}x, "
          f"bit-identical={identical}, {recompiles} steady recompiles")
    ok = (speedup >= 3.0 and identical and cached["audit_ok"]
          and cached["hits"] >= convs * (turns - 1) and recompiles == 0)
    if not ok:
        print("FAIL: prefix caching must cut prefix-turn TTFT p50 by "
              ">= 3x with bit-identical greedy transcripts, every "
              "prefix turn a trie hit, a refcount-exact audit and zero "
              "steady recompiles", file=sys.stderr)
    return 0 if ok else 1


# -------------------------------------------------------- serve-chaos mode
def serve_chaos_main(args):
    """Self-healing serving ablation (CPU-sized): sustained open-loop
    load on a 2-replica ``Router`` while (a) a hot weight swap lands
    mid-stream (``CheckpointWatcher`` over a freshly committed sharded
    checkpoint) and (b) one replica is killed by fault injection
    (``serving.faults``, the ``batcher.thread`` point).

    Acceptance: ZERO lost requests (every future resolves), responses
    carry both the old and the new ``weights_version`` (the swap neither
    dropped nor stalled the stream), ``serve/failovers >= 1``, and zero
    steady-state recompiles through both events."""
    import os
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import checkpoint_sharded as cs
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
    from mxnet_tpu.parallel import InferStep
    from mxnet_tpu.serving import (CheckpointWatcher, DynamicBatcher,
                                   Replica, Router, faults)

    V, B, T = args.vocab, args.batch_size, args.decode_tokens
    bucket = args.max_len
    rng = np.random.RandomState(args.seed)

    def make_net(seed):
        np.random.seed(seed)
        mx.random.seed(seed)
        net = TransformerModel(
            src_vocab=V, tgt_vocab=V, units=args.units,
            hidden_size=args.units * 2, num_layers=args.layers,
            num_heads=2, max_length=bucket + T + 8, dropout=0.0,
            prefix="serve_net_")
        net.initialize(mx.initializer.Xavier())
        net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                          nd.zeros((2, 8), dtype="int32"))
        return net

    # the serving net and the "newly trained" weights it will swap to
    net = make_net(args.seed)
    trained = make_net(args.seed + 1)
    ckpt_root = tempfile.mkdtemp(prefix="mxtpu_serve_chaos_")
    cs.save_sharded(
        os.path.join(ckpt_root, "step_1"),
        {n: p._data.data for n, p in trained.collect_params().items()})

    def make_replica(name):
        eng = InferStep(net, max_len=bucket + T + 4)
        bat = DynamicBatcher(eng, bucket_keys=(bucket,), slots=B,
                             timeout_ms=2.0, max_new_tokens=T,
                             warmup=True, name=name)
        return Replica(name, bat)

    replicas = [make_replica("r0"), make_replica("r1")]
    # shedding off: this mode measures failover/swap under a backlog
    # that deliberately outruns the CPU rig's service rate (the shed
    # policy is --procs mode's phase 3)
    router = Router(replicas, retry_backoff_s=0.01,
                    health_interval_s=0.02, shed_queue_depth=10 ** 6)
    watcher = CheckpointWatcher(router.engines, ckpt_root, start=False)

    n_requests = args.samples
    futs, lat = [], []
    faults.inject("batcher.thread", times=1, match="r1")
    t0 = time.perf_counter()
    for i in range(n_requests):
        n = rng.randint(args.min_len, bucket + 1)
        futs.append(router.submit(rng.randint(3, V, (n,)).astype("int32"),
                                  max_new_tokens=T))
        if i == n_requests // 3:
            watcher.poll_once()  # hot swap mid-stream
        time.sleep(0.001)
    errors = 0
    for f in futs:
        try:
            f.result(timeout=120)
            lat.append((time.perf_counter() - f.enqueued_at) * 1e3)
        except Exception:  # noqa: BLE001 - counted as lost
            errors += 1
    wall_s = time.perf_counter() - t0

    # distributed-tracing tax on this same serving path: identical
    # load with tracing forced off vs on, gated <= 2%
    from .common import trace_overhead_fields

    def _overhead_load():
        fs = [router.submit(
            rng.randint(3, V, (bucket,)).astype("int32"),
            max_new_tokens=T) for _ in range(4)]
        for f in fs:
            f.result(timeout=120)

    overhead = trace_overhead_fields(_overhead_load)
    router.stop()
    faults.clear()

    versions = sorted({f.weights_version for f in futs
                       if f.weights_version is not None})
    reg = mx.telemetry.registry()
    recompiles = sum(
        rep.engine.compile_guard.steady_state_recompiles
        for rep in replicas)
    lat.sort()
    row = {
        "metric": "transformer_serve_chaos_requests_per_sec",
        "value": round(len(lat) / wall_s, 1),
        "unit": "requests/sec",
        "requests": n_requests,
        "errors": errors,
        "latency_ms_p50": round(_q(lat, 50), 1) if lat else None,
        "latency_ms_p99": round(_q(lat, 99), 1) if lat else None,
        "weights_versions": versions,
        "serve_swaps": reg.counter("serve/swaps").value,
        "serve_failovers": reg.counter("serve/failovers").value,
        "serve_retries": reg.counter("serve/retries").value,
        "serve_dropped": reg.counter("serve/dropped").value,
        "steady_state_recompiles": recompiles,
        "batch": B, "prompt_bucket": bucket, "decode_tokens": T,
    }
    row.update(overhead)
    print(json.dumps(row))
    print(f"{n_requests} requests through swap+replica-kill: "
          f"{errors} lost, versions {versions}, "
          f"{row['serve_failovers']} failover(s), "
          f"{row['serve_retries']} retries, p99 "
          f"{row['latency_ms_p99']} ms, {recompiles} steady recompiles, "
          f"trace overhead {row['trace_overhead_pct']}%")
    ok = (errors == 0 and len(versions) >= 2 and
          row["serve_failovers"] >= 1 and recompiles == 0 and
          row["trace_overhead_ok"] is not False)
    if not ok:
        print("FAIL: swap+failover under load must lose zero requests, "
              "serve both weight versions, evict the killed replica and "
              "never recompile", file=sys.stderr)
    return 0 if ok else 1


# ------------------------------------------------- serve-chaos, real procs
def _parent_stays_off_chip(n_workers):
    """Multi-process modes: this parent does host work only (router, load
    generator, checkpoint files). A chip belongs to one process, so the
    parent pins its OWN backend to the CPU through the config API (the
    workers' environment is untouched) before anything here touches a
    device. Nothing assigns chips to workers yet: each worker claims what
    its environment names, so on a one-chip machine only ONE worker can
    hold the chip."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    print(f"parent holds no accelerator (jax_platforms=cpu in this process "
          f"only); {n_workers} worker process(es) each claim the device "
          "their environment names. One chip serves one worker: start "
          "more than one on a one-chip machine only with JAX_PLATFORMS=cpu",
          file=sys.stderr)


def serve_chaos_procs_main(args):
    """Cross-process chaos (``--serve-chaos --procs N``): N REAL
    ``serving.worker`` processes behind ``RemoteReplica``s, under
    open-loop load, through the full failure matrix —

    1. a coordinated hot swap lands mid-stream (two-phase stage/flip
       over the control channel; every process ends on ONE version tag),
    2. one worker is SIGKILL'd mid-decode (dead socket + stale
       heartbeat → eviction → transparent resubmission → the factory
       respawns a REAL process which rejoins at the swapped version),
    3. a deadline flood hits the now-degraded fleet and the router
       SHEDS at admission (``serve/shed_*``) with the backlog bounded
       by construction.

    Acceptance: zero lost requests through swap+SIGKILL, >= 1 failover,
    one coherent post-swap version across every live process, every
    flood request resolved (served or shed — none hanging), observed
    router backlog <= MXTPU_SHED_MAX_QUEUE, zero steady recompiles in
    this process (remote engines warm in their own)."""
    import os
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import checkpoint_sharded as cs
    from mxnet_tpu.serving import (Backpressure, CheckpointWatcher,
                                   RemoteReplica, Router)
    from mxnet_tpu.serving.worker import make_transformer_net, spawn_worker

    V, B, T = args.vocab, args.batch_size, args.decode_tokens
    bucket = args.max_len
    n_procs = args.procs
    _parent_stays_off_chip(n_procs)
    rng = np.random.RandomState(args.seed)
    root = tempfile.mkdtemp(prefix="mxtpu_serve_chaos_procs_")
    ckpt_root = os.path.join(root, "ckpt")
    model = dict(vocab=V, units=args.units, layers=args.layers,
                 heads=2, seed=args.seed, max_length=bucket + T + 8)
    wkw = dict(model=model, max_len=bucket + T + 4, bucket_keys=(bucket,),
               slots=B, max_new=T, ckpt_dir=ckpt_root)

    handles = [spawn_worker(os.path.join(root, f"w{i}"), name=f"w{i}",
                            **wkw) for i in range(n_procs)]
    spawned = [len(handles)]

    def factory():
        i = spawned[0]
        spawned[0] += 1
        h = spawn_worker(os.path.join(root, f"w{i}"), name=f"w{i}", **wkw)
        handles.append(h)
        return RemoteReplica.spawning(h, heartbeat_stale_s=2.0)

    print(f"spawning {n_procs} worker processes ...", file=sys.stderr)
    replicas = [RemoteReplica(h.name, address=h.address,
                              heartbeat_path=h.heartbeat_path,
                              heartbeat_stale_s=2.0) for h in handles]
    router = Router(replicas, retry_backoff_s=0.01, health_interval_s=0.05,
                    replica_factory=factory, respawn_backoff_s=0.05,
                    no_replica_timeout_s=60.0,
                    shed_queue_depth=10 ** 6)  # phase 3 tightens this
    trained = make_transformer_net(**dict(model, seed=args.seed + 1))
    cs.save_sharded(
        os.path.join(ckpt_root, "step_1"),
        {n: p._data.data for n, p in trained.collect_params().items()})
    watcher = CheckpointWatcher(router.engines, ckpt_root, start=False)

    # ---- phase 1+2: open-loop load through swap + SIGKILL
    n_requests = args.samples
    futs, lat = [], []
    swap_version = None
    t0 = time.perf_counter()
    for i in range(n_requests):
        n = rng.randint(args.min_len, bucket + 1)
        futs.append(router.submit(rng.randint(3, V, (n,)).astype("int32"),
                                  max_new_tokens=T))
        if i == n_requests // 3:
            swap_version = watcher.poll_once()
            assert swap_version is not None, "swap did not land"
        if i == n_requests // 2:
            print(f"SIGKILL {handles[1].name} (pid {handles[1].pid})",
                  file=sys.stderr)
            handles[1].kill()
        time.sleep(0.002)
    errors = 0
    for f in futs:
        try:
            f.result(timeout=240)
            lat.append((time.perf_counter() - f.enqueued_at) * 1e3)
        except Exception:  # noqa: BLE001 - counted as lost
            errors += 1
    wall_s = time.perf_counter() - t0
    versions = sorted({f.weights_version for f in futs
                       if f.weights_version is not None})

    # the respawned process must rejoin and report the swapped version
    deadline = time.perf_counter() + 120
    live = []
    while time.perf_counter() < deadline:
        live = [r for r in router.replicas if not r.evicted and r.healthy]
        if len(live) >= n_procs:
            break
        time.sleep(0.2)
    live_versions = sorted({r.weights_version for r in live})

    # ---- phase 3: shed flood against a deliberately degraded fleet
    router.shed_queue_depth = 2
    router.shed_max_queue = max(2 * B, 8)
    flood = []
    max_backlog = 0
    for _ in range(4 * router.shed_max_queue):
        flood.append(router.submit(
            rng.randint(3, V, (rng.randint(args.min_len, bucket + 1),))
            .astype("int32"), max_new_tokens=T, deadline_ms=10_000.0))
        max_backlog = max(max_backlog, len(router._inflight))
    shed = served = flood_lost = 0
    flood_waits = []
    for f in flood:
        try:
            f.result(timeout=240)
            served += 1
            if f.queue_wait_ms is not None:
                flood_waits.append(f.queue_wait_ms)
        except Backpressure:
            shed += 1
        except Exception:  # noqa: BLE001 - deadline/drop = lost
            flood_lost += 1

    # trace-overhead measurement on the surviving fleet: restore the
    # open admission phases 1+2 ran under, then identical load with
    # tracing forced off vs on (router-side spans; gate <= 2%)
    router.shed_queue_depth = 10 ** 6
    from .common import trace_overhead_fields

    def _overhead_load():
        fs = [router.submit(
            rng.randint(3, V, (bucket,)).astype("int32"),
            max_new_tokens=T) for _ in range(4)]
        for f in fs:
            f.result(timeout=240)

    overhead = trace_overhead_fields(_overhead_load)
    router.stop()
    reg = mx.telemetry.registry()
    shed_counted = sum(
        reg.counter(f"serve/shed_{k}").value
        for k in ("queue_full", "deadline"))

    # ---- graceful teardown: SIGTERM drains, exit 0
    rcs = []
    for h in handles:
        if h.alive():
            h.terminate()
    for h in handles:
        try:
            rcs.append(h.wait(timeout=60))
        except Exception:  # noqa: BLE001
            h.kill()
            rcs.append(-9)
    rcs = [rc for rc in rcs if rc != -9]  # the SIGKILL'd one

    lat.sort()
    flood_waits.sort()
    local_recompiles = 0  # remote engines warm in their own processes
    row = {
        "metric": "transformer_serve_chaos_procs_requests_per_sec",
        "value": round(len(lat) / wall_s, 1),
        "unit": "requests/sec",
        "procs": n_procs,
        "requests": n_requests,
        "errors": errors,
        "latency_ms_p50": round(_q(lat, 50), 1) if lat else None,
        "latency_ms_p99": round(_q(lat, 99), 1) if lat else None,
        "weights_versions": versions,
        "live_versions": live_versions,
        "serve_swaps": reg.counter("serve/swaps").value,
        "serve_failovers": reg.counter("serve/failovers").value,
        "serve_retries": reg.counter("serve/retries").value,
        "serve_dropped": reg.counter("serve/dropped").value,
        "serve_replica_restarts":
            reg.counter("serve/replica_restarts").value,
        "transport_reconnects":
            reg.counter("transport/reconnects").value,
        "transport_errors": reg.counter("transport/errors").value,
        "shed": shed, "shed_counted": shed_counted,
        "flood_served": served, "flood_lost": flood_lost,
        "flood_wait_ms_p95": round(_q(flood_waits, 95), 1)
            if flood_waits else None,
        "max_router_backlog": max_backlog,
        "shed_max_queue": router.shed_max_queue,
        "drain_exit_codes": rcs,
        "steady_state_recompiles": local_recompiles,
        "batch": B, "prompt_bucket": bucket, "decode_tokens": T,
    }
    row.update(overhead)
    print(json.dumps(row))
    print(f"{n_requests} requests through cross-process swap+SIGKILL: "
          f"{errors} lost, versions {versions}, "
          f"{row['serve_failovers']} failover(s), "
          f"{row['serve_replica_restarts']} respawn(s), live fleet on "
          f"{live_versions}; flood: {served} served / {shed} shed "
          f"({shed_counted} counted), backlog max {max_backlog} <= "
          f"{router.shed_max_queue}, drain rcs {rcs}, trace overhead "
          f"{row['trace_overhead_pct']}%")
    ok = (errors == 0 and len(versions) >= 2
          and row["serve_failovers"] >= 1
          and live_versions == [swap_version]
          and flood_lost == 0
          and shed >= 1 and shed_counted >= shed
          and max_backlog <= router.shed_max_queue
          and all(rc == 0 for rc in rcs)
          and row["trace_overhead_ok"] is not False)
    shutil.rmtree(root, ignore_errors=True)
    if not ok:
        print("FAIL: cross-process chaos must lose zero requests, "
              "evict+respawn the killed worker, converge every process "
              "on one swapped version, shed (with accounting) under a "
              "degraded fleet with bounded backlog, and drain cleanly "
              "on SIGTERM", file=sys.stderr)
    return 0 if ok else 1


def _q(sorted_vals, p):
    if not sorted_vals:
        return None
    rank = (p / 100.0) * (len(sorted_vals) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] * (1 - (rank - lo)) + sorted_vals[hi] * (rank - lo)


# -------------------------------------------------------------- disagg mode
def disagg_main(args):
    """Disaggregated prefill/decode ablation (``--disagg --procs N``,
    ISSUE-11 acceptance): ONE seeded mixed-class open-loop stream —
    interactive (short prompt, short response, 80 %) + batch (long
    prompt, long response, 20 %) — replayed against two REAL worker
    fleets of the same total size:

    1. **co-scheduled** — N ``both``-role workers, every worker prefills
       and decodes (the PR-10 baseline);
    2. **disaggregated** — 1 ``prefill``-role + (N-1) ``decode``-role
       workers (SAME total process count): the router sends every
       admission prefill to the prefill worker, which ships the filled
       KV over ``kv_push``; decode workers adopt without re-prefilling.

    Why this wins even on the 1-core CPU rig: a co-scheduled worker's
    scheduler loop is SEQUENTIAL — a long batch-class admission prefill
    (one indivisible ~100 ms dispatch at this size) blocks every queued
    interactive request on that worker; padding also drags short
    prompts up to the long bucket when classes mix in one admission
    round. Disaggregation moves prefills into a separate OS-scheduled
    process (decode iterations preempt them) and the prefill engine
    batches per bucket, smallest first — interactive admission on the
    decode worker becomes a ~10 ms host-side adoption instead of a
    prefill dispatch.

    Acceptance: interactive-class TTFT p95 improves under
    disaggregation while aggregate tokens/sec holds within 10 %, every
    request serves on both fleets, and every handoff adopts (0 router
    re-prefills on the happy path)."""
    import os
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu.serving import RemoteReplica, Router
    from mxnet_tpu.serving.worker import spawn_worker
    from .common import disagg_fields

    V, T = args.vocab, args.decode_tokens
    # the disaggregation regime: batch prompts LONG (their admission
    # prefill is the interference co-scheduling suffers from), the
    # model at a serving-real size so that prefill costs dominate the
    # handoff's fixed overhead (one extra RPC hop + host adoption,
    # ~30 ms on the CPU rig) — at micro sizes there is nothing worth
    # moving off the decode workers
    bucket = max(args.max_len, 256)
    short_bucket = max(args.min_len, 8)
    n_procs = max(args.procs, 2)
    _parent_stays_off_chip(n_procs)
    # default operating point validated on the CPU rig (procs=3,
    # samples=72): an SLO-feasible utilization — at saturating rates
    # BOTH fleets just queue and the comparison measures backlog, not
    # scheduling
    rate = args.open_loop if args.open_loop is not None else 12.0
    n_requests = args.samples
    units = max(args.units, 256)
    layers = max(args.layers, 2)
    # interactive responses sized to the scheduler's iteration burst:
    # a 4-token response retires exactly at the iteration boundary, so
    # neither fleet wastes decode steps on the 80 % class
    short_new = max(T // 4, 4)

    rng = np.random.RandomState(args.seed)
    stream = []
    for _ in range(n_requests):
        interactive = rng.rand() < 0.8
        n = rng.randint(3, short_bucket + 1) if interactive \
            else rng.randint(bucket // 2, bucket + 1)
        stream.append({
            "gap": rng.exponential(1.0 / rate) if rate > 0 else 0.0,
            "prompt": rng.randint(3, V, (n,)).astype("int32"),
            "max_new": short_new if interactive else T,
            "klass": "interactive" if interactive else "batch",
        })

    root = tempfile.mkdtemp(prefix="mxtpu_disagg_bench_")
    model = dict(vocab=V, units=units, layers=layers, heads=2,
                 seed=args.seed, max_length=bucket + T + 8)
    wkw = dict(model=model, max_len=bucket + T + 4,
               bucket_keys=(short_bucket, bucket),
               slots=args.batch_size, max_new=T,
               extra_env={"MXTPU_ITER_TOKENS": str(
                   args.iter_tokens if args.iter_tokens is not None
                   else max(T // 4, 4))})

    def spawn_fleet(tag, roles):
        handles = [spawn_worker(os.path.join(root, f"{tag}{i}"),
                                name=f"{tag}{i}", role=role, **wkw)
                   for i, role in enumerate(roles)]
        reps = [RemoteReplica(h.name, address=h.address,
                              heartbeat_path=h.heartbeat_path,
                              heartbeat_stale_s=10.0, role=role)
                for h, role in zip(handles, roles)]
        return handles, reps

    def drive(router):
        futs = []
        t0 = time.perf_counter()
        for r in stream:
            if r["gap"]:
                time.sleep(r["gap"])
            futs.append(router.submit(r["prompt"],
                                      max_new_tokens=r["max_new"],
                                      klass=r["klass"]))
        tokens = errors = 0
        ttft = {"interactive": [], "batch": []}
        for f, r in zip(futs, stream):
            try:
                out = f.result(timeout=600)
            except Exception:  # noqa: BLE001 - counted as lost
                errors += 1
                continue
            tokens += len(out)
            if f.first_token_at is not None:
                ttft[r["klass"]].append(
                    (f.first_token_at - f.enqueued_at) * 1e3)
        wall = time.perf_counter() - t0
        for v in ttft.values():
            v.sort()
        return {
            "tokens": tokens, "errors": errors,
            "tokens_per_sec": round(tokens / wall, 1),
            "wall_s": round(wall, 3),
            "ttft_interactive_p50":
                round(_q(ttft["interactive"], 50), 1)
                if ttft["interactive"] else None,
            "ttft_interactive_p95":
                round(_q(ttft["interactive"], 95), 1)
                if ttft["interactive"] else None,
            "ttft_batch_p50": round(_q(ttft["batch"], 50), 1)
                if ttft["batch"] else None,
            "ttft_batch_p95": round(_q(ttft["batch"], 95), 1)
                if ttft["batch"] else None,
        }

    def run_fleet(tag, roles):
        print(f"spawning {tag} fleet {roles} ...", file=sys.stderr)
        handles, reps = spawn_fleet(tag, roles)
        router = Router(reps, health_interval_s=0.05,
                        no_replica_timeout_s=120.0,
                        shed_queue_depth=10 ** 6)
        # fleet warmup: a few throwaway requests so first-contact costs
        # (peer connects, health probes, per-process page-ins) stay out
        # of BOTH fleets' percentiles
        warm = [router.submit(stream[i % len(stream)]["prompt"],
                              max_new_tokens=4)
                for i in range(2 * len(roles))]
        for f in warm:
            f.result(timeout=600)
        out = drive(router)
        adopted = re_prefilled = 0
        for rep in router.replicas:
            try:
                info = rep.client.call("health")
            except Exception:  # noqa: BLE001 - best-effort accounting
                continue
            adopted += info.get("disagg_adopted") or 0
            re_prefilled += info.get("disagg_re_prefills") or 0
        out["worker_adopted"] = adopted
        out["worker_re_prefills"] = re_prefilled

        # tracing tax on this fleet: identical load forced off vs on
        from .common import trace_overhead_fields

        def _overhead_load():
            fs = [router.submit(stream[i % len(stream)]["prompt"],
                                max_new_tokens=4) for i in range(4)]
            for f in fs:
                f.result(timeout=600)

        out.update(trace_overhead_fields(_overhead_load))
        router.stop()
        for h in handles:
            if h.alive():
                h.terminate()
        for h in handles:
            try:
                h.wait(timeout=60)
            except Exception:  # noqa: BLE001
                h.kill()
        return out

    cosched = run_fleet("both", ["both"] * n_procs)
    disagg = run_fleet("split", ["prefill"] + ["decode"] * (n_procs - 1))
    shutil.rmtree(root, ignore_errors=True)

    reg = mx.telemetry.registry()
    tps_ratio = round(disagg["tokens_per_sec"]
                      / max(cosched["tokens_per_sec"], 1e-9), 3)
    row = {
        "metric": "transformer_disagg_ttft_interactive_p95_ms",
        "value": disagg["ttft_interactive_p95"],
        "unit": "ms",
        "procs": n_procs,
        "requests": n_requests,
        "open_loop_rate": rate,
        "cosched": cosched,
        "disagg": disagg,
        "tokens_per_sec_ratio": tps_ratio,
        "router_re_prefills": reg.counter("disagg/re_prefills").value,
        "slots": args.batch_size, "prompt_buckets":
            [short_bucket, bucket], "decode_tokens": T,
        "trace_overhead_pct": disagg["trace_overhead_pct"],
        "trace_overhead_ok": disagg["trace_overhead_ok"],
    }
    row.update(disagg_fields())
    print(json.dumps(row))
    print(f"disagg vs co-scheduled ({n_procs} procs, {n_requests} req): "
          f"interactive ttft p95 {disagg['ttft_interactive_p95']} vs "
          f"{cosched['ttft_interactive_p95']} ms, tokens/sec "
          f"{disagg['tokens_per_sec']} vs {cosched['tokens_per_sec']} "
          f"({tps_ratio}x), {disagg['worker_adopted']} adopted / "
          f"{disagg['worker_re_prefills']} worker re-prefills / "
          f"{row['router_re_prefills']} router fallbacks")
    ok = (cosched["errors"] == 0 and disagg["errors"] == 0
          and disagg["worker_adopted"] >= 1
          and disagg["ttft_interactive_p95"] is not None
          and cosched["ttft_interactive_p95"] is not None
          and disagg["ttft_interactive_p95"]
          <= cosched["ttft_interactive_p95"]
          and tps_ratio >= 0.9
          and disagg["trace_overhead_ok"] is not False)
    if not ok:
        print("FAIL: disaggregation must lose zero requests, adopt "
              "handoffs, improve interactive TTFT p95 and hold "
              "aggregate tokens/sec within 10%", file=sys.stderr)
    return 0 if ok else 1


# ------------------------------------------------- speculative decoding mode
def speculative_main(args):
    """Flash/paged kernel x speculative decoding ablation (the ISSUE-14
    acceptance run, CPU-sized).

    One seeded prompt batch decodes through five configurations of the
    SAME weights:

    1. ``dense`` — the PR-8 engine (``decode_n``: dense KV slab, one
       fused while_loop program). The baseline every row gates against.
    2. ``paged`` — the paged-KV sequential path (``decode_spec_n`` with
       ``k=0``: one ``decode_iter`` round per token), kernels off.
    3. ``paged+spec`` — speculative decoding (draft proposes
       ``--spec-k`` tokens/round, ONE wide target dispatch verifies),
       kernels off. THE GATE ROW: >= 2x the dense baseline.
    4/5. the same two with ``MXTPU_FLASH_PAGED=force`` — the Pallas
       paged kernels in interpret mode (CPU correctness rows; on-TPU
       they are the perf path, here they are slower than dense math).

    The draft is an ORACLE built from the target itself: the target's
    tail ``--spec-layers - 1`` layers have their sublayer output
    projections zeroed (pre-LN residual blocks collapse to identity), so
    a 1-layer draft holding the surviving layer's weights computes the
    IDENTICAL function at 1/L the depth — full acceptance, maximal
    speedup, and the bit-identity gate still checks the real rejection
    machinery (acceptance only decides how many tokens land per round,
    never which). Gates: every row's transcript equals the dense
    baseline exactly; the spec row >= 2x dense tokens/sec; zero steady-
    state recompiles in every engine."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
    from mxnet_tpu.parallel import InferStep
    from .common import infer_fields

    V, B = args.vocab, args.batch_size
    L, K = args.spec_layers, args.spec_k
    # the spec ablation needs enough math per dispatch to measure: at the
    # shared CPU defaults (units=32, T=32) per-round host overhead
    # dominates every row equally and the comparison is noise, so this
    # mode floors both knobs at the smallest config where the dense
    # baseline is compute-bound
    units = max(args.units, 128)
    T = max(args.decode_tokens, 64)
    rng = np.random.RandomState(args.seed)

    def make_net(layers, seed):
        mx.random.seed(seed)
        net = TransformerModel(
            src_vocab=V, tgt_vocab=V, units=units,
            hidden_size=units * 2, num_layers=layers, num_heads=2,
            max_length=args.max_len + T + K + 16, dropout=0.0)
        net.initialize(mx.initializer.Xavier())
        net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                          nd.zeros((2, 8), dtype="int32"))
        return net

    target = make_net(L, args.seed)
    # collapse the tail layers to identity (pre-LN residual blocks: a
    # zeroed sublayer output projection contributes exactly 0)
    zero_suffixes = (
        "multiheadattention0_out_weight", "multiheadattention0_out_bias",
        "multiheadattention1_out_weight", "multiheadattention1_out_bias",
        "_ffn0_dense1_weight", "_ffn0_dense1_bias")
    for pname, p in target.collect_params().items():
        for li in range(1, L):
            for tag in (f"encoderlayer{li}_", f"decoderlayer{li}_"):
                if tag in pname and any(pname.endswith(z)
                                        for z in zero_suffixes):
                    p.set_data(nd.NDArray(np.zeros_like(
                        np.asarray(p._data.data))))
    draft = make_net(1, args.seed + 1)
    # draft layer-0/embedding/final-norm names are a subset of the
    # target's (indices match); copy by instance-prefix-stripped name
    tparams = {n.split("_", 1)[1]: p
               for n, p in target.collect_params().items()}
    for pname, p in draft.collect_params().items():
        p.set_data(nd.NDArray(tparams[pname.split("_", 1)[1]]._data.data))

    bucket = args.max_len
    lens = rng.randint(args.min_len, args.max_len + 1, size=B)
    src_np = np.zeros((B, bucket), "int32")
    for i, n in enumerate(lens):
        src_np[i, :n] = rng.randint(3, V, size=n)
    vl_np = lens.astype("int32")
    max_len = bucket + T + K + 8
    page_size = args.page_size or 16

    def timed(run_fn, eng, reps):
        out = run_fn()  # warm: compiles + caches every program
        eng.compile_guard.mark_steady()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = run_fn()
        toks, lengths = out
        toks = toks.asnumpy()
        elapsed = (time.perf_counter() - t0) / reps
        return toks, lengths.asnumpy(), B * T / elapsed

    spec_on = args.speculative or not args.flash_paged
    results = []
    prior = os.environ.get("MXTPU_FLASH_PAGED")
    try:
        for kernel in (False, True):
            os.environ["MXTPU_FLASH_PAGED"] = "force" if kernel else "0"
            reps = 1 if kernel else 3  # interpret rows: correctness pace
            if not kernel:
                eng = InferStep(target, max_len=max_len)
                toks_d, lens_d, dense_tps = timed(
                    lambda: eng.decode_n(src_np, vl_np, max_new_tokens=T),
                    eng, reps)
                results.append(("dense", False, False, dense_tps,
                                toks_d, lens_d, eng))
            peng = InferStep(target, max_len=max_len)
            peng.attach_draft(draft)
            toks_p, lens_p, paged_tps = timed(
                lambda: peng.decode_spec_n(
                    src_np, vl_np, max_new_tokens=T, k=0,
                    page_size=page_size), peng, reps)
            results.append(("paged", kernel, False, paged_tps,
                            toks_p, lens_p, peng))
            if spec_on:
                seng = InferStep(target, max_len=max_len)
                seng.attach_draft(draft)
                toks_s, lens_s, spec_tps = timed(
                    lambda: seng.decode_spec_n(
                        src_np, vl_np, max_new_tokens=T, k=K, wide=True,
                        page_size=page_size), seng, reps)
                results.append(("paged+spec", kernel, True, spec_tps,
                                toks_s, lens_s, seng))
    finally:
        if prior is None:
            os.environ.pop("MXTPU_FLASH_PAGED", None)
        else:
            os.environ["MXTPU_FLASH_PAGED"] = prior

    base = next(r for r in results if r[0] == "dense")
    base_tps, base_toks, base_lens = base[3], base[4], base[5]
    all_equal = True
    recompiles = 0
    for name, kernel, spec, tps, toks, lengths, eng in results:
        equal = bool(np.array_equal(toks, base_toks)
                     and np.array_equal(lengths, base_lens))
        all_equal = all_equal and equal
        recompiles += eng.compile_guard.steady_state_recompiles
        row = {
            "metric": "transformer_spec_decode_tokens_per_sec",
            "value": round(tps, 1),
            "unit": "tokens/sec",
            "config": name + ("+kernel" if kernel else ""),
            "flash_paged_kernel": kernel,
            "speculative": spec,
            "spec_k": K if spec else 0,
            "speedup_vs_dense": round(tps / base_tps, 2),
            "greedy_tokens_match_dense": equal,
            "steady_state_recompiles":
                eng.compile_guard.steady_state_recompiles,
            "batch": B, "prompt_bucket": bucket, "decode_tokens": T,
            "target_layers": L, "draft_layers": 1, "units": units,
        }
        row.update({k: v for k, v in infer_fields().items()
                    if k not in row})
        print(json.dumps(row))
    gate = next((r for r in results
                 if r[0] == "paged+spec" and not r[1]), None)
    for name, kernel, spec, tps, _t, _l, _e in results:
        tag = name + ("+kernel" if kernel else "")
        print(f"  {tag:<18} {tps:>9.1f} tok/s "
              f"({tps / base_tps:.2f}x dense)")
    ok = all_equal and recompiles == 0
    if spec_on:
        ok = ok and gate is not None and gate[3] >= 2 * base_tps
    if not ok:
        print("FAIL: speculative decoding must be >= 2x the dense "
              "engine at bit-identical greedy output with zero steady-"
              "state recompiles (and every kernel row must match too)",
              file=sys.stderr)
    return 0 if ok else 1


# ------------------------------------------------------- amp/auto-batch mode
def amp_auto_batch_main(args):
    """HBM-aware compute ablation: fp32 no-remat vs amp(+remat), each at
    the LARGEST batch its compiled step fits under one shared HBM budget
    (``plan_batch`` over ``memory_analysis`` — nothing materialized
    during planning). The amp+remat step must fit a strictly larger
    batch and hold ZERO steady-state recompiles after warmup; steady
    tokens/sec at the planned batches is the headline. Budget: device
    HBM (or MXTPU_HBM_BYTES) under MXTPU_HBM_HEADROOM; rigs with no
    limit at all fall back to the fp32 step's peak at 4x --batch-size so
    the ablation stays runnable on the CPU rig."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer as opt
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import TrainStep, hbm_budget_bytes, plan_batch
    import jax
    import jax.numpy as jnp

    V, key = args.vocab, args.max_len
    amp_dtype = args.amp or "bfloat16"
    remat = args.remat or "dots_saveable"

    class MaskedCE:
        def __call__(self, logits, label):
            x = logits.data.astype(jnp.float32)
            y = label.data
            mask = y >= 0
            safe = jnp.where(mask, y, 0).astype(jnp.int32)
            logp = jax.nn.log_softmax(x, axis=-1)
            nll = -jnp.take_along_axis(logp, safe[..., None],
                                       axis=-1)[..., 0]
            row = jnp.where(mask, nll, 0.0).sum(axis=-1)
            return NDArray(row.sum() / mask.sum())

    def make_step(**kw):
        net = TransformerModel(
            src_vocab=V, tgt_vocab=V, units=args.units,
            hidden_size=args.units * 2, num_layers=args.layers,
            num_heads=2, max_length=args.max_len + 8, dropout=0.0)
        net.initialize(mx.initializer.Xavier())
        net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                          nd.zeros((2, 8), dtype="int32"))
        return TrainStep(net, MaskedCE(), opt.AdamW(learning_rate=1e-4),
                         **kw)

    def sig(bs):
        return (((bs, key), "int32"), ((bs, key), "int32"),
                ((bs, key), "int32"))

    step32 = make_step()
    budget = hbm_budget_bytes()
    if budget is None:
        budget = step32.memory_analysis(
            sig(4 * args.batch_size))["peak_bytes_estimate"]
    b32, peak32 = plan_batch(step32, sig, budget, start=1,
                             max_batch=args.max_batch)
    step_ar = make_step(amp=amp_dtype, remat=remat)
    bar, peakar = plan_batch(step_ar, sig, budget, start=1,
                             max_batch=args.max_batch)

    def measure(step, bs, tag):
        if bs <= 0:
            return {"batch": 0, "steady_tokens_per_sec": 0.0}
        rng = np.random.RandomState(args.seed)
        batches = [tuple(nd.array(rng.randint(1, V, (bs, key)), dtype="int32")
                         for _ in range(3)) for _ in range(4)]
        step.warmup([sig(bs)])
        out = run_varlen_mode(step, lambda ep: iter(batches),
                              tokens_per_epoch=len(batches) * bs * key,
                              epochs=args.epochs)
        out["batch"] = bs
        out["hbm"] = step.memory_analysis(sig(bs))
        return out

    base = measure(step32, b32, "fp32")
    tuned = measure(step_ar, bar, "amp")
    row = {
        "metric": "transformer_amp_auto_batch_tokens_per_sec",
        "value": tuned["steady_tokens_per_sec"],
        "unit": "tokens/sec",
        "amp": amp_dtype, "remat": remat,
        "budget_bytes": int(budget),
        "fp32": base, "amp_remat": tuned,
    }
    print(json.dumps(row))
    print(f"budget {budget/1e6:.0f} MB @ seq {key}: fp32 fits batch "
          f"{b32} ({base['steady_tokens_per_sec']} tok/s steady), "
          f"{amp_dtype}+{remat} fits batch {bar} "
          f"({tuned['steady_tokens_per_sec']} tok/s steady), "
          f"{tuned.get('steady_state_recompiles', 0)} steady recompiles")
    ok = (bar > b32 and tuned.get("steady_state_recompiles", 1) == 0)
    if not ok:
        print("FAIL: amp+remat must fit a strictly larger batch with "
              "zero steady-state recompiles", file=sys.stderr)
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--variable-length", action="store_true",
                    help="run the bucketed-vs-unbucketed compile ablation")
    ap.add_argument("--amp", nargs="?", const="bfloat16", default=None,
                    help="mixed precision dtype (bfloat16/float16)")
    ap.add_argument("--remat", nargs="?", const="dots_saveable",
                    default=None,
                    help="remat policy (mxnet_tpu.remat.POLICIES)")
    ap.add_argument("--auto-batch", action="store_true",
                    help="memory-guided batch planning ablation: fp32 "
                         "vs amp+remat at their largest fitting batches")
    ap.add_argument("--mesh", default=None,
                    help="device mesh for the fixed-config row: '4', "
                         "'2x2' (data x model) or 'data=2,model=2' — the "
                         "step runs SPMD over that many devices and the "
                         "row carries mesh_shape/sharding columns")
    ap.add_argument("--sharding", default=None,
                    help="sharding rules with --mesh: 'replicated' "
                         "(data parallel) or 'fsdp' (default)")
    ap.add_argument("--decode", action="store_true",
                    help="KV-cached vs naive re-forward decode ablation")
    ap.add_argument("--decode-tokens", type=int, default=32,
                    help="tokens generated per row in --decode mode")
    ap.add_argument("--speculative", action="store_true",
                    help="with --decode: speculative-decoding ablation — "
                         "dense baseline vs paged sequential vs draft+"
                         "wide-verify, each with the Pallas paged flash "
                         "kernels off and forced (gate: spec >= 2x dense "
                         "at bit-identical greedy output)")
    ap.add_argument("--flash-paged", action="store_true",
                    help="with --decode: the kernel-only ablation rows "
                         "(dense vs paged, kernels off vs forced) "
                         "without the speculative rows")
    ap.add_argument("--spec-k", type=int, default=7,
                    help="draft tokens proposed per speculative round")
    ap.add_argument("--spec-layers", type=int, default=8,
                    help="target depth for --speculative (tail layers "
                         "are zeroed to identity so the 1-layer oracle "
                         "draft matches the target exactly)")
    ap.add_argument("--open-loop", type=float, nargs="?", const=500.0,
                    default=None, metavar="RATE",
                    help="with --decode: Poisson open-loop load at RATE "
                         "req/s (default 500 = saturating on the CPU "
                         "rig) through ContinuousBatcher vs the fixed "
                         "DynamicBatcher at the same mixed-length "
                         "workload")
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV pool page size for --open-loop "
                         "(MXTPU_PAGE_SIZE default)")
    ap.add_argument("--iter-tokens", type=int, default=None,
                    help="decode tokens per scheduler iteration for "
                         "--open-loop (MXTPU_ITER_TOKENS default)")
    ap.add_argument("--prefix-mix", action="store_true",
                    help="prefix caching ablation: a shared-system-"
                         "prompt + multi-turn chat mix through the same "
                         "engine with the prefix trie on vs off (TTFT "
                         "p50 on prefix turns, hit rate, COW copies, "
                         "bit-identity gate)")
    ap.add_argument("--serve-chaos", action="store_true",
                    help="self-healing serving ablation: hot weight swap "
                         "+ replica kill under sustained router load")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode ablation: a "
                         "mixed interactive+batch open-loop stream "
                         "against a co-scheduled fleet vs a 1-prefill + "
                         "(N-1)-decode fleet of the same size (per-class "
                         "TTFT + aggregate tokens/sec); use with "
                         "--procs N")
    ap.add_argument("--procs", type=int, default=0,
                    help="with --serve-chaos/--disagg: spawn N REAL "
                         "serving worker processes (serving.worker) "
                         "behind RemoteReplicas — the kill becomes "
                         "SIGKILL of a process, the swap a cross-process "
                         "two-phase flip, plus a shed flood against the "
                         "degraded fleet (0 = in-process replicas, the "
                         "PR-7 mode)")
    ap.add_argument("--max-batch", type=int, default=1024)
    ap.add_argument("--buckets", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--samples", type=int, default=192)
    ap.add_argument("--min-len", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=48)
    ap.add_argument("--vocab", type=int, default=1000)
    ap.add_argument("--units", type=int, default=32)
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--ratio", type=float, default=0.5,
                    help="FixedBucketSampler batch-scaling knob")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.prefix_mix:
        return prefix_mix_main(args)
    if args.disagg:
        return disagg_main(args)
    if args.serve_chaos:
        if args.procs >= 2:
            return serve_chaos_procs_main(args)
        return serve_chaos_main(args)
    if args.open_loop is not None:
        return open_loop_main(args)
    if args.speculative or args.flash_paged:
        return speculative_main(args)
    if args.decode:
        return decode_main(args)
    if args.auto_batch:
        return amp_auto_batch_main(args)
    if args.variable_length:
        return variable_length_main(args)
    return fixed_main(amp=args.amp, remat=args.remat, mesh=args.mesh,
                      sharding=args.sharding)


if __name__ == "__main__":
    sys.exit(main() or 0)

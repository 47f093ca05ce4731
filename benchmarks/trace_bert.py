"""Capture a profiler trace of the BERT train step and print the device-op
breakdown (device-busy time from the device's own timeline, which
host-clock noise does not reach).

    python -m benchmarks.trace_bert [--batch 64] [--keep /tmp/dir]
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import shutil
import tempfile

import numpy as np


def build_step(batch, seq=128, loss="fused"):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, optimizer as opt
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel
    from mxnet_tpu.parallel import TrainStep

    net = BERTModel(vocab_size=30522, units=768, hidden_size=3072,
                    num_layers=12, num_heads=12, max_length=512, dropout=0.1)
    net.initialize()
    net._probe_shapes(mx.nd.zeros((2, 8), dtype="int32"))
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    word_w = net.word_embed.weight

    def loss_fn(seq_out, pooled, label):
        w = word_w.data()
        if loss == "fused":
            return mx.nd.linear_cross_entropy(seq_out, w, label)
        logits = seq_out.reshape(-1, seq_out.shape[-1]).dot(w.T)
        return ce(logits, label.reshape(-1))

    step = TrainStep(net, loss_fn, opt.AdamW(learning_rate=1e-4),
                     compute_dtype="bfloat16", state_dtype="bfloat16")
    rng = np.random.RandomState(0)
    ids = mx.nd.array(rng.randint(0, 30522, (batch, seq)), dtype="int32")
    labels = mx.nd.array(rng.randint(0, 30522, (batch, seq)), dtype="int32")
    return step, ids, labels


def capture(step, ids, labels, trace_dir, steps=5):
    import jax

    for _ in range(3):
        loss = step(ids, labels)
    float(loss.asscalar())
    jax.profiler.start_trace(trace_dir)
    for _ in range(steps):
        loss = step(ids, labels)
    float(loss.asscalar())
    jax.profiler.stop_trace()


def analyze(trace_dir, steps=5, top=12):
    path = glob.glob(f"{trace_dir}/plugins/profile/*/*.trace.json.gz")[0]
    with gzip.open(path) as f:
        tr = json.load(f)
    agg = collections.Counter()
    tot = 0.0
    for e in tr["traceEvents"]:
        # XLA Ops leaf timeline: pid 3 / tid 3 in jax's chrome export
        if e.get("ph") == "X" and e.get("pid") == 3 and e.get("tid") == 3:
            tot += e.get("dur", 0)
            agg[e["name"].split(".")[0]] += e.get("dur", 0)
    ms = tot / steps / 1e3
    print(f"device busy per step: {ms:.2f} ms")
    for c, d in agg.most_common(top):
        print(f"{d / steps / 1e3:8.3f} ms  {c}")
    return ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--keep", default=None,
                    help="keep the trace at this directory")
    ap.add_argument("--loss", default="fused", choices=("fused", "naive"))
    args = ap.parse_args()
    trace_dir = args.keep or tempfile.mkdtemp(prefix="bert_trace_")
    step, ids, labels = build_step(args.batch, loss=args.loss)
    capture(step, ids, labels, trace_dir, args.steps)
    ms = analyze(trace_dir, args.steps)
    tok = args.batch * 128 / (ms / 1e3)
    print(f"device-bound tokens/s: {tok:.0f}")
    if not args.keep:
        shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    main()

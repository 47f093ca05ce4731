"""Capture a device-op trace for any BASELINE config and print the
breakdown (the generalization of ``trace_bert``: device timelines, which
host-clock noise does not reach).

    python -m benchmarks.trace_config --config resnet50|transformer|ssd|lenet
"""

from __future__ import annotations

import argparse
import shutil
import tempfile

import numpy as np

from .trace_bert import analyze


def build_resnet50(batch=64, layout="NCHW"):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, optimizer as opt
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    from mxnet_tpu.parallel import TrainStep

    net = get_model("resnet50_v1", layout=layout)
    net.initialize(mx.initializer.Xavier())
    shape = (2, 224, 224, 3) if layout == "NHWC" else (2, 3, 224, 224)
    net._probe_shapes(nd.zeros(shape))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = TrainStep(net, lambda o, l: loss_fn(o, l),
                     opt.SGD(learning_rate=0.1, momentum=0.9),
                     compute_dtype="bfloat16", state_dtype="bfloat16")
    rng = np.random.RandomState(0)
    xshape = (batch, 224, 224, 3) if layout == "NHWC" \
        else (batch, 3, 224, 224)
    x = nd.array(rng.rand(*xshape).astype(np.float32))
    y = nd.array(rng.randint(0, 1000, batch).astype(np.float32))
    return step, x, y, batch


def build_transformer(batch=32, seq=64, remat=None):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, optimizer as opt
    from mxnet_tpu.gluon.model_zoo.transformer import transformer_base
    from mxnet_tpu.parallel import TrainStep

    net = transformer_base(src_vocab=32768, tgt_vocab=32768,
                           max_length=512, dropout=0.1)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(logits, label):
        return ce(logits.reshape(-1, logits.shape[-1]), label.reshape(-1))

    step = TrainStep(net, loss_fn, opt.Adam(learning_rate=1e-4),
                     compute_dtype="bfloat16", state_dtype="bfloat16",
                     remat=remat)
    rng = np.random.RandomState(0)
    src = nd.array(rng.randint(0, 32000, (batch, seq)), dtype="int32")
    tgt = nd.array(rng.randint(0, 32000, (batch, seq)), dtype="int32")
    return step, (src, tgt), tgt, batch * seq


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="resnet50",
                    choices=("resnet50", "transformer"))
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--layout", default="NCHW", choices=("NCHW", "NHWC"))
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args()
    if args.config == "resnet50":
        step, x, y, items = build_resnet50(args.batch or 64, args.layout)
        inputs = (x, y)
    else:
        step, srctgt, y, items = build_transformer(args.batch or 32)
        inputs = (*srctgt, y)
    trace_dir = args.keep or tempfile.mkdtemp(prefix=f"{args.config}_trace_")
    import jax
    for _ in range(3):
        loss = step(*inputs)
    float(loss.asscalar())
    jax.profiler.start_trace(trace_dir)
    for _ in range(args.steps):
        loss = step(*inputs)
    float(loss.asscalar())
    jax.profiler.stop_trace()
    ms = analyze(trace_dir, args.steps)
    print(f"device-bound items/s: {items / (ms / 1e3):.0f}")
    if not args.keep:
        shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    main()

"""BASELINE config 5 (second half): Faster R-CNN two-stage training step
— backbone -> RPN -> Proposal (static-K NMS) -> deterministic sampler ->
batched ROIAlign -> RCNN heads, all in ONE jitted program.

SSD covers the one-stage half of config 5 (bench_ssd); this measures the
two-stage pipeline the reference ran via ``proposal.cc`` + the rcnn
example [unverified]."""

from __future__ import annotations

import numpy as np

from .common import run_bench

BATCH = 16
IMG = 256
# no reference number exists (BASELINE.json published={}); first-measured
# round-3 value becomes the regression floor, like bench_ssd's.
CEILING = 1.0e3


def main():
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.block import _trace_scope
    from mxnet_tpu.gluon.model_zoo.faster_rcnn import FasterRCNN
    from mxnet_tpu.gluon.parameter import param_override
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu import autograd

    net = FasterRCNN(num_classes=20, channels=(32, 64, 128),
                     scales=(2, 4, 8), rpn_pre_nms_top_n=1024,
                     rpn_post_nms_top_n=128, num_sample=64,
                     top_units=256)
    net.initialize(mx.initializer.Xavier())
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(2, 3, IMG, IMG).astype(np.float32))
    gt_small = nd.array(
        np.tile([[0, 32, 32, 96, 96], [-1, 0, 0, 0, 0]], (2, 1, 1))
        .astype(np.float32))
    net(x, gt_small)  # resolve shapes

    params = list(net.collect_params().items())
    name2param = dict(params)
    vals = {n: p.data().data for n, p in params}
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    huber = gluon.loss.HuberLoss()

    def loss_fn(vals, xb, gtb):
        mapping = {name2param[n]: NDArray(v) for n, v in vals.items()}
        with param_override(mapping), _trace_scope(), \
                autograd._scope(False, True):
            (cls, box, cls_t, box_t, box_m, rpn_cls, rpn_box, _rois) = net(
                NDArray(xb), NDArray(gtb))
            feat_hw = (IMG // net._stride, IMG // net._stride)
            bt, bm, ct = net.rpn_dense_targets(
                NDArray(gtb), (IMG, IMG), feat_hw)
            logits, deltas = net.rpn_per_anchor(rpn_cls, rpn_box)
            L = (ce(logits.reshape(-1, 2), ct.reshape(-1)).mean()
                 + huber(deltas * bm, bt * bm).mean() / (bm.mean() + 1e-6)
                 + ce(cls.reshape(-1, cls.shape[-1]),
                      cls_t.reshape(-1)).mean()
                 + huber(box * box_m, box_t).mean()
                 / (box_m.mean() + 1e-6))
        return L.data.astype(jnp.float32)

    # full train step: forward + backward + SGD apply in ONE executable,
    # params donated — same contract as every other config's TrainStep;
    # STEPS_PER_CALL steps scanned per dispatch (dispatch amortization,
    # same as every other config)
    STEPS_PER_CALL = 20

    def one_step(vals, xb, gtb):
        L, grads = jax.value_and_grad(loss_fn)(vals, xb, gtb)
        new_vals = {n: v - 0.01 * grads[n] for n, v in vals.items()}
        return L, new_vals

    @jax.jit
    def train_step(vals, xb, gtb):
        def body(carry, i):
            L, nv = one_step(carry, xb, gtb)
            return nv, L

        vals2, Ls = jax.lax.scan(
            body, vals, jnp.arange(STEPS_PER_CALL, dtype=jnp.float32))
        return Ls.mean(), vals2

    xb = jnp.asarray(rng.rand(BATCH, 3, IMG, IMG).astype(np.float32))
    gtb = np.full((BATCH, 4, 5), -1, np.float32)
    for b in range(BATCH):
        cx, cy = rng.randint(48, IMG - 48, 2)
        gtb[b, 0] = [rng.randint(0, 20), cx - 32, cy - 32, cx + 32, cy + 32]
    gtb = jnp.asarray(gtb)

    state = {"vals": vals}

    def step():
        L, state["vals"] = train_step(state["vals"], xb, gtb)
        return L

    run_bench(
        "faster_rcnn_two_stage_train_images_per_sec", "images/sec",
        CEILING, step, lambda out: float(out), BATCH * STEPS_PER_CALL,
        warmup=2, steps=8,
    )


if __name__ == "__main__":
    main()

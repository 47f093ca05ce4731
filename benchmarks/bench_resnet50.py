"""BASELINE config 2: ResNet-50 synthetic-ImageNet train throughput,
hybridized (fused TrainStep: forward+backward+SGD in one XLA program,
donated buffers, bf16 compute / f32 masters)."""

from __future__ import annotations

import numpy as np

from .common import run_bench

BATCH = 64
STEPS_PER_CALL = 20
# BASELINE.md derived ceiling: ~1e4 images/s/chip at the (optimistic) 45%
# matmul-MFU framing on v4; ResNet is conv/memory-bound so well below.
CEILING = 1.0e4


def main():
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, optimizer as opt
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    from mxnet_tpu.parallel import TrainStep

    net = get_model("resnet50_v1")
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 3, 224, 224)))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    class _Loss:
        def __call__(self, out, label):
            return loss_fn(out, label)

    # STEPS_PER_CALL full optimizer steps per dispatch on distinct microbatches
    # (device-side scan) — one host dispatch feeds many device steps
    step_fn = TrainStep(net, _Loss(),
                        opt.SGD(learning_rate=0.1, momentum=0.9),
                        compute_dtype="bfloat16", state_dtype="bfloat16",
                        steps_per_call=STEPS_PER_CALL)
    rng = np.random.RandomState(0)
    n = BATCH * STEPS_PER_CALL
    x = nd.array(rng.rand(n, 3, 224, 224).astype(np.float32))
    y = nd.array(rng.randint(0, 1000, n).astype(np.float32))

    run_bench(
        "resnet50_synthetic_imagenet_images_per_sec", "images/sec", CEILING,
        lambda: step_fn(x, y), lambda loss: float(loss.asscalar()),
        STEPS_PER_CALL * BATCH,
        warmup=2, steps=24,
    )


if __name__ == "__main__":
    main()

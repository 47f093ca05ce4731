"""BASELINE config 1: LeNet MNIST, single-chip IMPERATIVE NDArray path.

The point of this config is eager-dispatch overhead (the reference measured
the engine's per-op push cost; here it is per-op XLA dispatch): no
hybridize(), no fused TrainStep — autograd.record + backward + Trainer.step
per batch, exactly the reference ``example/gluon`` MNIST loop [unverified].
"""

from __future__ import annotations

import numpy as np

from .common import run_bench

BATCH = 128
# ceiling: LeNet is ~4.6 MFLOPs/image fwd (~14M train); at the BASELINE.md
# v4 45%-MFU framing that'd be ~9e6 img/s — absurd for an op-dispatch-bound
# eager loop, so the honest denominator is dispatch rate: ~60 engine pushes
# per step; the reference's imperative path sustained O(1e4) small-batch
# img/s on accelerators. Target 2e4 img/s.
CEILING = 2.0e4


def main():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd

    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(
            gluon.nn.Conv2D(20, kernel_size=5, activation="tanh"),
            gluon.nn.MaxPool2D(pool_size=2, strides=2),
            gluon.nn.Conv2D(50, kernel_size=5, activation="tanh"),
            gluon.nn.MaxPool2D(pool_size=2, strides=2),
            gluon.nn.Flatten(),
            gluon.nn.Dense(500, activation="tanh"),
            gluon.nn.Dense(10),
        )
    net.initialize(mx.initializer.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.02, "momentum": 0.9})

    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(BATCH, 1, 28, 28).astype(np.float32))
    y = nd.array(rng.randint(0, 10, BATCH).astype(np.float32))

    def step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(BATCH)
        return loss

    run_bench(
        "lenet_mnist_imperative_images_per_sec", "images/sec", CEILING,
        step, lambda loss: float(loss.mean().asscalar()), BATCH,
        warmup=3, steps=120,
    )
    # steps=120: long windows amortize the fixed cost of the one sync each
    # window pays, the same way the training configs' steps_per_call scans
    # do. The sync still waits for the WINDOW'S ENTIRE queued work, so the
    # rate is sustained throughput, not queueing.


if __name__ == "__main__":
    main()

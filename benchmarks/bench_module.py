"""Legacy Module/KVStore training loop (the
reference's §3.3/§3.4 path — symbol simple_bind executor +
forward/backward + per-param updater through the Module API — had no
perf floor; every other bench runs TrainStep).

    python -m benchmarks.bench_module
"""

from __future__ import annotations

import numpy as np

from .common import run_bench

BATCH = 128
# same config-1 dispatch-rate framing as bench_lenet (this is the same
# model on the LEGACY path; the delta between the two rows is the cost
# of the Module/executor machinery vs the gluon eager loop)
CEILING = 2.0e4


def main():
    import mxnet_tpu as mx
    from mxnet_tpu import sym, nd

    data = sym.var("data")
    c1 = sym.Convolution(data, sym.var("c1w"), sym.var("c1b"),
                         kernel=(5, 5), num_filter=20)
    t1 = sym.Activation(c1, act_type="tanh")
    p1 = sym.Pooling(t1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    c2 = sym.Convolution(p1, sym.var("c2w"), sym.var("c2b"),
                         kernel=(5, 5), num_filter=50)
    t2 = sym.Activation(c2, act_type="tanh")
    p2 = sym.Pooling(t2, pool_type="max", kernel=(2, 2), stride=(2, 2))
    fl = sym.Flatten(p2)
    f1 = sym.FullyConnected(fl, sym.var("f1w"), sym.var("f1b"),
                            num_hidden=500)
    t3 = sym.Activation(f1, act_type="tanh")
    f2 = sym.FullyConnected(t3, sym.var("f2w"), sym.var("f2b"),
                            num_hidden=10)
    out = sym.SoftmaxOutput(f2, sym.var("softmax_label"))

    from mxnet_tpu.module import Module

    mod = Module(out, data_names=("data",),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (BATCH, 1, 28, 28))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.02),
                                         ("momentum", 0.9)))

    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(BATCH, 1, 28, 28).astype(np.float32))
    y = nd.array(rng.randint(0, 10, BATCH).astype(np.float32))

    class _Batch:
        data = [x]
        label = [y]

    def step():
        mod.forward(_Batch)
        mod.backward()
        mod.update()
        return mod.get_outputs()[0]

    run_bench(
        "lenet_module_kvstore_images_per_sec", "images/sec", CEILING,
        step, lambda out: float(out.mean().asscalar()), BATCH,
        warmup=3, steps=30,
    )


if __name__ == "__main__":
    main()

"""2-bit gradient compression (reference:
``src/kvstore/gradient_compression.cc`` + ``tests/python/unittest/
test_kvstore.py`` compression cases [unverified])."""

import numpy as np
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.kvstore.compression import (
    GradientCompression, pack_2bit, quantize_2bit, unpack_2bit,
)


class TestQuantize:
    def test_threshold_semantics(self):
        g = jnp.asarray([-2.0, -0.5, -0.1, 0.0, 0.3, 0.5, 3.0])
        q, r = quantize_2bit(g, 0.5)
        np.testing.assert_allclose(
            np.asarray(q), [-0.5, -0.5, 0, 0, 0, 0.5, 0.5]
        )
        np.testing.assert_allclose(np.asarray(q + r), np.asarray(g), rtol=1e-6)

    def test_pack_unpack_roundtrip(self):
        rng = np.random.RandomState(0)
        g = jnp.asarray(rng.randn(37).astype(np.float32))  # non-multiple of 4
        q, _ = quantize_2bit(g, 0.7)
        packed, n = pack_2bit(q, 0.7)
        assert packed.dtype == jnp.uint8 and packed.shape[0] == (37 + 3) // 4
        out = unpack_2bit(packed, n, 0.7)
        np.testing.assert_allclose(np.asarray(out), np.asarray(q), rtol=1e-6)

    def test_error_feedback_accumulates(self):
        gc = GradientCompression({"type": "2bit", "threshold": 1.0})
        # constant small gradient 0.4 < threshold: quantizes to 0 at first,
        # residual builds until it crosses the threshold
        sent = [np.asarray(gc.compress("k", jnp.full((4,), 0.4)))
                for _ in range(5)]
        total = sum(s.sum() for s in sent)
        # after 5 pushes of 0.4 (=2.0 total per element), ~2.0/1.0 quanta
        # per element should have flowed (error feedback conserves mass)
        np.testing.assert_allclose(total, 4 * 2.0, atol=4 * 0.5)
        assert sent[0].sum() == 0.0  # first push below threshold


class TestKVStoreCompression:
    def test_push_applies_compression(self):
        kv = mx.kv.create("local")
        kv.init("w", nd.zeros((6,)))
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        kv.push("w", nd.array(np.array([2.0, -2.0, 0.1, 0, 0.6, -0.3],
                                       np.float32)))
        out = nd.zeros((6,))
        kv.pull("w", out=out)
        np.testing.assert_allclose(
            out.asnumpy(), [0.5, -0.5, 0.0, 0.0, 0.5, 0.0], rtol=1e-6
        )

    def test_multi_device_residuals_independent(self):
        kv = mx.kv.create("device")
        kv.init("0", nd.zeros((2,)))
        kv.set_gradient_compression({"type": "2bit", "threshold": 1.0})
        # replica 0 pushes 0.6, replica 1 pushes 0.6 -> both below threshold
        kv.push("0", [nd.array(np.array([0.6, 0.6], np.float32)),
                      nd.array(np.array([0.6, 0.6], np.float32))])
        out = nd.zeros((2,))
        kv.pull("0", out=out)
        np.testing.assert_allclose(out.asnumpy(), [0.0, 0.0])
        # second push: residual 0.6 + 0.6 = 1.2 >= 1.0 on each replica
        kv.push("0", [nd.array(np.array([0.6, 0.6], np.float32)),
                      nd.array(np.array([0.6, 0.6], np.float32))])
        kv.pull("0", out=out)
        np.testing.assert_allclose(out.asnumpy(), [2.0, 2.0])  # 1.0 x 2 replicas

    def test_unsupported_type_raises(self):
        kv = mx.kv.create("local")
        try:
            kv.set_gradient_compression({"type": "1bit"})
            assert False
        except mx.base.MXNetError:
            pass


def test_trainer_forwards_compression_params():
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.kvstore.compression import GradientCompression

    net = nn.Dense(2)
    net.initialize()
    net(nd.ones((2, 3)))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore="device",
                       compression_params={"type": "2bit", "threshold": 0.5})
    with autograd.record():
        loss = (net(nd.ones((2, 3))) ** 2).sum()
    loss.backward()
    tr.step(2)
    assert isinstance(tr._kvstore._compression, GradientCompression)


def test_wire_byte_pack_sum_exactness():
    """Round-4 wire path: sum of per-worker unpacked codes must equal the
    sum of per-worker quantized grads exactly (codes are {-t,0,+t})."""
    t = 0.5
    rng = np.random.default_rng(0)
    grads = [rng.normal(0, 1, (13,)).astype(np.float32) for _ in range(4)]
    total_q = np.zeros(13, np.float32)
    total_wire = np.zeros(13, np.float32)
    for g in grads:
        q, _ = quantize_2bit(jnp.asarray(g), t)
        total_q += np.asarray(q)
        packed, n = pack_2bit(q, t)
        total_wire += np.asarray(unpack_2bit(packed, n, t))
    np.testing.assert_array_equal(total_wire, total_q)


def test_compression_order_dynamics_harmless():
    """Per-replica compress-then-sum (local
    path) vs the reference's aggregate-then-compress (dist path, round-4
    wire implementation). Both run error feedback, so both converge on a
    toy least-squares problem; this measures the deviation and pins it
    harmless (both reach the same loss floor)."""
    rng = np.random.default_rng(1)
    dim, workers, steps, lr, t = 8, 4, 300, 0.05, 0.5
    target = rng.normal(0, 1, dim).astype(np.float32)

    def worker_grad(w, k):
        # worker k sees a noisy quadratic: grad = (w - target) + noise_k
        noise = rng.normal(0, 0.3, dim).astype(np.float32)
        return (w - target) / workers + noise / workers

    def run(order):
        w = np.zeros(dim, np.float32)
        resid = [np.zeros(dim, np.float32) for _ in range(workers + 1)]
        for _ in range(steps):
            gs = [worker_grad(w, k) for k in range(workers)]
            if order == "compress_then_sum":
                agg = np.zeros(dim, np.float32)
                for k, g in enumerate(gs):
                    q, r = quantize_2bit(jnp.asarray(g + resid[k]), t)
                    resid[k] = np.asarray(r)
                    agg += np.asarray(q)
            else:  # aggregate_then_compress (reference worker order)
                s = np.sum(gs, axis=0)
                q, r = quantize_2bit(jnp.asarray(s + resid[-1]), t)
                resid[-1] = np.asarray(r)
                agg = np.asarray(q)
            w = w - lr * agg
        return float(np.mean((w - target) ** 2))

    rng = np.random.default_rng(1)
    l1 = run("compress_then_sum")
    rng = np.random.default_rng(1)
    l2 = run("aggregate_then_compress")
    # both orders must converge to a small loss floor (error feedback
    # guarantees this); neither should diverge or stall
    assert l1 < 0.2, f"compress-then-sum stalled at {l1}"
    assert l2 < 0.2, f"aggregate-then-compress stalled at {l2}"

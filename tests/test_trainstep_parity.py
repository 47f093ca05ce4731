"""Trainer (eager per-param) vs TrainStep (fused jitted) optimizer parity.

The two training paths must agree for every fused
optimizer, not just SGD. Also covers the multi-precision AMP path
(compute-dtype grads + f32 masters, the reference ``mp_*_update`` scheme)
and the narrow optimizer-state option.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, optimizer as opt
from mxnet_tpu.gluon import nn
from mxnet_tpu import parallel

X = np.random.RandomState(0).randn(16, 8).astype("float32")
Y = np.random.RandomState(1).randn(16, 1).astype("float32")


def _build():
    mx.random.seed(11)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(1))
    net.initialize()
    net(mx.nd.array(X))
    return net


def _norm(params):
    # prefixes auto-increment per construction (hybridsequential0, 1, ...);
    # compare on the stable suffix
    return {k.split("dense", 1)[-1]: v for k, v in params.items()}


def _run_trainer(optimizer_name, kwargs, steps=5):
    net = _build()
    trainer = gluon.Trainer(net.collect_params(), optimizer_name, dict(kwargs))
    loss_fn = gluon.loss.L2Loss()
    for _ in range(steps):
        with autograd.record():
            L = loss_fn(net(mx.nd.array(X)), mx.nd.array(Y))
        L.backward()
        trainer.step(16)
    return _norm({k: v.data().asnumpy()
                  for k, v in net.collect_params().items()})


def _run_step(optimizer, steps=5, **step_kw):
    net = _build()
    step = parallel.TrainStep(net, gluon.loss.L2Loss(), optimizer, **step_kw)
    for _ in range(steps):
        step(mx.nd.array(X), mx.nd.array(Y))
    step.sync_params()
    return _norm({k: v.data().asnumpy()
                  for k, v in net.collect_params().items()})


def _compare(pa, pb, rtol, atol):
    assert set(pa) == set(pb)
    for k in pa:
        np.testing.assert_allclose(pa[k], pb[k], rtol=rtol, atol=atol,
                                   err_msg=k)


@pytest.mark.parametrize(
    "name,kwargs,make",
    [
        ("adam", {"learning_rate": 1e-2},
         lambda: opt.Adam(learning_rate=1e-2)),
        ("adamw", {"learning_rate": 1e-2, "wd": 0.01},
         lambda: opt.AdamW(learning_rate=1e-2, wd=0.01)),
        ("lamb", {"learning_rate": 1e-2, "wd": 0.01},
         lambda: opt.LAMB(learning_rate=1e-2, wd=0.01)),
        ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4},
         lambda: opt.SGD(learning_rate=0.05, momentum=0.9, wd=1e-4)),
    ],
)
def test_trainer_vs_trainstep(name, kwargs, make):
    pa = _run_trainer(name, kwargs)
    pb = _run_step(make())
    _compare(pa, pb, rtol=5e-4, atol=2e-5)


def test_mp_bf16_grads_track_f32():
    """compute_dtype=bf16 (bf16 grads, f32 masters) must track the f32 run
    to bf16-resolution tolerance."""
    pa = _run_step(opt.AdamW(learning_rate=1e-2))
    pb = _run_step(opt.AdamW(learning_rate=1e-2), compute_dtype="bfloat16")
    # Adam normalizes updates, so bf16 grad noise drifts weights by O(lr)
    # per step on near-zero entries — tolerance reflects 5 steps of that
    _compare(pa, pb, rtol=5e-2, atol=2e-2)


def test_state_dtype_bf16_tracks_f32():
    pa = _run_step(opt.AdamW(learning_rate=1e-2))
    pb = _run_step(opt.AdamW(learning_rate=1e-2), state_dtype="bfloat16")
    _compare(pa, pb, rtol=5e-2, atol=5e-3)
    # states actually stored narrow
    net = _build()
    st = parallel.TrainStep(net, gluon.loss.L2Loss(),
                            opt.AdamW(learning_rate=1e-2),
                            state_dtype="bfloat16")
    import jax.numpy as jnp

    for name, states in st._opt_state.items():
        for s in states:
            assert s.dtype == jnp.bfloat16


def test_mp_still_learns():
    net = _build()
    step = parallel.TrainStep(net, gluon.loss.L2Loss(),
                              opt.AdamW(learning_rate=1e-2),
                              compute_dtype="bfloat16",
                              state_dtype="bfloat16")
    l0 = float(step(mx.nd.array(X), mx.nd.array(Y)).asscalar())
    for _ in range(20):
        L = step(mx.nd.array(X), mx.nd.array(Y))
    l1 = float(L.asscalar())
    assert l1 < l0 * 0.7


def test_remat_parity():
    """TrainStep(remat=...) must not change numerics — only the
    recompute schedule (round-5: the transformer roofline's negative
    result keeps the option for long-sequence regimes)."""
    base = _run_step(opt.Adam(learning_rate=0.01))
    for mode in ("dots", "full"):
        got = _run_step(opt.Adam(learning_rate=0.01), remat=mode)
        for k in base:
            np.testing.assert_allclose(got[k], base[k], rtol=1e-5,
                                       atol=1e-6, err_msg=f"{mode}:{k}")

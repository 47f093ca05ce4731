"""Fused Pallas LayerNorm: numeric parity (fwd + grads) with the jnp
composition, across the shapes the BERT path uses."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas import layer_norm as pln


def _ref_ln(x, g, b, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


@pytest.mark.parametrize("n,c", [(64, 128), (300, 768), (1, 256),
                                 (257, 512)])
def test_forward_parity(n, c):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, c).astype(np.float32)) * 3 + 1
    g = jnp.asarray(rng.randn(c).astype(np.float32))
    b = jnp.asarray(rng.randn(c).astype(np.float32))
    out = pln.layer_norm_fused(x, g, b, 1e-5)
    ref = _ref_ln(x, g, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_forward_parity_bf16():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(128, 768).astype(np.float32)).astype(
        jnp.bfloat16
    )
    g = jnp.ones((768,), jnp.float32)
    b = jnp.zeros((768,), jnp.float32)
    out = pln.layer_norm_fused(x, g, b, 1e-5)
    assert out.dtype == jnp.bfloat16
    ref = _ref_ln(x.astype(jnp.float32), g, b)
    np.testing.assert_allclose(
        np.asarray(out).astype(np.float32), np.asarray(ref), rtol=2e-2,
        atol=2e-2,
    )


def test_gradient_parity():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(96, 256).astype(np.float32))
    g = jnp.asarray(rng.rand(256).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(256).astype(np.float32))
    dy = jnp.asarray(rng.randn(96, 256).astype(np.float32))

    def loss_fused(x, g, b):
        return (pln.layer_norm_fused(x, g, b, 1e-5) * dy).sum()

    def loss_ref(x, g, b):
        return (_ref_ln(x, g, b) * dy).sum()

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, g, b)
    for a, r, name in zip(gf, gr, "x g b".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


def test_op_dispatches_to_fused_and_matches():
    from mxnet_tpu import nd

    rng = np.random.RandomState(3)
    x = nd.array(rng.randn(4, 16, 256).astype(np.float32))
    g = nd.array(rng.rand(256).astype(np.float32) + 0.5)
    b = nd.array(rng.randn(256).astype(np.float32))
    out = nd.LayerNorm(x, g, b)
    ref = _ref_ln(x.data, g.data, b.data)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
    # unaligned channel count falls back to the jnp path
    x2 = nd.array(rng.randn(4, 100).astype(np.float32))
    g2 = nd.array(np.ones(100, np.float32))
    b2 = nd.array(np.zeros(100, np.float32))
    out2 = nd.LayerNorm(x2, g2, b2)
    assert np.isfinite(out2.asnumpy()).all()


def test_gluon_layernorm_trains_through_fused():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(128, flatten=False), nn.LayerNorm(in_channels=128),
            nn.Dense(1, flatten=False))
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    x = nd.array(np.random.RandomState(4).rand(16, 8).astype(np.float32))
    y = nd.array(np.random.RandomState(5).rand(16, 1).astype(np.float32))
    losses = []
    for _ in range(40):
        with autograd.record():
            L = ((net(x) - y) ** 2).mean()
        L.backward()
        tr.step(16)
        losses.append(float(L.asscalar()))
    # wiring smoke test (gradient parity is asserted above): loss drops
    assert losses[-1] < losses[0] * 0.8


@pytest.mark.parametrize("impl", ["_ln_fwd_impl", "_ln_bwd_impl"])
def test_kernel_names_the_benchmark_keys_on(impl):
    """``perf/layer_metrics/layernorm_time_share.py`` finds the fused
    kernels in a device trace by ``%_ln_(fwd|bwd)_impl``, XLA's rendering
    of the jitted Python functions' names: the functions keep those names,
    and a lowered forward/backward still carries them in its HLO text. A
    rename waits for the ``benchmark`` issue that changes the reader with
    it (PERF.md section 7)."""
    import re

    fn = getattr(pln, impl)
    assert fn.__name__ == impl and hasattr(fn, "lower")  # still a jit
    x = jnp.ones((64, 128), jnp.float32)
    g, b = jnp.ones((128,), jnp.float32), jnp.zeros((128,), jnp.float32)
    lowered = jax.jit(jax.grad(
        lambda x, g, b: pln.layer_norm_fused(x, g, b, 1e-5).sum(),
        argnums=(0, 1, 2))).lower(x, g, b)
    assert re.search(rf"\b{impl}\.\d+", lowered.as_text(dialect="hlo"))
    assert f"@{impl}" in lowered.as_text()

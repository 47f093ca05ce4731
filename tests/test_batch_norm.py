"""BatchNorm's statistics and its closed-form backward against a two-pass
mean and variance written here.

The round-3 one-pass BN was reverted for catastrophic cancellation at
|mean| >> std; these tests pin the shifted one-pass statistics
(``ops_nn._bn_stats``, the only form) in exactly that regime, plus full
fwd+bwd parity of the channel-last BatchNorm op with a two-pass BatchNorm
under ``jax.vjp``.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx  # noqa: F401 - the package sets JAX up
from mxnet_tpu.ops import nn as ops_nn


@pytest.mark.parametrize("shape", [(4, 7, 7, 8), (8, 14, 14, 64),
                                   (2, 5, 3, 16), (2, 1, 49, 160),
                                   (16, 3, 3, 600), (64, 2, 2, 2048)])
@pytest.mark.parametrize("mean_scale", [0.0, 200.0])
def test_bn_stats_parity(shape, mean_scale):
    rng = np.random.default_rng(0)
    C = shape[-1]
    x = rng.normal(mean_scale, 0.7, shape).astype(np.float32)
    mean, var, _, _ = ops_nn._bn_stats(jnp.asarray(x), -1)
    xr = x.reshape(-1, C)
    np.testing.assert_allclose(np.asarray(mean), xr.mean(0), rtol=0,
                               atol=1e-4 * max(1.0, mean_scale))
    np.testing.assert_allclose(np.asarray(var), xr.var(0), rtol=1e-4,
                               atol=1e-6)


def test_bn_stats_cancellation_regime():
    # mean/std = 2000: E[x^2]-E[x]^2 in f32 is useless here; the shifted
    # form must stay at ~1e-4 relative error
    rng = np.random.default_rng(1)
    x = rng.normal(1000.0, 0.5, (8, 16, 16, 8)).astype(np.float32)
    _, var, _, _ = ops_nn._bn_stats(jnp.asarray(x), -1)
    ref = x.reshape(-1, 8).var(0)
    np.testing.assert_allclose(np.asarray(var), ref, rtol=1e-4)


def test_bn_shifted_onepass_cancellation():
    """The statistics must survive the |mean| >> std regime that killed
    the round-3 one-pass, in either layout."""
    from mxnet_tpu.ops.nn import _bn_stats

    rng = np.random.default_rng(7)
    x = rng.normal(1000.0, 0.5, (8, 16, 16, 8)).astype(np.float32)
    _, var, _, _ = _bn_stats(jnp.asarray(x), -1)
    ref = x.reshape(-1, 8).var(0)
    np.testing.assert_allclose(np.asarray(var), ref, rtol=1e-4)
    # and for channel-first too (the shift works in any layout)
    xc = np.moveaxis(x, -1, 1).copy()
    _, var1, _, _ = _bn_stats(jnp.asarray(xc), 1)
    np.testing.assert_allclose(np.asarray(var1), ref, rtol=1e-4)


def _two_pass_batch_norm(x, g, b, eps):
    """Channel-last BatchNorm with a two-pass mean and variance in float32:
    ``(out in x's dtype, mean, var)``."""
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=(0, 1, 2))
    v = jnp.mean(jnp.square(xf - m), axis=(0, 1, 2))
    out = (xf - m) * jax.lax.rsqrt(v + eps) * g + b
    return out.astype(x.dtype), (m, v)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_batch_norm_op_fwd_bwd_parity(dtype):
    """Full op (channel-last axis): the shifted one-pass statistics and the
    closed-form backward against a two-pass BatchNorm under ``jax.vjp``,
    fwd + grads."""
    rng = np.random.default_rng(2)
    shape = (4, 6, 6, 16)
    x = rng.normal(1.5, 1.0, shape).astype(np.float32)
    g = rng.normal(1.0, 0.1, (16,)).astype(np.float32)
    b = rng.normal(0.0, 0.1, (16,)).astype(np.float32)
    dy = rng.normal(0, 1, shape).astype(np.float32)

    def op(x_, g_, b_):
        out, m, v = ops_nn.batch_norm(
            x_, g_, b_, jnp.zeros(16), jnp.ones(16),
            eps=1e-3, fix_gamma=False, training=True, axis=-1)
        return out, (m, v)

    def run(f):
        out, vjp, (m, v) = jax.vjp(f, jnp.asarray(x, dtype),
                                   jnp.asarray(g), jnp.asarray(b),
                                   has_aux=True)
        dx, dg, db = vjp(jnp.asarray(dy, dtype))
        return [np.asarray(t, np.float32) for t in (out, m, v, dx, dg, db)]

    fused = run(op)
    ref = run(lambda x_, g_, b_: _two_pass_batch_norm(x_, g_, b_, 1e-3))
    tol = 1e-5 if dtype == np.float32 else 2e-2
    for a, r, name in zip(fused, ref, ["out", "mean", "var", "dx", "dg", "db"]):
        np.testing.assert_allclose(a, r, rtol=tol, atol=tol,
                                   err_msg=f"mismatch in {name}")


def test_batch_norm_grad_vs_autodiff_reference():
    """Custom-vjp closed-form grads vs jax autodiff of a plain jnp BN.

    (Finite differences are useless here: d sum(BN)/dx is ~0 by
    normalization symmetry, far below f32 FD noise.)"""
    rng = np.random.default_rng(3)
    x = rng.normal(0.5, 1.0, (4, 5, 5, 8)).astype(np.float32)
    g = rng.normal(1, 0.1, (8,)).astype(np.float32)
    b = rng.normal(0, 0.1, (8,)).astype(np.float32)
    w = rng.normal(0, 1, x.shape).astype(np.float32)   # non-degenerate loss

    def ref(x_, g_, b_):
        out, _ = _two_pass_batch_norm(x_, g_, b_, 1e-3)
        return jnp.sum(out * w)

    def mine(x_, g_, b_):
        out, _, _ = ops_nn.batch_norm(
            x_, g_, b_, jnp.zeros(8), jnp.ones(8), eps=1e-3,
            fix_gamma=False, training=True, axis=-1)
        return jnp.sum(out * w)

    ga = jax.grad(ref, argnums=(0, 1, 2))(jnp.asarray(x), jnp.asarray(g),
                                          jnp.asarray(b))
    gm = jax.grad(mine, argnums=(0, 1, 2))(jnp.asarray(x), jnp.asarray(g),
                                           jnp.asarray(b))
    for a, m_, name in zip(ga, gm, ["dx", "dgamma", "dbeta"]):
        np.testing.assert_allclose(np.asarray(m_), np.asarray(a), rtol=1e-4,
                                   atol=1e-4, err_msg=name)


def test_batch_norm_nchw_grad_unchanged():
    """NCHW (axis=1): the shift works in any layout; the gradient stays
    finite."""
    rng = np.random.default_rng(5)
    x = rng.normal(0.5, 1.0, (4, 8, 5, 5)).astype(np.float32)

    def f(x_):
        out, _, _ = ops_nn.batch_norm(
            x_, jnp.ones(8), jnp.zeros(8), jnp.zeros(8), jnp.ones(8),
            eps=1e-3, fix_gamma=False, training=True, axis=1)
        return jnp.sum(out * out)

    g = jax.grad(f)(jnp.asarray(x))
    assert np.isfinite(np.asarray(g)).all()

"""The hyper-connected residual stream (``ops/hyper_connection.py``) on
hand-made numbers: the three maps, the Sinkhorn iteration (doubly
stochastic to 1e-5 after twenty passes, visibly not after one), the clamp's
side of a large draw, the mix against a plain ``einsum``, the Pallas
kernels (interpreted here) against the ``jax.numpy`` forms in float32 and
bfloat16, and YaRN's table and scale (``ops/mla.py``) against numbers
worked by hand below and far above the original length."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 - the package sets JAX up
from mxnet_tpu.ops import hyper_connection as hc
from mxnet_tpu.ops import mla

CFG = hc.HC(4, 20, 1e-6, -30.0, 30.0)
N, C, T = 4, 256, 24


@pytest.fixture(autouse=True)
def highest_precision():
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)


def mixer(seed, large=False, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    phi = r.standard_normal((N * C, N * (N + 2))) / math.sqrt(N * C)
    alpha = np.array([1.0, 1.0, 0.5]) * (1 + 0.1 * r.standard_normal(3))
    bias = 0.5 * r.standard_normal(N * (N + 2))
    if large:
        bias[2 * N:2 * N + 2] += (33.0, 31.0)
    return hc.Mixer(*(jnp.asarray(a, dtype) for a in (phi, alpha, bias)))


def test_the_maps_on_hand_made_numbers():
    """One token whose ``tilde`` is written down: ``H_pre`` and ``H_post``
    are the sigmoid and twice the sigmoid; ``H_res`` is the Sinkhorn limit
    of ``exp`` of the clipped entries, rows first."""
    tilde = np.zeros((1, 24), np.float32)
    tilde[0, :4] = (0.0, math.log(3.0), -math.log(3.0), 50.0)
    tilde[0, 4:8] = (0.0, math.log(3.0), -40.0, 40.0)
    tilde[0, 8:] = np.log(np.array([[2, 1, 1, 1], [1, 2, 1, 1],
                                    [1, 1, 2, 1], [1, 1, 1, 2]], float)) \
        .ravel()
    pre, post, res = hc.maps(jnp.asarray(tilde), CFG)
    np.testing.assert_allclose(pre[0], [0.5, 0.75, 0.25, 1.0], atol=1e-6)
    np.testing.assert_allclose(post[0], [1.0, 1.5, 0.0, 2.0], atol=1e-6)
    # a symmetric matrix with equal row sums is normalised in one pass
    np.testing.assert_allclose(
        np.asarray(res).reshape(4, 4),
        (np.ones((4, 4)) + np.eye(4)) / 5, atol=1e-6)


def test_sinkhorn_is_doubly_stochastic_after_twenty_passes_not_after_one():
    r = np.random.default_rng(3)
    tilde = np.zeros((512, 24), np.float32)
    tilde[:, 8:] = 0.5 * r.standard_normal((512, 16)) \
        + 0.5 * r.standard_normal(16)
    _, _, one = hc.maps(jnp.asarray(tilde), CFG._replace(iters=1))
    _, _, twenty = hc.maps(jnp.asarray(tilde), CFG)
    one = np.asarray(one).reshape(-1, 4, 4)
    twenty = np.asarray(twenty).reshape(-1, 4, 4)
    assert np.abs(twenty.sum(2) - 1).max() < 1e-5       # rows
    assert np.abs(twenty.sum(1) - 1).max() < 1e-5       # columns
    assert (twenty > 0).all()
    # one pass ends on the columns: they sum to one, the rows do not
    assert np.abs(one.sum(1) - 1).max() < 1e-5
    assert np.median(np.abs(one.sum(2) - 1).max(1)) > 1e-2
    assert np.abs(one - twenty).max() > 1e-2
    # rows before columns: ``sinkhorn`` sums over axis 1, then axis 0
    M = jnp.exp(jnp.asarray(tilde[:, 8:]).T.reshape(4, 4, -1))
    first = np.asarray(hc.sinkhorn(M, 1, 1e-6))
    assert np.abs(first.sum(0) - 1).max() < 1e-5
    assert np.abs(first.sum(1) - 1).max() > 1e-2


def test_the_clamp_decides_a_large_draw():
    """Two entries of a row past the clamp (33 and 31) are both cut to 30
    and split the row evenly before the iteration; without the clamp the
    first would weigh e^2 times the second."""
    X = jnp.asarray(np.random.default_rng(5).standard_normal((64, N * C)),
                    jnp.float32)
    tilde = hc.project(X, mixer(7, large=True), CFG)
    assert float(tilde[:, 8].min()) > 30 and float(tilde[:, 9].min()) > 30
    _, _, cut = hc.maps(tilde, CFG)
    _, _, free = hc.maps(tilde, CFG._replace(lo=-1e9, hi=1e9))
    cut, free = np.asarray(cut), np.asarray(free)
    # cut, the two are equal before the first pass: one column's scale
    # apart after it; free, e^2 apart and more
    M = np.exp(np.clip(np.asarray(tilde)[:, 8:10], -30, 30))
    assert (M[:, 0] == M[:, 1]).all()
    assert np.abs(cut - free).max() > 0.2
    assert np.isfinite(cut).all() and np.abs(cut.reshape(-1, 4, 4)
                                             .sum(2) - 1).max() < 1e-4


def test_the_stream_against_plain_sums():
    r = np.random.default_rng(11)
    X = r.standard_normal((T, N, C)).astype(np.float32)
    y = r.standard_normal((T, C)).astype(np.float32)
    m = mixer(1)
    tilde = hc.project(jnp.asarray(X.reshape(T, -1)), m, CFG)
    v = X.reshape(T, -1)
    x = v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-6)
    scale = np.repeat(np.asarray(m.alpha), [4, 4, 16])
    np.testing.assert_allclose(
        tilde, (x @ np.asarray(m.phi)) * scale + np.asarray(m.bias),
        atol=2e-5)
    pre, post, res = hc.maps(tilde, CFG)
    u = hc.collapse(jnp.asarray(v), pre, N)
    np.testing.assert_allclose(u, np.einsum("tn,tnc->tc", pre, X), atol=2e-5)
    new = hc.mix(jnp.asarray(v), jnp.asarray(y), res, post, N)
    want = np.einsum("tij,tjc->tic", np.asarray(res).reshape(T, N, N), X) \
        + np.asarray(post)[:, :, None] * y[:, None, :]
    np.testing.assert_allclose(np.asarray(new).reshape(T, N, C), want,
                               atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 0.13)])
@pytest.mark.parametrize("rows", [T, 64])
def test_the_kernels_against_the_jnp_forms(paged_kernels, dtype, tol, rows):
    """A stack of three mixers through ``enter``, ``step``, ``step``,
    ``leave``: each call as ONE Pallas call (interpreted) against the
    ``jax.numpy`` form, in float32 to the sums' order and in bfloat16 to a
    rounding of the stream (24 rows are padded to whole tiles)."""
    dt = jnp.dtype(dtype)
    r = np.random.default_rng(2)
    x = jnp.asarray(r.standard_normal((rows, C)), dt)
    ys = [jnp.asarray(r.standard_normal((rows, C)), dt) for _ in range(3)]
    ms = [mixer(1, True, dt), mixer(2, False, dt), mixer(3, True, dt)]

    def stack():
        s = hc.enter(x, ms[0], CFG)
        out = [s["u"]]
        for y, m in zip(ys, ms[1:]):
            s = hc.step(s, y, m, CFG)
            out.append(s["u"])
        return out + [hc.leave(s, ys[2], CFG), s["X"][:rows],
                      s["hres"][:rows], s["hpost"][:rows]]

    paged_kernels(False)
    plain = stack()
    paged_kernels(True)
    kernel = stack()
    for a, b in zip(plain, kernel):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol)


def test_the_kernels_are_named_for_the_trace(paged_kernels):
    paged_kernels(True)
    x = jnp.zeros((16, C), jnp.float32)

    def stack(x):
        s = hc.enter(x, mixer(1), CFG)
        s = hc.step(s, x, mixer(2), CFG)
        return hc.leave(s, x, CFG)

    text = str(jax.make_jaxpr(stack)(x))
    for name in ("mhc_enter", "mhc_mix", "mhc_leave"):
        assert name in text
    paged_kernels(False)      # (a trace is cached by the callable)
    assert "pallas_call" not in str(jax.make_jaxpr(lambda x: stack(x))(x))


# ------------------------------------------------------------------- YaRN
def test_yarns_table_and_scale_on_numbers_worked_by_hand():
    """theta 10,000, 64 rotary dimensions, factor 64 over 4,096 original
    positions, beta 32 / 1: the pair that turns 32 times in 4,096 positions
    is 64 ln(4096 / 64 pi) / (2 ln 10000) = 10.47, rounded down to 10; the
    pair that turns once is 22.51, rounded up to 23. Pairs up to 10 keep
    their frequency, pairs from 23 take a 64th, pair 15 lies 5/13 of the way."""
    inv = mla.yarn_inverse_frequencies(1e4, 32, 64, 4096, 32, 1)
    plain = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    np.testing.assert_allclose(
        inv[15], plain[15] / 64 * (5 / 13) + plain[15] * (8 / 13), rtol=1e-6)
    assert (np.diff(inv) < 0).all()
    assert mla.yarn_mscale(64, 1) == pytest.approx(0.1 * math.log(64) + 1)
    assert mla.yarn_mscale(1, 1) == 1.0
    np.testing.assert_allclose(mla.inverse_frequencies(1e4, 32), plain,
                               rtol=1e-6)
    # below the original length a fast pair turns as it always did; far
    # above it (position 30,000) a slow pair has turned 64 times less
    x = jnp.zeros((1, 64)).at[0, 0].set(1.0).at[0, 60].set(1.0)
    for pos in (100, 30_000):
        y = np.asarray(mla.rope_interleaved(x, jnp.asarray([pos]),
                                            jnp.asarray(inv)))[0]
        np.testing.assert_allclose(
            y[:2], [math.cos(pos * 1.0), math.sin(pos * 1.0)], atol=2e-3)
        slow = pos * 1e4 ** (-30 / 32) / 64
        np.testing.assert_allclose(y[60:62], [math.cos(slow), math.sin(slow)],
                                   atol=1e-5)
        unscaled = np.asarray(mla.rope_interleaved(
            x, jnp.asarray([pos]), mla.inverse_frequencies(1e4, 32)))[0]
        assert abs(unscaled[61] - y[61]) > 1e-3 * pos / 100
    # the factor multiplies cos and sin
    z = np.asarray(mla.rope_interleaved(x, jnp.asarray([7]),
                                        jnp.asarray(inv), 1.5))[0]
    np.testing.assert_allclose(z[:2], [1.5 * math.cos(7.0),
                                       1.5 * math.sin(7.0)], atol=1e-5)

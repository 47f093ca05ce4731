"""The state-space operations (``ops/ssm.py``) against the recurrence token
by token: the chunked scan with a carried state and with padding inside its
last block, the convolution's carried tail, the one-token update; and the
paged decode kernel's grouped query heads against the dense form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 - the package sets JAX up
from mxnet_tpu.ops import ssm


@pytest.fixture(autouse=True)
def highest_precision():
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)


def inputs(R, T, H=4, P=8, N=16, seed=0):
    rng = np.random.default_rng(seed)
    f = jnp.float32
    x = jnp.asarray(rng.normal(size=(R, T, H, P)), f)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                        (R, T, H))), f)
    a = -jnp.asarray(rng.uniform(1, 16, (H,)), f)
    b = jnp.asarray(rng.normal(size=(R, T, N)), f)
    c = jnp.asarray(rng.normal(size=(R, T, N)), f)
    s0 = jnp.asarray(rng.normal(size=(R, H, P, N)), f)
    return x, dt, a, b, c, s0


# ------------------------------------------------------- the chunked scan
@pytest.mark.parametrize("T,block", [(16, 8), (20, 8), (8, 256), (24, 24),
                                     (33, 16), (1, 4)])
def test_chunked_scan_equals_the_recurrence_with_a_carried_state(T, block):
    x, dt, a, b, c, s0 = inputs(2, T, seed=T)
    y, s = ssm.ssd_chunk_scan(x, dt, a, b, c, s0, block)
    want_y, want_s = ssm.ssd_scan_sequential(x, dt, a, b, c, s0)
    assert y.shape == want_y.shape and y.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-4,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=2e-5,
                               rtol=2e-5)
    # the state it started from matters: the test would not see a dropped one
    y0, _ = ssm.ssd_chunk_scan(x, dt, a, b, c, jnp.zeros_like(s0), block)
    assert np.abs(np.asarray(y0 - y)).max() > 0.1


@pytest.mark.parametrize("real", [(5, 13), (16, 1), (0, 9)])
def test_padding_inside_the_last_block_does_not_advance_the_state(real):
    """Positions past a row's real tokens carry ``dt`` 0: the state that
    comes out is the one after the row's last real token, whatever stands
    in the padding."""
    T, block = 16, 8
    x, dt, a, b, c, s0 = inputs(2, T, seed=3)
    vl = jnp.asarray(real)
    live = jnp.arange(T)[None, :] < vl[:, None]
    y, s = ssm.ssd_chunk_scan(x, jnp.where(live[..., None], dt, 0.0), a, b,
                              c, s0, block)
    for r, n in enumerate(real):
        want_y, want_s = ssm.ssd_scan_sequential(
            x[r:r + 1, :n], dt[r:r + 1, :n], a, b[r:r + 1, :n],
            c[r:r + 1, :n], s0[r:r + 1])
        np.testing.assert_allclose(np.asarray(s[r]), np.asarray(want_s[0]),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(y[r, :n]),
                                   np.asarray(want_y[0]), atol=2e-4,
                                   rtol=2e-5)
    if real[0] == 0:       # a row of padding alone: bit for bit
        np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(s0[0]))


def test_two_windows_chained_are_one_long_window():
    x, dt, a, b, c, s0 = inputs(1, 40, seed=9)
    y, s = ssm.ssd_chunk_scan(x, dt, a, b, c, s0, 8)
    y1, s1 = ssm.ssd_chunk_scan(x[:, :24], dt[:, :24], a, b[:, :24],
                                c[:, :24], s0, 8)
    y2, s2 = ssm.ssd_chunk_scan(x[:, 24:], dt[:, 24:], a, b[:, 24:],
                                c[:, 24:], s1, 8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y), atol=2e-4, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=2e-5,
                               rtol=2e-5)


# -------------------------------------------------- the one-token update
def test_one_token_updates_follow_the_recurrence_and_spare_idle_rows():
    T = 6
    x, dt, a, b, c, s0 = inputs(3, T, seed=5)
    active = jnp.asarray([True, False, True])
    s, ys = s0, []
    for t in range(T):
        y, s = ssm.ssm_state_update(s, x[:, t], dt[:, t], a, b[:, t],
                                    c[:, t], active)
        ys.append(y)
    want_y, want_s = ssm.ssd_scan_sequential(x, dt, a, b, c, s0)
    keep = np.asarray(active)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1))[keep],
                               np.asarray(want_y)[keep], atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s)[keep], np.asarray(want_s)[keep],
                               atol=1e-5, rtol=1e-5)
    # the row that was not active: bit for bit what it was
    np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(s0[1]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_update_keeps_the_states_dtype(dtype):
    x, dt, a, b, c, s0 = inputs(2, 1, seed=1)
    s0 = s0.astype(dtype)
    y, s = ssm.ssm_state_update(s0, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                                jnp.asarray([True, False]))
    assert s.dtype == jnp.dtype(dtype) and y.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(s[1].astype(jnp.float32)),
                                  np.asarray(s0[1].astype(jnp.float32)))
    assert np.abs(np.asarray((s[0] - s0[0]).astype(jnp.float32))).max() > 0


# ------------------------------------------------------- the convolution
def _conv_plain(x, w, bias):
    """Zero history: position t reads t-K+1..t."""
    K, T = w.shape[0], x.shape[0]
    ext = np.concatenate([np.zeros((K - 1, x.shape[1])), x], 0)
    return bias + sum(w[k] * ext[k:k + T] for k in range(K))


def test_convolution_carries_its_tail_from_chunk_to_chunk():
    rng = np.random.default_rng(0)
    K, D, T = 4, 6, 19
    x = rng.normal(size=(1, T, D)).astype(np.float32)
    w = rng.normal(size=(K, D)).astype(np.float32)
    bias = rng.normal(size=(D,)).astype(np.float32)
    want = _conv_plain(x[0], w, bias)
    tail = jnp.zeros((1, K - 1, D), jnp.float32)
    got, at = [], 0
    for n, width in ((8, 8), (2, 8), (1, 8), (8, 8)):  # ragged fills
        part = np.zeros((1, width, D), np.float32)
        part[0, :n] = x[0, at:at + n]
        part[0, n:] = 99.0                              # padding is not read
        y, tail = ssm.causal_conv(jnp.asarray(part), tail, jnp.asarray(w),
                                  jnp.asarray(bias), jnp.asarray([n]))
        got.append(np.asarray(y)[0, :n])
        at += n
        np.testing.assert_array_equal(
            np.asarray(tail)[0],
            np.concatenate([np.zeros((K - 1, D), np.float32),
                            x[0, :at]])[-(K - 1):])
    np.testing.assert_allclose(np.concatenate(got), want, atol=1e-5)


def test_a_row_with_no_real_position_keeps_its_tail():
    rng = np.random.default_rng(1)
    tail = jnp.asarray(rng.normal(size=(2, 3, 5)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(2, 1, 5)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(4, 5)), jnp.float32)
    y, new = ssm.causal_conv(x, tail, w, jnp.zeros((5,)), jnp.asarray([1, 0]))
    assert new.dtype == jnp.bfloat16 and y.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(new[1], np.float32),
                                  np.asarray(tail[1], np.float32))
    np.testing.assert_array_equal(np.asarray(new[0, :2], np.float32),
                                  np.asarray(tail[0, 1:], np.float32))
    np.testing.assert_array_equal(np.asarray(new[0, 2], np.float32),
                                  np.asarray(x[0, 0], np.float32))


# ------------------------------ the paged decode kernel's grouped heads
@pytest.mark.parametrize("Hkv,G", [(2, 4), (8, 4), (4, 1)])
def test_paged_decode_kernel_takes_grouped_query_heads(Hkv, G, monkeypatch):
    """``paged_decode_attention`` with ``G`` query heads a key/value head
    (interpreted here): the group rides the window axis, every query at the
    row's position; against dense attention over the gathered pages."""
    from mxnet_tpu.ops.pallas import paged_flash_attention as pfa

    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    rng = np.random.default_rng(Hkv * 10 + G)
    B, D, ps, P = 3, 16, 8, 5
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(1 + B * P, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(1 + B * P, ps, Hkv, D)), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(B * P).reshape(B, P), jnp.int32)
    pos = jnp.asarray([0, 17, 39], jnp.int32)
    got = pfa.paged_decode_attention(q, kp, vp, table, pos, sm_scale=0.3)
    # dense: query head i reads key/value head i // G
    want = pfa.paged_decode_reference(
        q, jnp.repeat(kp, G, axis=2), jnp.repeat(vp, G, axis=2), table, pos,
        sm_scale=0.3)
    assert got.shape == (B, Hkv * G, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

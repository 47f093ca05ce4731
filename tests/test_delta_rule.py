"""The gated delta rule (``ops/delta_rule.py``, ``ops/pallas/gated_delta
.py``): the blocked form of a window against the token-by-token recurrence
at lengths the block does and does not divide, a state carried in and out
and across windows, rows of different real lengths, padding that leaves the
state bit for bit, the write strength doubled or not, the one-token update,
and the step's Pallas kernel (interpreted here) against its ``jax.numpy``
form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 - the package sets JAX up
from mxnet_tpu.ops import delta_rule as dr
from mxnet_tpu.ops.pallas import gated_delta as gd

H, DK, DV = 3, 8, 16
# one program a shape: run eagerly the substitution's rows are a hundred
# dispatches a block
sequential = jax.jit(dr.delta_rule_sequential)
chunk = jax.jit(dr.delta_rule_chunk, static_argnums=6)
inverse = jax.jit(dr.unit_lower_inverse)


@pytest.fixture(autouse=True)
def highest_precision():
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)


def window(R, T, seed=0, neg=True, real=None, heads=H, dk=DK, dv=DV):
    """Seeded ``(q, k, v, g, beta, state)`` of a window as the net makes
    them: keys of unit length with a common positive part (``silu`` of a
    normal), a decay a head from a few tokens to a few hundred."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = dr.l2_heads(jax.nn.silu(jax.random.normal(ks[0], (R, T, heads, dk)))) \
        / np.sqrt(dk)
    k = dr.l2_heads(jax.nn.silu(jax.random.normal(ks[1], (R, T, heads, dk))))
    v = jax.random.normal(ks[2], (R, T, heads, dv))
    g, beta = dr.gates(
        jax.random.normal(ks[3], (R, T, heads)),
        jax.random.normal(ks[4], (R, T, heads)),
        jnp.log(jnp.linspace(1.0, 16.0, heads)), jnp.full((heads,), -5.0),
        neg)
    if real is not None:
        live = (jnp.arange(T)[None, :] < jnp.asarray(real)[:, None])[..., None]
        g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    state = jax.random.normal(ks[5], (R, heads, dk, dv))
    return q, k, v, g, beta, state


def close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                               rtol=tol)


# --------------------------------------------- blocked against sequential
@pytest.mark.parametrize("block", [4, 16, 64])
@pytest.mark.parametrize("T", [1, 3, 16, 37, 64, 130])
def test_blocked_form_follows_the_recurrence(T, block):
    """Window lengths under a block, at one, over one and at lengths the
    block does not divide, from a state that is not zero."""
    args = window(2, T, seed=T)
    o, s = sequential(*args)
    o2, s2 = chunk(*args, block)
    assert o2.shape == (2, T, H, DV) and s2.shape == (2, H, DK, DV)
    close(o2, o)
    close(s2, s)


@pytest.mark.parametrize("neg", [True, False], ids=["neg_eigval", "plain"])
def test_the_write_strength_is_doubled_only_where_asked(neg):
    """``allow_neg_eigval`` doubles ``beta`` (a transition with eigenvalues
    down to -1); both settings follow the recurrence, and they differ."""
    a = jax.random.normal(jax.random.PRNGKey(1), (2, 5, H))
    g, beta = dr.gates(a, a, jnp.zeros(H), jnp.zeros(H), neg)
    assert float(beta.max()) <= (2.0 if neg else 1.0)
    assert (float(beta.max()) > 1.0) == neg and float(g.max()) < 0
    args = window(1, 40, seed=3, neg=neg)
    close(chunk(*args, 16)[1],
          sequential(*args)[1])
    other = window(1, 40, seed=3, neg=not neg)
    assert np.abs(np.asarray(args[4]) - np.asarray(other[4])).max() > 0.1


def test_a_state_carried_over_two_windows_is_one_window():
    """The state out of one window is the state into the next, cut at a
    position that is no multiple of the block."""
    q, k, v, g, beta, s0 = window(1, 90, seed=5)
    whole_o, whole_s = sequential(q, k, v, g, beta, s0)
    cut = 37
    o1, s1 = chunk(q[:, :cut], k[:, :cut], v[:, :cut],
                                 g[:, :cut], beta[:, :cut], s0, 16)
    o2, s2 = chunk(q[:, cut:], k[:, cut:], v[:, cut:],
                                 g[:, cut:], beta[:, cut:], s1, 16)
    close(jnp.concatenate([o1, o2], 1), whole_o)
    close(s2, whole_s)
    # and a state that is dropped shows: the second window from zero
    _, lost = chunk(q[:, cut:], k[:, cut:], v[:, cut:],
                                  g[:, cut:], beta[:, cut:], s0 * 0, 16)
    assert np.abs(np.asarray(lost) - np.asarray(whole_s)).max() > 1e-2


def test_rows_of_other_lengths_stop_at_their_last_real_token():
    """Three rows of 50, 23 and 0 real positions in one window: each row's
    state is what the recurrence leaves after its own real tokens, and the
    row of padding alone keeps its state BIT FOR BIT."""
    real = [50, 23, 0]
    q, k, v, g, beta, s0 = window(3, 50, seed=7, real=real)
    o, s = chunk(q, k, v, g, beta, s0, 16)
    for r, n in enumerate(real):
        if n:
            wo, ws = sequential(
                q[r:r + 1, :n], k[r:r + 1, :n], v[r:r + 1, :n],
                g[r:r + 1, :n], beta[r:r + 1, :n], s0[r:r + 1])
            close(o[r, :n], wo[0])
            close(s[r], ws[0])
    assert np.array_equal(np.asarray(s[2]), np.asarray(s0[2]))


def test_the_solve_is_forward_substitution():
    """``(I + a)^-1`` of a strictly lower ``a`` whose entries are of the
    order of 1 (keys with a common part, ``beta`` near 2): the series
    ``I - a + a^2 - ...`` loses every digit there, substitution none."""
    rng = np.random.default_rng(0)
    a = np.tril(rng.uniform(0.5, 1.5, (2, 64, 64)), -1).astype(np.float32)
    got = np.asarray(inverse(jnp.asarray(a)))
    want = np.linalg.inv(np.eye(64) + a.astype(np.float64))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    assert np.abs(np.triu(got, 1)).max() == 0
    small = np.asarray(inverse(jnp.asarray(a[:, :5, :5])))
    np.testing.assert_allclose(
        small, np.linalg.inv(np.eye(5) + a[:, :5, :5]), atol=1e-5)


# ------------------------------------------------------------------ a step
def test_steps_one_token_at_a_time_follow_the_recurrence():
    q, k, v, g, beta, s0 = window(2, 9, seed=11)
    o, s = sequential(q, k, v, g, beta, s0)
    state, on = s0, jnp.ones((2,), bool)
    for t in range(9):
        ot, state = dr.delta_rule_step(state, q[:, t], k[:, t], v[:, t],
                                       g[:, t], beta[:, t], on)
        close(ot, o[:, t])
    close(state, s)


@pytest.mark.parametrize("form", ["jnp", "kernel"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_row_that_is_not_active_keeps_its_state_bit_for_bit(form, dtype):
    q, k, v, g, beta, s0 = window(3, 1, seed=13)
    s0 = s0.astype(dtype)
    fn = dr.delta_rule_step if form == "jnp" else gd.gated_delta_step
    active = jnp.asarray([True, False, True])
    o, s = fn(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], active)
    assert s.dtype == s0.dtype and o.dtype == jnp.float32
    assert np.array_equal(np.asarray(s[1], np.float32),
                          np.asarray(s0[1], np.float32))
    assert not np.array_equal(np.asarray(s[0], np.float32),
                              np.asarray(s0[0], np.float32))


# -------------------------------------------- the kernel, interpreted here
@pytest.mark.parametrize("heads,dk,dv", [(3, 8, 16), (30, 96, 192)])
def test_step_kernel_against_its_jnp_form(heads, dk, dv):
    """At the test's widths and at the model's own (30 heads of 96 x
    192)."""
    q, k, v, g, beta, s0 = window(2, 1, seed=19, heads=heads, dk=dk, dv=dv)
    active = jnp.asarray([True, True])
    o, s = dr.delta_rule_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                              beta[:, 0], active)
    o2, s2 = gd.gated_delta_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], active)
    close(o2, o, 1e-5)
    close(s2, s, 1e-5)


@pytest.mark.parametrize("on", [True, False], ids=["kernels", "jnp"])
def test_one_predicate_chooses_the_form(on, paged_kernels, monkeypatch):
    """``delta_step`` asks ``ops/paged.kernels_on()`` and nothing else; the
    window has one form."""
    paged_kernels(on)
    called = []
    monkeypatch.setattr(gd, "gated_delta_step",
                        lambda *a: called.append("step") or a[:2])
    q, k, v, g, beta, s0 = window(1, 4, seed=23)
    dr.delta_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                  jnp.ones((1,), bool))
    assert called == (["step"] if on else [])
    assert not hasattr(dr, "delta_chunk") and \
        not hasattr(gd, "gated_delta_chunk")

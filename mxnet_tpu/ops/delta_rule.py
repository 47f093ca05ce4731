"""The gated delta rule (Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464) of a hybrid language model's serving programs: the
blocked form for a window of tokens (state in, state out, padding that does
not advance it), the one-token update of a decode step, and the
token-by-token form both are held against.

The recurrence, for one head with a state ``S (d_k, d_v)``, a key ``k``
of length 1, a log-decay ``g <= 0`` and a write strength ``beta``::

    S <- exp(g) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q

Every token first READS the state back with its key and writes the
DIFFERENCE, so a window cannot be summed in parallel as ``ops/ssm.py``'s
scan is: inside a block of ``C`` tokens the ``u`` depend on each other
through ``A = tril(beta_t exp(G_t - G_s) k_t . k_s, -1)`` (``G`` the
running sum of ``g`` in the block) and come out of one unit-triangular
solve, ``(I + A) [W U] = [beta exp(G) k, beta v]``, ``u = U - W S_0``.
A position whose ``g`` is 0 and ``beta`` is 0 leaves the state bit for bit:
that is how padding is told.

``delta_rule_sequential``, ``delta_rule_chunk`` and ``delta_rule_step`` are
plain ``jax.numpy``, used on every backend and under a mesh: products round
their operands as the backend's default precision does (bfloat16 on a TPU)
and sum in float32; the solve, the decays and the carried state are
float32. ``delta_step`` is what a net's decode step calls: where
``ops/paged.kernels_on()`` the Pallas kernel of ``ops/pallas/gated_delta
.py`` takes the update's place, and nothing else decides. The window has no
kernel (the one written lost to ``delta_rule_chunk`` on the chip and went:
PERF.md section 6, PR 48).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import paged as _paged

__all__ = ["gates", "l2_heads", "block_terms", "pad_window",
           "unit_lower_inverse", "delta_rule_sequential",
           "delta_rule_chunk", "delta_rule_step", "delta_step", "BLOCK"]

BLOCK = 64      # tokens a block of the window's solve
F32 = jnp.float32


def l2_heads(x, eps=1e-6):
    """``x (..., heads, d)`` to unit length a head, in float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def gates(a, b, a_log, dt_bias, allow_neg_eigval=True):
    """``(g, beta)`` a head from the two gate projections ``a, b (...,
    heads)``: ``g = -exp(A_log) softplus(a + dt_bias)`` (the log of the
    decay, below 0) and ``beta = sigmoid(b)``, doubled where
    ``allow_neg_eigval`` so that a step's transition ``I - beta k k^T`` has
    eigenvalues in (-1, 1] (Grazzi et al., arXiv:2411.12537)."""
    g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
        a.astype(F32) + dt_bias.astype(F32))
    beta = jax.nn.sigmoid(b.astype(F32))
    return g, 2.0 * beta if allow_neg_eigval else beta


def delta_rule_sequential(q, k, v, g, beta, state):
    """The recurrence token by token: ``q, k (R, T, H, d_k)``, ``v (R, T,
    H, d_v)``, ``g, beta (R, T, H)``, ``state (R, H, d_k, d_v)``. Returns
    ``(o (R, T, H, d_v), state)`` in float32."""

    def step(s, inp):
        qt, kt, vt, gt, bt = inp
        s = jnp.exp(gt)[..., None, None] * s
        u = bt[..., None] * (vt - jnp.sum(s * kt[..., None], -2))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.sum(s * qt[..., None], -2)

    seq = tuple(jnp.moveaxis(x.astype(F32), 1, 0)
                for x in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state.astype(F32), seq)
    return jnp.moveaxis(o, 0, 1), state


def _substitute(a):
    """``(I + a)^-1`` row by row: ``T[i] = e_i - sum_(j<i) a[i, j] T[j]``,
    unrolled (``C`` is a diagonal block's, 16 or under)."""
    C = a.shape[-1]
    eye = jnp.eye(C, dtype=F32)
    rows = [jnp.broadcast_to(eye[0], a.shape[:-2] + (C,))]
    for i in range(1, C):
        rows.append(eye[i] - jnp.sum(
            a[..., i, :i, None] * jnp.stack(rows, -2), -2))
    return jnp.stack(rows, -2)


def unit_lower_inverse(a, leaf=16):
    """``(I + a)^-1`` for ``a (..., C, C)`` strictly lower triangular, by
    forward substitution in float32 whatever the backend's default
    precision: row by row inside the diagonal blocks of ``leaf`` (all of
    them at once), then block by block above them, pairs of blocks merged
    into one of twice the size (``[[T11, 0], [-T22 a21 T11, T22]]``) until
    one is left: 16 short steps and two levels of small products for a
    block of 64, where a row at a time would be 64 steps. A ``C`` that is
    not ``leaf`` times a power of two is padded with rows and columns of
    zeros, whose inverse is the identity there."""
    C = a.shape[-1]
    if C <= leaf:
        return _substitute(a)
    n = 1 << (-(-C // leaf) - 1).bit_length()      # blocks, a power of two
    if n * leaf != C:
        pad = [(0, 0)] * (a.ndim - 2) + [(0, n * leaf - C)] * 2
        return unit_lower_inverse(jnp.pad(a, pad), leaf)[..., :C, :C]

    def blocks(size, row, col):
        """Every other block of ``size`` on the block diagonal ``row -
        col``, stacked on a new axis."""
        return jnp.stack([
            a[..., (i + row) * size:(i + row + 1) * size,
              (i + col) * size:(i + col + 1) * size]
            for i in range(0, C // size, 1 + row)], -3)

    t, size = _substitute(blocks(leaf, 0, 0)), leaf
    while size < C:
        t11, t22 = t[..., 0::2, :, :], t[..., 1::2, :, :]
        t21 = -jnp.einsum("...ij,...jk,...kl->...il", t22,
                          blocks(size, 1, 0), t11,
                          precision=jax.lax.Precision.HIGHEST)
        t = jnp.concatenate([
            jnp.concatenate([t11, jnp.zeros_like(t11)], -1),
            jnp.concatenate([t21, t22], -1)], -2)
        size *= 2
    return t[..., 0, :, :]


def block_terms(q, k, v, g, beta, block):
    """What a block of the window needs that does not depend on the state:
    ``q, k (R, T, H, d_k)``, ``v (R, T, H, d_v)``, ``g, beta (R, T, H)``
    with ``T`` a multiple of ``block``. Returns, heads leading, ``(W (R, nb,
    H, C, d_k), U (.., C, d_v), qg (.., C, d_k) = exp(G) q, P (.., C, C) =
    tril(exp(G_t - G_s) q_t . k_s), kd (.., C, d_k) = exp(G_C - G) k, keep
    (R, nb, H) = exp(G_C))`` in float32. The exponent is masked before it
    is taken, so nothing above the diagonal overflows."""
    R, T, H = g.shape
    C, nb = int(block), T // int(block)

    def by_block(x):
        x = x.astype(F32).reshape((R, nb, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 2)

    q, k, v = by_block(q), by_block(k), by_block(v)      # (R, nb, H, C, d)
    g, beta = by_block(g), by_block(beta)                # (R, nb, H, C)
    cum = jnp.cumsum(g, -1)
    seg = cum[..., :, None] - cum[..., None, :]
    lower = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    kk = jnp.einsum("rbhtd,rbhsd->rbhts", k, k)
    a = jnp.where(jnp.tril(lower, -1), beta[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate([(beta * jnp.exp(cum))[..., None] * k,
                           beta[..., None] * v], -1)
    wu = jnp.einsum("rbhts,rbhsd->rbhtd", unit_lower_inverse(a), rhs,
                    precision=jax.lax.Precision.HIGHEST)
    dk = k.shape[-1]
    p = decay * jnp.einsum("rbhtd,rbhsd->rbhts", q, k)
    return (wu[..., :dk], wu[..., dk:], jnp.exp(cum)[..., None] * q, p,
            jnp.exp(cum[..., -1:] - cum)[..., None] * k,
            jnp.exp(cum[..., -1]))


def pad_window(q, k, v, g, beta, block):
    """The window padded at its end to whole blocks with positions that
    leave the state as it is (``g`` 0, ``beta`` 0)."""
    pad = -g.shape[1] % int(block)
    if not pad:
        return q, k, v, g, beta
    return tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                 for x in (q, k, v, g, beta))


def delta_rule_chunk(q, k, v, g, beta, state, block=BLOCK):
    """The same recurrence over a window of ``T`` tokens in blocks of
    ``block``: inside a block the unit-triangular solve, between blocks the
    state. Shapes as ``delta_rule_sequential``. Returns ``(o (R, T, H,
    d_v), new_state)`` in float32: the state after the window's last
    position with a nonzero ``g`` or ``beta``."""
    R, T, H = g.shape
    C = min(int(block), T)
    terms = block_terms(*pad_window(q, k, v, g, beta, C), C)

    def step(s, inp):
        w, u, qg, p, kd, keep = inp
        u = u - jnp.einsum("rhtk,rhkv->rhtv", w, s)
        o = jnp.einsum("rhtk,rhkv->rhtv", qg, s) \
            + jnp.einsum("rhts,rhsv->rhtv", p, u)
        s = keep[..., None, None] * s \
            + jnp.einsum("rhsk,rhsv->rhkv", kd, u)
        return s, o

    state, o = jax.lax.scan(step, state.astype(F32),
                            tuple(jnp.moveaxis(x, 1, 0) for x in terms))
    # (nb, R, H, C, d_v) -> (R, nb x C, H, d_v)
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(R, -1, H, o.shape[-1])
    return o[:, :T], state


def delta_rule_step(state, q, k, v, g, beta, active):
    """One token a row, in float32: ``state (B, H, d_k, d_v)`` (float32, or
    the dtype the configuration states), ``q, k (B, H, d_k)``, ``v (B, H,
    d_v)``, ``g, beta (B, H)``. Returns ``(o (B, H, d_v) float32,
    new_state)``; a row that is not ``active`` keeps its state bit for
    bit."""
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    s = jnp.exp(g)[..., None, None] * state.astype(F32)
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], -2))
    s = s + k[..., None] * u[..., None, :]
    o = jnp.sum(s * q[..., None], -2)
    return o, jnp.where(active[:, None, None, None], s.astype(state.dtype),
                        state)


# ------------------------------------------------- what a net calls
def delta_step(state, q, k, v, g, beta, active):
    """``delta_rule_step``, through ``%gated_delta_step`` where the kernels
    run."""
    if _paged.kernels_on():
        from .pallas import gated_delta as _kernel

        return _kernel.gated_delta_step(state, q, k, v, g, beta, active)
    return delta_rule_step(state, q, k, v, g, beta, active)

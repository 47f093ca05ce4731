"""State-space (Mamba-2) operations of a hybrid language model's serving
programs: a causal depthwise convolution with a carried tail, the chunked
state-space scan of a window of tokens (state in, state out, padding that
does not advance it), and the one-token state update of a decode step.

The recurrence, for one head with a scalar ``a < 0`` and a state ``S (P,
N)``: ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (outer) B_t`` and ``y_t = S_t
C_t``; ``B`` and ``C`` are shared by every head (one group). A position
whose ``dt`` is 0 leaves the state as it was (decay 1, input 0): that is
how padding is told.

Everything here is plain ``jax.numpy`` with float32 accumulation, used on
every backend and under a mesh (the scan has no kernel of its own: alone on
the chip it takes under a tenth of a chunk, PERF.md section 6, PR 31).
``ssd_scan_sequential`` is the token-by-token form the tests hold the
chunked one against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_conv", "ssd_chunk_scan", "ssd_scan_sequential",
           "ssm_state_update"]


def causal_conv(x, tail, w, bias, valid_len):
    """Depthwise causal convolution of ``x (R, T, D)`` with kernel ``w (K,
    D)`` and ``bias (D,)``: position ``t`` reads itself and the ``K - 1``
    before it, of which those before the window come from ``tail (R, K -
    1, D)`` (the last inputs of the row's history). Returns ``(y (R, T, D)
    float32, new_tail)``: the tail after the row's ``valid_len (R,)`` real
    positions, in ``tail``'s dtype (unchanged where that is 0)."""
    K, T = w.shape[0], x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    y = bias.astype(jnp.float32)
    for k in range(K):
        y = y + wf[k] * ext[:, k:k + T].astype(jnp.float32)
    at = valid_len[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    new_tail = jnp.take_along_axis(ext, at[:, :, None], axis=1)
    return y, new_tail.astype(tail.dtype)


def ssd_scan_sequential(x, dt, a, b, c, state):
    """The recurrence token by token: ``x (R, T, H, P)``, ``dt (R, T, H)``,
    ``a (H,)``, ``b`` and ``c (R, T, N)``, ``state (R, H, P, N)``. Returns
    ``(y (R, T, H, P), state)`` in float32."""
    f = jnp.float32
    a = a.astype(f)

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = jnp.exp(dtt * a)[..., None, None] * s \
            + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        return s, jnp.einsum("rhpn,rn->rhp", s, ct)

    seq = tuple(jnp.moveaxis(v.astype(f), 1, 0) for v in (x, dt, b, c))
    state, y = jax.lax.scan(step, state.astype(f), seq)
    return jnp.moveaxis(y, 0, 1), state


def ssd_chunk_scan(x, dt, a, b, c, state, block=256):
    """The same recurrence over a window of ``T`` tokens in blocks of
    ``block``: inside a block the quadratic form (every pair ``s <= t``
    weighs ``C_t . B_s`` times the decay between them), between blocks the
    state. Shapes as ``ssd_scan_sequential``; a ``T`` that ``block`` does
    not divide is padded with positions of ``dt`` 0. The products' operands
    are rounded as the backend's default precision rounds them (bfloat16 on
    a TPU), the sums and the state are float32. Returns ``(y (R, T, H, P),
    new_state)`` in float32; the state after the window's last position
    with a nonzero ``dt``."""
    f = jnp.float32
    R, T, H, P = x.shape
    L = min(int(block), T)
    pad = -T % L
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) *
                               (v.ndim - 2)) for v in (x, dt, b, c))
    nb = (T + pad) // L
    # heads lead, keys last: the (t, s) planes of a head lie in lanes
    xs = x.astype(f).reshape(R, nb, L, H, P).transpose(0, 1, 3, 2, 4)
    dts = dt.astype(f).reshape(R, nb, L, H).transpose(0, 1, 3, 2)
    bs = b.astype(f).reshape(R, nb, L, -1)
    cs = c.astype(f).reshape(R, nb, L, -1)
    cum = jnp.cumsum(dts * a.astype(f)[None, None, :, None], axis=-1)
    # inside a block: y_t += sum_{s<=t} (C_t.B_s) exp(cum_t - cum_s) dt_s x_s
    cb = jnp.einsum("rktn,rksn->rkts", cs, bs)
    lower = jnp.tril(jnp.ones((L, L), bool))
    seg = cum[..., :, None] - cum[..., None, :]            # (R,nb,H,t,s)
    w = jnp.exp(jnp.where(lower, seg, -jnp.inf)) * cb[:, :, None] \
        * dts[..., None, :]
    y = jnp.einsum("rkhts,rkhsp->rkhtp", w, xs)
    # what a block adds to the state, and how much of the old it keeps
    to_end = jnp.exp(cum[..., -1:] - cum) * dts            # (R,nb,H,s)
    added = jnp.einsum("rkhs,rkhsp,rksn->rkhpn", to_end, xs, bs)
    keep = jnp.exp(cum[..., -1])                           # (R,nb,H)

    def step(s, inp):
        add, kp = inp
        return kp[..., None, None] * s + add, s

    state, before = jax.lax.scan(
        step, state.astype(f),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(keep, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                    # (R,nb,H,P,N)
    # between blocks: y_t += exp(cum_t) C_t . S_(block's start)
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "rktn,rkhpn->rkhtp", cs, before)
    y = y.transpose(0, 1, 3, 2, 4).reshape(R, nb * L, H, P)
    return y[:, :T], state


def ssm_state_update(state, x, dt, a, b, c, active):
    """One token a row: ``state (B, H, P, N)`` float32 (or the dtype the
    configuration states), ``x (B, H, P)``, ``dt (B, H)``, ``b`` and ``c
    (B, N)``. Returns ``(y (B, H, P) float32, new_state)``; a row that is
    not ``active`` keeps its state bit for bit."""
    f = jnp.float32
    s = state.astype(f)
    dt = dt.astype(f)
    new = jnp.exp(dt * a.astype(f))[..., None, None] * s \
        + (dt[..., None] * x.astype(f))[..., None] \
        * b.astype(f)[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", new, c.astype(f))
    keep = active[:, None, None, None]
    return y, jnp.where(keep, new.astype(state.dtype), state)

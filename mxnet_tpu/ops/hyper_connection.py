"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): a
residual stream ``n`` wide.

A token's state is ``X (n, C)``; every sublayer ``F`` is wrapped by a MIXER
with its own ``phi (n x C, n + n + n x n)`` (the columns of ``phi_pre``,
``phi_post`` and ``phi_res`` side by side), ``alpha (3,)`` and ``bias (n +
n + n x n,)``:

    x      = RMSNorm(vec(X))                  over all n x C numbers, no gain
    tilde  = alpha * (x @ phi) + bias         (pre | post | res columns)
    H_pre  = sigmoid(tilde_pre)               H_post = 2 sigmoid(tilde_post)
    M      = exp(clip(mat(tilde_res), lo, hi))
    iters times:  M = M / (rowsum(M) + eps);  M = M / (colsum(M) + eps)
    u      = H_pre @ X                        the sublayer's input, (C,)
    X'     = M @ X + outer(H_post, F(u))

The arithmetic is float32 whatever the stream is kept in; the stream is a
flat ``(tokens, n x C)`` array (stream ``i`` in columns ``[i C, (i + 1)
C)``), so that no axis of 4 meets the chip's tiles. The ``(n, n)`` work of
the maps runs with TOKENS ON THE LANES (``(n, n, tokens)`` arrays: a
``(tokens, 4, 4)`` array would take a tile of 8 x 128 a token).

Three calls carry a stack: ``enter`` (the stream from one hidden state a
token, repeated, with the first mixer's input), ``step`` (a sublayer's
output mixed in and the NEXT mixer's input and maps taken), ``leave`` (the
last output mixed in and the streams summed). On a TPU each is one Pallas
call (``ops/pallas/mhc_mix.py``: ``%mhc_enter``, ``%mhc_mix``,
``%mhc_leave``) that reads the stream once and writes it once, with the
next mixer's sum of squares, projection and input taken while the new
stream is in VMEM; the small maps stay ``jax.numpy`` between the calls. On
the CPU and under a multi-device mesh the ``jax.numpy`` forms below stand
(``paged.kernels_on()``, the one answer the paged kernels ask too).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import paged

__all__ = ["HC", "Mixer", "enter", "step", "leave", "project", "maps",
           "sinkhorn", "collapse", "mix"]


class HC(NamedTuple):
    """The configuration's ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp_min`` and ``mhc_h_res_clamp_max``."""
    n: int
    iters: int
    eps: float
    lo: float
    hi: float


class Mixer(NamedTuple):
    """One mixer's parameters (see the module's docstring)."""
    phi: jax.Array
    alpha: jax.Array
    bias: jax.Array


def coefficients(m, n):
    """``(scale, bias)`` a column of ``x @ phi``, float32: ``alpha``
    repeated over its ``n``, ``n`` and ``n x n`` columns."""
    a = m.alpha.astype(jnp.float32)
    scale = jnp.concatenate([jnp.broadcast_to(a[i], (k,))
                             for i, k in enumerate((n, n, n * n))])
    return scale, m.bias.astype(jnp.float32)


# ------------------------------------------------------------- the maps
def project(X, m, hc):
    """``tilde (T, n + n + n x n)`` float32 of the stream ``X (T, n x C)``:
    the norm is one scalar a token, so it multiplies the product."""
    xf = X.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + hc.eps)
    p = jnp.dot(X, m.phi.astype(X.dtype), preferred_element_type=jnp.float32)
    scale, bias = coefficients(m, hc.n)
    return p * r * scale + bias


def sinkhorn(M, iters, eps):
    """``M (n, n, T)`` positive, normalised ``iters`` times: its rows
    (over axis 1), then its columns (over axis 0)."""
    for _ in range(int(iters)):
        M = M / (jnp.sum(M, 1, keepdims=True) + eps)
        M = M / (jnp.sum(M, 0, keepdims=True) + eps)
    return M


def maps(tilde, hc):
    """``(H_pre (T, n), H_post (T, n), H_res (T, n x n))`` float32 from
    ``tilde (T, >= n + n + n x n)``; ``H_res`` row-major, tokens on the
    lanes while it is made."""
    n, T = hc.n, tilde.shape[0]
    t = tilde[:, :n * (n + 2)].T                                # (24, T)
    M = jnp.exp(jnp.clip(t[2 * n:], hc.lo, hc.hi)).reshape(n, n, T)
    res = sinkhorn(M, hc.iters, hc.eps).reshape(n * n, T)
    return jax.nn.sigmoid(t[:n]).T, 2.0 * jax.nn.sigmoid(t[n:2 * n]).T, \
        res.T


# -------------------------------------------------- the stream, jax.numpy
def _streams(X, n):
    C = X.shape[-1] // n
    return [X[:, i * C:(i + 1) * C].astype(jnp.float32) for i in range(n)]


def collapse(X, hpre, n):
    """``u (T, C) = H_pre @ X`` in the stream's dtype."""
    xs = _streams(X, n)
    return sum(hpre[:, i:i + 1] * xs[i] for i in range(n)).astype(X.dtype)


def mix(X, y, hres, hpost, n):
    """``X' (T, n x C) = H_res @ X + outer(H_post, y)`` in the stream's
    dtype."""
    xs, yf = _streams(X, n), y.astype(jnp.float32)
    return jnp.concatenate([
        (hpost[:, i:i + 1] * yf + sum(
            hres[:, i * n + j:i * n + j + 1] * xs[j] for j in range(n))
         ).astype(X.dtype) for i in range(n)], -1)


# ------------------------------------------------------- a stack's calls
def _kernel():
    from .pallas import mhc_mix

    return mhc_mix if paged.kernels_on() else None


def _state(X, u, tilde, rows, hc):
    """What a call leaves for the next: the stream, the sublayer's input
    and the maps ``tilde`` gives for the sublayer's way back."""
    with jax.named_scope("mhc.maps"):
        _, hpost, hres = maps(tilde, hc)
    return {"X": X, "u": u[:rows], "hres": hres, "hpost": hpost,
            "rows": rows}


def _taken(X, m, hc):
    """The ``jax.numpy`` form of what mixer ``m`` takes from the stream
    ``X``."""
    with jax.named_scope("mhc.maps"):
        tilde = project(X, m, hc)
        hpre = jax.nn.sigmoid(tilde[:, :hc.n])
    with jax.named_scope("mhc.mix"):
        u = collapse(X, hpre, hc.n)
    return _state(X, u, tilde, X.shape[0], hc)


def enter(x, m, hc):
    """The stream of ``x (T, C)``, repeated ``n`` times, and what mixer
    ``m`` makes of it: ``{"X", "u", "hres", "hpost", "rows"}``. ``u``
    is the first sublayer's input ``(T, C)``."""
    k = _kernel()
    if k is not None:
        return _state(*k.mhc_enter(k.pad_rows(x), m, hc), x.shape[0], hc)
    with jax.named_scope("mhc.mix"):
        X = jnp.tile(x, (1, hc.n))
    return _taken(X, m, hc)


def step(s, y, m, hc):
    """The sublayer's output ``y (T, C)`` mixed into the stream by the maps
    ``s`` holds, and what the NEXT mixer ``m`` makes of the new stream."""
    k = _kernel()
    if k is not None:
        return _state(*k.mhc_mix(s["X"], k.pad_rows(y), s["hres"],
                                 s["hpost"], m, hc), s["rows"], hc)
    with jax.named_scope("mhc.mix"):
        X = mix(s["X"], y, s["hres"], s["hpost"], hc.n)
    return _taken(X, m, hc)


def leave(s, y, hc):
    """The last sublayer's output mixed in and the streams summed: ``h (T,
    C)``."""
    k = _kernel()
    if k is not None:
        return k.mhc_leave(s["X"], k.pad_rows(y), s["hres"], s["hpost"],
                           hc)[:s["rows"]]
    with jax.named_scope("mhc.mix"):
        X = mix(s["X"], y, s["hres"], s["hpost"], hc.n)
        return sum(_streams(X, hc.n)).astype(X.dtype)

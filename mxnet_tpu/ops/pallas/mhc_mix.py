"""The pass over a hyper-connected residual stream (``ops/hyper_connection
.py``) as Pallas kernels: a token tile's ``n`` streams are read ONCE, mixed
by the tile's own ``(n, n)`` maps with the sublayer's output, written ONCE,
and, while the new streams are in VMEM, the NEXT mixer's sum of squares,
its projection (one MXU product against ``phi``), its ``H_pre`` and the
next sublayer's input ``u' = H_pre @ X'`` are taken: ``10 x C`` numbers a
token move where the separate passes move two to four times that.

- ``mhc_enter`` (``%mhc_enter``): ``x (T, C)`` -> the stream ``X = [x] x
  n``, ``u``, ``tilde``;
- ``mhc_mix`` (``%mhc_mix``): ``X, y, H_res, H_post`` -> ``X'``, ``u'``,
  ``tilde'``;
- ``mhc_leave`` (``%mhc_leave``): ``X, y, H_res, H_post`` -> ``h = sum_i
  X'_i`` (the new stream is never written).

``tilde`` comes back ``(T, 128)`` float32 (the mixer's ``n + n + n x n``
columns, the rest zero); the caller makes ``H_post`` and the Sinkhorn
iteration of ``H_res`` from it in ``jax.numpy``. Every sum is float32; the
new stream is rounded to its dtype BEFORE anything is taken from it, as
the ``jax.numpy`` forms read it back rounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..hyper_connection import coefficients
from . import _use_interpret
from .page_walk import LANES, prec

__all__ = ["mhc_enter", "mhc_mix", "mhc_leave", "pad_rows", "tiles"]

_VMEM_LIMIT = 64 * 1024 * 1024
ROWS = 16               # token rows are padded to whole bfloat16 tiles


def pad_rows(x):
    """``x (T, ...)`` with zero rows up to a multiple of ``ROWS``."""
    pad = -x.shape[0] % ROWS
    return x if not pad else jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))


def tiles(T, C):
    """``(tokens, columns)`` a step: 64 tokens a grid step where they
    divide ``T`` (their streams in and out, twice buffered, and ``phi``
    stay under half the chip's VMEM), 512 columns a pass inside it (a pass's
    float32 values fit the register file's reach)."""
    tm = next((t for t in (64, 32, 16, 8) if T % t == 0), T)
    cw = next((c for c in (512, 256, 128) if C % c == 0), C)
    return tm, cw


def _kernel(*refs, mode, n, C, cw, eps):
    """One tile of ``tm`` tokens. ``mode`` ``enter``: ``x, phi, coef ->
    X, u, tilde``; ``mix``: ``X, y, maps, phi, coef -> X', u', tilde'``;
    ``leave``: ``X, y, maps -> h``. ``maps (tm, 128)`` float32 holds
    ``H_res`` row-major in columns ``[0, n x n)`` and ``H_post`` in the
    ``n`` after them; ``coef (2, 128)`` the next mixer's scale and bias a
    column."""
    if mode == "enter":
        x_ref, phi_ref, coef_ref, xo_ref, u_ref, t_ref = refs
    elif mode == "mix":
        x_ref, y_ref, m_ref, phi_ref, coef_ref, xo_ref, u_ref, t_ref = refs
    else:
        x_ref, y_ref, m_ref, h_ref = refs
    dtype = x_ref.dtype
    f32 = jnp.float32
    tm = x_ref.shape[0]
    if mode != "enter":
        m = m_ref[...]
        res = [[m[:, i * n + j:i * n + j + 1] for j in range(n)]
               for i in range(n)]
        post = [m[:, n * n + i:n * n + i + 1] for i in range(n)]

    def mixed(c):
        """The new streams' columns ``[c, c + cw)``, rounded."""
        if mode == "enter":
            return [x_ref[:, c:c + cw]] * n
        y = y_ref[:, c:c + cw].astype(f32)
        xs = [x_ref[:, j * C + c:j * C + c + cw].astype(f32)
              for j in range(n)]
        out = []
        for i in range(n):
            acc = post[i] * y
            for j in range(n):
                acc = acc + res[i][j] * xs[j]
            out.append(acc.astype(dtype))
        return out

    if mode == "leave":
        for c in range(0, C, cw):
            new = mixed(c)
            h = new[0].astype(f32)
            for i in range(1, n):
                h = h + new[i].astype(f32)
            h_ref[:, c:c + cw] = h.astype(dtype)
        return
    ss = jnp.zeros((tm, 1), f32)
    p = jnp.zeros((tm, LANES), f32)
    for c in range(0, C, cw):
        new = mixed(c)
        for i in range(n):
            xo_ref[:, i * C + c:i * C + c + cw] = new[i]
            v = new[i].astype(f32)
            ss = ss + jnp.sum(v * v, axis=1, keepdims=True)
            p = p + jnp.dot(new[i], phi_ref[i * C + c:i * C + c + cw, :],
                            preferred_element_type=f32,
                            precision=prec(dtype))
    r = jax.lax.rsqrt(ss / (n * C) + eps)
    tilde = p * r * coef_ref[0:1, :] + coef_ref[1:2, :]
    t_ref[...] = tilde
    pre = jax.nn.sigmoid(tilde)
    for c in range(0, C, cw):
        u = pre[:, 0:1] * xo_ref[:, c:c + cw].astype(f32)
        for i in range(1, n):
            u = u + pre[:, i:i + 1] * xo_ref[
                :, i * C + c:i * C + c + cw].astype(f32)
        u_ref[:, c:c + cw] = u.astype(dtype)


@functools.partial(jax.jit, static_argnames=("mode", "n", "eps", "interpret"))
def _call(args, mode, n, eps, interpret):
    x = args[0]
    T = x.shape[0]
    C = x.shape[1] if mode == "enter" else x.shape[1] // n
    tm, cw = tiles(T, C)

    def rows(width):
        return pl.BlockSpec((tm, width), lambda t: (t, 0))

    def whole(shape):
        return pl.BlockSpec(shape, lambda t: (0, 0))

    in_specs = [rows(x.shape[1])]
    if mode != "enter":
        in_specs += [rows(C), rows(LANES)]
    if mode != "leave":
        in_specs += [whole((n * C, LANES)), whole((2, LANES))]
        out_specs = [rows(n * C), rows(C), rows(LANES)]
        out_shape = [jax.ShapeDtypeStruct((T, n * C), x.dtype),
                     jax.ShapeDtypeStruct((T, C), x.dtype),
                     jax.ShapeDtypeStruct((T, LANES), jnp.float32)]
    else:
        out_specs = rows(C)
        out_shape = jax.ShapeDtypeStruct((T, C), x.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, mode=mode, n=n, C=C, cw=cw, eps=eps),
        grid=(T // tm,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="mhc_" + mode)(*args)


def _next(m, hc, dtype):
    """The next mixer as the kernel takes it: ``phi`` in whole lanes and
    the stream's dtype, scale and bias a column as two rows."""
    k = hc.n * (hc.n + 2)
    scale, bias = coefficients(m, hc.n)
    return (jnp.pad(m.phi.astype(dtype), ((0, 0), (0, LANES - k))),
            jnp.pad(jnp.stack([scale, bias]), ((0, 0), (0, LANES - k))))


def _maps(hres, hpost):
    m = jnp.concatenate([hres, hpost], -1).astype(jnp.float32)
    return jnp.pad(m, ((0, 0), (0, LANES - m.shape[1])))


def mhc_enter(x, m, hc):
    """``(X (T, n x C), u (T, C), tilde (T, 128))`` of ``x (T, C)``, ``T``
    a multiple of ``ROWS``."""
    return _call((x, *_next(m, hc, x.dtype)), mode="enter", n=hc.n,
                 eps=float(hc.eps), interpret=_use_interpret())


def mhc_mix(X, y, hres, hpost, m, hc):
    """``(X', u', tilde')``: ``X (T, n x C)`` mixed with ``y (T, C)`` by
    ``hres (T, n x n)`` and ``hpost (T, n)``, then what the next mixer ``m``
    takes from the new stream."""
    return _call((X, y, _maps(hres, hpost), *_next(m, hc, X.dtype)),
                 mode="mix", n=hc.n, eps=float(hc.eps),
                 interpret=_use_interpret())


def mhc_leave(X, y, hres, hpost, hc):
    """``h (T, C)``: the streams of the mixed ``X'`` summed."""
    return _call((X, y, _maps(hres, hpost)), mode="leave", n=hc.n,
                 eps=float(hc.eps), interpret=_use_interpret())

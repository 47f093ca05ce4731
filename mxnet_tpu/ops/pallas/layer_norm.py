"""Fused LayerNorm: Pallas TPU kernel, forward + backward.

Replaces the reference's ``src/operator/nn/layer_norm.cc`` hot path
[unverified]. Profiling the BERT step showed XLA's LayerNorm lowering
(convert_reduce / multiply_reduce fusions) running far below HBM bandwidth
— each (rows, C) tensor makes several passes for mean/var/normalize and
again for the three backward reductions. One Pallas kernel per direction
does a single pass: row statistics live in registers/VMEM, and the
gamma/beta gradients accumulate in-kernel into one (1, C) buffer that
every (sequential) grid step revisits.

Constraints: normalization over the LAST axis with C % 128 == 0 (TPU lane
tiling); anything else falls back to the jnp composition in ``ops/nn.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _partitionable, _use_interpret


def _fwd_kernel(x_ref, g_ref, b_ref, o_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)  # (R, C)
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = (xc * rstd * g + b).astype(o_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref, dx_ref, dg_ref,
                db_ref):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (x - mean) * rstd
    dyg = dy * g_ref[...].astype(jnp.float32)
    m1 = jnp.mean(dyg, axis=1, keepdims=True)
    m2 = jnp.mean(dyg * xhat, axis=1, keepdims=True)
    dx_ref[...] = ((dyg - m1 - xhat * m2) * rstd).astype(dx_ref.dtype)
    # gamma/beta grads: one (1, C) accumulator revisited by every grid
    # step — TPU grids run sequentially, so += is race-free
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dg_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


_BLOCK_ROWS = 256


def _pad_rows(x, block):
    pad = (-x.shape[0]) % block
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x, x.shape[0]


@functools.partial(jax.jit, static_argnames=("eps",))
def _ln_fwd_impl(x, gamma, beta, eps):
    N, C = x.shape
    xp, n = _pad_rows(x, _BLOCK_ROWS)
    Np = xp.shape[0]
    grid = (Np // _BLOCK_ROWS,)
    out, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_BLOCK_ROWS, C), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, C), x.dtype),
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
        ],
        interpret=_use_interpret(),
    )(xp, gamma.reshape(1, C), beta.reshape(1, C))
    return out[:n], mean[:n], rstd[:n]


@jax.jit
def _ln_bwd_impl(x, gamma, mean, rstd, dy):
    N, C = x.shape
    xp, n = _pad_rows(x, _BLOCK_ROWS)
    dyp, _ = _pad_rows(dy, _BLOCK_ROWS)
    meanp, _ = _pad_rows(mean, _BLOCK_ROWS)
    # rstd of zero-padded rows must stay finite; pad with ones
    pad = xp.shape[0] - N
    rstdp = jnp.pad(rstd, ((0, pad), (0, 0)), constant_values=1.0) \
        if pad else rstd
    Np = xp.shape[0]
    nb = Np // _BLOCK_ROWS
    dx, dg, db = pl.pallas_call(
        _bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, C), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_BLOCK_ROWS, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, C), x.dtype),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
        ],
        interpret=_use_interpret(),
    )(xp, gamma.reshape(1, C), meanp, rstdp, dyp)
    return dx[:n], dg.reshape(C), db.reshape(C)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm_fused(x, gamma, beta, eps=1e-5):
    """LayerNorm over the last axis of 2-D ``x`` (rows, C)."""
    out, _, _ = _ln_fwd_impl(x, gamma, beta, eps)
    return out


def _ln_fwd(x, gamma, beta, eps):
    out, mean, rstd = _ln_fwd_impl(x, gamma, beta, eps)
    return out, (x, gamma, mean, rstd)


def _ln_bwd(eps, res, dy):
    x, gamma, mean, rstd = res
    dx, dg, db = _ln_bwd_impl(x, gamma, mean, rstd, dy)
    return dx, dg.astype(gamma.dtype), db.astype(gamma.dtype)


layer_norm_fused.defvjp(_ln_fwd, _ln_bwd)


def supports(data, axis) -> bool:
    """Can the fused kernel serve this call?

    Bounds C so the backward's three (block_rows, C) f32 VMEM buffers fit
    the ~16 MB budget; wider norms fall back to the jnp path, and so does
    a trace under a multi-device mesh (``_partitionable``)."""
    C = data.shape[-1]
    return (axis in (-1, data.ndim - 1)) and C % 128 == 0 \
        and 128 <= C <= 4096 and data.ndim >= 2 and _partitionable()

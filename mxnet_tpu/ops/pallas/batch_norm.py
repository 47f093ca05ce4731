"""Fused BatchNorm reductions: Pallas TPU kernels (channel-last layout).

Replaces the stat passes of the reference's hand-written BN kernel
(``src/operator/nn/batch_norm.cu`` [unverified]) the TPU way. Profiling
on an earlier machine showed ResNet-50's BN reductions
running at XLA's HBM roofline with the *two-pass* centered statistics:
one full read of x for the mean, a second for the variance. The obvious
one-pass rewrite (E[x^2]-E[x]^2) was built and REVERTED in round 3 — it
cancels catastrophically whenever |mean| >> std, even with f32
accumulators.

These kernels get the one-pass traffic without the cancellation:

* ``bn_stats``      — ONE read of x. Blocks of the (M, C) channel-last
  view accumulate shifted partials sum(x-s) and sum((x-s)^2) in f32
  VMEM, where the per-channel shift ``s`` is the channel's first row (a
  single sample sits within ~std of the true mean, so
  var = E[(x-s)^2] - E[x-s]^2 only cancels O(1) bits, never the
  catastrophic mean^2/var ratio of the uncentered form).
* ``bn_bwd_reduce`` — ONE joint read of (x, dy) producing sum(dy) and
  sum(dy * xhat). The jnp backward relies on XLA multi-output fusion to
  merge those two reductions; the kernel makes the single pass a
  guarantee.

Layout matters more than the kernel: a first NCHW row-view attempt
measured 2x SLOWER end-to-end because Pallas operands take row-major
layout, and materializing an (N*C, L) view of what XLA keeps in its
internal (channel-minor) conv layout cost a full transpose + copy per
call. Channel-last input makes the (M, C) view genuinely free AND puts
C on the lane axis, so the row reduction never crosses lanes — which is
why ``supports()`` only accepts axis == ndim-1. Run BN-heavy models
with ``layout="NHWC"`` (the model zoo option) to engage it.

The normalize forward and the dx epilogue stay in jnp on purpose: they
are single-FMA elementwise passes XLA fuses into neighboring ops
(ReLU, residual adds), which a hand kernel would break.

Narrow layers (C < 128) would waste most of the 128-lane register; the
wrapper folds k = 128 // C rows into the lane axis (each lane column is
channel ``lane % C``) so conv1-era C=64 layers still run full-width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _use_interpret


def _scratch(shapes):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM(s, jnp.float32) for s in shapes]


_TARGET_ROWS = 1024  # rows per block: x block is TARGET_ROWS*C_LANES*4 bytes


def _row_tiles(M: int, C: int):
    lanes = min(512, ((C + 127) // 128) * 128)
    rows = max(8, min(_TARGET_ROWS, (1 << 18) // lanes))
    return rows, lanes


def _stats_kernel(x_ref, s1_ref, s2_ref, sh_ref, acc1, acc2, shift, *, M, C):
    i = pl.program_id(1)          # row-block sweep (inner grid dim)
    x = x_ref[...].astype(jnp.float32)
    rows, lanes = x.shape

    @pl.when(i == 0)
    def _init():
        shift[...] = x[0:1, :]
        acc1[...] = jnp.zeros_like(acc1)
        acc2[...] = jnp.zeros_like(acc2)

    ridx = i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
    cidx = pl.program_id(0) * lanes \
        + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    mask = (ridx < M) & (cidx < C)
    xs = jnp.where(mask, x - shift[...], 0.0)
    acc1[...] += jnp.sum(xs, axis=0, keepdims=True)
    acc2[...] += jnp.sum(xs * xs, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(1) - 1)
    def _flush():
        s1_ref[...] = acc1[...]
        s2_ref[...] = acc2[...]
        sh_ref[...] = shift[...]


@jax.jit
def _stats_call(x2d):
    M, C = x2d.shape
    rows, lanes = _row_tiles(M, C)
    nc = (C + lanes - 1) // lanes
    grid = (nc, (M + rows - 1) // rows)
    return pl.pallas_call(
        functools.partial(_stats_kernel, M=M, C=C),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, lanes), lambda c, i: (i, c))],
        out_specs=[pl.BlockSpec((1, lanes), lambda c, i: (0, c))] * 3,
        out_shape=[jax.ShapeDtypeStruct((1, nc * lanes), jnp.float32)] * 3,
        scratch_shapes=_scratch([(1, lanes)] * 3),
        interpret=_use_interpret(),
    )(x2d)


def _fold_narrow(M: int, C: int):
    """Fold k rows into lanes for narrow layers: (M, C) -> (M/k, k*C)."""
    if C >= 128 or 128 % C or C < 1:
        return 1
    k = 128 // C
    while k > 1 and M % k:
        k //= 2
    return k


def bn_stats(x2d):
    """Per-channel (mean, var) of channel-last x viewed as (M, C); f32.

    One HBM read of x; shifted one-pass partials per lane column,
    combined across the lane-folded copies in a tiny f32 epilogue."""
    M, C = x2d.shape
    k = _fold_narrow(M, C)
    xv = x2d.reshape(M // k, k * C)
    s1, s2, sh = _stats_call(xv)
    Cv = k * C
    s1, s2, sh = s1[0, :Cv], s2[0, :Cv], sh[0, :Cv]
    if k > 1:
        # each folded copy j covers rows j mod k: combine as k subgroups
        # of equal count via Chan's formula (all on (k, C)-sized arrays)
        n_g = M // k
        s1, s2, sh = (a.reshape(k, C) for a in (s1, s2, sh))
        mean_g = sh + s1 / n_g
        m2_g = s2 - s1 * s1 / n_g
        mean = jnp.mean(mean_g, axis=0)
        m2 = jnp.sum(m2_g, axis=0) + n_g * jnp.sum(
            jnp.square(mean_g - mean[None, :]), axis=0)
        return mean, m2 / M
    mean = sh + s1 / M
    var = s2 / M - jnp.square(s1 / M)
    return mean, var


def _bwd_kernel(x_ref, dy_ref, mi_ref, sd_ref, sdx_ref, acc1, acc2, *, M, C):
    i = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    rows, lanes = x.shape

    @pl.when(i == 0)
    def _init():
        acc1[...] = jnp.zeros_like(acc1)
        acc2[...] = jnp.zeros_like(acc2)

    ridx = i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
    cidx = pl.program_id(0) * lanes \
        + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    mask = (ridx < M) & (cidx < C)
    mean = mi_ref[0:1, :]
    inv = mi_ref[1:2, :]
    # mask BEFORE the product: padded lanes of x/mi hold garbage and
    # 0 * NaN would poison the accumulator
    xhat = jnp.where(mask, (x - mean) * inv, 0.0)
    dym = jnp.where(mask, dy, 0.0)
    acc1[...] += jnp.sum(dym, axis=0, keepdims=True)
    acc2[...] += jnp.sum(dym * xhat, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(1) - 1)
    def _flush():
        sd_ref[...] = acc1[...]
        sdx_ref[...] = acc2[...]


@jax.jit
def _bwd_call(x2d, dy2d, mi):
    M, C = x2d.shape
    rows, lanes = _row_tiles(M, C)
    nc = (C + lanes - 1) // lanes
    grid = (nc, (M + rows - 1) // rows)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, M=M, C=C),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, lanes), lambda c, i: (i, c)),
            pl.BlockSpec((rows, lanes), lambda c, i: (i, c)),
            pl.BlockSpec((2, lanes), lambda c, i: (0, c)),
        ],
        out_specs=[pl.BlockSpec((1, lanes), lambda c, i: (0, c))] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, nc * lanes), jnp.float32)] * 2,
        scratch_shapes=_scratch([(1, lanes)] * 2),
        interpret=_use_interpret(),
    )(x2d, dy2d, mi)


def bn_bwd_reduce(x2d, dy2d, mean, inv):
    """(sum dy, sum dy*xhat) per channel in ONE read of (x, dy);
    channel-last (M, C) views, f32 outputs."""
    M, C = x2d.shape
    k = _fold_narrow(M, C)
    Cv = k * C
    mi = jnp.stack([jnp.tile(mean, k), jnp.tile(inv, k)])  # (2, k*C)
    sd, sdx = _bwd_call(
        x2d.reshape(M // k, Cv), dy2d.reshape(M // k, Cv), mi)
    sd, sdx = sd[0, :Cv], sdx[0, :Cv]
    if k > 1:
        sd = jnp.sum(sd.reshape(k, C), axis=0)
        sdx = jnp.sum(sdx.reshape(k, C), axis=0)
    return sd, sdx


def supports(x, axis) -> bool:
    """Channel-last BN only: the (M, C) view must be layout-free (see
    module docstring for why NCHW goes through the jnp path)."""
    if x.ndim < 2 or axis not in (-1, x.ndim - 1):
        return False
    C = x.shape[-1]
    M = 1
    for d in x.shape[:-1]:
        M *= d
    return M >= 2 and C >= 1

"""Paged flash attention: Pallas kernels that read the serving stack's
``(num_pages, page_size, H, D)`` KV pools IN PLACE.

The continuous batcher's decode path (PR 8) gathers K/V through the page
table into a materialized ``(B, P*page_size, H, D)`` view and then runs
dense attention over it — two full copies of every cached key/value per
decoded token, plus an O(L) score row in HBM. These kernels close that
gap (the FlashAttention/PagedAttention fusion, ROADMAP item 2): the page
table rides the grid as a scalar-prefetch operand, a row's pages are
copied directly out of the pool (a page no query of the row sees is not
fetched), and an online-softmax carry in VMEM scratch accumulates across
the row's blocks — the gather never materializes and scores never leave
VMEM.

Two kernels, two entry points:

- ``paged_window_attention`` — an S-token query window per row, each
  query ``i`` at absolute position ``q_offset[b] + i`` (causal within
  and across the window), through ``_window_kernel``. Grid ``(B, key
  blocks)``; page ``t`` of row ``b``'s block ``j`` is pool block
  ``page_table[b, j * pages + t]``, each page an operand of its own that
  the pipeline copies, as many pages a block as ``_window_tiles`` gives
  for the shapes. A page is taken as its ``(key, head)`` rows, the pool's
  own order, and the rows of every head meet all of them in one product
  whose foreign columns are masked: no relayout, one softmax carry a
  block. This is the q_offset-aware PREFILL variant: suffix-only
  prefix-cache replay, a prompt's chunks and speculative verification all
  score a window against a long paged history in one pass.
- ``paged_decode_attention`` — single query token per row (the decode
  hot path), or the grouped query heads of one token. Heads of whole
  lanes (``D`` a multiple of 128: zaya, ouro) go through
  ``_decode_kernel`` (PR 41), the form ``mla_attention.py`` and
  ``dsa_decode.py`` have: grid ``(B,)``, the pools left whole in HBM, a
  row's LIVE pages walked in blocks by the kernel's own copies, two
  buffers deep (``page_walk.walk_live_pages``), the same product and
  carry. Narrower heads (granite's, transformer-big's and the smoke run's
  of 64) take the window's form at ``S = 1`` with ``q_offset = pos``, the
  group on the window axis.

Both take the pool in one of three declarations, the same numbers in the
same order, told apart by their shapes (``_page_size``, ``_lanes_packed``):
``(num_pages, page_size, Hkv, D)``; ``(num_pages, page_size x Hkv, D)``
with ``kv_heads=`` saying ``Hkv``, as the kernels above read heads of whole
lanes (ZAYA's two heads of 128, PR 40); and ``(num_pages, page_size, Hkv x
D)``, a page's positions on the rows and (head, d) on the lanes, which is
how heads NARROWER than the lanes are declared since PR 46: a last axis of
64 stands on 128 lanes at twice the bytes, XLA therefore kept such a pool
in a layout of its own and copied it to the kernels' and back around every
burst, and Mosaic copies no page out of it; ``heads x 64`` is whole lanes,
the pool lies row-major as declared and ``_lane_window_kernel`` /
``_lane_selected_window_kernel`` read its pages as they lie: the query rows
stand on their own head's lanes with zeros beside them, so one product
with the page, contracted over the lanes, gives each row its own head's
scores. Both entry points take grouped query heads: a window of ``S``
positions by ``G`` heads a key/value head rides the window kernel's window
axis whole.

- ``paged_selected_window_attention`` — the window over a SELECTED set
  of cached positions (learned sparse attention), for grouped-query
  heads: ``Hq`` query heads over ``Hkv`` key/value heads. A mask ``(B, C,
  L)`` says which cached positions each query reads; it rides the grid
  in blocks beside the pages. Grid ``(B, query blocks, key blocks)``: a
  step takes a block of several pages (1,024 keys), relays it head-major
  once, gives each key/value head one product with all the query rows
  that read it and updates the softmax carry once; blocks past the last
  one a query block can see are neither fetched nor computed. Pools ``(
  num_pages, page, Hkv, D)`` (keye, ouro: heads of 128), or ``(num_pages,
  page, Hkv x D)`` at narrower heads (granite's chunk under a causal
  mask), where nothing is relaid and two heads of 64 share a product.

All keep ``MXTPU_FLASH_INTERPRET`` (force/forbid/auto, shared with
``flash_attention.py``) and ship a dense jnp reference
(``*_reference``) used by the tolerance tests. Whether a caller comes here
at all is ``ops/paged.kernels_on()``'s to say (a TPU, no multi-device
mesh); where it says no, the callers' own ``jax.numpy`` forms stand
(``ops/paged.py``, ``ops/sparse_attention.py``, the attention layer's
gather + dense path, which stays bitwise-unchanged).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _use_interpret
from .flash_attention import NEG_INF
from .page_walk import (LANES, init_carry, nt, prec, softmax_step,
                        walk_live_pages)

__all__ = ["paged_decode_attention", "paged_window_attention",
           "paged_decode_reference", "paged_window_reference",
           "paged_selected_window_attention",
           "paged_selected_window_reference"]


# The window kernel's two sizes, in bytes of one pool. A grid step takes
# as many whole pages as make _WINDOW_STEP_BYTES, at most the row's, and
# computes them in blocks of _WINDOW_BLOCK_BYTES, each under its own
# ``pl.when``: a block past the row's position costs nothing, a longer
# block computes more keys no query sees, a shorter one pays more softmax
# carries. On a v5e (PERF.md, PR 32; kernel alone, microseconds a call):
# granite's call (64 rows of 12 pages of 128 keys, positions about 600)
# with a row in one step 336 / 313 / 308 at blocks of 128 / 256 / 512 KiB
# and 375 to 465 with a row in two or three steps (the pipeline looks one
# step ahead, so a row's first pages are waited for); transformer-big's
# (128 rows of 16 pages of 16 keys) at positions 0 to 40 407 / 404 / 422 /
# 456 at blocks of 64 / 128 / 256 / 512 KiB, every row full 762 / 581 /
# 499 / 456. What is left there is the pipeline's own work on 2 x 16 page
# operands a row, about 3 us, however they are spread over grid steps (7.5
# us a row at zaya's 2 x 32: PR 40). Since PR 41 the decode step at heads
# of 128 walks its live pages (``_decode_kernel``, below, with its own
# numbers) and this form serves the windows of ``S > 1`` (zaya's chunks)
# and whatever pool of narrower heads is still declared in four axes; since
# PR 46 granite, transformer-big and the smoke run declare theirs ``(pages,
# page, heads x 64)`` and run ``_lane_window_kernel`` on the same two sizes
# (the numbers above are the four-axis form's, on the padded page)
_WINDOW_STEP_BYTES = 2 * 1024 * 1024
_WINDOW_BLOCK_BYTES = 128 * 1024
_WINDOW_STEP_VMEM_LIMIT = 32 * 1024 * 1024


def _window_tiles(P, page_size, Hkv, D, itemsize):
    """``(pages a grid step, pages a block)`` of the window kernel, from
    the shapes alone: granite's row of 12 pages of 128 keys is one step of
    12 blocks of a page, transformer-big's row of 16 pages of 16 keys one
    step of 4 blocks of 4; a row longer than ``_WINDOW_STEP_BYTES`` takes
    several steps."""
    page_bytes = page_size * Hkv * D * itemsize
    pages = max(1, min(P, _WINDOW_STEP_BYTES // page_bytes))
    return pages, max(1, min(pages, _WINDOW_BLOCK_BYTES // page_bytes))


def _window_vmem_bytes(pages, block, page_size, Hkv, S, D, itemsize):
    """VMEM a grid step of the window kernel holds: the pipeline's two
    buffers of every operand (a row of ``D`` elements takes whole lanes),
    the scratch, one block of pages joined, and its scores with their
    exponentials (float32) and their cast for the second product."""
    rows, cols = Hkv * S, block * page_size * Hkv
    wide = -(-D // LANES) * LANES
    operands = 2 * (2 * rows + 2 * pages * page_size * Hkv) * wide * itemsize
    scratch = rows * (2 * LANES + wide) * 4
    live = 2 * cols * wide * itemsize + rows * cols * (4 + 4 + itemsize)
    return operands + scratch + live


def _window_kernel(pt_ref, off_ref, vl_ref, q_ref, *refs, page_size, pages,
                   block, sm_scale, window, shared_position=False, group=1):
    """Grid (B, steps), steps sequential per row: ``pages`` pool pages a
    step (each its own operand, so the pipeline copies them through the
    page table), taken in blocks of ``block`` pages; online-softmax carry
    (m, l, acc) in VMEM scratch, updated once a block. A page arrives as
    ``(page_size * Hkv, D)``, one row a (key, head), which is how the
    pool lies in HBM: nothing is relaid. The ``Hkv * S`` query rows (head
    ``r // S``, query ``r % S``) meet every row of the block in ONE
    product, and a query row keeps the columns of its own head: what it
    gives the others is masked like a key past its position, weighs
    ``exp(-inf) = 0`` in the second product and so never reaches the
    accumulator. Query ``i`` sits at absolute position ``off + i`` and
    masks keys above it; queries ``>= vl`` are padding and finalize to
    zero. With ``shared_position`` every query of the window sits at
    ``off`` (the grouped query heads of ONE token); with ``group`` the
    window is that many grouped query heads by ``window // group``
    positions (rows ``g * positions + i`` of a key/value head), query
    ``i`` of each at ``off + i``."""
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b, j = pl.program_id(0), pl.program_id(1)
    rows = q_ref.shape[1]
    Hkv = rows // window
    lw = l_ref.shape[-1]
    span = window // group          # positions the window holds

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off = off_ref[b]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    q_abs = off if shared_position else off + row % span
    # the window's LAST query bounds what any query can see: a block
    # wholly past it contributes nothing (the row's first block is never
    # skipped, so l is never all-zero for a live row)
    last = off + (0 if shared_position else span - 1)

    def accumulate(k_blk, v_blk, first_key):
        def joined(page_refs):
            if len(page_refs) == 1:
                return page_refs[0][0]
            return jnp.concatenate([r[0] for r in page_refs], axis=0)

        k, v = joined(k_blk), joined(v_blk)        # (cols, D)
        cols = k.shape[0]
        # operands go to the MXU as the pools hold them; a process-wide
        # "highest" precision is not one Mosaic takes for bfloat16
        prec = (jax.lax.Precision.DEFAULT if k.dtype == jnp.bfloat16
                else None)
        s = jax.lax.dot_general(
            q_ref[0].astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec) * sm_scale             # (rows, cols)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        s = jnp.where(jnp.logical_and(col % Hkv == row // window,
                                      first_key + col // Hkv <= q_abs),
                      s, NEG_INF)
        m_prev = m_ref[...]                        # (rows, LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p_act = jnp.exp(s - m_new[:, :1])
        # the denominator stays a sum a lane until the last block:
        # adding lane groups is elementwise, a sum a row is not
        l_new = alpha[:, :lw] * l_ref[...]
        for c in range(cols // lw):
            l_new = l_new + p_act[:, c * lw:(c + 1) * lw]
        l_ref[...] = l_new
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p_act.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)                        # (rows, D)
        m_ref[...] = m_new

    for lo in range(0, pages, block):
        hi = min(pages, lo + block)
        first_key = (j * pages + lo) * page_size
        pl.when(first_key <= last)(functools.partial(
            accumulate, k_refs[lo:hi], v_refs[lo:hi], first_key))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(jnp.sum(l_ref[...], axis=1, keepdims=True), 1e-30)
        o_ref[0] = jnp.where(row % span < vl_ref[b], acc_ref[...] / l,
                             0.0).astype(o_ref.dtype)


def _window_page(t, ps, P, pages, extra, one_step=False):
    """The index map of page operand ``t`` of the window kernels: page
    ``t`` of step ``j``, if the row's last query (``extra`` positions past
    its offset) sees it; else the page this operand held the step before,
    so that nothing is copied, and in step 0 pool page 0 (masked whatever
    it holds), which the next short row then finds in place too.
    ``one_step`` says the row is one grid step (``P <= pages``): the step
    is then 0 by construction, and leaving its arithmetic out takes a
    division by ``pages`` off every operand's map, 750 of the 3,300
    bundles a row of granite's call spends before its first product (the
    lane forms say so; the four-axis form keeps the program it had)."""
    def index(b, j, pt, off, vl):
        last = jnp.clip((off[b] + extra) // ps, 0, P - 1)
        if one_step:
            return (jnp.where(t <= last, pt[b, t], 0), 0, 0)
        jj = jnp.minimum(j, jnp.maximum(last - t, 0) // pages)
        return (jnp.where(t <= last, pt[b, jj * pages + t], 0), 0, 0)
    return index


# jitted so that one trace serves every layer and every program of a
# shape: the page operands' index maps are traced one by one, which cost
# transformer-big's warm-up (25 programs of six layers) 4 s of ``setup_s``
@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "shared_position", "pages", "block", "interpret", "group"))
def _paged_window_impl(q, k_pool, v_pool, page_table, q_offset, window_vl,
                       sm_scale, shared_position, pages, block, interpret,
                       group=1):
    B, S, H, D = q.shape
    N, ps = k_pool.shape[0], _page_size(k_pool, H, D)
    P = page_table.shape[1]
    # query rows (head, query); a page as its (key, head) rows: with 8 or
    # 16 heads of bfloat16 the pool's tiles in HBM are these rows already
    rows = jnp.swapaxes(q, 1, 2).reshape(B, H * S, D)
    extra = 0 if shared_position else S // group - 1
    pool_specs = [pl.BlockSpec((1, ps * H, D),
                               _window_page(t, ps, P, pages, extra))
                  for t in range(pages)]
    row_spec = pl.BlockSpec((1, H * S, D),
                            lambda b, j, pt, off, vl: (b, 0, 0))
    kernel = functools.partial(_window_kernel, page_size=ps, pages=pages,
                               block=block, sm_scale=sm_scale, window=S,
                               shared_position=shared_position, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, pl.cdiv(P, pages)),
        in_specs=[row_spec] + pool_specs + pool_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((H * S, LANES), jnp.float32),
            pltpu.VMEM((H * S, math.gcd(ps * H, LANES)), jnp.float32),
            pltpu.VMEM((H * S, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H * S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_WINDOW_STEP_VMEM_LIMIT),
        interpret=interpret,
        name="paged_window",
    )(page_table.astype(jnp.int32), q_offset.astype(jnp.int32),
      window_vl.astype(jnp.int32), rows,
      *([k_pool.reshape(N, ps * H, D)] * pages),
      *([v_pool.reshape(N, ps * H, D)] * pages))
    return jnp.swapaxes(out.reshape(B, H, S, D), 1, 2)   # (B, S, H, D)


# --------------------------- heads narrower than the lanes: the page as
# ``(positions, heads x D)``
# A pool of heads of 64 declared ``(num_pages, page, Hkv, 64)`` stands a
# head on half a lane row: XLA keeps such a parameter in a layout of its
# own and copied every pool to these kernels' layout before a burst's
# ``%while`` and back after it (granite: 16 copies of 101 MB a burst,
# PERF.md 7 (u)). Declared ``(num_pages, page, Hkv x D)`` a page is its
# positions on the rows and (head, d) on whole lanes, row-major as it
# stands, and the forms below read it so. Rows a product of the window may
# have before the heads are taken a few at a time:
_LANE_ROWS = 256


def _lanes_packed(pool, D):
    """Is ``pool`` declared ``(num_pages, page, heads x D)``, a page's
    (head, d) on the lanes? Told from the shapes alone: the other 3-D
    declaration, ``(num_pages, page x heads, D)``, ends in ``D`` (with one
    head the two are the same array)."""
    return pool.ndim == 3 and pool.shape[2] != D


def _lane_heads(Hkv, D, rows_a_head=None):
    """Key/value heads a product of the lane forms takes: as few as fill
    whole lanes (two heads of 64), so that the zero lanes cost the MXU no
    more than a head of 64 costs it anyway; or, where ``rows_a_head`` is
    given and all the heads' query rows are at most ``_LANE_ROWS`` (a
    decode step, a verification window), all of them: one product, one
    softmax carry."""
    if rows_a_head is not None and Hkv * rows_a_head <= _LANE_ROWS:
        return Hkv
    return math.gcd(Hkv, max(1, LANES // D))


def _own_lanes(x):
    """``x (..., hp, n, D)`` as ``(..., hp x n, hp x D)``: row ``(e, i)``
    holds ``x[e, i]`` on head ``e``'s ``D`` lanes and zeros on the others,
    so that its product with a page ``(keys, hp x D)``, contracted over the
    lanes, is head ``e``'s scores alone."""
    *lead, hp, n, D = x.shape
    own = jnp.eye(hp, dtype=bool)[:, None, :, None]
    return jnp.where(own, x[..., None, :], 0).reshape(*lead, hp * n, hp * D)


def _lane_window_tiles(P, page_size, Hkv, D, itemsize):
    """``_window_tiles`` for a page ``(positions, heads x D)``: a block's
    scores are ``(rows, keys)``, so it takes at least 128 keys where the
    step has them (transformer-big's pages of 16 go 8 a block)."""
    pages, block = _window_tiles(P, page_size, Hkv, D, itemsize)
    return pages, min(pages, max(block, -(-LANES // page_size)))


def _lane_window_kernel(pt_ref, off_ref, vl_ref, q_ref, *refs, page_size,
                        pages, block, sm_scale, window, heads,
                        shared_position=False, group=1):
    """``_window_kernel`` over pages ``(page_size, Hkv x D)``: the grid,
    the page operands, the blocks under their ``pl.when`` and the carry are
    its own. The ``Hkv * window`` query rows (head ``r // window``) come
    ``heads`` key/value heads at a time, each row with its query on its own
    head's ``D`` lanes of the ``heads x D`` and zeros beside it
    (``_own_lanes``): ONE product with those lanes of the block,
    contracted over the lanes, gives ``(rows, keys)`` scores that are the
    row's own head's, with no foreign column to mask; the second product
    gives ``(rows, heads x D)`` of which a row keeps its head's lanes at
    the end."""
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b, j = pl.program_id(0), pl.program_id(1)
    rows, width = q_ref.shape[1], q_ref.shape[2]
    D = o_ref.shape[2]
    per = heads * window                     # rows a product takes
    span = window // group                   # positions the window holds

    @pl.when(j == 0)
    def _init():
        init_carry(m_ref, l_ref, acc_ref)

    off = off_ref[b]
    row = jax.lax.broadcasted_iota(jnp.int32, (per, 1), 0)
    q_abs = off if shared_position else off + row % span
    last = off + (0 if shared_position else span - 1)

    def accumulate(k_blk, v_blk, first_key):
        keys = len(k_blk) * page_size
        seen = first_key + jax.lax.broadcasted_iota(
            jnp.int32, (1, keys), 1) <= q_abs
        for p in range(rows // per):
            def joined(page_refs):
                got = [r[0, :, p * width:(p + 1) * width] for r in page_refs]
                return got[0] if len(got) == 1 else jnp.concatenate(got, 0)

            at = pl.ds(p * per, per)
            s = nt(q_ref[0, at, :], joined(k_blk)) * sm_scale
            softmax_step(jnp.where(seen, s, NEG_INF), joined(v_blk),
                         m_ref.at[at], l_ref.at[at], acc_ref.at[at])

    for lo in range(0, pages, block):
        hi = min(pages, lo + block)
        first_key = (j * pages + lo) * page_size
        pl.when(first_key <= last)(functools.partial(
            accumulate, k_refs[lo:hi], v_refs[lo:hi], first_key))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        every = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        l = jnp.maximum(jnp.sum(l_ref[...], axis=1, keepdims=True), 1e-30)
        out = jnp.where(every % span < vl_ref[b], acc_ref[...] / l,
                        0.0).astype(o_ref.dtype)
        for h in range(rows // window):
            e = h % heads
            o_ref[0, h * window:(h + 1) * window, :] = \
                out[h * window:(h + 1) * window, e * D:(e + 1) * D]


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "shared_position", "pages", "block", "heads", "interpret",
    "group"))
def _lane_window_impl(q, k_pool, v_pool, page_table, q_offset, window_vl,
                      sm_scale, shared_position, pages, block, heads,
                      interpret, group=1):
    B, S, H, D = q.shape
    ps, P = k_pool.shape[1], page_table.shape[1]
    width = heads * D
    rows = _own_lanes(jnp.swapaxes(q, 1, 2).reshape(
        B, H // heads, heads, S, D)).reshape(B, H * S, width)
    extra = 0 if shared_position else S // group - 1
    pool_specs = [pl.BlockSpec((1, ps, H * D),
                               _window_page(t, ps, P, pages, extra,
                                            one_step=P <= pages))
                  for t in range(pages)]

    def row_spec(last):
        return pl.BlockSpec((1, H * S, last),
                            lambda b, j, pt, off, vl: (b, 0, 0))

    kernel = functools.partial(
        _lane_window_kernel, page_size=ps, pages=pages, block=block,
        sm_scale=sm_scale, window=S, heads=heads,
        shared_position=shared_position, group=group)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, pl.cdiv(P, pages)),
            in_specs=[row_spec(width)] + pool_specs + pool_specs,
            out_specs=row_spec(D),
            scratch_shapes=[
                pltpu.VMEM((H * S, LANES), jnp.float32),
                pltpu.VMEM((H * S, math.gcd(
                    ps * math.gcd(block, pages), LANES)), jnp.float32),
                pltpu.VMEM((H * S, width), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H * S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_WINDOW_STEP_VMEM_LIMIT),
        interpret=interpret,
        name="paged_window",
    )(page_table.astype(jnp.int32), q_offset.astype(jnp.int32),
      window_vl.astype(jnp.int32), rows, *([k_pool] * pages),
      *([v_pool] * pages))
    return jnp.swapaxes(out.reshape(B, H, S, D), 1, 2)   # (B, S, H, D)


def _page_size(pool, heads, D):
    """Positions a page of ``pool`` holds: its second axis, or, where the
    pool is declared as the kernel of heads of whole lanes reads it,
    ``(num_pages, page x heads, D)`` (a page's (key, head) rows on ONE
    axis: two heads of 128 are then whole ``(16, 128)`` tiles on the chip,
    where an axis of 2 heads would be padded to 16 rows, eight times the
    bytes), that axis over the heads."""
    if pool.ndim == 4 or _lanes_packed(pool, D):
        return pool.shape[1]
    return pool.shape[1] // heads


def paged_window_attention(q, k_pool, v_pool, page_table, q_offset,
                           window_vl=None, *, sm_scale,
                           shared_position=False, kv_heads=None):
    """S-token query window over a paged history, pools read in place.

    q ``(B, S, H, D)``; query ``i`` of row ``b`` sits at absolute
    position ``q_offset[b] + i`` and attends keys at positions ``<=``
    it (causal across the cached history AND within the window — the
    caller has already scattered the window's K/V into the pool).
    ``window_vl`` ``(B,)`` optionally marks queries ``>= window_vl[b]``
    as padding (their outputs are zeroed). ``shared_position`` puts every
    query of the window at ``q_offset[b]`` (``paged_decode_attention``'s
    grouped query heads). Pools are ``(num_pages, page, Hkv, D)``,
    ``(num_pages, page x Hkv, D)`` or ``(num_pages, page, Hkv x D)``
    (``_page_size``; the last runs ``_lane_window_kernel``); ``kv_heads`` says
    ``Hkv`` where ``H`` is ``G`` times it (grouped-query heads: query
    head ``i`` reads key/value head ``i // G``): the ``G`` heads of a
    group then ride the kernel's window axis beside the ``S`` positions,
    one softmax carry for the ``G x S`` rows of a key/value head. Returns
    ``(B, S, H, D)``."""
    B, S, H, D = q.shape
    lanes = _lanes_packed(k_pool, D)
    Hkv = (k_pool.shape[2] // D if lanes else H) if kv_heads is None \
        else int(kv_heads)
    G = H // Hkv
    if window_vl is None:
        window_vl = jnp.full((B,), S, jnp.int32)
    tiles = _lane_window_tiles if lanes else _window_tiles
    pages, block = tiles(
        page_table.shape[1], _page_size(k_pool, Hkv, D), Hkv, D,
        k_pool.dtype.itemsize)
    if G > 1:
        # (B, G x S, Hkv, D): window row g * S + i is head g of its group
        # at position i
        q = jnp.transpose(q.reshape(B, S, Hkv, G, D),
                          (0, 3, 1, 2, 4)).reshape(B, G * S, Hkv, D)
    impl, more = _paged_window_impl, {}
    if lanes:
        impl, more = _lane_window_impl, {"heads": _lane_heads(Hkv, D, G * S)}
    out = impl(
        q, k_pool, v_pool, page_table, q_offset, window_vl,
        sm_scale=float(sm_scale), shared_position=bool(shared_position),
        pages=pages, block=block, interpret=_use_interpret(), group=G,
        **more)
    if G > 1:
        out = jnp.transpose(out.reshape(B, G, S, Hkv, D),
                            (0, 2, 3, 1, 4)).reshape(B, S, H, D)
    return out


# ------------------------------------------------- decode (one position)
# The decode kernel's sizes. A block is one buffer of its scratch and the
# grain of its copies: as many whole pages as hold _DECODE_BLOCK_KEYS keys
# or _DECODE_BLOCK_BYTES of one pool, whichever is fewer, and never more
# than a row's table has. A step is what one softmax carry takes (one
# product with the keys, one carry, one product with the values): the pages
# of a block that make _DECODE_STEP_BYTES of one pool. On a v5e (PERF.md,
# PR 41; the kernel alone, 30 calls in one program, microseconds a call):
# zaya's call (64 rows at 1,265 positions, 87 MB of live pages of 64 KiB,
# 107 at 819 GB/s) with blocks of 8 pages in steps of 1 / 2 / 4 / 8 pages
# 339 / 229 / 185 / 172, blocks of 16 in steps of 4 / 8 189 / 179, blocks
# of 4 in one step 184, where the pipeline's form (``_window_kernel``,
# 2 x 32 page operands a row) takes 502; ouro's (10 rows at 213 positions,
# 24 MB of pages of 512 KiB, 29 at the peak) 58 to 62 at blocks of 1, 2 or
# 4 pages in steps of 1 or 2 against 70
_DECODE_BLOCK_KEYS = 1024
_DECODE_BLOCK_BYTES = 1024 * 1024
_DECODE_STEP_BYTES = 512 * 1024
_DECODE_VMEM_LIMIT = 32 * 1024 * 1024


def _decode_tiles(P, page_size, Hkv, D, itemsize):
    """``(pages a block, pages a softmax step)`` of the decode kernel, from
    the shapes alone: zaya's pages of 64 KiB (128 keys of 2 heads of 128)
    go 8 a block in one step, ouro's of 512 KiB (16 heads) 2 in steps of
    1."""
    page_bytes = page_size * Hkv * D * itemsize
    block = max(1, min(P, _DECODE_BLOCK_KEYS // page_size,
                       _DECODE_BLOCK_BYTES // page_bytes))
    return block, math.gcd(block, max(1, _DECODE_STEP_BYTES // page_bytes))


def _decode_kernel(pt_ref, pos_ref, q_ref, k_pool_ref, v_pool_ref, o_ref,
                   k_buf, v_buf, sem_ref, slot_ref, m_ref, l_ref, acc_ref, *,
                   block, step, group, sm_scale):
    """Grid (B,), a row a step, sequential: the scratch, its semaphores and
    the slot carry over from row to row. ``q_ref (1, H, D)``, query head
    ``r`` reading key/value head ``r // group``, all at ``pos``; a pool
    ``(num_pages, page x Hkv, D)`` whole in HBM, a page as its (key, head)
    rows; ``k_buf``, ``v_buf (2, block x page x Hkv, D)``. A row's live
    pages (``pos // page + 1``; none below 0: the row hands back zeros) are
    walked in blocks, each page one copy for K and one for V to its row
    offset of a buffer, a dead page neither copied nor computed. The
    arithmetic is ``_window_kernel``'s: the ``H`` query rows meet every row
    of a step's pages in ONE product, foreign-head columns and keys past
    ``pos`` masked, scores and the (m, l, acc) carry in float32."""
    b = pl.program_id(0)
    P = pt_ref.shape[1]
    rows = q_ref.shape[1]
    cols = k_pool_ref.shape[1]               # (key, head) rows a page
    Hkv = rows // group
    ps = cols // Hkv
    pos = pos_ref[b]

    def live_pages(r):
        return jnp.clip(pos_ref[r] // ps + 1, 0, P)

    def page_copies(page, slot, t):
        return [pltpu.make_async_copy(
            pool.at[page], buf.at[slot, pl.ds(t * cols, cols)],
            sem_ref.at[slot]) for pool, buf in ((k_pool_ref, k_buf),
                                                (v_pool_ref, v_buf))]

    width = step * cols
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    own = col % Hkv == \
        jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0) // group
    key = col // Hkv                         # a column's key in its step

    def attend(k, slot):
        n = live_pages(b) - k * block        # pages of this block that live
        for t in range(0, block, step):
            @pl.when(t < n)
            def _step():
                # the step's pages past the row's last hold what an earlier
                # row left there: masked keys weigh 0, and 0 x NaN is NaN
                for u in range(t + 1, t + step):
                    @pl.when(u >= n)
                    def _dead():
                        v_buf[slot, pl.ds(u * cols, cols), :] = jnp.zeros(
                            (cols, v_buf.shape[2]), v_buf.dtype)
                kt = k_buf[slot, pl.ds(t * cols, width), :]
                s = jax.lax.dot_general(
                    q_ref[0].astype(kt.dtype), kt, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=prec(kt.dtype)) * sm_scale     # (rows, width)
                seen = key <= pos - (k * block + t) * ps
                s = jnp.where(jnp.logical_and(own, seen), s, NEG_INF)
                softmax_step(s, v_buf[slot, pl.ds(t * cols, width), :],
                             m_ref, l_ref, acc_ref)

    init_carry(m_ref, l_ref, acc_ref)
    walk_live_pages(pt_ref, slot_ref, live_pages, block, page_copies,
                    attend)
    l = jnp.maximum(jnp.sum(l_ref[...], axis=1, keepdims=True), 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "kv_heads", "block", "step", "interpret"))
def _paged_decode_impl(q, k_pool, v_pool, page_table, pos, sm_scale,
                       kv_heads, block, step, interpret):
    B, H, D = q.shape
    N, cols = k_pool.shape[0], \
        _page_size(k_pool, kv_heads, D) * kv_heads
    kernel = functools.partial(_decode_kernel, block=block, step=step,
                               group=H // kv_heads, sm_scale=sm_scale)
    row = pl.BlockSpec((1, H, D), lambda b, pt, at: (b, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[row, pool, pool],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, block * cols, D), k_pool.dtype),
                pltpu.VMEM((2, block * cols, D), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((H, LANES), jnp.float32),
                pltpu.VMEM((H, math.gcd(cols, LANES)), jnp.float32),
                pltpu.VMEM((H, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DECODE_VMEM_LIMIT),
        interpret=interpret,
        name="paged_window",
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32), q,
      k_pool.reshape(N, cols, D), v_pool.reshape(N, cols, D))


def _decode_walk(q, k_pool, v_pool, page_table, pos, sm_scale, Hkv):
    """``paged_decode_attention`` through ``_decode_kernel``."""
    block, step = _decode_tiles(
        page_table.shape[1], _page_size(k_pool, Hkv, q.shape[2]), Hkv,
        q.shape[2], k_pool.dtype.itemsize)
    return _paged_decode_impl(
        q, k_pool, v_pool, page_table, pos, sm_scale=float(sm_scale),
        kv_heads=Hkv, block=block, step=step, interpret=_use_interpret())


def _decode_window(q, k_pool, v_pool, page_table, pos, sm_scale, Hkv):
    """``paged_decode_attention`` through ``_window_kernel``: the window
    at ``S = 1`` with ``q_offset = pos``, or, for grouped-query heads, the
    group on the window axis, every query at ``pos``."""
    B, H, D = q.shape
    if H == Hkv:
        return paged_window_attention(q[:, None], k_pool, v_pool,
                                      page_table, pos, sm_scale=sm_scale,
                                      kv_heads=Hkv)[:, 0]
    qg = jnp.swapaxes(q.reshape(B, Hkv, H // Hkv, D), 1, 2)
    out = paged_window_attention(qg, k_pool, v_pool, page_table, pos,
                                 sm_scale=sm_scale, shared_position=True,
                                 kv_heads=Hkv)
    return jnp.swapaxes(out, 1, 2).reshape(B, H, D)


def paged_decode_attention(q, k_pool, v_pool, page_table, pos, *,
                           sm_scale, kv_heads=None):
    """Single-token paged attention, pools read in place.

    q ``(B, H, D)``; pools ``(num_pages, page_size, Hkv, D)`` or
    ``(num_pages, page_size, Hkv x D)``, or ``(num_pages, page_size x Hkv,
    D)`` with ``kv_heads`` saying ``Hkv`` (``_page_size``); ``page_table``
    ``(B, P)`` int32; ``pos`` ``(B,)``
    int32 — row ``b`` attends keys at absolute positions ``<= pos[b]``
    (the caller has already scattered position ``pos`` into the pool).
    Returns ``(B, H, D)``. Where ``H`` is ``G`` times ``Hkv``
    (grouped-query heads) query head ``i`` reads key/value head ``i //
    G``.

    Heads of whole lanes (``D`` a multiple of 128) walk the row's live
    pages with the kernel's own copies (``_decode_kernel``); narrower heads
    keep the pipeline's page operands: ``_lane_window_kernel`` where the
    pool is declared ``(num_pages, page_size, Hkv x D)``, else
    ``_window_kernel`` on a page of 64-wide rows, out of which Mosaic
    copies nothing itself ("slice shape along dimension 2 must be aligned
    to tiling (128), but is 64")."""
    D = q.shape[2]
    lanes = _lanes_packed(k_pool, D)
    Hkv = k_pool.shape[2] // (D if lanes else 1) if kv_heads is None \
        else int(kv_heads)
    form = _decode_window if D % LANES or lanes else _decode_walk
    return form(q, k_pool, v_pool, page_table, pos, sm_scale, Hkv)


# ------------------------------------------------ selected window (GQA)
# keys a grid step of the selected window takes. The softmax carry (a
# cross-lane max a row, ``alpha``, the rescaled accumulator) is paid once
# a block: on a v5e the last chunk of a 16k prompt takes 5.6 ms at 512
# keys a step, 4.9 at 1,024 and 5.4 at 1,280 (PERF.md, PR 30)
_WINDOW_KEYS = 1024
_WINDOW_VMEM_LIMIT = 48 * 1024 * 1024


def _selected_window_tiles(C, P, page_size):
    """``(queries a block, pages a block)`` of the selected window, from
    the shapes alone: 256 queries where they divide the chunk (each
    key/value head then meets its ``G * 256`` query rows in one product),
    and as many whole pages as make ``_WINDOW_KEYS`` keys."""
    tq = next((t for t in (256, 128) if C % t == 0), C)
    return tq, max(1, min(P, _WINDOW_KEYS // page_size))


def _selected_window_vmem_bytes(tq, pages, page_size, Hkv, G, D, itemsize):
    """VMEM a grid step of the selected window holds: the pipeline's two
    buffers of every block, the scratch, and the block of scores with its
    exponentials (float32) and their cast for the second product."""
    rows, kb = G * tq, pages * page_size
    blocks = 2 * (2 * Hkv * rows * D * itemsize       # queries, output
                  + 2 * kb * Hkv * D * itemsize       # K and V pages
                  + tq * kb)                          # the mask, int8
    scratch = Hkv * rows * (2 * LANES + D) * 4 + 2 * Hkv * kb * D * itemsize
    live = rows * kb * (4 + 4 + itemsize) + tq * kb * 4
    return blocks + scratch + live


def _selected_window_kernel(pt_ref, off_ref, q_ref, *refs, page_size, pages,
                            length, sm_scale, tq, groups):
    """Grid (B, query blocks, key blocks), key blocks sequential: ``pages``
    pool pages a step (each its own operand, so the pipeline copies them
    through the page table), online-softmax carry in VMEM scratch. The
    pages are relaid head-major ONCE a block; then every key/value head
    meets the ``groups * tq`` query rows that read it (rows ``g * tq +
    t``) in one product, and the carry is updated once. The mask block
    ``(tq, keys)`` is the same for every head; past ``length`` (the last
    block may be partial) it is never trusted."""
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    mask_ref, o_ref, m_ref, l_ref, acc_ref, kt_ref, vt_ref = refs[2 * pages:]
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kb = pages * page_size
    lw = l_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the block's LAST query bounds what any of its queries can see
    @pl.when(j * kb <= off_ref[b] + (i + 1) * tq - 1)
    def _accumulate():
        def head_major(page_refs):     # (keys, Hkv, D) to (Hkv, keys, D)
            return jnp.swapaxes(
                jnp.concatenate([r[0] for r in page_refs], axis=0), 0, 1)

        kt_ref[...] = head_major(k_refs)
        vt_ref[...] = head_major(v_refs)
        keep = mask_ref[0].astype(jnp.int32)       # (tq, keys)
        if length % kb:
            pos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (tq, kb), 1)
            keep = jnp.where(pos < length, keep, 0)
        # -inf under a running max that starts finite: an unselected key
        # weighs exp(-inf) = 0 whatever the row has seen, no second select
        bias = jnp.where(keep != 0, 0.0, -jnp.inf).astype(jnp.float32)
        # a process-wide "highest" precision is not one Mosaic takes for
        # bfloat16 operands
        prec = (jax.lax.Precision.DEFAULT if kt_ref.dtype == jnp.bfloat16
                else None)

        def head(h, carry):
            s = jax.lax.dot_general(
                q_ref[0, 0, h], kt_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec)                    # (groups * tq, keys)
            s = (s.reshape(groups, tq, kb) * sm_scale + bias[None]) \
                .reshape(groups * tq, kb)
            m_prev = m_ref[h]                      # (groups * tq, LANES)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p_act = jnp.exp(s - m_new[:, :1])
            # the denominator stays a sum a lane until the last block:
            # adding lane groups is elementwise, a sum a row is not
            l_new = alpha[:, :lw] * l_ref[h]
            for c in range(kb // lw):
                l_new = l_new + p_act[:, c * lw:(c + 1) * lw]
            l_ref[h] = l_new
            acc_ref[h] = acc_ref[h] * alpha[:, :1] + jax.lax.dot_general(
                p_act.astype(vt_ref.dtype), vt_ref[h],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec)                    # (groups * tq, D)
            m_ref[h] = m_new
            return carry

        # a loop, not an unrolled body: 8 % slower on a v5e and a third
        # of the compile time (PERF.md, PR 30)
        jax.lax.fori_loop(0, kt_ref.shape[0], head, 0)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(jnp.sum(l_ref[...], axis=2, keepdims=True), 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _selected_blocks(tq, pages, ps, P, rest):
    """``(page, mask_block)``: the index maps of page operand ``t`` (a
    block ``(1,) + `` the pool's other axes whole, ``rest`` their zeros)
    and of the mask's block in the selected window's grid ``(b, i, j)``.
    Past the last key block a query block's LAST query sees, both stay
    where they were: an unseen block re-reads nothing."""
    kb, nkb = pages * ps, pl.cdiv(P, pages)

    def last_seen(b, i, off):       # the block's last query's position
        return off[b] + (i + 1) * tq - 1

    def page(t):
        return lambda b, i, j, pt, off: (pt[b, jnp.minimum(
            j * pages + t, jnp.minimum(last_seen(b, i, off) // ps, P - 1))],
            ) + rest

    def mask_block(b, i, j, pt, off):
        return (b, i, jnp.minimum(
            j, jnp.minimum(last_seen(b, i, off) // kb, nkb - 1)))

    return page, mask_block


@functools.partial(jax.jit, static_argnames=("sm_scale", "tq", "pages"))
def _dsa_selected_window_impl(q, k_pool, v_pool, page_table, q_offset,
                              mask, sm_scale, tq, pages):
    B, C, Hq, D = q.shape
    ps, Hkv = k_pool.shape[1], k_pool.shape[2]
    P, L = page_table.shape[1], mask.shape[2]
    G, nq = Hq // Hkv, C // tq
    kb, nkb = pages * ps, pl.cdiv(P, pages)
    # (B, nq, Hkv, G * tq, D): a block's rows are (query head of the
    # group, query) for each key/value head
    qb = q.reshape(B, nq, tq, Hkv, G, D).transpose(0, 1, 3, 4, 2, 5) \
        .reshape(B, nq, Hkv, G * tq, D)
    page, mask_block = _selected_blocks(tq, pages, ps, P, (0, 0, 0))
    pool_specs = [pl.BlockSpec((1, ps, Hkv, D), page(t))
                  for t in range(pages)]
    rows = pl.BlockSpec((1, 1, Hkv, G * tq, D),
                        lambda b, i, j, pt, off: (b, i, 0, 0, 0))
    kernel = functools.partial(_selected_window_kernel, page_size=ps,
                               pages=pages, length=L, sm_scale=sm_scale,
                               tq=tq, groups=G)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nq, nkb),
        in_specs=[rows] + pool_specs + pool_specs
        + [pl.BlockSpec((1, tq, kb), mask_block)],
        out_specs=rows,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G * tq, LANES), jnp.float32),
            pltpu.VMEM((Hkv, G * tq, math.gcd(kb, LANES)), jnp.float32),
            pltpu.VMEM((Hkv, G * tq, D), jnp.float32),
            pltpu.VMEM((Hkv, kb, D), k_pool.dtype),
            pltpu.VMEM((Hkv, kb, D), v_pool.dtype),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nq, Hkv, G * tq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_WINDOW_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="dsa_selected_window",
    )(page_table.astype(jnp.int32), q_offset.astype(jnp.int32), qb,
      *([k_pool] * pages), *([v_pool] * pages), mask.astype(jnp.int8))
    return out.reshape(B, nq, Hkv, G, tq, D).transpose(0, 1, 4, 2, 3, 5) \
        .reshape(B, C, Hq * D)


def _lane_selected_window_kernel(pt_ref, off_ref, q_ref, *refs, page_size,
                                 pages, length, sm_scale, tq, groups):
    """``_selected_window_kernel`` over pages ``(page_size, Hkv x D)``: the
    grid, the page operands, the mask and the carry are its own, and
    nothing is relaid. The key/value heads come as many at a time as fill
    whole lanes (``hp``: two heads of 64), a set's ``hp * groups * tq``
    query rows (head of the set, query head of its group, query) each with
    its query on its own head's ``D`` lanes and zeros beside it
    (``_own_lanes``): one product with the set's lanes of the block gives
    every row its own head's scores, at the depth of 128 a product of
    heads of 64 costs the MXU anyway; of the second product's ``hp x D``
    lanes a row keeps its head's at the end."""
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    mask_ref, o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kb = pages * page_size
    sets, per, width = q_ref.shape[2:]
    D = o_ref.shape[4]
    hp = width // D

    @pl.when(j == 0)
    def _init():
        init_carry(m_ref, l_ref, acc_ref)

    @pl.when(j * kb <= off_ref[b] + (i + 1) * tq - 1)
    def _accumulate():
        keep = mask_ref[0].astype(jnp.int32)       # (tq, keys)
        if length % kb:
            pos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (tq, kb), 1)
            keep = jnp.where(pos < length, keep, 0)
        bias = jnp.where(keep != 0, 0.0, -jnp.inf).astype(jnp.float32)
        for p in range(sets):
            def joined(page_refs):
                return jnp.concatenate(
                    [r[0, :, p * width:(p + 1) * width] for r in page_refs],
                    axis=0)                        # (keys, hp x D)

            s = nt(q_ref[0, 0, p], joined(k_refs))
            s = (s.reshape(hp * groups, tq, kb) * sm_scale + bias[None]) \
                .reshape(per, kb)
            softmax_step(s, joined(v_refs), m_ref.at[p], l_ref.at[p],
                         acc_ref.at[p])

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(jnp.sum(l_ref[...], axis=2, keepdims=True), 1e-30)
        out = (acc_ref[...] / l).astype(o_ref.dtype)   # (sets, per, width)
        rows = groups * tq
        for h in range(sets * hp):
            p, e = divmod(h, hp)
            o_ref[0, 0, h] = out[p, e * rows:(e + 1) * rows,
                                 e * D:(e + 1) * D]


@functools.partial(jax.jit, static_argnames=("sm_scale", "tq", "pages"))
def _lane_selected_window_impl(q, k_pool, v_pool, page_table, q_offset,
                               mask, sm_scale, tq, pages):
    B, C, Hq, D = q.shape
    ps, Hkv = k_pool.shape[1], k_pool.shape[2] // D
    P, L = page_table.shape[1], mask.shape[2]
    G, nq = Hq // Hkv, C // tq
    hp = _lane_heads(Hkv, D)                  # heads that fill whole lanes
    sets, per, width = Hkv // hp, hp * G * tq, hp * D
    kb, nkb = pages * ps, pl.cdiv(P, pages)
    # (B, nq, sets, hp x G x tq, hp x D): a set's rows are (head of the
    # set, query head of its group, query), each on its head's lanes
    qb = _own_lanes(
        q.reshape(B, nq, tq, sets, hp, G, D).transpose(0, 1, 3, 4, 5, 2, 6)
        .reshape(B, nq, sets, hp, G * tq, D))
    page, mask_block = _selected_blocks(tq, pages, ps, P, (0, 0))
    pool_specs = [pl.BlockSpec((1, ps, Hkv * D), page(t))
                  for t in range(pages)]
    kernel = functools.partial(_lane_selected_window_kernel, page_size=ps,
                               pages=pages, length=L, sm_scale=sm_scale,
                               tq=tq, groups=G)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nq, nkb),
            in_specs=[pl.BlockSpec((1, 1, sets, per, width),
                                   lambda b, i, j, pt, off: (b, i, 0, 0, 0))]
            + pool_specs + pool_specs
            + [pl.BlockSpec((1, tq, kb), mask_block)],
            out_specs=pl.BlockSpec((1, 1, Hkv, G * tq, D),
                                   lambda b, i, j, pt, off: (b, i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((sets, per, LANES), jnp.float32),
                pltpu.VMEM((sets, per, math.gcd(kb, LANES)), jnp.float32),
                pltpu.VMEM((sets, per, width), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, nq, Hkv, G * tq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_WINDOW_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="dsa_selected_window",
    )(page_table.astype(jnp.int32), q_offset.astype(jnp.int32), qb,
      *([k_pool] * pages), *([v_pool] * pages), mask.astype(jnp.int8))
    return out.reshape(B, nq, Hkv, G, tq, D).transpose(0, 1, 4, 2, 3, 5) \
        .reshape(B, C, Hq * D)


def paged_selected_window_attention(q, k_pool, v_pool, page_table,
                                    q_offset, mask, *, sm_scale):
    """A ``C``-query window a row over a SELECTED set of its cached
    positions, pools read in place. ``q (B, C, Hq, D)``; pools
    ``(num_pages, page, Hkv, D)`` or ``(num_pages, page, Hkv x D)`` (the
    lane form, nothing relaid) with ``Hq`` a multiple of ``Hkv`` (query
    head ``i`` reads key/value head ``i // (Hq // Hkv)``); ``mask (B, C,
    L)`` true where query ``c`` of row ``b`` reads cached position ``l``
    (the caller keeps it causal: nothing past ``q_offset[b] + c``).
    Returns ``(B, C, Hq * D)``."""
    # 256 queries and 8 pages of 128 a block read a chunk's last 2,048
    # queries over 16k keys in 4.9 ms on a v5e; a page a step, relaid for
    # every query head, took 13.2 (PERF.md, PR 30)
    tq, pages = _selected_window_tiles(q.shape[1], page_table.shape[1],
                                       k_pool.shape[1])
    impl = _dsa_selected_window_impl
    if _lanes_packed(k_pool, q.shape[3]):
        # a product takes the rows of as many heads as fill the lanes: 128
        # queries a block keep its scores what 256 queries of one head are
        impl, tq = _lane_selected_window_impl, math.gcd(tq, 128)
    return impl(q, k_pool, v_pool, page_table, q_offset, mask,
                sm_scale=sm_scale, tq=tq, pages=pages)


def paged_selected_window_reference(q, k_pool, v_pool, page_table,
                                    q_offset, mask, *, sm_scale):
    """Dense jnp reference for ``paged_selected_window_attention``."""
    B, C, Hq, D = q.shape
    ps, Hkv = k_pool.shape[1], k_pool.shape[2]
    P = page_table.shape[1]
    k = k_pool[page_table].reshape(B, P * ps, Hkv, D).astype(jnp.float32)
    v = v_pool[page_table].reshape(B, P * ps, Hkv, D).astype(jnp.float32)
    qg = q.astype(jnp.float32).reshape(B, C, Hkv, Hq // Hkv, D)
    s = jnp.einsum("bcngd,blnd->bngcl", qg, k) * sm_scale
    probs = jax.nn.softmax(jnp.where(mask[:, None, None], s, NEG_INF), -1)
    out = jnp.einsum("bngcl,blnd->bcngd", probs, v)
    return out.reshape(B, C, Hq * D).astype(q.dtype)


# ------------------------------------------------------------ references
def paged_decode_reference(q, k_pool, v_pool, page_table, pos, *,
                           sm_scale):
    """Dense jnp reference for ``paged_decode_attention`` (gathers the
    pages the kernel reads in place) — the tolerance-test oracle."""
    B, H, D = q.shape
    ps = k_pool.shape[1]
    P = page_table.shape[1]
    k = k_pool[page_table].reshape(B, P * ps, H, D).astype(jnp.float32)
    v = v_pool[page_table].reshape(B, P * ps, H, D).astype(jnp.float32)
    s = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32), k) * sm_scale
    mask = jnp.arange(P * ps)[None, None, :] <= pos[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,blhd->bhd", probs, v).astype(q.dtype)


def paged_window_reference(q, k_pool, v_pool, page_table, q_offset,
                           window_vl=None, *, sm_scale):
    """Dense jnp reference for ``paged_window_attention``."""
    B, S, H, D = q.shape
    ps = k_pool.shape[1]
    P = page_table.shape[1]
    if window_vl is None:
        window_vl = jnp.full((B,), S, jnp.int32)
    k = k_pool[page_table].reshape(B, P * ps, H, D).astype(jnp.float32)
    v = v_pool[page_table].reshape(B, P * ps, H, D).astype(jnp.float32)
    s = jnp.einsum("bshd,blhd->bhsl", q.astype(jnp.float32), k) * sm_scale
    key_abs = jnp.arange(P * ps)[None, None, None, :]
    q_abs = (q_offset[:, None, None, None]
             + jnp.arange(S)[None, None, :, None])
    s = jnp.where(key_abs <= q_abs, s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhsl,blhd->bshd", probs, v)
    live = jnp.arange(S)[None, :, None, None] < \
        window_vl[:, None, None, None]
    return jnp.where(live, out, 0.0).astype(q.dtype)

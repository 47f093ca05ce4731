"""The sparse attention's decode step as two Pallas kernels a layer: the
selection of ONE query a row leaves the chip as a mask, and attention walks
the row's live pages in place under it. Nothing is sorted, no indexer key is
gathered into a copy and no key or value is gathered by token.

``ops/sparse_attention.decode_select`` + ``selected_decode_attention`` are
the same step in ``jax.numpy`` (and these kernels' references): there a
row's 16,640 indexer keys are gathered into a copy, ``lax.top_k`` sorts 16 x
16,640 scores (a full sort on the TPU) and the ``topk`` positions it returns
are gathered from the pools by token. Here both kernels take a ROW a grid
step and issue their own copies through the page table, a block of pages
into one half of a VMEM scratch while the other half is computed
(``page_walk.walk_live_pages``): a row walks its live pages only, an inactive
row (a position below 0) none, and a row's last block starts the next live
row's first.

- ``dsa_decode_select`` (``%dsa_decode_select``): writes the row's own
  indexer key into its page, scores the live pages (``I = sum_j w_j relu(q_j
  . k) / sqrt(Di J)``, float32, the heads against a key block in one
  product), keeps the scores in VMEM as order-preserving int32 keys, a page a
  row of the scratch (130 pages of 128: 17 vector registers), finds the
  ``topk``-th largest a bit a pass from the top as
  ``index_select._index_select_kernel`` does for a query block, settles ties
  at the cut to the lower position (a second search, over positions, only
  where a row has more ties than room) and writes one int8 a position. A row
  with ``pos + 1 <= topk`` selects every position it sees and scores none.
  The pool is taken as ``(num_pages, Di, page)``, positions on the lanes:
  that is how XLA keeps a pool whose last axis is 64 on the chip, so the
  view is free, a page of 128 positions is whole tiles, and the product
  takes it as it lies. The kernel writes the row's key itself because an
  XLA scatter of 64 numbers a row wants the pool key-minor and copies all of
  it to the kernel's layout and back, every layer, every step.
- ``dsa_decode_window`` (``%dsa_decode_window``): a page of K or V as its
  ``(key, head)`` rows, the pool's own order (``paged_flash_attention.
  _window_kernel``'s form: nothing is relaid), the ``Hq`` query rows against
  all of them in ONE product a page, whose foreign-head and unselected
  columns read ``-inf``; the online softmax is carried in VMEM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _use_interpret
from .index_select import MIN_KEY, ordered_key
from .page_walk import (LANES, decode_tiles, init_carry, prec, softmax_step,
                        walk_live_pages)

__all__ = ["dsa_decode_select", "dsa_decode_window"]

# keys a step of the attention's online softmax (one product with the keys,
# one carry, one product with the values). On a v5e (PERF.md, PR 39; the
# kernel alone, 13 live rows at 9.1k positions): 0.487 ms a call at 128 (a
# page: eight products a block wait for one carry each), 0.365 / 0.361 /
# 0.357 at 256 / 512 / 1,024, where the copies alone take 0.353
_STEP_KEYS = 512
_VMEM_LIMIT = 32 * 1024 * 1024


# -------------------------------------------------------------- selection
def _select_kernel(pt_ref, pos_ref, q_ref, w_ref, own_ref, pool_ref,
                   code_ref, out_pool_ref, buf_ref, page_ref, sem_ref,
                   slot_ref, key_ref, *, block, topk, norm):
    """Grid (B,). ``q_ref (1, heads, Di)``, ``w_ref (1, heads, 1)``,
    ``own_ref (1, Di, 1)`` the row's own key; ``pool_ref (num_pages, Di,
    page)`` in HBM and ``out_pool_ref`` the same buffer; ``buf_ref (2, Di,
    block x page)`` a block of pages side by side on the lanes; ``key_ref
    (pages, page)`` the row's scores as ordered keys; ``code_ref (1, pages,
    page)`` int8."""
    b = pl.program_id(0)
    P = pt_ref.shape[1]
    Di, ps = page_ref.shape
    pos = pos_ref[b]
    page_i = jax.lax.broadcasted_iota(jnp.int32, key_ref.shape, 0)
    p_abs = page_i * ps + jax.lax.broadcasted_iota(jnp.int32, key_ref.shape, 1)
    seen = p_abs <= pos                      # nothing, for a row below 0
    position_bits = (key_ref.shape[0] * ps - 1).bit_length()

    def put_code(selected):
        code_ref[0] = jnp.where(selected, 1, 0).astype(jnp.int8)

    def write_back(page, do):
        # ``page (Di, page)`` in VMEM to the pool page that holds ``pos``
        do(pltpu.make_async_copy(
            page, out_pool_ref.at[pt_ref[b, jnp.maximum(pos, 0) // ps]],
            sem_ref.at[2]))

    def put_own_key(page):
        lane = jax.lax.broadcasted_iota(jnp.int32, (Di, ps), 1)
        page[...] = jnp.where(lane == pos % ps, own_ref[0],
                              page[...].astype(jnp.float32)) \
            .astype(page.dtype)
        write_back(page, lambda c: c.start())

    def live_pages(r):
        # pages a row's scores are taken from: none while every seen
        # position is selected (or the row is inactive, below 0)
        return jnp.where(pos_ref[r] >= topk,
                         jnp.clip(pos_ref[r] // ps + 1, 0, P), 0)

    def page_copies(page, slot, t):
        return [pltpu.make_async_copy(
            pool_ref.at[page], buf_ref.at[slot, :, pl.ds(t * ps, ps)],
            sem_ref.at[slot])]

    sub = jax.lax.broadcasted_iota(jnp.int32, (block, ps), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block, ps), 1)

    def score(k, slot):
        # the row's last block holds ``pos``: its key enters the page here
        for t in range(block):
            pl.when(k * block + t == pos // ps)(functools.partial(
                put_own_key, buf_ref.at[slot, :, pl.ds(t * ps, ps)]))
        s = jax.lax.dot_general(
            q_ref[0], buf_ref[slot], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec(buf_ref.dtype))           # (heads, block * ps)
        hit = jnp.maximum(s, 0.0) * w_ref[0]
        blk = jnp.zeros((block, ps), jnp.float32)
        for t in range(block):                       # a page a row
            blk = jnp.where(sub == t, jnp.sum(
                hit[:, t * ps:(t + 1) * ps], axis=0, keepdims=True), blk)
        at = (k * block + sub) * ps + lane
        blk = jnp.where(at <= pos, blk / norm, -jnp.inf)
        key_ref[pl.ds(pl.multiple_of(k * block, block), block), :] = \
            ordered_key(blk)

    # dead blocks hold the lowest key there is
    @pl.when(pos >= topk)
    def _dead():
        key_ref[...] = ordered_key(
            jnp.full(key_ref.shape, -jnp.inf, jnp.float32))

    walk_live_pages(pt_ref, slot_ref, live_pages, block, page_copies, score)

    # every seen position is selected while there are at most ``topk``
    @pl.when(pos < topk)
    def _all_seen():
        put_code(seen)

        @pl.when(pos >= 0)
        def _own_key():
            fetch = pltpu.make_async_copy(
                pool_ref.at[pt_ref[b, pos // ps]], page_ref, sem_ref.at[2])
            fetch.start()
            fetch.wait()
            put_own_key(page_ref)

    @pl.when(pos >= topk)
    def _select():
        keys = key_ref[...]

        def count(hits):
            return jnp.sum(jnp.where(hits, 1.0, 0.0))    # exact in float32

        # the largest T with count(keys >= T) >= topk, a bit a pass from
        # the top. T grows as an UNSIGNED key; the keys compare signed, so
        # the carry is T with its sign bit turned (zero is ``MIN_KEY``)
        def bit_pass(p, prefix):
            cand = prefix ^ jnp.left_shift(jnp.int32(1), 31 - p)
            return jnp.where(count(keys >= cand) >= topk, cand, prefix)

        kth = jax.lax.fori_loop(0, 32, bit_pass, jnp.int32(MIN_KEY))
        above = keys > kth
        tie = jnp.logical_and(keys == kth, seen)
        put_code(jnp.logical_or(above, tie))
        room = topk - count(above)           # at least 1: kth is the k-th

        # more ties than room: the lowest positions are kept. The largest
        # bound with count(ties below it) < room is the position of the
        # last tie kept
        @pl.when(count(tie) > room)
        def _crowded():
            def bit_pass(p, bound):
                cand = bound | jnp.left_shift(jnp.int32(1),
                                              position_bits - 1 - p)
                below = jnp.logical_and(tie, p_abs < cand)
                return jnp.where(count(below) < room, cand, bound)

            bound = jax.lax.fori_loop(0, position_bits, bit_pass,
                                      jnp.int32(0))
            put_code(jnp.logical_or(
                above, jnp.logical_and(tie, p_abs <= bound)))

    # the page is in the pool before the next row's copies may take the
    # buffer it left from
    pl.when(pos >= 0)(functools.partial(write_back, page_ref,
                                        lambda c: c.wait()))


@functools.partial(jax.jit, static_argnames=("topk", "block", "interpret"))
def _dsa_decode_select_impl(qi, wi, ki, ik_pool, page_table, pos, topk,
                            block, interpret):
    B, J, Di = qi.shape
    ps = ik_pool.shape[1]
    P = page_table.shape[1]
    pages = pl.cdiv(P, block) * block        # whole blocks of the scratch
    kernel = functools.partial(_select_kernel, block=block, topk=topk,
                               norm=math.sqrt(Di * J))
    # positions minor is how XLA keeps a pool whose last axis is 64 on the
    # chip (it would be padded to the lanes): the view is free there
    pool = jnp.swapaxes(ik_pool, 1, 2)

    def row(*shape):
        return pl.BlockSpec((1,) + shape, lambda b, pt, at: (b, 0, 0))

    code, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[row(J, Di), row(J, 1), row(Di, 1),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[row(pages, ps), pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((2, Di, block * ps), ik_pool.dtype),
                pltpu.VMEM((Di, ps), ik_pool.dtype),
                pltpu.SemaphoreType.DMA((3,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((pages, ps), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((B, pages, ps), jnp.int8),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={5: 1},         # the pool, written in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_decode_select",
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      qi.astype(ik_pool.dtype), wi.astype(jnp.float32)[..., None],
      ki.astype(ik_pool.dtype).astype(jnp.float32)[..., None], pool)
    return code, jnp.swapaxes(pool, 1, 2)


def dsa_decode_select(qi, wi, ki, ik_pool, page_table, pos, topk):
    """Index scores and selection of ONE query a row, ``qi (B, J, Di)``
    with head weights ``wi (B, J)`` at position ``pos (B,)``, over the
    row's cached indexer keys, read in place from ``ik_pool (num_pages,
    page, Di)`` through ``page_table (B, P)``: a row's live pages only,
    and none for a row at a position below ``topk``. The row's own key
    ``ki (B, Di)`` is written at ``pos`` first, by the kernel. A row at a
    position below 0 is inactive: it writes, reads and selects nothing.

    Returns ``(the selected set of each row, the pool)``. The set is ``(B,
    pages, page)`` int8, a page a row as the kernel keeps it, ``pages``
    being ``P`` rounded up to whole blocks (``decode_tiles``) and nothing
    selected past the table: 1 where selected, which is every position up
    to ``pos`` while ``pos + 1 <= topk``, else the ``topk`` of largest
    score, ties to the lower position."""
    return _dsa_decode_select_impl(
        qi, wi, ki, ik_pool, page_table, pos, topk=int(topk),
        block=decode_tiles(page_table.shape[1], ik_pool.shape[1]),
        interpret=_use_interpret())


# -------------------------------------------------------------- attention
def _window_kernel(pt_ref, pos_ref, q_ref, code_ref, k_pool_ref, v_pool_ref,
                   o_ref, k_buf, v_buf, keep_ref, sem_ref, slot_ref, m_ref,
                   l_ref, acc_ref, *, block, groups, sm_scale):
    """Grid (B,). ``q_ref (1, Hq, D)``; a pool ``(num_pages, page x Hkv,
    D)`` in HBM, a page as its (key, head) rows; ``k_buf``, ``v_buf (2,
    block x page x Hkv, D)``; ``code_ref (1, pages, page)`` int8, 1 at a
    selected position, and ``keep_ref (pages, page)`` the same in float32.
    Query row ``r`` is head ``r``, which reads key/value head ``r //
    groups``."""
    b = pl.program_id(0)
    P = pt_ref.shape[1]
    rows = q_ref.shape[1]
    cols = k_pool_ref.shape[1]               # (key, head) rows a page
    Hkv = rows // groups
    ps = cols // Hkv

    def live_pages(r):
        return jnp.clip(pos_ref[r] // ps + 1, 0, P)

    def page_copies(page, slot, t):
        return [pltpu.make_async_copy(
            pool.at[page], buf.at[slot, pl.ds(t * cols, cols)],
            sem_ref.at[slot]) for pool, buf in ((k_pool_ref, k_buf),
                                                (v_pool_ref, v_buf))]

    step = math.gcd(block, max(1, _STEP_KEYS // ps))     # pages a softmax step
    width = step * cols
    own = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1) % Hkv == \
        jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0) // groups

    # a position's selection spread over its (key, head) columns: a 0 / 1
    # product, exact in any precision
    keep_ref[...] = code_ref[0].astype(jnp.float32)
    spread = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (ps, cols), 1) // Hkv
        == jax.lax.broadcasted_iota(jnp.int32, (ps, cols), 0), 1.0, 0.0) \
        .astype(jnp.bfloat16)

    if step > 1:
        @pl.when(b == 0)
        def _finite():
            # what a partial step reads past the row's live pages is
            # masked, and has to be finite for the value product from the
            # first call on: later it holds pages a live row has read
            k_buf[...] = jnp.zeros_like(k_buf)
            v_buf[...] = jnp.zeros_like(v_buf)

    def attend(k, slot):
        n = live_pages(b) - k * block        # pages of this block that live
        keep = jax.lax.dot_general(
            keep_ref[pl.ds(pl.multiple_of(k * block, block), block), :]
            .astype(jnp.bfloat16), spread, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec(jnp.bfloat16))            # (block, cols)
        for t in range(0, block, step):
            @pl.when(t < n)
            def _step():
                kt = k_buf[slot, pl.ds(t * cols, width), :]
                s = jax.lax.dot_general(
                    q_ref[0], kt, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=prec(kt.dtype))        # (rows, width)
                chosen = jnp.concatenate(
                    [keep[u:u + 1] for u in range(t, t + step)], axis=1)
                # -inf under a running max that starts finite: a column
                # that is not the row's to read weighs exp(-inf) = 0
                # whatever the row has seen
                s = jnp.where(jnp.logical_and(own, chosen != 0.0),
                              s * sm_scale, -jnp.inf)
                softmax_step(s, v_buf[slot, pl.ds(t * cols, width), :],
                             m_ref, l_ref, acc_ref)

    init_carry(m_ref, l_ref, acc_ref)
    walk_live_pages(pt_ref, slot_ref, live_pages, block, page_copies,
                    attend)
    l = jnp.maximum(jnp.sum(l_ref[...], axis=1, keepdims=True), 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "block", "interpret"))
def _dsa_decode_window_impl(q, k_pool, v_pool, page_table, pos, mask,
                            sm_scale, block, interpret):
    B, Hq, D = q.shape
    N, ps, Hkv, _ = k_pool.shape
    pages = mask.shape[1]
    kernel = functools.partial(_window_kernel, block=block,
                               groups=Hq // Hkv, sm_scale=sm_scale)

    def row(*shape):
        return pl.BlockSpec((1,) + shape, lambda b, pt, at: (b, 0, 0))

    pool = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[row(Hq, D), row(pages, ps), pool, pool],
            out_specs=row(Hq, D),
            scratch_shapes=[
                pltpu.VMEM((2, block * ps * Hkv, D), k_pool.dtype),
                pltpu.VMEM((2, block * ps * Hkv, D), v_pool.dtype),
                pltpu.VMEM((pages, ps), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((Hq, LANES), jnp.float32),
                pltpu.VMEM((Hq, math.gcd(ps * Hkv, LANES)), jnp.float32),
                pltpu.VMEM((Hq, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_decode_window",
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      q.astype(k_pool.dtype), mask, k_pool.reshape(N, ps * Hkv, D),
      v_pool.reshape(N, ps * Hkv, D))
    return out.reshape(B, Hq * D)


def dsa_decode_window(q, k_pool, v_pool, page_table, pos, mask, *, sm_scale):
    """ONE query a row, ``q (B, Hq, D)`` at ``pos (B,)``, over the cached
    positions of its row that ``mask (B, pages, page)`` selects (nothing
    past ``pos``; ``dsa_decode_select``'s, whole blocks of pages), pools
    ``(num_pages, page, Hkv, D)`` read in place through ``page_table (B,
    P)``; query head ``i`` reads key/value head ``i // (Hq // Hkv)``. A row at a position below 0 reads nothing and hands
    back zeros, as does one whose mask is empty. Returns ``(B, Hq * D)``."""
    return _dsa_decode_window_impl(
        q, k_pool, v_pool, page_table, pos, mask, sm_scale=float(sm_scale),
        block=decode_tiles(page_table.shape[1], k_pool.shape[1]),
        interpret=_use_interpret())

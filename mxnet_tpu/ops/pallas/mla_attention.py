"""Latent attention kernels: Pallas kernels over a cache that holds ONE
vector a token a layer, ``[c (rank); k_r (rope)]``, for every head.

Multi-head latent attention keeps, for each cached position, the normed
latent ``c`` from which every head's key and value are linear maps, and one
rotary key ``k_r`` that all heads share. Two kernels, one for each way of
reading that cache:

- ``mla_latent_decode`` (``%mla_latent_decode``), the ABSORBED form: the
  up-projections are folded into the query and the output, so all heads of
  all query positions of a row are rows of ONE product against the latent
  pages, read in place through the page table. A key is the ``rank +
  rope`` numbers of a position and its value is the first ``rank`` of them:
  one read of a page serves both products. The kernel takes the pool as
  ONE operand left in HBM and issues its own copies through the page table:
  a grid step is a row, which walks its LIVE pages only, a block of pages
  at a time into one half of a VMEM scratch while the other half is
  computed; a row's last block starts the next live row's first, and each
  page lands at its own row offset, so both products read the block in
  place. The online softmax is carried in VMEM.
- ``mla_prefill`` (``%mla_prefill``), the EXPANDED form for a chunk of
  queries: keys ``[k_nope_h; k_r]`` and values per head, expanded once from
  the latent by the caller (a third of the absorbed form's operations at a
  chunk's widths). The score is the sum of two products, ``q_nope . k_nope``
  and ``q_rope . k_r``, because the score's width (nope + rope) is not the
  value's; the expanded keys and values are read as column blocks of the
  ``(L, heads x (nope + v))`` array the expansion writes, no relayout.
  The chunk's causal edge decides what the kernel DOES: a grid step is a
  head's block of queries, which walks the key blocks its last query sees
  with its own copies, two buffers deep, as the decode kernel walks pages
  (a grid axis over every key block of the table launched 65 steps a
  query block in ``xing`` of which a mean of 23 computed, and an empty
  step costs 0.2 us on a v5e: PERF.md, PR 45).

Queries come in scaled. Each has its dense ``jax.numpy`` form in
``ops/mla.py``, which stands on the CPU and under a multi-device mesh.
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
from .page_walk import LANES, decode_tiles, init_carry, nt, softmax_step

__all__ = ["mla_latent_decode", "mla_prefill", "prefill_tiles"]

_VMEM_LIMIT = 32 * 1024 * 1024


# ------------------------------------------------------- decode (absorbed)
def _latent_decode_kernel(pt_ref, pos_ref, qc_ref, qr_ref, pool_ref, o_ref,
                          buf_ref, sem_ref, slot_ref, m_ref, l_ref, acc_ref,
                          *, block, heads, rank):
    """Grid (B,), a row a step, sequential: the scratch, its semaphores and
    the slot carry over from row to row. ``pool_ref`` is the whole pool in
    HBM; block ``k`` of a row is its pages ``k x block ...``, each copied to
    its own row offset of ``buf_ref[slot]``, so that both products read the
    block in place. The ``S x heads`` query rows (query ``r // heads``, at
    position ``pos + r // heads``) meet the block's keys in one product:
    the latent part against the first ``rank`` columns, the rotary part
    against the rest; the second product takes the same first ``rank``
    columns as values."""
    b = pl.program_id(0)
    B, P = pt_ref.shape
    ps = pool_ref.shape[1]
    rows = qc_ref.shape[1]
    S = rows // heads

    def live_pages(r):
        # pages the last query of row r sees: none for a row with nothing
        # cached (a position below 0), never more than the table has
        return jnp.clip((pos_ref[r] + S - 1) // ps + 1, 0, P)

    def copies(r, k, slot, do):
        # the live pages of block k of row r <-> buf_ref[slot]; what lies
        # past them in the buffer is masked, and finite (see the first row)
        n = live_pages(r) - k * block
        for t in range(block):
            @pl.when(t < n)
            def _copy():
                do(pltpu.make_async_copy(
                    pool_ref.at[pt_ref[r, k * block + t]],
                    buf_ref.at[slot, pl.ds(t * ps, ps)], sem_ref.at[slot]))

    def start_first_block_after(r, slot):
        # the next LIVE row's first block: it lands while this row's last
        # block is computed, so no row waits for its first pages
        nxt = jax.lax.while_loop(
            lambda r: (r < B) & (live_pages(jnp.minimum(r, B - 1)) == 0),
            lambda r: r + 1, r + 1)

        @pl.when(nxt < B)
        def _start():
            copies(nxt, 0, slot, lambda c: c.start())

    @pl.when(b == 0)
    def _first_row():
        # zeros, so that the masked tail of a partial block holds finite
        # numbers for the value product from the first call on: later it
        # holds pages a live row has read
        buf_ref[...] = jnp.zeros_like(buf_ref)
        slot_ref[0] = 0
        start_first_block_after(-1, 0)

    init_carry(m_ref, l_ref, acc_ref)
    off = pos_ref[b]
    q_abs = off + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // heads
    blocks = pl.cdiv(live_pages(b), block)

    def body(k, slot):
        @pl.when(k + 1 < blocks)
        def _next_block():
            copies(b, k + 1, 1 - slot, lambda c: c.start())

        @pl.when(k + 1 == blocks)
        def _next_row():
            start_first_block_after(b, 1 - slot)

        copies(b, k, slot, lambda c: c.wait())
        c, kr = buf_ref[slot, :, :rank], buf_ref[slot, :, rank:]
        s = nt(qc_ref[0], c) + nt(qr_ref[0], kr)           # (rows, cols)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
        s = jnp.where(k * block * ps + col <= q_abs, s, NEG_INF)
        softmax_step(s, c, m_ref, l_ref, acc_ref)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, blocks, body, slot_ref[0])
    l = jnp.maximum(jnp.sum(l_ref[...], axis=1, keepdims=True), 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "block", "interpret"))
def _mla_latent_decode_impl(qc, qr, pool, page_table, pos, rank, block,
                            interpret):
    B, S, H, _ = qc.shape
    _, ps, W = pool.shape
    rows = S * H

    def row_spec(width):
        return pl.BlockSpec((1, rows, width), lambda b, pt, off: (b, 0, 0))

    kernel = functools.partial(_latent_decode_kernel, block=block, heads=H,
                               rank=rank)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[row_spec(rank), row_spec(W - rank),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_spec(rank),
            scratch_shapes=[
                pltpu.VMEM((2, block * ps, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((rows, LANES), jnp.float32),
                pltpu.VMEM((rows, math.gcd(ps, LANES)), jnp.float32),
                pltpu.VMEM((rows, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, rows, rank), qc.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mla_latent_decode",
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      qc.reshape(B, rows, rank), qr.reshape(B, rows, W - rank), pool)
    return out.reshape(B, S, H, rank)


def mla_latent_decode(qc, qr, pool, page_table, pos):
    """Absorbed latent attention of ``S`` query positions a row, the pool
    read in place. ``qc (B, S, H, rank)`` (the query through the key
    up-projection) and ``qr (B, S, H, rope)``, both scaled; ``pool
    (num_pages, page, rank + rope)``; query ``i`` of row ``b`` sits at
    ``pos[b] + i`` and reads the positions up to its own, which the caller
    has written. Returns the weighted latents ``(B, S, H, rank)``."""
    return _mla_latent_decode_impl(
        qc, qr, pool, page_table, pos, rank=qc.shape[-1],
        block=decode_tiles(page_table.shape[1], pool.shape[1]),
        interpret=_use_interpret())


# ------------------------------------------------------ prefill (expanded)
def prefill_tiles(C, L):
    """``(queries a grid step, keys a block of its walk)`` of the prefill
    kernel: 1,024 queries where they divide the chunk (the expanded keys
    are read once a query block), 512 keys where they divide the expanded
    length."""
    tq = next((t for t in (1024, 512, 256, 128) if C % t == 0), C)
    tk = next((t for t in (512, 256, 128) if L % t == 0), L)
    return tq, tk


def _prefill_kernel(off_ref, qn_ref, qr_ref, kv_ref, kr_ref, o_ref, kv_buf,
                    kr_buf, sem_ref, slot_ref, m_ref, l_ref, acc_ref,
                    *, tq, tk):
    """Grid (R, heads, query blocks), sequential: the scratch, its
    semaphores and the slot carry over from step to step. A step is one
    head's ``tq`` queries; it walks the key blocks of ``tk`` expanded keys
    its LAST query sees and no other. ``kv_ref`` and ``kr_ref`` are whole
    in HBM: block ``k`` is copied into one half of the scratch (the head's
    keys and values are adjacent columns: one copy, and one for the rotary
    keys) while the other half is computed, and a step's last block starts
    the next step's first, so no step waits for its first keys."""
    r, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    R, H, Q = pl.num_programs(0), pl.num_programs(1), pl.num_programs(2)
    D = qn_ref.shape[-1]
    off = off_ref[r]
    # key blocks the query block's last query sees: one at least (the
    # module's first query stands before position 0), the table's at most
    blocks = jnp.clip((off + (i + 1) * tq - 1) // tk + 1, 1,
                      kr_ref.shape[1] // tk)

    def copies(r, h, k, slot):
        # block k of row r, head h <-> scratch half `slot`
        rows = pl.ds(pl.multiple_of(k * tk, tk), tk)
        cols = pl.ds(pl.multiple_of(h * 2 * D, 2 * D), 2 * D)
        return (pltpu.make_async_copy(kv_ref.at[r, rows, cols],
                                      kv_buf.at[slot], sem_ref.at[slot]),
                pltpu.make_async_copy(kr_ref.at[r, rows], kr_buf.at[slot],
                                      sem_ref.at[slot]))

    def start(r, h, k, slot):
        for c in copies(r, h, k, slot):
            c.start()

    @pl.when((r == 0) & (h == 0) & (i == 0))
    def _first_step():
        slot_ref[0] = 0
        start(0, 0, 0, 0)

    init_carry(m_ref, l_ref, acc_ref)

    def body(k, slot):
        @pl.when(k + 1 < blocks)
        def _next_block():
            start(r, h, k + 1, 1 - slot)

        @pl.when(k + 1 == blocks)
        def _next_step():
            # block 0 of the step after this one: the next query block,
            # else the next head's first, else the next row's
            last_q = i + 1 == Q
            last_h = last_q & (h + 1 == H)
            nr = jnp.where(last_h, r + 1, r)
            nh = jnp.where(last_h, 0, jnp.where(last_q, h + 1, h))
            pl.when(nr < R)(functools.partial(start, nr, nh, 0, 1 - slot))

        for c in copies(r, h, k, slot):
            c.wait()
        s = nt(qn_ref[0, 0], kv_buf[slot, :, :D]) \
            + nt(qr_ref[0, 0], kr_buf[slot])
        q_pos = off + i * tq + jax.lax.broadcasted_iota(
            jnp.int32, (tq, 1), 0)
        k_pos = k * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        # every block is masked: leaving the blocks under the diagonal
        # unmasked read 0.1 to 0.5 % SLOWER on the chip (PERF.md, PR 45)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        softmax_step(s, kv_buf[slot, :, D:], m_ref, l_ref, acc_ref)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, blocks, body, slot_ref[0])
    l = jnp.maximum(jnp.sum(l_ref[...], axis=1, keepdims=True), 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tq", "tk", "interpret"))
def _mla_prefill_impl(qn, qr, kv, kr, q_offset, tq, tk, interpret):
    R, H, C, D = qn.shape
    rope = kr.shape[2]

    def query_spec(width):
        return pl.BlockSpec((1, 1, tq, width),
                            lambda r, h, i, off: (r, h, i, 0))

    kernel = functools.partial(_prefill_kernel, tq=tq, tk=tk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, H, C // tq),
            in_specs=[query_spec(D), query_spec(rope),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, tq, D),
                                   lambda r, h, i, off: (r, i, h)),
            scratch_shapes=[
                pltpu.VMEM((2, tk, 2 * D), kv.dtype),
                pltpu.VMEM((2, tk, rope), kr.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((tq, LANES), jnp.float32),
                pltpu.VMEM((tq, math.gcd(tk, LANES)), jnp.float32),
                pltpu.VMEM((tq, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, C, H * D), qn.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mla_prefill",
    )(q_offset.astype(jnp.int32), qn, qr, kv, kr)


def mla_prefill(qn, qr, kv, kr, q_offset):
    """Expanded latent attention of a chunk: ``qn (R, H, C, D)`` and ``qr
    (R, H, C, rope)``, both scaled, query ``i`` of row ``r`` at
    ``q_offset[r] + i``; ``kv (R, L, H x 2D)`` the expanded keys and values,
    head ``h``'s keys in columns ``[2hD, 2hD + D)`` and its values in the
    ``D`` after them (the value's width is the key's nope width); ``kr (R,
    L, rope)`` the one rotary key a position. Causal: a query reads the
    positions up to its own. ``L`` is a multiple of the key block and ``C``
    of the query block (``prefill_tiles``). The grid is ``(R, H, C //
    tq)``; how many key blocks a step walks is read from ``q_offset`` when
    the call runs, row by row. Returns ``(R, C, H x D)``."""
    tq, tk = prefill_tiles(qn.shape[2], kr.shape[1])
    return _mla_prefill_impl(qn, qr, kv, kr, q_offset, tq=tq, tk=tk,
                             interpret=_use_interpret())

"""What the decode kernels that issue their own copies share: the walk over
a row's live pages, the online softmax's carry, and the sizes both are cut
to.

Three kernels take a ROW a grid step, leave the pools whole in HBM and copy
a row's LIVE pages themselves, a block of pages into one half of a VMEM
scratch while the other half is computed (``walk_live_pages``):
``paged_flash_attention._decode_kernel`` (``%paged_window`` at heads of
128: zaya, ouro), ``dsa_decode._select_kernel`` and ``dsa_decode.
_window_kernel`` (``%dsa_decode_select``, ``%dsa_decode_window``: keye).
``mla_attention._latent_decode_kernel`` (``%mla_latent_decode``: joyai) has
the same walk written out in its body (ROADMAP.md D17). All of them, and
``mla_attention._prefill_kernel``, carry the softmax the same way
(``init_carry``, ``softmax_step``): a running max replicated over the
lanes, a denominator kept as a sum a lane until the last block, the
accumulator in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import NEG_INF

__all__ = ["LANES", "decode_tiles", "walk_live_pages", "init_carry",
           "softmax_step", "prec", "nt"]

# online-softmax m/l scratch is lane-replicated to the TPU register
# width (the flash-kernel convention): every lane of a row holds the
# same running max / denominator, so the elementwise update needs no
# cross-lane reduction beyond the score-block max itself
LANES = 128
# keys a block of a walk: one buffer of the kernel's scratch and the grain
# of its copies (PERF.md, PR 34: of 2, 4, 8 and 16 pages of 128 a block
# joyai's call read 1.00, 0.72, 0.61 and 0.61 ms where its copies alone take
# 0.58; under 8 the loop's own work on a block's copies shows)
BLOCK_KEYS = 1024


def decode_tiles(P, page_size):
    """Pages a block of a walk, from the shapes alone: 1,024 keys, so pages
    of 128 positions go 8 a block (one whole tile of the selection's
    scratch), and never more than a row's table has."""
    return max(1, min(P, BLOCK_KEYS // page_size))


def prec(dtype):
    # a process-wide "highest" matmul precision (the float32 parity tests
    # set it) is not one Mosaic takes for bfloat16 operands
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def nt(a, b):
    """``a (m, d) . b (n, d)^T`` in float32."""
    return jax.lax.dot_general(
        a.astype(b.dtype), b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec(b.dtype))


def init_carry(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def softmax_step(s, v, m_ref, l_ref, acc_ref):
    """One block of the online softmax: scores ``s (rows, cols)`` float32
    (masked already), values ``v (cols, D)``. The denominator stays a sum
    a lane until the last block: adding lane groups is elementwise, a sum a
    row is not."""
    lw = l_ref.shape[-1]
    m_prev = m_ref[...]                            # (rows, LANES)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_new = alpha[:, :lw] * l_ref[...]
    for c in range(s.shape[1] // lw):
        l_new = l_new + p[:, c * lw:(c + 1) * lw]
    l_ref[...] = l_new
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec(v.dtype))
    m_ref[...] = m_new


def walk_live_pages(pt_ref, slot_ref, live_pages, block, page_copies,
                    compute):
    """The copies of a grid step that is row ``b`` of ``B``, sequential.
    ``live_pages(r)`` pages of row ``r`` are walked in blocks of ``block``;
    ``page_copies(page, slot, t)`` are the copies that bring pool page
    ``page`` to place ``t`` of buffer ``slot``. Block ``k + 1`` is started
    before block ``k`` is waited for and ``compute(k, slot)`` runs; a row's
    last block starts the next live row's first, so no row waits for its
    first pages. The slot carries over from row to row in ``slot_ref``."""
    b = pl.program_id(0)
    B = pt_ref.shape[0]

    def copies(r, k, slot, do):
        # what lies past the live pages in the buffer is never computed
        n = live_pages(r) - k * block
        for t in range(block):
            @pl.when(t < n)
            def _copy():
                for c in page_copies(pt_ref[r, k * block + t], slot, t):
                    do(c)

    def start_first_block_after(r, slot):
        nxt = jax.lax.while_loop(
            lambda r: (r < B) & (live_pages(jnp.minimum(r, B - 1)) == 0),
            lambda r: r + 1, r + 1)

        @pl.when(nxt < B)
        def _start():
            copies(nxt, 0, slot, lambda c: c.start())

    @pl.when(b == 0)
    def _first_row():
        slot_ref[0] = 0
        start_first_block_after(-1, 0)

    blocks = pl.cdiv(live_pages(b), block)

    def body(k, slot):
        @pl.when(k + 1 < blocks)
        def _next_block():
            copies(b, k + 1, 1 - slot, lambda c: c.start())

        @pl.when(k + 1 == blocks)
        def _next_row():
            start_first_block_after(b, 1 - slot)

        copies(b, k, slot, lambda c: c.wait())
        compute(k, slot)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, blocks, body, slot_ref[0])

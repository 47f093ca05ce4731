"""Paged flash attention: Pallas kernels that read the serving stack's
``(num_pages, page_size, H, D)`` KV pools IN PLACE.

The continuous batcher's decode path (PR 8) gathers K/V through the page
table into a materialized ``(B, P*page_size, H, D)`` view and then runs
dense attention over it — two full copies of every cached key/value per
decoded token, plus an O(L) score row in HBM. These kernels close that
gap (the FlashAttention/PagedAttention fusion, ROADMAP item 2): the page
table rides the grid as a scalar-prefetch operand, each grid step DMAs
one page directly out of the pool, and an online-softmax carry in VMEM
scratch accumulates across the sequential page dimension — the gather
never materializes and scores never leave VMEM.

One kernel, two entry points:

- ``paged_window_attention`` — an S-token query window per row, each
  query ``i`` at absolute position ``q_offset[b] + i`` (causal within
  and across the window). Grid ``(B, pages_per_row)``; row ``b``'s step
  ``p`` reads pool block ``page_table[b, p]``. This is the
  q_offset-aware PREFILL variant: suffix-only prefix-cache replay and
  speculative verification both score a short window against a long
  paged history in one pass.
- ``paged_decode_attention`` — single query token per row (the decode
  hot path): the window kernel at ``S = 1`` with ``q_offset = pos``. A
  separate 2-D ``(H, D)`` query kernel is not expressible for the chip:
  Mosaic refuses a batched ``dot_general`` whose lhs has no free
  dimension, so the query keeps its unit window axis.

- ``paged_selected_window_attention`` — the window over a SELECTED set
  of cached positions (learned sparse attention), for grouped-query
  heads: ``Hq`` query heads over ``Hkv`` key/value heads. A mask ``(B, C,
  L)`` says which cached positions each query reads; it rides the grid
  in blocks beside the pages. Grid ``(B, query blocks, key blocks)``: a
  step takes a block of several pages (1,024 keys), relays it head-major
  once, gives each key/value head one product with all the query rows
  that read it and updates the softmax carry once; blocks past the last
  one a query block can see are neither fetched nor computed. The pools'
  layout in HBM is the serving stack's, as for the other two.

All keep ``MXTPU_FLASH_INTERPRET`` (force/forbid/auto, shared with
``flash_attention.py``) and ship a dense jnp reference
(``*_reference``) used by the tolerance tests; the MODULE-level
fallback when the kernel gate is off is the attention layer's existing
gather+dense path, which stays bitwise-unchanged. ``MXTPU_FLASH_PAGED``
gates routing: force on (``1``/``force``/``on``), force off
(``0``/``off``/``false``), default auto = on only when the backend is a
real TPU (the CPU rig would only ever run the kernels interpreted,
which is slower than the dense path it replaces).
"""

from __future__ import annotations

import functools
import math
import os as _os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _partitionable, _use_interpret
from .flash_attention import _NEG_INF

__all__ = ["paged_decode_attention", "paged_window_attention",
           "paged_decode_reference", "paged_window_reference",
           "paged_selected_window_attention",
           "paged_selected_window_reference", "flash_paged_enabled"]

# online-softmax m/l scratch is lane-replicated to the TPU register
# width (the flash-kernel convention): every lane of a row holds the
# same running max / denominator, so the elementwise update needs no
# cross-lane reduction beyond the score-block max itself
_LANES = 128


def flash_paged_enabled() -> bool:
    """``MXTPU_FLASH_PAGED``: route paged attention through the Pallas
    kernels (``1``/``true``/``force``/``on``), keep the dense
    gather fallback (``0``/``false``/``off``), or — default auto —
    kernels only on a real TPU backend (interpreted kernels on the CPU
    rig are slower than the dense path they replace). Under a
    multi-device mesh a compiled kernel is never routed to
    (``_partitionable``): the gather path is what XLA can partition."""
    v = _os.environ.get("MXTPU_FLASH_PAGED", "").strip().lower()
    if v in ("0", "false", "off") or not _partitionable():
        return False
    if v in ("1", "true", "force", "on"):
        return True
    return jax.default_backend() == "tpu"


def _window_kernel(pt_ref, off_ref, vl_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, page_size, sm_scale,
                   window, shared_position=False):
    """Grid (B, pages_per_row), pages sequential per row: one pool page
    per step, online-softmax carry (m, l, acc) in VMEM scratch. An
    S-query window rides each row: query ``i`` sits at absolute position
    ``off + i`` and masks keys above it; queries ``>= vl`` are padding
    and finalize to zero. With ``shared_position`` every query of the
    window sits at ``off`` (the grouped query heads of ONE token)."""
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off = off_ref[b]
    vl = vl_ref[b]

    # the window's LAST query (off + window - 1) bounds what any query
    # can see — pages wholly past it contribute nothing (page 0 is never
    # skipped, so l is never all-zero for a live row)
    @pl.when(p * page_size <= off + (0 if shared_position else window - 1))
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)          # (H, S, D)
        k = k_ref[0].astype(jnp.float32)          # (ps, H, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        ) * sm_scale                               # (H, S, ps)
        key_abs = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_size), 2)
        q_abs = off if shared_position else \
            off + jax.lax.broadcasted_iota(jnp.int32, (1, window, 1), 1)
        s = jnp.where(key_abs <= q_abs, s, _NEG_INF)
        m_prev = m_ref[...]                        # (H, S, LANES)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=2, keepdims=True)  # (H, S, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p_act = jnp.exp(s - m_new[:, :, :1])       # (H, S, ps)
        l_new = alpha * l_prev + jnp.sum(p_act, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :, :1] + jax.lax.dot_general(
            p_act, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )                                          # (H, S, D)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :, :1], 1e-30)
        out = acc_ref[...] / l                     # (H, S, D)
        live = jax.lax.broadcasted_iota(
            jnp.int32, (1, window, 1), 1) < vl
        o_ref[0] = jnp.where(live, out, 0.0).astype(o_ref.dtype)


def paged_window_attention(q, k_pool, v_pool, page_table, q_offset,
                           window_vl=None, *, sm_scale,
                           shared_position=False):
    """S-token query window over a paged history, pools read in place.

    q ``(B, S, H, D)``; query ``i`` of row ``b`` sits at absolute
    position ``q_offset[b] + i`` and attends keys at positions ``<=``
    it (causal across the cached history AND within the window — the
    caller has already scattered the window's K/V into the pool).
    ``window_vl`` ``(B,)`` optionally marks queries ``>= window_vl[b]``
    as padding (their outputs are zeroed). ``shared_position`` puts every
    query of the window at ``q_offset[b]`` (``paged_decode_attention``'s
    grouped query heads). Returns ``(B, S, H, D)``."""
    B, S, H, D = q.shape
    ps = k_pool.shape[1]
    P = page_table.shape[1]
    if window_vl is None:
        window_vl = jnp.full((B,), S, jnp.int32)
    qt = jnp.swapaxes(q, 1, 2)                     # (B, H, S, D)
    kernel = functools.partial(_window_kernel, page_size=ps,
                               sm_scale=sm_scale, window=S,
                               shared_position=bool(shared_position))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, H, S, D),
                         lambda b, p, pt, off, vl: (b, 0, 0, 0)),
            pl.BlockSpec((1, ps, H, D),
                         lambda b, p, pt, off, vl: (pt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, ps, H, D),
                         lambda b, p, pt, off, vl: (pt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, S, D),
                               lambda b, p, pt, off, vl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, S, _LANES), jnp.float32),
            pltpu.VMEM((H, S, _LANES), jnp.float32),
            pltpu.VMEM((H, S, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=_use_interpret(),
    )(page_table.astype(jnp.int32), q_offset.astype(jnp.int32),
      window_vl.astype(jnp.int32), qt, k_pool, v_pool)
    return jnp.swapaxes(out, 1, 2)                 # (B, S, H, D)


def paged_decode_attention(q, k_pool, v_pool, page_table, pos, *,
                           sm_scale):
    """Single-token paged attention, pools read in place.

    q ``(B, H, D)``; pools ``(num_pages, page_size, Hkv, D)``;
    ``page_table`` ``(B, P)`` int32; ``pos`` ``(B,)`` int32 — row ``b``
    attends keys at absolute positions ``<= pos[b]`` (the caller has
    already scattered position ``pos`` into the pool). Returns
    ``(B, H, D)``. Where ``H`` is ``G`` times ``Hkv`` (grouped-query
    heads: query head ``i`` reads key/value head ``i // G``) the ``G``
    heads of a group ride the kernel's window axis, all at ``pos``."""
    B, H, D = q.shape
    Hkv = k_pool.shape[2]
    if H == Hkv:
        return paged_window_attention(q[:, None], k_pool, v_pool,
                                      page_table, pos,
                                      sm_scale=sm_scale)[:, 0]
    qg = jnp.swapaxes(q.reshape(B, Hkv, H // Hkv, D), 1, 2)
    out = paged_window_attention(qg, k_pool, v_pool, page_table, pos,
                                 sm_scale=sm_scale, shared_position=True)
    return jnp.swapaxes(out, 1, 2).reshape(B, H, D)


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
    scratch = Hkv * rows * (2 * _LANES + D) * 4 + 2 * Hkv * kb * D * itemsize
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
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
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

    def last_seen(b, i, off):       # the block's last query's position
        return off[b] + (i + 1) * tq - 1

    def page(t):                    # an unseen page re-reads nothing
        return lambda b, i, j, pt, off: (pt[b, jnp.minimum(
            j * pages + t, jnp.minimum(last_seen(b, i, off) // ps, P - 1))],
            0, 0, 0)

    def mask_block(b, i, j, pt, off):
        return (b, i, jnp.minimum(
            j, jnp.minimum(last_seen(b, i, off) // kb, nkb - 1)))

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
            pltpu.VMEM((Hkv, G * tq, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, G * tq, math.gcd(kb, _LANES)), jnp.float32),
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


def paged_selected_window_attention(q, k_pool, v_pool, page_table,
                                    q_offset, mask, *, sm_scale):
    """A ``C``-query window a row over a SELECTED set of its cached
    positions, pools read in place. ``q (B, C, Hq, D)``; pools
    ``(num_pages, page, Hkv, D)`` with ``Hq`` a multiple of ``Hkv`` (query
    head ``i`` reads key/value head ``i // (Hq // Hkv)``); ``mask (B, C,
    L)`` true where query ``c`` of row ``b`` reads cached position ``l``
    (the caller keeps it causal: nothing past ``q_offset[b] + c``).
    Returns ``(B, C, Hq * D)``."""
    # 256 queries and 8 pages of 128 a block read a chunk's last 2,048
    # queries over 16k keys in 4.9 ms on a v5e; a page a step, relaid for
    # every query head, took 13.2 (PERF.md, PR 30)
    tq, pages = _selected_window_tiles(q.shape[1], page_table.shape[1],
                                       k_pool.shape[1])
    return _dsa_selected_window_impl(q, k_pool, v_pool, page_table,
                                     q_offset, mask, sm_scale=sm_scale,
                                     tq=tq, pages=pages)


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
    probs = jax.nn.softmax(jnp.where(mask[:, None, None], s, _NEG_INF), -1)
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
    s = jnp.where(mask, s, _NEG_INF)
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
    s = jnp.where(key_abs <= q_abs, s, _NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhsl,blhd->bshd", probs, v)
    live = jnp.arange(S)[None, :, None, None] < \
        window_vl[:, None, None, None]
    return jnp.where(live, out, 0.0).astype(q.dtype)

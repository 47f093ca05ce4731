"""Learned sparse attention over a paged cache, and attention under a mask.

Two things live here, the second serving the first and the dense nets both:

THE LEARNED SELECTION (keye's alone). An indexer scores every cached
position for a query, the ``topk`` best are selected, and attention reads
the selected set alone. ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])
/ sqrt(Di * J)`` for ``s <= t``; the selected set of ``t`` is every ``s <=
t`` while ``t + 1 <= topk``, else the ``topk`` positions of largest ``I[t,
.]``, ties to the lower position. Below ``topk + 1`` cached positions both
programs are plain causal attention. ``window_index_scores``,
``select_mask``, ``window_select`` for a prefill chunk; ``decode_select``
and ``selected_decode`` for the decode step.

ATTENTION UNDER A MASK, in the two shapes the serving plane has:

- the WINDOW (a prefill chunk), ``selected_window_attention``: ``C``
  queries a row at ``q_offset + i`` over the row's pages, which already
  hold the chunk's own keys, masked to ``(R, C, L)``. keye hands it the
  selection; granite and ouro a plain causal mask (zaya's chunk goes
  through ``ops/paged.window_attention``, whose ``jax.numpy`` half is this
  function under a causal mask).
- DECODE, ``selected_decode_attention``: one query a row over ``K``
  positions gathered from the pools BY TOKEN (not by page). keye hands it
  the ``topk`` positions it selected; ``ops/paged.decode_attention`` every
  cached position and a causal mask, which is the dense nets' decode step
  off the chip.

A row's places in the pools (``token_rows``, ``write_rows``,
``gather_row_pages``, ``kv_block``) and the answer to whether the kernels
run (``kernels_on``) are ``ops/paged.py``'s.

Which form runs where. Where the paged kernels are on
(``paged.kernels_on()``: a TPU, no multi-device mesh) the window is two
Pallas kernels a layer: ``ops/pallas/index_select.dsa_index_select`` (128
queries a grid step keep their index scores in VMEM from the products to
the ``topk``-th largest, found a bit a pass, and settle the ties at the
cut; what leaves the chip is the selection, and nothing is scored for a
query block that stands below ``topk``) and
``ops/pallas/paged_flash_attention.paged_selected_window_attention``
(dense and masked, 256 queries by 1,024 keys a step, every key/value head
against all its query heads in one product). On the CPU, under a mesh, or
where a window does not divide into the kernels' blocks, the same
functions are ``jax.numpy``: ``window_index_scores`` (a loop over key
blocks into one ``(R, C, L)`` float32 array), ``select_mask`` (a radix
select, two bits a pass over that array: ``lax.top_k`` is a full sort on
the TPU) and a flash loop over gathered keys; they are the kernels'
references. The decode step takes the chunk's form at one query a row, two
kernels a layer of ``ops/pallas/dsa_decode.py`` (PR 39), where the paged
kernels are on and a row can hold more than ``topk`` positions:
``dsa_decode_select`` (a row a grid step writes its own indexer key into
its page, scores its LIVE pages through the page table, keeps the 16,640
scores in VMEM to the ``topk``-th largest and hands back one int8 a
position) and ``dsa_decode_window`` (a page of K and V as its (key, head)
rows, the row's query heads against all of them in one product a page,
foreign heads and unselected keys masked; both kernels issue their own
copies, two buffers deep, and an inactive row costs nothing; 0.121 and
0.403 ms a call in keye's cell on a v5e where the forms below took 0.36
and 1.03: PERF.md, PR 39; the selected window's kernel at one query, 8
query rows a head, relays every block head-major and read 1.26 ms alone
against this form's 0.36, so it was not kept). Else it is
``write_rows`` + ``decode_select`` (a gathered copy of the row's indexer
keys, one product, ``lax.top_k``: a sort of every row on the TPU) +
``selected_decode_attention`` (two gathers by token, a dense softmax): the
CPU's and a mesh's form, and the kernels' references.

Everything accumulates in float32 (scores, softmax); the pools and the
queries keep their own dtype.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import paged

NEG = -1e30


# ----------------------------------------------------------------- window
def window_index_scores(qi, wi, ki_all, q_pos, n_blocks, block):
    """``I (R, C, L)`` float32 of window queries ``qi (R, C, J, Di)`` with
    head weights ``wi (R, C, J)`` against the row's cached indexer keys
    ``ki_all (R, L, Di)``; -inf where ``s > q_pos`` and past the
    ``n_blocks`` blocks walked."""
    R, C, J, Di = qi.shape
    L = ki_all.shape[1]
    scale = 1.0 / math.sqrt(Di * J)
    wi = wi.astype(jnp.float32)

    def body(j, buf):
        kb = jax.lax.dynamic_slice(ki_all, (0, j * block, 0),
                                   (R, block, Di))
        hit = jax.nn.relu(jnp.einsum("rcjd,rsd->rcjs", qi, kb,
                                     preferred_element_type=jnp.float32))
        blk = jnp.einsum("rcjs,rcj->rcs", hit, wi) * scale
        return jax.lax.dynamic_update_slice(buf, blk, (0, 0, j * block))

    scores = jax.lax.fori_loop(
        0, n_blocks, body, jnp.full((R, C, L), -jnp.inf, jnp.float32))
    seen = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]
    return jnp.where(seen, scores, -jnp.inf)


def _ordered_bits(x):
    """float32 as uint32 whose order is the floats' (-inf lowest)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(key, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def kth_largest_bits(keys, k):
    """The ``k``-th largest of each row of ``keys (..., L)`` uint32, as
    ``(..., 1)``: the largest ``T`` with ``count(keys >= T) >= k``, found
    two bits a pass from the top (sixteen counting passes over the rows;
    a sort of every row, which is what ``lax.top_k`` lowers to on the
    TPU, costs many times that at 2,048 rows of 16,640)."""
    def body(i, prefix):
        shift = (30 - 2 * i).astype(jnp.uint32)
        best = prefix
        for c in (1, 2, 3):        # counts fall as the candidate rises
            cand = prefix | (jnp.uint32(c) << shift)
            enough = jnp.sum(keys >= cand, -1, keepdims=True) >= k
            best = jnp.where(enough, cand, best)
        return best

    return jax.lax.fori_loop(
        0, 16, body, jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))


def select_mask(scores, q_pos, topk):
    """The selected set of each query as a mask ``(R, C, L)``: every seen
    position while there are at most ``topk``, else the ``topk`` of largest
    score, ties to the lower position. ``scores`` is -inf where unseen."""
    L = scores.shape[-1]
    seen = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]
    if L <= topk:
        return seen
    keys = _ordered_bits(scores)
    kth = kth_largest_bits(keys, topk)
    above = keys > kth
    tie = (keys == kth) & seen
    room = topk - jnp.sum(above, -1, keepdims=True)
    # nearly always the ties at the cut are exactly as many as there is
    # room for (one key equals the k-th value): only where a row has more
    # does their order matter, and the running count is paid for
    crowded = jnp.any(jnp.sum(tie, -1, keepdims=True) > room)
    return jax.lax.cond(
        crowded,
        lambda: seen & (above | (tie & (jnp.cumsum(tie, -1) <= room))),
        lambda: seen & (above | tie))


def window_select(qi, wi, ki_all, q_pos, n_blocks, block, topk):
    """The selected set of each window query as a mask ``(R, C, L)``:
    ``select_mask`` of ``window_index_scores``. Where the paged kernels are
    on (``paged.kernels_on()``: a TPU, no multi-device mesh) and the
    window divides into its blocks, ONE Pallas kernel
    (``ops/pallas/index_select.dsa_index_select``) keeps a query block's
    scores on the chip from the products to the k-th largest, settles
    the ties at the cut and hands back the set; else the two ``jax.numpy`` functions above, which are
    the CPU's and a mesh's form and the kernel's reference. The queries of
    a row sit at consecutive positions from ``q_pos[:, 0]``."""
    from .pallas import index_select as _ixs

    C, L = q_pos.shape[1], ki_all.shape[1]
    if L <= topk or not paged.kernels_on() \
            or _ixs.index_select_tiles(C, L) is None:
        return select_mask(
            window_index_scores(qi, wi, ki_all, q_pos, n_blocks, block),
            q_pos, topk)
    return _ixs.dsa_index_select(qi, wi, ki_all, q_pos[:, 0],
                                 n_blocks * block, topk) != 0


def selected_window_attention(q, k_pool, v_pool, page_tables, q_offset,
                              mask, n_blocks, block, sm_scale):
    """Attention of window queries ``q (R, C, Hq, D)`` at ``q_offset[r] +
    c`` over the row's cached positions that ``mask (R, C, L)`` selects;
    query head ``i`` reads key/value head ``i // (Hq // Hkv)``. The Pallas
    kernel reads the pools in place where the paged kernels are on
    (``paged.kernels_on()``: a TPU, no multi-device mesh); else a flash
    loop in jnp over the first ``n_blocks`` blocks of ``block`` gathered
    keys. Returns ``(R, C, Hq * D)`` in ``q``'s dtype."""
    if paged.kernels_on():
        from .pallas import paged_flash_attention as _pfa

        return _pfa.paged_selected_window_attention(
            q, k_pool, v_pool, page_tables, q_offset, mask,
            sm_scale=sm_scale)
    R, C, Hq, D = q.shape
    k_all = paged.gather_row_pages(paged.by_head(k_pool, D), page_tables)
    v_all = paged.gather_row_pages(paged.by_head(v_pool, D), page_tables)
    Hkv = k_all.shape[2]
    G = Hq // Hkv
    qg = q.reshape(R, C, Hkv, G, D)

    def body(j, carry):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice(k_all, (0, j * block, 0, 0),
                                   (R, block, Hkv, D))
        vb = jax.lax.dynamic_slice(v_all, (0, j * block, 0, 0),
                                   (R, block, Hkv, D))
        mb = jax.lax.dynamic_slice(mask, (0, 0, j * block),
                                   (R, C, block))[:, None, None]
        s = jnp.einsum("rcngd,rsnd->rngcs", qg, kb,
                       preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(mb, s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.where(mb, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, -1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "rngcs,rsnd->rngcd", p.astype(v_all.dtype), vb,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((R, Hkv, G, C), NEG, jnp.float32)
    l0 = jnp.zeros((R, Hkv, G, C), jnp.float32)
    a0 = jnp.zeros((R, Hkv, G, C, D), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]          # (R,Hkv,G,C,D)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(R, C, Hq * D) \
        .astype(q.dtype)


# ----------------------------------------------------------------- decode
def decode_select(qi, wi, ik_pool, page_tables, pos, topk):
    """``(positions (B, K) int32, valid (B, K))`` of the selected set of
    one query a row at position ``pos (B,)``: ``qi (B, J, Di)``, ``wi (B,
    J)``, the row's indexer keys read through ``page_tables``. ``K`` is
    ``topk``, or every cached position where a row holds no more."""
    B, J, Di = qi.shape
    ki = paged.gather_row_pages(ik_pool, page_tables)       # (B, L, Di)
    L = ki.shape[1]
    hit = jax.nn.relu(jnp.einsum("bjd,bsd->bjs", qi, ki,
                                 preferred_element_type=jnp.float32))
    scores = jnp.einsum("bjs,bj->bs", hit, wi.astype(jnp.float32)) \
        / math.sqrt(Di * J)
    seen = jnp.arange(L)[None, :] <= pos[:, None]
    if L <= topk:
        return jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                (B, L)), seen
    vals, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), topk)
    return idx.astype(jnp.int32), vals > -jnp.inf


def selected_decode_attention(q, k_pool, v_pool, page_tables, positions,
                              valid, sm_scale):
    """One query a row, ``q (B, Hq, D)``, over the ``positions (B, K)`` of
    its row that ``valid`` marks, gathered from the pools by token.
    Returns ``(B, Hq * D)``."""
    B, Hq, D = q.shape
    page_size, Hkv = k_pool.shape[1], k_pool.shape[2]
    rows = paged.token_rows(page_tables, positions, page_size)
    ks = k_pool.reshape((-1, Hkv, D))[rows]                  # (B, K, Hkv, D)
    vs = v_pool.reshape((-1, Hkv, D))[rows]
    qg = q.reshape(B, Hkv, Hq // Hkv, D)
    s = jnp.einsum("bngd,bsnd->bngs", qg, ks,
                   preferred_element_type=jnp.float32) * sm_scale
    p = jax.nn.softmax(jnp.where(valid[:, None, None, :], s, NEG), -1)
    out = jnp.einsum("bngs,bsnd->bngd", p.astype(vs.dtype), vs,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq * D).astype(q.dtype)


def selected_decode(q, qi, wi, ki, k_pool, v_pool, ik_pool, page_tables,
                    rows, pos, active, topk, sm_scale):
    """The decode step's attention of one layer: one query a row, ``q (B,
    Hq, D)`` with indexer query ``qi (B, J, Di)`` and head weights ``wi (B,
    J)`` at ``pos (B,)``, selects among the row's cached positions and
    attends over the selected set. ``k_pool`` and ``v_pool`` hold the
    row's own position already; its indexer key ``ki (B, Di)`` is written
    here, at ``rows`` of the flattened pool (``write_rows``). Returns
    ``(attention (B, Hq * D), ik_pool, keys the active rows selected)``.

    Where the paged kernels are on (``paged.kernels_on()``: a TPU, no
    multi-device mesh) and a row can hold more than ``topk`` positions, the
    selection never becomes positions: ``dsa_decode_select`` writes the
    row's key into its page, keeps the row's scores on the chip to the
    ``topk``-th largest and hands back a mask, and ``dsa_decode_window``
    walks the row's live pages in place under it (no sort, no gathered
    copy of the indexer keys, no gather by token; an inactive row writes
    nothing, not even to the trash page).
    Else ``write_rows``, ``decode_select`` and ``selected_decode_attention``,
    the CPU's and a mesh's form and the kernels' references."""
    from .pallas import dsa_decode as _dec

    L = page_tables.shape[1] * ik_pool.shape[1]
    if L > topk and paged.kernels_on():
        # an inactive row stands below 0: it reads nothing, selects nothing
        at = jnp.where(active, pos, -1)
        mask, ik_pool = _dec.dsa_decode_select(qi, wi, ki, ik_pool,
                                               page_tables, at, topk)
        attn = _dec.dsa_decode_window(q, k_pool, v_pool, page_tables, at,
                                      mask, sm_scale=sm_scale)
        return attn, ik_pool, jnp.sum(mask, dtype=jnp.int32)
    ik_pool = paged.write_rows(ik_pool, rows, ki)
    picked, valid = decode_select(qi, wi, ik_pool, page_tables, pos, topk)
    attn = selected_decode_attention(q, k_pool, v_pool, page_tables, picked,
                                     valid, sm_scale)
    return attn, ik_pool, jnp.sum(jnp.logical_and(valid, active[:, None]))

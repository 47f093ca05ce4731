"""What every net with a paged cache takes from ``ops/``: a row's places in
the pools, dense causal attention over them, and the ONE answer to whether
the Pallas kernels run.

The serving plane keeps a layer's keys and values in pools and a page
table ``(R, P)`` a row. A pool is declared one of three ways, the same
numbers in the same order, told apart by their shapes alone:

- ``(num_pages, page, heads, D)``, the plain one;
- ``(num_pages, page x heads, D)``, a page's (key, head) rows on one axis,
  as the kernels of heads of whole lanes read it (``D`` a multiple of 128:
  zaya, PR 40);
- ``(num_pages, page, heads x D)``, a page's positions on the rows and
  (head, d) on the lanes, as the kernels read heads NARROWER than the
  lanes (granite's and transformer-big's heads of 64, PR 46): the last
  axis is whole lanes, so the chip keeps the pool row-major as declared
  and no program copies it to another layout and back.

A net says what it attends over; which form runs is decided here and in
the two modules beside this one, from the platform and the mesh, the pool's
declaration and the heads' width, and from nothing else:

- ``kernels_on()``: a TPU and no multi-device mesh. ``ops/paged.py``,
  ``ops/sparse_attention.py`` (learned selection, attention under a mask),
  ``ops/mla.py`` (latent attention), the attention layer
  (``gluon/nn/attention.py``) and the scheduler's ``infer/flash_kernel``
  gauge ask it, as ``paged.kernels_on()`` while they are traced, so a test
  that sets the answer (``tests/conftest.py::paged_kernels``) holds for all
  of them.
- ``decode_attention``: one query a row over every cached position up to
  its own.
- ``window_attention``: a chunk of queries a row, causal, through the paged
  window kernel.
- ``token_rows``, ``write_rows``, ``gather_row_pages``, ``kv_block``: where
  a position lies in a pool, and what the ``jax.numpy`` forms gather.

The kernels are ``ops/pallas/paged_flash_attention.py``'s; the ``jax.numpy``
forms are ``ops/sparse_attention.py``'s two masked attentions under a plain
causal mask: the CPU's and a mesh's form, and the kernels' references.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .pallas import _partitionable
from .pallas import paged_flash_attention as _pfa

QUERY_BLOCK = 128   # window positions a call of the paged kernel takes


def kernels_on() -> bool:
    """Do the paged attention kernels run where this is traced? On a TPU,
    unless a multi-device mesh is in scope: GSPMD cannot partition a Mosaic
    kernel (``ops/pallas._partitionable``), and on the CPU the kernels
    would run interpreted, slower than the ``jax.numpy`` forms they
    replace."""
    return jax.default_backend() == "tpu" and _partitionable()


# ------------------------------------------------------------ a row's places
def kv_block(length: int, chunk: int = 512) -> int:
    """Keys a block of the window loops: the published ``kv_chunk_size``
    where it divides a row's cached length, else their largest common
    divisor."""
    return math.gcd(int(length), int(chunk))


def gather_row_pages(pool, page_tables):
    """``pool (num_pages, page, ...)`` through ``page_tables (R, P)`` as
    ``(R, P * page, ...)``: a row's cached positions in order."""
    got = pool[page_tables]
    return got.reshape((got.shape[0], got.shape[1] * got.shape[2])
                       + got.shape[3:])


def token_rows(page_tables, positions, page_size):
    """Rows of the flattened pool ``(num_pages * page, ...)`` that hold
    ``positions (R, K)`` of each row: the gather by token."""
    page = jnp.take_along_axis(page_tables, positions // page_size, axis=1)
    return page * page_size + positions % page_size


def write_rows(pool, rows, values):
    """``pool`` with ``values (N, ...)`` written at flattened rows ``rows
    (N,)`` (rows of the trash page for what must not land). A pool
    declared in three axes takes ``values (N, heads, D)``, as many axes as
    its own: where it is ``(num_pages, page x heads, D)`` position ``r``'s
    heads are rows ``r x heads`` onward, where it is ``(num_pages, page,
    heads x D)`` they lie side by side on row ``r``."""
    if values.ndim == pool.ndim and pool.shape[-1] != values.shape[-1]:
        values = values.reshape(values.shape[0], -1)
    elif values.ndim == pool.ndim:
        heads = values.shape[1]
        rows = (rows[:, None] * heads
                + jnp.arange(heads, dtype=rows.dtype)).reshape(-1)
        values = values.reshape((-1,) + values.shape[2:])
    flat = pool.reshape((-1,) + pool.shape[2:])
    return flat.at[rows].set(values.astype(pool.dtype)).reshape(pool.shape)


def by_head(pool, D, kv_heads=None):
    """A pool of heads of ``D`` as ``(num_pages, page, heads, D)`` however
    it is declared, for the ``jax.numpy`` forms (off the chip a free
    view); ``kv_heads`` says the heads of one declared ``(num_pages, page
    x heads, D)``."""
    if pool.ndim == 4:
        return pool
    if pool.shape[2] != D:
        return pool.reshape(pool.shape[:2] + (-1, D))
    return pool.reshape(pool.shape[0], -1, kv_heads, D)


# ------------------------------------------------------------------- decode
def decode_attention(q, k_pool, v_pool, page_tables, pos, sm_scale,
                     kv_heads=None):
    """One query a row, ``q (B, Hq, D)`` at ``pos (B,)``, over every cached
    position of its row up to ``pos`` (the caller has written it); query
    head ``i`` reads key/value head ``i // (Hq // Hkv)``. Pools ``(num_pages,
    page, Hkv, D)`` or ``(num_pages, page, Hkv x D)``, or ``(num_pages,
    page x Hkv, D)`` with ``kv_heads`` saying ``Hkv``. Returns ``(B, Hq *
    D)``.

    Where the kernels run, ``paged_decode_attention`` reads the pools in
    place (a row's live pages walked at heads of 128, the pipeline's page
    operands at narrower heads, a page ``(positions, Hkv x D)`` as it is
    declared: its own choice). Else a row gathers every cached position by
    token and masks (``selected_decode_attention``)."""
    B, Hq, D = q.shape
    if kernels_on():
        return _pfa.paged_decode_attention(
            q, k_pool, v_pool, page_tables, pos, sm_scale=sm_scale,
            kv_heads=kv_heads).reshape(B, Hq * D)
    from . import sparse_attention as _dsa  # it stands on this module

    k_pool, v_pool = by_head(k_pool, D, kv_heads), \
        by_head(v_pool, D, kv_heads)
    L = page_tables.shape[1] * k_pool.shape[1]
    every = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    return _dsa.selected_decode_attention(
        q, k_pool, v_pool, page_tables, every, every <= pos[:, None],
        sm_scale)


# ------------------------------------------------------------------- window
def window_attention(q, k_pool, v_pool, page_tables, q_offset, real,
                     sm_scale, kv_heads=None, kv_chunk=512):
    """A chunk of queries a row, ``q (R, C, Hq, D)``, query ``c`` at
    ``q_offset[r] + c`` and the first ``real[r]`` of them real, each over
    the cached positions up to its own (the caller has written the
    chunk's); pools as for ``decode_attention``. Returns ``(R, C, Hq * D)``;
    what a padding query gets is the caller's to ignore.

    Where the kernels run, the paged window kernel reads the pools in
    place: the chunk in blocks of ``QUERY_BLOCK`` positions, each a row of
    the kernel's grid with the row's page table and its own offset, the
    query heads of a key/value head on the window axis beside the
    positions. Else a flash loop in ``jax.numpy`` over gathered keys in
    blocks of ``kv_block(L, kv_chunk)``, to the last block a real query
    sees (``selected_window_attention`` under a causal mask)."""
    R, C, Hq, D = q.shape
    if kernels_on():
        tq = math.gcd(C, QUERY_BLOCK)
        first = jnp.arange(C // tq, dtype=jnp.int32) * tq
        vl = jnp.clip(real[:, None] - first, 0, tq).reshape(-1)
        # a block of padding alone reads one page
        off = jnp.where(vl > 0, (q_offset[:, None] + first).reshape(-1), 0)
        out = _pfa.paged_window_attention(
            q.reshape((R * (C // tq), tq) + q.shape[2:]), k_pool, v_pool,
            jnp.repeat(page_tables, C // tq, axis=0), off, vl,
            sm_scale=sm_scale, kv_heads=kv_heads)
        return out.reshape(R, C, Hq * D)
    from . import sparse_attention as _dsa

    k_pool, v_pool = by_head(k_pool, D, kv_heads), \
        by_head(v_pool, D, kv_heads)
    L = page_tables.shape[1] * k_pool.shape[1]
    block = kv_block(L, kv_chunk)
    q_pos = q_offset[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    live = jnp.arange(C)[None, :] < real[:, None]
    last = jnp.max(jnp.where(live, q_pos, 0))
    n_blocks = jnp.minimum(last // block + 1, L // block)
    causal = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]
    return _dsa.selected_window_attention(
        q, k_pool, v_pool, page_tables, q_offset, causal, n_blocks, block,
        sm_scale)

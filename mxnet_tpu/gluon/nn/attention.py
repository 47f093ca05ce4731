"""Attention layers over the Pallas flash kernel.

API parity target: the reference's interleaved multi-head attention ops
(``src/operator/contrib/transformer.cc`` [unverified], used by GluonNLP
BERT) — one fused QKV projection, heads split internally. The score matrix
is never materialized (flash path), so long sequences are O(S) memory:
beyond-reference capability per SURVEY.md §5.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from ...base import MXNetError
from ..block import HybridBlock
from .basic_layers import Dense, Dropout

__all__ = ["MultiHeadAttention"]


class MultiHeadAttention(HybridBlock):
    """Fused multi-head attention.

    Parameters
    ----------
    units : total hidden size (= num_heads * head_dim)
    num_heads : number of attention heads
    dropout : attention output dropout rate
    use_bias : bias on projections
    self_attention : if True one fused QKV projection (interleaved layout,
        matching ``_contrib_interleaved_matmul_selfatt_*`` semantics)
    causal : apply causal mask (decoder self-attention)
    """

    def __init__(self, units, num_heads, dropout=0.0, use_bias=True,
                 self_attention=True, causal=False, flatten=False,
                 ring_axis=None, seq_mode="ring", **kwargs):
        super().__init__(**kwargs)
        if units % num_heads != 0:
            raise MXNetError(
                f"units {units} not divisible by num_heads {num_heads}"
            )
        if seq_mode not in ("ring", "ulysses"):
            raise MXNetError(f"unknown seq_mode {seq_mode!r}")
        self._units = units
        self._num_heads = num_heads
        self._head_dim = units // num_heads
        self._causal = causal
        self._self_attention = self_attention
        # sequence/context parallelism: name of the mesh axis the sequence
        # dim is sharded over; resolved against parallel.current_mesh() at
        # forward time. seq_mode picks the collective pattern: 'ring'
        # (K/V ppermute rotation) or 'ulysses' (head<->seq all_to_all,
        # needs num_heads % axis_size == 0)
        self._ring_axis = ring_axis
        self._seq_mode = seq_mode
        with self.name_scope():
            if self_attention:
                self.qkv_proj = Dense(3 * units, use_bias=use_bias,
                                      flatten=False, prefix="qkv_")
            else:
                self.q_proj = Dense(units, use_bias=use_bias, flatten=False,
                                    prefix="q_")
                self.k_proj = Dense(units, use_bias=use_bias, flatten=False,
                                    prefix="k_")
                self.v_proj = Dense(units, use_bias=use_bias, flatten=False,
                                    prefix="v_")
            self.out_proj = Dense(units, use_bias=use_bias, flatten=False,
                                  prefix="out_")
            self.drop = Dropout(dropout) if dropout else None

    def _split(self, x):
        # (B, S, units) -> (B, H, S, head_dim)
        B, S = x.shape[0], x.shape[1]
        return x.reshape(B, S, self._num_heads, self._head_dim).transpose(
            0, 2, 1, 3
        )

    def _merge(self, x):
        B, H, S, D = x.shape
        return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)

    def hybrid_forward(self, F, query, key=None, value=None,
                       valid_length=None, q_offset=None):
        """``valid_length`` (B,) int: number of non-padding KEY positions per
        batch row (reference softmax ``use_length`` semantics); keys past it
        are masked out of the attention. ``q_offset`` (scalar or (B,)):
        absolute position of query row 0 for the causal mask — the
        incremental-decode contract where a short (typically length-1)
        query attends over a longer cached key prefix."""
        use_bshd = self._use_bshd()
        if self._self_attention:
            qkv = self.qkv_proj(query)  # (B, S, 3*units)
            B, S = qkv.shape[0], qkv.shape[1]
            qkv = qkv.reshape(B, S, self._num_heads, 3 * self._head_dim)
            if use_bshd:
                # transpose-free layout: slices stay (B, S, H, D) and the
                # bshd attention path consumes them directly (kept for
                # the simpler graphs, not for speed)
                d = self._head_dim
                q = qkv[:, :, :, 0 * d:1 * d]
                k = qkv[:, :, :, 1 * d:2 * d]
                v = qkv[:, :, :, 2 * d:3 * d]
            else:
                q = self._split_packed(qkv, 0)
                k = self._split_packed(qkv, 1)
                v = self._split_packed(qkv, 2)
        else:
            if key is None:
                key = query
            if value is None:
                value = key
            if use_bshd:
                def _heads(x):
                    return x.reshape(x.shape[0], x.shape[1],
                                     self._num_heads, self._head_dim)

                q = _heads(self.q_proj(query))
                k = _heads(self.k_proj(key))
                v = _heads(self.v_proj(value))
            else:
                q = self._split(self.q_proj(query))
                k = self._split(self.k_proj(key))
                v = self._split(self.v_proj(value))
        use_ring = self._ring_axis is not None
        if use_ring and q_offset is not None:
            raise MXNetError(
                "q_offset (incremental decode) is not supported under "
                "sequence-parallel attention; decode with ring_axis=None")
        if use_ring:
            from ..block import _in_probe
            from ...parallel import current_mesh
            from ...parallel.ring_attention import ring_flash_attention

            mesh = current_mesh()
            if _in_probe() or mesh is None:
                # shape probe and plain (meshless) inference — e.g. eval
                # after sync_params on one device — run the numerically
                # identical dense kernel; ring needs no mesh to be correct
                use_ring = False
            elif self._ring_axis not in mesh.axis_names:
                raise MXNetError(
                    f"ring_axis={self._ring_axis!r} not in the active "
                    f"mesh's axes {mesh.axis_names}"
                )
        if use_ring:
            if self._seq_mode == "ulysses":
                from ...parallel.ulysses import ulysses_attention

                out = ulysses_attention(
                    q, k, v, mesh, self._ring_axis, causal=self._causal,
                    sm_scale=1.0 / math.sqrt(self._head_dim),
                    valid_length=valid_length,
                )
            else:
                out = ring_flash_attention(
                    q, k, v, mesh, self._ring_axis, causal=self._causal,
                    sm_scale=1.0 / math.sqrt(self._head_dim),
                    valid_length=valid_length,
                )
        else:
            out = F.flash_attention(
                q, k, v, valid_length, causal=self._causal,
                sm_scale=1.0 / math.sqrt(self._head_dim),
                layout="BSHD" if use_bshd else "BHSD",
                q_offset=q_offset,
            )
        if use_bshd:
            out = out.reshape(out.shape[0], out.shape[1], self._units)
        else:
            out = self._merge(out)
        out = self.out_proj(out)
        if self.drop is not None:
            out = self.drop(out)
        return out

    def _use_bshd(self) -> bool:
        """Transpose-free (B, S, H, D) attention layout, kept as
        default for the simpler graphs (not for speed); ring/ulysses shard over explicit
        head-major arrays, so they keep BHSD. MXTPU_ATTN_BSHD=0 restores
        head-major."""
        import os

        return self._ring_axis is None and \
            os.environ.get("MXTPU_ATTN_BSHD", "1") != "0"

    def _split_packed(self, qkv, which):
        # qkv (B, S, H, 3*D) interleaved per head like the reference's
        # interleaved_matmul_selfatt layout
        d = self._head_dim
        part = qkv[:, :, :, which * d : (which + 1) * d]
        return part.transpose(0, 2, 1, 3)

    # ----------------------------------------------------- incremental mode
    # KV-cached decode (Pope et al. 2022). The incremental API always uses
    # the transpose-free (B, S, H, D) head layout — caches are raw jax
    # arrays (pytree leaves of the decode state the engine threads through
    # lax.while_loop), activations stay NDArrays. Self-attention caches are
    # (max_len, B, H, D) slots written with lax.dynamic_update_slice;
    # cross-attention "caches" are the memory projections, computed once at
    # prefill and static afterwards.

    def _heads_bshd(self, x):
        # (B, L, units) -> (B, L, H, D)
        return x.reshape(x.shape[0], x.shape[1], self._num_heads,
                         self._head_dim)

    def _sm_scale(self):
        return 1.0 / math.sqrt(self._head_dim)

    def _finish(self, F, out):
        out = out.reshape(out.shape[0], out.shape[1], self._units)
        out = self.out_proj(out)
        if self.drop is not None:
            out = self.drop(out)
        return out

    def prefill(self, query, valid_length=None):
        """Full-prefix forward that ALSO returns the projected K/V.

        Self-attention only. Returns ``(out, k, v)`` with ``out`` matching
        ``__call__`` bit-for-bit (same projections, same dense/flash
        dispatch) and ``k``/``v`` raw ``(B, S, H, D)`` arrays ready to be
        seeded into a decode cache."""
        from ... import ndarray as F

        if not self._self_attention:
            raise MXNetError("prefill() is the self-attention cache seed; "
                             "cross-attention uses project_kv()")
        qkv = self.qkv_proj(query)
        B, S = qkv.shape[0], qkv.shape[1]
        qkv = qkv.reshape(B, S, self._num_heads, 3 * self._head_dim)
        d = self._head_dim
        q = qkv[:, :, :, 0 * d:1 * d]
        k = qkv[:, :, :, 1 * d:2 * d]
        v = qkv[:, :, :, 2 * d:3 * d]
        out = F.flash_attention(
            q, k, v, valid_length, causal=self._causal,
            sm_scale=self._sm_scale(), layout="BSHD")
        return self._finish(F, out), k.data, v.data

    def project_kv(self, key, value=None):
        """Cross-attention prefill: project the (static) memory once into
        raw ``(B, S, H, D)`` K/V reused by every decode step."""
        if self._self_attention:
            raise MXNetError("project_kv() needs self_attention=False")
        if value is None:
            value = key
        k = self._heads_bshd(self.k_proj(key))
        v = self._heads_bshd(self.v_proj(value))
        return k.data, v.data

    def attend(self, query, k, v, valid_length=None, q_offset=None):
        """Attention of a projected query over precomputed raw
        ``(B, S, H, D)`` K/V (from ``project_kv``) — the cross-attention
        half of both prefill and decode."""
        from ... import ndarray as F
        from ...ndarray.ndarray import NDArray

        if self._self_attention:
            raise MXNetError("attend() runs over external K/V; "
                             "self-attention caches use step()")
        q = self._heads_bshd(self.q_proj(query))
        out = F.flash_attention(
            q, NDArray(k), NDArray(v), valid_length, causal=self._causal,
            sm_scale=self._sm_scale(), layout="BSHD", q_offset=q_offset)
        return self._finish(F, out)

    def step(self, query, k_cache, v_cache, pos, valid_length=None):
        """One incremental self-attention step: O(1) work per token.

        ``query`` (B, 1, units) is the current token's hidden state;
        ``k_cache``/``v_cache`` are raw ``(max_len, B, H, D)`` slots
        holding ``pos`` earlier entries; ``pos`` is a (traced) scalar
        int32 cache offset. The new token's K/V land at row ``pos`` via
        ``lax.dynamic_update_slice`` and the query attends causally over
        the cache with ``q_offset=pos`` (the non-square mask fix).
        Returns ``(out, k_cache, v_cache)`` with the updated caches."""
        import jax
        from ... import ndarray as F
        from ...ndarray.ndarray import NDArray

        if not self._self_attention:
            raise MXNetError("step() updates a self-attention cache; "
                             "cross-attention uses attend()")
        qkv = self.qkv_proj(query)
        B = qkv.shape[0]
        qkv = qkv.reshape(B, 1, self._num_heads, 3 * self._head_dim)
        d = self._head_dim
        q = qkv[:, :, :, 0 * d:1 * d]
        k_t = qkv[:, :, :, 1 * d:2 * d].data
        v_t = qkv[:, :, :, 2 * d:3 * d].data
        idx = (pos.data if hasattr(pos, "asnumpy") else pos, 0, 0, 0)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, jnp.swapaxes(k_t, 0, 1), idx)
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, jnp.swapaxes(v_t, 0, 1), idx)
        out = F.flash_attention(
            q, NDArray(jnp.swapaxes(k_cache, 0, 1)),
            NDArray(jnp.swapaxes(v_cache, 0, 1)),
            valid_length, causal=self._causal, sm_scale=self._sm_scale(),
            layout="BSHD", q_offset=idx[0])
        return self._finish(F, out), k_cache, v_cache

    def init_cache(self, batch_size, max_len, dtype=None):
        """Zeroed raw ``(max_len, B, H, D)`` K/V cache pair for ``step``.
        ``dtype`` defaults to the layer's parameter dtype (so AMP-cast
        engines allocate compute-dtype caches)."""
        if dtype is None:
            dtype = self.out_proj.weight.dtype
        shape = (int(max_len), int(batch_size), self._num_heads,
                 self._head_dim)
        # two buffers, never one array twice: the pair is donated
        return (jnp.zeros(shape, jnp.dtype(dtype)),
                jnp.zeros(shape, jnp.dtype(dtype)))

    # ------------------------------------------------------------ paged mode
    # Paged KV cache (Kwon et al., PagedAttention, SOSP 2023): instead of a
    # dense (max_len, B, H, D) slab per dispatch, K/V live in a shared
    # (num_pages, page_size, H, D) pool (declared (num_pages, page_size,
    # H x D) at heads narrower than the lanes); each batch row owns a PAGE TABLE
    # row mapping its logical token positions to pool pages. Reads gather
    # through the table, writes scatter through it — so a request holds
    # only ceil(len/page_size) pages, freed the moment it retires. Page 0
    # is reserved as the TRASH page: inactive/finished rows write there and
    # padded table entries point at it, keeping every dispatch shape-stable
    # with no masking branches. serving.pages.PagePool owns the free list.

    def init_page_pool(self, num_pages, page_size, dtype=None):
        """Zeroed K/V pool pair shared by every request decoding through
        this layer: ``(num_pages, page_size, H, D)`` at heads of whole
        lanes, and ``(num_pages, page_size, H x D)`` at narrower heads (a
        page's (head, d) on the lanes, the declaration the paged kernels
        read such heads in and the chip keeps row-major: ``ops/paged.py``;
        every writer below lays a position's heads side by side, which is
        the same numbers in the same order). ``dtype`` defaults to the
        layer's parameter dtype (AMP engines get compute-dtype pools)."""
        if dtype is None:
            dtype = self.out_proj.weight.dtype
        shape = (int(num_pages), int(page_size), self._num_heads,
                 self._head_dim)
        if self._head_dim % 128:
            shape = shape[:2] + (self._num_heads * self._head_dim,)
        # two buffers, never one array twice: the serving engine donates
        # the pools into every dispatch, and a buffer donated twice in one
        # call is refused off the CPU
        return (jnp.zeros(shape, jnp.dtype(dtype)),
                jnp.zeros(shape, jnp.dtype(dtype)))

    def paged_step(self, query, k_pool, v_pool, page_table, pos, active):
        """One incremental self-attention step through a paged KV cache.

        ``query`` (B, 1, units) is the current token's hidden state;
        ``k_pool``/``v_pool`` are the shared ``(num_pages, page_size, H,
        D)`` pools; ``page_table`` (B, P) int32 maps row ``b``'s logical
        position ``p`` to pool page ``page_table[b, p // page_size]``;
        ``pos`` (B,) int32 is each row's cache length (= this token's
        absolute position); ``active`` (B,) bool masks live rows — rows
        that finished (or hold no request) write to the trash page 0, so
        their garbage never lands in another request's pages.

        The new token's K/V scatter to ``(page, pos % page_size)``; then
        attention routes by ``ops/paged.kernels_on()``:
        the Pallas decode kernel walks the page table inside its grid and
        reads the pools IN PLACE (no gather), while the fallback gathers
        the ``(B, P*page_size, H, D)`` view and runs the dense path —
        identical masked-softmax math to the dense ``step`` path, so at
        equal logical capacity the two are bit-identical (asserted in
        tests/test_paged.py). Either way inactive rows only ever touch
        trash page 0. Returns ``(out, k_pool, v_pool)``."""
        from ... import ndarray as F
        from ...ndarray.ndarray import NDArray

        if not self._self_attention:
            raise MXNetError("paged_step() updates a self-attention cache; "
                             "cross-attention uses attend()")
        qkv = self.qkv_proj(query)
        B = qkv.shape[0]
        qkv = qkv.reshape(B, 1, self._num_heads, 3 * self._head_dim)
        d = self._head_dim
        q = qkv[:, :, :, 0 * d:1 * d]
        k_t = qkv[:, :, :, 1 * d:2 * d].data[:, 0]  # (B, H, D)
        v_t = qkv[:, :, :, 2 * d:3 * d].data[:, 0]
        pos = jnp.asarray(pos, jnp.int32)
        page_size = k_pool.shape[1]
        rows = jnp.arange(B)
        # inactive rows resolve to (trash page, offset 0); pos // page_size
        # is in-bounds for active rows by the PagePool.ensure() contract
        slot = jnp.where(active, pos // page_size, 0)
        page = jnp.where(active, page_table[rows, slot], 0)
        off = jnp.where(active, pos % page_size, 0)
        k_pool = k_pool.at[page, off].set(k_t.reshape(
            (B,) + k_pool.shape[2:]))
        v_pool = v_pool.at[page, off].set(v_t.reshape(
            (B,) + v_pool.shape[2:]))
        from ...ops import paged
        from ...ops.pallas import paged_flash_attention as _pfa

        if self._causal and paged.kernels_on():
            # Pallas decode kernel: the page table rides the grid as a
            # scalar-prefetch operand and each step reads a block of the
            # row's pool pages in place, none past the row's position —
            # the gather below never materializes
            out = NDArray(_pfa.paged_decode_attention(
                q.data[:, 0], k_pool, v_pool, page_table, pos,
                sm_scale=self._sm_scale())[:, None])
        else:
            # dense fallback: gather the logical (B, P*page_size, H, D)
            # view through the table (bitwise the pre-kernel path)
            P = page_table.shape[1]
            k = k_pool[page_table].reshape(B, P * page_size,
                                           self._num_heads, d)
            v = v_pool[page_table].reshape(B, P * page_size,
                                           self._num_heads, d)
            out = F.flash_attention(
                q, NDArray(k), NDArray(v), None, causal=self._causal,
                sm_scale=self._sm_scale(), layout="BSHD", q_offset=pos)
        return self._finish(F, out), k_pool, v_pool

    def paged_window_step(self, query, k_pool, v_pool, page_table, pos,
                          active, window_vl=None):
        """An S-token incremental window through the paged cache in ONE
        pass — the q_offset-aware prefill shape that suffix-only prefix
        replay and speculative verification both dispatch.

        ``query`` (B, S, units): token ``i`` of row ``b`` sits at
        absolute position ``pos[b] + i``. The window's K/V scatter
        through the page table first (inactive rows to trash page 0),
        then every query attends causally over the row's full paged
        history INCLUDING the window's earlier tokens. ``window_vl``
        (B,) marks tokens ``>= window_vl[b]`` as padding: their K/V go
        to the trash page and their outputs are zeroed under the kernel
        path (garbage-but-ignored under the dense fallback — callers
        only read rows ``< window_vl``). Routing matches ``paged_step``:
        Pallas window kernel when ``paged.kernels_on()``, dense
        gather otherwise. Returns ``(out, k_pool, v_pool)``."""
        from ... import ndarray as F
        from ...ndarray.ndarray import NDArray
        from ...ops import paged
        from ...ops.pallas import paged_flash_attention as _pfa

        if not self._self_attention:
            raise MXNetError("paged_window_step() updates a self-attention "
                             "cache; cross-attention uses attend()")
        qkv = self.qkv_proj(query)
        B, S = qkv.shape[0], qkv.shape[1]
        qkv = qkv.reshape(B, S, self._num_heads, 3 * self._head_dim)
        d = self._head_dim
        q = qkv[:, :, :, 0 * d:1 * d]
        k_t = qkv[:, :, :, 1 * d:2 * d].data  # (B, S, H, D)
        v_t = qkv[:, :, :, 2 * d:3 * d].data
        pos = jnp.asarray(pos, jnp.int32)
        page_size = k_pool.shape[1]
        steps = jnp.arange(S, dtype=jnp.int32)[None, :]
        abs_pos = pos[:, None] + steps                    # (B, S)
        live = active[:, None]
        if window_vl is not None:
            live = jnp.logical_and(
                live, steps < jnp.asarray(window_vl, jnp.int32)[:, None])
        rows = jnp.arange(B)[:, None]
        slot = jnp.where(live, abs_pos // page_size, 0)
        page = jnp.where(live, page_table[rows, slot], 0)
        off = jnp.where(live, abs_pos % page_size, 0)
        k_pool = k_pool.at[page, off].set(k_t.reshape(
            (B, S) + k_pool.shape[2:]))
        v_pool = v_pool.at[page, off].set(v_t.reshape(
            (B, S) + v_pool.shape[2:]))
        if self._causal and paged.kernels_on():
            out = NDArray(_pfa.paged_window_attention(
                q.data, k_pool, v_pool, page_table, pos, window_vl,
                sm_scale=self._sm_scale()))
        else:
            P = page_table.shape[1]
            k = k_pool[page_table].reshape(B, P * page_size,
                                           self._num_heads, d)
            v = v_pool[page_table].reshape(B, P * page_size,
                                           self._num_heads, d)
            out = F.flash_attention(
                q, NDArray(k), NDArray(v), None, causal=self._causal,
                sm_scale=self._sm_scale(), layout="BSHD", q_offset=pos)
        return self._finish(F, out), k_pool, v_pool

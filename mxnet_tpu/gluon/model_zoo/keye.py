"""Keye-VL-2.0's language model: a decoder-only transformer with RMSNorm,
grouped-query attention (per-head q/k RMSNorm, three-component rotary
embedding), a learned indexer that selects ``topk`` cached positions a
query (``ops/sparse_attention.py``), and SwiGLU experts behind a softmax
router (``ops/pallas/grouped_swiglu.py``). The vision tower is not built:
traffic is text, whose three rotary components are equal; ``forward`` takes
unequal ones.

The net speaks the paged protocol of a model with NO encoder
(``paged_slot_state``): a slot keeps three paged arrays a layer (keys,
values, the indexer's keys) under one page table and no static memory. The
prompt itself lives in the pages and enters in chunks through
``prefill_suffix_paged`` (queries at ``q_offset`` over the history pages
plus the chunk); ``decode_step_paged`` is one query a row.

Device-side counts ride in ``state["counts"]`` (int32, added to by every
layer): tokens routed to each expert of each layer, distinct experts
touched, keys seen and keys selected; ``InferStep`` appends them to the
tokens it hands back and zeroes them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ... import initializer as _init
from ...base import MXNetError
from ...ndarray import NDArray
from ...ops import paged as _paged
from ...ops import sparse_attention as _dsa
from ...ops.pallas import grouped_swiglu as _moe
from ..block import HybridBlock

__all__ = ["KeyeLM"]


def rms_norm(x, gain, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def mrope(x, pos3, sections, theta):
    """Rotary embedding of ``x (..., heads, D)`` at ``pos3 (..., 3)``: the
    half-dimension in three sections that take their angle from the
    position's components ``(t, h, w)``; dimension ``d`` pairs with ``d +
    D/2``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    comp = jnp.asarray(sum(([c] * n for c, n in enumerate(sections)), []),
                       jnp.int32)
    ang = jnp.take(pos3.astype(jnp.float32), comp, axis=-1) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


class KeyeLM(HybridBlock):
    """The language model. Widths default to the published ones; matrices
    are stored ``(in, out)`` and experts ``(E, in, out)``."""

    # what a serving slot keeps: paged arrays a layer, no encoder memory
    # (an instance adds ``counts``, whose lengths are its own)
    paged_slot_state = {"pools": ("k_pools", "v_pools", "ik_pools"),
                        "encoder_memory": False}

    def __init__(self, vocab_size=151936, hidden_size=2048, num_layers=48,
                 num_heads=32, num_kv_heads=4, head_dim=128,
                 num_experts=128, experts_per_tok=8, expert_width=768,
                 index_heads=16, index_head_dim=64, index_topk=2048,
                 kv_chunk=512, rope_theta=1e7, mrope_section=(16, 24, 24),
                 rms_eps=1e-6, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if sum(mrope_section) * 2 != head_dim or \
                sum(s // 2 for s in mrope_section) * 2 != index_head_dim:
            raise MXNetError(
                f"mrope_section {tuple(mrope_section)} must fill half of "
                f"head_dim {head_dim}, and halved half of index_head_dim "
                f"{index_head_dim}")
        self._h, self._n = hidden_size, num_layers
        self._nq, self._nkv, self._d = num_heads, num_kv_heads, head_dim
        self._e, self._k = num_experts, experts_per_tok
        self._ni, self._di, self._topk = index_heads, index_head_dim, \
            index_topk
        self._kv_chunk = int(kv_chunk)
        self._theta, self._eps = float(rope_theta), float(rms_eps)
        self._sec = tuple(int(s) for s in mrope_section)
        self._isec = tuple(s // 2 for s in self._sec)
        self.paged_slot_state = dict(type(self).paged_slot_state, counts=(
            ("expert_tokens", num_layers * num_experts),
            ("experts_touched", 1), ("expert_layers", 1), ("keys_seen", 1),
            ("keys_selected", 1)))
        h, d = hidden_size, head_dim
        shapes = {"embed": (vocab_size, h), "norm": (h,),
                  "head": (h, vocab_size)}
        for i in range(num_layers):
            p = f"l{i}_"
            shapes.update({
                p + "attn_norm": (h,), p + "wq": (h, num_heads * d),
                p + "wk": (h, num_kv_heads * d),
                p + "wv": (h, num_kv_heads * d),
                p + "q_norm": (d,), p + "k_norm": (d,),
                p + "wo": (num_heads * d, h),
                p + "idx_wq": (h, index_heads * index_head_dim),
                p + "idx_wk": (h, index_head_dim),
                p + "idx_k_norm": (index_head_dim,),
                p + "idx_ww": (h, index_heads),
                p + "moe_norm": (h,), p + "router": (h, num_experts),
                p + "w_gate": (num_experts, h, expert_width),
                p + "w_up": (num_experts, h, expert_width),
                p + "w_down": (num_experts, expert_width, h)})
        with self.name_scope():
            for name, shape in shapes.items():
                setattr(self, name, self.params.get(
                    name, shape=shape, dtype=dtype,
                    init=_init.One() if name.endswith("norm")
                    else _init.Normal(1.0 / math.sqrt(shape[-2]))))

    # ------------------------------------------------------------ pieces
    @property
    def counts_size(self) -> int:
        """Length of ``state["counts"]``: tokens an expert a layer, then
        distinct experts touched (summed over layers and steps), expert
        layers run, keys seen, keys selected."""
        return self._n * self._e + 4

    def _w(self, name):
        v = getattr(self, name).data()
        return v.data if isinstance(v, NDArray) else v

    def _project(self, i, x, pos3):
        """``(u-derived) q, k, v, qi, ki, wi`` of layer ``i`` for ``x (...,
        H)`` at ``pos3 (..., 3)``, normed and rotated."""
        p = f"l{i}_"
        lead = x.shape[:-1]
        u = rms_norm(x, self._w(p + "attn_norm"), self._eps)
        q = jnp.dot(u, self._w(p + "wq")).reshape(lead + (self._nq, self._d))
        k = jnp.dot(u, self._w(p + "wk")).reshape(lead + (self._nkv, self._d))
        v = jnp.dot(u, self._w(p + "wv")).reshape(lead + (self._nkv, self._d))
        q = mrope(rms_norm(q, self._w(p + "q_norm"), self._eps), pos3,
                  self._sec, self._theta)
        k = mrope(rms_norm(k, self._w(p + "k_norm"), self._eps), pos3,
                  self._sec, self._theta)
        qi = mrope(jnp.dot(u, self._w(p + "idx_wq")).reshape(
            lead + (self._ni, self._di)), pos3, self._isec, self._theta)
        ki = rms_norm(jnp.dot(u, self._w(p + "idx_wk")),
                      self._w(p + "idx_k_norm"), self._eps)
        ki = mrope(ki[..., None, :], pos3, self._isec, self._theta)[..., 0, :]
        wi = jnp.dot(u, self._w(p + "idx_ww"),
                     preferred_element_type=jnp.float32)
        return q, k, v, qi, ki, wi

    def _experts(self, i, h, valid):
        """``(h + MoE(RMSNorm(h)), counts (E,))`` for ``h (T, H)``."""
        p = f"l{i}_"
        u = rms_norm(h, self._w(p + "moe_norm"), self._eps)
        out, counts = _moe.moe_experts(
            u, self._w(p + "router"), self._w(p + "w_gate"),
            self._w(p + "w_up"), self._w(p + "w_down"), self._k, valid)
        return h + out, counts

    def _count(self, counts, i, per_expert, seen, selected):
        e = self._e
        counts = counts.at[i * e:(i + 1) * e].add(per_expert)
        tail = jnp.stack([jnp.sum(per_expert > 0), jnp.int32(1),
                          seen, selected]).astype(jnp.int32)
        return counts.at[self._n * e:].add(tail)

    def _logits(self, x):
        y = rms_norm(x, self._w("norm"), self._eps)
        return jnp.dot(y, self._w("head"),
                       preferred_element_type=jnp.float32)

    # ------------------------------------------------------ paged protocol
    def init_paged_state(self, slots, num_pages, page_size, mem_len,
                         dtype=None):
        """Three pools a layer under one page table: keys and values
        ``(num_pages, page, Hkv, D)``, the indexer's keys ``(num_pages,
        page, Di)``; page 0 is the trash page. No per-slot memory."""
        dt = jnp.dtype(dtype if dtype is not None
                       else self.l0_wk.dtype)
        kv = (int(num_pages), int(page_size), self._nkv, self._d)
        ik = (int(num_pages), int(page_size), self._di)
        # distinct buffers: the state is a donated carry
        return {
            "k_pools": tuple(jnp.zeros(kv, dt) for _ in range(self._n)),
            "v_pools": tuple(jnp.zeros(kv, dt) for _ in range(self._n)),
            "ik_pools": tuple(jnp.zeros(ik, dt) for _ in range(self._n)),
            "counts": jnp.zeros((self.counts_size,), jnp.int32),
        }

    def _window(self, tok, pos3, q_pos, token_vl, state, page_tables,
                active):
        """The window forward: ``tok (R, C)`` at positions ``q_pos (R, C)``
        (rotary components ``pos3 (R, C, 3)``) written into and read
        through ``page_tables``. Returns ``(x (R, C, H), new_state)``."""
        R, C = tok.shape
        page = state["k_pools"][0].shape[1]
        L = page_tables.shape[1] * page
        block = _paged.kv_block(L, self._kv_chunk)
        live = jnp.logical_and(active[:, None],
                               jnp.arange(C)[None, :] < token_vl[:, None])
        # padding queries write to the trash page
        rows = jnp.where(live, _paged.token_rows(
            page_tables, jnp.minimum(q_pos, L - 1), page),
            q_pos % page).reshape(R * C)
        last = jnp.max(jnp.where(live, q_pos, 0))
        n_blocks = jnp.minimum(last // block + 1, L // block)
        x = jnp.take(self._w("embed"), tok, axis=0)
        counts = state["counts"]
        k_pools, v_pools, ik_pools = [], [], []
        for i in range(self._n):
            q, k, v, qi, ki, wi = self._project(i, x, pos3)
            kp = _paged.write_rows(state["k_pools"][i], rows,
                                 k.reshape((R * C,) + k.shape[2:]))
            vp = _paged.write_rows(state["v_pools"][i], rows,
                                 v.reshape((R * C,) + v.shape[2:]))
            ip = _paged.write_rows(state["ik_pools"][i], rows,
                                 ki.reshape(R * C, self._di))
            mask = _dsa.window_select(
                qi, wi, _paged.gather_row_pages(ip, page_tables), q_pos,
                n_blocks, block, self._topk)
            attn = _dsa.selected_window_attention(
                q, kp, vp, page_tables, q_pos[:, 0], mask, n_blocks, block,
                1.0 / math.sqrt(self._d))
            h = x + jnp.dot(attn, self._w(f"l{i}_wo"))
            y, per_expert = self._experts(i, h.reshape(R * C, self._h),
                                          live.reshape(R * C))
            x = y.reshape(R, C, self._h)
            counts = self._count(
                counts, i, per_expert,
                jnp.sum(jnp.where(live, q_pos + 1, 0)),
                jnp.sum(jnp.logical_and(mask, live[:, :, None])))
            k_pools.append(kp), v_pools.append(vp), ik_pools.append(ip)
        return x, {"k_pools": tuple(k_pools), "v_pools": tuple(v_pools),
                   "ik_pools": tuple(ik_pools), "counts": counts}

    def prefill_suffix_paged(self, tokens, token_vl, q_offset, state,
                             page_tables, slot_ids, active, wide=True):
        """One chunk of a prompt: ``tokens (R, C)`` at positions
        ``q_offset[r] + j`` (``j < token_vl[r]``; the rest is padding),
        written into the row's pages and attended over the history pages
        plus the chunk. Returns ``(logits (R, vocab) of each row's last
        real token, new_state)``; only a prompt's last chunk samples from
        them. ``slot_ids`` is not needed: a slot keeps nothing but pages."""
        tok = (tokens.data if isinstance(tokens, NDArray)
               else jnp.asarray(tokens)).astype(jnp.int32)
        C = tok.shape[1]
        q_offset = jnp.asarray(q_offset, jnp.int32)
        token_vl = jnp.asarray(token_vl, jnp.int32)
        q_pos = q_offset[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        pos3 = jnp.broadcast_to(q_pos[..., None], q_pos.shape + (3,))
        x, new_state = self._window(
            tok, pos3, q_pos, token_vl, state,
            jnp.asarray(page_tables, jnp.int32),
            jnp.asarray(active, jnp.bool_))
        idx = jnp.clip(token_vl - 1, 0, C - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        return self._logits(last), new_state

    def decode_step_paged(self, tokens, pos, state, page_tables, active):
        """One paged decode step over the SLOT batch: ``tokens (B,)`` at
        per-row positions ``pos (B,)``. The indexer scans the row's cached
        keys through the page table, selects, and attention reads the
        selected positions from the pages (``ops/sparse_attention.
        selected_decode``: on the chip a mask and the live pages in place,
        else positions gathered by token). Inactive rows write to the trash
        page, if anywhere, and their logits are garbage."""
        tok = (tokens.data if isinstance(tokens, NDArray)
               else jnp.asarray(tokens)).astype(jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        active = jnp.asarray(active, jnp.bool_)
        page_tables = jnp.asarray(page_tables, jnp.int32)
        page = state["k_pools"][0].shape[1]
        L = page_tables.shape[1] * page
        pos = jnp.minimum(pos, L - 1)
        rows = jnp.where(active, _paged.token_rows(
            page_tables, pos[:, None], page)[:, 0], pos % page)
        pos3 = jnp.broadcast_to(pos[:, None], pos.shape + (3,))
        x = jnp.take(self._w("embed"), tok, axis=0)
        counts = state["counts"]
        k_pools, v_pools, ik_pools = [], [], []
        for i in range(self._n):
            q, k, v, qi, ki, wi = self._project(i, x, pos3)
            kp = _paged.write_rows(state["k_pools"][i], rows, k)
            vp = _paged.write_rows(state["v_pools"][i], rows, v)
            attn, ip, selected = _dsa.selected_decode(
                q, qi, wi, ki, kp, vp, state["ik_pools"][i], page_tables,
                rows, pos, active, self._topk, 1.0 / math.sqrt(self._d))
            h = x + jnp.dot(attn, self._w(f"l{i}_wo"))
            x, per_expert = self._experts(i, h, active)
            counts = self._count(
                counts, i, per_expert,
                jnp.sum(jnp.where(active, pos + 1, 0)), selected)
            k_pools.append(kp), v_pools.append(vp), ik_pools.append(ip)
        return self._logits(x), {
            "k_pools": tuple(k_pools), "v_pools": tuple(v_pools),
            "ik_pools": tuple(ik_pools), "counts": counts}

    # ------------------------------------------------------- full forward
    def hybrid_forward(self, F, tokens, positions=None, **params):
        """Teacher-forced logits ``(B, S, vocab)`` of ``tokens (B, S)``;
        ``positions (B, S, 3)`` are the rotary components (the index, three
        times, when None). One window over a throw-away cache whose pages
        lie in order."""
        tok = (tokens.data if isinstance(tokens, NDArray)
               else jnp.asarray(tokens)).astype(jnp.int32)
        B, S = tok.shape
        page = math.gcd(S, 128)
        pages = S // page
        state = self.init_paged_state(B, 1 + B * pages, page, 0,
                                      dtype=self._w("l0_wk").dtype)
        tables = 1 + jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
        q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if positions is None:
            pos3 = jnp.broadcast_to(q_pos[..., None], (B, S, 3))
        else:
            pos3 = (positions.data if isinstance(positions, NDArray)
                    else jnp.asarray(positions)).astype(jnp.int32)
        x, _ = self._window(tok, pos3, q_pos, jnp.full((B,), S, jnp.int32),
                            state, tables, jnp.ones((B,), jnp.bool_))
        return NDArray(self._logits(x))

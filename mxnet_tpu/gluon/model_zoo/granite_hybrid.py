"""Granite 4.0-H's language model (``granitemoehybrid`` with no experts): a
decoder-only stack in which most layers mix tokens through a Mamba-2
state-space recurrence (``ops/ssm.py``) and a few through grouped-query
attention with NO positional term and a scale that is a multiplier of the
configuration, each followed by one SwiGLU MLP; four multipliers (on the
embedding, on every residual branch, on the attention scores, under the
logits) and a head tied to the embedding.

The net speaks the paged protocol of a model with no encoder
(``paged_slot_state``), with TWO kinds of state under one page table: K/V
pools for the attention layers alone, and arrays indexed by SLOT for the
state-space layers, of a fixed size whatever the context: the recurrent
state ``ssm (slots, heads, head_dim, d_state)`` and the convolution's tail
``conv (slots, d_conv - 1, conv_dim)``. The chunk program
(``prefill_suffix_paged``) reads a slot's arrays, starts from zero where
the chunk is a prompt's first (``q_offset`` 0: admission and recompute need
no reset dispatch), stops advancing them at the row's last real token, and
writes them back; ``decode_step_paged`` updates the rows that are
``active`` and leaves every other slot's arrays bit for bit.

Device-side counts ride in ``state["counts"]`` (``paged_slot_state
["counts"]`` names them); ``InferStep`` appends them to the tokens it hands
back and zeroes them.
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
from ...ops import ssm as _ssm
from ..block import HybridBlock
from .keye import rms_norm

__all__ = ["GraniteHybridLM"]

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


class GraniteHybridLM(HybridBlock):
    """The language model. Widths default to granite-4.0-h-micro's;
    matrices are stored ``(in, out)``, the convolution ``(kernel,
    channels)``."""

    # what a serving slot keeps: K/V pages for the attention layers, and
    # per-slot arrays for the state-space layers; no encoder memory
    paged_slot_state = {
        "pools": ("k_pools", "v_pools"), "encoder_memory": False,
        "slot_arrays": ("ssm", "conv"),
        # a dispatch's counts: real and padded tokens through the scan,
        # chunks that started from a zero state, live rows x decode steps,
        # cached positions an attention layer read, calls of the program
        "counts": (("scan_tokens", 1), ("scan_padded", 1),
                   ("chunks_from_zero", 1), ("row_steps", 1),
                   ("attn_keys", 1), ("calls", 1))}

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 layer_types=PERIOD * 4, num_heads=32, num_kv_heads=8,
                 intermediate_size=8192, mamba_heads=64, mamba_head_dim=64,
                 mamba_state=128, mamba_groups=1, mamba_conv=4,
                 mamba_expand=2, mamba_chunk=256, attention_multiplier=1 / 64,
                 embedding_multiplier=12.0, residual_multiplier=0.22,
                 logits_scaling=8.0, rms_eps=1e-5, kv_chunk=512,
                 state_dtype="float32", dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if mamba_groups != 1:
            raise MXNetError("one group of B and C is built (mamba_n_groups "
                             f"1), not {mamba_groups}")
        if mamba_heads * mamba_head_dim != mamba_expand * hidden_size:
            raise MXNetError(
                f"mamba_heads x mamba_head_dim ({mamba_heads} x "
                f"{mamba_head_dim}) must be mamba_expand x hidden_size "
                f"({mamba_expand} x {hidden_size})")
        bad = set(layer_types) - {"mamba", "attention"}
        if bad or "attention" not in layer_types:
            raise MXNetError(f"layer_types must mix 'mamba' and 'attention' "
                             f"(at least one of the latter), got {bad}")
        self._types = tuple(layer_types)
        self._h, self._f = hidden_size, intermediate_size
        self._nq, self._nkv = num_heads, num_kv_heads
        self._d = hidden_size // num_heads
        self._mh, self._mp, self._mn = mamba_heads, mamba_head_dim, \
            mamba_state
        self._kc = int(mamba_conv)
        self._inner = mamba_heads * mamba_head_dim
        self._conv_dim = self._inner + 2 * mamba_state
        self._block = int(mamba_chunk)
        self._kv_chunk = int(kv_chunk)
        self._attn_scale = float(attention_multiplier)
        self._emb_mult = float(embedding_multiplier)
        self._res_mult = float(residual_multiplier)
        self._logit_div = float(logits_scaling)
        self._eps = float(rms_eps)
        self._state_dtype = jnp.dtype(state_dtype)
        # a layer's place among its kind: pool index, slot-array index
        self._at = []
        seen = {"mamba": 0, "attention": 0}
        for t in self._types:
            self._at.append(seen[t])
            seen[t] += 1
        h = hidden_size
        shapes = {"embed": (vocab_size, h), "norm": (h,)}
        for i, t in enumerate(self._types):
            p = f"l{i}_"
            shapes.update({p + "mixer_norm": (h,), p + "mlp_norm": (h,),
                           p + "mlp_in": (h, 2 * intermediate_size),
                           p + "mlp_out": (intermediate_size, h)})
            if t == "mamba":
                shapes.update({
                    p + "in_proj": (h, 2 * self._inner + 2 * mamba_state
                                    + mamba_heads),
                    p + "conv_w": (self._kc, self._conv_dim),
                    p + "conv_b": (self._conv_dim,),
                    p + "dt_bias": (mamba_heads,),
                    p + "a_log": (mamba_heads,), p + "d_skip": (mamba_heads,),
                    p + "ssm_norm": (self._inner,),
                    p + "out_proj": (self._inner, h)})
            else:
                shapes.update({
                    p + "wq": (h, num_heads * self._d),
                    p + "wk": (h, num_kv_heads * self._d),
                    p + "wv": (h, num_kv_heads * self._d),
                    p + "wo": (num_heads * self._d, h)})
        with self.name_scope():
            for name, shape in shapes.items():
                if name.endswith(("norm", "d_skip")):
                    init = _init.One()
                elif name.endswith(("conv_b", "dt_bias", "a_log")):
                    init = _init.Zero()
                else:
                    init = _init.Normal(1.0 / math.sqrt(shape[-2]))
                setattr(self, name, self.params.get(
                    name, shape=shape, dtype=dtype, init=init))

    # ------------------------------------------------------------ pieces
    def _w(self, name):
        v = getattr(self, name).data()
        return v.data if isinstance(v, NDArray) else v

    def _residual(self, x, y):
        return x + (self._res_mult * y.astype(jnp.float32)).astype(x.dtype)

    def _mlp(self, i, x):
        p = f"l{i}_"
        with jax.named_scope("mlp"):
            u = rms_norm(x, self._w(p + "mlp_norm"), self._eps)
            gu = jnp.dot(u, self._w(p + "mlp_in"))
            g, up = gu[..., :self._f], gu[..., self._f:]
            y = jnp.dot(jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype)
                        * up, self._w(p + "mlp_out"))
        return self._residual(x, y)

    def _mamba_in(self, i, x):
        """``(z, xBC, dt)`` of layer ``i`` for ``x (..., H)``: the gate, the
        convolution's input, the step before its bias."""
        p = f"l{i}_"
        with jax.named_scope("mamba.in_proj"):
            u = rms_norm(x, self._w(p + "mixer_norm"), self._eps)
            zxd = jnp.dot(u, self._w(p + "in_proj"))
        a, b = self._inner, self._inner + self._conv_dim
        return zxd[..., :a], zxd[..., a:b], zxd[..., b:]

    def _mamba_split(self, i, xbc, dt):
        """The convolution's output, activated and split: ``x (..., heads,
        head_dim)``, ``B`` and ``C (..., d_state)``, ``dt`` after its bias
        and softplus, ``A`` (negative, a head)."""
        p = f"l{i}_"
        f = jnp.float32
        xbc = jax.nn.silu(xbc)
        x = xbc[..., :self._inner].reshape(
            xbc.shape[:-1] + (self._mh, self._mp))
        b = xbc[..., self._inner:self._inner + self._mn]
        c = xbc[..., self._inner + self._mn:]
        dt = jax.nn.softplus(dt.astype(f) + self._w(p + "dt_bias").astype(f))
        return x, b, c, dt, -jnp.exp(self._w(p + "a_log").astype(f))

    def _mamba_out(self, i, y, x, z, dtype):
        """``out_proj(RMSNorm((y + D x) silu(z)))``: the gate BEFORE the
        norm, over all of ``d_inner``."""
        p = f"l{i}_"
        f = jnp.float32
        with jax.named_scope("mamba.gate_norm"):
            y = y + self._w(p + "d_skip").astype(f)[:, None] * x.astype(f)
            y = y.reshape(y.shape[:-2] + (self._inner,)) \
                * jax.nn.silu(z.astype(f))
            y = rms_norm(y, self._w(p + "ssm_norm"), self._eps)
        with jax.named_scope("mamba.out_proj"):
            return jnp.dot(y.astype(dtype), self._w(p + "out_proj"))

    def _attn_in(self, i, x):
        """``(q, k, v)`` of attention layer ``i`` for ``x (..., H)``, heads
        apart: ``(..., heads, D)``. No bias, no positional term, no
        norm."""
        p = f"l{i}_"
        u = rms_norm(x, self._w(p + "mixer_norm"), self._eps)
        lead = x.shape[:-1]
        return (jnp.dot(u, self._w(p + "wq")).reshape(
                    lead + (self._nq, self._d)),
                jnp.dot(u, self._w(p + "wk")).reshape(
                    lead + (self._nkv, self._d)),
                jnp.dot(u, self._w(p + "wv")).reshape(
                    lead + (self._nkv, self._d)))

    def _logits(self, x):
        y = rms_norm(x, self._w("norm"), self._eps)
        logits = jnp.einsum("...h,vh->...v", y, self._w("embed"),
                            preferred_element_type=jnp.float32)
        return logits / self._logit_div

    def _embed(self, tok):
        x = jnp.take(self._w("embed"), tok, axis=0)
        return (x.astype(jnp.float32) * self._emb_mult).astype(x.dtype)

    # ------------------------------------------------------ paged protocol
    def init_paged_state(self, slots, num_pages, page_size, mem_len,
                         dtype=None):
        """K/V pools ``(num_pages, page, Hkv x D)`` for the attention layers
        alone (page 0 is the trash page; a page's (head, d) on the lanes,
        as the paged kernels read heads of 64: ``ops/paged.py``), and for
        each state-space layer its slots' recurrent state ``(slots, heads,
        head_dim, d_state)`` in the state's own dtype and convolution tail
        ``(slots, d_conv - 1, conv_dim)``."""
        dt = jnp.dtype(dtype if dtype is not None else self.embed.dtype)
        kv = (int(num_pages), int(page_size), self._nkv * self._d)
        n_attn = self._types.count("attention")
        n_ssm = len(self._types) - n_attn
        ssm = (int(slots), self._mh, self._mp, self._mn)
        conv = (int(slots), self._kc - 1, self._conv_dim)
        # distinct buffers: the state is a donated carry
        return {
            "k_pools": tuple(jnp.zeros(kv, dt) for _ in range(n_attn)),
            "v_pools": tuple(jnp.zeros(kv, dt) for _ in range(n_attn)),
            "ssm": tuple(jnp.zeros(ssm, self._state_dtype)
                         for _ in range(n_ssm)),
            "conv": tuple(jnp.zeros(conv, dt) for _ in range(n_ssm)),
            "counts": jnp.zeros(
                (len(self.paged_slot_state["counts"]),), jnp.int32),
        }

    def _window(self, tok, q_pos, token_vl, state, page_tables, slot_ids,
                active):
        """The window forward: ``tok (R, C)`` at positions ``q_pos (R, C)``,
        of which the first ``token_vl`` of an ``active`` row are real. K/V
        go into and come through ``page_tables``; the state-space layers
        read slot ``slot_ids[r]``'s arrays (zero where the row starts at
        position 0) and write them back as they stand after the row's last
        real token. Returns ``(x (R, C, H), new_state)``."""
        R, C = tok.shape
        slots = state["ssm"][0].shape[0]
        page = state["k_pools"][0].shape[1]
        L = page_tables.shape[1] * page
        block = _paged.kv_block(L, self._kv_chunk)
        live = jnp.logical_and(active[:, None],
                               jnp.arange(C)[None, :] < token_vl[:, None])
        real = jnp.where(active, token_vl, 0)
        # padding queries write to the trash page
        rows = jnp.where(live, _paged.token_rows(
            page_tables, jnp.minimum(q_pos, L - 1), page),
            q_pos % page).reshape(R * C)
        last = jnp.max(jnp.where(live, q_pos, 0))
        n_blocks = jnp.minimum(last // block + 1, L // block)
        causal = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]
        # an inert row reads slot 0 and writes nowhere
        read = jnp.clip(slot_ids, 0, slots - 1)
        write = jnp.where(active, slot_ids, slots)
        fresh = (q_pos[:, 0] == 0)
        x = self._embed(tok)
        k_pools, v_pools = list(state["k_pools"]), list(state["v_pools"])
        ssm, conv = list(state["ssm"]), list(state["conv"])
        for i, kind in enumerate(self._types):
            j = self._at[i]
            if kind == "attention":
                with jax.named_scope("attention"):
                    q, k, v = self._attn_in(i, x)
                    k_pools[j] = _paged.write_rows(
                        k_pools[j], rows, k.reshape((R * C,) + k.shape[2:]))
                    v_pools[j] = _paged.write_rows(
                        v_pools[j], rows, v.reshape((R * C,) + v.shape[2:]))
                    attn = _dsa.selected_window_attention(
                        q, k_pools[j], v_pools[j], page_tables, q_pos[:, 0],
                        causal, n_blocks, block, self._attn_scale)
                    y = jnp.dot(attn, self._w(f"l{i}_wo"))
            else:
                z, xbc, dt = self._mamba_in(i, x)
                with jax.named_scope("mamba.conv"):
                    tail = jnp.where(fresh[:, None, None], 0,
                                     jnp.take(conv[j], read, axis=0))
                    xbc, tail = _ssm.causal_conv(
                        xbc, tail, self._w(f"l{i}_conv_w"),
                        self._w(f"l{i}_conv_b"), real)
                    conv[j] = conv[j].at[write].set(tail, mode="drop")
                with jax.named_scope("mamba.scan"):
                    xs, b, c, dt, a = self._mamba_split(i, xbc, dt)
                    s0 = jnp.where(fresh[:, None, None, None], 0,
                                   jnp.take(ssm[j], read, axis=0))
                    ys, s1 = _ssm.ssd_chunk_scan(
                        xs, jnp.where(live[..., None], dt, 0.0), a, b, c,
                        s0, self._block)
                    ssm[j] = ssm[j].at[write].set(
                        s1.astype(ssm[j].dtype), mode="drop")
                y = self._mamba_out(i, ys, xs, z, x.dtype)
            x = self._mlp(i, self._residual(x, y))
        n_real = jnp.sum(real)
        counts = state["counts"] + jnp.stack([
            n_real, jnp.sum(active) * C - n_real,
            jnp.sum(jnp.logical_and(active, fresh)), jnp.int32(0),
            jnp.sum(jnp.where(live, q_pos + 1, 0)),
            jnp.int32(1)]).astype(jnp.int32)
        return x, {"k_pools": tuple(k_pools), "v_pools": tuple(v_pools),
                   "ssm": tuple(ssm), "conv": tuple(conv), "counts": counts}

    def prefill_suffix_paged(self, tokens, token_vl, q_offset, state,
                             page_tables, slot_ids, active, wide=True):
        """One chunk of a prompt: ``tokens (R, C)`` at positions
        ``q_offset[r] + j`` (``j < token_vl[r]``; the rest is padding),
        K/V written into the row's pages, the state-space layers carried
        in slot ``slot_ids[r]``'s arrays from the chunk before (from zero
        where ``q_offset[r]`` is 0). Returns ``(logits (R, vocab) of each
        row's last real token, new_state)``; only a prompt's last chunk
        samples from them."""
        tok = (tokens.data if isinstance(tokens, NDArray)
               else jnp.asarray(tokens)).astype(jnp.int32)
        C = tok.shape[1]
        q_offset = jnp.asarray(q_offset, jnp.int32)
        token_vl = jnp.asarray(token_vl, jnp.int32)
        q_pos = q_offset[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        x, new_state = self._window(
            tok, q_pos, token_vl, state, jnp.asarray(page_tables, jnp.int32),
            jnp.asarray(slot_ids, jnp.int32), jnp.asarray(active, jnp.bool_))
        idx = jnp.clip(token_vl - 1, 0, C - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        return self._logits(last), new_state

    def decode_step_paged(self, tokens, pos, state, page_tables, active):
        """One paged decode step over the SLOT batch: ``tokens (B,)`` at
        per-row positions ``pos (B,)``; row ``b`` IS slot ``b``. A row that
        is not ``active`` writes its K/V to the trash page and keeps its
        recurrent state and its convolution tail bit for bit; its logits
        are garbage."""
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
        step = active.astype(jnp.int32)
        x = self._embed(tok)
        k_pools, v_pools = list(state["k_pools"]), list(state["v_pools"])
        ssm, conv = list(state["ssm"]), list(state["conv"])
        for i, kind in enumerate(self._types):
            j = self._at[i]
            if kind == "attention":
                with jax.named_scope("attention"):
                    q, k, v = self._attn_in(i, x)
                    k_pools[j] = _paged.write_rows(k_pools[j], rows, k)
                    v_pools[j] = _paged.write_rows(v_pools[j], rows, v)
                    attn = _paged.decode_attention(
                        q, k_pools[j], v_pools[j], page_tables, pos,
                        self._attn_scale)
                    y = jnp.dot(attn, self._w(f"l{i}_wo"))
            else:
                z, xbc, dt = self._mamba_in(i, x)
                with jax.named_scope("mamba.conv"):
                    xbc, conv[j] = _ssm.causal_conv(
                        xbc[:, None], conv[j], self._w(f"l{i}_conv_w"),
                        self._w(f"l{i}_conv_b"), step)
                with jax.named_scope("mamba.state_update"):
                    xs, b, c, dt, a = self._mamba_split(i, xbc[:, 0], dt)
                    ys, ssm[j] = _ssm.ssm_state_update(
                        ssm[j], xs, dt, a, b, c, active)
                y = self._mamba_out(i, ys, xs, z, x.dtype)
            x = self._mlp(i, self._residual(x, y))
        n_live = jnp.sum(step)
        counts = state["counts"] + jnp.stack([
            jnp.int32(0), jnp.int32(0), jnp.int32(0), n_live,
            jnp.sum(jnp.where(active, pos + 1, 0)),
            jnp.int32(1)]).astype(jnp.int32)
        return self._logits(x), {
            "k_pools": tuple(k_pools), "v_pools": tuple(v_pools),
            "ssm": tuple(ssm), "conv": tuple(conv), "counts": counts}

    # ------------------------------------------------------- full forward
    def hybrid_forward(self, F, tokens, **params):
        """Teacher-forced logits ``(B, S, vocab)`` of ``tokens (B, S)``: one
        window from a zero state over a throw-away cache whose pages lie in
        order."""
        tok = (tokens.data if isinstance(tokens, NDArray)
               else jnp.asarray(tokens)).astype(jnp.int32)
        B, S = tok.shape
        page = math.gcd(S, 128)
        pages = S // page
        state = self.init_paged_state(B, 1 + B * pages, page, 0,
                                      dtype=self._w("embed").dtype)
        tables = 1 + jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
        q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, _ = self._window(tok, q_pos, jnp.full((B,), S, jnp.int32), state,
                            tables, jnp.arange(B, dtype=jnp.int32),
                            jnp.ones((B,), jnp.bool_))
        return NDArray(self._logits(x))

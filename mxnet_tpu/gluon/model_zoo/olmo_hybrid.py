"""Olmo-Hybrid's language model (``olmo_hybrid``): a decoder-only stack in
which three layers in four mix tokens through the gated delta rule
(``ops/delta_rule.py``: a matrix state a head, every token reading the
state back with its key and writing the difference) and the fourth through
full attention with as many key/value heads as query heads, one norm over
the whole query and key projections and NO positional term; every sublayer's
OUTPUT is normed before it joins the residual (``h = x + RMSNorm(mixer(x))``,
``y = h + RMSNorm(mlp(h))``), one SwiGLU MLP a layer, an untied head.

The net speaks the paged protocol of a model with no encoder
(``paged_slot_state``), with two kinds of state under one page table. K/V
pools for the full-attention layers alone, declared ``(num_pages, page,
heads x 128)``: a page's positions on the rows, a head's 128 numbers on
whole lanes, the form in which ``ops/paged.py``'s kernels take 30 ungrouped
heads ONE at a time (a page of (key, head) rows on one axis meets every
query head with every key head in one product, thirty times the work, and
asked 77 MB of VMEM for a chunk). And TWO arrays indexed by SLOT for each
delta-rule layer, of a fixed size whatever the context: the state ``delta
(slots, heads, d_k, d_v)`` (float32 unless the configuration says
otherwise) and the convolutions' tail ``conv (slots, taps - 1, 2 heads d_k
+ heads d_v)``. The chunk program (``prefill_suffix_paged``) reads a slot's
arrays, starts from zero where the chunk is a prompt's first (``q_offset``
0: admission and recompute need no reset dispatch), stops advancing them at
the row's last real token, and writes them back; ``decode_step_paged``
updates the rows that are ``active`` and leaves every other slot's arrays
bit for bit.

Device-side counts ride in ``state["counts"]`` under the names
``granite_hybrid.py`` declares (``paged_slot_state["counts"]``), so the
scheduler's and the benchmark's readers of a hybrid cell read them as they
stand.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ... import initializer as _init
from ...base import MXNetError
from ...ndarray import NDArray
from ...ops import delta_rule as _delta
from ...ops import paged as _paged
from ...ops import ssm as _ssm
from ..block import HybridBlock
from .keye import rms_norm

__all__ = ["OlmoHybridLM"]

PERIOD = ("linear_attention",) * 3 + ("full_attention",)
F32 = jnp.float32


class OlmoHybridLM(HybridBlock):
    """The language model. Widths default to Olmo-Hybrid-7B's; matrices
    are stored ``(in, out)``, the convolutions' taps ``(taps, channels)``
    over the query, key and value channels side by side."""

    # what a serving slot keeps: K/V pages for the full-attention layers,
    # and per-slot arrays for the delta-rule layers; no encoder memory
    paged_slot_state = {
        "pools": ("k_pools", "v_pools"), "encoder_memory": False,
        "slot_arrays": ("delta", "conv"),
        # a dispatch's counts, under granite's names: real and padded
        # tokens through the blocked rule, chunks that started from a zero
        # state, live rows x decode steps, cached positions a
        # full-attention layer read, calls of the program
        "counts": (("scan_tokens", 1), ("scan_padded", 1),
                   ("chunks_from_zero", 1), ("row_steps", 1),
                   ("attn_keys", 1), ("calls", 1))}

    def __init__(self, vocab_size=100352, hidden_size=3840,
                 layer_types=PERIOD * 8, num_heads=30, num_kv_heads=30,
                 intermediate_size=11008, linear_key_heads=30,
                 linear_value_heads=30, linear_key_dim=96,
                 linear_value_dim=192, linear_conv=4, allow_neg_eigval=True,
                 delta_block=_delta.BLOCK, rms_eps=1e-6, kv_chunk=512,
                 state_dtype="float32", dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if linear_key_heads != linear_value_heads:
            raise MXNetError(
                "as many value heads as key heads are built (a key head's "
                f"state is one matrix), not {linear_value_heads} over "
                f"{linear_key_heads}")
        if num_kv_heads != num_heads:
            raise MXNetError("the full-attention layers are built with a "
                             f"key/value head a query head, not "
                             f"{num_kv_heads} under {num_heads}")
        bad = set(layer_types) - set(PERIOD)
        if bad or "full_attention" not in layer_types \
                or "linear_attention" not in layer_types:
            raise MXNetError("layer_types must mix 'linear_attention' and "
                             f"'full_attention', got {sorted(set(layer_types))}")
        self._types = tuple(layer_types)
        self._h, self._f = hidden_size, intermediate_size
        self._nq = num_heads
        self._d = hidden_size // num_heads
        self._lh, self._dk, self._dv = linear_key_heads, linear_key_dim, \
            linear_value_dim
        self._qk, self._vw = linear_key_heads * linear_key_dim, \
            linear_value_heads * linear_value_dim
        self._conv_dim = 2 * self._qk + self._vw
        self._kc = int(linear_conv)
        self._neg = bool(allow_neg_eigval)
        self._block = int(delta_block)
        self._kv_chunk = int(kv_chunk)
        self._sm = 1.0 / math.sqrt(self._d)
        self._eps = float(rms_eps)
        self._state_dtype = jnp.dtype(state_dtype)
        # a layer's place among its kind: pool index, slot-array index
        self._at = []
        seen = dict.fromkeys(PERIOD, 0)
        for t in self._types:
            self._at.append(seen[t])
            seen[t] += 1
        h = hidden_size
        shapes = {"embed": (vocab_size, h), "norm": (h,),
                  "head": (h, vocab_size)}
        for i, t in enumerate(self._types):
            p = f"l{i}_"
            shapes.update({p + "mixer_norm": (h,), p + "mlp_norm": (h,),
                           p + "mlp_in": (h, 2 * intermediate_size),
                           p + "mlp_out": (intermediate_size, h)})
            if t == "linear_attention":
                shapes.update({
                    p + "wq": (h, self._qk), p + "wk": (h, self._qk),
                    p + "wv": (h, self._vw), p + "wg": (h, self._vw),
                    p + "wa": (h, self._lh), p + "wb": (h, self._lh),
                    p + "conv_w": (self._kc, self._conv_dim),
                    p + "dt_bias": (self._lh,), p + "a_log": (self._lh,),
                    p + "o_norm": (self._dv,), p + "wo": (self._vw, h)})
            else:
                shapes.update({
                    p + "wq": (h, h), p + "wk": (h, h), p + "wv": (h, h),
                    p + "q_norm": (h,), p + "k_norm": (h,),
                    p + "wo": (h, h)})
        with self.name_scope():
            for name, shape in shapes.items():
                if name.endswith("norm"):
                    init = _init.One()
                elif name.endswith(("dt_bias", "a_log")):
                    init = _init.Zero()
                else:
                    init = _init.Normal(1.0 / math.sqrt(shape[-2]))
                setattr(self, name, self.params.get(
                    name, shape=shape, dtype=dtype, init=init))

    # ------------------------------------------------------------ pieces
    def _w(self, name):
        v = getattr(self, name).data()
        return v.data if isinstance(v, NDArray) else v

    def _join(self, i, sub, x, y):
        """``x + RMSNorm(y)``: the sublayer's output normed, then the
        residual."""
        return x + rms_norm(y, self._w(f"l{i}_{sub}_norm"), self._eps)

    def _mlp(self, i, x):
        p = f"l{i}_"
        with jax.named_scope("mlp"):
            gu = jnp.dot(x, self._w(p + "mlp_in"))
            g, up = gu[..., :self._f], gu[..., self._f:]
            y = jnp.dot(jax.nn.silu(g.astype(F32)).astype(g.dtype) * up,
                        self._w(p + "mlp_out"))
        return self._join(i, "mlp", x, y)

    def _delta_in(self, i, x):
        """``(qkv, gate, a, b)`` of delta-rule layer ``i`` for ``x (...,
        H)``: the convolutions' input (query, key and value channels side
        by side), the output gate before its ``silu``, the two gate
        projections a head."""
        p = f"l{i}_"
        with jax.named_scope("delta.in_proj"):
            qkv = jnp.concatenate([jnp.dot(x, self._w(p + "wq")),
                                   jnp.dot(x, self._w(p + "wk")),
                                   jnp.dot(x, self._w(p + "wv"))], -1)
            return qkv, jnp.dot(x, self._w(p + "wg")), \
                jnp.dot(x, self._w(p + "wa")), jnp.dot(x, self._w(p + "wb"))

    def _delta_split(self, i, qkv, a, b):
        """The convolutions' output, activated and split by head: ``q``
        (unit length over ``sqrt(d_k)``), ``k`` (unit length) ``(..., heads,
        d_k)`` float32, ``v (..., heads, d_v)``, the log-decay ``g`` and the
        write strength ``beta (..., heads)``."""
        p = f"l{i}_"
        qkv = jax.nn.silu(qkv)
        lead = qkv.shape[:-1]
        q = _delta.l2_heads(qkv[..., :self._qk].reshape(
            lead + (self._lh, self._dk))) * (1.0 / math.sqrt(self._dk))
        k = _delta.l2_heads(qkv[..., self._qk:2 * self._qk].reshape(
            lead + (self._lh, self._dk)))
        v = qkv[..., 2 * self._qk:].reshape(lead + (self._lh, self._dv))
        g, beta = _delta.gates(a, b, self._w(p + "a_log"),
                               self._w(p + "dt_bias"), self._neg)
        return q, k, v, g, beta

    def _delta_out(self, i, o, gate, dtype):
        """``W_o (RMSNorm(o) silu(gate))``: the norm over a head's ``d_v``
        with one gain shared by the heads."""
        p = f"l{i}_"
        with jax.named_scope("delta.gate_norm"):
            o = rms_norm(o, self._w(p + "o_norm"), self._eps)
            o = o.reshape(o.shape[:-2] + (self._vw,)) \
                * jax.nn.silu(gate.astype(F32))
        with jax.named_scope("delta.out_proj"):
            return jnp.dot(o.astype(dtype), self._w(p + "wo"))

    def _attn_in(self, i, x):
        """``(q, k, v)`` of full-attention layer ``i`` for ``x (..., H)``,
        heads apart ``(..., heads, D)``: one norm over all of the query
        projection and one over the key's, no bias, no positional term."""
        p = f"l{i}_"
        by_head = x.shape[:-1] + (self._nq, self._d)
        q = rms_norm(jnp.dot(x, self._w(p + "wq")), self._w(p + "q_norm"),
                     self._eps)
        k = rms_norm(jnp.dot(x, self._w(p + "wk")), self._w(p + "k_norm"),
                     self._eps)
        return q.reshape(by_head), k.reshape(by_head), \
            jnp.dot(x, self._w(p + "wv")).reshape(by_head)

    def _logits(self, x):
        y = rms_norm(x, self._w("norm"), self._eps)
        return jnp.dot(y, self._w("head"), preferred_element_type=F32)

    # ------------------------------------------------------ paged protocol
    def init_paged_state(self, slots, num_pages, page_size, mem_len,
                         dtype=None):
        """K/V pools ``(num_pages, page, heads x D)`` for the
        full-attention layers alone (page 0 is the trash page; a page's
        (head, d) on the lanes, a head a whole lane tile: ``ops/paged.py``),
        and for each delta-rule layer its slots' state
        ``(slots, heads, d_k, d_v)`` in the state's own dtype and
        convolution tail ``(slots, taps - 1, channels)``."""
        dt = jnp.dtype(dtype if dtype is not None else self.embed.dtype)
        kv = (int(num_pages), int(page_size), self._nq * self._d)
        n_full = self._types.count("full_attention")
        n_delta = len(self._types) - n_full
        delta = (int(slots), self._lh, self._dk, self._dv)
        conv = (int(slots), self._kc - 1, self._conv_dim)
        # distinct buffers: the state is a donated carry
        return {
            "k_pools": tuple(jnp.zeros(kv, dt) for _ in range(n_full)),
            "v_pools": tuple(jnp.zeros(kv, dt) for _ in range(n_full)),
            "delta": tuple(jnp.zeros(delta, self._state_dtype)
                           for _ in range(n_delta)),
            "conv": tuple(jnp.zeros(conv, dt) for _ in range(n_delta)),
            "counts": jnp.zeros(
                (len(self.paged_slot_state["counts"]),), jnp.int32),
        }

    def _window(self, tok, q_pos, token_vl, state, page_tables, slot_ids,
                active):
        """The window forward: ``tok (R, C)`` at positions ``q_pos (R, C)``,
        of which the first ``token_vl`` of an ``active`` row are real. K/V
        go into and come through ``page_tables``; the delta-rule layers
        read slot ``slot_ids[r]``'s arrays (zero where the row starts at
        position 0) and write them back as they stand after the row's last
        real token. Returns ``(x (R, C, H), new_state)``."""
        R, C = tok.shape
        slots = state["delta"][0].shape[0]
        page = state["k_pools"][0].shape[1]
        L = page_tables.shape[1] * page
        live = jnp.logical_and(active[:, None],
                               jnp.arange(C)[None, :] < token_vl[:, None])
        real = jnp.where(active, token_vl, 0)
        # padding queries write to the trash page
        rows = jnp.where(live, _paged.token_rows(
            page_tables, jnp.minimum(q_pos, L - 1), page),
            q_pos % page).reshape(R * C)
        # an inert row reads slot 0 and writes nowhere
        read = jnp.clip(slot_ids, 0, slots - 1)
        write = jnp.where(active, slot_ids, slots)
        fresh = (q_pos[:, 0] == 0)
        x = jnp.take(self._w("embed"), tok, axis=0)
        k_pools, v_pools = list(state["k_pools"]), list(state["v_pools"])
        delta, conv = list(state["delta"]), list(state["conv"])
        for i, kind in enumerate(self._types):
            j = self._at[i]
            if kind == "full_attention":
                with jax.named_scope("attention"):
                    q, k, v = self._attn_in(i, x)
                    k_pools[j] = _paged.write_rows(
                        k_pools[j], rows, k.reshape((R * C,) + k.shape[2:]))
                    v_pools[j] = _paged.write_rows(
                        v_pools[j], rows, v.reshape((R * C,) + v.shape[2:]))
                    attn = _paged.window_attention(
                        q, k_pools[j], v_pools[j], page_tables, q_pos[:, 0],
                        real, self._sm, kv_chunk=self._kv_chunk)
                    y = jnp.dot(attn, self._w(f"l{i}_wo"))
            else:
                qkv, gate, a, b = self._delta_in(i, x)
                with jax.named_scope("delta.conv"):
                    tail = jnp.where(fresh[:, None, None], 0,
                                     jnp.take(conv[j], read, axis=0))
                    qkv, tail = _ssm.causal_conv(
                        qkv, tail, self._w(f"l{i}_conv_w"),
                        jnp.zeros((self._conv_dim,), F32), real)
                    conv[j] = conv[j].at[write].set(tail, mode="drop")
                with jax.named_scope("delta.rule"):
                    q, k, v, g, beta = self._delta_split(i, qkv, a, b)
                    s0 = jnp.where(fresh[:, None, None, None], 0,
                                   jnp.take(delta[j], read, axis=0))
                    # a position past the row's last real token: decay 1,
                    # write strength 0
                    o, s1 = _delta.delta_rule_chunk(
                        q, k, v, jnp.where(live[..., None], g, 0.0),
                        jnp.where(live[..., None], beta, 0.0), s0,
                        self._block)
                    delta[j] = delta[j].at[write].set(
                        s1.astype(delta[j].dtype), mode="drop")
                y = self._delta_out(i, o, gate, x.dtype)
            x = self._mlp(i, self._join(i, "mixer", x, y))
        n_real = jnp.sum(real)
        counts = state["counts"] + jnp.stack([
            n_real, jnp.sum(active) * C - n_real,
            jnp.sum(jnp.logical_and(active, fresh)), jnp.int32(0),
            jnp.sum(jnp.where(live, q_pos + 1, 0)),
            jnp.int32(1)]).astype(jnp.int32)
        return x, {"k_pools": tuple(k_pools), "v_pools": tuple(v_pools),
                   "delta": tuple(delta), "conv": tuple(conv),
                   "counts": counts}

    def prefill_suffix_paged(self, tokens, token_vl, q_offset, state,
                             page_tables, slot_ids, active, wide=True):
        """One chunk of a prompt: ``tokens (R, C)`` at positions
        ``q_offset[r] + j`` (``j < token_vl[r]``; the rest is padding),
        K/V written into the row's pages, the delta-rule layers carried in
        slot ``slot_ids[r]``'s arrays from the chunk before (from zero
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
        state and its convolution tail bit for bit; its logits are
        garbage."""
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
        x = jnp.take(self._w("embed"), tok, axis=0)
        k_pools, v_pools = list(state["k_pools"]), list(state["v_pools"])
        delta, conv = list(state["delta"]), list(state["conv"])
        for i, kind in enumerate(self._types):
            j = self._at[i]
            if kind == "full_attention":
                with jax.named_scope("attention"):
                    q, k, v = self._attn_in(i, x)
                    k_pools[j] = _paged.write_rows(k_pools[j], rows, k)
                    v_pools[j] = _paged.write_rows(v_pools[j], rows, v)
                    attn = _paged.decode_attention(
                        q, k_pools[j], v_pools[j], page_tables, pos,
                        self._sm)
                    y = jnp.dot(attn, self._w(f"l{i}_wo"))
            else:
                qkv, gate, a, b = self._delta_in(i, x)
                with jax.named_scope("delta.conv"):
                    qkv, conv[j] = _ssm.causal_conv(
                        qkv[:, None], conv[j], self._w(f"l{i}_conv_w"),
                        jnp.zeros((self._conv_dim,), F32), step)
                with jax.named_scope("delta.state_update"):
                    q, k, v, g, beta = self._delta_split(i, qkv[:, 0], a, b)
                    o, delta[j] = _delta.delta_step(
                        delta[j], q, k, v, g, beta, active)
                y = self._delta_out(i, o, gate, x.dtype)
            x = self._mlp(i, self._join(i, "mixer", x, y))
        n_live = jnp.sum(step)
        counts = state["counts"] + jnp.stack([
            jnp.int32(0), jnp.int32(0), jnp.int32(0), n_live,
            jnp.sum(jnp.where(active, pos + 1, 0)),
            jnp.int32(1)]).astype(jnp.int32)
        return self._logits(x), {
            "k_pools": tuple(k_pools), "v_pools": tuple(v_pools),
            "delta": tuple(delta), "conv": tuple(conv), "counts": counts}

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

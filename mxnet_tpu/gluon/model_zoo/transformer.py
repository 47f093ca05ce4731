"""Transformer encoder-decoder for seq2seq (reference workload: GluonNLP
Transformer WMT En-De over contrib interleaved encdec attention ops
[unverified]; BASELINE.md config 4).

Pre-LN arrangement (more stable; graph fusion identical), flash attention
everywhere: causal self-attention in the decoder, cross-attention over
encoder memory.

Inference: every decoder level also speaks the INCREMENTAL protocol
(``prefill``/``decode_step`` with a preallocated ``(max_len, B, H, D)``
KV cache per layer, written via ``lax.dynamic_update_slice``), so one
jitted step emits a token at O(1) cost instead of the O(T²) full
re-forward. ``parallel.infer.InferStep`` drives it; ``model.generate``
is the convenience wrapper. A custom ``encoder=`` block (e.g.
``bert.BERTEncoderForGeneration``) swaps the memory encoder — the
"BERT-as-encoder" prefill configuration."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...base import MXNetError
from ...ndarray.ndarray import NDArray
from ..block import HybridBlock
from ..nn import (
    Dense, Dropout, Embedding, HybridSequential, LayerNorm,
    MultiHeadAttention,
)

__all__ = ["TransformerEncoder", "TransformerDecoder", "TransformerModel",
           "transformer_base", "transformer_big"]


class _FFN(HybridBlock):
    def __init__(self, units, hidden_size, dropout, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ffn_1 = Dense(hidden_size, activation="relu", flatten=False)
            self.ffn_2 = Dense(units, flatten=False)
            self.drop = Dropout(dropout)

    def hybrid_forward(self, F, x):
        return self.drop(self.ffn_2(self.ffn_1(x)))


class TransformerEncoderLayer(HybridBlock):
    _remat_unit = True  # hybridize(remat=...): one checkpoint region/layer

    def __init__(self, units, hidden_size, num_heads, dropout, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = LayerNorm(in_channels=units)
            self.attn = MultiHeadAttention(units, num_heads, dropout=dropout)
            self.ln2 = LayerNorm(in_channels=units)
            self.ffn = _FFN(units, hidden_size, dropout)
            self.drop = Dropout(dropout)

    def hybrid_forward(self, F, x, valid_length=None):
        # tags feed the names-based remat policy (remat='names:attn_out,
        # ffn_out' keeps exactly these resident); identity otherwise
        x = x + self.drop(F.checkpoint_name(
            self.attn(self.ln1(x), valid_length=valid_length),
            name="attn_out"))
        return x + F.checkpoint_name(self.ffn(self.ln2(x)), name="ffn_out")


class TransformerDecoderLayer(HybridBlock):
    _remat_unit = True

    def __init__(self, units, hidden_size, num_heads, dropout, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = LayerNorm(in_channels=units)
            self.self_attn = MultiHeadAttention(
                units, num_heads, dropout=dropout, causal=True
            )
            self.ln2 = LayerNorm(in_channels=units)
            self.cross_attn = MultiHeadAttention(
                units, num_heads, dropout=dropout, self_attention=False
            )
            self.ln3 = LayerNorm(in_channels=units)
            self.ffn = _FFN(units, hidden_size, dropout)
            self.drop = Dropout(dropout)

    def hybrid_forward(self, F, x, memory, mem_valid_length=None):
        x = x + self.drop(F.checkpoint_name(self.self_attn(self.ln1(x)),
                                            name="attn_out"))
        x = x + self.drop(F.checkpoint_name(
            self.cross_attn(self.ln2(x), memory, memory,
                            valid_length=mem_valid_length),
            name="attn_out"))
        return x + F.checkpoint_name(self.ffn(self.ln3(x)), name="ffn_out")

    # ----------------------------------------------------- incremental mode
    def prefill(self, x, memory, mem_valid_length=None):
        """Full-prefix forward that seeds the decode state: returns
        ``(y, (k_self, v_self), (k_mem, v_mem))`` — the layer output
        (bit-matching ``__call__``), the causal prefix K/V ``(B, Lp, H,
        D)``, and the memory projections reused by every decode step."""
        a, k_s, v_s = self.self_attn.prefill(self.ln1(x))
        x = x + self.drop(a)
        k_m, v_m = self.cross_attn.project_kv(memory)
        c = self.cross_attn.attend(self.ln2(x), k_m, v_m,
                                   valid_length=mem_valid_length)
        x = x + self.drop(c)
        y = x + self.ffn(self.ln3(x))
        return y, (k_s, v_s), (k_m, v_m)

    def step(self, x, self_kv, pos, cross_kv, mem_valid_length=None):
        """One incremental token: ``x`` (B, 1, units), ``self_kv`` the
        raw ``(max_len, B, H, D)`` cache pair (updated in place via
        dynamic_update_slice and returned), ``pos`` the traced cache
        offset, ``cross_kv`` the static memory projections."""
        a, k_c, v_c = self.self_attn.step(
            self.ln1(x), self_kv[0], self_kv[1], pos)
        x = x + self.drop(a)
        c = self.cross_attn.attend(self.ln2(x), cross_kv[0], cross_kv[1],
                                   valid_length=mem_valid_length)
        x = x + self.drop(c)
        y = x + self.ffn(self.ln3(x))
        return y, (k_c, v_c)

    def step_paged(self, x, k_pool, v_pool, page_table, pos, active,
                   cross_kv, mem_valid_length=None):
        """``step`` over the paged KV pool: per-row ``pos`` (B,) cache
        lengths instead of one shared scalar offset — the continuous-
        batching contract where every slot sits at its own depth."""
        a, k_pool, v_pool = self.self_attn.paged_step(
            self.ln1(x), k_pool, v_pool, page_table, pos, active)
        x = x + self.drop(a)
        c = self.cross_attn.attend(self.ln2(x), cross_kv[0], cross_kv[1],
                                   valid_length=mem_valid_length)
        x = x + self.drop(c)
        y = x + self.ffn(self.ln3(x))
        return y, k_pool, v_pool

    def step_window_paged(self, x, k_pool, v_pool, page_table, pos, active,
                          cross_kv, mem_valid_length=None, window_vl=None):
        """``step_paged`` widened to an S-token window: ``x`` (B, S,
        units) sits at per-row absolute positions ``pos[b] + i``, all S
        tokens scatter and attend in ONE pass (speculative verification
        and wide suffix replay). ``window_vl`` marks per-row padding
        tails inside the window."""
        a, k_pool, v_pool = self.self_attn.paged_window_step(
            self.ln1(x), k_pool, v_pool, page_table, pos, active,
            window_vl=window_vl)
        x = x + self.drop(a)
        c = self.cross_attn.attend(self.ln2(x), cross_kv[0], cross_kv[1],
                                   valid_length=mem_valid_length)
        x = x + self.drop(c)
        y = x + self.ffn(self.ln3(x))
        return y, k_pool, v_pool


class TransformerEncoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads, dropout,
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.layers = HybridSequential()
            for _ in range(num_layers):
                self.layers.add(
                    TransformerEncoderLayer(units, hidden_size, num_heads,
                                            dropout)
                )
            self.ln = LayerNorm(in_channels=units)

    def hybrid_forward(self, F, x, valid_length=None):
        for layer in self.layers:
            x = layer(x, valid_length=valid_length)
        return self.ln(x)


class TransformerDecoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads, dropout,
                 **kwargs):
        super().__init__(**kwargs)
        self._n = num_layers
        with self.name_scope():
            for i in range(num_layers):
                setattr(self, f"layer{i}",
                        TransformerDecoderLayer(units, hidden_size, num_heads,
                                                dropout))
            self.ln = LayerNorm(in_channels=units)

    def hybrid_forward(self, F, x, memory, mem_valid_length=None):
        for i in range(self._n):
            x = getattr(self, f"layer{i}")(x, memory,
                                           mem_valid_length=mem_valid_length)
        return self.ln(x)


class TransformerModel(HybridBlock):
    """forward(src_ids, tgt_ids[, src_valid_length]) -> logits
    (B, T_tgt, vocab). ``src_valid_length`` (B,) masks source padding out
    of encoder self-attention AND decoder cross-attention — the bucketed
    (pad-to-menu) prefill contract.

    ``encoder``: optional custom memory encoder block with call signature
    ``encoder(src_ids, valid_length) -> (B, S, units)`` replacing the
    built-in embedding + TransformerEncoder stack (its output width must
    equal ``units``) — e.g. ``bert.BERTEncoderForGeneration``."""

    def __init__(self, src_vocab=32768, tgt_vocab=32768, units=512,
                 hidden_size=2048, num_layers=6, num_heads=8, max_length=1024,
                 dropout=0.1, tie_weights=True, encoder=None, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._custom_encoder = encoder is not None
        with self.name_scope():
            if not self._custom_encoder:
                self.src_embed = Embedding(src_vocab, units,
                                           prefix="src_embed_")
            self.tgt_embed = Embedding(tgt_vocab, units, prefix="tgt_embed_")
            self.pos_embed = Embedding(max_length, units, prefix="pos_embed_")
            self.drop = Dropout(dropout)
            if self._custom_encoder:
                self.encoder = encoder
            else:
                self.encoder = TransformerEncoder(
                    num_layers, units, hidden_size, num_heads, dropout,
                    prefix="enc_",
                )
            self.decoder = TransformerDecoder(
                num_layers, units, hidden_size, num_heads, dropout,
                prefix="dec_",
            )
            self._tied = tie_weights
            if not tie_weights:
                self.proj = Dense(tgt_vocab, flatten=False, prefix="proj_")

    def _embed(self, F, embed, ids):
        B, S = ids.shape[0], ids.shape[1]
        pos = F.arange(0, S).reshape(1, S).broadcast_to((B, S))
        return self.drop(embed(ids) * (self._units ** 0.5)
                         + self.pos_embed(pos))

    def _logits(self, F, out):
        if self._tied:
            w = self.tgt_embed.weight.data()
            return F.dot(out, w.T)
        return self.proj(out)

    def encode(self, src_ids, valid_length=None):
        """Source ids -> (B, S, units) memory (the prefill encoder half;
        padding past ``valid_length`` is masked out of attention)."""
        from ... import ndarray as F

        if self._custom_encoder:
            out = self.encoder(src_ids, valid_length)
            return out[0] if isinstance(out, tuple) else out
        return self.encoder(self._embed(F, self.src_embed, src_ids),
                            valid_length=valid_length)

    def hybrid_forward(self, F, src_ids, tgt_ids, src_valid_length=None):
        memory = self.encode(src_ids, src_valid_length)
        out = self.decoder(self._embed(F, self.tgt_embed, tgt_ids), memory,
                           mem_valid_length=src_valid_length)
        return self._logits(F, out)

    # ----------------------------------------------------- incremental mode
    def prefill(self, src_ids, tgt_prefix, src_valid_length=None,
                max_len=64, cache_dtype=None):
        """Encode the source and run the target prefix ONCE, seeding the
        per-layer KV caches.

        Returns ``(last_logits, state)``: ``last_logits`` (B, vocab) are
        the logits predicting the token AFTER the prefix (bit-matching
        column ``Lp-1`` of the full forward), ``state`` is the decode
        pytree — per-layer ``(max_len, B, H, D)`` self-attention cache
        pairs (prefix written at rows ``[0, Lp)``), static cross-attention
        memory projections, and the source mask."""
        logits, self_parts, cross_parts, vl_raw = self.prefill_parts(
            src_ids, tgt_prefix, src_valid_length)
        B = tgt_prefix.shape[0]
        self_kv, cross_kv = [], []
        for i in range(self.decoder._n):
            layer = getattr(self.decoder, f"layer{i}")
            k_s, v_s = self_parts[i]
            kc, vc = layer.self_attn.init_cache(
                B, max_len, cache_dtype or k_s.dtype)
            zero = (0, 0, 0, 0)
            kc = jax.lax.dynamic_update_slice(kc, jnp.swapaxes(k_s, 0, 1),
                                              zero)
            vc = jax.lax.dynamic_update_slice(vc, jnp.swapaxes(v_s, 0, 1),
                                              zero)
            self_kv.append((kc, vc))
            cross_kv.append(cross_parts[i])
        state = {"self_kv": tuple(self_kv), "cross_kv": tuple(cross_kv),
                 "mem_vl": vl_raw}
        return logits, state

    def prefill_parts(self, src_ids, tgt_prefix, src_valid_length=None):
        """The prefill compute WITHOUT a cache layout: encode the source,
        run the target prefix, and return the raw per-layer pieces —
        ``(last_logits, [(k_s, v_s)], [(k_m, v_m)], mem_vl)`` with the
        prefix K/V as ``(B, Lp, H, D)`` arrays. ``prefill`` packs them
        into dense ``(max_len, B, H, D)`` caches; the paged engine
        scatters them into pool pages instead — both consume the exact
        same forward, so the two layouts start from identical state."""
        from ... import ndarray as F

        memory = self.encode(src_ids, src_valid_length)
        x = self._embed(F, self.tgt_embed, tgt_prefix)
        vl_raw = None if src_valid_length is None else (
            src_valid_length.data if isinstance(src_valid_length, NDArray)
            else jnp.asarray(src_valid_length))
        self_parts, cross_parts = [], []
        for i in range(self.decoder._n):
            layer = getattr(self.decoder, f"layer{i}")
            x, (k_s, v_s), (k_m, v_m) = layer.prefill(
                x, memory, mem_valid_length=src_valid_length)
            self_parts.append((k_s, v_s))
            cross_parts.append((k_m, v_m))
        out = self.decoder.ln(x)
        logits = self._logits(F, out[:, -1:, :])[:, 0]
        return logits, self_parts, cross_parts, vl_raw

    def decode_step(self, tokens, pos, state):
        """One O(1) incremental decode step: place ``tokens`` (B,) int32
        at absolute target position ``pos`` (a traced scalar; the number
        of tokens already cached) and return ``(logits, new_state)`` —
        ``logits`` (B, vocab) predict position ``pos + 1``'s token and
        bit-match column ``pos`` of a full re-forward."""
        from ... import ndarray as F

        x = self._embed_step(tokens, pos)
        mem_vl = state["mem_vl"]
        mem_vl_nd = None if mem_vl is None else NDArray(mem_vl)
        new_self = []
        for i in range(self.decoder._n):
            layer = getattr(self.decoder, f"layer{i}")
            x, kv = layer.step(x, state["self_kv"][i], pos,
                               state["cross_kv"][i],
                               mem_valid_length=mem_vl_nd)
            new_self.append(kv)
        out = self.decoder.ln(x)
        logits = self._logits(F, out)[:, 0]
        return logits, {"self_kv": tuple(new_self),
                        "cross_kv": state["cross_kv"], "mem_vl": mem_vl}

    def _embed_step(self, tokens, pos):
        """Single-position target embedding (token + absolute position).
        ``pos`` is a scalar (every row at the same depth — the dense
        decode loop) or a per-row (B,) vector (paged continuous batching,
        where each slot sits at its own depth)."""
        tok = tokens.data if isinstance(tokens, NDArray) else \
            jnp.asarray(tokens)
        B = tok.shape[0]
        ids = NDArray(tok.reshape(B, 1).astype(jnp.int32))
        pos_ids = NDArray(jnp.broadcast_to(
            jnp.asarray(pos, jnp.int32).reshape(-1, 1), (B, 1)))
        return self.drop(self.tgt_embed(ids) * (self._units ** 0.5)
                         + self.pos_embed(pos_ids))

    # -------------------------------------------------------- paged decode
    # The paged protocol (continuous batching, ISSUE 8): K/V live in shared
    # per-layer (num_pages, page_size, H, D) pools (declared (num_pages,
    # page_size, H x D) at heads narrower than the lanes) with per-slot page
    # tables; cross-attention memory sits in per-slot (slots, mem_len, H,
    # D) buffers written once at admission. The batch dimension is the
    # SLOT menu — static shape, dynamic occupancy.

    def init_paged_state(self, slots, num_pages, page_size, mem_len,
                         dtype=None):
        """Allocate the paged decode state: per-decoder-layer K/V pools,
        per-slot cross-attention memory buffers, and the per-slot source
        valid lengths. ``state['page_tables']`` starts all-trash (page 0);
        the serving-side ``PagePool`` owns the real table."""
        k_pools, v_pools, cross_k, cross_v = [], [], [], []
        for i in range(self.decoder._n):
            layer = getattr(self.decoder, f"layer{i}")
            kp, vp = layer.self_attn.init_page_pool(num_pages, page_size,
                                                    dtype)
            k_pools.append(kp)
            v_pools.append(vp)
            H = layer.cross_attn._num_heads
            D = layer.cross_attn._head_dim
            dt = dtype if dtype is not None \
                else layer.cross_attn.out_proj.weight.dtype
            # distinct buffers: the state is a donated carry
            shape = (int(slots), int(mem_len), H, D)
            cross_k.append(jnp.zeros(shape, jnp.dtype(dt)))
            cross_v.append(jnp.zeros(shape, jnp.dtype(dt)))
        return {
            "k_pools": tuple(k_pools), "v_pools": tuple(v_pools),
            "cross_k": tuple(cross_k), "cross_v": tuple(cross_v),
            "mem_vl": jnp.zeros((int(slots),), jnp.int32),
        }

    def prefill_paged(self, src_ids, tgt_prime, src_valid_length, state,
                      slot_ids, first_pages, active):
        """Admission prefill INTO pages: run the identical prefill forward
        (``prefill_parts``) over a padded admission batch, then scatter
        row ``r``'s prefix K/V into page ``first_pages[r]`` and its memory
        projections into slot ``slot_ids[r]``'s cross buffers.

        Rows with ``active[r]`` False are padding: their page writes land
        in the trash page 0 and their slot writes carry an out-of-bounds
        ``slot_ids[r]`` (= slots), which jax scatter semantics DROP — so
        one fixed ``(slots, bucket)`` admission shape serves any number of
        admitted requests without touching live slots. Returns
        ``(last_logits, new_state)``; the single-column prime (BOS) lands
        at logical position 0, so the admitted row starts with cache
        length 1."""
        if tgt_prime.shape[1] != 1:
            raise MXNetError(
                "prefill_paged primes with a single BOS column; explicit "
                "prefixes decode through the dense engine path")
        logits, self_parts, cross_parts, vl_raw = self.prefill_parts(
            src_ids, tgt_prime, src_valid_length)
        first_pages = jnp.where(active, jnp.asarray(first_pages, jnp.int32),
                                0)
        mem_len = state["cross_k"][0].shape[1]
        k_pools, v_pools, cross_k, cross_v = [], [], [], []
        for i in range(self.decoder._n):
            k_s, v_s = self_parts[i]
            kp, vp = state["k_pools"][i], state["v_pools"][i]
            row = (k_s.shape[0],) + kp.shape[2:]    # as the pool is declared
            kp = kp.at[first_pages, 0].set(
                k_s[:, 0].astype(kp.dtype).reshape(row))
            vp = vp.at[first_pages, 0].set(
                v_s[:, 0].astype(vp.dtype).reshape(row))
            k_pools.append(kp)
            v_pools.append(vp)
            k_m, v_m = cross_parts[i]
            pad = mem_len - k_m.shape[1]
            if pad:
                widths = ((0, 0), (0, pad), (0, 0), (0, 0))
                k_m = jnp.pad(k_m, widths)
                v_m = jnp.pad(v_m, widths)
            dt = state["cross_k"][i].dtype
            cross_k.append(state["cross_k"][i].at[slot_ids].set(
                k_m.astype(dt)))
            cross_v.append(state["cross_v"][i].at[slot_ids].set(
                v_m.astype(dt)))
        vl = vl_raw if vl_raw is not None else jnp.full(
            (src_ids.shape[0],), src_ids.shape[1], jnp.int32)
        mem_vl = state["mem_vl"].at[slot_ids].set(vl.astype(jnp.int32))
        new_state = {"k_pools": tuple(k_pools), "v_pools": tuple(v_pools),
                     "cross_k": tuple(cross_k), "cross_v": tuple(cross_v),
                     "mem_vl": mem_vl}
        return logits, new_state

    def prefill_suffix_paged(self, tokens, token_vl, q_offset, state,
                             page_tables, slot_ids, active, wide=False):
        """Prefix-cache suffix prefill: decode-side forward over ONLY the
        uncached tail of each admitted row's target prefix, at absolute
        positions ``q_offset[r] + j``.

        ``tokens`` (B, S) int32 are the left-aligned suffix token ids
        (``token_vl`` (B,) of them real per row); ``page_tables`` (B, P)
        are the admitted rows' page-table rows (padding rows all-trash);
        ``slot_ids`` (B,) map rows to slots for the cross-memory gather —
        the slot's cross buffers and ``mem_vl`` must already be populated
        (by a prior ``prefill_paged``, an adopted cache root, or a disagg
        handoff; this method deliberately runs NO encoder — skipping it
        is the point of a prefix hit). Padding rows carry out-of-bounds
        ``slot_ids`` whose gathers clamp harmlessly and whose page writes
        land in trash.

        Bit-identity contract: each position runs through the exact
        ``decode_step_paged`` program (a teacher-forced ``fori_loop``,
        one position per step) rather than one batched multi-token
        attention — a wide-S pass computes the same math but rounds
        differently per shape, so cached pages would drift from the
        token-at-a-time stream in the last float bits. Per-step bodies
        are shape-identical no matter where the cached/uncached split
        falls, which is what makes a cache-hit replay bit-identical to
        the cold path (asserted in tests/test_prefix.py). ``wide=True``
        opts out of that contract for speed: the whole suffix runs as
        ONE ``decode_window_paged`` pass — the q_offset-aware shape the
        Pallas paged window kernel accelerates — computing the same
        masked-softmax math with wide-shape rounding (equal argmax in
        practice, not bit-exact). Returns ``(last_logits, new_state)``
        with row ``r``'s logits taken at suffix position
        ``token_vl[r] - 1`` — the first new token's."""
        import jax

        tok = tokens.data if isinstance(tokens, NDArray) else \
            jnp.asarray(tokens)
        tok = tok.astype(jnp.int32)
        S = tok.shape[1]
        q_offset = jnp.asarray(q_offset, jnp.int32)
        token_vl = jnp.asarray(token_vl, jnp.int32)
        active = jnp.asarray(active, jnp.bool_)
        # per-row cross memory gathered by slot once; empty slots report
        # mem_vl 0 — clamp so padding rows' masked softmax stays finite
        # (their output is discarded anyway)
        sub = {"k_pools": state["k_pools"], "v_pools": state["v_pools"],
               "cross_k": tuple(c[slot_ids] for c in state["cross_k"]),
               "cross_v": tuple(c[slot_ids] for c in state["cross_v"]),
               "mem_vl": jnp.maximum(state["mem_vl"][slot_ids], 1)}

        if wide:
            logits, sub = self.decode_window_paged(
                NDArray(tok), q_offset, sub, page_tables, active,
                window_vl=token_vl)
            lg = logits.data if isinstance(logits, NDArray) else logits
            idx = jnp.clip(token_vl - 1, 0, S - 1).astype(jnp.int32)
            last = jnp.take_along_axis(lg, idx[:, None, None], axis=1)[:, 0]
            new_state = dict(state)
            new_state["k_pools"] = sub["k_pools"]
            new_state["v_pools"] = sub["v_pools"]
            return last, new_state

        def one(j, sub):
            tok_j = jax.lax.dynamic_index_in_dim(tok, j, axis=1,
                                                 keepdims=False)
            live = jnp.logical_and(active, j < token_vl)
            lg, sub = self.decode_step_paged(
                NDArray(tok_j), q_offset + j, sub, page_tables, live)
            return (lg.data if isinstance(lg, NDArray) else lg), sub

        last, sub = one(0, sub)

        def body(j, carry):
            sub, last = carry
            lg, sub = one(j, sub)
            return sub, jnp.where((j == token_vl - 1)[:, None], lg, last)

        if S > 1:
            sub, last = jax.lax.fori_loop(1, S, body, (sub, last))
        new_state = dict(state)
        new_state["k_pools"] = sub["k_pools"]
        new_state["v_pools"] = sub["v_pools"]
        return last, new_state

    def decode_window_paged(self, tokens, pos, state, page_tables, active,
                            window_vl=None):
        """An S-token window through the paged cache in ONE forward:
        ``tokens`` (slots, S) int32 at per-row absolute positions
        ``pos[r] + j``. This is the speculative-verification shape — one
        dispatch scores a drafted window against the target model — and
        the wide (non-bit-exact) suffix-replay shape. ``window_vl``
        (slots,) marks tokens ``>= window_vl[r]`` as padding (their K/V
        land in trash, their logits are garbage). Returns ``(logits
        (slots, S, vocab), new_state)``; column ``j`` predicts position
        ``pos[r] + j + 1``'s token, matching ``decode_step_paged`` run
        sequentially up to attention-order float rounding."""
        from ... import ndarray as F

        tok = tokens.data if isinstance(tokens, NDArray) else \
            jnp.asarray(tokens)
        tok = tok.astype(jnp.int32)
        B, S = tok.shape
        pos = jnp.asarray(pos, jnp.int32)
        pos_ids = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        x = self.drop(self.tgt_embed(NDArray(tok)) * (self._units ** 0.5)
                      + self.pos_embed(NDArray(pos_ids)))
        mem_vl_nd = NDArray(state["mem_vl"])
        k_pools, v_pools = [], []
        for i in range(self.decoder._n):
            layer = getattr(self.decoder, f"layer{i}")
            x, kp, vp = layer.step_window_paged(
                x, state["k_pools"][i], state["v_pools"][i], page_tables,
                pos, active, (state["cross_k"][i], state["cross_v"][i]),
                mem_valid_length=mem_vl_nd, window_vl=window_vl)
            k_pools.append(kp)
            v_pools.append(vp)
        out = self.decoder.ln(x)
        logits = self._logits(F, out)
        new_state = dict(state)
        new_state["k_pools"] = tuple(k_pools)
        new_state["v_pools"] = tuple(v_pools)
        return logits, new_state

    def decode_step_paged(self, tokens, pos, state, page_tables, active):
        """One O(1) paged decode step over the SLOT batch: ``tokens``
        (slots,) int32 at per-row absolute positions ``pos`` (slots,),
        gathered/scattered through ``page_tables`` (slots, P). Rows with
        ``active`` False write to the trash page and their logits are
        garbage (the scheduler discards them). Returns ``(logits,
        new_state)`` with the updated pools."""
        from ... import ndarray as F

        x = self._embed_step(tokens, pos)
        mem_vl_nd = NDArray(state["mem_vl"])
        k_pools, v_pools = [], []
        for i in range(self.decoder._n):
            layer = getattr(self.decoder, f"layer{i}")
            x, kp, vp = layer.step_paged(
                x, state["k_pools"][i], state["v_pools"][i], page_tables,
                pos, active, (state["cross_k"][i], state["cross_v"][i]),
                mem_valid_length=mem_vl_nd)
            k_pools.append(kp)
            v_pools.append(vp)
        out = self.decoder.ln(x)
        logits = self._logits(F, out)[:, 0]
        new_state = dict(state)
        new_state["k_pools"] = tuple(k_pools)
        new_state["v_pools"] = tuple(v_pools)
        return logits, new_state

    def generate(self, src_ids, src_valid_length=None, max_new_tokens=32,
                 **kwargs):
        """KV-cached generation through a lazily-built (and cached)
        ``parallel.infer.InferStep``. Engine kwargs (``amp``, ``max_len``,
        ``bos_id``/``eos_id``/``pad_id``) configure the cached engine;
        the rest (``method``, ``top_k``, ``temperature``, ``seed``) pass
        through.

        Greedy calls route through a cached ``serving.ContinuousBatcher``
        (iteration-level scheduling over the paged KV pool — rows that hit
        EOS free their slot and pages immediately). Sampling, or an
        explicit ``seed``, keeps the direct path (``engine.generate``):
        its key schedule is per-dispatch and only reproducible there.
        Returns ``(tokens, lengths)`` NDArrays either way."""
        from ...parallel.infer import InferStep

        eng_keys = ("amp", "max_len", "bos_id", "eos_id", "pad_id")
        eng_kw = {k: kwargs.pop(k) for k in eng_keys if k in kwargs}
        cache_key = tuple(sorted(eng_kw.items()))
        steps = getattr(self, "_infer_steps", None)
        if steps is None:
            steps = {}
            object.__setattr__(self, "_infer_steps", steps)
        if cache_key not in steps:
            steps[cache_key] = InferStep(self, **eng_kw)
        engine = steps[cache_key]
        if self._use_batcher_path(kwargs):
            return self._generate_batched(engine, cache_key, src_ids,
                                          src_valid_length,
                                          max_new_tokens, **kwargs)
        return engine.generate(
            src_ids, src_valid_length, max_new_tokens=max_new_tokens,
            **kwargs)

    @staticmethod
    def _use_batcher_path(kwargs) -> bool:
        # a sampled run's key schedule is per-dispatch: direct path only
        return kwargs.get("method", "greedy") == "greedy" and \
            kwargs.get("seed") is None

    def _generate_batched(self, engine, cache_key, src_ids,
                          src_valid_length, max_new_tokens, **kwargs):
        """One synchronous generate() call as N serving requests through a
        cached ContinuousBatcher: submit every row, gather the trimmed
        token lists back into the ``decode_n``-shaped ``(tokens (B,
        max_new), lengths (B,))`` pair."""
        import numpy as _np

        from ... import ndarray as _nd
        from ...serving.batcher import ContinuousBatcher

        src = src_ids.asnumpy() if hasattr(src_ids, "asnumpy") \
            else _np.asarray(src_ids)
        src = src.astype(_np.int32)
        B, L = src.shape
        if src_valid_length is None:
            vl = _np.full((B,), L, _np.int32)
        else:
            vl = (src_valid_length.asnumpy()
                  if hasattr(src_valid_length, "asnumpy")
                  else _np.asarray(src_valid_length)).astype(_np.int32)
        max_new = int(max_new_tokens)
        batchers = getattr(self, "_batchers", None)
        if batchers is None:
            batchers = {}
            object.__setattr__(self, "_batchers", batchers)
        bk = (cache_key, B, L)
        bat = batchers.get(bk)
        if bat is None or bat.max_new < max_new:
            if bat is not None:
                bat.stop()
            bat = ContinuousBatcher(
                engine, bucket_keys=(L,), slots=min(B, 8),
                max_new_tokens=max(max_new, 8),
                sampling={k: v for k, v in kwargs.items()
                          if k in ("method", "top_k", "temperature")},
                name="generate")
            batchers[bk] = bat
        futs = [bat.submit(src[i, :vl[i]] if vl[i] else src[i, :1],
                           max_new_tokens=max_new) for i in range(B)]
        toks = _np.full((B, max_new), bat._pad, _np.int32)
        lengths = _np.zeros((B,), _np.int32)
        for i, f in enumerate(futs):
            got = f.result(timeout=600)
            n = min(len(got), max_new)
            toks[i, :n] = got[:n]
            lengths[i] = n
        return _nd.array(toks, dtype="int32"), \
            _nd.array(lengths, dtype="int32")


def transformer_base(**kwargs):
    return TransformerModel(units=512, hidden_size=2048, num_layers=6,
                            num_heads=8, **kwargs)


def transformer_big(**kwargs):
    return TransformerModel(units=1024, hidden_size=4096, num_layers=6,
                            num_heads=16, **kwargs)

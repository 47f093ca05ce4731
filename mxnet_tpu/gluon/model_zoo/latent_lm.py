"""The latent-attention language model both ``joyai.JoyAILM`` and
``xing.XingLM`` are (the DeepSeek-V3 lineage): a decoder-only stack with
RMSNorm, multi-head LATENT attention (``ops/mla.py``: a low-rank query, one
cached latent vector and one shared rotary key a token, interleaved rotary
pairs, plain or stretched by YaRN), leading dense SwiGLU layers, then expert
layers whose router scores by a sigmoid, selects with a bias that does not
weigh, renormalises and scales, beside a shared expert that every token
takes (``ops/pallas/grouped_swiglu.py``), an untied head, and ONE
multi-token-prediction module: a block of the expert-layer kind that reads
the last layer's hidden state and the NEXT token and predicts the token
after it.

What a net of this family may change is HOW A SUBLAYER MEETS THE RESIDUAL
STREAM, through four small methods: ``_enter`` (the stream from an
embedding), ``_sublayer`` (a sublayer's input taken from the stream and its
output put back), ``_shape`` (the stream's leading axes) and ``_leave``
(the one hidden state a token has at the stack's end). Here the stream is
the hidden state itself and a sublayer's output is added to it.

``experts_held = (first, n)`` builds one chip's share of an expert-parallel
deployment: the router keeps all ``num_experts`` outputs and its ``k`` a
token, the weights hold experts ``first .. first + n - 1``, and what the
absent experts would add is left out (no code stands in for the other
chips).

The net speaks the paged protocol of a model with no encoder
(``paged_slot_state``) with a page of ONE vector a token: one pool a layer,
``(num_pages, page, width)``, the module's own among them, where the nets
before it keep K and V. ``width`` is ``rank + rope`` rounded up to whole
lanes of 128 and the rest is zero (576 numbers in 640): the chip tiles an
array's last axis by 128, and for a last axis of 576 its compiler would
rather make the PAGE axis the last one in memory, which a kernel that
reads a page as rows of positions can only take through a copy of the
whole pool a call. The chunk program (``prefill_suffix_paged``)
expands the latents to per-head keys and values; the decode step absorbs
the up-projections into the query and the output and reads the pages as
they lie.

A decode step yields ONE OR TWO tokens a row (``step_tokens`` 2): the
module drafts the token after next, the step feeds ``[last token, draft]``
at positions ``[p, p + 1]`` and hands back both positions' logits with the
draft; ``InferStep`` keeps the second token where the draft was the first
position's own. The module runs one position BEHIND the model (its input
at position ``i`` is the hidden state there and token ``i + 1``), so three
arrays indexed by slot carry what it needs across steps and chunks: the
hidden states of the last step's positions ``mtp_h (slots, 3, H)``, their
next tokens ``mtp_tok (slots, 2)``, and that step's position ``mtp_pos``.
The next step reads from its own position how many tokens were kept and
takes the pair of hidden states that stand. A refused draft costs one
cached position, which the next step overwrites.

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
from ...ops import mla as _mla
from ...ops import paged as _paged
from ...ops.pallas import grouped_swiglu as _moe
from ..block import HybridBlock
from .keye import rms_norm

__all__ = ["LatentLM", "COUNTS"]

COUNTS = ("latent_keys", "row_steps", "calls", "pairs_all", "pairs_held",
          "experts_touched", "expert_layers", "mtp_drafts", "mtp_accepted")


def _swiglu(x, gate, up, down):
    g = jnp.dot(x, gate)
    return jnp.dot(jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype)
                   * jnp.dot(x, up), down)


def slot_state(names):
    """``paged_slot_state`` of a net of this family that keeps ``names`` as
    its device-side counts."""
    return {
        "pools": ("latent_pools",), "encoder_memory": False,
        "slot_arrays": ("mtp_h", "mtp_tok", "mtp_pos"), "step_tokens": 2,
        "counts": tuple((name, 1) for name in names)}


class LatentLM(HybridBlock):
    """The language model. Widths default to JoyAI-LLM-Flash's published
    ones; matrices are stored ``(in, out)`` and experts ``(n, in, out)``.
    ``rope_scaling`` is the configuration's group (``type: yarn``) or
    None."""

    # a dispatch's counts: cached positions ONE layer read (a row's, once:
    # a step's two queries share the read), live rows x steps, calls of
    # the program, (token, expert) pairs the routers made and those that
    # fell on held experts, distinct held experts touched (summed over
    # expert layers and calls), expert layers run, drafts verified and
    # drafts kept (InferStep adds these two)
    COUNTS = COUNTS
    # what a serving slot keeps: one paged array a layer (the latent
    # vector), three small arrays indexed by slot for the module, no
    # encoder memory; a decode step yields up to two tokens a row
    paged_slot_state = slot_state(COUNTS)

    def __init__(self, vocab_size=129280, hidden_size=2048, num_layers=40,
                 num_heads=32, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 intermediate_size=7168, first_dense=1, num_experts=256,
                 experts_held=None, experts_per_tok=8, expert_width=768,
                 shared_experts=1, routed_scaling=2.5, rope_theta=32e6,
                 rms_eps=1e-6, rope_scaling=None, latent_dtype=None,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if v_head_dim != qk_nope_head_dim:
            raise MXNetError(
                "the chunk program reads a head's keys and values as two "
                f"column blocks of one width: v_head_dim ({v_head_dim}) "
                f"must be qk_nope_head_dim ({qk_nope_head_dim})")
        first, held = (0, num_experts) if experts_held is None \
            else (int(experts_held[0]), int(experts_held[1]))
        if not 0 <= first < first + held <= num_experts:
            raise MXNetError(f"experts_held {experts_held} is no run of the "
                             f"{num_experts} experts")
        self._h, self._n = hidden_size, num_layers
        self._nh, self._dn, self._dr = num_heads, qk_nope_head_dim, \
            qk_rope_head_dim
        self._rank = kv_lora_rank
        # a cached position's row: whole lanes (see the module's docstring)
        self._width = -(-(kv_lora_rank + qk_rope_head_dim) // 128) * 128
        self._dense = {f"l{i}_" for i in range(int(first_dense))}
        self._e, self._k = num_experts, experts_per_tok
        self._held = None if held == num_experts else (first, held)
        self._scale = float(routed_scaling)
        self._theta, self._eps = float(rope_theta), float(rms_eps)
        self._sm = 1.0 / math.sqrt(qk_nope_head_dim + qk_rope_head_dim)
        self._yarn = self._rope_factor = None
        if rope_scaling is not None:
            y = dict(rope_scaling)
            if y.get("type") != "yarn":
                raise MXNetError(f"rope_scaling of type {y.get('type')!r}: "
                                 "only 'yarn' is built")
            self._yarn = _mla.yarn_inverse_frequencies(
                self._theta, qk_rope_head_dim // 2, y["factor"],
                y["original_max_position_embeddings"], y["beta_fast"],
                y["beta_slow"])
            all_dim = _mla.yarn_mscale(y["factor"], y["mscale_all_dim"])
            self._rope_factor = _mla.yarn_mscale(y["factor"], y["mscale"]) \
                / all_dim
            if y["mscale_all_dim"]:
                self._sm *= all_dim * all_dim
        # the precision a latent is cached in. A type narrower than the
        # pool's cells (a float8) is rounded to at the write and kept in
        # those cells: no kernel here reads one-byte pages
        self._latent_dtype = None if latent_dtype is None \
            else jnp.dtype(latent_dtype)
        h, f, sf = hidden_size, expert_width, expert_width * shared_experts
        shapes = {"embed": (vocab_size, h), "norm": (h,),
                  "head": (h, vocab_size), "mtp_enorm": (h,),
                  "mtp_hnorm": (h,), "mtp_eh_proj": (2 * h, h),
                  "mtp_norm": (h,)}
        for p, dense in [(f"l{i}_", i < first_dense)
                         for i in range(num_layers)] + [("mtp_", False)]:
            shapes.update({
                p + "attn_norm": (h,), p + "wq_a": (h, q_lora_rank),
                p + "q_norm": (q_lora_rank,),
                p + "wq_b": (q_lora_rank, num_heads
                             * (qk_nope_head_dim + qk_rope_head_dim)),
                p + "wkv_a": (h, kv_lora_rank + qk_rope_head_dim),
                p + "kv_norm": (kv_lora_rank,),
                p + "wkv_b": (kv_lora_rank, num_heads
                              * (qk_nope_head_dim + v_head_dim)),
                p + "wo": (num_heads * v_head_dim, h),
                p + "mlp_norm": (h,)})
            if dense:
                shapes.update({p + "dense_gate": (h, intermediate_size),
                               p + "dense_up": (h, intermediate_size),
                               p + "dense_down": (intermediate_size, h)})
            else:
                shapes.update({
                    p + "router": (h, num_experts),
                    p + "router_bias": (num_experts,),
                    p + "w_gate": (held, h, f), p + "w_up": (held, h, f),
                    p + "w_down": (held, f, h),
                    p + "shared_gate": (h, sf), p + "shared_up": (h, sf),
                    p + "shared_down": (sf, h)})
            shapes.update(self._sublayer_shapes(p))
        with self.name_scope():
            for name, shape in shapes.items():
                if name.endswith(("norm", "alpha")):
                    init = _init.One()
                elif name.endswith("bias"):
                    init = _init.Zero()
                else:
                    init = _init.Normal(1.0 / math.sqrt(shape[-2]))
                setattr(self, name, self.params.get(
                    name, shape=shape, dtype=dtype, init=init))

    # ------------------------------------------- a sublayer and the stream
    def _sublayer_shapes(self, p):
        """Parameters block ``p`` keeps for its sublayers' meeting with
        the stream: none here."""
        return {}

    def _enter(self, x, p):
        """The stream of a stack whose first block is ``p``, from its
        input ``x (..., H)``."""
        return x

    def _sublayer(self, name, s, f):
        """Stream ``s`` through sublayer ``name`` (a block's prefix and
        ``attn`` or ``mlp``). ``f`` takes the sublayer's input as the
        stream gives it, norms it itself, and returns ``(terms, rest)``:
        what the sublayer adds, and whatever else the caller wants back
        (called, where it is a function, once the terms are in). Returns
        ``(stream, rest)``."""
        terms, rest = f(s)
        for t in terms:
            s = s + t
        return s, rest() if callable(rest) else rest

    def _shape(self, s, lead):
        """The stream with the leading axes ``lead`` over its tokens."""
        return s.reshape(tuple(lead) + (self._h,))

    def _leave(self, s):
        """A stack's one hidden state a token, ``(..., H)``."""
        return s

    def _extra_counts(self, live, m_live, q_pos=None):
        """Counts a subclass keeps past ``COUNTS``'s nine, for one
        dispatch over the model's ``live`` and the module's ``m_live``
        positions (the latter made when asked for; ``q_pos`` in a
        window, None in a decode step)."""
        return ()

    # ------------------------------------------------------------ pieces
    def _w(self, name):
        v = getattr(self, name).data()
        return v.data if isinstance(v, NDArray) else v

    def _embed(self, tok):
        return jnp.take(self._w("embed"), tok, axis=0)

    def _logits(self, x, norm="norm"):
        y = rms_norm(x, self._w(norm), self._eps)
        return jnp.dot(y, self._w("head"),
                       preferred_element_type=jnp.float32)

    def _queries(self, p, u, pos):
        """``(q_nope (..., heads, nope), q_rope (..., heads, rope))`` of
        block ``p`` for the normed input ``u (..., H)`` at ``pos``, the
        rotary part turned, both scaled by the softmax's."""
        cq = rms_norm(jnp.dot(u, self._w(p + "wq_a")),
                      self._w(p + "q_norm"), self._eps)
        q = jnp.dot(cq, self._w(p + "wq_b")).reshape(
            u.shape[:-1] + (self._nh, self._dn + self._dr))
        qr = self._rope(q[..., self._dn:], pos[..., None])
        sm = jnp.asarray(self._sm, q.dtype)
        return q[..., :self._dn] * sm, self._lanes(qr * sm, self._dr)

    def _rope(self, x, pos):
        """Interleaved rotary pairs at ``pos``: by ``theta``, or by YaRN's
        table where the configuration stretches the context."""
        if self._yarn is None:
            return _mla.rope_interleaved(x, pos, _mla.inverse_frequencies(
                self._theta, x.shape[-1] // 2))
        return _mla.rope_interleaved(x, pos, jnp.asarray(self._yarn),
                                     self._rope_factor)

    def _lanes(self, x, used):
        """``x (..., used)`` with zeros up to the cached row's rotary part
        (``width - rank``): the query's and the key's alike."""
        pad = self._width - self._rank - used
        return x if not pad else jnp.pad(
            x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def _latent(self, p, u, pos):
        """What a position caches, ``[norm(c); rope(k_r); zeros] (...,
        width)``, in the precision the latent is cached in."""
        ckr = jnp.dot(u, self._w(p + "wkv_a"))
        c = rms_norm(ckr[..., :self._rank], self._w(p + "kv_norm"),
                     self._eps)
        kr = self._rope(ckr[..., self._rank:], pos)
        lat = jnp.concatenate([c, self._lanes(kr, self._dr)], -1)
        if self._latent_dtype is not None:
            lat = lat.astype(self._latent_dtype).astype(ckr.dtype)
        return lat

    def _up_proj(self, p):
        """``W_kvb`` by head: ``(rank, heads, nope + v)``."""
        return self._w(p + "wkv_b").reshape(self._rank, self._nh,
                                            2 * self._dn)

    def _ffn(self, p, h, valid):
        """The feed-forward sublayer of block ``p`` for ``_sublayer``: the
        terms of ``FFN(RMSNorm(h))`` for ``h (T, H)`` (the dense SwiGLU
        where the block has one, else the routed experts this net holds
        and the shared expert), and ``(pairs_all, pairs_held, touched)``."""
        u = rms_norm(h, self._w(p + "mlp_norm"), self._eps)
        if p in self._dense:
            zero = jnp.int32(0)
            return [_swiglu(u, self._w(p + "dense_gate"),
                            self._w(p + "dense_up"),
                            self._w(p + "dense_down"))], (zero, zero, zero)
        out, per_expert = _moe.moe_experts(
            u, self._w(p + "router"), self._w(p + "w_gate"),
            self._w(p + "w_up"), self._w(p + "w_down"), self._k, valid,
            scoring="sigmoid", bias=self._w(p + "router_bias"),
            scale=self._scale, held=self._held)
        first, n = self._held or (0, self._e)
        mine = per_expert[first:first + n]
        shared = _swiglu(u, self._w(p + "shared_gate"),
                         self._w(p + "shared_up"), self._w(p + "shared_down"))
        return [out, shared], lambda: (
            jnp.sum(per_expert), jnp.sum(mine), jnp.sum(mine > 0))

    def _blocks(self):
        """``(parameter prefix, pool index)`` of the model's own layers."""
        return [(f"l{i}_", i) for i in range(self._n)]

    @staticmethod
    def _tally(counts, keys, rows, calls, parts, more=()):
        """``counts`` plus one dispatch's: ``parts`` are the ``(pairs_all,
        pairs_held, touched)`` of the blocks it ran, ``more`` what a
        subclass counts past the nine."""
        expert = [p for p in parts if p is not None]
        add = [keys, rows, calls, sum(p[0] for p in expert),
               sum(p[1] for p in expert), sum(p[2] for p in expert),
               len(expert), 0, 0, *more]
        return counts + jnp.stack([jnp.asarray(a, jnp.int32) for a in add])

    # ------------------------------------------------------ paged protocol
    def init_paged_state(self, slots, num_pages, page_size, mem_len,
                         dtype=None):
        """One pool a block, the module's last: ``(num_pages, page,
        width)``, page 0 the trash page; and the module's carry a slot."""
        dt = jnp.dtype(dtype if dtype is not None else self.embed.dtype)
        page = (int(num_pages), int(page_size), self._width)
        slots = int(slots)
        # distinct buffers: the state is a donated carry
        return {
            "latent_pools": tuple(jnp.zeros(page, dt)
                                  for _ in range(self._n + 1)),
            "mtp_h": (jnp.zeros((slots, 3, self._h), dt),),
            "mtp_tok": (jnp.zeros((slots, 2), jnp.int32),),
            "mtp_pos": (jnp.zeros((slots,), jnp.int32),),
            "counts": jnp.zeros((len(self.COUNTS),), jnp.int32)}

    def _window_block(self, p, s, pool, q_offset, rows, live, page_tables,
                      last, buf):
        """One block over a window: the stream ``s`` of ``(R, C)`` tokens
        at ``q_offset[r] + c`` (the module's first query may stand before
        position 0, where it sees nothing and nobody reads it), its latents
        written at ``rows`` of ``pool``, expanded attention, then the
        block's feed-forward. Returns ``(s, pool, buf, parts)``."""
        R, C = live.shape
        q_pos = q_offset[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]

        def attend(x):
            u = rms_norm(x, self._w(p + "attn_norm"), self._eps)
            qn, qr = self._queries(p, u, q_pos)
            written = _paged.write_rows(pool, rows, self._latent(p, u, q_pos)
                                        .reshape(R * C, -1))
            attn, new_buf = _mla.window_attention(
                qn, qr, written, self._w(p + "wkv_b"), page_tables, q_offset,
                last, buf)
            return [jnp.dot(attn, self._w(p + "wo"))], (written, new_buf)

        s, (pool, buf) = self._sublayer(p + "attn", s, attend)
        s = self._shape(s, (R * C,))
        s, parts = self._sublayer(
            p + "mlp", s, lambda h: self._ffn(p, h, live.reshape(R * C)))
        return self._shape(s, (R, C)), pool, buf, \
            None if p in self._dense else parts

    def _rows(self, page_tables, pos, live, page):
        """Flattened pool rows of positions ``pos``; what must not land
        (padding, an inert row, a position before 0 or past the table)
        goes to the trash page."""
        L = page_tables.shape[1] * page
        ok = jnp.logical_and(live, jnp.logical_and(pos >= 0, pos < L))
        at = jnp.clip(pos, 0, L - 1)
        return jnp.where(ok, _paged.token_rows(page_tables, at, page),
                         at % page)

    def _window(self, tok, q_pos, token_vl, state, page_tables, slot_ids,
                active):
        """The window forward: ``tok (R, C)`` at positions ``q_pos (R,
        C)``, of which the first ``token_vl`` of an ``active`` row are
        real. The model's blocks run at ``q_pos``; the module runs one
        position behind (at ``q_pos - 1``: the hidden state there, which
        for the window's first query is what slot ``slot_ids[r]`` carries,
        and this window's token). Returns ``(x, module's x, new_state)``,
        both ``(R, C, H)``."""
        R, C = tok.shape
        pools = list(state["latent_pools"])
        slots = state["mtp_pos"][0].shape[0]
        page = pools[0].shape[1]
        live = jnp.logical_and(active[:, None],
                               jnp.arange(C)[None, :] < token_vl[:, None])
        rows = self._rows(page_tables, q_pos, live, page).reshape(R * C)
        last = jnp.max(jnp.where(live, q_pos, 0))
        buf = _mla.expansion_buffer(
            R, page_tables.shape[1] * page,
            self._nh * 2 * self._dn, C, pools[0].dtype)
        emb = self._embed(tok)
        blocks = self._blocks()
        s, parts = self._enter(emb, blocks[0][0]), []
        for p, i in blocks:
            s, pools[i], buf, part = self._window_block(
                p, s, pools[i], q_pos[:, 0], rows, live, page_tables, last,
                buf)
            parts.append(part)
        x = self._leave(s)
        # the module, one position behind: an inert row reads slot 0 and
        # writes nowhere
        read = jnp.clip(slot_ids, 0, slots - 1)
        write = jnp.where(active, slot_ids, slots)
        kept = jnp.take(state["mtp_h"][0], read, axis=0)    # (R, 3, H)
        behind = jnp.concatenate([kept[:, 1:2], x[:, :-1]], 1)
        m_pos = q_pos - 1
        m_live = jnp.logical_and(live, m_pos >= 0)
        xm = jnp.dot(jnp.concatenate([
            rms_norm(emb, self._w("mtp_enorm"), self._eps),
            rms_norm(behind, self._w("mtp_hnorm"), self._eps)], -1),
            self._w("mtp_eh_proj"))
        sm, pools[self._n], buf, part = self._window_block(
            "mtp_", self._enter(xm, "mtp_"), pools[self._n], m_pos[:, 0],
            self._rows(page_tables, m_pos, m_live, page).reshape(R * C),
            m_live, page_tables, last, buf)
        xm = self._leave(sm)
        parts.append(part)
        # what the next chunk, or the first decode step, finds: the hidden
        # states of the row's last two real positions and the last token
        tail = jnp.concatenate([kept[:, :2], x], 1)         # at q_pos - 2
        at = jnp.clip(token_vl, 0, C)[:, None, None] \
            + jnp.arange(2)[None, :, None]
        two = jnp.take_along_axis(tail, at, axis=1)
        end = jnp.clip(token_vl - 1, 0, C - 1)
        new = {
            "mtp_h": jnp.concatenate([two, two[:, :1]], 1),
            "mtp_tok": jnp.stack([
                jnp.take_along_axis(tok, end[:, None], 1)[:, 0],
                jnp.zeros((R,), jnp.int32)], 1),
            "mtp_pos": q_pos[:, 0] + token_vl - 1}
        slot = {k: (state[k][0].at[write].set(
            v.astype(state[k][0].dtype), mode="drop"),)
            for k, v in new.items()}
        counts = self._tally(
            state["counts"], jnp.sum(jnp.where(
                active, q_pos[:, 0] + token_vl, 0)), 0, 1, parts,
            self._extra_counts(live, lambda: m_live, q_pos))
        return x, xm, dict(slot, latent_pools=tuple(pools), counts=counts)

    def prefill_suffix_paged(self, tokens, token_vl, q_offset, state,
                             page_tables, slot_ids, active, wide=True):
        """One chunk of a prompt: ``tokens (R, C)`` at positions
        ``q_offset[r] + j`` (``j < token_vl[r]``; the rest is padding),
        latents written into the row's pages, the module carried in slot
        ``slot_ids[r]``'s arrays from the chunk before. Returns ``(logits
        (R, vocab) of each row's last real token, new_state)``; only a
        prompt's last chunk samples from them."""
        tok = (tokens.data if isinstance(tokens, NDArray)
               else jnp.asarray(tokens)).astype(jnp.int32)
        C = tok.shape[1]
        q_offset = jnp.asarray(q_offset, jnp.int32)
        token_vl = jnp.asarray(token_vl, jnp.int32)
        q_pos = q_offset[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        x, _, new_state = self._window(
            tok, q_pos, token_vl, state, jnp.asarray(page_tables, jnp.int32),
            jnp.asarray(slot_ids, jnp.int32), jnp.asarray(active, jnp.bool_))
        idx = jnp.clip(token_vl - 1, 0, C - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        return self._logits(last), new_state

    def _decode_block(self, p, s, pool, pos, live, page_tables):
        """One block over ``S`` positions a row, absorbed: the stream ``s``
        of ``(B, S)`` tokens at ``pos[b] + s``, ``live (B, S)``. Returns
        ``(s, pool, parts)``."""
        B, S = live.shape
        page = pool.shape[1]
        q_pos = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]

        def attend(x):
            u = rms_norm(x, self._w(p + "attn_norm"), self._eps)
            qn, qr = self._queries(p, u, q_pos)
            written = _paged.write_rows(
                pool,
                self._rows(page_tables, q_pos, live, page).reshape(B * S),
                self._latent(p, u, q_pos).reshape(B * S, -1))
            w = self._up_proj(p)
            qc = jnp.einsum("bshd,chd->bshc", qn, w[..., :self._dn])
            oc = _mla.decode_attention(qc, qr, written, page_tables, pos)
            attn = jnp.einsum("bshc,chd->bshd", oc, w[..., self._dn:]) \
                .reshape(B, S, self._nh * self._dn)
            return [jnp.dot(attn, self._w(p + "wo"))], written

        s, pool = self._sublayer(p + "attn", s, attend)
        s = self._shape(s, (B * S,))
        s, parts = self._sublayer(
            p + "mlp", s, lambda h: self._ffn(p, h, live.reshape(B * S)))
        return self._shape(s, (B, S)), pool, \
            None if p in self._dense else parts

    def _model_step(self, toks, pos, pools, live, page_tables):
        """The model's blocks over ``toks (B, S)`` at ``pos[b] + s``.
        Returns ``(x (B, S, H), pools, parts)``."""
        blocks = self._blocks()
        s, parts = self._enter(self._embed(toks), blocks[0][0]), []
        for p, i in blocks:
            s, pools[i], part = self._decode_block(
                p, s, pools[i], pos, live, page_tables)
            parts.append(part)
        return self._leave(s), pools, parts

    def _propose(self, tokens, pos, state, pools, page_tables, active):
        """The module's draft of the token at ``pos + 1``: its block over
        the row's two positions behind ``pos`` (the hidden states the last
        step left and the tokens after them), the second one's logits.
        How many tokens the last step kept is read from how far ``pos``
        moved. Returns ``(draft (B,), h_prev (B, H), pool, parts)``."""
        kept = jnp.clip(pos - state["mtp_pos"][0] - 1, 0, 1)
        at = kept[:, None, None] + jnp.arange(2)[None, :, None]
        h2 = jnp.take_along_axis(state["mtp_h"][0], at, axis=1)  # (B, 2, H)
        first = jnp.take_along_axis(state["mtp_tok"][0], kept[:, None], 1)
        nxt = jnp.concatenate([first, tokens[:, None]], 1)
        m_pos = pos - 2
        m_live = jnp.logical_and(
            active[:, None], m_pos[:, None] + jnp.arange(2)[None, :] >= 0)
        xm = jnp.dot(jnp.concatenate([
            rms_norm(self._embed(nxt), self._w("mtp_enorm"), self._eps),
            rms_norm(h2, self._w("mtp_hnorm"), self._eps)], -1),
            self._w("mtp_eh_proj"))
        sm, pool, part = self._decode_block(
            "mtp_", self._enter(xm, "mtp_"), pools[self._n], m_pos, m_live,
            page_tables)
        xm = self._leave(sm)
        draft = jnp.argmax(self._logits(xm[:, 1], "mtp_norm"), -1) \
            .astype(jnp.int32)
        return draft, h2[:, 1], pool, part

    def decode_step_paged(self, tokens, pos, state, page_tables, active):
        """One paged decode step over the SLOT batch, two positions a row:
        ``tokens (B,)`` at ``pos (B,)`` and the module's draft at ``pos +
        1``; row ``b`` IS slot ``b``. Returns ``((logits (B, 2, vocab),
        draft (B,)), new_state)``: where the draft is the token the caller
        takes from the first position's logits, the second position's are
        the next token's, and the caller moves the row two positions; else
        one, and the next step overwrites what this one cached at ``pos +
        1``. A row that is not ``active`` writes to the trash page and
        keeps its slot's arrays."""
        tok = (tokens.data if isinstance(tokens, NDArray)
               else jnp.asarray(tokens)).astype(jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        active = jnp.asarray(active, jnp.bool_)
        page_tables = jnp.asarray(page_tables, jnp.int32)
        pools = list(state["latent_pools"])
        draft, h_prev, pools[self._n], m_part = self._propose(
            tok, pos, state, pools, page_tables, active)
        both = jnp.stack([tok, draft], 1)
        live = jnp.broadcast_to(active[:, None], both.shape)
        x, pools, parts = self._model_step(both, pos, pools, live,
                                           page_tables)
        new = {"mtp_h": jnp.concatenate([h_prev[:, None], x], 1),
               "mtp_tok": jnp.stack([tok, draft], 1), "mtp_pos": pos}
        keep = lambda k, v: (jnp.where(  # noqa: E731
            active.reshape((-1,) + (1,) * (v.ndim - 1)),
            v.astype(state[k][0].dtype), state[k][0]),)
        n_live = jnp.sum(active)
        counts = self._tally(
            state["counts"], jnp.sum(jnp.where(active, pos + 2, 0)),
            n_live, 1, parts + [m_part],
            self._extra_counts(live, lambda: jnp.logical_and(
                live, pos[:, None] - 2 + jnp.arange(2)[None, :] >= 0)))
        return (self._logits(x), draft), dict(
            {k: keep(k, v) for k, v in new.items()},
            latent_pools=tuple(pools), counts=counts)

    # ------------------------------------------------------- full forward
    def forward_with_draft(self, tokens):
        """Teacher-forced ``(logits (B, S, vocab), module's logits (B, S -
        1, vocab))`` of ``tokens (B, S)``: the module's row ``i`` is its
        prediction of token ``i + 2`` from the hidden state at ``i`` and
        token ``i + 1``. One window over a throw-away cache whose pages
        lie in order."""
        tok = (tokens.data if isinstance(tokens, NDArray)
               else jnp.asarray(tokens)).astype(jnp.int32)
        B, S = tok.shape
        page = math.gcd(S, 128)
        pages = S // page
        state = self.init_paged_state(B, 1 + B * pages, page, 0,
                                      dtype=self._w("embed").dtype)
        tables = 1 + jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
        q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, xm, _ = self._window(
            tok, q_pos, jnp.full((B,), S, jnp.int32), state, tables,
            jnp.arange(B, dtype=jnp.int32), jnp.ones((B,), jnp.bool_))
        return self._logits(x), self._logits(xm[:, 1:], "mtp_norm")

    def hybrid_forward(self, F, tokens, **params):
        """Teacher-forced logits ``(B, S, vocab)`` of ``tokens (B, S)``."""
        return NDArray(self.forward_with_draft(tokens)[0])

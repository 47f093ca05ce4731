"""ZAYA1's language model (``model_type: zaya``; compressed convolutional
attention, arXiv:2510.04476, and the ZAYA1 report, arXiv:2511.17127): a
decoder-only stack whose every layer is an attention sublayer IN A
COMPRESSED LATENT and an expert sublayer of ONE full-width expert a token.

*Attention.* The residual is projected down to 8 query heads and 2
key/value heads of 128; queries and keys are then mixed over the last
three positions by two small causal convolutions (``cca_time0`` 2
depthwise, ``cca_time1`` 2 by head) over the 1,280 channels ``[q ; k]``, a
mean term couples each query head with its key head, every head is scaled
to length ``sqrt(128)`` (keys times a learned temperature a head), rotary
embedding turns the first half of a head, and the VALUE is shifted: head 0
is the token's own projection, head 1 the projection of the token before.
What a page keeps, ``k`` as attention reads it and the shifted ``v``, is
therefore a function of THREE positions, and 1 KB a token a layer.

*Experts.* The router is a small MLP with its own norm whose input is a
256-wide state carried from layer to layer (``r_l = d_l + gamma_l *
r_{l-1}``); it picks one of 16 SwiGLU experts of the model's own width by
``argmax(p + beta)`` and weighs it by ``p`` as it stands
(``ops/pallas/grouped_swiglu.dispatch_experts``: the grouping and the
grouped product Keye and JoyAI run, after a routing of this net's own).
Both sublayers merge into the residual with learned gains and biases a
channel: ``x <- (x + b_x) s_x + (o + b_o) s_o``.

The net speaks the paged protocol of a model with no encoder
(``paged_slot_state``) and is the first here whose EVERY layer keeps both
kinds of slot state: K/V pools under the page table, declared ``(num_pages,
page x 2, 128)``, a page's (key, head) rows on one axis as the paged window
kernel reads them (two heads on an axis of their own would be padded to
sixteen rows on the chip, eight times the bytes), and two arrays indexed
by SLOT: ``tail (slots, 2, 1280)``, what the two convolutions need of the
position before (row 0 its projections ``z[t-1]``, row 1 the first
convolution's output ``c0[t-1]``, which carries ``z[t-2]``), and
``value_half (slots, 128)``, the projection ``h[t-1] W_v2`` that the next
token's value takes as its second head. The chunk program
(``prefill_suffix_paged``) computes the convolutions over the chunk with
the slot's tail in front, starts from zero where ``q_offset`` is 0 (no
reset dispatch), and writes back the tail of the row's last REAL token;
``decode_step_paged`` leaves the arrays of rows that are not ``active``
bit for bit. The router's state lives within one token's pass through the
stack and is no slot state.

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
from ...ops.pallas import grouped_swiglu as _moe
from ..block import HybridBlock
from .keye import rms_norm

__all__ = ["ZayaLM"]

F32 = jnp.float32


def rope_partial(x, cos, sin):
    """Rotary embedding of the first ``2 x cos.shape[-1]`` dimensions of
    ``x (..., heads, D)`` float32 (dimension ``d`` pairs with ``d + n``
    within them); the rest passes."""
    n = cos.shape[-1]
    a, b, rest = x[..., :n], x[..., n:2 * n], x[..., 2 * n:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def to_length(x, length):
    """Each head of ``x (..., heads, D)`` float32 scaled to ``length``."""
    return x * (length * jax.lax.rsqrt(
        jnp.maximum(jnp.sum(x * x, -1, keepdims=True), 1e-24)))


class ZayaLM(HybridBlock):
    """The language model. Widths default to ZAYA1-8B's; matrices are
    stored ``(in, out)``, the first convolution ``(taps, channels)``, the
    second ``(taps, heads, in, out)``, an expert matrix ``(experts, in,
    out)``."""

    # what a serving slot keeps: K/V pages AND per-slot arrays in every
    # layer, no encoder memory (an instance adds ``counts``: the experts'
    # tokens have an entry a layer an expert)
    paged_slot_state = {"pools": ("k_pools", "v_pools"),
                        "encoder_memory": False,
                        "slot_arrays": ("tail", "value_half")}

    def __init__(self, vocab_size=262272, hidden_size=2048, num_layers=40,
                 num_heads=8, num_kv_heads=2, head_dim=128, num_experts=16,
                 experts_per_tok=1, expert_width=2048, router_hidden=256,
                 cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
                 rope_theta=5e6, rms_eps=1e-5, kv_chunk=512,
                 cache_dtype=None, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if int(experts_per_tok) != 1:
            raise MXNetError(
                f"num_experts_per_tok {experts_per_tok} is not built: the "
                "router picks ONE expert by argmax(p + beta) and weighs it "
                "by p as it stands")
        if (int(cca_time0), int(cca_time1)) != (2, 2):
            raise MXNetError(
                f"cca_time0 / cca_time1 ({cca_time0}, {cca_time1}) must be "
                "(2, 2): a slot's tail is the one position before, for "
                "each of the two convolutions")
        if num_heads % num_kv_heads or head_dim % 4:
            raise MXNetError("num_attention_heads must be a multiple of "
                             "num_key_value_heads and head_dim of 4")
        self._h, self._n = hidden_size, int(num_layers)
        self._nq, self._nkv, self._d = num_heads, num_kv_heads, head_dim
        self._g = num_heads // num_kv_heads
        self._e, self._rh = int(num_experts), router_hidden
        self._ch = (num_heads + num_kv_heads) * head_dim   # [q ; k]
        self._rot = int(head_dim * partial_rotary_factor) // 2
        self._theta, self._eps = float(rope_theta), float(rms_eps)
        self._sm = 1.0 / math.sqrt(head_dim)
        self._kv_chunk = int(kv_chunk)
        # the precision keys and values are cached in. A type narrower
        # than the pool's cells (a float8) is rounded to at the write and
        # kept in those cells: no kernel here reads one-byte pages
        self._cache_dtype = None if cache_dtype is None \
            else jnp.dtype(cache_dtype)
        # a dispatch's counts: live rows x decode steps, cached positions
        # an attention layer read, valid tokens an expert a layer, experts
        # the grouped product read (summed over layers), real and padded
        # tokens of a chunk, chunks that started from a zero tail, calls
        self.paged_slot_state = dict(type(self).paged_slot_state, counts=(
            ("row_steps", 1), ("attn_keys", 1),
            ("expert_tokens", self._n * self._e), ("experts_touched", 1),
            ("chunk_tokens", 1), ("chunk_padded", 1),
            ("chunks_from_zero", 1), ("calls", 1)))
        h, d, e, f, rh = hidden_size, head_dim, self._e, expert_width, \
            router_hidden
        heads = num_heads + num_kv_heads
        shapes = {"embed": (vocab_size, h), "norm": (h,)}
        for i in range(self._n):
            p = f"l{i}_"
            shapes.update({
                p + "attn_norm": (h,), p + "wq": (h, num_heads * d),
                p + "wk": (h, num_kv_heads * d), p + "wv1": (h, d),
                p + "wv2": (h, d), p + "conv0_w": (2, self._ch),
                p + "conv0_b": (self._ch,),
                p + "conv1_w": (2, heads, d, d), p + "conv1_b": (self._ch,),
                p + "k_temp": (num_kv_heads,),
                p + "wo": (num_heads * d, h),
                p + "moe_norm": (h,), p + "router_down": (h, rh),
                p + "router_down_b": (rh,), p + "router_gamma": (rh,),
                p + "router_norm": (rh,), p + "router_w1": (rh, rh),
                p + "router_b1": (rh,), p + "router_w2": (rh, rh),
                p + "router_b2": (rh,), p + "router_w3": (rh, e),
                p + "router_bias": (e,),
                p + "w_gate": (e, h, f), p + "w_up": (e, h, f),
                p + "w_down": (e, f, h)})
            for sub in ("attn", "moe"):
                shapes.update({p + f"{sub}_res_{part}": (h,) for part in
                               ("bias", "gain", "out_bias", "out_gain")})
        with self.name_scope():
            for name, shape in shapes.items():
                if name.endswith(("norm", "gain", "k_temp", "router_gamma")):
                    init = _init.One()
                elif name.endswith(("_b", "bias", "_b1", "_b2")):
                    init = _init.Zero()
                else:
                    init = _init.Normal(1.0 / math.sqrt(shape[-2]))
                setattr(self, name, self.params.get(
                    name, shape=shape, dtype=dtype, init=init))

    # ------------------------------------------------------------ pieces
    def _w(self, name):
        v = getattr(self, name).data()
        return v.data if isinstance(v, NDArray) else v

    def _angles(self, pos):
        """``(cos, sin)`` of the rotary angles at ``pos (...)``, each
        ``(..., 1, rotary / 2)`` float32: the same for every layer."""
        inv = self._theta ** (-jnp.arange(self._rot, dtype=F32) / self._rot)
        ang = pos.astype(F32)[..., None, None] * inv
        return jnp.cos(ang), jnp.sin(ang)

    def _mix(self, i, x, tail, value_half, cos, sin):
        """The compressed latent of layer ``i`` for a window ``x (R, C,
        H)`` whose rows stand behind ``tail (R, 2, channels)`` and
        ``value_half (R, D)``. Returns ``(q (R, C, Hq, D), k, v (R, C,
        Hkv, D)``, all as attention reads them, ``z, c0 (R, C, channels),
        v2 (R, C, D))``: what a tail keeps of each position."""
        p = f"l{i}_"
        R, C = x.shape[:2]
        nq, nkv, g, d = self._nq, self._nkv, self._g, self._d
        dt = x.dtype
        h = rms_norm(x, self._w(p + "attn_norm"), self._eps)
        qt = jnp.dot(h, self._w(p + "wq"))
        kt = jnp.dot(h, self._w(p + "wk"))
        v1 = jnp.dot(h, self._w(p + "wv1"))
        v2 = jnp.dot(h, self._w(p + "wv2"))
        z = jnp.concatenate([qt, kt], -1)                  # (R, C, 1280)
        # the two convolutions, each over the position and the one before
        a = self._w(p + "conv0_w").astype(F32)
        zf = z.astype(F32)
        before = jnp.concatenate([tail[:, :1].astype(F32), zf[:, :-1]], 1)
        c0 = (a[0] * before + a[1] * zf
              + self._w(p + "conv0_b").astype(F32)).astype(dt)
        before = jnp.concatenate([tail[:, 1:].astype(dt), c0[:, :-1]], 1)
        A = self._w(p + "conv1_w")
        by_head = (R, C, nq + nkv, d)
        c1 = (jnp.einsum("rcni,nio->rcno", before.reshape(by_head), A[0],
                         preferred_element_type=F32)
              + jnp.einsum("rcni,nio->rcno", c0.reshape(by_head), A[1],
                           preferred_element_type=F32)
              + self._w(p + "conv1_b").astype(F32).reshape(nq + nkv, d))
        # the mean term: a query head with its key head, a key head with
        # the mean of its query heads
        qf = qt.astype(F32).reshape(R, C, nkv, g, d)
        kf = kt.astype(F32).reshape(R, C, nkv, d)
        m_q = (0.5 * (qf + kf[:, :, :, None])).reshape(R, C, nq, d)
        m_k = 0.5 * (jnp.mean(qf, 3) + kf)
        length = math.sqrt(d)
        q = to_length(c1[:, :, :nq] + m_q, length)
        k = to_length(c1[:, :, nq:] + m_k, length) \
            * self._w(p + "k_temp").astype(F32)[:, None]
        q, k = rope_partial(q, cos, sin), rope_partial(k, cos, sin)
        # the shifted value: head 0 the token's own, head 1 the one
        # before's
        shifted = jnp.concatenate([value_half[:, None].astype(dt),
                                   v2[:, :-1]], 1)
        v = jnp.stack([v1, shifted], 2)
        return q.astype(dt), k.astype(dt), v, z, c0, v2

    def _kept(self, x, at):
        """Row ``at (R, 1, 1)`` of a window's ``x (R, C, ...)``: what a
        tail keeps, the row's last REAL position."""
        return jnp.take_along_axis(x, at, axis=1)

    def _cached(self, pool, rows, x):
        """``pool`` with ``x (N, Hkv, D)`` written at ``rows``, in the
        precision the cache is kept in."""
        if self._cache_dtype is not None:
            x = x.astype(self._cache_dtype)
        return _paged.write_rows(pool, rows, x)

    def _merge(self, i, sub, x, out):
        p = f"l{i}_{sub}_res_"
        with jax.named_scope("merge"):
            def w(part):
                return self._w(p + part).astype(F32)
            return ((x.astype(F32) + w("bias")) * w("gain")
                    + (out.astype(F32) + w("out_bias")) * w("out_gain")) \
                .astype(x.dtype)

    def _route(self, i, u, r):
        """``(expert (T,) int32, weight (T,) float32, r_l (T, 256))`` of
        tokens ``u (T, H)`` (normed) whose router state after the layer
        before is ``r (T, 256)`` float32."""
        p = f"l{i}_router_"

        def w(name):
            return self._w(p + name).astype(F32)
        with jax.named_scope("router"):
            down = jnp.dot(u, self._w(p + "down"),
                           preferred_element_type=F32) + w("down_b")
            r = down + w("gamma") * r
            y = rms_norm(r, w("norm"), self._eps)
            y = jax.nn.gelu(jnp.dot(y, w("w1")) + w("b1"), approximate=False)
            y = jax.nn.gelu(jnp.dot(y, w("w2")) + w("b2"), approximate=False)
            prob = jax.nn.softmax(jnp.dot(y, w("w3")), -1)
            # the bias selects and does not weigh; the chosen probability
            # weighs as it stands
            expert = jnp.argmax(prob + w("bias"), -1).astype(jnp.int32)
            weight = jnp.take_along_axis(prob, expert[:, None], -1)[:, 0]
        return expert, weight, r

    def _experts(self, i, x, r, valid):
        """The expert sublayer on ``x (T, H)``. Returns ``(x, r_l, valid
        tokens an expert (E,), experts the product read)``."""
        p = f"l{i}_"
        u = rms_norm(x, self._w(p + "moe_norm"), self._eps)
        expert, weight, r = self._route(i, u, r)
        with jax.named_scope("experts"):
            out, grouped = _moe.dispatch_experts(
                u, expert[:, None], weight[:, None], self._w(p + "w_gate"),
                self._w(p + "w_up"), self._w(p + "w_down"))
        tokens = jnp.zeros((self._e,), jnp.int32).at[expert].add(
            valid.astype(jnp.int32))
        return self._merge(i, "moe", x, out), r, tokens, \
            jnp.sum(grouped > 0).astype(jnp.int32)

    def _tally(self, state, expert_tokens, head):
        """``state["counts"]`` plus one call's: ``head`` the two counts
        before the experts', then the experts' and the five after."""
        return state["counts"] + jnp.concatenate([
            jnp.stack(head[:2]).astype(jnp.int32),
            jnp.concatenate(expert_tokens),
            jnp.stack(head[2:]).astype(jnp.int32)])

    def _logits(self, x):
        y = rms_norm(x, self._w("norm"), self._eps)
        return jnp.einsum("...h,vh->...v", y, self._w("embed"),
                          preferred_element_type=F32)

    # ------------------------------------------------------ paged protocol
    def init_paged_state(self, slots, num_pages, page_size, mem_len,
                         dtype=None):
        """K and V pools ``(num_pages, page x Hkv, D)`` a layer (page 0 is
        the trash page), and each layer's slots' tail ``(slots, 2,
        channels)`` and value half ``(slots, D)``."""
        dt = jnp.dtype(dtype if dtype is not None else self.embed.dtype)
        kv = (int(num_pages), int(page_size) * self._nkv, self._d)
        # distinct buffers: the state is a donated carry
        return {
            "k_pools": tuple(jnp.zeros(kv, dt) for _ in range(self._n)),
            "v_pools": tuple(jnp.zeros(kv, dt) for _ in range(self._n)),
            "tail": tuple(jnp.zeros((int(slots), 2, self._ch), dt)
                          for _ in range(self._n)),
            "value_half": tuple(jnp.zeros((int(slots), self._d), dt)
                                for _ in range(self._n)),
            "counts": jnp.zeros(
                (sum(n for _, n in self.paged_slot_state["counts"]),),
                jnp.int32)}

    def _window(self, tok, q_pos, token_vl, state, page_tables, slot_ids,
                active):
        """The window forward: ``tok (R, C)`` at positions ``q_pos (R, C)``,
        of which the first ``token_vl`` of an ``active`` row are real. K/V
        go into and come through ``page_tables``; every layer reads slot
        ``slot_ids[r]``'s tail and value half (zero where the row starts
        at position 0) and writes them back as they stand after the row's
        last real token. Returns ``(x (R, C, H), new_state)``."""
        R, C = tok.shape
        slots = state["tail"][0].shape[0]
        page = state["k_pools"][0].shape[1] // self._nkv
        L = page_tables.shape[1] * page
        live = jnp.logical_and(active[:, None],
                               jnp.arange(C)[None, :] < token_vl[:, None])
        real = jnp.where(active, token_vl, 0)
        # padding queries write to the trash page
        rows = jnp.where(live, _paged.token_rows(
            page_tables, jnp.minimum(q_pos, L - 1), page),
            q_pos % page).reshape(R * C)
        # an inert row reads slot 0 and writes nowhere; a row without a
        # real token keeps what its slot holds
        read = jnp.clip(slot_ids, 0, slots - 1)
        write = jnp.where(real > 0, slot_ids, slots)
        at = jnp.clip(real - 1, 0, C - 1)[:, None, None]
        fresh = (q_pos[:, 0] == 0)
        cos, sin = self._angles(q_pos)
        valid = live.reshape(R * C)
        x = jnp.take(self._w("embed"), tok, axis=0)
        r = jnp.zeros((R * C, self._rh), F32)
        k_pools, v_pools = list(state["k_pools"]), list(state["v_pools"])
        tails, halves = list(state["tail"]), list(state["value_half"])
        per_expert, touched = [], jnp.int32(0)
        for i in range(self._n):
            with jax.named_scope("cca.mix"):
                tail = jnp.where(fresh[:, None, None], 0,
                                 jnp.take(tails[i], read, axis=0))
                half = jnp.where(fresh[:, None], 0,
                                 jnp.take(halves[i], read, axis=0))
                q, k, v, z, c0, v2 = self._mix(i, x, tail, half, cos, sin)
                # the tail of the row's last real token
                keep = jnp.concatenate([self._kept(z, at),
                                        self._kept(c0, at)], 1)
                tails[i] = tails[i].at[write].set(
                    keep.astype(tails[i].dtype), mode="drop")
                halves[i] = halves[i].at[write].set(
                    self._kept(v2, at)[:, 0].astype(halves[i].dtype),
                    mode="drop")
            with jax.named_scope("attention"):
                k_pools[i] = self._cached(
                    k_pools[i], rows, k.reshape((R * C,) + k.shape[2:]))
                v_pools[i] = self._cached(
                    v_pools[i], rows, v.reshape((R * C,) + v.shape[2:]))
                heads = _paged.window_attention(
                    q, k_pools[i], v_pools[i], page_tables, q_pos[:, 0],
                    real, self._sm, kv_heads=self._nkv,
                    kv_chunk=self._kv_chunk)
                out = jnp.dot(heads, self._w(f"l{i}_wo"))
            x = self._merge(i, "attn", x, out)
            y, r, tokens, read_experts = self._experts(
                i, x.reshape(R * C, self._h), r, valid)
            x = y.reshape(R, C, self._h)
            per_expert.append(tokens)
            touched = touched + read_experts
        n_real = jnp.sum(real)
        counts = self._tally(state, per_expert, [
            jnp.int32(0), jnp.sum(jnp.where(live, q_pos + 1, 0)), touched,
            n_real, jnp.sum(active) * C - n_real,
            jnp.sum(jnp.logical_and(real > 0, fresh)), jnp.int32(1)])
        return x, {"k_pools": tuple(k_pools), "v_pools": tuple(v_pools),
                   "tail": tuple(tails), "value_half": tuple(halves),
                   "counts": counts}

    def prefill_suffix_paged(self, tokens, token_vl, q_offset, state,
                             page_tables, slot_ids, active, wide=True):
        """One chunk of a prompt: ``tokens (R, C)`` at positions
        ``q_offset[r] + j`` (``j < token_vl[r]``; the rest is padding),
        K/V written into the row's pages, the convolutions and the shifted
        value carried in slot ``slot_ids[r]``'s arrays from the chunk
        before (from zero where ``q_offset[r]`` is 0). Returns ``(logits
        (R, vocab) of each row's last real token, new_state)``; only a
        prompt's last chunk samples from them."""
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
        tail and its value half bit for bit; its logits are garbage."""
        tok = (tokens.data if isinstance(tokens, NDArray)
               else jnp.asarray(tokens)).astype(jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        active = jnp.asarray(active, jnp.bool_)
        page_tables = jnp.asarray(page_tables, jnp.int32)
        B = tok.shape[0]
        page = state["k_pools"][0].shape[1] // self._nkv
        L = page_tables.shape[1] * page
        pos = jnp.minimum(pos, L - 1)
        rows = jnp.where(active, _paged.token_rows(
            page_tables, pos[:, None], page)[:, 0], pos % page)
        cos, sin = self._angles(pos[:, None])
        x = jnp.take(self._w("embed"), tok, axis=0)
        r = jnp.zeros((B, self._rh), F32)
        k_pools, v_pools = list(state["k_pools"]), list(state["v_pools"])
        tails, halves = list(state["tail"]), list(state["value_half"])
        per_expert, touched = [], jnp.int32(0)
        for i in range(self._n):
            with jax.named_scope("cca.mix"):
                q, k, v, z, c0, v2 = self._mix(
                    i, x[:, None], tails[i], halves[i], cos, sin)
                tails[i] = jnp.where(
                    active[:, None, None],
                    jnp.concatenate([z, c0], 1).astype(tails[i].dtype),
                    tails[i])
                halves[i] = jnp.where(
                    active[:, None], v2[:, 0].astype(halves[i].dtype),
                    halves[i])
            with jax.named_scope("attention"):
                k_pools[i] = self._cached(k_pools[i], rows, k[:, 0])
                v_pools[i] = self._cached(v_pools[i], rows, v[:, 0])
                heads = _paged.decode_attention(
                    q[:, 0], k_pools[i], v_pools[i], page_tables, pos,
                    self._sm, kv_heads=self._nkv)
                out = jnp.dot(heads, self._w(f"l{i}_wo"))
            x = self._merge(i, "attn", x, out)
            x, r, tokens, read_experts = self._experts(i, x, r, active)
            per_expert.append(tokens)
            touched = touched + read_experts
        zero = jnp.int32(0)
        counts = self._tally(state, per_expert, [
            jnp.sum(active), jnp.sum(jnp.where(active, pos + 1, 0)),
            touched, zero, zero, zero, jnp.int32(1)])
        return self._logits(x), {
            "k_pools": tuple(k_pools), "v_pools": tuple(v_pools),
            "tail": tuple(tails), "value_half": tuple(halves),
            "counts": counts}

    # ------------------------------------------------------- full forward
    def hybrid_forward(self, F, tokens, **params):
        """Teacher-forced logits ``(B, S, vocab)`` of ``tokens (B, S)``: one
        window from a zero tail over a throw-away cache whose pages lie in
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

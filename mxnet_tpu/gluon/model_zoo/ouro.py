"""Ouro's language model (``model_type: ouro``, the looped language model of
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741): a
decoder-only stack of RMSNorm, rotary multi-head attention and SwiGLU
layers that is run ``total_ut_steps`` times over the SAME weights. A layer
norms before AND after each sublayer, inside the residual branch (``a = u +
N2(Attn(N1(u)))``, ``u = a + N4(MLP(N3(a)))``); the final norm closes every
pass and its output is the next pass's input; one linear gate reads each
pass's output (``lam_t = sigmoid(w_g . h_t + b_g)``) and the head reads the
last. At ``early_exit_threshold`` 1, the only value built, every token
takes every pass and the gates' exit distribution ``p_t = lam_t prod_{j<t}
(1 - lam_j)`` (``p_T`` the rest) is reported and decides nothing.

The layers are traced ONCE a program and a device loop (``lax.fori_loop``)
runs them ``total_ut_steps`` times: unrolled, a program would hold that
many copies of the stack's body.

The net speaks the paged protocol of a model with no encoder
(``paged_slot_state``) with a page that holds ``total_ut_steps`` PLANES a
layer: pass ``t`` of layer ``l`` attends to the keys and values that pass
``t`` of layer ``l`` wrote for the earlier positions, so a layer's pools
are ``(total_ut_steps, num_pages, page, heads, head_dim)``, all planes of
all layers under the one page table. The pools ride the loop's carry
flattened over planes and pages, which is how they lie: plane ``t`` is
pages ``t * num_pages`` onward, so a pass adds that to the page table and
to the rows it writes (the table is a traced operand of the paged kernels
already) and every kernel and row helper is the one the other nets call.

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
from ..block import HybridBlock
from .keye import rms_norm

__all__ = ["OuroLM"]

PPM = 1e6      # the exit distribution is counted in parts per million


def rope_half(x, cos, sin):
    """Rotary embedding of ``x (..., heads, D)`` by the angles whose
    ``cos`` and ``sin`` are ``(..., 1, D / 2)``: dimension ``d`` pairs
    with ``d + D / 2``; in float32, back in ``x``'s dtype."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


class OuroLM(HybridBlock):
    """The language model. Widths default to Ouro-2.6B's; matrices are
    stored ``(in, out)``, a layer's gate and up projections as one
    (``mlp_in``)."""

    # what a serving slot keeps: K/V pages with a plane for every pass, no
    # encoder memory, no arrays indexed by slot (an instance adds
    # ``counts``, of which ``exit_mass`` has an entry a pass)
    paged_slot_state = {"pools": ("k_pools", "v_pools"),
                        "encoder_memory": False}

    def __init__(self, vocab_size=49152, hidden_size=2048, num_layers=48,
                 num_heads=16, num_kv_heads=16, head_dim=128,
                 intermediate_size=5632, total_ut_steps=4,
                 early_exit_threshold=1.0, rope_theta=1e6, rms_eps=1e-6,
                 cache_dtype=None, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if float(early_exit_threshold) != 1.0:
            raise MXNetError(
                f"early_exit_threshold {early_exit_threshold} is not built: "
                "below 1 the rows of one batch leave the loop at different "
                "depths, and every program here runs every row through all "
                "total_ut_steps passes (threshold 1)")
        if num_kv_heads != num_heads:
            raise MXNetError(
                f"num_key_value_heads ({num_kv_heads}) must be "
                f"num_attention_heads ({num_heads}): the release has no "
                "grouped heads and none are built")
        if int(total_ut_steps) < 1 or head_dim % 2:
            raise MXNetError("total_ut_steps must be at least 1 and "
                             "head_dim even")
        self._h, self._n, self._f = hidden_size, int(num_layers), \
            intermediate_size
        self._nh, self._d = num_heads, head_dim
        self._passes = int(total_ut_steps)
        self._theta, self._eps = float(rope_theta), float(rms_eps)
        self._sm = 1.0 / math.sqrt(head_dim)
        # the precision keys and values are cached in. A type narrower
        # than the pool's cells (a float8) is rounded to at the write and
        # kept in those cells: no kernel here reads one-byte pages
        self._cache_dtype = None if cache_dtype is None \
            else jnp.dtype(cache_dtype)
        # a dispatch's counts: rows x passes of the stack run for a
        # position whose logits went back, those rows, cached positions
        # the attention calls read (a row's, once a call), the calls,
        # calls of the program, and the exit distribution summed over
        # those rows, in parts per million, an entry a pass
        self.paged_slot_state = dict(type(self).paged_slot_state, counts=(
            ("stack_passes", 1), ("row_steps", 1), ("attn_keys", 1),
            ("attn_calls", 1), ("calls", 1),
            ("exit_mass", self._passes)))
        h, a = hidden_size, num_heads * head_dim
        shapes = {"embed": (vocab_size, h), "norm": (h,),
                  "head": (h, vocab_size), "exit_w": (h, 1),
                  "exit_b": (1,)}
        for i in range(self._n):
            p = f"l{i}_"
            shapes.update({
                p + "attn_in_norm": (h,), p + "wq": (h, a),
                p + "wk": (h, a), p + "wv": (h, a), p + "wo": (a, h),
                p + "attn_out_norm": (h,),
                p + "mlp_in_norm": (h,),
                p + "mlp_in": (h, 2 * intermediate_size),
                p + "mlp_out": (intermediate_size, h),
                p + "mlp_out_norm": (h,)})
        with self.name_scope():
            for name, shape in shapes.items():
                if name.endswith("norm"):
                    init = _init.One()
                elif name == "exit_b":
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
        ``(..., 1, head_dim / 2)`` float32: the same for every layer and
        every pass, so made once a program."""
        half = self._d // 2
        inv = self._theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos.astype(jnp.float32)[..., None, None] * inv
        return jnp.cos(ang), jnp.sin(ang)

    def _qkv(self, i, x, cos, sin):
        """``(q, k, v)`` of layer ``i`` for ``x (..., H)``, heads apart
        ``(..., heads, D)``, queries and keys turned."""
        u = rms_norm(x, self._w(f"l{i}_attn_in_norm"), self._eps)
        q, k, v = (jnp.einsum("...h,hnd->...nd", u, self._w(
            f"l{i}_{w}").reshape(-1, self._nh, self._d))
            for w in ("wq", "wk", "wv"))
        return rope_half(q, cos, sin), rope_half(k, cos, sin), v

    def _cached(self, pool, rows, x):
        """``pool`` with ``x (N, heads, D)`` written at ``rows``, in the
        precision the cache is kept in."""
        if self._cache_dtype is not None:
            x = x.astype(self._cache_dtype)
        return _paged.write_rows(pool, rows, x)

    def _plane_start(self, t, num_pages):
        """The first page of plane ``t`` in a pool flattened over planes
        and pages."""
        return t * num_pages

    def _layer_out(self, i, x, heads):
        """The rest of layer ``i`` once its attention's heads ``(...,
        heads x D)`` are in: the output projection, and the MLP, each
        normed again inside its residual branch."""
        p = f"l{i}_"
        with jax.named_scope("attention"):
            y = jnp.dot(heads, self._w(p + "wo"))
            x = x + rms_norm(y, self._w(p + "attn_out_norm"), self._eps)
        with jax.named_scope("mlp"):
            gu = jnp.dot(rms_norm(x, self._w(p + "mlp_in_norm"), self._eps),
                         self._w(p + "mlp_in"))
            g, up = gu[..., :self._f], gu[..., self._f:]
            y = jnp.dot(jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype)
                        * up, self._w(p + "mlp_out"))
            return x + rms_norm(y, self._w(p + "mlp_out_norm"), self._eps)

    def _loop(self, x, state, page_tables, rows, cos, sin, served, attend):
        """``total_ut_steps`` passes of the one stack over ``x (..., H)``
        in a device loop, the pools in its carry, flattened over planes
        and pages. ``attend(q, k, v, k_pool, v_pool, tables, rows)`` is a
        layer's attention in one pass: ``tables`` and ``rows`` are
        ``page_tables`` and the ``rows`` to write moved into the pass's
        plane; it writes the keys and values there and hands back
        ``(heads' outputs (..., heads x D), k_pool, v_pool)``. ``served
        (...)`` weighs the positions whose exit distribution is counted.
        Returns ``(h_T, k_pools, v_pools, exit_mass (passes,)
        float32)``."""
        T = self._passes
        shape = state["k_pools"][0].shape
        num_pages, page = shape[1], shape[2]

        def one_pass(t, carry):
            x, k_pools, v_pools, stay, mass = carry
            k_pools, v_pools = list(k_pools), list(v_pools)
            first = self._plane_start(t, num_pages)
            tables, at = page_tables + first, rows + first * page
            with jax.named_scope("loop.pass"):
                for i in range(self._n):
                    with jax.named_scope("attention"):
                        heads, k_pools[i], v_pools[i] = attend(
                            *self._qkv(i, x, cos, sin), k_pools[i],
                            v_pools[i], tables, at)
                    x = self._layer_out(i, x, heads)
                x = rms_norm(x, self._w("norm"), self._eps)
            with jax.named_scope("loop.exit_gate"):
                lam = jax.nn.sigmoid(jnp.dot(
                    x, self._w("exit_w"),
                    preferred_element_type=jnp.float32)[..., 0]
                    + self._w("exit_b").astype(jnp.float32)[0])
                # the last pass takes what is left
                p = jnp.where(t < T - 1, lam * stay, stay)
                mass = mass.at[t].add(jnp.sum(p * served))
            return x, tuple(k_pools), tuple(v_pools), \
                stay * (1.0 - lam), mass

        def pages(pools):
            return tuple(p.reshape((-1,) + shape[2:]) for p in pools)

        x, k_pools, v_pools, _, mass = jax.lax.fori_loop(
            0, T, one_pass,
            (x, pages(state["k_pools"]), pages(state["v_pools"]),
             jnp.ones(x.shape[:-1], jnp.float32),
             jnp.zeros((T,), jnp.float32)))
        return x, tuple(p.reshape(shape) for p in k_pools), \
            tuple(p.reshape(shape) for p in v_pools), mass

    def _tally(self, state, rows, keys, mass):
        """``state["counts"]`` plus one call's: ``rows`` whose logits go
        back, ``keys`` cached positions one attention call read."""
        calls = self._passes * self._n
        head = jnp.stack([rows * self._passes, rows, keys * calls,
                          jnp.int32(calls), jnp.int32(1)])
        return state["counts"] + jnp.concatenate([
            head.astype(jnp.int32),
            jnp.round(mass * PPM).astype(jnp.int32)])

    def _logits(self, h):
        return jnp.dot(h, self._w("head"),
                       preferred_element_type=jnp.float32)

    # ------------------------------------------------------ paged protocol
    def init_paged_state(self, slots, num_pages, page_size, mem_len,
                         dtype=None):
        """K and V pools ``(total_ut_steps, num_pages, page, heads, D)`` a
        layer: a plane for every pass, page 0 of every plane the trash
        page."""
        dt = jnp.dtype(dtype if dtype is not None else self.embed.dtype)
        kv = (self._passes, int(num_pages), int(page_size), self._nh,
              self._d)
        # distinct buffers: the state is a donated carry
        return {
            "k_pools": tuple(jnp.zeros(kv, dt) for _ in range(self._n)),
            "v_pools": tuple(jnp.zeros(kv, dt) for _ in range(self._n)),
            "counts": jnp.zeros(
                (sum(n for _, n in self.paged_slot_state["counts"]),),
                jnp.int32)}

    def _window(self, tok, q_pos, token_vl, state, page_tables, active):
        """The window forward: ``tok (R, C)`` at positions ``q_pos (R,
        C)``, of which the first ``token_vl`` of an ``active`` row are
        real; every pass's K/V go into and come through ``page_tables`` at
        the pass's own plane. Returns ``(h_T (R, C, H), new_state)``; the
        exit distribution is counted at each active row's last real
        position."""
        R, C = tok.shape
        page = state["k_pools"][0].shape[2]
        L = page_tables.shape[1] * page
        block = _paged.kv_block(L)
        live = jnp.logical_and(active[:, None],
                               jnp.arange(C)[None, :] < token_vl[:, None])
        # padding queries write to the trash page
        rows = jnp.where(live, _paged.token_rows(
            page_tables, jnp.minimum(q_pos, L - 1), page),
            q_pos % page).reshape(R * C)
        last = jnp.max(jnp.where(live, q_pos, 0))
        n_blocks = jnp.minimum(last // block + 1, L // block)
        causal = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]
        served = jnp.logical_and(
            active[:, None],
            jnp.arange(C)[None, :] == token_vl[:, None] - 1)
        cos, sin = self._angles(q_pos)

        def attend(q, k, v, k_pool, v_pool, tables, rows):
            k_pool = self._cached(
                k_pool, rows, k.reshape((R * C,) + k.shape[2:]))
            v_pool = self._cached(
                v_pool, rows, v.reshape((R * C,) + v.shape[2:]))
            return _dsa.selected_window_attention(
                q, k_pool, v_pool, tables, q_pos[:, 0], causal, n_blocks,
                block, self._sm), k_pool, v_pool

        x, k_pools, v_pools, mass = self._loop(
            jnp.take(self._w("embed"), tok, axis=0), state, page_tables,
            rows, cos, sin, served, attend)
        n_rows = jnp.sum(jnp.logical_and(active, token_vl > 0))
        counts = self._tally(
            state, n_rows,
            jnp.sum(jnp.where(active, q_pos[:, 0] + token_vl, 0)), mass)
        return x, {"k_pools": k_pools, "v_pools": v_pools, "counts": counts}

    def prefill_suffix_paged(self, tokens, token_vl, q_offset, state,
                             page_tables, slot_ids, active, wide=True):
        """One chunk of a prompt: ``tokens (R, C)`` at positions
        ``q_offset[r] + j`` (``j < token_vl[r]``; the rest is padding),
        every pass's K/V written into the row's pages. Returns ``(logits
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
            jnp.asarray(active, jnp.bool_))
        idx = jnp.clip(token_vl - 1, 0, C - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        return self._logits(last), new_state

    def decode_step_paged(self, tokens, pos, state, page_tables, active):
        """One paged decode step over the SLOT batch: ``tokens (B,)`` at
        per-row positions ``pos (B,)``; row ``b`` IS slot ``b``. A row that
        is not ``active`` writes its K/V to the trash page of each plane;
        its logits are garbage."""
        tok = (tokens.data if isinstance(tokens, NDArray)
               else jnp.asarray(tokens)).astype(jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        active = jnp.asarray(active, jnp.bool_)
        page_tables = jnp.asarray(page_tables, jnp.int32)
        page = state["k_pools"][0].shape[2]
        L = page_tables.shape[1] * page
        pos = jnp.minimum(pos, L - 1)
        rows = jnp.where(active, _paged.token_rows(
            page_tables, pos[:, None], page)[:, 0], pos % page)
        cos, sin = self._angles(pos)

        def attend(q, k, v, k_pool, v_pool, tables, rows):
            k_pool = self._cached(k_pool, rows, k)
            v_pool = self._cached(v_pool, rows, v)
            return _paged.decode_attention(
                q, k_pool, v_pool, tables, pos, self._sm), k_pool, v_pool

        x, k_pools, v_pools, mass = self._loop(
            jnp.take(self._w("embed"), tok, axis=0), state, page_tables,
            rows, cos, sin, active, attend)
        counts = self._tally(state, jnp.sum(active),
                             jnp.sum(jnp.where(active, pos + 1, 0)), mass)
        return self._logits(x), {"k_pools": k_pools, "v_pools": v_pools,
                                 "counts": counts}

    # ------------------------------------------------------- full forward
    def hybrid_forward(self, F, tokens, **params):
        """Teacher-forced logits ``(B, S, vocab)`` of ``tokens (B, S)``: one
        window over a throw-away cache whose pages lie in order."""
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
                            tables, jnp.ones((B,), jnp.bool_))
        return NDArray(self._logits(x))

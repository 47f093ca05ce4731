"""Multi-head latent attention over a paged cache of ONE vector a token.

A layer caches, for each position, ``[c; k_r]``: the normed latent ``c``
(``rank`` numbers) from which every head's key and value are linear maps
(``W_kvb``), and one rotary key ``k_r`` (``rope`` numbers) that all heads
share. A page of the pool is ``(page, rank + rope)``: no head axis. With
``q_nope_h``, ``q_rope_h`` a head's query,

    score_h = (q_nope_h . (W^K_h c) + q_rope_h . k_r) x scale

where ``scale`` is the CALLER's: queries come in already multiplied by it,
and it is ``1 / sqrt(nope + rope)`` only where the rotary embedding is not
stretched (with YaRN the net multiplies it by ``mscale^2``:
``yarn_mscale``). Likewise ``rope_interleaved`` turns by the inverse
frequencies it is GIVEN: ``inverse_frequencies`` for a plain ``theta``,
``yarn_inverse_frequencies`` for a table blended between the original
frequencies and those of a context ``factor`` times as long.

Two orders of the same sums, as the serving plane has them:

- the WINDOW (a prefill chunk), EXPANDED: the row's cached latents up to
  the chunk's last position become per-head keys and values once
  (``expand_latents``), and ``C`` queries a row attend them causally
  (``window_attention``; ``%mla_prefill`` on the TPU, which walks the key
  blocks up to each query block's causal edge and no further, as the
  expansion's loop stops at the chunk's);
- DECODE, ABSORBED: ``q'_h = W^K_h^T q_nope_h`` is ``rank`` wide, every
  head of every query position is a row of one product against the latent
  pages, the weighted latents come back and ``W^V_h`` is applied after
  (``decode_attention``; ``%mla_latent_decode`` on the TPU, which copies
  a row's live pages itself, a block behind the two products). A step
  reads ``rank + rope`` numbers a cached position whatever the head count.

The ``jax.numpy`` forms below stand on the CPU and under a multi-device
mesh (``paged.kernels_on()``); softmax and scores are float32 everywhere.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import paged

NEG = -1e30
EXPAND_KEYS = 512       # cached positions a step of the expansion's loop


def inverse_frequencies(theta, half):
    """``theta^(-i / half)`` for the ``half`` rotary pairs ``i``."""
    return theta ** (-jnp.arange(half, dtype=jnp.float32) / half)


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention factor for a context stretched ``factor`` times:
    ``0.1 x mscale x ln(factor) + 1`` (1 where nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inverse_frequencies(theta, half, factor, original, beta_fast,
                             beta_slow):
    """YaRN's table of the ``half`` inverse frequencies, as the DeepSeek-V3
    release computes it (float32, on the host, once a net): pair ``i``
    keeps ``theta^(-i / half)`` while it turns more than ``beta_fast``
    times over the ``original`` positions, takes that over ``factor`` where
    it turns fewer than ``beta_slow`` times, and a linear ramp between the
    two pairs that bound those (the lower rounded down, the upper up)."""
    dim = 2 * half

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    base = np.float32(theta) ** (np.arange(half, dtype=np.float32)
                                 / np.float32(half))
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0, 1).astype(np.float32)
    return ((1 / (factor * base)) * ramp + (1 / base) * (1 - ramp)) \
        .astype(np.float32)


def rope_interleaved(x, pos, inv, factor=1.0):
    """Rotary embedding of ``x (..., D)`` at ``pos`` (broadcast against
    ``x``'s leading axes): dimension ``2i`` pairs with ``2i + 1`` and turns
    by ``pos x inv[i]``, ``inv (D / 2,)`` the inverse frequencies as given
    (``inverse_frequencies``, ``yarn_inverse_frequencies``); ``factor``
    multiplies cos and sin (YaRN's ``mscale / mscale_all_dim``)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    a, b = xf[..., 0], xf[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1) \
        .reshape(x.shape).astype(x.dtype)


# ------------------------------------------------------------------ window
def expand_latents(buf, latents, wkvb, n_keys, rank):
    """``buf (R, L, H x 2D)`` with the first ``n_keys`` cached positions
    (rounded up to ``EXPAND_KEYS``) of ``latents (R, L, rank + rope)``
    expanded through ``wkvb (rank, H x 2D)``: per head its keys' nope part
    and its values. The loop stops at the last position any query sees, so
    a prompt's first chunk expands one chunk and its last all of them; what
    lies past it in ``buf`` is whatever the layer before left there, which
    no query reads (causal)."""
    R, L, _ = latents.shape
    step = EXPAND_KEYS if L % EXPAND_KEYS == 0 else L

    def body(j, buf):
        c = jax.lax.dynamic_slice(latents, (0, j * step, 0),
                                  (R, step, rank))
        return jax.lax.dynamic_update_slice(
            buf, jnp.dot(c, wkvb).astype(buf.dtype), (0, j * step, 0))

    return jax.lax.fori_loop(0, (n_keys + step - 1) // step, body, buf)


def window_attention(qn, qr, pool, wkvb, page_tables, q_offset, last,
                     buf=None):
    """Expanded attention of window queries ``qn (R, C, H, D)``, ``qr (R,
    C, H, rope)`` (scaled) at ``q_offset[r] + c`` over the row's cached
    latents, ``pool (num_pages, page, rank + rope)`` through
    ``page_tables``, expanded by ``wkvb (rank, H x 2D)``. ``last`` is the
    last position any query of the window sees. ``buf`` is the buffer the
    kernel path expands into (``expansion_buffer``), handed from layer to
    layer so that one allocation serves them all. Returns ``((R, C, H x
    D), buf)``."""
    R, C, H, D = qn.shape
    rank = wkvb.shape[0]
    lat = paged.gather_row_pages(pool, page_tables)          # (R, L, W)
    if buf is not None:
        from .pallas import mla_attention as _k

        pad = buf.shape[1] - lat.shape[1]
        lat = jnp.pad(lat, ((0, 0), (0, pad), (0, 0)))
        buf = expand_latents(buf, lat, wkvb, last + 1, rank)
        out = _k.mla_prefill(jnp.swapaxes(qn, 1, 2), jnp.swapaxes(qr, 1, 2),
                             buf, lat[..., rank:], q_offset)
        return out, buf
    L = lat.shape[1]
    kv = jnp.dot(lat[..., :rank], wkvb).reshape(R, L, H, 2 * D)
    s = jnp.einsum("rchd,rlhd->rhcl", qn, kv[..., :D],
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("rchd,rld->rhcl", qr, lat[..., rank:],
                     preferred_element_type=jnp.float32)
    q_pos = q_offset[:, None] + jnp.arange(C)[None, :]
    seen = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, NEG), -1)
    out = jnp.einsum("rhcl,rlhd->rchd", p.astype(kv.dtype), kv[..., D:],
                     preferred_element_type=jnp.float32)
    return out.reshape(R, C, H * D).astype(qn.dtype), None


def expansion_buffer(rows, cached, width, chunk, dtype):
    """The buffer ``window_attention``'s kernel path expands a row's
    cached latents into, or None where the ``jax.numpy`` form stands (the
    CPU, a multi-device mesh, a chunk the kernel's blocks do not divide):
    ``(rows, cached rounded up to whole key blocks, width)``."""
    if not paged.kernels_on() or chunk % 128:
        return None
    padded = -(-cached // EXPAND_KEYS) * EXPAND_KEYS
    return jnp.zeros((rows, padded, width), dtype)


# ------------------------------------------------------------------ decode
def decode_attention(qc, qr, pool, page_tables, pos):
    """Absorbed attention of ``S`` query positions a row: ``qc (B, S, H,
    rank)``, ``qr (B, S, H, rope)`` (scaled), query ``i`` of row ``b`` at
    ``pos[b] + i`` over the row's cached latents up to its own position.
    Returns the weighted latents ``(B, S, H, rank)``."""
    if paged.kernels_on():
        from .pallas import mla_attention as _k

        return _k.mla_latent_decode(qc, qr, pool, page_tables, pos)
    rank = qc.shape[-1]
    lat = paged.gather_row_pages(pool, page_tables)          # (B, L, W)
    c, kr = lat[..., :rank], lat[..., rank:]
    s = jnp.einsum("bshc,blc->bshl", qc, c,
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("bshd,bld->bshl", qr, kr,
                     preferred_element_type=jnp.float32)
    q_pos = pos[:, None] + jnp.arange(qc.shape[1])[None, :]
    seen = jnp.arange(lat.shape[1])[None, None, :] <= q_pos[:, :, None]
    p = jax.nn.softmax(jnp.where(seen[:, :, None], s, NEG), -1)
    return jnp.einsum("bshl,blc->bshc", p.astype(c.dtype), c,
                      preferred_element_type=jnp.float32).astype(qc.dtype)

"""Xing4.0's language model (``xing4_0``): the latent-attention model of
``latent_lm.py`` (latent attention, sigmoid-routed experts beside a shared
one, one multi-token module, the paged protocol) with two things of its
own.

**A residual stream ``hc_mult`` wide** (manifold-constrained
hyper-connections, ``ops/hyper_connection.py``): a token's state is ``X (n,
C)``, kept as a flat ``(tokens, n x C)`` array; the stack's input is
repeated ``n`` times, every sublayer (a block's attention, then its
feed-forward) is wrapped by a MIXER of its own (``<block><attn|mlp>_hc_phi``,
``_hc_alpha``, ``_hc_bias``) that gives it ``u = H_pre @ X`` (the block's
own input norm acts on ``u``) and puts its output back as ``X' = H_res @ X
+ outer(H_post, y)`` with ``H_res`` Sinkhorn-normalised, and the streams
are summed at the stack's end. The module meets the stream collapsed: its
input is the summed hidden state before the final norm, its one block runs
on a stream repeated from the joint's output and summed at its end, under
its own two mixers. One call of ``hyper_connection.step`` does a mixer's
second half and the NEXT mixer's first, so the stream is read once and
written once a sublayer.

**YaRN-stretched rotary pairs** (``rope_scaling``): the base class turns by
the blended table and scales its queries by ``mscale^2``.

Two device-side counts ride with the family's nine: ``mhc_pairs``, (token,
mixer) pairs mixed, and ``scored_pairs``, (query, key) pairs ONE latent
cache's chunk attention scored (a live query at position ``q`` scores ``q +
1`` keys; the decode step's are ``latent_keys``).
"""

import jax.numpy as jnp

from ...ops import hyper_connection as _hc
from . import latent_lm as _base

__all__ = ["XingLM", "COUNTS"]

COUNTS = _base.COUNTS + ("mhc_pairs", "scored_pairs")


class XingLM(_base.LatentLM):
    """The language model. Widths default to the published ones
    (Xing4.0-29B-A4B); ``hc_clamp`` is ``(mhc_h_res_clamp_min,
    mhc_h_res_clamp_max)``."""

    COUNTS = COUNTS
    paged_slot_state = _base.slot_state(COUNTS)

    def __init__(self, vocab_size=131072, hidden_size=3584, num_layers=40,
                 num_heads=32, q_lora_rank=768, kv_lora_rank=512,
                 intermediate_size=9216, first_dense=2, num_experts=64,
                 experts_per_tok=4, expert_width=1024, routed_scaling=2.0,
                 rope_theta=1e4, hc_mult=4, hc_sinkhorn_iters=20,
                 hc_eps=1e-6, hc_clamp=(-30.0, 30.0), **kwargs):
        self._hc = _hc.HC(int(hc_mult), int(hc_sinkhorn_iters),
                          float(hc_eps), float(hc_clamp[0]),
                          float(hc_clamp[1]))
        super().__init__(
            vocab_size=vocab_size, hidden_size=hidden_size,
            num_layers=num_layers, num_heads=num_heads,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            intermediate_size=intermediate_size, first_dense=first_dense,
            num_experts=num_experts, experts_per_tok=experts_per_tok,
            expert_width=expert_width, routed_scaling=routed_scaling,
            rope_theta=rope_theta, **kwargs)
        # the mixer after each: a stack's sublayers in order, the module's
        # two a stack of their own
        chain = [p + k for p, _ in self._blocks() for k in ("attn", "mlp")]
        self._after = dict(zip(chain, chain[1:] + [None]),
                           mtp_attn="mtp_mlp", mtp_mlp=None)

    def _sublayer_shapes(self, p):
        n = self._hc.n
        return {p + k + part: shape for k in ("attn", "mlp")
                for part, shape in (("_hc_phi", (n * self._h, n * (n + 2))),
                                    ("_hc_alpha", (3,)),
                                    ("_hc_bias", (n * (n + 2),)))}

    def _mixer(self, name):
        return _hc.Mixer(self._w(name + "_hc_phi"),
                         self._w(name + "_hc_alpha"),
                         self._w(name + "_hc_bias"))

    # the stream: what ``hyper_connection`` carries, with the leading axes
    # of its tokens (``lead``) and, once the stack's last sublayer has
    # run, that sublayer's output (``y``), which ``_leave`` mixes in
    def _enter(self, x, p):
        lead = x.shape[:-1]
        s = _hc.enter(x.reshape(-1, self._h), self._mixer(p + "attn"),
                      self._hc)
        return dict(s, lead=lead)

    def _sublayer(self, name, s, f):
        terms, rest = f(s["u"].reshape(s["lead"] + (self._h,)))
        y = sum(terms[1:], terms[0]).reshape(-1, self._h)
        rest = rest() if callable(rest) else rest
        nxt = self._after[name]
        if nxt is None:
            return dict(s, y=y), rest
        lead = s["lead"]
        return dict(_hc.step(s, y, self._mixer(nxt), self._hc),
                    lead=lead), rest

    def _shape(self, s, lead):
        return dict(s, lead=tuple(lead))

    def _leave(self, s):
        return _hc.leave(s, s["y"], self._hc).reshape(
            s["lead"] + (self._h,))

    def _extra_counts(self, live, m_live, q_pos=None):
        mixers = 2 * self._n * jnp.sum(live) + 2 * jnp.sum(m_live())
        scored = 0 if q_pos is None else jnp.sum(
            jnp.where(live, q_pos + 1, 0))
        return mixers, scored

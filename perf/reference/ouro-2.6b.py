"""Plain reference of the ``ouro-2.6b`` configuration: Ouro's looped language
model (``model_type: ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741). One stack of layers, run ``total_ut_steps``
times over the same weights:

    u = E[x]
    for t in 1..T:
        for l in 1..L:
            a = u + N2_l(Attn_l(N1_l(u)))
            u = a + N4_l(MLP_l(N3_l(a)))
        u = h_t = Nf(u)                       # the final norm after EVERY pass
        lam_t = sigmoid(w_g . h_t + b_g)      # the exit gate
    logits = W_head h_T

``Attn_l``: ``q, k, v = x Wq, x Wk, x Wv`` (no bias), as many key/value
heads as query heads; rotary embedding on ``q`` and ``k`` over the whole
head, dimension ``d`` paired with ``d + D / 2``; causal ``softmax(q k^T /
sqrt(D)) v``; ``Wo``. ``MLP_l``: ``W_down(silu(W_gate x) * W_up x)``. Every
norm is an RMSNorm with its own gain. The exit distribution ``p_t = lam_t
prod_{j<t}(1 - lam_j)`` for ``t < T``, ``p_T = prod_{j<T}(1 - lam_j)``, is
reported; at ``early_exit_threshold`` 1 it decides nothing.

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision:
one teacher-forced full forward of ONE sequence, a Python loop over passes
and layers, dense causal softmax. No cache, no planes, no paging, no
kernels, no batching; nothing of the program is imported.

Weights come from the seed TENSOR BY TENSOR, each keyed by the seed and its
own name, and a layer's are made where the layer runs (once a pass: the
same name gives the same tensor), so that the stack's 9.9 GB of float32
never stand on the device at once. Names are the program's structural
parameter names; matrices are stored ``(in, out)``, the gate and up
projections as one ``mlp_in`` (gate, then up), as the program holds them.

Departures from the description, each where it is made: a layer is one
jitted function and the sequence is padded to few lengths (``forward``);
logits at the wanted positions only.

``quant="fp8"`` is the control: both operands of every matrix product
rounded to float8 (e4m3, scaled per tensor).
"""

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-6
NEG = -jnp.inf
# the exit gate's bias, of either sign and never near zero: 1.5 + |normal(0,
# 0.5)|, so that a gate without it moves its sigmoid by 0.1 to 0.4
BIAS_FLOOR, BIAS_STD = 1.5, 0.5


# ---------------------------------------------------------------- weights
def layer_specs(cfg, p):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    a = cfg["num_attention_heads"] * cfg["head_dim"]
    return {p + "attn_in_norm": (h,), p + "wq": (h, a), p + "wk": (h, a),
            p + "wv": (h, a), p + "wo": (a, h), p + "attn_out_norm": (h,),
            p + "mlp_in_norm": (h,), p + "mlp_in": (h, 2 * f),
            p + "mlp_out": (f, h), p + "mlp_out_norm": (h,)}


def tensor_specs(cfg):
    """``{name: shape}`` of every tensor, in the order they are made: ONE
    stack, whatever ``total_ut_steps``."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_specs(cfg, f"l{i}_"))
    out.update({"norm": (h,), "exit_w": (h, 1), "exit_b": (1,),
                "head": (h, v)})
    return out


def _normal(seed, name, shape):
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.normal(key, shape, jnp.float32)


def tensor(seed, cfg, name, shape=None):
    """One tensor from the seed and its own name, float32. Norm gains are
    1 + normal(0, 0.02); the embedding normal(0, 1), so the residual
    stream starts at unit scale; the gate's bias ``+-(1.5 + |normal(0,
    0.5)|)`` (away from zero whatever the seed: a program that drops it
    fails); every matrix, the gate's weight among them, normal(0, 1 /
    fan_in)."""
    shape = tuple(shape or tensor_specs(cfg)[name])
    if name.endswith("norm"):
        return 1.0 + 0.02 * _normal(seed, name, shape)
    if name == "embed":
        return _normal(seed, name, shape)
    if name == "exit_b":
        n = _normal(seed, name, shape)
        return jnp.where(n < 0, -1.0, 1.0) * (BIAS_FLOOR
                                              + BIAS_STD * jnp.abs(n))
    return _normal(seed, name, shape) / math.sqrt(shape[-2])


def init_params(seed, cfg):
    """Every tensor in turn as ``(name, float32 array)``, made when asked
    for: the caller casts and hands over each one and drops it before the
    next is made."""
    for name, shape in tensor_specs(cfg).items():
        yield name, tensor(seed, cfg, name, shape)


# -------------------------------------------------------------- equations
def _fp8(x):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(spec, a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b)


def rms_norm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) * g


def rope(x, theta):
    """Rotary embedding of ``x (S, heads, D)`` at positions ``0 .. S - 1``:
    dimension ``d`` pairs with ``d + D / 2`` and turns by ``pos x
    theta^(-2d / D)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "theta", "quant"))
def _layer(x, n1, wq, wk, wv, wo, n2, n3, mlp_in, mlp_out, n4, heads,
           theta, quant):
    """One layer over one sequence ``x (S, H)``. Returns ``(x, keys (S,
    heads, D))``: the keys are what a cache of this pass would hold."""
    u = rms_norm(x, n1)
    q, k, v = (_mm("sh,ha->sa", u, w, quant).reshape(x.shape[0], heads, -1)
               for w in (wq, wk, wv))
    q, k = rope(q, theta), rope(k, theta)
    score = _mm("thd,shd->hts", q, k, quant) / math.sqrt(q.shape[-1])
    t = jnp.arange(x.shape[0])
    prob = jax.nn.softmax(
        jnp.where((t[None, :] <= t[:, None])[None], score, NEG), -1)
    if quant == "fp8":
        prob = _fp8(prob)
    attn = _mm("hts,shd->thd", prob, v, quant).reshape(x.shape[0], -1)
    a = x + rms_norm(_mm("sd,dh->sh", attn, wo, quant), n2)
    gu = _mm("sh,hf->sf", rms_norm(a, n3), mlp_in, quant)
    f = gu.shape[-1] // 2
    y = _mm("sf,fh->sh", jax.nn.silu(gu[:, :f]) * gu[:, f:], mlp_out, quant)
    return a + rms_norm(y, n4), k


@functools.partial(jax.jit, static_argnames=("quant",))
def _close(x, g, exit_w, exit_b, quant):
    """The end of a pass: ``(h_t, lam_t (S,))``."""
    h = rms_norm(x, g)
    return h, jax.nn.sigmoid(_mm("sh,ho->so", h, exit_w, quant)[:, 0]
                             + exit_b[0])


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(h, want, head, quant):
    return _mm("sh,hv->sv", h[want], head, quant)


def _padded(want, multiple=64):
    """The wanted positions, the last one repeated up to a multiple: one
    program of the head for replies of many lengths."""
    want = np.asarray(want, np.int32)
    return jnp.asarray(np.concatenate(
        [want, np.full(-len(want) % multiple, want[-1], np.int32)]))


def forward(seed, cfg, tokens, quant=None, want=None, tap=None, pad_to=None):
    """``(logits (len(want), vocab), lam (T, S))`` of one sequence ``tokens
    (S,)``: row ``j`` of the first scores the token after ``tokens[:want[j]
    + 1]`` (``want`` None is every position), the second is every pass's
    exit gate at every position. ``tap``, a dict, receives ``keys[(t,
    l)]``, the keys ``(S, heads, D)`` of the (pass, layer) pairs it
    names under ``tap["planes"]``."""
    tokens = np.asarray(tokens, np.int32)
    n_real = len(tokens)
    # a short sequence pads to a multiple of 16 and the check's sequences
    # all to ``pad_to``, so that few distinct shapes are compiled.
    # Attention is causal, so what lies past the sequence changes nothing
    # before it
    pad = (pad_to - n_real) if pad_to is not None and n_real <= pad_to \
        else -n_real % 16
    tokens = jnp.asarray(np.concatenate([tokens, np.zeros(pad, np.int32)]))
    heads, theta = cfg["num_attention_heads"], float(cfg["rope_theta"])
    planes = set(tap["planes"]) if tap is not None else ()
    lams = []
    with jax.default_matmul_precision("highest"):
        x = tensor(seed, cfg, "embed")[tokens]
        close = [tensor(seed, cfg, n) for n in ("norm", "exit_w", "exit_b")]
        for t in range(cfg["total_ut_steps"]):      # the SAME weights
            for i in range(cfg["num_hidden_layers"]):
                w = [tensor(seed, cfg, n, s)
                     for n, s in layer_specs(cfg, f"l{i}_").items()]
                x, k = _layer(x, *w, heads=heads, theta=theta, quant=quant)
                if (t, i) in planes:
                    tap.setdefault("keys", {})[(t, i)] = \
                        np.asarray(k[:n_real])
                del w
            x, lam = _close(x, *close, quant=quant)  # and h_t goes on
            lams.append(lam[:n_real])
        want = np.arange(n_real) if want is None else np.asarray(want)
        logits = _head(x, _padded(want), tensor(seed, cfg, "head"),
                       quant=quant)[:len(want)]
    return logits, jnp.stack(lams)


def exit_distribution(lam):
    """``p (T, ...)`` of the gates ``lam (T, ...)``: ``p_t = lam_t prod_{j <
    t}(1 - lam_j)``, and the last pass takes what is left."""
    lam = np.asarray(lam, np.float64)
    stay = np.cumprod(1.0 - lam, 0)
    before = np.concatenate([np.ones_like(lam[:1]), stay[:-1]], 0)
    p = lam * before
    p[-1] = before[-1]
    return p


# --------------------------------------------------------------- the check
def _below_best(logits, tokens):
    got = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], -1)[:, 0]
    return np.asarray(logits.max(-1) - got)


def served_gaps(seed, cfg, prompt, served, quant=None, pad_to=None):
    """``(served token gaps (len(served),), p (T, len(served)))``. The
    sequence is the prompt followed by the served tokens; served token
    ``j`` is scored at position ``len(prompt) - 1 + j`` by how far its
    logit lies below the reference's best there, and ``p`` is the
    reference's exit distribution at those positions. With ``quant`` the
    served tokens only place the positions: the tokens the lower precision
    puts first stand in their place (the control need not decode)."""
    prompt, served = np.asarray(prompt, np.int32), np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    want = len(prompt) - 1 + np.arange(len(served))
    ref, lam = forward(seed, cfg, seq, want=want, pad_to=pad_to)
    if quant is not None:
        low, _ = forward(seed, cfg, seq, quant=quant, want=want,
                         pad_to=pad_to)
        served = jnp.argmax(low, -1)
    return _below_best(ref, served), \
        exit_distribution(np.asarray(lam)[:, want])


def served_token_gaps(seed, cfg, prompt, served, quant=None, pad_to=None):
    """``served_gaps``'s first, under the name ``serve-lm.py`` asks for."""
    return served_gaps(seed, cfg, prompt, served, quant, pad_to)[0]


def plane_keys(seed, cfg, tokens, planes, pad_to=None):
    """``{(t, l): keys (S, heads, D)}`` of ``tokens (S,)`` for the (pass,
    layer) pairs ``planes``: what plane ``t`` of layer ``l``'s cache has to
    hold for these positions."""
    tap = {"planes": tuple(planes)}
    forward(seed, cfg, tokens, want=[len(tokens) - 1], tap=tap,
            pad_to=pad_to)
    return tap["keys"]


def greedy(seed, cfg, prompt, n):
    """Greedy decode by full forwards (tests, tiny sizes)."""
    seq = list(np.asarray(prompt))
    for _ in range(n):
        logits, _ = forward(seed, cfg, seq, want=[len(seq) - 1])
        seq.append(int(jnp.argmax(logits[0])))
    return seq[len(prompt):]

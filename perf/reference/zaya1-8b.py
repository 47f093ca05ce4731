"""Plain reference of the ``zaya1-8b`` configuration: ZAYA1's language model
(``model_type: zaya``; compressed convolutional attention, arXiv:2510.04476,
and the ZAYA1 report, arXiv:2511.17127). Per layer ``l``, residual ``x (S,
H)``, router state ``r_{l-1} (S, 256)``, ``RMS(.; g)`` an RMSNorm with gain
``g``, matrices ``(in, out)``:

    h  = RMS(x; g_a);  qt = h W_q (S, 8, 128);  kt = h W_k (S, 2, 128)
    m_q[i] = (qt[i] + kt[i // 4]) / 2                     # the mean term
    m_k[j] = (mean_{i // 4 = j} qt[i] + kt[j]) / 2
    z  = [qt ; kt]                                        # 1,280 channels
    c0[t] = a_0 * z[t-1]  + a_1 * z[t]  + b               # depthwise
    c1[t] = A_0 c0[t-1] + A_1 c0[t] + b'                  # by head, 128 x 128
    q = c1[:1024] + m_q;  k = c1[1024:] + m_k
    q, k each head to length sqrt(128); k times a temperature a key head
    rotary on the first 64 of a head's 128, theta 5e6 (d pairs with d + 32)
    v[t] = [h[t] W_v1 ; h[t-1] W_v2]                      # the shifted value
    o  = causal softmax(q k^T / sqrt(128)) v, head i on key head i // 4; W_o
    x  = (x + b_x) s_x + (o + b_o) s_o                    # the merge
    u  = RMS(x; g_m);  d = u W_d + b_d;  r_l = d + gamma_l * r_{l-1}
    p  = softmax(W_3 gelu(W_2 gelu(W_1 RMS(r_l; g_r) + b_1) + b_2))
    e  = argmax(p + beta);  a = p[e]                      # not renormalised
    y  = a * W_down[e] (silu(u W_gate[e]) * (u W_up[e]))
    x  = (x + b_x') s_x' + (y + b_y') s_y'

and ``logits = RMS(x; g_f) E^T`` with the embedding's own matrix. Positions
before the sequence are zero: ``z[-1] = c0[-1] = 0`` and ``h[-1] W_v2 =
0``, so position ``t`` sees ``t - 2 .. t`` and nothing later. ``r_0 = d``
(the state before the first layer is zero).

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision:
one teacher-forced full forward of ONE sequence, a Python loop over the
layers, dense causal softmax, every expert over every token and the chosen
one kept. No cache, no tail, no paging, no kernels, no batching; nothing of
the program is imported.

Weights come from the seed TENSOR BY TENSOR, each keyed by the seed and its
own name, and a layer's are made where the layer runs, so that the stack's
33 GB of float32 never stand on the device at once. Names are the program's
structural parameter names.

Departures from the description, each where it is made: a layer is one
jitted function and the sequence is padded to few lengths (``forward``);
logits are made for the wanted positions only, a block of them at a time
(a row of logits is 262,272 numbers).

``quant="fp8"`` is the control: both operands of every matrix product
rounded to float8 (e4m3, scaled per tensor).
"""

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

NEG = -jnp.inf
EMBED_STD = 0.02        # a tied head: at unit scale every reply repeats
# the merge's biases are a tenth of an embedding's entry: what a token
# adds to the residual has to stand over what every token adds alike, or
# the router, which reads the residual, sends every token to one expert
MERGE_BIAS_STD = 0.1 * EMBED_STD
ROUTER_OUT = 4.0        # the last router matrix, times 1 / sqrt(fan_in)
LOGIT_BLOCK = 256       # wanted positions a product with the embedding
# a routing whose two best lie nearer than this is one a bfloat16 program
# may settle the other way (its router reads a residual rounded to 8 bits)
ROUTE_MARGIN = 0.02


# ---------------------------------------------------------------- weights
def layer_specs(cfg, p):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f, rh = cfg["num_experts"], cfg["moe_intermediate_size"], \
        cfg["router_hidden_size"]
    ch = (nq + nkv) * d
    out = {p + "attn_norm": (h,), p + "wq": (h, nq * d),
           p + "wk": (h, nkv * d), p + "wv1": (h, d), p + "wv2": (h, d),
           p + "conv0_w": (2, ch), p + "conv0_b": (ch,),
           p + "conv1_w": (2, nq + nkv, d, d), p + "conv1_b": (ch,),
           p + "k_temp": (nkv,), p + "wo": (nq * d, h)}
    for sub in ("attn", "moe"):
        out.update({p + f"{sub}_res_{part}": (h,) for part in
                    ("bias", "gain", "out_bias", "out_gain")})
    out.update({p + "moe_norm": (h,), p + "router_down": (h, rh),
                p + "router_down_b": (rh,), p + "router_gamma": (rh,),
                p + "router_norm": (rh,), p + "router_w1": (rh, rh),
                p + "router_b1": (rh,), p + "router_w2": (rh, rh),
                p + "router_b2": (rh,), p + "router_w3": (rh, e),
                p + "router_bias": (e,), p + "w_gate": (e, h, f),
                p + "w_up": (e, h, f), p + "w_down": (e, f, h)})
    return out


def tensor_specs(cfg):
    """``{name: shape}`` of every tensor, in the order they are made."""
    out = {"embed": (cfg["vocab_size"], cfg["hidden_size"])}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_specs(cfg, f"l{i}_"))
    out["norm"] = (cfg["hidden_size"],)
    return out


def _normal(seed, name, shape):
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.normal(key, shape, jnp.float32)


def tensor(seed, cfg, name, shape=None):
    """One tensor from the seed and its own name, float32. Norm gains are
    1 + normal(0, 0.02); the merge's gains 1 + normal(0, 0.1) and its
    biases normal(0, 0.002), NOT 1 and 0 (a program that drops them fails);
    the router's carry ``gamma`` 1 + normal(0, 0.1), its biases and the
    convolutions' normal(0, 0.1), its selection bias ``beta`` normal(0,
    0.1); a key head's temperature 1 + tanh(normal) / 4; the embedding
    normal(0, 0.02) (the head is tied to it); the first convolution's two
    taps normal(0, 1 / 2) and the second's normal(0, 1 / 256), so that
    each hands on the variance it is given; the router's last matrix 4 /
    sqrt(fan_in), so that the chosen probability lies well off 1 / 16 and
    an expert's weight is felt; every other matrix normal(0, 1 /
    fan_in)."""
    shape = tuple(shape or tensor_specs(cfg)[name])
    n = _normal(seed, name, shape)
    if name.endswith("norm"):
        return 1.0 + 0.02 * n
    if name.endswith(("_gain", "router_gamma")):
        return 1.0 + 0.1 * n
    if name.endswith(("res_bias", "res_out_bias")):
        return MERGE_BIAS_STD * n
    if name.endswith(("_b", "_b1", "_b2", "router_bias")):
        return 0.1 * n
    if name.endswith("k_temp"):
        return 1.0 + 0.25 * jnp.tanh(n)
    if name == "embed":
        return EMBED_STD * n
    if name.endswith("conv0_w"):
        return n / math.sqrt(2.0)
    if name.endswith("conv1_w"):
        return n / math.sqrt(2.0 * shape[-2])
    if name.endswith("router_w3"):
        return n * (ROUTER_OUT / math.sqrt(shape[-2]))
    return n / math.sqrt(shape[-2])


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


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta, rotary):
    """Rotary embedding of the first ``rotary`` dimensions of ``x (S,
    heads, D)`` at positions ``0 .. S - 1``: within them dimension ``d``
    pairs with ``d + rotary / 2`` and turns by ``pos x theta^(-2d /
    rotary)``; the rest passes."""
    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], -1)


def _before(x):
    """``x (S, ...)`` moved one position on: row ``t`` holds ``x[t - 1]``
    and row 0 zero."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)


def _merge(x, out, w, sub):
    return (x + w[sub + "_res_bias"]) * w[sub + "_res_gain"] \
        + (out + w[sub + "_res_out_bias"]) * w[sub + "_res_out_gain"]


@functools.partial(jax.jit, static_argnames=(
    "nq", "nkv", "theta", "rotary", "eps", "quant"))
def _layer(x, r, w, nq, nkv, theta, rotary, eps, quant):
    """One layer over one sequence ``x (S, H)`` with the router state ``r
    (S, 256)`` of the layer before. ``w``: the layer's tensors by their
    names without the layer's prefix. Returns ``(x, r_l, taps)``: ``k, v
    (S, 2, 128)`` as a cache would hold them, ``z, c0 (S, 1280)`` and ``v2
    (S, 128)`` as a tail would, and the router's margin ``(S,)`` between
    its two best."""
    S = x.shape[0]
    g = nq // nkv
    h = rms_norm(x, w["attn_norm"], eps)
    qt = _mm("sh,ha->sa", h, w["wq"], quant)
    kt = _mm("sh,ha->sa", h, w["wk"], quant)
    d = qt.shape[1] // nq
    z = jnp.concatenate([qt, kt], -1)
    c0 = w["conv0_w"][0] * _before(z) + w["conv0_w"][1] * z + w["conv0_b"]
    by_head = c0.reshape(S, nq + nkv, d)
    c1 = _mm("sni,nio->sno", _before(by_head), w["conv1_w"][0], quant) \
        + _mm("sni,nio->sno", by_head, w["conv1_w"][1], quant) \
        + w["conv1_b"].reshape(nq + nkv, d)
    qh, kh = qt.reshape(S, nkv, g, d), kt.reshape(S, nkv, d)
    m_q = ((qh + kh[:, :, None]) / 2).reshape(S, nq, d)
    m_k = (qh.mean(2) + kh) / 2
    q, k = c1[:, :nq] + m_q, c1[:, nq:] + m_k

    def to_length(y):
        return y * math.sqrt(d) / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True))

    q = rope(to_length(q), theta, rotary)
    k = rope(to_length(k) * w["k_temp"][:, None], theta, rotary)
    v2 = _mm("sh,hd->sd", h, w["wv2"], quant)
    v = jnp.stack([_mm("sh,hd->sd", h, w["wv1"], quant), _before(v2)], 1)
    score = _mm("tngd,snd->ngts", q.reshape(S, nkv, g, d), k, quant) \
        / math.sqrt(d)
    t = jnp.arange(S)
    prob = jax.nn.softmax(
        jnp.where((t[None, :] <= t[:, None])[None, None], score, NEG), -1)
    if quant == "fp8":
        prob = _fp8(prob)
    heads = _mm("ngts,snd->tngd", prob, v, quant).reshape(S, nq * d)
    x = _merge(x, _mm("sa,ah->sh", heads, w["wo"], quant), w, "attn")
    # ---- the router, its state carried from the layer before
    u = rms_norm(x, w["moe_norm"], eps)
    r = _mm("sh,hr->sr", u, w["router_down"], quant) + w["router_down_b"] \
        + w["router_gamma"] * r
    y = rms_norm(r, w["router_norm"], eps)
    y = jax.nn.gelu(_mm("sr,rq->sq", y, w["router_w1"], quant)
                    + w["router_b1"], approximate=False)
    y = jax.nn.gelu(_mm("sr,rq->sq", y, w["router_w2"], quant)
                    + w["router_b2"], approximate=False)
    p = jax.nn.softmax(_mm("sr,re->se", y, w["router_w3"], quant), -1)
    pick = p + w["router_bias"]
    e = jnp.argmax(pick, -1)
    a = jnp.take_along_axis(p, e[:, None], -1)
    best = jnp.sort(pick, -1)
    # ---- every expert over every token, the chosen one kept
    out = jnp.zeros_like(x)
    for j in range(w["w_gate"].shape[0]):
        mid = jax.nn.silu(_mm("sh,hf->sf", u, w["w_gate"][j], quant)) \
            * _mm("sh,hf->sf", u, w["w_up"][j], quant)
        out = out + jnp.where((e == j)[:, None],
                              _mm("sf,fh->sh", mid, w["w_down"][j], quant),
                              0.0)
    x = _merge(x, a * out, w, "moe")
    return x, r, {"k": k, "v": v, "z": z, "c0": c0, "v2": v2,
                  "margin": best[:, -1] - best[:, -2], "expert": e}


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, want, g, embed, eps, quant):
    return _mm("sh,vh->sv", rms_norm(x[want], g, eps), embed, quant)


def _padded(want, multiple):
    """The wanted positions, the last one repeated up to a multiple: one
    program of the head for replies of many lengths."""
    want = np.asarray(want, np.int32)
    return jnp.asarray(np.concatenate(
        [want, np.full(-len(want) % multiple, want[-1], np.int32)]))


def hidden(seed, cfg, tokens, quant=None, tap=None, pad_to=None):
    """The residual after the last layer, ``(S_padded, H)``, of one
    sequence ``tokens (S,)``. ``tap``, a dict, receives what it names:
    ``"kv_layers"``: ``k[l], v[l] (S, 2, 128)`` of those layers (what
    their pages have to hold); ``"tail_at"``, a position: ``tails (L, 2,
    1280)`` (rows ``z``, ``c0``) and ``halves (L, 128)`` there, every
    layer's (what a slot that stands after that position has to hold);
    and always ``margins (L, S)``, the router's margin between its two
    best, and ``experts (L, S)``."""
    tokens = np.asarray(tokens, np.int32)
    n_real = len(tokens)
    # a short sequence pads to a multiple of 16 and the check's sequences
    # all to ``pad_to``, so that few distinct shapes are compiled.
    # Attention and the convolutions are causal, so what lies past the
    # sequence changes nothing before it
    pad = (pad_to - n_real) if pad_to is not None and n_real <= pad_to \
        else -n_real % 16
    tokens = jnp.asarray(np.concatenate([tokens, np.zeros(pad, np.int32)]))
    rope_cfg = cfg["rope_parameters"]["hybrid"]
    static = dict(
        nq=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
        theta=float(rope_cfg["rope_theta"]),
        rotary=int(cfg["head_dim"] * rope_cfg["partial_rotary_factor"]),
        eps=float(cfg["rms_norm_eps"]), quant=quant)
    tap = {} if tap is None else tap
    at = tap.get("tail_at")
    margins, experts, tails, halves = [], [], [], []
    x = tensor(seed, cfg, "embed")[tokens]
    r = jnp.zeros((x.shape[0], cfg["router_hidden_size"]), jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}_"
        w = {n[len(p):]: tensor(seed, cfg, n, s)
             for n, s in layer_specs(cfg, p).items()}
        with jax.default_matmul_precision("highest"):
            x, r, got = _layer(x, r, w, **static)
        del w
        margins.append(np.asarray(got["margin"][:n_real]))
        experts.append(np.asarray(got["expert"][:n_real]))
        if i in tap.get("kv_layers", ()):
            tap.setdefault("k", {})[i] = np.asarray(got["k"][:n_real])
            tap.setdefault("v", {})[i] = np.asarray(got["v"][:n_real])
        if at is not None:
            tails.append(np.stack([np.asarray(got["z"][at]),
                                   np.asarray(got["c0"][at])]))
            halves.append(np.asarray(got["v2"][at]))
    tap["margins"], tap["experts"] = np.stack(margins), np.stack(experts)
    if at is not None:
        tap["tails"], tap["halves"] = np.stack(tails), np.stack(halves)
    return x


def logit_blocks(seed, cfg, x, want, quant=None):
    """Logits ``(n, vocab)`` of the positions ``want`` of the residual
    ``x``, a block of ``LOGIT_BLOCK`` positions at a time."""
    g, embed = tensor(seed, cfg, "norm"), tensor(seed, cfg, "embed")
    eps = float(cfg["rms_norm_eps"])
    want = np.asarray(want, np.int32)
    for lo in range(0, len(want), LOGIT_BLOCK):
        part = want[lo:lo + LOGIT_BLOCK]
        with jax.default_matmul_precision("highest"):
            block = _head(x, _padded(part, LOGIT_BLOCK), g, embed, eps=eps,
                          quant=quant)
        yield block[:len(part)]


def forward(seed, cfg, tokens, quant=None, want=None, tap=None, pad_to=None):
    """Logits ``(len(want), vocab)`` of one sequence ``tokens (S,)``: row
    ``j`` scores the token after ``tokens[:want[j] + 1]`` (``want`` None is
    every position). Small sizes: the whole block of logits is made."""
    x = hidden(seed, cfg, tokens, quant, tap, pad_to)
    want = np.arange(len(tokens)) if want is None else np.asarray(want)
    return jnp.concatenate(list(logit_blocks(seed, cfg, x, want, quant)))


# --------------------------------------------------------------- the check
def served_gaps(seed, cfg, prompt, served, quant=None, pad_to=None,
                tap=None):
    """``(served token gaps (len(served),), near ties)``. The sequence is
    the prompt followed by the served tokens; served token ``j`` is scored
    at position ``len(prompt) - 1 + j`` by how far its logit lies below
    the reference's best there; ``near ties`` is the share of (served
    position, layer) pairs whose router margin is under ``ROUTE_MARGIN``.
    With ``quant`` the served tokens only place the positions: the tokens
    the lower precision puts first stand in their place (the control need
    not decode)."""
    prompt, served = np.asarray(prompt, np.int32), np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    want = len(prompt) - 1 + np.arange(len(served))
    tap = {} if tap is None else tap
    gaps, at = [], 0
    x = hidden(seed, cfg, seq, None, tap, pad_to)
    low = None if quant is None else logit_blocks(
        seed, cfg, hidden(seed, cfg, seq, quant, None, pad_to), want, quant)
    for ref in logit_blocks(seed, cfg, x, want):
        toks = jnp.asarray(served[at:at + len(ref)]) if low is None \
            else jnp.argmax(next(low), -1)
        got = jnp.take_along_axis(ref, toks[:, None], -1)[:, 0]
        gaps.append(np.asarray(ref.max(-1) - got))
        at += len(ref)
    near = float((tap["margins"][:, want] < ROUTE_MARGIN).mean())
    return np.concatenate(gaps), near


def served_token_gaps(seed, cfg, prompt, served, quant=None, pad_to=None):
    """``served_gaps``'s first, under the name ``serve-lm.py`` asks for."""
    return served_gaps(seed, cfg, prompt, served, quant, pad_to)[0]


def greedy(seed, cfg, prompt, n):
    """Greedy decode by full forwards (tests, tiny sizes)."""
    seq = list(np.asarray(prompt))
    for _ in range(n):
        logits = forward(seed, cfg, seq, want=[len(seq) - 1])
        seq.append(int(jnp.argmax(logits[0])))
    return seq[len(prompt):]

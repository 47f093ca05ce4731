"""Plain reference of the ``olmo-hybrid-7b`` configuration: Ai2's
Olmo-Hybrid-7B language model (``olmo_hybrid``; decoder only: three gated
delta-rule layers to every full-attention layer, each followed by one
SwiGLU MLP; every sublayer's OUTPUT normed before it joins the residual; a
q/k norm over the whole projection and no positional term in the
full-attention layers; an untied head).

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision:
one teacher-forced full forward of ONE sequence. The delta rule is a plain
``lax.scan`` over tokens (``S <- alpha S``, ``u = beta (v - S^T k)``, ``S <-
S + k u^T``, ``o = S^T q``), the convolutions a sum over their four taps of
the zero-padded sequence, attention dense and causal, one head at a time so
that 5,120 positions at full width fit beside a layer's weights. No blocked
form, no solve, no carried state, no cache, no paging, no kernels; nothing
of the program is imported. A layer is one jitted function of its weights
and the sequence, so that the chip compiles three programs a length. The
same forward hands out each delta-rule layer's state after a given number
of tokens (``final_states``), for the check that holds a slot's state
itself, and not only the logits it leads to, against this file.

Weights come from the seed TENSOR BY TENSOR, each keyed by the seed and its
own name (``tensor``): the cut's 4.1 G parameters are 16.4 GB in float32, so
a layer's tensors are made when the forward reaches that layer and dropped
after it. Names are the program's structural parameter names; matrices are
stored ``(in, out)``, the convolutions' taps ``(taps, channels)`` over the
query, key and value channels side by side.

``quant="fp8"`` is the control: both operands of every matrix product
rounded to float8 (e4m3, scaled per tensor); the recurrence stays float32.
"""

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

NEG = -jnp.inf
L2_EPS = 1e-6


# ---------------------------------------------------------------- weights
def _sizes(cfg):
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"] \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or cfg["tie_word_embeddings"]:
        raise ValueError("the reference is written for a value head a key "
                         "head, a key/value head a query head and an untied "
                         "head")
    qk = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vw = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return qk, vw


def layer_specs(cfg, i):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    p = f"l{i}_"
    if cfg["layer_types"][i] == "linear_attention":
        qk, vw = _sizes(cfg)
        nh = cfg["linear_num_key_heads"]
        out = {p + "wq": (h, qk), p + "wk": (h, qk), p + "wv": (h, vw),
               p + "wg": (h, vw), p + "wa": (h, nh), p + "wb": (h, nh),
               p + "conv_w": (cfg["linear_conv_kernel_dim"], 2 * qk + vw),
               p + "dt_bias": (nh,), p + "a_log": (nh,),
               p + "o_norm": (cfg["linear_value_head_dim"],),
               p + "wo": (vw, h)}
    else:
        out = {p + "wq": (h, h), p + "wk": (h, h), p + "wv": (h, h),
               p + "q_norm": (h,), p + "k_norm": (h,), p + "wo": (h, h)}
    out.update({p + "mixer_norm": (h,), p + "mlp_in": (h, 2 * f),
                p + "mlp_out": (f, h), p + "mlp_norm": (h,)})
    return out


def tensor_specs(cfg):
    """``{name: shape}`` of every tensor, in the order they are made."""
    out = {"embed": (cfg["vocab_size"], cfg["hidden_size"])}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_specs(cfg, i))
    out["norm"] = (cfg["hidden_size"],)
    out["head"] = (cfg["hidden_size"], cfg["vocab_size"])
    return out


def tensor(seed, cfg, name, shape=None):
    """One tensor from the seed and its own name, float32. Every sublayer's
    output is normed before it joins the residual, so a matrix's scale
    moves nothing downstream of its norm: gains are 1 + normal(0, 0.02),
    the embedding unit normal, every matrix and the convolutions' taps
    normal(0, 1 / fan_in). The two gate projections are a quarter of that:
    no norm stands before a mixer, the stream's size grows as the square
    root of the sublayers behind it (5.7 at the last of 16 layers), and at
    full scale the step ``dt`` would swing over five octaves a token and
    wipe a head's state every few tokens, which hides a dropped state. The
    recurrence's own parameters follow the family's initialisation: ``A``
    uniform in 1..16 (``a_log`` its logarithm), the step ``dt`` log-uniform
    in 0.001..0.1 (``dt_bias`` its inverse softplus)."""
    shape = tuple(shape or tensor_specs(cfg)[name])
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if name.endswith("a_log"):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name.endswith("dt_bias"):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    w = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm"):
        return 1.0 + 0.02 * w
    if name == "embed":
        return w
    if name.endswith(("_wa", "_wb")):
        return w * (0.25 / math.sqrt(shape[-2]))
    return w * (1.0 / math.sqrt(shape[-2]))


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


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _hyper(cfg):
    """The configuration's numbers a layer needs, hashable (a jitted
    layer's static argument)."""
    qk, vw = _sizes(cfg)
    return (("eps", cfg["rms_norm_eps"]), ("qk", qk), ("vw", vw),
            ("heads", cfg["linear_num_key_heads"]),
            ("d_k", cfg["linear_key_head_dim"]),
            ("d_v", cfg["linear_value_head_dim"]),
            ("taps", cfg["linear_conv_kernel_dim"]),
            ("neg", bool(cfg["linear_allow_neg_eigval"])),
            ("nq", cfg["num_attention_heads"]),
            ("width", cfg["intermediate_size"]))


@functools.partial(jax.jit, static_argnames=("hp", "quant"))
def _delta(w, x, at, hp, quant):
    """The gated delta-rule mixer on one sequence ``x (S, H)`` (NOT normed:
    the block norms a sublayer's output); ``w`` holds the layer's tensors
    under their names without the layer's prefix. Returns the mixer's
    output and the state as it stood after the first ``at[k]`` tokens,
    ``(len(at), heads, d_k, d_v)``."""
    hp = dict(hp)
    S = x.shape[0]
    qk, nh, dk, dv = hp["qk"], hp["heads"], hp["d_k"], hp["d_v"]
    qkv = jnp.concatenate([_mm("sh,hd->sd", x, w["wq"], quant),
                           _mm("sh,hd->sd", x, w["wk"], quant),
                           _mm("sh,hd->sd", x, w["wv"], quant)], -1)
    # causal depthwise convolutions without bias: position t reads t-3..t,
    # zeros before 0
    K = hp["taps"]
    ext = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1])), qkv], 0)
    qkv = jax.nn.silu(sum(w["conv_w"][j] * ext[j:j + S] for j in range(K)))
    q = _unit(qkv[:, :qk].reshape(S, nh, dk)) / math.sqrt(dk)
    k = _unit(qkv[:, qk:2 * qk].reshape(S, nh, dk))
    v = qkv[:, 2 * qk:].reshape(S, nh, dv)
    beta = jax.nn.sigmoid(_mm("sh,hn->sn", x, w["wb"], quant))
    if hp["neg"]:
        beta = 2.0 * beta
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(
        _mm("sh,hn->sn", x, w["wa"], quant) + w["dt_bias"])

    def step(carry, inp):
        s, kept = carry
        t, qt, kt, vt, gt, bt = inp
        s = jnp.exp(gt)[:, None, None] * s
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
        s = s + kt[:, :, None] * u[:, None, :]
        kept = jnp.where((at == t + 1)[:, None, None, None], s, kept)
        return (s, kept), jnp.einsum("hkv,hk->hv", s, qt)

    zero = jnp.zeros((nh, dk, dv))
    (_, kept), o = jax.lax.scan(
        step, (zero, jnp.zeros((at.shape[0],) + zero.shape)),
        (jnp.arange(S), q, k, v, g, beta))
    # the norm over a head's d_v with one gain for every head, then the gate
    o = rms_norm(o, w["o_norm"], hp["eps"]).reshape(S, -1) \
        * jax.nn.silu(_mm("sh,hd->sd", x, w["wg"], quant))
    return _mm("sd,dh->sh", o, w["wo"], quant), kept


@functools.partial(jax.jit, static_argnames=("hp", "quant"))
def _attention(w, x, hp, quant):
    """Causal attention with a key/value head a query head, one norm over
    the whole query projection and one over the key's, no positional term,
    a head at a time."""
    hp = dict(hp)
    S, nq = x.shape[0], hp["nq"]
    q = rms_norm(_mm("sh,hd->sd", x, w["wq"], quant), w["q_norm"], hp["eps"])
    k = rms_norm(_mm("sh,hd->sd", x, w["wk"], quant), w["k_norm"], hp["eps"])
    v = _mm("sh,hd->sd", x, w["wv"], quant)
    d = q.shape[1] // nq
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def head(qkv):
        qh, kh, vh = qkv
        score = _mm("td,sd->ts", qh, kh, quant) / math.sqrt(d)
        prob = jax.nn.softmax(jnp.where(causal, score, NEG), -1)
        if quant == "fp8":
            prob = _fp8(prob)
        return _mm("ts,sd->td", prob, vh, quant)

    by_head = tuple(jnp.moveaxis(t.reshape(S, nq, d), 1, 0)
                    for t in (q, k, v))
    o = jnp.moveaxis(jax.lax.map(head, by_head), 0, 1).reshape(S, -1)
    return _mm("sd,dh->sh", o, w["wo"], quant)


@functools.partial(jax.jit, static_argnames=("hp", "quant"))
def _mlp(w, x, hp, quant):
    f = dict(hp)["width"]
    gu = _mm("sh,hf->sf", x, w["mlp_in"], quant)
    return _mm("sf,fh->sh", jax.nn.silu(gu[:, :f]) * gu[:, f:], w["mlp_out"],
               quant)


def _stream(seed, cfg, tokens, quant, pad_to, at=None):
    """The residual stream after the last layer of one sequence ``tokens``
    (S,) and each delta-rule layer's state after the first ``at[k]``
    tokens. The sequence is padded at its END (to ``pad_to``, else to a
    multiple of 16) so that few distinct lengths are compiled; every layer
    is causal, so what lies past a position changes nothing before it."""
    tokens = np.asarray(tokens, np.int32)
    n_real = len(tokens)
    pad = pad_to - n_real if pad_to is not None and n_real <= pad_to \
        else -n_real % 16
    tokens = np.concatenate([tokens, np.zeros(pad, np.int32)])
    at = jnp.asarray([n_real] if at is None else at, jnp.int32)
    hp, eps = _hyper(cfg), cfg["rms_norm_eps"]
    states = []
    x = tensor(seed, cfg, "embed")[jnp.asarray(tokens)]
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"l{i}_"
        w = {n[len(p):]: tensor(seed, cfg, n, s)
             for n, s in layer_specs(cfg, i).items()}
        if kind == "linear_attention":
            y, kept = _delta(w, x, at, hp=hp, quant=quant)
            states.append(kept)
        else:
            y = _attention(w, x, hp=hp, quant=quant)
        x = x + rms_norm(y, w["mixer_norm"], eps)
        x = x + rms_norm(_mlp(w, x, hp=hp, quant=quant), w["mlp_norm"], eps)
        del w
    return x, states


def forward(seed, cfg, tokens, quant=None, want=None, pad_to=None):
    """Logits (len(want), vocab) of one sequence ``tokens`` (S,) at the
    positions ``want`` (all of them when None): row ``j`` scores the token
    after ``tokens[:want[j] + 1]``."""
    with jax.default_matmul_precision("highest"):
        x, _ = _stream(seed, cfg, tokens, quant, pad_to)
        want = np.arange(len(tokens)) if want is None else np.asarray(want)
        y = rms_norm(x[jnp.asarray(want)], tensor(seed, cfg, "norm"),
                     cfg["rms_norm_eps"])
        return _mm("sh,hv->sv", y, tensor(seed, cfg, "head"), quant)


def final_states(seed, cfg, tokens, at, pad_to=None):
    """Each delta-rule layer's state after the first ``at[k]`` tokens of one
    sequence, ``(layers, len(at), heads, d_k, d_v)`` float32: what a serving
    slot should hold once it has taken that many positions of the sequence
    in."""
    with jax.default_matmul_precision("highest"):
        _, states = _stream(seed, cfg, tokens, None, pad_to, at)
    return np.stack([np.asarray(s) for s in states])


# --------------------------------------------------------------- the check
def served_token_gaps(seed, cfg, prompt, served, quant=None, pad_to=None):
    """For each served token, how far its logit lies below the reference's
    best at its position, (len(served),) float32: the sequence is the prompt
    followed by the served tokens, and served token ``j`` is scored at
    position ``len(prompt) - 1 + j``. With ``quant`` the served tokens only
    place the positions: the token the lower precision puts first stands in
    their place (the control need not decode)."""
    prompt, served = np.asarray(prompt, np.int32), np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    want = len(prompt) - 1 + np.arange(len(served))
    ref = forward(seed, cfg, seq, want=want, pad_to=pad_to)
    if quant is not None:
        served = jnp.argmax(forward(seed, cfg, seq, quant=quant, want=want,
                                    pad_to=pad_to), -1)
    got = jnp.take_along_axis(ref, jnp.asarray(served)[:, None], -1)[:, 0]
    return np.asarray(ref.max(-1) - got)


def greedy(seed, cfg, prompt, n):
    """Greedy decode by full forwards (tests, tiny sizes)."""
    seq = list(np.asarray(prompt))
    for _ in range(n):
        logits = forward(seed, cfg, seq, want=[len(seq) - 1])
        seq.append(int(jnp.argmax(logits[0])))
    return seq[len(prompt):]

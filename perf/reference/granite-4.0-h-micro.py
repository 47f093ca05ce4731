"""Plain reference of the ``granite-4.0-h-micro`` configuration: IBM's
Granite 4.0-H Micro language model (``granitemoehybrid`` with no experts;
decoder only: Mamba-2 state-space layers among a few grouped-query
attention layers with no positional term, each followed by one SwiGLU MLP;
multipliers on the embedding, the residual branches, the attention scores
and under the logits; the head is the embedding).

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision:
one teacher-forced full forward of ONE sequence. The state-space recurrence
is a plain ``lax.scan`` over tokens (``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
(outer) B_t``, ``y_t = S_t C_t + D x_t``), the convolution a sum over its
four taps of the zero-padded sequence, attention dense and causal. No
chunked form, no carried state, no cache, no paging, no kernels; nothing of
the program is imported. A layer is one jitted function of its weights and
the sequence, so that the chip compiles three programs a length. The same
forward hands out each state-space layer's state after a given number of
tokens (``final_states``), for the check that holds a slot's recurrent
state itself, and not only the logits it leads to, against this file.

Weights come from the seed TENSOR BY TENSOR, each keyed by the seed and its
own name (``tensor``): 3.19 G parameters are 12.8 GB in float32, so a
layer's tensors are made when the forward reaches that layer and dropped
after it. Names are the program's structural parameter names; matrices are
stored ``(in, out)``, the convolution ``(kernel, channels)``.

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


# ---------------------------------------------------------------- weights
def _sizes(cfg):
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    if inner != cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
            or cfg["mamba_n_groups"] != 1 or cfg["num_local_experts"] != 0 \
            or not cfg["tie_word_embeddings"]:
        raise ValueError("the reference is written for one group of B and "
                         "C, no experts, a tied head and d_inner = heads x "
                         "head size")
    conv_dim = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return inner, conv_dim


def layer_specs(cfg, i):
    h, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    p = f"l{i}_"
    out = {p + "mixer_norm": (h,)}
    if cfg["layer_types"][i] == "mamba":
        inner, conv_dim = _sizes(cfg)
        nh = cfg["mamba_n_heads"]
        out.update({p + "in_proj": (h, inner + conv_dim + nh),
                    p + "conv_w": (cfg["mamba_d_conv"], conv_dim),
                    p + "conv_b": (conv_dim,), p + "dt_bias": (nh,),
                    p + "a_log": (nh,), p + "d_skip": (nh,),
                    p + "ssm_norm": (inner,), p + "out_proj": (inner, h)})
    else:
        d = h // cfg["num_attention_heads"]
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        out.update({p + "wq": (h, nq * d), p + "wk": (h, nkv * d),
                    p + "wv": (h, nkv * d), p + "wo": (nq * d, h)})
    out.update({p + "mlp_norm": (h,), p + "mlp_in": (h, 2 * f),
                p + "mlp_out": (f, h)})
    return out


def tensor_specs(cfg):
    """``{name: shape}`` of every tensor, in the order they are made. The
    head is the embedding (``tie_word_embeddings``)."""
    out = {"embed": (cfg["vocab_size"], cfg["hidden_size"])}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_specs(cfg, i))
    out["norm"] = (cfg["hidden_size"],)
    return out


def tensor(seed, cfg, name, shape=None):
    """One tensor from the seed and its own name, float32. Norm gains are 1
    + normal(0, 0.02). Every matrix (and the convolution over its 4 taps)
    is normal(0, 1 / fan_in), so that every sub-layer weighs in at
    ``residual_multiplier``. The embedding is normal(0, 1 / (16 x
    embedding_multiplier)): the head is the embedding, so a token's own row
    scores the stream it started; at unit scale (1 / embedding_multiplier)
    that term stands 70 deviations above the other tokens' logits and every
    reply repeats the prompt's last token whatever the layers do, which no
    comparison of logits would see through. At a sixteenth it stands 1.4
    deviations above, one token among the 100,352. The recurrence's own
    parameters follow the family's initialisation: ``A`` uniform in 1..16
    (``a_log`` its logarithm), the step ``dt`` log-uniform in 0.001..0.1
    (``dt_bias`` its inverse softplus), ``D`` ones; the convolution's bias
    normal(0, 0.1)."""
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
    if name.endswith("d_skip"):
        return jnp.ones(shape, jnp.float32)
    w = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm"):
        return 1.0 + 0.02 * w
    if name.endswith("conv_b"):
        return 0.1 * w
    if name == "embed":
        return w / (16.0 * float(cfg["embedding_multiplier"]))
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


def _hyper(cfg):
    """The configuration's numbers a layer needs, hashable (a jitted
    layer's static argument)."""
    inner, conv_dim = _sizes(cfg)
    return (("eps", cfg["rms_norm_eps"]), ("inner", inner),
            ("heads", cfg["mamba_n_heads"]), ("d_head", cfg["mamba_d_head"]),
            ("d_state", cfg["mamba_d_state"]), ("d_conv", cfg["mamba_d_conv"]),
            ("nq", cfg["num_attention_heads"]),
            ("nkv", cfg["num_key_value_heads"]),
            ("scale", cfg["attention_multiplier"]),
            ("width", cfg["shared_intermediate_size"]))


@functools.partial(jax.jit, static_argnames=("hp", "quant"))
def _mamba(w, y, at, hp, quant):
    """The Mamba-2 mixer on one normed sequence ``y (S, H)``; ``w`` holds
    the layer's tensors under their names without the layer's prefix.
    Returns the mixer's output and the recurrent state as it stood after
    the first ``at[k]`` tokens, ``(len(at), heads, d_head, d_state)``."""
    hp = dict(hp)
    S = y.shape[0]
    inner, nh, dh, n = hp["inner"], hp["heads"], hp["d_head"], hp["d_state"]
    zxd = _mm("sh,hd->sd", y, w["in_proj"], quant)
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:-nh], zxd[:, -nh:]
    # causal depthwise convolution: position t reads t-3..t, zeros before 0
    K = hp["d_conv"]
    ext = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], 0)
    conv = w["conv_b"] + sum(w["conv_w"][k] * ext[k:k + S] for k in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(S, nh, dh)
    b, c = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])        # no clamp: (0, inf)
    a = -jnp.exp(w["a_log"])

    def step(carry, inp):
        s, kept = carry
        t, xt, dtt, bt, ct = inp
        s = jnp.exp(dtt * a)[:, None, None] * s \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        kept = jnp.where((at == t + 1)[:, None, None, None], s, kept)
        return (s, kept), jnp.einsum("hpn,n->hp", s, ct)

    zero = jnp.zeros((nh, dh, n))
    (_, kept), ys = jax.lax.scan(
        step, (zero, jnp.zeros((at.shape[0],) + zero.shape)),
        (jnp.arange(S), x, dt, b, c))
    ys = ys + w["d_skip"][None, :, None] * x
    # the gate BEFORE the norm, over all of d_inner
    g = rms_norm(ys.reshape(S, inner) * jax.nn.silu(z), w["ssm_norm"],
                 hp["eps"])
    return _mm("sd,dh->sh", g, w["out_proj"], quant), kept


@functools.partial(jax.jit, static_argnames=("hp", "quant"))
def _attention(w, y, hp, quant):
    """Causal grouped-query attention with no positional term, scores times
    ``attention_multiplier``."""
    hp = dict(hp)
    S = y.shape[0]
    nq, nkv = hp["nq"], hp["nkv"]
    q = _mm("sh,hd->sd", y, w["wq"], quant).reshape(S, nkv, nq // nkv, -1)
    k = _mm("sh,hd->sd", y, w["wk"], quant).reshape(S, nkv, -1)
    v = _mm("sh,hd->sd", y, w["wv"], quant).reshape(S, nkv, -1)
    score = _mm("tgid,sgd->gits", q, k, quant) * hp["scale"]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    prob = jax.nn.softmax(jnp.where(causal[None, None], score, NEG), -1)
    if quant == "fp8":
        prob = _fp8(prob)
    o = _mm("gits,sgd->tgid", prob, v, quant).reshape(S, -1)
    return _mm("sd,dh->sh", o, w["wo"], quant)


@functools.partial(jax.jit, static_argnames=("hp", "quant"))
def _mlp(w, y, hp, quant):
    f = dict(hp)["width"]
    gu = _mm("sh,hf->sf", y, w["mlp_in"], quant)
    return _mm("sf,fh->sh", jax.nn.silu(gu[:, :f]) * gu[:, f:], w["mlp_out"],
               quant)


def _stream(seed, cfg, tokens, quant, pad_to, at=None):
    """The residual stream after the last layer of one sequence ``tokens``
    (S,), the embedding, and each state-space layer's state after the first
    ``at[k]`` tokens. The sequence is padded at its END (to ``pad_to``,
    else to a multiple of 16) so that few distinct lengths are compiled;
    every layer is causal, so what lies past a position changes nothing
    before it."""
    tokens = np.asarray(tokens, np.int32)
    n_real = len(tokens)
    pad = pad_to - n_real if pad_to is not None and n_real <= pad_to \
        else -n_real % 16
    tokens = np.concatenate([tokens, np.zeros(pad, np.int32)])
    at = jnp.asarray([n_real] if at is None else at, jnp.int32)
    hp, eps, res = _hyper(cfg), cfg["rms_norm_eps"], \
        cfg["residual_multiplier"]
    states = []
    embed = tensor(seed, cfg, "embed")
    x = embed[jnp.asarray(tokens)] * cfg["embedding_multiplier"]
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"l{i}_"
        w = {n[len(p):]: tensor(seed, cfg, n, s)
             for n, s in layer_specs(cfg, i).items()}
        y = rms_norm(x, w["mixer_norm"], eps)
        if kind == "mamba":
            y, kept = _mamba(w, y, at, hp=hp, quant=quant)
            states.append(kept)
        else:
            y = _attention(w, y, hp=hp, quant=quant)
        x = x + res * y
        x = x + res * _mlp(w, rms_norm(x, w["mlp_norm"], eps), hp=hp,
                           quant=quant)
        del w
    return x, embed, states


def forward(seed, cfg, tokens, quant=None, want=None, pad_to=None):
    """Logits (len(want), vocab) of one sequence ``tokens`` (S,) at the
    positions ``want`` (all of them when None): row ``j`` scores the token
    after ``tokens[:want[j] + 1]``."""
    with jax.default_matmul_precision("highest"):
        x, embed, _ = _stream(seed, cfg, tokens, quant, pad_to)
        want = np.arange(len(tokens)) if want is None else np.asarray(want)
        y = rms_norm(x[jnp.asarray(want)], tensor(seed, cfg, "norm"),
                     cfg["rms_norm_eps"])
        return _mm("sh,vh->sv", y, embed, quant) / cfg["logits_scaling"]


def final_states(seed, cfg, tokens, at, pad_to=None):
    """Each state-space layer's recurrent state after the first ``at[k]``
    tokens of one sequence, ``(layers, len(at), heads, d_head, d_state)``
    float32: what a serving slot should hold once it has taken that many
    positions of the sequence in."""
    with jax.default_matmul_precision("highest"):
        _, _, states = _stream(seed, cfg, tokens, None, pad_to, at)
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

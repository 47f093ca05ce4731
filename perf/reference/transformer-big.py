"""Plain reference of the ``transformer-big`` configuration: the
encoder-decoder of Vaswani et al. 2017 in its pre-LayerNorm arrangement
(tensor2tensor ``transformer_big``: normalise before each sub-layer, add
after), learned positions, ReLU feed-forward, target embedding tied to the
output projection. Straightforward ``jax.numpy`` in float32 with ``highest``
matmul precision: one full teacher-forced forward pass, no cache, no paging,
no kernels. It imports nothing of the program and makes its own weights from
the seed.

Parameters are a flat dict keyed by the path of each array in the model.
The fused QKV projection of self-attention holds, for each head, its query,
key and value rows in turn; cross-attention has three projections.

``quant="fp8"`` is the control: both operands of every matrix product
rounded to float8 (e4m3, scaled per tensor), the nearest precision below
the bf16 the configuration serves in.
"""

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
NEG = -1e30
EMBED_SCALE = 0.1


def param_shapes(cfg):
    u, f, v = cfg["hidden_size"], cfg["filter_size"], cfg["vocab_size"]
    shapes = {"src_embed.weight": (v, u), "tgt_embed.weight": (v, u),
              "pos_embed.weight": (cfg["max_length"], u),
              "encoder.ln.gamma": (u,), "encoder.ln.beta": (u,),
              "decoder.ln.gamma": (u,), "decoder.ln.beta": (u,)}

    def dense(p, o, i):
        shapes[p + ".weight"] = (o, i)
        shapes[p + ".bias"] = (o,)

    def norm(p):
        shapes[p + ".gamma"] = (u,)
        shapes[p + ".beta"] = (u,)

    for i in range(cfg["num_hidden_layers"]):
        e = f"encoder.layers.{i}"
        norm(e + ".ln1"), norm(e + ".ln2")
        dense(e + ".attn.qkv_proj", 3 * u, u), dense(e + ".attn.out_proj", u, u)
        dense(e + ".ffn.ffn_1", f, u), dense(e + ".ffn.ffn_2", u, f)
        d = f"decoder.layer{i}"
        norm(d + ".ln1"), norm(d + ".ln2"), norm(d + ".ln3")
        dense(d + ".self_attn.qkv_proj", 3 * u, u)
        dense(d + ".self_attn.out_proj", u, u)
        for part in ("q_proj", "k_proj", "v_proj", "out_proj"):
            dense(d + ".cross_attn." + part, u, u)
        dense(d + ".ffn.ffn_1", f, u), dense(d + ".ffn.ffn_2", u, f)
    return shapes


def init_params(seed, cfg):
    """Every weight from the seed in ONE jitted call on the device, float32.
    Embeddings normal(0, (0.1 / sqrt(hidden))^2): a tenth of tensor2tensor's
    spread. At the full spread an untrained model with a tied output copies
    its input token with a margin of some twenty logits, and a check on the
    served token would see no precision at all; at a tenth the sub-layers
    carry the residual stream and the best token depends on all of them.
    Matrices normal with Glorot's variance; biases, LayerNorm shifts and
    positions normal(0, 0.02); LayerNorm scales 1 + normal(0, 0.02)."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)
    u = cfg["hidden_size"]

    def std(name, shape):
        if name in ("src_embed.weight", "tgt_embed.weight"):
            return EMBED_SCALE * u ** -0.5
        if name.endswith(".weight") and len(shape) == 2 \
                and not name.startswith("pos_embed"):
            return math.sqrt(2.0 / (shape[0] + shape[1]))
        return 0.02

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, n in zip(keys, names):
            w = std(n, shapes[n]) * jax.random.normal(k, shapes[n],
                                                      jnp.float32)
            out[n] = 1.0 + w if n.endswith("gamma") else w
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return make(key)


# ------------------------------------------------------------- equations
def _fp8(x):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _dense(params, p, x, quant):
    w = params[p + ".weight"]
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum("...i,oi->...o", x, w) + params[p + ".bias"]


def _norm(params, p, x):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * params[p + ".gamma"] \
        + params[p + ".beta"]


def _attend(q, k, v, mask, quant):
    """q (B,Lq,H,D), k and v (B,Lk,H,D), mask (B,1,Lq,Lk) True = may look."""
    if quant == "fp8":
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    score = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    prob = jax.nn.softmax(jnp.where(mask, score, NEG), axis=-1)
    if quant == "fp8":
        prob = _fp8(prob)
    out = jnp.einsum("bhqk,bkhd->bqhd", prob, v)
    return out.reshape(out.shape[0], out.shape[1], -1)


def _self_attention(params, p, x, mask, heads, quant):
    B, L, u = x.shape
    D = u // heads
    qkv = _dense(params, p + ".qkv_proj", x, quant).reshape(B, L, heads, 3 * D)
    a = _attend(qkv[..., :D], qkv[..., D:2 * D], qkv[..., 2 * D:], mask,
                quant)
    return _dense(params, p + ".out_proj", a, quant)


def _cross_attention(params, p, x, memory, mask, heads, quant):
    B, L, u = x.shape
    D = u // heads

    def split(t):
        return t.reshape(t.shape[0], t.shape[1], heads, D)

    a = _attend(split(_dense(params, p + ".q_proj", x, quant)),
                split(_dense(params, p + ".k_proj", memory, quant)),
                split(_dense(params, p + ".v_proj", memory, quant)),
                mask, quant)
    return _dense(params, p + ".out_proj", a, quant)


def _ffn(params, p, x, quant):
    h = jax.nn.relu(_dense(params, p + ".ffn_1", x, quant))
    return _dense(params, p + ".ffn_2", h, quant)


def _embed(params, table, ids, u):
    L = ids.shape[1]
    return params[table][ids] * math.sqrt(u) \
        + params["pos_embed.weight"][:L][None]


def logits(params, src, src_len, tgt_in, cfg, quant=None):
    """Teacher-forced logits (B, T, vocab): column ``j`` scores the token
    after ``tgt_in[:, :j + 1]``. ``src`` (B, S) is padded past ``src_len``."""
    u, heads = cfg["hidden_size"], cfg["num_heads"]
    S, T = src.shape[1], tgt_in.shape[1]
    src_ok = (jnp.arange(S)[None, :] < src_len[:, None])[:, None, None, :]
    x = _embed(params, "src_embed.weight", src, u)
    for i in range(cfg["num_hidden_layers"]):
        p = f"encoder.layers.{i}"
        x = x + _self_attention(params, p + ".attn",
                                _norm(params, p + ".ln1", x), src_ok, heads,
                                quant)
        x = x + _ffn(params, p + ".ffn", _norm(params, p + ".ln2", x), quant)
    memory = _norm(params, "encoder.ln", x)
    causal = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None, None]
    y = _embed(params, "tgt_embed.weight", tgt_in, u)
    for i in range(cfg["num_hidden_layers"]):
        p = f"decoder.layer{i}"
        y = y + _self_attention(params, p + ".self_attn",
                                _norm(params, p + ".ln1", y), causal, heads,
                                quant)
        y = y + _cross_attention(params, p + ".cross_attn",
                                 _norm(params, p + ".ln2", y), memory, src_ok,
                                 heads, quant)
        y = y + _ffn(params, p + ".ffn", _norm(params, p + ".ln3", y), quant)
    y = _norm(params, "decoder.ln", y)
    w = params["tgt_embed.weight"]
    if quant == "fp8":
        y, w = _fp8(y), _fp8(w)
    return jnp.einsum("bti,vi->btv", y, w)


# ------------------------------------------------------------ the check
_GAPS = {}


def _gaps_fn(cfg):
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, str))))
    if key not in _GAPS:
        @functools.partial(jax.jit, static_argnames=("quant",))
        def gaps(params, src, src_len, tgt_in, served, quant):
            with jax.default_matmul_precision("highest"):
                ref = logits(params, src, src_len, tgt_in, cfg)
                if quant is not None:
                    served = jnp.argmax(logits(params, src, src_len, tgt_in,
                                               cfg, quant), axis=-1)
            got = jnp.take_along_axis(ref, served[..., None], axis=-1)[..., 0]
            return ref.max(-1) - got

        _GAPS[key] = gaps
    return _GAPS[key]


def served_token_gaps(params, src, src_len, tgt_in, served, cfg, quant=None):
    """For each position, how far the served token's logit lies below the
    reference's best, (B, T) float32. With ``quant`` the served tokens are
    ignored: the token that the lower precision puts first takes their place
    (the control need not decode)."""
    return _gaps_fn(cfg)(params, jnp.asarray(src), jnp.asarray(src_len),
                         jnp.asarray(tgt_in), jnp.asarray(served), quant)

"""Plain reference of the ``xing4.0-29b-a4b`` configuration: Xing4.0's
language model (decoder only: a residual stream ``hc_mult`` wide mixed by
manifold-constrained hyper-connections, arXiv:2512.24880, around RMSNorm,
multi-head latent attention with a low-rank query and interleaved rotary
pairs stretched by YaRN, two dense SwiGLU layers, then SwiGLU experts
behind a sigmoid router with a selection bias beside a shared expert, an
untied head) and its one multi-token-prediction module.

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision:
one teacher-forced full forward of ONE sequence, the stream as ``(S, n,
C)``, the EXPANDED attention (every head's keys and values made from the
latent, dense causal softmax), experts by a plain loop. No cache, no
paging, no absorbed product, no kernels, no batching; nothing of the
program is imported (``ops/hyper_connection.py``, ``ops/mla.py`` and this
file each compute the maps and YaRN's table their own way). Queries go
through in blocks so that a 33k-token sequence fits; a block of queries, a
mixer's two halves, a layer's projections, the router, an expert on its
tokens and the head are each one jitted function.

**The layer.** ``n = hc_mult``, ``C = hidden_size``; ``X_0`` is the
embedding row repeated ``n`` times. Every sublayer ``F`` (a block's
attention, then its feed-forward: two mixers a block, each with its own
``phi``, ``alpha``, ``bias``) is wrapped so:

    x   = RMSNorm(vec(X))               over all n*C numbers, no gain, hc_eps
    Hp~ = a_pre  * (x @ phi_pre ) + b_pre     phi = [phi_pre | phi_post |
    Ho~ = a_post * (x @ phi_post) + b_post           phi_res], (n*C, n+n+n*n)
    Hr~ = a_res  * mat(x @ phi_res) + b_res   (n, n), row-major
    H_pre = sigmoid(Hp~)        H_post = 2 * sigmoid(Ho~)
    M = exp(clip(Hr~, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    hc_sinkhorn_iters times:  M = M / (rowsum(M) + hc_eps)
                              M = M / (colsum(M) + hc_eps)
    u  = H_pre @ X                      (C,)
    y  = F(RMSNorm_gain(u))             the block's own input norm
    X' = M @ X + outer(H_post, y)

After the last block ``h = sum_i X_i``, then the final norm and the head.
The module's input is that ``h`` and the next token; its block runs on a
stream repeated from the joint's output under its own two mixers and is
summed at its end.

**YaRN**, as the DeepSeek-V3 release computes it: pair ``i`` of the 32
turns by ``theta^(-2i/64)`` below the pair that turns ``beta_fast`` times
in the original 4,096 positions, by that over ``factor`` above the pair
that turns ``beta_slow`` times, a linear ramp between; cos and sin times
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``; the softmax
scale is ``(nope + rope)^-0.5 x mscale(factor, mscale_all_dim)^2``.

Weights come from the seed TENSOR BY TENSOR, each keyed by the seed and its
own name, an expert's by the expert's own number as well. Names are the
program's structural parameter names; matrices are stored ``(in, out)``.

Departures from the description, each where it is made: the query blocks
and the padding to few shapes (``forward``); an expert's token list padded
to a multiple (``_experts``); logits at the wanted positions only.

``quant="fp8"`` is the control: both operands of every matrix product
rounded to float8 (e4m3, scaled per tensor), the mixers' projection among
them.
"""

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-6
NEG = -jnp.inf
Q_BLOCK = 256          # queries a block (33k keys: 1.1 GB of scores)
EXPERT_PAD = 256       # an expert's token list is padded to a multiple
BIAS_STD = 0.1         # the router's selection bias: normal(0, 0.1)
HC_BIAS_STD = 0.5      # a mixer's biases: normal(0, 0.5)
HC_ALPHA = (1.0, 1.0, 0.5)     # a_pre, a_post, a_res: x (1 + normal(0, 0.1))
HC_LARGE = (33.0, 31.0)        # added to H_res~[0, 0] and [0, 1] of every
                               # attention mixer: both past the clamp


# ---------------------------------------------------------------- weights
def block_specs(cfg, p, dense):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    out = {p + "attn_norm": (h,), p + "wq_a": (h, rq), p + "q_norm": (rq,),
           p + "wq_b": (rq, nh * (dn + dr)), p + "wkv_a": (h, rkv + dr),
           p + "kv_norm": (rkv,), p + "wkv_b": (rkv, nh * (dn + dv)),
           p + "wo": (nh * dv, h), p + "mlp_norm": (h,)}
    n = cfg["hc_mult"]
    for k in ("attn", "mlp"):
        out.update({p + k + "_hc_phi": (n * h, n * (n + 2)),
                    p + k + "_hc_alpha": (3,),
                    p + k + "_hc_bias": (n * (n + 2),)})
    if dense:
        f = cfg["intermediate_size"]
        out.update({p + "dense_gate": (h, f), p + "dense_up": (h, f),
                    p + "dense_down": (f, h)})
    else:
        f, n = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        sf = f * cfg["n_shared_experts"]
        out.update({p + "router": (h, n), p + "router_bias": (n,),
                    p + "w_gate": (n, h, f), p + "w_up": (n, h, f),
                    p + "w_down": (n, f, h), p + "shared_gate": (h, sf),
                    p + "shared_up": (h, sf), p + "shared_down": (sf, h)})
    return out


def blocks(cfg):
    """``(prefix, dense)`` of the model's layers, in order."""
    return [(f"l{i}_", i < cfg["first_k_dense_replace"])
            for i in range(cfg["num_hidden_layers"])]


def module_specs(cfg):
    h = cfg["hidden_size"]
    return {"mtp_enorm": (h,), "mtp_hnorm": (h,), "mtp_eh_proj": (2 * h, h),
            **block_specs(cfg, "mtp_", False), "mtp_norm": (h,)}


def tensor_specs(cfg):
    """``{name: shape}`` of every tensor, in the order they are made."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": (v, h)}
    for p, dense in blocks(cfg):
        out.update(block_specs(cfg, p, dense))
    out["norm"] = (h,)
    out["head"] = (h, v)
    out.update(module_specs(cfg))
    return out


def _normal(seed, name, shape):
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.normal(key, shape, jnp.float32)


def expert_tensor(seed, cfg, name, e):
    """Expert ``e``'s matrix of the stack ``name`` (``..w_gate``,
    ``..w_up``, ``..w_down``), keyed by its own number."""
    shape = tensor_specs(cfg)[name][1:]
    return _normal(seed, f"{name}.{e}", shape) / math.sqrt(shape[-2])


def tensor(seed, cfg, name, shape=None):
    """One tensor from the seed and its own name, float32. Norm gains are
    1 + normal(0, 0.02); the embedding normal(0, 1), so the residual stream
    starts at unit scale; the router's selection bias normal(0, 0.1)
    (nonzero: a program that weighs by the biased score fails); a mixer's
    ``alpha`` is (1, 1, 0.5) x (1 + normal(0, 0.1)) and its ``bias``
    normal(0, 0.5), an attention mixer's with 33 and 31 added to the first
    two entries of ``H_res~``'s first row (``HC_LARGE``); every matrix
    (``phi`` among them) normal(0, 1 / fan_in). The experts' stacks are
    made an expert at a time, each keyed by its own number."""
    shape = tuple(shape or tensor_specs(cfg)[name])
    if name.endswith("norm"):
        return 1.0 + 0.02 * _normal(seed, name, shape)
    if name == "embed":
        return _normal(seed, name, shape)
    if name.endswith("router_bias"):
        return BIAS_STD * _normal(seed, name, shape)
    if name.endswith("hc_alpha"):
        return jnp.asarray(HC_ALPHA) * (1.0 + 0.1 * _normal(seed, name,
                                                           shape))
    if name.endswith("hc_bias"):
        b = HC_BIAS_STD * _normal(seed, name, shape)
        if name.endswith("attn_hc_bias"):
            n = cfg["hc_mult"]
            b = b.at[2 * n:2 * n + 2].add(jnp.asarray(HC_LARGE))
        return b
    if name.endswith(("w_gate", "w_up", "w_down")):
        return jnp.stack([expert_tensor(seed, cfg, name, e)
                          for e in range(shape[0])])
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


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary(cfg):
    """``(inverse frequencies (rope / 2,), factor on cos and sin, softmax
    scale)`` of the configuration: YaRN where ``rope_scaling`` is given,
    else ``theta^(-2i/d)``, 1 and ``(nope + rope)^-0.5``."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    scale = 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + d)
    i = np.arange(0, d, 2, dtype=np.float32)
    extra = 1.0 / (np.float32(theta) ** (i / np.float32(d)))
    y = cfg.get("rope_scaling")
    if not y:
        return tuple(float(f) for f in extra), 1.0, scale

    def pair_of(turns):     # the pair that turns ``turns`` times in the
        # original context (``yarn_find_correction_dim``)
        return d * math.log(y["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(y["beta_fast"])), 0)
    high = min(math.ceil(pair_of(y["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                   # 1: the original frequency stays
    inv = (extra / y["factor"]) * (1 - keep) + extra * keep
    all_dim = _mscale(y["factor"], y["mscale_all_dim"])
    if y["mscale_all_dim"]:
        scale = scale * all_dim * all_dim
    return tuple(float(f) for f in inv.astype(np.float32)), \
        _mscale(y["factor"], y["mscale"]) / all_dim, scale


def rope(x, pos, inv, factor):
    """Rotary embedding of ``x (S, ..., D)`` at positions ``pos (S,)``,
    interleaved: dimension ``2i`` pairs with ``2i + 1`` and turns by ``pos x
    inv[i]``; cos and sin times ``factor``."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1) \
        .reshape(x.shape)


# ------------------------------------------------------------ the mixers
@functools.partial(jax.jit, static_argnames=("hc", "quant"))
def _pre(X, phi, alpha, bias, hc, quant):
    """A mixer's first half on ``X (S, n, C)``: ``(u (S, C), H_post (S, n),
    H_res (S, n, n))``. ``hc`` is ``(iters, eps, clamp_min, clamp_max)``."""
    iters, eps, lo, hi = hc
    S, n, C = X.shape
    v = X.reshape(S, n * C)
    x = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
    t = _mm("sk,kc->sc", x, phi, quant)
    h_pre = jax.nn.sigmoid(alpha[0] * t[:, :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * t[:, n:2 * n] + bias[n:2 * n])
    M = jnp.exp(jnp.clip((alpha[2] * t[:, 2 * n:] + bias[2 * n:])
                         .reshape(S, n, n), lo, hi))
    for _ in range(iters):
        M = M / (jnp.sum(M, -1, keepdims=True) + eps)       # rows
        M = M / (jnp.sum(M, -2, keepdims=True) + eps)       # columns
    return jnp.einsum("sn,snc->sc", h_pre, X), h_post, M


@jax.jit
def _post(X, h_post, h_res, y):
    """A mixer's second half: ``X' = H_res @ X + outer(H_post, y)``."""
    return jnp.einsum("sij,sjc->sic", h_res, X) \
        + h_post[:, :, None] * y[:, None, :]


def _hc(cfg):
    return (int(cfg["hc_sinkhorn_iters"]), float(cfg["hc_eps"]),
            float(cfg["mhc_h_res_clamp_min"]),
            float(cfg["mhc_h_res_clamp_max"]))


def _mixed(w, name, X, cfg, quant, F):
    """Sublayer ``F`` (which norms its own input) wrapped by mixer
    ``name``."""
    u, h_post, h_res = _pre(X, w[name + "_hc_phi"], w[name + "_hc_alpha"],
                            w[name + "_hc_bias"], hc=_hc(cfg), quant=quant)
    return _post(X, h_post, h_res, F(u))


@functools.partial(jax.jit, static_argnames=("scale", "quant"))
def _attend_block(q, t, k, v, scale, quant):
    """One block of queries ``q (T, heads, 192)`` at positions ``t``
    against every head's keys ``k (S, heads, 192)`` and values ``v (S,
    heads, 128)``: dense causal softmax in float32, the scores times
    ``scale``."""
    score = _mm("thd,shd->hts", q, k, quant) * scale
    seen = jnp.arange(k.shape[0])[None, :] <= t[:, None]
    prob = jax.nn.softmax(jnp.where(seen[None], score, NEG), -1)
    if quant == "fp8":
        prob = _fp8(prob)
    return _mm("hts,shd->thd", prob, v, quant).reshape(q.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("dims", "inv", "factor",
                                               "quant"))
def _project(x, g, wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, dims, inv,
             factor, quant):
    """``(q, k, v)`` by head of the normed ``x``: ``c_q = norm(u W_qa)``, a
    head's query ``[q_nope; rope(q_rope)] = c_q W_qb``; ``[c; k_r] = u
    W_kva``, ``c = norm(c)``, ONE rotary key ``rope(k_r)`` for all heads; a
    head's key ``[c W_kvb^K; k_r]`` and value ``c W_kvb^V``."""
    nh, rkv, dn, dr, dv = dims
    S = x.shape[0]
    u, pos = rms_norm(x, g), jnp.arange(S)
    cq = rms_norm(_mm("sh,hr->sr", u, wq_a, quant), q_norm)
    q = _mm("sr,rd->sd", cq, wq_b, quant).reshape(S, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn],
                         rope(q[..., dn:], pos, inv, factor)], -1)
    ckr = _mm("sh,hr->sr", u, wkv_a, quant)
    c = rms_norm(ckr[:, :rkv], kv_norm)
    kr = rope(ckr[:, rkv:], pos, inv, factor)
    kv = _mm("sr,rd->sd", c, wkv_b, quant).reshape(S, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(kr[:, None], (S, nh, dr))], -1)
    return q, k, kv[..., dn:]


def _attention(w, p, u, cfg, quant):
    """``attention(norm(u))``: latent attention, expanded, a block of
    queries at a time, through the output projection."""
    dims = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])
    inv, factor, scale = rotary(cfg)
    q, k, v = _project(u, w[p + "attn_norm"], w[p + "wq_a"], w[p + "q_norm"],
                       w[p + "wq_b"], w[p + "wkv_a"], w[p + "kv_norm"],
                       w[p + "wkv_b"], dims=dims, inv=inv, factor=factor,
                       quant=quant)
    pos = jnp.arange(u.shape[0])
    out = [_attend_block(q[q0:q0 + Q_BLOCK], pos[q0:q0 + Q_BLOCK], k, v,
                         scale=scale, quant=quant)
           for q0 in range(0, u.shape[0], Q_BLOCK)]
    del q, k, v
    return _out(jnp.concatenate(out, 0), w[p + "wo"], quant=quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _out(heads, wo, quant):
    return _mm("sd,dh->sh", heads, wo, quant)


@jax.jit
def _normed(u, g):
    return rms_norm(u, g)


@functools.partial(jax.jit, static_argnames=("k", "scaling", "quant"))
def _route(u, router, bias, k, scaling, quant):
    s = jax.nn.sigmoid(_mm("sh,he->se", u, router, quant))
    idx = jax.lax.top_k(s + bias, k)[1]
    top = jnp.take_along_axis(s, idx, -1)
    return idx, scaling * top / top.sum(-1, keepdims=True), s


def route(w, p, u, cfg, quant):
    """``(experts (S, k), weights (S, k), scores (S, E))``: ``s = sigmoid(u
    W_g)`` over all the router's outputs in float32; the ``k`` largest of
    ``s + b`` are chosen (the bias selects and does not weigh; one group,
    so no grouping); ``a = scaling x s[chosen] / sum s[chosen]``."""
    return _route(u, w[p + "router"], w[p + "router_bias"],
                  k=cfg["num_experts_per_tok"],
                  scaling=float(cfg["routed_scaling_factor"]), quant=quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _swiglu(x, w_gate, w_up, w_down, quant):
    g = _mm("th,hf->tf", x, w_gate, quant)
    up = _mm("th,hf->tf", x, w_up, quant)
    return _mm("tf,fh->th", jax.nn.silu(g) * up, w_down, quant)


def _experts(seed, w, p, u, n_real, cfg, quant, tap):
    """``sum_e a_e SwiGLU_e(u)`` over each token's ``k`` experts, plus the
    shared expert, which every token takes once. An expert's matrices are
    made when its turn comes."""
    idx, a, _ = route(w, p, u, cfg, quant)
    idx_h, a_h = np.asarray(idx)[:n_real], np.asarray(a)[:n_real]
    if tap is not None:
        tap[p + "experts"], tap[p + "weights"] = idx_h, a_h
    out = _swiglu(u, w[p + "shared_gate"], w[p + "shared_up"],
                  w[p + "shared_down"], quant=quant)
    for e in np.unique(idx_h):
        rows, col = np.nonzero(idx_h == e)
        weight = a_h[rows, col]
        pad = -len(rows) % EXPERT_PAD    # few distinct shapes to compile
        rows = np.concatenate([rows, np.zeros(pad, rows.dtype)])
        weight = np.concatenate([weight, np.zeros(pad, weight.dtype)])
        out = _add_expert(
            out, u, jnp.asarray(rows), jnp.asarray(weight),
            *(expert_tensor(seed, cfg, p + m, int(e))
              for m in ("w_gate", "w_up", "w_down")), quant=quant)
    return out


@functools.partial(jax.jit, static_argnames=("quant",))
def _add_expert(out, u, rows, weight, w_gate, w_up, w_down, quant):
    """One expert on its own tokens, weighted, added where they sit."""
    y = _swiglu(u[rows], w_gate, w_up, w_down, quant=quant)
    return out.at[rows].add(weight[:, None] * y)


def _block(seed, cfg, p, dense, X, n_real, quant, tap):
    """One block on the stream ``X (S, n, C)``: attention under its mixer,
    then the feed-forward under its own."""
    w = {n: tensor(seed, cfg, n, s)
         for n, s in block_specs(cfg, p, dense).items()
         if not n.endswith(("w_gate", "w_up", "w_down"))}
    X = _mixed(w, p + "attn", X, cfg, quant,
               lambda u: _attention(w, p, u, cfg, quant))

    def feed(u):
        u = _normed(u, w[p + "mlp_norm"])
        if dense:
            return _swiglu(u, w[p + "dense_gate"], w[p + "dense_up"],
                           w[p + "dense_down"], quant=quant)
        return _experts(seed, w, p, u, n_real, cfg, quant, tap)

    return _mixed(w, p + "mlp", X, cfg, quant, feed)


def _repeat(x, cfg):
    """The stream's start: the one hidden state a token has, ``n`` times."""
    return jnp.repeat(x[:, None, :], cfg["hc_mult"], 1)


def forward(seed, cfg, tokens, quant=None, want=None, want_draft=None,
            tap=None, pad_to=None):
    """``(logits (len(want), vocab), module's logits (len(want_draft),
    vocab))`` of one sequence ``tokens (S,)``: row ``j`` of the first scores
    the token after ``tokens[:want[j] + 1]``; row ``j`` of the second is
    the module's prediction of token ``i + 2`` at ``i = want_draft[j]``,
    from the hidden state at ``i`` (the last layer's output BEFORE the
    final norm) and the true token ``i + 1``. ``want`` None is every
    position, ``want_draft`` None none. ``tap``, a dict, receives what a
    test compares (routing)."""
    tokens = np.asarray(tokens, np.int32)
    n_real = len(tokens)
    # whole query blocks; a short sequence pads to a multiple of 16 and the
    # check's sequences all to ``pad_to``, so that few distinct shapes are
    # compiled. Attention is causal and padding tokens are routed to no
    # expert, so what lies past the sequence changes nothing before it
    pad = -n_real % (Q_BLOCK if n_real >= Q_BLOCK else 16)
    if pad_to is not None and n_real <= pad_to:
        pad = pad_to - n_real
    tokens = jnp.asarray(np.concatenate([tokens, np.zeros(pad, np.int32)]))
    with jax.default_matmul_precision("highest"):
        emb = tensor(seed, cfg, "embed")[tokens]
        X = _repeat(emb, cfg)
        for p, dense in blocks(cfg):
            X = _block(seed, cfg, p, dense, X, n_real, quant, tap)
        x = jnp.sum(X, 1)               # the stream's end: summed
        del X
        want = np.arange(n_real) if want is None else np.asarray(want)
        head = tensor(seed, cfg, "head")
        logits = _head(x, _padded(want), tensor(seed, cfg, "norm"), head,
                       quant=quant)[:len(want)]
        if want_draft is None:
            return logits, None
        # the module: h' = W_eh [norm_e(Emb(t_{i+1})); norm_h(h_i)] with
        # h_i the SUMMED stream, one block of the expert-layer kind on a
        # stream repeated from h' under its own two mixers, summed at its
        # end, its own final norm, the model's embedding and head. Position S - 1 has no next
        # token: its row takes token 0 and nobody reads it
        xm = _module_in(emb, x, tensor(seed, cfg, "mtp_enorm"),
                        tensor(seed, cfg, "mtp_hnorm"),
                        tensor(seed, cfg, "mtp_eh_proj"), quant=quant)
        xm = jnp.sum(_block(seed, cfg, "mtp_", False, _repeat(xm, cfg),
                            n_real - 1, quant, tap), 1)
        return logits, _head(xm, _padded(want_draft),
                             tensor(seed, cfg, "mtp_norm"), head,
                             quant=quant)[:len(want_draft)]


def _padded(want, multiple=256):
    """The wanted positions, the last one repeated up to a multiple: one
    program of the head for replies of many lengths."""
    want = np.asarray(want, np.int32)
    return jnp.asarray(np.concatenate(
        [want, np.full(-len(want) % multiple, want[-1], np.int32)]))


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(x, want, g, head, quant):
    return _mm("sh,hv->sv", rms_norm(x[want], g), head, quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _module_in(emb, x, enorm, hnorm, eh_proj, quant):
    nxt = jnp.concatenate([emb[1:], emb[:1]], 0)
    return _mm("sh,hd->sd", jnp.concatenate([
        rms_norm(nxt, enorm), rms_norm(x, hnorm)], -1), eh_proj, quant)


# --------------------------------------------------------------- the check
def _below_best(logits, tokens):
    got = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], -1)[:, 0]
    return np.asarray(logits.max(-1) - got)


def served_gaps(seed, cfg, prompt, served, drafts, quant=None, pad_to=None):
    """``(served token gaps (len(served),), draft gaps (len(drafts),))``.
    The sequence is the prompt followed by the served tokens; served token
    ``j`` is scored at position ``len(prompt) - 1 + j`` by how far its logit
    lies below the reference's best there. ``drafts`` holds ``(j, token)``:
    the module's proposal for served token ``j`` (``j >= 1``), which it made
    from the hidden state at position ``len(prompt) + j - 2`` and served
    token ``j - 1``; it is scored the same way against the reference
    module's logits there. With ``quant`` the served tokens and the drafts
    only place the positions: the tokens the lower precision puts first
    stand in their place (the control need not decode)."""
    prompt, served = np.asarray(prompt, np.int32), np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    want = len(prompt) - 1 + np.arange(len(served))
    drafts = [(j, d) for j, d in drafts if 1 <= j < len(served)]
    at = np.array([len(prompt) + j - 2 for j, _ in drafts], np.int64)
    proposed = np.array([d for _, d in drafts], np.int32)
    ref, ref_m = forward(seed, cfg, seq, want=want,
                         want_draft=at if len(at) else None, pad_to=pad_to)
    if quant is not None:
        low, low_m = forward(seed, cfg, seq, quant=quant, want=want,
                             want_draft=at if len(at) else None,
                             pad_to=pad_to)
        served = jnp.argmax(low, -1)
        proposed = jnp.argmax(low_m, -1) if len(at) else proposed
    return _below_best(ref, served), \
        (_below_best(ref_m, proposed) if len(at) else np.zeros((0,)))


def first_latents(seed, cfg, tokens):
    """What the FIRST layer caches for ``tokens (S,)``, ``(S, rank +
    rope)``: ``[norm(c); rope(k_r)]`` of ``[c; k_r] = norm(u) W_kva`` with
    ``u`` the first mixer's input from the repeated embedding. The first
    layer's, because nothing upstream of it but the embedding, the mixer
    and one product rounds: a cache held in a lower precision than the
    configuration states stands out against it."""
    rkv = cfg["kv_lora_rank"]
    inv, factor, _ = rotary(cfg)
    with jax.default_matmul_precision("highest"):
        x = tensor(seed, cfg, "embed")[jnp.asarray(tokens, jnp.int32)]
        u, _, _ = _pre(_repeat(x, cfg), tensor(seed, cfg, "l0_attn_hc_phi"),
                       tensor(seed, cfg, "l0_attn_hc_alpha"),
                       tensor(seed, cfg, "l0_attn_hc_bias"), hc=_hc(cfg),
                       quant=None)
        u = rms_norm(u, tensor(seed, cfg, "l0_attn_norm"))
        ckr = jnp.einsum("sh,hr->sr", u, tensor(seed, cfg, "l0_wkv_a"))
        c = rms_norm(ckr[:, :rkv], tensor(seed, cfg, "l0_kv_norm"))
        kr = rope(ckr[:, rkv:], jnp.arange(len(tokens)), inv, factor)
        return np.asarray(jnp.concatenate([c, kr], -1))


def greedy(seed, cfg, prompt, n):
    """Greedy decode by full forwards (tests, tiny sizes)."""
    seq = list(np.asarray(prompt))
    for _ in range(n):
        logits, _ = forward(seed, cfg, seq, want=[len(seq) - 1])
        seq.append(int(jnp.argmax(logits[0])))
    return seq[len(prompt):]

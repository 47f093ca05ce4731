"""Plain reference of the ``joyai-llm-flash`` configuration: JoyAI-LLM-Flash's
language model (decoder only: RMSNorm, multi-head latent attention with a
low-rank query and interleaved rotary pairs, a dense SwiGLU first layer,
then SwiGLU experts behind a sigmoid router with a selection bias beside a
shared expert, an untied head) and its one multi-token-prediction module.

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision:
one teacher-forced full forward of ONE sequence, the EXPANDED attention
(every head's keys and values made from the latent, dense causal softmax),
experts by a plain loop over the experts it is given. No cache, no paging,
no absorbed product, no kernels, no batching; nothing of the program is
imported. Queries go through in blocks so that a 16k-token sequence fits; a
block of queries, a layer's projections, the router, an expert on its tokens
and the head are each one jitted function, so that the chip compiles some
twenty programs a sequence length and not every operation (the first run on
the chip spent 370 s compiling three hundred of them).

``held`` (``cfg["experts_held"]``: first and count) says which experts the
layer HOLDS: the router ranks all ``cfg["router_width"]`` outputs and keeps
its ``k`` a token, the loop runs over the held ones, and what the others
would add is left out. ``held = (0, router_width)`` is the whole layer; a
run of sixteen shares adds up to it (``tests/test_joyai_lm.py``).

Weights come from the seed TENSOR BY TENSOR, each keyed by the seed and its
own name, an expert's by the expert's own number as well, so that a share's
experts are the whole layer's. Names are the program's structural parameter
names; matrices are stored ``(in, out)``.

Departures from the description, each where it is made: the query blocks
and the padding to few shapes (``forward``); an expert's token list padded
to a multiple (``_experts``); logits at the wanted positions only.

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
Q_BLOCK = 512          # queries a block
EXPERT_PAD = 256       # an expert's token list is padded to a multiple
BIAS_STD = 0.1         # the router's selection bias: normal(0, 0.1)


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
    if dense:
        f = cfg["intermediate_size"]
        out.update({p + "dense_gate": (h, f), p + "dense_up": (h, f),
                    p + "dense_down": (f, h)})
    else:
        f, n = cfg["moe_intermediate_size"], cfg["experts_held"][1]
        sf = f * cfg["n_shared_experts"]
        out.update({p + "router": (h, cfg["router_width"]),
                    p + "router_bias": (cfg["router_width"],),
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


def tensor(seed, cfg, name, shape=None):
    """One tensor from the seed and its own name, float32. Norm gains are
    1 + normal(0, 0.02); the embedding normal(0, 1), so the residual stream
    starts at unit scale; the router's selection bias normal(0, 0.1)
    (nonzero: a program that weighs by the biased score fails); every
    matrix normal(0, 1 / fan_in). The experts' stacks are made an expert at
    a time, each keyed by its own number among the router's outputs."""
    shape = tuple(shape or tensor_specs(cfg)[name])
    if name.endswith("norm"):
        return 1.0 + 0.02 * _normal(seed, name, shape)
    if name == "embed":
        return _normal(seed, name, shape)
    if name.endswith("router_bias"):
        return BIAS_STD * _normal(seed, name, shape)
    scale = 1.0 / math.sqrt(shape[-2])
    if name.endswith(("w_gate", "w_up", "w_down")):
        first = cfg["experts_held"][0]
        return jnp.stack([_normal(seed, f"{name}.{first + e}", shape[1:])
                          for e in range(shape[0])]) * scale
    return _normal(seed, name, shape) * scale


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


def rope(x, pos, theta):
    """Rotary embedding of ``x (S, ..., D)`` at positions ``pos (S,)``,
    interleaved: dimension ``2i`` pairs with ``2i + 1`` and turns by ``pos x
    theta^(-2i / D)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # (S, half)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1) \
        .reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("quant",))
def _attend_block(q, t, k, v, quant):
    """One block of queries ``q (T, heads, 192)`` at positions ``t``
    against every head's keys ``k (S, heads, 192)`` and values ``v (S,
    heads, 128)``: dense causal softmax in float32."""
    score = _mm("thd,shd->hts", q, k, quant) / math.sqrt(q.shape[-1])
    seen = jnp.arange(k.shape[0])[None, :] <= t[:, None]
    prob = jax.nn.softmax(jnp.where(seen[None], score, NEG), -1)
    if quant == "fp8":
        prob = _fp8(prob)
    return _mm("hts,shd->thd", prob, v, quant).reshape(q.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("dims", "theta", "quant"))
def _project(x, g, wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, dims, theta,
             quant):
    """``(q, k, v)`` by head of the normed ``x``: ``c_q = norm(u W_qa)``, a
    head's query ``[q_nope; rope(q_rope)] = c_q W_qb``; ``[c; k_r] = u
    W_kva``, ``c = norm(c)``, ONE rotary key ``rope(k_r)`` for all heads; a
    head's key ``[c W_kvb^K; k_r]`` and value ``c W_kvb^V``."""
    nh, rkv, dn, dr, dv = dims
    S = x.shape[0]
    u, pos = rms_norm(x, g), jnp.arange(S)
    cq = rms_norm(_mm("sh,hr->sr", u, wq_a, quant), q_norm)
    q = _mm("sr,rd->sd", cq, wq_b, quant).reshape(S, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, theta)], -1)
    ckr = _mm("sh,hr->sr", u, wkv_a, quant)
    c = rms_norm(ckr[:, :rkv], kv_norm)
    kr = rope(ckr[:, rkv:], pos, theta)
    kv = _mm("sr,rd->sd", c, wkv_b, quant).reshape(S, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(kr[:, None], (S, nh, dr))], -1)
    return q, k, kv[..., dn:]


@functools.partial(jax.jit, static_argnames=("quant",))
def _attended(x, heads, wo, g, quant):
    """``(x + heads W_o, its norm for the feed-forward)``."""
    x = x + _mm("sd,dh->sh", heads, wo, quant)
    return x, rms_norm(x, g)


def _attention(w, p, x, cfg, quant):
    """``(x + attention(norm(x)), norm of that)``: latent attention,
    expanded, a block of queries at a time."""
    dims = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])
    q, k, v = _project(x, w[p + "attn_norm"], w[p + "wq_a"], w[p + "q_norm"],
                       w[p + "wq_b"], w[p + "wkv_a"], w[p + "kv_norm"],
                       w[p + "wkv_b"], dims=dims,
                       theta=float(cfg["rope_theta"]), quant=quant)
    pos = jnp.arange(x.shape[0])
    out = [_attend_block(q[q0:q0 + Q_BLOCK], pos[q0:q0 + Q_BLOCK], k, v,
                         quant=quant)
           for q0 in range(0, x.shape[0], Q_BLOCK)]
    return _attended(x, jnp.concatenate(out, 0), w[p + "wo"],
                     w[p + "mlp_norm"], quant=quant)


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


def _experts(w, p, u, n_real, cfg, quant, tap):
    """``sum_e a_e SwiGLU_e(u)`` over the HELD experts among each token's
    ``k``, plus the shared expert, which every token takes once."""
    idx, a, _ = route(w, p, u, cfg, quant)
    idx_h, a_h = np.asarray(idx)[:n_real], np.asarray(a)[:n_real]
    first, n = cfg["experts_held"]
    if tap is not None:
        tap[p + "experts"], tap[p + "weights"] = idx_h, a_h
    out = _swiglu(u, w[p + "shared_gate"], w[p + "shared_up"],
                  w[p + "shared_down"], quant=quant)
    for e in np.unique(idx_h):
        if not first <= e < first + n:
            continue                    # held by another chip: left out
        rows, col = np.nonzero(idx_h == e)
        weight = a_h[rows, col]
        pad = -len(rows) % EXPERT_PAD    # few distinct shapes to compile
        rows = np.concatenate([rows, np.zeros(pad, rows.dtype)])
        weight = np.concatenate([weight, np.zeros(pad, weight.dtype)])
        out = _add_expert(out, u, jnp.asarray(rows), jnp.asarray(weight),
                          w[p + "w_gate"][e - first], w[p + "w_up"][e - first],
                          w[p + "w_down"][e - first], quant=quant)
    return out


@functools.partial(jax.jit, static_argnames=("quant",))
def _add_expert(out, u, rows, weight, w_gate, w_up, w_down, quant):
    """One expert on its own tokens, weighted, added where they sit."""
    y = _swiglu(u[rows], w_gate, w_up, w_down, quant=quant)
    return out.at[rows].add(weight[:, None] * y)


def _block(seed, cfg, p, dense, x, n_real, quant, tap):
    w = {n: tensor(seed, cfg, n, s)
         for n, s in block_specs(cfg, p, dense).items()}
    x, u = _attention(w, p, x, cfg, quant)
    if dense:
        return x + _swiglu(u, w[p + "dense_gate"], w[p + "dense_up"],
                           w[p + "dense_down"], quant=quant)
    return x + _experts(w, p, u, n_real, cfg, quant, tap)


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
        x = emb
        for p, dense in blocks(cfg):
            x = _block(seed, cfg, p, dense, x, n_real, quant, tap)
        want = np.arange(n_real) if want is None else np.asarray(want)
        head = tensor(seed, cfg, "head")
        logits = _head(x, _padded(want), tensor(seed, cfg, "norm"), head,
                       quant=quant)[:len(want)]
        if want_draft is None:
            return logits, None
        # the module: h' = W_eh [norm_e(Emb(t_{i+1})); norm_h(h_i)], one
        # block of the expert-layer kind over its own inputs, its own final
        # norm, the model's embedding and head. Position S - 1 has no next
        # token: its row takes token 0 and nobody reads it
        xm = _module_in(emb, x, tensor(seed, cfg, "mtp_enorm"),
                        tensor(seed, cfg, "mtp_hnorm"),
                        tensor(seed, cfg, "mtp_eh_proj"), quant=quant)
        xm = _block(seed, cfg, "mtp_", False, xm, n_real - 1, quant, tap)
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
    rope)``: ``[norm(c); rope(k_r)]`` of ``[c; k_r] = norm(Emb(t)) W_kva``.
    The first layer's, because nothing upstream of it but the embedding
    and one product rounds: a cache held in a lower precision than the
    configuration states stands out against it."""
    rkv = cfg["kv_lora_rank"]
    with jax.default_matmul_precision("highest"):
        x = tensor(seed, cfg, "embed")[jnp.asarray(tokens, jnp.int32)]
        u = rms_norm(x, tensor(seed, cfg, "l0_attn_norm"))
        ckr = jnp.einsum("sh,hr->sr", u, tensor(seed, cfg, "l0_wkv_a"))
        c = rms_norm(ckr[:, :rkv], tensor(seed, cfg, "l0_kv_norm"))
        kr = rope(ckr[:, rkv:], jnp.arange(len(tokens)), cfg["rope_theta"])
        return np.asarray(jnp.concatenate([c, kr], -1))


def greedy(seed, cfg, prompt, n):
    """Greedy decode by full forwards (tests, tiny sizes)."""
    seq = list(np.asarray(prompt))
    for _ in range(n):
        logits, _ = forward(seed, cfg, seq, want=[len(seq) - 1])
        seq.append(int(jnp.argmax(logits[0])))
    return seq[len(prompt):]

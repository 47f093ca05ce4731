"""Plain reference of the ``keye-vl2-30b-a3b`` configuration: the language
model of Keye-VL-2.0-30B-A3B (decoder only: RMSNorm, grouped-query
attention with per-head q/k norm and three-component rotary embedding, a
learned indexer that selects ``topk`` cached positions a query, SwiGLU
experts behind a softmax router, untied head).

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision:
one teacher-forced full forward of ONE sequence, dense causal attention
masked to the selected set, experts by a plain loop over the experts that
have tokens. No cache, no paging, no kernels; nothing of the program is
imported. Queries go through in blocks so that a 16k-token sequence fits;
a block, and an expert on its tokens, is each one jitted function, so that
the chip compiles a few programs and not every operation for every length.

Weights come from the seed TENSOR BY TENSOR, each keyed by the seed and its
own name (``tensor``): 4.37 G parameters in float32 fit neither the chip
nor one jitted call, so a layer's tensors are made when the forward reaches
that layer and dropped after it. Names are the program's structural
parameter names; matrices are stored ``(in, out)``.

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
Q_BLOCK = 512          # queries a block (the published kernel's q_chunk_size)
EXPERT_PAD = 256       # an expert's token list is padded to a multiple


# ---------------------------------------------------------------- weights
def layer_specs(cfg, i):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    p = f"l{i}_"
    return {p + "attn_norm": (h,), p + "wq": (h, nq * d),
            p + "wk": (h, nkv * d), p + "wv": (h, nkv * d),
            p + "q_norm": (d,), p + "k_norm": (d,), p + "wo": (nq * d, h),
            p + "idx_wq": (h, ni * di), p + "idx_wk": (h, di),
            p + "idx_k_norm": (di,), p + "idx_ww": (h, ni),
            p + "moe_norm": (h,), p + "router": (h, e),
            p + "w_gate": (e, h, f), p + "w_up": (e, h, f),
            p + "w_down": (e, f, h)}


def tensor_specs(cfg):
    """``{name: shape}`` of every tensor, in the order they are made."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_specs(cfg, i))
    out["norm"] = (h,)
    out["head"] = (h, v)
    return out


def _is_gain(name):
    return name.endswith("norm")


def tensor(seed, cfg, name, shape=None):
    """One tensor from the seed and its own name, float32. Norm gains are
    1 + normal(0, 0.02); the embedding normal(0, 1), so the residual stream
    starts at unit scale; every matrix normal(0, 1 / fan_in), so a product
    of a unit-scale input keeps unit scale and every sub-layer weighs in."""
    shape = tuple(shape or tensor_specs(cfg)[name])
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    w = jax.random.normal(key, shape, jnp.float32)
    if _is_gain(name):
        return 1.0 + 0.02 * w
    if name == "embed":
        return w
    return w * (1.0 / math.sqrt(shape[-2]))


def init_params(seed, cfg):
    """Every tensor in turn as ``(name, float32 array)``, made when asked
    for: the caller casts and hands over each one and drops it before the
    next is made, since all of them together fit nowhere."""
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


def rope(x, pos3, sections, theta):
    """Rotary embedding of ``x`` (S, heads, D) at positions ``pos3`` (S, 3).
    Half-dimension D/2 in three sections; frequency ``i`` takes its angle
    from the position component of its section; dimension ``d`` pairs with
    ``d + D/2``."""
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    comp = jnp.asarray(np.repeat(np.arange(3), sections))      # (half,)
    ang = pos3.astype(jnp.float32)[:, comp] * inv[None, :]     # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def select(scores, t, topk):
    """The selected set of each query, as a mask (Q, S). ``scores`` (Q, S)
    is -inf where ``s > t``. Every ``s <= t`` while ``t + 1 <= topk``, else
    the ``topk`` largest, ties to the lower position: everything above the
    ``topk``-th largest value of the row (by a sort of the row), and of the
    positions that equal it the lowest, as many as there is room for."""
    S = scores.shape[1]
    causal = jnp.arange(S)[None, :] <= t[:, None]
    if S <= topk:
        return causal
    kth = jnp.sort(scores, axis=-1)[:, S - topk][:, None]
    above = scores > kth
    tie = (scores == kth) & causal
    room = topk - above.sum(-1, keepdims=True)
    return causal & (above | (tie & (jnp.cumsum(tie, -1) <= room)))


@functools.partial(jax.jit, static_argnames=("topk", "quant"))
def _attend_block(qi, wi, q, t, ki, k, v, topk, quant):
    """One block of queries (positions ``t``) against the whole sequence:
    index scores, the selected sets, dense attention masked to them.
    Jitted so that a block is one program, compiled once for a sequence
    length; the equations are the plain ones."""
    S, (ni, di), d = ki.shape[0], qi.shape[1:], q.shape[-1]
    nkv = k.shape[1]
    hit = jax.nn.relu(_mm("tjd,sd->tjs", qi, ki, quant))
    index = jnp.einsum("tjs,tj->ts", hit, wi) / math.sqrt(di * ni)
    index = jnp.where(jnp.arange(S)[None, :] <= t[:, None], index, NEG)
    sel = select(index, t, topk)
    qb = q.reshape(q.shape[0], nkv, q.shape[1] // nkv, d)
    score = _mm("tgid,sgd->gits", qb, k, quant) / math.sqrt(d)
    prob = jax.nn.softmax(jnp.where(sel[None, None], score, NEG), -1)
    if quant == "fp8":
        prob = _fp8(prob)
    o = _mm("gits,sgd->tgid", prob, v, quant)
    return o.reshape(q.shape[0], -1), sel, index


def _attention(w, p, u, pos3, cfg, quant, tap):
    S = u.shape[0]
    d = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    ni, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    theta = cfg["rope_theta"]
    sec = list(cfg["rope_scaling"]["mrope_section"])
    q = _mm("sh,hd->sd", u, w[p + "wq"], quant).reshape(S, nq, d)
    k = _mm("sh,hd->sd", u, w[p + "wk"], quant).reshape(S, nkv, d)
    v = _mm("sh,hd->sd", u, w[p + "wv"], quant).reshape(S, nkv, d)
    q = rope(rms_norm(q, w[p + "q_norm"]), pos3, sec, theta)
    k = rope(rms_norm(k, w[p + "k_norm"]), pos3, sec, theta)
    isec = [s // 2 for s in sec]
    qi = rope(_mm("sh,hd->sd", u, w[p + "idx_wq"], quant).reshape(S, ni, di),
              pos3, isec, theta)
    ki = rms_norm(_mm("sh,hd->sd", u, w[p + "idx_wk"], quant),
                  w[p + "idx_k_norm"])
    ki = rope(ki[:, None, :], pos3, isec, theta)[:, 0]
    wi = _mm("sh,hj->sj", u, w[p + "idx_ww"], quant)
    out = []
    for q0 in range(0, S, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, S)
        o, sel, index = _attend_block(
            qi[q0:q1], wi[q0:q1], q[q0:q1], jnp.arange(q0, q1), ki, k, v,
            topk=topk, quant=quant)
        if tap is not None:
            tap.setdefault(p + "selected", []).append(np.asarray(sel))
            if S > topk:
                top = jax.lax.top_k(index, topk + 1)[0]
                tap.setdefault(p + "select_margin", []).append(
                    np.asarray(top[:, topk - 1] - top[:, topk]))
        out.append(o)
    return _mm("sd,dh->sh", jnp.concatenate(out, 0), w[p + "wo"], quant)


def route(w, p, u, cfg, quant):
    """``(experts (S, k), weights (S, k))``: softmax over all experts in
    float32, the ``k`` largest, their probabilities renormalised."""
    k = cfg["num_experts_per_tok"]
    prob = jax.nn.softmax(_mm("sh,he->se", u, w[p + "router"], quant), -1)
    top, idx = jax.lax.top_k(prob, k)
    return idx, top / top.sum(-1, keepdims=True), prob


def _experts(w, p, u, n_real, cfg, quant, tap):
    idx, a, prob = route(w, p, u, cfg, quant)
    idx_h, a_h = np.asarray(idx)[:n_real], np.asarray(a)[:n_real]
    if tap is not None:
        tap[p + "experts"], tap[p + "weights"] = idx_h, a_h
        tap[p + "counts"] = np.bincount(idx_h.ravel(),
                                        minlength=cfg["num_experts"])
        top = np.asarray(jax.lax.top_k(prob, a.shape[1] + 1)[0])[:n_real]
        tap[p + "router_margin"] = (top[:, -2] - top[:, -1]) / top[:, -2]
    out = jnp.zeros_like(u)
    for e in np.unique(idx_h):
        rows, col = np.nonzero(idx_h == e)
        weight = a_h[rows, col]
        pad = -len(rows) % EXPERT_PAD    # few distinct shapes to compile
        rows = np.concatenate([rows, np.zeros(pad, rows.dtype)])
        weight = np.concatenate([weight, np.zeros(pad, weight.dtype)])
        y = _expert(u[rows], w[p + "w_gate"][e], w[p + "w_up"][e],
                    w[p + "w_down"][e], jnp.asarray(weight), quant=quant)
        out = out.at[rows].add(y)
    return out


@functools.partial(jax.jit, static_argnames=("quant",))
def _expert(x, w_gate, w_up, w_down, weight, quant):
    """One expert on its own tokens, weighted: one program a row count."""
    g = _mm("th,hf->tf", x, w_gate, quant)
    up = _mm("th,hf->tf", x, w_up, quant)
    y = _mm("tf,fh->th", jax.nn.silu(g) * up, w_down, quant)
    return weight[:, None] * y


def forward(seed, cfg, tokens, positions=None, quant=None, want=None,
            tap=None, pad_to=None):
    """Logits (len(want), vocab) of one sequence ``tokens`` (S,) at the
    positions ``want`` (all of them when None): row ``j`` scores the token
    after ``tokens[:want[j] + 1]``. ``positions`` (S, 3) are the rotary
    components (t, h, w); for text the three are the index. ``tap``, a dict,
    receives what a test compares: selected sets, routing, counts."""
    tokens = np.asarray(tokens, np.int32)
    n_real = len(tokens)
    # whole query blocks; a short sequence pads to a multiple of 16 and the
    # check's sequences all to ``pad_to``, so that few distinct shapes are
    # compiled. Attention is causal and padding tokens are routed to no
    # expert, so what lies past the sequence changes nothing before it
    pad = -n_real % (Q_BLOCK if n_real >= Q_BLOCK else 16)
    if pad_to is not None and n_real <= pad_to:
        pad = pad_to - n_real
    tokens = np.concatenate([tokens, np.zeros(pad, np.int32)])
    S = len(tokens)
    if positions is None:
        pos3 = jnp.broadcast_to(jnp.arange(S)[:, None], (S, 3))
    else:
        pos3 = jnp.concatenate([jnp.asarray(positions, jnp.int32),
                                jnp.zeros((pad, 3), jnp.int32)], 0)
    with jax.default_matmul_precision("highest"):
        x = tensor(seed, cfg, "embed")[jnp.asarray(tokens)]
        for i in range(cfg["num_hidden_layers"]):
            p = f"l{i}_"
            w = {n: tensor(seed, cfg, n, s)
                 for n, s in layer_specs(cfg, i).items()}
            x = x + _attention(w, p, rms_norm(x, w[p + "attn_norm"]), pos3,
                               cfg, quant, tap)
            x = x + _experts(w, p, rms_norm(x, w[p + "moe_norm"]), n_real,
                             cfg, quant, tap)
            del w
        want = np.arange(n_real) if want is None else np.asarray(want)
        y = rms_norm(x[jnp.asarray(want)], tensor(seed, cfg, "norm"))
        return _mm("sh,hv->sv", y, tensor(seed, cfg, "head"), quant)


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

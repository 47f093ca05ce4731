"""Plain reference of the ``bert-base`` configuration: the encoder of
Devlin et al. 2018 (post-LN, learned positions, exact GELU), cross-entropy
against the tied word embedding on every position, AdamW. Straightforward
``jax.numpy`` in float32 with ``highest`` matmul precision: no kernels, no
scan, no mixed precision. It imports nothing of the program and makes its
own weights from the seed.

Departures from the paper, as the configuration's ``assumed`` lists them:
no segment embedding is added (one segment), the pooler is not part of the
loss, and the loss is taken on all positions, not 20 masked ones plus NSP.

Parameters are a flat dict keyed by the path of each array in the model
(``encoder.layers.<i>.attention.qkv_proj.weight`` ...). The fused QKV
projection holds, for each head, its query, key and value rows in turn.

``quant="fp8"`` is the control: the same equations with both operands of
every matrix product rounded to float8 (e4m3, scaled per tensor to its
largest magnitude), the nearest precision below the bf16 the configuration
states. The comparison has to fail it.
"""

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
INIT_STD = 0.02  # the paper's truncated normal, here a plain normal


def param_shapes(cfg):
    u, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    shapes = {
        "word_embed.weight": (v, u),
        "token_type_embed.weight": (cfg["type_vocab_size"], u),
        "position_embed.weight": (cfg["max_position_embeddings"], u),
        "embed_ln.gamma": (u,), "embed_ln.beta": (u,),
        "pooler.weight": (u, u), "pooler.bias": (u,),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"encoder.layers.{i}."
        shapes.update({
            p + "attention.qkv_proj.weight": (3 * u, u),
            p + "attention.qkv_proj.bias": (3 * u,),
            p + "attention.out_proj.weight": (u, u),
            p + "attention.out_proj.bias": (u,),
            p + "ln_attn.gamma": (u,), p + "ln_attn.beta": (u,),
            p + "ffn.ffn_1.weight": (f, u), p + "ffn.ffn_1.bias": (f,),
            p + "ffn.ffn_2.weight": (u, f), p + "ffn.ffn_2.bias": (u,),
            p + "ln_ffn.gamma": (u,), p + "ln_ffn.beta": (u,),
        })
    return shapes


def init_params(seed, cfg):
    """Every weight from the seed in ONE jitted call on the device, float32:
    matrices and embeddings normal(0, 0.02), LayerNorm scales 1, biases and
    LayerNorm shifts normal(0, 0.02) too (a zero bias would hide a bias
    gradient that is wrong)."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, n in zip(keys, names):
            w = INIT_STD * jax.random.normal(k, shapes[n], jnp.float32)
            out[n] = 1.0 + w if n.endswith("gamma") else w
        return out

    # seeds run a little over 2**31: fold the high bits in
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return make(key)


# ------------------------------------------------------------- equations
def _fp8(x):
    """Round to float8 e4m3 scaled to the tensor's largest magnitude; the
    gradient passes straight through."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant):
    """``x @ w.T`` for a weight stored (out, in)."""
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum("...i,oi->...o", x, w)


def _layer_norm(x, gamma, beta):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * gamma + beta


def encode(params, ids, cfg, quant=None):
    """Token ids (B, S) -> hidden states (B, S, hidden)."""
    B, S = ids.shape
    H = cfg["num_attention_heads"]
    D = cfg["hidden_size"] // H
    x = params["word_embed.weight"][ids] \
        + params["position_embed.weight"][:S][None]
    x = _layer_norm(x, params["embed_ln.gamma"], params["embed_ln.beta"])
    for i in range(cfg["num_hidden_layers"]):
        p = f"encoder.layers.{i}."
        qkv = _mm(x, params[p + "attention.qkv_proj.weight"], quant) \
            + params[p + "attention.qkv_proj.bias"]
        qkv = qkv.reshape(B, S, H, 3 * D)
        q, k, v = qkv[..., :D], qkv[..., D:2 * D], qkv[..., 2 * D:]
        if quant == "fp8":
            q, k, v = _fp8(q), _fp8(k), _fp8(v)
        score = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
        prob = jax.nn.softmax(score, axis=-1)
        if quant == "fp8":
            prob = _fp8(prob)
        a = jnp.einsum("bhqk,bkhd->bqhd", prob, v).reshape(B, S, H * D)
        a = _mm(a, params[p + "attention.out_proj.weight"], quant) \
            + params[p + "attention.out_proj.bias"]
        x = _layer_norm(x + a, params[p + "ln_attn.gamma"],
                        params[p + "ln_attn.beta"])
        h = _mm(x, params[p + "ffn.ffn_1.weight"], quant) \
            + params[p + "ffn.ffn_1.bias"]
        h = jax.nn.gelu(h, approximate=False)
        h = _mm(h, params[p + "ffn.ffn_2.weight"], quant) \
            + params[p + "ffn.ffn_2.bias"]
        x = _layer_norm(x + h, params[p + "ln_ffn.gamma"],
                        params[p + "ln_ffn.beta"])
    return x


def loss_sum(params, ids, labels, cfg, quant=None):
    """Sum over the positions of ``ids`` of the cross-entropy of the label
    under the logits against the tied word embedding."""
    h = encode(params, ids, cfg, quant)
    logits = _mm(h, params["word_embed.weight"], quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).sum()


# ------------------------------------------------------------ the check
def _norms(tree):
    names = sorted(tree)
    return names, jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        tree[n].astype(jnp.float32)))) for n in names])


def train_check(params, batches, cfg, hyper, rows_per_block=8, quant=None):
    """Follow the first ``len(batches)`` optimizer steps from ``params`` in
    blocks of rows. Returns the loss of each step, the first gradient and
    the norm of each of its leaves, and the norm of each leaf's change after
    the last step.

    ``hyper``: ``learning_rate``, ``beta1``, ``beta2``, ``epsilon``, ``wd``
    of AdamW with bias correction."""
    lr, b1, b2 = hyper["learning_rate"], hyper["beta1"], hyper["beta2"]
    eps, wd = hyper["epsilon"], hyper["wd"]

    with jax.default_matmul_precision("highest"):
        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def block(p, acc, loss_acc, ids, labels):
            loss, g = jax.value_and_grad(loss_sum)(p, ids, labels, cfg, quant)
            return jax.tree.map(jnp.add, acc, g), loss_acc + loss

        @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
        def adamw(p, g, m, v, t, n_tokens):
            g = jax.tree.map(lambda a: a / n_tokens, g)
            m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
            v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            step = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
            p = jax.tree.map(
                lambda w, a, b: w - step * (a / (jnp.sqrt(b) + eps) + wd * w),
                p, m, v)
            return p, m, v

        @jax.jit
        def grad_norms(g, n_tokens):
            return _norms(jax.tree.map(lambda a: a / n_tokens, g))[1]

        @jax.jit
        def mean_grad(g, n_tokens):
            return jax.tree.map(lambda a: a / n_tokens, g)

        @jax.jit
        def delta_norms(p, p0):
            return _norms(jax.tree.map(jnp.subtract, p, p0))[1]

        names = sorted(params)
        p = jax.tree.map(jnp.copy, params)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, first = [], None
        for t, (ids, labels) in enumerate(batches, start=1):
            acc = jax.tree.map(jnp.zeros_like, params)
            total = jnp.float32(0)
            n = ids.shape[0] * ids.shape[1]
            for r in range(0, ids.shape[0], rows_per_block):
                acc, total = block(p, acc, total,
                                   jnp.asarray(ids[r:r + rows_per_block]),
                                   jnp.asarray(labels[r:r + rows_per_block]))
            losses.append(float(total) / n)
            if first is None:
                first = grad_norms(acc, jnp.float32(n))
                first_grad = mean_grad(acc, jnp.float32(n))
            p, m, v = adamw(p, acc, m, v, jnp.float32(t), jnp.float32(n))
        change = delta_norms(p, params)
        return {"losses": losses, "first_grad": first_grad,
                "grad_norm": dict(zip(names, map(float, first))),
                "change_norm": dict(zip(names, map(float, change)))}

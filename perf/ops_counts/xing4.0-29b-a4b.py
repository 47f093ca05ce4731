"""Operations and bytes of the ``xing4.0-29b-a4b`` configuration's stages,
from the configuration's shapes and the window's OWN counts ((token, mixer)
pairs mixed, (query, key) pairs the chunk's attention scored, cached
positions a layer read, experts touched, as ``ContinuousBatcher.stats``
holds them), never from expected values, and the same whatever implements
the stage. Two operations to a multiply-add; weights, the stream and the
latent cache are bfloat16 (2 bytes)."""

BYTES = 2


def latent_width(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def caches(cfg):
    """Latent caches: the model's layers and the module's block."""
    return cfg["num_hidden_layers"] + 1


def mixer_params(cfg):
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * n * (n + 2) + 3 + n * (n + 2)


def attention_params(cfg):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return (h * rq + rq * nh * (dn + dr) + h * (rkv + dr)
            + rkv * nh * (dn + dv) + nh * dv * h + 2 * h + rq + rkv)


def expert_params(cfg):
    """One routed expert's three matrices (the shared expert's too, times
    ``n_shared_experts``)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_expert_block_params(cfg):
    """An expert block beside its routed experts: attention, the two
    mixers, router and bias, the shared expert."""
    e = cfg["n_routed_experts"]
    return attention_params(cfg) + 2 * mixer_params(cfg) \
        + cfg["hidden_size"] * e + e \
        + cfg["n_shared_experts"] * expert_params(cfg)


def dense_block_params(cfg):
    return attention_params(cfg) + 2 * mixer_params(cfg) \
        + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"] + cfg["hidden_size"]


def module_own_params(cfg):
    """The module's joint and its three norms."""
    h = cfg["hidden_size"]
    return 2 * h * h + 3 * h


def weight_params(cfg):
    """Every parameter the chip holds: nothing of a layer is cut."""
    dense = cfg["first_k_dense_replace"]
    blocks = cfg["num_hidden_layers"] - dense + 1        # and the module
    return (cfg["vocab_size"] * cfg["hidden_size"] + head_params(cfg)
            + dense * dense_block_params(cfg)
            + blocks * (fixed_expert_block_params(cfg)
                        + cfg["n_routed_experts"] * expert_params(cfg))
            + module_own_params(cfg))


# ------------------------------------------------------------- the mixers
def mhc_pair(cfg):
    """``(operations, least bytes)`` of ONE (token, mixer) pair: the
    projection onto the mixer's ``n + n + n x n`` columns; the stream read
    once and written once, the sublayer's output read, the next sublayer's
    input written: ``10 x C`` numbers."""
    n, h = cfg["hc_mult"], cfg["hidden_size"]
    return 2 * n * h * n * (n + 2), (2 * n + 2) * h * BYTES


def mhc_call(cfg, counts, program):
    """``(operations, bytes)`` of the mixing ONE dispatch of ``program``
    (``prefill``: a chunk; ``decode``: a step) must do, averaged over the
    window's dispatches."""
    calls = counts[program + "_calls"]
    if calls <= 0:
        return None
    pairs = counts[program + "_mhc_pairs"] / calls
    ops, moved = mhc_pair(cfg)
    return pairs * ops, pairs * moved


# -------------------------------------------------------------- the chunk
def prefill_call(cfg, counts):
    """``(operations, bytes)`` of ONE call of the chunk's expanded
    attention (one a latent cache a chunk), averaged over the window's
    calls: every scored (query, key) pair of every head through the score
    (``nope + rope``) and the value (``v``); the expanded keys and values
    and the rotary keys of the positions read, once, the queries in and
    the outputs out. The module's block scores one key less a query."""
    chunks = counts["prefill_calls"]
    if chunks <= 0:
        return None
    n = caches(cfg)
    pairs = (n * counts["prefill_scored_pairs"]
             - counts["prompt_tokens"]) / (n * chunks)
    keys = counts["prefill_latent_keys"] / chunks
    tokens = counts["prompt_tokens"] / chunks
    nh = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    ops = 2 * pairs * nh * (dn + dr + dv)
    moved = (keys * (nh * (dn + dv) + dr)
             + tokens * nh * (dn + dr + dv)) * BYTES
    return ops, moved


def chunk_parts(cfg, counts):
    """Bytes ONE chunk must move, by part, averaged over the window's
    chunks: the residual stream (``mhc_call``); every weight but the
    routed experts once (the mixers' among them; the embedding by row);
    the routed experts the MODEL's expert blocks touched (the count runs
    over the module's block too, whose feed-forward a chunk does not need:
    the module is there for its cache, and its share of the count, one
    block's of ``n - dense + 1``, is taken out); the head, read for the
    chunk's one sampled position; the latent caches (the positions read, the chunk's
    own written) and their expansion to keys and values (written once,
    read once a cache)."""
    chunks = counts["prefill_calls"]
    if chunks <= 0:
        return None
    dense = cfg["first_k_dense_replace"]
    blocks = cfg["num_hidden_layers"] - dense + 1
    n = caches(cfg)
    keys = counts["prefill_latent_keys"] / chunks
    tokens = counts["prompt_tokens"] / chunks
    nh = cfg["num_attention_heads"]
    kv = nh * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    return {
        "stream": mhc_call(cfg, counts, "prefill")[1],
        "weights": (dense * dense_block_params(cfg)
                    + blocks * fixed_expert_block_params(cfg)
                    + module_own_params(cfg)
                    + tokens * cfg["hidden_size"]) * BYTES,
        "experts": counts["prefill_experts_touched"] * (blocks - 1) / blocks
        * expert_params(cfg) * BYTES / chunks,
        "head": head_params(cfg) * BYTES,
        "latents": n * (keys + tokens) * latent_width(cfg) * BYTES,
        "expansion": n * 2 * keys * kv * BYTES}

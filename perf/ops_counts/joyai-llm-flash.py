"""Operations and bytes of the ``joyai-llm-flash`` configuration's stages,
from the configuration's shapes and the window's OWN counts (cached
positions a layer read, live rows x steps, (token, expert) pairs on held
experts, distinct held experts touched, as ``ContinuousBatcher.stats``
holds them), never from expected values. Two operations to a multiply-add;
weights and the latent cache are bfloat16 (2 bytes)."""

BYTES = 2


def latent_width(cfg):
    """Numbers a cached position holds in one layer: the latent and the
    one rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_bytes_position(cfg):
    """Bytes a cached position takes over all the latent caches: the
    model's layers and the module's block."""
    return (cfg["num_hidden_layers"] + 1) * latent_width(cfg) * BYTES


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
    """An expert block beside its routed experts: attention, router and
    bias, the shared expert."""
    return attention_params(cfg) \
        + cfg["hidden_size"] * cfg["router_width"] + cfg["router_width"] \
        + cfg["n_shared_experts"] * expert_params(cfg)


def dense_block_params(cfg):
    return attention_params(cfg) \
        + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"] + cfg["hidden_size"]


def weight_params(cfg):
    """Every parameter the chip holds."""
    dense = cfg["first_k_dense_replace"]
    blocks = cfg["num_hidden_layers"] - dense + 1        # and the module
    h = cfg["hidden_size"]
    return (cfg["vocab_size"] * h + head_params(cfg)
            + dense * dense_block_params(cfg)
            + blocks * (fixed_expert_block_params(cfg)
                        + cfg["experts_held"][1] * expert_params(cfg))
            + 2 * h * h + 3 * h)                         # the module's own


def decode_step_parts(cfg, counts):
    """Bytes ONE decode step must move, by part, averaged over the window's
    steps: the latent cache (each live row's cached positions, read once
    in each latent cache: a step's two query positions share the read),
    the routed experts it touched, every other weight of every block (the
    embedding is read by row), and the head, read for the step's two
    positions and again for the draft. ``counts``: the window's ``decode_*``
    sums."""
    steps = counts["decode_calls"]
    if steps <= 0:
        return None
    dense = cfg["first_k_dense_replace"]
    blocks = cfg["num_hidden_layers"] - dense + 1
    h = cfg["hidden_size"]
    return {
        "latent": counts["decode_latent_keys"] * latent_bytes_position(cfg)
        / steps,
        "experts": counts["decode_experts_touched"] * expert_params(cfg)
        * BYTES / steps,
        "weights": (dense * dense_block_params(cfg)
                    + blocks * fixed_expert_block_params(cfg)
                    + 2 * h * h + 3 * h) * BYTES,
        "head": 2 * head_params(cfg) * BYTES}


def decode_step_bytes(cfg, counts):
    parts = decode_step_parts(cfg, counts)
    return None if parts is None else sum(parts.values())


def decode_step_ops(cfg, counts):
    """Operations of ONE decode step, averaged over the window's steps: two
    positions a live row through every block's projections (absorbed
    attention over the row's cached positions: ``rank + rope`` for the
    score and ``rank`` for the value, for every head), the shared expert
    and the held pairs, the head for both positions and the draft."""
    steps = counts["decode_calls"]
    if steps <= 0:
        return None
    rows = counts["decode_row_steps"] / steps
    keys = counts["decode_latent_keys"] / steps
    dense = cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"]
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    absorb = 2 * cfg["num_attention_heads"] * rank \
        * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    per_position = 2 * (
        dense * dense_block_params(cfg)
        + (n - dense + 1) * fixed_expert_block_params(cfg)) \
        + (n + 1) * absorb + 2 * 2 * cfg["hidden_size"] ** 2
    attention = 2 * 2 * keys * (n + 1) * nh * (2 * rank
                                              + cfg["qk_rope_head_dim"])
    experts = 2 * counts["decode_pairs_held"] * expert_params(cfg) / steps
    head = 2 * 3 * rows * head_params(cfg)
    return 2 * rows * per_position + attention + experts + head


def latent_call(cfg, counts):
    """``(operations, bytes)`` of ONE call of the decode kernel over the
    latent pages (one a latent cache a step), averaged over the window's
    calls: the live rows' cached positions read once; two query positions
    of every head against each."""
    calls = counts["decode_calls"]
    if calls <= 0:
        return None
    keys = counts["decode_latent_keys"] / calls
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    ops = 2 * 2 * keys * cfg["num_attention_heads"] * (2 * rank + rope)
    return ops, keys * (rank + rope) * BYTES

"""Operations and bytes of the ``keye-vl2-30b-a3b`` configuration's stages,
from the configuration's shapes and the window's OWN counts (tokens routed
to each expert, distinct experts touched, keys seen and keys selected, as
``ContinuousBatcher.stats`` holds them), never from expected values. Two
operations to a multiply-add; weights and caches are bfloat16 (2 bytes)."""

BYTES = 2


def expert_bytes(cfg):
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * BYTES


def other_layer_bytes(cfg):
    """A layer's weights beside its experts: attention, indexer, router,
    the gains."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    attention = h * nq * d * 2 + h * nkv * d * 2
    indexer = h * ni * di + h * di + h * ni
    return (attention + indexer + h * cfg["num_experts"]
            + 2 * h + 2 * d + di) * BYTES


def head_bytes(cfg):
    return (cfg["hidden_size"] * cfg["vocab_size"]
            + cfg["hidden_size"]) * BYTES


def decode_step_bytes(cfg, counts):
    """Bytes ONE decode step must read, averaged over the window's steps:
    the weights of the experts it touched, the other weights of every
    layer, the head, the selected K and V, the indexer keys it scanned.
    ``counts``: the window's ``decode_*`` sums."""
    layers = cfg["num_hidden_layers"]
    steps = counts["decode_expert_layers"] / layers
    if steps <= 0:
        return None
    kv_token = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES
    ik_token = cfg["sa_config"]["indexer_head_dim"] * BYTES
    total = (counts["decode_experts_touched"] * expert_bytes(cfg)
             + counts["decode_expert_layers"] * other_layer_bytes(cfg)
             + steps * head_bytes(cfg)
             + counts["decode_keys_selected"] * kv_token
             + counts["decode_keys_seen"] * ik_token)
    return total / steps


def moe_call(cfg, counts):
    """``(operations, bytes)`` of ONE call of the grouped expert product
    in the chunk program, averaged over the window's calls: three products
    of every routed (token, expert) pair; the weights of the experts the
    call touched, and each TOKEN's row read once and its result written
    once. (The program copies a row for every pair, eight a token; the
    algorithm needs a token's row once, and counting the copies read the
    share at 101.7 % of the bandwidth roofline: my chip run, PR 27.)"""
    calls = counts["prefill_expert_layers"]
    if calls <= 0:
        return None
    pairs = float(sum(counts["prefill_expert_tokens"]))
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ops = pairs * 3 * 2 * h * f
    tokens = pairs / cfg["num_experts_per_tok"]
    moved = counts["prefill_experts_touched"] * expert_bytes(cfg) \
        + tokens * 2 * h * BYTES
    return ops / calls, moved / calls


def selected_window_call(cfg, counts):
    """``(operations, bytes)`` of ONE call of the chunk program's attention
    over the selected set, averaged over the window's calls (one a layer a
    chunk): QK and PV of every selected (query, key) pair for every query
    head; the keys and values a query can see read once, the queries read
    and the result written."""
    calls = counts["prefill_expert_layers"]
    if calls <= 0:
        return None
    nq, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    ops = counts["prefill_keys_selected"] * 2 * 2 * nq * d
    chunk = cfg["serving"]["prefill_chunk"]
    seen_keys = counts["prefill_keys_seen"] / chunk      # about: a chunk's
    moved = (seen_keys * 2 * nkv * d                     # longest query
             + counts["prompt_tokens"] * cfg["num_hidden_layers"]
             * 2 * nq * d) * BYTES
    return ops / calls, moved / calls

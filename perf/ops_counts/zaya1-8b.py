"""Operations and bytes of the ``zaya1-8b`` configuration's stages, from
the configuration's shapes and the window's OWN counts (live rows x steps,
cached positions an attention layer read, experts the grouped product
read, calls, as ``ContinuousBatcher.stats`` holds them), never from
expected values. Two operations to a multiply-add; weights, pages and tails
are bfloat16 (2 bytes)."""

BYTES = 2
ROW_TILE = 16       # rows a tile of the grouped product in a decode step


def kv_bytes_position(cfg):
    """Bytes ONE layer keeps, and one attention call reads, for one cached
    position: keys and values, both heads (1,024 at the published
    widths)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def tail_bytes_row(cfg):
    """Bytes of one slot's tail (two rows of the ``[q ; k]`` channels) and
    value half in ONE layer."""
    channels = (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) \
        * cfg["head_dim"]
    return (2 * channels + cfg["head_dim"]) * BYTES


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_other_params(cfg):
    """What a layer holds beside its experts: the attention's projections
    and convolutions, the router, the norms, the merge's gains and
    biases."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    rh, e = cfg["router_hidden_size"], cfg["num_experts"]
    ch = (nq + nkv) * d
    attention = 2 * h * nq * d + h * nkv * d + 2 * h * d \
        + 3 * ch + 2 * (nq + nkv) * d * d + ch + nkv
    router = h * rh + 3 * rh + 2 * (rh * rh + rh) + rh * e + e
    return attention + router + 2 * h + 8 * h


def layer_params(cfg):
    return cfg["num_experts"] * expert_params(cfg) + layer_other_params(cfg)


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def weight_params(cfg):
    """Every parameter the chip holds: the layers, the final norm and the
    embedding, which is the head too."""
    return cfg["num_hidden_layers"] * layer_params(cfg) \
        + cfg["hidden_size"] + head_params(cfg)


def decode_step_parts(cfg, counts):
    """Bytes ONE decode step must move, by part, averaged over the window's
    steps: the experts the grouped product READ (``experts_touched``, summed
    over the layers), every other weight and the head once (the embedding
    is read by row), the live rows' cached positions in every layer, and
    every live row's tail and value half read and written in every layer.
    ``counts``: the window's ``decode_*`` sums."""
    steps = counts["decode_calls"]
    if steps <= 0:
        return None
    layers = cfg["num_hidden_layers"]
    return {
        "experts": counts["decode_experts_touched"] * expert_params(cfg)
        * BYTES / steps,
        "other_weights": layers * layer_other_params(cfg) * BYTES,
        "head": head_params(cfg) * BYTES,
        "pages": layers * counts["decode_attn_keys"]
        * kv_bytes_position(cfg) / steps,
        "tails": 2 * layers * counts["decode_row_steps"]
        * tail_bytes_row(cfg) / steps}


def decode_step_bytes(cfg, counts):
    parts = decode_step_parts(cfg, counts)
    return None if parts is None else sum(parts.values())


def decode_step_ops(cfg, counts):
    """Operations of ONE decode step, averaged over the window's steps:
    every live row through ONE expert and the rest of every layer and
    through the head, every query head against the cached positions its
    layer read (score and value)."""
    steps = counts["decode_calls"]
    if steps <= 0:
        return None
    layers = cfg["num_hidden_layers"]
    rows = counts["decode_row_steps"] / steps
    keys = counts["decode_attn_keys"] / steps
    return 2 * rows * (layers * (expert_params(cfg)
                                 + layer_other_params(cfg))
                       + head_params(cfg)) \
        + 2 * 2 * layers * keys * cfg["num_attention_heads"] \
        * cfg["head_dim"]


def decode_moe_rows(cfg, slots):
    """Rows of the padded, expert-sorted layout the decode step's grouped
    product walks (its output's first axis, which names its event on the
    device): ``slots`` pairs, every expert's run padded to a tile."""
    pairs = slots * cfg["num_experts_per_tok"]
    return (pairs + cfg["num_experts"] * (ROW_TILE - 1) + ROW_TILE - 1) \
        // ROW_TILE * ROW_TILE


def decode_moe_call(cfg, counts):
    """``(operations, bytes)`` of ONE call of the grouped product in a
    decode step (one a layer a step), averaged over the window's calls:
    the experts it read, three matrices each, and its rows in and out;
    every live row through one expert (a tile of 16 rows is computed
    whole whatever it holds: that is the kernel's, not the work's)."""
    calls = counts["decode_calls"] * cfg["num_hidden_layers"]
    if calls <= 0:
        return None
    touched = counts["decode_experts_touched"] / calls
    rows = counts["decode_row_steps"] / counts["decode_calls"]
    moved = touched * expert_params(cfg) * BYTES \
        + 2 * rows * cfg["hidden_size"] * BYTES
    return 2 * rows * expert_params(cfg), moved

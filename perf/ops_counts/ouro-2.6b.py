"""Operations and bytes of the ``ouro-2.6b`` configuration's stages, from
the configuration's shapes and the window's OWN counts (live rows x steps,
cached positions the attention calls read, the calls, as
``ContinuousBatcher.stats`` holds them), never from expected values. Two
operations to a multiply-add; weights and the K/V planes are bfloat16 (2
bytes)."""

BYTES = 2


def kv_bytes_position(cfg):
    """Bytes ONE attention call reads for one cached position: its keys
    and its values, every head, in one plane of one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def plane_bytes_position(cfg):
    """Bytes a cached position takes on the chip: a plane for every pass
    in every layer."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"] \
        * kv_bytes_position(cfg)


def layer_params(cfg):
    h, a = cfg["hidden_size"], cfg["num_attention_heads"] * cfg["head_dim"]
    return 4 * h * a + 3 * h * cfg["intermediate_size"] + 4 * h


def stack_params(cfg):
    """What ONE pass reads: the layers, the final norm and the exit gate."""
    return cfg["num_hidden_layers"] * layer_params(cfg) \
        + 2 * cfg["hidden_size"] + 1


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def weight_params(cfg):
    """Every parameter the chip holds: one stack whatever the passes, the
    embedding and the untied head."""
    return stack_params(cfg) + 2 * head_params(cfg)


def decode_step_parts(cfg, counts):
    """Bytes ONE decode step must move, by part, averaged over the window's
    steps: the stack's weights once a PASS (pass ``t + 1`` of a token needs
    pass ``t``, so no order of the work reads them fewer times), the head
    once (the embedding is read by row), and the planes: every attention
    call reads its rows' cached positions in its own plane of its own
    layer. ``counts``: the window's ``decode_*`` sums."""
    steps = counts["decode_calls"]
    if steps <= 0:
        return None
    return {
        "weights": cfg["total_ut_steps"] * stack_params(cfg) * BYTES,
        "head": head_params(cfg) * BYTES,
        "planes": counts["decode_attn_keys"] * kv_bytes_position(cfg)
        / steps}


def decode_step_bytes(cfg, counts):
    parts = decode_step_parts(cfg, counts)
    return None if parts is None else sum(parts.values())


def decode_step_ops(cfg, counts):
    """Operations of ONE decode step, averaged over the window's steps:
    every live row through the stack once a pass and through the head,
    every head's query against the cached positions its call read (score
    and value)."""
    steps = counts["decode_calls"]
    if steps <= 0:
        return None
    rows = counts["decode_row_steps"] / steps
    keys = counts["decode_attn_keys"] / steps
    return 2 * rows * (cfg["total_ut_steps"] * stack_params(cfg)
                       + head_params(cfg)) \
        + 2 * 2 * keys * cfg["num_attention_heads"] * cfg["head_dim"]


def attention_call(cfg, counts):
    """``(operations, bytes)`` of ONE call of the decode kernel over one
    plane's pages (one a layer a pass a step), averaged over the window's
    calls: the live rows' cached positions read once, keys and values;
    every head's one query against each."""
    calls = counts["decode_attn_calls"]
    if calls <= 0:
        return None
    keys = counts["decode_attn_keys"] / calls
    return 2 * 2 * keys * cfg["num_attention_heads"] * cfg["head_dim"], \
        keys * kv_bytes_position(cfg)

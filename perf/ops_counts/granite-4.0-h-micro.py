"""Operations and bytes of the ``granite-4.0-h-micro`` configuration's
stages, from the configuration's shapes and the window's OWN counts (real
tokens through the scan, live rows x decode steps, cached positions the
attention layers read, as ``ContinuousBatcher.stats`` holds them), never
from expected values. Two operations to a multiply-add. What is counted is
what a stage MUST move: a live row's recurrent state is read once and
written once a step, every weight is read once."""

ITEM = {"float32": 4, "bfloat16": 2}


def _layers(cfg):
    kinds = cfg["layer_types"]
    return kinds.count("mamba"), kinds.count("attention")


def _inner(cfg):
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    return inner, inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def weight_bytes(cfg):
    """Every weight a decode step reads: each layer's mixer and MLP, the
    final gain, and the embedding once as the head (the step's own 64
    embedding rows are not counted)."""
    w = ITEM[cfg["precision"]["weights"]]
    h, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    inner, conv_dim = _inner(cfg)
    nh = cfg["mamba_n_heads"]
    mamba = (h * (inner + conv_dim + nh) + inner * h
             + cfg["mamba_d_conv"] * conv_dim + conv_dim + 3 * nh + inner)
    d = h // cfg["num_attention_heads"]
    attention = 2 * h * cfg["num_attention_heads"] * d \
        + 2 * h * cfg["num_key_value_heads"] * d
    mlp = h * 2 * f + f * h + 2 * h          # and the layer's two gains
    n_ssm, n_attn = _layers(cfg)
    return (n_ssm * mamba + n_attn * attention + (n_ssm + n_attn) * mlp
            + h + cfg["vocab_size"] * h) * w


def state_bytes_row(cfg):
    """One slot's recurrent state, all state-space layers."""
    return _layers(cfg)[0] * cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
        * cfg["mamba_d_state"] * ITEM[cfg["precision"]["state"]]


def tail_bytes_row(cfg):
    """One slot's convolution tails, all state-space layers."""
    return _layers(cfg)[0] * (cfg["mamba_d_conv"] - 1) * _inner(cfg)[1] \
        * ITEM[cfg["precision"]["conv_tail"]]


def kv_bytes_position(cfg):
    """K and V of one cached position, all attention layers."""
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return _layers(cfg)[1] * 2 * cfg["num_key_value_heads"] * d \
        * ITEM[cfg["precision"]["cache"]]


def decode_step_parts(cfg, counts):
    """``{"weights", "state", "tails", "kv"}``: bytes ONE decode step must
    move, averaged over the window's steps: every weight once; each live
    row's recurrent state read and written; its tails read and written;
    K and V up to each live row's position. ``counts``: the window's
    ``decode_*`` sums (``decode_attn_keys`` is counted once a step, for
    one layer)."""
    steps = counts["decode_calls"]
    if steps <= 0:
        return None
    rows = counts["decode_row_steps"] / steps
    return {"weights": weight_bytes(cfg),
            "state": rows * 2 * state_bytes_row(cfg),
            "tails": rows * 2 * tail_bytes_row(cfg),
            "kv": counts["decode_attn_keys"] / steps
            * kv_bytes_position(cfg)}


def decode_step_bytes(cfg, counts):
    parts = decode_step_parts(cfg, counts)
    return None if parts is None else sum(parts.values())


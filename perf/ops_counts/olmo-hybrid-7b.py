"""Operations and bytes of the ``olmo-hybrid-7b`` configuration's stages,
from the configuration's shapes and the window's OWN counts (real tokens
through the blocked rule, live rows x decode steps, cached positions the
full-attention layers read, as ``ContinuousBatcher.stats`` holds them),
never from expected values. Two operations to a multiply-add. What is
counted is what a stage MUST move: a live row's state is read once and
written once a step, every weight is read once."""

ITEM = {"float32": 4, "bfloat16": 2}


def _layers(cfg):
    kinds = cfg["layer_types"]
    return kinds.count("linear_attention"), kinds.count("full_attention")


def _widths(cfg):
    """``(heads, d_k, d_v, query or key channels, value channels)`` of a
    delta-rule layer."""
    nh, dk, dv = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"], \
        cfg["linear_value_head_dim"]
    return nh, dk, dv, nh * dk, cfg["linear_num_value_heads"] * dv


def weight_bytes(cfg):
    """Every weight a decode step reads: each layer's mixer, its two gains
    and its MLP, the final gain and the head (the step's own 16 embedding
    rows are not counted)."""
    w = ITEM[cfg["precision"]["weights"]]
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    nh, dk, dv, qk, vw = _widths(cfg)
    delta = (h * (2 * qk + 2 * vw + 2 * nh) + vw * h
             + cfg["linear_conv_kernel_dim"] * (2 * qk + vw) + 2 * nh + dv)
    full = 4 * h * h + 2 * h
    mlp = 3 * h * f + 2 * h                  # and the layer's two gains
    n_delta, n_full = _layers(cfg)
    return (n_delta * delta + n_full * full + (n_delta + n_full) * mlp
            + h + h * cfg["vocab_size"]) * w


def state_bytes_row(cfg):
    """One slot's matrix state, all delta-rule layers."""
    nh, dk, dv, _, _ = _widths(cfg)
    return _layers(cfg)[0] * nh * dk * dv * ITEM[cfg["precision"]["state"]]


def tail_bytes_row(cfg):
    """One slot's convolution tails, all delta-rule layers."""
    _, _, _, qk, vw = _widths(cfg)
    return _layers(cfg)[0] * (cfg["linear_conv_kernel_dim"] - 1) \
        * (2 * qk + vw) * ITEM[cfg["precision"]["conv_tail"]]


def kv_bytes_position(cfg):
    """K and V of one cached position, all full-attention layers."""
    return _layers(cfg)[1] * 2 * cfg["hidden_size"] \
        * ITEM[cfg["precision"]["cache"]]


def decode_step_parts(cfg, counts):
    """``{"weights", "state", "tails", "kv"}``: bytes ONE decode step must
    move, averaged over the window's steps: every weight once; each live
    row's state read and written; its tails read and written; K and V up to
    each live row's position. ``counts``: the window's ``decode_*`` sums
    (``decode_attn_keys`` is counted once a step, for one layer)."""
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


# ----------------------------------------------------- the kernel's call
def delta_step_call(cfg, counts):
    """``(operations, bytes)`` of ONE call of ``%gated_delta_step`` (a
    delta-rule layer of a decode step), averaged over the window's steps:
    each LIVE row's state once in and once out in the state's own dtype,
    its key, query, value, decay, write strength and output beside it; the
    decay, two read-outs and the rank-one write, seven operations a state
    entry."""
    steps = counts["decode_calls"]
    if steps <= 0:
        return None
    nh, dk, dv, _, _ = _widths(cfg)
    rows = counts["decode_row_steps"] / steps
    state = nh * dk * dv * ITEM[cfg["precision"]["state"]]
    moved = rows * (2 * state + nh * (2 * dk + 4 * dv) * 4)
    return rows * nh * 7 * dk * dv, moved

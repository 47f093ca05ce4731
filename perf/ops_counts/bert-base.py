"""Operations of one token of the ``bert-base`` step, from the
configuration's shapes: what the algorithm computes, forward and backward
(three times the forward's matrix products), with no recomputation
counted. Two operations to a multiply-add."""


def forward_flops_per_token(cfg, seq_len):
    u, f = cfg["hidden_size"], cfg["intermediate_size"]
    layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    encoder = layers * 2 * (3 * u * u + u * u + 2 * u * f)  # qkv, out, ffn
    scores = layers * 2 * 2 * seq_len * u                   # QK^T and PV
    head = 2 * u * vocab                                    # tied embedding
    return {"encoder": encoder, "attention_scores": scores,
            "vocabulary_head": head}


def train_flops_per_token(cfg, seq_len):
    parts = forward_flops_per_token(cfg, seq_len)
    return 3 * sum(parts.values())

"""The busiest expert's tokens over the mean expert's, a layer, averaged
over the layers, in the decode steps of the window: what a balancing bias
that selects and does not weigh is for, and how full the grouped product's
tiles can be (``decode_expert_tokens``, an entry a layer an expert)."""

import numpy as np

from perf.harness import cca_counts

NAME = "top1_expert_load_imbalance"
UNIT = "ratio"
LAYER = "expert layer"
MOVES = "tpot_p95_ms"


def read(run):
    counts = cca_counts.window_counts(run)
    if counts is None:
        return None
    cfg = run.obs["config"]
    by_layer = np.asarray(counts["decode_expert_tokens"], np.float64) \
        .reshape(cfg["num_hidden_layers"], cfg["num_experts"])
    mean = by_layer.mean(1)
    if not (mean > 0).all():
        return None
    return float((by_layer.max(1) / mean).mean())

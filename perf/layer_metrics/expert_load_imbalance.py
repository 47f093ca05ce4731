"""How unevenly the decode steps' tokens fall on the experts: for each
layer the busiest expert's tokens over the mean expert's, averaged over
the layers (1.0 is even). From the counts that rode the bursts'
read-backs."""

from perf.harness import lm_counts

NAME = "expert_load_imbalance"
UNIT = "ratio"
LAYER = "expert layer"
MOVES = "tpot_p95_ms"


def read(run):
    counts = lm_counts.window_counts(run)
    if counts is None:
        return None
    layers = run.obs["config"]["num_hidden_layers"]
    per = counts["decode_expert_tokens"].reshape(layers, -1)
    if not per.sum():
        return None
    return float((per.max(1) / per.mean(1)).mean())

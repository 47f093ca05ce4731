"""Experts the decode step's grouped product READ, a layer a step, over the
experts a layer holds: with one expert a token, 64 rows touch nearly all 16
and the expert layer's bytes hardly depend on the routing; with few rows
they do. From the counts that rode the read-backs
(``decode_experts_touched``, summed over layers and steps)."""

from perf.harness import cca_counts

NAME = "top1_experts_touched_share"
UNIT = "%"
LAYER = "expert layer"
MOVES = "tpot_p95_ms"


def read(run):
    counts = cca_counts.window_counts(run)
    if counts is None:
        return None
    cfg = run.obs["config"]
    calls = counts["decode_calls"] * cfg["num_hidden_layers"]
    if calls <= 0:
        return None
    return 100.0 * counts["decode_experts_touched"] \
        / (calls * cfg["num_experts"])

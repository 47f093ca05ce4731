"""Of the bytes one decode step must move, the share that is recurrent
state (each live row's, read once and written once): how much of a step
the state-space mechanism is, against the weights, the tails and the
attention layers' K and V. From the counts that rode the read-backs."""

from perf.harness import hybrid_counts

NAME = "ssm_state_bytes_share"
UNIT = "%"
LAYER = "state-space layer"
MOVES = "tpot_p95_ms"


def read(run):
    counts = hybrid_counts.window_counts(run)
    if counts is None:
        return None
    cfg = run.obs["config"]
    parts = run.ctx.bench.ops_counts(cfg["name"]).decode_step_parts(
        cfg, counts)
    if parts is None:
        return None
    return 100.0 * parts["state"] / sum(parts.values())

"""Of the bytes one decode step of a looped model must move, the share that
is the stack's weights read once a PASS: how much of a step the loop is,
against the head and the K/V planes. The cell is sound while it reads over
50. From the counts that rode the read-backs."""

from perf.harness import loop_counts

NAME = "loop_weight_bytes_share"
UNIT = "%"
LAYER = "looped stack"
MOVES = "tpot_p95_ms"


def read(run):
    counts = loop_counts.window_counts(run)
    if counts is None:
        return None
    cfg = run.obs["config"]
    parts = run.ctx.bench.ops_counts(cfg["name"]).decode_step_parts(
        cfg, counts)
    if parts is None:
        return None
    return 100.0 * parts["weights"] / sum(parts.values())

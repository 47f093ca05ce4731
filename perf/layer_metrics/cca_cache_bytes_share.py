"""Of the bytes one decode step of the compressed-latent model must move,
the share that is the CACHE: the live rows' K/V pages (1 KB a position a
layer) and their tails and value halves, against the experts the step
read, the other weights and the head. What this attention is for: the
share a cache of 16 heads would take is eight times this one's pages. From
the counts that rode the read-backs."""

from perf.harness import cca_counts

NAME = "cca_cache_bytes_share"
UNIT = "%"
LAYER = "attention"
MOVES = "tpot_p95_ms"


def read(run):
    counts = cca_counts.window_counts(run)
    if counts is None:
        return None
    cfg = run.obs["config"]
    parts = run.ctx.bench.ops_counts(cfg["name"]).decode_step_parts(
        cfg, counts)
    if parts is None:
        return None
    return 100.0 * (parts["pages"] + parts["tails"]) / sum(parts.values())

"""Of the bytes one decode step must move, the share that is latent cache
(each live row's cached positions, once in each latent cache): how much of
a step the mechanism is, against the weights, the touched experts and the
head. The cell is sound while it reads over 50. From the counts that rode
the read-backs."""

from perf.harness import mla_counts

NAME = "mla_cache_bytes_share"
UNIT = "%"
LAYER = "attention"
MOVES = "tpot_p95_ms"


def read(run):
    counts = mla_counts.window_counts(run)
    if counts is None:
        return None
    cfg = run.obs["config"]
    parts = run.ctx.bench.ops_counts(cfg["name"]).decode_step_parts(
        cfg, counts)
    if parts is None:
        return None
    return 100.0 * parts["latent"] / sum(parts.values())

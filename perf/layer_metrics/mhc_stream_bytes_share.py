"""Of the bytes one chunk of a prompt must move, the share that is the
residual stream (each live token's ``n`` streams read once and written once
a mixer, with the sublayer's output and the next one's input): how much of
a chunk the mechanism is, against the weights, the experts, the head, the
latent caches and their expansion. From the counts that rode the
read-backs."""

from perf.harness import mhc_counts

NAME = "mhc_stream_bytes_share"
UNIT = "%"
LAYER = "residual stream"
MOVES = "ttft_p95_ms"


def read(run):
    counts = mhc_counts.window_counts(run)
    if counts is None:
        return None
    cfg = run.obs["config"]
    parts = run.ctx.bench.ops_counts(cfg["name"]).chunk_parts(cfg, counts)
    if parts is None:
        return None
    return 100.0 * parts["stream"] / sum(parts.values())

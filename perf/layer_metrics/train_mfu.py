"""Model FLOP/s utilization of a training cell: tokens per second in the
steady state (tokens of a dispatch over the median gap between dispatches,
which a traced stretch does not disturb) times the operations a token needs
(from the config's shapes, no recomputation), over chips times the chip's
bf16 peak."""

from perf.harness.clock import percentile

NAME = "train_mfu"
UNIT = "%"
LAYER = "engine, training"
MOVES = "train_tokens_per_s"


def read(run):
    gaps = run.obs.get("dispatch_gap_s")
    if MOVES not in run.e2e or not gaps or run.ctx.peaks is None:
        return None
    rate = run.obs["tokens_per_dispatch"] / percentile(gaps, 50)
    cfg, mix = run.obs["config"], run.obs["traffic"]
    flops = run.ctx.bench.ops_counts(cfg["name"]).train_flops_per_token(
        cfg, mix["seq_len"])
    return 100.0 * rate * flops / (run.ctx.chips
                                      * run.ctx.peaks["flops_bf16"])

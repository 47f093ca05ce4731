"""Share of its roofline that the grouped expert product reaches in the
chunk program: the larger of its operations over the peak rate and its
bytes over the peak bandwidth (one call's, averaged over the window's
calls: ``perf/ops_counts``), against the mean device time of a call in the
traced stretch."""

from perf.harness import lm_counts

NAME = "moe_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"


def read(run):
    counts = lm_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    seconds, calls = run.trace.op_seconds(lm_counts.MOE_KERNEL)
    cfg = run.obs["config"]
    call = run.ctx.bench.ops_counts(cfg["name"]).moe_call(cfg, counts)
    if not calls or call is None:
        return None
    ops, moved = call
    least = max(ops / run.ctx.peaks["flops_bf16"],
                moved / run.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)

"""Share of its roofline that the grouped expert product reaches in the
DECODE step at this model's width (16 experts of 2048 x 2048 x 3, one a
token: tiles of 16 rows a quarter full): the larger of one call's bytes
(the experts it read) over the peak bandwidth and its operations over the
peak rate, averaged over the window's calls (``perf/ops_counts``), against
the mean device time of the decode step's events in the traced stretch
(``%moe_grouped_swiglu.<n>`` by the rows of its own layout: the chunk's
calls carry the same name and other rows)."""

from perf.harness import cca_counts

NAME = "cca_moe_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"


def read(run):
    counts = cca_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    cfg = run.obs["config"]
    ops = run.ctx.bench.ops_counts(cfg["name"])
    seconds, calls = run.trace.op_seconds(cca_counts.decode_moe_kernel(
        ops.decode_moe_rows(cfg, run.obs["slots"])))
    call = ops.decode_moe_call(cfg, counts)
    if not calls or call is None:
        return None
    work, moved = call
    least = max(work / run.ctx.peaks["flops_bf16"],
                moved / run.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)

"""Share of its roofline that the decode kernel over the latent pages
reaches: the larger of one call's bytes (the live rows' cached positions,
read once) over the peak bandwidth and its operations (two query positions
of every head against each) over the peak rate, averaged over the window's
calls (``perf/ops_counts``), against the mean device time of the events the
trace has (``%mla_latent_decode.<n>``: an event a step a latent cache)."""

from perf.harness import mla_counts

NAME = "mla_latent_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p95_ms"


def read(run):
    counts = mla_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    seconds, calls = run.trace.op_seconds(mla_counts.LATENT_KERNEL)
    cfg = run.obs["config"]
    call = run.ctx.bench.ops_counts(cfg["name"]).latent_call(cfg, counts)
    if not calls or call is None:
        return None
    ops, moved = call
    least = max(ops / run.ctx.peaks["flops_bf16"],
                moved / run.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)

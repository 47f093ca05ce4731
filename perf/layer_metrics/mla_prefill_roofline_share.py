"""Share of its roofline that the chunk program's expanded latent attention
reaches: the larger of one call's operations (every scored (query, key)
pair of every head through score and value, from the window's own count)
over the peak rate and its bytes (the expanded keys and values of the
positions read, once) over the peak bandwidth, averaged over the window's
calls (``perf/ops_counts``), against the mean device time of the events
the trace has (``%mla_prefill.<n>``: an event a chunk a latent cache, one
population)."""

from perf.harness import mhc_counts

NAME = "mla_prefill_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "ttft_p95_ms"


def read(run):
    counts = mhc_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    seconds, calls = run.trace.op_seconds(mhc_counts.PREFILL_KERNEL)
    cfg = run.obs["config"]
    call = run.ctx.bench.ops_counts(cfg["name"]).prefill_call(cfg, counts)
    if not calls or call is None:
        return None
    ops, moved = call
    least = max(ops / run.ctx.peaks["flops_bf16"],
                moved / run.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)

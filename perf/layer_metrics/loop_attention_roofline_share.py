"""Share of its roofline that the decode step's attention call over one
plane's pages reaches: the larger of one call's bytes (the live rows'
cached positions in that plane, keys and values, read once) over the peak
bandwidth and its operations over the peak rate, averaged over the window's
calls (``perf/ops_counts``), against the mean device time of the events the
trace has (``%paged_window.<n>``: an event a layer a pass a step)."""

from perf.harness import loop_counts

NAME = "loop_attention_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p95_ms"


def read(run):
    counts = loop_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    seconds, calls = run.trace.op_seconds(loop_counts.DECODE_KERNEL)
    cfg = run.obs["config"]
    call = run.ctx.bench.ops_counts(cfg["name"]).attention_call(cfg, counts)
    if not calls or call is None:
        return None
    ops, moved = call
    least = max(ops / run.ctx.peaks["flops_bf16"],
                moved / run.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)

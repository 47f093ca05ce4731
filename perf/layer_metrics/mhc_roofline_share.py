"""Share of its roofline that the mixing of the residual stream reaches,
over BOTH programs at once: the least time of all the mixing the traced
stretch dispatched (each chunk's and each decode step's (token, mixer)
pairs from the window's own counts, ``perf/ops_counts``: 10 x C numbers a
pair over the peak bandwidth, or the projection's operations over the peak
rate if more), over the summed seconds of the stretch's ``%mhc_*`` events.
Summed, not a mean call against a mean event: the two programs' calls are
two populations (2,048 rows and some thirty), and a mean over both would
move with the ratio of bursts to chunks in the stretch (PERF.md 7 (ah))."""

from perf.harness import mhc_counts

NAME = "mhc_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "ttft_p95_ms"


def read(run):
    counts = mhc_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    cfg = run.obs["config"]
    found = mhc_counts.dispatches(run.trace, mhc_counts.MHC_KERNEL,
                                  cfg["serving"]["prefill_chunk"])
    ops = run.ctx.bench.ops_counts(cfg["name"])
    least = seconds = 0.0
    for program, (spent, times) in found.items():
        call = ops.mhc_call(cfg, counts, program)
        if not times or call is None:
            continue
        least += times * max(call[0] / run.ctx.peaks["flops_bf16"],
                             call[1] / run.ctx.peaks["hbm_bytes_per_s"])
        seconds += spent
    if not seconds:
        return None
    return 100.0 * least / seconds

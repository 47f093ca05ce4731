"""Share of the memory roofline that one decode step of the hybrid model
reaches: the bytes a step must move (every weight once, each live row's
recurrent state read and written, its tails, K and V up to the live
positions; from the window's own counts, ``perf/ops_counts``) over the peak
bandwidth, against the device time of a step: the burst is ONE event on
the device's timeline (a while of ``iter_tokens`` steps), so its seconds
over its steps."""

from perf.harness import hybrid_counts

NAME = "ssm_decode_step_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p95_ms"


def read(run):
    counts = hybrid_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    seconds, bursts = run.trace.op_seconds(
        hybrid_counts.decode_burst(run.obs["slots"]))
    cfg = run.obs["config"]
    step_bytes = run.ctx.bench.ops_counts(cfg["name"]).decode_step_bytes(
        cfg, counts)
    if not bursts or not step_bytes:
        return None
    step_s = seconds / (bursts * run.obs["iter_tokens"])
    return 100.0 * step_bytes / run.ctx.peaks["hbm_bytes_per_s"] / step_s

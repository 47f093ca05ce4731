"""Share of the memory roofline that one decode step reaches: the bytes a
step must read (the weights of the experts it touched, the other weights,
the head, the selected K and V, the scanned indexer keys; from the window's
own counts, ``perf/ops_counts``) over the peak bandwidth, against the
device time of a step: the burst is ONE event on the device's timeline (a
while of ``iter_tokens`` steps), so its seconds over its steps."""

from perf.harness import lm_counts

NAME = "decode_step_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p95_ms"


def read(run):
    counts = lm_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    seconds, bursts = run.trace.op_seconds(
        lm_counts.decode_burst(run.obs["slots"]))
    cfg = run.obs["config"]
    step_bytes = run.ctx.bench.ops_counts(cfg["name"]).decode_step_bytes(
        cfg, counts)
    if not bursts or not step_bytes:
        return None
    step_s = seconds / (bursts * run.obs["iter_tokens"])
    return 100.0 * step_bytes / run.ctx.peaks["hbm_bytes_per_s"] / step_s

"""Share of its roofline that one decode step reaches: the larger of the
bytes a step must move (the latent cache of the live rows, the experts it
touched, the other weights, the head for the step and for the draft) over
the peak bandwidth and its operations over the peak rate, both from the
window's own counts (``perf/ops_counts``), against the device time of a
step: the burst is ONE event on the device's timeline (a while of
``iter_tokens`` steps), so its seconds over its steps. A step is two
positions a row whatever it yields."""

from perf.harness import mla_counts

NAME = "mla_decode_step_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p95_ms"


def read(run):
    counts = mla_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    seconds, bursts = run.trace.op_seconds(
        mla_counts.decode_burst(run.obs["slots"]))
    cfg = run.obs["config"]
    ops = run.ctx.bench.ops_counts(cfg["name"])
    moved, work = ops.decode_step_bytes(cfg, counts), \
        ops.decode_step_ops(cfg, counts)
    if not bursts or not moved or not work:
        return None
    least = max(moved / run.ctx.peaks["hbm_bytes_per_s"],
                work / run.ctx.peaks["flops_bf16"])
    return 100.0 * least / (seconds / (bursts * run.obs["iter_tokens"]))

"""Share of its roofline that one decode step of a looped model reaches:
the larger of the bytes a step must move (the stack's weights once a PASS,
the head, the live rows' cached positions in every plane of every layer)
over the peak bandwidth and its operations over the peak rate, both from
the window's own counts (``perf/ops_counts``), against the device time of a
step: the burst is ONE event on the device's timeline (a while of
``iter_tokens`` steps, the loop over passes inside it), so its seconds over
its steps."""

from perf.harness import loop_counts

NAME = "loop_decode_step_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p95_ms"


def read(run):
    counts = loop_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    seconds, bursts = run.trace.op_seconds(
        loop_counts.decode_burst(run.obs["slots"]))
    cfg = run.obs["config"]
    ops = run.ctx.bench.ops_counts(cfg["name"])
    moved, work = ops.decode_step_bytes(cfg, counts), \
        ops.decode_step_ops(cfg, counts)
    if not bursts or not moved or not work:
        return None
    least = max(moved / run.ctx.peaks["hbm_bytes_per_s"],
                work / run.ctx.peaks["flops_bf16"])
    return 100.0 * least / (seconds / (bursts * run.obs["iter_tokens"]))

"""Share of its roofline that one WHOLE decode step of the compressed-latent
model reaches: the larger of the bytes a step must move (the experts the
grouped product read, every other weight, the head, the live rows' cached
positions and tails in every layer) over the peak bandwidth and its
operations over the peak rate, both from the window's own counts
(``perf/ops_counts``), against the device time of a step: the burst is ONE
event on the device's timeline (a while of ``iter_tokens`` steps), so its
seconds over its steps."""

from perf.harness import cca_counts

NAME = "cca_decode_step_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p95_ms"


def read(run):
    counts = cca_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    seconds, bursts = run.trace.op_seconds(
        cca_counts.decode_burst(run.obs["slots"]))
    cfg = run.obs["config"]
    ops = run.ctx.bench.ops_counts(cfg["name"])
    moved, work = ops.decode_step_bytes(cfg, counts), \
        ops.decode_step_ops(cfg, counts)
    if not bursts or not moved or not work:
        return None
    least = max(moved / run.ctx.peaks["hbm_bytes_per_s"],
                work / run.ctx.peaks["flops_bf16"])
    return 100.0 * least / (seconds / (bursts * run.obs["iter_tokens"]))

"""Share of its roofline that the selected window reaches: the operations
attention over the SELECTED keys needs (two products of every selected
(query, key) pair of every query head, from the window's own count of keys
selected) over the peak rate, or its bytes over the peak bandwidth if that
is more, against the mean device time of a call in the traced stretch. The
kernel computes every key a query block can see and masks, so this share
falls as the selection gets sparser."""

from perf.harness import lm_counts

NAME = "dsa_roofline_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "ttft_p95_ms"


def read(run):
    counts = lm_counts.window_counts(run)
    if counts is None or run.trace is None or not run.trace.devices \
            or run.ctx.peaks is None:
        return None
    seconds, calls = run.trace.op_seconds(lm_counts.DSA_KERNEL)
    cfg = run.obs["config"]
    call = run.ctx.bench.ops_counts(cfg["name"]).selected_window_call(
        cfg, counts)
    if not calls or call is None:
        return None
    ops, moved = call
    least = max(ops / run.ctx.peaks["flops_bf16"],
                moved / run.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)

"""Share of the device's busy time spent in the grouped expert product
(``%moe_grouped_swiglu.<n>``) where it has events of its own: the chunk
program. Inside the decode burst's while it has none."""

from perf.harness import lm_counts

NAME = "moe_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    seconds, calls = run.trace.op_seconds(lm_counts.MOE_KERNEL)
    if not calls:
        return None
    return 100.0 * seconds / run.trace.busy_s_of(run.trace.devices[0])

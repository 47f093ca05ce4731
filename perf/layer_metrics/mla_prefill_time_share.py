"""Share of the device's busy time spent in the chunk program's expanded
latent attention (``%mla_prefill.<n>``: one name a latent cache, an event a
chunk; PERF.md 7 (w): the events had no metric). Read in the cell whose
rows are long enough for it to be most of a chunk."""

from perf.harness import mhc_counts

NAME = "mla_prefill_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"


def read(run):
    if run.trace is None or not run.trace.devices \
            or mhc_counts.window_counts(run) is None:
        return None
    seconds, calls = run.trace.op_seconds(mhc_counts.PREFILL_KERNEL)
    if not calls:
        return None
    return 100.0 * seconds / run.trace.busy_s_of(run.trace.devices[0])

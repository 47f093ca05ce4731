"""Share of the device's busy time spent in the decode kernel over the
latent pages (``%mla_latent_decode.<n>``: one name a latent cache, an event
a step; the trace keeps a Mosaic call's events inside the burst's while,
where XLA's own fusions have none)."""

from perf.harness import mla_counts

NAME = "mla_latent_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    seconds, calls = run.trace.op_seconds(mla_counts.LATENT_KERNEL)
    if not calls:
        return None
    return 100.0 * seconds / run.trace.busy_s_of(run.trace.devices[0])

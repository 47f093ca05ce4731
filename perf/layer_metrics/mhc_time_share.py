"""Share of the device's busy time spent mixing the residual stream
(events ``%mhc_*``: the Pallas calls of ``ops/pallas/mhc_mix.py``, in the
chunk program and inside the burst's ``%while``, where a Mosaic call keeps
its events). A program that mixes by XLA's own fusions (the CPU's, a
mesh's) or has no such stream has no such event and the metric is left
out."""

from perf.harness import mhc_counts

NAME = "mhc_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    seconds, calls = run.trace.op_seconds(mhc_counts.MHC_KERNEL)
    if not calls:
        return None
    return 100.0 * seconds / run.trace.busy_s_of(run.trace.devices[0])

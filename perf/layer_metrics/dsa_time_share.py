"""Share of the device's busy time spent in the sparse attention's selected
window (``%dsa_selected_window.<n>``, the chunk program's attention over the
selected set). The index scores and the selection before it are XLA fusions
and a loop with XLA's names, and are not in this share; a decode step's
gather and attention lie inside the burst's while and have no events."""

from perf.harness import lm_counts

NAME = "dsa_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    seconds, calls = run.trace.op_seconds(lm_counts.DSA_KERNEL)
    if not calls:
        return None
    return 100.0 * seconds / run.trace.busy_s_of(run.trace.devices[0])

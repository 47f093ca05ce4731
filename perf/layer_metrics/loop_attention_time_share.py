"""Share of the device's busy time spent in the attention calls that read a
plane of the K/V pools: the decode step's ``%paged_window.<n>`` (one name a
layer, an event a pass a step; the trace keeps a Mosaic call's events
inside the burst's while, where XLA's own fusions have none) and the
chunk's ``%dsa_selected_window.<n>``."""

from perf.harness import loop_counts

NAME = "loop_attention_time_share"
UNIT = "%"
LAYER = "kernels"
MOVES = "tpot_p95_ms"


def read(run):
    if run.trace is None or not run.trace.devices \
            or loop_counts.window_counts(run) is None:
        return None
    seconds, calls = run.trace.op_seconds(loop_counts.PLANE_KERNELS)
    if not calls:
        return None
    return 100.0 * seconds / run.trace.busy_s_of(run.trace.devices[0])

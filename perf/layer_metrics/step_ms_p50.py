"""Median time of one optimizer step in the steady state: the host-clock
gap between the ends of successive groups of dispatches (each group spans
250 ms or more), over the steps of a group (one dispatch is one step)."""

from perf.harness.clock import percentile

NAME = "step_ms_p50"
UNIT = "ms"
LAYER = "engine, training"
MOVES = "train_tokens_per_s"


def read(run):
    gaps = run.obs.get("dispatch_gap_s")
    if not gaps:
        return None
    return 1e3 * percentile(gaps, 50)

"""Self time of the scheduler's admit phase per iteration: ``admit_s`` less the
prefill dispatches inside it (``prefill_s``), which ``prefill_wait_ms``
reports."""

from perf.harness.phases import per_iteration_ms

NAME = "sched_admit_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p95_ms"


def read(run):
    return per_iteration_ms(run, ("admit_s",), ("prefill_s",))

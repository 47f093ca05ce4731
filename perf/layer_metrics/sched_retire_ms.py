"""Self time of the scheduler's retire phase per iteration: ``retire_s`` less
the prefix registration inside it (``register_prefix_s``), which
``prefix_register_ms`` reports."""

from perf.harness.phases import per_iteration_ms

NAME = "sched_retire_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"


def read(run):
    return per_iteration_ms(run, ("retire_s",), ("register_prefix_s",))

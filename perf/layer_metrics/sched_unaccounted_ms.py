"""What no phase span sees of a scheduler pass: ``step_s`` less the seven
phases that lie side by side inside it (intake, retire, admit, capacity,
dispatch, read-back, collect). The phase metrics, intake, capacity, collect
and this one add up to ``sched_iter_busy_ms`` by construction."""

from perf.harness.phases import per_iteration_ms

NAME = "sched_unaccounted_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"


def read(run):
    return per_iteration_ms(
        run, ("step_s",),
        ("intake_s", "retire_s", "admit_s", "capacity_s",
         "dispatch_s", "readback_s", "collect_s"))

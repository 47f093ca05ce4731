"""One working pass of the scheduler timed where it runs: the seconds of the
``mxtpu.sched.step`` span (``stats["step_s"]``) over the iterations of the
window. It equals ``iter_wall_ms`` only while the loop never waits."""

from perf.harness.phases import per_iteration_ms

NAME = "sched_iter_busy_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"


def read(run):
    return per_iteration_ms(run, ("step_s",))

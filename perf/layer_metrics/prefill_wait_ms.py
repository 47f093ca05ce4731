"""Time per iteration from an admission prefill's dispatch to its first tokens
on the host (``mxtpu.sched.admit.prefill``, ``stats["prefill_s"]``)."""

from perf.harness.phases import per_iteration_ms

NAME = "prefill_wait_ms"
UNIT = "ms"
LAYER = "engine, serving"
MOVES = "ttft_p95_ms"


def read(run):
    return per_iteration_ms(run, ("prefill_s",))

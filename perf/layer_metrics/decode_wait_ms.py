"""Time per iteration from the decode dispatch's host build to its tokens on
the host: ``dispatch_s`` (build and enqueue) plus ``readback_s`` (the one
sync point, the device's part of the iteration)."""

from perf.harness.phases import per_iteration_ms

NAME = "decode_wait_ms"
UNIT = "ms"
LAYER = "engine, serving"
MOVES = "tpot_p95_ms"


def read(run):
    return per_iteration_ms(run, ("dispatch_s", "readback_s"))

"""Median wait of a request between its submit and its admission to a slot,
as the scheduler stamps it on the request (``GenerationResult.queue_wait_ms``),
over the requests sent in the window."""

from perf.harness.clock import percentile

NAME = "queue_wait_p50_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p95_ms"


def read(run):
    waits = run.obs.get("queue_wait_ms")
    if not waits:
        return None
    return percentile(waits, 50)

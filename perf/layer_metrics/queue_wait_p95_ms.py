"""95th percentile, over the requests whose first token the window saw, of
the wait from submit to a slot (``GenerationResult.phases["queue_ms"]``),
from the scheduler's window histogram ``stats["h_queue_ms"]``. In a closed loop
a freed slot goes straight to the caller's next request: about a pass."""

from perf.harness.window_hist import window_percentile_ms

NAME = "queue_wait_p95_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p95_ms"


def read(run):
    return window_percentile_ms(run, "h_queue_ms")

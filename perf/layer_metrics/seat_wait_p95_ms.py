"""95th percentile of the wait from the slot to the dispatch of the request's
first chunk (``phases["seat_ms"]``, ``stats["h_seat_ms"]``): the queue for the
one chunk seat a pass, where a prompt enters its pages in chunks; 0 where the
slot is taken at the admission prefill's dispatch."""

from perf.harness.window_hist import window_percentile_ms

NAME = "seat_wait_p95_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p95_ms"


def read(run):
    return window_percentile_ms(run, "h_seat_ms")

"""95th percentile of the time from the dispatch of a request's first chunk (or
of its admission prefill) to its first token on the host
(``phases["service_ms"]``, ``stats["h_service_ms"]``): its own chunks and the
decode bursts that ran between them."""

from perf.harness.window_hist import window_percentile_ms

NAME = "prefill_service_p95_ms"
UNIT = "ms"
LAYER = "engine, serving"
MOVES = "ttft_p95_ms"


def read(run):
    return window_percentile_ms(run, "h_service_ms")

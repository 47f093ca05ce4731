"""95th percentile of the time from a request's first token in its stream to the
CALLER's thread taking it (``phases["deliver_ms"]``, ``stats["h_deliver_ms"]``,
observed at retire for requests whose caller had read): the wake-up of a
waiting thread, which is its turn at the interpreter lock."""

from perf.harness.window_hist import window_percentile_ms

NAME = "first_token_deliver_p95_ms"
UNIT = "ms"
LAYER = "scheduler"
MOVES = "ttft_p95_ms"


def read(run):
    return window_percentile_ms(run, "h_deliver_ms")

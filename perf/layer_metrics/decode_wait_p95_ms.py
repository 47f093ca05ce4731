"""95th percentile of a decode burst from its dispatch to its tokens on the host
(``dispatch_s + readback_s`` of one pass, ``stats["h_burst_ms"]``): the tail of
what ``decode_wait_ms`` gives the mean of."""

from perf.harness.window_hist import window_percentile_ms

NAME = "decode_wait_p95_ms"
UNIT = "ms"
LAYER = "engine, serving"
MOVES = "tpot_p95_ms"


def read(run):
    return window_percentile_ms(run, "h_burst_ms")

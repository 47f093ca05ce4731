"""95th percentile of a working scheduler pass (the seconds of the
``mxtpu.sched.step`` span, one observation a pass, ``stats["h_pass_ms"]``):
beside ``iter_wall_ms``, the window's mean, it says whether a slow tail of
``tpot_p95_ms`` was slow passes."""

from perf.harness.window_hist import window_percentile_ms

NAME = "pass_wall_p95_ms"
UNIT = "ms"
LAYER = "engine, serving"
MOVES = "tpot_p95_ms"


def read(run):
    return window_percentile_ms(run, "h_pass_ms")

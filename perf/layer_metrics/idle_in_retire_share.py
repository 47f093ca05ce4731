"""Share of the first chip's idle time in the traced stretch that lies
inside the scheduler's retire phase, on the profiler's one clock: the idle
gaps between the device's operations against the intervals of the retire
phase. The phase is the program's span ``mxtpu.sched.retire`` or the
profiler's Python frame of the function it brackets
(``$batcher.py:<line> _retire``): the harness keeps only the second today,
and the first is taken as soon as it keeps ``mxtpu.*`` host events."""

from perf.harness import phases
from perf.harness.trace import gaps_ns

NAME = "idle_in_retire_share"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SPAN = "mxtpu.sched.retire"
FRAME = r"^\$batcher\.py:\d+ _retire$"


def read(run):
    t = run.trace
    if t is None or MOVES not in run.e2e or not t.devices:
        return None
    retire = phases.intervals(t.host_spans, SPAN, FRAME)
    gaps = gaps_ns([(s, e) for _, s, e in t._dev[t.devices[0]]], t.lo, t.hi)
    idle = sum(e - s for s, e in gaps)
    if not retire or not idle:
        return None
    return 100.0 * phases.overlap_ns(gaps, retire) / idle

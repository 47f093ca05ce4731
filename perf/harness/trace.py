"""Reduction of a profiler trace to device busy time, kernel time and the
host's part in each idle gap.

The reduction works on plain event tuples ``(plane, line, name, start_ns,
duration_ns)`` so that it can be checked on a hand-made list;
``events_from_xplane`` makes them from the ``.xplane.pb`` that
``jax.profiler`` writes, with nothing but JAX. What the first real trace
showed (TPU v5 lite, jax 0.9.0) is written beside each constant.
"""

import glob
import os
import re
import shutil
from collections import defaultdict, namedtuple

Event = namedtuple("Event", "plane line name start_ns dur_ns")

# one plane per chip, named "/device:TPU:<n>"; the operations a program
# runs on the chip are the events of its "XLA Ops" line ("XLA Modules" holds
# one event per whole program, "Steps" one per step)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
# spans the benchmark's own files put on the host's timeline
HOST_SPAN_PREFIX = "perf."
WINDOW_SPAN = "perf.window"
UNATTRIBUTED = "host:no_benchmark_span"


PYTHON_FRAME = "$"        # the profiler's Python tracer: "$file.py:123 func"
MIN_FRAME_NS = 20_000     # shorter frames explain no gap worth a line


def _keep_host(name, dur_ns):
    return name.startswith(HOST_SPAN_PREFIX) or (
        name.startswith(PYTHON_FRAME) and dur_ns >= MIN_FRAME_NS)


def short_name(name, limit=96):
    """``%fusion.21 = bf16[30522,768]`` of an operation's full HLO text."""
    return name.split("{", 1)[0].split("(", 1)[0].strip()[:limit]


def events_from_xplane(path, keep_host=_keep_host):
    """Device operations, the benchmark's host spans and the longer Python
    frames of one trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if device and line.name != OP_LINE:
                continue
            for e in line.events:
                if device or keep_host(e.name, e.duration_ns):
                    out.append(Event(plane.name, line.name, e.name,
                                     int(e.start_ns), int(e.duration_ns)))
    return out


def xplane_files(trace_dir):
    return sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))


def union_ns(intervals):
    """Total length covered by ``(start, end)`` intervals that may overlap."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo, hi):
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def _clip(s, e, lo, hi):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


class TraceSummary:
    """What the per-layer readers and the result line take from a trace."""

    def __init__(self, events, chips=None):
        host = [e for e in events if not DEVICE_PLANE.match(e.plane)]
        windows = [e for e in host if e.name == WINDOW_SPAN]
        dev = defaultdict(list)
        for e in events:
            m = DEVICE_PLANE.match(e.plane)
            if m:
                dev[int(m.group(1))].append(e)
        if chips is not None:
            dev = {k: v for k, v in dev.items() if k < chips}
        self.devices = sorted(dev)
        if windows:
            self.lo = min(w.start_ns for w in windows)
            self.hi = max(w.start_ns + w.dur_ns for w in windows)
        elif dev:
            every = [e for v in dev.values() for e in v]
            self.lo = min(e.start_ns for e in every)
            self.hi = max(e.start_ns + e.dur_ns for e in every)
        else:
            self.lo = self.hi = 0
        self.window_s = (self.hi - self.lo) / 1e9
        self._dev = {}
        for d, evs in dev.items():
            clipped = []
            for e in evs:
                c = _clip(e.start_ns, e.start_ns + e.dur_ns, self.lo, self.hi)
                if c:
                    clipped.append((e.name, c[0], c[1]))
            self._dev[d] = clipped
        self.host_spans = [
            (e.name, e.start_ns, e.start_ns + e.dur_ns) for e in host
            if e.name != WINDOW_SPAN]

    # ------------------------------------------------------------ busy
    def busy_s_of(self, device):
        return union_ns([(s, e) for _, s, e in self._dev[device]]) / 1e9

    @property
    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(self.busy_s_of(d) for d in self.devices) / len(self.devices)

    @property
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s if self.window_s else None

    # ---------------------------------------------------------- kernels
    def op_seconds(self, pattern=None, device=None):
        """``(seconds, calls)`` of the device operations whose name matches
        ``pattern`` (all of them when None), on one chip (the first)."""
        if not self.devices:
            return 0.0, 0
        d = self.devices[0] if device is None else device
        rx = re.compile(pattern) if pattern else None
        hit = [(s, e) for n, s, e in self._dev[d] if not rx or rx.search(n)]
        return sum(e - s for s, e in hit) / 1e9, len(hit)

    def top_ops(self, n=10):
        if not self.devices:
            return []
        by = defaultdict(float)
        for name, s, e in self._dev[self.devices[0]]:
            by[short_name(name)] += (e - s) / 1e9
        return [[k, v] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:n]]

    # -------------------------------------------------------------- gaps
    def idle_gaps(self, n=10):
        """Idle time of the first chip by what the host was doing: each gap
        goes to the shortest host span (the benchmark's own, or a Python
        frame) that covers half of it or more, else to the one that covers
        most of it."""
        if not self.devices:
            return []
        d = self.devices[0]
        by = defaultdict(float)
        spans = sorted(self.host_spans, key=lambda x: x[1])
        for gs, ge in gaps_ns([(s, e) for _, s, e in self._dev[d]],
                              self.lo, self.hi):
            best, best_ov, inner, inner_len = UNATTRIBUTED, 0, None, None
            for name, s, e in spans:
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                if ov > best_ov:
                    best, best_ov = name, ov
                if 2 * ov >= ge - gs and (inner is None or e - s < inner_len):
                    inner, inner_len = name, e - s
            by[inner or best] += (ge - gs) / 1e9
        return [[k, v] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:n]]

    def breakdown(self):
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


class Tracer:
    """Traces a stretch of the measured window with ``jax.profiler`` into a
    directory inside the checkout, reduces it and deletes the raw trace."""

    AFTER_S = 2.0    # steady seconds of window before the traced stretch
    SECONDS = 2.0    # its length: traces are large and tracing slows the host

    def __init__(self, enabled, directory, chips):
        self.enabled = enabled
        self.directory = directory
        self.chips = chips
        self._span = None

    def stretch(self, window_s):
        """``(start, length)`` of the traced stretch inside a window, in
        seconds from its opening; a short (rehearsal) window scales it."""
        return (min(self.AFTER_S, window_s / 4),
                min(self.SECONDS, window_s / 4))

    def start(self):
        if not self.enabled:
            return
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(self.directory)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self):
        if not self.enabled or self._span is None:
            return
        import jax

        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()

    def span(self, name):
        """A host span on the profiler's timeline (free when not tracing)."""
        import jax

        return jax.profiler.TraceAnnotation(HOST_SPAN_PREFIX + name)

    def reduce(self):
        if not self.enabled:
            return None
        files = xplane_files(self.directory)
        if not files:
            raise SystemExit("perf: the profiler wrote no .xplane.pb")
        events = []
        for f in files:
            events.extend(events_from_xplane(f))
        shutil.rmtree(self.directory, ignore_errors=True)
        return TraceSummary(events, chips=self.chips)

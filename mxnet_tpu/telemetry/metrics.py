"""Metrics registry: counters, gauges, rolling histograms.

One process-global registry is the telemetry spine: the trainer records
step wall time and samples, the kvstore records allreduce bytes/latency,
``profiler.py``'s aggregate per-op stats live here too (``op/`` prefix),
and ``jax.monitoring`` compile events land under ``jax/``. The registry is
always usable (metric objects are a few machine words); the telemetry
ENABLED flag gates only hot-path instrumentation and event emission.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, Optional

import numpy as np

__all__ = ["Counter", "Gauge", "Histogram", "Registry",
           "merge_summaries", "BUCKET_CELLS", "BucketBlock", "bucket_of",
           "bucket_percentile"]

# ---------------------------------------------------- window histograms
# A histogram whose two snapshots can be subtracted: an int64 array of
# counts over FIXED log-spaced edges, so that ``later - earlier`` is the
# histogram of what was observed between the two (the serving scheduler
# keeps its request parts and its passes this way in ``stats``, and a
# benchmark reads a window's own 95th percentile from the difference).
# Cell 0 holds [0, LO); cell i of 1..N holds [LO * R**(i-1), LO * R**i)
# with R = 2**(1/48), so an edge lies at most 1.46 % above the one below
# and a percentile read back is within that of the exact one whatever
# the sample (below LO: within LO); cell N+1 holds everything from HI
# on; the last cell is the SUM of the observations in whole nanoseconds
# (a mean, and the exact value where one observation was made).
_PER_OCTAVE = 48
_LO_LOG2, _HI_LOG2 = -6, 17          # 2**-6 ms (15.6 us) .. 2**17 ms (131 s)
_N = (_HI_LOG2 - _LO_LOG2) * _PER_OCTAVE
_LO, _HI = 2.0 ** _LO_LOG2, 2.0 ** _HI_LOG2
BUCKET_CELLS = _N + 3


def bucket_of(ms: float) -> int:
    """The cell of an observation in milliseconds."""
    if ms < _LO:
        return 0
    if ms >= _HI:
        return _N + 1
    return int((math.log2(ms) - _LO_LOG2) * _PER_OCTAVE) + 1


class BucketBlock:
    """The window histograms of one owner, a row of ``BUCKET_CELLS`` a key
    in ONE int64 block, made for a hot path. ``observe`` is an append by
    the owner's thread and no more; ``flush`` does the arithmetic and
    turns what was observed since the last one into a NEW block, whose
    rows it returns for the owner to publish (the owner calls it where
    its thread would otherwise wait: the serving scheduler while the
    device runs a burst). An earlier block is never written again, so
    rows handed out before stay a sound snapshot."""

    __slots__ = ("keys", "observe", "_base", "_block", "_seen")

    def __init__(self, keys):
        self.keys = tuple(keys)
        self._base = {k: i * BUCKET_CELLS for i, k in enumerate(self.keys)}
        self._block = np.zeros((len(self.keys), BUCKET_CELLS), np.int64)
        self._seen = []         # (key, milliseconds), not yet bucketed
        self.observe = self._seen.append   # observe((key, ms))

    def rows(self) -> dict:
        """``{key: its row of the block as it stands}``."""
        return {k: self._block[i] for i, k in enumerate(self.keys)}

    def flush(self):
        """The rows of a new block that holds every observation so far;
        None where nothing was observed since the last flush."""
        if not self._seen:
            return None
        cells, sums, base = {}, {}, self._base   # flat cell -> count; ms
        for key, ms in self._seen:
            i = base[key] + bucket_of(ms)
            cells[i] = cells.get(i, 0) + 1
            sums[key] = sums.get(key, 0.0) + ms
        self._seen.clear()
        block = self._block.copy()
        flat = block.reshape(-1)
        # the cells are distinct, so one indexed add takes them all
        flat[list(cells)] += list(cells.values())
        for key, ms in sums.items():
            flat[base[key] + BUCKET_CELLS - 1] += int(ms * 1e6)
        self._block = block
        return self.rows()


def _edge(i: int) -> float:
    """Lower edge of cell ``i`` (1..N+1) in milliseconds."""
    return 2.0 ** (_LO_LOG2 + (i - 1) / _PER_OCTAVE)


def bucket_percentile(counts, p: float) -> Optional[float]:
    """The ``p``-th percentile (0..100) in milliseconds of a window
    histogram, or of a difference of two, as
    ``perf/harness/clock.percentile`` takes it: interpolated between the
    two nearest ranks, each rank placed inside its cell as if the cell's
    observations lay evenly across it. None where nothing was observed;
    the observation itself where there was one; 0 where every
    observation was 0; ``HI`` for a rank in the last cell (read it as
    "at least")."""
    cells = [int(c) for c in counts[:-1]]
    n = sum(cells)
    if n <= 0:
        return None
    total_ns = int(counts[-1])
    if n == 1:
        return total_ns / 1e6
    rank = (p / 100.0) * (n - 1)
    lo = int(rank)
    hi = min(lo + 1, n - 1)

    def at(j):
        seen = 0
        for i, c in enumerate(cells):
            if j < seen + c:
                if i == 0:
                    return 0.0 if total_ns == 0 \
                        else _LO * (j - seen + 0.5) / c
                if i == _N + 1:
                    return _HI
                a = _edge(i)
                return a + (_edge(i + 1) - a) * (j - seen + 0.5) / c
            seen += c
        return _HI

    return at(lo) * (1 - (rank - lo)) + at(hi) * (rank - lo)


def merge_summaries(summaries) -> dict:
    """Merge per-replica ``Histogram.summary()`` dicts into one fleet
    summary (the scrape/aggregation plane, ``serving.tracing``).

    ``count``/``sum`` add exactly and ``min``/``max`` take extremes, so
    the fleet mean is exact. Percentiles cannot be recovered from
    summaries — the merged p50/p95/p99 are the count-weighted average of
    the inputs' percentiles, a documented approximation that is exact
    when the replicas' distributions agree and deterministic always
    (replaying a recorded scrape stream re-derives identical values).
    Empty inputs (count 0) are ignored; all-empty merges to the empty
    summary."""
    live = [s for s in summaries if s and s.get("count")]
    if not live:
        return {"count": 0, "sum": 0.0, "mean": None, "min": None,
                "max": None, "p50": None, "p95": None, "p99": None}
    count = sum(s["count"] for s in live)
    total = sum(s["sum"] for s in live)
    out = {
        "count": count,
        "sum": total,
        "mean": total / count,
        "min": min(s["min"] for s in live if s["min"] is not None),
        "max": max(s["max"] for s in live if s["max"] is not None),
    }
    for q in ("p50", "p95", "p99"):
        vals = [(s[q], s["count"]) for s in live if s[q] is not None]
        w = sum(c for _v, c in vals)
        out[q] = sum(v * c for v, c in vals) / w if w else None
    return out


class Counter:
    """Monotonic counter (allreduce bytes, samples, stall count)."""

    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n=1):
        with self._lock:
            self._value += n

    @property
    def value(self):
        return self._value


class Gauge:
    """Last-write-wins value (HBM high-water mark, queue depth)."""

    __slots__ = ("_value",)

    def __init__(self):
        self._value = None

    def set(self, v):
        self._value = v

    def max(self, v):
        """Retain the high-water mark."""
        if self._value is None or v > self._value:
            self._value = v

    @property
    def value(self):
        return self._value


class Histogram:
    """Rolling-window histogram with cumulative count/sum.

    Percentiles come from the last ``window`` observations (a ring
    buffer — O(window) memory regardless of run length); ``count`` and
    ``sum`` are cumulative so rates (samples/sec over the whole run)
    stay exact.
    """

    __slots__ = ("_ring", "_idx", "_filled", "_count", "_sum", "_min",
                 "_max", "_lock", "window")

    def __init__(self, window: int = 1024):
        self.window = window
        self._ring = [0.0] * window
        self._idx = 0
        self._filled = 0
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf
        self._lock = threading.Lock()

    def observe(self, v: float):
        v = float(v)
        with self._lock:
            self._ring[self._idx] = v
            self._idx = (self._idx + 1) % self.window
            if self._filled < self.window:
                self._filled += 1
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def _window_sorted(self):
        with self._lock:
            vals = self._ring[: self._filled]
        return sorted(vals)

    def last(self, n: int) -> list:
        """The newest ``n`` observations the window still holds, oldest
        first (fewer when fewer were made or kept): a reader that knows
        how many of them are its own takes those and no others."""
        with self._lock:
            n = max(0, min(n, self._filled))
            start = self._idx - n
            if start >= 0:
                return self._ring[start:self._idx]
            return self._ring[start:] + self._ring[:self._idx]

    def percentile(self, p: float) -> Optional[float]:
        """Nearest-rank-with-interpolation percentile of the rolling
        window; None when nothing was observed."""
        vals = self._window_sorted()
        if not vals:
            return None
        if len(vals) == 1:
            return vals[0]
        rank = (p / 100.0) * (len(vals) - 1)
        lo = int(math.floor(rank))
        hi = min(lo + 1, len(vals) - 1)
        frac = rank - lo
        return vals[lo] * (1 - frac) + vals[hi] * frac

    def summary(self) -> dict:
        vals = self._window_sorted()
        if not vals:
            return {"count": 0, "sum": 0.0, "mean": None, "min": None,
                    "max": None, "p50": None, "p95": None, "p99": None}
        return {
            "count": self._count,
            "sum": self._sum,
            "mean": self._sum / self._count,
            "min": self._min,
            "max": self._max,
            "p50": self.percentile(50),
            "p95": self.percentile(95),
            "p99": self.percentile(99),
        }


class Registry:
    """Get-or-create metric registry, thread-safe, name-keyed."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            m = self._counters.get(name)
            if m is None:
                m = self._counters[name] = Counter()
            return m

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            m = self._gauges.get(name)
            if m is None:
                m = self._gauges[name] = Gauge()
            return m

    def histogram(self, name: str, window: int = 1024) -> Histogram:
        with self._lock:
            m = self._histograms.get(name)
            if m is None:
                m = self._histograms[name] = Histogram(window)
            return m

    def clear(self, prefix: Optional[str] = None):
        """Drop metrics (all, or those whose name starts with prefix) —
        used by ``profiler.dumps(reset=True)`` for its ``op/`` family."""
        with self._lock:
            for d in (self._counters, self._gauges, self._histograms):
                if prefix is None:
                    d.clear()
                else:
                    for k in [k for k in d if k.startswith(prefix)]:
                        del d[k]

    def snapshot(self) -> dict:
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            histograms = dict(self._histograms)
        return {
            "counters": {k: v.value for k, v in counters.items()},
            "gauges": {k: v.value for k, v in gauges.items()},
            "histograms": {k: v.summary() for k, v in histograms.items()},
        }

    def histograms_with_prefix(self, prefix: str):
        with self._lock:
            return {k: v for k, v in self._histograms.items()
                    if k.startswith(prefix)}

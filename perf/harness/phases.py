"""Arithmetic shared by the per-layer readers of the program's own phase
spans (``mxnet_tpu.telemetry.phase``).

Two sinks of a phase reach a reader. Its cumulative seconds are in
``ContinuousBatcher.stats``, which the serving driver copies at the
window's two ends (``run.obs["stats0"]`` / ``["stats1"]``):
``per_iteration_ms`` turns a difference of them into milliseconds of one
scheduler iteration. Its span is on the profiler's timeline beside the
device's operations: ``intervals`` finds it there and ``overlap_ns`` says
how much of the device's idle gaps lies inside it. A program that has no
such counter or span gives None or nothing, never an error.
"""

import re


def per_iteration_ms(run, plus, minus=()):
    """Milliseconds per scheduler iteration of the window spent in the
    phases ``plus`` less those in ``minus`` (a phase's self time is its
    own seconds less its children's). None where a key is missing or the
    window held no iteration."""
    a, b = run.obs.get("stats0"), run.obs.get("stats1")
    if not a or not b:
        return None
    if any(k not in a or k not in b for k in (*plus, *minus)):
        return None
    iterations = b["iterations"] - a["iterations"]
    if not iterations:
        return None
    seconds = sum(b[k] - a[k] for k in plus) \
        - sum(b[k] - a[k] for k in minus)
    return 1e3 * seconds / iterations


def intervals(host_spans, span, frame):
    """``(start, end)`` of the host spans ``(name, start, end)`` that are a
    phase: the program's span of that name, or the profiler's Python frame
    of the function the span brackets (``frame``, a pattern). Both forms
    of one call overlap and count once in ``overlap_ns``."""
    rx = re.compile(frame)
    return [(s, e) for name, s, e in host_spans
            if name == span or rx.match(name)]


def overlap_ns(gaps, spans):
    """Nanoseconds of the disjoint ``gaps`` that the union of ``spans``
    covers."""
    merged = []
    for s, e in sorted(spans):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    total = 0
    for gs, ge in gaps:
        for s, e in merged:
            if s >= ge:
                break
            total += max(0, min(e, ge) - max(s, gs))
    return total

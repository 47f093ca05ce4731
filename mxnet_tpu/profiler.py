"""Profiler facade (reference: ``python/mxnet/profiler.py`` over
``src/profiler/profiler.cc`` [unverified]).

The reference instrumented every engine op push and dumped Chrome-trace
JSON. On TPU the equivalent telemetry comes from XLA's profiler (XProf):
``jax.profiler`` emits a trace viewable in TensorBoard/Perfetto covering
compiled-program timelines, HBM usage, and per-op device time. This module
keeps the reference's API shape (set_config/start/stop/dump + scopes) over
that machinery.

Host-side aggregate per-call stats live in the ``mx.telemetry`` metrics
registry (the ``op/`` histogram family) — ONE telemetry spine: Scopes feed
the same registry the trainer/kvstore/dataloader instrumentation uses, so
``mx.telemetry.report()`` and ``profiler.dumps()`` read consistent data,
and ``profiler.dump()`` merges the registry aggregates with any buffered
telemetry spans into one Chrome trace.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
from typing import Dict, Optional

import jax

from . import telemetry as _telemetry
from .base import MXNetError

__all__ = [
    "set_config",
    "start",
    "stop",
    "pause",
    "resume",
    "dump",
    "dumps",
    "set_state",
    "Scope",
    "Task",
    "Frame",
    "Event",
    "Counter",
    "Marker",
]

_CONFIG = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": True,
    "profile_api": True,
    "aggregate_stats": False,
}
_STATE = {"running": False, "dir": None}
_LOCK = threading.Lock()
_OP_PREFIX = "op/"  # registry family holding per-op aggregate stats


def set_config(**kwargs):
    """Reference: ``mx.profiler.set_config`` (filename, profile_all, …)."""
    for k, v in kwargs.items():
        _CONFIG[k] = v


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        start()
    elif state == "stop":
        stop()
    else:
        raise MXNetError(f"invalid profiler state {state!r}")


def start(profile_process="worker"):
    """Start an XProf trace (plus host aggregate stats)."""
    if _STATE["running"]:
        return
    trace_dir = os.path.splitext(_CONFIG["filename"])[0] + "_xplane"
    _STATE["dir"] = trace_dir
    try:
        jax.profiler.start_trace(trace_dir)
    except Exception:
        # tracing may be unsupported on some backends; keep host stats only
        _STATE["dir"] = None
    _STATE["running"] = True


def stop(profile_process="worker"):
    if not _STATE["running"]:
        return
    if _STATE["dir"] is not None:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
    _STATE["running"] = False


def pause(profile_process="worker"):
    stop()


def resume(profile_process="worker"):
    start()


def record_host_op(name: str, seconds: float):
    """Hook used by the imperative layer when aggregate stats are enabled.

    Rebased onto the telemetry registry: each op is a rolling histogram
    under ``op/{name}`` (cumulative count/sum preserved), so the same
    spine serves ``dumps()``, ``mx.telemetry.report()`` and the bench
    schema."""
    _telemetry.registry().histogram(_OP_PREFIX + name).observe(seconds)


def _op_rows():
    """(name, count, total_s) rows from the registry's op/ family."""
    hists = _telemetry.registry().histograms_with_prefix(_OP_PREFIX)
    return [(name[len(_OP_PREFIX):], h.count, h.sum)
            for name, h in hists.items()]


def dumps(reset=False) -> str:
    """Aggregate per-op stats table (reference: ``mx.profiler.dumps``)."""
    rows = sorted(_op_rows(), key=lambda r: -r[2])
    lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(us)':>10}"]
    for name, count, total in rows:
        lines.append(
            f"{name:<40}{count:>8}{total * 1e3:>12.2f}"
            f"{total / max(count, 1) * 1e6:>10.1f}"
        )
    if reset:
        _telemetry.registry().clear(prefix=_OP_PREFIX)
    return "\n".join(lines)


def dump(finished=True, profile_process="worker"):
    """Write host-side aggregate stats (plus any buffered telemetry spans)
    as ONE Chrome-trace JSON; the XProf trace directory (if any) sits next
    to it for TensorBoard."""
    stop()
    events = []
    ts = 0
    for name, count, total in _op_rows():
        events.append(
            {
                "name": name,
                "ph": "X",
                "ts": ts,
                "dur": total * 1e6,
                "pid": 0,
                "tid": 0,
                "args": {"calls": count},
            }
        )
        ts += total * 1e6
    log = _telemetry._LOG
    if log is not None:
        events.extend(log.chrome_events())
    with open(_CONFIG["filename"], "w") as f:
        json.dump({"traceEvents": events}, f)


class Scope:
    """Annotation scope; shows up in the XProf timeline as
    ``mxtpu.<name>`` (reference: profiler scopes / NVTX ranges). It is
    ``telemetry.phase`` with the ``op/`` histogram as its accumulator's
    reader."""

    def __init__(self, name="<unk>", append_mode=True):
        self._name = name

    def __enter__(self):
        self._spent = [0.0]
        self._phase = _telemetry.phase(self._name, self._spent, 0)
        self._phase.__enter__()
        return self

    def __exit__(self, *exc):
        self._phase.__exit__(*exc)
        record_host_op(self._name, self._spent[0])
        return False


class Task(Scope):
    def __init__(self, domain=None, name="<unk>"):
        super().__init__(name)


class Frame(Scope):
    def __init__(self, domain=None, name="<unk>"):
        super().__init__(name)


class Event(Scope):
    def __init__(self, name="<unk>"):
        super().__init__(name)


class Counter:
    def __init__(self, domain=None, name="<unk>", value=None):
        self._name = name
        self._value = value or 0

    def set_value(self, value):
        self._value = value

    def increment(self, delta=1):
        self._value += delta

    def decrement(self, delta=1):
        self._value -= delta


class Marker:
    def __init__(self, domain=None, name="<unk>"):
        self._name = name

    def mark(self, scope="process"):
        record_host_op(f"marker:{self._name}", 0.0)


atexit.register(stop)

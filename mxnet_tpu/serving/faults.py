"""Fault-injection harness: deterministic failure points for the serving
plane.

A resilience feature that is never exercised is a liability: the failover
and hot-swap paths must be drivable through their FAILURE branches in
tier-1, on demand, without flaky sleeps or real crashes. This module
plants named failure points in the serving hot paths; each point is inert
(one dict lookup) until armed, either programmatically (``inject()`` in
tests) or by environment spec (``MXTPU_FAULT_*`` — the chaos-harness
contract, usable against a real serving process).

Failure points wired in this package:

==================== ====================================================
``batcher.dispatch``  raises before a ``ContinuousBatcher`` dispatch
                      (decode burst, admission prefill, prefix hits) —
                      the engine call fails, futures get the error, the
                      dispatcher thread survives.
``batcher.thread``    raises at the top of the dispatcher loop, OUTSIDE
                      the dispatch try — the thread dies, simulating a
                      crashed replica (``healthy`` flips false).
``batcher.hang``      sleeps ``delay`` seconds inside the dispatch — a
                      wedged engine (watchdog heartbeat goes stale).
``watchdog.heartbeat`` suppresses heartbeat writes — a stale heartbeat
                      with the process otherwise alive.
``ckpt.load``         raises inside ``CheckpointWatcher``'s load (and the
                      worker's ``stage`` verb) — a torn / unreadable
                      checkpoint mid-swap.
``transport.send``    fires before a frame write (client or server side
                      of the cross-process RPC): raise-mode drops the
                      connection, delay-mode is a slow link; armed
                      ``times=None`` on send AND recv = a partition.
``transport.recv``    the receive half of the same — fires before a
                      frame read; tags are the client/server name.
``worker.exit``       hard-kills a serving worker process from inside
                      its main loop (``os._exit``) — sudden process
                      death on a deterministic schedule.
``transport.kv_push`` fires in a prefill-role worker's KV-handoff push
                      path (socket or spill): raise-mode drops the
                      handoff (the decode side re-prefills from the
                      prompt, ``disagg/re_prefills``), delay-mode is a
                      slow push; tags are the ``push_to`` address.
``router.place``      fires inside the router's placement decision:
                      raise-mode makes that pass place nothing (the
                      monitor retries), delay-mode is a slow placement;
                      tags are the request class.
==================== ====================================================

Env spec grammar (one var per point, ``.`` becomes ``_``)::

    MXTPU_FAULT_BATCHER_THREAD="times=1;after=2;match=replica-1"
    MXTPU_FAULT_BATCHER_HANG="delay=30"
    MXTPU_FAULT_WATCHDOG_HEARTBEAT="on"

``times`` caps how often the fault fires (default 1; ``on``/``1`` alone
means unlimited), ``after`` skips the first N matching hits, ``delay``
makes the point sleep instead of raise, ``match`` restricts the fault to
call sites whose tag (replica/batcher name, directory) contains the
substring. Hits and firings are counted per spec — deterministic
("the 3rd dispatch fails once") rather than probabilistic.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from ..base import MXNetError

__all__ = ["FaultInjected", "inject", "clear", "check", "fire",
           "specs"]


class FaultInjected(MXNetError):
    """Raised by an armed raise-mode failure point."""


class _Spec:
    __slots__ = ("point", "times", "after", "delay", "match", "hits",
                 "fired", "source")

    def __init__(self, point, times=1, after=0, delay=0.0, match=None,
                 source="inject"):
        self.point = point
        self.times = times  # None = unlimited
        self.after = int(after)
        self.delay = float(delay)
        self.match = match
        self.hits = 0
        self.fired = 0
        self.source = source

    def matches(self, tag) -> bool:
        if self.match is None:
            return True
        return tag is not None and self.match in str(tag)

    def try_fire(self) -> bool:
        """Count one matching hit; True iff the fault fires on it."""
        self.hits += 1
        if self.hits <= self.after:
            return False
        if self.times is not None and self.fired >= self.times:
            return False
        self.fired += 1
        return True

    def describe(self) -> dict:
        return {"point": self.point, "times": self.times,
                "after": self.after, "delay": self.delay,
                "match": self.match, "hits": self.hits,
                "fired": self.fired, "source": self.source}


_LOCK = threading.Lock()
_SPECS: dict = {}  # point -> list[_Spec]
_ENV_SCANNED: set = set()  # points whose MXTPU_FAULT_* var was parsed


def _env_var(point: str) -> str:
    return "MXTPU_FAULT_" + point.upper().replace(".", "_")


def _parse_env_spec(point: str, raw: str) -> Optional[_Spec]:
    raw = raw.strip()
    if raw.lower() in ("", "0", "off", "false"):
        return None
    kw = {"times": None, "after": 0, "delay": 0.0, "match": None}
    if raw.lower() not in ("1", "on", "true"):
        for part in raw.split(";"):
            part = part.strip()
            if not part or part.lower() in ("1", "on", "true"):
                continue
            if "=" not in part:
                raise MXNetError(
                    f"bad fault spec {_env_var(point)}={raw!r}: "
                    f"expected key=value, got {part!r}")
            k, v = part.split("=", 1)
            k, v = k.strip(), v.strip()
            if k in ("times", "after"):
                kw[k] = int(v)
            elif k == "delay":
                kw[k] = float(v)
            elif k == "match":
                kw[k] = v
            else:
                raise MXNetError(
                    f"bad fault spec {_env_var(point)}={raw!r}: "
                    f"unknown key {k!r} (times/after/delay/match)")
    return _Spec(point, source="env", **kw)


def inject(point: str, times: Optional[int] = 1, after: int = 0,
           delay: float = 0.0, match: Optional[str] = None) -> None:
    """Arm ``point`` programmatically (tests / chaos drivers).

    ``times=None`` fires on every matching hit; ``delay`` turns the point
    into a sleep instead of a raise; ``match`` restricts it to tags
    containing the substring."""
    with _LOCK:
        _SPECS.setdefault(point, []).append(
            _Spec(point, times=times, after=after, delay=delay,
                  match=match))


def clear(point: Optional[str] = None) -> None:
    """Disarm one point, or everything (including the env-spec cache, so
    a monkeypatched ``MXTPU_FAULT_*`` is re-read)."""
    with _LOCK:
        if point is None:
            _SPECS.clear()
            _ENV_SCANNED.clear()
        else:
            _SPECS.pop(point, None)
            _ENV_SCANNED.discard(point)


def specs() -> list:
    """Snapshot of every armed spec (hit/fire counters included)."""
    with _LOCK:
        return [s.describe() for ss in _SPECS.values() for s in ss]


def check(point: str, tag=None) -> Optional[dict]:
    """Consume one firing of ``point`` if armed and matching.

    Returns the firing spec's description (``delay`` tells the caller to
    stall instead of fail) or None. Used directly by suppress-style call
    sites (the watchdog skips a heartbeat write when this returns
    non-None); raise/sleep sites go through :func:`fire`."""
    with _LOCK:
        if point not in _ENV_SCANNED:
            _ENV_SCANNED.add(point)
            raw = os.environ.get(_env_var(point))
            if raw is not None:
                spec = _parse_env_spec(point, raw)
                if spec is not None:
                    _SPECS.setdefault(point, []).append(spec)
        for spec in _SPECS.get(point, ()):
            if spec.matches(tag) and spec.try_fire():
                fired = spec.describe()
                break
        else:
            return None
    # counter outside the lock: telemetry must not serialize hot paths
    try:
        from .. import telemetry as _tel
        from . import tracing as _tracing  # lazy: no import cycle

        _tel.registry().counter("serve/faults_injected").inc()
        _tel.instant("serve.fault",
                     {"point": point, "tag": tag, "spec": fired,
                      "request_id": _tracing.current_request_id()})
    except Exception:  # noqa: BLE001 - accounting must not mask the fault
        pass
    return fired


def fire(point: str, tag=None) -> None:
    """Trip ``point`` if armed: sleep ``delay`` seconds when the spec is
    delay-mode, else raise :class:`FaultInjected`. No-op when unarmed —
    this is the one-liner planted in hot paths."""
    spec = check(point, tag)
    if spec is None:
        return
    if spec["delay"] > 0:
        time.sleep(spec["delay"])
        return
    raise FaultInjected(
        f"injected fault at {point!r}"
        + (f" (tag={tag})" if tag is not None else ""))

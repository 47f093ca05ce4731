"""Heartbeat file + hang/slow-step watchdog.

Motivation: a run that hangs and still exits 0 is indistinguishable
from a clean one. This module makes liveness a first-class artifact:

- a background thread writes ``heartbeat.json`` (last completed step +
  wall/monotonic timestamps) every ``interval`` seconds, so an external
  supervisor can distinguish "alive and stepping" from "wedged" without
  attaching anything to the process;
- a STALL fires when no step completes for ``stall_factor`` x the
  rolling-MEDIAN step time (floored at ``min_stall_s``): the watchdog
  dumps every thread's stack via ``faulthandler`` (signal handlers cannot
  preempt a main thread blocked inside a C call, but
  faulthandler runs from THIS thread and inspects the others) and emits a
  telemetry instant event;
- a HARD HANG (no progress for ``hard_timeout_s``) dumps stacks one last
  time, flushes the heartbeat with ``status: "hard_hang"`` and
  ``os._exit``\\ s nonzero so the process status finally agrees with
  reality.
"""

from __future__ import annotations

import collections
import faulthandler
import json
import os
import statistics
import threading
import time
from typing import Callable, Optional

__all__ = ["Watchdog", "read_heartbeat"]


def read_heartbeat(path: str) -> Optional[dict]:
    """Parse a ``heartbeat.json``; None when missing or torn.

    The writer publishes via ``os.replace`` so a torn read should be
    impossible on a POSIX filesystem — but a health check must never
    crash on a weird one, so decode failures degrade to None (= unknown)
    rather than raising."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


class Watchdog:
    """Progress monitor for step-structured work.

    ``notify_step(seconds)`` is the only hot-path call (lock + deque
    append). Everything else happens on the watchdog thread.
    """

    def __init__(self, directory: str, interval: float = 5.0,
                 stall_factor: float = 10.0, min_stall_s: float = 30.0,
                 hard_timeout_s: Optional[float] = None,
                 exit_code: int = 43,
                 on_stall: Optional[Callable[[dict], None]] = None,
                 _exit_fn: Optional[Callable[[int], None]] = None):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self.heartbeat_path = os.path.join(directory, "heartbeat.json")
        self.interval = float(interval)
        self.stall_factor = float(stall_factor)
        self.min_stall_s = float(min_stall_s)
        self.hard_timeout_s = hard_timeout_s
        self.exit_code = int(exit_code)
        self.on_stall = on_stall
        self._exit_fn = _exit_fn or os._exit
        self._lock = threading.Lock()
        self._step = 0
        self._last_progress = time.monotonic()
        self._step_times = collections.deque(maxlen=64)
        self._inflight = 0
        self._last_request_id = None
        self._requests_completed = 0
        self._stalled = False
        self.stall_count = 0
        self._stop = threading.Event()
        self._thread = None

    # ------------------------------------------------------------ control
    def start(self):
        if self._thread is not None:
            return self
        self._stop.clear()
        self._last_progress = time.monotonic()
        self._thread = threading.Thread(
            target=self._run, name="mxtpu-watchdog", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=self.interval + 1.0)
        self._write_heartbeat(status="stopped")

    # ----------------------------------------------------------- hot path
    def notify_step(self, seconds: Optional[float] = None,
                    step: Optional[int] = None):
        with self._lock:
            self._step = self._step + 1 if step is None else int(step)
            self._last_progress = time.monotonic()
            if seconds is not None:
                self._step_times.append(float(seconds))
            self._stalled = False

    def note_request(self, inflight=None, request_id=None, completed=0):
        """Request-level progress for the heartbeat (serving batchers):
        lets a health reader distinguish "hung with work" from "idle"
        straight from ``heartbeat.json``, without an RPC scrape. Same
        hot-path contract as ``notify_step`` — one lock, a few stores."""
        with self._lock:
            if inflight is not None:
                self._inflight = int(inflight)
            if request_id is not None:
                self._last_request_id = request_id
            if completed:
                self._requests_completed += int(completed)

    # ------------------------------------------------------------- thread
    def _stall_threshold(self) -> Optional[float]:
        """None until a step time exists — a run that never stepped is a
        startup/compile phase, not a stall (the hard timeout still
        covers it)."""
        with self._lock:
            if not self._step_times:
                return None
            med = statistics.median(self._step_times)
        return max(self.min_stall_s, self.stall_factor * med)

    def _state(self) -> dict:
        with self._lock:
            idle = time.monotonic() - self._last_progress
            return {
                "step": self._step,
                "idle_s": idle,
                "median_step_s": (statistics.median(self._step_times)
                                  if self._step_times else None),
                "inflight": self._inflight,
                "last_request_id": self._last_request_id,
                "requests_completed": self._requests_completed,
            }

    def _write_heartbeat(self, status="alive"):
        if self._heartbeat_suppressed():
            return
        state = self._state()
        state.update({
            "status": status,
            "pid": os.getpid(),
            "time": time.time(),
            "monotonic": time.monotonic(),
        })
        # atomic publish: unique tmp per writer (two watchdogs sharing a
        # directory never interleave into one tmp file), fsync'd before
        # the rename so the visible file is always complete JSON — a
        # router health-reading this file concurrently can never observe
        # a partial write
        tmp = (f"{self.heartbeat_path}.{os.getpid()}"
               f".{threading.get_ident()}.tmp")
        try:
            with open(tmp, "w") as f:
                json.dump(state, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.heartbeat_path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def _heartbeat_suppressed(self) -> bool:
        """Fault point ``watchdog.heartbeat`` (serving.faults): a stale
        heartbeat with the process otherwise alive — the condition the
        serving router's health scoring must catch."""
        try:
            from ..serving import faults as _faults
        except Exception:  # noqa: BLE001 - minimal installs
            return False
        return _faults.check("watchdog.heartbeat",
                             tag=self.directory) is not None

    def _dump_stacks(self, tag: str) -> Optional[str]:
        path = os.path.join(self.directory, f"stacks_{tag}.txt")
        try:
            with open(path, "w") as f:
                f.write(f"# {tag} at {time.strftime('%Y-%m-%dT%H:%M:%S')} "
                        f"pid={os.getpid()}\n")
                faulthandler.dump_traceback(file=f, all_threads=True)
            return path
        except OSError:
            return None

    def _fire_stall(self):
        state = self._state()
        state["stacks"] = self._dump_stacks(f"stall_step{state['step']}")
        self.stall_count += 1
        self._write_heartbeat(status="stalled")
        try:
            from . import _on_watchdog_stall

            _on_watchdog_stall(state)
        except Exception:  # noqa: BLE001 - telemetry must not kill the run
            pass
        if self.on_stall is not None:
            try:
                self.on_stall(state)
            except Exception:  # noqa: BLE001 - user callback
                pass

    def _run(self):
        while not self._stop.wait(self.interval):
            self._write_heartbeat()
            with self._lock:
                idle = time.monotonic() - self._last_progress
                stalled = self._stalled
            if self.hard_timeout_s is not None and \
                    idle > self.hard_timeout_s:
                self._dump_stacks("hard_hang")
                self._write_heartbeat(status="hard_hang")
                self._exit_fn(self.exit_code)
                return  # only reached with an injected _exit_fn (tests)
            threshold = self._stall_threshold()
            if threshold is not None and idle > threshold and not stalled:
                with self._lock:
                    self._stalled = True
                self._fire_stall()

"""Execution-engine seam: dispatch policy + sync points.

The reference's dependency engine (``src/engine/threaded_engine*.cc``
[unverified]) sequenced asynchronous op closures by read/write variable
dependencies across device worker threads. On TPU, XLA's asynchronous dispatch
plays that role natively: every jax op call enqueues device work and returns a
future-like ``jax.Array``; data dependencies *are* the value graph, so
RAW/WAR/WAW ordering is by construction and the race class the ThreadedEngine
guarded against does not exist (SURVEY.md section 5).

What survives is the *policy seam*:

- ``MXNET_ENGINE_TYPE=NaiveEngine`` selects synchronous execution (each op
  blocks until its results are ready) — the reference's de-facto debugging
  mode for bisecting async issues. ``ThreadedEngine`` /
  ``ThreadedEnginePerDevice`` (the default) mean "let XLA dispatch async".
- ``wait_for_var`` / ``wait_for_all`` are the explicit sync points
  (reference: ``Engine::WaitForVar`` / ``WaitForAll``).
- A bulk-execution hint mirrors ``MXNET_GLUON_EXEC_BULK_SIZE`` but is advisory:
  under ``hybridize()`` the whole graph is one XLA executable, which is the
  limit case of bulking.
"""

from __future__ import annotations

import contextlib
from typing import Iterable

import jax

from .base import env_str

__all__ = [
    "Engine",
    "engine",
    "is_async",
    "wait_for_all",
    "bulk",
    "set_bulk_size",
]


class Engine:
    """Dispatch policy singleton (reference: ``Engine::Get()``)."""

    def __init__(self):
        kind = env_str("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice")
        self._async = kind not in ("NaiveEngine", "naive", "sync")
        self._bulk_size = 0
        self._live_arrays = 0  # informational

    @property
    def kind(self) -> str:
        return "ThreadedEnginePerDevice" if self._async else "NaiveEngine"

    def set_async(self, flag: bool):
        self._async = bool(flag)

    def is_async(self) -> bool:
        return self._async

    def on_outputs(self, arrays: Iterable[jax.Array]):
        """Post-dispatch hook: in naive mode, block until results are ready."""
        if not self._async:
            for a in arrays:
                if hasattr(a, "block_until_ready"):
                    a.block_until_ready()

    # -- sync points --------------------------------------------------------
    @staticmethod
    def wait_for_var(array):
        if hasattr(array, "block_until_ready"):
            array.block_until_ready()

    @staticmethod
    def wait_for_all():
        """Reference: ``Engine::WaitForAll`` — barrier on all pending work."""
        jax.effects_barrier()
        for dev in jax.devices():
            # synchronize per device; jax has no public global barrier, so
            # run a trivial computation and block on it.
            jax.device_put(0, dev).block_until_ready()


_ENGINE = None


def engine() -> Engine:
    global _ENGINE
    if _ENGINE is None:
        _ENGINE = Engine()
    return _ENGINE


def is_async() -> bool:
    return engine().is_async()


def wait_for_all():
    engine().wait_for_all()


def set_bulk_size(size: int) -> int:
    """Advisory (reference: ``MXEngineSetBulkSize``). Returns previous value."""
    eng = engine()
    prev, eng._bulk_size = eng._bulk_size, int(size)
    return prev


@contextlib.contextmanager
def bulk(size: int):
    prev = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(prev)

"""Shape-stable execution: persistent XLA compilation cache + recompile guard.

On TPU the classic failure mode of a variable-shape input pipeline is the
XLA compile storm: every distinct ``(batch, seq_len)`` signature retraces
and recompiles the whole step program, and nothing survives the process,
so elastic restarts and multi-process launches pay the full compile bill
again. This module is the process-level half of the cure (the input-side
half is ``gluon.data.bucketing``; the ahead-of-time half is
``TrainStep.warmup`` / ``CachedOp.warmup``):

- **Persistent compilation cache** — wires JAX's on-disk cache so XLA
  binaries outlive the process. The directory is placed from OUTSIDE:
  where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and
  this module sets no directory at all (it only reports that one);
  otherwise the cache lives at one fixed, git-ignored path inside the
  checkout (``.mxtpu_cache/xla`` — the path is part of the cache key, so
  it never carries a pid, a time or a temporary name). Write thresholds
  are JAX's own (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` /
  ``JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES``).
  ``MXTPU_COMPILE_CACHE_DIR=0``/``off`` wires nothing.

- **Cache hit/miss telemetry** — a ``jax.monitoring`` event listener
  lands ``compile/cache_hits`` and ``compile/cache_misses`` counters in
  the telemetry registry (always-on: the registry is usable even with
  event emission disabled).

- **RecompileGuard** — per-``TrainStep``/``CachedOp`` signature
  accounting: every distinct operand-aval signature is one XLA program,
  so the guard's counters are exact compile counters without touching
  JAX internals (``compile/signatures``,
  ``compile/steady_state_recompiles``). After warmup marks the guard
  steady, a new signature is an *accidental* recompile: it warns, or
  raises once the count exceeds ``MXTPU_RECOMPILE_LIMIT``.

Env knobs: ``MXTPU_COMPILE_CACHE_DIR`` (``0``/``off`` only; the directory
itself is ``JAX_COMPILATION_CACHE_DIR``'s to name),
``MXTPU_RECOMPILE_LIMIT`` (unset = warn-only; ``N`` = raise after N
steady-state recompiles; negative = silence the guard).
"""

from __future__ import annotations

import os
import threading
import warnings
from typing import Optional

from . import telemetry as _tel
from .base import GENERATED_DIR, MXNetError

__all__ = [
    "setup", "is_enabled", "cache_dir", "cache_stats",
    "recompile_limit", "RecompileGuard",
]

_ENABLED = False
_DIR: Optional[str] = None
_LISTENER_INSTALLED = False

# signature-count warning threshold when MXTPU_RECOMPILE_LIMIT is unset:
# a staged cache holding more programs than this is almost certainly
# shape churn, not intent
_DEFAULT_SIG_WARN = 32


def recompile_limit() -> Optional[int]:
    """``MXTPU_RECOMPILE_LIMIT`` parsed: None when unset/empty (warn-only
    guard), an int otherwise (negative silences the guard entirely)."""
    v = os.environ.get("MXTPU_RECOMPILE_LIMIT", "").strip()
    if not v:
        return None
    try:
        return int(v)
    except ValueError:
        warnings.warn(
            f"MXTPU_RECOMPILE_LIMIT={v!r} is not an integer; ignoring",
            RuntimeWarning)
        return None


# ------------------------------------------------------------- cache wiring
def _install_metrics_listener():
    """Count persistent-cache hit/miss monitoring events into the
    registry. Registration is append-only in jax, so install once."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    from jax import monitoring as _mon

    def _on_event(event, **kwargs):
        if event.endswith("/cache_hits"):
            _tel.registry().counter("compile/cache_hits").inc()
        elif event.endswith("/cache_misses"):
            _tel.registry().counter("compile/cache_misses").inc()

    _mon.register_event_listener(_on_event)
    _LISTENER_INSTALLED = True


def is_enabled() -> bool:
    return _ENABLED


def cache_dir() -> Optional[str]:
    return _DIR


def setup():
    """Import-time wiring of the persistent cache:

    - ``MXTPU_COMPILE_CACHE_DIR=0``/``off`` -> nothing is wired
    - ``JAX_COMPILATION_CACHE_DIR`` set -> JAX reads it itself; no
      directory is set here, that one is reported
    - otherwise -> the fixed ``.mxtpu_cache/xla`` inside the checkout
    """
    global _ENABLED, _DIR
    off = os.environ.get("MXTPU_COMPILE_CACHE_DIR", "").strip().lower()
    if off in ("0", "off", "false", "none"):
        return
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        import jax

        directory = os.path.join(GENERATED_DIR, "xla")
        jax.config.update("jax_compilation_cache_dir", directory)
    _install_metrics_listener()
    _ENABLED = True
    _DIR = directory
    _tel.registry().gauge("compile/persistent_cache_enabled").set(1)


def cache_stats() -> dict:
    """Persistent-cache status + hit/miss counters (process lifetime)."""
    snap = _tel.registry().snapshot()["counters"]
    return {
        "enabled": _ENABLED,
        "dir": _DIR,
        "hits": snap.get("compile/cache_hits", 0),
        "misses": snap.get("compile/cache_misses", 0),
    }


# ---------------------------------------------------------- recompile guard
class RecompileGuard:
    """Signature accounting for one staged callable (a ``TrainStep`` or a
    ``CachedOp``): each distinct operand-aval signature is exactly one
    XLA program, so ``signatures`` is a compile counter that needs no JAX
    internals. ``mark_steady()`` (called by ``warmup``) arms the
    shape-churn alarm: a new signature afterwards bumps
    ``compile/steady_state_recompiles`` and warns — or raises once the
    count exceeds ``MXTPU_RECOMPILE_LIMIT``."""

    def __init__(self, name: str):
        self.name = name
        self._sigs: dict = {}  # key -> {count, last_used, aval}
        self._steady = False
        self._steady_recompiles = 0
        self._warned_unbounded = False
        self._seq = 0
        self._lock = threading.Lock()

    # `summary` is a human-readable aval description stored for
    # cache_info(); a callable defers the string build to the (rare)
    # new-signature case so the hot dispatch never pays for it
    def observe(self, key, summary=None) -> bool:
        """Record one dispatch under signature ``key``; returns True when
        the signature is new (== this dispatch compiled)."""
        with self._lock:
            self._seq += 1
            info = self._sigs.get(key)
            if info is not None:
                info["count"] += 1
                info["last_used"] = self._seq
                return False
            if callable(summary):
                summary = summary()
            self._sigs[key] = {
                "count": 1, "last_used": self._seq,
                "aval": summary if summary is not None else str(key),
            }
            n_sigs = len(self._sigs)
            steady = self._steady
            if steady:
                self._steady_recompiles += 1
            n_steady = self._steady_recompiles
        reg = _tel.registry()
        reg.counter("compile/signatures").inc()
        limit = recompile_limit()
        silenced = limit is not None and limit < 0
        if steady:
            reg.counter("compile/steady_state_recompiles").inc()
            if not silenced:
                msg = (
                    f"{self.name}: shape-churn recompile after warmup "
                    f"(new signature {summary}; {n_steady} steady-state "
                    "recompile(s) so far). Pad/bucket inputs to the warmed "
                    "shapes (gluon.data.bucketing) to keep the step loop "
                    "compile-free."
                )
                if limit is not None and n_steady > limit:
                    raise MXNetError(
                        msg + f" MXTPU_RECOMPILE_LIMIT={limit} exceeded.")
                warnings.warn(msg, RuntimeWarning, stacklevel=3)
        bound = limit if limit is not None and limit >= 0 \
            else _DEFAULT_SIG_WARN
        if n_sigs > bound and not self._warned_unbounded and not silenced:
            self._warned_unbounded = True
            warnings.warn(
                f"{self.name} holds {n_sigs} staged signatures (> {bound}) "
                "— each is a separately compiled XLA program held for the "
                "object's lifetime. Bucket or pad inputs "
                "(gluon.data.bucketing) to bound shape churn.",
                RuntimeWarning, stacklevel=3)
        return True

    def mark_steady(self):
        """Declare warmup complete: any new signature from here on is an
        accidental recompile."""
        self._steady = True

    @property
    def steady(self) -> bool:
        return self._steady

    @property
    def signatures(self) -> int:
        return len(self._sigs)

    @property
    def steady_state_recompiles(self) -> int:
        return self._steady_recompiles

    def info(self) -> dict:
        """Per-signature summary: held programs, use counts, recency."""
        with self._lock:
            entries = [
                {"signature": info["aval"], "count": info["count"],
                 "last_used": info["last_used"]}
                for info in self._sigs.values()
            ]
        entries.sort(key=lambda e: -e["last_used"])
        return {
            "name": self.name,
            "signatures": len(entries),
            "steady": self._steady,
            "steady_state_recompiles": self._steady_recompiles,
            "entries": entries,
        }


def normalize_spec(spec):
    """One warmup array spec -> ``(shape tuple, numpy dtype)``.

    Accepts anything with ``.shape``/``.dtype`` (NDArray, jax/numpy
    array, ``jax.ShapeDtypeStruct``) or an explicit ``(shape, dtype)``
    pair."""
    import numpy as _np

    if hasattr(spec, "shape") and hasattr(spec, "dtype"):
        return tuple(spec.shape), _np.dtype(spec.dtype)
    if isinstance(spec, (tuple, list)) and len(spec) == 2:
        shape, dtype = spec
        try:
            return tuple(int(d) for d in shape), _np.dtype(dtype)
        except (TypeError, ValueError):
            pass
    raise MXNetError(
        f"warmup signature entry {spec!r} is not an array, "
        "ShapeDtypeStruct, or (shape, dtype) pair")


def aval_summary(arrays) -> str:
    """Compact ``shape/dtype`` rendering of an operand list for guard
    summaries and ``cache_info``."""
    parts = []
    for a in arrays:
        shape = "x".join(str(d) for d in getattr(a, "shape", ()))
        parts.append(f"{getattr(a, 'dtype', '?')}[{shape}]")
    return "(" + ", ".join(parts) + ")"

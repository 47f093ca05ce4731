"""Unified training telemetry: step metrics, trace events, watchdog.

One spine for "why was this step slow?" and "is the run alive?":

- ``events``   — structured spans (Chrome-trace ``.json`` + append-only
  JSONL), thread-safe nesting, zero overhead when disabled.
- ``phase``    — the hot paths' span (scheduler pass, train step,
  ``profiler.Scope``): one interval, three sinks — the profiler's
  timeline, an always-on accumulator, and the ``events`` stream.
- ``metrics``  — process-global registry (counters/gauges/rolling
  histograms): per-step wall time, samples/sec, JAX compile events
  (``jax.monitoring``), device memory, kvstore allreduce bytes/latency,
  and ``profiler.py``'s per-op aggregates (``op/`` family).
- ``watchdog`` — heartbeat file + stalled-step detection with thread
  stack dumps, nonzero exit on hard hangs.

Usage::

    import mxnet_tpu as mx
    mx.telemetry.enable()            # or MXNET_TELEMETRY=1 in the env
    ... train ...
    print(mx.telemetry.report())     # step-time p50/p95, samples/sec, ...
    mx.telemetry.dump()              # chrome://tracing-loadable trace.json

Env knobs: ``MXNET_TELEMETRY=1`` enables at import;
``MXNET_TELEMETRY_DIR`` sets the output directory (default
``./telemetry``); ``MXNET_TELEMETRY_WATCHDOG=1`` starts the watchdog on
enable; ``MXNET_TELEMETRY_HARD_TIMEOUT_S`` arms the hard-hang exit.

Hot paths gate on the module flag (``telemetry._ENABLED`` via
``enabled()``) so a disabled build pays a single flag check per step —
no span or metric objects are allocated.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from jax.profiler import TraceAnnotation

from .events import EventLog, NULL_SPAN, _T0 as _EVENTS_T0, \
    now_us as _events_now_us
from .metrics import Counter, Gauge, Histogram, Registry, merge_summaries
from .watchdog import Watchdog

__all__ = [
    "enable", "disable", "enabled", "span", "instant", "complete", "phase",
    "clock_us", "us_of", "registry", "report", "dump", "record_step",
    "start_watchdog", "stop_watchdog", "hbm_peak_bytes",
    "hbm_limit_bytes", "hbm_headroom_bytes", "device_memory_stats",
    "set_info", "run_info", "Registry", "Counter", "Gauge", "Histogram",
    "Watchdog", "EventLog", "NULL_SPAN", "merge_summaries",
]

# module-level fast flag: hot paths read `telemetry._ENABLED` directly —
# the whole disabled-mode cost is that one attribute load + branch
_ENABLED = False
_LOG: Optional[EventLog] = None
_REGISTRY = Registry()
_WATCHDOG: Optional[Watchdog] = None
_LOCK = threading.RLock()
_JAX_LISTENER_INSTALLED = False
# non-numeric run configuration surfaced in report() (amp dtype, remat
# policy, ...) — set by the components that own the knob, e.g. TrainStep
_RUN_INFO: dict = {}


def set_info(**kwargs):
    """Attach run-configuration facts (strings allowed — the registry is
    numeric-only) to ``report()``; None values clear the key."""
    for k, v in kwargs.items():
        if v is None:
            _RUN_INFO.pop(k, None)
        else:
            _RUN_INFO[k] = v


def run_info() -> dict:
    return dict(_RUN_INFO)


def enabled() -> bool:
    return _ENABLED


def registry() -> Registry:
    """The process-global metrics registry (usable even when event
    emission is disabled — metric objects are cheap and always live)."""
    return _REGISTRY


def default_dir() -> str:
    return os.environ.get("MXNET_TELEMETRY_DIR", "telemetry")


# ------------------------------------------------------------------ enable
def enable(directory: Optional[str] = None, watchdog: Optional[bool] = None,
           **watchdog_kwargs):
    """Turn on span emission (+ optionally the watchdog); idempotent.

    ``watchdog=None`` defers to ``MXNET_TELEMETRY_WATCHDOG``.
    """
    global _ENABLED, _LOG, _WATCHDOG
    with _LOCK:
        if _LOG is None:
            _LOG = EventLog(directory or default_dir())
        _ENABLED = True
        _install_jax_compile_listener()
        if watchdog is None:
            watchdog = os.environ.get(
                "MXNET_TELEMETRY_WATCHDOG", "0") not in ("0", "", "false")
        if watchdog and _WATCHDOG is None:
            start_watchdog(**watchdog_kwargs)
    return _LOG


def disable():
    """Stop emitting; buffered events stay dumpable via ``dump()``."""
    global _ENABLED, _WATCHDOG
    with _LOCK:
        _ENABLED = False
        if _WATCHDOG is not None:
            _WATCHDOG.stop()
            _WATCHDOG = None


def reset():
    """Full teardown (tests): drop the log, registry contents, watchdog."""
    global _ENABLED, _LOG
    with _LOCK:
        disable()
        if _LOG is not None:
            _LOG.close()
            _LOG = None
        _REGISTRY.clear()
        _RUN_INFO.clear()


# ------------------------------------------------------------------- spans
def span(name: str, args: Optional[dict] = None):
    """Context manager emitting one Chrome-trace span; a shared no-op
    singleton when disabled (no allocation)."""
    log = _LOG
    if not _ENABLED or log is None:
        return NULL_SPAN
    return log.span(name, args)


def instant(name: str, args: Optional[dict] = None):
    log = _LOG
    if _ENABLED and log is not None:
        log.instant(name, args)


def complete(name: str, ts_us: float, dur_us: float,
             args: Optional[dict] = None):
    """Emit one complete span with explicit start/duration (request-
    lifetime spans whose endpoints cross threads); no-op when disabled."""
    log = _LOG
    if _ENABLED and log is not None:
        log.complete(name, ts_us, dur_us, args)


class phase:
    """One phase of a hot path as ONE interval with three sinks:

    - always a ``jax.profiler.TraceAnnotation("mxtpu." + name, **args)``:
      inside a profiler session the span lies on the profiler's timeline
      beside the device's operations (one clock, by construction); outside
      one, TraceMe is a flag check;
    - always ``acc[key] += seconds`` when an accumulator is given (a dict
      slot or a one-element list; no lock here — the owner of ``acc``
      publishes it under its own);
    - while ``enable()`` is in force, the same interval under the same
      name and ``args`` as one complete event of the JSONL / Chrome
      stream, so either trace can be laid over the other.

    With tracing off: two clock reads, one flag check, one float add.
    The two reads are the interval's for everyone: ``t0`` is its start
    (a ``perf_counter`` instant, the clock a request's timeline is
    stamped on) and, once closed, ``seconds`` its length, so that nothing
    beside a phase reads the clock again for the same interval.
    """

    __slots__ = ("_name", "_acc", "_key", "_args", "_ann", "t0", "seconds")

    def __init__(self, name: str, acc=None, key=None,
                 args: Optional[dict] = None):
        self._name = "mxtpu." + name
        self._acc, self._key, self._args = acc, key, args

    def __enter__(self):
        self._ann = TraceAnnotation(self._name, **(self._args or {}))
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t0, t1 = self.t0, time.perf_counter()
        self.seconds = t1 - t0
        self._ann.__exit__(*exc)
        if self._acc is not None:
            self._acc[self._key] += t1 - t0
        log = _LOG
        if _ENABLED and log is not None:
            log.complete(self._name, (t0 - _EVENTS_T0) * 1e6,
                         (t1 - t0) * 1e6, self._args)
        return False


def clock_us() -> float:
    """The process trace clock (µs since telemetry module import) — the
    timebase of every emitted event, exposed so the serving plane can
    answer clock-alignment probes (``ping``/``telemetry`` verbs)."""
    return _events_now_us()


def us_of(t: float) -> float:
    """A ``time.perf_counter`` instant on the process trace clock: the
    events' timebase IS ``perf_counter`` less this module's origin, so a
    span made later from stamped instants (a request's timeline) lies
    where a ``phase`` of the same instants would."""
    return (t - _EVENTS_T0) * 1e6


# ------------------------------------------------------------------- steps
def record_step(samples: int, seconds: float):
    """Record one completed optimizer step: wall time + throughput
    accounting, and watchdog progress. Called by ``Trainer.step`` (only
    when telemetry is enabled) and available to custom loops."""
    _REGISTRY.histogram("trainer/step_time_s").observe(seconds)
    _REGISTRY.counter("trainer/steps").inc()
    _REGISTRY.counter("trainer/samples").inc(samples)
    wd = _WATCHDOG
    if wd is not None:
        wd.notify_step(seconds=seconds)
    _update_memory_gauges()


def _update_memory_gauges():
    peak = hbm_peak_bytes()
    if peak is not None:
        _REGISTRY.gauge("device/hbm_peak_bytes").max(peak)


# ---------------------------------------------------------------- watchdog
def start_watchdog(directory: Optional[str] = None, interval: float = 5.0,
                   stall_factor: float = 10.0, min_stall_s: float = 30.0,
                   hard_timeout_s: Optional[float] = None,
                   **kwargs) -> Watchdog:
    global _WATCHDOG
    with _LOCK:
        if _WATCHDOG is not None:
            return _WATCHDOG
        if hard_timeout_s is None:
            env = os.environ.get("MXNET_TELEMETRY_HARD_TIMEOUT_S")
            hard_timeout_s = float(env) if env else None
        _WATCHDOG = Watchdog(
            directory or (_LOG.directory if _LOG else default_dir()),
            interval=interval, stall_factor=stall_factor,
            min_stall_s=min_stall_s, hard_timeout_s=hard_timeout_s,
            **kwargs)
        _WATCHDOG.start()
        return _WATCHDOG


def stop_watchdog():
    global _WATCHDOG
    with _LOCK:
        if _WATCHDOG is not None:
            _WATCHDOG.stop()
            _WATCHDOG = None


def watchdog() -> Optional[Watchdog]:
    return _WATCHDOG


def _on_watchdog_stall(state: dict):
    """Watchdog -> telemetry bridge: count the stall and mark it in the
    trace so the gap is visible next to the last completed span."""
    _REGISTRY.counter("watchdog/stalls").inc()
    instant("watchdog.stall", {
        "step": state.get("step"),
        "idle_s": state.get("idle_s"),
        "stacks": state.get("stacks"),
    })


# ---------------------------------------------------------- device memory
def device_memory_stats():
    """Per-device ``memory_stats()`` dicts; empty list when the backend
    exposes none (CPU)."""
    try:
        import jax

        out = []
        for d in jax.local_devices():
            try:
                ms = d.memory_stats()
            except Exception:  # noqa: BLE001 - backend-dependent
                ms = None
            if ms:
                out.append({"device": str(d), **ms})
        return out
    except Exception:  # noqa: BLE001 - jax not importable in odd envs
        return []


def hbm_peak_bytes() -> Optional[int]:
    """Max peak-bytes-in-use over local devices; None on backends without
    memory stats (CPU) — null-safe by construction."""
    stats = device_memory_stats()
    peaks = [s.get("peak_bytes_in_use") for s in stats
             if s.get("peak_bytes_in_use") is not None]
    return max(peaks) if peaks else None


def hbm_limit_bytes() -> Optional[int]:
    """Per-device HBM capacity: min ``bytes_limit`` over local devices,
    falling back to ``MXTPU_HBM_BYTES`` (planning on rigs without memory
    stats, e.g. the CPU test backend). None when neither is known."""
    stats = device_memory_stats()
    limits = [s.get("bytes_limit") for s in stats
              if s.get("bytes_limit") is not None]
    if limits:
        return min(limits)
    env = os.environ.get("MXTPU_HBM_BYTES")
    if env:
        try:
            return int(float(env))
        except ValueError:
            pass
    return None


def hbm_headroom_bytes() -> Optional[int]:
    """HBM limit minus the high-water mark — how much larger the working
    set could grow. None when either side is unknown (CPU)."""
    limit = hbm_limit_bytes()
    peak = hbm_peak_bytes()
    if limit is None or peak is None:
        return None
    return limit - peak


# ------------------------------------------------------------ jax compile
def _install_jax_compile_listener():
    """Route ``jax.monitoring`` duration events (jit tracing/compilation)
    into the registry. Listener registration is append-only in jax, so
    the callback itself checks the ENABLED flag."""
    global _JAX_LISTENER_INSTALLED
    if _JAX_LISTENER_INSTALLED:
        return
    from jax import monitoring as _mon

    def _on_duration(event, duration, **kwargs):
        if not _ENABLED:
            return
        key = event.strip("/").replace("/", "_")
        _REGISTRY.histogram(f"jax/{key}").observe(duration)
        if "compil" in event or "backend_compile" in event:
            _REGISTRY.histogram("jax/compile_time_s").observe(duration)

    _mon.register_event_duration_secs_listener(_on_duration)
    _JAX_LISTENER_INSTALLED = True


# ------------------------------------------------------------------ report
def _hp(snap, name, q):
    """Histogram percentile from a registry snapshot, None-safe."""
    h = snap["histograms"].get(name)
    return h[q] if h else None


def report() -> dict:
    """One-call run summary: step-time percentiles, throughput, compile
    time, HBM high-water mark, plus the full registry snapshot."""
    _update_memory_gauges()
    snap = _REGISTRY.snapshot()
    step_hist = snap["histograms"].get("trainer/step_time_s")
    compile_hist = snap["histograms"].get("jax/compile_time_s")
    wait_hist = snap["histograms"].get("input/wait_ms")
    samples = snap["counters"].get("trainer/samples", 0)
    step_sum = step_hist["sum"] if step_hist else 0.0
    return {
        "enabled": _ENABLED,
        "steps": snap["counters"].get("trainer/steps", 0),
        "step_time_p50": step_hist["p50"] if step_hist else None,
        "step_time_p95": step_hist["p95"] if step_hist else None,
        "step_time_p99": step_hist["p99"] if step_hist else None,
        "samples_per_sec": (samples / step_sum) if step_sum > 0 else None,
        "compile_time_s": compile_hist["sum"] if compile_hist else None,
        "hbm_peak_bytes": snap["gauges"].get("device/hbm_peak_bytes"),
        # memory/precision config + headroom (HBM-aware compute): the
        # dtype/remat knobs the run was built with and how much HBM is
        # left above the high-water mark (None on CPU)
        "amp_dtype": _RUN_INFO.get("amp_dtype"),
        "remat_policy": _RUN_INFO.get("remat_policy"),
        "hbm_headroom_bytes": hbm_headroom_bytes(),
        # SPMD sharding (parallel.sharding): the mesh/rules in force and
        # the shard/ family's headline figures — how many bytes one
        # device actually holds and the estimated per-step collective
        # traffic (None/absent in unsharded processes)
        "mesh_shape": _RUN_INFO.get("mesh_shape"),
        "sharding": _RUN_INFO.get("sharding"),
        "shard_param_bytes_total": snap["gauges"].get(
            "shard/param_bytes_total"),
        "shard_param_bytes_per_shard": snap["gauges"].get(
            "shard/param_bytes_per_shard"),
        "shard_collective_bytes_per_step": snap["gauges"].get(
            "shard/collective_bytes_per_step_est"),
        "watchdog_stalls": snap["counters"].get("watchdog/stalls", 0),
        # shape stability (compile_cache): distinct compiled signatures,
        # post-warmup recompiles (should stay 0), persistent-cache reuse
        "compile_signatures": snap["counters"].get("compile/signatures", 0),
        "compile_steady_state_recompiles": snap["counters"].get(
            "compile/steady_state_recompiles", 0),
        "compile_warmup_compiles": snap["counters"].get(
            "compile/warmup_compiles", 0),
        "compile_cache_hits": snap["counters"].get("compile/cache_hits", 0),
        "compile_cache_misses": snap["counters"].get(
            "compile/cache_misses", 0),
        # async device feed (gluon.data.prefetch): per-pull consumer stall
        # — after overlap, the residual input wait per step
        "input_wait_ms": wait_hist,
        "input_wait_ms_p50": wait_hist["p50"] if wait_hist else None,
        "input_wait_ms_p95": wait_hist["p95"] if wait_hist else None,
        "input_queue_depth": snap["gauges"].get("input/queue_depth"),
        # inference/serving (parallel.infer + serving.batcher): dispatch
        # prefill/decode timing, serving throughput, admission latency,
        # slot utilization — all None/0 in training-only processes
        "infer_prefill_ms_p50": _hp(snap, "infer/prefill_ms", "p50"),
        "infer_prefill_ms_p95": _hp(snap, "infer/prefill_ms", "p95"),
        "infer_decode_ms_per_token_p50": _hp(
            snap, "infer/decode_ms_per_token", "p50"),
        "infer_tokens_per_sec": snap["gauges"].get("infer/tokens_per_sec"),
        "infer_batch_occupancy": snap["gauges"].get(
            "infer/batch_occupancy"),
        "infer_queue_wait_ms_p50": _hp(snap, "infer/queue_wait_ms", "p50"),
        "infer_queue_wait_ms_p95": _hp(snap, "infer/queue_wait_ms", "p95"),
        "infer_requests": snap["counters"].get("infer/requests", 0),
        "infer_tokens": snap["counters"].get("infer/tokens", 0),
        # continuous batching + paged KV (serving.ContinuousBatcher /
        # serving.pages): time-to-first-token, pool pressure, per-
        # iteration admission and the backpressure/preemption self-
        # protection counters
        "infer_ttft_ms_p50": _hp(snap, "infer/ttft_ms", "p50"),
        "infer_ttft_ms_p95": _hp(snap, "infer/ttft_ms", "p95"),
        "infer_pages_in_use": snap["gauges"].get("infer/pages_in_use"),
        "infer_page_fragmentation": snap["gauges"].get(
            "infer/page_fragmentation"),
        "infer_admitted_per_iter_p50": _hp(
            snap, "infer/admitted_per_iter", "p50"),
        "infer_rejected_backpressure": snap["counters"].get(
            "infer/rejected_backpressure", 0),
        "infer_preempted": snap["counters"].get("infer/preempted", 0),
        # self-healing serving (serving.router/.watcher/.faults): which
        # weights are live and how often the plane healed itself — hot
        # swaps, replica evictions (failovers), transparent retries, and
        # the requests that were genuinely lost (should stay 0)
        "weights_version": _RUN_INFO.get("weights_version"),
        "serve_swaps": snap["counters"].get("serve/swaps", 0),
        "serve_swap_failures": snap["counters"].get(
            "serve/swap_failures", 0),
        "serve_failovers": snap["counters"].get("serve/failovers", 0),
        "serve_retries": snap["counters"].get("serve/retries", 0),
        "serve_dropped": snap["counters"].get("serve/dropped", 0),
        "serve_deadline_exceeded": snap["counters"].get(
            "serve/deadline_exceeded", 0),
        "serve_replica_restarts": snap["counters"].get(
            "serve/replica_restarts", 0),
        "serve_replicas_healthy": snap["gauges"].get(
            "serve/replicas_healthy"),
        "serve_faults_injected": snap["counters"].get(
            "serve/faults_injected", 0),
        "counters": snap["counters"],
        "gauges": snap["gauges"],
        "histograms": snap["histograms"],
    }


def dump(path: Optional[str] = None) -> Optional[str]:
    """Write the Chrome-trace JSON (plus a ``report.json`` snapshot next
    to it); returns the trace path, or None if never enabled."""
    log = _LOG
    if log is None:
        return None
    trace_path = log.dump(path)
    try:
        import json as _json

        with open(os.path.join(log.directory, "report.json"), "w") as f:
            _json.dump(report(), f, indent=2, default=str)
    except OSError:
        pass
    return trace_path


def jsonl_path() -> Optional[str]:
    return _LOG.jsonl_path if _LOG is not None else None


# auto-enable from the environment (MXNET_TELEMETRY=1 / true / yes)
if os.environ.get("MXNET_TELEMETRY", "0").lower() not in ("0", "", "false",
                                                          "no"):
    enable()

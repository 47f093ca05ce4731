"""Multi-replica serving router: health-scored placement, watchdog-driven
failover, bounded transparent retries.

One ``submit()`` front-end over N engine+batcher replicas. The router
owns the request lifecycle end to end:

- **Placement (SLO-aware)**: each request goes to the healthy
  decode-serving replica with the lowest PREDICTED WAIT — the replica's
  rolling queue-wait p50 (worker-reported over the health verb, or the
  local batcher's window) times its backlog + 1 — rather than the
  instantaneous backlog count alone; replicas with no wait signal yet
  degenerate to backlog ordering. Ties break round-robin via a rotating
  cursor, so equal-score replicas share load instead of the first one
  absorbing everything.
- **Request classes**: ``submit(..., klass="interactive"|"batch")``
  tags each request; a request without an explicit ``deadline_ms``
  picks up its class default (``MXTPU_SLO_INTERACTIVE_MS`` /
  ``MXTPU_SLO_BATCH_MS``), and under a degraded fleet BATCH traffic
  sheds at HALF the ``MXTPU_SHED_MAX_QUEUE`` backlog bound — batch
  sheds before interactive by construction.
- **Disaggregation**: when the fleet contains prefill-role replicas
  (``serving.disagg.worker_role``), placement picks a decode replica
  AND a prefill replica: the prefill worker runs the admission prefill
  and ships the KV frames to the decode worker (``kv_push`` /
  ``MXTPU_KV_SPILL_DIR`` spill), whose batcher adopts them without
  re-prefilling. Any handoff failure degrades to the decode worker
  re-prefilling from the prompt (``disagg/re_prefills``) — requests
  are never lost to a handoff.
- **Health**: a replica is healthy while (a) its batcher's dispatcher
  thread is alive (``ContinuousBatcher.healthy``), (b) its watchdog
  heartbeat — the PR-1 ``heartbeat.json``, written atomically — is fresh
  and not flagged ``stalled``/``hard_hang``, and (c) it has not been
  evicted. The health loop re-scores every ``health_interval_s``.
- **Failover**: an unhealthy replica is evicted — its queued-but-
  undispatched requests are cancelled out of its batcher and every
  router request assigned to it is transparently resubmitted to a
  healthy replica, with bounded retries (``MXTPU_RETRY_MAX``),
  exponential backoff with jitter, and per-request deadlines
  (``DeadlineExceeded`` rather than a late dispatch).
- **Replacement**: with a ``replica_factory``, evictions trigger
  respawn attempts under the same capped exponential backoff
  (``MXTPU_RESTART_BACKOFF_S``) that ``tools/launch.py`` uses for
  whole-job elastic restarts. A factory-returned replica may report
  ``starting`` (a worker process booting): it is skipped for placement
  but not evicted until it either comes up or fails.
- **Load shedding**: when EVERY replica is degraded — unhealthy,
  backlogged past ``MXTPU_SHED_QUEUE_DEPTH``, or the router's rolling
  completed-request queue-wait p50 past ``MXTPU_SHED_WAIT_MS`` — new
  submits are shed at admission with ``Backpressure`` instead of
  queueing behind work that cannot finish in time: a request whose
  deadline is infeasible under the current p50 wait is shed
  immediately (``serve/shed_deadline``), and once the router backlog
  reaches ``MXTPU_SHED_MAX_QUEUE`` everything is
  (``serve/shed_queue_full``) — queue growth is bounded by
  construction, rather than by deadlines expiring inside the queue.

Telemetry (``serve/`` family): ``requests``/``completed`` counters,
``failovers`` (evictions), ``retries`` (resubmissions), ``dropped``
(failed after retries exhausted), ``deadline_exceeded``,
``shed_deadline``/``shed_queue_full`` (admission sheds),
``replica_restarts``, ``replicas_healthy`` +
``shed_degraded_replicas`` gauges.
"""

from __future__ import annotations

import collections
import os
import random
import threading
import time
from typing import Callable, Optional, Sequence

from ..base import MXNetError
from .. import telemetry as _tel
from ..telemetry.watchdog import read_heartbeat
from . import faults as _faults
from . import prefix as _prefix
from . import tracing as _tracing
from .batcher import Backpressure, ContinuousBatcher, DeadlineExceeded, \
    GenerationResult, PHASE_DETAIL

__all__ = ["Router", "Replica", "ReplicaUnavailable", "retry_max",
           "restart_backoff_s", "shed_queue_depth", "shed_wait_ms",
           "shed_max_queue", "slo_interactive_ms", "slo_batch_ms",
           "REQUEST_CLASSES"]

REQUEST_CLASSES = ("interactive", "batch")


class ReplicaUnavailable(MXNetError):
    """The replica holding a request was evicted before dispatching it —
    a retriable condition (the router resubmits elsewhere)."""


def retry_max(default: int = 2) -> int:
    """``MXTPU_RETRY_MAX``: resubmissions per request after its first
    placement (0 = fail on the first replica error)."""
    v = os.environ.get("MXTPU_RETRY_MAX", "").strip()
    try:
        return int(v) if v else default
    except ValueError:
        return default


def restart_backoff_s(default: float = 1.0) -> float:
    """``MXTPU_RESTART_BACKOFF_S``: base of the capped exponential
    backoff between restart attempts — shared contract with
    ``tools/launch.py``'s elastic relaunch."""
    v = os.environ.get("MXTPU_RESTART_BACKOFF_S", "").strip()
    try:
        return float(v) if v else default
    except ValueError:
        return default


def shed_queue_depth(default: int = 16) -> int:
    """``MXTPU_SHED_QUEUE_DEPTH``: a replica whose load (router-assigned
    in-flight + its own backlog) reaches this counts as DEGRADED for the
    all-replicas-degraded shedding gate."""
    v = os.environ.get("MXTPU_SHED_QUEUE_DEPTH", "").strip()
    try:
        return int(v) if v else default
    except ValueError:
        return default


def shed_wait_ms(default: float = 0.0) -> float:
    """``MXTPU_SHED_WAIT_MS``: rolling completed-request queue-wait p50
    beyond which the fleet counts as degraded (0/unset disables the
    wait-based gate; queue depth and health still apply)."""
    v = os.environ.get("MXTPU_SHED_WAIT_MS", "").strip()
    try:
        return float(v) if v else default
    except ValueError:
        return default


def shed_max_queue(default: int = 128) -> int:
    """``MXTPU_SHED_MAX_QUEUE``: hard bound on the router's in-flight
    backlog while all replicas are degraded — admission beyond it sheds
    with ``Backpressure`` (bounded queue growth by construction)."""
    v = os.environ.get("MXTPU_SHED_MAX_QUEUE", "").strip()
    try:
        return int(v) if v else default
    except ValueError:
        return default


def disagg_min_prompt(default: int = 16) -> int:
    """``MXTPU_DISAGG_MIN_PROMPT``: prompts SHORTER than this prefill in
    place on the decode worker even when prefill-role replicas exist —
    a short prompt's prefill costs less than the handoff's extra hop,
    and keeping long-prompt prefills (and only those) off the decode
    workers is the whole point of the split. 0/1 = hand off
    everything."""
    v = os.environ.get("MXTPU_DISAGG_MIN_PROMPT", "").strip()
    try:
        return max(int(v), 1) if v else default
    except ValueError:
        return default


def slo_interactive_ms(default: float = 0.0) -> float:
    """``MXTPU_SLO_INTERACTIVE_MS``: default deadline for
    ``klass="interactive"`` requests submitted without an explicit
    ``deadline_ms`` (0/unset = no class default; the router-wide
    ``deadline_ms`` still applies)."""
    v = os.environ.get("MXTPU_SLO_INTERACTIVE_MS", "").strip()
    try:
        return float(v) if v else default
    except ValueError:
        return default


def slo_batch_ms(default: float = 0.0) -> float:
    """``MXTPU_SLO_BATCH_MS``: default deadline for ``klass="batch"``
    requests submitted without an explicit ``deadline_ms`` (0/unset =
    no class default)."""
    v = os.environ.get("MXTPU_SLO_BATCH_MS", "").strip()
    try:
        return float(v) if v else default
    except ValueError:
        return default


def backoff_delay(base: float, attempt: int, cap: float = 30.0,
                  jitter: float = 0.25) -> float:
    """Capped exponential backoff with multiplicative jitter: attempt 0
    waits ~base, each further attempt doubles, never exceeding ``cap``
    (pre-jitter). Jitter decorrelates replicas/restarts that failed at
    the same instant."""
    d = min(float(base) * (2.0 ** max(int(attempt), 0)), float(cap))
    return d * (1.0 + float(jitter) * random.random())


class Replica:
    """One engine+batcher unit behind the router.

    ``heartbeat_path`` points at a watchdog ``heartbeat.json`` (wire the
    same ``Watchdog`` into the batcher via ``ContinuousBatcher(...,
    watchdog=...)`` so dispatches feed it). No path = liveness from the
    dispatcher thread alone."""

    def __init__(self, name: str, batcher: ContinuousBatcher,
                 heartbeat_path: Optional[str] = None,
                 heartbeat_stale_s: float = 10.0, role: str = "both"):
        self.name = str(name)
        self.batcher = batcher
        if batcher.name is None:
            batcher.name = self.name
        self.heartbeat_path = heartbeat_path
        self.heartbeat_stale_s = float(heartbeat_stale_s)
        self.evicted = False
        # disaggregated fleet role (serving.disagg.worker_role):
        # "prefill" replicas never receive decode placements; they serve
        # as KV-handoff sources and still join the coordinated hot swap
        self.role = str(role)
        # deliberate scale-down (Router.retire_replica): excluded from
        # placement, its eventual eviction schedules NO respawn
        self.retired = False
        self.inflight = 0  # router-assigned, guarded by the router lock

    @property
    def engine(self):
        return self.batcher._engine

    def health(self) -> tuple:
        """(healthy, reason). Never raises — a health check that crashes
        is itself an outage."""
        if self.evicted:
            return False, "evicted"
        if not self.batcher.healthy:
            return False, "dispatcher thread down"
        if self.heartbeat_path is not None:
            hb = read_heartbeat(self.heartbeat_path)
            if hb is not None:
                if hb.get("status") in ("stalled", "hard_hang"):
                    return False, f"heartbeat status {hb['status']}"
                age = time.time() - float(hb.get("time", 0.0))
                if age > self.heartbeat_stale_s:
                    return False, f"heartbeat stale ({age:.1f}s)"
            # missing/torn file = unknown, not unhealthy: the watchdog
            # may simply not have written yet
        return True, "ok"

    @property
    def healthy(self) -> bool:
        return self.health()[0]

    @property
    def starting(self) -> bool:
        """True while the replica is still coming up (a spawning worker
        process): unhealthy for placement, exempt from eviction. In-
        process replicas are ready at construction."""
        return False

    @property
    def serves_decode(self) -> bool:
        """Whether decode placements may land here (everything but a
        dedicated prefill worker)."""
        return self.role != "prefill"

    @property
    def serves_prefill(self) -> bool:
        """Whether this replica is a KV-handoff source — only DEDICATED
        prefill workers; a ``both`` replica co-schedules instead."""
        return self.role == "prefill"

    def load(self) -> int:
        """Backlog: requests the router has in flight here plus the
        batcher's queued backlog (infer/ telemetry's queue_wait is this
        backlog measured in time)."""
        return self.inflight + self.batcher._queue.qsize()

    def queue_wait_p50_ms(self) -> Optional[float]:
        """Rolling queue-wait p50 this replica reports (the local
        batcher's window; remote replicas report it over the health
        verb). None until enough samples exist."""
        fn = getattr(self.batcher, "rolling_wait_ms", None)
        return fn() if fn is not None else None

    def predicted_wait_ms(self) -> float:
        """SLO placement score: rolling queue-wait p50 × (backlog + 1).
        With no wait signal yet the p50 factor is 1 ms, so scoring
        degenerates to backlog ordering on a fresh fleet."""
        p50 = self.queue_wait_p50_ms()
        return (p50 if p50 else 1.0) * (self.load() + 1)

    def prefix_digests(self) -> tuple:
        """Compact digest of the prompts this replica's prefix cache
        holds (``serving.prefix.prompt_digest`` per trie root) — what
        prefix-affinity placement matches against. Empty when the local
        batcher has no cache (remote replicas report theirs over the
        health verb)."""
        fn = getattr(self.batcher, "prefix_digests", None)
        if fn is None:
            return ()
        try:
            return tuple(fn(_prefix.prefix_digest_max()))
        except Exception:  # noqa: BLE001 - affinity is advisory only
            return ()


class _Routed:
    """Router-side record of one request across (re)submissions."""

    __slots__ = ("prompt", "max_new", "deadline", "outer", "replica",
                 "inner", "attempts", "next_try_at", "created", "klass",
                 "prefix", "digest", "request_id", "assigned_at")

    def __init__(self, prompt, max_new, deadline, outer,
                 klass="interactive", prefix=None, digest=None,
                 request_id=None):
        self.prompt = prompt
        self.max_new = max_new
        self.deadline = deadline  # absolute perf_counter instant or None
        self.outer = outer
        self.replica = None
        self.inner = None
        self.attempts = 0  # placements so far
        self.next_try_at = 0.0
        self.created = time.perf_counter()
        self.klass = klass  # SLO class: "interactive" | "batch"
        self.prefix = prefix  # forced history for prefix-cache replay
        self.digest = digest  # prompt digest for affinity placement
        self.request_id = request_id  # fleet-wide trace id
        self.assigned_at = None  # perf_counter of the LAST placement


class Router:
    """Self-healing serving front-end over N replicas.

    Parameters
    ----------
    replicas : sequence of ``Replica``.
    max_retries : resubmissions per request after its first placement
        (``MXTPU_RETRY_MAX`` default).
    retry_backoff_s : base backoff between a request's placements.
    deadline_ms : default per-request deadline (None = unbounded).
    health_interval_s : replica re-scoring period.
    replica_factory : zero-arg callable returning a fresh ``Replica``;
        evictions schedule respawns under capped exponential backoff.
    no_replica_timeout_s : how long a request may wait for ANY healthy
        replica (e.g. during respawn) before failing.
    """

    def __init__(self, replicas: Sequence[Replica],
                 max_retries: Optional[int] = None,
                 retry_backoff_s: float = 0.05,
                 deadline_ms: Optional[float] = None,
                 health_interval_s: float = 0.05,
                 replica_factory: Optional[Callable[[], Replica]] = None,
                 respawn_backoff_s: Optional[float] = None,
                 no_replica_timeout_s: float = 5.0,
                 shed_queue_depth: Optional[int] = None,
                 shed_wait_ms: Optional[float] = None,
                 shed_max_queue: Optional[int] = None,
                 disagg_min_prompt: Optional[int] = None,
                 telemetry_scrape_s: Optional[float] = None,
                 start: bool = True):
        from . import router as _self  # module fns shadowed by kwargs

        self._replicas = list(replicas)
        if not self._replicas:
            raise MXNetError("Router needs at least one replica")
        self.max_retries = max_retries if max_retries is not None \
            else retry_max()
        self.retry_backoff_s = float(retry_backoff_s)
        self.default_deadline_ms = deadline_ms
        self.health_interval_s = float(health_interval_s)
        self._factory = replica_factory
        self._respawn_base = respawn_backoff_s if respawn_backoff_s \
            is not None else restart_backoff_s()
        self.no_replica_timeout_s = float(no_replica_timeout_s)
        self.shed_queue_depth = shed_queue_depth \
            if shed_queue_depth is not None else _self.shed_queue_depth()
        self.shed_wait_ms = shed_wait_ms \
            if shed_wait_ms is not None else _self.shed_wait_ms()
        self.shed_max_queue = shed_max_queue \
            if shed_max_queue is not None else _self.shed_max_queue()
        self.disagg_min_prompt = disagg_min_prompt \
            if disagg_min_prompt is not None \
            else _self.disagg_min_prompt()
        self._recent_waits = collections.deque(maxlen=64)
        self._lock = threading.Lock()
        self._rr = 0  # rotating tie-break cursor, guarded by the lock
        self._inflight: list = []
        self._respawn_at = None  # next respawn attempt instant
        self._respawn_attempt = 0
        # fleet observability: periodic clock probes per remote replica
        # (tools/fleet_trace.py alignment) and the telemetry scrape
        # plane (MXTPU_SCRAPE_S / telemetry_scrape_s)
        self._clock_sample_at: dict = {}
        scrape_s = telemetry_scrape_s if telemetry_scrape_s is not None \
            else _tracing.scrape_interval_s()
        self._fleet_telemetry = None
        if scrape_s > 0:
            self._fleet_telemetry = _tracing.FleetTelemetry(
                self._replica_snapshot, interval_s=scrape_s)
        self._stop = threading.Event()
        self._thread = None
        if start:
            self.start()

    # ------------------------------------------------------------ lifecycle
    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="mxtpu-router", daemon=True)
        self._thread.start()
        if self._fleet_telemetry is not None:
            self._fleet_telemetry.start()

    def stop(self, stop_replicas: bool = True, timeout: float = 30.0):
        if self._fleet_telemetry is not None:
            self._fleet_telemetry.stop()
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=timeout)
        with self._lock:
            pending = list(self._inflight)
            self._inflight.clear()
        for r in pending:
            if not r.outer.done():
                r.outer._fail(RuntimeError("router stopped"))
        if stop_replicas:
            for rep in self._replica_snapshot():
                try:
                    rep.batcher.stop(drain=False, timeout=1.0)
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    @property
    def replicas(self) -> list:
        return self._replica_snapshot()

    @property
    def engines(self) -> list:
        """Live engines (for ``CheckpointWatcher`` wiring: one watcher
        hot-swaps every replica)."""
        return [rep.engine for rep in self._replica_snapshot()
                if not rep.evicted]

    @property
    def fleet_telemetry(self):
        """The scrape/aggregation plane (``tracing.FleetTelemetry``),
        or None when ``MXTPU_SCRAPE_S``/``telemetry_scrape_s`` left it
        disabled."""
        return self._fleet_telemetry

    # ------------------------------------------------------------- requests
    def submit(self, prompt_ids, max_new_tokens: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               klass: str = "interactive",
               prefix_ids=None) -> GenerationResult:
        """Route one prompt to a healthy replica. The returned future
        resolves even across replica failures (transparent resubmission)
        — it fails only on retry exhaustion, deadline expiry, or total
        replica loss.

        ``klass`` is the SLO class (``interactive`` default, or
        ``batch``): without an explicit ``deadline_ms`` the class
        default (``MXTPU_SLO_INTERACTIVE_MS``/``MXTPU_SLO_BATCH_MS``)
        applies, per-class TTFT is recorded
        (``disagg/ttft_interactive_ms``/``disagg/ttft_batch_ms``), and
        under a degraded fleet batch traffic sheds first.

        ``prefix_ids`` is the already-generated conversation history to
        teacher-force before decoding (multi-turn). Placement then
        PREFERS replicas advertising this prompt's digest in their
        prefix cache (``MXTPU_PREFIX_AFFINITY``) so the cached KV is
        actually reused, falling back to predicted-wait placement when
        no replica holds it; prefix requests always route to the decode
        replica directly (the forced history makes a KV handoff moot)."""
        if klass not in REQUEST_CLASSES:
            raise MXNetError(
                f"unknown request class {klass!r} "
                f"(one of {REQUEST_CLASSES})")
        outer = GenerationResult()
        # minted unconditionally (a uuid4 slice): SLO attribution and
        # shed/failover/deadline event tagging must work even when span
        # emission (MXTPU_TRACE) is off
        outer.request_id = rid = _tracing.new_request_id()
        dl_ms = deadline_ms
        if dl_ms is None:
            slo = slo_batch_ms() if klass == "batch" \
                else slo_interactive_ms()
            dl_ms = slo if slo > 0 else self.default_deadline_ms
        deadline = None if dl_ms is None \
            else time.perf_counter() + float(dl_ms) / 1e3
        prefix = digest = None
        if prefix_ids is not None and len(prefix_ids) > 0:
            prefix = [int(t) for t in prefix_ids]
            digest = _prefix.prompt_digest(prompt_ids)
        r = _Routed(prompt_ids, max_new_tokens, deadline, outer,
                    klass=klass, prefix=prefix, digest=digest,
                    request_id=rid)
        _tel.registry().counter("serve/requests").inc()
        try:
            with self._lock:
                shed = self._shed_reason_locked(r)
                if shed is not None:
                    kind, parts = shed
                elif not self._assign_locked(r) \
                        and not self._may_recover_locked():
                    outer._fail(RuntimeError(
                        "no healthy replicas and no replica_factory — "
                        "request cannot be placed"))
                    return outer
                else:
                    self._inflight.append(r)
                    return outer
        except Exception as e:  # noqa: BLE001 - r may already be placed
            # _assign_locked can raise AFTER handing r to a replica: the
            # replica's relay thread then holds `outer` and would feed a
            # future whose submit-side caller never saw — fail it so
            # every holder observes the same error instead of a hang.
            if not outer.done():
                outer._fail(e)
            raise
        msg = "; ".join(parts)  # formatted OUTSIDE the router lock
        reg = _tel.registry()
        reg.counter(f"serve/shed_{kind}").inc()
        _tel.instant("serve.shed", {"kind": kind, "reason": msg,
                                    "request_id": rid, "klass": klass})
        outer._fail(Backpressure(f"router shed the request: {msg}"))
        return outer

    # ------------------------------------------------------------- shedding
    def _degraded_locked(self) -> Optional[list]:
        """Per-replica degradation reasons when EVERY replica is
        degraded — not healthy, or backlogged past ``shed_queue_depth``
        — plus the fleet-wide rolling-wait gate; None while any replica
        is in good shape (admission stays open). Returns reason PARTS
        (callers format outside the lock)."""
        reasons = []
        for rep in self._replicas:
            if rep.evicted or rep.retired or not rep.serves_decode:
                # prefill-only replicas cannot absorb decode work and a
                # retiring replica is on its way out: neither keeps
                # admission open
                continue
            if rep.starting or not rep.healthy:
                reasons.append(f"{rep.name}: unhealthy")
            elif rep.load() >= self.shed_queue_depth:
                reasons.append(f"{rep.name}: backlog {rep.load()} >= "
                               f"{self.shed_queue_depth}")
            else:
                return None  # a replica in good shape: no shedding
        if reasons:
            return reasons
        if self.shed_wait_ms > 0:
            waits = sorted(self._recent_waits)
            if len(waits) >= 8:
                p50 = waits[len(waits) // 2]
                if p50 > self.shed_wait_ms:
                    return [f"queue wait p50 {p50:.0f} ms > "
                            f"{self.shed_wait_ms:.0f} ms"]
        return None

    def _shed_reason_locked(self, r: _Routed) -> Optional[tuple]:
        """Admission-time shed decision for one request; None admits.
        Runs under the router lock (submit holds it); returns
        ``(kind, message parts)`` — no string assembly here."""
        degraded = self._degraded_locked()
        if degraded is None:
            return None
        backlog = len(self._inflight)
        # batch traffic sheds FIRST: under a degraded fleet its backlog
        # bound is half the interactive one, so the queue that remains
        # is spent on the latency-sensitive class
        limit = self.shed_max_queue if r.klass != "batch" \
            else max(1, self.shed_max_queue // 2)
        if backlog >= limit:
            return ("queue_full", [
                f"router backlog hit {backlog} >= {limit} "
                f"({r.klass} bound, MXTPU_SHED_MAX_QUEUE="
                f"{self.shed_max_queue}) with all replicas degraded"]
                + degraded)
        if r.deadline is not None:
            budget_ms = (r.deadline - time.perf_counter()) * 1e3
            waits = sorted(self._recent_waits)
            p50 = waits[len(waits) // 2] if len(waits) >= 8 else 0.0
            if budget_ms <= 0 or p50 > budget_ms:
                return ("deadline", [
                    f"deadline budget {budget_ms:.0f} ms is infeasible "
                    f"at queue-wait p50 {p50:.0f} ms with all replicas "
                    "degraded"] + degraded)
        return None

    def _may_recover_locked(self) -> bool:
        """Whether waiting could produce a healthy replica: a respawn
        factory exists, or some replica is merely degraded (not
        evicted) and may come back fresh. Runs under the router lock
        (submit holds it)."""
        return self._factory is not None or any(
            not rep.evicted for rep in self._replicas)

    def _replica_snapshot(self) -> list:
        """Coherent copy of the replica list for lock-free iteration:
        ``_respawn`` appends from the monitor thread while callers read
        ``replicas``/``engines`` — iterating the live list unlocked is
        the torn-read shape the mxlint lock-order pass flags."""
        with self._lock:
            return list(self._replicas)

    def _pick_locked(self, candidates: list):
        """Lowest predicted wait (rolling p50 × backlog) wins; exact
        ties rotate through a cursor so equal-score replicas share load
        instead of the first in replica order absorbing everything."""
        scored = [(rep.predicted_wait_ms(), rep) for rep in candidates]
        best = min(s for s, _ in scored)
        ties = [rep for s, rep in scored if s == best]
        rep = ties[self._rr % len(ties)]
        self._rr += 1
        return rep

    def _pick_prefill_locked(self):
        """A healthy dedicated prefill-role replica for the KV handoff,
        or None (the decode replica then prefills locally)."""
        pre = [rep for rep in self._replicas
               if not rep.evicted and not rep.retired
               and rep.serves_prefill and rep.healthy]
        return self._pick_locked(pre) if pre else None

    def _assign_locked(self, r: _Routed) -> bool:
        """Place ``r`` on the decode-serving healthy replica with the
        lowest predicted wait; False when none is available (the monitor
        retries until ``no_replica_timeout_s``). With prefill-role
        replicas in the fleet the placement is DISAGGREGATED: the
        chosen prefill worker computes and ships the KV, the decode
        replica adopts it (``RemoteReplica.submit_disagg``). A request
        carrying a prompt digest (multi-turn ``prefix_ids``) first
        narrows the candidates to replicas ADVERTISING that digest —
        prefix affinity — and only falls back to the whole fleet when
        no replica holds the cached prefix."""
        now = time.perf_counter()
        candidates = [rep for rep in self._replicas
                      if rep.healthy and rep.serves_decode
                      and not rep.retired]
        if not candidates:
            r.inner = None
            r.next_try_at = now + self.health_interval_s
            return False
        try:
            # fault point: a placement decision that fails/stalls (raise
            # = this pass places nothing and the monitor retries; delay
            # = a slow placement)
            _faults.fire("router.place", tag=r.klass)
        except _faults.FaultInjected:
            r.inner = None
            r.next_try_at = now + self.health_interval_s
            return False
        pool = candidates
        if r.digest is not None and _prefix.prefix_affinity_enabled():
            affine = [rep for rep in candidates
                      if r.digest in rep.prefix_digests()]
            if affine:
                pool = affine
                _tel.registry().counter("serve/prefix_affinity").inc()
        rep = self._pick_locked(pool)
        remaining_ms = None
        if r.deadline is not None:
            remaining_ms = (r.deadline - time.perf_counter()) * 1e3
            if remaining_ms <= 0:
                return True  # monitor fails it on the next tick
        r.replica = rep
        r.attempts += 1
        r.assigned_at = now
        rep.inflight += 1
        # hand off only prefill-HEAVY prompts: a short prompt's local
        # prefill is cheaper than the handoff's extra RPC hop, and the
        # split's whole point is keeping the long prefills off the
        # decode workers
        pre = None
        if r.prefix is None and hasattr(rep, "submit_disagg") \
                and len(r.prompt) >= self.disagg_min_prompt:
            pre = self._pick_prefill_locked()
        if pre is not None:
            r.inner = rep.submit_disagg(pre, r.prompt, r.max_new,
                                        deadline_ms=remaining_ms,
                                        klass=r.klass,
                                        request_id=r.request_id)
        elif r.prefix is not None:
            r.inner = rep.batcher.submit(r.prompt, r.max_new,
                                         deadline_ms=remaining_ms,
                                         prefix_ids=r.prefix,
                                         request_id=r.request_id)
        else:
            r.inner = rep.batcher.submit(r.prompt, r.max_new,
                                         deadline_ms=remaining_ms,
                                         request_id=r.request_id)
        return True

    # ----------------------------------------------------------- elasticity
    def add_replica(self, rep: Replica) -> Replica:
        """Register a replica mid-flight (fleet elasticity scale-up —
        ``tools.launch.FleetScaler`` spawns a worker, wraps it in a
        ``RemoteReplica`` and hands it here)."""
        with self._lock:
            self._replicas.append(rep)
        _tel.instant("serve.scale", {"action": "add", "replica": rep.name})
        return rep

    def retire_replica(self, rep: Replica) -> Replica:
        """Deliberate scale-down: exclude ``rep`` from placement and
        from the shed gate, let its in-flight work finish on the worker
        (the caller SIGTERMs it — the existing graceful drain), and when
        its health finally fails the eviction schedules NO respawn."""
        rep.retired = True
        _tel.instant("serve.scale", {"action": "retire",
                                     "replica": rep.name})
        return rep

    # -------------------------------------------------------------- monitor
    def _run(self):
        last_health = 0.0
        while not self._stop.wait(0.005):
            now = time.perf_counter()
            if now - last_health >= self.health_interval_s:
                last_health = now
                self._health_pass(now)
            self._request_pass(now)

    def _health_pass(self, now):
        reps = self._replica_snapshot()
        for rep in reps:
            if rep.evicted:
                continue
            ok, reason = rep.health()
            if not ok and not rep.starting:
                # a replica still booting (factory respawn: a worker
                # process importing + warming) is skipped for placement
                # but not evicted — its spawn failure is what evicts it
                self._evict(rep, reason)
        healthy = sum(1 for rep in reps if rep.healthy)
        degraded = sum(1 for rep in reps if not rep.evicted
                       and (rep.starting or not rep.healthy
                            or rep.load() >= self.shed_queue_depth))
        reg = _tel.registry()
        reg.gauge("serve/replicas_healthy").set(healthy)
        reg.gauge("serve/shed_degraded_replicas").set(degraded)
        if _tracing.trace_enabled():
            # throttled clock sampling piggybacks on the health cadence:
            # one ping RTT per remote replica per second keeps the
            # cross-process offset estimate fresh for trace merging
            for rep in reps:
                if rep.evicted or not hasattr(rep, "sample_clock"):
                    continue
                if now >= self._clock_sample_at.get(rep.name, 0.0):
                    self._clock_sample_at[rep.name] = now + 1.0
                    rep.sample_clock()
        if self._factory is not None and self._respawn_at is not None \
                and now >= self._respawn_at:
            self._respawn()

    def _evict(self, rep: Replica, reason: str):
        """Drain an unhealthy replica and mark every routed request on it
        for resubmission."""
        rep.evicted = True
        reg = _tel.registry()
        reg.counter("serve/failovers").inc()
        # cancel what sits undispatched in its queue: the inner futures
        # fail with ReplicaUnavailable and the request pass resubmits
        try:
            rep.batcher.cancel_pending(ReplicaUnavailable(
                f"replica {rep.name} evicted: {reason}"))
        except Exception:  # noqa: BLE001 - the queue may be torn mid-crash
            pass
        # a hung (not dead) dispatcher also holds requests it already
        # popped; their inner futures will never resolve — fail them over
        # too. A zombie completion later is ignored (outer settles once).
        affected = []
        with self._lock:
            for r in self._inflight:
                if r.replica is rep and r.inner is not None \
                        and not r.inner.done():
                    r.inner = None
                    r.replica = None
                    r.next_try_at = 0.0
                    if r.request_id is not None:
                        affected.append(r.request_id)
        # emitted outside the lock: the event write is I/O
        _tel.instant("serve.failover", {"replica": rep.name,
                                        "reason": reason,
                                        "requests": affected[:8],
                                        "n_requests": len(affected)})
        # stop the batcher without waiting on a possibly-hung thread
        try:
            rep.batcher.stop(drain=False, timeout=0.1)
        except Exception:  # noqa: BLE001
            pass
        # a deliberately retired replica (scale-down) leaves for good —
        # respawning it would defeat the scaler
        if self._factory is not None and self._respawn_at is None \
                and not rep.retired:
            self._respawn_at = time.perf_counter() + backoff_delay(
                self._respawn_base, self._respawn_attempt)

    def _respawn(self):
        try:
            rep = self._factory()
        except Exception as e:  # noqa: BLE001 - retry under backoff
            self._respawn_attempt += 1
            self._respawn_at = time.perf_counter() + backoff_delay(
                self._respawn_base, self._respawn_attempt)
            _tel.instant("serve.respawn_failed", {"error": repr(e)})
            return
        with self._lock:
            self._replicas.append(rep)
        self._respawn_attempt = 0
        self._respawn_at = None
        _tel.registry().counter("serve/replica_restarts").inc()
        _tel.instant("serve.replica_restart", {"replica": rep.name})

    def _request_pass(self, now):
        reg = _tel.registry()
        with self._lock:
            records = list(self._inflight)
        done = []
        for r in records:
            if r.outer.done():
                done.append(r)
                continue
            if r.inner is None:
                # waiting for a retry slot / a healthy replica
                if r.deadline is not None and now > r.deadline:
                    reg.counter("serve/deadline_exceeded").inc()
                    _tel.instant("serve.deadline",
                                 {"request_id": r.request_id,
                                  "replica": None, "klass": r.klass,
                                  "where": "unplaced"})
                    r.outer._fail(DeadlineExceeded(
                        "request deadline passed before it could be "
                        "(re)placed on a healthy replica"))
                    done.append(r)
                elif now - r.created > self.no_replica_timeout_s \
                        and not any(rep.healthy
                                    for rep in self._replica_snapshot()):
                    reg.counter("serve/dropped").inc()
                    r.outer._fail(RuntimeError(
                        f"no healthy replica within "
                        f"{self.no_replica_timeout_s:.1f}s"))
                    done.append(r)
                elif now >= r.next_try_at:
                    with self._lock:
                        self._assign_locked(r)
                continue
            if r.inner.done():
                wait = r.inner.queue_wait_ms
                with self._lock:
                    if r.replica is not None:
                        r.replica.inflight = max(0, r.replica.inflight - 1)
                    if r.inner.exception() is None and wait is not None:
                        # feeds the shed gate's rolling p50
                        self._recent_waits.append(wait)
                err = r.inner.exception()
                if err is None:
                    r.outer.weights_version = r.inner.weights_version
                    r.outer.replica = r.inner.replica
                    r.outer.queue_wait_ms = r.inner.queue_wait_ms
                    ft = getattr(r.inner, "first_token_at", None)
                    if ft is not None:
                        # per-class TTFT, measured from the router's
                        # admission instant (the SLO the classes exist
                        # for)
                        ttft = (ft - r.created) * 1e3
                        if r.klass == "batch":
                            reg.histogram(
                                "disagg/ttft_batch_ms").observe(ttft)
                        else:
                            reg.histogram(
                                "disagg/ttft_interactive_ms").observe(
                                    ttft)
                    # SLO attribution: the per-phase breakdown stamped
                    # by the worker (queue/prefill/decode), extended
                    # with router-side phases. ``other_ms`` is the
                    # residual and is deliberately UNCLAMPED so the
                    # ``*_ms`` phases that follow one another (all but
                    # the batcher's ``PHASE_DETAIL``, which lie inside
                    # ``prefill_ms`` and ``decode_ms``) sum to the
                    # observed end-to-end latency exactly, by
                    # construction.
                    tdone = time.perf_counter()
                    phases = dict(getattr(r.inner, "phases", None) or {})
                    if r.attempts > 1 and r.assigned_at is not None:
                        phases["retry_ms"] = \
                            (r.assigned_at - r.created) * 1e3
                    e2e_ms = (tdone - r.created) * 1e3
                    named = sum(v for k, v in phases.items()
                                if k.endswith("_ms")
                                and k not in PHASE_DETAIL
                                and isinstance(v, (int, float)))
                    phases["other_ms"] = e2e_ms - named
                    r.outer.phases = phases
                    slo = slo_batch_ms() if r.klass == "batch" \
                        else slo_interactive_ms()
                    if slo > 0 and e2e_ms > slo:
                        reg.counter(
                            f"serve/slo_burn_{r.klass}").inc()
                    if _tracing.trace_enabled():
                        _tracing.span(
                            "trace.request", _tel.us_of(r.created),
                            {"replica": r.inner.replica,
                             "klass": r.klass,
                             "attempts": r.attempts,
                             "e2e_ms": e2e_ms},
                            request_id=r.request_id,
                            end_us=_tel.us_of(tdone))
                    r.outer._resolve(r.inner.result())
                    reg.counter("serve/completed").inc()
                    done.append(r)
                elif isinstance(err, DeadlineExceeded):
                    _tel.instant("serve.deadline",
                                 {"request_id": r.request_id,
                                  "replica": getattr(
                                      r.replica, "name", None),
                                  "klass": r.klass,
                                  "where": "batcher"})
                    r.outer._fail(err)  # counted at the batcher
                    done.append(r)
                else:
                    self._note_failure(r, err, now)
                    if r.outer.done():
                        done.append(r)
            elif r.deadline is not None and now > r.deadline:
                # dispatched but not resolving (e.g. hung engine): the
                # deadline settles the OUTER future; a zombie inner
                # completion is discarded
                reg.counter("serve/deadline_exceeded").inc()
                _tel.instant("serve.deadline",
                             {"request_id": r.request_id,
                              "replica": getattr(
                                  r.replica, "name", None),
                              "klass": r.klass,
                              "where": "dispatched"})
                r.outer._fail(DeadlineExceeded(
                    "request deadline passed while dispatched"))
                done.append(r)
        if done:
            with self._lock:
                self._inflight = [r for r in self._inflight
                                  if r not in done]

    def _note_failure(self, r: _Routed, err, now):
        """Inner attempt failed: resubmit under bounded backoff, or fail
        the outer future for good."""
        reg = _tel.registry()
        out_of_time = r.deadline is not None and now > r.deadline
        if r.attempts > self.max_retries and not isinstance(
                err, ReplicaUnavailable) or out_of_time:
            reg.counter("serve/dropped").inc()
            r.outer._fail(err if not out_of_time else DeadlineExceeded(
                f"deadline passed after {r.attempts} attempts "
                f"(last error: {err!r})"))
            return
        reg.counter("serve/retries").inc()
        rep_name = getattr(r.replica, "name", None)
        r.inner = None
        r.replica = None
        r.next_try_at = now + backoff_delay(
            self.retry_backoff_s, r.attempts - 1, cap=5.0)
        _tracing.instant("trace.retry",
                         {"replica": rep_name,
                          "attempt": r.attempts,
                          "error": type(err).__name__},
                         request_id=r.request_id)

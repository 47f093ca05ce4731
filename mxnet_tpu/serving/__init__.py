"""Serving front-end: continuous batching, hot weight swap, multi-replica
routing with failover — the self-healing serving plane.

The inference engine (``parallel.infer.InferStep``) turns one *batch* of
prompts into tokens at O(1)/token; this package turns *concurrent
requests* into those batches and keeps doing so across weight updates
and replica failures:

- ``ContinuousBatcher``, the one scheduler (``make_batcher`` builds it),
  runs Orca-style ITERATION-LEVEL scheduling over a paged KV cache
  (``serving.pages`` + the paged attention mode): between decode
  iterations it retires EOS/deadline rows, frees their pages, and
  admits queued requests into the vacated slots via a jitted
  prefill-into-pages dispatch — occupancy is dynamic, shapes are
  static, tokens stream per iteration, and admission control rejects
  with ``Backpressure`` when the pool can't absorb more work. Requests
  carry their own deadlines and resolve their own futures (Yu et al.,
  Orca, OSDI 2022: between decode dispatches is the safe point for
  everything below).
- ``CheckpointWatcher`` hot-swaps newly committed checkpoints into live
  engines between dispatches (double-buffered device params,
  version-tagged responses, zero dropped requests).
- ``Router`` fronts N replicas behind one ``submit()``: health scoring
  from the watchdog heartbeat + per-replica backlog, eviction with
  transparent resubmission (bounded retries, exponential backoff,
  per-request deadlines), respawn via a replica factory, and
  load-shedding admission (``Backpressure`` at submit, ``serve/shed_*``
  accounting) once EVERY replica is degraded.
- ``transport``/``worker``/``remote`` cross the process boundary:
  replicas run as real worker processes (``python -m
  mxnet_tpu.serving.worker``) behind a length-prefixed socket RPC
  (submit/stream/health/stage/swap/drain verbs, no pickle);
  ``RemoteReplica`` gives the router process-level failover (SIGKILL'd
  worker → dead socket/stale heartbeat → eviction + transparent
  resubmission → factory respawns a REAL process) and the
  ``CheckpointWatcher`` drives the same stage-all-then-flip-all hot
  swap over the control channel so every process flips coherently.
- ``disagg`` splits the fleet into prefill and decode roles
  (``MXTPU_ROLE``): prefill workers run the admission prefill and ship
  the filled KV page frames over the ``kv_push`` transport verb (or the
  ``MXTPU_KV_SPILL_DIR`` filesystem spill) to decode workers whose
  batcher ADOPTS them without re-prefilling — bit-identical greedy
  tokens, with any handoff failure degrading to a local re-prefill
  (zero lost requests). The router is SLO-aware: predicted-wait
  placement (worker-reported rolling p50 × backlog, rotating
  tie-break), request classes (``interactive``/``batch``) with
  per-class deadline defaults (``MXTPU_SLO_*_MS``) and batch-first
  shedding, and ``tools.launch.FleetScaler`` elasticity
  (``MXTPU_SCALE_*``).
- ``prefix`` caches computed KV across requests: a radix trie per
  exact prompt maps page-aligned target-token blocks to refcounted KV
  pages; retiring slots donate their chains, admission adopts matched
  prefixes read-only (copy-on-write on the partial tail page) and
  replays only the uncached suffix through a teacher-forced program
  that is bit-identical to the token-at-a-time decode. The router
  prefers replicas advertising the request's prompt digest
  (prefix-affinity placement, ``MXTPU_PREFIX_AFFINITY``).
- ``faults`` plants deterministic failure points in all of the above
  (``MXTPU_FAULT_*``), so the failure paths are testable in tier-1.
- ``tracing`` is the fleet-scope observability plane: distributed
  request tracing (a ``request_id`` minted at ``Router.submit`` rides
  every RPC frame; each process appends parent-linked spans to its own
  events JSONL; ``tools/fleet_trace.py`` merges them into one
  clock-aligned Chrome trace), a telemetry scrape/aggregation loop
  (``FleetTelemetry`` polls each worker's ``telemetry`` verb on
  ``MXTPU_SCRAPE_S``), and per-request SLO attribution
  (``GenerationResult.phases`` — queue/handoff/prefill/decode/retry
  breakdown summing to the observed end-to-end latency, computed from
  the request's own timeline of stamped instants; ``seat``, ``service``
  and ``deliver`` split the first token's latency further).

Env knobs: ``MXTPU_PAGE_SIZE``/``MXTPU_PAGES`` (KV pool geometry),
``MXTPU_ITER_TOKENS`` (decode tokens per scheduler iteration),
``MXTPU_ADMIT_*`` (backpressure thresholds — see ``serving.pages``),
``MXTPU_BATCHER_SLOTS`` (batch slots per dispatch, default 8),
``MXTPU_DECODE_MAX_LEN`` (engine cache capacity — see ``parallel.infer``),
``MXTPU_SWAP_POLL_S`` (checkpoint poll period), ``MXTPU_RETRY_MAX``
(router resubmissions per request), ``MXTPU_RESTART_BACKOFF_S`` (restart
backoff base, shared with ``tools/launch.py``), ``MXTPU_SERVE_PORT`` /
``MXTPU_RPC_TIMEOUT_S`` / ``MXTPU_RPC_CONNECT_S`` (worker transport),
``MXTPU_WORKER_DRAIN_S`` (SIGTERM drain budget), ``MXTPU_SHED_*``
(router load-shedding thresholds), ``MXTPU_PREFIX_CACHE`` /
``MXTPU_PREFIX_MAX_PAGES`` / ``MXTPU_PREFIX_MAX_ROOTS`` /
``MXTPU_PREFIX_AFFINITY`` / ``MXTPU_PREFIX_DIGEST_MAX`` (prefix cache +
affinity — see ``serving.prefix``), ``MXTPU_FAULT_*`` (fault-injection
specs — see ``serving.faults``), ``MXTPU_TRACE`` / ``MXTPU_TRACE_DIR`` /
``MXTPU_SCRAPE_S`` (fleet tracing + telemetry scraping — see
``serving.tracing``).
"""

from . import disagg
from . import faults
from . import pages
from . import prefix
from . import tracing
from .batcher import Backpressure, ContinuousBatcher, DeadlineExceeded, \
    GenerationResult, batcher_slots, iter_tokens_default, make_batcher
from .disagg import HandoffStash, PrefillEngine, kv_spill_dir, \
    worker_role
from .pages import PagePool
from .prefix import PrefixCache, prefix_affinity_enabled, \
    prefix_cache_enabled, prefix_digest_max, prefix_max_pages, \
    prefix_max_roots, prompt_digest
from .router import REQUEST_CLASSES, Replica, ReplicaUnavailable, \
    Router, restart_backoff_s, retry_max, shed_max_queue, \
    shed_queue_depth, shed_wait_ms, slo_batch_ms, slo_interactive_ms
from .remote import RemoteEngineHandle, RemoteReplica
from .tracing import FleetTelemetry, aggregate_snapshots, \
    estimate_offset, replay_scrapes, scrape_interval_s, trace_enabled
from .transport import RpcClient, RpcServer, TransportError, \
    rpc_connect_s, rpc_timeout_s, serve_port
from .watcher import CheckpointWatcher, swap_poll_s, version_for

__all__ = ["ContinuousBatcher", "GenerationResult",
           "DeadlineExceeded", "Backpressure", "PagePool", "pages",
           "Router", "Replica", "ReplicaUnavailable", "CheckpointWatcher",
           "RemoteReplica", "RemoteEngineHandle", "RpcClient", "RpcServer",
           "TransportError", "faults", "batcher_slots",
           "iter_tokens_default", "make_batcher", "swap_poll_s", "version_for", "retry_max",
           "restart_backoff_s", "shed_queue_depth", "shed_wait_ms",
           "shed_max_queue", "rpc_timeout_s", "rpc_connect_s",
           "serve_port", "disagg", "PrefillEngine", "HandoffStash",
           "worker_role", "kv_spill_dir", "REQUEST_CLASSES",
           "slo_interactive_ms", "slo_batch_ms", "prefix", "PrefixCache",
           "prompt_digest", "prefix_cache_enabled", "prefix_max_pages",
           "prefix_max_roots", "prefix_affinity_enabled",
           "prefix_digest_max", "tracing", "FleetTelemetry",
           "aggregate_snapshots", "estimate_offset", "replay_scrapes",
           "scrape_interval_s", "trace_enabled"]

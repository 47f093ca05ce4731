"""Serving worker process: one engine + batcher behind the RPC transport.

The process half of the cross-process serving plane
(``serving.transport``): ``python -m mxnet_tpu.serving.worker --dir D``
builds a net, wraps it in an ``InferStep`` + the serving scheduler
(``serving.make_batcher``: a ``ContinuousBatcher``), writes the PR-1
watchdog heartbeat into ``--dir``, announces itself in ``worker.json``
(name/host/port/pid — written AFTER warmup, so its existence is the
readiness signal), and serves the transport verbs until told to stop:

- **SIGTERM** (or the ``drain`` verb) drains gracefully: new submits
  are rejected with ``ReplicaUnavailable`` (the router replays them
  elsewhere for free), in-flight requests finish and stream their final
  frames, then the process exits 0.
- **SIGKILL** is the crash case the plane exists for: the heartbeat
  goes stale, the router's socket dies, the replica is evicted and its
  in-flight requests transparently resubmit (see
  ``serving.remote.RemoteReplica``).

``--ckpt-dir`` makes a (re)spawned worker adopt the newest committed
checkpoint at boot — a worker respawned after a coordinated hot swap
rejoins at the fleet's CURRENT ``weights_version``, not at its net
factory's initial weights (same version-tag derivation as
``CheckpointWatcher``, so tags stay coherent across the fleet).

Nets come from ``--model transformer`` (a built-in model-zoo
transformer, seeded deterministically — two processes with the same
spec build bit-identical params) or ``--net-factory module:callable``
(any importable zero-config factory). Under ``tools/launch.py`` the
worker picks its identity up from ``MXNET_TPU_PROC_ID``: name defaults
to ``worker-<id>``, the port offsets from ``MXTPU_SERVE_PORT``, and the
heartbeat/announce files land in ``<dir>/worker-<id>`` — so
``python tools/launch.py -n 4 -- python -m mxnet_tpu.serving.worker
--dir /tmp/fleet`` brings up a 4-worker fleet in one line.

Disaggregated serving (``--role`` / ``MXTPU_ROLE``, ``serving.disagg``):
a ``prefill``-role worker serves the ``prefill`` verb — one admission
prefill per request, KV frames shipped to the decode worker named in
``push_to`` over ``kv_push`` (or spilled to ``MXTPU_KV_SPILL_DIR``) —
and REFUSES decode submits; a ``decode``-role worker stashes pushed
frames (``HandoffStash``) until the router's ``submit`` with the same
handoff id claims them, adopting the KV without re-prefilling (missing
or unusable frames re-prefill from the prompt: ``disagg/re_prefills``,
zero lost requests). The default ``both`` co-schedules as before. The
health verb reports the role plus the rolling queue-wait/TTFT p50s the
SLO-aware router places by.

Fault point: ``worker.exit`` (``MXTPU_FAULT_WORKER_EXIT``) hard-kills
the process from the inside (``os._exit``) — sudden process death on a
deterministic schedule, for the chaos bench. ``transport.kv_push``
fires in the prefill worker's push path (raise = the handoff fails and
the decode side re-prefills; delay = a slow push).

Env knobs: ``MXTPU_SERVE_PORT`` (base port, 0 = ephemeral),
``MXTPU_WORKER_DRAIN_S`` (SIGTERM drain budget, default 30),
``MXTPU_RPC_TIMEOUT_S``/``MXTPU_RPC_CONNECT_S`` (transport),
``MXTPU_ROLE``/``MXTPU_KV_SPILL_DIR`` (disaggregation).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time
from typing import Optional

from ..base import MXNetError
from .. import telemetry as _tel
from . import disagg as _disagg
from . import faults as _faults
from . import prefix as _prefix
from . import tracing as _tracing
from .transport import RpcClient, RpcServer, serve_port

__all__ = ["ServingWorker", "WorkerHandle", "spawn_worker", "main",
           "worker_drain_s"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def worker_drain_s(default: float = 30.0) -> float:
    """``MXTPU_WORKER_DRAIN_S``: how long a SIGTERM'd worker may spend
    draining in-flight requests before it stops waiting and exits."""
    v = os.environ.get("MXTPU_WORKER_DRAIN_S", "").strip()
    try:
        return float(v) if v else default
    except ValueError:
        return default


def _proc_id() -> Optional[int]:
    """Rank under ``tools/launch.py`` (``MXNET_TPU_PROC_ID``), else None."""
    v = os.environ.get("MXNET_TPU_PROC_ID", "").strip()
    try:
        return int(v) if v else None
    except ValueError:
        return None


# ------------------------------------------------------------- net factory
def make_transformer_net(vocab: int = 61, units: int = 16, layers: int = 1,
                         heads: int = 2, seed: int = 0,
                         max_length: int = 64,
                         prefix: str = "serve_net_"):
    """Built-in deterministic factory: the model-zoo transformer at a
    CPU-testable size. Two processes calling this with the same spec get
    bit-identical params — the cross-process analogue of the trainer and
    server building the net from the same code."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel

    np.random.seed(seed)
    mx.random.seed(seed)
    net = TransformerModel(src_vocab=vocab, tgt_vocab=vocab, units=units,
                           hidden_size=units * 2, num_layers=layers,
                           num_heads=heads, max_length=max_length,
                           dropout=0.0, prefix=prefix)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    return net


def _net_from_factory(spec: str):
    """``module:callable`` — import and call a zero-arg net factory."""
    mod_name, _, fn_name = spec.partition(":")
    if not mod_name or not fn_name:
        raise MXNetError(
            f"--net-factory wants 'module:callable', got {spec!r}")
    import importlib

    return getattr(importlib.import_module(mod_name), fn_name)()


# ------------------------------------------------------------------ worker
class ServingWorker:
    """One worker process's serving state: engine, batcher, watchdog
    heartbeat, RPC handlers, drain lifecycle."""

    def __init__(self, net, directory: str, name: str,
                 port: int = 0, max_len: int = 24,
                 bucket_keys=(8,), slots: int = 2, max_new: int = 4,
                 warmup: bool = True, heartbeat_s: float = 0.5,
                 ckpt_dir: Optional[str] = None,
                 drain_s: Optional[float] = None,
                 role: Optional[str] = None,
                 max_prefix: int = 0):
        from ..parallel import InferStep
        from ..telemetry.watchdog import Watchdog
        from . import make_batcher

        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self.name = name
        self.drain_s = drain_s if drain_s is not None else worker_drain_s()
        self.role = role if role else _disagg.worker_role()
        if self.role not in _disagg.ROLES:
            raise MXNetError(f"unknown worker role {self.role!r} "
                             f"(one of {_disagg.ROLES})")
        self._lock = threading.Lock()   # guards _staged/_streamers
        self._staged = None             # (arrays staged, pending version)
        self._streamers: list = []
        self._stop = threading.Event()
        self._draining = False
        self.exit_code = 0
        # disaggregated serving state: arrival stash for pushed KV
        # (decode side) and cached worker-to-worker clients (prefill
        # side); _peer_lock guards the cache, never held across a
        # connect or a call
        self._handoffs = _disagg.HandoffStash()
        self._peers: dict = {}
        self._peer_lock = threading.Lock()

        self.engine = InferStep(net, max_len=max_len)
        if ckpt_dir:
            self._adopt_checkpoint(ckpt_dir)
        self.watchdog = Watchdog(directory, interval=heartbeat_s)
        # a dedicated prefill worker never decodes: skip the batcher's
        # decode-program warmup and warm the prefill engine instead
        bat_warmup = warmup and self.role != "prefill"
        self.batcher = make_batcher(
            self.engine, tuple(bucket_keys), slots=slots,
            max_new_tokens=max_new, warmup=bat_warmup, name=name,
            watchdog=self.watchdog, max_prefix_tokens=int(max_prefix))
        self.prefiller = None
        if self.role == "prefill":
            self.prefiller = _disagg.PrefillEngine(
                self.engine, tuple(bucket_keys), warmup=warmup)
        self.watchdog.start()
        self.server = RpcServer({
            "ping": self._handle_ping,
            "health": self._handle_health,
            "submit": self._handle_submit,
            "prefill": self._handle_prefill,
            "kv_push": self._handle_kv_push,
            "stage": self._handle_stage,
            "swap": self._handle_swap,
            "drain": self._handle_drain,
            "telemetry": self._handle_telemetry,
        }, port=port, name=name)

    def _adopt_checkpoint(self, ckpt_dir: str):
        """Boot-time version adoption: a worker (re)spawned after the
        fleet hot-swapped must serve the swapped weights, tagged with
        the SAME version string the watcher handed everyone else."""
        from .. import checkpoint_sharded as _cs
        from .watcher import version_for

        found = _cs.latest_committed(ckpt_dir)
        if found is None:
            return
        path, token = found
        self.engine.swap_params(arrays=_cs.load_sharded(path),
                                version=version_for(path, token))

    # ----------------------------------------------------------- lifecycle
    def announce(self):
        """Publish ``worker.json`` (atomic rename): existence = ready."""
        info = {"name": self.name, "host": self.server.host,
                "port": self.server.port, "pid": os.getpid(),
                "heartbeat": self.watchdog.heartbeat_path,
                "dir": self.directory, "role": self.role}
        path = os.path.join(self.directory, "worker.json")
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(info, f)
        os.replace(tmp, path)
        return info

    def serve_forever(self) -> int:
        """Main-thread loop: idle heartbeat + the ``worker.exit`` fault
        point, until SIGTERM / the drain verb sets the stop event. Then
        drain and tear down. Returns the process exit code."""
        self.server.start()
        self.announce()
        while not self._stop.wait(0.05):
            if _faults.check("worker.exit", tag=self.name) is not None:
                os._exit(29)  # sudden process death, by request
            if self.batcher._drained():
                # idle is progress, not a stall: keep the heartbeat
                # honest while no work exists (a wedged dispatch still
                # goes stale — notify_step only runs when drained)
                self.watchdog.notify_step()
        self.shutdown()
        return self.exit_code

    def request_stop(self):
        self._draining = True
        self._stop.set()

    def shutdown(self):
        """Graceful teardown: drain the batcher (in-flight requests
        finish and stream their final frames), then close transport and
        heartbeat."""
        self._draining = True
        try:
            self.batcher.stop(drain=True, timeout=self.drain_s)
        except Exception:  # noqa: BLE001 - teardown must complete
            pass
        with self._lock:
            streamers = list(self._streamers)
        for t in streamers:
            t.join(timeout=5.0)
        with self._peer_lock:
            peers, self._peers = list(self._peers.values()), {}
        for client in peers:
            client.close()
        self.server.stop()
        self.watchdog.stop()

    # ------------------------------------------------------------ handlers
    def _handle_ping(self, msg, respond):
        # clock_us lets the caller estimate this process's event-clock
        # offset from one round trip (serving.tracing.estimate_offset)
        respond(pong=True, name=self.name, pid=os.getpid(),
                clock_us=_tel.clock_us())

    def _handle_health(self, msg, respond):
        bat = self.batcher
        busy = 0
        slots = getattr(bat, "_slots", None)
        if slots is not None:
            busy = sum(1 for s in slots if s is not None)
        adopted = re_prefilled = None
        stats_lock = getattr(bat, "_stats_lock", None)
        if stats_lock is not None:
            with stats_lock:
                adopted = bat.stats.get("adopted")
                re_prefilled = bat.stats.get("re_prefills")
        digests = prefix_stats = None
        fn = getattr(bat, "prefix_digests", None)
        if fn is not None:
            # the affinity signal: which prompts this worker's prefix
            # cache holds, as compact digests (bounded by the env knob —
            # the health frame must stay small)
            digests = list(fn(_prefix.prefix_digest_max()))
            prefix_stats = bat.prefix_stats()
        respond(healthy=bool(bat.healthy and not self._draining),
                status="draining" if self._draining else "serving",
                queue_depth=bat._queue.qsize() + busy,
                weights_version=self.engine.weights_version,
                role=self.role,
                queue_wait_p50_ms=bat.rolling_wait_ms(),
                ttft_p50_ms=bat.rolling_ttft_ms(),
                disagg_adopted=adopted,
                disagg_re_prefills=re_prefilled,
                prefix_digests=digests,
                prefix_stats=prefix_stats,
                name=self.name, pid=os.getpid(),
                clock_us=_tel.clock_us())

    def _handle_telemetry(self, msg, respond):
        """Scrape verb: one frame with the full registry snapshot plus
        this process's event clock, so the router-side aggregation plane
        (``serving.tracing.FleetTelemetry``) gets counters, histogram
        summaries, and a clock sample from a single round trip."""
        respond(snapshot=_tel.registry().snapshot(),
                clock_us=_tel.clock_us(),
                name=self.name, pid=os.getpid())

    def _handle_submit(self, msg, respond):
        import numpy as np

        if self._draining or not self.batcher.healthy:
            respond(ok=False, error={
                "type": "ReplicaUnavailable",
                "message": f"worker {self.name!r} is draining"})
            return
        if self.role == "prefill":
            respond(ok=False, error={
                "type": "ReplicaUnavailable",
                "message": f"worker {self.name!r} is prefill-role: it "
                           "does not serve decode submits"})
            return
        prompt = np.asarray(msg.get("prompt", ()), np.int32).reshape(-1)
        frames = None
        handoff = msg.get("handoff")
        if handoff:
            frames = self._handoffs.pop(str(handoff))
            if frames is None:
                spill = _disagg.kv_spill_dir()
                if spill:
                    frames = _disagg.load_spilled(spill, str(handoff))
            if frames is None:
                # the push never landed (dead prefill worker, dropped
                # link, torn spill): prefill locally from the prompt
                _tel.registry().counter("disagg/re_prefills").inc()
        fut = self.batcher.submit(
            prompt, msg.get("max_new_tokens"),
            deadline_ms=msg.get("deadline_ms"), frames=frames,
            prefix_ids=msg.get("prefix_ids"),
            request_id=(msg.get("trace") or {}).get("request_id"))
        try:
            t = threading.Thread(target=self._stream_result,
                                 args=(fut, respond),
                                 name="mxtpu-worker-stream", daemon=True)
            with self._lock:
                self._streamers.append(t)
                if len(self._streamers) > 64:
                    self._streamers = [s for s in self._streamers
                                       if s.is_alive()]
            t.start()
        except Exception as e:  # noqa: BLE001 - fail the row, answer the peer
            # without this, a thread-spawn failure leaves a future whose
            # tokens nobody will ever stream and the caller camped on
            # its deadline: fail it, then let _dispatch answer ok=False.
            if not fut.done():
                fut._fail(e)
            raise

    def _stream_result(self, fut, respond):
        """Relay one request's token stream, then its final frame — runs
        on its own thread so the connection's reader never blocks on a
        decode."""
        try:
            for chunk in fut.tokens_iter():
                if not respond(done=False, stream=chunk):
                    break  # peer gone: the batcher still finishes the row
            tokens = fut.result(timeout=0)
        except BaseException as e:  # noqa: BLE001 - relay the failure
            respond(ok=False, error={"type": type(e).__name__,
                                     "message": str(e)})
            return
        respond(tokens=tokens, weights_version=fut.weights_version,
                replica=self.name, queue_wait_ms=fut.queue_wait_ms,
                phases=fut.phases, request_id=fut.request_id)

    # ------------------------------------------------ disaggregated verbs
    def _peer(self, address) -> RpcClient:
        """Cached worker-to-worker RPC client (prefill -> decode
        ``kv_push``). A dead cached link is replaced; connects happen
        OUTSIDE the cache lock."""
        with self._peer_lock:
            client = self._peers.get(address)
        if client is not None and client.dead is None:
            return client
        fresh = RpcClient(address,
                          name=f"{self.name}->{address}").connect(
                              budget_s=5.0)
        with self._peer_lock:
            held = self._peers.get(address)
            if held is not None and held is not client \
                    and held.dead is None:
                chosen = held  # another handler won the connect race
            else:
                self._peers[address] = fresh
                chosen = fresh
        if chosen is not fresh:
            fresh.close()
        return chosen

    def _handle_prefill(self, msg, respond):
        """Prefill-role verb: run ONE admission prefill and ship the
        filled KV frames to the decode worker named in ``push_to`` (or
        the ``MXTPU_KV_SPILL_DIR`` spill). The frames reproduce exactly
        what the decode worker's own ``prefill_paged`` would have
        written, so adopted decode is bit-identical.

        The work runs on its OWN thread: all of a router's prefill
        verbs arrive over one connection, and the transport dispatches
        a connection's verbs inline on its reader thread — served
        inline they would serialize (and the ``PrefillEngine``'s
        request batching could never engage)."""
        if self.prefiller is None:
            raise MXNetError(
                f"worker {self.name!r} has role {self.role!r}: no "
                "prefill engine (spawn it with --role prefill)")
        if self._draining:
            respond(ok=False, error={
                "type": "ReplicaUnavailable",
                "message": f"worker {self.name!r} is draining"})
            return
        handoff = str(msg.get("handoff") or "")
        if not handoff:
            raise MXNetError("prefill verb needs a 'handoff' id")
        t = threading.Thread(target=self._run_prefill,
                             args=(msg, handoff, respond),
                             name="mxtpu-worker-prefill", daemon=True)
        with self._lock:
            self._streamers.append(t)
            if len(self._streamers) > 64:
                self._streamers = [s for s in self._streamers
                                   if s.is_alive()]
        t.start()

    def _run_prefill(self, msg, handoff, respond):
        """Prefill-thread body: prefill (batched with concurrent
        callers), push, respond — exceptions relay as error frames (the
        transport's inline catch does not cover this thread)."""
        try:
            with _tracing.request_scope(
                    (msg.get("trace") or {}).get("request_id")):
                self._prefill_and_push(msg, handoff, respond)
        except BaseException as e:  # noqa: BLE001 - relay the failure
            respond(ok=False, error={"type": type(e).__name__,
                                     "message": str(e)})

    def _prefill_and_push(self, msg, handoff, respond):
        tp0 = _tracing.clock_us()
        frames = self.prefiller.prefill(msg.get("prompt", ()))
        _tracing.span("trace.prefill", tp0,
                      {"replica": self.name, "handoff": handoff})
        nbytes = _disagg.frame_bytes(frames)
        t0 = time.perf_counter()
        tk0 = _tracing.clock_us()
        # fault point: the push itself drops (raise) or crawls (delay) —
        # the decode side then re-prefills from the prompt
        _faults.fire("transport.kv_push",
                     tag=str(msg.get("push_to") or handoff))
        spill = _disagg.kv_spill_dir()
        if spill:
            _disagg.spill_frames(spill, handoff, frames)
        else:
            push_to = msg.get("push_to")
            if not push_to:
                raise MXNetError("prefill verb needs 'push_to' when "
                                 "MXTPU_KV_SPILL_DIR is unset")
            meta, bufs = _disagg.pack_frames(frames)
            self._peer(str(push_to)).call(
                "kv_push", {"handoff": handoff, "meta": meta},
                bin_frames=bufs)
        reg = _tel.registry()
        reg.histogram("disagg/kv_push_ms").observe(
            (time.perf_counter() - t0) * 1e3)
        reg.counter("disagg/kv_bytes").inc(nbytes)
        _tracing.span("trace.kv_push", tk0,
                      {"replica": self.name, "handoff": handoff,
                       "kv_bytes": nbytes, "spilled": bool(spill)})
        respond(pushed=True, handoff=handoff, kv_bytes=nbytes,
                spilled=bool(spill))

    def _handle_kv_push(self, msg, respond):
        """Decode-role verb: stash one handoff's KV frames (JSON meta +
        the binary frames the transport read after the header) until the
        matching ``submit`` claims them."""
        handoff = str(msg.get("handoff") or "")
        if not handoff:
            raise MXNetError("kv_push needs a 'handoff' id")
        frames = _disagg.unpack_frames(msg.get("meta") or {},
                                       msg.get("_bin") or [])
        self._handoffs.put(handoff, frames)
        respond(received=True, handoff=handoff)

    def _handle_stage(self, msg, respond):
        """Swap phase 1: load the committed checkpoint host-side and
        stage it into the engine's standby buffer. The live set is
        untouched — serving continues on the old weights."""
        from .. import checkpoint_sharded as _cs

        path = msg.get("path")
        if not path:
            raise MXNetError("stage verb needs a checkpoint 'path'")
        with _tracing.request_scope(
                (msg.get("trace") or {}).get("request_id")):
            t0 = _tracing.clock_us()
            _faults.fire("ckpt.load", tag=path)
            staged = self.engine.stage_params(_cs.load_sharded(path))
            with self._lock:
                self._staged = staged
            _tracing.span("trace.stage", t0,
                          {"replica": self.name, "path": path})
        respond(staged=True, path=path)

    def _handle_swap(self, msg, respond):
        """Swap phase 2: flip the staged buffer live — one reference
        assignment, taken by the next dispatch."""
        with self._lock:
            staged, self._staged = self._staged, None
        if staged is None:
            raise MXNetError(
                "swap verb with nothing staged (stage must precede swap)")
        with _tracing.request_scope(
                (msg.get("trace") or {}).get("request_id")):
            t0 = _tracing.clock_us()
            version = self.engine.swap_params(staged=staged,
                                              version=msg.get("version"))
            _tracing.span("trace.swap", t0,
                          {"replica": self.name, "version": version})
        respond(version=version)

    def _handle_drain(self, msg, respond):
        """Stop accepting, wait for the queue+slots to empty (in-flight
        streams finish meanwhile), then acknowledge and schedule exit."""
        self._draining = True
        deadline = time.monotonic() + self.drain_s
        while not self.batcher._drained() and time.monotonic() < deadline:
            time.sleep(0.01)
        respond(drained=self.batcher._drained())
        self._stop.set()


# ------------------------------------------------------------- spawn helper
class WorkerHandle:
    """Parent-side handle for one spawned worker process."""

    def __init__(self, proc, directory: str, name: str):
        self.proc = proc
        self.directory = directory
        self.name = name

    @property
    def pid(self) -> int:
        return self.proc.pid

    def info(self) -> Optional[dict]:
        """Parsed ``worker.json``, or None while the worker boots."""
        try:
            with open(os.path.join(self.directory, "worker.json")) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def wait_ready(self, timeout: float = 120.0) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            info = self.info()
            if info is not None:
                return info
            if self.proc.poll() is not None:
                raise MXNetError(
                    f"worker {self.name!r} exited rc={self.proc.returncode} "
                    f"before announcing (see {self.log_path})")
            time.sleep(0.05)
        raise MXNetError(f"worker {self.name!r} not ready in {timeout}s")

    @property
    def address(self) -> str:
        info = self.wait_ready()
        return f"{info['host']}:{info['port']}"

    @property
    def heartbeat_path(self) -> str:
        return os.path.join(self.directory, "heartbeat.json")

    @property
    def log_path(self) -> str:
        return os.path.join(self.directory, "worker.log")

    def alive(self) -> bool:
        return self.proc.poll() is None

    def terminate(self):
        """SIGTERM: the worker drains in-flight requests and exits 0."""
        self.proc.terminate()

    def kill(self):
        """SIGKILL: sudden death — the failure the plane must absorb."""
        self.proc.kill()

    def wait(self, timeout: Optional[float] = None) -> int:
        return self.proc.wait(timeout=timeout)


def spawn_worker(directory: str, name: Optional[str] = None,
                 port: int = 0, model: Optional[dict] = None,
                 net_factory: Optional[str] = None,
                 max_len: int = 24, bucket_keys=(8,), slots: int = 2,
                 max_new: int = 4, ckpt_dir: Optional[str] = None,
                 warmup: bool = True,
                 heartbeat_s: float = 0.1,
                 extra_env: Optional[dict] = None,
                 python: Optional[str] = None,
                 role: Optional[str] = None,
                 max_prefix: int = 0) -> WorkerHandle:
    """Spawn one serving worker process (``-m mxnet_tpu.serving.worker``)
    with stdout/stderr captured to ``<directory>/worker.log``. Readiness
    is ``handle.wait_ready()`` (the worker announces after warmup)."""
    import subprocess

    os.makedirs(directory, exist_ok=True)
    name = name or os.path.basename(os.path.normpath(directory))
    cmd = [python or sys.executable, "-m", "mxnet_tpu.serving.worker",
           "--dir", directory, "--name", name, "--port", str(port),
           "--max-len", str(max_len),
           "--bucket-keys", ",".join(str(k) for k in bucket_keys),
           "--slots", str(slots), "--max-new", str(max_new),
           "--heartbeat-s", str(heartbeat_s)]
    if net_factory:
        cmd += ["--net-factory", net_factory]
    else:
        for k, v in (model or {}).items():
            cmd += [f"--{k.replace('_', '-')}", str(v)]
    if ckpt_dir:
        cmd += ["--ckpt-dir", ckpt_dir]
    if role:
        cmd += ["--role", role]
    if max_prefix:
        cmd += ["--max-prefix", str(max_prefix)]
    if not warmup:
        cmd += ["--no-warmup"]
    env = dict(os.environ)
    env.update(extra_env or {})
    # resolve `-m mxnet_tpu...` via cwd: the child imports the same
    # checkout the parent runs from
    log = open(os.path.join(directory, "worker.log"), "ab")
    try:
        proc = subprocess.Popen(cmd, env=env, cwd=_REPO_ROOT,
                                stdout=log, stderr=log)
    finally:
        log.close()
    return WorkerHandle(proc, directory, name)


# --------------------------------------------------------------- entrypoint
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--dir", required=True,
                    help="worker state dir: heartbeat.json, worker.json, "
                    "worker.log (per-proc subdir under tools/launch.py)")
    ap.add_argument("--name", default=None)
    ap.add_argument("--port", type=int, default=None,
                    help="listen port (default MXTPU_SERVE_PORT [+rank]; "
                    "0 = ephemeral, announced in worker.json)")
    ap.add_argument("--net-factory", default=None,
                    help="module:callable returning an initialized net")
    ap.add_argument("--model", default="transformer",
                    choices=["transformer"])
    ap.add_argument("--vocab", type=int, default=61)
    ap.add_argument("--units", type=int, default=16)
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-length", type=int, default=64)
    ap.add_argument("--prefix", default="serve_net_")
    ap.add_argument("--max-len", type=int, default=24,
                    help="engine KV capacity (InferStep max_len)")
    ap.add_argument("--bucket-keys", default="8",
                    help="comma-separated prompt bucket menu")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--role", default=None,
                    choices=["both", "prefill", "decode"],
                    help="disaggregated-fleet role (default MXTPU_ROLE "
                    "or 'both')")
    ap.add_argument("--max-prefix", type=int, default=0,
                    help="max forced-history tokens per request (> 0 "
                    "sizes the suffix-replay menu and enables the "
                    "prefix cache per MXTPU_PREFIX_CACHE)")
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--heartbeat-s", type=float, default=0.5)
    ap.add_argument("--ckpt-dir", default=None,
                    help="adopt the newest committed checkpoint at boot")
    ap.add_argument("--drain-s", type=float, default=None)
    args = ap.parse_args(argv)

    rank = _proc_id()
    name = args.name or (f"worker-{rank}" if rank is not None
                         else f"worker-{os.getpid()}")
    directory = args.dir
    if rank is not None and args.name is None:
        directory = os.path.join(directory, name)
    port = args.port if args.port is not None else serve_port()
    if port and rank:
        port += rank
    # per-process trace sink (MXTPU_TRACE + MXTPU_TRACE_DIR): each
    # worker writes its own events.jsonl; tools/fleet_trace.py merges
    # them onto the router's timeline afterwards
    _tracing.maybe_enable_process(name)

    if args.net_factory:
        net = _net_from_factory(args.net_factory)
    else:
        net = make_transformer_net(
            vocab=args.vocab, units=args.units, layers=args.layers,
            heads=args.heads, seed=args.seed, max_length=args.max_length,
            prefix=args.prefix)
    worker = ServingWorker(
        net, directory, name, port=port, max_len=args.max_len,
        bucket_keys=tuple(int(k) for k in args.bucket_keys.split(",")),
        slots=args.slots, max_new=args.max_new,
        warmup=not args.no_warmup,
        heartbeat_s=args.heartbeat_s, ckpt_dir=args.ckpt_dir,
        drain_s=args.drain_s, role=args.role,
        max_prefix=args.max_prefix)

    def _sigterm(signum, frame):
        worker.request_stop()

    signal.signal(signal.SIGTERM, _sigterm)
    signal.signal(signal.SIGINT, _sigterm)
    return worker.serve_forever()


if __name__ == "__main__":
    sys.exit(main())

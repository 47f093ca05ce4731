#!/usr/bin/env python
"""Multi-process / multi-host job launcher.

TPU-native analogue of the reference's ``tools/launch.py`` + dmlc-tracker
[unverified]: that stack started a ZMQ scheduler and spawned workers/servers
over ssh/mpi/yarn with ``DMLC_*`` env vars. Here there are no parameter
servers — every process is a worker that joins one JAX coordination service
(`jax.distributed`) — so the launcher's whole job is: pick a coordinator
address, spawn N processes with the ``MXNET_TPU_*`` rendezvous env vars
(read by ``mxnet_tpu.parallel.init_process_group`` and ``KVStoreDist``),
stream their output, and propagate failures.

Launchers:
  local  spawn all N processes on this machine (testing / single-host
         multi-process; the reference's ``--launcher local``). Each child
         gets the parent's whole environment plus the three rendezvous
         variables and NO chip assignment: a chip belongs to one process,
         so this is a CPU launcher until chips are assigned per child.
  ssh    one process per line of --hostfile via ssh (multi-host; the
         reference's ssh tracker). Assumes a shared working directory and
         passwordless ssh, like the reference.

The same spawn machinery brings up a SERVING fleet: each
``mxnet_tpu.serving.worker`` process reads its rank from
``MXNET_TPU_PROC_ID`` to derive its name (``worker-<rank>``), its state
subdirectory and its port offset from ``MXTPU_SERVE_PORT``, so one
launch line starts N workers a router can front via
``serving.RemoteReplica``.

Examples:
  python tools/launch.py -n 4 -- python train.py --kv-store dist_sync
  python tools/launch.py -n 8 --launcher ssh -H hosts.txt -- python train.py
  MXTPU_SERVE_PORT=7070 python tools/launch.py -n 2 -- \\
      python -m mxnet_tpu.serving.worker --dir /tmp/fleet
"""

from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys
import threading
import time


def find_free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def worker_env(coordinator: str, num_procs: int, proc_id: int) -> dict:
    env = dict(os.environ)
    env.update(
        {
            "MXNET_TPU_COORDINATOR": coordinator,
            "MXNET_TPU_NUM_PROCS": str(num_procs),
            "MXNET_TPU_PROC_ID": str(proc_id),
        }
    )
    return env


def _pump(proc: subprocess.Popen, tag: str):
    for line in iter(proc.stdout.readline, b""):
        sys.stdout.write(f"[{tag}] {line.decode(errors='replace')}")
        sys.stdout.flush()


def spawn_procs(num_procs: int, command, coordinator: str | None = None,
                env_extra: dict | None = None):
    """Spawn ``command`` num_procs times with the rendezvous env vars;
    returns ``(procs, pumps)`` — the reusable half of :func:`launch_local`
    (chaos drivers spawn serving-worker fleets through it and keep the
    per-process handles so they can SIGKILL/SIGTERM individuals)."""
    procs = []
    pumps = []
    for pid in range(num_procs):
        env = worker_env(coordinator, num_procs, pid)
        env.update(env_extra or {})
        p = subprocess.Popen(
            command,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        t = threading.Thread(target=_pump, args=(p, f"worker-{pid}"), daemon=True)
        t.start()
        procs.append(p)
        pumps.append(t)
    return procs, pumps


def launch_local(num_procs: int, command, coordinator: str | None = None,
                 timeout: float | None = None):
    """Spawn ``command`` num_procs times locally; returns max exit code.

    Failure PROPAGATES: when any worker exits nonzero (or dies on a
    signal), the remaining workers are terminated instead of being left
    hung in a collective that will never complete — the reference's
    tracker killed the job the same way. ``timeout`` (seconds) bounds the
    whole job; expiry kills all workers and returns 124."""
    coordinator = coordinator or f"localhost:{find_free_port()}"
    procs, pumps = spawn_procs(num_procs, command, coordinator)

    def _kill_all():
        for p in procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + 5
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(0.1, deadline - time.time()))
                except subprocess.TimeoutExpired:
                    pass
        # SIGKILL anything that survived the grace period — a worker
        # ignoring SIGTERM inside a collective must not outlive the job
        for p in procs:
            if p.poll() is None:
                p.kill()

    rc = 0
    start = time.time()
    try:
        live = set(range(num_procs))
        while live:
            if timeout is not None and time.time() - start > timeout:
                print(f"launch: job timed out after {timeout}s; killing "
                      f"workers {sorted(live)}")
                _kill_all()
                return 124
            for pid in sorted(live):
                code = procs[pid].poll()
                if code is None:
                    continue
                live.discard(pid)
                if code != 0:
                    print(f"launch: worker-{pid} exited with {code}; "
                          f"terminating remaining workers {sorted(live)}")
                    _kill_all()
                    return code
            time.sleep(0.05)
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        raise
    for t in pumps:
        t.join(timeout=5)
    return rc


def restart_backoff_s(default: float = 1.0) -> float:
    """``MXTPU_RESTART_BACKOFF_S``: base delay of the capped exponential
    backoff between elastic restart attempts (shared contract with the
    serving router's replica respawn)."""
    v = os.environ.get("MXTPU_RESTART_BACKOFF_S", "").strip()
    try:
        return float(v) if v else default
    except ValueError:
        return default


def scale_min(default: int = 1) -> int:
    """``MXTPU_SCALE_MIN``: the serving fleet's decode-worker floor —
    :class:`FleetScaler` never retires below it."""
    v = os.environ.get("MXTPU_SCALE_MIN", "").strip()
    try:
        return max(int(v), 1) if v else default
    except ValueError:
        return default


def scale_max(default: int = 4) -> int:
    """``MXTPU_SCALE_MAX``: the decode-worker ceiling —
    :class:`FleetScaler` never grows past it."""
    v = os.environ.get("MXTPU_SCALE_MAX", "").strip()
    try:
        return max(int(v), 1) if v else default
    except ValueError:
        return default


def scale_cooldown_s(default: float = 30.0) -> float:
    """``MXTPU_SCALE_COOLDOWN_S``: minimum seconds between scaling
    actions (either direction) — a spawn takes import+warmup time, so
    back-to-back decisions would thrash on a signal the previous action
    has not yet moved."""
    v = os.environ.get("MXTPU_SCALE_COOLDOWN_S", "").strip()
    try:
        return float(v) if v else default
    except ValueError:
        return default


def scale_wait_ms(default: float = 0.0) -> float:
    """``MXTPU_SCALE_WAIT_MS``: rolling queue-wait p50 (ms) above which
    a pool counts as hot regardless of occupancy — the PREFILL pool's
    primary pressure signal (prefill workers run one admission prefill
    per request, so occupancy says little; the queue wait the decode
    handoffs see says everything). 0 disables the wait gate."""
    v = os.environ.get("MXTPU_SCALE_WAIT_MS", "").strip()
    try:
        return max(float(v), 0.0) if v else default
    except ValueError:
        return default


class FleetScaler:
    """Serving-fleet elasticity supervisor: grow a worker pool on
    sustained pressure, drain and retire workers when idle. One scaler
    supervises ONE role pool (``role="decode"`` default); a
    disaggregated fleet runs a second instance with ``role="prefill"``
    over its prefill workers — same loop, different pressure signal.

    The scaler is deliberately decoupled from the serving package — it
    drives three callables, so the same loop supervises an in-process
    router fleet, a ``spawn_worker`` process fleet, or a test fake:

    ``pressure()``
        -> dict with ``size`` (current workers in this pool),
        ``occupancy`` (mean decode-batch occupancy, 0..1), ``shed``
        (CUMULATIVE router shed count; the scaler differences it) and
        optionally ``queue_wait_ms`` (the pool's rolling queue-wait
        p50 — for a prefill pool, the mean of the prefill replicas'
        worker-reported p50s; occupancy is meaningless for workers
        that run one admission prefill per request).
    ``spawn()``
        start one worker of this role and register it (e.g.
        ``spawn_worker(role=...)`` + ``RemoteReplica.spawning`` +
        ``Router.add_replica``).
    ``retire()``
        pick one idle worker of this role, ``Router.retire_replica``
        it and SIGTERM the process (the existing graceful drain) —
        return False when nothing is retirable (the scaler just waits).

    Policy: ``sustain`` consecutive samples of occupancy >= ``high``,
    queue-wait p50 >= ``wait_high_ms`` (``MXTPU_SCALE_WAIT_MS``; 0
    disables) or ANY shed growth scale UP; ``sustain`` samples of
    occupancy <= ``low`` with no sheds and the wait below the gate
    scale DOWN; every action is separated by ``cooldown_s``
    (``MXTPU_SCALE_COOLDOWN_S``) and clamped to [``MXTPU_SCALE_MIN``,
    ``MXTPU_SCALE_MAX``]. Actions are counted per role:
    ``serve/scale_up``/``serve/scale_down`` for the decode pool,
    ``serve/scale_up_prefill``/``serve/scale_down_prefill`` for a
    prefill pool (the ``serve.scale`` instant carries ``role`` too).

    Thread shape: decisions run under the scaler lock
    (``_decide_locked``); the spawn/retire callables — which may block
    for seconds — run OUTSIDE it, on whichever thread called
    :meth:`step` (the supervisor loop, or a test driving steps
    manually).
    """

    def __init__(self, pressure, spawn, retire,
                 min_workers: int | None = None,
                 max_workers: int | None = None,
                 cooldown_s: float | None = None,
                 interval_s: float = 1.0, high: float = 0.85,
                 low: float = 0.15, sustain: int = 3,
                 start: bool = False, role: str = "decode",
                 wait_high_ms: float | None = None):
        self._pressure = pressure
        self._spawn = spawn
        self._retire = retire
        self.role = str(role)
        self.min_workers = min_workers if min_workers is not None \
            else scale_min()
        self.max_workers = max_workers if max_workers is not None \
            else scale_max()
        self.cooldown_s = cooldown_s if cooldown_s is not None \
            else scale_cooldown_s()
        self.wait_high_ms = wait_high_ms if wait_high_ms is not None \
            else scale_wait_ms()
        self.interval_s = float(interval_s)
        self.high = float(high)
        self.low = float(low)
        self.sustain = max(int(sustain), 1)
        self._lock = threading.Lock()
        self._hot = 0           # consecutive high-pressure samples
        self._cold = 0          # consecutive idle samples
        self._last_shed = None  # previous cumulative shed count
        self._last_action_at = None  # monotonic instant; None = never
        self.actions: list = []  # ("up"/"down", monotonic instant)
        self._stop_evt = threading.Event()
        self._thread = None
        if start:
            self.start()

    # ------------------------------------------------------------ lifecycle
    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._run, name="mxtpu-fleet-scaler", daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 10.0):
        self._stop_evt.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=timeout)

    def _run(self):
        while not self._stop_evt.wait(self.interval_s):
            try:
                self.step()
            except Exception:  # noqa: BLE001 - a scaler crash must never
                pass           # take the serving plane down

    # --------------------------------------------------------------- policy
    def _decide_locked(self, sample: dict, now: float):
        """Pure decision under the scaler lock: update the sustained
        counters and return 'up'/'down'/None. No callable (and nothing
        blocking) runs in here."""
        size = int(sample.get("size", 0))
        occ = float(sample.get("occupancy", 0.0))
        shed = sample.get("shed")
        shed_delta = 0
        if shed is not None:
            if self._last_shed is not None:
                shed_delta = max(int(shed) - self._last_shed, 0)
            self._last_shed = int(shed)
        wait = sample.get("queue_wait_ms")
        wait_hot = bool(self.wait_high_ms) and wait is not None \
            and float(wait) >= self.wait_high_ms
        hot = occ >= self.high or shed_delta > 0 or wait_hot
        cold = occ <= self.low and shed_delta == 0 and not wait_hot
        self._hot = self._hot + 1 if hot else 0
        self._cold = self._cold + 1 if cold else 0
        if self._last_action_at is not None \
                and now - self._last_action_at < self.cooldown_s:
            return None
        if self._hot >= self.sustain and size < self.max_workers:
            self._hot = 0
            self._cold = 0
            self._last_action_at = now
            self.actions.append(("up", now))
            return "up"
        if self._cold >= self.sustain and size > self.min_workers:
            self._hot = 0
            self._cold = 0
            self._last_action_at = now
            self.actions.append(("down", now))
            return "down"
        return None

    def step(self):
        """One supervision sample: read pressure, decide, act. Returns
        the action taken ('up'/'down'/None)."""
        sample = self._pressure()
        now = time.monotonic()
        with self._lock:
            action = self._decide_locked(dict(sample), now)
        if action == "up":
            self._spawn()
            self._count("serve/scale_up", sample)
        elif action == "down":
            if self._retire() is False:
                with self._lock:
                    # nothing retirable: undo the action record, spend
                    # no cooldown
                    self._last_action_at = None
                    self.actions.pop()
                return None
            self._count("serve/scale_down", sample)
        return action

    def _count(self, counter: str, sample: dict):
        """Scaling accounting (best-effort — the launcher must run even
        where the package is not importable). Non-decode pools count
        under a role-suffixed name so the prefill pool's elasticity is
        visible separately from the decode pool's."""
        if self.role != "decode":
            counter = f"{counter}_{self.role}"
        try:
            from mxnet_tpu import telemetry as _tel

            _tel.registry().counter(counter).inc()
            _tel.instant("serve.scale", {
                "counter": counter,
                "role": self.role,
                "occupancy": sample.get("occupancy"),
                "queue_wait_ms": sample.get("queue_wait_ms"),
                "size": sample.get("size")})
        except Exception:  # noqa: BLE001
            pass


def _count_restart(attempt: int, rc: int, delay: float):
    """Restart accounting in the launcher's telemetry registry (the
    ``launch/`` family; best-effort — the launcher must run even where
    the package is not importable)."""
    try:
        from mxnet_tpu import telemetry as _tel

        _tel.registry().counter("launch/restarts").inc()
        _tel.instant("launch.restart",
                     {"attempt": attempt, "rc": rc, "backoff_s": delay})
    except Exception:  # noqa: BLE001
        pass


def launch_elastic(num_procs: int, command, max_restarts: int = 0,
                   coordinator: str | None = None,
                   timeout: float | None = None,
                   backoff_s: float | None = None,
                   max_backoff_s: float = 30.0,
                   _sleep=time.sleep):
    """Restart-based failure recovery (SURVEY §5: the reference
    ecosystem's answer to worker failure was checkpoint + full-job
    restart — there is no partial-membership mode in a bulk-synchronous
    collectives job, so ELASTIC here means: when any worker dies, tear
    the job down and relaunch ALL workers, which resume from the latest
    committed checkpoint (``mxnet_tpu.checkpoint`` /
    ``TrainStep.load_checkpoint``). Each attempt gets a fresh
    coordinator port (a user-supplied ``coordinator`` is honored on the
    FIRST attempt only — relaunching on the dead attempt's port could
    collide with TIME_WAIT sockets or stale coordination-service state);
    ``MXNET_TPU_RESTART_COUNT`` tells workers which attempt they are.

    Restarts are spaced by capped exponential backoff with jitter
    (``backoff_s`` base, ``MXTPU_RESTART_BACKOFF_S`` default 1.0,
    doubling per attempt up to ``max_backoff_s``): a job that dies
    instantly — bad binary, dead coordinator host, full disk — must not
    hammer the scheduler/rendezvous with back-to-back relaunches.
    Restarts are counted in the telemetry registry (``launch/restarts``)."""
    import random

    attempts = max_restarts + 1
    base = backoff_s if backoff_s is not None else restart_backoff_s()
    rc = 0
    for attempt in range(attempts):
        os.environ["MXNET_TPU_RESTART_COUNT"] = str(attempt)
        rc = launch_local(num_procs, command,
                          coordinator=coordinator if attempt == 0
                          else None, timeout=timeout)
        if rc == 0:
            return 0
        if attempt + 1 >= attempts:
            print(f"launch: attempt {attempt + 1}/{attempts} failed "
                  f"rc={rc}; giving up")
            break
        delay = min(base * (2.0 ** attempt), max_backoff_s) \
            * (1.0 + 0.25 * random.random())
        print(f"launch: attempt {attempt + 1}/{attempts} failed rc={rc}; "
              f"restarting from the latest checkpoint in {delay:.1f}s")
        _count_restart(attempt, rc, delay)
        if delay > 0:
            _sleep(delay)
    return rc


def launch_ssh(hosts, command, coordinator: str | None = None):
    """One process per host via ssh (reference ssh tracker semantics)."""
    num = len(hosts)
    coordinator = coordinator or f"{hosts[0]}:{find_free_port()}"
    cwd = os.getcwd()
    procs = []
    pumps = []
    for pid, host in enumerate(hosts):
        envs = " ".join(
            f"{k}={shlex.quote(v)}"
            for k, v in worker_env(coordinator, num, pid).items()
            if k.startswith(("MXNET_", "JAX_", "XLA_", "TPU_", "PYTHON"))
        )
        remote = f"cd {shlex.quote(cwd)} && env {envs} {' '.join(shlex.quote(c) for c in command)}"
        p = subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", host, remote],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        t = threading.Thread(target=_pump, args=(p, host), daemon=True)
        t.start()
        procs.append(p)
        pumps.append(t)
    rc = 0
    for p in procs:
        rc = max(rc, p.wait())
    for t in pumps:
        t.join(timeout=5)
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument(
        "--launcher", choices=["local", "ssh"], default="local",
    )
    ap.add_argument("-H", "--hostfile", help="one host per line (ssh launcher)")
    ap.add_argument(
        "--coordinator",
        help="host:port of the jax.distributed coordinator "
        "(default: this host, a free port)",
    )
    ap.add_argument(
        "--max-restarts", type=int, default=0,
        help="relaunch the whole job up to N times when a worker dies "
        "(workers resume from the latest committed checkpoint)",
    )
    ap.add_argument(
        "--restart-backoff", type=float, default=None,
        help="base seconds of the capped exponential backoff between "
        "restart attempts (default: MXTPU_RESTART_BACKOFF_S or 1.0)",
    )
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        ap.error("no worker command given")
    if args.launcher == "local":
        if args.max_restarts > 0:
            rc = launch_elastic(args.num_workers, command,
                                max_restarts=args.max_restarts,
                                coordinator=args.coordinator,
                                backoff_s=args.restart_backoff)
        else:
            rc = launch_local(args.num_workers, command, args.coordinator)
    else:
        if not args.hostfile:
            ap.error("--launcher ssh requires --hostfile")
        with open(args.hostfile) as f:
            hosts = [h.strip() for h in f if h.strip() and not h.startswith("#")]
        if len(hosts) < args.num_workers:
            ap.error(f"hostfile has {len(hosts)} hosts < -n {args.num_workers}")
        rc = launch_ssh(hosts[: args.num_workers], command, args.coordinator)
    sys.exit(rc)


if __name__ == "__main__":
    main()

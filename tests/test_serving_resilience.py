"""Self-healing serving plane: hot weight swap, router failover, faults.

Contracts under test (ISSUE 7 tentpole + ISSUE 10 cross-process plane):

- a hot weight swap under sustained ``ContinuousBatcher`` load loses ZERO
  requests, responses carry the ``weights_version`` their dispatch
  actually served, and post-swap greedy outputs are BIT-identical to a
  fresh engine built from the same checkpoint;
- killing one of two router replicas mid-load (fault injection, no real
  process death needed) completes every submitted future with
  ``serve/failovers >= 1`` and zero steady-state recompiles;
- the failure paths themselves are deterministic: ``serving.faults``
  drives dispatch raises, dispatcher-thread death, hangs, stale
  heartbeats, and torn checkpoints from env specs or test code;
- CROSS-PROCESS (ISSUE 10): real ``serving.worker`` processes behind
  the socket transport — SIGKILL mid-decode loses zero requests (one
  failover, a respawned REAL process rejoins at the current version),
  SIGTERM drains gracefully (exit 0, every in-flight request served),
  and a coordinated swap flips every process onto ONE version tag with
  post-swap greedy tokens bit-identical to a fresh engine;
- LOAD SHEDDING: with every replica degraded the router sheds at
  admission (``Backpressure`` + ``serve/shed_*``) and the backlog stays
  bounded by construction; any healthy replica keeps admission open.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu import checkpoint_sharded as cs
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import (Backpressure, CheckpointWatcher,
                               ContinuousBatcher, DeadlineExceeded,
                               RemoteReplica, Replica, ReplicaUnavailable,
                               Router, RpcClient, RpcServer,
                               TransportError, faults)
from mxnet_tpu.serving.worker import make_transformer_net, spawn_worker
from mxnet_tpu.telemetry.watchdog import Watchdog, read_heartbeat

WORKER_ENV = {"JAX_PLATFORMS": os.environ.get("MXTPU_TEST_PLATFORM",
                                              "cpu")}


def _make_net(seed, prefix="serve_net_"):
    """Tiny decode-capable transformer. A FIXED prefix keeps param names
    identical across instances — the train->serve checkpoint contract
    (trainer and server build the net from the same code)."""
    np.random.seed(seed)
    mx.random.seed(seed)
    net = TransformerModel(src_vocab=61, tgt_vocab=61, units=16,
                           hidden_size=32, num_layers=1, num_heads=2,
                           max_length=64, dropout=0.0, prefix=prefix)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    return net


@pytest.fixture(scope="module")
def net_a():
    return _make_net(0)


@pytest.fixture(scope="module")
def net_b():
    return _make_net(1)


@pytest.fixture(scope="module")
def shared_engine(net_a):
    """One warmed engine reused by the batcher/router tests (router
    replicas may share an engine — two batchers, one param set). Warm are
    the paged programs every ``_batcher`` over it dispatches: one batcher
    built warm on the engine, and stopped."""
    eng = InferStep(net_a, max_len=24)
    _batcher(eng, warmup=True).stop()
    return eng


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _batcher(engine, **kw):
    cfg = dict(bucket_keys=(8,), slots=2, max_new_tokens=4)
    cfg.update(kw)
    return ContinuousBatcher(engine, **cfg)


def _prompts(rng, n, lo=3, hi=61, lmin=3, lmax=8):
    return [rng.randint(lo, hi, (rng.randint(lmin, lmax + 1),))
            .astype(np.int32) for _ in range(n)]


def _save_params(directory, net):
    return cs.save_sharded(
        directory, {n: p._data.data
                    for n, p in net.collect_params().items()})


# ---------------------------------------------------------------- faults
class TestFaultHarness:
    def test_programmatic_inject_and_fire(self):
        faults.inject("x.p", times=2)
        with pytest.raises(faults.FaultInjected):
            faults.fire("x.p")
        with pytest.raises(faults.FaultInjected):
            faults.fire("x.p")
        faults.fire("x.p")  # exhausted -> no-op
        assert faults.specs()[0]["fired"] == 2

    def test_after_skips_hits(self):
        faults.inject("x.after", times=1, after=2)
        faults.fire("x.after")
        faults.fire("x.after")
        with pytest.raises(faults.FaultInjected):
            faults.fire("x.after")

    def test_match_restricts_tag(self):
        faults.inject("x.m", times=None, match="r1")
        faults.fire("x.m", tag="r2")  # no match -> no-op
        with pytest.raises(faults.FaultInjected):
            faults.fire("x.m", tag="r1-main")
        faults.fire("x.m", tag=None)  # no tag can never match

    def test_delay_mode_sleeps_not_raises(self):
        faults.inject("x.d", times=1, delay=0.05)
        t0 = time.perf_counter()
        faults.fire("x.d")
        assert time.perf_counter() - t0 >= 0.045

    def test_env_spec_parsed(self, monkeypatch):
        monkeypatch.setenv("MXTPU_FAULT_E_P", "times=1;match=zz")
        assert faults.check("e.p", tag="aa") is None
        assert faults.check("e.p", tag="a-zz-a") is not None
        assert faults.check("e.p", tag="a-zz-a") is None  # exhausted

    def test_env_spec_bad_key_raises(self, monkeypatch):
        monkeypatch.setenv("MXTPU_FAULT_E_BAD", "bogus=1")
        with pytest.raises(MXNetError):
            faults.check("e.bad")

    def test_fault_counter(self):
        before = mx.telemetry.registry().counter(
            "serve/faults_injected").value
        faults.inject("x.c", times=1)
        with pytest.raises(faults.FaultInjected):
            faults.fire("x.c")
        assert mx.telemetry.registry().counter(
            "serve/faults_injected").value == before + 1


# -------------------------------------------------------------- heartbeat
class TestAtomicHeartbeat:
    def test_never_observes_partial_json(self, tmp_path):
        """Hammer heartbeat writes from two watchdogs sharing a
        directory while reading concurrently: every read parses — the
        tmp+fsync+rename publish can never expose a partial file."""
        wds = [Watchdog(str(tmp_path), interval=9.0) for _ in range(2)]
        stop = threading.Event()
        bad = []

        def writer(wd):
            while not stop.is_set():
                wd._write_heartbeat()

        threads = [threading.Thread(target=writer, args=(wd,), daemon=True)
                   for wd in wds]
        for t in threads:
            t.start()
        path = os.path.join(str(tmp_path), "heartbeat.json")
        deadline = time.perf_counter() + 1.0
        reads = 0
        while time.perf_counter() < deadline:
            try:
                with open(path) as f:
                    json.load(f)
                reads += 1
            except FileNotFoundError:
                continue
            except ValueError as e:
                bad.append(e)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert reads > 0 and not bad, \
            f"{len(bad)} torn heartbeat reads out of {reads}"

    def test_tmp_name_unique_per_writer(self, tmp_path):
        wd = Watchdog(str(tmp_path), interval=9.0)
        wd._write_heartbeat()
        # the shared fixed-name tmp file of the old scheme must be gone
        assert not os.path.exists(wd.heartbeat_path + ".tmp")
        assert read_heartbeat(wd.heartbeat_path)["status"] == "alive"

    def test_read_heartbeat_torn_is_none(self, tmp_path):
        p = tmp_path / "heartbeat.json"
        p.write_text('{"status": "al')  # torn mid-write
        assert read_heartbeat(str(p)) is None
        assert read_heartbeat(str(tmp_path / "missing.json")) is None

    def test_suppression_fault_freezes_heartbeat(self, tmp_path):
        wd = Watchdog(str(tmp_path), interval=9.0)
        wd._write_heartbeat()
        first = read_heartbeat(wd.heartbeat_path)
        faults.inject("watchdog.heartbeat", times=None,
                      match=str(tmp_path))
        time.sleep(0.01)
        wd._write_heartbeat()
        assert read_heartbeat(wd.heartbeat_path)["time"] == first["time"]


# ---------------------------------------------------------- batcher health
class TestBatcherHealth:
    def test_healthy_lifecycle(self, shared_engine):
        bat = _batcher(shared_engine)
        assert bat.healthy
        bat.stop()
        assert not bat.healthy

    def test_submit_after_stop_fails_future_immediately(
            self, shared_engine):
        bat = _batcher(shared_engine)
        bat.stop()
        fut = bat.submit([3, 4, 5])
        assert fut.done()
        with pytest.raises(RuntimeError, match="not accepting"):
            fut.result(timeout=0)

    def test_submit_after_thread_death_fails_future(self, shared_engine):
        faults.inject("batcher.thread", times=1, match="dead-replica")
        bat = _batcher(shared_engine, name="dead-replica")
        deadline = time.perf_counter() + 10
        while bat._thread.is_alive() and time.perf_counter() < deadline:
            time.sleep(0.005)
        assert not bat.healthy
        fut = bat.submit([3, 4])
        assert fut.done() and isinstance(fut.exception(), RuntimeError)

    def test_stop_fails_undrained_queue(self, shared_engine):
        """stop(drain=False) with work still queued (here: stuck behind
        a hung dispatch) fails those futures instead of leaking them, and
        the request whose slot the hung dispatch held with them: nothing
        is left unresolved and every page is back in the pool."""
        faults.inject("batcher.hang", times=1, delay=0.3,
                      match="undrained")
        bat = _batcher(shared_engine, name="undrained")
        blocker = bat.submit([9, 10])  # in a slot; its burst hangs 300 ms
        time.sleep(0.05)
        queued = bat.submit([3, 4, 5])
        assert not queued.done()
        bat.stop(drain=False)
        assert queued.done() and blocker.done()
        with pytest.raises(RuntimeError, match="queued"):
            queued.result(timeout=0)
        with pytest.raises(RuntimeError, match="in flight"):
            blocker.result(timeout=0)
        assert bat.pool.free_pages == bat.pool.num_pages

    def test_thread_death_fails_queued_futures(self, shared_engine):
        """A crashing dispatcher fails what it held queued and what its
        slots held — no future is ever left unresolvable."""
        faults.inject("batcher.hang", times=1, delay=0.3,
                      match="dying-replica")
        faults.inject("batcher.thread", times=1, after=1,
                      match="dying-replica")
        bat = _batcher(shared_engine, name="dying-replica")
        fut = bat.submit([3, 4])  # in a slot; its burst hangs 300 ms
        time.sleep(0.1)
        fut2 = bat.submit([5, 6])  # queued; the thread dies next pass
        for f in (fut, fut2):
            with pytest.raises(RuntimeError, match="thread died"):
                f.result(timeout=60)
        assert not bat.healthy

    def test_drain_waits_for_a_request_inside_its_admission_prefill(
            self, shared_engine):
        """stop(drain=True) while ``_admit`` holds a request in neither
        the waiting line nor a slot (here: a slow admission prefill) waits
        for it: the request is served, not failed as in flight."""
        faults.inject("batcher.dispatch", times=1, delay=0.3,
                      match="drain-admit")
        bat = _batcher(shared_engine, name="drain-admit")
        fut = bat.submit([3, 4, 5])
        deadline = time.perf_counter() + 10
        while (not bat._queue.empty() or bat._pending) \
                and time.perf_counter() < deadline:
            time.sleep(0.001)
        # taken off the line, not yet in a slot: the prefill holds it
        assert not any(bat._slots) and not fut.done()
        assert not bat._drained()
        bat.stop(drain=True)
        assert isinstance(fut.result(timeout=0), list)
        assert bat.pool.free_pages == bat.pool.num_pages

    def test_drained_is_never_true_over_an_unresolved_request(
            self, shared_engine):
        """Stress: submitters on more threads than cores against a reader
        of ``_drained()``. Whenever it reads true, every request whose
        ``submit`` had returned before the read is resolved: a request on
        its way from the queue to a slot reads as not drained."""
        # every dispatch 2 ms slow: an admission prefill holds its
        # requests long enough for the reader to look
        faults.inject("batcher.dispatch", times=None, delay=0.002,
                      match="drain-stress")
        bat = _batcher(shared_engine, name="drain-stress")
        futs, stop, broken = [], threading.Event(), []

        def submitter(seed):
            rng = np.random.RandomState(seed)
            while not stop.is_set():
                futs.append(bat.submit(_prompts(rng, 1)[0]))
                time.sleep(rng.uniform(0.02, 0.08))

        def reader():
            while not stop.is_set():
                n = len(futs)
                if bat._drained() and not all(f.done() for f in futs[:n]):
                    broken.append(n)

        threads = [threading.Thread(target=submitter, args=(i,),
                                    daemon=True)
                   for i in range((os.cpu_count() or 4) + 2)]
        threads.append(threading.Thread(target=reader, daemon=True))
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            time.sleep(1.5)
            stop.set()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(interval)
            stop.set()
            bat.stop()
        assert futs and not broken, \
            f"_drained() read true over unresolved requests at {broken[:5]}"
        assert all(f.done() for f in futs)
        assert bat.pool.free_pages == bat.pool.num_pages


# ------------------------------------------------------------- deadlines
class TestDeadlines:
    def test_expired_in_queue_fails_not_dispatches(self, shared_engine):
        """A request whose deadline passes while queued (here: behind a
        hung dispatch) is failed with DeadlineExceeded; the batch it
        would have ridden dispatches without it and occupancy telemetry
        reflects only the live rows."""
        mx.telemetry.reset()
        faults.inject("batcher.hang", times=1, delay=0.2,
                      match="dl-replica")
        bat = _batcher(shared_engine, slots=2, name="dl-replica")
        try:
            blocker = bat.submit([9, 10])  # dispatched, hangs 200 ms
            time.sleep(0.05)  # blocker is in its (hung) dispatch alone
            doomed = bat.submit([3, 4, 5], deadline_ms=20.0)
            live = bat.submit([6, 7, 8])  # same batch as doomed, no limit
            assert isinstance(blocker.result(timeout=60), list)
            assert isinstance(live.result(timeout=60), list)
            with pytest.raises(DeadlineExceeded):
                doomed.result(timeout=60)
            reg = mx.telemetry.registry()
            assert reg.counter("serve/deadline_exceeded").value == 1
            # the expired row never occupied a slot: the second dispatch
            # carried 1 live row of 2 slots, and only 2 requests total
            # were ever dispatched
            assert reg.gauge("infer/batch_occupancy").value == 0.5
            assert reg.counter("infer/requests").value == 2
        finally:
            bat.stop()
            mx.telemetry.reset()

    def test_unexpired_deadline_dispatches_normally(self, shared_engine):
        bat = _batcher(shared_engine)
        try:
            fut = bat.submit([3, 4, 5], deadline_ms=60_000.0)
            assert isinstance(fut.result(timeout=60), list)
        finally:
            bat.stop()

    def test_router_deadline_on_hung_replica(self, shared_engine):
        """A dispatched-but-hung request settles via its deadline
        instead of waiting on the wedged engine forever."""
        faults.inject("batcher.hang", times=1, delay=1.5,
                      match="hang-replica")
        bat = _batcher(shared_engine, name="hang-replica")
        router = Router([Replica("hang-replica", bat)],
                        health_interval_s=10.0, start=True)
        try:
            fut = router.submit([3, 4, 5], deadline_ms=150.0)
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=60)
        finally:
            router.stop()


# ------------------------------------------------------------ weight swap
class TestHotWeightSwap:
    def test_swap_params_flips_version_and_values(self, net_a, net_b):
        eng = InferStep(net_a, max_len=24)
        assert eng.weights_version == "v0"
        arrays = {n: p._data.data
                  for n, p in net_b.collect_params().items()}
        ver = eng.swap_params(arrays)
        assert ver == "v1" and eng.weights_version == "v1"
        name = next(iter(arrays))
        np.testing.assert_array_equal(
            np.asarray(eng._values[name]), np.asarray(arrays[name]))

    def test_swap_validates_names_and_shapes(self, net_a):
        eng = InferStep(net_a, max_len=24)
        with pytest.raises(MXNetError, match="missing parameter"):
            eng.swap_params({})
        arrays = {n: p._data.data
                  for n, p in net_a.collect_params().items()}
        k = next(iter(arrays))
        bad = dict(arrays)
        bad[k] = np.zeros((3, 3), np.float32)
        with pytest.raises(MXNetError, match="shape mismatch"):
            eng.swap_params(bad)

    def test_swap_accepts_trainstep_naming(self, net_a, net_b):
        eng = InferStep(net_a, max_len=24)
        arrays = {"values/" + n: p._data.data
                  for n, p in net_b.collect_params().items()}
        arrays["opt/m/whatever"] = np.zeros((1,), np.float32)  # ignored
        assert eng.swap_params(arrays) == "v1"

    def test_swapped_outputs_bit_identical_to_fresh_engine(
            self, net_a, net_b, tmp_path):
        """Acceptance: post-swap greedy outputs == a fresh engine loaded
        from the same checkpoint, bit-identically."""
        _save_params(str(tmp_path / "step_1"), net_b)
        eng = InferStep(net_a, max_len=24)
        rng = np.random.RandomState(3)
        src = rng.randint(3, 61, (2, 8)).astype(np.int32)
        vl = np.array([6, 8], np.int32)
        before = eng.decode_n(src, vl, max_new_tokens=4)
        before = (before[0].asnumpy(), before[1].asnumpy())
        w = CheckpointWatcher(eng, str(tmp_path), start=False)
        ver = w.poll_once()
        assert ver is not None and eng.weights_version == ver
        after = eng.decode_n(src, vl, max_new_tokens=4)
        after = (after[0].asnumpy(), after[1].asnumpy())
        fresh_eng = InferStep(net_b, max_len=24)
        fresh = fresh_eng.decode_n(src, vl, max_new_tokens=4)
        fresh = (fresh[0].asnumpy(), fresh[1].asnumpy())
        assert not np.array_equal(after[0], before[0])
        np.testing.assert_array_equal(after[0], fresh[0])
        np.testing.assert_array_equal(after[1], fresh[1])
        # a swap to IDENTICAL shapes/dtypes adds no program signatures
        assert eng.compile_guard.steady_state_recompiles == 0

    def test_swap_under_load_loses_nothing(self, net_b, tmp_path):
        """Acceptance: a swap mid-stream resolves every future, tags
        each response with the version of its FINAL iteration, and never
        recompiles. Tags are monotonic in the order the requests retire
        (not in the order they were submitted: a slot freed early goes to
        a later request). No token is the end of sequence here, so every
        request's final iteration is a decode burst, and the requests of
        one retire pass carry one tag."""
        net = _make_net(7)
        eng = InferStep(net, max_len=24, eos_id=-1)
        _save_params(str(tmp_path / "step_9"), net_b)
        watcher = CheckpointWatcher(eng, str(tmp_path), start=False)
        bat = _batcher(eng, warmup=True)
        rng = np.random.RandomState(11)
        futs, retired = [], []

        def note_retired():
            retired.extend(f for f in futs
                           if f.done() and f not in retired)

        try:
            for i, p in enumerate(_prompts(rng, 30)):
                futs.append(bat.submit(p))
                if i == 12:
                    futs[0].result(timeout=120)  # v0 has served some
                    note_retired()
                    assert watcher.poll_once() is not None
                note_retired()
                time.sleep(0.002)
            deadline = time.perf_counter() + 120
            while len(retired) < len(futs) \
                    and time.perf_counter() < deadline:
                note_retired()
                time.sleep(0.001)
            results = [f.result(timeout=120) for f in futs]
        finally:
            bat.stop()
        assert all(isinstance(r, list) for r in results)
        versions = {f.weights_version for f in futs}
        assert "v0" in versions and len(versions) == 2, versions
        # version tags are MONOTONIC: once the swap lands, no later
        # dispatch serves the old weights
        assert len(retired) == len(futs)
        seen_new = False
        for f in retired:
            if f.weights_version != "v0":
                seen_new = True
            else:
                assert not seen_new, "old version served after the swap"
        assert eng.compile_guard.steady_state_recompiles == 0

    def test_torn_checkpoint_keeps_serving_old(self, net_a, net_b,
                                               tmp_path):
        mx.telemetry.reset()
        _save_params(str(tmp_path / "step_1"), net_b)
        eng = InferStep(net_a, max_len=24)
        w = CheckpointWatcher(eng, str(tmp_path), start=False)
        faults.inject("ckpt.load", times=1)
        assert w.poll_once() is None
        assert isinstance(w.last_error, faults.FaultInjected)
        assert eng.weights_version == "v0"
        assert mx.telemetry.registry().counter(
            "serve/swap_failures").value == 1
        # fault exhausted: the NEXT poll retries the same commit and wins
        assert w.poll_once() is not None
        assert mx.telemetry.registry().counter("serve/swaps").value == 1
        mx.telemetry.reset()

    def test_uncommitted_checkpoint_invisible(self, net_a, net_b,
                                              tmp_path):
        d = tmp_path / "step_1"
        _save_params(str(d), net_b)
        os.unlink(d / "DONE.p0")  # retract the commit
        assert cs.latest_committed(str(tmp_path)) is None
        w = CheckpointWatcher(InferStep(net_a, max_len=24), str(tmp_path),
                              start=False)
        assert w.poll_once() is None

    def test_latest_committed_prefers_newest(self, net_a, net_b,
                                             tmp_path):
        _save_params(str(tmp_path / "step_1"), net_a)
        time.sleep(0.01)
        _save_params(str(tmp_path / "step_2"), net_b)
        path, token = cs.latest_committed(str(tmp_path))
        assert path.endswith("step_2") and token is not None

    def test_commit_token_changes_on_resave(self, net_a, tmp_path):
        d = str(tmp_path / "ck")
        _save_params(d, net_a)
        t1 = cs.commit_token(d)
        time.sleep(0.01)
        _save_params(d, net_a)
        t2 = cs.commit_token(d)
        assert t1 is not None and t2 is not None and t1 != t2

    def test_background_thread_swaps(self, net_a, net_b, tmp_path):
        eng = InferStep(net_a, max_len=24)
        w = CheckpointWatcher(eng, str(tmp_path), poll_s=0.02)
        try:
            assert eng.weights_version == "v0"
            _save_params(str(tmp_path / "step_3"), net_b)
            deadline = time.perf_counter() + 30
            while eng.weights_version == "v0" and \
                    time.perf_counter() < deadline:
                time.sleep(0.01)
            assert eng.weights_version.startswith("step_3:")
        finally:
            w.stop()


# ----------------------------------------------------------------- router
class TestRouter:
    def _two_replicas(self, engine, **bkw):
        b1 = _batcher(engine, name="r1", **bkw)
        b2 = _batcher(engine, name="r2", **bkw)
        return [Replica("r1", b1), Replica("r2", b2)]

    def test_basic_routing_completes(self, shared_engine):
        router = Router(self._two_replicas(shared_engine),
                        health_interval_s=0.02)
        rng = np.random.RandomState(5)
        try:
            futs = [router.submit(p) for p in _prompts(rng, 8)]
            res = [f.result(timeout=120) for f in futs]
        finally:
            router.stop()
        assert all(isinstance(r, list) for r in res)
        assert all(f.replica in ("r1", "r2") for f in futs)

    def test_failover_on_replica_death(self, shared_engine):
        """Acceptance: killing one of two replicas mid-load completes
        every future, serve/failovers >= 1, zero steady recompiles."""
        mx.telemetry.reset()
        router = Router(self._two_replicas(shared_engine),
                        retry_backoff_s=0.01, health_interval_s=0.02)
        faults.inject("batcher.thread", times=1, after=1, match="r1")
        rng = np.random.RandomState(6)
        futs = []
        try:
            for p in _prompts(rng, 16):
                futs.append(router.submit(p))
                time.sleep(0.002)
            res = [f.result(timeout=120) for f in futs]
        finally:
            router.stop()
        assert all(isinstance(r, list) for r in res)
        reg = mx.telemetry.registry()
        assert reg.counter("serve/failovers").value >= 1
        assert reg.counter("serve/dropped").value == 0
        assert reg.counter("serve/completed").value == len(futs)
        assert [r for r in router.replicas if r.name == "r1"][0].evicted
        assert shared_engine.compile_guard.steady_state_recompiles == 0
        mx.telemetry.reset()

    def test_dispatch_error_retries_on_other_replica(self, shared_engine):
        """A transient dispatch failure is retried transparently — the
        caller sees tokens, the registry sees the retry."""
        mx.telemetry.reset()
        router = Router(self._two_replicas(shared_engine),
                        retry_backoff_s=0.01, health_interval_s=0.02)
        faults.inject("batcher.dispatch", times=1)
        rng = np.random.RandomState(7)
        try:
            fut = router.submit(rng.randint(3, 61, (5,)).astype(np.int32))
            assert isinstance(fut.result(timeout=120), list)
        finally:
            router.stop()
        assert mx.telemetry.registry().counter(
            "serve/retries").value >= 1
        mx.telemetry.reset()

    def test_retries_bounded_then_dropped(self, shared_engine):
        mx.telemetry.reset()
        router = Router(self._two_replicas(shared_engine),
                        max_retries=1, retry_backoff_s=0.01,
                        health_interval_s=0.02)
        faults.inject("batcher.dispatch", times=None)  # every dispatch
        rng = np.random.RandomState(8)
        try:
            fut = router.submit(rng.randint(3, 61, (5,)).astype(np.int32))
            with pytest.raises(faults.FaultInjected):
                fut.result(timeout=120)
        finally:
            router.stop()
        reg = mx.telemetry.registry()
        assert reg.counter("serve/dropped").value == 1
        assert reg.counter("serve/retries").value == 1  # bounded
        mx.telemetry.reset()

    def test_no_healthy_replica_fails_fast(self, shared_engine):
        rep = Replica("r1", _batcher(shared_engine))
        router = Router([rep], health_interval_s=0.02,
                        no_replica_timeout_s=0.2)
        try:
            rep.batcher.stop()
            deadline = time.perf_counter() + 10
            while not rep.evicted and time.perf_counter() < deadline:
                time.sleep(0.01)
            fut = router.submit([3, 4, 5])
            with pytest.raises(RuntimeError, match="no healthy"):
                fut.result(timeout=60)
        finally:
            router.stop()

    def test_queued_requests_resubmitted_on_eviction(self, shared_engine):
        """The eviction contract end-to-end: requests queued (and even
        in-flight) on a replica when it is evicted are transparently
        replayed on the healthy one — every future resolves, on r2."""
        faults.inject("batcher.hang", times=1, delay=0.5, match="r1")
        b1 = _batcher(shared_engine, name="r1")
        b2 = _batcher(shared_engine, name="r2")
        rep1, rep2 = Replica("r1", b1), Replica("r2", b2)
        router = Router([rep1, rep2], retry_backoff_s=0.01,
                        health_interval_s=0.02)
        rng = np.random.RandomState(9)
        try:
            # bias placement onto r1, whose first dispatch will hang
            rep2.inflight = 100
            futs = [router.submit(p) for p in _prompts(rng, 4)]
            time.sleep(0.05)  # first req dispatched+hung, rest queued
            rep2.inflight = 0
            router._evict(rep1, "test: operator eviction")
            res = [f.result(timeout=120) for f in futs]
        finally:
            router.stop()
        assert all(isinstance(r, list) for r in res)
        assert all(f.replica == "r2" for f in futs)
        assert rep1.evicted
        assert mx.telemetry.registry().counter(
            "serve/failovers").value >= 1

    def test_heartbeat_staleness_evicts(self, shared_engine, tmp_path):
        """Watchdog-driven failover: the replica's dispatcher is alive
        but its heartbeat is frozen (suppression fault) — the router
        evicts on staleness and the healthy replica serves."""
        mx.telemetry.reset()
        hb_dir = str(tmp_path / "wd1")
        wd = Watchdog(hb_dir, interval=0.02)
        b1 = _batcher(shared_engine, name="r1", watchdog=wd)
        b2 = _batcher(shared_engine, name="r2")
        wd.start()
        rep1 = Replica("r1", b1, heartbeat_path=wd.heartbeat_path,
                       heartbeat_stale_s=0.15)
        router = Router([rep1, Replica("r2", b2)],
                        retry_backoff_s=0.01, health_interval_s=0.02)
        try:
            rng = np.random.RandomState(10)
            # serves normally while the heartbeat is fresh
            fut = router.submit(rng.randint(3, 61, (5,)).astype(np.int32))
            fut.result(timeout=120)
            # wait until the FIRST heartbeat actually landed: freezing a
            # never-written heartbeat is indistinguishable from "no
            # watchdog wired", which health() treats as unknown
            deadline = time.perf_counter() + 30
            while read_heartbeat(wd.heartbeat_path) is None and \
                    time.perf_counter() < deadline:
                time.sleep(0.01)
            assert read_heartbeat(wd.heartbeat_path) is not None
            faults.inject("watchdog.heartbeat", times=None, match=hb_dir)
            deadline = time.perf_counter() + 30
            while not rep1.evicted and time.perf_counter() < deadline:
                time.sleep(0.01)
            assert rep1.evicted
            fut2 = router.submit(
                rng.randint(3, 61, (5,)).astype(np.int32))
            assert isinstance(fut2.result(timeout=120), list)
            assert fut2.replica == "r2"
            assert mx.telemetry.registry().counter(
                "serve/failovers").value >= 1
        finally:
            router.stop()
            wd.stop()
            mx.telemetry.reset()

    def test_respawn_via_factory(self, shared_engine):
        mx.telemetry.reset()
        made = []

        def factory():
            rep = Replica(f"r{2 + len(made)}", _batcher(shared_engine))
            made.append(rep)
            return rep

        rep1 = Replica("r1", _batcher(shared_engine))
        router = Router([rep1], replica_factory=factory,
                        respawn_backoff_s=0.01, retry_backoff_s=0.01,
                        health_interval_s=0.02)
        rng = np.random.RandomState(12)
        try:
            faults.inject("batcher.thread", times=1, match="r1")
            # poke r1 so its thread hits the fault point and dies
            deadline = time.perf_counter() + 30
            while not made and time.perf_counter() < deadline:
                time.sleep(0.01)
            assert made, "factory never invoked after eviction"
            fut = router.submit(rng.randint(3, 61, (5,)).astype(np.int32))
            assert isinstance(fut.result(timeout=120), list)
            assert fut.replica == made[0].name
            assert mx.telemetry.registry().counter(
                "serve/replica_restarts").value == 1
        finally:
            router.stop()
            mx.telemetry.reset()

    def test_backoff_delay_shape(self):
        from mxnet_tpu.serving.router import backoff_delay

        d0 = backoff_delay(1.0, 0, jitter=0.0)
        d3 = backoff_delay(1.0, 3, jitter=0.0)
        dcap = backoff_delay(1.0, 30, cap=30.0, jitter=0.0)
        assert d0 == 1.0 and d3 == 8.0 and dcap == 30.0
        j = backoff_delay(1.0, 0, jitter=0.25)
        assert 1.0 <= j <= 1.25


# -------------------------------------------------------- elastic restarts
class TestElasticBackoff:
    def test_restart_backoff_and_counter(self):
        import sys

        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "tools"))
        import launch

        mx.telemetry.reset()
        delays = []
        rc = launch.launch_elastic(
            1, [sys.executable, "-c", "import sys; sys.exit(3)"],
            max_restarts=2, backoff_s=0.2, _sleep=delays.append)
        assert rc == 3
        assert len(delays) == 2  # no sleep after the final attempt
        assert 0.2 <= delays[0] <= 0.25 * 1.01
        assert 0.4 <= delays[1] <= 0.5 * 1.01
        assert mx.telemetry.registry().counter(
            "launch/restarts").value == 2
        mx.telemetry.reset()

    def test_env_default_backoff(self, monkeypatch):
        import sys

        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "tools"))
        import launch

        monkeypatch.setenv("MXTPU_RESTART_BACKOFF_S", "0.125")
        assert launch.restart_backoff_s() == 0.125
        monkeypatch.setenv("MXTPU_RESTART_BACKOFF_S", "junk")
        assert launch.restart_backoff_s() == 1.0


# ------------------------------------------------------------- telemetry
class TestServeTelemetry:
    def test_report_serve_fields(self):
        mx.telemetry.reset()
        reg = mx.telemetry.registry()
        reg.counter("serve/swaps").inc(2)
        reg.counter("serve/failovers").inc()
        reg.gauge("serve/replicas_healthy").set(3)
        mx.telemetry.set_info(weights_version="step_5:abc")
        rep = mx.telemetry.report()
        assert rep["serve_swaps"] == 2
        assert rep["serve_failovers"] == 1
        assert rep["serve_replicas_healthy"] == 3
        assert rep["serve_dropped"] == 0
        assert rep["weights_version"] == "step_5:abc"
        mx.telemetry.reset()

    def test_telemetry_report_tool_prints_serve_family(self, tmp_path,
                                                       capsys):
        import sys

        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "tools"))
        import telemetry_report

        report = {
            "weights_version": "step_7:123",
            "counters": {"serve/swaps": 1, "serve/failovers": 2,
                         "serve/dropped": 1, "launch/restarts": 3},
            "gauges": {"serve/replicas_healthy": 1},
        }
        p = tmp_path / "report.json"
        p.write_text(json.dumps(report))
        telemetry_report._print_serve_family(str(p))
        out = capsys.readouterr().out
        assert "Self-healing serving" in out
        assert "serve/failovers" in out and "2" in out
        assert "launch/restarts" in out
        assert "WARNING" in out  # dropped > 0


# -------------------------------------------------------------- transport
class TestTransport:
    """In-process RPC protocol tests (no worker processes): schema,
    timeouts, streaming, and the transport fault points."""

    def _server(self, handlers, name="srv"):
        return RpcServer(handlers, name=name).start()

    def test_roundtrip_and_unknown_verb(self):
        srv = self._server({"ping": lambda m, r: r(pong=True, who="srv")})
        cli = RpcClient(("127.0.0.1", srv.port), name="cli").connect(
            budget_s=5.0)
        try:
            out = cli.call("ping", timeout_s=5.0)
            assert out["pong"] and out["who"] == "srv"
            with pytest.raises(MXNetError, match="unknown verb"):
                cli.call("bogus", timeout_s=5.0)
        finally:
            cli.close()
            srv.stop()

    def test_per_call_timeout(self):
        srv = self._server({"slow": lambda m, r: None})  # never replies
        cli = RpcClient(("127.0.0.1", srv.port), name="cli").connect(
            budget_s=5.0)
        try:
            t0 = time.perf_counter()
            with pytest.raises(TransportError, match="timed out"):
                cli.call("slow", timeout_s=0.2)
            assert time.perf_counter() - t0 < 5.0
            # the connection survives a timed-out call
            assert cli.dead is None
        finally:
            cli.close()
            srv.stop()

    def test_connect_refused_within_budget(self):
        cli = RpcClient(("127.0.0.1", 1), name="nobody")
        with pytest.raises(TransportError, match="could not connect"):
            cli.connect(budget_s=0.3)

    def test_submit_streams_then_resolves(self):
        def submit(msg, respond):
            respond(done=False, stream=[1, 2])
            respond(done=False, stream=[3])
            respond(tokens=[1, 2, 3], weights_version="v7",
                    queue_wait_ms=1.5, replica="srv")

        srv = self._server({"submit": submit})
        cli = RpcClient(("127.0.0.1", srv.port), name="cli").connect(
            budget_s=5.0)
        try:
            fut = cli.submit([9, 9], 3)
            chunks = list(fut.tokens_iter(timeout=10.0))
            assert [t for c in chunks for t in c] == [1, 2, 3]
            assert fut.result(timeout=10) == [1, 2, 3]
            assert fut.weights_version == "v7" and fut.replica == "srv"
        finally:
            cli.close()
            srv.stop()

    def test_remote_error_maps_to_local_class(self):
        def submit(msg, respond):
            respond(ok=False, error={"type": "Backpressure",
                                     "message": "pool full"})

        srv = self._server({"submit": submit})
        cli = RpcClient(("127.0.0.1", srv.port), name="cli").connect(
            budget_s=5.0)
        try:
            fut = cli.submit([1], 2)
            with pytest.raises(Backpressure, match="pool full"):
                fut.result(timeout=10)
        finally:
            cli.close()
            srv.stop()

    def test_recv_fault_kills_connection_and_fails_pending(self):
        """The `transport.recv` point in raise mode = a dropped link:
        every pending call fails with the client's dead_error and the
        client reports dead (the router's eviction signal)."""
        srv = self._server({"submit": lambda m, r: None})  # holds forever
        cli = RpcClient(("127.0.0.1", srv.port), name="cli-drop",
                        dead_error=ReplicaUnavailable).connect(budget_s=5.0)
        try:
            fut = cli.submit([1, 2], 2)
            assert not fut.done()
            faults.inject("transport.recv", times=1, match="cli-drop")
            # next inbound frame attempt trips the fault in the reader
            srv_conns = srv._conns
            deadline = time.perf_counter() + 10
            while not srv_conns and time.perf_counter() < deadline:
                time.sleep(0.01)
            for conn in list(srv_conns):
                conn.send({"id": 999, "ok": True, "done": True})
            deadline = time.perf_counter() + 10
            while cli.dead is None and time.perf_counter() < deadline:
                time.sleep(0.01)
            assert cli.dead is not None
            with pytest.raises(ReplicaUnavailable):
                fut.result(timeout=10)
        finally:
            cli.close()
            srv.stop()

    def test_send_fault_marks_dead(self):
        srv = self._server({"ping": lambda m, r: r(pong=True)})
        cli = RpcClient(("127.0.0.1", srv.port), name="cli-send",
                        dead_error=ReplicaUnavailable).connect(budget_s=5.0)
        try:
            faults.inject("transport.send", times=1, match="cli-send")
            with pytest.raises(TransportError):
                cli.call("ping", timeout_s=5.0)
            assert cli.dead is not None
        finally:
            cli.close()
            srv.stop()


# ----------------------------------------------------------- load shedding
class TestLoadShedding:
    def _hung_replicas(self, engine, names=("shed-r1", "shed-r2"),
                       delay=0.25):
        for n in names:
            faults.inject("batcher.hang", times=None, delay=delay,
                          match=n)
        return [Replica(n, _batcher(engine, name=n)) for n in names]

    def test_all_degraded_bounds_queue(self, shared_engine):
        """Acceptance: with every replica degraded (backlog past the
        threshold) the router backlog never exceeds shed_max_queue and
        every excess request is shed with Backpressure, counted in
        serve/shed_queue_full."""
        mx.telemetry.reset()
        router = Router(self._hung_replicas(shared_engine),
                        retry_backoff_s=0.01, health_interval_s=0.02,
                        shed_queue_depth=1, shed_max_queue=3)
        rng = np.random.RandomState(31)
        futs, max_backlog = [], 0
        try:
            for p in _prompts(rng, 12):
                futs.append(router.submit(p))
                max_backlog = max(max_backlog, len(router._inflight))
            shed = [f for f in futs
                    if isinstance(f.exception(), Backpressure)]
            assert shed, "no request was shed under a degraded fleet"
            assert max_backlog <= 3, max_backlog
            reg = mx.telemetry.registry()
            assert reg.counter("serve/shed_queue_full").value == len(shed)
            # the admitted ones still complete (bounded, not starved)
            for f in futs:
                if f not in shed:
                    assert isinstance(f.result(timeout=120), list)
        finally:
            router.stop()
            mx.telemetry.reset()

    def test_deadline_infeasible_shed_immediately(self, shared_engine):
        """A deadline the rolling wait p50 cannot meet is shed AT
        admission (serve/shed_deadline) instead of queueing until the
        deadline fails it."""
        mx.telemetry.reset()
        router = Router(self._hung_replicas(
            shared_engine, names=("shed-r3", "shed-r4")),
            retry_backoff_s=0.01, health_interval_s=0.02,
            shed_queue_depth=1, shed_max_queue=64)
        rng = np.random.RandomState(32)
        try:
            # occupy both replicas so the fleet counts as degraded
            pinned = [router.submit(p) for p in _prompts(rng, 2)]
            time.sleep(0.05)
            with router._lock:  # prime the rolling wait window
                router._recent_waits.extend([200.0] * 10)
            doomed = router.submit(rng.randint(3, 61, (5,))
                                   .astype(np.int32), deadline_ms=50.0)
            assert isinstance(doomed.exception(), Backpressure)
            assert mx.telemetry.registry().counter(
                "serve/shed_deadline").value == 1
            # a feasible deadline is still admitted
            ok = router.submit(rng.randint(3, 61, (5,)).astype(np.int32),
                               deadline_ms=60_000.0)
            assert isinstance(ok.result(timeout=120), list)
            for f in pinned:
                f.result(timeout=120)
        finally:
            router.stop()
            mx.telemetry.reset()

    def test_healthy_replica_keeps_admission_open(self, shared_engine):
        """Shedding must NOT engage while any replica is in good shape —
        placement, not admission control, handles partial degradation."""
        faults.inject("batcher.hang", times=None, delay=0.25,
                      match="shed-r5")
        reps = [Replica("shed-r5", _batcher(shared_engine, name="shed-r5")),
                Replica("shed-ok", _batcher(shared_engine, name="shed-ok"))]
        router = Router(reps, retry_backoff_s=0.01,
                        health_interval_s=0.02, shed_queue_depth=3,
                        shed_max_queue=2)
        rng = np.random.RandomState(33)
        try:
            futs = []
            for p in _prompts(rng, 6):
                futs.append(router.submit(p))
                time.sleep(0.05)  # the healthy replica keeps draining
            assert not any(isinstance(f.exception(), Backpressure)
                           for f in futs)
            for f in futs:
                assert isinstance(f.result(timeout=120), list)
        finally:
            router.stop()

    def test_report_shed_fields_and_transport_section(self, tmp_path,
                                                      capsys):
        import sys

        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "tools"))
        import telemetry_report

        report = {
            "counters": {"serve/shed_queue_full": 4,
                         "serve/shed_deadline": 2,
                         "transport/reconnects": 1,
                         "transport/errors": 1},
            "histograms": {"transport/rpc_ms":
                           {"p50": 1.0, "p95": 2.0, "count": 9}},
        }
        p = tmp_path / "report.json"
        p.write_text(json.dumps(report))
        telemetry_report._print_transport_family(str(p))
        out = capsys.readouterr().out
        assert "Cross-process transport" in out
        assert "transport/rpc_ms" in out
        assert "serve/shed_queue_full" in out
        assert "shed at router admission" in out  # shed warning
        assert "dead worker connection" in out    # error warning


# ------------------------------------------------------------ cross-process
def _spawn_pair(tmp_path, ckpt_dir, n=2, **kw):
    wkw = dict(model=dict(seed=0), max_len=24, bucket_keys=(8,), slots=2,
               max_new=4, ckpt_dir=ckpt_dir, extra_env=WORKER_ENV,
               heartbeat_s=0.1)
    wkw.update(kw)
    return [spawn_worker(str(tmp_path / f"w{i}"), name=f"w{i}", **wkw)
            for i in range(n)]


@pytest.mark.chaos
class TestCrossProcess:
    def test_sigkill_failover_respawn_and_coordinated_swap(self, tmp_path):
        """THE cross-process acceptance scenario: 2 real worker
        processes under load; a coordinated swap lands, then one worker
        is SIGKILL'd mid-decode. Zero lost requests, exactly one
        failover, the factory respawns a REAL process that rejoins at
        the swapped version, every live process reports ONE coherent
        version tag, and post-swap greedy tokens are bit-identical to a
        fresh in-process engine from the same checkpoint."""
        mx.telemetry.reset()
        ckpt = str(tmp_path / "ckpt")
        handles = _spawn_pair(tmp_path, ckpt)
        made = []

        def factory():
            h = spawn_worker(str(tmp_path / f"w{2 + len(made)}"),
                             name=f"w{2 + len(made)}", model=dict(seed=0),
                             max_len=24, bucket_keys=(8,), slots=2,
                             max_new=4, ckpt_dir=ckpt,
                             extra_env=WORKER_ENV, heartbeat_s=0.1)
            made.append(h)
            return RemoteReplica.spawning(h, heartbeat_stale_s=1.0)

        reps = [RemoteReplica(h.name, address=h.address,
                              heartbeat_path=h.heartbeat_path,
                              heartbeat_stale_s=1.0) for h in handles]
        router = Router(reps, retry_backoff_s=0.02,
                        health_interval_s=0.05, replica_factory=factory,
                        respawn_backoff_s=0.05, no_replica_timeout_s=60.0)
        net_b = make_transformer_net(seed=1)
        cs.save_sharded(os.path.join(ckpt, "step_1"),
                        {n: p._data.data
                         for n, p in net_b.collect_params().items()})
        watcher = CheckpointWatcher(router.engines, ckpt, start=False)
        rng = np.random.RandomState(17)
        futs, swap_ver = [], None
        try:
            for i, p in enumerate(_prompts(rng, 30)):
                futs.append(router.submit(p))
                if i == 8:
                    swap_ver = watcher.poll_once()
                    assert swap_ver is not None
                if i == 16:
                    handles[1].kill()  # SIGKILL mid-decode
                time.sleep(0.01)
            results = [f.result(timeout=240) for f in futs]
            assert all(isinstance(r, list) for r in results)
            reg = mx.telemetry.registry()
            assert reg.counter("serve/failovers").value == 1
            assert reg.counter("serve/dropped").value == 0
            versions = {f.weights_version for f in futs}
            assert versions == {"v0", swap_ver}, versions
            # respawned process rejoins, healthy, on the swapped version
            deadline = time.perf_counter() + 120
            live = []
            while time.perf_counter() < deadline:
                live = [r for r in router.replicas
                        if not r.evicted and r.healthy]
                if len(live) >= 2:
                    break
                time.sleep(0.1)
            assert len(live) >= 2, "respawned worker never became healthy"
            assert made, "factory never invoked"
            assert {r.weights_version for r in live} == {swap_ver}
            assert reg.counter("serve/replica_restarts").value == 1
            # post-swap greedy tokens bit-identical to a fresh engine
            fresh = InferStep(net_b, max_len=24)
            src = rng.randint(3, 61, (2, 8)).astype(np.int32)
            toks, lens = fresh.decode_n(src, np.array([8, 8], np.int32),
                                        max_new_tokens=4)
            toks, lens = toks.asnumpy(), lens.asnumpy()
            for r in live:
                for row in range(2):
                    got = r.batcher.submit(src[row], 4).result(timeout=120)
                    want = toks[row, :min(int(lens[row]), 4)].tolist()
                    assert got == want, (r.name, got, want)
        finally:
            router.stop()
            for h in handles + made:
                if h.alive():
                    h.terminate()
            for h in handles + made:
                try:
                    h.wait(timeout=60)
                except Exception:  # noqa: BLE001 - teardown best-effort
                    h.kill()
            mx.telemetry.reset()

    def test_sigterm_drains_gracefully(self, tmp_path):
        """SIGTERM mid-load: every already-accepted request is served
        (drained, not dropped), the worker exits 0, and post-drain
        submits are rejected as retriable ReplicaUnavailable."""
        h = _spawn_pair(tmp_path, None, n=1)[0]
        rep = RemoteReplica(h.name, address=h.address,
                            heartbeat_path=h.heartbeat_path)
        rng = np.random.RandomState(19)
        try:
            futs = [rep.batcher.submit(p, 4) for p in _prompts(rng, 6)]
            time.sleep(0.2)  # ensure the worker accepted them
            h.terminate()
            results = [f.result(timeout=240) for f in futs]
            assert all(isinstance(r, list) for r in results)
            assert h.wait(timeout=120) == 0
        finally:
            if h.alive():
                h.kill()
            rep.batcher.stop(drain=False)


# ----------------------------------------------- future-path regressions
class TestFuturePathRegressions:
    """ISSUE 15 host-level regressions for the mxlint
    ``resource-leak.future-path`` findings: every error path that can
    strand a ``GenerationResult`` nobody will ever resolve must fail it
    instead — a stranded future is a caller camped on its deadline."""

    def test_disagg_handoff_wire_failure_fails_the_future(self):
        """``RemoteReplica._disagg_handoff``: the tail ``submit`` (after
        the prefill fallback) dying on the wire must fail the future the
        router holds, not leave it unresolved forever."""
        import types

        from mxnet_tpu.serving.batcher import GenerationResult

        fut = GenerationResult()

        class _DeadClient:
            address = ("127.0.0.1", 9)

            def submit(self, *a, **k):
                raise TransportError("dead socket")

        prefill_rep = types.SimpleNamespace(client=types.SimpleNamespace(
            call=lambda *a, **k: (_ for _ in ()).throw(
                TransportError("prefill worker gone"))))
        me = types.SimpleNamespace(_client=_DeadClient(), name="r-dec")
        # thread body called directly: it must swallow-and-fail, the
        # real thread has nobody above it to catch
        RemoteReplica._disagg_handoff(me, prefill_rep, [3, 4, 5], 4,
                                      None, "interactive", fut)
        assert fut.done()
        with pytest.raises(TransportError, match="dead socket"):
            fut.result(timeout=0)

    def test_submit_disagg_thread_spawn_failure_fails_the_future(
            self, monkeypatch):
        """``RemoteReplica.submit_disagg``: if the handoff thread cannot
        even start, the returned future must carry the error."""
        import types

        from mxnet_tpu.serving import remote as remote_mod

        class _BoomThread:
            def __init__(self, *a, **k):
                pass

            def start(self):
                raise RuntimeError("can't fork")

        monkeypatch.setattr(
            remote_mod, "threading",
            types.SimpleNamespace(Thread=_BoomThread))
        created = []
        real_fut = remote_mod.GenerationResult

        def _capturing():
            f = real_fut()
            created.append(f)
            return f

        monkeypatch.setattr(remote_mod, "GenerationResult", _capturing)
        me = types.SimpleNamespace(
            name="r-dec",
            _disagg_handoff=lambda *a, **k: None)
        with pytest.raises(RuntimeError, match="can't fork"):
            RemoteReplica.submit_disagg(me, object(), [3, 4, 5], 4)
        assert created and created[0].done()
        with pytest.raises(RuntimeError, match="can't fork"):
            created[0].result(timeout=0)

    def test_worker_submit_thread_spawn_failure_fails_the_future(
            self, monkeypatch):
        """``ServingWorker._handle_submit``: a stream-thread spawn
        failure must fail the batcher future (and propagate so the
        dispatch wrapper answers ok=False), not strand the row."""
        import types

        from mxnet_tpu.serving import worker as worker_mod

        failed = []

        class _Fut:
            def done(self):
                return False

            def _fail(self, e):
                failed.append(e)

        fut = _Fut()

        class _BoomThread:
            def __init__(self, *a, **k):
                pass

            def start(self):
                raise RuntimeError("no threads left")

        monkeypatch.setattr(
            worker_mod, "threading",
            types.SimpleNamespace(Thread=_BoomThread))
        me = types.SimpleNamespace(
            _draining=False, role="both", name="w0",
            batcher=types.SimpleNamespace(
                healthy=True, submit=lambda *a, **k: fut),
            _lock=threading.Lock(), _streamers=[],
            _stream_result=lambda *a, **k: None)
        with pytest.raises(RuntimeError, match="no threads left"):
            worker_mod.ServingWorker._handle_submit(
                me, {"prompt": [3, 4, 5], "max_new_tokens": 4},
                lambda **k: True)
        assert len(failed) == 1
        assert "no threads left" in str(failed[0])

    def test_router_submit_placement_raise_fails_the_future(self):
        """``Router.submit``: ``_assign_locked`` raising AFTER the
        request was handed to a replica must fail the outer future every
        holder shares, not strand it."""
        class _StubReplica:
            name = "stub"

        router = Router([_StubReplica()], start=False)
        seen = []

        def _boom(r):
            seen.append(r)  # the replica now "holds" r (and r.outer)
            raise RuntimeError("placement exploded")

        router._shed_reason_locked = lambda r: None
        router._assign_locked = _boom
        with pytest.raises(RuntimeError, match="placement exploded"):
            router.submit(np.array([3, 4, 5], np.int32), 4)
        assert seen and seen[0].outer.done()
        with pytest.raises(RuntimeError, match="placement exploded"):
            seen[0].outer.result(timeout=0)


# ------------------------------------------------------------ chaos smoke
@pytest.mark.chaos
def test_chaos_smoke_swap_and_failover_end_to_end(tmp_path, monkeypatch,
                                                  net_b):
    """Tier-1 chaos scenario, env-spec driven end to end: 2 replicas
    behind a router + checkpoint watcher; MXTPU_FAULT_BATCHER_THREAD
    kills replica r1 mid-load while a hot swap lands. Every future
    resolves, both weight versions served, serve/failovers >= 1, zero
    steady recompiles."""
    mx.telemetry.reset()
    monkeypatch.setenv("MXTPU_FAULT_BATCHER_THREAD",
                       "times=1;after=1;match=r1")
    faults.clear()  # drop the cached (unset) env scan for this point

    net = _make_net(21)
    eng = InferStep(net, max_len=24)
    # r1 warms the paged programs both replicas dispatch
    reps = [Replica("r1", _batcher(eng, name="r1", warmup=True)),
            Replica("r2", _batcher(eng, name="r2"))]
    router = Router(reps, retry_backoff_s=0.01, health_interval_s=0.02)
    _save_params(str(tmp_path / "step_1"), net_b)
    watcher = CheckpointWatcher(router.engines, str(tmp_path),
                                start=False)
    rng = np.random.RandomState(13)
    futs = []
    try:
        for i, p in enumerate(_prompts(rng, 24)):
            futs.append(router.submit(p))
            if i == 10:
                futs[0].result(timeout=120)  # v0 has served some
                assert watcher.poll_once() is not None
            time.sleep(0.002)
        results = [f.result(timeout=120) for f in futs]
    finally:
        router.stop()
        mx.telemetry.disable()
    assert all(isinstance(r, list) for r in results)
    versions = {f.weights_version for f in futs}
    assert "v0" in versions and len(versions) == 2, versions
    reg = mx.telemetry.registry()
    assert reg.counter("serve/failovers").value >= 1
    assert reg.counter("serve/swaps").value == 1
    assert reg.counter("serve/dropped").value == 0
    assert eng.compile_guard.steady_state_recompiles == 0
    mx.telemetry.reset()

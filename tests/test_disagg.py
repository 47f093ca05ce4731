"""Disaggregated prefill/decode over the cross-process plane (ISSUE 11).

Contracts under test:

- KV HANDOFF: ``PrefillEngine`` frames adopted by a ``ContinuousBatcher``
  produce BIT-identical greedy tokens to the co-scheduled path — through
  the in-process adopt API, through ``pack_frames``/``unpack_frames``
  (the ``kv_push`` wire format), and through the ``MXTPU_KV_SPILL_DIR``
  filesystem fallback; any unusable handoff re-prefills from the prompt
  (``disagg/re_prefills``) and the request is served anyway.
- SLO-AWARE PLACEMENT: the router scores replicas by predicted wait
  (rolling p50 × backlog) instead of raw backlog, equal scores rotate
  round-robin (the PR-7 docstring promised this; ``min()`` never did
  it), request classes carry per-class deadline defaults, and batch
  traffic sheds before interactive under a degraded fleet.
- FAULT POINTS: ``transport.kv_push`` and ``router.place`` ride the
  standard ``times/after/delay/match`` grammar; a kv_push failure
  degrades to re-prefill, a placement failure retries.
- ELASTICITY: ``tools.launch.FleetScaler`` grows on sustained
  occupancy/shed pressure and retires when idle under
  ``MXTPU_SCALE_MIN/MAX/COOLDOWN_S``; ``Router.retire_replica`` excludes
  the replica from placement and its eviction schedules no respawn.
- CHAOS (cross-process): SIGKILL a prefill worker mid-handoff under
  load — 0/60 requests lost, post-recovery greedy tokens bit-identical
  to a co-scheduled fleet.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import (Backpressure, ContinuousBatcher,
                               DeadlineExceeded, PrefillEngine, RemoteReplica, Replica,
                               ReplicaUnavailable, Router, RpcClient,
                               disagg, faults)
from mxnet_tpu.serving.disagg import (HandoffStash, load_spilled,
                                      pack_frames, spill_frames,
                                      unpack_frames)
from mxnet_tpu.serving.worker import (ServingWorker, make_transformer_net,
                                      spawn_worker)

WORKER_ENV = {"JAX_PLATFORMS": os.environ.get("MXTPU_TEST_PLATFORM",
                                              "cpu")}


def _make_net(seed=0, prefix="serve_net_"):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = TransformerModel(src_vocab=61, tgt_vocab=61, units=16,
                           hidden_size=32, num_layers=1, num_heads=2,
                           max_length=64, dropout=0.0, prefix=prefix)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    return net


def _prompts(rng, n, lmin=3, lmax=8):
    return [rng.randint(3, 61, (rng.randint(lmin, lmax + 1),))
            .astype(np.int32) for _ in range(n)]


@pytest.fixture(scope="module")
def prefill_engine():
    eng = InferStep(_make_net(0), max_len=24)
    return PrefillEngine(eng, (8,), warmup=True)


@pytest.fixture(scope="module")
def decode_batcher():
    eng = InferStep(_make_net(0), max_len=24)
    bat = ContinuousBatcher(eng, (8,), slots=2, max_new_tokens=4,
                            warmup=True, name="disagg-dec")
    yield bat
    bat.stop()


@pytest.fixture(scope="module")
def shared_engine():
    """One engine under the router tests' batchers, its paged programs
    warm: one batcher built warm on it, and stopped."""
    eng = InferStep(_make_net(0), max_len=24)
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


# ----------------------------------------------------------------- frames
class TestFrames:
    def _frames(self, prefill_engine, prompt):
        return prefill_engine.prefill(prompt)

    def test_prefill_frames_shape_contract(self, prefill_engine):
        fr = self._frames(prefill_engine,
                          np.array([5, 6, 7], dtype=np.int32))
        assert fr["length"] == 1 and fr["mem_vl"] == 3
        assert fr["emitted"] == [fr["carry"]]
        for g in ("k", "v"):
            assert all(a.shape[0] == 1 for a in fr[g])
        for g in ("ck", "cv"):
            assert all(a.shape[0] == 3 for a in fr[g])

    def test_pack_unpack_roundtrip_bit_exact(self, prefill_engine):
        fr = self._frames(prefill_engine,
                          np.array([9, 10, 11, 12], dtype=np.int32))
        meta, bufs = pack_frames(fr)
        assert len(bufs) == len(meta["arrays"])
        fr2 = unpack_frames(meta, bufs)
        assert fr2["length"] == fr["length"]
        assert fr2["carry"] == fr["carry"]
        assert fr2["mem_vl"] == fr["mem_vl"]
        for g in ("k", "v", "ck", "cv"):
            for a, b in zip(fr[g], fr2[g]):
                assert a.dtype == b.dtype
                assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_unpack_mismatch_raises(self, prefill_engine):
        fr = self._frames(prefill_engine,
                          np.array([3, 4], dtype=np.int32))
        meta, bufs = pack_frames(fr)
        with pytest.raises(MXNetError):
            unpack_frames(meta, bufs[:-1])

    def test_spill_roundtrip_and_consume(self, prefill_engine, tmp_path):
        fr = self._frames(prefill_engine,
                          np.array([7, 8, 9], dtype=np.int32))
        path = spill_frames(str(tmp_path), "h1", fr)
        assert os.path.exists(path)
        fr2 = load_spilled(str(tmp_path), "h1")
        assert fr2 is not None and fr2["carry"] == fr["carry"]
        for g in ("k", "v", "ck", "cv"):
            for a, b in zip(fr[g], fr2[g]):
                assert np.array_equal(np.asarray(a), np.asarray(b))
        # consumed: the spill file is gone, a second load is None
        assert not os.path.exists(path)
        assert load_spilled(str(tmp_path), "h1") is None

    def test_load_spilled_missing_or_torn_is_none(self, tmp_path):
        assert load_spilled(str(tmp_path), "nope") is None
        (tmp_path / "torn.npz").write_bytes(b"not an npz")
        assert load_spilled(str(tmp_path), "torn") is None

    def test_stash_bounded_oldest_dropped(self):
        stash = HandoffStash(capacity=2)
        stash.put("a", {"x": 1})
        stash.put("b", {"x": 2})
        stash.put("c", {"x": 3})
        assert stash.pop("a") is None  # oldest evicted
        assert stash.pop("b") == {"x": 2}
        assert stash.pop("c") == {"x": 3}
        assert stash.dropped == 1 and len(stash) == 0

    def test_stash_ttl_expires_unclaimed_entries(self, monkeypatch):
        """ISSUE 15 regression (mxlint resource-leak.stash-expiry): a
        push whose submit never arrives must expire on the next touch,
        not pin KV bytes until 64 later pushes shove it out."""
        import types

        from mxnet_tpu.serving import disagg as _disagg_mod
        now = [100.0]
        monkeypatch.setattr(_disagg_mod, "time",
                            types.SimpleNamespace(monotonic=lambda: now[0]))
        stash = HandoffStash(capacity=8, ttl_s=5.0)
        stash.put("a", {"x": 1})
        now[0] += 2.0
        stash.put("b", {"x": 2})
        now[0] += 4.0  # "a" is now 6 s old (expired); "b" 4 s (alive)
        assert stash.pop("a") is None
        assert stash.pop("b") == {"x": 2}
        assert stash.expired == 1 and len(stash) == 0
        # a re-put refreshes the stamp: the entry survives a further wait
        stash.put("c", {"x": 3})
        now[0] += 4.0
        stash.put("c", {"x": 33})
        now[0] += 4.0  # 8 s since first put, 4 s since refresh
        assert stash.pop("c") == {"x": 33}
        assert stash.expired == 1

    def test_stash_ttl_zero_disables_expiry(self, monkeypatch):
        import types

        from mxnet_tpu.serving import disagg as _disagg_mod
        now = [0.0]
        monkeypatch.setattr(_disagg_mod, "time",
                            types.SimpleNamespace(monotonic=lambda: now[0]))
        stash = HandoffStash(capacity=4, ttl_s=0)
        stash.put("a", {"x": 1})
        now[0] += 1e9
        assert stash.pop("a") == {"x": 1}
        assert stash.expired == 0

    def test_stash_ttl_env_knob(self, monkeypatch):
        from mxnet_tpu.serving.disagg import handoff_ttl_s
        monkeypatch.delenv("MXTPU_HANDOFF_TTL_S", raising=False)
        assert handoff_ttl_s() == 120.0
        assert HandoffStash().ttl_s == 120.0
        monkeypatch.setenv("MXTPU_HANDOFF_TTL_S", "7.5")
        assert handoff_ttl_s() == 7.5
        assert HandoffStash().ttl_s == 7.5
        monkeypatch.setenv("MXTPU_HANDOFF_TTL_S", "not-a-number")
        assert handoff_ttl_s() == 120.0


# --------------------------------------------------------------- adoption
class TestAdoption:
    def test_adopted_tokens_bit_identical(self, prefill_engine,
                                          decode_batcher):
        """THE handoff contract: prefill on engine A, adopt on engine B
        (same weights) — greedy tokens bit-identical to B prefilling
        locally, every handoff adopted (no silent re-prefill)."""
        rng = np.random.RandomState(7)
        prompts = _prompts(rng, 6)
        ref = [decode_batcher.submit(p).result(timeout=120)
               for p in prompts]
        with decode_batcher._stats_lock:
            adopted0 = decode_batcher.stats["adopted"]
        outs = []
        for p in prompts:
            fr = prefill_engine.prefill(p)
            meta, bufs = pack_frames(fr)  # through the wire format
            outs.append(decode_batcher.submit(
                p, frames=unpack_frames(meta, bufs)).result(timeout=120))
        assert outs == ref
        with decode_batcher._stats_lock:
            assert decode_batcher.stats["adopted"] - adopted0 == 6

    def test_corrupt_frames_re_prefill_same_tokens(self, prefill_engine,
                                                   decode_batcher):
        mx.telemetry.reset()
        rng = np.random.RandomState(8)
        p = _prompts(rng, 1)[0]
        ref = decode_batcher.submit(p).result(timeout=120)
        fr = prefill_engine.prefill(p)
        fr["k"][0] = fr["k"][0][:, :1]  # wrong head geometry
        with decode_batcher._stats_lock:
            before = decode_batcher.stats["re_prefills"]
        out = decode_batcher.submit(p, frames=fr).result(timeout=120)
        assert out == ref
        with decode_batcher._stats_lock:
            assert decode_batcher.stats["re_prefills"] == before + 1
        assert mx.telemetry.registry().counter(
            "disagg/re_prefills").value >= 1
        mx.telemetry.reset()

    def test_spilled_frames_adopt_bit_identical(self, prefill_engine,
                                                decode_batcher, tmp_path):
        rng = np.random.RandomState(9)
        p = _prompts(rng, 1)[0]
        ref = decode_batcher.submit(p).result(timeout=120)
        spill_frames(str(tmp_path), "h9", prefill_engine.prefill(p))
        fr = load_spilled(str(tmp_path), "h9")
        assert decode_batcher.submit(
            p, frames=fr).result(timeout=120) == ref

    def test_dynamic_batcher_ignores_frames(self, shared_engine,
                                            prefill_engine):
        """Frames handed to a batcher that was never warmed (the
        fixture's, not the decode worker's): the request serves the
        tokens its prompt alone gives, adopted or re-prefilled."""
        bat = _batcher(shared_engine, name="cold-frames")
        rng = np.random.RandomState(10)
        p = _prompts(rng, 1)[0]
        try:
            ref = bat.submit(p).result(timeout=120)
            fr = prefill_engine.prefill(p)
            assert bat.submit(p, frames=fr).result(timeout=120) == ref
        finally:
            bat.stop()


# ---------------------------------------------------------- SLO placement
class TestSloPlacement:
    def test_equal_load_placement_cycles_replicas(self, shared_engine):
        """Regression (satellite): the PR-7 docstring promised
        round-robin ties but ``min()`` always picked the first replica —
        equal-score placement must now CYCLE through the fleet."""
        reps = [Replica(f"rr-{i}", _batcher(shared_engine, name=f"rr-{i}"))
                for i in range(3)]
        router = Router(reps, health_interval_s=0.02)
        try:
            placed = []
            for _ in range(6):  # sequential: loads are all-zero ties
                rng_p = np.array([5, 6, 7], dtype=np.int32)
                f = router.submit(rng_p)
                f.result(timeout=120)
                placed.append(f.replica)
            assert placed == ["rr-0", "rr-1", "rr-2"] * 2, placed
        finally:
            router.stop()

    def test_predicted_wait_beats_raw_backlog(self, shared_engine):
        """A replica with 3 queued-but-fast requests (p50 10 ms) must
        win over an empty-but-slow one (p50 500 ms) — the PR-10 backlog
        count chose the slow one."""
        class Stub(Replica):
            def __init__(self, name, batcher, p50, backlog):
                super().__init__(name, batcher)
                self._p50 = p50
                self._backlog = backlog

            def queue_wait_p50_ms(self):
                return self._p50

            def load(self):
                return self._backlog

        slow = Stub("slow", _batcher(shared_engine, name="slow"),
                    p50=500.0, backlog=0)
        fast = Stub("fast", _batcher(shared_engine, name="fast"),
                    p50=10.0, backlog=3)
        router = Router([slow, fast], start=False)
        try:
            assert slow.predicted_wait_ms() == 500.0
            assert fast.predicted_wait_ms() == 40.0
            with router._lock:
                assert router._pick_locked([slow, fast]) is fast
        finally:
            router.stop(stop_replicas=True)

    def test_unknown_class_rejected(self, shared_engine):
        router = Router([Replica("k1", _batcher(shared_engine))],
                        start=False)
        try:
            with pytest.raises(MXNetError):
                router.submit(np.array([3], dtype=np.int32),
                              klass="bulk")
        finally:
            router.stop()

    def test_class_default_deadline_applies(self, shared_engine,
                                            monkeypatch):
        """MXTPU_SLO_INTERACTIVE_MS is the interactive class's default
        deadline: a hung fleet fails the request with DeadlineExceeded
        at that budget instead of waiting forever."""
        monkeypatch.setenv("MXTPU_SLO_INTERACTIVE_MS", "120")
        faults.inject("batcher.hang", times=None, delay=0.5,
                      match="slo-hang")
        router = Router([Replica("slo-hang",
                                 _batcher(shared_engine,
                                          name="slo-hang"))],
                        health_interval_s=0.02)
        try:
            f = router.submit(np.array([4, 5], dtype=np.int32))
            with pytest.raises(DeadlineExceeded):
                f.result(timeout=60)
        finally:
            router.stop()

    def test_batch_sheds_before_interactive(self, shared_engine):
        """Under a degraded fleet batch requests shed at HALF the
        backlog bound: with shed_max_queue=8, batch sheds at backlog 4
        while interactive is still admitted."""
        mx.telemetry.reset()
        faults.inject("batcher.hang", times=None, delay=1.0,
                      match="cls-shed")
        rep = Replica("cls-shed", _batcher(shared_engine,
                                           name="cls-shed"))
        router = Router([rep], health_interval_s=0.02,
                        shed_queue_depth=1, shed_max_queue=8)
        rng = np.random.RandomState(21)
        try:
            admitted_batch = [router.submit(p, klass="batch")
                              for p in _prompts(rng, 4)]
            assert not any(f.done() and isinstance(f.exception(),
                                                   Backpressure)
                           for f in admitted_batch)
            doomed = router.submit(_prompts(rng, 1)[0], klass="batch")
            assert isinstance(doomed.exception(), Backpressure)
            ok = router.submit(_prompts(rng, 1)[0], klass="interactive")
            assert not (ok.done()
                        and isinstance(ok.exception(), Backpressure))
            assert mx.telemetry.registry().counter(
                "serve/shed_queue_full").value == 1
        finally:
            router.stop()
            mx.telemetry.reset()

    def test_router_place_fault_retries(self, shared_engine):
        """router.place raise-mode: the placement pass places nothing
        once, the monitor retries, the request still completes."""
        mx.telemetry.reset()
        faults.inject("router.place", times=1)
        router = Router([Replica("pl-1", _batcher(shared_engine,
                                                  name="pl-1"))],
                        health_interval_s=0.02)
        try:
            out = router.submit(np.array([5, 6, 7], dtype=np.int32)) \
                .result(timeout=120)
            assert isinstance(out, list)
            assert mx.telemetry.registry().counter(
                "serve/faults_injected").value >= 1
        finally:
            router.stop()
            mx.telemetry.reset()

    def test_per_class_ttft_recorded(self, shared_engine):
        mx.telemetry.reset()
        router = Router([Replica("ttft-1", _batcher(shared_engine,
                                                    name="ttft-1"))],
                        health_interval_s=0.02)
        rng = np.random.RandomState(22)
        try:
            router.submit(_prompts(rng, 1)[0],
                          klass="interactive").result(timeout=120)
            router.submit(_prompts(rng, 1)[0],
                          klass="batch").result(timeout=120)
            deadline = time.perf_counter() + 30
            reg = mx.telemetry.registry()
            while time.perf_counter() < deadline:
                snap = reg.snapshot()["histograms"]
                if "disagg/ttft_interactive_ms" in snap and \
                        "disagg/ttft_batch_ms" in snap:
                    break
                time.sleep(0.02)
            snap = reg.snapshot()["histograms"]
            assert snap["disagg/ttft_interactive_ms"]["count"] >= 1
            assert snap["disagg/ttft_batch_ms"]["count"] >= 1
        finally:
            router.stop()
            mx.telemetry.reset()


def _launch_mod():
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(__file__), "..", "tools"))
    import launch

    return launch


# -------------------------------------------------------------- elasticity
class TestElasticity:
    def _scaler(self, state, **kw):
        FleetScaler = _launch_mod().FleetScaler

        calls = {"spawn": 0, "retire": 0}

        def pressure():
            return {"size": state["size"],
                    "occupancy": state["occ"], "shed": state["shed"]}

        def spawn():
            calls["spawn"] += 1
            state["size"] += 1

        def retire():
            calls["retire"] += 1
            state["size"] -= 1
            return True

        cfg = dict(min_workers=1, max_workers=3, cooldown_s=0.0,
                   sustain=2)
        cfg.update(kw)
        return FleetScaler(pressure, spawn, retire, **cfg), calls

    def test_sustained_occupancy_scales_up_to_max(self):
        mx.telemetry.reset()
        state = {"size": 1, "occ": 0.95, "shed": 0}
        sc, calls = self._scaler(state)
        assert sc.step() is None      # 1 hot sample: not sustained yet
        assert sc.step() == "up"
        assert sc.step() is None and sc.step() == "up"
        assert state["size"] == 3
        for _ in range(4):            # at the ceiling: no more spawns
            sc.step()
        assert state["size"] == 3 and calls["spawn"] == 2
        assert mx.telemetry.registry().counter(
            "serve/scale_up").value == 2
        mx.telemetry.reset()

    def test_shed_growth_counts_as_pressure(self):
        state = {"size": 1, "occ": 0.0, "shed": 0}
        sc, calls = self._scaler(state)
        sc.step()                      # shed baseline
        state["shed"] = 5              # sheds grew: hot despite idle occ
        assert sc.step() is None
        state["shed"] = 9
        assert sc.step() == "up"
        assert calls["spawn"] == 1

    def test_idle_retires_down_to_min(self):
        mx.telemetry.reset()
        state = {"size": 3, "occ": 0.01, "shed": 0}
        sc, calls = self._scaler(state)
        acts = [sc.step() for _ in range(6)]
        assert acts.count("down") == 2 and state["size"] == 1
        for _ in range(3):
            sc.step()
        assert state["size"] == 1 and calls["retire"] == 2
        assert mx.telemetry.registry().counter(
            "serve/scale_down").value == 2
        mx.telemetry.reset()

    def test_cooldown_spaces_actions(self):
        state = {"size": 1, "occ": 1.0, "shed": 0}
        sc, calls = self._scaler(state, cooldown_s=3600.0)
        assert sc.step() is None
        assert sc.step() == "up"
        for _ in range(5):             # inside the cooldown window
            assert sc.step() is None
        assert calls["spawn"] == 1

    def test_retire_refusal_refunds_cooldown(self):
        state = {"size": 2, "occ": 0.0, "shed": 0}
        FleetScaler = _launch_mod().FleetScaler

        def pressure():
            return {"size": state["size"], "occupancy": state["occ"],
                    "shed": 0}

        sc = FleetScaler(pressure, lambda: None, lambda: False,
                         min_workers=1, max_workers=3,
                         cooldown_s=3600.0, sustain=1)
        assert sc.step() is None       # decided "down" but nothing
        assert sc.actions == []        # retirable: no action recorded
        with sc._lock:
            assert sc._last_action_at is None  # cooldown refunded

    def test_env_knobs_configure_defaults(self, monkeypatch):
        FleetScaler = _launch_mod().FleetScaler

        monkeypatch.setenv("MXTPU_SCALE_MIN", "2")
        monkeypatch.setenv("MXTPU_SCALE_MAX", "7")
        monkeypatch.setenv("MXTPU_SCALE_COOLDOWN_S", "11.5")
        sc = FleetScaler(lambda: {}, lambda: None, lambda: True)
        assert sc.min_workers == 2
        assert sc.max_workers == 7
        assert sc.cooldown_s == 11.5

    def test_retired_replica_excluded_and_never_respawned(
            self, shared_engine):
        """Router.retire_replica: no further placements, and its
        eventual eviction schedules NO respawn even with a factory."""
        made = []

        def factory():
            made.append(1)
            return Replica("resp", _batcher(shared_engine, name="resp"))

        reps = [Replica(f"ret-{i}",
                        _batcher(shared_engine, name=f"ret-{i}"))
                for i in range(2)]
        router = Router(reps, health_interval_s=0.02,
                        replica_factory=factory)
        rng = np.random.RandomState(23)
        try:
            router.retire_replica(reps[0])
            futs = [router.submit(p) for p in _prompts(rng, 4)]
            for f in futs:
                f.result(timeout=120)
            assert all(f.replica == "ret-1" for f in futs)
            # kill the retired replica's batcher: eviction, no respawn
            reps[0].batcher.stop(drain=False, timeout=5.0)
            deadline = time.perf_counter() + 30
            while time.perf_counter() < deadline and not reps[0].evicted:
                time.sleep(0.02)
            assert reps[0].evicted
            time.sleep(0.2)
            assert router._respawn_at is None and not made
        finally:
            router.stop()


# --------------------------------------------- in-process worker verb path
@pytest.fixture(scope="module")
def worker_trio(tmp_path_factory):
    """One prefill-role + one decode-role ServingWorker IN-PROCESS (real
    sockets, no process spawn cost), behind a router with
    RemoteReplicas."""
    root = tmp_path_factory.mktemp("disagg_workers")
    pre = ServingWorker(make_transformer_net(), str(root / "pre"),
                        "pre0", role="prefill", warmup=True,
                        heartbeat_s=0.2)
    dec = ServingWorker(make_transformer_net(), str(root / "dec"),
                        "dec0", role="decode", warmup=True,
                        heartbeat_s=0.2)
    pre.server.start()
    dec.server.start()
    yield pre, dec
    pre.shutdown()
    dec.shutdown()


def _trio_router(pre, dec, **kw):
    reps = [RemoteReplica("pre0", address=(pre.server.host,
                                           pre.server.port),
                          role="prefill"),
            RemoteReplica("dec0", address=(dec.server.host,
                                           dec.server.port),
                          role="decode")]
    cfg = dict(health_interval_s=0.05, no_replica_timeout_s=60.0,
               disagg_min_prompt=1)  # test prompts are short: hand off
    cfg.update(kw)                   # everything unless a test says not
    return Router(reps, **cfg), reps


class TestWorkerVerbs:
    def test_health_reports_role_and_slo_fields(self, worker_trio):
        pre, dec = worker_trio
        client = RpcClient((dec.server.host,
                            dec.server.port)).connect(budget_s=10.0)
        try:
            info = client.call("health")
            assert info["role"] == "decode"
            assert "queue_wait_p50_ms" in info
            assert "disagg_adopted" in info
        finally:
            client.close()

    def test_prefill_worker_refuses_submit(self, worker_trio):
        pre, _ = worker_trio
        client = RpcClient((pre.server.host, pre.server.port),
                           dead_error=ReplicaUnavailable) \
            .connect(budget_s=10.0)
        try:
            fut = client.submit(np.array([4, 5], dtype=np.int32))
            with pytest.raises(ReplicaUnavailable):
                fut.result(timeout=60)
        finally:
            client.close()

    def test_router_disagg_submit_adopts_and_matches_plain(
            self, worker_trio):
        """Full verb path: router → prefill verb → kv_push binary
        frames → decode submit with handoff → adoption. Tokens equal
        the plain (no-handoff) path on the same worker; every handoff
        adopted."""
        pre, dec = worker_trio
        rng = np.random.RandomState(11)
        prompts = _prompts(rng, 5)
        client = RpcClient((dec.server.host,
                            dec.server.port)).connect(budget_s=10.0)
        try:
            ref = [client.submit(p).result(timeout=120) for p in prompts]
        finally:
            client.close()
        with dec.batcher._stats_lock:
            adopted0 = dec.batcher.stats["adopted"]
        router, _ = _trio_router(pre, dec)
        try:
            futs = [router.submit(p) for p in prompts]
            outs = [f.result(timeout=120) for f in futs]
            assert outs == ref
            assert all(f.replica == "dec0" for f in futs)
        finally:
            router.stop()
        with dec.batcher._stats_lock:
            assert dec.batcher.stats["adopted"] - adopted0 == 5

    def test_kv_push_fault_degrades_to_re_prefill(self, worker_trio):
        """transport.kv_push raise-mode: the push fails, the router
        submits WITHOUT a handoff, the decode worker prefills locally —
        same tokens, disagg/re_prefills counted."""
        mx.telemetry.reset()
        pre, dec = worker_trio
        rng = np.random.RandomState(12)
        p = _prompts(rng, 1)[0]
        client = RpcClient((dec.server.host,
                            dec.server.port)).connect(budget_s=10.0)
        try:
            ref = client.submit(p).result(timeout=120)
        finally:
            client.close()
        faults.inject("transport.kv_push", times=1)
        router, _ = _trio_router(pre, dec)
        try:
            out = router.submit(p).result(timeout=120)
            assert out == ref
            assert mx.telemetry.registry().counter(
                "disagg/re_prefills").value >= 1
        finally:
            router.stop()
            mx.telemetry.reset()

    def test_short_prompts_prefill_in_place(self, worker_trio):
        """MXTPU_DISAGG_MIN_PROMPT: prompts below the threshold skip
        the handoff — the decode worker prefills locally and the
        prefill worker is never asked."""
        pre, dec = worker_trio
        before = pre.prefiller.prefills
        with dec.batcher._stats_lock:
            adopted0 = dec.batcher.stats["adopted"]
        router, _ = _trio_router(pre, dec, disagg_min_prompt=64)
        try:
            out = router.submit(
                np.array([5, 6, 7], dtype=np.int32)).result(timeout=120)
            assert isinstance(out, list)
        finally:
            router.stop()
        assert pre.prefiller.prefills == before
        with dec.batcher._stats_lock:
            assert dec.batcher.stats["adopted"] == adopted0

    def test_spill_dir_handoff(self, worker_trio, tmp_path, monkeypatch):
        """MXTPU_KV_SPILL_DIR: frames ride the filesystem instead of a
        worker-to-worker socket; adoption still happens."""
        pre, dec = worker_trio
        monkeypatch.setenv("MXTPU_KV_SPILL_DIR", str(tmp_path))
        rng = np.random.RandomState(13)
        p = _prompts(rng, 1)[0]
        client = RpcClient((dec.server.host,
                            dec.server.port)).connect(budget_s=10.0)
        try:
            ref = client.submit(p).result(timeout=120)
        finally:
            client.close()
        with dec.batcher._stats_lock:
            adopted0 = dec.batcher.stats["adopted"]
        router, _ = _trio_router(pre, dec)
        try:
            assert router.submit(p).result(timeout=120) == ref
        finally:
            router.stop()
        with dec.batcher._stats_lock:
            assert dec.batcher.stats["adopted"] == adopted0 + 1


# ---------------------------------------------------------------- reporting
class TestDisaggTelemetry:
    def test_report_tool_prints_disagg_section(self, tmp_path, capsys):
        import sys

        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "tools"))
        import telemetry_report

        report = {
            "counters": {"disagg/handoffs": 3, "disagg/re_prefills": 5,
                         "disagg/kv_bytes": 4096,
                         "serve/scale_up": 2, "serve/scale_down": 1},
            "histograms": {
                "disagg/kv_push_ms": {"p50": 1.5, "p95": 3.0,
                                      "count": 3},
                "disagg/ttft_interactive_ms": {"p50": 40.0, "p95": 90.0,
                                               "count": 8}},
        }
        p = tmp_path / "report.json"
        p.write_text(json.dumps(report))
        telemetry_report._print_disagg_family(str(p))
        out = capsys.readouterr().out
        assert "Disaggregated serving" in out
        assert "disagg/kv_push_ms" in out
        assert "serve/scale_up" in out
        assert "paying prefill twice" in out  # re_prefills >= handoffs

    def test_disagg_family_registered(self):
        import sys

        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "tools"))
        import telemetry_report

        assert telemetry_report.KNOWN_METRIC_FAMILIES.get("disagg") \
            == "Disaggregated serving"
        assert "disagg" in telemetry_report.KNOWN_SPAN_FAMILIES


# ------------------------------------------------------------------- chaos
@pytest.mark.chaos
class TestDisaggChaos:
    def test_sigkill_prefill_mid_handoff_zero_lost_bit_identical(
            self, tmp_path):
        """THE disaggregation chaos scenario (ISSUE-11 acceptance):
        1 prefill + 2 decode REAL worker processes under a 60-request
        load; the prefill worker is SIGKILL'd mid-handoff. Zero lost
        requests (handoff failures degrade to decode-side re-prefill)
        and every token bit-identical to a co-scheduled fleet from the
        same seed."""
        mx.telemetry.reset()
        wkw = dict(model=dict(seed=0), max_len=24, bucket_keys=(8,),
                   slots=2, max_new=4, extra_env=WORKER_ENV,
                   heartbeat_s=0.1)
        rng = np.random.RandomState(41)
        prompts = _prompts(rng, 60)

        # reference: one co-scheduled worker, same seed
        ref_h = spawn_worker(str(tmp_path / "ref"), name="ref", **wkw)
        ref_rep = RemoteReplica("ref", address=ref_h.address,
                                heartbeat_path=ref_h.heartbeat_path)
        ref_router = Router([ref_rep], health_interval_s=0.05,
                            no_replica_timeout_s=120.0)
        try:
            ref = [ref_router.submit(p).result(timeout=240)
                   for p in prompts]
        finally:
            ref_router.stop()
            ref_h.terminate()

        handles = [
            spawn_worker(str(tmp_path / "pre0"), name="pre0",
                         role="prefill", **wkw),
            spawn_worker(str(tmp_path / "dec0"), name="dec0",
                         role="decode", **wkw),
            spawn_worker(str(tmp_path / "dec1"), name="dec1",
                         role="decode", **wkw),
        ]
        roles = ["prefill", "decode", "decode"]
        reps = [RemoteReplica(h.name, address=h.address,
                              heartbeat_path=h.heartbeat_path,
                              heartbeat_stale_s=2.0, role=r)
                for h, r in zip(handles, roles)]
        router = Router(reps, retry_backoff_s=0.02,
                        health_interval_s=0.05,
                        no_replica_timeout_s=120.0,
                        disagg_min_prompt=1)  # short prompts: hand off
        futs = []
        try:
            for i, p in enumerate(prompts):
                futs.append(router.submit(p))
                if i == 25:
                    handles[0].kill()  # SIGKILL the prefill worker
                time.sleep(0.005)
            outs, errors = [], 0
            for f in futs:
                try:
                    outs.append(f.result(timeout=240))
                except Exception:  # noqa: BLE001 - counted as lost
                    errors += 1
                    outs.append(None)
            assert errors == 0, f"{errors}/60 requests lost"
            assert outs == ref, "post-recovery tokens diverged"
            # the decode fleet really adopted handoffs before the kill
            adopted = 0
            for rep in reps[1:]:
                try:
                    info = rep.client.call("health")
                except Exception:  # noqa: BLE001
                    continue
                adopted += info.get("disagg_adopted") or 0
            assert adopted >= 1, "no handoff was ever adopted"
            # and the kill produced at least one observable failover or
            # re-prefill fallback
            reg = mx.telemetry.registry()
            assert (reg.counter("disagg/re_prefills").value
                    + reg.counter("serve/failovers").value) >= 1
        finally:
            router.stop()
            for h in handles:
                if h.alive():
                    h.terminate()
            for h in handles:
                try:
                    h.wait(timeout=60)
                except Exception:  # noqa: BLE001
                    h.kill()
            mx.telemetry.reset()

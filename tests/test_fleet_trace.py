"""Fleet-scope observability (PR 16): distributed request tracing, the
telemetry scrape/aggregation plane, and per-request SLO attribution.

Contracts under test:

- CLOCK ALIGNMENT: ``estimate_offset`` recovers a synthetic true offset
  under symmetric delay and follows NTP's minimum-RTT selection rule;
  ``tools/fleet_trace.py`` shifts worker streams onto the reference
  (router) timeline using the ``trace.clock_offset`` instants.
- AGGREGATION: ``merge_summaries`` is identity on one summary and
  additive over several; ``aggregate_snapshots`` sums counters, merges
  histograms and keeps gauges per-replica; replaying the recorded
  ``fleet_telemetry.jsonl`` re-derives identical aggregates (the
  replayable-by-construction guarantee).
- END-TO-END (real processes): one disaggregated request through REAL
  prefill + decode worker processes with ``MXTPU_TRACE=1`` renders as a
  single request_id's spans across >= 2 distinct pids on one aligned
  timeline, with the ``GenerationResult.phases`` breakdown summing to
  the router-observed end-to-end latency; a ``FleetTelemetry`` scrape
  reaches every worker's registry.
- CHAOS: SIGKILL the only worker mid-stream — the merged trace shows
  the failover and the retry under ONE request_id with monotonic
  aligned timestamps, and the retried request's phases carry
  ``retry_ms``. The killed worker's append-only stream survives.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.serving import RemoteReplica, Router, faults, tracing
from mxnet_tpu.serving.batcher import PHASE_DETAIL
from mxnet_tpu.serving.tracing import (FleetTelemetry, aggregate_snapshots,
                                       estimate_offset, replay_scrapes)
from mxnet_tpu.serving.worker import spawn_worker
from mxnet_tpu.telemetry.metrics import merge_summaries

WORKER_ENV = {"JAX_PLATFORMS": os.environ.get("MXTPU_TEST_PLATFORM",
                                              "cpu")}


def _prompts(rng, n, lmin=3, lmax=8):
    return [rng.randint(3, 61, (rng.randint(lmin, lmax + 1),))
            .astype(np.int32) for _ in range(n)]


def _fleet_trace_mod():
    sys.path.insert(0, os.path.join(
        os.path.dirname(__file__), "..", "tools"))
    import fleet_trace

    return fleet_trace


def _merge_root(root, request_id=None):
    ft = _fleet_trace_mod()
    found = ft.discover_streams(str(root))
    streams = [(label, ft.load_stream(path)) for label, path in found]
    events, report = ft.merge_streams(streams, request_id=request_id)
    return events, report


# ------------------------------------------------------- clock alignment
class TestOffsetEstimation:
    def test_no_samples_is_none(self):
        assert estimate_offset([]) is None

    def test_single_sample_midpoint(self):
        off, rtt = estimate_offset([(100.0, 200.0, 1000.0)])
        assert off == 150.0 - 1000.0
        assert rtt == 100.0

    def test_symmetric_delay_recovers_true_offset(self):
        """Peer clock lags the caller by exactly 5000 µs; with symmetric
        one-way delay d the midpoint estimator is EXACT regardless of
        d: peer_ts + offset == caller_ts."""
        true_off = 5000.0
        samples = []
        for t0, d in ((10_000.0, 50.0), (20_000.0, 400.0),
                      (30_000.0, 10.0)):
            peer = t0 + d - true_off  # peer stamps mid-flight
            samples.append((t0, t0 + 2 * d, peer))
        off, rtt = estimate_offset(samples)
        assert off == pytest.approx(true_off)
        assert rtt == 20.0  # the d=10 probe won

    def test_min_rtt_sample_wins(self):
        """NTP's selection rule: a tight probe with a small offset beats
        a fat probe claiming a huge one."""
        off, rtt = estimate_offset([
            (0.0, 1000.0, -7.0),    # rtt 1000, offset 507
            (0.0, 100.0, 30.0),     # rtt 100, offset 20  <- wins
            (0.0, 5000.0, 99.0),    # rtt 5000
        ])
        assert rtt == 100.0
        assert off == 50.0 - 30.0


# ----------------------------------------------------------- aggregation
def _summary(values):
    from mxnet_tpu.telemetry.metrics import Histogram

    h = Histogram()
    for v in values:
        h.observe(v)
    return h.summary()


class TestAggregation:
    def test_merge_single_summary_is_identity(self):
        s = _summary([1.0, 2.0, 3.0, 10.0])
        m = merge_summaries([s])
        for k in ("count", "sum", "min", "max", "p50", "p95", "p99"):
            assert m[k] == pytest.approx(s[k]), k

    def test_merge_is_additive(self):
        a = _summary([1.0, 2.0, 3.0])
        b = _summary([10.0, 20.0, 30.0, 40.0, 50.0, 60.0])
        m = merge_summaries([a, b])
        assert m["count"] == 9
        assert m["sum"] == pytest.approx(a["sum"] + b["sum"])
        assert m["min"] == 1.0 and m["max"] == 60.0
        # count-weighted percentile merge: between the two p50s,
        # nearer the bigger population's
        assert a["p50"] < m["p50"] < b["p50"]
        expect = (a["p50"] * 3 + b["p50"] * 6) / 9
        assert m["p50"] == pytest.approx(expect)

    def test_aggregate_snapshots_sums_counters_keeps_gauges(self):
        snaps = {
            "w0": {"counters": {"serve/completed": 3},
                   "gauges": {"infer/tokens_per_sec": 10.0},
                   "histograms": {"infer/ttft_ms": _summary([5.0])}},
            "w1": {"counters": {"serve/completed": 4,
                                "serve/retries": 1},
                   "gauges": {"infer/tokens_per_sec": 20.0},
                   "histograms": {"infer/ttft_ms": _summary([15.0])}},
        }
        agg = aggregate_snapshots(snaps)
        assert agg["replicas"] == ["w0", "w1"]
        assert agg["counters"] == {"serve/completed": 7,
                                   "serve/retries": 1}
        assert agg["histograms"]["infer/ttft_ms"]["count"] == 2
        # gauges do NOT aggregate — they stay per-replica
        assert "infer/tokens_per_sec" not in agg.get("counters")
        assert agg["per_replica"]["w0"]["gauges"][
            "infer/tokens_per_sec"] == 10.0

    def test_replay_reproduces_aggregates(self, tmp_path):
        snaps = {
            "w0": {"counters": {"serve/completed": 2},
                   "histograms": {"infer/ttft_ms": _summary([1.0, 9.0])}},
            "router": {"counters": {"fleet/scrapes": 1}},
        }
        path = tmp_path / "fleet_telemetry.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps({"t": 1.5, "snapshots": snaps}) + "\n")
            f.write("{torn line\n")  # append-only stream may tear
        replayed = replay_scrapes(str(path))
        assert len(replayed) == 1
        assert replayed[0]["t"] == 1.5
        assert replayed[0]["aggregate"] == aggregate_snapshots(snaps)


# -------------------------------------------------- tracing primitives
class TestTracingPrimitives:
    def test_request_scope_is_reentrant_and_restores(self):
        assert tracing.current_request_id() is None
        with tracing.request_scope("aaa"):
            assert tracing.current_request_id() == "aaa"
            with tracing.request_scope("bbb"):
                assert tracing.current_request_id() == "bbb"
            assert tracing.current_request_id() == "aaa"
            with tracing.request_scope(None):  # no-op scope
                assert tracing.current_request_id() == "aaa"
        assert tracing.current_request_id() is None

    def test_context_propagates_in_scope_id(self):
        assert tracing.context() is None
        with tracing.request_scope("ctx1"):
            assert tracing.context() == {"request_id": "ctx1"}
        assert tracing.context("explicit") == {"request_id": "explicit"}

    def test_force_overrides_env(self, monkeypatch):
        monkeypatch.delenv("MXTPU_TRACE", raising=False)
        assert not tracing.trace_enabled()
        try:
            tracing.force(True)
            assert tracing.trace_enabled()
            tracing.force(False)
            monkeypatch.setenv("MXTPU_TRACE", "1")
            assert not tracing.trace_enabled()
            tracing.force(None)
            assert tracing.trace_enabled()
        finally:
            tracing.force(None)

    def test_fault_instant_carries_spec_and_request_id(self, tmp_path):
        """Satellite: an armed fault's instant names the point, the
        firing spec (hit/fire counters included) and the in-scope
        request id."""
        mx.telemetry.reset()
        mx.telemetry.enable(str(tmp_path))
        faults.inject("router.place", times=1)
        try:
            with tracing.request_scope("deadbeef00000001"):
                with pytest.raises(faults.FaultInjected):
                    faults.fire("router.place", tag="interactive")
            events = [json.loads(ln) for ln in
                      open(mx.telemetry.jsonl_path())]
            fired = [e for e in events if e["name"] == "serve.fault"]
            assert len(fired) == 1
            args = fired[0]["args"]
            assert args["point"] == "router.place"
            assert args["request_id"] == "deadbeef00000001"
            assert args["spec"]["point"] == "router.place"
            assert args["spec"]["fired"] == 1
        finally:
            faults.clear()
            mx.telemetry.reset()


# ------------------------------------------------------- merge tool unit
class TestFleetTraceTool:
    def _streams(self):
        router = [
            {"name": "trace.clock_offset", "ph": "i", "ts": 50.0,
             "pid": 1, "tid": 1,
             "args": {"replica": "w0", "peer_pid": 2,
                      "offset_us": 999.0, "rtt_us": 900.0}},
            {"name": "trace.clock_offset", "ph": "i", "ts": 60.0,
             "pid": 1, "tid": 1,
             "args": {"replica": "w0", "peer_pid": 2,
                      "offset_us": 1_000_000.0, "rtt_us": 80.0}},
            {"name": "trace.request", "ph": "X", "ts": 2_000_000.0,
             "dur": 500_000.0, "pid": 1, "tid": 1,
             "args": {"request_id": "r1"}},
        ]
        worker = [
            {"name": "trace.decode", "ph": "X", "ts": 1_100_000.0,
             "dur": 1000.0, "pid": 2, "tid": 9,
             "args": {"request_id": "r1"}},
            {"name": "trace.queue", "ph": "X", "ts": 1_050_000.0,
             "dur": 10.0, "pid": 2, "tid": 9,
             "args": {"request_id": "r2"}},
        ]
        return [("router_1", router), ("w0_2", worker)]

    def test_min_rtt_offset_shifts_worker_stream(self):
        ft = _fleet_trace_mod()
        events, report = ft.merge_streams(self._streams())
        assert report["reference"] == "router_1"
        assert report["offsets"]["2"]["offset_us"] == 1_000_000.0
        assert report["offsets"]["2"]["rtt_us"] == 80.0  # min-RTT won
        assert report["unaligned_pids"] == []
        dec = [e for e in events if e["name"] == "trace.decode"][0]
        assert dec["ts"] == 1_100_000.0 + 1_000_000.0
        req = [e for e in events if e["name"] == "trace.request"][0]
        assert req["ts"] == 2_000_000.0  # reference stream: unshifted
        # aligned: the worker's decode now sits INSIDE the router's
        # request envelope
        assert req["ts"] <= dec["ts"] <= req["ts"] + req["dur"]

    def test_process_name_metadata_per_pid(self):
        ft = _fleet_trace_mod()
        events, _ = ft.merge_streams(self._streams())
        meta = {e["pid"]: e["args"]["name"] for e in events
                if e.get("ph") == "M"}
        assert meta == {1: "router_1", 2: "w0_2"}

    def test_request_filter(self):
        ft = _fleet_trace_mod()
        events, _ = ft.merge_streams(self._streams(), request_id="r1")
        names = [e["name"] for e in events if e.get("ph") == "X"]
        assert sorted(names) == ["trace.decode", "trace.request"]

    def test_unaligned_pid_reported(self):
        ft = _fleet_trace_mod()
        streams = self._streams()
        streams.append(("w9_9", [
            {"name": "trace.decode", "ph": "X", "ts": 5.0, "dur": 1.0,
             "pid": 9, "tid": 1, "args": {}}]))
        _, report = ft.merge_streams(streams)
        assert report["unaligned_pids"] == [9]

    def test_load_stream_skips_torn_lines(self, tmp_path):
        ft = _fleet_trace_mod()
        p = tmp_path / "events.jsonl"
        p.write_text('{"name": "a", "ph": "i", "ts": 1, "pid": 1}\n'
                     '{"name": "b", "ph"')
        events = ft.load_stream(str(p))
        assert [e["name"] for e in events] == ["a"]


# -------------------------------------------------------------- reporting
class TestFleetReporting:
    def test_fleet_family_registered(self):
        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "tools"))
        import telemetry_report

        assert telemetry_report.KNOWN_METRIC_FAMILIES.get("fleet") \
            == "Fleet observability"
        assert "trace" in telemetry_report.KNOWN_SPAN_FAMILIES

    def test_report_tool_prints_fleet_section(self, tmp_path, capsys):
        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), "..", "tools"))
        import telemetry_report

        report = {
            "counters": {"fleet/scrapes": 2, "fleet/scrape_errors": 5,
                         "serve/slo_burn_interactive": 3},
            "gauges": {"fleet/replicas": 2},
        }
        p = tmp_path / "report.json"
        p.write_text(json.dumps(report))
        telemetry_report._print_fleet_family(str(p))
        out = capsys.readouterr().out
        assert "Fleet observability" in out
        assert "fleet/scrapes" in out
        assert "serve/slo_burn_interactive" in out
        assert "unreachable" in out       # errors >= scrapes warning
        assert "phase breakdowns" in out  # slo burn warning


# --------------------------------------------- end-to-end, real processes
@pytest.fixture(scope="module")
def traced_fleet(tmp_path_factory):
    """A REAL traced disaggregated fleet: 1 prefill + 1 decode worker
    process with MXTPU_TRACE/MXTPU_TRACE_DIR, the router process tracing
    into its own subdirectory, three requests served, one telemetry
    scrape taken — torn down before the tests read the artifacts."""
    root = tmp_path_factory.mktemp("fleet_trace_e2e")
    mx.telemetry.reset()
    tracing.force(True)
    mx.telemetry.enable(str(root / "router_0"))
    env = dict(WORKER_ENV, MXTPU_TRACE="1", MXTPU_TRACE_DIR=str(root))
    wkw = dict(model=dict(seed=0), max_len=24, bucket_keys=(8,),
               slots=2, max_new=4, extra_env=env, heartbeat_s=0.1)
    handles = [
        spawn_worker(str(root / "pre"), name="pre0", role="prefill",
                     **wkw),
        spawn_worker(str(root / "dec"), name="dec0", role="decode",
                     **wkw),
    ]
    for h in handles:
        h.wait_ready(timeout=240)
    reps = [RemoteReplica(h.name, address=h.address,
                          heartbeat_path=h.heartbeat_path,
                          heartbeat_stale_s=10.0, role=r)
            for h, r in zip(handles, ["prefill", "decode"])]
    router = Router(reps, health_interval_s=0.05,
                    no_replica_timeout_s=120.0,
                    disagg_min_prompt=1)  # short prompts: hand off
    rng = np.random.RandomState(31)
    prompts = _prompts(rng, 3)
    scrape = None
    try:
        time.sleep(0.3)  # >= 1 clock sample per worker (health cadence)
        futs = [router.submit(p) for p in prompts]
        outs = [f.result(timeout=240) for f in futs]
        ft = FleetTelemetry(router._replica_snapshot, interval_s=0,
                            directory=str(root), rpc_timeout_s=10.0)
        snaps = ft.scrape_once()
        scrape = {"snaps": snaps, "aggregate": ft.aggregate(),
                  "path": ft.path}
        time.sleep(0.3)  # a final heartbeat carrying request counters
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
        tracing.force(None)
        mx.telemetry.reset()
    yield {"root": root, "futs": futs, "outs": outs,
           "handles": handles, "scrape": scrape}


class TestFleetTraceE2E:
    def test_one_request_spans_multiple_processes_aligned(
            self, traced_fleet):
        """THE tentpole acceptance: one disaggregated request's spans,
        from >= 2 REAL processes, merge onto one aligned timeline under
        a single request_id — with every remote span inside the
        router's request envelope (alignment tolerance << the seconds
        of raw clock skew between process start times)."""
        root = traced_fleet["root"]
        fut = traced_fleet["futs"][0]
        assert fut.request_id is not None
        events, report = _merge_root(root, request_id=fut.request_id)
        assert report["reference"].startswith("router")
        assert report["unaligned_pids"] == []
        spans = [e for e in events if e.get("ph") == "X"]
        pids = {e["pid"] for e in spans}
        assert len(pids) >= 2, f"spans only from pids {pids}"
        names = {e["name"] for e in spans}
        assert "trace.request" in names
        assert "trace.queue" in names and "trace.decode" in names
        req = [e for e in spans if e["name"] == "trace.request"][0]
        slack = 50_000.0  # µs; loopback RTT error is well under this
        for e in spans:
            assert req["ts"] - slack <= e["ts"] \
                <= req["ts"] + req["dur"] + slack, \
                (e["name"], e["pid"], e["ts"], req["ts"], req["dur"])

    def test_prefill_and_kv_push_spans_from_prefill_worker(
            self, traced_fleet):
        root = traced_fleet["root"]
        events, _ = _merge_root(root)
        by_name = {}
        for e in events:
            if e.get("ph") == "X":
                by_name.setdefault(e["name"], []).append(e)
        assert "trace.prefill" in by_name
        assert "trace.kv_push" in by_name
        # the prefill worker's spans carry the router-minted ids
        rids = {f.request_id for f in traced_fleet["futs"]}
        assert any(e["args"].get("request_id") in rids
                   for e in by_name["trace.prefill"])

    def test_phase_breakdown_sums_to_observed_e2e(self, traced_fleet):
        """SLO attribution: GenerationResult.phases *_ms entries sum to
        the router-observed end-to-end latency EXACTLY (other_ms is the
        unclamped residual), cross-checked against the e2e_ms the
        trace.request span recorded."""
        root = traced_fleet["root"]
        for fut in traced_fleet["futs"]:
            phases = fut.phases
            assert phases is not None
            for key in ("queue_ms", "prefill_ms", "decode_ms",
                        "handoff_ms", "other_ms"):
                assert key in phases, (key, phases)
            # the phases that follow one another; ``seat_ms`` and
            # ``service_ms`` split ``prefill_ms`` and ``deliver_ms`` lies
            # inside ``decode_ms`` (``batcher.PHASE_DETAIL``)
            total = sum(v for k, v in phases.items()
                        if k.endswith("_ms") and isinstance(v, float)
                        and k not in PHASE_DETAIL)
            assert phases["prefill_ms"] == pytest.approx(
                phases["seat_ms"] + phases["service_ms"], abs=1e-6)
            events, _ = _merge_root(root, request_id=fut.request_id)
            req = [e for e in events if e["name"] == "trace.request"]
            assert len(req) == 1
            assert total == pytest.approx(req[0]["args"]["e2e_ms"],
                                          rel=1e-6)

    def test_scrape_reaches_every_worker_and_replays(self, traced_fleet):
        scrape = traced_fleet["scrape"]
        snaps = scrape["snaps"]
        assert set(snaps) >= {"pre0", "dec0", "router"}
        # the decode worker really served: its own registry says so
        dec = snaps["dec0"]["counters"]
        assert dec.get("infer/requests", 0) >= 3
        agg = scrape["aggregate"]
        assert agg["counters"], "fleet aggregate is empty"
        # replay identity: the recorded JSONL re-derives the aggregate
        replayed = replay_scrapes(scrape["path"])
        assert replayed
        assert replayed[-1]["aggregate"] == aggregate_snapshots(snaps)

    def test_worker_heartbeat_carries_request_fields(self, traced_fleet):
        """Satellite: the worker watchdog heartbeat now reports
        inflight / last_request_id / requests_completed."""
        dec = traced_fleet["handles"][1]
        hb = json.loads(open(dec.heartbeat_path).read())
        assert hb.get("requests_completed", 0) >= 3
        assert hb.get("last_request_id")
        assert "inflight" in hb

    def test_tokens_unaffected_by_tracing(self, traced_fleet):
        outs = traced_fleet["outs"]
        assert all(isinstance(o, list) and o for o in outs)


# ------------------------------------------------------------------- chaos
@pytest.mark.chaos
class TestTraceChaos:
    def test_sigkill_failover_and_retry_under_one_request_id(
            self, tmp_path):
        """Cross-process chaos: SIGKILL the only worker mid-stream. The
        factory respawns a real process, every request completes, and
        the MERGED trace shows the failover + the retry instants under
        ONE request_id with monotonic aligned timestamps — including
        spans recovered from the killed worker's surviving append-only
        stream."""
        mx.telemetry.reset()
        tracing.force(True)
        mx.telemetry.enable(str(tmp_path / "router_0"))
        env = dict(WORKER_ENV, MXTPU_TRACE="1",
                   MXTPU_TRACE_DIR=str(tmp_path))
        wkw = dict(model=dict(seed=0), max_len=24, bucket_keys=(8,),
                   slots=2, max_new=4, extra_env=env, heartbeat_s=0.1)
        handles = [spawn_worker(str(tmp_path / "w0"), name="w0", **wkw)]
        handles[0].wait_ready(timeout=240)
        spawned = [1]

        def factory():
            i = spawned[0]
            spawned[0] += 1
            h = spawn_worker(str(tmp_path / f"w{i}"), name=f"w{i}",
                             **wkw)
            handles.append(h)
            return RemoteReplica.spawning(h, heartbeat_stale_s=2.0)

        reps = [RemoteReplica("w0", address=handles[0].address,
                              heartbeat_path=handles[0].heartbeat_path,
                              heartbeat_stale_s=2.0)]
        router = Router(reps, retry_backoff_s=0.02,
                        health_interval_s=0.05, replica_factory=factory,
                        respawn_backoff_s=0.05,
                        no_replica_timeout_s=240.0)
        rng = np.random.RandomState(43)
        prompts = _prompts(rng, 10)
        try:
            time.sleep(0.3)  # >= 1 clock sample for w0 BEFORE the kill
            futs = [router.submit(p) for p in prompts]
            handles[0].kill()  # SIGKILL mid-stream: requests inflight
            outs = [f.result(timeout=240) for f in futs]
            assert all(isinstance(o, list) for o in outs)
            reg = mx.telemetry.registry()
            assert reg.counter("serve/failovers").value >= 1
            assert reg.counter("serve/retries").value >= 1
            time.sleep(1.2)  # a clock sample for the respawned worker
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
            tracing.force(None)
            mx.telemetry.reset()

        events, report = _merge_root(tmp_path)
        # the killed worker's stream survived the SIGKILL
        assert any(lbl.startswith("w0_") for lbl in report["streams"])
        assert report["unaligned_pids"] == []
        retries = [e for e in events if e["name"] == "trace.retry"]
        assert retries, "no trace.retry instant was recorded"
        rid = retries[0]["args"]["request_id"]
        assert rid is not None
        fut = next(f for f in futs if f.request_id == rid)
        assert fut.phases and "retry_ms" in fut.phases
        # the failover instant blames the dead replica and lists the
        # requests it took down
        failovers = [e for e in events if e["name"] == "serve.failover"]
        assert failovers and failovers[0]["args"]["replica"] == "w0"
        # the requests list is only non-empty when eviction catches the
        # inflight requests BEFORE the dead-socket retry path reassigns
        # them — either ordering is valid, so only check the shape
        assert "requests" in failovers[0]["args"]
        assert "n_requests" in failovers[0]["args"]
        # monotonic aligned timeline for THE retried request: its spans
        # and instants, from both worker processes, sit inside the
        # router's request envelope
        rid_events = [e for e in events
                      if (e.get("args") or {}).get("request_id") == rid
                      and e.get("ph") in ("X", "i")]
        req = [e for e in rid_events if e["name"] == "trace.request"]
        assert len(req) == 1
        req = req[0]
        slack = 50_000.0  # µs
        for e in rid_events:
            assert req["ts"] - slack <= e["ts"] \
                <= req["ts"] + req["dur"] + slack, \
                (e["name"], e.get("pid"), e["ts"])
        retry_ts = [e["ts"] for e in rid_events
                    if e["name"] == "trace.retry"]
        decode_spans = [e for e in rid_events
                        if e["name"] == "trace.decode"]
        assert decode_spans, "retried request never decoded"
        final_decode = max(decode_spans, key=lambda e: e["ts"])
        # the retry happened before the (respawned) decode finished
        assert min(retry_ts) <= final_decode["ts"] + final_decode["dur"]

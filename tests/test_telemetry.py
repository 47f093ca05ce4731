"""Unified telemetry subsystem: spans, metrics, watchdog, integrations.

Covers the ISSUE-1 acceptance surface: span nesting + disabled-mode
no-op, histogram percentiles, heartbeat progress + simulated-stall
detection, Chrome-trace/JSONL dump round-trip, trainer-step metric
emission on a tiny model (with dataloader + kvstore spans in the same
trace), and the bench watchdog nonzero-exit regression.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu import telemetry as tel
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry.metrics import Histogram
from mxnet_tpu.telemetry.watchdog import Watchdog

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_sandbox(tmp_path, monkeypatch):
    """Each test gets a fresh telemetry dir and a clean global state."""
    monkeypatch.setenv("MXNET_TELEMETRY_DIR", str(tmp_path / "tel"))
    monkeypatch.delenv("MXNET_TELEMETRY_WATCHDOG", raising=False)
    tel.reset()
    yield
    tel.reset()


# ----------------------------------------------------------------- events
def test_disabled_mode_is_noop(tmp_path):
    assert not tel.enabled()
    # the disabled span is ONE shared singleton — no per-call allocation
    assert tel.span("a") is tel.NULL_SPAN
    assert tel.span("b", {"k": 1}) is tel.NULL_SPAN
    with tel.span("a"):
        pass
    tel.instant("marker")
    assert tel.jsonl_path() is None
    assert tel.dump() is None
    assert not (tmp_path / "tel").exists()


def test_span_nesting_and_dump_roundtrip(tmp_path):
    tel.enable(watchdog=False)
    with tel.span("outer", {"k": "v"}):
        with tel.span("inner"):
            time.sleep(0.005)
    tel.instant("phase.marker", {"step": 3})

    # JSONL: depth/parent recorded, stream is one JSON object per line
    lines = [json.loads(l) for l in open(tel.jsonl_path())]
    outer = next(l for l in lines if l["name"] == "outer")
    inner = next(l for l in lines if l["name"] == "inner")
    assert inner["depth"] == 1 and inner["parent"] == "outer"
    assert outer["depth"] == 0 and outer["parent"] is None
    # containment: inner lies within outer on the same tid
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3

    # Chrome-trace dump loads and holds the same spans + the instant
    trace = json.load(open(tel.dump()))
    events = trace["traceEvents"]
    names = {e["name"] for e in events}
    assert {"outer", "inner", "phase.marker"} <= names
    for e in events:
        if e["name"] == "inner":
            assert e["ph"] == "X" and e["dur"] >= 4000  # >= 4ms in us
        if e["name"] == "phase.marker":
            assert e["ph"] == "i" and e["args"]["step"] == 3


def test_span_nesting_is_thread_local():
    tel.enable(watchdog=False)
    seen = {}

    def worker():
        with tel.span("t2.outer"):
            with tel.span("t2.inner"):
                pass

    with tel.span("main.outer"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    lines = [json.loads(l) for l in open(tel.jsonl_path())]
    for l in lines:
        seen[l["name"]] = l
    # the worker thread's stack does not see main's open span
    assert seen["t2.outer"]["depth"] == 0
    assert seen["t2.outer"]["parent"] is None
    assert seen["t2.inner"]["parent"] == "t2.outer"


def test_non_serializable_span_args_survive():
    tel.enable(watchdog=False)
    with tel.span("odd", {"obj": object()}):
        pass
    lines = [json.loads(l) for l in open(tel.jsonl_path())]
    assert any(l["name"] == "odd" for l in lines)


# ---------------------------------------------------------------- metrics
def test_histogram_percentiles():
    h = Histogram(window=1024)
    for v in range(1, 101):
        h.observe(v / 100.0)
    assert h.count == 100
    assert abs(h.percentile(50) - 0.505) < 1e-9
    assert abs(h.percentile(95) - 0.9505) < 1e-3
    assert abs(h.percentile(99) - 0.9901) < 1e-3
    s = h.summary()
    assert s["min"] == 0.01 and s["max"] == 1.0
    assert abs(s["mean"] - 0.505) < 1e-9


def test_histogram_rolling_window_with_cumulative_totals():
    h = Histogram(window=10)
    for v in range(100):
        h.observe(float(v))
    # percentiles reflect only the last 10 observations (90..99) ...
    assert h.percentile(50) >= 90.0
    # ... while count/sum stay cumulative
    assert h.count == 100
    assert h.sum == sum(range(100))


@pytest.mark.parametrize("made,n,want", [
    (0, 3, []),                          # nothing observed
    (3, 2, [2.0, 3.0]),                  # window not yet full
    (3, 9, [1.0, 2.0, 3.0]),             # fewer made than asked for
    (6, 3, [4.0, 5.0, 6.0]),             # wrapped: 5, 6 | 3, 4 in the ring
    (6, 4, [3.0, 4.0, 5.0, 6.0]),        # the whole ring across the seam
    (6, 10, [3.0, 4.0, 5.0, 6.0]),       # fewer kept than asked for
    (8, 1, [8.0]),                       # the write index back at 0
    (8, 0, []),
])
def test_histogram_last_gives_the_newest_observations_in_order(made, n,
                                                               want):
    h = Histogram(window=4)
    for v in range(1, made + 1):
        h.observe(v)
    assert h.last(n) == want


def test_empty_histogram_is_null_safe():
    h = Histogram()
    assert h.percentile(50) is None
    assert h.summary()["p95"] is None


def test_registry_get_or_create_and_clear():
    reg = tel.registry()
    c = reg.counter("test/c")
    assert reg.counter("test/c") is c
    c.inc(5)
    reg.gauge("test/g").max(10)
    reg.gauge("test/g").max(3)  # high-water mark keeps 10
    snap = reg.snapshot()
    assert snap["counters"]["test/c"] == 5
    assert snap["gauges"]["test/g"] == 10
    reg.clear(prefix="test/")
    assert "test/c" not in reg.snapshot()["counters"]


def test_report_step_metrics():
    tel.enable(watchdog=False)
    for dt in (0.01, 0.02, 0.03, 0.04, 0.05):
        tel.record_step(samples=32, seconds=dt)
    r = tel.report()
    assert r["steps"] == 5
    assert abs(r["step_time_p50"] - 0.03) < 1e-9
    assert r["step_time_p95"] is not None
    # 160 samples over 0.15s of recorded step time
    assert abs(r["samples_per_sec"] - 160 / 0.15) < 1e-6
    # null-safe accelerator columns on CPU
    assert r["hbm_peak_bytes"] is None


def test_profiler_rebased_on_registry():
    mx.profiler.record_host_op("myop", 0.002)
    mx.profiler.record_host_op("myop", 0.004)
    table = mx.profiler.dumps()
    assert "myop" in table
    hist = tel.registry().histograms_with_prefix("op/")["op/myop"]
    assert hist.count == 2 and abs(hist.sum - 0.006) < 1e-9
    mx.profiler.dumps(reset=True)
    assert "myop" not in mx.profiler.dumps()


# --------------------------------------------------------------- watchdog
def test_watchdog_heartbeat_progress(tmp_path):
    wd = Watchdog(str(tmp_path), interval=0.05, stall_factor=10,
                  min_stall_s=30)
    wd.start()
    try:
        for _ in range(3):
            wd.notify_step(seconds=0.01)
        time.sleep(0.2)
        hb = json.load(open(wd.heartbeat_path))
        assert hb["step"] == 3
        assert hb["status"] == "alive"
        assert hb["median_step_s"] == 0.01
    finally:
        wd.stop()
    assert json.load(open(wd.heartbeat_path))["status"] == "stopped"


def test_watchdog_detects_simulated_stall(tmp_path):
    stalls = []
    wd = Watchdog(str(tmp_path), interval=0.05, stall_factor=3,
                  min_stall_s=0.1, on_stall=stalls.append)
    wd.start()
    try:
        for _ in range(4):
            wd.notify_step(seconds=0.01)
        # simulated stalled step: sleep far beyond 3x the 10ms median
        deadline = time.time() + 5.0
        while not stalls and time.time() < deadline:
            time.sleep(0.05)
    finally:
        wd.stop()
    assert stalls, "watchdog never fired on a stalled step"
    state = stalls[0]
    assert state["step"] == 4
    # the stall dumps every thread's stack
    assert state["stacks"] and os.path.exists(state["stacks"])
    dump_txt = open(state["stacks"]).read()
    assert "Thread" in dump_txt
    assert json.load(open(wd.heartbeat_path))["status"] == "stopped"
    # one stall episode, not one per interval tick
    assert wd.stall_count == 1


def test_watchdog_hard_hang_exits_nonzero(tmp_path):
    codes = []
    wd = Watchdog(str(tmp_path), interval=0.05, stall_factor=100,
                  min_stall_s=100, hard_timeout_s=0.2, exit_code=43,
                  _exit_fn=codes.append)
    wd.start()
    try:
        deadline = time.time() + 5.0
        while not codes and time.time() < deadline:
            time.sleep(0.05)
        assert codes == [43]
        # heartbeat flushed BEFORE the exit call (in production os._exit
        # ends the process here; stop() below is test-only teardown)
        assert json.load(open(wd.heartbeat_path))["status"] == "hard_hang"
    finally:
        wd.stop()


def test_watchdog_no_stall_before_first_step(tmp_path):
    # a run still compiling has no step times: stall detection stays
    # quiet (the hard timeout is the backstop for that phase)
    stalls = []
    wd = Watchdog(str(tmp_path), interval=0.05, stall_factor=1,
                  min_stall_s=0.05, on_stall=stalls.append)
    wd.start()
    time.sleep(0.3)
    wd.stop()
    assert not stalls


def test_record_step_feeds_watchdog():
    tel.enable(watchdog=False)
    wd = tel.start_watchdog(interval=0.05, stall_factor=10,
                            min_stall_s=30)
    try:
        tel.record_step(samples=8, seconds=0.01)
        tel.record_step(samples=8, seconds=0.01)
        time.sleep(0.15)
        hb = json.load(open(wd.heartbeat_path))
        assert hb["step"] == 2
    finally:
        tel.stop_watchdog()


# ----------------------------------------------------- trainer integration
def _toy_training_run(steps=5):
    """5-step toy run exercising trainer + dataloader + kvstore spans."""
    net = nn.Dense(2, in_units=4)
    net.initialize()
    # update_on_kvstore routes the optimizer through kvstore push/pull —
    # the single-process path that emits kvstore spans
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1},
                            update_on_kvstore=True)
    xs = np.random.RandomState(0).randn(steps * 2, 4).astype(np.float32)
    ys = np.zeros((steps * 2,), dtype=np.float32)
    dataset = gluon.data.ArrayDataset(mx.nd.array(xs), mx.nd.array(ys))
    loader = gluon.data.DataLoader(dataset, batch_size=2)
    n = 0
    for data, label in loader:
        if n >= steps:
            break
        with autograd.record():
            loss = (net(data).sum() - label.sum()) ** 2
        loss.backward()
        trainer.step(2)
        n += 1
    return net


def test_trainer_step_emits_spans_and_metrics():
    tel.enable(watchdog=False)
    _toy_training_run(steps=5)
    r = tel.report()
    assert r["steps"] == 5
    assert r["step_time_p50"] is not None
    assert r["step_time_p95"] is not None
    assert r["samples_per_sec"] is not None and r["samples_per_sec"] > 0
    assert r["counters"]["trainer/samples"] == 10
    # Chrome-trace dump is loadable and carries all three span families
    trace = json.load(open(tel.dump()))
    names = {e["name"] for e in trace["traceEvents"]}
    assert "trainer.step" in names
    assert "trainer.update" in names
    assert "dataloader.load" in names
    assert "kvstore.push" in names
    assert "kvstore.pull" in names
    # kvstore metrics recorded alongside the spans
    assert r["counters"]["kvstore/push_bytes"] > 0


def test_trainer_disabled_telemetry_records_nothing():
    assert not tel.enabled()
    _toy_training_run(steps=2)
    snap = tel.registry().snapshot()
    assert snap["counters"].get("trainer/steps", 0) == 0
    assert "trainer/step_time_s" not in snap["histograms"]
    assert tel.jsonl_path() is None


def test_env_var_enables_telemetry(tmp_path):
    out_dir = tmp_path / "envtel"
    code = (
        "import json\n"
        "import mxnet_tpu as mx\n"
        "assert mx.telemetry.enabled()\n"
        "with mx.telemetry.span('probe'):\n"
        "    pass\n"
        "print(json.dumps({'trace': mx.telemetry.dump()}))\n"
    )
    env = dict(os.environ, MXNET_TELEMETRY="1",
               MXNET_TELEMETRY_DIR=str(out_dir), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    trace_path = json.loads(proc.stdout.strip().splitlines()[-1])["trace"]
    names = {e["name"]
             for e in json.load(open(trace_path))["traceEvents"]}
    assert "probe" in names


# ----------------------------------------------------- bench watchdog rc
def test_bench_watchdog_exits_nonzero():
    """Regression: a hard bench hang must exit
    nonzero AND still print the error JSON line."""
    code = (
        "import time\n"
        "import bench\n"
        "bench._watchdog(seconds=0.5)\n"
        "time.sleep(30)\n"  # simulated hang: never reaches a result
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 1, (proc.returncode, proc.stderr[-500:])
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "watchdog" in row["error"]
    assert row["value"] == 0.0
    # schema carries the telemetry columns even on the error path
    assert "step_time_p50" in row and "hbm_peak_bytes" in row


def test_bench_watchdog_cancelled_on_success():
    """main() completing normally cancels the timer: no late os._exit."""
    code = (
        "import bench\n"
        "t = bench._watchdog(seconds=0.3)\n"
        "t.cancel()\n"
        "import time; time.sleep(0.6)\n"
        "print('clean')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-500:]
    assert "clean" in proc.stdout


# ------------------------------------------------------------ CLI report
def test_telemetry_report_cli(tmp_path):
    tel.enable(watchdog=False)
    with tel.span("cli.span"):
        pass
    tel.instant("cli.marker", {"step": 1})
    tel.dump()
    jsonl = tel.jsonl_path()
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    # file mode
    assert telemetry_report.main([jsonl]) == 0
    # directory mode (picks up events.jsonl + report.json)
    assert telemetry_report.main([os.path.dirname(jsonl)]) == 0
    spans, instants = telemetry_report.summarize(
        telemetry_report.load_events(jsonl))
    assert "cli.span" in spans
    assert any(e["name"] == "cli.marker" for e in instants)
    out = telemetry_report.format_spans(spans)
    assert "cli.span" in out

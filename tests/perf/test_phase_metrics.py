"""The per-layer readers of the program's phase spans (PR 25): each on
hand-made counters or a hand-made event list whose answer is known, on an
excerpt of a real trace of the serving cell, and in traced rehearsals of
both cells."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from perf.harness import phases
from perf.harness.loader import Benchmark
from perf.harness.trace import Event, TraceSummary

from _runs import REPO

MS = 1_000_000
DEV = "/device:TPU:0"
SERVING = "transformer-big.translate-closed"
TRAINING = "bert-base.pretrain-s128"

# ten iterations; seconds of each phase over them
STATS0 = {"iterations": 100, "step_s": 10.0, "intake_s": 0.10,
          "retire_s": 5.0, "register_prefix_s": 4.0,
          "register_readback_s": 3.0,
          "admit_s": 1.0, "prefill_s": 0.5, "capacity_s": 0.05,
          "dispatch_s": 0.2, "readback_s": 1.0, "collect_s": 0.4}
STATS1 = {"iterations": 110, "step_s": 13.4, "intake_s": 0.11,
          "retire_s": 7.7, "register_prefix_s": 6.6,
          "register_readback_s": 5.5,
          "admit_s": 1.2, "prefill_s": 0.65, "capacity_s": 0.06,
          "dispatch_s": 0.22, "readback_s": 1.23, "collect_s": 0.49}
WANT = {                       # ms per iteration
    "sched_iter_busy_ms": 340.0,
    "sched_retire_ms": 10.0,          # 270 of retire less 260 inside it
    "prefix_register_ms": 260.0,
    "prefix_readback_ms": 250.0,
    "sched_admit_ms": 5.0,            # 20 of admit less 15 of prefill
    "prefill_wait_ms": 15.0,
    "decode_wait_ms": 25.0,           # 2 of dispatch + 23 of read-back
    "sched_unaccounted_ms": 14.0,     # 340 - (1 + 270 + 20 + 1 + 2 + 23 + 9)
}
USES = {
    "sched_iter_busy_ms": ["step_s"],
    "sched_retire_ms": ["retire_s", "register_prefix_s"],
    "prefix_register_ms": ["register_prefix_s"],
    "prefix_readback_ms": ["register_readback_s"],
    "sched_admit_ms": ["admit_s", "prefill_s"],
    "prefill_wait_ms": ["prefill_s"],
    "decode_wait_ms": ["dispatch_s", "readback_s"],
    "sched_unaccounted_ms": ["step_s", "collect_s", "intake_s"],
}


@pytest.fixture(scope="module")
def bench():
    return Benchmark(REPO)


def _run(stats0=STATS0, stats1=STATS1):
    return types.SimpleNamespace(obs={"stats0": stats0, "stats1": stats1},
                                 window_s=3.4, e2e={}, trace=None)


@pytest.mark.parametrize("name", sorted(WANT))
def test_serving_reader_on_hand_made_counters(bench, name):
    reader = bench.layer_metric(name)
    assert reader.NAME == name and reader.UNIT == "ms"
    assert reader.read(_run()) == pytest.approx(WANT[name])
    # a program that lacks a counter (the parent commit): nothing, no error
    for key in USES[name]:
        short = {k: v for k, v in STATS1.items() if k != key}
        assert reader.read(_run(stats1=short)) is None
        assert reader.read(_run(stats0={k: v for k, v in STATS0.items()
                                        if k != key})) is None
    assert reader.read(_run(stats1=dict(STATS1, iterations=100))) is None
    assert reader.read(types.SimpleNamespace(obs={}, e2e={})) is None


def test_the_phase_metrics_add_up_to_the_pass(bench):
    """By construction: the six phase metrics, intake, capacity, collect
    and what no span sees are the whole of ``sched_iter_busy_ms``."""
    parts = sum(bench.layer_metric(n).read(_run()) for n in WANT
                if n not in ("sched_iter_busy_ms", "prefix_readback_ms"))
    rest = phases.per_iteration_ms(
        _run(), ("intake_s", "capacity_s", "collect_s"))
    assert parts + rest == pytest.approx(WANT["sched_iter_busy_ms"])


def test_every_new_entry_of_the_manifest_has_its_reader(bench):
    listed = {m["name"]: m for m in bench.manifest["per_layer"]}
    for name in list(WANT) + ["idle_in_retire_share", "train_host_ms_p50"]:
        reader = bench.layer_metric(name)
        entry = listed[name]
        assert (reader.UNIT, reader.LAYER, reader.MOVES) == \
            (entry["unit"], entry["layer"], entry["moves"])
        assert "workloads" not in entry


# ----------------------------------------------- overlap on one clock
@pytest.mark.parametrize("gaps,spans,want", [
    ([(0, 10)], [(0, 10)], 10),
    ([(0, 10)], [(5, 20)], 5),                   # straddles the gap's end
    ([(0, 10), (20, 30)], [(5, 25)], 10),        # one span, two gaps
    ([(0, 10)], [(2, 4), (3, 8)], 6),            # overlapping spans once
    ([(0, 10)], [(2, 4), (2, 4)], 2),            # both name forms of a call
    ([(0, 10)], [(10, 20)], 0),
    ([(0, 10)], [], 0),
    ([], [(0, 10)], 0),
])
def test_overlap_of_gaps_and_spans(gaps, spans, want):
    assert phases.overlap_ns(gaps, spans) == want


def _ev(plane, name, start_ms, dur_ms, line="XLA Ops"):
    return Event(plane, line, name, int(start_ms * MS), int(dur_ms * MS))


FRAME = "$batcher.py:1117 _retire"
SPAN = "mxtpu.sched.retire"


def _serving_trace(retire_names):
    """A 100 ms window. The device runs [10,20), [50,60) and [90,100):
    idle 70 ms in [0,10), [20,50), [60,90). Retire runs [15,45) and
    [55,70): the first covers 25 ms of the gap [20,50) and starts before
    it (a gap that straddles the phase's edge), the second 10 ms of
    [60,90): 35 of 70 ms."""
    events = [_ev("/host:CPU", "perf.window", 0, 100, line="python3"),
              _ev(DEV, "%while.4 = (s32[]) while(...)", 10, 10),
              _ev(DEV, "%fusion.7 = bf16[8]{0} fusion(...)", 50, 10),
              _ev(DEV, "%while.4 = (s32[]) while(...)", 90, 10),
              # another function's frame and another thread's span
              _ev("/host:CPU", "$batcher.py:1456 _admit", 45, 10,
                  line="python3"),
              _ev("/host:CPU", "mxtpu.sched.step", 14, 86, line="python3")]
    for name in retire_names:
        events += [_ev("/host:CPU", name, 15, 30, line="python3"),
                   _ev("/host:CPU", name, 55, 15, line="python3")]
    return TraceSummary(events, chips=1)


@pytest.mark.parametrize("names,want", [
    ([FRAME], 50.0),          # what today's harness keeps
    ([SPAN], 50.0),           # what it keeps once mxtpu.* passes _keep_host
    ([FRAME, SPAN], 50.0),    # both bracket the same call: counted once
    ([], None),               # a program with neither: nothing to read
])
def test_idle_in_retire_share_on_a_hand_made_trace(bench, names, want):
    reader = bench.layer_metric("idle_in_retire_share")
    run = types.SimpleNamespace(
        trace=_serving_trace(names), obs={},
        e2e={"serve_tokens_per_s": (1.0, "tokens/s")})
    got = reader.read(run)
    assert got == (pytest.approx(want) if want is not None else None)
    # not a serving cell, no trace, no device in the trace
    assert reader.read(types.SimpleNamespace(
        trace=run.trace, obs={}, e2e={})) is None
    assert reader.read(types.SimpleNamespace(
        trace=None, obs={}, e2e=run.e2e)) is None
    assert reader.read(types.SimpleNamespace(
        trace=TraceSummary([], chips=1), obs={}, e2e=run.e2e)) is None


def test_excerpt_of_a_real_serving_trace(bench):
    """The end of one scheduler pass and the whole of the next of
    ``transformer-big.translate-closed``, as a plain
    ``jax.profiler.start_trace`` recorded them on the chip (PR 25): the
    program's ``mxtpu.sched.*`` spans lie on the scheduler thread's line,
    on the clock of the device's ``XLA Ops``; each decode loop (``%while``)
    of the device lies inside exactly one ``sched.step``; the span of
    ``_retire`` holds the Python frame of the call it brackets; and the
    reader gives one answer by either."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "serve_phase_excerpt.json")
    with open(path) as f:
        doc = json.load(f)
    events = [Event(DEV, "XLA Ops", *e) for e in doc["device_ops"]] + \
        [Event("/host:CPU", "python3", *e) for e in doc["host"]]
    host = [(e.name, e.start_ns, e.start_ns + e.dur_ns) for e in events
            if e.plane != DEV]
    steps = [h for h in host if h[0] == "mxtpu.sched.step"]
    assert len(steps) == 2 and doc["iters"] == [42, 43]
    loops = [e for e in events if e.plane == DEV
             and e.name.startswith("%while")]
    assert len(loops) == 2
    for w in loops:
        assert sum(s <= w.start_ns and w.start_ns + w.dur_ns <= e
                   for _, s, e in steps) == 1
    spans = phases.intervals(host, SPAN, r"^never$")
    reader = bench.layer_metric("idle_in_retire_share")
    frames = phases.intervals(host, "never", reader.FRAME)
    assert len(spans) == len(frames) == 1
    (ss, se), (fs, fe) = spans[0], frames[0]
    assert ss <= fs and fe <= se and (se - ss) - (fe - fs) < 20_000
    # twelve requests retired in that pass, each with its read-back
    inside = [h for h in host if ss <= h[1] and h[2] <= se]
    assert sum(h[0] == "mxtpu.sched.register_prefix" for h in inside) == \
        sum(h[0] == "mxtpu.sched.register_prefix.readback"
            for h in inside) == 12

    e2e = {"serve_tokens_per_s": (1.0, "tokens/s")}

    def share(keep):
        t = TraceSummary([e for e in events if keep(e.name)], chips=1)
        return reader.read(types.SimpleNamespace(trace=t, obs={}, e2e=e2e))

    by_frame = share(lambda n: not n.startswith("mxtpu."))  # today's harness
    by_span = share(lambda n: not n.startswith("$"))
    assert 50.0 < by_frame < 100.0
    assert by_span == pytest.approx(by_frame, abs=0.01)
    assert share(lambda n: True) == pytest.approx(by_span, abs=1e-9)
    # the answer by a slow, obvious method: a timeline of microseconds
    t = TraceSummary(events, chips=1)
    busy = bytearray((t.hi - t.lo) // 1000 + 1)
    for e in events:
        if e.plane == DEV:
            a = (e.start_ns - t.lo) // 1000
            busy[a:(e.start_ns + e.dur_ns - t.lo) // 1000 + 1] = \
                b"\x01" * ((e.start_ns + e.dur_ns - t.lo) // 1000 + 1 - a)
    idle = [i for i, b in enumerate(busy) if not b]
    in_retire = sum((ss - t.lo) // 1000 <= i <= (se - t.lo) // 1000
                    for i in idle)
    assert by_span == pytest.approx(100.0 * in_retire / len(idle), abs=1.5)


# ------------------------------------------------- the training reader
def test_train_host_ms_p50_reads_the_windows_own_steps(bench):
    """The histogram is the process's: the warm-up's compile, the steps
    that decide ``correct`` and another ``TrainStep``'s calls lie before
    the window's, and the reader takes the window's alone."""
    from mxnet_tpu import telemetry

    reader = bench.layer_metric("train_host_ms_p50")

    def run(steps):
        return types.SimpleNamespace(
            obs={"dispatches": steps}, trace=None,
            e2e={"train_tokens_per_s": (1.0, "tokens/s")})

    telemetry.registry().clear(prefix="trainstep/")
    assert reader.read(run(3)) is None
    # a look that creates nothing
    assert not telemetry.registry().histograms_with_prefix("trainstep/")
    hist = telemetry.registry().histogram("trainstep/host_ms")
    try:
        for v in (9000.0, 40.0, 40.0, 40.0):  # a compile, then ``correct``
            hist.observe(v)
        for v in (1.0, 2.0, 9.0):             # the window
            hist.observe(v)
        assert hist.percentile(50) == pytest.approx(40.0)
        assert reader.read(run(3)) == pytest.approx(2.0)
        # more steps than the process made: not this histogram's window
        assert reader.read(run(8)) is None
        assert reader.read(run(0)) is None
        assert reader.read(types.SimpleNamespace(obs={}, e2e={})) is None
        assert reader.read(types.SimpleNamespace(
            obs={"dispatches": 3}, e2e={})) is None
        # a window longer than the rolling 1024: its last 1024 steps
        for i in range(hist.window + 10):
            hist.observe(5.0 if i >= 10 else 500.0)
        assert reader.read(run(hist.window + 10)) == pytest.approx(5.0)
    finally:
        telemetry.registry().clear(prefix="trainstep/")


# ------------------------------------------------------ whole rehearsals
def _rehearse_in_a_copy(tmp_path, cell):
    """A traced rehearsal from a copy of the benchmark's files, the
    program imported from the repo: the harness keeps a cell's trace in
    one directory of its root, which ``test_harness.py``'s traced
    rehearsal of the same cell, on another worker, would share."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "perf"), tmp_path / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)  # the suite's eight virtual devices
    return subprocess.run(
        [sys.executable, str(tmp_path / "perf" / "run.py"), "--workload",
         cell, "--seed", str(2**31 + 25), "--seconds", "2", "--trace", "1",
         "--rehearse"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=600)


@pytest.mark.parametrize("cell,reported,absent", [
    (SERVING, set(WANT), {"idle_in_retire_share", "train_host_ms_p50"}),
    (TRAINING, {"train_host_ms_p50"}, set(WANT) | {"idle_in_retire_share"}),
])
def test_a_traced_rehearsal_reports_the_new_metrics(tmp_path, cell, reported,
                                                    absent):
    proc = _rehearse_in_a_copy(tmp_path, cell)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    got = set(last["metrics_reported"])
    # the device's share needs a device's plane in the trace: not on a CPU
    assert reported <= got and not (absent & got)

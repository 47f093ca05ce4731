"""``telemetry.phase``: one span of a hot path with three sinks (the
profiler's timeline, an always-on accumulator, the program's own JSONL
stream), as the scheduler's pass, ``TrainStep.__call__`` and
``profiler.Scope`` use it.

One tiny zoo transformer behind a warmed ``ContinuousBatcher`` and one tiny
``TrainStep`` serve every test; one profiler session (with telemetry
enabled into a directory of its own) is recorded once and read by the
tests that need a timeline.
"""

import glob
import json
import os
import time

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, optimizer as opt, parallel
from mxnet_tpu import telemetry as tel
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import ContinuousBatcher

SCHED_PHASES = ("intake_s", "retire_s", "admit_s", "capacity_s",
                "dispatch_s", "readback_s", "collect_s")
NESTED = (("register_prefix_s", "retire_s"),
          ("register_readback_s", "register_prefix_s"),
          ("prefill_s", "admit_s"))
NEW_KEYS = SCHED_PHASES + ("step_s", "register_prefix_s",
                           "register_readback_s", "prefill_s")


@pytest.fixture(scope="module")
def batcher():
    np.random.seed(0)
    net = TransformerModel(src_vocab=61, tgt_vocab=61, units=16,
                           hidden_size=32, num_layers=2, num_heads=2,
                           max_length=64, dropout=0.0)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    bat = ContinuousBatcher(InferStep(net, max_len=24), bucket_keys=(8,),
                            slots=2, max_new_tokens=6, page_size=4,
                            iter_tokens=2, warmup=True)
    yield bat
    bat.stop()


@pytest.fixture(scope="module")
def train_step():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(1))
    net.initialize()
    net(mx.nd.zeros((16, 8)))
    return parallel.TrainStep(net, gluon.loss.L2Loss(),
                              opt.SGD(learning_rate=0.01))


def _serve(bat, n=5, seed=1):
    rng = np.random.RandomState(seed)
    futs = [bat.submit(rng.randint(3, 61, (rng.randint(2, 8),))
                       .astype(np.int32), request_id=f"req-{seed}-{i}")
            for i in range(n)]
    out = [f.result(timeout=120) for f in futs]
    # a future resolves inside the retire phase: let that pass end (its
    # phase seconds are published, its ``sched.step`` closed) before the
    # caller starts or stops a trace or compares ``stats``
    deadline = time.monotonic() + 30
    while not bat._drained() or bat._pass:
        assert time.monotonic() < deadline
        time.sleep(0.002)
    return out


def _train(step, n=2):
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype("float32")
    y = rng.randn(16, 1).astype("float32")
    for _ in range(n):
        loss = step(x, y)
    jax.block_until_ready(loss.data)


def _host_events(trace_dir):
    """``(thread line, name, start, end, stats)`` of the ``mxtpu.*`` events
    of a profiler trace, read with nothing but JAX."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        for n, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("mxtpu."):
                    out.append(((plane.name, n), e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def recorded(batcher, train_step, tmp_path_factory):
    """A few scheduler passes and two train steps under ONE profiler
    session, telemetry enabled: the xplane's events and the JSONL's."""
    _serve(batcher, n=2)
    _train(train_step)  # every shape compiled before the session
    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    tel_dir = str(tmp_path_factory.mktemp("tel"))
    tel.reset()
    tel.enable(tel_dir, watchdog=False)
    jax.profiler.start_trace(trace_dir)
    try:
        _serve(batcher, n=5)
        _train(train_step)
        with mx.profiler.Scope("user_scope"):
            pass
    finally:
        jax.profiler.stop_trace()
        path = tel.jsonl_path()
        tel.reset()
    with open(path) as f:
        jsonl = [json.loads(ln) for ln in f]
    return _host_events(trace_dir), jsonl


# ------------------------------------------------- sink 1: the profiler
@pytest.mark.parametrize("name", [
    "mxtpu.sched.step", "mxtpu.sched.intake", "mxtpu.sched.retire",
    "mxtpu.sched.register_prefix", "mxtpu.sched.register_prefix.store",
    "mxtpu.sched.register_prefix.readback",
    "mxtpu.sched.admit", "mxtpu.sched.admit.prefill",
    "mxtpu.sched.capacity", "mxtpu.sched.dispatch",
    "mxtpu.sched.collect.readback", "mxtpu.sched.collect",
    "mxtpu.train.stage", "mxtpu.train.dispatch", "mxtpu.user_scope"])
def test_span_is_on_the_profilers_timeline(recorded, name):
    xplane, _ = recorded
    assert any(e[1] == name for e in xplane), sorted({e[1] for e in xplane})


def test_sched_phases_nest_in_a_step_of_their_thread(recorded):
    xplane, _ = recorded
    steps = [e for e in xplane if e[1] == "mxtpu.sched.step"]
    assert len({e[0] for e in steps}) == 1  # the scheduler's thread alone
    phases = [e for e in xplane if e[1].startswith("mxtpu.sched.")
              and e[1] != "mxtpu.sched.step"]
    assert phases
    for line, name, s, e, _ in phases:
        assert any(st[0] == line and st[2] <= s and e <= st[3]
                   for st in steps), name
    iters = [st[4]["iter"] for st in sorted(steps, key=lambda x: x[2])]
    assert iters == sorted(iters) and iters[-1] > iters[0]
    # one decode dispatch to a pass, so passes that dispatched count on
    dispatched = {st[4]["iter"] for st in steps if any(
        p[1] == "mxtpu.sched.dispatch" and st[2] <= p[2] and p[3] <= st[3]
        for p in phases)}
    assert len(dispatched) == sum(p[1] == "mxtpu.sched.dispatch"
                                  for p in phases)


def test_register_prefix_carries_the_requests_identifier(recorded):
    xplane, jsonl = recorded
    spans = [e for e in xplane if e[1] == "mxtpu.sched.register_prefix"]
    ids = [e[4]["request_id"] for e in sorted(spans, key=lambda x: x[2])]
    assert sorted(ids) == [f"req-1-{i}" for i in range(5)]
    assert [e["args"]["request_id"] for e in jsonl
            if e["name"] == "mxtpu.sched.register_prefix"] == ids


# ---------------------------------------- sink 3: the program's own trace
def test_jsonl_holds_the_same_spans_as_the_xplane(recorded):
    xplane, jsonl = recorded
    mine = [e for e in jsonl if e["name"].startswith("mxtpu.")]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in mine)

    def count(names):
        return {n: names.count(n) for n in set(names)}

    assert count([e["name"] for e in mine]) == count(
        [e[1] for e in xplane])
    # a pass that dispatches nothing repeats the next pass's number (a
    # starved submitter leaves such passes), so the two sinks' steps are
    # paired by the order they started in, not by ``iter``
    theirs = sorted((e for e in xplane if e[1] == "mxtpu.sched.step"),
                    key=lambda e: e[2])
    steps = sorted((e for e in mine if e["name"] == "mxtpu.sched.step"),
                   key=lambda e: e["ts"])
    assert [e["args"]["iter"] for e in steps] == \
        [e[4]["iter"] for e in theirs]
    # one clock for a span's two ends in each sink: durations agree
    for e, x in zip(steps, theirs):
        assert e["dur"] * 1e3 == pytest.approx(x[3] - x[2], rel=0.2,
                                               abs=2e5)


# ------------------------------ sink 2: always on, and nothing else is
def test_off_means_no_event_and_counters_still_advance(batcher, train_step):
    tel.reset()
    assert not tel.enabled() and tel._LOG is None
    before = dict(batcher.stats)
    _serve(batcher, n=3)
    _train(train_step)
    assert tel._LOG is None and tel.jsonl_path() is None  # no event made
    after = dict(batcher.stats)
    assert after["step_s"] > before["step_s"]
    assert after["iterations"] > before["iterations"]
    host = tel.registry().histograms_with_prefix("trainstep/host_ms")
    assert host["trainstep/host_ms"].count == 2
    assert 0 < host["trainstep/host_ms"].percentile(50) < 10_000


def test_an_idle_batcher_with_telemetry_on_writes_no_event(batcher,
                                                           tmp_path):
    """The idle loop polls its queue every 50 ms for as long as the
    process lives: a span there would grow the stream without end."""
    tel.reset()
    tel.enable(str(tmp_path), watchdog=False)
    try:
        _serve(batcher, n=1)
        path = tel.jsonl_path()
        with open(path) as f:
            before = f.read()
        assert "mxtpu.sched.step" in before
        stats = dict(batcher.stats)
        time.sleep(0.4)  # eight timeouts of the idle loop's queue read
        assert batcher.healthy
        with open(path) as f:
            assert f.read() == before
        assert dict(batcher.stats) == stats
    finally:
        tel.reset()


def test_phase_alone_adds_to_a_dict_slot_or_a_list_cell():
    slot, cell = {"x_s": 1.0}, [0.0]
    with tel.phase("unit.a", slot, "x_s"), tel.phase("unit.b", cell, 0), \
            tel.phase("unit.c"):
        pass
    assert slot["x_s"] > 1.0 and cell[0] > 0.0
    with pytest.raises(KeyError), tel.phase("unit.d", cell, 0):
        raise KeyError("the span closes and the error goes on")
    assert cell[0] > 0.0


def test_counters_are_monotone_and_phases_sum_inside_the_pass(batcher):
    snaps = [dict(batcher.stats)]
    for seed in (3, 4, 5):
        _serve(batcher, n=4, seed=seed)
        snaps.append(dict(batcher.stats))
    for a, b in zip(snaps, snaps[1:]):
        for k in NEW_KEYS:
            assert b[k] >= a[k] >= 0, k
    first, last = snaps[0], snaps[-1]
    d = {k: last[k] - first[k] for k in last}
    assert d["iterations"] > 0 and d["step_s"] > 0
    assert sum(d[k] for k in SCHED_PHASES) <= d["step_s"]
    for child, parent in NESTED:
        assert 0 < d[child] <= d[parent], (child, parent)
    assert d["retired"] == 12  # each with its ``sched.register_prefix``


def test_scope_still_feeds_the_op_histograms():
    tel.reset()
    for _ in range(2):
        with mx.profiler.Scope("scoped_op"):
            pass
    with mx.profiler.Task(name="a_task"):
        pass
    hists = tel.registry().histograms_with_prefix("op/")
    assert hists["op/scoped_op"].count == 2
    assert hists["op/scoped_op"].sum > 0
    assert hists["op/a_task"].count == 1
    assert "scoped_op" in mx.profiler.dumps(reset=True)

"""A request's own timeline (``GenerationResult``: instants on
``time.perf_counter``, each stamped where the thing happens) and what the
scheduler computes from it: ``phases``, the window histograms in
``ContinuousBatcher.stats`` (``telemetry.metrics.BucketBlock``), the
``trace.*`` spans made at retire, the ``infer/`` histograms, and the
request's identifier on the spans of its prefill.

Two tiny programs serve every test: the zoo's transformer (a request takes
its slot where its admission prefill is dispatched: the cold path, and the
suffix replay of a forced prefix) and the hybrid state-space model (a prompt
enters its pages in chunks, one chunk a pass: the chunk seat).
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
from mxnet_tpu import telemetry as tel
from mxnet_tpu.gluon.model_zoo.granite_hybrid import GraniteHybridLM
from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import ContinuousBatcher, make_batcher, tracing
from mxnet_tpu.serving.batcher import HIST_KEYS, PHASE_DETAIL, \
    TTFT_PARTS, _one_request
from mxnet_tpu.telemetry import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perf.harness.clock import percentile  # noqa: E402

CHUNK = 8
PARTS = tuple(f"{p}_ms" for p in TTFT_PARTS)


# ------------------------------------------------------------ the programs
@pytest.fixture(scope="module")
def encdec():
    """Two slots, a forced prefix of up to 4 tokens, the prefix trie on."""
    np.random.seed(0)
    net = TransformerModel(src_vocab=61, tgt_vocab=61, units=16,
                           hidden_size=32, num_layers=2, num_heads=2,
                           max_length=64, dropout=0.0)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    bat = ContinuousBatcher(InferStep(net, max_len=24), bucket_keys=(8,),
                            slots=2, max_new_tokens=6, page_size=4,
                            iter_tokens=2, max_prefix_tokens=4,
                            prefix_cache=True, warmup=True, name="encdec")
    yield _gated(bat)
    bat.stop()


@pytest.fixture(scope="module")
def hybrid_net():
    np.random.seed(1)
    net = GraniteHybridLM(
        vocab_size=128, hidden_size=32,
        layer_types=("mamba", "attention", "mamba"), num_heads=4,
        num_kv_heads=2, intermediate_size=48, mamba_heads=8,
        mamba_head_dim=8, mamba_state=16, mamba_groups=1, mamba_conv=4,
        mamba_expand=2, mamba_chunk=8, attention_multiplier=0.125,
        embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
        rms_eps=1e-5, state_dtype="float32", dtype="float32")
    net.initialize(mx.initializer.Xavier())
    return net


@pytest.fixture(scope="module")
def chunked(hybrid_net):
    """Three slots, prompts of up to four chunks of 8, an end token no
    vocabulary holds (a reply runs to its ``max_new_tokens``)."""
    bat = make_batcher(InferStep(hybrid_net, eos_id=-1), [8, 32], slots=3,
                       max_new_tokens=6, page_size=4, prefill_chunk=CHUNK,
                       iter_tokens=2, prefix_cache=False, warmup=True,
                       name="chunked")
    yield _gated(bat)
    bat.stop()


def _gated(bat):
    """``bat.gate``: while it is clear the scheduler holds a retire pass
    until the caller of every finished request has taken its first chunk,
    so that a test decides whether a caller reads before or after its
    request is retired."""
    bat.gate = threading.Event()
    bat.gate.set()
    retire = bat._retire

    def gated():
        deadline = time.monotonic() + 60
        while not bat.gate.is_set() and any(
                s is not None and s.finished
                and s.req.future.first_read_at is None for s in bat._slots):
            assert time.monotonic() < deadline
            time.sleep(0.001)
        retire()

    bat._retire = gated
    return bat


def _prompt(n, seed, vocab=61):
    return np.random.default_rng(seed).integers(3, vocab, n).astype(np.int32)


def _settle(bat):
    """A future resolves inside the retire phase: let that pass end and
    publish before ``stats`` is compared."""
    deadline = time.monotonic() + 60
    while not bat._drained() or bat._pass:
        assert time.monotonic() < deadline
        time.sleep(0.002)


def _serve_streaming(bat, prompts, each=None):
    """Every caller takes its first chunk BEFORE its request is retired
    (the gate holds the retire), then drains its stream."""
    bat.gate.clear()
    try:
        futs = [bat.submit(p, request_id=f"r{i}", **(each[i] if each else {}))
                for i, p in enumerate(prompts)]
        its = [f.tokens_iter(timeout=120) for f in futs]
        heads = [next(it) for it in its]
    finally:
        bat.gate.set()
    for f, it, head in zip(futs, its, heads):
        assert head + [t for c in it for t in c] == f.result(timeout=0)
    _settle(bat)
    return futs


def _check_timeline(fut):
    ph = fut.phases
    since = fut.enqueued_at if fut.requeued_at is None else fut.requeued_at
    assert since <= fut.admitted_at <= fut.first_chunk_at <= fut.active_at \
        <= fut.first_read_at
    assert fut.active_at <= fut.finished_at
    for k in PARTS + ("prefill_ms", "decode_ms"):
        assert ph[k] >= 0, (k, ph)
    assert sum(ph[k] for k in PARTS) == pytest.approx(
        (fut.first_read_at - since) * 1e3, abs=1e-3)   # to a microsecond
    assert ph["prefill_ms"] == pytest.approx(
        ph["seat_ms"] + ph["service_ms"], abs=1e-3)
    assert ph["decode_ms"] == pytest.approx(
        (fut.finished_at - fut.active_at) * 1e3, abs=1e-3)
    assert fut.queue_wait_ms == pytest.approx(
        (fut.admitted_at - fut.enqueued_at) * 1e3, abs=1e-3)
    assert set(PHASE_DETAIL) <= set(ph)


# ------------------------------------------------ the parts of a request
@pytest.mark.parametrize("path", ["cold", "suffix", "chunked"])
@pytest.mark.parametrize("caller", ["tokens_iter", "result"])
def test_the_four_parts_add_up_to_what_the_caller_waited(
        request, path, caller):
    bat = request.getfixturevalue("chunked" if path == "chunked"
                                  else "encdec")
    if path == "chunked":
        prompts = [_prompt(n, 10 + n, 128) for n in (21, 5, 30)]
        each = [{}] * 3
    else:
        prompts = [_prompt(n, 20 + n) for n in (5, 7)]
        each = [{"prefix_ids": [7, 9, 11]}] * 2 if path == "suffix" \
            else [{}] * 2
    if caller == "tokens_iter":
        futs = _serve_streaming(bat, prompts, each=each)
    else:
        futs = [bat.submit(p, **kw) for p, kw in zip(prompts, each)]
        for f in futs:
            f.result(timeout=120)
        _settle(bat)
    for f in futs:
        _check_timeline(f)
        assert f.first_token_at == f.active_at   # one instant, read once
        if caller == "result":
            # read after its retirement: the caller's thread completed it
            assert f.first_read_at >= f.finished_at
            assert f.phases["deliver_ms"] >= f.phases["decode_ms"]
        else:
            assert f.first_read_at <= f.finished_at
        if path != "chunked":
            # the slot is taken where the one prefill is dispatched
            assert f.phases["seat_ms"] == 0.0
            assert f.admitted_at == f.first_chunk_at
    if path == "chunked":
        # the seat takes the oldest prompt first, one chunk a pass: a
        # prompt waits for every chunk of the prompts seated ahead of it
        seat = [f.phases["seat_ms"] for f in futs]
        service = [f.phases["service_ms"] for f in futs]
        assert seat[0] < seat[1] < seat[2]
        for ahead, f in zip(futs, futs[1:]):
            assert f.first_chunk_at >= ahead.active_at
            assert f.admitted_at < ahead.first_chunk_at   # one pass took all
        # three chunks and the bursts between them against one chunk
        assert service[0] > service[1]


def test_a_prefix_hit_is_served_by_the_suffix_replay_with_no_seat(encdec):
    prompt = _prompt(6, 77)
    first = encdec.submit(prompt, max_new_tokens=4)
    history = [int(t) for t in first.result(timeout=120)][:3]
    _settle(encdec)
    hits = encdec.stats["prefix_hits"]
    again = _serve_streaming(encdec, [prompt],
                             each=[{"prefix_ids": history}])[0]
    assert encdec.stats["prefix_hits"] == hits + 1
    _check_timeline(again)
    assert again.phases["seat_ms"] == 0.0


def test_a_preempted_request_reports_its_last_admission(hybrid_net):
    """A pool too small for both replies: the younger request is preempted
    and recomputed; its parts are its last admission's, counted from the
    preemption, and the time before it is ``preempt_ms``."""
    bat = make_batcher(InferStep(hybrid_net, eos_id=-1), [16], slots=2,
                       max_new_tokens=12, page_size=4, prefill_chunk=CHUNK,
                       iter_tokens=2, prefix_cache=False, num_pages=9,
                       admit_free_pages=0, warmup=True, name="small-pool")
    try:
        futs = [bat.submit(_prompt(8, s, 128), max_new_tokens=12)
                for s in (91, 92)]
        chunks = [[c for c in f.tokens_iter(timeout=300)] for f in futs]
        _settle(bat)
    finally:
        bat.stop()
    assert bat.stats["preempted"] >= 1
    again = [f for f in futs if f.requeued_at is not None]
    assert again
    for f, got in zip(futs, chunks):
        assert len(f.result(timeout=0)) == 12
        _check_timeline(f)
        ph = f.phases
        if f in again:
            assert f.enqueued_at < f.requeued_at <= f.admitted_at
            assert ph["preempt_ms"] == pytest.approx(
                (f.requeued_at - f.enqueued_at) * 1e3, abs=1e-3)
            assert sum(ph[k] for k in PARTS) + ph["preempt_ms"] == \
                pytest.approx((f.first_read_at - f.enqueued_at) * 1e3,
                              abs=1e-3)
            # the first stream's first token stays the TTFT instant
            assert f.first_token_at < f.requeued_at < f.active_at
        else:
            assert "preempt_ms" not in ph
    # both admissions of the preempted request were observed
    assert int(bat.stats["h_queue_ms"][:-1].sum()) == 2 + bat.stats[
        "preempted"]


def test_a_read_that_races_the_retire_leaves_every_request_whole(encdec):
    """``deliver_ms`` is written by whichever of the two comes second, the
    caller's first read or the scheduler's retire, under the future's one
    condition: with more callers than cores, replies of one or two tokens
    (retired a pass after their first token) and a shortened switch
    interval, no request ends without it and every one adds up."""
    before = dict(encdec.stats)
    done, errors = [], []

    def caller(c):
        try:
            for i in range(6):
                fut = encdec.submit(_prompt(4 + (c + i) % 4, 100 * c + i),
                                    max_new_tokens=1 + (c + i) % 2)
                if c % 2:
                    for _ in fut.tokens_iter(timeout=120):
                        pass
                fut.result(timeout=120)
                done.append(fut)
        except Exception as e:  # noqa: BLE001 - reported by the assert below
            errors.append(repr(e))

    threads = [threading.Thread(target=caller, args=(c,))
               for c in range(2 * (os.cpu_count() or 4))]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(done) == 6 * len(threads)
    _settle(encdec)
    for fut in done:
        _check_timeline(fut)
    after = dict(encdec.stats)
    seen = _count(after["h_deliver_ms"] - before["h_deliver_ms"])
    assert seen == _count(after["h_ttft_ms"] - before["h_ttft_ms"])
    assert 0 <= seen <= len(done) == _count(
        after["h_queue_ms"] - before["h_queue_ms"])


# ------------------------------------------------- the window histograms
def _count(h):
    return int(np.asarray(h)[:-1].sum())


@pytest.mark.parametrize("which", ["encdec", "chunked"])
def test_the_difference_of_two_snapshots_is_the_window_between_them(
        request, which):
    bat = request.getfixturevalue(which)
    vocab = 61 if which == "encdec" else 128
    _serve_streaming(bat, [_prompt(5, 1, vocab)])     # something before
    a = dict(bat.stats)
    kept = {k: np.array(v, copy=True) for k, v in a.items()}
    futs = []
    for round_ in range(3):                # two a round: both slots' worth
        futs += _serve_streaming(bat, [_prompt(4 + round_, 50 + round_, vocab),
                                       _prompt(7, 60 + round_, vocab)])
    late = bat.submit(_prompt(6, 70, vocab))        # read after its retire
    late.result(timeout=120)
    _settle(bat)
    b = dict(bat.stats)
    d = {k: b[k] - a[k] for k in b}
    assert d["retired"] == 7 and d["admitted"] == 7
    for k in ("h_queue_ms", "h_seat_ms", "h_service_ms"):
        assert _count(d[k]) == 7, k
    # a request whose caller had not read when it was retired is skipped
    assert _count(d["h_deliver_ms"]) == _count(d["h_ttft_ms"]) == 6
    assert _count(d["h_burst_ms"]) == d["iterations"] > 0
    assert _count(d["h_pass_ms"]) >= d["iterations"]
    assert _count(d["h_chunk_ms"]) == d["prompt_chunks"] == (
        0 if which == "encdec" else 7)
    # the cells hold what the requests report, and the last cell their sum
    for part in TTFT_PARTS:
        seen = [f.phases[f"{part}_ms"] for f in futs]
        if part != "deliver":
            seen.append(late.phases[f"{part}_ms"])
        h = d[f"h_{part}_ms"]
        assert int(h[-1]) == pytest.approx(sum(seen) * 1e6, abs=len(seen))
        cells = np.zeros_like(h[:-1])
        for ms in seen:
            cells[metrics.bucket_of(ms)] += 1
        assert (h[:-1] == cells).all(), part
        assert metrics.bucket_percentile(h, 95) == pytest.approx(
            percentile(seen, 95), rel=0.015, abs=metrics._LO)
    ttft = [sum(f.phases[k] for k in PARTS) for f in futs]
    assert metrics.bucket_percentile(d["h_ttft_ms"], 95) == pytest.approx(
        percentile(ttft, 95), rel=0.015)
    # the sums are the phases' own seconds, cut to whole nanoseconds a pass
    for key, seconds in (("h_pass_ms", d["step_s"]),
                         ("h_burst_ms", d["dispatch_s"] + d["readback_s"]),
                         ("h_chunk_ms", d["prefill_chunk_s"])):
        assert int(d[key][-1]) == pytest.approx(
            seconds * 1e9, abs=_count(d[key]) + 1), key
    # a pass publishes new arrays: the earlier snapshot is as it was
    for k, v in kept.items():
        assert np.array_equal(a[k], v), k
    for k in HIST_KEYS:
        assert b[k] is not a[k] or _count(d[k]) == 0
        assert b[k].dtype == np.int64 and (d[k] >= 0).all()


def _hist(values):
    block = metrics.BucketBlock(["k"])
    for ms in values:
        block.observe(("k", ms))
    return block.rows()["k"] if not values else block.flush()["k"]


@pytest.mark.parametrize("sigma", [0.02, 0.3, 1.2])
@pytest.mark.parametrize("n", [2, 57, 4000])
def test_bucket_percentile_is_within_its_bound_of_the_exact_one(sigma, n):
    rng = np.random.default_rng([n, int(sigma * 100)])
    for median in (2.0, 45.0, 600.0):
        sample = (median * np.exp(sigma * rng.standard_normal(n))).tolist()
        h = _hist(sample)
        assert h.shape == (metrics.BUCKET_CELLS,) and h.dtype == np.int64
        for p in (0, 50, 95, 99, 100):
            assert metrics.bucket_percentile(h, p) == pytest.approx(
                percentile(sample, p), rel=0.015), (median, p)
        # a window: what was observed after an earlier snapshot
        assert metrics.bucket_percentile(
            h - _hist(sample[:n // 2]), 95) == pytest.approx(
                percentile(sample[n // 2:], 95), rel=0.015)


def test_bucket_percentile_at_the_edges():
    assert metrics.bucket_percentile(_hist([]), 95) is None
    assert metrics.bucket_percentile(_hist([12.345678]), 95) \
        == pytest.approx(12.345678, abs=1e-6)          # the observation
    assert metrics.bucket_percentile(_hist([0.0] * 20), 95) == 0.0
    assert metrics.bucket_of(0.0) == 0
    assert metrics.bucket_of(metrics._LO) == 1
    assert metrics.bucket_of(metrics._HI * 0.9999) == metrics.BUCKET_CELLS - 3
    assert metrics.bucket_of(1e12) == metrics.BUCKET_CELLS - 2
    assert metrics.bucket_percentile(_hist([1e9] * 3), 95) \
        == metrics._HI                                  # "at least"
    # a cell's upper edge lies under 1.5 % above its lower one
    assert metrics._edge(2) / metrics._edge(1) < 1.015


def test_a_flush_makes_a_new_block_and_leaves_the_rows_handed_out():
    block = metrics.BucketBlock(["a", "b"])
    assert block.flush() is None                        # nothing observed
    block.observe(("a", 3.0))
    first = block.flush()
    kept = first["a"].copy()
    block.observe(("a", 5.0))
    block.observe(("b", 0.5))
    block.observe(("b", 0.25))
    second = block.flush()
    assert (first["a"] == kept).all() and second["a"] is not first["a"]
    assert int(second["a"][:-1].sum()) == 2 and int(second["a"][-1]) == 8e6
    assert int(second["b"][:-1].sum()) == 2 and int(second["b"][-1]) == 75e4
    assert int((second["a"] - first["a"])[metrics.bucket_of(5.0)]) == 1
    assert block.flush() is None and block.rows()["a"] is not None


# ------------------------------------------------------ spans and sinks
def test_telemetry_off_makes_no_event_and_the_histograms_advance(chunked):
    tel.reset()
    assert not tel.enabled() and tel._LOG is None
    before = dict(chunked.stats)
    _serve_streaming(chunked, [_prompt(19, 5, 128), _prompt(3, 6, 128)])
    assert tel._LOG is None and tel.jsonl_path() is None
    after = dict(chunked.stats)
    for k in HIST_KEYS:
        assert _count(after[k] - before[k]) >= 2, k
    assert _count(after["h_chunk_ms"] - before["h_chunk_ms"]) == 4


@pytest.fixture
def recorded(tmp_path):
    """Telemetry enabled into a directory of its own with request tracing
    forced on; yields a function that reads the events written so far."""
    tel.reset()
    tel.enable(str(tmp_path), watchdog=False)
    tracing.force(True)

    def events():
        with open(tel.jsonl_path()) as f:
            return [json.loads(ln) for ln in f]

    try:
        yield events
    finally:
        tracing.force(None)
        tel.reset()


def test_a_requests_chunks_carry_its_identifier(chunked, recorded):
    futs = _serve_streaming(chunked, [_prompt(21, 31, 128),
                                      _prompt(5, 32, 128)])
    chunks = [e["args"] for e in recorded()
              if e["name"] == "mxtpu.sched.admit.prefill_chunk"]
    assert [(a["request_id"], a["chunk"]) for a in chunks] == [
        ("r0", 0), ("r0", 1), ("r0", 2), ("r1", 0)]
    assert {f.request_id for f in futs} == {"r0", "r1"}


def test_a_prefill_of_one_request_carries_its_identifier(encdec, recorded):
    fut = _serve_streaming(encdec, [_prompt(5, 41)])[0]
    args = [e["args"] for e in recorded()
            if e["name"] == "mxtpu.sched.admit.prefill"]
    assert args == [{"request_id": "r0"}]
    # a dispatch of several requests names none
    row = (0, type("R", (), {"future": fut}))
    assert _one_request([row]) == {"request_id": "r0"}
    assert _one_request([row, row]) is None


@pytest.mark.parametrize("which", ["encdec", "chunked"])
def test_request_spans_are_made_from_the_timeline_at_retire(
        request, recorded, which):
    bat = request.getfixturevalue(which)
    vocab = 61 if which == "encdec" else 128
    futs = _serve_streaming(bat, [_prompt(7, 51, vocab),
                                  _prompt(6, 52, vocab)])
    spans = [e for e in recorded() if e["name"].startswith("trace.")]
    for f in futs:
        mine = sorted((e for e in spans
                       if e["args"]["request_id"] == f.request_id),
                      key=lambda e: e["ts"])
        names = [e["name"] for e in mine]
        assert names == (["trace.queue", "trace.seat", "trace.prefill",
                          "trace.decode"] if which == "chunked" else
                         ["trace.queue", "trace.prefill", "trace.decode"])
        # they follow one another from the enqueue to the retirement
        assert mine[0]["ts"] == pytest.approx(tel.us_of(f.enqueued_at))
        for e, nxt in zip(mine, mine[1:]):
            assert e["ts"] + e["dur"] == pytest.approx(nxt["ts"], abs=1e-3)
        assert mine[-1]["ts"] + mine[-1]["dur"] == pytest.approx(
            tel.us_of(f.finished_at), abs=1e-3)
        by = {e["name"]: e["dur"] / 1e3 for e in mine}
        assert by["trace.queue"] == pytest.approx(f.phases["queue_ms"],
                                                  abs=1e-3)
        assert by["trace.prefill"] == pytest.approx(
            f.phases["service_ms"], abs=1e-3)
        assert by["trace.decode"] == pytest.approx(f.phases["decode_ms"],
                                                   abs=1e-3)
        assert mine[-1]["args"]["tokens"] == len(f.result(timeout=0))
        assert all(e["args"]["replica"] == which for e in mine)


def test_the_registry_takes_its_values_from_the_timeline(encdec):
    tel.reset()                      # a fresh registry; telemetry stays off
    fut = _serve_streaming(encdec, [_prompt(6, 61)])[0]
    hists = tel.registry().histograms_with_prefix("infer/")
    assert hists["infer/queue_wait_ms"].last(1) == [fut.queue_wait_ms]
    assert fut.queue_wait_ms == pytest.approx(fut.phases["queue_ms"])
    assert hists["infer/ttft_ms"].last(1) == [
        (fut.first_token_at - fut.enqueued_at) * 1e3]
    assert hists["infer/prefill_ms"].count == 1
    # the dispatch's own seconds: the phase's, which the stats hold too
    assert hists["infer/prefill_ms"].last(1)[0] <= fut.phases["service_ms"]
    assert encdec.rolling_wait_ms(min_samples=1) is not None

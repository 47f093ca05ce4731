"""One decode burst queued ahead while every slot decodes
(``ContinuousBatcher._may_run_ahead`` / ``_dispatch_ahead``,
``InferStep.next_carry``), on the CPU at tiny sizes: the served tokens are
the engine's own greedy tokens with and without a burst ahead; the rule's
four refusals; an end token inside the burst in flight; and every way out
of a pass with a burst in flight. Over a net whose step yields one token
(ZAYA1's tiny preset: pools and slot arrays in every layer; the
encoder-decoder transformer where encoder memory and the prefix trie
matter) and the net whose step yields up to two (JoyAI's tiny preset, with
its own drafts and with a draft that always agrees)."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon.model_zoo.joyai import JoyAILM
from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
from mxnet_tpu.gluon.model_zoo.zaya import ZayaLM
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import faults, make_batcher
from mxnet_tpu.serving.batcher import GenerationResult, _Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perf.harness.loader import load_module  # noqa: E402

PAGE, CHUNK, SEED, NO_END, STEPS = 4, 8, 11, -1, 2
HORIZON = 24        # tokens of a greedy stream reckoned at once
ZAYA = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 4, "num_experts_per_tok": 1,
    "moe_intermediate_size": 64, "router_hidden_size": 32,
    "cca_time0": 2, "cca_time1": 2, "rms_norm_eps": 1e-5,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 5000000}},
    "precision": {"weights": "float32"}}
JOYAI = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "first_k_dense_replace": 1, "router_width": 8,
    "experts_held": [0, 8], "n_shared_experts": 1, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "routed_scaling_factor": 2.5,
    "rope_theta": 32e6, "rms_norm_eps": 1e-6,
    "precision": {"weights": "float32"}}


class _Oracle(JoyAILM):
    """A net whose draft is the model's own next token: every step yields
    two (``tests/test_joyai_lm.py``)."""

    def _propose(self, tokens, pos, state, pools, page_tables, active):
        _, h_prev, pool, part = super()._propose(
            tokens, pos, state, pools, page_tables, active)
        x, _, _ = self._model_step(tokens[:, None], pos, list(pools),
                                   active[:, None], page_tables)
        own = jnp.argmax(self._logits(x[:, 0]), -1).astype(jnp.int32)
        return own, h_prev, pool, part


class Rig:
    """A tiny net, the engine over it, a batcher NOT started (the test
    runs the passes) and the engine's own greedy generation."""

    step_tokens = 1
    chunked = True              # the prompt enters its pages in chunks

    def __init__(self, cfg, cls, reference, driver):
        self.cfg = cfg
        self.ref = load_module(os.path.join(REPO, "perf", "reference",
                                            reference))
        kwargs = load_module(os.path.join(REPO, "perf", "drivers",
                                          driver))._model_kwargs(cfg)
        self.net = cls(**kwargs)
        for name, p in self.net._collect_params_with_prefix().items():
            p.set_data(nd.NDArray(np.asarray(
                self.ref.tensor(SEED, cfg, name))))
        self._streams = {}

    def engine(self, eos=NO_END):
        return InferStep(self.net, eos_id=eos)

    def batcher(self, eng, slots, max_new, **kw):
        args = dict(slots=slots, max_new_tokens=max_new, page_size=PAGE,
                    prefill_chunk=CHUNK, iter_tokens=STEPS,
                    prefix_cache=False, warmup=True, start=False)
        args.update(kw)
        return make_batcher(eng, args.pop("buckets", [8, 32]), **args)

    def prompt(self, i, n=None):
        n = 3 + (5 * i) % 6 if n is None else n
        return np.random.default_rng(100 + i).integers(
            3, 128, n).astype(np.int32)

    def greedy(self, prompt, n, eos=NO_END):
        """The first ``n`` greedy tokens of the engine's own generation,
        cut after the first ``eos``: one row alone through the engine's
        paged programs by hand, a chunk at a time and then a step at a
        time, no scheduler."""
        key = tuple(int(t) for t in prompt)
        have = self._streams.get(key, [])
        if len(have) < n:
            have = self._streams[key] = self._alone(prompt, HORIZON)
        assert n <= HORIZON
        return _cut(have[:n], eos)

    def _alone(self, prompt, n):
        eng = self._hand = getattr(self, "_hand", None) or self.engine()
        pages = -(-(32 + HORIZON + 2) // PAGE)
        state = eng.init_paged_state(1, pages, PAGE, 0)
        table = 1 + np.arange(pages, dtype=np.int32)[None]
        for at in range(0, len(prompt), CHUNK):
            part = prompt[at:at + CHUNK]
            toks = np.zeros((1, CHUNK), np.int32)
            toks[0, :len(part)] = part
            out, state = eng.prefill_suffix_paged(
                state, toks, [len(part)], [at], table, [0], [True],
                wide=True)
        served, length = [int(out.asnumpy()[0])], len(prompt)
        while len(served) < n:
            buf, state = eng.decode_iter(state, table, [served[-1]],
                                         [length], [True], steps=1)
            row = buf.asnumpy()[0]
            if self.step_tokens == 1:
                served.append(int(row[0]))
                length += 1
            else:
                served += [int(t) for t in row[:int(row[2])]]
                length += int(row[2])
        return served[:n]


class TransformerRig(Rig):
    chunked = False

    def __init__(self):
        np.random.seed(0)
        net = TransformerModel(src_vocab=61, tgt_vocab=61, units=16,
                               hidden_size=32, num_layers=2, num_heads=2,
                               max_length=64, dropout=0.0)
        net.initialize(mx.initializer.Xavier())
        net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                          nd.zeros((2, 8), dtype="int32"))
        self.net = net
        self._streams = {}

    def engine(self, eos=NO_END):
        return InferStep(self.net, max_len=40, eos_id=eos)

    def batcher(self, eng, slots, max_new, **kw):
        args = dict(slots=slots, max_new_tokens=max_new, page_size=PAGE,
                    iter_tokens=STEPS, prefix_cache=True, warmup=True,
                    start=False)
        args.update(kw)
        return make_batcher(eng, (8,), **args)

    def prompt(self, i, n=None):
        n = 3 + (5 * i) % 6 if n is None else n
        return np.random.default_rng(100 + i).integers(
            3, 61, n).astype(np.int32)

    def greedy(self, prompt, n, eos=NO_END):
        key = tuple(int(t) for t in prompt)
        have = self._streams.get(key, [])
        if len(have) < n:
            src = np.zeros((1, 8), np.int32)
            src[0, :len(prompt)] = prompt
            toks, _ = self.engine().decode_n(
                src, np.array([len(prompt)], np.int32),
                max_new_tokens=HORIZON)
            have = self._streams[key] = [int(t) for t in toks.asnumpy()[0]]
        return _cut(have[:n], eos)


class TwoTokenRig(Rig):
    step_tokens = 2


def _cut(stream, eos):
    return stream[:stream.index(eos) + 1] if eos in stream else stream


@pytest.fixture(scope="module")
def rigs():
    made = {}

    def get(kind):
        if kind not in made:
            made[kind] = {
                "zaya": lambda: Rig(ZAYA, ZayaLM, "zaya1-8b.py",
                                    "serve-cca-lm.py"),
                "transformer": TransformerRig,
                "joyai": lambda: TwoTokenRig(JOYAI, JoyAILM,
                                             "joyai-llm-flash.py",
                                             "serve-mla-lm.py"),
                "joyai-oracle": lambda: TwoTokenRig(JOYAI, _Oracle,
                                                    "joyai-llm-flash.py",
                                                    "serve-mla-lm.py"),
            }[kind]()
        return made[kind]

    return get


@pytest.fixture(autouse=True)
def highest_precision():
    """The program's products in float32 proper, as the references have
    them."""
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)
    faults.clear()


ONE_AND_TWO = ["zaya", "joyai"]             # a one-token net, the two-token
EVERY = ["zaya", "transformer", "joyai", "joyai-oracle"]


def _queue(bat, prompt, max_new, deadline=None):
    fut = GenerationResult()
    bat._queue.put(_Request(np.asarray(prompt, np.int32), max_new, fut,
                            deadline))
    return fut


def _audit(bat):
    """The pool holds exactly what the live rows (and the trie) hold: no
    page referenced by a slot that was left, none lost."""
    live = {i for i, s in enumerate(bat._slots) if s is not None}
    bat.cache.check_invariants()
    bat.pool.check_invariants(live, cache_pages=bat.cache.pages())
    held = set().union(*(bat.pool.owned(i) for i in live)) \
        | set(bat.cache.pages())
    assert bat.pool.pages_in_use == len(held)


def _drive(bat, after_pass=None):
    """Scheduler passes on this thread until the batcher is drained; after
    every pass the pool holds exactly what the live rows hold."""
    n = 0
    while bat._step_once():
        n += 1
        assert n < 2000, "the scheduler does not drain"
        _audit(bat)
        if after_pass is not None:
            after_pass(bat)
    assert bat._flight is None
    return n


def _until_ahead(bat):
    """Passes until a burst is in flight between two passes."""
    for _ in range(200):
        assert bat._step_once()
        if bat._flight is not None:
            return
    raise AssertionError("no burst was dispatched ahead")


def _settled(bat, eng):
    assert bat._flight is None and bat._drained()
    bat.cache.flush()
    bat.stop()
    assert bat.pool.free_pages == bat.pool.num_pages
    bat.pool.check_invariants(set())
    assert eng.compile_guard.steady_state_recompiles == 0


# ------------------------------------------------- the program on the device
@pytest.mark.parametrize("kind", ONE_AND_TWO)
def test_next_carry_is_the_hosts_arithmetic(rigs, kind):
    """``next_carry`` on a block as ``decode_iter`` hands it back, counts'
    columns and all, against what ``_collect`` and ``_take_steps`` make of
    the same block; nothing comes to the host but what the test reads."""
    rig = rigs(kind)
    eng = rig.engine()
    bat = rig.batcher(eng, 3, 12)       # a slot stays free: nothing ahead
    futs = [_queue(bat, rig.prompt(i), 12) for i in range(2)]
    while not all(s is not None and s.decoding for s in bat._slots[:2]):
        assert bat._step_once()
    assert bat._flight is None
    rows = [(i, bat._slots[i]) for i in range(2)]
    before = [(s.carry, s.length) for _, s in rows]
    bat._ensure_capacity([0, 1])
    flight = bat._dispatch([0, 1])
    tokens, lengths = eng.next_carry(flight.buf, flight.lengths,
                                     steps=STEPS)
    assert isinstance(tokens, jax.Array) and isinstance(lengths, jax.Array)
    assert flight.buf.shape[1] > STEPS * bat._step_cols     # counts ride
    bat._collect(flight)
    assert [(s.carry, s.length) for _, s in rows] == \
        list(zip(np.asarray(tokens).tolist()[:2],
                 np.asarray(lengths).tolist()[:2]))
    assert [(s.carry, s.length) for _, s in rows] != before
    _drive(bat)
    for i, f in enumerate(futs):
        assert f.result(timeout=0) == rig.greedy(rig.prompt(i), 12)
    _settled(bat, eng)


# ----------------------------------------------------------- the same tokens
@pytest.mark.parametrize("kind", EVERY)
def test_tokens_with_bursts_ahead_are_the_engines_greedy_tokens(rigs, kind):
    """Five requests through two slots (every slot decoding for most of a
    reply: bursts go ahead) and through six (a slot is always free: the
    rule never holds). The same tokens, the engine's own."""
    rig = rigs(kind)
    news = [14, 16, 9, 12, 15]
    prompts = [rig.prompt(i) for i in range(5)]
    want = [rig.greedy(p, n) for p, n in zip(prompts, news)]
    got, stats = {}, {}
    for slots in (2, 6):
        eng = rig.engine()
        bat = rig.batcher(eng, slots, 16)
        futs = [_queue(bat, p, n) for p, n in zip(prompts, news)]
        _drive(bat)
        got[slots] = [f.result(timeout=0) for f in futs]
        stats[slots] = dict(bat.stats)
        _settled(bat, eng)
    assert got[2] == want
    assert got[6] == want
    if rig.chunked:
        # ... which are the plain reference's, by full forwards
        assert want[2] == [int(t) for t in rig.ref.greedy(
            SEED, rig.cfg, prompts[2], news[2])]
    assert stats[2]["bursts_ahead"] > 0
    assert stats[6]["bursts_ahead"] == 0
    # a burst is a pass's, ahead or not: every burst was read and counted
    assert stats[2]["bursts_ahead"] < stats[2]["iterations"]
    assert stats[2]["tokens"] + 5 == sum(news) == stats[6]["tokens"] + 5
    assert stats[2]["retired"] == stats[6]["retired"] == 5


def test_a_started_batcher_goes_ahead_and_serves_the_same(rigs):
    """The scheduler's own thread, callers' threads beside it."""
    rig = rigs("zaya")
    eng = rig.engine()
    bat = rig.batcher(eng, 2, 16, start=True)
    try:
        futs = [bat.submit(rig.prompt(i), max_new_tokens=16)
                for i in range(4)]
        got = [f.result(timeout=300) for f in futs]
    finally:
        _settled(bat, eng)
    assert got == [rig.greedy(rig.prompt(i), 16) for i in range(4)]
    assert bat.stats["bursts_ahead"] > 0


# --------------------------------------------------------------- the rule
class _Spy:
    """Every call of ``_may_run_ahead`` with the four conditions as the
    batcher's state gives them, reckoned here BEFORE the call (which may
    take pages), beside the answer."""

    def __init__(self, bat, rig):
        self.calls, self.bat = [], bat
        self.most = STEPS * rig.step_tokens
        real = bat._may_run_ahead

        def spied(flight):
            why = self.conditions(flight)
            answer = real(flight)
            self.calls.append((why, answer))
            return answer

        bat._may_run_ahead = spied

    def conditions(self, flight):
        bat, pool = self.bat, self.bat.pool
        why = set()
        if any(s is None for s in bat._slots):
            why.add("a free slot")
        if any(s is not None and not s.finished and s.carry is None
               for s in bat._slots):
            why.add("a prompt entering")
        if any(bat._slots[i] is not s for i, s in flight.rows):
            why.add("a row has left")
        if any(len(s.emitted) + self.most >= s.req.max_new
               for _, s in flight.rows):
            why.add("a row within a burst of max_new")
        need = 0
        for i, s in flight.rows:
            upto = min(s.length + 2 * self.most, s.base + s.req.max_new)
            need += max(0, -(-upto // PAGE) - len(pool.owned(i)))
        if need > pool.free_pages:
            why.add("the pool short of a second burst's pages")
        return why

    def alone(self, reason):
        return [a for why, a in self.calls if why == {reason}]


def _check_rule(spy):
    """Ahead exactly where no condition stands in the way."""
    assert spy.calls
    for why, answer in spy.calls:
        assert answer == (not why), (why, answer)


@pytest.mark.parametrize("kind", ONE_AND_TWO)
def test_refused_while_a_slot_is_free(rigs, kind):
    rig = rigs(kind)
    eng = rig.engine()
    bat = rig.batcher(eng, 3, 16)
    spy = _Spy(bat, rig)
    futs = [_queue(bat, rig.prompt(i), 16) for i in range(2)]
    _drive(bat, lambda b: b._flight is None or pytest.fail("went ahead"))
    _check_rule(spy)
    assert spy.alone("a free slot") and not any(a for _, a in spy.calls)
    assert bat.stats["bursts_ahead"] == 0
    assert [f.result(timeout=0) for f in futs] == \
        [rig.greedy(rig.prompt(i), 16) for i in range(2)]
    _settled(bat, eng)


@pytest.mark.parametrize("kind", ONE_AND_TWO)
def test_refused_while_a_prompt_is_entering(rigs, kind):
    """A prompt of four chunks takes the second slot: four passes in which
    the first slot's row takes its bursts and none goes ahead; then both
    decode and the bursts do."""
    rig = rigs(kind)
    eng = rig.engine()
    bat = rig.batcher(eng, 2, 20)
    spy = _Spy(bat, rig)
    prompts = [rig.prompt(0, 5), rig.prompt(1, 30)]
    futs = [_queue(bat, p, 20) for p in prompts]

    def entering_means_no_flight(b):
        if any(s is not None and s.carry is None for s in b._slots):
            assert b._flight is None

    _drive(bat, entering_means_no_flight)
    _check_rule(spy)
    assert len(spy.alone("a prompt entering")) >= 2
    assert bat.stats["bursts_ahead"] > 0
    assert [f.result(timeout=0) for f in futs] == \
        [rig.greedy(p, 20) for p in prompts]
    _settled(bat, eng)


@pytest.mark.parametrize("kind", ONE_AND_TWO)
def test_refused_for_a_row_within_a_burst_of_max_new(rigs, kind):
    """Two rows, one of which ends early: while it is further than a burst
    from its limit bursts go ahead; the burst in which it can end is read
    with nothing queued behind it, so its successor's chunk finds an idle
    device."""
    rig = rigs(kind)
    eng = rig.engine()
    most = STEPS * rig.step_tokens
    bat = rig.batcher(eng, 2, 24)
    spy = _Spy(bat, rig)
    news = [24, 2 * most + 2, 8]
    futs = [_queue(bat, rig.prompt(i, 4), n) for i, n in enumerate(news)]

    def nothing_queued_behind_a_last_burst(b):
        if b._flight is not None:
            for _, s in b._flight.rows:
                # after the read of the burst before: still a burst away
                assert len(s.emitted) < s.req.max_new

    _drive(bat, nothing_queued_behind_a_last_burst)
    _check_rule(spy)
    assert spy.alone("a row within a burst of max_new")
    assert any(a for _, a in spy.calls)
    assert [f.result(timeout=0) for f in futs] == \
        [rig.greedy(rig.prompt(i, 4), n) for i, n in enumerate(news)]
    _settled(bat, eng)


@pytest.mark.parametrize("kind", ONE_AND_TWO)
def test_refused_where_the_pool_is_short_of_a_second_bursts_pages(rigs,
                                                                  kind):
    """A pool one page short of both replies: a burst goes ahead while the
    second burst's pages are there, none where they are not (and no row is
    preempted FOR a burst ahead); the pass that follows fights for pages
    as ever and the youngest row starts again."""
    rig = rigs(kind)
    eng = rig.engine()
    # 4 + 16 positions are 5 pages a row at the end
    bat = rig.batcher(eng, 2, 16, buckets=[8], num_pages=9)
    spy = _Spy(bat, rig)
    preempted = []
    real = bat._preempt
    bat._preempt = lambda slot: (preempted.append(bat._flight), real(slot))
    futs = [_queue(bat, rig.prompt(i, 4), 16) for i in range(2)]
    _drive(bat)
    _check_rule(spy)
    assert spy.alone("the pool short of a second burst's pages")
    assert any(a for _, a in spy.calls)
    # the fight is the pass's own, with no burst in flight
    assert preempted == [None] * bat.stats["preempted"] and preempted
    assert [f.result(timeout=0) for f in futs] == \
        [rig.greedy(rig.prompt(i, 4), 16) for i in range(2)]
    _settled(bat, eng)


# ------------------------------------ an end token inside the burst in flight
def _an_end_token(rig, prompts, n):
    """A token one request's greedy stream holds first at an index a few
    bursts in, so that it ends a row while every slot decodes."""
    most = STEPS * rig.step_tokens
    streams = [rig.greedy(p, n) for p in prompts]
    for at in range(most + 1, n - 2 * most):
        for mine, other in (streams[:2], streams[1::-1]):
            tok = mine[at]
            if tok not in mine[:at] and tok not in other[:at + most]:
                return tok
    raise AssertionError("no such token in these streams")


@pytest.mark.parametrize("kind", EVERY)
def test_an_end_token_inside_the_burst_in_flight(rigs, kind):
    """A row ends on its end token in burst B while B' is already queued
    for it: its part of B' is dropped, its slot goes to the next request
    (whose chunk or prefill the device runs after B'), every request gets
    its own greedy tokens up to its end token, and after every pass the
    pool holds what the live rows hold."""
    rig = rigs(kind)
    prompts = [rig.prompt(i) for i in range(4)]
    eos = _an_end_token(rig, prompts, 20)
    eng = rig.engine(eos)
    bat = rig.batcher(eng, 2, 20)
    futs = [_queue(bat, p, 20) for p in prompts]
    dropped = []

    def note(b):
        if b._flight is not None:
            dropped.extend(s for _, s in b._flight.rows if s.finished)

    _drive(bat, note)
    assert dropped, "no row ended with a burst queued behind it"
    for s in dropped:
        # nothing of the burst that ran for nothing reached the request
        assert s.emitted[-1] == eos and s.emitted.count(eos) == 1
    got = [f.result(timeout=0) for f in futs]
    assert got == [rig.greedy(p, 20, eos) for p in prompts]
    assert any(g[-1] == eos and len(g) < 20 for g in got)
    assert bat.stats["retired"] == 4
    _settled(bat, eng)


@pytest.mark.parametrize("kind", ONE_AND_TWO)
def test_a_deadline_inside_the_burst_in_flight(rigs, kind):
    """A row whose deadline passes while a burst is queued for it: it is
    failed at the next pass's retire, its part of the burst dropped, the
    other row's tokens right."""
    from mxnet_tpu.serving import DeadlineExceeded

    rig = rigs(kind)
    eng = rig.engine()
    bat = rig.batcher(eng, 2, 20)
    ok = _queue(bat, rig.prompt(0), 20)
    doomed = _queue(bat, rig.prompt(1), 20,
                    deadline=time.perf_counter() + 3600)
    _until_ahead(bat)
    doomed_slot = next(s for s in bat._slots if s.req.future is doomed)
    doomed_slot.req.deadline = time.perf_counter() - 1
    n = len(doomed_slot.emitted)
    _drive(bat)
    assert isinstance(doomed.exception(), DeadlineExceeded)
    assert len(doomed_slot.emitted) == n
    assert ok.result(timeout=0) == rig.greedy(rig.prompt(0), 20)
    _settled(bat, eng)


# ------------------------------------- ways out of a pass, a burst in flight
@pytest.mark.parametrize("kind", ONE_AND_TWO)
def test_a_failed_dispatch_with_a_burst_in_flight(rigs, kind):
    """``_poison``: the burst in flight is dropped with the state it ran
    on, every future fails, no page stays referenced, and the batcher
    serves the next request right."""
    rig = rigs(kind)
    eng = rig.engine()
    bat = rig.batcher(eng, 2, 16)
    futs = [_queue(bat, rig.prompt(i), 16) for i in range(2)]
    _until_ahead(bat)
    faults.inject("batcher.dispatch", times=1)
    assert bat._step_once()
    assert bat._flight is None
    for f in futs:
        assert isinstance(f.exception(), faults.FaultInjected)
    assert bat.pool.free_pages == bat.pool.num_pages
    again = _queue(bat, rig.prompt(2), 16)
    _drive(bat)
    assert again.result(timeout=0) == rig.greedy(rig.prompt(2), 16)
    bat.stop()
    assert bat.pool.free_pages == bat.pool.num_pages
    bat.pool.check_invariants(set())


@pytest.mark.parametrize("kind", ONE_AND_TWO)
@pytest.mark.parametrize("drain", [True, False], ids=["drain", "no-drain"])
def test_stop_with_a_burst_in_flight(rigs, kind, drain):
    """``stop(drain=True)`` serves what is in the slots to its end, bursts
    ahead and all; ``stop(drain=False)`` fails it. Either way every future
    is resolved or failed and every page is back."""
    rig = rigs(kind)
    eng = rig.engine()
    bat = rig.batcher(eng, 2, 24)
    futs = [_queue(bat, rig.prompt(i), 24) for i in range(2)]
    _until_ahead(bat)
    bat.start()             # the scheduler's own thread takes over
    bat.stop(drain=drain, timeout=300)
    if drain:
        assert [f.result(timeout=0) for f in futs] == \
            [rig.greedy(rig.prompt(i), 24) for i in range(2)]
        assert bat.stats["bursts_ahead"] > 1
    else:
        want = [rig.greedy(rig.prompt(i), 24) for i in range(2)]
        for f, w in zip(futs, want):
            # failed, or served to its end before the stop was seen
            assert f.done() and (isinstance(f.exception(), RuntimeError)
                                 or f.result(timeout=0) == w)
    assert bat._flight is None and bat._drained()
    assert bat.pool.free_pages == bat.pool.num_pages
    bat.pool.check_invariants(set())


@pytest.mark.parametrize("kind", EVERY)
def test_a_preemption_with_a_burst_in_flight(rigs, kind):
    """A row that leaves its slot while a burst is queued for it: its part
    of that burst is dropped, its request starts again from its prompt
    (after that burst, in the device's order) and gets the same tokens;
    the pool holds what the live rows hold after every pass."""
    rig = rigs(kind)
    eng = rig.engine()
    bat = rig.batcher(eng, 2, 16)
    futs = [_queue(bat, rig.prompt(i), 16) for i in range(2)]
    _until_ahead(bat)
    victim = bat._flight.rows[1][1]
    had = len(victim.emitted)
    bat._preempt(1)
    _audit(bat)
    _drive(bat)
    assert len(victim.emitted) == had       # the old row got nothing more
    assert bat.stats["preempted"] == 1
    assert [f.result(timeout=0) for f in futs] == \
        [rig.greedy(rig.prompt(i), 16) for i in range(2)]
    _settled(bat, eng)


def test_a_weight_swap_between_two_bursts(rigs):
    """A burst keeps the version it was dispatched with: the burst in
    flight at the swap is the old weights', the one dispatched after it
    the new ones', and no request is lost."""
    rig = rigs("transformer")
    np.random.seed(11)
    other = TransformerModel(src_vocab=61, tgt_vocab=61, units=16,
                             hidden_size=32, num_layers=2, num_heads=2,
                             max_length=64, dropout=0.0,
                             prefix=rig.net.prefix)
    other.initialize(mx.initializer.Xavier())
    other._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                        nd.zeros((2, 8), dtype="int32"))
    eng = rig.engine()
    first = eng.weights_version
    staged = eng.stage_params(
        {n: p._data.data for n, p in other.collect_params().items()})
    bat = rig.batcher(eng, 2, 16, prefix_cache=False)
    futs = [_queue(bat, rig.prompt(i), 16) for i in range(2)]
    _until_ahead(bat)
    assert bat._flight.version == first
    eng.swap_params(staged=staged, version="v-next")
    assert bat._step_once()             # reads the old burst, queues a new
    assert bat._flight is not None and bat._flight.version == "v-next"
    assert {s.version for s in bat._slots} == {first}
    _drive(bat)
    assert [len(f.result(timeout=0)) for f in futs] == [16, 16]
    assert {f.weights_version for f in futs} == {"v-next"}
    _settled(bat, eng)


def test_the_early_dispatch_is_a_span_of_its_own_inside_the_pass(rigs,
                                                                tmp_path):
    """``mxtpu.sched.dispatch.ahead``: one span a burst dispatched ahead,
    inside its pass's ``sched.step``, before that pass's read-back; its
    seconds are counted as a dispatch's."""
    import json

    from mxnet_tpu import telemetry as tel

    rig = rigs("zaya")
    eng = rig.engine()
    bat = rig.batcher(eng, 2, 16)
    for i in range(2):
        _queue(bat, rig.prompt(i), 16)
    tel.reset()
    tel.enable(str(tmp_path), watchdog=False)
    try:
        _drive(bat)
        path = tel.jsonl_path()
    finally:
        tel.reset()
    with open(path) as f:
        spans = [json.loads(ln) for ln in f]
    spans = [e for e in spans if e.get("name", "").startswith("mxtpu.sched")]

    def named(name):
        return [(e["ts"], e["ts"] + e["dur"]) for e in spans
                if e["name"] == "mxtpu.sched." + name]

    ahead, steps, reads = named("dispatch.ahead"), named("step"), \
        named("collect.readback")
    assert len(ahead) == bat.stats["bursts_ahead"] > 0
    assert len(named("dispatch")) + len(ahead) == bat.stats["iterations"]
    for t0, t1 in ahead:
        step, = [(a, b) for a, b in steps if a <= t0 and t1 <= b]
        read, = [(a, b) for a, b in reads if step[0] <= a and b <= step[1]]
        assert t1 <= read[0]
    _settled(bat, eng)


def test_the_seconds_of_a_burst_ahead_start_at_the_read_before_it(rigs):
    """``notify_step`` takes a burst's seconds from the later of its
    dispatch and the read-back before it: a burst dispatched a whole burst
    ago did not run that long."""
    rig = rigs("zaya")
    eng = rig.engine()
    seen = []

    class Dog:
        def notify_step(self, seconds):
            seen.append((seconds, time.perf_counter()))

        def note_request(self, **kw):
            pass

    bat = rig.batcher(eng, 2, 16, watchdog=Dog())
    for i in range(2):
        _queue(bat, rig.prompt(i), 16)
    _until_ahead(bat)
    before = bat._read_at
    assert bat._flight.t0 < before      # dispatched before the last read
    bat._flight.t0 -= 5.0               # ... say, long before it
    assert bat._step_once()
    seconds, at = seen[-1]
    assert seconds == pytest.approx(at - before, abs=0.05)
    # a burst dispatched after the last read counts from its dispatch
    for _ in range(50):
        if bat._flight is None:
            break
        assert bat._step_once()
    assert bat._flight is None and any(bat._slots)
    bat._read_at -= 5.0
    assert bat._step_once()
    assert seen[-1][0] < 4.0
    _drive(bat)
    _settled(bat, eng)

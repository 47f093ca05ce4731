"""Prefix caching: COW KV pages, radix-trie matching, affinity (ISSUE 13).

Contracts under test:

- TRIE: ``PrefixCache.add_root`` gives an exact prompt its root and the
  store row it pins; ``insert`` registers page-aligned blocks under it
  (dedup on re-insert, partial tail as a leaf, nothing for a prompt
  with no root), ``match`` returns the longest cached cover capped at
  ``len(target) - 1`` with the partial page flagged for COW and the
  root's row, and ``check_invariants`` proves the trie's page ledger
  and its row ledger exact.
- ROOT STORE: a retire copies the slot's cross rows into the new root's
  row of the batcher's device store bit for bit, one dispatch a pass
  and one row a prompt; an evicted root's row is handed out again;
  ``flush`` frees every row.
- REFCOUNTS: every page's refcount equals its slot mappings plus cache
  membership through arbitrary alloc / adopt_ref / cache_acquire /
  release / evict interleavings — ``PagePool.check_invariants(...,
  cache_pages=cache.pages())`` passes after every step and pages only
  return to the free list at refcount 0.
- EVICTION: ``evict`` frees LRU sole-ref leaves only (pages a live slot
  still maps survive), ``flush`` returns every cached page, and a full
  pool evicts cached-but-idle pages to admit new work instead of
  refusing it.
- BIT-IDENTITY: greedy decode through a cache hit (adopted pages + COW
  tail + suffix replay) emits exactly the tokens of an uncached batcher
  forced with the same history — including after COW divergence, which
  must not corrupt the shared page for the original history.
- ZERO RECOMPILES: the warmed engine serves cold, hit, and COW paths
  without a single steady-state recompile.
- AFFINITY: the router narrows placement to replicas advertising the
  prompt digest, falls back to predicted-wait placement when none does
  (or when ``MXTPU_PREFIX_AFFINITY=0``), and prefix requests bypass the
  disaggregated KV handoff.
- DISAGG SEEDING: adopting pushed prefill frames registers the prompt
  in the decode-side trie, so the next turn hits the cache.
"""

import queue
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import (ContinuousBatcher, PagePool, PrefillEngine,
                               PrefixCache, Replica, Router, prompt_digest)
from mxnet_tpu.serving.batcher import GenerationResult, _Request
from mxnet_tpu.serving.pages import TRASH_PAGE, pages_for

V = 61


def _make_net(seed=0, prefix="pfx_net_", num_layers=1):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = TransformerModel(src_vocab=V, tgt_vocab=V, units=16,
                           hidden_size=32, num_layers=num_layers,
                           num_heads=2,
                           max_length=64, dropout=0.0, prefix=prefix)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    return net


@pytest.fixture(scope="module")
def engine():
    return InferStep(_make_net(0), max_len=64)


def _batcher(engine, cache_on, name):
    return ContinuousBatcher(engine, (8,), slots=2, max_new_tokens=6,
                             page_size=4, iter_tokens=2,
                             max_prefix_tokens=16, prefix_cache=cache_on,
                             warmup=True, name=name)


@pytest.fixture(scope="module")
def cached_batcher(engine):
    bat = _batcher(engine, True, "pfx-cached")
    yield bat
    bat.stop()


@pytest.fixture(scope="module")
def cold_batcher(engine):
    # identical weights + bucket/suffix menus, no trie: the bitwise
    # reference for every cache-hit path
    bat = _batcher(engine, False, "pfx-cold")
    yield bat
    bat.stop()


def _pool_cache(num_pages=12, page_size=4, slots=3, pages_per_slot=6,
                **kw):
    pool = PagePool(num_pages, page_size, slots, pages_per_slot)
    cache = PrefixCache(pool, page_size, enabled=True, **kw)
    return pool, cache


def _retire(cache, prompt, target, pages):
    """A retire as the batcher makes it: the prompt's root first (a store
    row, where the prompt is new), then its page chain under the root."""
    cache.add_root(prompt)
    return cache.insert(prompt, target, pages)


def _audit(pool, cache, live=()):
    cache.check_invariants()
    pool.check_invariants(live_slots=live, cache_pages=cache.pages())


class TestTrie:
    def test_insert_without_a_root_creates_none(self):
        pool, cache = _pool_cache()
        assert pool.alloc(0, 2)
        assert cache.insert([5, 6], range(1, 8), pool.owned(0)) == 0
        assert not cache.has_root([5, 6])
        assert cache.match([5, 6], range(1, 8)) is None  # counted miss
        assert cache.snapshot()["misses"] == 1
        _audit(pool, cache, live=(0,))

    def test_insert_match_roundtrip_with_cow_tail(self):
        pool, cache = _pool_cache()
        prompt, target = [5, 9, 11], [1, 2, 3, 4, 5, 6, 7]  # 1 full + tail
        assert pool.alloc(0, pages_for(len(target), 4))
        pages = pool.owned(0)
        row = cache.add_root(prompt)
        assert row == 0 and cache.add_root(prompt) is None  # one row
        assert cache.insert(prompt, target, pages) == 2
        assert cache.has_root(prompt)
        assert prompt_digest(prompt) in cache.digests()
        hit = cache.match(prompt, target)
        # positions 0..5 adopted (cap at len-1): one full page + 2 of
        # the 3-token tail via COW
        assert hit.matched == 6
        assert hit.full_pages == (pages[0],)
        assert hit.cow == (pages[1], 2)
        assert hit.row == row
        _audit(pool, cache, live=(0,))

    def test_reinsert_dedups_blocks(self):
        pool, cache = _pool_cache()
        target = list(range(1, 9))  # exactly 2 full blocks
        assert pool.alloc(0, 2)
        assert _retire(cache, [7], target, pool.owned(0)) == 2
        assert pool.alloc(1, 2)
        # same prompt+target from another slot: nothing new is cached
        assert _retire(cache, [7], target, pool.owned(1)) == 0
        assert cache.total_pages == 2
        pool.release(1)  # its pages were never adopted by the trie
        assert pool.free_pages == 12 - 2
        _audit(pool, cache, live=(0,))

    def test_divergent_second_block_branches(self):
        pool, cache = _pool_cache()
        a = [1, 2, 3, 4, 5, 6, 7, 8]
        b = [1, 2, 3, 4, 9, 9, 9, 9]  # shares block 0 only
        assert pool.alloc(0, 2) and pool.alloc(1, 2)
        assert _retire(cache, [7], a, pool.owned(0)) == 2
        # block 0 dedups against slot 0's page; block 1 branches
        assert _retire(cache, [7], b, pool.owned(1)) == 1
        assert cache.total_pages == 3
        ha, hb = cache.match([7], a), cache.match([7], b)
        assert ha.full_pages[0] == hb.full_pages[0]
        assert ha.matched == hb.matched == 7  # cap at len(target) - 1
        assert ha.cow[0] != hb.cow[0] and ha.cow[1] == hb.cow[1] == 3
        _audit(pool, cache, live=(0, 1))

    def test_partial_tail_extends_in_place_same_page(self):
        pool, cache = _pool_cache()
        assert pool.alloc(0, 2)
        p0, p1 = pool.owned(0)
        # a short handoff seeds a 1-token tail; the slot keeps filling
        # that SAME page and re-registers the grown chain at retire —
        # the longer block supersedes the node instead of
        # double-acquiring its page
        assert _retire(cache, [5], [1], (p0,)) == 1
        assert cache.insert([5], [1, 2, 3, 4, 9], (p0, p1)) == 1
        assert cache.total_pages == 2 and pool.ref(p0) == 2
        hit = cache.match([5], [1, 2, 3, 4, 9])
        assert hit.matched == 4 and hit.full_pages == (p0,)
        _audit(pool, cache, live=(0,))

    def test_match_caps_below_full_cover(self):
        pool, cache = _pool_cache()
        target = [1, 2, 3, 4]  # one exactly-full block
        assert pool.alloc(0, 1)
        assert _retire(cache, [3], target, pool.owned(0)) == 1
        hit = cache.match([3], target)
        # the final position must still run to produce first-token
        # logits: a full-block cover degrades to a 3-token COW
        assert hit.matched == 3
        assert hit.full_pages == () and hit.cow[1] == 3
        _audit(pool, cache, live=(0,))

    def test_max_roots_evicts_lru_root(self):
        pool, cache = _pool_cache(max_roots=2)
        rows = []
        for i in range(3):
            assert pool.alloc(i, 1)
            rows.append(cache.add_root([i]))
            assert cache.insert([i], [1, 2, 3], pool.owned(i)) == 1
            pool.release(i)
            _audit(pool, cache)
        assert len(cache) == 2
        assert not cache.has_root([0])  # LRU root dropped, pages freed
        assert rows == [0, 1, 0]  # ... and its row handed out again
        assert cache.snapshot()["evicted_roots"] == 1
        assert pool.free_pages == 12 - 2
        _audit(pool, cache)

    def test_flush_returns_every_page(self):
        pool, cache = _pool_cache()
        assert pool.alloc(0, 3)
        _retire(cache, [5], list(range(1, 12)), pool.owned(0))
        pool.release(0)
        assert pool.free_pages == 12 - 3
        assert cache.flush() == 1
        assert pool.free_pages == 12 and cache.total_pages == 0
        assert sorted(cache._free_rows) == list(range(cache.max_roots))
        _audit(pool, cache)


class TestRefcounts:
    def test_release_keeps_cached_pages_alive(self):
        pool, cache = _pool_cache()
        assert pool.alloc(0, 2)
        p0, p1 = pool.owned(0)
        _retire(cache, [9], list(range(1, 8)), (p0, p1))
        assert pool.ref(p0) == pool.ref(p1) == 2
        _audit(pool, cache, live=(0,))
        assert pool.release(0) == 0  # cache still holds both
        assert pool.ref(p0) == 1 and p0 not in set(pool._free)
        _audit(pool, cache)

    def test_adopt_release_interleaving_is_ref_exact(self):
        pool, cache = _pool_cache()
        assert pool.alloc(0, 2)
        pages = pool.owned(0)
        _retire(cache, [9], list(range(1, 8)), pages)
        pool.release(0)
        # two readers adopt the cached chain (shared, read-only) …
        for s in (1, 2):
            assert pool.adopt_ref(s, pages)
            _audit(pool, cache, live=(1, 2)[:s])
        assert pool.ref(pages[0]) == 3
        assert pool.shared_pages == 2
        # … then one grows privately and both retire (preempt-style)
        assert pool.alloc(1, 1)
        assert pool.release(1) == 1  # only the private page frees
        assert pool.release(2) == 0
        assert pool.ref(pages[0]) == 1
        _audit(pool, cache)

    def test_evict_skips_pages_live_slots_still_map(self):
        pool, cache = _pool_cache()
        assert pool.alloc(0, 2)
        pages = pool.owned(0)
        _retire(cache, [9], list(range(1, 8)), pages)
        pool.release(0)
        assert pool.adopt_ref(1, pages)  # a live reader
        assert cache.evict(2) == 0  # nothing is sole-ref
        assert cache.total_pages == 2
        pool.release(1)
        assert cache.evict(2) == 2  # now LRU leaves free for real
        assert pool.free_pages == 12
        _audit(pool, cache)

    def test_double_acquire_and_trash_adopt_raise(self):
        from mxnet_tpu.base import MXNetError
        pool, _ = _pool_cache()
        assert pool.alloc(0, 1)
        page = pool.owned(0)[0]
        pool.cache_acquire((page,))
        with pytest.raises(MXNetError):
            pool.cache_acquire((page,))
        with pytest.raises(MXNetError):
            pool.adopt_ref(1, (TRASH_PAGE,))


class TestEviction:
    def test_lru_order_and_partial_progress(self):
        pool, cache = _pool_cache()
        held = {}
        for i in range(3):
            assert pool.alloc(i, 1)
            _retire(cache, [i], [1, 2, 3], pool.owned(i))
            held[i] = pool.owned(i)[0]
            pool.release(i)
        cache.match([0], [1, 2, 3])  # refresh root 0: root 1 is now LRU
        assert cache.evict(1) == 1
        # root 1's page went back to the pool (the root and its store
        # row stay for encoder-skip); root 0's refreshed page survives
        assert held[1] not in cache.pages()
        assert held[0] in cache.pages()
        assert cache.match([1], [1, 2, 3]).matched == 0
        # asking for more than exists frees what it can
        assert cache.evict(10) == 2
        assert pool.free_pages == 12
        _audit(pool, cache)

    def test_max_pages_caps_trie_footprint(self):
        pool, cache = _pool_cache(max_pages=2)
        for i in range(3):
            assert pool.alloc(i, 1)
            _retire(cache, [i], [1, 2, 3], pool.owned(i))
            pool.release(i)
            assert cache.total_pages <= 2
            _audit(pool, cache)
        assert cache.snapshot()["evicted_pages"] == 1


def _serve(bat, prompt, prefix=None, timeout=120):
    return list(bat.submit(prompt, max_new_tokens=6,
                           prefix_ids=prefix).result(timeout=timeout))


def _settled_audit(bat):
    """Audit once every slot has retired (the scheduler releases pages
    just after resolving the future)."""
    for _ in range(400):
        with bat._stats_lock:
            busy = any(s is not None for s in bat._slots)
        if not busy:
            break
        time.sleep(0.01)
    bat.cache.check_invariants()
    bat.pool.check_invariants(cache_pages=bat.cache.pages())


class TestEndToEnd:
    def test_hit_is_bit_identical_to_cold(self, cached_batcher,
                                          cold_batcher):
        cached_batcher.cache.flush()
        prompt = [5, 9, 11, 2, 7]
        turn1 = _serve(cached_batcher, prompt)
        assert cached_batcher.cache.has_root(prompt)  # retire seeded it
        base = cached_batcher.prefix_stats()
        turn2 = _serve(cached_batcher, prompt, prefix=turn1)
        stats = cached_batcher.prefix_stats()
        assert stats["hits"] == base["hits"] + 1
        assert stats["tokens_saved"] > base["tokens_saved"]
        assert turn2 == _serve(cold_batcher, prompt, prefix=turn1)
        # deeper history: trie now holds turn1+turn2; still bit-exact
        hist = turn1 + turn2
        assert _serve(cached_batcher, prompt, prefix=hist) \
            == _serve(cold_batcher, prompt, prefix=hist)
        _settled_audit(cached_batcher)

    def test_cow_divergence_preserves_shared_page(self, cached_batcher,
                                                  cold_batcher):
        cached_batcher.cache.flush()
        prompt = [8, 3, 14, 6]
        turn1 = _serve(cached_batcher, prompt)
        out_a = _serve(cached_batcher, prompt, prefix=turn1)
        # client edits the last history token: partial-page divergence
        hist_b = list(turn1)
        hist_b[-1] = (hist_b[-1] + 1) % (V - 3) + 2
        base = cached_batcher.prefix_stats()
        out_b = _serve(cached_batcher, prompt, prefix=hist_b)
        stats = cached_batcher.prefix_stats()
        assert stats["cow_copies"] > base["cow_copies"]
        assert out_b == _serve(cold_batcher, prompt, prefix=hist_b)
        # the divergent write went to a private copy: the original
        # history must replay to the exact same tokens afterwards
        assert _serve(cached_batcher, prompt, prefix=turn1) == out_a
        _settled_audit(cached_batcher)

    def test_full_pool_evicts_idle_cache_to_admit(self, cached_batcher,
                                                  cold_batcher):
        cached_batcher.cache.flush()
        # each retired request caches pages_for(1+6, 4) = 2 pages; six
        # distinct prompts exhaust the 12-page pool entirely
        for i in range(6):
            _serve(cached_batcher, [2 + i, 30, 41])
        _settled_audit(cached_batcher)
        assert cached_batcher.pool.free_pages == 0
        base = cached_batcher.cache.snapshot()["evicted_pages"]
        prompt = [50, 51, 52]
        out = _serve(cached_batcher, prompt)
        assert out == _serve(cold_batcher, prompt)
        assert cached_batcher.cache.snapshot()["evicted_pages"] > base
        _settled_audit(cached_batcher)

    def test_zero_steady_state_recompiles(self, engine, cached_batcher):
        # runs after the cold/hit/COW/eviction traffic above: none of it
        # may have minted a new program on the warmed engine
        assert engine.compile_guard.steady
        assert engine.compile_guard.steady_state_recompiles == 0

    def test_suffix_wide_replay_bit_identical(self, cold_batcher):
        """ISSUE 14 follow-up: a batcher routing the prefix-hit suffix
        replay through the ONE-pass q_offset window program
        (``suffix_wide=True``) serves the same transcripts as the
        per-token teacher-forced replay, hit for hit."""
        eng = InferStep(_make_net(0, prefix="pfx_wide_"), max_len=64)
        bat = ContinuousBatcher(eng, (8,), slots=2, max_new_tokens=6,
                                page_size=4, iter_tokens=2,
                                max_prefix_tokens=16, prefix_cache=True,
                                suffix_wide=True, warmup=True,
                                name="pfx-wide")
        try:
            prompt = [4, 12, 9, 33, 6]
            turn1 = _serve(bat, prompt)
            assert bat.cache.has_root(prompt)
            base = bat.prefix_stats()
            turn2 = _serve(bat, prompt, prefix=turn1)
            assert bat.prefix_stats()["hits"] == base["hits"] + 1
            # same weights, wide replay vs the cold teacher-forced path
            assert turn1 == _serve(cold_batcher, prompt)
            assert turn2 == _serve(cold_batcher, prompt, prefix=turn1)
            hist = turn1 + turn2
            assert _serve(bat, prompt, prefix=hist) \
                == _serve(cold_batcher, prompt, prefix=hist)
            _settled_audit(bat)
            assert eng.compile_guard.steady_state_recompiles == 0
        finally:
            bat.stop()


def _drive(bat, prompts, prefix=None):
    """Queue requests on an UNSTARTED batcher and run scheduler passes on
    this thread until it is drained: which requests share a pass is then
    the test's to decide, not the clock's."""
    futs = []
    for p in prompts:
        fut = GenerationResult()
        bat._queue.put(_Request(
            np.asarray(p, np.int32), 6, fut,
            prefix=None if prefix is None else np.asarray(prefix, np.int32)))
        futs.append(fut)
    while bat._step_once():
        pass
    return [list(f.result(timeout=0)) for f in futs]


@pytest.fixture(scope="module")
def store_pair():
    """A cached and a cold batcher over one two-layer engine, neither
    started, the cached one with a store of two rows."""
    eng = InferStep(_make_net(1, prefix="pfx_store_", num_layers=2),
                    max_len=64)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("MXTPU_PREFIX_MAX_ROOTS", "2")
        bats = [ContinuousBatcher(eng, (8,), slots=3, max_new_tokens=6,
                                  page_size=4, iter_tokens=2,
                                  max_prefix_tokens=16, prefix_cache=on,
                                  warmup=True, start=False,
                                  name=f"pfx-store-{on}")
                for on in (True, False)]
    yield eng, bats[0], bats[1]
    for bat in bats:
        bat.stop()


class TestRootStore:
    A, B, C = [5, 9, 11, 2, 7], [8, 3, 14], [4, 12, 9, 33, 6, 20]

    @staticmethod
    def _row(bat, prompt):
        return bat.cache._roots[tuple(prompt)].row

    def test_store_row_is_the_slots_cross_rows_bit_for_bit(self,
                                                           store_pair):
        _, bat, _ = store_pair
        assert bat.cache.max_roots == 2
        bat.cache.flush()
        _drive(bat, [self.A])  # an empty batcher serves it in slot 0
        row, st = self._row(bat, self.A), bat._state
        frames, vl = bat._store
        bufs = st["cross_k"] + st["cross_v"]
        assert len(frames) == len(bufs) == 4  # two layers' K, then V
        for buf, rows in zip(bufs, frames):
            assert rows.dtype == buf.dtype
            assert rows.shape == (2,) + buf.shape[1:]
            got = np.asarray(rows[row])
            assert got.any()
            assert np.array_equal(got, np.asarray(buf[0]))
        assert vl.shape == (2,)
        assert int(vl[row]) == int(st["mem_vl"][0]) == len(self.A)
        _settled_audit(bat)

    def test_one_dispatch_a_pass_and_one_row_a_prompt(self, store_pair):
        _, bat, _ = store_pair
        bat.cache.flush()
        before = dict(bat.stats)
        # three slots admitted in one pass retire in one pass: each
        # serves its six tokens (no early EOS), two of them one prompt
        outs = _drive(bat, [self.A, self.B, self.A])
        assert [len(o) for o in outs] == [6, 6, 6]
        after = dict(bat.stats)
        assert after["retired"] - before["retired"] == 3
        assert after["prefix_store_dispatches"] \
            - before["prefix_store_dispatches"] == 1
        assert after["prefix_rows_stored"] \
            - before["prefix_rows_stored"] == 2
        assert {self._row(bat, self.A), self._row(bat, self.B)} == {0, 1}
        # a prompt the trie knows retires with no device step at all
        _drive(bat, [self.A, self.B])
        assert bat.stats["prefix_store_dispatches"] \
            == after["prefix_store_dispatches"]
        _settled_audit(bat)

    def test_third_prompt_reuses_the_oldest_roots_row(self, store_pair):
        _, bat, cold = store_pair
        bat.cache.flush()
        (turn1,) = _drive(bat, [self.A])
        _drive(bat, [self.B])
        _drive(bat, [self.A])  # refresh A: B is the oldest root now
        rows = {p: self._row(bat, getattr(self, p)) for p in "AB"}
        _drive(bat, [self.C])
        assert not bat.cache.has_root(self.B)
        assert bat.cache.snapshot()["evicted_roots"] >= 1
        assert self._row(bat, self.C) == rows["B"]
        assert self._row(bat, self.A) == rows["A"]
        # the surviving root still serves its hit (adopted page, COW
        # tail, cross rows out of the store) bit-identical to cold
        base = bat.prefix_stats()
        assert _drive(bat, [self.A], prefix=turn1) \
            == _drive(cold, [self.A], prefix=turn1)
        stats = bat.prefix_stats()
        assert stats["hits"] == base["hits"] + 1
        assert stats["cow_copies"] == base["cow_copies"] + 1
        _settled_audit(bat)

    def test_flush_frees_every_row(self, store_pair):
        _, bat, _ = store_pair
        _drive(bat, [self.A, self.B])
        assert len(bat.cache) == 2 and bat.cache._free_rows == []
        assert bat.cache.flush() == 2
        assert sorted(bat.cache._free_rows) == [0, 1]
        assert _drive(bat, [self.C]) and self._row(bat, self.C) in (0, 1)
        _settled_audit(bat)

    def test_two_programs_and_no_recompile(self, store_pair):
        # runs after the retire, hit, COW and eviction traffic above
        eng, bat, _ = store_pair
        assert eng.compile_guard.steady
        assert eng.compile_guard.steady_state_recompiles == 0
        assert bat._store_fn._cache_size() == 1
        assert bat._hits_fn._cache_size() == 1


class _StubBatcher:
    """Placement-only batcher stub: no engine, records submits."""

    healthy = True

    def __init__(self, name, digests=(), backlog=0):
        self.name = name
        self._digests = list(digests)
        self._queue = queue.Queue()
        for _ in range(backlog):
            self._queue.put(None)
        self.calls = []

    def prefix_digests(self, limit=None):
        return list(self._digests)

    def rolling_wait_ms(self):
        return None

    def submit(self, prompt, max_new, deadline_ms=None, prefix_ids=None,
               request_id=None):
        self.calls.append((list(prompt),
                           None if prefix_ids is None else list(prefix_ids)))
        return GenerationResult()


class TestAffinityPlacement:
    def _fleet(self, digest):
        # the digest holder carries MORE backlog: predicted-wait
        # placement alone would always pick "idle"
        holder = Replica("holder", _StubBatcher("holder", (digest,),
                                                backlog=3))
        idle = Replica("idle", _StubBatcher("idle"))
        return holder, idle, Router([holder, idle], start=False)

    def test_affinity_beats_predicted_wait(self):
        prompt, hist = [5, 6, 7], [9, 9]
        holder, idle, router = self._fleet(prompt_digest(prompt))
        router.submit(prompt, 4)  # no history: placement ignores the trie
        assert idle.batcher.calls == [([5, 6, 7], None)]
        router.submit(prompt, 4, prefix_ids=hist)
        assert holder.batcher.calls == [([5, 6, 7], [9, 9])]

    def test_fallback_when_no_replica_holds_digest(self):
        holder, idle, router = self._fleet(prompt_digest([1, 2, 3]))
        router.submit([5, 6, 7], 4, prefix_ids=[9])
        assert idle.batcher.calls and not holder.batcher.calls

    def test_env_disables_affinity(self, monkeypatch):
        monkeypatch.setenv("MXTPU_PREFIX_AFFINITY", "0")
        prompt = [5, 6, 7]
        holder, idle, router = self._fleet(prompt_digest(prompt))
        router.submit(prompt, 4, prefix_ids=[9])
        assert idle.batcher.calls and not holder.batcher.calls

    def test_prefix_requests_bypass_disagg_handoff(self):
        class _DisaggReplica(Replica):
            def __init__(self, name, batcher):
                super().__init__(name, batcher)
                self.handoffs = []

            def submit_disagg(self, pre, prompt, max_new,
                              deadline_ms=None, klass="interactive",
                              request_id=None):
                self.handoffs.append(list(prompt))
                return GenerationResult()

        dec = _DisaggReplica("dec", _StubBatcher("dec"))
        pre = Replica("pre", _StubBatcher("pre"), role="prefill")
        router = Router([dec, pre], start=False, disagg_min_prompt=4)
        long_prompt = list(range(2, 10))
        router.submit(long_prompt, 4)
        assert dec.handoffs == [long_prompt]  # handoff path
        router.submit(long_prompt, 4, prefix_ids=[9, 9])
        # forced history makes the KV handoff moot: direct submit
        assert dec.handoffs == [long_prompt]
        assert dec.batcher.calls == [(long_prompt, [9, 9])]


class TestDisaggSeeding:
    def test_adopted_frames_seed_the_trie(self, cached_batcher,
                                          cold_batcher):
        cached_batcher.cache.flush()
        pre = PrefillEngine(InferStep(_make_net(0), max_len=64), (8,),
                            rows=2, page_size=4, warmup=True)
        prompt = [4, 17, 33, 8, 21]
        frames = pre.prefill(prompt)
        out = list(cached_batcher.submit(
            prompt, max_new_tokens=6, frames=frames).result(timeout=120))
        assert out == _serve(cold_batcher, prompt)  # handoff bit-exact
        assert cached_batcher.cache.has_root(prompt)  # seeded at adopt
        base = cached_batcher.prefix_stats()
        turn2 = _serve(cached_batcher, prompt, prefix=out)
        assert cached_batcher.prefix_stats()["hits"] == base["hits"] + 1
        assert turn2 == _serve(cold_batcher, prompt, prefix=out)
        _settled_audit(cached_batcher)

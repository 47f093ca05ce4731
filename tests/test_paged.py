"""Continuous batching over a paged KV cache (ISSUE 8 tentpole).

Contracts under test:

- **Page-pool invariants**: alloc/free round-trips leave the free list
  EXACT (free + owned partition the pool), no page is ever aliased by two
  live requests, the trash page is never allocated.
- **Paged read parity**: at equal logical capacity the gather-through-
  the-table attention read is BIT-identical to the dense
  ``(max_len, B, H, D)`` path at fp32 — layer level and end-to-end
  (``ContinuousBatcher`` greedy tokens == ``InferStep.decode_n``).
- **Iteration-level scheduling**: retired rows free their slots/pages
  mid-stream, the warmed program menu holds zero steady-state
  recompiles, tokens stream per iteration, deadlines retire rows
  mid-decode, pool exhaustion preempts (and restarts) rather than
  wedging, admission control rejects with ``Backpressure``.
- **Self-healing interop**: a replica crash with paged requests in
  flight frees its pages and fails over through the Router (chaos
  marker); a hot weight swap lands between iterations with zero lost
  requests.
"""

import math
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
from mxnet_tpu.gluon.nn import MultiHeadAttention
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import (Backpressure, ContinuousBatcher,
                               DeadlineExceeded, PagePool, Replica, Router,
                               faults, make_batcher)
from mxnet_tpu.serving import pages as pages_mod


def _rows(x):
    """``x (B, H, D)`` as a row of the attention layer's pools holds it:
    heads of less than whole lanes lie side by side, ``(B, H x D)``."""
    return x.reshape(x.shape[0], -1)


def _make_transformer(V=61, units=16, layers=2, seed=0, **kw):
    np.random.seed(seed)
    net = TransformerModel(src_vocab=V, tgt_vocab=V, units=units,
                           hidden_size=2 * units, num_layers=layers,
                           num_heads=2, max_length=64, dropout=0.0, **kw)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    return net


@pytest.fixture(scope="module")
def tmodel():
    return _make_transformer()


# ------------------------------------------------------------- page pool
class TestPagePool:
    def test_alloc_free_round_trip_exact(self):
        pool = PagePool(num_pages=8, page_size=4, slots=3,
                        pages_per_slot=3)
        assert pool.free_pages == 8 and pool.pages_in_use == 0
        assert pool.alloc(0, 2) and pool.alloc(1, 3) and pool.alloc(2, 1)
        assert pool.pages_in_use == 6 and pool.free_pages == 2
        pool.check_invariants({0, 1, 2})
        assert pool.release(1) == 3
        assert pool.free_pages == 5
        pool.check_invariants({0, 2})
        assert pool.release(0) == 2 and pool.release(2) == 1
        assert pool.free_pages == 8 and pool.pages_in_use == 0
        pool.check_invariants(set())
        # table fully pointed back at trash
        assert (pool.table == pages_mod.TRASH_PAGE).all()

    def test_no_page_aliased_by_two_slots(self):
        pool = PagePool(num_pages=6, page_size=2, slots=3,
                        pages_per_slot=3)
        pool.alloc(0, 3)
        pool.alloc(1, 3)
        owned = set(pool.owned(0)) | set(pool.owned(1))
        assert len(owned) == 6  # disjoint
        assert pages_mod.TRASH_PAGE not in owned
        assert not pool.alloc(2, 1)  # exhausted: state unchanged
        assert pool.owned(2) == ()
        pool.check_invariants({0, 1})
        # freed pages are reusable, still exclusive
        pool.release(0)
        assert pool.alloc(2, 2)
        assert not set(pool.owned(2)) & set(pool.owned(1))
        pool.check_invariants({1, 2})

    def test_ensure_grows_on_demand(self):
        pool = PagePool(num_pages=4, page_size=4, slots=1,
                        pages_per_slot=4)
        pool.alloc(0, 1)
        assert pool.ensure(0, 4)  # fits the first page
        assert pool.pages_in_use == 1
        assert pool.ensure(0, 5)  # crosses the boundary
        assert pool.pages_in_use == 2
        assert not pool.ensure(0, 17)  # table row can hold only 4 pages
        pool.check_invariants({0})

    def test_fragmentation(self):
        pool = PagePool(num_pages=4, page_size=8, slots=2,
                        pages_per_slot=2)
        assert pool.fragmentation([0, 0]) == 0.0
        pool.alloc(0, 1)
        assert pool.fragmentation([2, 0]) == pytest.approx(0.75)

    def test_env_defaults(self, monkeypatch):
        monkeypatch.setenv("MXTPU_PAGE_SIZE", "32")
        monkeypatch.setenv("MXTPU_PAGES", "7")
        monkeypatch.setenv("MXTPU_ADMIT_MAX_QUEUE", "5")
        assert pages_mod.page_size_default() == 32
        assert pages_mod.num_pages_default(4, 10) == 7
        assert pages_mod.admit_max_queue() == 5
        monkeypatch.delenv("MXTPU_PAGES")
        assert pages_mod.num_pages_default(4, 10) == 40  # full provision


# ------------------------------------------------------- bit-parity reads
class TestPagedParity:
    def test_paged_step_bitwise_vs_dense_step(self):
        """Layer level: gather-through-table attention == the dense
        (max_len, B, H, D) cache path, bit for bit, at equal capacity."""
        mha = MultiHeadAttention(16, 2, dropout=0.0, causal=True)
        mha.initialize()
        B, S, cap = 2, 8, 8  # capacity 8 = 2 pages x 4
        x = nd.array(np.random.RandomState(1).randn(B, S, 16)
                     .astype(np.float32))
        _, k, v = mha.prefill(x[:, :1])
        kc, vc = mha.init_cache(B, cap)
        kc = jax.lax.dynamic_update_slice(kc, jnp.swapaxes(k, 0, 1),
                                          (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, jnp.swapaxes(v, 0, 1),
                                          (0, 0, 0, 0))
        kp, vp = mha.init_page_pool(5, 4)
        table = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))
        kp = kp.at[table[:, 0], 0].set(_rows(k[:, 0]))
        vp = vp.at[table[:, 0], 0].set(_rows(v[:, 0]))
        for p in range(1, S):
            od, kc, vc = mha.step(x[:, p:p + 1], kc, vc, jnp.int32(p))
            op, kp, vp = mha.paged_step(
                x[:, p:p + 1], kp, vp, table,
                jnp.full((B,), p, jnp.int32), jnp.ones((B,), bool))
            np.testing.assert_array_equal(od.asnumpy(), op.asnumpy(),
                                          err_msg=f"position {p}")
        # dense cache contents == gathered view, bit for bit
        np.testing.assert_array_equal(
            np.asarray(jnp.swapaxes(kc, 0, 1)),
            np.asarray(kp[table].reshape(B, cap, 2, 8)))

    def test_inactive_rows_write_trash_only(self):
        """A masked (inactive) row must never touch an allocated page —
        its write lands in the reserved trash page 0."""
        mha = MultiHeadAttention(16, 2, dropout=0.0, causal=True)
        mha.initialize()
        kp, vp = mha.init_page_pool(3, 4)
        table = jnp.asarray(np.array([[1], [2]], np.int32))
        x = nd.array(np.random.RandomState(0).randn(2, 1, 16)
                     .astype(np.float32))
        before_k = np.asarray(kp[1:])
        _, kp2, _ = mha.paged_step(x, kp, vp, table,
                                   jnp.zeros((2,), jnp.int32),
                                   jnp.zeros((2,), bool))
        np.testing.assert_array_equal(before_k, np.asarray(kp2[1:]))
        assert np.abs(np.asarray(kp2[0])).sum() > 0  # trash took the write

    @pytest.mark.parametrize("seed,vl,T", [
        (3, (4, 7, 8), 6),   # more requests than slots: a slot is reused
        (10, (5, 7), 4),     # two submits fill the batch: one dispatch
    ], ids=["slot_reuse", "full_batch"])
    def test_continuous_greedy_bitwise_vs_decode_n(self, tmodel, seed, vl,
                                                   T):
        """End to end: every request's greedy tokens through the paged
        scheduler == ONE hand-assembled (batch, bucket) dispatch of the
        dense engine, row for row (single-bucket menu => identical
        program shapes => bitwise logits)."""
        eng = InferStep(tmodel, max_len=24)
        rng = np.random.RandomState(seed)
        B, Ls = len(vl), 8
        src = rng.randint(3, 61, (B, Ls)).astype(np.int32)
        vl = np.array(vl, np.int32)
        toks_d, lens_d = eng.decode_n(src, vl, max_new_tokens=T)
        toks_d, lens_d = toks_d.asnumpy(), lens_d.asnumpy()
        bat = ContinuousBatcher(eng, bucket_keys=(Ls,), slots=2,
                                max_new_tokens=T, page_size=4,
                                iter_tokens=2, warmup=True)
        try:
            futs = [bat.submit(src[i, :vl[i]]) for i in range(B)]
            got = [f.result(timeout=120) for f in futs]
        finally:
            bat.stop()
        for i in range(B):
            assert got[i] == toks_d[i, :int(lens_d[i])].tolist(), f"row {i}"
        assert eng.compile_guard.steady_state_recompiles == 0
        # every page returned: free list exact after full drain
        assert bat.pool.free_pages == bat.pool.num_pages
        bat.pool.check_invariants(set())


# ------------------------------------------- Pallas kernels & speculation
class TestFlashPagedKernel:
    """ISSUE 14: the Pallas paged flash kernels (interpret mode on the
    CPU rig) against their dense references, and speculative decoding
    through the batcher against the dense engine."""

    # (page, key/value heads, query heads a group, head size, pages a row,
    #  pages a block or None for the kernel's own choice, dtype, positions)
    DECODE_CASES = {
        # the case this test had: pages of 4, two a row (table [[1, 2],
        # [3, 4]] over a pool of 5), mid-page tails
        "page4_two_pages": (4, 2, 1, 8, 2, None, "float32", [2, 6]),
        # position 0, the last key of a page, the first of the next, the
        # row's last position; two blocks of two pages
        "page16_edges": (16, 4, 1, 16, 4, 2, "float32", [0, 15, 16, 63]),
        # a row of 5 pages in blocks of 2: the last block is partial
        "page16_grouped_partial_block":
            (16, 2, 4, 16, 5, 2, "float32", [0, 31, 32, 79]),
        "page16_one_page_a_block":
            (16, 4, 1, 16, 3, 1, "float32", [5, 16, 47]),
        # granite's widths: a row of 3 pages of 128 in one step of three
        # blocks by the kernel's own rule, grouped heads
        "page128_grouped_bf16":
            (128, 8, 4, 64, 3, None, "bfloat16", [0, 127, 128, 383]),
        "page128_f32": (128, 8, 1, 64, 3, None, "float32", [1, 200, 383]),
        # the shipped widths. zaya: 2 key/value heads x 4 of 128, the pool
        # declared (pages, 256, 128) (``FLAT_POOLS``); 10 pages a row, so
        # the walk's second block of 8 is partial
        "zaya_page128_flat_pool": (128, 2, 4, 128, 10, None, "bfloat16",
                                   [0, 127, 128, 600, 1279]),
        # ouro: 16 heads of 128, 4 pages a row in blocks of 2
        "ouro_page128_h16": (128, 16, 1, 128, 4, None, "bfloat16",
                             [0, 127, 128, 300, 511]),
        # transformer-big: pages of 16, 16 heads of 64, positions 0 to 40
        # of 16 pages
        "big_page16_h16_d64": (16, 16, 1, 64, 16, None, "bfloat16",
                               [0, 15, 16, 31, 40]),
        # the walk's edges: 7 pages a row in blocks of 4 and softmax steps
        # of 2 (``WALK_STEPS``): live pages that end in a block's first
        # step, at its end, mid-block, and on the row's last page
        "page16_live_pages_end_mid_block":
            (16, 2, 2, 16, 7, 4, "float32", [3, 31, 32, 47, 70, 111]),
        "page16_one_row": (16, 4, 1, 16, 5, 2, "float32", [40]),
    }
    # cases whose pools are declared as the kernels read them, ``(pages,
    # page x Hkv, D)`` with ``kv_heads=`` saying ``Hkv``
    FLAT_POOLS = {"zaya_page128_flat_pool"}
    # pages a softmax step of the walk where a case sets its block; the
    # others take one step a block
    WALK_STEPS = {"page16_live_pages_end_mid_block": 2,
                  "page16_grouped_partial_block": 1}
    FORMS = ("walk", "pipeline")
    # (page, heads, head size, pages a row, pages a block, dtype, window,
    #  offsets, real queries a row)
    WINDOW_CASES = {
        # the case this test had: a suffix-replay row, a padded query
        "page4_offset_and_padding":
            (4, 2, 8, 2, None, "float32", 3, [0, 5], [3, 2]),
        # windows that start at 0, cross a page, cross a block, and end on
        # the row's last position; padding in two rows
        "page16_s4": (16, 4, 16, 4, 2, "float32", 4,
                      [0, 13, 30, 60], [4, 2, 4, 1]),
        "page16_s1": (16, 4, 16, 5, 2, "float32", 1, [0, 16, 79], [1, 1, 1]),
        "page128_s4_bf16": (128, 8, 64, 3, None, "bfloat16", 4,
                            [0, 125, 380], [4, 3, 4]),
    }
    # float32 pools: the tolerance these tests always had. bfloat16 pools:
    # the output's own rounding (2 ** -8 of values up to about 2) and the
    # probabilities' cast for the second product
    TOL = {"float32": dict(rtol=1e-5, atol=1e-5),
           "bfloat16": dict(rtol=2e-2, atol=2e-2)}

    @staticmethod
    def _decode(pfa, form):
        """``paged_decode_attention`` in one of its two forms, whatever
        the entry point would pick for the head size."""
        return {"walk": pfa._decode_walk,
                "pipeline": pfa._decode_window}[form]

    def _paged(self, rng, B, ps, Hkv, D, P, dtype, block_pages, monkeypatch,
               step_pages=None):
        """Pools whose page 0 is the trash page, a table with each row's
        own pages, and the kernels' blocks set to ``block_pages`` pages
        (the walk's softmax step to ``step_pages``, a block by default)."""
        from mxnet_tpu.ops.pallas import paged_flash_attention as pfa
        kp = jnp.asarray(rng.randn(B * P + 1, ps, Hkv, D), dtype)
        vp = jnp.asarray(rng.randn(B * P + 1, ps, Hkv, D), dtype)
        table = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
        if block_pages is not None:
            # a grid step of two blocks, so that rows take several steps
            # of several blocks each
            page_bytes = ps * Hkv * D * jnp.dtype(dtype).itemsize
            monkeypatch.setattr(pfa, "_WINDOW_BLOCK_BYTES",
                                block_pages * page_bytes)
            monkeypatch.setattr(pfa, "_WINDOW_STEP_BYTES",
                                2 * block_pages * page_bytes)
            assert pfa._window_tiles(
                P, ps, Hkv, D, jnp.dtype(dtype).itemsize) == (
                    min(P, 2 * block_pages), min(P, block_pages))
            step_pages = step_pages or block_pages
            monkeypatch.setattr(pfa, "_DECODE_BLOCK_KEYS", block_pages * ps)
            monkeypatch.setattr(pfa, "_DECODE_STEP_BYTES",
                                step_pages * page_bytes)
            assert pfa._decode_tiles(
                P, ps, Hkv, D, jnp.dtype(dtype).itemsize) == (
                    min(P, block_pages),
                    math.gcd(min(P, block_pages), step_pages))
        return pfa, kp, vp, table

    @pytest.mark.parametrize("form", FORMS)
    @pytest.mark.parametrize("case", sorted(DECODE_CASES))
    def test_decode_kernel_matches_reference(self, case, form, monkeypatch):
        ps, Hkv, G, D, P, block, dtype, pos = self.DECODE_CASES[case]
        rng = np.random.RandomState(0)
        B = len(pos)
        pfa, kp, vp, table = self._paged(rng, B, ps, Hkv, D, P, dtype,
                                         block, monkeypatch,
                                         self.WALK_STEPS.get(case))
        q = jnp.asarray(rng.randn(B, Hkv * G, D), dtype)
        pos = jnp.asarray(np.array(pos, np.int32))
        flat = (lambda p: p.reshape(p.shape[0], ps * Hkv, D)) \
            if case in self.FLAT_POOLS else (lambda p: p)
        got = self._decode(pfa, form)(q, flat(kp), flat(vp),
                                      jnp.asarray(table), pos, D ** -0.5, Hkv)
        # query head i reads key/value head i // G: the reference takes
        # one key/value head a query head
        want = pfa.paged_decode_reference(
            q, jnp.repeat(kp, G, axis=2), jnp.repeat(vp, G, axis=2),
            jnp.asarray(table), pos, sm_scale=D ** -0.5)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **self.TOL[dtype])

    @pytest.mark.parametrize("D,form", [(128, "walk"), (256, "walk"),
                                        (64, "pipeline"), (16, "pipeline")])
    def test_entry_point_picks_the_form_by_the_head_size(self, D, form,
                                                         monkeypatch):
        """Heads of whole lanes walk the live pages with the kernel's own
        copies; Mosaic copies no page out of a pool whose rows are
        narrower, so those keep the pipeline's page operands. The choice
        reads the query's last axis and nothing else."""
        from mxnet_tpu.ops.pallas import paged_flash_attention as pfa
        took = []
        for name in ("walk", "window"):
            monkeypatch.setattr(
                pfa, f"_decode_{name}",
                lambda q, *a, _n=name: (took.append(_n), q)[1])
        q = jnp.zeros((2, 4, D), jnp.bfloat16)
        pool = jnp.zeros((5, 8, 2, D), jnp.bfloat16)
        pfa.paged_decode_attention(q, pool, pool, jnp.zeros((2, 2), jnp.int32),
                                   jnp.zeros((2,), jnp.int32), sm_scale=1.0)
        assert took == [{"walk": "walk", "pipeline": "window"}[form]]

    # rows of the batch: a position, or None for a row that is not live
    # at all (position -1, its table on the trash page). Between them
    # rows parked on the trash page at a position of their own, as the
    # burst leaves an inactive row
    WALK_ROWS = {
        # blocks of 4 pages in steps of 2: a dead row first, so that the
        # first live row starts the walk; two dead rows in a row; a dead
        # row last
        "dead_rows_between_live_ones":
            (4, 2, [None, 5, "trash:40", None, 63, 16, "trash:0", 111, None]),
        "one_live_row_among_dead_ones": (4, 4, [None, "trash:70", 100, None]),
        "a_page_a_block": (1, 1, [0, None, 17, "trash:3", 111]),
        "no_live_row": (2, 2, [None, None]),
    }

    @pytest.mark.parametrize("case", sorted(WALK_ROWS))
    def test_walk_skips_dead_rows_and_reads_no_dead_page(self, case,
                                                         monkeypatch):
        """The pool's untouched pages AND the trash page hold NaN and inf:
        a row below position 0 copies nothing and hands back zeros, a row
        parked on the trash page reads it alone, and neither a dead page
        nor what a block's dead tail still holds from an earlier row (the
        trash page's NaN among it) reaches a live row's output."""
        block, step, rows = self.WALK_ROWS[case]
        rng = np.random.RandomState(11)
        ps, Hkv, G, D, P = 16, 2, 2, 16, 7
        B = len(rows)
        pfa, kp, vp, table = self._paged(rng, B, ps, Hkv, D, P, "float32",
                                         block, monkeypatch, step)
        pos = np.zeros(B, np.int32)
        live = np.zeros(B, bool)
        for b, row in enumerate(rows):
            if row is None:
                pos[b], table[b] = -1, 0
            elif isinstance(row, str):
                pos[b], table[b] = int(row.split(":")[1]), 0
            else:
                pos[b], live[b] = row, True
        clean_k, clean_v = kp, vp
        bad = jnp.asarray(np.resize([np.nan, np.inf, -np.inf],
                                    kp.shape[1:]), kp.dtype)
        for page in {0} | {int(table[b, p]) for b in range(B)
                           for p in range(pos[b] // ps + 1, P)}:
            kp, vp = kp.at[page].set(bad), vp.at[page].set(bad)
        q = jnp.asarray(rng.randn(B, Hkv * G, D).astype(np.float32))
        args = (jnp.asarray(table), jnp.asarray(pos))
        got = np.asarray(pfa._decode_walk(q, kp, vp, *args, 0.25, Hkv))
        want = np.asarray(pfa.paged_decode_reference(
            q, jnp.repeat(clean_k, G, axis=2), jnp.repeat(clean_v, G, axis=2),
            args[0], jnp.maximum(args[1], 0), sm_scale=0.25))
        np.testing.assert_allclose(got[live], want[live], rtol=1e-5,
                                   atol=1e-5)
        assert np.abs(got[pos < 0]).sum() == 0.0

    @pytest.mark.parametrize("case", sorted(WINDOW_CASES))
    def test_window_kernel_matches_reference_offset_and_padding(
            self, case, monkeypatch):
        ps, H, D, P, block, dtype, S, off, vl = self.WINDOW_CASES[case]
        rng = np.random.RandomState(1)
        B = len(off)
        pfa, kp, vp, table = self._paged(rng, B, ps, H, D, P, dtype, block,
                                         monkeypatch)
        q = jnp.asarray(rng.randn(B, S, H, D), dtype)
        off = jnp.asarray(np.array(off, np.int32))
        vl = jnp.asarray(np.array(vl, np.int32))
        got = pfa.paged_window_attention(q, kp, vp, jnp.asarray(table), off,
                                         vl, sm_scale=D ** -0.5)
        want = pfa.paged_window_reference(q, kp, vp, jnp.asarray(table), off,
                                          vl, sm_scale=D ** -0.5)
        got = np.asarray(got, np.float32)
        np.testing.assert_allclose(got, np.asarray(want, np.float32),
                                   **self.TOL[dtype])
        # padded query rows finalize to exact zero in both
        for b in range(B):
            assert np.abs(got[b, int(vl[b]):]).sum() == 0.0

    @pytest.mark.parametrize("form", FORMS)
    @pytest.mark.parametrize("block_pages", [1, 2, 4])
    def test_kernel_reads_no_page_past_the_position(self, block_pages, form,
                                                    monkeypatch):
        """A table's entries past a row's position may point anywhere (a
        retired request's pages, the trash page): the kernel fetches none
        of them, whole blocks or the tail of a live block, so what they
        hold (NaN here) reaches no output; and an inactive row parked on
        the trash page beside live rows moves none of them."""
        rng = np.random.RandomState(3)
        ps, H, D, P = 16, 4, 16, 4
        pos = np.array([0, 15, 16, 40, 63, 7], np.int32)
        B = len(pos)
        pfa, kp, vp, table = self._paged(rng, B, ps, H, D, P, "float32",
                                         block_pages, monkeypatch)
        table[5] = 0                     # the inactive row: trash page
        clean_k, clean_v = kp, vp
        for b in range(B - 1):
            for p in range(int(pos[b]) // ps + 1, P):
                kp = kp.at[table[b, p]].set(np.nan)
                vp = vp.at[table[b, p]].set(np.nan)
        q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
        args = (jnp.asarray(table), jnp.asarray(pos))
        got = np.asarray(self._decode(pfa, form)(q, kp, vp, *args, 0.25, H))
        want = np.asarray(pfa.paged_decode_reference(
            q, clean_k, clean_v, *args, sm_scale=0.25))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got[:5], want[:5], rtol=1e-5, atol=1e-5)

    # a pool that keeps FOUR planes under one page table, ``(4, num_pages,
    # page, H, D)``, is read by the kernels as they are: flattened over
    # planes and pages, through the table plus ``t * num_pages``.
    # (page, key/value heads, query heads a group, head size, pages a row,
    #  pages a block, dtype, positions)
    PLANE_CASES = {
        "page4_two_pages": (4, 2, 1, 8, 2, None, "float32", [2, 6]),
        "page16_grouped_partial_block":
            (16, 2, 4, 16, 5, 2, "float32", [0, 31, 32, 79]),
        # the looped model's widths: 16 heads of 128, a row of 4 pages
        "page128_h16_bf16": (128, 16, 1, 128, 4, None, "bfloat16",
                             [0, 127, 300]),
    }

    def _planes(self, rng, planes, B, ps, Hkv, D, P, dtype, block,
                monkeypatch):
        pfa, _, _, table = self._paged(rng, B, ps, Hkv, D, P, dtype, block,
                                       monkeypatch)
        shape = (planes, B * P + 1, ps, Hkv, D)
        return pfa, jnp.asarray(rng.randn(*shape), dtype), \
            jnp.asarray(rng.randn(*shape), dtype), jnp.asarray(table)

    @staticmethod
    def _every_plane(call, kp, vp, table, like):
        """``call(k pages, v pages, table of plane t)`` for a TRACED ``t``
        inside a ``fori_loop``, stacked over the four planes."""
        N = kp.shape[1]

        @jax.jit
        def run(kp, vp):
            def body(t, out):
                assert isinstance(t, jax.core.Tracer)
                return out.at[t].set(call(
                    kp.reshape((-1,) + kp.shape[2:]),
                    vp.reshape((-1,) + vp.shape[2:]), table + t * N))
            return jax.lax.fori_loop(
                0, 4, body, jnp.zeros((4,) + like.shape, like.dtype))
        return np.asarray(run(kp, vp), np.float32)

    @pytest.mark.parametrize("form", FORMS)
    @pytest.mark.parametrize("case", sorted(PLANE_CASES))
    def test_decode_kernel_reads_a_plane_through_a_moved_table(
            self, case, form, monkeypatch):
        ps, Hkv, G, D, P, block, dtype, pos = self.PLANE_CASES[case]
        rng = np.random.RandomState(5)
        B = len(pos)
        pfa, kp, vp, table = self._planes(rng, 4, B, ps, Hkv, D, P, dtype,
                                          block, monkeypatch)
        q = jnp.asarray(rng.randn(B, Hkv * G, D), dtype)
        pos = jnp.asarray(np.array(pos, np.int32))
        got = self._every_plane(
            lambda k, v, pt: self._decode(pfa, form)(
                q, k, v, pt, pos, D ** -0.5, Hkv), kp, vp, table, q)
        for t in range(4):
            want = pfa.paged_decode_reference(
                q, jnp.repeat(kp[t], G, axis=2), jnp.repeat(vp[t], G, axis=2),
                table, pos, sm_scale=D ** -0.5)
            np.testing.assert_allclose(got[t], np.asarray(want, np.float32),
                                       **self.TOL[dtype])
        assert np.abs(got[0] - got[3]).max() > 0.05

    @pytest.mark.parametrize("case", ["page4_offset_and_padding",
                                      "page16_s4", "page128_s4_bf16"])
    def test_window_kernel_reads_a_plane_through_a_moved_table(
            self, case, monkeypatch):
        ps, H, D, P, block, dtype, S, off, vl = self.WINDOW_CASES[case]
        rng = np.random.RandomState(6)
        B = len(off)
        pfa, kp, vp, table = self._planes(rng, 4, B, ps, H, D, P, dtype,
                                          block, monkeypatch)
        q = jnp.asarray(rng.randn(B, S, H, D), dtype)
        off = jnp.asarray(np.array(off, np.int32))
        vl = jnp.asarray(np.array(vl, np.int32))
        got = self._every_plane(
            lambda k, v, pt: pfa.paged_window_attention(
                q, k, v, pt, off, vl, sm_scale=D ** -0.5), kp, vp, table, q)
        for t in range(4):
            want = pfa.paged_window_reference(q, kp[t], vp[t], table, off,
                                              vl, sm_scale=D ** -0.5)
            np.testing.assert_allclose(got[t], np.asarray(want, np.float32),
                                       **self.TOL[dtype])
        assert np.abs(got[1] - got[2]).max() > 0.05

    def test_selected_window_reads_a_plane_through_a_moved_table(
            self, monkeypatch):
        """The chunk's kernel over a selected set and its ``jax.numpy``
        form, on plane ``t`` of a pool of four planes."""
        from mxnet_tpu.ops import sparse_attention as dsa
        rng = np.random.RandomState(7)
        ps, H, D, P, C = 8, 4, 16, 4, 16
        pfa, kp, vp, table = self._planes(rng, 4, 2, ps, H, D, P, "float32",
                                          None, monkeypatch)
        q = jnp.asarray(rng.randn(2, C, H, D).astype(np.float32))
        off = jnp.asarray(np.array([0, 13], np.int32))
        q_pos = off[:, None] + jnp.arange(C)[None, :]
        mask = jnp.arange(P * ps)[None, None, :] <= q_pos[:, :, None]
        like = jnp.zeros((2, C, H * D), jnp.float32)
        got = self._every_plane(
            lambda k, v, pt: pfa.paged_selected_window_attention(
                q, k, v, pt, off, mask, sm_scale=0.25), kp, vp, table, like)
        loop = self._every_plane(
            lambda k, v, pt: dsa.selected_window_attention(
                q, k, v, pt, off, mask, 2, 16, 0.25), kp, vp, table, like)
        for t in range(4):
            want = pfa.paged_selected_window_reference(
                q, kp[t], vp[t], table, off, mask, sm_scale=0.25)
            np.testing.assert_allclose(got[t], want, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(loop[t], want, rtol=1e-5, atol=1e-5)

    def test_window_tiling_is_a_function_of_the_shapes(self):
        """``(pages a grid step, pages a block)``: granite's row of 12
        pages of 128 keys (8 key/value heads of 64, bfloat16) is one step
        of blocks of a page, transformer-big's 16 pages of 16 (16 heads of
        64) one step of blocks of 4; never more than a row has; a 16k row
        of granite's takes steps of 16 pages."""
        from mxnet_tpu.ops.pallas import paged_flash_attention as pfa
        assert pfa._window_tiles(12, 128, 8, 64, 2) == (12, 1)
        assert pfa._window_tiles(16, 16, 16, 64, 2) == (16, 4)
        assert pfa._window_tiles(128, 128, 8, 64, 2) == (16, 1)
        assert pfa._window_tiles(2, 16, 8, 64, 4) == (2, 2)   # the smoke run
        assert pfa._window_tiles(1, 128, 8, 64, 2) == (1, 1)  # a row of one
        assert pfa._window_tiles(12, 128, 8, 64, 4) == (8, 1)  # float32
        assert pfa._window_tiles(3, 4, 2, 8, 4) == (3, 3)     # these tests'

    @pytest.mark.parametrize("cell,P,page,Hkv,S", [
        ("granite decode", 12, 128, 8, 4),
        ("transformer-big decode", 16, 16, 16, 1),
        ("transformer-big widest warm-up window", 16, 16, 16, 16)])
    def test_window_vmem_reckoning_is_under_the_limit_passed(
            self, cell, P, page, Hkv, S):
        """What a grid step holds at the published shapes is under the
        limit the kernel hands the compiler (which refuses what does not
        fit: tests/test_tpu_compile.py compiles the same shapes)."""
        from mxnet_tpu.ops.pallas import paged_flash_attention as pfa
        pages, block = pfa._window_tiles(P, page, Hkv, 64, 2)
        need = pfa._window_vmem_bytes(pages, block, page, Hkv, S, 64,
                                      itemsize=2)
        assert 1 << 20 < need < pfa._WINDOW_STEP_VMEM_LIMIT
        assert pfa._WINDOW_STEP_VMEM_LIMIT <= 64 << 20

    def test_forced_kernel_paged_step_matches_fallback(self, paged_kernels):
        """Layer level: ``paged_step`` with the kernel forced (interpret
        mode here) == the dense gather fallback to fp tolerance."""
        mha = MultiHeadAttention(16, 2, dropout=0.0, causal=True)
        mha.initialize()
        rng = np.random.RandomState(2)
        table = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))
        x = nd.array(rng.randn(2, 1, 16).astype(np.float32))
        x0 = nd.array(rng.randn(2, 1, 16).astype(np.float32))
        outs = {}
        for mode in ("0", "force"):
            paged_kernels(mode == "force")
            kp, vp = mha.init_page_pool(5, 4)
            _, k, v = mha.prefill(x0)
            kp = kp.at[table[:, 0], 0].set(_rows(k[:, 0]))
            vp = vp.at[table[:, 0], 0].set(_rows(v[:, 0]))
            o, _, _ = mha.paged_step(x, kp, vp, table,
                                     jnp.ones((2,), jnp.int32),
                                     jnp.ones((2,), bool))
            outs[mode] = o.asnumpy()
        np.testing.assert_allclose(outs["force"], outs["0"],
                                   rtol=1e-5, atol=1e-5)

    def test_kernel_active_rows_isolated_from_trash_page(self,
                                                         paged_kernels):
        """Inactive rows park their table on trash page 0; the kernel's
        in-place page walk must give active rows identical output no
        matter what garbage page 0 holds."""
        paged_kernels(True)
        mha = MultiHeadAttention(16, 2, dropout=0.0, causal=True)
        mha.initialize()
        rng = np.random.RandomState(3)
        table = jnp.asarray(np.array([[1, 2], [0, 0]], np.int32))
        active = jnp.asarray(np.array([True, False]))
        x = nd.array(rng.randn(2, 1, 16).astype(np.float32))
        kp, vp = mha.init_page_pool(5, 4)
        _, k, v = mha.prefill(nd.array(
            rng.randn(2, 1, 16).astype(np.float32)))
        kp = kp.at[table[:, 0], 0].set(_rows(k[:, 0]))
        vp = vp.at[table[:, 0], 0].set(_rows(v[:, 0]))
        o_clean, kp2, _ = mha.paged_step(x, kp, vp, table,
                                         jnp.ones((2,), jnp.int32), active)
        # poison the trash page with huge values and replay
        kp_bad = kp.at[0].set(1e9)
        vp_bad = vp.at[0].set(-1e9)
        o_bad, _, _ = mha.paged_step(x, kp_bad, vp_bad, table,
                                     jnp.ones((2,), jnp.int32), active)
        np.testing.assert_array_equal(o_clean.asnumpy()[0],
                                      o_bad.asnumpy()[0])
        # the inactive row's write landed on trash page 0 only: every
        # page beyond the active row's current one is untouched
        np.testing.assert_array_equal(np.asarray(kp[2:]),
                                      np.asarray(kp2[2:]))

    def test_spec_batcher_bitwise_vs_decode_n(self, tmodel):
        """End to end: speculative rounds through the scheduler emit the
        SAME greedy tokens as the dense engine — with an oracle draft
        (weight copy, full acceptance) AND a garbage draft (near-zero
        acceptance): the acceptance rule only sets the burst length."""
        rng = np.random.RandomState(5)
        B, Ls, T = 3, 8, 6
        src = rng.randint(3, 61, (B, Ls)).astype(np.int32)
        vl = np.array([4, 7, 8], np.int32)
        ref_eng = InferStep(tmodel, max_len=24)
        toks_d, lens_d = ref_eng.decode_n(src, vl, max_new_tokens=T)
        toks_d, lens_d = toks_d.asnumpy(), lens_d.asnumpy()
        ref = [toks_d[i, :int(lens_d[i])].tolist() for i in range(B)]

        oracle = _make_transformer(seed=0)   # same seed = same weights
        tp = {n.split("_", 1)[1]: p
              for n, p in tmodel.collect_params().items()}
        for name, p in oracle.collect_params().items():
            p.set_data(nd.NDArray(tp[name.split("_", 1)[1]]._data.data))
        garbage = _make_transformer(seed=7)
        for draft, tag in ((oracle, "oracle"), (garbage, "garbage")):
            eng = InferStep(tmodel, max_len=24)
            eng.attach_draft(draft)
            bat = ContinuousBatcher(eng, bucket_keys=(Ls,), slots=2,
                                    max_new_tokens=T, page_size=4,
                                    iter_tokens=2, spec_k=3, warmup=True)
            assert bat._spec_on
            try:
                futs = [bat.submit(src[i, :vl[i]]) for i in range(B)]
                got = [f.result(timeout=120) for f in futs]
            finally:
                bat.stop()
            assert got == ref, tag
            assert eng.compile_guard.steady_state_recompiles == 0, tag
            assert bat.pool.free_pages == bat.pool.num_pages, tag
            bat.pool.check_invariants(set())


# ------------------------------------------------- scheduler behaviour
class TestContinuousBatcher:
    def _batcher(self, tmodel, **kw):
        eng = InferStep(tmodel, max_len=24)
        cfg = dict(bucket_keys=(8,), slots=2, max_new_tokens=6,
                   page_size=4, iter_tokens=2, warmup=True)
        cfg.update(kw)
        return ContinuousBatcher(eng, **cfg), eng

    def test_requires_paged_protocol(self):
        from mxnet_tpu.gluon.model_zoo.bert import BERTModel

        bert = BERTModel(vocab_size=31, units=16, hidden_size=32,
                         num_layers=1, num_heads=2, max_length=32,
                         dropout=0.0)
        bert.initialize()
        bert._probe_shapes(nd.zeros((2, 8), dtype="int32"))
        with pytest.raises(MXNetError):
            ContinuousBatcher(InferStep(bert), bucket_keys=(8,))

    def test_request_validation(self, tmodel):
        bat, eng = self._batcher(tmodel, bucket_keys=(8, 12), warmup=False,
                                 start=False)
        with pytest.raises(MXNetError):
            bat.submit(np.zeros((13,), np.int32))  # > largest bucket
        with pytest.raises(MXNetError):
            bat.submit([3, 4], max_new_tokens=99)  # > batcher max_new
        with pytest.raises(MXNetError, match="paged protocol"):
            ContinuousBatcher(object(), bucket_keys=(8,))
        with pytest.raises(MXNetError):
            ContinuousBatcher(eng, bucket_keys=())

    def test_per_request_max_new_trim(self, tmodel):
        """A request's own max_new_tokens (< the batcher's) trims its
        result: the row retires at its own cap."""
        bat, _ = self._batcher(tmodel)
        try:
            fut = bat.submit([7, 8, 9, 10], max_new_tokens=2)
            assert len(fut.result(timeout=60)) <= 2
        finally:
            bat.stop()
        assert bat.pool.free_pages == bat.pool.num_pages

    def test_warmed_batcher_zero_steady_recompiles(self, tmodel):
        """warmup=True compiles the whole (rows, bucket) menu up front;
        serving traffic across both buckets then never compiles."""
        bat, eng = self._batcher(tmodel, bucket_keys=(8, 12))
        assert eng.compile_guard.steady
        rng = np.random.RandomState(11)
        try:
            for n in (5, 10, 8, 12):  # both buckets, repeated
                fut = bat.submit(rng.randint(3, 61, (n,)).astype(np.int32))
                fut.result(timeout=60)
        finally:
            bat.stop()
        assert eng.compile_guard.steady_state_recompiles == 0

    def test_pool_too_small_for_one_request_raises(self, tmodel):
        eng = InferStep(tmodel, max_len=24)
        with pytest.raises(MXNetError, match="pages"):
            ContinuousBatcher(eng, bucket_keys=(8,), slots=1,
                              max_new_tokens=32, page_size=2, num_pages=3)

    def test_streaming_tokens_iter(self, tmodel):
        bat, _ = self._batcher(tmodel)
        try:
            fut = bat.submit(np.array([5, 6, 7], np.int32))
            chunks = list(fut.tokens_iter(timeout=60))
        finally:
            bat.stop()
        flat = [t for c in chunks for t in c]
        assert flat == fut.result()
        # per-iteration granularity: more than one chunk for 6 tokens at
        # iter_tokens=2 (first from admission, the rest per iteration)
        assert len(chunks) >= 2
        assert fut.first_token_at is not None
        assert fut.first_token_at >= fut.enqueued_at

    def test_slot_reuse_keeps_occupancy(self, tmodel):
        """More requests than slots: retired rows hand their slots to
        queued requests mid-stream (iterations << what a fixed batcher
        would need) and the pool ends exact."""
        bat, eng = self._batcher(tmodel, slots=2)
        rng = np.random.RandomState(0)
        try:
            futs = [bat.submit(rng.randint(3, 61, (5,)).astype(np.int32),
                               max_new_tokens=2 + (i % 5))
                    for i in range(8)]
            for f in futs:
                f.result(timeout=120)
        finally:
            bat.stop()
        assert bat.stats["retired"] == 8
        assert bat.stats["admitted"] == 8
        assert bat.pool.free_pages == bat.pool.num_pages
        assert eng.compile_guard.steady_state_recompiles == 0

    def test_deadline_retires_mid_decode(self, tmodel):
        """A deadline passing DURING decode retires the row at the next
        iteration boundary (DeadlineExceeded), frees its pages, and the
        other slots keep decoding."""
        bat, _ = self._batcher(tmodel, max_new_tokens=32, page_size=4,
                               iter_tokens=1)
        try:
            doomed = bat.submit([5, 6, 7], deadline_ms=1.0)
            ok = bat.submit([8, 9, 10], max_new_tokens=4)
            with pytest.raises(DeadlineExceeded):
                doomed.result(timeout=60)
            assert len(ok.result(timeout=60)) <= 4
        finally:
            bat.stop()
        assert bat.pool.free_pages == bat.pool.num_pages

    def test_preemption_restarts_and_completes(self, tmodel):
        """Pool oversubscription: the youngest row is preempted (pages
        freed, request restarted) and every request still completes with
        the full greedy result."""
        eng = InferStep(tmodel, max_len=24)
        bat = ContinuousBatcher(eng, bucket_keys=(8,), slots=2,
                                max_new_tokens=8, page_size=2,
                                num_pages=5, iter_tokens=2, warmup=True)
        rng = np.random.RandomState(3)
        try:
            futs = [bat.submit(rng.randint(3, 61, (6,)).astype(np.int32),
                               max_new_tokens=8) for _ in range(3)]
            got = [f.result(timeout=120) for f in futs]
        finally:
            bat.stop()
        assert all(len(g) == 8 for g in got)
        assert bat.stats["preempted"] >= 1
        assert bat.pool.free_pages == bat.pool.num_pages
        bat.pool.check_invariants(set())

    def test_backpressure_rejects_at_submit(self, tmodel):
        bat, _ = self._batcher(tmodel, admit_max_queue=0)
        try:
            fut = bat.submit([5, 6, 7])
            assert isinstance(fut.exception(), Backpressure)
            assert bat.stats["rejected"] == 1
        finally:
            bat.stop()

    def test_free_page_watermark_defers_admission(self, tmodel):
        """With a watermark covering the whole pool, admission defers
        while pages are in use (the queued request waits its turn instead
        of fragmenting the pool)."""
        eng = InferStep(tmodel, max_len=24)
        bat = ContinuousBatcher(eng, bucket_keys=(8,), slots=2,
                                max_new_tokens=4, page_size=2,
                                num_pages=6, iter_tokens=1,
                                admit_free_pages=3, warmup=True)
        rng = np.random.RandomState(1)
        try:
            futs = [bat.submit(rng.randint(3, 61, (5,)).astype(np.int32))
                    for _ in range(4)]
            for f in futs:
                assert len(f.result(timeout=120)) <= 4
        finally:
            bat.stop()
        assert bat.pool.free_pages == bat.pool.num_pages

    def test_submit_after_stop_fails_fast(self, tmodel):
        bat, _ = self._batcher(tmodel)
        bat.stop()
        fut = bat.submit([3, 4, 5])
        assert isinstance(fut.exception(), RuntimeError)
        assert "not accepting" in str(fut.exception())
        assert bat.pool.free_pages == bat.pool.num_pages

    @pytest.mark.parametrize("after", [1, 0],
                             ids=["decode_burst", "admission_prefill"])
    def test_dispatch_error_fails_slots_not_thread(self, tmodel, after):
        """An engine error (mid-iteration, or in the admission prefill)
        resolves the in-flight futures with the exception and rebuilds
        the pools; the scheduler thread survives and keeps serving."""
        bat, _ = self._batcher(tmodel)
        try:
            faults.inject("batcher.dispatch", times=1, after=after)
            fut = bat.submit([3, 4, 5], max_new_tokens=6)
            with pytest.raises(faults.FaultInjected):
                fut.result(timeout=60)
            assert bat.healthy
            ok = bat.submit([6, 7, 8], max_new_tokens=2)
            assert len(ok.result(timeout=60)) <= 2
        finally:
            faults.clear()
            bat.stop()
        assert bat.pool.free_pages == bat.pool.num_pages

    def test_telemetry_fields(self, tmodel):
        mx.telemetry.reset()
        mx.telemetry.enable()
        try:
            bat, _ = self._batcher(tmodel)
            fut = bat.submit([5, 6, 7])
            fut.result(timeout=60)
            bat.stop()
            rep = mx.telemetry.report()
            assert rep["infer_ttft_ms_p50"] is not None
            assert rep["infer_pages_in_use"] is not None
            assert rep["infer_page_fragmentation"] is not None
            assert rep["infer_admitted_per_iter_p50"] is not None
            assert rep["infer_rejected_backpressure"] == 0
            assert rep["infer_requests"] >= 1
        finally:
            mx.telemetry.reset()

    def test_sustained_occupancy_stat(self, tmodel):
        bat, _ = self._batcher(tmodel)
        rng = np.random.RandomState(5)
        try:
            futs = [bat.submit(rng.randint(3, 61, (5,)).astype(np.int32))
                    for _ in range(6)]
            for f in futs:
                f.result(timeout=120)
        finally:
            bat.stop()
        assert 0.0 < bat.sustained_occupancy <= 1.0
        assert bat.stats["iterations"] > 0


# ------------------------------------------------------- API routing
class TestRouting:
    def test_make_batcher_default_and_refusals(self, tmodel):
        """The default is ``ContinuousBatcher`` with every keyword handed
        on; a net without the paged protocol is refused by name, and so
        is a keyword the scheduler does not take (nothing is dropped)."""
        eng = InferStep(tmodel, max_len=24)
        bat = make_batcher(eng, bucket_keys=(8,), slots=2,
                           max_new_tokens=4, iter_tokens=3, start=False)
        assert type(bat) is ContinuousBatcher and bat.iter_tokens == 3
        with pytest.raises(MXNetError, match="paged protocol"):
            make_batcher(object(), bucket_keys=(8,))
        with pytest.raises(TypeError):
            make_batcher(eng, bucket_keys=(8,), timeout_ms=5.0, start=False)

    def test_generate_routes_through_continuous(self, tmodel):
        src = np.random.RandomState(2).randint(3, 61, (2, 7)) \
            .astype(np.int32)
        toks_c, lens_c = tmodel.generate(src, max_new_tokens=4, max_len=24)
        assert getattr(tmodel, "_batchers", None), \
            "greedy generate must route through the ContinuousBatcher"
        toks_d, lens_d = InferStep(tmodel, max_len=24).generate(
            src, max_new_tokens=4)
        np.testing.assert_array_equal(toks_c.asnumpy(), toks_d.asnumpy())
        np.testing.assert_array_equal(lens_c.asnumpy(), lens_d.asnumpy())

    def test_generate_sampling_seed_stays_direct(self, tmodel):
        src = np.random.RandomState(2).randint(3, 61, (2, 7)) \
            .astype(np.int32)
        before = dict(getattr(tmodel, "_batchers", {}) or {})
        a, _ = tmodel.generate(src, max_new_tokens=3, max_len=24,
                               method="top_k", top_k=4, seed=9)
        b, _ = tmodel.generate(src, max_new_tokens=3, max_len=24,
                               method="top_k", top_k=4, seed=9)
        np.testing.assert_array_equal(a.asnumpy(), b.asnumpy())
        after = dict(getattr(tmodel, "_batchers", {}) or {})
        assert before == after  # no batcher built for seeded sampling

    def test_estimator_predict_through_batcher(self, tmodel):
        from mxnet_tpu.gluon.contrib.estimator import Estimator
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

        eng = InferStep(tmodel, max_len=24)
        bat = ContinuousBatcher(eng, bucket_keys=(8,), slots=2,
                                max_new_tokens=4, page_size=4,
                                iter_tokens=2, warmup=True)
        rng = np.random.RandomState(3)
        src = rng.randint(3, 61, (2, 7)).astype(np.int32)
        vl = np.array([5, 7], np.int32)
        est = Estimator(tmodel, SoftmaxCrossEntropyLoss())
        try:
            outs = est.predict([(src, vl)], engine=bat)
        finally:
            bat.stop()
        assert len(outs) == 1
        toks, lengths = outs[0]
        assert toks.shape == (2, 4) and lengths.shape == (2,)
        ref_t, ref_l = eng.decode_n(src, vl, max_new_tokens=4)
        np.testing.assert_array_equal(toks.asnumpy(), ref_t.asnumpy())


# --------------------------------------------------- self-healing interop
class TestPagedResilience:
    @pytest.mark.chaos
    def test_replica_crash_frees_pages_and_fails_over(self, tmodel):
        """Kill one replica's scheduler mid-decode: its pages return to
        the free list, its in-flight/queued requests fail over through
        the Router, and every future still resolves."""

        def make_replica(name):
            eng = InferStep(tmodel, max_len=24)
            bat = ContinuousBatcher(eng, bucket_keys=(8,), slots=2,
                                    max_new_tokens=8, page_size=4,
                                    iter_tokens=1, warmup=True, name=name)
            return Replica(name, bat)

        mx.telemetry.reset()
        r0, r1 = make_replica("pg-r0"), make_replica("pg-r1")
        router = Router([r0, r1], retry_backoff_s=0.01,
                        health_interval_s=0.02)
        rng = np.random.RandomState(7)
        # let r1 run a couple of scheduler iterations, then die mid-decode
        faults.inject("batcher.thread", times=1, after=3, match="pg-r1")
        try:
            futs = [router.submit(rng.randint(3, 61, (6,))
                                  .astype(np.int32), max_new_tokens=8)
                    for _ in range(10)]
            results = [f.result(timeout=120) for f in futs]
        finally:
            faults.clear()
            router.stop()
        assert all(len(r) == 8 for r in results)
        reg = mx.telemetry.registry()
        assert reg.counter("serve/failovers").value >= 1
        # the dead replica's pool is exact again: eviction freed its pages
        for rep in (r0, r1):
            assert rep.batcher.pool.free_pages == rep.batcher.pool.num_pages
            rep.batcher.pool.check_invariants(set())
        mx.telemetry.reset()

    def test_admission_failure_poisons_not_kills(self, tmodel):
        """ISSUE 15 regression (mxlint resource-leak.leak-on-raise): an
        exception during admission — a partial ``_stage_slot`` that
        already adopted prefix pages — must hit the poison path (fail
        slots, reset the pool, keep the scheduler alive), not unwind the
        thread with pages still referenced. Before the fix, _retire and
        _admit ran OUTSIDE _step_once's try and the scheduler died."""
        eng = InferStep(tmodel, max_len=24)
        bat = ContinuousBatcher(eng, bucket_keys=(8,), slots=2,
                                max_new_tokens=4, page_size=4,
                                iter_tokens=2, warmup=True)
        try:
            armed = [True]
            orig_admit = bat._admit

            def flaky_admit():
                if armed[0] and bat._pending:
                    armed[0] = False
                    raise RuntimeError("admission blew up")
                return orig_admit()

            bat._admit = flaky_admit
            src = np.arange(3, 9, dtype=np.int32)
            # first request trips the fault; poison keeps it pending, so
            # the surviving scheduler re-admits and serves it
            f1 = bat.submit(src)
            r1 = f1.result(timeout=120)
            assert isinstance(r1, list) and len(r1) == 4
            assert not armed[0]  # the fault really fired
            # thread survived: a second request decodes normally
            f2 = bat.submit(src)
            assert f2.result(timeout=120) == r1
        finally:
            bat.stop()
        assert bat.pool.free_pages == bat.pool.num_pages
        bat.pool.check_invariants(set())

    def test_hot_swap_with_paged_requests_in_flight(self, tmodel):
        """A weight swap between iterations: zero lost requests and both
        versions appear in the served stream."""
        other = _make_transformer(seed=11, prefix=tmodel.prefix)
        eng = InferStep(tmodel, max_len=24)
        staged = eng.stage_params(
            {n: p._data.data for n, p in other.collect_params().items()})
        bat = ContinuousBatcher(eng, bucket_keys=(8,), slots=2,
                                max_new_tokens=6, page_size=4,
                                iter_tokens=1, warmup=True)
        rng = np.random.RandomState(9)
        futs = []
        try:
            for i in range(12):
                futs.append(bat.submit(
                    rng.randint(3, 61, (6,)).astype(np.int32)))
                if i == 5:
                    # guarantee at least one pre-swap completion, then
                    # flip between iterations with requests in flight
                    futs[0].result(timeout=60)
                    eng.swap_params(staged=staged, version="v-next")
                time.sleep(0.002)
            results = [f.result(timeout=120) for f in futs]
        finally:
            bat.stop()
        assert all(len(r) >= 1 for r in results)
        versions = {f.weights_version for f in futs}
        assert "v-next" in versions and len(versions) >= 2
        assert bat.pool.free_pages == bat.pool.num_pages

"""The sparse attention's indexer for a prefill chunk, as ONE Pallas kernel:
a block of queries keeps its index scores in VMEM from the products to the
``topk``-th largest, and what leaves the chip is the selection.

``ops/sparse_attention.window_index_scores`` + ``select_mask`` are the same
function in ``jax.numpy`` (and this kernel's reference): there the scores
of a chunk are one float32 array ``(R, C, L)`` in HBM (136 MB at 2,048
queries over 16,640 cached positions) that the radix select walks sixteen
times. Here a grid step takes ``tq`` queries of a row and

(a) for the key blocks its LAST query can see, and only those, accumulates
    ``I = sum_j w_j relu(q_j . k) / sqrt(Di J)`` in float32, heads in order
    (all heads of the block meet a key block in one product), and keeps it
    as an order-preserving int32 key, -inf where ``s > t``;
(b) where its last query stands at or past ``topk`` (else every seen
    position is selected and nothing is scored or counted), finds each
    query's ``topk``-th largest key bit by bit from the top: 32 counting
    passes over the live key blocks, all in VMEM;
(c) writes the selection, one int8 a (query, position): what lies above
    the cut and the seen positions at it. Ties beyond the room at the cut
    are rare a query and common a chunk (one query in a thousand has two
    equal scores at its cut): a query block that holds such a query, and
    only it, settles them to the lower position by a second search, over
    positions (15 counting passes at 16,640 cached positions).

The counts are over the live columns alone; the dead ones are -inf, the
lowest key there is, so the ``topk``-th largest is the same.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _use_interpret
from .page_walk import LANES

__all__ = ["dsa_index_select", "index_select_tiles"]

# queries a grid step and keys a block of its loops (PERF.md, PR 37)
_QUERIES = 128
_KEYS = 256
_VMEM_LIMIT = 48 * 1024 * 1024
MIN_KEY = -2 ** 31          # the sign bit: unsigned order from signed


def index_select_tiles(C, L):
    """``(queries a grid step, keys a block)`` of the kernel, from the
    shapes alone, or None where they do not divide into its blocks."""
    if C % _QUERIES or L % LANES:
        return None
    return _QUERIES, math.gcd(L, _KEYS)


def index_select_vmem_bytes(tq, kb, L, heads, Di, itemsize):
    """VMEM a grid step holds: the pipeline's two buffers of every block
    (a last axis takes whole lanes), the keys of a query block, the head
    weights spread over the lanes, and one key block's products."""
    wide = -(-Di // LANES) * LANES
    blocks = 2 * ((L + heads * tq) * wide * itemsize      # keys, queries
                  + tq * LANES * 4                       # head weights
                  + tq * L)                               # the selection
    scratch = tq * L * 4 + heads * tq * LANES * 4
    live = heads * tq * kb * 4 + 4 * tq * kb * 4
    return blocks + scratch + live


def ordered_key(x):
    """float32 as int32 whose SIGNED order is the floats' (-inf lowest)."""
    bits = pltpu.bitcast(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _index_select_kernel(off_ref, lim_ref, q_ref, w_ref, k_ref, code_ref,
                         key_ref, wb_ref, *, tq, kb, heads, topk, scale):
    """Grid (R, query blocks). ``q_ref (1, 1, heads * tq, Di)`` rows (head,
    query); ``w_ref (1, tq, heads)``; ``k_ref (1, L, Di)`` the row's
    indexer keys, resident; ``key_ref (L / kb, tq, kb)`` the block's keys;
    ``wb_ref (heads, tq, 128)`` a head's weights on every lane."""
    r, i = pl.program_id(0), pl.program_id(1)
    nkb = key_ref.shape[0]
    first = off_ref[r] + i * tq              # the block's first query
    last = first + tq - 1                    # bounds what any query sees
    limit = lim_ref[0]                       # positions the caller walked
    n_live = jnp.minimum(last // kb + 1, nkb)
    position_bits = (nkb * kb - 1).bit_length()
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, LANES), 1)
    q_abs = first + jax.lax.broadcasted_iota(jnp.int32, (tq, LANES), 0)
    chunks = range(0, kb, LANES)
    code_ref[...] = jnp.zeros_like(code_ref)

    def lanes(c):
        return slice(c, c + LANES)

    def seen(j, c):                          # lanes ``c`` of key block j
        return j * kb + c + col <= q_abs

    def put_code(j, c, selected):
        start = pl.multiple_of(j * kb + c, LANES)
        code_ref[0, :, pl.ds(start, LANES)] = jnp.where(
            selected, 1, 0).astype(jnp.int8)

    def row_sum(lane_counts):                # (tq, 128) to (tq, 1)
        return jnp.sum(lane_counts, axis=1, keepdims=True)

    # every seen position is selected while there are at most ``topk``
    @pl.when(last < topk)
    def _all_seen():
        def emit(j, carry):
            for c in chunks:
                put_code(j, c, seen(j, c))
            return carry

        jax.lax.fori_loop(0, n_live, emit, 0)

    @pl.when(last >= topk)
    def _select():
        for h in range(heads):
            wb_ref[h] = jnp.broadcast_to(w_ref[0, :, h:h + 1], (tq, LANES))
        # operands go to the MXU as they are; a process-wide "highest"
        # precision is not one Mosaic takes for bfloat16
        prec = (jax.lax.Precision.DEFAULT if k_ref.dtype == jnp.bfloat16
                else None)

        def score(j, carry):
            start = pl.multiple_of(j * kb, kb)

            @pl.when(start < limit)
            def _walked():
                s = jax.lax.dot_general(
                    q_ref[0, 0], k_ref[0, pl.ds(start, kb), :],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=prec)                  # (heads * tq, kb)
                for c in chunks:
                    acc = jnp.zeros((tq, LANES), jnp.float32)
                    for h in range(heads):
                        acc = acc + jnp.maximum(
                            s[h * tq:(h + 1) * tq, lanes(c)], 0.0) * wb_ref[h]
                    pos = start + c + col
                    acc = jnp.where(
                        jnp.logical_and(pos <= q_abs, pos < limit),
                        acc * scale, -jnp.inf)
                    key_ref[j, :, lanes(c)] = ordered_key(acc)

            @pl.when(start >= limit)
            def _unwalked():
                key_ref[j] = ordered_key(
                    jnp.full((tq, kb), -jnp.inf, jnp.float32))
            return carry

        jax.lax.fori_loop(0, n_live, score, 0)

        # the largest T with count(keys >= T) >= topk, found from the top
        # bit down. T grows as an UNSIGNED key; the keys compare signed, so
        # the carry is T with its sign bit turned (zero is ``MIN_KEY``) and a
        # candidate turns one more bit of it
        def bit_pass(p, prefix):
            cand = prefix ^ jnp.left_shift(jnp.int32(1), 31 - p)

            def count(j, cnt):
                for c in chunks:
                    cnt = cnt + jnp.where(key_ref[j, :, lanes(c)] >= cand,
                                          1.0, 0.0)
                return cnt

            # counts a lane in float32 (exact): adding lane groups is
            # elementwise, a sum a row is paid once a pass
            cnt = jax.lax.fori_loop(0, n_live, count,
                                    jnp.zeros((tq, LANES), jnp.float32))
            enough = row_sum(cnt) >= topk
            return jnp.where(enough, cand, prefix)

        kth = jax.lax.fori_loop(
            0, 32, bit_pass, jnp.full((tq, LANES), MIN_KEY, jnp.int32))

        def cut(j, c):      # above the cut; a seen position at the cut
            keys = key_ref[j, :, lanes(c)]
            return keys > kth, jnp.logical_and(keys == kth, seen(j, c))

        def emit(j, carry):
            above_n, tie_n = carry
            for c in chunks:
                above, tie = cut(j, c)
                put_code(j, c, jnp.logical_or(above, tie))
                above_n = above_n + jnp.where(above, 1.0, 0.0)
                tie_n = tie_n + jnp.where(tie, 1.0, 0.0)
            return above_n, tie_n

        zero = jnp.zeros((tq, LANES), jnp.float32)
        above_n, tie_n = jax.lax.fori_loop(0, n_live, emit, (zero, zero))
        room = topk - row_sum(above_n)       # at least 1: kth is the k-th

        # a query with more ties than room keeps the lowest positions: the
        # largest bound with count(ties below it) < room is the position
        # of the last tie kept (past every position where all of them are)
        @pl.when(jnp.max(row_sum(tie_n) - room) > 0)
        def _crowded():
            def bit_pass(p, bound):
                cand = bound | jnp.left_shift(jnp.int32(1),
                                              position_bits - 1 - p)

                def count(j, cnt):
                    for c in chunks:
                        below = jnp.logical_and(cut(j, c)[1],
                                                j * kb + c + col < cand)
                        cnt = cnt + jnp.where(below, 1.0, 0.0)
                    return cnt

                cnt = jax.lax.fori_loop(0, n_live, count, zero)
                return jnp.where(row_sum(cnt) < room, cand, bound)

            bound = jax.lax.fori_loop(
                0, position_bits, bit_pass,
                jnp.zeros((tq, LANES), jnp.int32))

            def settle(j, carry):
                for c in chunks:
                    above, tie = cut(j, c)
                    kept = jnp.logical_and(tie, j * kb + c + col <= bound)
                    put_code(j, c, jnp.logical_or(above, kept))
                return carry

            jax.lax.fori_loop(0, n_live, settle, 0)


@functools.partial(jax.jit, static_argnames=("topk", "tq", "kb", "interpret"))
def _dsa_index_select_impl(qi, wi, ki_all, q_offset, limit, topk, tq, kb,
                           interpret):
    R, C, J, Di = qi.shape
    L = ki_all.shape[1]
    nq = C // tq
    dtype = jnp.result_type(qi.dtype, ki_all.dtype)
    # (R, nq, J * tq, Di): a block's rows are (head, query)
    qb = qi.reshape(R, nq, tq, J, Di).transpose(0, 1, 3, 2, 4) \
        .reshape(R, nq, J * tq, Di).astype(dtype)
    kernel = functools.partial(
        _index_select_kernel, tq=tq, kb=kb, heads=J, topk=topk,
        scale=1.0 / math.sqrt(Di * J))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, nq),
        in_specs=[
            pl.BlockSpec((1, 1, J * tq, Di),
                         lambda r, i, off, lim: (r, i, 0, 0)),
            pl.BlockSpec((1, tq, J), lambda r, i, off, lim: (r, i, 0)),
            pl.BlockSpec((1, L, Di), lambda r, i, off, lim: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, L), lambda r, i, off, lim: (r, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((L // kb, tq, kb), jnp.int32),
            pltpu.VMEM((J, tq, LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, C, L), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_index_select",
    )(q_offset.astype(jnp.int32), limit.astype(jnp.int32).reshape(1), qb,
      wi.astype(jnp.float32), ki_all.astype(dtype))


def dsa_index_select(qi, wi, ki_all, q_offset, limit, topk):
    """Index scores and selection of ``C`` window queries a row, ``qi (R,
    C, J, Di)`` with head weights ``wi (R, C, J)`` at positions
    ``q_offset[r] + c``, over the row's cached indexer keys ``ki_all (R,
    L, Di)``, of which the first ``limit`` positions are scored (the rest
    read -inf, as past a query's own position).

    Returns the selected set of each query ``(R, C, L)`` int8, 1 where
    selected: every seen position while ``t + 1 <= topk``, else the
    ``topk`` of largest score, ties to the lower position. ``(C, L)`` must
    divide into ``index_select_tiles``."""
    tq, kb = index_select_tiles(qi.shape[1], ki_all.shape[1])
    return _dsa_index_select_impl(
        qi, wi, ki_all, q_offset, jnp.asarray(limit), topk=int(topk),
        tq=tq, kb=kb, interpret=_use_interpret())

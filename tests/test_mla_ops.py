"""Latent attention (``ops/mla.py``, ``ops/pallas/mla_attention.py``) and
the router's second scoring (``ops/pallas/grouped_swiglu.py``): the
absorbed and the expanded order agree on one cache, the Pallas kernels
agree with their ``jax.numpy`` forms in interpret mode, and the sigmoid
router's bias selects without weighing."""

import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import mxnet_tpu as mx  # noqa: F401 - the package sets JAX up
from mxnet_tpu.ops import mla
from mxnet_tpu.ops.pallas import grouped_swiglu as moe
from mxnet_tpu.ops.pallas import mla_attention as kern
from mxnet_tpu.ops.pallas import page_walk as walk

H, RANK, ROPE, D = 4, 32, 8, 16
PAGE, P = 4, 6


@pytest.fixture(autouse=True)
def highest_precision():
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)


def _cache(rng, rows, width=RANK + ROPE):
    """A pool whose pages lie in a scrambled order, and the rows' tables."""
    pool = rng.standard_normal((1 + rows * P, PAGE, width)).astype(np.float32)
    tables = 1 + rng.permutation(rows * P).reshape(rows, P).astype(np.int32)
    return jnp.asarray(pool), jnp.asarray(tables)


def _queries(rng, *lead):
    f = np.float32
    return (jnp.asarray(rng.standard_normal(lead + (H, D)).astype(f)),
            jnp.asarray(rng.standard_normal(lead + (H, ROPE)).astype(f)))


# --------------------------------------------------- one cache, two orders
@pytest.mark.parametrize("positions", [1, 2])
def test_absorbed_and_expanded_agree_on_one_cache(positions):
    """The decode step's order (the up-projection folded into the query
    and the output, all heads against the latent itself) and the chunk
    program's (per-head keys and values made from the latent) are the same
    sums: on one cache they give the same attention output."""
    rng = np.random.default_rng(3)
    B = 3
    pool, tables = _cache(rng, B)
    wkvb = jnp.asarray(rng.standard_normal((RANK, H * 2 * D))
                       .astype(np.float32) / np.sqrt(RANK))
    qn, qr = _queries(rng, B, positions)
    pos = jnp.asarray([5, 17, 0], jnp.int32)
    w = wkvb.reshape(RANK, H, 2 * D)
    qc = jnp.einsum("bshd,chd->bshc", qn, w[..., :D])
    oc = mla.decode_attention(qc, qr, pool, tables, pos)
    absorbed = jnp.einsum("bshc,chd->bshd", oc, w[..., D:]) \
        .reshape(B, positions, H * D)
    expanded, _ = mla.window_attention(qn, qr, pool, wkvb, tables, pos,
                                       jnp.max(pos) + positions - 1)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5)
    # and both are attention: a row at position 0 with one query reads the
    # one position it has, so its output is that position's value
    if positions == 1:
        lat0 = pool[tables[2, 0], 0, :RANK]
        want = jnp.einsum("c,chd->hd", lat0, w[..., D:]).reshape(-1)
        np.testing.assert_allclose(expanded[2, 0], want, atol=2e-5)


INV = mla.inverse_frequencies(1e4, 4)


def test_rope_pairs_neighbours_and_keeps_the_dot_product_relative():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 8)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 8)).astype(np.float32))
    dot = lambda a, b: float(jnp.sum(  # noqa: E731
        mla.rope_interleaved(q, jnp.asarray([a]), INV)
        * mla.rope_interleaved(k, jnp.asarray([b]), INV)))
    assert dot(7, 3) == pytest.approx(dot(104, 100), abs=1e-4)
    assert abs(dot(7, 3) - dot(7, 4)) > 1e-3
    # dimension 2i turns with 2i + 1: the first pair at the fastest rate
    x = jnp.zeros((1, 8)).at[0, 0].set(1.0)
    y = np.asarray(mla.rope_interleaved(x, jnp.asarray([1]), INV))[0]
    np.testing.assert_allclose(y[:2], [np.cos(1.0), np.sin(1.0)], atol=1e-6)
    assert np.abs(y[2:]).max() == 0


# ---------------------------------------------- kernels, interpret mode
# (query positions a row, the rows' positions, the pool's dtype, whether the
# pages no live row reads hold NaN and Inf)
DECODE_CASES = {
    "two_positions": (2, [0, 9, 22], "float32", False),
    "one_position": (1, [3, 23, 11], "float32", False),
    "before_position_0": (2, [-1, 5, 21], "float32", False),
    # pos -2 with two positions: no block; -1: one block for the second
    "nothing_cached_beside_live_rows": (2, [-2, -1, 9], "float32", False),
    # 9 + 2 positions are three pages: the last block of 2 or 4 is partial,
    # and what the kernel must not read would poison the value product
    "partial_last_block_unused_pages_nan": (2, [9, 1, 17], "float32", True),
    # the copy a row starts for "the next row" is the next LIVE row's
    "live_dead_live": (2, [7, -5, 13, -9, -3, 20], "float32", True),
    "every_page_of_the_table": (2, [P * PAGE - 2, 3, P * PAGE - 2],
                                "float32", False),
    "bfloat16": (2, [0, 9, 22, -4, 14], "bfloat16", False),
}


@pytest.mark.parametrize("block", [1, 2, 4, 6])
@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_latent_decode_kernel_matches_its_jnp_form(case, block):
    """``%mla_latent_decode`` in interpret mode, ``block`` pages a block:
    the kernel's own copies walk a row's live pages alone, two buffers
    deep; rows past their position, before position 0 and with nothing
    cached; two query positions a row."""
    positions, pos, dtype, poison = DECODE_CASES[case]
    rng = np.random.default_rng(5)
    B = len(pos)
    pool, tables = _cache(rng, B)
    qc = jnp.asarray(rng.standard_normal((B, positions, H, RANK))
                     .astype(np.float32), dtype)
    _, qr = _queries(rng, B, positions)
    pool, qr = pool.astype(dtype), qr.astype(dtype)
    pos = jnp.asarray(pos, jnp.int32)
    want = np.asarray(mla.decode_attention(qc, qr, pool, tables, pos),
                      np.float32)
    if poison:
        pages = np.clip((np.asarray(pos) + positions - 1) // PAGE + 1, 0, P)
        read = {int(t) for row, n in zip(np.asarray(tables), pages)
                for t in row[:n]}
        unread = [n for n in range(pool.shape[0]) if n not in read]
        pool = pool.at[jnp.asarray(unread[::2])].set(jnp.nan) \
                   .at[jnp.asarray(unread[1::2])].set(jnp.inf)
    got = np.asarray(kern._mla_latent_decode_impl(
        qc, qr, pool, tables, pos, rank=RANK, block=block, interpret=True),
        np.float32)
    assert np.isfinite(got).all()
    live = np.asarray(pos)[:, None] + np.arange(positions)[None] >= 0
    np.testing.assert_allclose(
        got[live], want[live], atol=2e-2 if dtype == "bfloat16" else 2e-5)
    # a row with nothing cached reads nothing and yields zeros
    assert not got[np.asarray(pos) + positions - 1 < 0].any()
    assert walk.decode_tiles(130, 128) == 8
    assert walk.decode_tiles(P, PAGE) == P


def _parent_prefill(qn, qr, kv, kr, q_offset, tq, tk):
    """``%mla_prefill`` as it was before its walk ended at the causal edge
    (PR 44's body, kept here as the plain reference): a STATIC grid over
    every key block of the table, every computed block masked."""
    R, Hq, C, Dh = qn.shape
    L, rope = kr.shape[1], kr.shape[2]

    def kernel(off_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref):
        r, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
        off = off_ref[r]
        pl.when(j == 0)(functools.partial(
            walk.init_carry, m_ref, l_ref, acc_ref))

        @pl.when(j * tk <= off + (i + 1) * tq - 1)
        def _accumulate():
            s = walk.nt(qn_ref[0, 0], kn_ref[0]) \
                + walk.nt(qr_ref[0, 0], kr_ref[0])
            q_pos = off + i * tq + jax.lax.broadcasted_iota(
                jnp.int32, (tq, 1), 0)
            k_pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
            s = jnp.where(k_pos <= q_pos, s, kern.NEG_INF)
            walk.softmax_step(s, v_ref[0], m_ref, l_ref, acc_ref)

        @pl.when(j == pl.num_programs(3) - 1)
        def _finalize():
            l = jnp.maximum(jnp.sum(l_ref[...], axis=1, keepdims=True), 1e-30)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    def key_block(r, i, j, off):
        return jnp.minimum(j, (off[r] + (i + 1) * tq - 1) // tk)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, Hq, C // tq, L // tk),
            in_specs=[
                pl.BlockSpec((1, 1, tq, Dh),
                             lambda r, h, i, j, off: (r, h, i, 0)),
                pl.BlockSpec((1, 1, tq, rope),
                             lambda r, h, i, j, off: (r, h, i, 0)),
                pl.BlockSpec((1, tk, Dh), lambda r, h, i, j, off: (
                    r, key_block(r, i, j, off), 2 * h)),
                pl.BlockSpec((1, tk, rope), lambda r, h, i, j, off: (
                    r, key_block(r, i, j, off), 0)),
                pl.BlockSpec((1, tk, Dh), lambda r, h, i, j, off: (
                    r, key_block(r, i, j, off), 2 * h + 1))],
            out_specs=pl.BlockSpec((1, tq, Dh),
                                   lambda r, h, i, j, off: (r, i, h)),
            scratch_shapes=[
                pltpu.VMEM((tq, walk.LANES), jnp.float32),
                pltpu.VMEM((tq, math.gcd(tk, walk.LANES)), jnp.float32),
                pltpu.VMEM((tq, Dh), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, C, Hq * Dh), qn.dtype),
        interpret=True,
    )(q_offset.astype(jnp.int32), qn, qr, kv, kr, kv)


# a chunk of 8 queries a row over a table of 24 positions: (the two rows'
# offsets, queries a step, keys a step)
PREFILL_CASES = {
    "a_prompts_first_chunk": ((0, 4), 4, 4),
    "a_later_chunk": ((8, 12), 4, 4),
    "the_module_one_position_early": ((-1, 4), 4, 4),
    "the_tables_last_chunk": ((16, 16), 4, 4),           # L - C
    "edges_four_key_blocks_apart": ((0, 16), 4, 4),      # each row its own
    "the_module_beside_the_last_chunk": ((-1, 15), 4, 4),
    "two_diagonal_blocks_a_query_block": ((4, 9), 8, 4),  # 1,024 against 512
    "three_diagonal_blocks_a_query_block": ((5, 10), 8, 4),
    "a_block_seen_whole_by_one_position": ((3, 7), 4, 4),
    "a_block_one_position_short_of_whole": ((2, 6), 4, 4),
    "the_last_query_on_a_blocks_first_key": ((1, 13), 4, 4),
    "one_key_block_the_table": ((0, 16), 4, 24),
}


@pytest.mark.parametrize("case", list(PREFILL_CASES))
def test_prefill_kernel_matches_its_jnp_form(case, monkeypatch):
    """``%mla_prefill`` in interpret mode: a chunk of 8 queries at an
    offset over the expanded keys of its row. Its values are the
    ``jax.numpy`` form's and, bit for bit, those of the kernel as it was
    (every key block of the table a grid step, every computed block
    masked); a query block walks the key blocks its last query sees, each
    once, and no other, whatever the other row's offset."""
    offsets, tq, tk = PREFILL_CASES[case]
    rng = np.random.default_rng(7)
    R, C = 2, 8
    L = P * PAGE
    pool, tables = _cache(rng, R)
    wkvb = jnp.asarray(rng.standard_normal((RANK, H * 2 * D))
                       .astype(np.float32) / np.sqrt(RANK))
    qn, qr = _queries(rng, R, C)
    off = jnp.asarray(offsets, jnp.int32)
    last = jnp.max(off) + C - 1
    want, _ = mla.window_attention(qn, qr, pool, wkvb, tables, off, last)
    lat = mla.paged.gather_row_pages(pool, tables)
    buf = mla.expand_latents(jnp.zeros((R, L, H * 2 * D), jnp.float32), lat,
                             wkvb, last + 1, RANK)
    args = (jnp.swapaxes(qn, 1, 2), jnp.swapaxes(qr, 1, 2), buf,
            lat[..., RANK:], off)

    # what ran: a softmax step tells as it runs
    ran = []

    def softmax_step(*a):
        jax.debug.callback(lambda: ran.append(1))
        walk.softmax_step(*a)

    monkeypatch.setattr(kern, "softmax_step", softmax_step)
    got = kern._mla_prefill_impl.__wrapped__(*args, tq=tq, tk=tk,
                                             interpret=True)
    jax.effects_barrier()

    seen = np.asarray(off)[:, None] + np.arange(C)[None] >= 0
    np.testing.assert_allclose(np.asarray(got)[seen], np.asarray(want)[seen],
                               atol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_parent_prefill(*args, tq=tq, tk=tk)))
    # from the positions themselves: a head computes a key block where the
    # query block's last query sees the block's first key, and no other
    computed = [(o, i, j) for o in offsets for i in range(C // tq)
                for j in range(L // tk) if j * tk <= o + (i + 1) * tq - 1]
    assert len(ran) == H * len(computed)
    assert kern.prefill_tiles(2048, 16896) == (1024, 512)


def test_the_expansion_stops_at_the_last_position_seen():
    rng = np.random.default_rng(2)
    lat = jnp.asarray(rng.standard_normal((1, 1024, RANK + ROPE))
                      .astype(np.float32))
    wkvb = jnp.asarray(rng.standard_normal((RANK, 8)).astype(np.float32))
    buf = jnp.full((1, 1024, 8), 7.0)
    out = np.asarray(mla.expand_latents(buf, lat, wkvb, 513, RANK))
    full = np.asarray(lat[0, :, :RANK] @ wkvb)
    assert mla.EXPAND_KEYS == 512
    np.testing.assert_allclose(out[0], full, atol=2e-5)  # two steps of 512
    out = np.asarray(mla.expand_latents(buf, lat, wkvb, 512, RANK))
    np.testing.assert_allclose(out[0, :512], full[:512], atol=2e-5)
    assert (out[0, 512:] == 7.0).all()                   # left as it was


# ------------------------------------------------------------- the router
def _router(rng, T=24, Hd=16, E=8):
    u = jnp.asarray(rng.standard_normal((T, Hd)).astype(np.float32))
    r = jnp.asarray(rng.standard_normal((Hd, E)).astype(np.float32) / 4)
    return u, r


def test_sigmoid_router_weighs_by_the_score_without_its_bias():
    """A bias that changes the choice does not change the weights of what
    is chosen: they are the sigmoid scores of the chosen experts,
    renormalised and scaled, whatever the bias that chose them."""
    rng = np.random.default_rng(0)
    u, r = _router(rng)
    score = jax.nn.sigmoid(u @ r)
    plain, w0 = moe.route(u, r, 2, "sigmoid", None, 2.5)
    bias = jnp.zeros((8,)).at[5].set(10.0)           # expert 5 always in
    biased, w1 = moe.route(u, r, 2, "sigmoid", bias, 2.5)
    assert (np.asarray(biased) == 5).any(1).all()
    assert (np.asarray(plain) != np.asarray(biased)).any()
    for idx, w in ((plain, w0), (biased, w1)):
        top = jnp.take_along_axis(score, idx, -1)
        np.testing.assert_allclose(w, 2.5 * top / top.sum(-1, keepdims=True),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)
    # where the bias did not change a token's choice, nothing changed
    same = (np.sort(plain, -1) == np.sort(biased, -1)).all(-1)
    if same.any():
        np.testing.assert_allclose(np.sort(w0, -1)[same],
                                   np.sort(w1, -1)[same], rtol=1e-6)


def test_softmax_router_is_as_it_was():
    rng = np.random.default_rng(4)
    u, r = _router(rng)
    idx, w = moe.route(u, r, 2)
    prob = jax.nn.softmax(u @ r, -1)
    top, want = jax.lax.top_k(prob, 2)
    assert (np.asarray(idx) == np.asarray(want)).all()
    np.testing.assert_allclose(w, top / top.sum(-1, keepdims=True), rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(u, r, 2, "tanh")


@pytest.mark.parametrize("tokens", [6, 40])          # row tiles of 16 and 128
def test_shares_of_held_experts_add_up_to_the_layer(tokens):
    """``held``: the router ranks all experts, each share computes the
    pairs that fall on its own, and the shares' sum is the whole layer."""
    rng = np.random.default_rng(tokens)
    Hd, F, E, k = 16, 8, 8, 2
    u, r = _router(rng, tokens, Hd, E)
    bias = jnp.asarray(0.1 * rng.standard_normal(E).astype(np.float32))
    wg, wu = (jnp.asarray(rng.standard_normal((E, Hd, F)).astype(np.float32)
                          / 4) for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((E, F, Hd)).astype(np.float32) / 4)
    kw = dict(scoring="sigmoid", bias=bias, scale=2.5)
    whole, counts = moe.moe_experts(u, r, wg, wu, wd, k, **kw)
    assert int(counts.sum()) == tokens * k
    parts = []
    for first in range(0, E, 2):
        s = slice(first, first + 2)
        out, c = moe.moe_experts(u, r, wg[s], wu[s], wd[s], k,
                                 held=(first, 2), **kw)
        assert (np.asarray(c) == np.asarray(counts)).all()   # all E ranked
        parts.append(out)
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5)
    # a share that holds nothing a token chose adds nothing for it
    idx, _ = moe.route(u, r, k, **kw)
    none = ~((np.asarray(idx) >= 0) & (np.asarray(idx) < 2)).any(-1)
    assert np.abs(np.asarray(parts[0])[none]).max(initial=0) == 0

"""Pools of heads narrower than the lanes, declared ``(num_pages, page,
heads x D)`` (PR 46): the paged kernels' lane forms, interpreted on the CPU,
against the dense references; ``write_rows`` and the ``jax.numpy`` forms
over all three declarations; the nets that declare their pools so."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import paged

pfa = importlib.import_module("mxnet_tpu.ops.pallas.paged_flash_attention")

TOL = {"float32": dict(rtol=1e-5, atol=1e-5),
       "bfloat16": dict(rtol=2e-2, atol=2e-2)}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _pools(rng, rows, P, page, Hkv, D, dtype):
    """K and V pools ``(N, page, Hkv, D)`` and a table whose rows' pages
    lie scattered over the pool (page 0 is the trash page)."""
    N = rows * P + 1
    k, v = (jnp.asarray(rng.standard_normal((N, page, Hkv, D)), dtype)
            for _ in range(2))
    table = rng.permutation(N - 1).reshape(rows, P) + 1
    return k, v, jnp.asarray(table, jnp.int32)


def _lanes(pool):
    return pool.reshape(pool.shape[:2] + (-1,))


def _close(got, want, dtype):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


# (page, key/value heads, query heads a group, D, pages a row, dtype,
#  positions: one a row)
DECODE = {
    # granite's widths: position 0, a page's last key, the next page's
    # first, a last page partly filled, the row's last position
    "granite_grouped": (128, 8, 4, 64, 3, "bfloat16",
                        [0, 127, 128, 300, 383]),
    "granite_f32": (128, 8, 4, 64, 2, "float32", [5, 200]),
    # ten float32 pages a row are two grid steps of eight and two: the
    # carry crosses steps, a row that ends in the first skips the second
    "granite_two_steps": (128, 8, 4, 64, 10, "float32",
                          [1000, 1023, 1024, 1279]),
    # transformer-big's: 16 heads, pages of 16, nine a row: a block of
    # eight pages and a block of one
    "big_h16_page16": (16, 16, 1, 64, 9, "bfloat16", [0, 15, 16, 40, 143]),
    "big_f32": (16, 16, 1, 64, 9, "float32", [7, 127, 128]),
    # the tiny presets': heads of 8 on 16 lanes
    "tiny": (4, 2, 2, 8, 3, "float32", [0, 3, 4, 11]),
}


@pytest.mark.parametrize("case", sorted(DECODE))
def test_decode_reads_a_page_of_heads_on_the_lanes(case):
    page, Hkv, G, D, P, dtype, positions = DECODE[case]
    rng = np.random.default_rng(3)
    B = len(positions)
    k, v, table = _pools(rng, B, P, page, Hkv, D, dtype)
    q = jnp.asarray(rng.standard_normal((B, Hkv * G, D)), dtype)
    pos = jnp.asarray(positions, jnp.int32)
    want = pfa.paged_decode_reference(
        q, jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2), table, pos,
        sm_scale=D ** -0.5)
    got = pfa.paged_decode_attention(q, _lanes(k), _lanes(v), table, pos,
                                     sm_scale=D ** -0.5)
    _close(got, want, dtype)
    # and the declaration in four axes keeps the form it had
    _close(pfa.paged_decode_attention(q, k, v, table, pos,
                                      sm_scale=D ** -0.5, kv_heads=Hkv),
           want, dtype)


# (page, heads, D, pages a row, dtype, window, offsets, real queries a row)
WINDOW = {
    "big_s1": (16, 16, 64, 9, "bfloat16", 1, [0, 16, 143], [1, 1, 0]),
    "big_s3": (16, 16, 64, 9, "bfloat16", 3, [0, 14, 141], [3, 2, 3]),
    "big_s4": (16, 16, 64, 9, "float32", 4, [0, 13, 140], [4, 0, 1]),
    # 16 heads x 16 queries: the last window the whole block-diagonal takes
    "big_s16": (16, 16, 64, 9, "bfloat16", 16, [0, 9, 128], [16, 5, 16]),
    # past it the heads come two at a time, whole lanes a product
    "big_s32_pairs": (16, 16, 64, 9, "float32", 32, [0, 100], [32, 17]),
    "granite_s4": (128, 8, 64, 3, "bfloat16", 4, [0, 125, 380], [4, 3, 4]),
    "tiny_s5": (4, 2, 8, 3, "float32", 5, [0, 6], [5, 2]),
}


@pytest.mark.parametrize("case", sorted(WINDOW))
def test_window_reads_a_page_of_heads_on_the_lanes(case):
    page, H, D, P, dtype, S, offsets, real = WINDOW[case]
    rng = np.random.default_rng(4)
    B = len(offsets)
    k, v, table = _pools(rng, B, P, page, H, D, dtype)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    off, vl = jnp.asarray(offsets, jnp.int32), jnp.asarray(real, jnp.int32)
    want = pfa.paged_window_reference(q, k, v, table, off, vl,
                                      sm_scale=D ** -0.5)
    got = pfa.paged_window_attention(q, _lanes(k), _lanes(v), table, off,
                                     vl, sm_scale=D ** -0.5)
    _close(got, want, dtype)
    heads = pfa._lane_heads(H, D, S)
    assert heads == (2 if "pairs" in case else H)


def test_grouped_heads_ride_the_window_beside_the_positions():
    """``kv_heads`` under a window of several positions: row ``g * S + i``
    of a key/value head is head ``g`` of its group at position ``i``."""
    page, Hkv, G, D, P, S = 16, 4, 2, 32, 3, 3
    rng = np.random.default_rng(5)
    k, v, table = _pools(rng, 2, P, page, Hkv, D, "float32")
    q = jnp.asarray(rng.standard_normal((2, S, Hkv * G, D)), jnp.float32)
    off, vl = jnp.asarray([0, 30], jnp.int32), jnp.asarray([3, 2], jnp.int32)
    want = pfa.paged_window_reference(
        q, jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2), table, off,
        vl, sm_scale=D ** -0.5)
    got = pfa.paged_window_attention(q, _lanes(k), _lanes(v), table, off,
                                     vl, sm_scale=D ** -0.5, kv_heads=Hkv)
    _close(got, want, "float32")


# (page, key/value heads, group, D, pages a row, chunk, offsets)
SELECTED = {
    # granite's widths: two heads of 64 a product, 128 queries a block
    "granite": (128, 4, 4, 64, 3, 256, [0, 128]),
    # one block of 8 queries; four heads of 32 on 128 lanes; a last block
    # of keys partly past the row's length
    "heads_of_32": (4, 4, 2, 32, 5, 8, [12]),
    "tiny": (4, 2, 2, 16, 5, 8, [0, 9]),
}


@pytest.mark.parametrize("case", sorted(SELECTED))
def test_selected_window_reads_a_page_of_heads_on_the_lanes(case):
    page, Hkv, G, D, P, C, offsets = SELECTED[case]
    rng = np.random.default_rng(6)
    B, L = len(offsets), P * page
    k, v, table = _pools(rng, B, P, page, Hkv, D, "float32")
    q = jnp.asarray(rng.standard_normal((B, C, Hkv * G, D)), jnp.float32)
    off = jnp.asarray(offsets, jnp.int32)
    at = off[:, None] + jnp.arange(C)[None]
    key = jnp.arange(L)[None, None]
    mask = jnp.logical_and(key <= at[:, :, None], jnp.logical_or(
        jnp.asarray(rng.random((B, C, L)) < 0.6), key == at[:, :, None]))
    want = pfa.paged_selected_window_reference(
        q, k, v, table, off, mask, sm_scale=D ** -0.5)
    got = pfa.paged_selected_window_attention(
        q, _lanes(k), _lanes(v), table, off, mask, sm_scale=D ** -0.5)
    _close(got, want, "float32")


DECLARED = {
    "by_head": lambda N, page, H, D: (N, page, H, D),
    "key_head_rows": lambda N, page, H, D: (N, page * H, D),
    "heads_on_lanes": lambda N, page, H, D: (N, page, H * D),
}


@pytest.mark.parametrize("declared", sorted(DECLARED))
def test_write_rows_round_trip(declared):
    """What ``write_rows`` puts at a row comes back through ``by_head``
    and ``gather_row_pages`` at that position, head by head, in every
    declaration; the other rows keep what they held."""
    N, page, H, D = 4, 8, 2, 16
    rng = np.random.default_rng(7)
    shape = DECLARED[declared](N, page, H, D)
    before = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    rows = jnp.asarray([9, 30, 31, 8], jnp.int32)
    values = jnp.asarray(rng.standard_normal((4, H, D)), jnp.float32)
    after = paged.write_rows(before, rows, values)
    assert after.shape == shape
    seen = paged.by_head(after, D, H).reshape(N * page, H, D)
    np.testing.assert_array_equal(seen[rows], values)
    kept = np.setdiff1d(np.arange(N * page), np.asarray(rows))
    np.testing.assert_array_equal(
        seen[kept], paged.by_head(before, D, H).reshape(-1, H, D)[kept])
    table = jnp.asarray([[3, 1]], jnp.int32)
    got = paged.gather_row_pages(paged.by_head(after, D, H), table)
    np.testing.assert_array_equal(got[0, 6], values[1])      # row 30


@pytest.mark.parametrize("kernels", [False, True])
def test_decode_and_window_entry_points_take_the_lane_declaration(
        paged_kernels, kernels):
    """``ops/paged.py``'s two attentions over a pool of heads on the lanes,
    kernels and ``jax.numpy`` forms, against the declaration in four
    axes."""
    paged_kernels(kernels)
    page, Hkv, G, D, P, C = 8, 2, 2, 16, 3, 8
    rng = np.random.default_rng(8)
    k, v, table = _pools(rng, 2, P, page, Hkv, D, "float32")
    q = jnp.asarray(rng.standard_normal((2, C, Hkv * G, D)), jnp.float32)
    pos = jnp.asarray([5, 23], jnp.int32)
    want = paged.decode_attention(q[:, 0], k, v, table, pos, D ** -0.5)
    got = paged.decode_attention(q[:, 0], _lanes(k), _lanes(v), table, pos,
                                 D ** -0.5)
    _close(got, want, "float32")
    off, real = jnp.asarray([0, 13], jnp.int32), jnp.asarray([8, 5], jnp.int32)
    want = paged.window_attention(q, k, v, table, off, real, D ** -0.5,
                                  kv_heads=Hkv)
    got = paged.window_attention(q, _lanes(k), _lanes(v), table, off, real,
                                 D ** -0.5)
    live = np.arange(C)[None, :] < np.asarray(real)[:, None]
    _close(np.where(live[..., None], got, 0),
           np.where(live[..., None], want, 0), "float32")


def test_the_nets_of_heads_of_64_declare_their_pools_on_the_lanes():
    """granite's attention layers and the attention layer transformer-big
    is made of declare ``(num_pages, page, heads x D)``; a layer of heads
    of whole lanes keeps four axes."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo.granite_hybrid import GraniteHybridLM

    net = GraniteHybridLM(vocab_size=64, hidden_size=32,
                          layer_types=("mamba", "attention"),
                          num_heads=4, num_kv_heads=2,
                          intermediate_size=48, mamba_heads=4,
                          mamba_head_dim=16, mamba_state=8)
    state = jax.eval_shape(lambda: net.init_paged_state(2, 5, 4, 0))
    assert [p.shape for p in state["k_pools"] + state["v_pools"]] == \
        [(5, 4, 16)] * 2
    narrow = nn.MultiHeadAttention(128, 2, self_attention=True)   # D = 64
    whole = nn.MultiHeadAttention(256, 2, self_attention=True)    # D = 128
    for layer in (narrow, whole):
        layer.initialize()
    assert [p.shape for p in narrow.init_page_pool(5, 16)] == \
        [(5, 16, 128)] * 2
    assert [p.shape for p in whole.init_page_pool(5, 16)] == \
        [(5, 16, 2, 128)] * 2


# ------------------------------------ the nets whose pools were not moved
# sha256 of ``str(jax.make_jaxpr(...))`` of the chunk program and of the
# decode step (what a burst loops) of the three paged nets that keep their
# declarations, at the sizes below, taken from the commit BEFORE the lane
# forms (cae2ba6) with this very function: heads of 128 where the kernels
# run (zaya's and ouro's decode step walks its live pages, keye's chunk
# and ouro's go through ``%dsa_selected_window``, zaya's through
# ``%paged_window``), the tiny heads of 16 where they do not. A later
# change to what one of them computes recomputes its line (the function
# prints what it finds; the text depends on the suite's JAX settings, so
# take it from a run under pytest).
UNMOVED = {
    ("keye", False): {"chunk": "56abde403be27a89",
                      "decode": "681a305b15bbd5a2"},
    ("keye", True): {"chunk": "2eab2ad98763e0ae",
                     "decode": "440efd9281f5ca37"},
    ("ouro", False): {"chunk": "d6e58a385c24f19d",
                      "decode": "3c0a18020ca65fca"},
    ("ouro", True): {"chunk": "fd27c3ddefd70c65",
                     "decode": "86609dabf9eda6e8"},
    ("zaya", False): {"chunk": "7a7fd60af40ec65f",
                      "decode": "656823c638758507"},
    ("zaya", True): {"chunk": "6fe85a2a6a9b86f7",
                     "decode": "ae2e4fb2eda0e87d"},
}


def _unmoved_net(which, D):
    from mxnet_tpu.gluon.model_zoo.keye import KeyeLM
    from mxnet_tpu.gluon.model_zoo.ouro import OuroLM
    from mxnet_tpu.gluon.model_zoo.zaya import ZayaLM

    if which == "zaya":
        return ZayaLM(vocab_size=128, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, head_dim=D, num_experts=4,
                      experts_per_tok=1, expert_width=64, router_hidden=32,
                      cca_time0=2, cca_time1=2, prefix="z_")
    if which == "ouro":
        return OuroLM(vocab_size=128, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=4, head_dim=D,
                      intermediate_size=96, total_ut_steps=2, prefix="o_")
    return KeyeLM(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, head_dim=D, num_experts=8,
                  experts_per_tok=2, expert_width=32, index_heads=2,
                  index_head_dim=D // 2, index_topk=8, kv_chunk=4,
                  mrope_section=[D // 8, D // 8, D // 4], prefix="k_")


@pytest.mark.parametrize("kernels", [False, True])
@pytest.mark.parametrize("which", ["keye", "ouro", "zaya"])
def test_the_unmoved_nets_trace_to_the_programs_they_had(paged_kernels,
                                                         which, kernels):
    import hashlib

    paged_kernels(kernels)
    net = _unmoved_net(which, 128 if kernels else 16)
    net.initialize()
    page, chunk = (128, 128) if kernels else (4, 8)
    state = net.init_paged_state(3, 9, page, 0)
    pt = jnp.arange(6, dtype=jnp.int32).reshape(3, 2) + 1
    programs = {
        "chunk": jax.make_jaxpr(lambda s, t: net.prefill_suffix_paged(
            t, jnp.array([chunk - 3]), jnp.array([page]), s, pt[:1],
            jnp.array([1]), jnp.array([True])))(
                state, jnp.zeros((1, chunk), jnp.int32)),
        "decode": jax.make_jaxpr(lambda s, t: net.decode_step_paged(
            t, jnp.array([5, 6, 7]), s, pt,
            jnp.array([True, True, False])))(
                state, jnp.zeros((3,), jnp.int32))}
    found = {k: hashlib.sha256(str(v).encode()).hexdigest()[:16]
             for k, v in programs.items()}
    print((which, kernels), found)
    assert found == UNMOVED[which, kernels]

"""The chip's compiler, asked without the chip.

Every Pallas kernel on the main path is compiled here for a DESCRIBED
TPU v5e at the widths BERT-base training and Transformer-base serving
use, with interpret mode forbidden — what Mosaic refuses costs no chip
time. Nothing runs: a compile that passes says nothing about results or
speed (``chip_smoke.py`` checks results on the chip).

All of these live in this ONE file and the topology is described inside
a module-scoped fixture: only one process may load the TPU library, so
under several test workers only the worker that is handed this file may
touch it, and never while a module is imported (no top-level call, no
``skipif`` condition, no ``parametrize`` argument, no child process).
"""

import importlib
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Transformer-base serving shapes as chip_smoke.py drives them
HEADS, HEAD_DIM, PAGE = 8, 64, 16
SLOTS, PAGES_PER_SLOT = 4, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it.
    # Traces are cached per shape, not per interpret mode: start clean and
    # leave nothing compiled-for-the-chip behind for the CPU tests
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield t
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_chip(one_chip, monkeypatch):
    """Forbid interpret mode and hand out shapes placed on the described
    chip plus a compile-and-check helper."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "0")

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    def compile_(fn, *specs):
        compiled = jax.jit(fn).lower(*specs).compile()
        assert "tpu_custom_call" in compiled.as_text(), \
            "the kernel did not reach the chip's compiler"
        return compiled

    return spec, compile_


def _mod(name):
    # the package attribute `flash_attention` is the function, not the
    # module: fetch kernel modules through importlib
    return importlib.import_module(f"mxnet_tpu.ops.pallas.{name}")


@pytest.mark.parametrize("rows,C,dtype", [
    (8192, 768, "bfloat16"),   # BERT-base, batch 64 x seq 128
    (8192, 768, "float32"),
    (4096, 512, "bfloat16"),   # Transformer-base
    (4096, 512, "float32"),
])
def test_layer_norm_fused_compiles(for_chip, rows, C, dtype):
    spec, compile_ = for_chip
    ln = _mod("layer_norm")
    x, g = spec((rows, C), dtype), spec((C,), dtype)

    def fwd(x, g, b):
        return ln.layer_norm_fused(x, g, b, 1e-5)

    def loss(x, g, b):
        return fwd(x, g, b).astype(jnp.float32).sum()

    compile_(fwd, x, g, g)
    compile_(jax.grad(loss, argnums=(0, 1, 2)), x, g, g)


def test_layer_norm_under_a_mesh_takes_the_partitionable_form(
        topo, monkeypatch):
    """GSPMD cannot partition a Mosaic kernel (found on four chips, PR
    22): under a multi-device mesh scope the LayerNorm op must lower to
    its jnp form, and the bare kernel is refused as the chip refuses it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu.ops import nn as ops_nn
    from mxnet_tpu.ops import paged
    from mxnet_tpu.parallel import mesh_scope

    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "0")
    ln = _mod("layer_norm")
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    x = jax.ShapeDtypeStruct((8192, 768), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    g = jax.ShapeDtypeStruct((768,), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    # the paged kernels' one predicate asks the platform, then the mesh
    with monkeypatch.context() as on_a_tpu:
        on_a_tpu.setattr(jax, "default_backend", lambda: "tpu")
        assert paged.kernels_on()
        with mesh_scope(mesh):
            assert not paged.kernels_on()
    with mesh_scope(mesh):
        assert not ln.supports(x, -1)
        compiled = jax.jit(ops_nn.layer_norm).lower(x, g, g).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert ln.supports(x, -1)  # no mesh scope: one chip takes the kernel
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        jax.jit(lambda x, g, b: ln.layer_norm_fused(x, g, b, 1e-5)
                ).lower(x, g, g).compile()


# the paged window kernel's callers at the shapes they ship: the smoke
# run's (chip_smoke.py) and transformer-big.translate-closed's (128 slots,
# 16 pages of 16 a slot, 16 heads of 64, a pool of 1,153 pages; PERF.md
# section 4)
SMOKE = (SLOTS, PAGES_PER_SLOT, PAGE, HEADS, HEAD_DIM,
         SLOTS * PAGES_PER_SLOT + 1)
BIG = (128, 16, 16, 16, 64, 1153)
# the decode call of the three other cells that make it, as (rows, pages a
# row, query heads, key/value heads, head size, the pool's shape): zaya's
# pools are declared as the kernel reads them, ouro's ride flattened over
# their four planes
ZAYA = (64, 32, 8, 2, 128, (64 * 32 + 1, 128 * 2, 128))
OURO = (10, 4, 16, 16, 128, (4 * 41, 128, 16, 128))
GRANITE = (64, 12, 32, 8, 64, (64 * 12 + 1, 128, 8, 64))


def _named_once(compiled, name="%paged_window"):
    """One call, under the name the ledger's breakdown lists it by; its
    operands' shapes."""
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert name in text
    call = next(ln for ln in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln)
    return re.findall(r"\w+\[[\d,]*\]", call.split(
        "operand_layout_constraints={")[1].split("frontend_attributes")[0])


def _decode_call(for_chip, B, P, Hq, Hkv, D, pool_shape, dtype):
    spec, compile_ = for_chip
    pfa = _mod("paged_flash_attention")
    pool = spec(pool_shape, dtype)
    return _named_once(compile_(
        lambda q, k, v, pt, pos: pfa.paged_decode_attention(
            q, k, v, pt, pos, sm_scale=D ** -0.5, kv_heads=Hkv),
        spec((B, Hq, D), dtype), pool, pool,
        spec((B, P), "int32"), spec((B,), "int32")))


@pytest.mark.parametrize("shape,dtype", [
    (SMOKE, "float32"), (SMOKE, "bfloat16"), (BIG, "bfloat16")])
def test_paged_decode_attention_compiles(for_chip, shape, dtype):
    """Heads of 64: the pipeline's form, a row a grid step whose operands
    are the row's pages one by one (Mosaic copies no page of ``(page x
    heads, 64)`` out of a pool left in HBM: "slice shape along dimension 2
    must be aligned to tiling (128), but is 64")."""
    pfa = _mod("paged_flash_attention")
    B, P, page, H, D, pool_pages = shape
    operands = _decode_call(for_chip, B, P, H, H, D,
                            (pool_pages, page, H, D), dtype)
    pages = pfa._window_tiles(P, page, H, D, jnp.dtype(dtype).itemsize)[0]
    assert len(operands) == 4 + 2 * pages


@pytest.mark.parametrize("cell", ["zaya", "ouro"])
def test_paged_decode_attention_walks_whole_pools(for_chip, cell):
    """Heads of 128 (PR 41): ONE call named ``%paged_window`` whose
    operands are the page table, the positions, the queries and the two
    pools WHOLE, left in HBM: no operand a page. The block and the softmax
    step come from the shapes alone, and two buffers of a block of K and
    of V stay far under the limit the kernel asks the compiler for."""
    pfa = _mod("paged_flash_attention")
    B, P, Hq, Hkv, D, pool_shape = {"zaya": ZAYA, "ouro": OURO}[cell]
    page = 128
    block, step = pfa._decode_tiles(P, page, Hkv, D, 2)
    assert (block, step) == {"zaya": (8, 8), "ouro": (2, 1)}[cell]
    assert 2 * 2 * block * page * Hkv * D * 2 <= 4 << 20 \
        and pfa._DECODE_VMEM_LIMIT <= 32 << 20
    operands = _decode_call(for_chip, B, P, Hq, Hkv, D, pool_shape,
                            "bfloat16")
    assert len(operands) == 5
    assert operands[3:] == \
        [f"bf16[{pool_shape[0]},{page * Hkv},{D}]"] * 2, operands


@pytest.mark.parametrize("tokens,tile", [(16, 16), (2048, 128)])
def test_grouped_expert_product_compiles(for_chip, tokens, tile):
    """The expert layer's grouped SwiGLU product at the published widths
    (128 experts of 768 over hidden 2048, 8 a token): a decode batch of 16
    rows (row tile 16) and a prefill chunk of 2,048 (row tile 128)."""
    spec, compile_ = for_chip
    gs = _mod("grouped_swiglu")
    E, H, F, k = 128, 2048, 768, 8
    assert gs.row_tile(tokens * k, E) == tile and gs.f_block(F) == 384
    M = -(-(tokens * k + E * (tile - 1)) // tile) * tile
    compile_(
        lambda x, te, nt, wg, wu, wd: gs.grouped_swiglu(
            x, te, nt, wg, wu, wd, tile),
        spec((M, H), "bfloat16"), spec((M // tile,), "int32"),
        spec((1,), "int32"), spec((E, H, F), "bfloat16"),
        spec((E, H, F), "bfloat16"), spec((E, F, H), "bfloat16"))


def test_selected_window_attention_compiles(for_chip):
    """The window over a selected set at the published widths: a chunk of
    2,048 queries, 32 query heads over 4 key/value heads of 128, pages of
    128 positions, 130 pages a row: 256 queries and 8 pages (1,024 keys) a
    grid step, whose blocks, scratch and scores stay under the limit the
    kernel asks the compiler for (which refuses what does not fit)."""
    spec, compile_ = for_chip
    pfa = _mod("paged_flash_attention")
    C, Hq, Hkv, D, page, P = 2048, 32, 4, 128, 128, 130
    tq, pages = pfa._selected_window_tiles(C, P, page)
    assert (tq, pages) == (256, 8)
    need = pfa._selected_window_vmem_bytes(tq, pages, page, Hkv, Hq // Hkv,
                                           D, itemsize=2)
    assert 24 << 20 < need < pfa._WINDOW_VMEM_LIMIT <= 64 << 20
    pool = spec((16 * P + 1, page, Hkv, D), "bfloat16")
    compiled = compile_(
        lambda q, k, v, pt, off, m: pfa.paged_selected_window_attention(
            q, k, v, pt, off, m, sm_scale=D ** -0.5),
        spec((1, C, Hq, D), "bfloat16"), pool, pool,
        spec((1, P), "int32"), spec((1,), "int32"),
        spec((1, C, P * page), "bool"))
    # one call, under the name the benchmark's reader keys on
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%dsa_selected_window" in text


def test_index_select_compiles(for_chip, paged_kernels):
    """The window's indexer at the published widths (PR 37): a chunk of
    2,048 queries of 16 index heads of 64 over 130 pages of 128 positions,
    ``topk`` 2,048. ONE Mosaic call a layer under the name the benchmark's
    reader keys on, 128 queries a grid step in key blocks of 256, its
    blocks and scratch under the limit it asks the compiler for; and the
    window's selection holds no loop over the ``(2048, 16640)`` scores any
    more (the radix select's sixteen trips and the score blocks' loop are
    the ``jax.numpy`` form's)."""
    from mxnet_tpu.ops import paged
    from mxnet_tpu.ops import sparse_attention as dsa

    spec, compile_ = for_chip
    ixs = _mod("index_select")
    paged_kernels(True)
    C, J, Di, L, topk = 2048, 16, 64, 16640, 2048
    assert ixs.index_select_tiles(C, L) == (128, 256)
    need = ixs.index_select_vmem_bytes(128, 256, L, J, Di, itemsize=2)
    assert 16 << 20 < need < ixs._VMEM_LIMIT <= 64 << 20
    block = paged.kv_block(L, 512)

    def window(qi, wi, ki, off, last):
        q_pos = off[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        n_blocks = jnp.minimum(last // block + 1, L // block)
        return dsa.window_select(qi, wi, ki, q_pos, n_blocks, block, topk)

    def loops_over_the_scores(text):
        return [ln for ln in text.splitlines()
                if re.search(r"= .* while\(", ln) and "2048,16640" in ln]

    args = (spec((1, C, J, Di), "bfloat16"), spec((1, C, J), "float32"),
            spec((1, L, Di), "bfloat16"), spec((1,), "int32"),
            spec((), "int32"))
    text = compile_(window, *args).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%dsa_index_select" in text
    assert not loops_over_the_scores(text)
    # with the paged kernels off (the CPU's and a mesh's form) the same
    # function is the two jax.numpy loops over the scores (another
    # callable: a trace is cached by the function, not by the environment)
    paged_kernels(False)
    plain = jax.jit(lambda *a: window(*a)).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in plain
    assert len(loops_over_the_scores(plain)) >= 2


def test_decode_step_selection_and_attention_compile(for_chip,
                                                     paged_kernels):
    """keye's decode step at the published widths (PR 39): 16 rows, 16
    index heads of 64, 32 query heads over 4 key/value heads of 128, 130
    pages of 128 positions, ``topk`` 2,048, bfloat16. The attention part of
    a layer is exactly TWO Mosaic calls under the names the benchmark's
    reader keys on, their blocks and scratch far under the limit they ask
    the compiler for, the indexer pool handed over as a view (no copy of
    it), and the text holds no sort and no gather. With the paged kernels
    off (the CPU's and a mesh's form) the same function sorts 16 x 16,640
    scores, gathers by token and makes no custom call."""
    from mxnet_tpu.ops import sparse_attention as dsa

    spec, compile_ = for_chip
    dec = _mod("dsa_decode")
    paged_kernels(True)
    B, J, Di, Hq, Hkv, D, page, P, topk = 16, 16, 64, 32, 4, 128, 128, 130, \
        2048
    block = _mod("page_walk").decode_tiles(P, page)
    pages = -(-P // block) * block
    assert (block, pages) == (8, 136)
    # two buffers of a block of pages, the page that takes the row's key,
    # the row's keys; two buffers of a block of K and of V, the selection a
    # (key, head) column twice (the pipeline's), the softmax carry
    select = 2 * Di * block * page * 2 + Di * page * 2 + pages * page * 4
    window = 2 * 2 * block * page * Hkv * D * 2 \
        + 2 * pages * page * Hkv * 4 + Hq * (128 + 128 + D) * 4
    assert select < 1 << 20 and 4 << 20 < window < 6 << 20 \
        and dec._VMEM_LIMIT <= 32 << 20
    kv = spec((B * P + 1, page, Hkv, D), "bfloat16")

    def step(q, qi, wi, ki, kp, vp, ip, pt, rows, pos, active):
        return dsa.selected_decode(q, qi, wi, ki, kp, vp, ip, pt, rows, pos,
                                   active, topk, D ** -0.5)

    args = (spec((B, Hq, D), "bfloat16"), spec((B, J, Di), "bfloat16"),
            spec((B, J), "float32"), spec((B, Di), "bfloat16"), kv, kv,
            spec((B * P + 1, page, Di), "bfloat16"), spec((B, P), "int32"),
            spec((B,), "int32"), spec((B,), "int32"), spec((B,), "bool"))
    text = compile_(step, *args).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "%dsa_decode_select" in text and "%dsa_decode_window" in text
    assert " sort(" not in text and " gather(" not in text
    # no pool is copied or relaid on its way to a kernel
    assert not [ln for ln in text.splitlines() if " copy(" in ln
                and f"[{B * P + 1}," in ln.split(" copy(")[0]]
    # another callable: a trace is cached by the function, not by what
    # the predicate answers
    paged_kernels(False)
    plain = jax.jit(lambda *a: step(*a)).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in plain
    assert [ln for ln in plain.splitlines()
            if " sort(" in ln and f"[{B},{P * page}]" in ln]
    assert " gather(" in plain


def test_grouped_decode_attention_compiles(for_chip):
    """granite-4.0-h-micro's decode attention: 64 rows, 32 query heads
    over 8 key/value heads of 64 (the group of 4 rides the window axis,
    every query at the row's position), 12 pages of 128 a row: heads of
    64 keep the pipeline's form, a row's 12 pages of K and of V as
    operands of one grid step."""
    B, P, Hq, Hkv, D, pool_shape = GRANITE
    assert len(_decode_call(for_chip, B, P, Hq, Hkv, D, pool_shape,
                            "bfloat16")) == 4 + 2 * P


def test_long_row_decode_attention_compiles(for_chip):
    """A row longer than a grid step takes: granite's widths at 128 pages
    a row (16k positions) go in steps of 16 pages, the widest step the
    tiling gives, under the limit the kernel hands the compiler."""
    spec, compile_ = for_chip
    pfa = _mod("paged_flash_attention")
    B, Hq, Hkv, D, page, P = 8, 32, 8, 64, 128, 128
    assert pfa._window_tiles(P, page, Hkv, D, 2) == (16, 1)
    pool = spec((B * P + 1, page, Hkv, D), "bfloat16")
    _named_once(compile_(
        lambda q, k, v, pt, pos: pfa.paged_decode_attention(
            q, k, v, pt, pos, sm_scale=1 / 64),
        spec((B, Hq, D), "bfloat16"), pool, pool, spec((B, P), "int32"),
        spec((B,), "int32")))


def test_hybrid_chunk_attention_compiles(for_chip):
    """The same model's chunk attention through the selected-window kernel
    with a causal mask: a chunk of 512 queries, heads of 64."""
    spec, compile_ = for_chip
    pfa = _mod("paged_flash_attention")
    C, Hq, Hkv, D, page, P = 512, 32, 8, 64, 128, 12
    pool = spec((64 * P + 1, page, Hkv, D), "bfloat16")
    compile_(
        lambda q, k, v, pt, off, m: pfa.paged_selected_window_attention(
            q, k, v, pt, off, m, sm_scale=1 / 64),
        spec((1, C, Hq, D), "bfloat16"), pool, pool, spec((1, P), "int32"),
        spec((1,), "int32"), spec((1, C, P * page), "bool"))


# ---- pools of heads of 64 as granite, transformer-big and the smoke run
# declare them since PR 46: ``(num_pages, page, heads x 64)``, a page's
# (head, d) on whole lanes
LANES = {
    # cell: (rows, pages a row, query heads, key/value heads, head size,
    # page, pool pages)
    "granite": (64, 12, 32, 8, 64, 128, 64 * 12 + 1),
    "transformer-big": (128, 9, 16, 16, 64, 16, 1153),
    "smoke": (SLOTS, PAGES_PER_SLOT, HEADS, HEADS, HEAD_DIM, PAGE,
              SLOTS * PAGES_PER_SLOT + 1),
}


@pytest.mark.parametrize("cell", sorted(LANES))
def test_decode_attention_over_heads_on_the_lanes_compiles(for_chip, cell):
    """The decode call of the cells of heads of 64 over a pool ``(pages,
    page, heads x 64)``: ONE call named ``%paged_window`` whose page
    operands are ``(1, page, heads x 64)`` blocks of the pool AS DECLARED
    (Mosaic takes such a page: its last axis is whole lanes), the query
    rows on their own head's lanes of the ``heads x 64``."""
    pfa = _mod("paged_flash_attention")
    B, P, Hq, Hkv, D, page, pool_pages = LANES[cell]
    pages, block = pfa._lane_window_tiles(P, page, Hkv, D, 2)
    assert (pages, block) == {"granite": (12, 1), "transformer-big": (9, 8),
                              "smoke": (2, 2)}[cell]
    assert pfa._lane_heads(Hkv, D, Hq // Hkv) == Hkv   # one product a block
    operands = _decode_call(for_chip, B, P, Hq, Hkv, D,
                            (pool_pages, page, Hkv * D), "bfloat16")
    assert len(operands) == 4 + 2 * pages
    assert operands[3] == f"bf16[{B},{Hq},{Hkv * D}]"
    assert set(operands[4:]) == {f"bf16[{pool_pages},{page},{Hkv * D}]"}


@pytest.mark.parametrize("window", [3, 4, 16, 32, 128])
def test_window_attention_over_heads_on_the_lanes_compiles(for_chip, window):
    """transformer-big's windows (suffix replay, speculative verification)
    over its pool ``(1153, 16, 1024)``: the whole block-diagonal to 256
    query rows (16 heads x 16 positions), two heads a product past it,
    under the limit the kernel hands the compiler."""
    spec, compile_ = for_chip
    pfa = _mod("paged_flash_attention")
    _, P, H, _, D, page, pool_pages = LANES["transformer-big"]
    assert pfa._lane_heads(H, D, window) == (H if window <= 16 else 2)
    B = 8
    pool = spec((pool_pages, page, H * D), "bfloat16")
    rows = spec((B,), "int32")
    _named_once(compile_(
        lambda q, k, v, pt, off, vl: pfa.paged_window_attention(
            q, k, v, pt, off, vl, sm_scale=D ** -0.5),
        spec((B, window, H, D), "bfloat16"), pool, pool,
        spec((B, P), "int32"), rows, rows))


def test_hybrid_chunk_attention_over_heads_on_the_lanes_compiles(for_chip):
    """granite's chunk attention as it runs since PR 46: the selected
    window under a causal mask over pools ``(769, 128, 512)``, two heads
    of 64 a product, 128 queries a block, nothing relaid."""
    spec, compile_ = for_chip
    pfa = _mod("paged_flash_attention")
    C, Hq, Hkv, D, page, P = 512, 32, 8, 64, 128, 12
    pool = spec((64 * P + 1, page, Hkv * D), "bfloat16")
    operands = _named_once(compile_(
        lambda q, k, v, pt, off, m: pfa.paged_selected_window_attention(
            q, k, v, pt, off, m, sm_scale=1 / 64),
        spec((1, C, Hq, D), "bfloat16"), pool, pool, spec((1, P), "int32"),
        spec((1,), "int32"), spec((1, C, P * page), "bool")),
        "%dsa_selected_window")
    # (blocks of 128 queries, sets of two heads, 2 x 4 x 128 rows, 128 lanes)
    assert operands[2] == "bf16[1,4,4,1024,128]"


@pytest.mark.parametrize("which", ["step", "chunk"])
def test_attention_over_a_pool_of_planes_compiles(for_chip, which):
    """Ouro-2.6B's two attention calls over pools with a plane a pass
    ``(4, 41, 128, 16, 128)``, read flattened over planes and pages
    through the page table moved by a ``fori_loop``'s carried index: the
    decode step's 10 rows of 16 heads of 128 over 4 pages a row, and a
    chunk of 256 queries through the selected-window kernel with a causal
    mask. No whole-pool copy stands before either (a page is read where
    it lies)."""
    spec, compile_ = for_chip
    pfa = _mod("paged_flash_attention")
    T, B, H, D, page, P = 4, 10, 16, 128, 128, 4
    N = B * P + 1
    pool = spec((T, N, page, H, D), "bfloat16")

    def pages(p):
        return p.reshape((-1,) + p.shape[2:])

    def step(q, k, v, pt, pos):
        def body(t, acc):
            return acc + pfa.paged_decode_attention(
                q, pages(k), pages(v), pt + t * N, pos, sm_scale=D ** -0.5)
        return jax.lax.fori_loop(0, T, body, jnp.zeros_like(q))

    def chunk(q, k, v, pt, off, m):
        def body(t, acc):
            return acc + pfa.paged_selected_window_attention(
                q, pages(k), pages(v), pt + t * N, off, m,
                sm_scale=D ** -0.5)
        return jax.lax.fori_loop(
            0, T, body, jnp.zeros(q.shape[:2] + (H * D,), q.dtype))

    if which == "step":
        compiled = compile_(step, spec((B, H, D), "bfloat16"), pool, pool,
                            spec((B, P), "int32"), spec((B,), "int32"))
    else:
        compiled = compile_(chunk, spec((1, 256, H, D), "bfloat16"), pool,
                            pool, spec((1, P), "int32"), spec((1,), "int32"),
                            spec((1, 256, P * page), "bool"))
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("shape,dtype,window", [
    (SMOKE, dtype, window) for dtype in ("float32", "bfloat16")
    for window in (1, 2, 4, 16)] + [
    (SMOKE, "bfloat16", 3), (BIG, "bfloat16", 4), (BIG, "bfloat16", 16)])
def test_paged_window_attention_compiles(for_chip, shape, dtype, window):
    """Suffix replay and speculative verification windows: the smoke run's
    shapes, a window that is no power of two, and transformer-big's
    published shape at the windows its warm-up compiles."""
    spec, compile_ = for_chip
    pfa = _mod("paged_flash_attention")
    B, P, page, H, D, pool_pages = shape
    pool = spec((pool_pages, page, H, D), dtype)
    rows = spec((B,), "int32")
    _named_once(compile_(
        lambda q, k, v, pt, off, vl: pfa.paged_window_attention(
            q, k, v, pt, off, vl, sm_scale=D ** -0.5),
        spec((B, window, H, D), dtype), pool, pool,
        spec((B, P), "int32"), rows, rows))


@pytest.mark.parametrize("backward", ["xla", "pallas"])
def test_flash_attention_compiles_above_dense_max(for_chip, monkeypatch,
                                                  backward):
    """``examples/long_context_attention.py`` depends on the kernel path
    that ``ops/contrib.py`` takes above ``MXTPU_ATTN_DENSE_MAX``."""
    from mxnet_tpu.ops.contrib import _dense_max_seq

    spec, compile_ = for_chip
    fa = _mod("flash_attention")
    S = 1024
    assert S > _dense_max_seq()
    monkeypatch.setattr(fa, "_BWD_IMPL", backward)
    q = spec((2, HEADS, S, HEAD_DIM), "bfloat16")
    vl = spec((2,), "int32")

    def fwd(q, k, v, vl):
        return fa.flash_attention(q, k, v, vl, True)

    def loss(q, k, v, vl):
        return fwd(q, k, v, vl).astype(jnp.float32).sum()

    compile_(fwd, q, q, q, vl)
    compile_(jax.grad(loss, argnums=(0, 1, 2)), q, q, q, vl)


# ------------------------------------------------------------- chip_smoke
def _run_smoke(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"), *args],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_chip_smoke_rehearses_every_phase():
    proc = _run_smoke("--rehearse")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rows = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.startswith("{")]
    assert [r.get("phase") for r in rows[:-1]] == \
        ["device", "train", "serve", "total"]
    last = rows[-1]
    # a rehearsal reports its platform truthfully and is never a success
    assert last == {"rehearsal": "passed",
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    serve = rows[2]
    assert serve["steady_state_recompiles"] == 0
    assert serve["free_pages"] == serve["num_pages"]


def test_chip_smoke_without_a_chip_fails():
    proc = _run_smoke()
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stdout


# ---------------------------------------------------------- compile cache
def test_setup_leaves_cache_dir_to_jax_when_placed_from_outside(
        monkeypatch, tmp_path):
    from mxnet_tpu import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append(name))
    monkeypatch.setattr(compile_cache, "_DIR", None)
    monkeypatch.setattr(compile_cache, "_ENABLED", False)
    monkeypatch.delenv("MXTPU_COMPILE_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.setup()
    assert "jax_compilation_cache_dir" not in calls
    assert compile_cache.cache_dir() == str(tmp_path)
    assert compile_cache.cache_stats()["dir"] == str(tmp_path)
    # unset: one fixed, git-ignored path inside the checkout
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    compile_cache.setup()
    assert calls == ["jax_compilation_cache_dir"]
    assert compile_cache.cache_dir() == os.path.join(
        REPO_ROOT, ".mxtpu_cache", "xla")


# ---- joyai-llm-flash's latent attention at the published widths (PR 33):
# 32 heads, a latent of 512 with one rotary key of 64 a position in a row of
# 640 (whole lanes), pages of 128 positions, 130 pages a row
def _named(compiled, name, calls=1):
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == calls
    assert name in text


@pytest.mark.parametrize("rows,positions", [(40, 2), (1, 1)])
def test_latent_decode_attention_compiles(for_chip, rows, positions):
    """The absorbed decode kernel: 40 rows of two query positions of 32
    heads (64 query rows) over latent pages of 640 numbers a position, the
    pool ONE operand left in HBM and a block of 8 pages a copy, two buffers
    deep; and one row of one position."""
    spec, compile_ = for_chip
    mla = _mod("mla_attention")
    H, rank, rope, page, P = 32, 512, 128, 128, 130
    block = _mod("page_walk").decode_tiles(P, page)
    assert block == 8
    # what the kernel asks of VMEM: two buffers of a block of the pool and
    # the softmax carry (two float32 lane groups and the weighted latents
    # for a row's query rows)
    scratch = 2 * block * page * (rank + rope) * 2 \
        + positions * H * (128 + 128 + rank) * 4
    assert scratch < mla._VMEM_LIMIT
    compiled = compile_(
        mla.mla_latent_decode,
        spec((rows, positions, H, rank), "bfloat16"),
        spec((rows, positions, H, rope), "bfloat16"),
        spec((40 * P + 1, page, rank + rope), "bfloat16"),
        spec((rows, P), "int32"), spec((rows,), "int32"))
    _named(compiled, "%mla_latent_decode")
    # the pool goes to the kernel as it lies: nothing copies or relays it
    assert "copy(%pool" not in compiled.as_text()


def test_latent_prefill_attention_compiles(for_chip):
    """The expanded chunk kernel: 2,048 queries of 32 heads of 128 + 64
    against 16,896 expanded positions (130 pages rounded up to whole key
    blocks), 1,024 queries by 512 keys a grid step, a head's keys and
    values read as column blocks of the expansion."""
    spec, compile_ = for_chip
    mla = _mod("mla_attention")
    H, D, rope, C, L = 32, 128, 128, 2048, 16896
    assert mla.prefill_tiles(C, L) == (1024, 512)
    _named(compile_(
        mla.mla_prefill,
        spec((1, H, C, D), "bfloat16"), spec((1, H, C, rope), "bfloat16"),
        spec((1, L, H * 2 * D), "bfloat16"), spec((1, L, rope), "bfloat16"),
        spec((1,), "int32")), "%mla_prefill")


def test_latent_prefill_attention_compiles_at_rows_of_33k(for_chip):
    """The same kernel at the rows ``xing4.0-29b-a4b`` serves: 258 pages of
    128 positions rounded up to whole key blocks, 65 of them a query
    block."""
    spec, compile_ = for_chip
    mla = _mod("mla_attention")
    H, D, rope, C, L = 32, 128, 128, 2048, 33280
    assert mla.prefill_tiles(C, L) == (1024, 512)
    _named(compile_(
        mla.mla_prefill,
        spec((1, H, C, D), "bfloat16"), spec((1, H, C, rope), "bfloat16"),
        spec((1, L, H * 2 * D), "bfloat16"), spec((1, L, rope), "bfloat16"),
        spec((1,), "int32")), "%mla_prefill")


def test_latent_prefill_attention_walks_to_the_causal_edge(for_chip):
    """What PR 45 changed is in the compiled call: the grid has THREE axes
    (rows, heads, query blocks: the key blocks are a loop inside, as long
    as the offset says) and the expansion goes in ONCE, whole, as it lies
    (a static grid's call took it twice, as keys and as values): no
    temporary, no copy."""
    spec, compile_ = for_chip
    mla = _mod("mla_attention")
    H, D, rope, C, L = 32, 128, 128, 2048, 33280
    args = (spec((1, H, C, D), "bfloat16"), spec((1, H, C, rope), "bfloat16"),
            spec((1, L, H * 2 * D), "bfloat16"),
            spec((1, L, rope), "bfloat16"), spec((1,), "int32"))
    compiled = compile_(mla.mla_prefill, *args)
    assert _named_once(compiled, "%mla_prefill") == [
        "s32[1]", "bf16[1,32,2048,128]", "bf16[1,32,2048,128]",
        "bf16[1,33280,8192]", "bf16[1,33280,128]"]
    text = compiled.as_text()
    assert "%mla_prefill.1 = bf16[1,2048,4096]" in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    assert "copy(%kv" not in text
    assert "grid=(1, 32, 2)," in str(jax.make_jaxpr(mla.mla_prefill)(*args))


@pytest.mark.parametrize("rows", [2048, 24])
def test_the_stream_mixing_kernels_compile(for_chip, rows):
    """The pass over a residual stream four wide at Xing4.0's width (3,584
    a stream, 14,336 a token): a chunk's 2,048 tokens in tiles of 64, and a
    decode step's 24 padded to 32, each of the three calls."""
    from mxnet_tpu.ops import hyper_connection as hc

    spec, compile_ = for_chip
    k = _mod("mhc_mix")
    n, C = 4, 3584
    cfg = hc.HC(n, 20, 1e-6, -30.0, 30.0)
    T = -(-rows // k.ROWS) * k.ROWS
    assert k.tiles(T, C) == ((64, 512) if rows == 2048 else (32, 512))
    mixer = hc.Mixer(spec((n * C, 24), "bfloat16"), spec((3,), "bfloat16"),
                     spec((24,), "bfloat16"))
    X, y = spec((T, n * C), "bfloat16"), spec((T, C), "bfloat16")
    res, post = spec((T, n * n), "float32"), spec((T, n), "float32")
    _named(compile_(lambda x, *m: k.mhc_enter(x, hc.Mixer(*m), cfg),
                    y, *mixer), "%mhc_enter")
    _named(compile_(lambda X, y, r, p, *m: k.mhc_mix(
        X, y, r, p, hc.Mixer(*m), cfg), X, y, res, post, *mixer),
        "%mhc_mix")
    _named(compile_(lambda X, y, r, p: k.mhc_leave(X, y, r, p, cfg),
                    X, y, res, post), "%mhc_leave")


def test_held_experts_product_compiles(for_chip):
    """The grouped expert product over ONE CHIP'S SHARE, 16 experts held of
    256: a decode step's 80 tokens (row tile 16) and a chunk's 2,048 (row
    tile 128), the sink of the pairs held elsewhere sorted last."""
    spec, compile_ = for_chip
    gs = _mod("grouped_swiglu")
    E, n, H, F, k = 256, 16, 2048, 768, 8
    for tokens, tile in ((80, 16), (2048, 128)):
        assert gs.row_tile(tokens * k, n) == tile
        compile_(
            lambda u, r, b, wg, wu, wd: gs.moe_experts(
                u, r, wg, wu, wd, k, scoring="sigmoid", bias=b, scale=2.5,
                held=(0, n))[0],
            spec((tokens, H), "bfloat16"), spec((H, E), "bfloat16"),
            spec((E,), "bfloat16"), spec((n, H, F), "bfloat16"),
            spec((n, H, F), "bfloat16"), spec((n, F, H), "bfloat16"))


@pytest.mark.parametrize("tokens,tile", [(64, 16), (1024, 128)])
def test_top1_full_width_expert_product_compiles(for_chip, tokens, tile):
    """ZAYA1-8B's expert layer: 16 experts of the model's own width (hidden
    2048, expert width 2048), ONE a token: a decode step's 64 rows (tiles
    of 16, a quarter full) and a chunk's 1,024 (tiles of 128). The width
    takes columns 512 a grid step: three weight blocks of 2 MB, two
    buffers each, beside the row tile and its accumulator, inside the 48
    MB the kernel asks for."""
    spec, compile_ = for_chip
    gs = _mod("grouped_swiglu")
    E, H, F = 16, 2048, 2048
    assert gs.row_tile(tokens, E) == tile and gs.f_block(F) == 512
    assert 3 * 2 * H * gs.f_block(F) * 2 + tile * H * (2 * 2 * 2 + 4) \
        < 48 * 1024 * 1024
    compile_(
        lambda u, e, a, wg, wu, wd: gs.dispatch_experts(
            u, e, a, wg, wu, wd)[0],
        spec((tokens, H), "bfloat16"), spec((tokens, 1), "int32"),
        spec((tokens, 1), "float32"), spec((E, H, F), "bfloat16"),
        spec((E, H, F), "bfloat16"), spec((E, F, H), "bfloat16"))


@pytest.mark.parametrize("which", ["step", "chunk"])
def test_attention_over_a_pool_of_key_head_rows_compiles(for_chip, which):
    """ZAYA1-8B's attention: 8 query heads over 2 key/value heads of 128,
    the pools declared ``(num_pages, 128 x 2, 128)`` (a page's (key, head)
    rows on one axis: whole ``(16, 128)`` tiles, where ``(num_pages, 128,
    2, 128)`` pads two heads to sixteen rows). The decode step's 64 rows of
    32 pages walk their live pages, 8 a block (``test_paged_decode_
    attention_walks_whole_pools``); a chunk of 1,024 positions goes as 8
    rows of the window kernel's grid, 4 heads x 128 positions on the window
    axis each, a row's pages in one grid step, inside the VMEM the kernel
    asks for."""
    spec, compile_ = for_chip
    pfa = _mod("paged_flash_attention")
    B, P, Hq, Hkv, D, pool_shape = ZAYA
    page = pool_shape[1] // Hkv
    pool = spec(pool_shape, "bfloat16")
    assert pfa._page_size(pool, Hkv, D) == page
    if which == "step":
        assert _decode_call(for_chip, *ZAYA, "bfloat16")[3:] == \
            ["bf16[2049,256,128]"] * 2
    else:
        C, tq = 1024, 128
        pages, block = pfa._window_tiles(P, page, Hkv, D, 2)
        assert (pages, block) == (32, 2)
        assert pfa._window_vmem_bytes(pages, block, page, Hkv,
                                      Hq // Hkv * tq, D, 2) \
            < pfa._WINDOW_STEP_VMEM_LIMIT
        assert len(_named_once(compile_(
            lambda q, k, v, pt, off, vl: pfa.paged_window_attention(
                q, k, v, pt, off, vl, sm_scale=0.088, kv_heads=Hkv),
            spec((C // tq, tq, Hq, D), "bfloat16"), pool, pool,
            spec((C // tq, P), "int32"), spec((C // tq,), "int32"),
            spec((C // tq,), "int32")))) == 4 + 2 * pages


# ---- the decode bursts of the three cells whose step calls
# ``paged_decode_attention``, at the configurations' own widths, slots and
# pages and a few layers (the whole depths, compiled in a scratch script:
# PERF.md section 6, PR 41 and PR 46)
BURSTS = {
    # cell: (layers kept, a bound on the temporaries in bytes: what the
    # burst of the WHOLE depth reads (PERF.md sections 4 and 6))
    "zaya1-8b": (2, 0.047e9),
    "ouro-2.6b": (2, 0.07e9),
    # granite's pools of heads of 64 were copied to the kernel's layout
    # before the loop and back after it, 16 copies of 101 MB and 1.6 GB of
    # temporaries at the whole depth (PERF.md 7 (u)), until PR 46 declared
    # them ``(pages, page, heads x 64)``: 0.171 GB at the whole depth since.
    # Six layers hold ONE attention layer
    "granite-4.0-h-micro": (6, 0.2e9),
}
# the chunk program of the cell whose pools PR 46 moved: the parent's copied
# the 16 pools too (1.505 GB of temporaries at the whole depth, 0.379 since)
CHUNKS = {"granite-4.0-h-micro": (6, 0.4e9)}


def _cell_engine(monkeypatch, paged_kernels, cell, layers):
    """``(engine, paged state as shapes, serving block, pages a slot)`` of a
    cell's own configuration cut to ``layers``, zeros for weights, the
    paged kernels on and interpret mode forbidden."""
    from mxnet_tpu import nd
    from mxnet_tpu.parallel import InferStep

    monkeypatch.syspath_prepend(REPO_ROOT)
    from perf.harness.loader import load_module

    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "0")
    paged_kernels(True)
    perf = os.path.join(REPO_ROOT, "perf")
    with open(os.path.join(perf, "configs", cell + ".json")) as f:
        cfg = json.load(f)
    cfg["num_hidden_layers"] = layers
    if "layer_types" in cfg:
        cfg["layer_types"] = cfg["layer_types"][:layers]
    driver = load_module(os.path.join(perf, "drivers",
                                      cfg["driver"] + ".py"))
    ref = load_module(os.path.join(perf, "reference", cell + ".py"))
    mod, cls = cfg["program"]["model"].split(":")
    net = getattr(importlib.import_module(mod), cls)(
        **driver._model_kwargs(cfg))
    net.collect_params().setattr("grad_req", "null")
    params, dtype = net._collect_params_with_prefix(), \
        cfg["precision"]["weights"]
    for name, shape in ref.tensor_specs(cfg).items():
        params[name].set_data(nd.NDArray(jnp.zeros(shape, dtype)))
    eng = InferStep(net, amp=dtype, eos_id=-1)
    srv = cfg["serving"]
    slots, page = srv["slots"], srv["page_size"]
    P = -(-(max(srv["prompt_buckets"]) + srv["max_new_tokens"]) // page)
    state = jax.eval_shape(
        lambda: eng.init_paged_state(slots, slots * P, page, 0))
    return eng, state, srv, P


def _compiled_for(one_chip, fn, *operands):
    return fn.lower(*jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        operands)).compile()


def _pool_copies(compiled, state):
    """The lines of the compiled program that copy something of a K/V
    pool's size, whatever view of the pool it is."""
    sizes = {p.size for p in state["k_pools"]}
    return [ln for ln in compiled.as_text().splitlines()
            if " copy(" in ln and sizes & {
                math.prod(map(int, dims.split(","))) for dims in re.findall(
                    r"\[([\d,]+)\]", ln.split(" copy(")[0])}]


def _int(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


@pytest.mark.parametrize("cell", sorted(BURSTS))
def test_decode_burst_copies_no_pool(one_chip, monkeypatch, paged_kernels,
                                     cell):
    """The burst program (``iter_tokens`` decode steps in one ``while``)
    compiled for the described chip from the cell's own configuration,
    zeros for weights: no pool is copied whole on its way to
    ``%paged_window`` (one such copy a layer is 268 MB in zaya, which holds
    14.92 of 16.9 GB; 101 MB in granite, where the parent made four a
    layer), and the temporaries stay under what the burst reads at the
    whole depth."""
    layers, temporaries = BURSTS[cell]
    eng, state, srv, P = _cell_engine(monkeypatch, paged_kernels, cell,
                                      layers)
    slots = srv["slots"]
    compiled = _compiled_for(
        one_chip, eng._get_decode_iter_fn(srv["iter_tokens"], "greedy", 0),
        eng._values, state, _int(slots, P), _int(slots), _int(slots),
        jax.ShapeDtypeStruct((slots,), jnp.bool_), _int(),
        jax.ShapeDtypeStruct((), jnp.float32))
    assert compiled.as_text().count("%paged_window") >= len(state["k_pools"])
    assert _pool_copies(compiled, state) == []
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries


@pytest.mark.parametrize("cell", sorted(CHUNKS))
def test_chunk_program_copies_no_pool(one_chip, monkeypatch, paged_kernels,
                                      cell):
    """The chunk program (one row of ``prefill_chunk`` positions, as the
    scheduler dispatches it) of the cell whose pools are declared
    ``(pages, page, heads x 64)``: the pools go to ``write_rows``' scatter
    and to ``%dsa_selected_window`` as they lie, none copied, none among
    the temporaries."""
    layers, temporaries = CHUNKS[cell]
    eng, state, srv, P = _cell_engine(monkeypatch, paged_kernels, cell,
                                      layers)
    assert {p.shape for p in state["k_pools"]} == {(769, 128, 512)}
    compiled = _compiled_for(
        one_chip, eng._get_suffix_fn("greedy", 0, True), eng._values, state,
        _int(1, srv["prefill_chunk"]), _int(1), _int(1), _int(1, P), _int(1),
        jax.ShapeDtypeStruct((1,), jnp.bool_), _int(),
        jax.ShapeDtypeStruct((), jnp.float32))
    assert compiled.as_text().count("%dsa_selected_window") >= \
        len(state["k_pools"])
    assert _pool_copies(compiled, state) == []
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries


@pytest.mark.parametrize("which", ["burst", "admission"])
def test_transformer_big_programs_copy_no_pool(one_chip, monkeypatch,
                                               paged_kernels, which):
    """transformer-big at its whole depth, zeros for weights: the decode
    burst and the admission prefill (which writes ONE position a row into
    the pools) over pools ``(1153, 16, 1024)``. The parent's made 24 copies
    of 38 MB in each (0.92 GB of temporaries in the burst: PERF.md 7
    (u))."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.parallel import InferStep

    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "0")
    paged_kernels(True)
    with open(os.path.join(REPO_ROOT, "perf", "configs",
                           "transformer-big.json")) as f:
        cfg = json.load(f)
    prog, srv = cfg["program"], cfg["serving"]
    mod, cls = prog["model"].split(":")
    net = getattr(importlib.import_module(mod), cls)(
        **{k: cfg[v] for k, v in prog["kwargs"].items()})
    net.initialize(mx.initializer.Zero())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    eng = InferStep(net, amp=cfg["precision"]["weights"],
                    max_len=srv["max_len"])
    slots, page, bucket = srv["slots"], srv["page_size"], \
        max(srv["prompt_buckets"])
    P = -(-(1 + srv["max_new_tokens"]) // page)
    state = jax.eval_shape(
        lambda: eng.init_paged_state(slots, slots * P, page, bucket))
    assert {p.shape for p in state["k_pools"]} == {(1153, 16, 1024)}
    flags = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    scalars = (_int(), jax.ShapeDtypeStruct((), jnp.float32))
    if which == "burst":
        compiled = _compiled_for(
            one_chip, eng._get_decode_iter_fn(8, "greedy", 0), eng._values,
            state, _int(slots, P), _int(slots), _int(slots), flags, *scalars)
        assert compiled.as_text().count("%paged_window") >= 6
        temporaries = 0.1e9
    else:
        # 128 rows of 128 source positions: the encoder's own activations
        temporaries = 0.5e9
        compiled = _compiled_for(
            one_chip, eng._get_paged_prefill_fn("greedy", 0), eng._values,
            state, _int(slots, bucket), _int(slots), _int(slots),
            _int(slots), flags, *scalars)
    assert _pool_copies(compiled, state) == []
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries


# ---- the gated delta rule (PR 48): the step's kernel at olmo-hybrid-7b's
# own shapes (16 rows of 30 heads of 96 x 192), and the cell's two programs
# at one period of its layers
def test_gated_delta_step_kernel_compiles(for_chip):
    """``%gated_delta_step`` takes a row's 30 states (2.2 MB) a grid step, a
    head's key a COLUMN (``(d_k, heads)`` operands) and its decay a row: a
    number broadcast along sublanes AND lanes is what Mosaic refused."""
    spec, compile_ = for_chip
    gd = _mod("gated_delta")
    B, H, dk, dv = 16, 30, 96, 192
    compiled = compile_(
        gd.gated_delta_step, spec((B, H, dk, dv), "float32"),
        spec((B, H, dk), "float32"), spec((B, H, dk), "float32"),
        spec((B, H, dv), "float32"), spec((B, H), "float32"),
        spec((B, H), "float32"), spec((B,), "bool"))
    _named(compiled, "%gated_delta_step")


@pytest.mark.parametrize("which", ["burst", "chunk"])
def test_olmo_programs_copy_no_pool_and_no_state(one_chip, monkeypatch,
                                                 paged_kernels, which):
    """The cell's two programs at the configuration's own widths, slots and
    pages and ONE period of its layers (three delta-rule layers and a
    full-attention layer; the whole depth, compiled in a scratch script,
    reads 13.82 GB of arguments, 0.05 GB of temporaries in the burst and
    0.76 in a chunk of 1,024: PERF.md section 6, PR 48), zeros for weights: the
    pools ``(641, 128, 3840)`` reach the lane forms of ``%paged_window``
    as they lie (a page of (key, head) rows on one axis made the chunk's
    call ask 77 MB of VMEM: 30 query heads against 30 key heads in one
    product), the slots' states ``(16, 30, 96, 192)`` reach
    ``%gated_delta_step`` aliased onto themselves (the chunk's blocked rule
    is XLA's own: its kernel lost on the chip and went), and no program
    copies either."""
    eng, state, srv, P = _cell_engine(monkeypatch, paged_kernels,
                                      "olmo-hybrid-7b", 4)
    slots = srv["slots"]
    assert {p.shape for p in state["k_pools"]} == {(641, 128, 3840)}
    assert {p.shape for p in state["delta"]} == {(16, 30, 96, 192)}
    if which == "burst":
        compiled = _compiled_for(
            one_chip,
            eng._get_decode_iter_fn(srv["iter_tokens"], "greedy", 0),
            eng._values, state, _int(slots, P), _int(slots), _int(slots),
            jax.ShapeDtypeStruct((slots,), jnp.bool_), _int(),
            jax.ShapeDtypeStruct((), jnp.float32))
        kernel, temporaries = "%gated_delta_step", 0.05e9
    else:
        compiled = _compiled_for(
            one_chip, eng._get_suffix_fn("greedy", 0, True), eng._values,
            state, _int(1, srv["prefill_chunk"]), _int(1), _int(1),
            _int(1, P), _int(1), jax.ShapeDtypeStruct((1,), jnp.bool_),
            _int(), jax.ShapeDtypeStruct((), jnp.float32))
        # a chunk of 2,048 since the second hand-in (0.69 GB at one period
        # of layers; 0.34 at the issue's first chunk of 1,024)
        kernel, temporaries = "%paged_window", 0.9e9
    text = compiled.as_text()
    assert text.count("%paged_window") >= 1 and text.count(kernel) >= 1
    assert "%gated_delta_chunk" not in text
    assert _pool_copies(compiled, state) == []
    assert _pool_copies(compiled, {"k_pools": state["delta"]}) == []
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < temporaries
    # one period's weights, the head and the embedding, the pools and the
    # slot arrays: what the configuration reckons, a period for four
    assert 4.55e9 < memory.argument_size_in_bytes < 4.65e9

"""The decoder-only language model (``model_zoo/keye.py``) against its plain
reference (``perf/reference/keye-vl2-30b-a3b.py``) at the tiny preset, on
seeded random weights: full forward, chunked prefill and paged decode,
selected sets, rotary components, routing, and the whole path through
``ContinuousBatcher``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 - the package sets JAX up
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.keye import KeyeLM
from mxnet_tpu.ops import paged
from mxnet_tpu.ops import sparse_attention as dsa
from mxnet_tpu.ops.pallas import grouped_swiglu as moe
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import make_batcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perf.harness.loader import load_module  # noqa: E402

TINY = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "rope_theta": 1e7, "rope_scaling": {"mrope_section": [2, 2, 4]},
    "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 8,
                  "indexer_num_kv_heads": 1, "topk": 8, "kv_chunk_size": 4,
                  "q_chunk_size": 4}}
PAGE, CHUNK, SEED = 4, 8, 11


@pytest.fixture(scope="module")
def ref():
    return load_module(os.path.join(REPO, "perf", "reference",
                                    "keye-vl2-30b-a3b.py"))


@pytest.fixture(autouse=True)
def highest_precision():
    """The program's products in float32 proper, on every thread (the
    scheduler's too), as the reference has them."""
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)


def build(ref, cfg=TINY, seed=SEED, dtype="float32"):
    sa = cfg["sa_config"]
    net = KeyeLM(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        kv_chunk=sa["kv_chunk_size"], rope_theta=cfg["rope_theta"],
        mrope_section=cfg["rope_scaling"]["mrope_section"], dtype=dtype)
    params = net._collect_params_with_prefix()
    assert set(params) == set(ref.tensor_specs(cfg))
    for name, p in params.items():
        p.set_data(nd.NDArray(ref.tensor(seed, cfg, name).astype(dtype)))
    return net


@pytest.fixture(scope="module")
def net(ref):
    return build(ref)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, TINY["vocab_size"], n) \
        .astype(np.int32)


# ------------------------------------------------------------ full forward
@pytest.mark.parametrize("length", [5, 8, 20])     # under, at, over topk
def test_full_forward_logits(ref, net, length):
    toks = tokens(length, length)
    got = net(nd.array(toks[None], dtype="int32")).asnumpy()[0]
    want = np.asarray(ref.forward(SEED, TINY, toks))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_full_forward_through_the_chips_kernels(ref, net, monkeypatch,
                                                paged_kernels):
    """256 tokens in one window of 128-position pages, the paged kernels
    forced on: index scores and selection in the Pallas indexer, attention
    in the selected-window kernel (both interpreted here), against the
    reference's logits. ``topk`` 8 falls inside the first query block."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    paged_kernels(True)
    toks = tokens(256, 9)
    x = jnp.asarray(toks[None], jnp.int32)
    assert "dsa_index_select" in str(jax.make_jaxpr(
        lambda t: net.hybrid_forward(None, t).data)(x))
    got = net(nd.array(toks[None], dtype="int32")).asnumpy()[0]
    want = np.asarray(ref.forward(SEED, TINY, toks))
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_mrope_with_unequal_components(ref, net):
    toks = tokens(12, 3)
    rng = np.random.default_rng(4)
    pos3 = np.stack([np.arange(12), rng.integers(0, 9, 12),
                     rng.integers(0, 9, 12)], -1).astype(np.int32)
    got = net(nd.array(toks[None], dtype="int32"),
              nd.array(pos3[None], dtype="int32")).asnumpy()[0]
    want = np.asarray(ref.forward(SEED, TINY, toks, positions=pos3))
    np.testing.assert_allclose(got, want, atol=2e-5)
    text = np.asarray(ref.forward(SEED, TINY, toks))
    assert np.abs(want - text).max() > 1e-2     # the components matter


# ------------------------------------------- chunked prefill, paged decode
def _serve_by_hand(net, prompt, n_new, slots=2, slot=1):
    """Chunked prefill then one-step decodes through the engine's paged
    programs; returns the logits' argmax path and the counts read."""
    eng = InferStep(net)
    pages = -(-(len(prompt) + n_new) // PAGE)
    state = eng.init_paged_state(slots, slots * pages, PAGE, 0)
    table = np.zeros((slots, pages), np.int32)
    table[slot] = 1 + slot * pages + np.arange(pages)
    counts = np.zeros((net.counts_size,), np.int64)
    at = 0
    while at < len(prompt):
        part = prompt[at:at + CHUNK]
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :len(part)] = part
        out, state = eng.prefill_suffix_paged(
            state, toks, [len(part)], [at], table[slot:slot + 1], [slot],
            [True], wide=True)
        out = out.asnumpy()
        counts += out[1:]
        at += len(part)
    served = [int(out[0])]
    active = np.arange(slots) == slot
    for j in range(n_new - 1):
        carry = np.where(active, served[-1], 0).astype(np.int32)
        lengths = np.where(active, len(prompt) + j, 0).astype(np.int32)
        buf, state = eng.decode_iter(state, table, carry, lengths, active,
                                     steps=1)
        buf = buf.asnumpy()
        counts += buf[:, 1:].ravel()[:net.counts_size]
        served.append(int(buf[slot, 0]))
    return served, counts


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "kernels"])
@pytest.mark.parametrize("length", [3, 8, 9, 21])   # the last chunk ragged
def test_chunked_prefill_and_decode_follow_the_reference(ref, net, length,
                                                         kernels,
                                                         monkeypatch,
                                                         paged_kernels):
    """``kernels``: the paged kernels forced on (interpreted here), so the
    whole decode step runs through ``dsa_decode_select`` and
    ``dsa_decode_window`` (every cached length here is past ``topk``)."""
    if kernels:
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
        paged_kernels(True)
    prompt, n_new = tokens(length, 10 + length), 6
    served, counts = _serve_by_hand(net, prompt, n_new)
    seq = np.concatenate([prompt, served[:-1]])
    want_at = len(prompt) - 1 + np.arange(n_new)
    tap = {}
    logits = np.asarray(ref.forward(SEED, TINY, seq, want=want_at, tap=tap))
    assert served == [int(t) for t in logits.argmax(-1)]
    gaps = ref.served_token_gaps(SEED, TINY, prompt, served)
    assert gaps.max() < 1e-5
    # the counts that rode the read-backs are the reference's own
    E, L = TINY["num_experts"], TINY["num_hidden_layers"]
    n = len(seq)
    for i in range(L):
        np.testing.assert_array_equal(counts[i * E:(i + 1) * E],
                                      tap[f"l{i}_counts"])
    selected = sum(int(np.concatenate(tap[f"l{i}_selected"])[:n].sum())
                   for i in range(L))
    assert counts[L * E + 2] == L * n * (n + 1) // 2      # keys seen
    assert counts[L * E + 3] == selected


def test_selected_sets_equal_the_references(ref, net):
    """Layer 0's selected sets, from the program's own projections."""
    toks = tokens(24, 5)
    tap = {}
    ref.forward(SEED, TINY, toks, tap=tap)
    want = np.concatenate(tap["l0_selected"])[:24, :24]
    pos = jnp.arange(24, dtype=jnp.int32)[None]
    pos3 = jnp.broadcast_to(pos[..., None], (1, 24, 3))
    x = jnp.take(net._w("embed"), jnp.asarray(toks)[None], axis=0)
    _, _, _, qi, ki, wi = net._project(0, x, pos3)
    scores = dsa.window_index_scores(qi, wi, ki, pos, 24 // 4, 4)
    got = np.asarray(dsa.select_mask(scores, pos, TINY["sa_config"]["topk"]))
    np.testing.assert_array_equal(got[0], want)
    assert (want.sum(-1) == np.minimum(np.arange(24) + 1, 8)).all()
    # decode: the last query's set, as positions
    picked, valid = dsa.decode_select(
        qi[:, -1], wi[:, -1], ki.reshape(6, 4, 8), jnp.arange(6)[None],
        jnp.asarray([23]), 8)
    assert sorted(np.asarray(picked)[0][np.asarray(valid)[0]]) == \
        sorted(np.nonzero(want[-1])[0])


def test_selection_breaks_ties_to_the_lower_position(ref):
    scores = np.full((1, 3, 12), -np.inf, np.float32)
    scores[0, 0, :4] = [1, 1, 1, 1]
    scores[0, 1, :10] = [5, 2, 2, 2, 2, 9, 2, 2, 2, 2]
    scores[0, 2, :12] = 0.0
    pos = np.asarray([[3, 9, 11]], np.int32)
    got = np.asarray(dsa.select_mask(jnp.asarray(scores), jnp.asarray(pos), 4))
    want = np.stack([np.asarray(ref.select(jnp.asarray(scores[0, q:q + 1]),
                                           jnp.asarray(pos[0, q:q + 1]), 4))[0]
                     for q in range(3)])
    np.testing.assert_array_equal(got[0], want)
    assert list(np.nonzero(got[0, 1])[0]) == [0, 1, 2, 5]
    assert list(np.nonzero(got[0, 2])[0]) == [0, 1, 2, 3]


# ------------------------------------------------------------ expert layer
def test_router_weights_and_counts(ref):
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    w = {"l0_router": jnp.asarray(rng.normal(size=(64, 8)) / 8, jnp.float32)}
    idx, a, _ = ref.route(w, "l0_", u, TINY, None)
    got_idx, got_a = moe.route(u, w["l0_router"], 2)
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(got_a), np.asarray(a), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_a).sum(-1), 1.0, atol=1e-6)
    dest, src, tile_e, n_tiles, counts = moe.group_by_expert(got_idx, 8, 16)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(np.asarray(idx).ravel(), minlength=8))
    dest, src, tile_e = map(np.asarray, (dest, src, tile_e))
    assert len(set(dest.ravel())) == dest.size          # no row twice
    for t in range(40):
        for j in range(2):
            assert src[dest[t, j]] == t
            assert tile_e[dest[t, j] // 16] == int(idx[t, j])
    assert int(n_tiles[0]) == sum(-(-c // 16) for c in np.asarray(counts))


@pytest.mark.parametrize("tokens_,tile", [(16, 16), (1024, 128)])
def test_grouped_swiglu_kernel_against_its_jnp_form(tokens_, tile,
                                                    monkeypatch):
    """The Pallas kernel (interpreted here) and the jnp form agree, and no
    token is dropped whatever the load: every pair's row is its expert's
    product."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    rng = np.random.default_rng(tokens_)
    E, H, F, k = 8, 128, 256, 2
    assert moe.row_tile(tokens_ * k, E) == tile
    u = jnp.asarray(rng.normal(size=(tokens_, H)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(E, H, F)) / 11, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(E, H, F)) / 11, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(E, F, H)) / 16, jnp.float32)
    # a skewed router: most pairs land on two experts
    experts = jnp.asarray(np.where(rng.random((tokens_, k)) < 0.7,
                                   [[0, 1]], rng.integers(2, E, (tokens_, k))),
                          jnp.int32)
    experts = experts.at[:, 1].set(jnp.where(experts[:, 1] == experts[:, 0],
                                             (experts[:, 0] + 1) % E,
                                             experts[:, 1]))
    dest, src, tile_e, n_tiles, _ = moe.group_by_expert(experts, E, tile)
    x = u[src]
    got = moe._moe_grouped_swiglu_impl(x, tile_e, n_tiles, wg, wu, wd,
                                       tile=tile)
    want = moe.grouped_swiglu_reference(x, tile_e, n_tiles, wg, wu, wd, tile)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    t, j = 3, 1
    e = int(experts[t, j])
    plain = (jax.nn.silu(u[t] @ wg[e]) * (u[t] @ wu[e])) @ wd[e]
    np.testing.assert_allclose(np.asarray(got[dest[t, j]]),
                               np.asarray(plain), atol=1e-4)


def test_kernel_name_the_benchmark_keys_on():
    """The trace names a Mosaic call after its ``pallas_call(name=...)``
    (``%moe_grouped_swiglu.<n>``, ``%dsa_selected_window.<n>``: read on
    the chip in PR 27); ``perf/layer_metrics/moe_*`` and ``dsa_*`` key on
    those names (``perf/harness/lm_counts.py``;
    ``dsa_select_time_share`` on ``%dsa_index_select.<n>``)."""
    import inspect

    from mxnet_tpu.ops.pallas import paged_flash_attention as pfa

    assert 'name="moe_grouped_swiglu"' in inspect.getsource(
        moe._moe_grouped_swiglu_impl.__wrapped__)
    assert 'name="dsa_selected_window"' in inspect.getsource(
        pfa._dsa_selected_window_impl.__wrapped__)
    from perf.harness import lm_counts
    import re
    assert re.search(lm_counts.MOE_KERNEL, "%moe_grouped_swiglu.6 = bf16[")
    assert re.search(lm_counts.DSA_KERNEL, "%dsa_selected_window.11 = bf")
    # the indexer's kernel (PR 37) and the reader that keeps its own pattern
    from mxnet_tpu.ops.pallas import index_select as ixs

    assert 'name="dsa_index_select"' in inspect.getsource(
        ixs._dsa_index_select_impl.__wrapped__)
    reader = load_module(os.path.join(
        REPO, "perf", "layer_metrics", "dsa_select_time_share.py"))
    assert re.search(reader.KERNEL, "%dsa_index_select.3 = s8[1,2048,16640]")
    assert re.search(reader.KERNEL, "%dsa_index_select = s8[")
    assert not re.search(reader.KERNEL, "%dsa_selected_window.11 = bf")
    # the decode step's two kernels (PR 39) have names of their own: a
    # reader of the chunk's kernels divides a CHUNK call's work by its mean
    # event, and must not meet a decode call under its name
    from mxnet_tpu.ops.pallas import dsa_decode as dec

    decode = load_module(os.path.join(
        REPO, "perf", "layer_metrics", "dsa_decode_time_share.py"))
    for impl, name, event in (
            (dec._dsa_decode_select_impl, "dsa_decode_select",
             "%dsa_decode_select.90 = (s8[16,136,128]{2,1,0:T(8,128)(4,1)"),
            (dec._dsa_decode_window_impl, "dsa_decode_window",
             "%dsa_decode_window.93 = bf16[16,32,128]{2,1,0:T(8,128)(2,1)")):
        assert f'name="{name}"' in inspect.getsource(impl.__wrapped__)
        assert re.search(decode.KERNEL, event)
        assert re.search(decode.KERNEL, f"%{name} = bf16[")
        for chunk in (lm_counts.DSA_KERNEL, reader.KERNEL,
                      lm_counts.MOE_KERNEL):
            assert not re.search(chunk, event)
    for event in ("%dsa_selected_window.11 = bf", "%dsa_index_select.3 = s8"):
        assert not re.search(decode.KERNEL, event)


# ------------------------------------------------- through the batcher
def test_batcher_serves_the_references_greedy_tokens(ref, net):
    """Five requests through two slots: slots retire and refill while
    another slot's prompt is still entering in chunks."""
    eng = InferStep(net)
    assert eng.slot_state["pools"] == ("k_pools", "v_pools", "ik_pools")
    assert eng.slot_state["encoder_memory"] is False
    assert eng.slot_state["slot_arrays"] == ()
    assert sum(n for _, n in eng.slot_state["counts"]) == net.counts_size
    bat = make_batcher(eng, [8, 32], slots=2, max_new_tokens=6,
                       page_size=PAGE, prefill_chunk=CHUNK, iter_tokens=2,
                       prefix_cache=False, warmup=True, name="keye")
    assert bat._store is None and "cross_k" not in bat._state
    assert bat.pages_per_slot == (32 + 6 + PAGE - 1) // PAGE
    lengths, news = [21, 3, 30, 9, 17], [6, 4, 5, 6, 3]
    prompts = [tokens(n, 40 + n) for n in lengths]
    try:
        futs = [bat.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, news)]
        got = [f.result(timeout=300) for f in futs]
    finally:
        bat.stop()
    for p, m, g in zip(prompts, news, got):
        assert [int(t) for t in g] == ref.greedy(SEED, TINY, p, m)
    assert bat.pool.free_pages == bat.pool.num_pages
    bat.pool.check_invariants(set())
    assert eng.compile_guard.steady_state_recompiles == 0
    st = bat.stats
    assert st["prompt_tokens"] == sum(lengths)
    assert st["prompt_chunks"] == sum(-(-n // CHUNK) for n in lengths)
    assert st["admitted"] == st["retired"] == 5
    assert st["prefill_chunk_s"] > 0
    k, L = TINY["num_experts_per_tok"], TINY["num_hidden_layers"]
    assert st["prefill_expert_tokens"].sum() == sum(lengths) * k * L
    assert st["prefill_keys_selected"] <= st["prefill_keys_seen"]
    assert st["decode_expert_layers"] == st["iterations"] * 2 * L
    assert 0 < st["decode_experts_touched"] <= \
        st["decode_expert_layers"] * TINY["num_experts"]


def test_what_the_serving_plane_refuses_for_this_net(ref, net):
    eng = InferStep(net)
    with pytest.raises(MXNetError, match="speculative decoding"):
        eng.attach_draft(net)
    with pytest.raises(MXNetError, match="hot weight swap"):
        eng.stage_params({})
    with pytest.raises(MXNetError, match="prefill_paged"):
        eng.prefill_paged(None, np.zeros((1, 8)), [8], [0], [0], [True])
    with pytest.raises(MXNetError, match="prefix cache"):
        make_batcher(eng, [8], slots=1, prefix_cache=True, start=False)
    with pytest.raises(MXNetError, match="forced prefix"):
        make_batcher(eng, [8], slots=1, max_prefix_tokens=4, start=False)
    bat = make_batcher(eng, [8], slots=1, page_size=PAGE, prefill_chunk=8,
                       start=False)
    with pytest.raises(MXNetError, match="handoff frames"):
        bat.submit([3, 4], frames={"length": 1})


# what one case of the kernel's test varies; the rest is the first two
# cases' (2 rows, 256 queries, 24 pages of 16, 2 key/value heads of 2
# query heads, float32 pools, 40 % of the seen keys selected)
_WINDOW = dict(B=2, C=256, Hkv=2, G=2, D=32, ps=16, P=24, dtype="float32",
               density=0.4, atol=2e-5)
WINDOW_CASES = {
    # one block of 24 pages: everything the kernel had to get right before
    # a block held more than a page
    "offset0": dict(offset=0),
    "offset70": dict(offset=70),
    # 130 pages of 128 are sixteen blocks of 8 and one of 2, and the chunk
    # sits in the last: it clamps its page indices past the table's end
    # and is handed a mask block that ends past the row's length
    "pages130-not-a-multiple": dict(offset=16_384, B=1, ps=128, P=130),
    # the causal limit falls inside a page (128) and inside a block (1,024)
    # and crosses the first block's end inside the chunk (1,000 + 23)
    "limit-inside-a-block": dict(offset=1_000, ps=128, P=20),
    # no query of either row sees past the first of three blocks
    "trailing-blocks-unseen": dict(offset=0, ps=128, P=24),
    # the published grouping: 4 key/value heads of 8 query heads
    "hkv4-g8": dict(offset=1_000, B=1, C=128, Hkv=4, G=8, ps=128, P=11),
    # the serving dtype: bfloat16 queries, pools and second product against
    # the float32 reference of the same values (one bfloat16 ulp of an
    # output near 1 is 0.0078)
    "bfloat16-pools": dict(offset=1_000, ps=128, P=20, dtype="bfloat16",
                           atol=2e-2),
    # every query reads its own position and nothing else: whole blocks of
    # -inf scores before the one finite score of a row
    "single-selected-key": dict(offset=1_500, B=1, ps=128, P=20,
                                density=0.0),
}


@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_selected_window_kernel_against_its_reference(case, monkeypatch):
    """The Pallas window over a selected set (interpreted here), grouped
    query heads over fewer key/value heads, pools read through a shuffled
    page table, against the dense jnp form and the jnp loop."""
    from mxnet_tpu.ops.pallas import paged_flash_attention as pfa

    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    w = dict(_WINDOW, **WINDOW_CASES[case])
    offset, B, C, Hkv, G, D, ps, P = (w[k] for k in (
        "offset", "B", "C", "Hkv", "G", "D", "ps", "P"))
    L, dtype = P * ps, jnp.dtype(w["dtype"])
    tq, pages = pfa._selected_window_tiles(C, P, ps)
    assert pages == min(P, 1024 // ps) and C % tq == 0
    rng = np.random.default_rng(offset)
    q = jnp.asarray(rng.normal(size=(B, C, Hkv * G, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(1 + B * P, ps, Hkv, D)), dtype)
    vp = jnp.asarray(rng.normal(size=(1 + B * P, ps, Hkv, D)), dtype)
    table = jnp.asarray(1 + rng.permutation(B * P).reshape(B, P), jnp.int32)
    off = jnp.asarray([offset, offset // 2][:B], jnp.int32)
    q_pos = off[:, None] + jnp.arange(C)[None]
    seen = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]
    mask = seen & jnp.asarray(rng.random((B, C, L)) < w["density"])
    mask = mask | (jnp.arange(L)[None, None, :] == q_pos[:, :, None])
    got = pfa.paged_selected_window_attention(q, kp, vp, table, off, mask,
                                              sm_scale=D ** -0.5)
    f32 = [x.astype(jnp.float32) for x in (q, kp, vp)]
    want = pfa.paged_selected_window_reference(*f32, table, off, mask,
                                               sm_scale=D ** -0.5)
    assert got.dtype == dtype and np.isfinite(np.asarray(want)).all()
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want), atol=w["atol"])
    block = paged.kv_block(L)
    loop = dsa.selected_window_attention(*f32, table, off, mask,
                                         L // block, block, D ** -0.5)
    np.testing.assert_allclose(np.asarray(loop), np.asarray(want), atol=2e-5)


# what one case of the index-select kernel's test varies; the rest is the
# first case's (1 row, 256 queries of 4 heads of 16 over 1,024 cached
# positions in key blocks of 256, topk 128, float32). "exact" draws small
# dyadic numbers, so every product and sum is exact in float32 whatever
# its order, many scores are equal and the rule for ties decides the set
_SELECT = dict(R=1, C=256, J=4, Di=16, L=1024, topk=128, dtype="float32",
               draw="exact", walked=None, poison=False)
SELECT_CASES = {
    # nothing is scored or counted: every query stands below topk
    "offset0-chunk-within-topk": dict(offsets=[0], topk=256),
    # topk falls inside the second query block: its first queries select
    # every seen position, the rest the topk best
    "topk-inside-a-query-block": dict(offsets=[0], topk=200),
    # two rows at their own offsets, one block of each past all the keys
    # the other sees
    "two-rows": dict(R=2, offsets=[100, 700], topk=300),
    # a prompt's last chunk: 90 live queries, the caller walked two blocks
    # of 128 positions, the padding queries see -inf past them; the cached
    # length is five key blocks of 256 and three of them are dead
    "partial-last-chunk": dict(offsets=[150], L=1280, walked=2),
    # whatever lies past the last seen position must not count: NaN and
    # Inf keys there (pages of another life)
    "dead-blocks-of-nan-and-inf": dict(offsets=[300], L=1536, poison=True),
    # a zero query (every score equal: the cut is all ties, the lower
    # positions win) and odd queries whose heads all weigh nothing
    "a-row-of-equal-scores": dict(offsets=[200], draw="zero"),
    # columns of -inf that every query sees: the caller walked three
    # blocks of 128 and the queries stand past them
    "all-inf-columns": dict(offsets=[600], J=16, draw="normal", walked=3),
    # the serving dtype; a cached length that takes key blocks of 128
    "bfloat16-operands": dict(offsets=[300], dtype="bfloat16", L=1152,
                              J=16, draw="normal"),
    "float32-normal": dict(offsets=[700], J=16, draw="normal"),
}
# the cases in which no query has more ties at the cut than room for them
# (the others take the kernel's second search, over positions)
_NEVER_CROWDED = ("offset0-chunk-within-topk", "all-inf-columns",
                  "bfloat16-operands", "float32-normal")


@pytest.mark.parametrize("case", list(SELECT_CASES))
def test_index_select_kernel_against_its_jnp_form(case, monkeypatch,
                                                  paged_kernels):
    """The Pallas indexer (interpreted here) against ``select_mask`` of
    ``window_index_scores``: the same sets, bit for bit, through
    ``window_select`` with the paged kernels forced on."""
    from mxnet_tpu.ops.pallas import index_select as ixs

    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    paged_kernels(True)
    w = dict(_SELECT, **SELECT_CASES[case])
    R, C, J, Di, L, topk = (w[k] for k in ("R", "C", "J", "Di", "L", "topk"))
    dtype, block = jnp.dtype(w["dtype"]), 128
    assert ixs.index_select_tiles(C, L) == (128, 256 if L % 256 == 0 else 128)
    rng = np.random.default_rng(len(case))
    if w["draw"] == "normal":
        qi, ki, wi = (rng.normal(size=s) for s in (
            (R, C, J, Di), (R, L, Di), (R, C, J)))
    else:
        qi = rng.integers(-4, 5, size=(R, C, J, Di)) / 2.0
        ki = rng.integers(-4, 5, size=(R, L, Di)) / 2.0
        wi = rng.integers(-4, 5, size=(R, C, J)) / 4.0
        if w["draw"] == "zero":
            qi[:, 7] = 0.0
            wi[:, 1::2] = 0.0
    off = jnp.asarray(w["offsets"], jnp.int32)
    q_pos = off[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    last = int(q_pos.max())
    n_blocks = w["walked"] or min(last // block + 1, L // block)
    if w["poison"]:
        ki[:, last + 1:] = np.tile([np.nan, np.inf, -np.inf, 1e30],
                                   Di // 4)
    qi, ki = jnp.asarray(qi, dtype), jnp.asarray(ki, dtype)
    wi = jnp.asarray(wi, jnp.float32)
    want = dsa.select_mask(
        dsa.window_index_scores(qi, wi, ki, q_pos, n_blocks, block),
        q_pos, topk)
    got = dsa.window_select(qi, wi, ki, q_pos, n_blocks, block, topk)
    assert got.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the kernel ran (the jnp form makes no such call), and a live query
    # selects what the rule says: all it sees, or topk of it
    assert "dsa_index_select" in str(jax.make_jaxpr(
        lambda: dsa.window_select(qi, wi, ki, q_pos, n_blocks, block,
                                  topk))())
    live = np.asarray(q_pos) < n_blocks * block
    picked = np.asarray(want).sum(-1)
    assert (picked[live] == np.minimum(np.asarray(q_pos)[live] + 1,
                                       topk)).all()
    # which cases make the kernel settle ties by position (a query with
    # more ties at its cut than room for them), by the jnp form's own test
    keys = dsa._ordered_bits(
        dsa.window_index_scores(qi, wi, ki, q_pos, n_blocks, block))
    kth = dsa.kth_largest_bits(keys, topk)
    seen = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]
    crowded = bool(jnp.any(
        jnp.sum((keys == kth) & seen, -1) > topk - jnp.sum(keys > kth, -1)))
    assert crowded == (case not in _NEVER_CROWDED)


# what one case of the decode selection's test varies; the rest is the
# first case's (2 rows of 4 heads of 16 over 10 pages of 128, topk 128,
# float32, "exact" as above: dyadic numbers, so sums are exact, scores tie
# and the rule for ties decides the set). A position below 0 is an
# inactive row
_DECODE = dict(J=4, Di=16, ps=128, P=10, topk=128, dtype="float32",
               draw="exact", poison=False)
DECODE_SELECT_CASES = {
    # nothing is scored: every row stands below topk
    "under-topk": dict(pos=[100, 50]),
    # pos + 1 == topk selects all it sees; one more selects topk of them
    "at-and-over-topk": dict(pos=[127, 128, 200]),
    # the second block of pages is partial (10 pages in blocks of 8), one
    # row fills its table, the other walks one block
    "rows-at-different-positions": dict(pos=[1279, 300]),
    "an-inactive-row-between": dict(pos=[700, -1, 400]),
    "every-row-inactive": dict(pos=[-1, -1]),
    # the last live page holds 105 positions; what follows in it is stale
    "a-ragged-last-page": dict(pos=[1000, 232]),
    # a zero query and a row whose heads all weigh nothing: every score is
    # equal, the cut is all ties and the lower positions win
    "every-score-ties": dict(pos=[700, 1000], draw="zero"),
    # whatever lies past a row's position must not count: NaN and Inf keys
    # there, in the live page and in the dead ones
    "dead-pages-of-nan-and-inf": dict(pos=[700, 300], poison=True),
    "float32-normal": dict(pos=[900, 1100], J=16, Di=64, draw="normal"),
    # the serving dtype
    "bfloat16-operands": dict(pos=[900, 1100], J=16, Di=64, draw="normal",
                              dtype="bfloat16"),
    # 20 pages: three blocks, the last of four pages
    "three-blocks": dict(pos=[2500, 1023], P=20, topk=512),
    # the tiny preset's pages: a block is the whole table
    "pages-of-4": dict(pos=[26, 12, 8], J=2, Di=8, ps=4, P=7, topk=8),
}
# the cases in which no row has more ties at the cut than room for them
# (the others take the kernel's second search, over positions)
_DECODE_NEVER_CROWDED = ("under-topk", "every-row-inactive",
                         "float32-normal", "bfloat16-operands")


def _decode_select_inputs(case):
    w = dict(_DECODE, **DECODE_SELECT_CASES[case])
    J, Di, ps, P = (w[k] for k in ("J", "Di", "ps", "P"))
    pos = np.asarray(w["pos"], np.int32)
    B, dtype = len(pos), jnp.dtype(w["dtype"])
    rng = np.random.default_rng(len(case))
    if w["draw"] == "normal":
        qi, pool, wi, own = (rng.normal(size=s) for s in (
            (B, J, Di), (1 + B * P, ps, Di), (B, J), (B, Di)))
    else:
        # a few dozen distinct scores over hundreds of positions: every
        # cut falls among equal scores
        qi = rng.integers(-1, 2, size=(B, J, Di)) / 1.0
        pool = rng.integers(-1, 2, size=(1 + B * P, ps, Di)) / 1.0
        wi = rng.integers(-1, 3, size=(B, J)) / 2.0
        own = rng.integers(-1, 2, size=(B, Di)) / 1.0
        if w["draw"] == "zero":
            qi[0], wi[1] = 0.0, 0.0
    table = 1 + rng.permutation(B * P).reshape(B, P)
    if w["poison"]:
        dead = np.arange(P * ps)[None] > pos[:, None]
        for b in range(B):
            flat = pool[table[b]].reshape(P * ps, Di)
            flat[dead[b]] = np.tile([np.nan, np.inf, -np.inf, 1e30], Di // 4)
            pool[table[b]] = flat.reshape(P, ps, Di)
    at = np.maximum(pos, 0)
    rows = np.where(pos >= 0, table[np.arange(B), at // ps] * ps + at % ps,
                    at % ps)                    # an inactive row: the trash
    return (w, jnp.asarray(qi, dtype), jnp.asarray(wi, jnp.float32),
            jnp.asarray(own, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(table, jnp.int32), jnp.asarray(rows, jnp.int32), pos)


@pytest.mark.parametrize("case", list(DECODE_SELECT_CASES))
def test_decode_select_kernel_against_its_jnp_form(case, monkeypatch):
    """The decode step's selection kernel (interpreted here) against
    ``write_rows`` + ``decode_select``: the same SET row for row, ties to
    the lower position, the row's own key in its page and nothing else of
    the pool touched."""
    from mxnet_tpu.ops.pallas import dsa_decode as dec
    from mxnet_tpu.ops.pallas import page_walk as walk

    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    w, qi, wi, own, pool, table, rows, pos = _decode_select_inputs(case)
    B, (ps, P, topk) = len(pos), (w[k] for k in ("ps", "P", "topk"))
    L, live = P * ps, pos >= 0
    written = paged.write_rows(pool, rows, own)
    picked, valid = dsa.decode_select(qi, wi, written, table,
                                      jnp.asarray(np.maximum(pos, 0)), topk)
    want = np.zeros((B, L), bool)
    for b in np.nonzero(live)[0]:
        want[b, np.asarray(picked)[b][np.asarray(valid)[b]]] = True
    code, got_pool = dec.dsa_decode_select(qi, wi, own, pool, table,
                                           jnp.asarray(pos), topk)
    assert code.dtype == jnp.int8 and code.shape[1] % walk.decode_tiles(
        P, ps) == 0 and not np.asarray(code)[:, P:].any()
    got = np.asarray(code)[:, :P].reshape(B, L) != 0
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.where(live, np.minimum(pos + 1, topk), 0)).all()
    # the pool: the live rows' keys written, an inactive row's not even to
    # the trash page (page 0 is the reference's to scribble on)
    np.testing.assert_array_equal(
        np.asarray(got_pool.astype(jnp.float32))[1:],
        np.asarray(written.astype(jnp.float32))[1:])
    # which cases make the kernel settle ties by position, by the jnp
    # form's own arithmetic
    ki = paged.gather_row_pages(written, table).astype(jnp.float32)
    hit = jax.nn.relu(jnp.einsum("bjd,bsd->bjs", qi.astype(jnp.float32), ki))
    scores = jnp.einsum("bjs,bj->bs", hit, wi)
    seen = jnp.arange(L)[None] <= pos[:, None]
    keys = dsa._ordered_bits(jnp.where(seen, scores, -jnp.inf))
    kth = dsa.kth_largest_bits(keys, topk)
    crowded = bool(jnp.any(
        (pos >= topk) & (jnp.sum((keys == kth) & seen, -1)
                         > topk - jnp.sum(keys > kth, -1))))
    assert crowded == (case not in _DECODE_NEVER_CROWDED)


# what one case of the decode attention's test varies; the rest is the
# first case's (2 rows, 2 key/value heads of 2 query heads of 32, 24 pages
# of 16 in one block, float32, 40 % of the seen keys selected)
_DECODE_WINDOW = dict(Hkv=2, G=2, D=32, ps=16, P=24, dtype="float32",
                      density=0.4, atol=2e-5)
DECODE_WINDOW_CASES = {
    "two-rows": dict(pos=[300, 100]),
    # the published grouping and page: 4 key/value heads of 8 query heads,
    # 11 pages of 128 in blocks of 8, the limit inside a page
    "hkv4-g8": dict(pos=[1300, 500], Hkv=4, G=8, D=128, ps=128, P=11),
    "an-inactive-row-between": dict(pos=[700, -1, 90], ps=128, P=10),
    # the serving dtype against the float32 reference of the same values
    "bfloat16-pools": dict(pos=[2500, 1023], Hkv=4, G=8, D=128, ps=128,
                           P=20, dtype="bfloat16", atol=2e-2),
    # a row reads its own position and nothing else: whole pages and
    # blocks of -inf scores before the one finite score
    "single-selected-key": dict(pos=[1500, 1023], ps=128, P=20,
                                density=0.0),
    "pages-of-4": dict(pos=[26, 12, 0], D=16, ps=4, P=7),
}


@pytest.mark.parametrize("case", list(DECODE_WINDOW_CASES))
def test_decode_window_kernel_against_its_reference(case, monkeypatch):
    """The decode step's attention kernel (interpreted here) under a mask,
    pools read through a shuffled page table, against
    ``selected_decode_attention`` over the same set as positions."""
    from mxnet_tpu.ops.pallas import dsa_decode as dec
    from mxnet_tpu.ops.pallas import page_walk as walk

    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    w = dict(_DECODE_WINDOW, **DECODE_WINDOW_CASES[case])
    Hkv, G, D, ps, P = (w[k] for k in ("Hkv", "G", "D", "ps", "P"))
    pos = np.asarray(w["pos"], np.int32)
    B, L, dtype = len(pos), P * ps, jnp.dtype(w["dtype"])
    rng = np.random.default_rng(len(case))
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(1 + B * P, ps, Hkv, D)), dtype)
    vp = jnp.asarray(rng.normal(size=(1 + B * P, ps, Hkv, D)), dtype)
    table = jnp.asarray(1 + rng.permutation(B * P).reshape(B, P), jnp.int32)
    at = np.arange(L)[None]
    mask = (at <= pos[:, None]) & (rng.random((B, L)) < w["density"])
    mask |= at == pos[:, None]
    pages = -(-P // walk.decode_tiles(P, ps)) * walk.decode_tiles(P, ps)
    code = np.zeros((B, pages, ps), np.int8)
    code[:, :P] = mask.reshape(B, P, ps)
    got = dec.dsa_decode_window(q, kp, vp, table, jnp.asarray(pos),
                                jnp.asarray(code), sm_scale=D ** -0.5)
    assert got.dtype == dtype and got.shape == (B, Hkv * G * D)
    K = int(mask.sum(-1).max())
    picked, valid = np.zeros((B, K), np.int32), np.zeros((B, K), bool)
    for b in range(B):
        idx = np.nonzero(mask[b])[0]
        picked[b, :len(idx)], valid[b, :len(idx)] = idx, True
    want = dsa.selected_decode_attention(
        *(x.astype(jnp.float32) for x in (q, kp, vp)), table,
        jnp.asarray(picked), jnp.asarray(valid), D ** -0.5)
    got, want, live = np.asarray(got.astype(jnp.float32)), \
        np.asarray(want), pos >= 0
    assert np.isfinite(want[live]).all()
    np.testing.assert_allclose(got[live], want[live], atol=w["atol"])
    assert not got[~live].any()         # an inactive row reads nothing


def test_decode_counts_and_pools_whichever_form_runs(monkeypatch,
                                                     paged_kernels):
    """``selected_decode`` with the paged kernels forced on and off: the
    same attention, the same indexer pool and the same INTEGER of keys the
    active rows selected (``decode_keys_selected``), an inactive row
    counting nothing in either."""
    w, qi, wi, own, pool, table, rows, pos = _decode_select_inputs(
        "an-inactive-row-between")
    B, ps, P, topk = len(pos), w["ps"], w["P"], w["topk"]
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, 4, 32)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(1 + B * P, ps, 2, 32)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(1 + B * P, ps, 2, 32)), jnp.float32)
    active = jnp.asarray(pos >= 0)
    at = jnp.asarray(np.maximum(pos, 0))

    def step(kernels):
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
        paged_kernels(kernels)
        text = str(jax.make_jaxpr(lambda: dsa.selected_decode(
            q, qi, wi, own, kp, vp, pool, table, rows, at, active, topk,
            32 ** -0.5))())
        assert ("dsa_decode_select" in text and "dsa_decode_window" in text) \
            == kernels
        return dsa.selected_decode(q, qi, wi, own, kp, vp, pool, table,
                                   rows, at, active, topk, 32 ** -0.5)

    attn, ip, n = step(True)
    attn0, ip0, n0 = step(False)
    assert int(n) == int(n0) == int(np.minimum(pos + 1, topk)[pos >= 0].sum())
    live = pos >= 0
    np.testing.assert_allclose(np.asarray(attn)[live],
                               np.asarray(attn0)[live], atol=2e-5)
    np.testing.assert_array_equal(np.asarray(ip)[1:], np.asarray(ip0)[1:])


def test_bfloat16_weights_and_caches_serve(ref):
    """The serving dtype end to end on the CPU (the chip's run decides
    `correct`; here: dtypes flow, pools are bfloat16, tokens stay close).
    At these widths one bfloat16 flip of a key or an expert moves a logit
    by half, so only a loose mean gap is asked for."""
    net = build(ref, dtype="bfloat16")
    eng = InferStep(net, amp="bfloat16")
    bat = make_batcher(eng, [32], slots=2, max_new_tokens=5, page_size=PAGE,
                       prefill_chunk=CHUNK, iter_tokens=2,
                       prefix_cache=False, name="keye-bf16")
    assert bat._state["ik_pools"][0].dtype == jnp.bfloat16
    prompts = [tokens(n, 70 + n) for n in (19, 6)]
    try:
        got = [bat.submit(p, max_new_tokens=5).result(timeout=300)
               for p in prompts]
    finally:
        bat.stop()
    gaps = np.concatenate([ref.served_token_gaps(SEED, TINY, p, g)
                           for p, g in zip(prompts, got)])
    assert len(gaps) == 10 and np.isfinite(gaps).all()
    assert gaps.mean() < 0.3
    assert bat.pool.free_pages == bat.pool.num_pages


# --------------------------- the seam between routing and dispatch (PR 40)
def _moe_experts_before_the_split(u, router, w_gate, w_up, w_down, k, valid=None,
                scoring="softmax", bias=None, scale=1.0, held=None):
    """The expert layer on tokens ``u (T, H)``: ``sum_{e in top-k} a_e
    w_down[e] (silu(u w_gate[e]) * (u w_up[e]))`` with ``a`` the router's
    weights (``route``: softmax probabilities renormalised, or sigmoid
    scores chosen with ``bias`` and scaled by ``scale``). ``valid (T,)``
    marks padding tokens, which are computed and not counted.

    ``held = (first, n)`` says WHICH experts the weights hold: ``w_gate``,
    ``w_up``, ``w_down`` are experts ``first .. first + n - 1`` of the
    router's ``E`` outputs (one chip's share of an expert-parallel layer).
    The router still ranks all ``E``; a pair that falls on an expert held
    elsewhere is neither computed nor added, and the result is this
    share's part of the layer's sum.

    Returns ``(out (T, H), counts)``: tokens routed to each expert, ``(E,)``
    int32, over the router's whole width."""
    T, H = u.shape
    E = router.shape[1]
    experts, weights = moe.route(u, router, k, scoring, bias, scale)
    local, n = experts, E
    if held is not None:
        first, n = held
        inside = jnp.logical_and(experts >= first, experts < first + n)
        weights = jnp.where(inside, weights, 0.0)
        local = jnp.where(inside, experts - first, n)   # a sink, sorted last
    tile = moe.row_tile(T * k, n)
    dest, src_token, tile_expert, n_tiles, grouped = moe.group_by_expert(
        local, n + (held is not None), tile)
    if held is not None:
        # the sink's tiles lie last: they are left out of the product
        n_tiles = n_tiles - (grouped[n] + tile - 1) // tile
        tile_expert = jnp.minimum(tile_expert, jnp.minimum(
            tile_expert[jnp.maximum(n_tiles[0] - 1, 0)], n - 1))
    y = moe.grouped_swiglu(u[src_token], tile_expert, n_tiles, w_gate, w_up,
                       w_down, tile)
    picked = y[dest.reshape(T * k)].reshape(T, k, H).astype(jnp.float32)
    out = jnp.einsum("tkh,tk->th", picked, weights).astype(u.dtype)
    if valid is None and held is None:
        return out, grouped
    counts = jnp.zeros((E,), jnp.int32).at[experts.reshape(T * k)].add(
        1 if valid is None else jnp.repeat(valid.astype(jnp.int32), k))
    return out, counts


SEAM_CALLS = {
    # (tokens, router outputs, held experts or None, k, keywords)
    "keye-chunk": (2048, 128, None, 8, {}),
    "keye-decode": (16, 128, None, 8, {}),
    "joyai-chunk": (2048, 256, (0, 16), 8,
                    dict(scoring="sigmoid", scale=2.5)),
    "joyai-decode": (80, 256, (0, 16), 8,
                     dict(scoring="sigmoid", scale=2.5)),
}


@pytest.mark.parametrize("valid", [False, True])
@pytest.mark.parametrize("call", sorted(SEAM_CALLS))
def test_moe_experts_is_route_then_dispatch_to_the_letter(call, valid):
    """``moe_experts`` since the split (``route`` + ``dispatch_experts``)
    traces to the jaxpr it traced to before, for Keye's and JoyAI's calls
    at the published widths (chunk and decode shapes, all experts held and
    a share of them, padding marked and not): their programs do not
    move."""
    import hashlib

    tokens_, E, held, k, kw = SEAM_CALLS[call]
    n, H, F = (held[1] if held else E), 2048, 768

    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype)

    args = (shape(tokens_, H), shape(H, E), shape(n, H, F), shape(n, H, F),
            shape(n, F, H), shape(E), shape(tokens_, dtype=jnp.bool_))

    def digest(fn):
        def layer(u, router, wg, wu, wd, bias, ok):
            more = dict(kw, held=held) if held else dict(kw)
            if "scoring" in kw:
                more["bias"] = bias
            return fn(u, router, wg, wu, wd, k, ok if valid else None,
                      **more)
        text = str(jax.make_jaxpr(layer)(*args))
        return hashlib.sha256(text.encode()).hexdigest(), len(text)

    now, before = digest(moe.moe_experts), \
        digest(_moe_experts_before_the_split)
    assert now == before and now[1] > 2000


@pytest.mark.parametrize("tokens_,tile", [(64, 16), (1024, 128)])
def test_dispatch_of_one_full_width_expert_a_token(tokens_, tile,
                                                   monkeypatch):
    """ZAYA's expert layer: 16 experts of width 2048 (four column steps of
    512), ONE a token, weighed by the weight it is given and not by 1.0;
    some experts get no token and are never read. The kernel (interpreted
    here) against ``grouped_swiglu_reference`` and against each token's
    own expert, plainly."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    rng = np.random.default_rng(tokens_)
    E, H, F = 16, 128, 2048
    assert moe.row_tile(tokens_, E) == tile and moe.f_block(F) == 512
    u = jnp.asarray(rng.normal(size=(tokens_, H)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(E, H, F)) / 11, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(E, H, F)) / 11, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(E, F, H)) / 45, jnp.float32)
    # experts 3, 7 and 15 get no token; expert 0 gets a third of them
    live = np.asarray([e for e in range(E) if e not in (3, 7, 15)])
    experts = np.where(rng.random(tokens_) < 0.33, 0,
                       live[rng.integers(0, len(live), tokens_)])
    experts = jnp.asarray(experts[:, None], jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 0.9, (tokens_, 1)), jnp.float32)
    valid = jnp.asarray(rng.random(tokens_) < 0.8)
    got, grouped = moe.dispatch_experts(u, experts, weights, wg, wu, wd)
    _, counted = moe.dispatch_experts(u, experts, weights, wg, wu, wd, valid)
    monkeypatch.setattr(moe, "grouped_swiglu", moe.grouped_swiglu_reference)
    want, _ = moe.dispatch_experts(u, experts, weights, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    by_expert = np.bincount(np.asarray(experts[:, 0]), minlength=E)
    np.testing.assert_array_equal(np.asarray(grouped), by_expert)
    assert not by_expert[[3, 7, 15]].any()
    np.testing.assert_array_equal(
        np.asarray(counted),
        np.bincount(np.asarray(experts[:, 0]), np.asarray(valid),
                    minlength=E).astype(np.int32))
    for t in (0, 5, tokens_ - 1):
        e = int(experts[t, 0])
        plain = float(weights[t, 0]) * (
            (jax.nn.silu(u[t] @ wg[e]) * (u[t] @ wu[e])) @ wd[e])
        np.testing.assert_allclose(np.asarray(got[t]), np.asarray(plain),
                                   atol=1e-4)

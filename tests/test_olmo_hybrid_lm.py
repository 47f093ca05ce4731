"""The gated delta-rule hybrid language model (``model_zoo/olmo_hybrid.py``)
against its plain reference (``perf/reference/olmo-hybrid-7b.py``) at a tiny
preset, on seeded random weights: full forward, chunks cut at offsets that
are no multiple of the rule's block and paged decode at every served
position, logits AND the slots' final state; what a slot's arrays do between
chunks and across a burst; preemption by recompute; and the whole path
through ``ContinuousBatcher``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 - the package sets JAX up
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.olmo_hybrid import OlmoHybridLM
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import make_batcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perf.harness.loader import load_module  # noqa: E402

LIN, FULL = "linear_attention", "full_attention"
TINY = {
    "vocab_size": 128, "hidden_size": 32, "num_hidden_layers": 5,
    "layer_types": [LIN, LIN, FULL, LIN, LIN],
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 48, "linear_num_key_heads": 4,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "delta_block": 4, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "precision": {"weights": "float32", "state": "float32"}}
PAGE, CHUNK, SEED = 4, 8, 13
N_DELTA = TINY["layer_types"].count(LIN)


@pytest.fixture(scope="module")
def ref():
    return load_module(os.path.join(REPO, "perf", "reference",
                                    "olmo-hybrid-7b.py"))


@pytest.fixture(scope="module")
def driver():
    return load_module(os.path.join(REPO, "perf", "drivers",
                                    "serve-delta-lm.py"))


@pytest.fixture(autouse=True)
def highest_precision():
    """The program's products in float32 proper, on every thread (the
    scheduler's too), as the reference has them."""
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)


def build(ref, driver, cfg=TINY, seed=SEED, **precision):
    cfg = dict(cfg, precision=dict(cfg["precision"], **precision))
    net = OlmoHybridLM(**driver._model_kwargs(cfg))
    params = net._collect_params_with_prefix()
    assert set(params) == set(ref.tensor_specs(cfg))
    for name, p in params.items():
        p.set_data(nd.NDArray(ref.tensor(seed, cfg, name).astype(
            cfg["precision"]["weights"])))
    return net


@pytest.fixture(scope="module")
def net(ref, driver):
    return build(ref, driver)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, TINY["vocab_size"], n) \
        .astype(np.int32)


# ------------------------------------------------------------ full forward
@pytest.mark.parametrize("length", [3, 8, 23])   # under, over, off a block
def test_full_forward_logits(ref, net, length):
    toks = tokens(length, length)
    got = net(nd.array(toks[None], dtype="int32")).asnumpy()[0]
    want = np.asarray(ref.forward(SEED, TINY, toks))
    np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize("key,value", [
    ("linear_allow_neg_eigval", False), ("rms_norm_eps", 1e-1),
    ("linear_conv_kernel_dim", 3)])
def test_every_part_of_the_equations_weighs_in(ref, key, value):
    """The reference with one term changed differs by far more than the
    tolerance of these tests: the doubled write strength, the norms and the
    convolutions' reach are all seen by the comparison above."""
    toks = tokens(24, 1)
    want = np.asarray(ref.forward(SEED, TINY, toks))
    other = np.asarray(ref.forward(SEED, dict(TINY, **{key: value}), toks))
    assert np.abs(other - want).max() > 1e-2, key


# ------------------------------------------- chunked prefill, paged decode
def _table(slots, pages, slot):
    table = np.zeros((slots, pages), np.int32)
    table[slot] = 1 + slot * pages + np.arange(pages)
    return table


def _enter(eng, state, prompt, table, slot, fills=None):
    """The prompt through the chunk program, ``fills`` real tokens a chunk
    (full chunks when None). Returns the last chunk's read-back, the state
    and the counts that rode the read-backs."""
    counts = np.zeros((6,), np.int64)
    at, k, out = 0, 0, None
    while at < len(prompt):
        n = min(CHUNK if fills is None else fills[k % len(fills)],
                len(prompt) - at)
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :n] = prompt[at:at + n]
        toks[0, n:] = 77                  # padding is a real token id
        out, state = eng.prefill_suffix_paged(
            state, toks, [n], [at], table[slot:slot + 1], [slot], [True],
            wide=True)
        out = out.asnumpy()
        counts += out[1:]
        at, k = at + n, k + 1
    return out, state, counts


def _serve_by_hand(net, prompt, n_new, slots=2, slot=1, fills=None):
    eng = InferStep(net)
    pages = -(-(len(prompt) + n_new) // PAGE)
    state = eng.init_paged_state(slots, slots * pages, PAGE, 0)
    table = _table(slots, pages, slot)
    out, state, counts = _enter(eng, state, prompt, table, slot, fills)
    served = [int(out[0])]
    active = np.arange(slots) == slot
    for j in range(n_new - 1):
        carry = np.where(active, served[-1], 0).astype(np.int32)
        lengths = np.where(active, len(prompt) + j, 0).astype(np.int32)
        buf, state = eng.decode_iter(state, table, carry, lengths, active,
                                     steps=1)
        buf = buf.asnumpy()
        counts += buf[:, 1:].ravel()[:6]
        served.append(int(buf[slot, 0]))
    return served, counts, state


@pytest.mark.parametrize("length,fills", [
    (3, None), (8, None), (9, None), (21, None),
    # chunks cut at offsets that are no multiple of the block of 4
    (21, (5, 8, 1)), (30, (7, 3)), (17, (1,))])
def test_chunks_then_decode_follow_the_reference_at_every_position(
        ref, net, length, fills):
    """Logits at every served position AND the slot's final state in every
    delta-rule layer, head by head."""
    prompt, n_new = tokens(length, 10 + length), 6
    served, counts, state = _serve_by_hand(net, prompt, n_new, fills=fills)
    seq = np.concatenate([prompt, served[:-1]])
    want_at = len(prompt) - 1 + np.arange(n_new)
    logits = np.asarray(ref.forward(SEED, TINY, seq, want=want_at))
    assert served == [int(t) for t in logits.argmax(-1)]
    assert ref.served_token_gaps(SEED, TINY, prompt, served).max() < 1e-5
    want = ref.final_states(SEED, TINY, seq, [len(seq)])
    assert want.shape == (N_DELTA, 1, 4, 8, 16)
    for layer in range(N_DELTA):
        got = np.asarray(state["delta"][layer])
        np.testing.assert_allclose(got[1], want[layer, 0], atol=5e-5,
                                   rtol=1e-4)
        assert np.abs(want[layer, 0]).max() > 1e-3
        assert not got[0].any()              # the other slot: untouched
    # the counts that rode the read-backs
    chunks = counts[5] - (n_new - 1)
    assert counts[0] == length and counts[0] + counts[1] == chunks * CHUNK
    assert counts[2] == 1                       # one chunk began from zero
    assert counts[3] == n_new - 1               # live rows x steps
    n = len(seq)
    assert counts[4] == n * (n + 1) // 2        # positions attention read


def test_full_chunks_and_ragged_chunks_leave_the_same_arrays(net):
    """The state and the tail after a prompt do not depend on how it was
    cut."""
    eng = InferStep(net)
    prompt = tokens(19, 4)
    states = []
    for fills in (None, (3, 8, 2)):
        state = eng.init_paged_state(2, 2 * 6, PAGE, 0)
        _, state, _ = _enter(eng, state, prompt, _table(2, 6, 1), 1, fills)
        states.append(state)
    for name in ("delta", "conv"):
        for a, b in zip(states[0][name], states[1][name]):
            np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]),
                                       atol=5e-5, rtol=1e-4)
            assert np.abs(np.asarray(a[1])).max() > 1e-3
            assert not np.asarray(a[0]).any()    # the other slot: untouched


# -------------------------------------------------- what a slot's state does
@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "kernels"])
def test_a_slot_between_two_chunks_keeps_its_arrays_through_a_burst(
        ref, driver, kernels, paged_kernels, monkeypatch):
    """Slot 1's prompt is half in; slot 0 decodes a burst of three steps.
    Slot 1's state and tail are bit for bit what they were, and slot 0's
    moved; with the kernels on (interpreted here) as with the ``jax.numpy``
    forms."""
    paged_kernels(kernels)
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    eng = InferStep(build(ref, driver))
    pages = 8
    state = eng.init_paged_state(2, 2 * pages, PAGE, 0)
    t0, t1 = _table(2, pages, 0), _table(2, pages, 1)
    out, state, _ = _enter(eng, state, tokens(8, 1), t0, 0)
    _, state, _ = _enter(eng, state, tokens(8, 2), t1, 1)   # first chunk of 2
    before = {n: [np.asarray(a) for a in state[n]] for n in ("delta", "conv")}
    buf, state = eng.decode_iter(
        state, t0 + t1, np.asarray([int(out[0]), 0], np.int32),
        np.asarray([8, 0], np.int32), np.asarray([True, False]), steps=3)
    for n in ("delta", "conv"):
        for a, b in zip(before[n], state[n]):
            np.testing.assert_array_equal(a[1], np.asarray(b[1]))
            assert np.abs(a[0] - np.asarray(b[0])).max() > 0


def test_a_chunk_at_position_zero_starts_from_zero_whatever_the_slot_held(
        ref, net):
    """A slot reused by a shorter request shows nothing of the last one,
    with no reset between them: the chunk program starts from zero where
    ``q_offset`` is 0 (and carries the state where it is not)."""
    eng = InferStep(net)
    pages = 10
    state = eng.init_paged_state(1, pages, PAGE, 0)
    table = _table(1, pages, 0)
    _, state, _ = _enter(eng, state, tokens(30, 5), table, 0)
    assert np.abs(np.asarray(state["delta"][0])).max() > 1e-3
    short = tokens(3, 6)
    out, state, _ = _enter(eng, state, short, table, 0)
    want = np.asarray(ref.forward(SEED, TINY, short))[-1]
    assert int(out[0]) == int(want.argmax())
    np.testing.assert_allclose(
        np.asarray(state["delta"][-1])[0],
        ref.final_states(SEED, TINY, short, [3])[-1, 0], atol=5e-5,
        rtol=1e-4)


def test_the_kernels_serve_what_the_jnp_forms_serve(ref, driver,
                                                    monkeypatch,
                                                    paged_kernels):
    """With the kernels forced on (interpreted here) the delta-rule layers
    update their states through ``%gated_delta_step`` and the full-attention
    layers go through the paged kernels' lane forms: the same tokens, the
    same state."""
    paged_kernels(True)
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    prompt = tokens(19, 8)
    served, _, state = _serve_by_hand(build(ref, driver), prompt, 4,
                                      fills=(7, 8))
    assert served == ref.greedy(SEED, TINY, prompt, 4)
    seq = np.concatenate([prompt, served[:-1]])
    want = ref.final_states(SEED, TINY, seq, [len(seq)])
    np.testing.assert_allclose(np.asarray(state["delta"][-1])[1],
                               want[-1, 0], atol=5e-5, rtol=1e-4)


# ------------------------------------------------- through the batcher
def _batcher(eng, **kw):
    args = dict(slots=2, max_new_tokens=6, page_size=PAGE,
                prefill_chunk=CHUNK, iter_tokens=2, prefix_cache=False,
                warmup=True)
    args.update(kw)
    return make_batcher(eng, args.pop("buckets", [8, 32]), **args)


def test_batcher_serves_the_references_greedy_tokens(ref, net):
    """Five requests through two slots: slots retire and are reused by
    shorter and longer prompts while another slot's prompt is still
    entering in chunks between the bursts."""
    eng = InferStep(net, eos_id=-1)
    assert eng.slot_state["slot_arrays"] == ("delta", "conv")
    assert eng.slot_state["pools"] == ("k_pools", "v_pools")
    assert eng.slot_state["encoder_memory"] is False
    bat = _batcher(eng, name="olmo")
    assert bat._store is None and "cross_k" not in bat._state
    assert len(bat._state["k_pools"]) == 1          # the full layer's
    assert len(bat._state["delta"]) == len(bat._state["conv"]) == N_DELTA
    assert bat._state["k_pools"][0].shape[1:] == (PAGE, 4 * 8)
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
        assert ref.served_token_gaps(SEED, TINY, p, g).max() < 1e-5
    # what the slots are left with: some slot holds the last request's
    # state as the reference's token-by-token rule leaves it (a burst of 2
    # runs whole: the prompt and 2 of the 3 served tokens went in)
    left = bat.slot_arrays()
    assert set(left) == {"delta", "conv"} and len(left["delta"]) == N_DELTA
    seq = np.concatenate([prompts[-1], np.asarray(got[-1][:2], np.int32)])
    want = ref.final_states(SEED, TINY, seq, [len(seq) - 1, len(seq)])
    assert want.shape == (N_DELTA, 2, 4, 8, 16)
    gaps = [np.abs(np.asarray(left["delta"][-1])[s] - want[-1, 1]).max()
            for s in range(2)]
    assert min(gaps) < 1e-4 * np.abs(want[-1, 1]).max() < max(gaps)
    assert bat.pool.free_pages == bat.pool.num_pages
    bat.pool.check_invariants(set())
    assert eng.compile_guard.steady_state_recompiles == 0
    st = bat.stats
    chunks = sum(-(-n // CHUNK) for n in lengths)
    assert st["prompt_tokens"] == st["prefill_scan_tokens"] == sum(lengths)
    assert st["prompt_chunks"] == st["prefill_calls"] == chunks
    assert st["prefill_scan_padded"] == chunks * CHUNK - sum(lengths)
    assert st["prefill_chunks_from_zero"] == st["admitted"] == 5
    assert st["decode_calls"] == st["iterations"] * 2
    assert 0 < st["decode_row_steps"] <= st["decode_calls"] * 2
    assert st["decode_attn_keys"] > 0 and st["prefill_row_steps"] == 0


def test_the_batcher_reports_what_it_provisioned(net):
    eng = InferStep(net)
    bat = _batcher(eng, slots=3, start=False, warmup=False)
    pages = bat.num_pages + 1                       # and the trash page
    kv = pages * PAGE * 4 * 8 * 4                   # 4 heads of 8, float32
    conv_dim = 2 * 4 * 8 + 4 * 16
    assert bat.state_bytes == {
        "pages": 2 * kv,                            # K and V, ONE layer
        "slot_arrays": 3 * N_DELTA * (4 * 8 * 16 * 4 + 3 * conv_dim * 4),
        "encoder_memory": 0}


def test_a_preempted_request_regenerates_its_tokens(ref, net):
    """A pool too small for both requests' replies: the younger one is
    preempted, goes back to the head of the line, and is recomputed from
    its prompt (its slot's state starts from zero again)."""
    eng = InferStep(net, eos_id=-1)
    bat = _batcher(eng, buckets=[16], max_new_tokens=12, num_pages=9,
                   admit_free_pages=0, name="olmo-small-pool")
    prompts = [tokens(8, 91), tokens(8, 92)]
    try:
        futs = [bat.submit(p, max_new_tokens=12) for p in prompts]
        got = [f.result(timeout=300) for f in futs]
    finally:
        bat.stop()
    assert bat.stats["preempted"] >= 1
    assert bat.stats["prefill_chunks_from_zero"] > 2
    for p, g in zip(prompts, got):
        assert [int(t) for t in g] == ref.greedy(SEED, TINY, p, 12)
    assert bat.pool.free_pages == bat.pool.num_pages


def test_what_the_serving_plane_refuses_for_this_net(net):
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
    with pytest.raises(MXNetError, match="value heads"):
        OlmoHybridLM(linear_value_heads=60)
    with pytest.raises(MXNetError, match="key/value head a query head"):
        OlmoHybridLM(num_kv_heads=6)
    with pytest.raises(MXNetError, match="layer_types"):
        OlmoHybridLM(layer_types=(LIN, LIN))


def test_bfloat16_weights_and_caches_serve_with_a_float32_state(ref, driver):
    """The serving dtypes end to end on the CPU (the chip's run decides
    `correct`): weights, K/V and the tail bfloat16, the state in the dtype
    the configuration states."""
    net = build(ref, driver, weights="bfloat16")
    eng = InferStep(net, amp="bfloat16", eos_id=-1)
    bat = _batcher(eng, buckets=[32], max_new_tokens=5, name="olmo-bf16")
    st = bat._state
    assert st["k_pools"][0].dtype == st["conv"][0].dtype == jnp.bfloat16
    assert st["delta"][0].dtype == jnp.float32
    prompts = [tokens(n, 70 + n) for n in (19, 6)]
    try:
        got = [bat.submit(p, max_new_tokens=5).result(timeout=300)
               for p in prompts]
    finally:
        bat.stop()
    gaps = np.concatenate([ref.served_token_gaps(SEED, TINY, p, g)
                           for p, g in zip(prompts, got)])
    assert len(gaps) == 10 and np.isfinite(gaps).all()
    assert gaps.mean() < 0.5
    low = build(ref, driver, weights="bfloat16", state="bfloat16")
    assert low.init_paged_state(2, 3, PAGE, 0)["delta"][0].dtype == \
        jnp.bfloat16

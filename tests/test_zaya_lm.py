"""ZAYA1's language model (``model_zoo/zaya.py``) against its plain
reference (``perf/reference/zaya1-8b.py``) at a tiny preset, on seeded
random weights: the full forward, a prompt in chunks and then decode steps
through the pages and the tail (logits at every served position), what the
pages and the tail hold however the prompt was cut, what a slot's arrays
do between requests, what each part of the equations weighs, the counts,
and the same through ``ContinuousBatcher``."""

import os
import sys

import jax
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 - the package sets JAX up
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.zaya import ZayaLM
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import make_batcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perf.harness.loader import load_module  # noqa: E402

LAYERS, EXPERTS, HEADS, KV, D = 3, 4, 4, 2, 16
TINY = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": LAYERS,
    "num_attention_heads": HEADS, "num_key_value_heads": KV, "head_dim": D,
    "num_experts": EXPERTS, "num_experts_per_tok": 1,
    "moe_intermediate_size": 64, "router_hidden_size": 32,
    "cca_time0": 2, "cca_time1": 2, "rms_norm_eps": 1e-5,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 5000000}},
    "precision": {"weights": "float32"}}
CHANNELS = (HEADS + KV) * D
PAGE, CHUNK, SEED = 4, 8, 11
N_COUNTS = 7 + LAYERS * EXPERTS
# where each count lies in the vector that rides the read-backs
ROW_STEPS, ATTN_KEYS, EXPERT_TOKENS = 0, 1, slice(2, 2 + LAYERS * EXPERTS)
TOUCHED, CHUNK_TOKENS, CHUNK_PADDED, FROM_ZERO, CALLS = range(
    2 + LAYERS * EXPERTS, N_COUNTS)


@pytest.fixture(scope="module")
def ref():
    return load_module(os.path.join(REPO, "perf", "reference",
                                    "zaya1-8b.py"))


@pytest.fixture(scope="module")
def driver():
    return load_module(os.path.join(REPO, "perf", "drivers",
                                    "serve-cca-lm.py"))


@pytest.fixture(autouse=True)
def highest_precision():
    """The program's products in float32 proper, on every thread (the
    scheduler's too), as the reference has them."""
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)


def build(ref, driver, alter=None, seed=SEED):
    """The net with the reference's seeded weights; ``alter(name, array)``
    may hand back another array for a tensor."""
    net = ZayaLM(**driver._model_kwargs(TINY))
    params = net._collect_params_with_prefix()
    assert set(params) == set(ref.tensor_specs(TINY))
    for name, p in params.items():
        w = np.asarray(ref.tensor(seed, TINY, name))
        p.set_data(nd.NDArray(w if alter is None else alter(name, w)))
    return net


@pytest.fixture(scope="module")
def net(ref, driver):
    return build(ref, driver)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, TINY["vocab_size"], n) \
        .astype(np.int32)


def full_logits(net, toks):
    return net(nd.array(np.asarray(toks)[None], dtype="int32")).asnumpy()[0]


# ------------------------------------------------------------ full forward
@pytest.mark.parametrize("length", [1, 2, 3, 8, 21])
def test_full_forward_logits(ref, net, length):
    toks = tokens(length, length)
    want = np.asarray(ref.forward(SEED, TINY, toks))
    np.testing.assert_allclose(full_logits(net, toks), want, atol=2e-5)


PARTS = {
    # the router's state is carried from layer to layer
    "router_carry": lambda n, w: w * 0 if n.endswith("router_gamma") and
    not n.startswith("l0_") else w,
    # the merge's gains and biases, each sublayer's
    "merge_gain": lambda n, w: np.ones_like(w) if n.endswith("_gain") else w,
    "merge_bias": lambda n, w: w * 0 if n.endswith(
        ("res_bias", "res_out_bias")) else w,
    # the selection bias, the key's temperature, the convolutions' taps of
    # the position before, the value's second head
    "router_bias": lambda n, w: w * 0 if n.endswith("router_bias") else w,
    "k_temp": lambda n, w: np.ones_like(w) if n.endswith("k_temp") else w,
    "conv0_before": lambda n, w: np.stack([w[0] * 0, w[1]])
    if n.endswith("conv0_w") else w,
    "conv1_before": lambda n, w: np.stack([w[0] * 0, w[1]])
    if n.endswith("conv1_w") else w,
    "value_shift": lambda n, w: w * 0 if n.endswith("wv2") else w,
}


@pytest.mark.parametrize("part", sorted(PARTS))
def test_every_part_of_the_equations_weighs_in(ref, driver, net, part):
    """A program that drops the part is another model: its logits leave
    the reference's by far more than rounding."""
    toks = tokens(12, 3)
    want = np.asarray(ref.forward(SEED, TINY, toks))
    got = full_logits(build(ref, driver, PARTS[part]), toks)
    assert np.abs(got - want).max() > 20 * 2e-5
    # position 0 stands before nothing: the taps of the position before
    # and the shifted value leave it alone
    if part in ("conv0_before", "conv1_before", "value_shift"):
        np.testing.assert_allclose(got[0], want[0], atol=2e-5)


def test_the_chosen_expert_is_weighed_by_its_probability_not_by_one(
        ref, net, monkeypatch):
    """``a = p[e]`` as it stands: a dispatch that renormalises the chosen
    weights (at one expert a token: 1.0) is another model."""
    from mxnet_tpu.ops.pallas import grouped_swiglu as moe

    toks = tokens(12, 5)
    want = np.asarray(ref.forward(SEED, TINY, toks))
    seen, dispatch = [], moe.dispatch_experts

    def renormalised(u, experts, weights, *rest, **kw):
        seen.append(np.asarray(weights))
        return dispatch(u, experts, weights / weights.sum(-1, keepdims=True),
                        *rest, **kw)

    monkeypatch.setattr(moe, "dispatch_experts", renormalised)
    got = full_logits(net, toks)
    assert np.abs(got - want).max() > 20 * 2e-5
    weights = np.concatenate(seen).ravel()
    assert 1.0 / EXPERTS <= weights.min() and weights.max() < 1.0


# --------------------------------- chunks, then decode steps, by hand
def _table(slots, pages, slot):
    table = np.zeros((slots, pages), np.int32)
    table[slot] = 1 + slot * pages + np.arange(pages)
    return table


def _enter(eng, state, prompt, table, slot, fills=None):
    """The prompt through the chunk program, ``fills`` real tokens a chunk
    (full chunks when None). Returns the last chunk's read-back, the state
    and the counts that rode the read-backs."""
    counts = np.zeros((N_COUNTS,), np.int64)
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
    state = eng.init_paged_state(slots, slots * pages + 1, PAGE, 0)
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
        counts += buf[:, 1:].ravel()[:N_COUNTS]
        served.append(int(buf[slot, 0]))
    return served, counts, state


@pytest.fixture(params=["jnp", "kernels"])
def paged_form(request, monkeypatch, paged_kernels):
    """The ``jax.numpy`` forms of attention (the CPU's), and the paged
    window kernel over the pools as they are declared (interpreted here)."""
    if request.param == "kernels":
        paged_kernels(True)
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    return request.param


@pytest.mark.parametrize("length,fills", [
    (1, None), (3, None), (8, None), (9, None), (21, None),
    (21, (5, 8, 1)), (30, (8, 3)), (17, (1,))])    # chunks of unequal fill
def test_chunks_then_decode_follow_the_reference_at_every_position(
        ref, driver, paged_form, length, fills):
    net = build(ref, driver)     # a trace is cached by the net's functions
    prompt, n_new = tokens(length, 10 + length), 6
    served, counts, _ = _serve_by_hand(net, prompt, n_new, fills=fills)
    seq = np.concatenate([prompt, served[:-1]])
    want_at = len(prompt) - 1 + np.arange(n_new)
    logits = np.asarray(ref.forward(SEED, TINY, seq, want=want_at))
    assert served == [int(t) for t in logits.argmax(-1)]
    assert ref.served_token_gaps(SEED, TINY, prompt, served).max() < 1e-5
    # ---- the counts that rode the read-backs, reckoned by hand
    chunks = counts[CALLS] - (n_new - 1)
    assert counts[CHUNK_TOKENS] == length
    assert counts[CHUNK_TOKENS] + counts[CHUNK_PADDED] == chunks * CHUNK
    assert counts[FROM_ZERO] == 1               # one chunk began from zero
    assert counts[ROW_STEPS] == n_new - 1       # live rows x decode steps
    n = len(seq)
    assert counts[ATTN_KEYS] == n * (n + 1) // 2   # positions a layer read
    # every real token goes to ONE expert a layer: the reference's
    tap = {}
    ref.hidden(SEED, TINY, seq, tap=tap)
    by_expert = np.stack([np.bincount(e, minlength=EXPERTS)
                          for e in tap["experts"]])
    np.testing.assert_array_equal(
        counts[EXPERT_TOKENS].reshape(LAYERS, EXPERTS), by_expert)
    # the grouped product read at least the experts that got a real token
    # (a chunk's padding and the idle slot's row go to experts too), and
    # at most every expert of every layer in every call
    calls = chunks + n_new - 1
    assert (by_expert > 0).sum() <= counts[TOUCHED] <= \
        calls * LAYERS * EXPERTS


def test_heads_of_128_decode_through_the_walk(ref, driver, monkeypatch,
                                              paged_kernels):
    """At the published head size the decode step's call is the kernel
    that walks a row's live pages with its own copies (interpreted here;
    ``paged_decode_attention`` picks it by ``D % 128``): pools declared
    ``(pages, page x 2, 128)``, the group of two on the query rows. The
    served tokens are the reference's at every position."""
    from mxnet_tpu.ops.pallas import paged_flash_attention as pfa

    monkeypatch.setitem(TINY, "head_dim", 128)
    paged_kernels(True)
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    walked, walk = [], pfa._decode_walk
    monkeypatch.setattr(pfa, "_decode_walk", lambda q, k_pool, *a: (
        walked.append(k_pool.shape[1:]), walk(q, k_pool, *a))[1])
    prompt, n_new = tokens(9, 19), 6
    served, counts, _ = _serve_by_hand(build(ref, driver), prompt, n_new)
    assert set(walked) == {(PAGE * KV, 128)}
    seq = np.concatenate([prompt, served[:-1]])
    logits = np.asarray(ref.forward(
        SEED, TINY, seq, want=len(prompt) - 1 + np.arange(n_new)))
    assert served == [int(t) for t in logits.argmax(-1)]
    assert ref.served_token_gaps(SEED, TINY, prompt, served).max() < 1e-5
    assert counts[ATTN_KEYS] == len(seq) * (len(seq) + 1) // 2


# ------------------------------- a page is a function of three positions
def _first_pages(state, n):
    """The first ``n`` positions of slot 1's first-layer K and V, ``(n,
    heads, D)`` each (its pages lie in order)."""
    out = []
    for name in ("k_pools", "v_pools"):
        pool = np.asarray(state[name][0])
        out.append(pool.reshape(pool.shape[0], PAGE, KV, D)
                   [_table(2, 8, 1)[1]].reshape(-1, KV, D)[:n])
    return out


def _after_prompt(net, prompt, fills):
    eng = InferStep(net)
    state = eng.init_paged_state(2, 2 * 8 + 1, PAGE, 0)
    _, state, _ = _enter(eng, state, prompt, _table(2, 8, 1), 1, fills)
    return state


@pytest.mark.parametrize("cut", range(1, CHUNK + 1))
def test_a_prompt_cut_anywhere_leaves_the_pages_of_one_chunk(ref, net, cut):
    """The first chunk holds ``cut`` tokens, so the tail crosses the
    boundary at every offset of a chunk (with 0, 1 and 2 and more
    positions behind it): the pages, the tail and the value half are what
    whole chunks leave, and the reference's."""
    prompt = tokens(19, 4)
    whole = _after_prompt(net, prompt, None)
    parts = _after_prompt(net, prompt, (cut, CHUNK, CHUNK, CHUNK))
    tap = {"kv_layers": (0,), "tail_at": len(prompt) - 1}
    ref.hidden(SEED, TINY, prompt, tap=tap)
    for got, same, want in zip(_first_pages(parts, 19),
                               _first_pages(whole, 19),
                               (tap["k"][0], tap["v"][0])):
        np.testing.assert_allclose(got, same, atol=2e-6)
        np.testing.assert_allclose(got, want, atol=2e-5)
    for i in range(LAYERS):
        for state in (whole, parts):
            np.testing.assert_allclose(np.asarray(state["tail"][i][1]),
                                       tap["tails"][i], atol=2e-5)
            np.testing.assert_allclose(np.asarray(state["value_half"][i][1]),
                                       tap["halves"][i], atol=2e-5)
            assert not np.asarray(state["tail"][i][0]).any()   # slot 0


def test_a_tail_from_the_wrong_position_leaves_a_wrong_page(
        ref, driver, monkeypatch):
    """What the check of the cell has to catch: a chunk program that keeps
    the tail of the chunk's LAST position, real or not, writes wrong keys
    at the first two positions of the next chunk, and nowhere else."""
    prompt = tokens(19, 4)
    tap = {"kv_layers": (0,)}
    ref.hidden(SEED, TINY, prompt, tap=tap)
    monkeypatch.setattr(
        ZayaLM, "_kept", lambda self, x, at: x[:, CHUNK - 1:CHUNK])
    wrong = _after_prompt(build(ref, driver), prompt, (5, CHUNK, CHUNK))
    gap = np.abs(_first_pages(wrong, 19)[0] - tap["k"][0]).max((1, 2))
    assert gap[5] > 0.05 and gap[6] > 0.05
    assert np.delete(gap, [5, 6]).max() < 2e-5


def test_a_row_that_ends_inside_a_chunk_leaves_its_last_real_tokens_tail(
        ref, net):
    """Five real tokens in a chunk of eight, the rest padding of a real
    token id: the tail is position 4's."""
    prompt = tokens(5, 9)
    state = _after_prompt(net, prompt, None)
    tap = {"tail_at": 4}
    ref.hidden(SEED, TINY, prompt, tap=tap)
    for i in range(LAYERS):
        np.testing.assert_allclose(np.asarray(state["tail"][i][1]),
                                   tap["tails"][i], atol=2e-5)
        np.testing.assert_allclose(np.asarray(state["value_half"][i][1]),
                                   tap["halves"][i], atol=2e-5)
    assert np.abs(tap["tails"]).max() > 1e-2


# -------------------------------------------------- what a slot's state does
def test_a_slot_between_two_chunks_keeps_its_arrays_through_a_burst(net):
    """Slot 1's prompt is half in; slot 0 decodes a burst of three steps.
    Slot 1's tail and value half are bit for bit what they were, and slot
    0's moved."""
    eng = InferStep(net)
    pages = 8
    state = eng.init_paged_state(2, 2 * pages + 1, PAGE, 0)
    t0, t1 = _table(2, pages, 0), _table(2, pages, 1)
    out, state, _ = _enter(eng, state, tokens(8, 1), t0, 0)
    _, state, _ = _enter(eng, state, tokens(8, 2), t1, 1)   # first chunk of 2
    names = ("tail", "value_half")
    before = {n: [np.asarray(a) for a in state[n]] for n in names}
    buf, state = eng.decode_iter(
        state, t0 + t1, np.asarray([int(out[0]), 0], np.int32),
        np.asarray([8, 0], np.int32), np.asarray([True, False]), steps=3)
    for n in names:
        for a, b in zip(before[n], state[n]):
            np.testing.assert_array_equal(a[1], np.asarray(b[1]))
            assert np.abs(a[0] - np.asarray(b[0])).max() > 0


def test_a_chunk_at_position_zero_starts_from_zero_whatever_the_slot_held(
        ref, net):
    """A slot reused by a shorter request shows nothing of the last one,
    with no reset between them: the chunk program starts from zero where
    ``q_offset`` is 0 (and carries the tail where it is not)."""
    eng = InferStep(net)
    pages = 10
    state = eng.init_paged_state(1, pages + 1, PAGE, 0)
    table = _table(1, pages, 0)
    _, state, _ = _enter(eng, state, tokens(30, 5), table, 0)
    assert np.abs(np.asarray(state["tail"][0])).max() > 1e-2
    short = tokens(3, 6)
    out, state, counts = _enter(eng, state, short, table, 0)
    assert counts[FROM_ZERO] == 1
    want = np.asarray(ref.forward(SEED, TINY, short))[-1]
    assert int(out[0]) == int(want.argmax())
    tap = {"tail_at": 2}
    ref.hidden(SEED, TINY, short, tap=tap)
    np.testing.assert_allclose(np.asarray(state["tail"][LAYERS - 1][0]),
                               tap["tails"][LAYERS - 1], atol=2e-5)


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
    assert eng.slot_state["slot_arrays"] == ("tail", "value_half")
    assert eng.slot_state["pools"] == ("k_pools", "v_pools")
    assert eng.slot_state["encoder_memory"] is False
    assert eng.slot_state["step_tokens"] == 1
    bat = _batcher(eng, name="zaya")
    assert bat._store is None and "cross_k" not in bat._state
    # EVERY layer keeps both kinds
    for name in ("k_pools", "v_pools", "tail", "value_half"):
        assert len(bat._state[name]) == LAYERS
    pages = bat._state["k_pools"][0].shape[0]     # the pool and page 0
    assert bat._state["k_pools"][0].shape == (pages, PAGE * KV, D)
    assert bat._state["tail"][0].shape == (2, 2, CHANNELS)
    assert bat.state_bytes["pages"] == \
        2 * LAYERS * pages * PAGE * KV * D * 4
    assert bat.state_bytes["slot_arrays"] == \
        LAYERS * 2 * (2 * CHANNELS + D) * 4
    lengths, news = [21, 3, 30, 9, 17], [6, 4, 5, 6, 3]
    prompts = [tokens(n, 40 + n) for n in lengths]
    try:
        futs = [bat.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, news)]
        got = [f.result(timeout=300) for f in futs]
        with pytest.raises(MXNetError, match="stopped batcher"):
            bat.slot_arrays()              # a running scheduler donates them
    finally:
        bat.stop()
    for p, m, g in zip(prompts, news, got):
        assert [int(t) for t in g] == ref.greedy(SEED, TINY, p, m)
        assert ref.served_token_gaps(SEED, TINY, p, g).max() < 1e-5
    assert bat.pool.free_pages == bat.pool.num_pages
    bat.pool.check_invariants(set())
    assert eng.compile_guard.steady_state_recompiles == 0
    st = bat.stats
    chunks = sum(-(-n // CHUNK) for n in lengths)
    assert st["prompt_tokens"] == st["prefill_chunk_tokens"] == sum(lengths)
    assert st["prompt_chunks"] == st["prefill_calls"] == chunks
    assert st["prefill_chunk_padded"] == chunks * CHUNK - sum(lengths)
    assert st["prefill_chunks_from_zero"] == st["admitted"] == 5
    assert st["decode_calls"] == st["iterations"] * 2
    assert 0 < st["decode_row_steps"] <= st["decode_calls"] * 2
    assert st["decode_attn_keys"] > 0 and st["prefill_row_steps"] == 0
    assert st["prefill_expert_tokens"].shape == (LAYERS * EXPERTS,)
    # a real token goes to one expert a layer
    assert st["prefill_expert_tokens"].sum() == LAYERS * sum(lengths)
    assert st["decode_expert_tokens"].sum() == \
        LAYERS * st["decode_row_steps"]
    assert 0 < st["decode_experts_touched"] <= \
        st["decode_calls"] * LAYERS * EXPERTS


def test_the_cells_check_reads_the_pages_and_the_tail_a_request_left(
        ref, driver, net):
    """``serve-cca-lm.py``'s reading of a stopped batcher: the request
    that ended last among those whose fed tokens are all known, its pages
    found by their keys, its slot by its tail, at the position its last
    burst ran to."""
    import types

    record = types.SimpleNamespace
    # 6 served tokens need 5 steps and bursts of 4 run 8: tokens 6 and 7
    # are nobody's; 5 served tokens need 4 steps, which is one burst
    assert driver.steps_fed(6, 4) == 8 and driver.steps_fed(5, 4) == 4
    assert driver.steps_fed(1, 4) == 0
    fake = [record(error=None, tokens=[0] * 5, last=1.0),
            record(error=None, tokens=[0] * 6, last=2.0),
            record(error="x", tokens=[0] * 5, last=3.0)]
    assert driver._settled(fake, 4) is fake[0]
    assert driver._settled(fake, 1) is fake[1]
    assert driver._settled(fake[1:], 4) is None

    eng = InferStep(net, eos_id=-1)
    bat = _batcher(eng, name="zaya-check")
    prompts, news = [tokens(n, 70 + n) for n in (21, 12, 9)], [5, 6, 4]
    try:
        got = [bat.submit(p, max_new_tokens=m).result(timeout=300)
               for p, m in zip(prompts, news)]
    finally:
        bat.stop()
    last = record(error=None, prompt=prompts[-1],
                  tokens=[int(t) for t in got[-1]], last=1.0)
    state, arrays = bat.paged_state(), bat.slot_arrays()
    read = {"k": np.asarray(state["k_pools"][0], np.float32),
            "v": np.asarray(state["v_pools"][0], np.float32),
            "tail": np.stack([np.asarray(a) for a in arrays["tail"]]),
            "half": np.stack([np.asarray(a) for a in arrays["value_half"]]),
            "last": last, "settled": last,
            "iter_tokens": bat.iter_tokens}
    cfg = dict(TINY, serving={"page_size": PAGE}, check={})
    # 4 served tokens, bursts of 2: the prompt and all 4 went in
    numbers, more = driver.cache_and_tail_gaps(ref, SEED, cfg, read)
    assert more["positions"] == 9 + 4 and more["prompt"] == 9
    assert max(numbers.values()) < 1e-5
    assert 0.0 <= more["near_tie_share"] <= 1.0
    # held against the position before, the tail is another position's
    numbers, more = driver.cache_and_tail_gaps(
        ref, SEED, cfg, dict(read, iter_tokens=1))
    assert more["positions"] == 9 + 3
    assert numbers["page_gap_widest"] < 1e-5 and numbers["tail_gap"] > 0.1
    # no request to hold them against: nothing is inside
    numbers, more = driver.cache_and_tail_gaps(
        ref, SEED, cfg, dict(read, last=None))
    assert more["positions"] == 0 and np.isnan(numbers["page_gap"])

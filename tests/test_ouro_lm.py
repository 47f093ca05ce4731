"""The looped language model (``model_zoo/ouro.py``) against its plain
reference (``perf/reference/ouro-2.6b.py``) at a tiny preset, on seeded
random weights: the full forward, a prompt in chunks and then decode steps
through the planes (logits at every served position), the same through
``InferStep`` and ``ContinuousBatcher``, what each (pass, layer) plane
holds, the exit gate's counted distribution, and what is refused by name."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 - the package sets JAX up
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo import ouro
from mxnet_tpu.gluon.model_zoo.ouro import OuroLM
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import make_batcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perf.harness.loader import load_module  # noqa: E402

TINY = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 96, "total_ut_steps": 4, "early_exit_threshold": 1,
    "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "precision": {"weights": "float32"}}
PAGE, CHUNK, SEED = 4, 8, 11
NO_END = -1
PASSES = [1, 2, 4]


def tiny(passes):
    return dict(TINY, total_ut_steps=passes)


@pytest.fixture(scope="module")
def ref():
    return load_module(os.path.join(REPO, "perf", "reference",
                                    "ouro-2.6b.py"))


@pytest.fixture(scope="module")
def driver():
    return load_module(os.path.join(REPO, "perf", "drivers",
                                    "serve-loop-lm.py"))


@pytest.fixture(autouse=True)
def highest_precision():
    """The program's products in float32 proper, on every thread (the
    scheduler's too), as the reference has them."""
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)


def build(ref, driver, cfg=TINY, seed=SEED, **more):
    net = OuroLM(**dict(driver._model_kwargs(cfg), **more))
    params = net._collect_params_with_prefix()
    assert set(params) == set(ref.tensor_specs(cfg))
    for name, p in params.items():
        p.set_data(nd.NDArray(ref.tensor(seed, cfg, name)))
    return net


@pytest.fixture(scope="module")
def nets(ref, driver):
    return {t: build(ref, driver, tiny(t)) for t in PASSES}


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, TINY["vocab_size"], n) \
        .astype(np.int32)


# ------------------------------------------------------------ full forward
@pytest.mark.parametrize("passes", PASSES)
def test_full_forward_is_the_references(ref, nets, passes):
    toks = tokens(20, passes)
    want, _ = ref.forward(SEED, tiny(passes), toks)
    got = nets[passes](nd.array(toks[None], dtype="int32")).asnumpy()[0]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_passes_share_one_stack_of_weights(ref, nets):
    """The parameter count is ONE stack's whatever ``total_ut_steps``, and
    a pass more is another model all the same."""
    count = {t: sum(int(np.prod(p.shape)) for p in
                    n._collect_params_with_prefix().values())
             for t, n in nets.items()}
    h, f, v, a = 64, 96, 128, 64
    one = 3 * (4 * h * a + 3 * h * f + 4 * h) + 2 * h + 1 + 2 * v * h
    assert count == {1: one, 2: one, 4: one}
    toks = tokens(12, 3)
    out = {t: n(nd.array(toks[None], dtype="int32")).asnumpy()
           for t, n in nets.items()}
    assert np.abs(out[4] - out[2]).max() > 0.1
    assert np.abs(out[2] - out[1]).max() > 0.1
    # the published widths: 51,388,416 a layer, 2,667,974,657 in all
    big = load_module(os.path.join(REPO, "perf", "ops_counts",
                                   "ouro-2.6b.py"))
    import json
    with open(os.path.join(REPO, "perf", "configs", "ouro-2.6b.json")) as fh:
        cfg = json.load(fh)
    assert big.layer_params(cfg) == 51388416
    assert big.weight_params(cfg) == 2667974657
    assert big.plane_bytes_position(cfg) == 1572864
    assert sum(int(np.prod(s)) for s in ref.tensor_specs(cfg).values()) \
        == 2667974657


def test_a_threshold_below_one_is_refused_by_name(ref, driver):
    with pytest.raises(MXNetError, match="early_exit_threshold 0.5"):
        build(ref, driver, early_exit_threshold=0.5)
    with pytest.raises(MXNetError, match="num_key_value_heads"):
        OuroLM(num_heads=4, num_kv_heads=2)
    build(ref, driver, early_exit_threshold=1.0)


# ------------------------------------- chunked prefill, decode through planes
def _serve_by_hand(net, prompt, n_steps, slots=2, slot=1):
    """A prompt in chunks and then decode steps through the net's own paged
    programs, one row among inert ones. Returns the logits at every served
    position, the greedy tokens, the state and the table."""
    pages = -(-(len(prompt) + n_steps + 1) // PAGE)
    state = net.init_paged_state(slots, 1 + slots * pages, PAGE, 0)
    table = np.zeros((slots, pages), np.int32)
    table[slot] = 1 + slot * pages + np.arange(pages)
    at, logits = 0, []
    while at < len(prompt):
        part = prompt[at:at + CHUNK]
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :len(part)] = part
        out, state = net.prefill_suffix_paged(
            toks, [len(part)], [at], state, table[slot:slot + 1], [slot],
            [True])
        at += len(part)
    logits.append(np.asarray(out[0]))
    served = [int(np.argmax(logits[-1]))]
    active = np.arange(slots) == slot
    for j in range(n_steps):
        tok = np.where(active, served[-1], 0).astype(np.int32)
        pos = np.where(active, len(prompt) + j, 0).astype(np.int32)
        out, state = net.decode_step_paged(tok, pos, state, table, active)
        logits.append(np.asarray(out[slot]))
        served.append(int(np.argmax(logits[-1])))
    return np.stack(logits), served, state, table[slot]


@pytest.mark.parametrize("passes", PASSES)
@pytest.mark.parametrize("length", [5, 19])
def test_chunked_prefill_then_decode_equal_the_full_forward(ref, nets,
                                                            passes, length):
    """The logits at every served position: a prompt in chunks (the second
    chunk's queries read the first's keys in every plane), then steps
    that read what the chunks and the steps before wrote, each pass its
    own plane."""
    cfg, prompt = tiny(passes), tokens(length, 10 + length)
    got, served, state, _ = _serve_by_hand(nets[passes], prompt, 5)
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    want, _ = ref.forward(SEED, cfg, seq,
                          want=length - 1 + np.arange(len(served)))
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert served == ref.greedy(SEED, cfg, prompt, len(served))
    counts = np.asarray(state["counts"])
    calls = -(-length // CHUNK) + 5
    assert counts[4] == calls and counts[1] == calls
    assert counts[0] == calls * passes
    assert counts[3] == calls * passes * 3
    # a row's cached positions, once a call, in every plane of every layer
    keys = sum(min(length, a + CHUNK) for a in range(0, length, CHUNK)) \
        + sum(length + j + 1 for j in range(5))
    assert counts[2] == keys * passes * 3


def test_each_plane_holds_the_keys_of_its_own_pass(ref, nets):
    """Plane ``t`` of layer ``l`` holds the reference's keys of pass ``t``
    of layer ``l``, for prompt and served positions alike; two planes of a
    layer differ."""
    prompt = tokens(13, 7)
    _, served, state, pages = _serve_by_hand(nets[4], prompt, 4)
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    planes = [(t, i) for t in range(4) for i in range(3)]
    want = ref.plane_keys(SEED, TINY, seq, planes)
    for (t, i), k in want.items():
        got = np.asarray(state["k_pools"][i][t, pages]).reshape(
            -1, 4, 16)[:len(seq)]
        np.testing.assert_allclose(got, k, atol=2e-5)
    assert np.abs(want[(0, 0)] - want[(3, 0)]).max() > 0.1
    # every plane has its own trash page; nothing else was written
    other = np.asarray(state["k_pools"][0][:, 1:1 + len(pages)])
    assert not other.any()


@pytest.mark.parametrize("which", ["chunk", "step"])
def test_a_program_that_reads_the_last_plane_in_every_pass_fails(
        ref, driver, monkeypatch, which):
    """Made to read and write plane ``T - 1`` whatever the pass, the chunk
    program or the decode step no longer gives the reference's logits."""
    name = {"chunk": "prefill_suffix_paged", "step": "decode_step_paged"}[
        which]
    real = getattr(ouro.OuroLM, name)

    def last_plane(self, *args, **kw):
        self._plane_start = lambda t, num_pages: 0 * t + 3 * num_pages
        try:
            return real(self, *args, **kw)
        finally:
            del self._plane_start

    monkeypatch.setattr(ouro.OuroLM, name, last_plane)
    net = build(ref, driver)
    prompt = tokens(13, 7)
    got, served, _, _ = _serve_by_hand(net, prompt, 4)
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    want, _ = ref.forward(SEED, TINY, seq,
                          want=12 + np.arange(len(served)))
    assert np.abs(got - np.asarray(want)).max() > 0.05


@pytest.mark.parametrize("passes", PASSES)
def test_the_exit_distribution_is_counted_and_is_the_references(
        ref, nets, passes):
    """``exit_mass`` sums to the positions whose logits went back, a
    million each, and is the sum of the reference's ``p_t`` there."""
    cfg, prompt = tiny(passes), tokens(11, 5)
    _, served, state, _ = _serve_by_hand(nets[passes], prompt, 6)
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    _, lam = ref.forward(SEED, cfg, seq)
    # the chunks' last positions (7 and 10) and the six steps'
    at = [CHUNK - 1] + list(range(10, 10 + 7))
    want = ref.exit_distribution(np.asarray(lam)[:, at]).sum(1)
    mass = np.asarray(state["counts"])[5:] / 1e6
    assert len(mass) == passes
    np.testing.assert_allclose(mass, want, atol=2e-5)
    assert abs(mass.sum() - len(at)) < 1e-5
    if passes > 1:
        # the gate's bias is not zero, and dropping it shows
        assert abs(float(ref.tensor(SEED, cfg, "exit_b")[0])) > 0.01
        assert 0.02 < mass[0] / len(at) < 0.98


# --------------------------------------------------- through the scheduler
def _through_batcher(net, prompts, max_new, iter_tokens=2, slots=3):
    eng = InferStep(net, eos_id=NO_END)
    bat = make_batcher(eng, [16, 40], slots=slots, max_new_tokens=8,
                       page_size=PAGE, prefill_chunk=CHUNK,
                       iter_tokens=iter_tokens, prefix_cache=False,
                       warmup=True, name="t")
    try:
        futs = [bat.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, max_new)]
        out = [f.result(timeout=300) for f in futs]
    finally:
        bat.stop()
    assert bat.pool.free_pages == bat.pool.num_pages
    bat.pool.check_invariants(set())
    assert eng.compile_guard.steady_state_recompiles == 0
    return out, dict(bat.stats), bat


@pytest.mark.parametrize("passes", PASSES)
def test_the_scheduler_serves_the_references_greedy_stream(ref, nets,
                                                           passes):
    """Through ``InferStep`` and the batcher: chunks between bursts, rows
    coming and going, pages reused: every request gets the tokens of the
    reference's full forwards."""
    cfg = tiny(passes)
    prompts = [tokens(n, 20 + n) for n in (5, 23, 9, 16, 3, 38)]
    max_new = [5, 8, 2, 7, 1, 6]
    out, stats, bat = _through_batcher(nets[passes], prompts, max_new)
    for p, n, got in zip(prompts, max_new, out):
        assert got == ref.greedy(SEED, cfg, p, n)
    assert stats["tokens"] + len(prompts) == sum(max_new)
    rows = stats["prefill_row_steps"] + stats["decode_row_steps"]
    assert stats["prefill_stack_passes"] + stats["decode_stack_passes"] \
        == rows * passes
    assert stats["decode_attn_calls"] == stats["decode_calls"] * passes * 3
    mass = np.asarray(stats["prefill_exit_mass"]) \
        + np.asarray(stats["decode_exit_mass"])
    assert abs(mass.sum() / 1e6 - rows) < 1e-3 * rows
    # slots x pages (and the trash page) x page bytes x K and V x L x T
    pages = bat.pages_per_slot * 3 + 1
    assert bat.state_bytes == {
        "pages": pages * (PAGE * 4 * 16 * 4) * 2 * 3 * passes,
        "slot_arrays": 0, "encoder_memory": 0}
    assert bat.paged_state()["k_pools"][0].shape == (passes, pages, PAGE,
                                                     4, 16)


def test_a_burst_runs_the_paged_kernels_and_serves_the_same_tokens(
        ref, driver, nets, monkeypatch, paged_kernels):
    """With the paged kernels routed to (``paged_kernels(True)``; here
    interpreted) the chunk's selected window and the step's decode kernel
    read a pool flattened over planes and pages through a page table moved
    by the loop's carried index: the tokens are the ``jax.numpy`` form's,
    which are the reference's."""
    from mxnet_tpu.ops.pallas import paged_flash_attention as pfa

    traced = []
    real_d, real_w = pfa.paged_decode_attention, \
        pfa.paged_selected_window_attention

    def seen(what, pool, table):
        assert pool.shape[1:] == (PAGE, 4, 16)     # pages of every plane
        traced.append((what, isinstance(table, jax.core.Tracer)))

    def decode(q, k_pool, v_pool, table, *a, **kw):
        seen("step", k_pool, table)
        return real_d(q, k_pool, v_pool, table, *a, **kw)

    def window(q, k_pool, v_pool, table, *a, **kw):
        seen("chunk", v_pool, table)
        return real_w(q, k_pool, v_pool, table, *a, **kw)

    monkeypatch.setattr(pfa, "paged_decode_attention", decode)
    monkeypatch.setattr(pfa, "paged_selected_window_attention", window)
    prompts = [tokens(n, 20 + n) for n in (5, 23, 9, 3)]
    max_new = [5, 8, 2, 6]
    paged_kernels(False)
    plain, *_ = _through_batcher(nets[2], prompts, max_new)
    assert not traced
    paged_kernels(True)
    net = build(ref, driver, tiny(2))            # a trace of its own
    out, stats, _ = _through_batcher(net, prompts, max_new)
    # the layers are traced ONCE a program, the plane's pages a traced value
    assert sorted(set(traced)) == [("chunk", True), ("step", True)]
    assert traced.count(("chunk", True)) == 3
    assert traced.count(("step", True)) == 3
    assert out == plain
    for p, n, got in zip(prompts, max_new, out):
        assert got == ref.greedy(SEED, tiny(2), p, n)


def test_what_is_refused_for_this_net_is_refused_by_name(nets):
    net = nets[4]
    eng = InferStep(net)
    assert eng.supports_paged and not eng.supports_decode
    decl = eng.slot_state
    assert decl["pools"] == ("k_pools", "v_pools")
    assert decl["step_tokens"] == 1 and decl["slot_arrays"] == ()
    assert not decl["encoder_memory"]
    assert decl["counts"] == (
        ("stack_passes", 1), ("row_steps", 1), ("attn_keys", 1),
        ("attn_calls", 1), ("calls", 1), ("exit_mass", 4))
    with pytest.raises(MXNetError, match="attach_draft"):
        eng.attach_draft(net)
    with pytest.raises(MXNetError, match="hot weight swap"):
        eng.stage_params({})
    with pytest.raises(MXNetError, match="prefix cache"):
        make_batcher(eng, [16], slots=2, page_size=PAGE, prefill_chunk=CHUNK,
                     prefix_cache=True, start=False)
    with pytest.raises(MXNetError, match="forced prefix"):
        make_batcher(eng, [16], slots=2, max_prefix_tokens=4, start=False)
    bat = make_batcher(eng, [16], slots=2, page_size=PAGE,
                       prefill_chunk=CHUNK, start=False)
    with pytest.raises(MXNetError, match="handoff frames"):
        bat.submit([3, 4], frames={"length": 1})
    state = eng.init_paged_state(2, 4, PAGE, 0)
    assert len(state["k_pools"]) == len(state["v_pools"]) == 3
    # the plane axis names the compiled program
    assert eng._state_sig(state) == ((4, 5, PAGE, 4, 16), None)
    assert state["counts"].shape == (9,)


def test_a_cache_kept_in_float8_is_rounded_at_the_write(ref, driver):
    """The control's program: keys and values go through float8 on their
    way into the pool's cells, and the served logits move."""
    plain = build(ref, driver)
    low = build(ref, driver, cache_dtype="float8_e4m3fn")
    toks = tokens(12, 9)
    a = plain(nd.array(toks[None], dtype="int32")).asnumpy()
    b = low(nd.array(toks[None], dtype="int32")).asnumpy()
    assert 1e-3 < np.abs(a - b).max() < 1.0
    _, _, state, pages = _serve_by_hand(low, toks, 2)
    for name in ("k_pools", "v_pools"):
        got = np.asarray(state[name][1][2, pages])
        assert got.any()
        assert (got == np.asarray(jnp.asarray(got).astype(
            jnp.float8_e4m3fn).astype(jnp.float32))).all()

"""The latent-attention language model (``model_zoo/joyai.py``) against its
plain reference (``perf/reference/joyai-llm-flash.py``) at the tiny preset,
on seeded random weights: full forward and the draft module's logits,
chunked prefill and decoding through the latent pages, sixteen shares of
the experts adding up to the whole layer, and the step that yields up to
two tokens through ``InferStep`` and ``ContinuousBatcher``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 - the package sets JAX up
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.joyai import COUNTS, JoyAILM
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import make_batcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from perf.harness.loader import load_module  # noqa: E402

TINY = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "first_k_dense_replace": 1, "router_width": 8,
    "experts_held": [0, 8], "n_shared_experts": 1, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "routed_scaling_factor": 2.5,
    "rope_theta": 32e6, "rms_norm_eps": 1e-6,
    "precision": {"weights": "float32"}}
PAGE, CHUNK, SEED = 4, 8, 11
NO_END = -1


@pytest.fixture(scope="module")
def ref():
    return load_module(os.path.join(REPO, "perf", "reference",
                                    "joyai-llm-flash.py"))


@pytest.fixture(scope="module")
def driver():
    return load_module(os.path.join(REPO, "perf", "drivers",
                                    "serve-mla-lm.py"))


@pytest.fixture(autouse=True)
def highest_precision():
    """The program's products in float32 proper, on every thread (the
    scheduler's too), as the reference has them."""
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)


def build(ref, driver, cfg=TINY, seed=SEED, cls=JoyAILM, **more):
    net = cls(**dict(driver._model_kwargs(cfg), **more))
    params = net._collect_params_with_prefix()
    assert set(params) == set(ref.tensor_specs(cfg))
    for name, p in params.items():
        p.set_data(nd.NDArray(ref.tensor(seed, cfg, name)))
    return net


@pytest.fixture(scope="module")
def net(ref, driver):
    return build(ref, driver)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(3, TINY["vocab_size"], n) \
        .astype(np.int32)


# ------------------------------------------------------------ full forward
@pytest.mark.parametrize("length", [5, 8, 20])
def test_full_forward_and_the_modules_logits(ref, net, length):
    """(e) The module's prediction of token ``i + 2`` from the hidden state
    at ``i`` and the true token ``i + 1``, at every position, beside the
    model's own logits."""
    toks = tokens(length, length)
    got, got_m = net.forward_with_draft(toks[None])
    want, want_m = ref.forward(SEED, TINY, toks,
                               want_draft=np.arange(length - 1))
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    np.testing.assert_allclose(got_m[0], want_m, atol=2e-5)
    served = net(nd.array(toks[None], dtype="int32")).asnumpy()[0]
    np.testing.assert_allclose(served, want, atol=2e-5)
    # the module is no copy of the model: it predicts one token further
    assert np.abs(np.asarray(want_m) - np.asarray(want[:-1])).max() > 0.1


def test_sixteen_shares_add_up_to_the_uncut_layer(ref, driver):
    """(d) The share test of the model-configs guide's section 4: the
    reference over ALL experts of a layer against the sum of its shares,
    each holding a run of the experts, the shared expert counted once."""
    cfg = dict(TINY, router_width=16, experts_held=[0, 16])
    h = cfg["hidden_size"]
    u = jax.random.normal(jax.random.PRNGKey(3), (24, h), jnp.float32)

    def layer(held, shared=True):
        c = dict(cfg, experts_held=list(held))
        w = {n: ref.tensor(SEED, c, n, s)
             for n, s in ref.block_specs(c, "l1_", False).items()}
        if not shared:
            for n in ("l1_shared_gate", "l1_shared_up", "l1_shared_down"):
                w[n] = w[n] * 0
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref._experts(w, "l1_", u, 24, c, None, None))

    whole = layer((0, 16))
    shares = [layer((first, 1), shared=first == 0) for first in range(16)]
    np.testing.assert_allclose(sum(shares), whole, atol=2e-5)
    four = [layer((first, 4), shared=first == 0) for first in range(0, 16, 4)]
    np.testing.assert_allclose(sum(four), whole, atol=2e-5)
    # every share's router ranks all sixteen, and a share is not the whole
    assert np.abs(four[1] - whole).max() > 1e-2
    # the program's share is the reference's share
    c = dict(cfg, experts_held=[4, 4])
    part = build(ref, driver, c)
    full = build(ref, driver, cfg)
    toks = tokens(12, 5)
    got = part(nd.array(toks[None], dtype="int32")).asnumpy()[0]
    want, _ = ref.forward(SEED, c, toks)
    np.testing.assert_allclose(got, want, atol=2e-5)
    whole_logits = full(nd.array(toks[None], dtype="int32")).asnumpy()[0]
    assert np.abs(got - whole_logits).max() > 1e-2


def test_the_router_weighs_without_the_bias_and_counts_the_shared_once(
        ref, net):
    """(c) In the reference as in the program: the bias moves the choice
    and never the weights; the weights sum to the scaling factor; removing
    the shared expert removes exactly its output, once."""
    cfg = TINY
    w = {n: ref.tensor(SEED, cfg, n, s)
         for n, s in ref.block_specs(cfg, "l1_", False).items()}
    u = jax.random.normal(jax.random.PRNGKey(1), (32, cfg["hidden_size"]))
    idx, a, score = ref.route(w, "l1_", u, cfg, None)
    np.testing.assert_allclose(np.asarray(a).sum(-1), 2.5, rtol=1e-6)
    top = jnp.take_along_axis(score, idx, -1)
    np.testing.assert_allclose(a, 2.5 * top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    unbiased = jax.lax.top_k(score, 2)[1]
    assert (np.sort(idx, -1) != np.sort(unbiased, -1)).any()   # it selects
    with jax.default_matmul_precision("highest"):
        both = ref._experts(w, "l1_", u, 32, cfg, None, None)
        shared = ref._swiglu(u, w["l1_shared_gate"], w["l1_shared_up"],
                             w["l1_shared_down"], quant=None)
        w0 = dict(w, l1_shared_down=w["l1_shared_down"] * 0)
        routed = ref._experts(w0, "l1_", u, 32, cfg, None, None)
    np.testing.assert_allclose(both - routed, shared, atol=2e-5)


# ------------------------------------------- chunked prefill, paged decode
def _serve_by_hand(net, prompt, n_steps, slots=2, slot=1, steps=1):
    """Chunked prefill, then bursts through the engine's paged programs.
    Returns the served tokens, the rows of the token block and the counts
    read."""
    eng = InferStep(net, eos_id=NO_END)
    pages = -(-(len(prompt) + 2 * n_steps * steps + 2) // PAGE)
    state = eng.init_paged_state(slots, slots * pages, PAGE, 0)
    table = np.zeros((slots, pages), np.int32)
    table[slot] = 1 + slot * pages + np.arange(pages)
    counts = {"prefill": np.zeros(len(COUNTS), np.int64),
              "decode": np.zeros(len(COUNTS), np.int64)}
    at = 0
    while at < len(prompt):
        part = prompt[at:at + CHUNK]
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :len(part)] = part
        out, state = eng.prefill_suffix_paged(
            state, toks, [len(part)], [at], table[slot:slot + 1], [slot],
            [True], wide=True)
        out = out.asnumpy()
        counts["prefill"] += out[1:]
        at += len(part)
    served, rows = [int(out[0])], []
    active = np.arange(slots) == slot
    length = len(prompt)
    for _ in range(n_steps):
        carry = np.where(active, served[-1], 0).astype(np.int32)
        lengths = np.where(active, length, 0).astype(np.int32)
        buf, state = eng.decode_iter(state, table, carry, lengths, active,
                                     steps=steps)
        buf = buf.asnumpy()
        counts["decode"] += buf[:, 4 * steps:].ravel()[:len(COUNTS)]
        for j in range(steps):
            g0, g1, n, draft = buf[slot, 4 * j:4 * j + 4]
            rows.append((int(g0), int(g1), int(n), int(draft)))
            served += [int(g0), int(g1)][:n]
            length += int(n)
        assert (buf[~active, :4 * steps].reshape(-1, 4)[:, 2] == 0).all()
    return served, rows, dict(zip(COUNTS, counts["decode"])), \
        dict(zip(COUNTS, counts["prefill"]))


@pytest.mark.parametrize("length,steps", [(5, 1), (19, 1), (8, 3), (1, 2)])
def test_chunked_prefill_then_decode_through_the_latent_pages(
        ref, net, length, steps):
    """(a) The served stream is the reference's greedy stream of full
    forwards, and the module's drafts are what the reference's module puts
    first: the chunk program (expanded) and the decode step (absorbed) read
    one cache."""
    prompt = tokens(length, 10 + length)
    served, rows, dec, pre = _serve_by_hand(net, prompt, 4, steps=steps)
    want = ref.greedy(SEED, TINY, prompt, len(served))
    assert served == want
    # the draft of step j is the module's choice at the position before the
    # step's, from the true next token
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])
    at = len(prompt)
    for g0, g1, n, draft in rows:
        _, m = ref.forward(SEED, TINY, seq[:at + 1], want_draft=[at - 1])
        assert draft == int(jnp.argmax(m[0]))
        assert n == (2 if draft == g0 else 1)
        at += n
    assert dec["calls"] == 4 * steps and dec["row_steps"] == 4 * steps
    assert dec["mtp_drafts"] == 4 * steps
    assert dec["mtp_accepted"] == sum(n == 2 for *_, n, _ in rows)
    assert pre["calls"] == -(-length // CHUNK) and pre["row_steps"] == 0
    assert pre["latent_keys"] == sum(
        min(length, at + CHUNK) for at in range(0, length, CHUNK))
    # two positions a row a step through 2 expert layers and the module's
    assert dec["expert_layers"] == 3 * 4 * steps
    # (the module's first position lies before 0 for a prompt of one token)
    assert dec["pairs_all"] == 3 * 4 * steps * 2 * 2 - 2 * (length == 1)
    assert dec["pairs_held"] == dec["pairs_all"]        # every expert held


class _Oracle(JoyAILM):
    """A net whose draft is the model's own next token, or never is."""

    agree = True

    def _propose(self, tokens, pos, state, pools, page_tables, active):
        draft, h_prev, pool, part = super()._propose(
            tokens, pos, state, pools, page_tables, active)
        x, _, _ = self._model_step(tokens[:, None], pos, list(pools),
                                   active[:, None], page_tables)
        own = jnp.argmax(self._logits(x[:, 0]), -1).astype(jnp.int32)
        return (own if self.agree else (own + 1) % 128), h_prev, pool, part


class _Never(_Oracle):
    agree = False


@pytest.mark.parametrize("cls,rate", [(_Oracle, 1), (_Never, 0)])
def test_the_two_token_step_serves_the_models_own_stream(ref, driver, cls,
                                                         rate):
    """(f) With a draft that always equals the model's token and with one
    that never does, the served stream is the same greedy stream as with
    one position a step; the count a row says how far it moved."""
    net = build(ref, driver, cls=cls)
    prompt = tokens(11, 4)
    served, rows, dec, _ = _serve_by_hand(net, prompt, 3, steps=2)
    assert served == ref.greedy(SEED, TINY, prompt, len(served))
    assert [n for *_, n, _ in rows] == [1 + rate] * 6
    assert len(served) == 1 + 6 * (1 + rate)
    assert dec["mtp_drafts"] == 6 and dec["mtp_accepted"] == 6 * rate
    assert dec["latent_keys"] == sum(
        11 + 2 + j * (1 + rate) for j in range(6))


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
        drafts = [f.drafts for f in futs]
    finally:
        bat.stop()
    assert bat.pool.free_pages == bat.pool.num_pages
    bat.pool.check_invariants(set())
    assert eng.compile_guard.steady_state_recompiles == 0
    return out, drafts, dict(bat.stats), bat


@pytest.mark.parametrize("cls,rate", [(JoyAILM, None), (_Oracle, 1),
                                      (_Never, 0)])
def test_the_scheduler_follows_the_count_a_row(ref, driver, cls, rate):
    """(f) Lengths, pages and ``max_new_tokens`` end alike whatever the
    draft: every request gets the reference's greedy tokens, exactly as
    many as it asked for (a second token that would pass the limit is
    cut), every page comes back, and the accept rate reads 1 and 0."""
    net = build(ref, driver, cls=cls)
    prompts = [tokens(n, 20 + n) for n in (5, 23, 9, 16, 3, 38)]
    max_new = [5, 8, 2, 7, 1, 6]
    out, drafts, stats, bat = _through_batcher(net, prompts, max_new)
    for p, n, got in zip(prompts, max_new, out):
        assert got == ref.greedy(SEED, TINY, p, n)
    assert stats["tokens"] + len(prompts) == sum(max_new)
    assert stats["decode_mtp_drafts"] == stats["decode_row_steps"] > 0
    if rate is not None:
        assert stats["decode_mtp_accepted"] == \
            rate * stats["decode_mtp_drafts"]
    # a draft is recorded for the generated token it was proposed for
    for got, d in zip(out, drafts):
        if len(got) > 1:
            assert d and all(1 <= j < len(got) for j, _ in d)
            if rate == 1:
                assert all(got[j] == tok for j, tok in d)
            if rate == 0:
                assert all(got[j] != tok for j, tok in d)
        else:
            assert d is None
    assert bat.state_bytes["pages"] > 0
    assert bat.state_bytes["slot_arrays"] == \
        3 * (3 * 64 * 4 + 2 * 4 + 4)
    assert bat.state_bytes["encoder_memory"] == 0


def test_a_burst_runs_the_decode_kernel_and_serves_the_same_tokens(
        ref, net, monkeypatch, paged_kernels):
    """With the paged kernels routed to (``paged_kernels(True)``; here
    interpreted) a burst's loop runs ``%mla_latent_decode``, its own copies
    and semaphores under the loop's carry, a call a latent cache a step,
    rows coming and going beside it: the tokens are the ``jax.numpy``
    form's, which are the reference's."""
    from mxnet_tpu.ops.pallas import mla_attention as kern

    traced, real = [], kern.mla_latent_decode

    def counted(qc, qr, pool, page_table, pos):
        traced.append(pool.shape)
        return real(qc, qr, pool, page_table, pos)

    monkeypatch.setattr(kern, "mla_latent_decode", counted)
    prompts = [tokens(n, 20 + n) for n in (5, 23, 9, 16, 3, 38)]
    max_new = [5, 8, 2, 7, 1, 6]
    paged_kernels(False)
    plain, *_ = _through_batcher(net, prompts, max_new)
    assert not traced
    paged_kernels(True)
    out, _, stats, _ = _through_batcher(net, prompts, max_new)
    # the three blocks' caches and the module's, in every program traced
    assert traced and len(traced) % 4 == 0
    assert out == plain
    for p, n, got in zip(prompts, max_new, out):
        assert got == ref.greedy(SEED, TINY, p, n)
    assert stats["decode_latent_keys"] > 0


def test_sampling_other_than_greedy_serves_one_token_a_step(ref, driver):
    net = build(ref, driver, cls=_Oracle)
    eng = InferStep(net, eos_id=NO_END)
    state = eng.init_paged_state(1, 8, PAGE, 0)
    table = 1 + np.arange(8, dtype=np.int32)[None]
    toks = np.zeros((1, CHUNK), np.int32)
    toks[0, :5] = tokens(5, 1)
    out, state = eng.prefill_suffix_paged(state, toks, [5], [0], table, [0],
                                          [True], wide=True)
    buf, state = eng.decode_iter(state, table, [int(out.asnumpy()[0])], [5],
                                 [True], steps=3, method="sample", seed=3)
    block = buf.asnumpy()[0, :12].reshape(3, 4)
    assert (block[:, 2] == 1).all() and (block[:, 1] == eng._pad).all()
    assert buf.asnumpy()[0, 12:][COUNTS.index("mtp_drafts")] == 0


def test_what_is_refused_for_this_net_is_refused_by_name(net):
    eng = InferStep(net)
    assert eng.supports_paged and not eng.supports_decode
    decl = eng.slot_state
    assert decl["pools"] == ("latent_pools",) and decl["step_tokens"] == 2
    assert decl["slot_arrays"] == ("mtp_h", "mtp_tok", "mtp_pos")
    assert not decl["encoder_memory"]
    assert [n for n, _ in decl["counts"]] == list(COUNTS)
    with pytest.raises(MXNetError, match="attach_draft"):
        eng.attach_draft(net)
    with pytest.raises(MXNetError, match="hot weight swap"):
        eng.stage_params({})
    with pytest.raises(MXNetError, match="prefix cache"):
        make_batcher(eng, [16], slots=2, page_size=PAGE, prefill_chunk=CHUNK,
                     prefix_cache=True, start=False)
    with pytest.raises(MXNetError, match="v_head_dim"):
        JoyAILM(v_head_dim=64)
    with pytest.raises(MXNetError, match="experts_held"):
        JoyAILM(num_experts=8, experts_held=(6, 4))
    state = eng.init_paged_state(2, 4, PAGE, 0)
    assert len(state["latent_pools"]) == 4               # 3 layers + module
    # a cached position is one row of whole lanes: 32 + 8 numbers in 128
    assert state["latent_pools"][0].shape == (5, PAGE, 128)
    assert state["mtp_h"][0].shape == (2, 3, 64)


def test_a_latent_cached_in_float8_is_rounded_at_the_write(ref, driver):
    """The control's program: latents go through float8 on their way into
    the pool's cells, and the served logits move."""
    plain = build(ref, driver)
    low = build(ref, driver, latent_dtype="float8_e4m3fn")
    toks = tokens(12, 9)
    a = plain(nd.array(toks[None], dtype="int32")).asnumpy()
    b = low(nd.array(toks[None], dtype="int32")).asnumpy()
    assert 1e-3 < np.abs(a - b).max() < 1.0
    u = jnp.ones((1, 64))
    lat = np.asarray(low._latent("l0_", u, jnp.zeros((1,), jnp.int32)))
    assert (lat == np.asarray(jnp.asarray(lat).astype(jnp.float8_e4m3fn)
                              .astype(jnp.float32))).all()
    assert (lat[:, 40:] == 0).all()

"""Jitted inference engine: KV-cached incremental decode.

Contracts under test (ISSUE 5 tentpole):

- the causal/valid-length mask accepts ``query_len=1`` with a nonzero
  cache offset (``q_offset``) instead of assuming square (L, L) scores;
- incremental ``decode_step`` over a cached prefix matches the
  full-sequence forward logits at float32 resolution (a few ULPs — XLA
  fuses the (B, 1, ·) decode matmuls differently from the (B, T, ·)
  full-forward ones, so strict bitwise equality across the two program
  shapes is not physical; greedy trajectories ARE identical, asserted
  end-to-end) and within tolerance under ``amp='bfloat16'`` — for both
  TransformerModel and the BERT-as-encoder prefill configuration;
- ``InferStep.warmup`` over the prompt-bucket menu leaves ZERO
  steady-state recompiles across the real prefill+decode programs.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.bert import BERTEncoderForGeneration, \
    BERTModel
from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
from mxnet_tpu.gluon.nn import MultiHeadAttention
from mxnet_tpu.parallel import InferStep

# float32-resolution tolerance for incremental-vs-full logits parity
ATOL = 5e-6
PREFILL_ATOL = 2e-6  # under ten times the 3.6e-07 read (see the test)
RTOL = 1e-5


def _naive_attention(q, k, v, valid_length=None, causal=False,
                     q_offset=0, sm_scale=None):
    """Dense O(S^2) reference in f32 with absolute query positions."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    Sq, Sk = q.shape[2], k.shape[2]
    mask = jnp.ones((q.shape[0], 1, Sq, Sk), bool)
    if valid_length is not None:
        mask = mask & (jnp.arange(Sk)[None, None, None, :]
                       < valid_length[:, None, None, None])
    if causal:
        qpos = jnp.arange(Sq)[None, None, :, None] + \
            jnp.asarray(q_offset, jnp.int32).reshape((-1, 1, 1, 1))
        mask = mask & (jnp.arange(Sk)[None, None, None, :] <= qpos)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


# --------------------------------------------------------------- mask fix
class TestQOffsetMask:
    """Satellite: single-token causal queries with a cache offset."""

    def test_scalar_offset_single_query(self):
        rng = np.random.RandomState(0)
        B, H, Sk, D = 2, 3, 24, 8
        q = jnp.asarray(rng.randn(B, H, 1, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32))
        for off in (0, 5, 11, 23):
            out = mx.nd.flash_attention(q, k, v, causal=True, q_offset=off)
            ref = _naive_attention(q, k, v, causal=True, q_offset=off)
            np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"offset {off}")

    def test_per_row_offset(self):
        rng = np.random.RandomState(1)
        B, H, Sk, D = 3, 2, 16, 4
        q = jnp.asarray(rng.randn(B, H, 1, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32))
        off = jnp.asarray([2, 9, 15], jnp.int32)
        out = mx.nd.flash_attention(q, k, v, causal=True, q_offset=off)
        ref = _naive_attention(q, k, v, causal=True, q_offset=off)
        np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_offset_with_valid_length(self):
        rng = np.random.RandomState(2)
        B, H, Sk, D = 2, 2, 16, 4
        q = jnp.asarray(rng.randn(B, H, 1, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32))
        vl = jnp.asarray([6, 12], jnp.int32)
        out = mx.nd.flash_attention(q, k, v, vl, causal=True, q_offset=10)
        ref = _naive_attention(q, k, v, vl, causal=True, q_offset=10)
        np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_offset_equals_square_mask_when_zero(self):
        """q_offset=0 with Sq=Sk must reproduce the historical square
        causal mask bit-for-bit (same dense path, same where-mask)."""
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(1, 2, 12, 4).astype(np.float32))
        a = mx.nd.flash_attention(q, q, q, causal=True)
        b = mx.nd.flash_attention(q, q, q, causal=True, q_offset=0)
        np.testing.assert_array_equal(a.asnumpy(), b.asnumpy())

    def test_mha_rejects_offset_under_ring(self):
        mha = MultiHeadAttention(8, 2, ring_axis="seq")
        mha.initialize()
        x = nd.array(np.zeros((1, 4, 8), np.float32))
        with pytest.raises(MXNetError):
            mha(x, q_offset=2)


# ------------------------------------------------------- MHA incremental
class TestMHAIncremental:
    def _mha(self, causal=True):
        mha = MultiHeadAttention(16, 2, dropout=0.0, causal=causal)
        mha.initialize()
        return mha

    def test_prefill_output_is_bitwise_forward(self):
        mha = self._mha()
        x = nd.array(np.random.RandomState(0).randn(2, 9, 16)
                     .astype(np.float32))
        out_full = mha(x)
        out_pre, k, v = mha.prefill(x)
        np.testing.assert_array_equal(out_pre.asnumpy(), out_full.asnumpy())
        assert k.shape == (2, 9, 2, 8) and v.shape == (2, 9, 2, 8)

    def test_step_matches_full_forward(self):
        rng = np.random.RandomState(1)
        B, S = 2, 9
        mha = self._mha()
        x = nd.array(rng.randn(B, S, 16).astype(np.float32))
        full = mha(x).asnumpy()
        _, k, v = mha.prefill(x[:, :4])
        kc, vc = mha.init_cache(B, S)
        kc = jax.lax.dynamic_update_slice(kc, jnp.swapaxes(k, 0, 1),
                                          (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, jnp.swapaxes(v, 0, 1),
                                          (0, 0, 0, 0))
        for p in range(4, S):
            out, kc, vc = mha.step(x[:, p:p + 1], kc, vc, jnp.int32(p))
            np.testing.assert_allclose(out.asnumpy()[:, 0], full[:, p],
                                       rtol=RTOL, atol=ATOL)

    def test_step_rejects_cross_attention(self):
        cross = MultiHeadAttention(16, 2, self_attention=False)
        cross.initialize()
        x = nd.array(np.zeros((1, 1, 16), np.float32))
        kc, vc = jnp.zeros((4, 1, 2, 8)), jnp.zeros((4, 1, 2, 8))
        with pytest.raises(MXNetError):
            cross.step(x, kc, vc, jnp.int32(0))
        with pytest.raises(MXNetError):
            self._mha().project_kv(x)


# ------------------------------------------------- model decode bit-parity
def _make_transformer(V=61, units=16, layers=2, dropout=0.0, **kw):
    net = TransformerModel(src_vocab=V, tgt_vocab=V, units=units,
                           hidden_size=2 * units, num_layers=layers,
                           num_heads=2, max_length=64, dropout=dropout,
                           **kw)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    return net


@pytest.fixture(scope="module")
def tmodel():
    np.random.seed(0)
    return _make_transformer()


@pytest.fixture(scope="module")
def bert_encdec():
    """TransformerModel with a BERT memory encoder (BERT-as-encoder)."""
    np.random.seed(1)
    bert = BERTModel(vocab_size=61, units=16, hidden_size=32, num_layers=2,
                     num_heads=2, max_length=64, dropout=0.0)
    net = TransformerModel(src_vocab=61, tgt_vocab=61, units=16,
                           hidden_size=32, num_layers=2, num_heads=2,
                           max_length=64, dropout=0.0,
                           encoder=BERTEncoderForGeneration(bert))
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    return net


def _teacher_forced_parity(net, prefix_len=3, Ls=7, Lt=9, atol=ATOL):
    """Prefill a prefix, then teacher-force decode_step across the rest;
    compare every position's logits against ONE full re-forward."""
    rng = np.random.RandomState(7)
    B, V = 2, 61
    src = nd.array(rng.randint(3, V, (B, Ls)), dtype="int32")
    tgt = nd.array(rng.randint(3, V, (B, Lt)), dtype="int32")
    vl = nd.array(np.array([5, Ls]), dtype="int32")
    full = net(src, tgt, vl).asnumpy()
    logits, state = net.prefill(src, tgt[:, :prefix_len],
                                src_valid_length=vl, max_len=24)
    # two program shapes (a prefix of prefix_len against the full Lt),
    # one reduction order each: equal to float32 rounding, not to the bit.
    # Read on this JAX: 3.6e-07 absolute (3 ulp of logits of order 1) in
    # 110 of 122 elements, for both nets
    got = logits.asnumpy()
    np.testing.assert_allclose(got, full[:, prefix_len - 1], rtol=0,
                               atol=PREFILL_ATOL, err_msg="prefill")
    assert (got.argmax(-1) == full[:, prefix_len - 1].argmax(-1)).all(), \
        "greedy token flipped at the prefill"
    for p in range(prefix_len, Lt):
        tok = nd.array(tgt.asnumpy()[:, p], dtype="int32")
        logits, state = net.decode_step(tok, jnp.int32(p), state)
        got = logits.asnumpy()
        np.testing.assert_allclose(got, full[:, p], rtol=RTOL, atol=atol,
                                   err_msg=f"position {p}")
        assert (got.argmax(-1) == full[:, p].argmax(-1)).all(), \
            f"greedy token flipped at position {p}"


class TestDecodeParity:
    def test_transformer_fp32(self, tmodel):
        _teacher_forced_parity(tmodel)

    def test_bert_as_encoder_fp32(self, bert_encdec):
        _teacher_forced_parity(bert_encdec)

    def test_transformer_bf16_tolerance(self, tmodel):
        """amp='bfloat16' engine logits stay within bf16 tolerance of the
        fp32 full forward on a teacher-forced trajectory."""
        rng = np.random.RandomState(8)
        B, V, Ls, Lt = 2, 61, 7, 8
        src = rng.randint(3, V, (B, Ls)).astype(np.int32)
        vl = np.array([5, 7], np.int32)
        full32 = tmodel(nd.array(src), nd.array(
            rng.randint(3, V, (B, Lt)).astype(np.int32)),
            nd.array(vl, dtype="int32"))
        eng16 = InferStep(tmodel, amp="bfloat16", max_len=24)
        eng32 = InferStep(tmodel, max_len=24)
        t16, _ = eng16.decode_n(src, vl, max_new_tokens=6)
        t32, _ = eng32.decode_n(src, vl, max_new_tokens=6)
        assert t16.shape == t32.shape == (B, 6)
        # param cast audit: float params bf16 except pinned norm families
        from mxnet_tpu import amp as amp_mod

        pinned = amp_mod.fp32_param_names(tmodel)
        for name, v in eng16._values.items():
            if not jnp.issubdtype(v.dtype, jnp.floating):
                continue
            want = jnp.float32 if name in pinned else jnp.bfloat16
            assert v.dtype == want, (name, v.dtype)
        assert full32 is not None  # full fp32 forward stays runnable

    def test_bf16_logits_close_to_fp32(self, tmodel):
        rng = np.random.RandomState(9)
        B, V, Ls = 2, 61, 7
        src = nd.array(rng.randint(3, V, (B, Ls)), dtype="int32")
        tgt = nd.array(rng.randint(3, V, (B, 5)), dtype="int32")
        vl = nd.array(np.array([5, 7]), dtype="int32")
        full = tmodel(src, tgt, vl).asnumpy()
        # bf16-cast prefill of the same prefix: bf16-resolution tolerance
        from mxnet_tpu import amp as amp_mod

        pinned = amp_mod.fp32_param_names(tmodel)
        orig = {}
        for name, p in tmodel.collect_params().items():
            if name not in pinned and \
                    jnp.issubdtype(p._data.data.dtype, jnp.floating):
                orig[name] = p._data.data
                p._data._rebind(p._data.data.astype(jnp.bfloat16))
        try:
            logits, _ = tmodel.prefill(src, tgt, src_valid_length=vl,
                                       max_len=16)
            np.testing.assert_allclose(
                logits.asnumpy().astype(np.float32), full[:, -1],
                rtol=5e-2, atol=5e-2)
        finally:
            for name, p in tmodel.collect_params().items():
                if name in orig:
                    p._data._rebind(orig[name])


# ------------------------------------------------------------ InferStep
class TestInferStep:
    def test_greedy_decode_matches_naive_reforward(self, tmodel):
        """End-to-end: decode_n's greedy trajectory == the naive
        re-forward loop's (token-identical, per row up to its length)."""
        rng = np.random.RandomState(3)
        B, V, Ls, T = 2, 61, 7, 8
        src_np = rng.randint(3, V, (B, Ls)).astype(np.int32)
        vl_np = np.array([4, 7], np.int32)
        tgt = np.full((B, 1), 1, np.int32)
        for _ in range(T):
            logits = tmodel(nd.array(src_np), nd.array(tgt),
                            nd.array(vl_np, dtype="int32"))
            nxt = logits.asnumpy()[:, -1].argmax(-1).astype(np.int32)
            tgt = np.concatenate([tgt, nxt[:, None]], axis=1)
        naive = tgt[:, 1:]
        eng = InferStep(tmodel, max_len=24)
        toks, lengths = eng.decode_n(src_np, vl_np, max_new_tokens=T)
        toks, lengths = toks.asnumpy(), lengths.asnumpy()
        for i in range(B):
            n = int(lengths[i])
            np.testing.assert_array_equal(toks[i, :n], naive[i, :n])

    def test_eos_early_exit_and_lengths(self, tmodel):
        """Re-decoding with eos_id = the first greedily emitted token
        must stop every row at length 1 and pad the rest of the buffer."""
        rng = np.random.RandomState(4)
        src = rng.randint(3, 61, (2, 7)).astype(np.int32)
        probe = InferStep(tmodel, max_len=24)
        first = int(probe.decode_n(src, None, max_new_tokens=1)[0]
                    .asnumpy()[0, 0])
        eng = InferStep(tmodel, max_len=24, eos_id=first, pad_id=0)
        toks, lengths = eng.decode_n(src, None, max_new_tokens=6)
        toks, lengths = toks.asnumpy(), lengths.asnumpy()
        assert lengths[0] == 1
        assert toks[0, 0] == first
        assert (toks[0, 1:] == 0).all()

    def test_warmup_menu_zero_steady_recompiles(self, tmodel):
        eng = InferStep(tmodel, max_len=32)
        menu = [(2, 7), (2, 12)]
        compiled = eng.warmup(menu, max_new_tokens=5)
        assert compiled >= 2
        assert eng.compile_guard.steady
        for bs, bucket in menu:
            src = np.zeros((bs, bucket), np.int32)
            eng.decode_n(src, None, max_new_tokens=5)
        assert eng.compile_guard.steady_state_recompiles == 0

    def test_post_warmup_shape_churn_is_flagged(self, tmodel):
        eng = InferStep(tmodel, max_len=32)
        eng.warmup([(2, 7)], max_new_tokens=4)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            eng.decode_n(np.zeros((2, 9), np.int32), None,
                         max_new_tokens=4)
        assert eng.compile_guard.steady_state_recompiles == 1
        assert any("recompile" in str(x.message) for x in w)

    def test_sampling_deterministic_and_in_topk(self, tmodel):
        src = np.random.RandomState(5).randint(3, 61, (2, 7)) \
            .astype(np.int32)
        eng = InferStep(tmodel, max_len=24)
        a, _ = eng.decode_n(src, None, max_new_tokens=5, method="top_k",
                            top_k=4, temperature=0.7, seed=11)
        b, _ = eng.decode_n(src, None, max_new_tokens=5, method="top_k",
                            top_k=4, temperature=0.7, seed=11)
        np.testing.assert_array_equal(a.asnumpy(), b.asnumpy())
        c, _ = eng.decode_n(src, None, max_new_tokens=5, method="sample",
                            temperature=1.3, seed=1)
        assert c.shape == (2, 5)

    def test_cache_capacity_guard(self, tmodel):
        eng = InferStep(tmodel, max_len=8)
        with pytest.raises(MXNetError):
            eng.decode_n(np.zeros((1, 4), np.int32), None,
                         max_new_tokens=20)

    def test_decode_requires_protocol(self):
        bert = BERTModel(vocab_size=31, units=16, hidden_size=32,
                         num_layers=1, num_heads=2, max_length=32,
                         dropout=0.0)
        bert.initialize()
        bert._probe_shapes(nd.zeros((2, 8), dtype="int32"))
        eng = InferStep(bert)
        with pytest.raises(MXNetError):
            eng.decode_n(np.zeros((1, 4), np.int32), None)

    def test_forward_engine_bert_prefill(self):
        """Generic jitted forward: BERT bucket-padded prefill through the
        engine matches the eager net on the valid region, and the warmed
        menu holds zero steady recompiles."""
        np.random.seed(6)
        bert = BERTModel(vocab_size=31, units=16, hidden_size=32,
                         num_layers=2, num_heads=2, max_length=32,
                         dropout=0.0)
        bert.initialize()
        bert._probe_shapes(nd.zeros((2, 8), dtype="int32"))
        eng = InferStep(bert)
        sigs = [(((2, key), "int32"), ((2, key), "int32"), ((2,), "int32"))
                for key in (8, 12)]
        eng.warmup(sigs)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 31, (2, 8)).astype(np.int32)
        types = np.zeros_like(ids)
        vl = np.array([5, 8], np.int32)
        seq_e, pooled_e = eng(ids, types, vl)
        seq_d, pooled_d = bert(nd.array(ids), nd.array(types),
                               nd.array(vl, dtype="int32"))
        np.testing.assert_allclose(seq_e.asnumpy(), seq_d.asnumpy(),
                                   rtol=RTOL, atol=ATOL)
        # bucket-pad to 12: valid region must not move
        ids12 = np.zeros((2, 12), np.int32)
        ids12[:, :8] = ids
        seq12, _ = eng(ids12, np.zeros_like(ids12), vl)
        np.testing.assert_allclose(seq12.asnumpy()[0, :5],
                                   seq_e.asnumpy()[0, :5],
                                   rtol=2e-4, atol=2e-4)
        assert eng.compile_guard.steady_state_recompiles == 0

    def test_model_generate_api(self, tmodel):
        src = np.random.RandomState(2).randint(3, 61, (2, 7)) \
            .astype(np.int32)
        toks, lengths = tmodel.generate(src, max_new_tokens=4, max_len=24)
        assert toks.shape == (2, 4)
        assert lengths.shape == (2,)
        # engine is cached per config
        assert len(tmodel._infer_steps) == 1
        tmodel.generate(src, max_new_tokens=3, max_len=24)
        assert len(tmodel._infer_steps) == 1

    def test_estimator_predict(self, tmodel):
        from mxnet_tpu.gluon.contrib.estimator import Estimator
        from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

        est = Estimator(tmodel, SoftmaxCrossEntropyLoss())
        rng = np.random.RandomState(3)
        batches = [(nd.array(rng.randint(3, 61, (2, 7)), dtype="int32"),
                    nd.array(rng.randint(3, 61, (2, 5)), dtype="int32"))
                   for _ in range(2)]
        outs = est.predict(batches)
        assert len(outs) == 2
        assert outs[0].shape == (2, 5, 61)
        # with an engine: same results through the jitted forward
        eng = InferStep(tmodel)
        outs_e = est.predict(batches, engine=eng)
        np.testing.assert_allclose(outs_e[0].asnumpy(),
                                   outs[0].asnumpy(), rtol=RTOL, atol=ATOL)

    def test_infer_report_fields(self, tmodel):
        """mx.telemetry.report() carries the infer/ family (timed path)."""
        mx.telemetry.reset()
        mx.telemetry.enable()
        try:
            eng = InferStep(tmodel, max_len=24)
            src = np.random.RandomState(1).randint(3, 61, (2, 7)) \
                .astype(np.int32)
            eng.generate(src, max_new_tokens=4)
            rep = mx.telemetry.report()
            assert rep["infer_tokens"] > 0
            assert rep["infer_prefill_ms_p50"] is not None
            assert rep["infer_decode_ms_per_token_p50"] is not None
            assert rep["infer_tokens_per_sec"] is not None
        finally:
            mx.telemetry.reset()


# -------------------------------------------------- speculative decoding
class TestSpeculativeDecode:
    """ISSUE 14: draft-proposes / target-verifies greedy speculation.
    The acceptance rule (draft token j lands iff it equals the target
    argmax at its position) makes the emitted stream the target's greedy
    output BIT-identically for ANY draft — these tests pin that down for
    the degenerate (k=0), oracle (full acceptance), and garbage
    (full rejection) drafts, plus the swap plane's pair coherence."""

    def _prompts(self, seed=11, B=3, Ls=8):
        rng = np.random.RandomState(seed)
        src = rng.randint(3, 61, (B, Ls)).astype(np.int32)
        vl = np.array([5, 7, 8], np.int32)
        return src, vl

    def _ref(self, tmodel, src, vl, T):
        eng = InferStep(tmodel, max_len=32)
        toks, lens = eng.decode_n(src, vl, max_new_tokens=T)
        return toks.asnumpy(), lens.asnumpy()

    def _oracle_draft(self, tmodel):
        np.random.seed(0)
        draft = _make_transformer()
        tp = {n.split("_", 1)[1]: p
              for n, p in tmodel.collect_params().items()}
        for name, p in draft.collect_params().items():
            p.set_data(nd.NDArray(tp[name.split("_", 1)[1]]._data.data))
        return draft

    def test_k0_bit_identical_to_decode_n(self, tmodel):
        src, vl = self._prompts()
        T = 6
        toks_d, lens_d = self._ref(tmodel, src, vl, T)
        eng = InferStep(tmodel, max_len=32)
        eng.attach_draft(self._oracle_draft(tmodel))
        toks, lens = eng.decode_spec_n(src, vl, max_new_tokens=T, k=0,
                                       page_size=4)
        np.testing.assert_array_equal(lens.asnumpy(), lens_d)
        np.testing.assert_array_equal(toks.asnumpy(), toks_d)
        eng.compile_guard.mark_steady()
        eng.decode_spec_n(src, vl, max_new_tokens=T, k=0, page_size=4)
        assert eng.compile_guard.steady_state_recompiles == 0

    @pytest.mark.parametrize("wide", [False, True])
    def test_oracle_draft_bit_identical(self, tmodel, wide):
        src, vl = self._prompts()
        T = 6
        toks_d, lens_d = self._ref(tmodel, src, vl, T)
        eng = InferStep(tmodel, max_len=32)
        eng.attach_draft(self._oracle_draft(tmodel))
        toks, lens = eng.decode_spec_n(src, vl, max_new_tokens=T, k=3,
                                       wide=wide, page_size=4)
        np.testing.assert_array_equal(lens.asnumpy(), lens_d)
        np.testing.assert_array_equal(toks.asnumpy(), toks_d)
        eng.compile_guard.mark_steady()
        eng.decode_spec_n(src, vl, max_new_tokens=T, k=3, wide=wide,
                          page_size=4)
        assert eng.compile_guard.steady_state_recompiles == 0

    def test_garbage_draft_full_rejection_still_exact(self, tmodel):
        """A draft with unrelated weights rejects (almost) every
        proposal; the output must STILL be the target's greedy stream —
        acceptance only sets the per-round burst length."""
        np.random.seed(9)
        garbage = _make_transformer()
        src, vl = self._prompts()
        T = 6
        toks_d, lens_d = self._ref(tmodel, src, vl, T)
        eng = InferStep(tmodel, max_len=32)
        eng.attach_draft(garbage)
        for wide in (False, True):
            toks, lens = eng.decode_spec_n(src, vl, max_new_tokens=T,
                                           k=3, wide=wide, page_size=4)
            np.testing.assert_array_equal(lens.asnumpy(), lens_d)
            np.testing.assert_array_equal(toks.asnumpy(), toks_d)

    def test_spec_pair_swap_coherence(self, tmodel):
        """swap_params flips (target, draft, version) as ONE tuple:
        draft/ checkpoint keys land on the draft engine, the pair
        version tracks weights_version, and the pre-swap snapshot keeps
        serving the OLD pair."""
        eng = InferStep(tmodel, max_len=32)
        draft = self._oracle_draft(tmodel)
        eng.attach_draft(draft)
        pair0 = eng.spec_pair()
        assert pair0[2] == eng.weights_version
        arrays = {n: np.asarray(p._data.data)
                  for n, p in tmodel.collect_params().items()}
        np.random.seed(13)
        other = _make_transformer()
        # draft/ keys use the DRAFT engine's own param names; map the
        # donor net's params over by instance-prefix-stripped name
        donor = {n.split("_", 1)[1]: np.asarray(p._data.data)
                 for n, p in other.collect_params().items()}
        for n in eng.draft._values:
            arrays["draft/" + n] = donor[n.split("_", 1)[1]]
        ver = eng.swap_params(arrays)
        pair1 = eng.spec_pair()
        assert pair1[2] == ver == eng.weights_version
        assert pair1 is not pair0 and pair0[2] != ver
        # draft values actually flipped to the staged draft/ arrays
        name = next(iter(eng.draft._values))
        np.testing.assert_array_equal(
            np.asarray(pair1[1][name]), arrays["draft/" + name])
        # the old snapshot still holds the old values (in-flight safety)
        assert pair0[1] is not pair1[1]

    def test_spec_requires_attach_draft(self, tmodel):
        eng = InferStep(tmodel, max_len=32)
        assert not eng.has_draft
        with pytest.raises(MXNetError, match="attach_draft"):
            eng.spec_pair()


# ---------------------------------------- a paged dispatch is one enqueue
# ISSUE 28: host operands reach the compiled program as they are (numpy,
# uploaded by the one call), the key is made inside it from an int32 seed.
def _key_bits(key):
    return np.asarray(jax.random.key_data(key))


class TestSeedOperand:
    @pytest.mark.parametrize("seed", [
        0, 1, 7, 123456, 2**31 - 1, -1, -2**31,          # an int32 holds
        2**31, 2**32 + 5, 2**40 + 7, -2**33 - 3])         # folded
    def test_in_program_key_equals_eager_key(self, seed):
        operand = InferStep._seed_operand(seed)
        assert type(operand) is np.int32
        inside = jax.jit(lambda s: jax.random.key_data(
            jax.random.PRNGKey(s)))(operand)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")     # the eager fold may warn
            eager = jax.random.PRNGKey(seed)
        np.testing.assert_array_equal(np.asarray(inside), _key_bits(eager))

    def test_none_is_zero_and_a_seed_no_int64_holds_is_refused(self):
        assert InferStep._seed_operand(None) == 0
        with pytest.raises(OverflowError):
            InferStep._seed_operand(2**64)
        with pytest.raises(OverflowError):
            jax.random.PRNGKey(2**64)           # as the eager form did

    def test_operand_avals_are_what_the_eager_forms_gave(self):
        from jax.api_util import shaped_abstractify as aval

        assert aval(InferStep._seed_operand(3)) == aval(jnp.int32(3))
        t, = InferStep._operands(np.float32, 0.7)
        assert aval(t) == aval(jnp.float32(0.7))
        assert not aval(t).weak_type
        x, = InferStep._operands(np.int32, [[1, 2], [3, 4]])
        assert aval(x) == aval(jnp.asarray([[1, 2], [3, 4]], jnp.int32))
        b, = InferStep._operands(np.bool_, np.ones((3,), bool))
        assert aval(b) == aval(jnp.asarray(np.ones((3,), bool), jnp.bool_))


def _aligned(arr, boundary=64):
    """A copy of ``arr`` whose memory starts on a ``boundary``: what the
    CPU client takes as a device buffer without copying."""
    raw = np.empty(arr.nbytes + boundary, np.uint8)
    at = -raw.ctypes.data % boundary
    out = raw[at:at + arr.nbytes].view(arr.dtype).reshape(arr.shape)
    out[...] = arr
    return out


class TestOneEnqueueDispatch:
    SLOTS, PAGE, PAGES, MEM = 4, 4, 3, 8

    def _primed(self, tmodel, **sampling):
        """An engine, its paged state after one admission prefill of
        every slot, and that dispatch's host operands and first tokens."""
        eng = InferStep(tmodel, max_len=24)
        n = self.SLOTS
        state = eng.init_paged_state(n, n * self.PAGES, self.PAGE, self.MEM)
        rng = np.random.RandomState(3)
        ops = {
            "src": rng.randint(3, 61, (n, self.MEM)).astype(np.int32),
            "vl": np.array([8, 5, 7, 3], np.int32),
            "slot_ids": np.arange(n, dtype=np.int32),
            "table": (1 + np.arange(n * self.PAGES, dtype=np.int32)
                      ).reshape(n, self.PAGES),
            "active": np.ones((n,), bool)}
        tok0, state = eng.prefill_paged(
            state, ops["src"], ops["vl"], ops["slot_ids"],
            ops["table"][:, 0], ops["active"], **sampling)
        return eng, state, ops, tok0.asnumpy()

    @pytest.mark.parametrize("seed", [7, 123456])
    @pytest.mark.parametrize("sampling", [
        dict(method="sample", temperature=1.3),
        dict(method="top_k", top_k=4, temperature=0.7)],
        ids=["sample", "top_k"])
    def test_sampled_streams_equal_the_eager_key_form(
            self, tmodel, monkeypatch, sampling, seed):
        """The same program bodies fed an EAGER key, as every dispatch
        was before: ``PRNGKey`` lets a raw key through, so the engine's
        own programs trace with the key as their operand."""
        eng, state, ops, tok0 = self._primed(tmodel, seed=seed, **sampling)
        lengths = np.ones((self.SLOTS,), np.int32)
        buf, _ = eng.decode_iter(state, ops["table"], tok0, lengths,
                                 ops["active"], steps=3, seed=seed + 1,
                                 **sampling)
        make_key = jax.random.PRNGKey

        def key_or_seed(s, **kw):
            raw = getattr(s, "dtype", None) == np.uint32 and s.ndim == 1
            return s if raw else make_key(s, **kw)

        monkeypatch.setattr(jax.random, "PRNGKey", key_or_seed)
        ref = InferStep(tmodel, max_len=24)
        cfg = (sampling["method"], sampling.get("top_k", 0))
        temp = np.float32(sampling["temperature"])
        rstate = ref.init_paged_state(self.SLOTS, self.SLOTS * self.PAGES,
                                      self.PAGE, self.MEM)
        rtok0, rstate = ref._get_paged_prefill_fn(*cfg)(
            ref._values, rstate, ops["src"], ops["vl"], ops["slot_ids"],
            ops["table"][:, 0], ops["active"], make_key(seed), temp)
        np.testing.assert_array_equal(tok0, np.asarray(rtok0))
        rbuf, _ = ref._get_decode_iter_fn(3, *cfg)(
            ref._values, rstate, ops["table"], tok0, lengths,
            ops["active"], make_key(seed + 1), temp)
        np.testing.assert_array_equal(buf.asnumpy(), np.asarray(rbuf))
        # and the key is read: another seed, another stream
        eng2, state2, _, tok0b = self._primed(tmodel, seed=seed, **sampling)
        np.testing.assert_array_equal(tok0, tok0b)
        other, _ = eng2.decode_iter(state2, ops["table"], tok0, lengths,
                                    ops["active"], steps=3, seed=seed + 2,
                                    **sampling)
        assert (other.asnumpy() != buf.asnumpy()).any()

    @pytest.mark.parametrize("entry", ["decode_iter", "prefill_paged",
                                       "prefill_suffix_paged"])
    def test_host_operands_may_be_rewritten_once_the_call_returns(
            self, tmodel, entry):
        """``pool.table`` and the staging arrays are rewritten for the
        next pass while the device may not have started this one."""
        def run(mutate):
            eng, state, ops, tok0 = self._primed(tmodel)
            n = self.SLOTS
            table, active = _aligned(ops["table"]), _aligned(ops["active"])
            toks, ones = _aligned(tok0), _aligned(np.ones((n,), np.int32))
            if entry == "decode_iter":
                host = (table, toks, ones, active)
                out, _ = eng.decode_iter(state, *host, steps=3)
            elif entry == "prefill_paged":
                src, vl = _aligned(ops["src"][:, ::-1]), _aligned(ops["vl"])
                host = (src, vl, _aligned(ops["slot_ids"]),
                        _aligned(table[:, 0]), active)
                out, _ = eng.prefill_paged(state, *host)
            else:
                replay = _aligned(np.stack([toks, toks + 1], 1))
                host = (replay, _aligned(2 * ones), ones, table,
                        _aligned(ops["slot_ids"]), active)
                out, _ = eng.prefill_suffix_paged(state, *host)
            if mutate:
                for a in host:
                    a[...] = 0
            return out.asnumpy()

        np.testing.assert_array_equal(run(mutate=True), run(mutate=False))

    def test_host_operands_are_private_copies(self):
        table = np.arange(12, dtype=np.int32).reshape(4, 3)
        for given in (table, table[:, 0], table.astype(np.int64),
                      [1, 2, 3]):
            got, = InferStep._operands(np.int32, given)
            assert type(got) is np.ndarray and got.dtype == np.int32
            assert got.flags.owndata
            assert not np.shares_memory(got, np.asarray(given))
            np.testing.assert_array_equal(got, np.asarray(given))

    @pytest.mark.parametrize("wrap", [lambda a: a, nd.NDArray],
                             ids=["jax.Array", "NDArray"])
    def test_device_operand_reaches_the_program_without_a_read_back(
            self, tmodel, monkeypatch, wrap):
        from jax._src.array import ArrayImpl

        eng, state, ops, tok0 = self._primed(tmodel)
        eng2, state2, _, _ = self._primed(tmodel)
        lengths = np.ones((self.SLOTS,), np.int32)
        want, _ = eng2.decode_iter(state2, ops["table"], tok0, lengths,
                                   ops["active"], steps=2)
        want = want.asnumpy()
        on_device = [jnp.asarray(ops["table"]), jnp.asarray(tok0),
                     jnp.asarray(lengths), jnp.asarray(ops["active"])]
        same = InferStep._operands(np.int32, *map(wrap, on_device[:3]))
        assert all(a is b for a, b in zip(same, on_device))
        # a read-back goes through ``__array__`` / ``_value`` (on the CPU
        # numpy may take the buffer instead, so the program's own
        # arguments are held against the given arrays as well)
        pulled, given = [], []
        read, value = ArrayImpl.__array__, ArrayImpl._value
        monkeypatch.setattr(
            ArrayImpl, "__array__",
            lambda self, *a, **kw: (pulled.append(self.shape),
                                    read(self, *a, **kw))[1])
        monkeypatch.setattr(
            ArrayImpl, "_value", property(
                lambda self: (pulled.append(self.shape),
                              value.fget(self))[1]))
        fn = eng._get_decode_iter_fn(2, "greedy", 0)
        monkeypatch.setitem(
            eng._paged_fns, ("decode_iter", 2, "greedy", 0),
            lambda *args: (given.extend(args[2:6]), fn(*args))[1])
        got, _ = eng.decode_iter(state, *map(wrap, on_device), steps=2)
        assert pulled == []
        assert all(a is b for a, b in zip(given, on_device))
        monkeypatch.undo()
        np.testing.assert_array_equal(got.asnumpy(), want)

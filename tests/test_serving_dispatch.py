"""A dispatch on the scheduler's pass is exactly one enqueue (ISSUE 28).

Host operands go to the compiled programs as numpy and the sampling key
is made inside them, so between two compiled calls the scheduler binds no
primitive from Python: no ``jnp.asarray`` put, no ``jnp.float32``, no
``jax.random.PRNGKey`` (four binds under the ``rbg`` default). A cached
compiled call does not pass through ``Primitive.bind``; anything eager
does. The count is taken over whole steady passes of warmed batchers,
with retirements (the root store dispatch), prefix hits (the batched
adoption dispatch and the suffix replay) and chunked admission in them.
"""

import numpy as np
import pytest
from jax._src import core as jax_core

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
from mxnet_tpu.parallel import InferStep
from mxnet_tpu.serving import make_batcher

V = 61


def _encoder_decoder():
    np.random.seed(0)
    net = TransformerModel(src_vocab=V, tgt_vocab=V, units=16,
                           hidden_size=32, num_layers=1, num_heads=2,
                           max_length=64, dropout=0.0,
                           prefix="one_enqueue_net_")
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    return net


def _decoder_only():
    from mxnet_tpu.gluon.model_zoo.keye import KeyeLM

    np.random.seed(0)
    net = KeyeLM(vocab_size=V, hidden_size=32, num_layers=1, num_heads=2,
                 num_kv_heads=1, head_dim=16, num_experts=4,
                 experts_per_tok=2, expert_width=16, index_heads=1,
                 index_head_dim=8, index_topk=4, kv_chunk=4,
                 rope_theta=1e4, mrope_section=[2, 2, 4], dtype="float32")
    net.initialize(mx.initializer.Xavier())
    net(nd.zeros((1, 8), dtype="int32"))
    return net


def _prompts(n, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, V, rng.randint(3, 9)).astype(np.int32)
            for _ in range(n)]


def _round(bat, prompts, histories=None):
    futs = [bat.submit(p, max_new_tokens=5,
                       **({} if histories is None
                          else {"prefix_ids": histories[i]}))
            for i, p in enumerate(prompts)]
    return [list(f.result(timeout=300)) for f in futs]


CASES = {
    # retirements register new roots: the store dispatch is in the pass
    "encoder_decoder": dict(net=_encoder_decoder, history=False, build=dict(
        max_prefix_tokens=0)),
    # the second turn adopts cached pages: hit adoption and suffix replay
    "encoder_decoder_prefix_hits": dict(
        net=_encoder_decoder, history=True,
        build=dict(max_prefix_tokens=16, prefix_cache=True)),
    # no encoder memory: prompts enter in chunks, no prefix cache
    "decoder_only": dict(net=_decoder_only, history=False, build=dict(
        prefill_chunk=8, prefix_cache=False)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_steady_pass_binds_no_primitive(case, monkeypatch):
    spec = CASES[case]
    eng = InferStep(spec["net"](), max_len=64)
    bat = make_batcher(eng, [8], slots=2, max_new_tokens=6, page_size=4,
                       iter_tokens=2, warmup=True, name="one-enqueue",
                       **spec["build"])
    binds = []
    bind = jax_core.Primitive.bind
    try:
        first = _prompts(5, seed=1)
        turn1 = _round(bat, first)            # ramp: slots fill and retire
        before = dict(bat.stats)
        monkeypatch.setattr(
            jax_core.Primitive, "bind",
            lambda self, *a, **kw: (binds.append(self.name),
                                    bind(self, *a, **kw))[1])
        if spec["history"]:
            _round(bat, first, [np.asarray(t, np.int32) for t in turn1])
        _round(bat, _prompts(5, seed=2))
        monkeypatch.undo()
        after = dict(bat.stats)
    finally:
        monkeypatch.undo()
        bat.stop()
    assert after["iterations"] - before["iterations"] >= 3
    assert after["retired"] - before["retired"] >= 5
    if case == "encoder_decoder":
        assert after["prefix_store_dispatches"] > \
            before["prefix_store_dispatches"]
    if spec["history"]:
        assert after["prefix_hits"] > before["prefix_hits"]
    if case == "decoder_only":
        assert after["prompt_chunks"] > before["prompt_chunks"]
    assert binds == []
    assert eng.compile_guard.steady_state_recompiles == 0
    assert bat.pool.free_pages == bat.pool.num_pages      # after stop()

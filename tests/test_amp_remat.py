"""HBM-aware compute: AMP (bf16/fp16) in TrainStep, in-graph loss
scaling with overflow skip, activation rematerialization parity, fused
multi-precision Adam, and memory-guided batch planning.

Contracts locked here:

- remat on/off/policy is a MEMORY choice, never a numerics choice:
  losses are bit-identical across every policy and the per-layer grain;
- bf16 AMP tracks the fp32 loss curve within tolerance on a tiny net;
- an fp16 overflow step is skipped ENTIRELY in-graph: params, moments,
  and the bias-correction clock are untouched, the scale halves, and
  the schedule re-grows after the configured window;
- the host LossScaler implements the documented tolerance-based skip
  accounting (grow / halve / skip sequencing);
- the fused multi-tensor Adam covers the multi-precision (fp32 master +
  fp16 weight) layout and matches the per-param reference path;
- memory_analysis/plan_batch cost hypothetical batches without running.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, nd, optimizer as opt
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.parallel import TrainStep, plan_batch


# --------------------------------------------------------------- helpers
def _tiny_transformer_step(seed=0, **step_kw):
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel

    np.random.seed(seed)
    mx.random.seed(seed)
    net = TransformerModel(src_vocab=50, tgt_vocab=50, units=16,
                           hidden_size=32, num_layers=1, num_heads=2,
                           max_length=32, dropout=0.0)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    hyb_remat = step_kw.pop("hybridize_remat", None)
    if hyb_remat:
        net.hybridize(active=False, remat=hyb_remat)

    class CE:
        def __call__(self, logits, label):
            x = logits.data.astype(jnp.float32)
            logp = jax.nn.log_softmax(x, axis=-1)
            nll = -jnp.take_along_axis(
                logp, label.data.astype(jnp.int32)[..., None], axis=-1)
            return NDArray(nll.mean())

    return TrainStep(net, CE(), opt.AdamW(learning_rate=1e-3), **step_kw)


def _tok_batch(n=4, s=10, v=50, seed=1):
    rng = np.random.RandomState(seed)
    return (nd.array(rng.randint(0, v, (n, s)), dtype="int32"),
            nd.array(rng.randint(0, v, (n, s)), dtype="int32"),
            nd.array(rng.randint(0, v, (n, s)), dtype="int32"))


def _dense_step(seed=0, **step_kw):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, flatten=False),
            nn.LayerNorm(in_channels=16),
            nn.Dense(4, flatten=False))
    net.initialize()
    net(nd.zeros((2, 8)))
    return TrainStep(net, gluon.loss.L2Loss(),
                     opt.AdamW(learning_rate=1e-2), **step_kw)


# ---------------------------------------------------------- remat parity
REMAT_POLICIES = [None, "nothing_saveable", "dots_saveable",
                  "dots_with_no_batch_dims_saveable",
                  "names:attn_out,ffn_out"]


def test_remat_policies_bit_identical_losses():
    """Every remat policy gives the same losses as every other, to the
    bit. Against the program with no remat they are equal to float32
    rounding: two programs, one fusion (and so one reduction order) each.
    Read on this JAX: 1 and 2 ulp on the first two losses, 0 on the
    third."""
    batch = _tok_batch()
    plain = remat = None
    for policy in REMAT_POLICIES:
        step = _tiny_transformer_step(remat=policy)
        losses = np.array([step(*batch).asscalar() for _ in range(3)],
                          np.float32)
        if policy is None:
            plain = losses
        elif remat is None:
            remat = losses
            np.testing.assert_array_max_ulp(losses, plain, maxulp=8)
        else:
            assert losses.tolist() == remat.tolist(), \
                f"remat={policy} diverged: {losses} vs {remat}"


def test_per_layer_remat_bit_identical_losses():
    batch = _tok_batch()
    base = _tiny_transformer_step()
    per_layer = _tiny_transformer_step(hybridize_remat="dots_saveable")
    l0 = [float(base(*batch).asscalar()) for _ in range(3)]
    l1 = [float(per_layer(*batch).asscalar()) for _ in range(3)]
    assert l0 == l1


def test_remat_policy_validation():
    from mxnet_tpu.base import MXNetError

    with pytest.raises(MXNetError):
        _tiny_transformer_step(remat="bogus_policy")


def test_hybridize_remat_arms_only_remat_units():
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel

    net = TransformerModel(src_vocab=20, tgt_vocab=20, units=8,
                           hidden_size=16, num_layers=1, num_heads=2,
                           max_length=16, dropout=0.0)
    net.hybridize(active=False, remat="dots_saveable")
    layer = net.encoder.layers._children["0"]
    assert layer._remat_policy == "dots_saveable"
    assert net.encoder._remat_policy is None  # stack is not a unit
    assert net.src_embed._remat_policy is None
    net.hybridize(active=False, remat=False)
    assert layer._remat_policy is None


# -------------------------------------------------------------- bf16 AMP
def test_bf16_amp_tracks_fp32_loss_curve():
    x = nd.array(np.random.RandomState(0).rand(8, 8).astype("float32"))
    y = nd.array(np.random.RandomState(1).rand(8, 4).astype("float32"))
    s32 = _dense_step()
    s16 = _dense_step(amp="bfloat16")
    l32 = [float(s32(x, y).asscalar()) for _ in range(20)]
    l16 = [float(s16(x, y).asscalar()) for _ in range(20)]
    assert l32[-1] < l32[0]  # both actually learn
    assert l16[-1] < l16[0]
    np.testing.assert_allclose(l16, l32, rtol=0.1, atol=5e-3)


def test_amp_masters_stay_fp32_and_norms_pinned():
    s = _dense_step(amp="bfloat16")
    # master values and optimizer state live in f32 regardless of amp
    assert all(v.dtype == jnp.float32 for v in s._train_vals.values())
    # norm params are excluded from the cast set
    ln = [n for n in s._train_vals if "layernorm" in n]
    assert ln and all(n in s._amp_fp32 for n in ln)
    dense = [n for n in s._train_vals if "dense" in n]
    assert dense and all(n not in s._amp_fp32 for n in dense)


def test_amp_and_compute_dtype_are_exclusive():
    from mxnet_tpu.base import MXNetError

    with pytest.raises(MXNetError):
        _dense_step(amp="bfloat16", compute_dtype="bfloat16")
    with pytest.raises(MXNetError):
        _dense_step(amp="int8")


def test_amp_init_sets_trainstep_default():
    try:
        amp.init("bfloat16")
        s = _dense_step()
        assert s._amp == "bfloat16"
    finally:
        amp.reset()
    s2 = _dense_step()
    assert s2._amp is None


def test_mxtpu_amp_env_default():
    os.environ["MXTPU_AMP"] = "bfloat16"
    try:
        assert amp.default_amp() == "bfloat16"
        s = _dense_step()
        assert s._amp == "bfloat16"
    finally:
        del os.environ["MXTPU_AMP"]
    assert amp.default_amp() is None


# ------------------------------------------------- fp16 in-graph scaling
def _scaled_step(**scaler_kw):
    scaler_kw.setdefault("init_scale", 2.0 ** 10)
    scaler_kw.setdefault("scale_window", 3)
    scaler_kw.setdefault("scale_factor", 2.0)
    return _dense_step(amp="float16",
                       loss_scaler=amp.LossScaler(**scaler_kw))


def test_fp16_overflow_skip_leaves_state_untouched():
    s = _scaled_step()
    y = nd.array(np.random.RandomState(1).rand(4, 4).astype("float32"))
    bad = nd.array(np.full((4, 8), 1e30, "float32"))  # inf in f16
    w0 = {n: np.asarray(v) for n, v in s._train_vals.items()}
    o0 = {n: tuple(np.asarray(x) for x in st)
          for n, st in s._opt_state.items()}
    float(s(bad, y).asscalar())
    st = s.scaler_stats()
    assert st["skipped_steps"] == 1
    assert st["loss_scale"] == 512.0  # halved from 1024
    assert int(s._t_dev) == 0  # bias-correction clock untouched
    for n, v in s._train_vals.items():
        np.testing.assert_array_equal(w0[n], np.asarray(v))
    for n, stt in s._opt_state.items():
        for a, b in zip(o0[n], stt):
            np.testing.assert_array_equal(a, np.asarray(b))


def test_fp16_scale_regrows_after_window():
    s = _scaled_step()
    x = nd.array(np.random.RandomState(0).rand(4, 8).astype("float32"))
    y = nd.array(np.random.RandomState(1).rand(4, 4).astype("float32"))
    bad = nd.array(np.full((4, 8), 1e30, "float32"))
    float(s(bad, y).asscalar())
    assert s.loss_scale == 512.0
    w_skip = {n: np.asarray(v) for n, v in s._train_vals.items()}
    for i in range(3):  # scale_window=3 clean steps
        float(s(x, y).asscalar())
    st = s.scaler_stats()
    assert st["loss_scale"] == 1024.0  # doubled back
    assert int(s._t_dev) == 3  # only clean steps advance t
    assert any((np.asarray(v) != w_skip[n]).any()
               for n, v in s._train_vals.items())


def test_fp16_scaler_state_roundtrips_through_state_dict():
    s = _scaled_step()
    x = nd.array(np.random.RandomState(0).rand(4, 8).astype("float32"))
    y = nd.array(np.random.RandomState(1).rand(4, 4).astype("float32"))
    float(s(x, y).asscalar())
    sd = s.state_dict()
    assert "scaler" in sd
    s2 = _scaled_step()
    s2.load_state_dict(sd)
    assert s2.scaler_stats() == s.scaler_stats()


# ------------------------------------------------------ LossScaler (host)
def test_loss_scaler_grows_after_window():
    ls = amp.LossScaler(init_scale=8.0, scale_factor=2.0, scale_window=4)
    for _ in range(3):
        ls.update_scale(False)
    assert ls.loss_scale == 8.0
    ls.update_scale(False)
    assert ls.loss_scale == 16.0  # 4th clean step doubles
    assert ls.stats()["unskipped_streak"] == 0


def test_loss_scaler_zero_tolerance_halves_every_overflow():
    ls = amp.LossScaler(init_scale=8.0, scale_factor=2.0, scale_window=10,
                        tolerance=0.0)
    ls.update_scale(True)
    assert ls.loss_scale == 4.0
    ls.update_scale(True)
    assert ls.loss_scale == 2.0
    assert ls.total_skipped == 2


def test_loss_scaler_tolerance_absorbs_rare_overflow():
    # one overflow in 100 steps at tolerance 5%: skip but DON'T halve
    ls = amp.LossScaler(init_scale=8.0, scale_factor=2.0,
                        scale_window=1000, tolerance=0.05)
    for _ in range(99):
        ls.update_scale(False)
    ls.update_scale(True)
    assert ls.total_skipped == 1
    assert ls.loss_scale == 8.0  # 1/100 = 1% < 5% tolerance
    # a sustained burst of overflows crosses the 5% rate and halves
    while ls.loss_scale == 8.0:
        ls.update_scale(True)
        assert ls.stats()["steps"] < 150, "tolerance never tripped"
    assert ls.loss_scale == 4.0
    # ...exactly once: the rate accounting reset at the rescale
    ls.update_scale(False)
    assert ls.loss_scale == 4.0


def test_loss_scaler_floors_at_one():
    ls = amp.LossScaler(init_scale=2.0, scale_factor=4.0, tolerance=0.0)
    ls.update_scale(True)
    assert ls.loss_scale == 1.0
    ls.update_scale(True)
    assert ls.loss_scale == 1.0


def test_loss_scaler_grow_resets_after_overflow():
    # the clean-step streak resets on overflow: no growth until a FULL
    # window of consecutive clean steps follows
    ls = amp.LossScaler(init_scale=8.0, scale_factor=2.0, scale_window=3,
                        tolerance=0.0)
    ls.update_scale(False)
    ls.update_scale(False)
    ls.update_scale(True)  # halve, streak resets
    assert ls.loss_scale == 4.0
    ls.update_scale(False)
    ls.update_scale(False)
    assert ls.loss_scale == 4.0
    ls.update_scale(False)
    assert ls.loss_scale == 8.0


# ----------------------------------------------- fused multi-precision Adam
@pytest.mark.parametrize("optimizer", ["adam", "adamw"])
def test_fused_adam_multi_precision_matches_per_param(optimizer):
    def run(eager_jit):
        os.environ["MXTPU_EAGER_JIT"] = eager_jit
        try:
            np.random.seed(0)
            mx.random.seed(0)
            net = nn.Dense(4, in_units=8)
            net.cast("float16")
            net.initialize(mx.initializer.Constant(0.5))
            cls = opt.Adam if optimizer == "adam" else opt.AdamW
            tr = gluon.Trainer(net.collect_params(),
                               cls(learning_rate=1e-2,
                                   multi_precision=True))
            x = nd.array(np.random.RandomState(0).rand(4, 8)
                         .astype("float16"))
            for _ in range(3):
                with autograd.record():
                    y = net(x)
                    loss = (y * y).mean()
                loss.backward()
                tr.step(1)
            ws = [np.asarray(p.data().data, dtype="float32")
                  for _, p in sorted(net.collect_params().items())]
            return ws, tr
        finally:
            os.environ.pop("MXTPU_EAGER_JIT", None)

    w_fused, tr = run("1")
    # the fused path must actually have engaged on the mp layout
    st = tr._updaters[0].states[0]
    assert isinstance(st, tuple) and isinstance(st[0], tuple), \
        "expected multi-precision ((m, v), master) state"
    w_ref, _ = run("0")
    for a, b in zip(w_fused, w_ref):
        np.testing.assert_array_equal(a, b)
    # weights stayed fp16 on the param (master is separate)
    assert all(p.data().dtype == np.float16 for p in tr._params)


# -------------------------------------------------- memory-guided planning
def test_memory_analysis_reports_and_scales_with_batch():
    s = _dense_step()

    def sig(bs):
        return (((bs, 8), "float32"), ((bs, 4), "float32"))

    ma4 = s.memory_analysis(sig(4))
    ma64 = s.memory_analysis(sig(64))
    for k in ("argument_bytes", "output_bytes", "temp_bytes",
              "peak_bytes_estimate"):
        assert ma4[k] >= 0
    assert ma64["peak_bytes_estimate"] > ma4["peak_bytes_estimate"]


def test_memory_analysis_requires_call_or_signature():
    from mxnet_tpu.base import MXNetError

    s = _dense_step()
    with pytest.raises(MXNetError):
        s.memory_analysis()
    x = nd.array(np.random.rand(4, 8).astype("float32"))
    y = nd.array(np.random.rand(4, 4).astype("float32"))
    s(x, y)
    assert s.memory_analysis()["peak_bytes_estimate"] > 0


def test_plan_batch_finds_largest_fitting_batch():
    s = _dense_step()

    def sig(bs):
        return (((bs, 8), "float32"), ((bs, 4), "float32"))

    budget = s.memory_analysis(sig(16))["peak_bytes_estimate"]
    b, peak = plan_batch(s, sig, budget, start=2, max_batch=256)
    assert b >= 16
    assert peak <= budget
    # and one past the answer must NOT fit
    assert s.memory_analysis(sig(b + 1))["peak_bytes_estimate"] > budget


def test_plan_batch_returns_zero_when_nothing_fits():
    s = _dense_step()

    def sig(bs):
        return (((bs, 8), "float32"), ((bs, 4), "float32"))

    b, peak = plan_batch(s, sig, budget_bytes=16, start=2)
    assert (b, peak) == (0, None)


def test_hbm_budget_env_headroom(monkeypatch):
    from mxnet_tpu.parallel import hbm_budget_bytes

    monkeypatch.setenv("MXTPU_HBM_BYTES", "1000000")
    monkeypatch.setenv("MXTPU_HBM_HEADROOM", "0.8")
    assert hbm_budget_bytes() == 800000
    monkeypatch.setenv("MXTPU_HBM_HEADROOM", "250000")  # absolute reserve
    assert hbm_budget_bytes() == 750000


def test_telemetry_reports_amp_and_remat_fields():
    from mxnet_tpu import telemetry as tel

    _tiny_transformer_step(remat="dots_saveable", amp="bfloat16")
    rep = tel.report()
    assert rep["amp_dtype"] == "bfloat16"
    assert rep["remat_policy"] == "dots_saveable"
    assert "hbm_headroom_bytes" in rep

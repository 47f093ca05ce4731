"""Neural-network ops: conv, pooling, normalization, dropout, softmax, RNN.

TPU-native analogue of ``src/operator/nn/`` [unverified] (convolution.cc,
fully_connected.cc, batch_norm.cc, layer_norm.cc, softmax.cc, pooling.cc,
dropout.cc, rnn.cc with its cuDNN fused path). Layout follows the reference's
NCHW/NCW/NCDHW default; ``jax.lax.conv_general_dilated`` takes the layout
spec directly, and XLA lays tensors out for the MXU internally, so no NHWC
rewrite is imposed on user code.

Stateful pieces of the reference are made functional:
- BatchNorm returns (out, batch_mean, batch_var); the Gluon layer owns the
  moving-stat update (the reference mutated aux states inside the op).
- Dropout draws its mask key from ``mxnet_tpu.random`` (global state eagerly,
  key-supply under jit tracing).
- RNN is a ``lax.scan`` over time with the reference's packed-parameter
  layout (i2h/h2h weights+biases per layer/direction), replacing the cuDNN
  descriptor path.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register, alias


def _tuplify(x, n):
    if x is None:
        return (1,) * n
    if isinstance(x, int):
        return (x,) * n
    t = tuple(int(v) for v in x)
    return t if len(t) == n else t * n


# ------------------------------------------------------------------ softmax
@register("softmax")
def softmax(data, length=None, axis=-1, temperature=None, dtype=None, use_length=False, **kw):
    # length may arrive as a keyword NDArray (bypasses invoke unwrapping);
    # NOT getattr(..., "data"): numpy arrays expose a .data memoryview
    if hasattr(length, "asnumpy"):
        length = length.data
    d = data / temperature if temperature else data
    if use_length and length is not None:
        steps = jnp.arange(d.shape[axis])
        shape = [1] * d.ndim
        shape[axis] = d.shape[axis]
        mask = steps.reshape(shape) < length.reshape(
            length.shape + (1,) * (d.ndim - length.ndim)
        ).astype(jnp.int32)
        d = jnp.where(mask, d, -jnp.inf)
    out = jax.nn.softmax(d, axis=axis)
    return out.astype(jnp.dtype(dtype)) if dtype else out


register("log_softmax")(
    lambda data, axis=-1, temperature=None, dtype=None, **kw: jax.nn.log_softmax(
        data / temperature if temperature else data, axis=axis
    )
)
register("softmin")(
    lambda data, axis=-1, **kw: jax.nn.softmax(-data, axis=axis)
)
register("SoftmaxActivation")(
    lambda data, mode="instance", **kw: jax.nn.softmax(
        data, axis=1 if mode == "channel" else -1
    )
)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label, **kw):
    logp = jax.nn.log_softmax(data, axis=-1)
    nll = -jnp.take_along_axis(
        logp, label.astype(jnp.int32)[..., None], axis=-1
    ).squeeze(-1)
    return jnp.sum(nll)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _softmax_output_core(data, label, grad_scale, ignore_label, use_ignore,
                         smooth_alpha):
    return jax.nn.softmax(data, axis=-1)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                        smooth_alpha):
    prob = jax.nn.softmax(data, axis=-1)
    return prob, (prob, label)


def _softmax_output_bwd(grad_scale, ignore_label, use_ignore, smooth_alpha,
                        res, g):
    # loss-layer semantics (reference src/operator/softmax_output.cc
    # [unverified]): incoming cotangent is IGNORED; d(data) is the cross-
    # entropy gradient softmax(data) - onehot(label), optionally masked
    prob, label = res
    n_class = prob.shape[-1]
    onehot = jax.nn.one_hot(label.astype(jnp.int32), n_class,
                            dtype=prob.dtype)
    if smooth_alpha > 0:
        onehot = onehot * (1 - smooth_alpha) + smooth_alpha / n_class
    grad = (prob - onehot) * grad_scale
    if use_ignore:
        mask = (label.astype(jnp.int32) != int(ignore_label)).astype(prob.dtype)
        grad = grad * mask[..., None]
    if jnp.issubdtype(label.dtype, jnp.floating):
        label_ct = jnp.zeros_like(label)
    else:
        # integer primals require a float0 cotangent under custom_vjp
        label_ct = np.zeros(label.shape, jax.dtypes.float0)
    return grad, label_ct


_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput")
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1, multi_output=False,
                   use_ignore=False, preserve_shape=False, normalization="null",
                   out_grad=False, smooth_alpha=0.0, **kw):
    """Legacy op: forward = softmax; backward = (softmax - onehot(label))."""
    return _softmax_output_core(data, label, float(grad_scale),
                                int(ignore_label), bool(use_ignore),
                                float(smooth_alpha))


register("smooth_l1")(
    lambda data, scalar=1.0, **kw: jnp.where(
        jnp.abs(data) < 1.0 / (scalar * scalar),
        0.5 * jnp.square(data * scalar * scalar) / (scalar * scalar),
        jnp.abs(data) - 0.5 / (scalar * scalar),
    )
)


# --------------------------------------------------------------- activation
@register("Activation")
def activation(data, act_type="relu", **kw):
    return {
        "relu": lambda d: jnp.maximum(d, 0),
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
        "gelu": lambda d: jax.nn.gelu(d, approximate=False),
        "gelu_tanh": lambda d: jax.nn.gelu(d, approximate=True),
        "silu": jax.nn.silu,
        "swish": jax.nn.silu,
        "mish": lambda d: d * jnp.tanh(jax.nn.softplus(d)),
    }[act_type](data)


# ----------------------------------------------------------- fully connected
@register("FullyConnected")
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True, **kw):
    """Reference: ``src/operator/nn/fully_connected.cc`` [unverified].

    weight is (num_hidden, in_units) like the reference; the matmul rides the
    MXU as data @ weight.T.
    """
    x = data.reshape(data.shape[0], -1) if flatten and data.ndim > 2 else data
    out = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# -------------------------------------------------------------- convolution
@register("Convolution")
def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, workspace=1024,
                no_bias=False, cudnn_tune=None, cudnn_off=False, layout=None, **kw):
    """Reference: ``src/operator/nn/convolution.cc`` [unverified].

    N-D conv in NC[DHW] layout over ``jax.lax.conv_general_dilated`` —
    XLA tiles it onto the MXU (the reference dispatched to cuDNN algos).
    """
    nd = data.ndim - 2
    stride = _tuplify(stride, nd)
    dilate = _tuplify(dilate, nd)
    pad = _tuplify(pad if pad is not None else 0, nd)
    if isinstance(pad, tuple) and pad == (0,) * nd and kw.get("pad_mode") == "same":
        padding = "SAME"
    else:
        padding = [(p, p) for p in pad]
    spatial = "DHW"[-nd:] if nd <= 3 else None
    # layout: channel-first (NCHW, reference default) or channel-last
    # (NHWC — the TPU-preferred layout: channels ride the lane dimension,
    # so per-channel BatchNorm reductions and conv epilogues fuse without
    # strided access). Weights stay (O, I/g, *k) in BOTH layouts so
    # checkpoints are layout-portable.
    channel_last = bool(layout) and layout[-1] == "C"
    lhs_spec = ("N" + spatial + "C") if channel_last else ("NC" + spatial)
    rhs_spec = "OI" + spatial
    out = jax.lax.conv_general_dilated(
        data,
        weight,
        window_strides=stride,
        padding=padding,
        rhs_dilation=dilate,
        dimension_numbers=(lhs_spec, rhs_spec, lhs_spec),
        feature_group_count=num_group,
    )
    if bias is not None and not no_bias:
        bshape = ((1,) * (nd + 1) + (-1,)) if channel_last \
            else ((1, -1) + (1,) * nd)
        out = out + bias.reshape(bshape)
    return out


@register("Deconvolution")
def deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, target_shape=None, num_filter=None,
                  num_group=1, no_bias=True, layout=None, **kw):
    """Transposed conv (reference: ``src/operator/nn/deconvolution.cc``)."""
    if layout is not None and layout[-1] == "C":
        raise NotImplementedError(
            "channel-last Deconvolution not supported yet; use NC* layouts"
        )
    nd = data.ndim - 2
    stride = _tuplify(stride, nd)
    dilate = _tuplify(dilate, nd)
    pad = _tuplify(pad if pad is not None else 0, nd)
    adj = _tuplify(adj if adj is not None else 0, nd)
    if num_group != 1:
        raise NotImplementedError("grouped Deconvolution not supported yet")
    spatial = "DHW"[-nd:]
    kernel = _tuplify(kernel if kernel is not None else weight.shape[2:], nd)
    # gradient-of-conv semantics (out = (i-1)*s + k' - 2p + adj, k' = dilated
    # kernel extent): pad the stride-dilated input by k'-1-p per side, adj on
    # the high side; weight layout is (in, out, *k) like the reference, read
    # as OI + transpose_kernel so XLA flips/swaps into the grad kernel.
    pads = []
    for k, d, p, a in zip(kernel, dilate, pad, adj):
        eff_k = (k - 1) * d + 1
        pads.append((eff_k - 1 - p, eff_k - 1 - p + a))
    out = jax.lax.conv_transpose(
        data,
        weight,
        strides=stride,
        padding=pads,
        rhs_dilation=dilate,
        dimension_numbers=("NC" + spatial, "OI" + spatial, "NC" + spatial),
        transpose_kernel=True,
    )
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ------------------------------------------------------------------ pooling
@register("Pooling")
def pooling(data, kernel=None, pool_type="max", global_pool=False, cudnn_off=False,
            pooling_convention="valid", stride=None, pad=None, p_value=2,
            count_include_pad=True, layout=None, **kw):
    """Reference: ``src/operator/nn/pooling.cc`` [unverified]. ``layout``
    ending in C selects channel-last (spatial dims at 1..ndim-2)."""
    nd = data.ndim - 2
    channel_last = bool(layout) and layout[-1] == "C"
    sp0 = 1 if channel_last else 2  # first spatial axis
    if global_pool:
        axes = tuple(range(sp0, sp0 + nd))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        return jnp.mean(data, axis=axes, keepdims=True)
    kernel = _tuplify(kernel, nd)
    stride = _tuplify(stride if stride is not None else 1, nd)
    pad = _tuplify(pad if pad is not None else 0, nd)
    if channel_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        base_pads = ((0, 0),) + tuple((p, p) for p in pad) + ((0, 0),)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        base_pads = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    pads = base_pads
    if pooling_convention == "full":
        # ceil-mode: extend padding on the high side so the last window fits
        extra = []
        for i in range(nd):
            size = data.shape[sp0 + i] + 2 * pad[i] - kernel[i]
            rem = size % stride[i]
            extra.append(stride[i] - rem if rem else 0)
        sp_pads = tuple((p, p + e) for p, e in zip(pad, extra))
        pads = (((0, 0),) + sp_pads + ((0, 0),)) if channel_last \
            else (((0, 0), (0, 0)) + sp_pads)
    if pool_type == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(data, init, jax.lax.max, window, strides, pads)
        return out.astype(data.dtype)
    if pool_type in ("avg", "sum"):
        summed = jax.lax.reduce_window(data, 0.0, jax.lax.add, window, strides, pads)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            denom = 1.0
            for k in kernel:
                denom *= k
            return summed / denom
        ones = jnp.ones_like(data)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, pads)
        return summed / counts
    if pool_type == "lp":
        powed = jax.lax.reduce_window(
            jnp.power(jnp.abs(data), p_value), 0.0, jax.lax.add, window, strides, pads
        )
        return jnp.power(powed, 1.0 / p_value)
    raise ValueError(f"unknown pool_type {pool_type}")


# ------------------------------------------------------------ normalization
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train(data, gamma, beta, eps, axis):
    """Training BN returning (out, batch_mean, batch_var); the stat
    outputs are moving-average side products and carry no gradient (the
    reference treated them as aux states)."""
    return _bn_train_fwd_rule(data, gamma, beta, eps, axis)[0]


def _bn_stats(data, axis):
    """Per-channel ``(mean, var)`` in float32, with the reduced axes and
    the broadcast shape. SHIFTED one-pass statistics: subtract a
    per-channel sample s (one element of the channel) before the
    sum/sumsq — XLA's multi-output fusion computes both reductions in a
    single read of x (measured round 3: 26.86 vs 29.28 ms on the
    ResNet-50 step against two passes). The raw one-pass E[x^2]-E[x]^2
    form was REVERTED in round 3: it cancels catastrophically whenever
    |mean| >> std. With the shift, E[x-s] is ~std-sized (s sits within a
    few std of the mean with overwhelming probability), so E[(x-s)^2] -
    E[x-s]^2 only cancels O(1) bits — safe in f32 for any channel
    distribution."""
    red = tuple(i for i in range(data.ndim) if i != (axis % data.ndim))
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    n = 1
    for i in red:
        n *= data.shape[i]
    idx = tuple(slice(None) if i == (axis % data.ndim) else 0
                for i in range(data.ndim))
    s = jax.lax.stop_gradient(data[idx]).astype(jnp.float32)
    xs = data.astype(jnp.float32) - s.reshape(bshape)
    s1 = jnp.sum(xs, axis=red)
    s2 = jnp.sum(jnp.square(xs), axis=red)
    mean = s + s1 / n
    var = s2 / n - jnp.square(s1 / n)
    return mean, var, red, bshape


def _bn_apply(data, mean, var, gamma, beta, eps, bshape):
    # normalize as ONE fma in the activation dtype: precompute per-channel
    # scale/shift in f32, cast once — the (B,H,W)-sized math stays bf16
    # under AMP instead of promoting to f32 through a broadcast subtract
    inv = jax.lax.rsqrt(var + eps)
    scale = inv * gamma.astype(jnp.float32)
    shift = beta.astype(jnp.float32) - mean * scale
    out = data * scale.astype(data.dtype).reshape(bshape) \
        + shift.astype(data.dtype).reshape(bshape)
    return out, inv


def _bn_train_fwd_rule(data, gamma, beta, eps, axis):
    mean, var, red, bshape = _bn_stats(data, axis)
    out, inv = _bn_apply(data, mean, var, gamma, beta, eps, bshape)
    return (out, mean, var), (data, gamma, mean, inv, beta)


def _bn_train_bwd_rule(eps, axis, res, cts):
    """Closed-form fused BN backward (the hand-derived 2-pass kernel the
    reference wrote in CUDA): one fused pass for the two reductions
    (sum dy, sum dy*xhat), one jnp pass for dx that XLA fuses with
    neighbors. XLA's autodiff of
    the forward chain emits ~6 reduction/elementwise passes instead.

    Cotangents for the mean/var outputs are ignored: they are
    moving-average aux products, not differentiable paths (reference
    semantics)."""
    data, gamma, mean, inv, beta = res
    dy = cts[0]
    red = tuple(i for i in range(data.ndim) if i != (axis % data.ndim))
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    n = 1
    for i in red:
        n *= data.shape[i]
    dyf = dy.astype(jnp.float32)
    xhat = (data.astype(jnp.float32) - mean.reshape(bshape)) \
        * inv.reshape(bshape)
    sum_dy = jnp.sum(dyf, axis=red)
    sum_dy_xhat = jnp.sum(dyf * xhat, axis=red)
    gscale = (gamma.astype(jnp.float32) * inv).reshape(bshape)
    dx = gscale * (
        dyf - (sum_dy / n).reshape(bshape)
        - xhat * (sum_dy_xhat / n).reshape(bshape)
    )
    dgamma = sum_dy_xhat.astype(gamma.dtype)
    dbeta = sum_dy.astype(beta.dtype)
    return dx.astype(data.dtype), dgamma, dbeta


_bn_train.defvjp(_bn_train_fwd_rule, _bn_train_bwd_rule)


@register("BatchNorm", num_outputs=None)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
               fix_gamma=True, use_global_stats=False, output_mean_var=False,
               axis=1, cudnn_off=False, training=False, **kw):
    """Reference: ``src/operator/nn/batch_norm.cc`` [unverified].

    Pure: returns (out, batch_mean, batch_var); the caller (gluon BatchNorm
    layer / CachedOp state threading) applies the moving-average update the
    reference performed in-place on aux states. Training gradients use the
    closed-form fused backward (``_bn_train_bwd_rule``).
    """
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    if training and not use_global_stats:
        out, mean, var = _bn_train(data, g, beta, float(eps),
                                   axis % data.ndim)
        mean = jax.lax.stop_gradient(mean)
        var = jax.lax.stop_gradient(var)
        return (out, mean.astype(moving_mean.dtype),
                var.astype(moving_var.dtype))
    mean = moving_mean.astype(jnp.float32)
    var = moving_var.astype(jnp.float32)
    out, _ = _bn_apply(data, mean, var, g, beta, eps, bshape)
    return out, mean.astype(moving_mean.dtype), var.astype(moving_var.dtype)


@register("LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False, **kw):
    """Reference: ``src/operator/nn/layer_norm.cc`` [unverified].

    Last-axis norms with lane-aligned channels go through the fused Pallas
    kernel (single pass fwd, single pass bwd — see ``pallas/layer_norm``);
    everything else uses the jnp composition XLA fuses itself."""
    from .pallas import layer_norm as _pln

    if not output_mean_var and _pln.supports(data, axis) \
            and gamma.dtype == data.dtype:
        C = data.shape[-1]
        out2d = _pln.layer_norm_fused(
            data.reshape(-1, C), gamma, beta, float(eps)
        )
        return out2d.reshape(data.shape)
    # statistics in f32, output in the ACTIVATION dtype: under AMP the
    # layer's params stay fp32 masters (amp.lists) while activations run
    # bf16/f16 — a dtype-preserving norm keeps the low-precision stream
    # low-precision instead of promoting everything downstream to f32
    # (f32 in -> f32 out is bit-identical to the old path)
    xf = data.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axis, keepdims=True)
    var = jnp.var(xf, axis=axis, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    out = (xf - mean) * inv * gamma.astype(jnp.float32).reshape(shape) \
        + beta.astype(jnp.float32).reshape(shape)
    out = out.astype(data.dtype)
    if output_mean_var:
        return (out, jnp.squeeze(mean, axis).astype(data.dtype),
                jnp.squeeze(var, axis).astype(data.dtype))
    return out


@register("GroupNorm")
def group_norm(data, gamma, beta, num_groups=1, eps=1e-5, **kw):
    n, c = data.shape[:2]
    x = data.astype(jnp.float32).reshape(
        (n, num_groups, c // num_groups) + data.shape[2:])
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    shape = (1, c) + (1,) * (data.ndim - 2)
    out = x * gamma.astype(jnp.float32).reshape(shape) \
        + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)  # dtype-preserving (see layer_norm)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3, **kw):
    xf = data.astype(jnp.float32)
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(xf, axis=red, keepdims=True)
    var = jnp.var(xf, axis=red, keepdims=True)
    shape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    out = (xf - mean) * jax.lax.rsqrt(var + eps) \
        * gamma.astype(jnp.float32).reshape(shape) \
        + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)  # dtype-preserving (see layer_norm)


@register("LRN")
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **kw):
    sq = jnp.square(data)
    pad = nsize // 2
    summed = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add, (1, nsize, 1, 1), (1, 1, 1, 1),
        ((0, 0), (pad, pad), (0, 0), (0, 0)),
    )
    return data / jnp.power(knorm + alpha * summed / nsize, beta)


# ------------------------------------------------------------------ dropout
@register("Dropout")
def dropout(data, p=0.5, mode="training", axes=None, cudnn_off=False,
            training=None, **kw):
    """Reference: ``src/operator/nn/dropout.cc`` [unverified].

    Key comes from mxnet_tpu.random (supply-scoped under jit so hybridized
    graphs stay pure while masks vary per step).
    """
    from .. import autograd
    from ..random import next_key

    if training is None:
        training = autograd.is_training()
    if not training and mode != "always":
        return data
    if p <= 0.0:
        return data
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(next_key(), keep, shape)
    return jnp.where(mask, data / keep, jnp.zeros_like(data))


# ---------------------------------------------------------------------- rnn
@register("RNN", num_outputs=None)
def rnn(data, parameters, state, state_cell=None, state_size=None, num_layers=1,
        bidirectional=False, mode="lstm", p=0.0, state_outputs=False,
        projection_size=None, sequence_length=None, use_sequence_length=False,
        training=False, **kw):
    """Fused multi-layer RNN (reference: ``src/operator/rnn.cc`` + cuDNN path
    [unverified]). data: (T, N, I); packed ``parameters`` use the reference
    layout: for each layer & direction, i2h_weight, h2h_weight then all
    biases (i2h_bias, h2h_bias).

    Implemented as ``lax.scan`` over time — XLA compiles the step once and
    keeps the matmuls on the MXU.
    """
    T, N, I = data.shape
    H = int(state_size)
    D = 2 if bidirectional else 1
    ngates = {"lstm": 4, "gru": 3, "rnn_tanh": 1, "rnn_relu": 1}[mode]

    # unpack parameter vector
    offset = 0
    layers = []

    def take(n, shape):
        nonlocal offset
        w = jax.lax.dynamic_slice_in_dim(parameters, offset, n).reshape(shape)
        offset += n
        return w

    sizes = []
    for layer in range(num_layers):
        inp = I if layer == 0 else H * D
        for d in range(D):
            sizes.append((ngates * H, inp))
            sizes.append((ngates * H, H))
    weights = []
    for shp in sizes:
        weights.append(take(shp[0] * shp[1], shp))
    biases = []
    for shp in sizes:
        biases.append(take(shp[0], (shp[0],)))

    def cell_step(mode, x, h, c, wx, wh, bx, bh):
        gates = x @ wx.T + bx + h @ wh.T + bh
        if mode == "lstm":
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c2 = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h2 = jax.nn.sigmoid(o) * jnp.tanh(c2)
            return h2, c2
        if mode == "gru":
            xr, xz, xn = jnp.split(x @ wx.T + bx, 3, axis=-1)
            hr, hz, hn = jnp.split(h @ wh.T + bh, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h2 = (1 - z) * n + z * h
            return h2, c
        act = jnp.tanh if mode == "rnn_tanh" else (lambda v: jnp.maximum(v, 0))
        h2 = act(gates)
        return h2, c

    x = data
    h_out, c_out = [], []
    wi = 0
    for layer in range(num_layers):
        outs = []
        for d in range(D):
            wx, wh = weights[wi * 2], weights[wi * 2 + 1]
            bx, bh = biases[wi * 2], biases[wi * 2 + 1]
            wi += 1
            h0 = state[layer * D + d]
            c0 = state_cell[layer * D + d] if state_cell is not None else jnp.zeros_like(h0)
            seq = x if d == 0 else jnp.flip(x, axis=0)

            def step(carry, xt, wx=wx, wh=wh, bx=bx, bh=bh):
                h, c = carry
                h2, c2 = cell_step(mode, xt, h, c, wx, wh, bx, bh)
                return (h2, c2), h2

            (hT, cT), ys = jax.lax.scan(step, (h0, c0), seq)
            if d == 1:
                ys = jnp.flip(ys, axis=0)
            outs.append(ys)
            h_out.append(hT)
            c_out.append(cT)
        x = outs[0] if D == 1 else jnp.concatenate(outs, axis=-1)

    hN = jnp.stack(h_out)
    if mode == "lstm":
        return x, hN, jnp.stack(c_out)
    return x, hN


# ---------------------------------------------------------------- upsampling
@register("UpSampling")
def upsampling(*args, scale=1, sample_type="nearest", num_args=1, **kw):
    data = args[0]
    if sample_type == "nearest":
        return jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * scale, w * scale), method="bilinear")


# ------------------------------------------------- legacy regression heads
# Reference: ``src/operator/regression_output.cc``, ``make_loss.cc``,
# ``svm_output.cc`` [unverified] — loss-layer ops whose FORWARD is the
# prediction (identity / sigmoid) and whose BACKWARD injects the loss
# gradient directly, ignoring the incoming cotangent (Module-era training
# heads; the same custom_vjp shape as SoftmaxOutput above).
def _reg_head(fwd_fn, grad_fn):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def core(data, label, grad_scale):
        return fwd_fn(data)

    def fwd(data, label, grad_scale):
        out = fwd_fn(data)
        return out, (out, label)

    def bwd(grad_scale, res, g):
        out, label = res
        n = 1
        for d in label.shape[1:]:
            n *= d
        grad = grad_fn(out, label.reshape(out.shape).astype(out.dtype)) \
            * (grad_scale / n)
        if jnp.issubdtype(label.dtype, jnp.floating):
            lct = jnp.zeros_like(label)
        else:
            # integer primals require a float0 cotangent under custom_vjp
            lct = np.zeros(label.shape, jax.dtypes.float0)
        return grad.astype(out.dtype), lct

    core.defvjp(fwd, bwd)
    return core


_lin_reg = _reg_head(lambda d: d, lambda o, l: o - l)
_mae_reg = _reg_head(lambda d: d, lambda o, l: jnp.sign(o - l))
_log_reg = _reg_head(jax.nn.sigmoid, lambda o, l: o - l)


@register("LinearRegressionOutput")
def linear_regression_output(data, label, grad_scale=1.0, **kw):
    """forward = data; backward = (data - label) * grad_scale / n."""
    return _lin_reg(data, label, float(grad_scale))


@register("MAERegressionOutput")
def mae_regression_output(data, label, grad_scale=1.0, **kw):
    return _mae_reg(data, label, float(grad_scale))


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label, grad_scale=1.0, **kw):
    """forward = sigmoid(data); backward = (sigmoid(data) - label)."""
    return _log_reg(data, label, float(grad_scale))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _make_loss_core(data, grad_scale, valid_thresh):
    return data


def _make_loss_fwd(data, grad_scale, valid_thresh):
    return data, None


def _make_loss_bwd(grad_scale, valid_thresh, res, g):
    # reference make_loss: d(data) = grad_scale (the head IS the loss);
    # normalization folds into grad_scale before the call
    return (jnp.full_like(g, grad_scale),)


_make_loss_core.defvjp(_make_loss_fwd, _make_loss_bwd)


@register("MakeLoss")
def make_loss(data, grad_scale=1.0, valid_thresh=0.0,
              normalization="null", **kw):
    """forward = data (reference: identity); backward seeds
    d(data) = grad_scale, divided by batch size under
    normalization='batch' (the scale reaches the GRADIENT, where the
    reference applied it)."""
    scale = float(grad_scale)
    if normalization == "batch":
        scale /= data.shape[0]
    return _make_loss_core(data, scale, float(valid_thresh))


def _svm_grad(out, label, margin, reg_coef, use_linear):
    n_class = out.shape[-1]
    lab = jax.nn.one_hot(label.astype(jnp.int32), n_class, dtype=out.dtype)
    # hinge: grad = -1 at label where violated, +1 at violating others
    score_at_label = jnp.sum(out * lab, axis=-1, keepdims=True)
    if use_linear:
        viol_other = ((out - score_at_label + margin) > 0) & (lab == 0)
        grad = viol_other.astype(out.dtype)
        grad = grad - lab * jnp.sum(grad, axis=-1, keepdims=True)
    else:  # squared hinge
        m = jnp.maximum(out - score_at_label + margin, 0) * (lab == 0)
        grad = 2 * m
        grad = grad - lab * jnp.sum(grad, axis=-1, keepdims=True)
    return grad * reg_coef


def _svm_head():
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
    def core(data, label, margin, reg_coef, use_linear):
        return data

    def fwd(data, label, margin, reg_coef, use_linear):
        return data, (data, label)

    def bwd(margin, reg_coef, use_linear, res, g):
        data, label = res
        grad = _svm_grad(data, label, margin, reg_coef, use_linear)
        if jnp.issubdtype(label.dtype, jnp.floating):
            lct = jnp.zeros_like(label)
        else:
            lct = np.zeros(label.shape, jax.dtypes.float0)
        return grad.astype(data.dtype), lct

    core.defvjp(fwd, bwd)
    return core


_svm_core = _svm_head()


@register("SVMOutput")
def svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
               use_linear=False, **kw):
    """forward = data (scores); backward = hinge-loss gradient
    (reference svm_output.cc)."""
    return _svm_core(data, label, float(margin),
                     float(regularization_coefficient), bool(use_linear))


@register("CTCLoss", aliases=["ctc_loss", "_contrib_CTCLoss",
                              "_contrib_ctc_loss"])
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False,
             blank_label="first", **kw):
    """Connectionist temporal classification loss (reference:
    ``src/operator/nn/ctc_loss.cc`` over warp-ctc [unverified]; here the
    optax forward-algorithm implementation drives the same contract).

    data: (T, N, C) unnormalized activations (reference layout);
    label: (N, L) int class ids, 0-padded unless label lengths given.
    Returns (N,) negative log-likelihoods. ``blank_label``: 'first'
    (blank = id 0, labels 1-based like the reference default) or 'last'
    (blank = C-1, labels 0-based).
    """
    import optax

    T, N, C = data.shape
    logits = jnp.transpose(data, (1, 0, 2)).astype(jnp.float32)  # (N,T,C)
    lab = label.astype(jnp.int32)
    if use_data_lengths and data_lengths is not None:
        dl = data_lengths.astype(jnp.int32)
        logit_pad = (jnp.arange(T)[None, :] >= dl[:, None]
                     ).astype(jnp.float32)
    else:
        logit_pad = jnp.zeros((N, T), jnp.float32)
    if use_label_lengths and label_lengths is not None:
        ll = label_lengths.astype(jnp.int32)
        label_pad = (jnp.arange(lab.shape[1])[None, :] >= ll[:, None]
                     ).astype(jnp.float32)
    else:
        # reference padding conventions without explicit lengths:
        # 0 marks padding under blank_label='first' (labels 1-based),
        # -1 under blank_label='last' (labels 0-based)
        pad_id = 0 if blank_label == "first" else -1
        label_pad = (lab == pad_id).astype(jnp.float32)
    if blank_label == "first":
        blank_id = 0
    elif blank_label == "last":
        blank_id = C - 1
    else:
        raise ValueError(f"blank_label must be 'first' or 'last', got "
                         f"{blank_label!r}")
    lab = jnp.where(label_pad > 0, 0, lab)  # padded slots: any valid id
    return optax.ctc_loss(logits, logit_pad, lab, label_pad,
                          blank_id=blank_id)

"""mx.np fidelity vs NumPy (the reference ships
``tests/python/unittest/test_numpy_op.py`` with thousands of semantic
checks [unverified]; this covers the load-bearing subset — results,
dtype promotion, reductions, indexing, linalg/fft/random sub-namespaces,
out=, and autograd integration)."""

import os

import numpy as onp
import pytest

# complex/FFT coverage stays on the CPU platform run: an accelerator
# backend is not assumed to implement complex dtypes
_skip_no_complex = pytest.mark.skipif(
    os.environ.get("MXTPU_TEST_PLATFORM", "cpu") != "cpu",
    reason="complex dtypes are only checked on the CPU platform",
)

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu import np as mnp
from mxnet_tpu.ndarray.ndarray import NDArray


def _r(*shape, seed=0):
    return onp.random.RandomState(seed).rand(*shape).astype(onp.float32)


def _check(m_out, n_out, rtol=1e-5, atol=1e-6):
    onp.testing.assert_allclose(
        m_out.asnumpy() if isinstance(m_out, NDArray) else onp.asarray(m_out),
        n_out, rtol=rtol, atol=atol,
    )


UNARY = ["exp", "log", "sqrt", "abs", "sin", "cos", "tanh", "floor", "ceil",
         "sign", "square", "negative"]
BINARY = ["add", "subtract", "multiply", "divide", "power", "maximum",
          "minimum", "hypot", "arctan2"]
REDUCE = ["sum", "mean", "max", "min", "prod", "std", "var", "argmax",
          "argmin"]


class TestElementwise:
    @pytest.mark.parametrize("name", UNARY)
    def test_unary(self, name):
        x = _r(3, 4) + 0.5
        # loosen only for TPU transcendental approximations (~7e-5 on
        # log/tanh); CPU keeps the tight bound
        if os.environ.get("MXTPU_TEST_PLATFORM", "cpu") != "cpu":
            tol = dict(rtol=1e-4, atol=1e-4)
        else:
            tol = dict(rtol=1e-5, atol=1e-6)
        _check(getattr(mnp, name)(mnp.array(x)), getattr(onp, name)(x),
               **tol)

    @pytest.mark.parametrize("name", BINARY)
    def test_binary(self, name):
        a, b = _r(3, 4) + 0.5, _r(3, 4, seed=1) + 0.5
        _check(getattr(mnp, name)(mnp.array(a), mnp.array(b)),
               getattr(onp, name)(a, b), rtol=1e-5)

    def test_broadcasting(self):
        a, b = _r(3, 1), _r(1, 4)
        _check(mnp.array(a) + mnp.array(b), a + b)
        _check(mnp.array(a) * 2.0, a * 2.0)

    def test_python_scalar_promotion(self):
        x = mnp.array(_r(2, 2))
        assert (x + 1).dtype == onp.float32  # scalar must not upcast f32


class TestReductions:
    @pytest.mark.parametrize("name", REDUCE)
    def test_full_reduce(self, name):
        x = _r(4, 5)
        _check(getattr(mnp, name)(mnp.array(x)), getattr(onp, name)(x),
               rtol=1e-5)

    @pytest.mark.parametrize("name", ["sum", "mean", "max", "argmax"])
    def test_axis_keepdims(self, name):
        x = _r(4, 5)
        kw = {} if name == "argmax" else {"keepdims": True}
        _check(getattr(mnp, name)(mnp.array(x), axis=1, **kw),
               getattr(onp, name)(x, axis=1, **kw), rtol=1e-5)

    def test_argmax_dtype_is_integer(self):
        x = mnp.array(_r(3, 4))
        assert onp.issubdtype(mnp.argmax(x).asnumpy().dtype, onp.integer)


class TestShapes:
    def test_reshape_transpose_stack_concat(self):
        x = _r(2, 6)
        _check(mnp.reshape(mnp.array(x), (3, 4)), x.reshape(3, 4))
        _check(mnp.transpose(mnp.array(x)), x.T)
        _check(mnp.stack([mnp.array(x), mnp.array(x)]), onp.stack([x, x]))
        _check(mnp.concatenate([mnp.array(x), mnp.array(x)], axis=1),
               onp.concatenate([x, x], axis=1))

    def test_split_returns_list(self):
        x = _r(6, 2)
        parts = mnp.split(mnp.array(x), 3)
        ref = onp.split(x, 3)
        assert len(parts) == 3
        for p, r in zip(parts, ref):
            _check(p, r)

    def test_where_and_clip(self):
        x = _r(3, 4) - 0.5
        _check(mnp.where(mnp.array(x) > 0, mnp.array(x), mnp.zeros((3, 4))),
               onp.where(x > 0, x, onp.zeros((3, 4), onp.float32)))
        _check(mnp.clip(mnp.array(x), 0.0, 0.3), onp.clip(x, 0.0, 0.3))


class TestCreation:
    def test_creation_defaults_f32(self):
        # MXNet numpy defaults to float32 (unlike numpy's float64)
        for arr in (mnp.zeros((2, 3)), mnp.ones((2, 3)),
                    mnp.full((2,), 7.0)):
            assert arr.dtype == onp.float32
        _check(mnp.arange(5), onp.arange(5, dtype=onp.float32))
        _check(mnp.linspace(0, 1, 5), onp.linspace(0, 1, 5,
                                                   dtype=onp.float32))
        _check(mnp.eye(3), onp.eye(3, dtype=onp.float32))


class TestLinalgFftRandom:
    def test_linalg(self):
        a = _r(3, 3) + onp.eye(3, dtype=onp.float32) * 3
        _check(mnp.linalg.norm(mnp.array(a)), onp.linalg.norm(a), rtol=1e-5)
        _check(mnp.linalg.inv(mnp.array(a)), onp.linalg.inv(a), rtol=1e-3,
               atol=1e-4)
        _check(mnp.dot(mnp.array(a), mnp.array(a)), onp.dot(a, a), rtol=1e-4)

    @_skip_no_complex
    def test_fft_roundtrip(self):
        x = _r(8)
        out = mnp.fft.ifft(mnp.fft.fft(mnp.array(x)))
        onp.testing.assert_allclose(out.asnumpy().real, x, rtol=1e-4,
                                    atol=1e-5)

    def test_random_shapes_and_determinism(self):
        mx.random.seed(3)
        a = mnp.random.uniform(0, 1, (3, 4))
        mx.random.seed(3)
        b = mnp.random.uniform(0, 1, (3, 4))
        assert a.shape == (3, 4)
        _check(a, b.asnumpy())  # same seed, same stream
        n = mnp.random.normal(0, 1, (500,))
        assert abs(float(n.asnumpy().mean())) < 0.2


class TestAutogradIntegration:
    def test_np_ops_record_on_tape(self):
        x = mx.nd.array(_r(3))
        x.attach_grad()
        with autograd.record():
            y = mnp.sum(mnp.exp(x) * 2)
        y.backward()
        onp.testing.assert_allclose(
            x.grad.asnumpy(), 2 * onp.exp(_r(3)), rtol=1e-5
        )

    def test_mixed_nd_np(self):
        x = mx.nd.ones((2, 2))
        out = mnp.add(x, mnp.ones((2, 2)))
        _check(out, onp.full((2, 2), 2.0, onp.float32))


class TestPassthroughStatics:
    def test_positional_axis_under_record(self):
        """Positional axis ints must stay static — not vjp-traced."""
        x = mx.nd.array(_r(2, 3))
        y = mx.nd.array(_r(2, 3, seed=1))
        x.attach_grad()
        with autograd.record():
            out = mnp.concatenate((x, y), 1)
            s = mnp.stack([out, out], 0)
            s.sum().backward()
        onp.testing.assert_allclose(x.grad.asnumpy(),
                                    onp.full((2, 3), 2.0), rtol=1e-6)

    def test_scalar_operand_still_works(self):
        x = mnp.array(_r(2, 2))
        _check(mnp.add(x, 2.0), _r(2, 2) + 2.0)
        _check(mnp.power(x, 2), _r(2, 2) ** 2)

"""Test utilities (reference: ``python/mxnet/test_utils.py`` [unverified]).

The reference's testing leverage (SURVEY.md §4): NumPy reference impls +
finite-difference gradient checks + cross-context consistency. All three are
here: ``check_numeric_gradient`` (central differences vs autograd),
``check_consistency`` (re-run across contexts/dtypes), dtype-aware
``assert_almost_equal``.
"""

from __future__ import annotations

import functools
import random as _pyrandom
from typing import Callable, Dict, List, Optional, Sequence

import numpy as _np

from .base import MXNetError
from .context import Context, cpu, current_context
from .ndarray.ndarray import NDArray
from .ndarray import array as nd_array
from . import autograd
from . import random as _random

__all__ = [
    "default_context",
    "default_dtype",
    "get_atol",
    "get_rtol",
    "rand_ndarray",
    "rand_shape_2d",
    "rand_shape_3d",
    "rand_shape_nd",
    "assert_almost_equal",
    "almost_equal",
    "same",
    "check_numeric_gradient",
    "check_symbolic_forward",
    "numeric_grad",
    "check_consistency",
    "with_seed",
    "assert_exception",
]

_DEFAULT_RTOL = {
    _np.dtype(_np.float16): 1e-2,
    _np.dtype(_np.float32): 1e-4,
    _np.dtype(_np.float64): 1e-5,
    _np.dtype(_np.int32): 0,
    _np.dtype(_np.int64): 0,
}
_DEFAULT_ATOL = {
    _np.dtype(_np.float16): 1e-1,
    _np.dtype(_np.float32): 1e-3,
    _np.dtype(_np.float64): 1e-20,
    _np.dtype(_np.int32): 0,
    _np.dtype(_np.int64): 0,
}


def default_context():
    return current_context()


def default_dtype():
    return _np.float32


def get_rtol(rtol=None):
    return _DEFAULT_RTOL[_np.dtype(_np.float32)] if rtol is None else rtol


def get_atol(atol=None):
    return _DEFAULT_ATOL[_np.dtype(_np.float32)] if atol is None else atol


def rand_shape_2d(dim0=10, dim1=10):
    return (_pyrandom.randint(1, dim0), _pyrandom.randint(1, dim1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (
        _pyrandom.randint(1, dim0),
        _pyrandom.randint(1, dim1),
        _pyrandom.randint(1, dim2),
    )


def rand_shape_nd(num_dim, dim=10):
    return tuple(_np.random.randint(1, dim + 1, size=num_dim))


def rand_ndarray(shape, stype="default", density=None, dtype=None,
                 distribution="uniform"):
    """Random array incl. sparse stypes (reference: test_utils.rand_ndarray;
    sparse here is the dense-backed facade with real sparsity pattern)."""
    if distribution == "uniform":
        data = _np.random.uniform(-1, 1, size=shape)
    elif distribution == "normal":
        data = _np.random.normal(size=shape)
    elif distribution == "powerlaw":
        data = _np.random.pareto(2.0, size=shape)
    else:
        raise MXNetError(f"unknown distribution {distribution}")
    data = data.astype(dtype or "float32")
    if stype == "default":
        return nd_array(data)
    density = 0.5 if density is None else float(density)
    if stype == "row_sparse":
        from .ndarray.sparse import RowSparseNDArray

        keep = _np.random.uniform(size=shape[0]) < density
        data[~keep] = 0
        return RowSparseNDArray(_jnp_asarray(data))
    if stype == "csr":
        from .ndarray.sparse import CSRNDArray

        mask = _np.random.uniform(size=shape) < density
        data = data * mask
        return CSRNDArray(_jnp_asarray(data))
    raise MXNetError(f"unknown stype {stype}")


def _jnp_asarray(a):
    import jax.numpy as jnp

    return jnp.asarray(a)


def same(a, b):
    return _np.array_equal(_as_np(a), _as_np(b))


def _as_np(x):
    if isinstance(x, NDArray):
        return x.asnumpy()
    return _np.asarray(x)


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    return _np.allclose(
        _as_np(a), _as_np(b), rtol=get_rtol(rtol), atol=get_atol(atol),
        equal_nan=equal_nan,
    )


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    a_np, b_np = _as_np(a), _as_np(b)
    rtol, atol = get_rtol(rtol), get_atol(atol)
    if not _np.allclose(a_np, b_np, rtol=rtol, atol=atol, equal_nan=equal_nan):
        idx = _np.unravel_index(
            _np.argmax(_np.abs(a_np - b_np)), a_np.shape
        ) if a_np.shape else ()
        raise AssertionError(
            f"{names[0]} and {names[1]} differ beyond rtol={rtol} atol={atol}:"
            f" max abs err {float(_np.max(_np.abs(a_np - b_np))):.3e} at {idx};"
            f" {names[0]}={a_np[idx] if a_np.shape else a_np}"
            f" {names[1]}={b_np[idx] if b_np.shape else b_np}"
        )


def numeric_grad(f: Callable, inputs: List[_np.ndarray], eps=1e-4):
    """Central finite differences of scalar-valued f wrt each input array."""
    grads = []
    for i, x in enumerate(inputs):
        g = _np.zeros_like(x, dtype=_np.float64)
        flat = x.reshape(-1)
        gflat = g.reshape(-1)
        for j in range(flat.size):
            orig = flat[j]
            flat[j] = orig + eps
            fp = float(f(*inputs))
            flat[j] = orig - eps
            fm = float(f(*inputs))
            flat[j] = orig
            gflat[j] = (fp - fm) / (2 * eps)
        grads.append(g)
    return grads


def check_numeric_gradient(fn: Callable, inputs: Sequence, eps=1e-3,
                           rtol=1e-2, atol=1e-3):
    """Compare autograd gradients of ``sum(fn(*inputs))`` against central
    finite differences (reference: ``check_numeric_gradient``).

    ``fn`` maps NDArrays -> NDArray.
    """
    nds = [
        x if isinstance(x, NDArray) else nd_array(_np.asarray(x, "float64"))
        for x in inputs
    ]
    for x in nds:
        x.attach_grad()
    with autograd.record():
        out = fn(*nds)
        loss = out.sum()
    loss.backward()
    analytic = [x.grad.asnumpy() for x in nds]

    def scalar_f(*np_inputs):
        outs = fn(*[nd_array(a) for a in np_inputs])
        return outs.sum().asscalar()

    host_inputs = [x.asnumpy().astype(_np.float64) for x in nds]
    numeric = numeric_grad(scalar_f, host_inputs, eps=eps)
    for i, (a, n) in enumerate(zip(analytic, numeric)):
        assert_almost_equal(
            a, n, rtol=rtol, atol=atol, names=(f"analytic[{i}]", f"numeric[{i}]")
        )


def check_symbolic_forward(fn: Callable, inputs: Sequence,
                           expected: Sequence[_np.ndarray], rtol=None,
                           atol=None):
    """Run fn on NDArray inputs, compare each output against numpy expected."""
    nds = [x if isinstance(x, NDArray) else nd_array(x) for x in inputs]
    outs = fn(*nds)
    if not isinstance(outs, (list, tuple)):
        outs = [outs]
    for i, (o, e) in enumerate(zip(outs, expected)):
        assert_almost_equal(o, e, rtol, atol, names=(f"out[{i}]", f"expected[{i}]"))


def check_consistency(fn: Callable, inputs: Sequence, ctx_list=None,
                      dtypes=("float32",), rtol=None, atol=None):
    """Re-run fn across contexts/dtypes and compare results (reference:
    ``check_consistency`` CPU-vs-GPU; here CPU vs TPU vs dtype variants)."""
    baseline = None
    for dtype in dtypes:
        nds = [nd_array(_as_np(x).astype(dtype)) for x in inputs]
        out = _as_np(fn(*nds))
        if baseline is None:
            baseline = out
        else:
            assert_almost_equal(
                out.astype("float32"), baseline.astype("float32"),
                rtol=_DEFAULT_RTOL.get(_np.dtype(dtype), 1e-3),
                atol=_DEFAULT_ATOL.get(_np.dtype(dtype), 1e-2),
                names=(f"dtype:{dtype}", "baseline"),
            )
    return baseline


def with_seed(seed=None):
    """Decorator giving each test a reproducible seed (reference: @with_seed)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            s = seed if seed is not None else _np.random.randint(0, 2 ** 31)
            _np.random.seed(s)
            _pyrandom.seed(s)
            _random.seed(s)
            try:
                return fn(*args, **kwargs)
            except Exception:
                print(f"test failed with seed={s}")
                raise

        return wrapper

    return deco


def assert_exception(fn, exception_type, *args, **kwargs):
    try:
        fn(*args, **kwargs)
    except exception_type:
        return
    raise AssertionError(f"{fn} did not raise {exception_type}")

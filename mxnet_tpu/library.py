"""Runtime-loaded C++ operator extensions.

TPU-native analogue of the reference's custom-op extension ABI
(``include/mxnet/lib_api.h`` + ``mx.library.load`` [unverified]). The
reference dlopens a user .so exporting registration symbols and runs its
FCompute on CPU. Here the contract is a small C ABI (below); loaded ops are
registered in the SAME operator registry as built-ins, so they appear in
``mx.nd.*`` and work with autograd:

- host compute stays C++ (called through ctypes on numpy buffers);
- under ``jit``/``hybridize`` tracing the call lowers to
  ``jax.pure_callback`` (XLA host callback) wrapped in ``jax.custom_vjp``
  when the library exports a backward — the staged-graph path of the
  reference's CustomOp, XLA-style. (A backend without host callbacks
  raises a clear error for a traced custom op.)

C ABI version 1 (elementwise contract: output shape == input[0] shape):

.. code-block:: c

    int  mxtpu_abi_version(void);              // must return 1
    int  mxtpu_op_count(void);
    const char* mxtpu_op_name(int op);
    int  mxtpu_op_num_inputs(int op);
    void mxtpu_op_compute(int op, const float** ins, const long long* lens,
                          int nin, float* out, long long out_len);
    int  mxtpu_op_has_backward(int op);        // optional, default 0
    // in-grad w.r.t. input 0 (reference CustomOp backward contract)
    void mxtpu_op_backward(int op, const float* out_grad, const float** ins,
                           const long long* lens, int nin, float* grad0,
                           long long len);

C ABI version 2 (``mxtpu_abi_version() == 2`` — the full lib_api.h
contract: per-op shape/dtype inference, multi-output, non-f32 dtypes,
scalar params as a "k=v;k=v" string):

.. code-block:: c

    // dtype codes: 0=f32 1=f64 2=i32 3=i64 4=u8 5=bool
    int  mxtpu_op_num_outputs(int op);
    // writes out_ndims/out_shapes (row-major, max_ndim per output) and
    // out_dtypes given the input signature; returns 0 on success
    int  mxtpu_op_infer(int op, const long long* in_shapes,
                        const int* in_ndims, const int* in_dtypes, int nin,
                        long long* out_shapes, int* out_ndims,
                        int* out_dtypes, int max_ndim, const char* params);
    void mxtpu_op_compute2(int op, const void** ins,
                           const long long* in_shapes, const int* in_ndims,
                           const int* in_dtypes, int nin, void** outs,
                           const long long* out_shapes, const int* out_ndims,
                           const int* out_dtypes, int nout,
                           const char* params);
    int  mxtpu_op_has_backward(int op);        // optional
    // grads for EVERY input (same signature layout; integer inputs get
    // zero-filled buffers the library may ignore)
    void mxtpu_op_backward2(int op, const void** out_grads, const void** ins,
                            const long long* in_shapes, const int* in_ndims,
                            const int* in_dtypes, int nin, void** in_grads,
                            const char* params);

Both versions load through the same ``mx.library.load``. For users
without a C++ toolchain, the pure-Python ``mx.operator.CustomOp`` path
(``mxnet_tpu/operator.py``) offers the same hook — the reference's
``custom.cc`` callback operator.

See ``examples/extensions/`` for complete libraries + build lines.
"""

from __future__ import annotations

import ctypes
from typing import List

import jax
import jax.numpy as jnp
import numpy as _np

from .base import MXNetError
from .ops import registry as _registry

__all__ = ["load"]

_LOADED: List[ctypes.CDLL] = []


def _compute_via_c(lib, op_id, nin):
    def compute(*arrays):
        ins = [
            _np.ascontiguousarray(_np.asarray(a, dtype=_np.float32))
            for a in arrays
        ]
        if len(ins) != nin:
            raise MXNetError(
                f"custom op expects {nin} inputs, got {len(ins)}"
            )
        out = _np.empty_like(ins[0])
        in_ptrs = (ctypes.POINTER(ctypes.c_float) * nin)(
            *[i.ctypes.data_as(ctypes.POINTER(ctypes.c_float)) for i in ins]
        )
        lens = (ctypes.c_longlong * nin)(*[i.size for i in ins])
        lib.mxtpu_op_compute(
            op_id, in_ptrs, lens, nin,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), out.size,
        )
        return out

    return compute


def _backward_via_c(lib, op_id, nin):
    def backward(out_grad, *arrays):
        og = _np.ascontiguousarray(_np.asarray(out_grad, _np.float32))
        ins = [
            _np.ascontiguousarray(_np.asarray(a, _np.float32))
            for a in arrays
        ]
        grad0 = _np.empty_like(ins[0])
        in_ptrs = (ctypes.POINTER(ctypes.c_float) * nin)(
            *[i.ctypes.data_as(ctypes.POINTER(ctypes.c_float)) for i in ins]
        )
        lens = (ctypes.c_longlong * nin)(*[i.size for i in ins])
        lib.mxtpu_op_backward(
            op_id, og.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            in_ptrs, lens, nin,
            grad0.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), grad0.size,
        )
        return grad0

    return backward


def _make_op_fn(name, compute, backward, nin):
    """Build the registry-level fn: numpy fast path eagerly, pure_callback
    (+ custom_vjp when backward exists) under tracing."""

    def _host_call(*arrays):
        out_aval = jax.ShapeDtypeStruct(
            jnp.shape(arrays[0]), jnp.float32
        )
        return jax.pure_callback(
            lambda *a: compute(*a), out_aval, *arrays, vmap_method="sequential"
        )

    if backward is not None:
        traced = jax.custom_vjp(_host_call)

        def fwd(*arrays):
            return _host_call(*arrays), arrays

        def bwd(res, ct):
            g_aval = jax.ShapeDtypeStruct(jnp.shape(res[0]), jnp.float32)
            g0 = jax.pure_callback(
                lambda ctg, *a: backward(ctg, *a), g_aval, ct, *res,
                vmap_method="sequential",
            )
            return (g0,) + tuple(None for _ in res[1:])

        traced.defvjp(fwd, bwd)
    else:
        traced = _host_call

    def fn(*arrays, **kw):
        if any(isinstance(a, jax.core.Tracer) for a in arrays):
            return traced(*arrays)
        # eager: straight to C++ on host buffers (reference FCompute-on-CPU)
        return jnp.asarray(compute(*[_np.asarray(a) for a in arrays]))

    fn.__name__ = name
    fn.__doc__ = f"Custom C++ operator ``{name}`` (loaded via mx.library.load)."
    return fn


# numpy dtype <-> ABI v2 dtype code
_DTYPES = [_np.float32, _np.float64, _np.int32, _np.int64, _np.uint8,
           _np.bool_]


def _dtype_code(dt) -> int:
    dt = _np.dtype(dt)
    for i, d in enumerate(_DTYPES):
        if dt == _np.dtype(d):
            return i
    raise MXNetError(f"unsupported extension dtype {dt}")


def _params_str(kw: dict) -> bytes:
    return ";".join(f"{k}={v}" for k, v in sorted(kw.items())).encode()


_MAX_NDIM = 8


def _make_v2_compute(lib, op_id, nin, nout):
    def compute(*arrays, **kw):
        ins = [_np.ascontiguousarray(a) for a in arrays]
        if len(ins) != nin:
            raise MXNetError(
                f"custom op expects {nin} inputs, got {len(ins)}"
            )
        params = _params_str(kw)
        in_shapes = (ctypes.c_longlong * (nin * _MAX_NDIM))()
        in_ndims = (ctypes.c_int * nin)()
        in_dtypes = (ctypes.c_int * nin)()
        for i, a in enumerate(ins):
            in_ndims[i] = a.ndim
            in_dtypes[i] = _dtype_code(a.dtype)
            for d, s in enumerate(a.shape):
                in_shapes[i * _MAX_NDIM + d] = s
        out_shapes = (ctypes.c_longlong * (nout * _MAX_NDIM))()
        out_ndims = (ctypes.c_int * nout)()
        out_dtypes = (ctypes.c_int * nout)()
        rc = lib.mxtpu_op_infer(op_id, in_shapes, in_ndims, in_dtypes, nin,
                                out_shapes, out_ndims, out_dtypes,
                                _MAX_NDIM, params)
        if rc != 0:
            raise MXNetError(f"custom op infer failed (rc={rc})")
        outs = []
        for o in range(nout):
            shape = tuple(out_shapes[o * _MAX_NDIM + d]
                          for d in range(out_ndims[o]))
            outs.append(_np.empty(shape, _DTYPES[out_dtypes[o]]))
        in_ptrs = (ctypes.c_void_p * nin)(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in ins])
        out_ptrs = (ctypes.c_void_p * nout)(
            *[o.ctypes.data_as(ctypes.c_void_p) for o in outs])
        lib.mxtpu_op_compute2(op_id, in_ptrs, in_shapes, in_ndims,
                              in_dtypes, nin, out_ptrs, out_shapes,
                              out_ndims, out_dtypes, nout, params)
        return outs[0] if nout == 1 else tuple(outs)

    return compute


def _make_v2_backward(lib, op_id, nin, nout):
    def backward(out_grads, ins_np, **kw):
        params = _params_str(kw)
        ins = [_np.ascontiguousarray(a) for a in ins_np]
        ogs = [_np.ascontiguousarray(g) for g in out_grads]
        in_shapes = (ctypes.c_longlong * (nin * _MAX_NDIM))()
        in_ndims = (ctypes.c_int * nin)()
        in_dtypes = (ctypes.c_int * nin)()
        for i, a in enumerate(ins):
            in_ndims[i] = a.ndim
            in_dtypes[i] = _dtype_code(a.dtype)
            for d, s in enumerate(a.shape):
                in_shapes[i * _MAX_NDIM + d] = s
        grads = [_np.zeros_like(a) for a in ins]
        og_ptrs = (ctypes.c_void_p * nout)(
            *[g.ctypes.data_as(ctypes.c_void_p) for g in ogs])
        in_ptrs = (ctypes.c_void_p * nin)(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in ins])
        g_ptrs = (ctypes.c_void_p * nin)(
            *[g.ctypes.data_as(ctypes.c_void_p) for g in grads])
        lib.mxtpu_op_backward2(op_id, og_ptrs, in_ptrs, in_shapes,
                               in_ndims, in_dtypes, nin, g_ptrs, params)
        return grads

    return backward


def _make_v2_op_fn(name, compute, backward, nin, nout):
    """Registry fn for a v2 op (self_recording): receives the caller's
    NDArrays, runs the C++ body on host numpy, and registers its own
    tape entry when the lib exports a backward — eager only (the v2
    contract's dynamic output shapes can't stage through pure_callback
    without a host-side infer pass)."""
    from . import autograd as _ag
    from .ndarray.ndarray import NDArray

    def fn(*arrays, **kw):
        if any(isinstance(a, jax.core.Tracer) for a in arrays):
            raise MXNetError(
                f"custom op {name!r} (ABI v2) supports eager execution "
                "only; call outside jit/hybridize"
            )
        in_nds = [a if isinstance(a, NDArray) else NDArray(jnp.asarray(a))
                  for a in arrays]
        np_in = [a.asnumpy() for a in in_nds]
        out = compute(*np_in, **kw)
        if backward is None or not _ag.is_recording():
            if isinstance(out, tuple):
                return tuple(jnp.asarray(o) for o in out)
            return jnp.asarray(out)

        class _Fn(_ag.Function):
            def forward(self, *ins):
                o = out
                if isinstance(o, tuple):
                    return tuple(NDArray(jnp.asarray(x)) for x in o)
                return NDArray(jnp.asarray(o))

            def backward(self, *ogs):
                gs = backward([_np.asarray(g.data) for g in ogs],
                              np_in, **kw)
                return tuple(NDArray(jnp.asarray(g)) for g in gs)

        return _Fn()(*in_nds)

    fn.__name__ = name
    fn.__doc__ = (
        f"Custom C++ operator ``{name}`` (ABI v2: shape/dtype inference, "
        f"{nout} output(s), scalar params)."
    )
    return fn


def load(path, verbose=True):
    """dlopen an extension library and register its operators
    (reference: ``mx.library.load('libmyop.so')``)."""
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        raise MXNetError(f"cannot load extension library {path!r}: {e}")
    for sym in ("mxtpu_abi_version", "mxtpu_op_count", "mxtpu_op_name",
                "mxtpu_op_num_inputs"):
        if not hasattr(lib, sym):
            raise MXNetError(
                f"{path}: missing required symbol {sym!r} (not an mxtpu "
                "extension library)"
            )
    lib.mxtpu_abi_version.restype = ctypes.c_int
    lib.mxtpu_op_count.restype = ctypes.c_int
    lib.mxtpu_op_name.restype = ctypes.c_char_p
    lib.mxtpu_op_name.argtypes = [ctypes.c_int]
    lib.mxtpu_op_num_inputs.restype = ctypes.c_int
    lib.mxtpu_op_num_inputs.argtypes = [ctypes.c_int]
    abi = lib.mxtpu_abi_version()
    if abi == 2:
        for sym in ("mxtpu_op_num_outputs", "mxtpu_op_infer",
                    "mxtpu_op_compute2"):
            if not hasattr(lib, sym):
                raise MXNetError(
                    f"{path}: ABI v2 library missing required symbol "
                    f"{sym!r}"
                )
        return _load_v2(path, lib, verbose)
    if abi != 1:
        raise MXNetError(f"{path}: unsupported mxtpu ABI version {abi}")
    if not hasattr(lib, "mxtpu_op_compute"):
        raise MXNetError(
            f"{path}: ABI v1 library missing required symbol "
            "'mxtpu_op_compute'"
        )
    lib.mxtpu_op_compute.argtypes = [
        ctypes.c_int, ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
        ctypes.POINTER(ctypes.c_float), ctypes.c_longlong,
    ]
    has_bwd_fn = getattr(lib, "mxtpu_op_has_backward", None)
    if has_bwd_fn is not None:
        has_bwd_fn.restype = ctypes.c_int
        has_bwd_fn.argtypes = [ctypes.c_int]
        lib.mxtpu_op_backward.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.c_longlong,
        ]

    names = []
    for i in range(lib.mxtpu_op_count()):
        name = lib.mxtpu_op_name(i).decode()
        nin = lib.mxtpu_op_num_inputs(i)
        compute = _compute_via_c(lib, i, nin)
        backward = None
        if has_bwd_fn is not None and has_bwd_fn(i):
            backward = _backward_via_c(lib, i, nin)
        fn = _make_op_fn(name, compute, backward, nin)
        if _registry.maybe_get(name) is not None:
            raise MXNetError(
                f"{path}: operator {name!r} already registered"
            )
        _registry.register(
            name, differentiable=backward is not None
        )(fn)
        names.append(name)
    _LOADED.append(lib)  # keep the handle alive
    # refresh generated namespaces so mx.nd.<name> appears
    import sys

    from .ndarray import register as _nd_register

    _nd_register.populate_module(sys.modules["mxnet_tpu.ndarray"], "nd")
    if verbose:
        print(f"loaded library {path}: ops {names}")
    return names


def _load_v2(path, lib, verbose):
    lib.mxtpu_op_num_outputs.restype = ctypes.c_int
    lib.mxtpu_op_num_outputs.argtypes = [ctypes.c_int]
    lib.mxtpu_op_infer.restype = ctypes.c_int
    lib.mxtpu_op_infer.argtypes = [
        ctypes.c_int, ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.c_int, ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.c_int, ctypes.c_char_p,
    ]
    lib.mxtpu_op_compute2.argtypes = [
        ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_longlong), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.c_int, ctypes.c_char_p,
    ]
    has_bwd_fn = getattr(lib, "mxtpu_op_has_backward", None)
    if has_bwd_fn is not None:
        has_bwd_fn.restype = ctypes.c_int
        has_bwd_fn.argtypes = [ctypes.c_int]
        lib.mxtpu_op_backward2.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_longlong), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.c_int,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_char_p,
        ]

    names = []
    for i in range(lib.mxtpu_op_count()):
        name = lib.mxtpu_op_name(i).decode()
        nin = lib.mxtpu_op_num_inputs(i)
        nout = lib.mxtpu_op_num_outputs(i)
        compute = _make_v2_compute(lib, i, nin, nout)
        backward = None
        if has_bwd_fn is not None and has_bwd_fn(i):
            backward = _make_v2_backward(lib, i, nin, nout)
        fn = _make_v2_op_fn(name, compute, backward, nin, nout)
        if _registry.maybe_get(name) is not None:
            raise MXNetError(f"{path}: operator {name!r} already registered")
        # differentiable=False: the fn manages its own tape entry (the
        # Function above); the invoke layer's jax.vjp routing would hand
        # it tracers the host C++ cannot consume
        _registry.register(name, num_outputs=nout, differentiable=False,
                           self_recording=True)(fn)
        names.append(name)
    _LOADED.append(lib)
    import sys

    from .ndarray import register as _nd_register

    _nd_register.populate_module(sys.modules["mxnet_tpu.ndarray"], "nd")
    if verbose:
        print(f"loaded library {path} (ABI v2): ops {names}")
    return names

"""mxnet_tpu: a TPU-native deep-learning framework with MXNet's capabilities.

Brand-new implementation targeting TPU (JAX/XLA/Pallas/pjit) with the API
surface of the reference (``ZheyuYe/incubator-mxnet``, an apache/mxnet fork —
see SURVEY.md at the repo root for the structural analysis and provenance).
Not a port: no dependency engine (XLA async dispatch), no nnvm dual IR
(``hybridize()`` stages through ``jax.jit``), no ps-lite/NCCL transport
(mesh + GSPMD collectives over ICI/DCN).

Conventional entry point::

    import mxnet_tpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu())
"""

from __future__ import annotations

__version__ = "0.1.0"

import os as _os

import jax as _jax

# TPU-native PRNG default: threefry key derivation burns measurable step
# time in vector ops on TPU (profiled ~6ms/step of a 40ms BERT step just
# for dropout masks); rbg uses the hardware RNG path and is the accepted
# accelerator default. Semantics (splittable, deterministic per seed) are
# unchanged — only the stream values differ. This is process-global and
# affects co-resident jax code; set MXNET_TPU_PRNG=threefry (or any other
# jax impl name, or "default") to opt out before import.
_prng = _os.environ.get("MXNET_TPU_PRNG", "rbg")
if _prng != "default":
    _jax.config.update("jax_default_prng_impl", _prng)

from . import base
from .base import MXNetError
from . import context
from .context import Context, cpu, cpu_pinned, gpu, tpu, current_context, num_gpus, num_tpus
from . import engine
from . import random
from . import ndarray
from . import ndarray as nd
from .ndarray.ndarray import NDArray, waitall
from . import numpy as np  # noqa: F401 - mx.np
from . import numpy_extension as npx  # noqa: F401 - mx.npx
from . import autograd
from . import imperative
from . import util
from .util import is_np_array, is_np_shape, set_np, reset_np

# Higher layers (grown incrementally; see SURVEY.md section 7 build order).
# Each import is optional only until its module lands this round.
import importlib as _importlib

for _mod, _aliases in [
    ("initializer", ()),
    ("optimizer", ()),
    ("metric", ()),
    ("symbol", ("sym",)),
    ("executor", ()),
    ("gluon", ()),
    ("module", ()),
    ("kvstore", ("kv",)),
    ("parallel", ()),
    ("serving", ()),
    ("recordio", ()),
    ("io", ()),
    ("image", ()),
    ("telemetry", ()),
    ("compile_cache", ()),
    ("profiler", ()),
    ("amp", ()),
    ("runtime", ()),
    ("test_utils", ()),
    ("checkpoint", ()),
    ("callback", ()),
    ("library", ()),
    ("operator", ()),
    ("contrib", ()),
    ("onnx", ()),
    ("debug", ()),
]:
    try:
        _m = _importlib.import_module(f".{_mod}", __name__)
    except ModuleNotFoundError as _e:
        # tolerate only "module not written yet" — real import bugs surface
        if _e.name != f"{__name__}.{_mod}":
            raise
        continue
    globals()[_mod] = _m
    for _a in _aliases:
        globals()[_a] = _m

if "initializer" in globals():
    init = initializer.init  # mx.init alias namespace
if "optimizer" in globals():
    lr_scheduler = optimizer.lr_scheduler
if "compile_cache" in globals():
    # persistent XLA compilation cache: default-on, at
    # JAX_COMPILATION_CACHE_DIR when that is set, else at the fixed
    # .mxtpu_cache/xla of the checkout; MXTPU_COMPILE_CACHE_DIR=off skips
    compile_cache.setup()

"""Imperative runtime: eager op dispatch with optional autograd recording.

TPU-native analogue of ``Imperative::Invoke`` in
``src/imperative/imperative.cc`` [unverified]. The reference's invoke path was:
infer shape/type -> allocate deferred outputs -> (maybe) record tape node ->
push FCompute closure to the dependency engine. Here the "engine push" is the
jax op call itself (XLA async dispatch), shape/dtype inference is implicit in
tracing, and recording captures a ``jax.vjp`` closure per invocation — the
tape node analogue of ``AGInfo``.

Two entry points:

- ``invoke_fn(fn, *args)``: dispatch a pure jax-level function over a mix of
  NDArray / raw operands. Used by NDArray operators and generated namespaces.
- ``invoke(op, *args, **params)``: dispatch a registered ``Operator`` by
  binding its keyword params first (reference: op ``Param`` structs).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import numpy as _np
from jax.core import Tracer as _Tracer

from . import telemetry as _tel
from .base import MXNetError
from .engine import engine
from .ndarray.ndarray import NDArray, _Pending
from .ops.registry import Operator, get as get_op

__all__ = ["invoke", "invoke_fn"]


def _wrap_outputs(outs, rec_nodes=None):
    from . import autograd

    single = not isinstance(outs, (tuple, list))
    outs_t = (outs,) if single else tuple(outs)
    wrapped = []
    for i, o in enumerate(outs_t):
        if isinstance(o, NDArray):  # fn may pass through
            wrapped.append(o)
            continue
        nd = NDArray(o)
        if rec_nodes is not None:
            autograd._mark_output(nd, rec_nodes, i)
        wrapped.append(nd)
    eng = engine()
    if not eng.is_async():
        eng.on_outputs([w.data for w in wrapped])
    return wrapped[0] if single else tuple(wrapped)


_DERIVE = object()  # sentinel: derive the jit key from fn itself


def invoke_fn(fn: Callable, *args, _jit_key=_DERIVE, **static_params):
    """Dispatch ``fn(*arrays, **static_params)`` eagerly with autograd support.

    ``args`` may contain NDArrays (tracked for autograd), jax arrays, numpy
    arrays, or python scalars. ``static_params`` are closed over (never
    differentiated). ``_jit_key`` (private): hashable key for the per-op
    jit cache, ``None`` to force the un-jitted path, or left at the
    sentinel to derive one from ``fn``'s code identity.
    """
    from . import autograd

    if static_params:
        fn = functools.partial(fn, **static_params)
    if _jit_key is _DERIVE:
        _jit_key = _fn_jit_key(fn)
    if _jit_key is not None and _EAGER_FWD_CACHE.get(_jit_key) is _FAILED:
        _jit_key = None
    if _jit_key is not None and _bulk_fwd_enabled():
        lazy = [_lazy_data(a) for a in args]
        if any(isinstance(d, _Tracer) for d in lazy):
            # inside an outer jax trace (TrainStep/hybridize staging):
            # deferring would leak tracers out of the transform — run now
            q = None
        else:
            q = _try_enqueue(_jit_key, fn, args, lazy,
                             autograd._should_record(args))
        if q is not None:
            outs, multi, node = q
            if node is not None:
                for i, o in enumerate(outs):
                    autograd._mark_output(o, node, i)
            return tuple(outs) if multi else outs[0]
    datas = [a.data if isinstance(a, NDArray) else a for a in args]
    if autograd._should_record(args):
        if _jit_key is not None:
            try:
                outs, node = autograd._record_cached(
                    _fwd_jit(_jit_key, fn), _bwd_jit(_jit_key, fn),
                    fn, args, datas, bulk_key=_jit_key)
                return _wrap_outputs(outs, rec_nodes=node)
            except Exception:
                outs, node = autograd._record(fn, args, datas)
                # the plain path succeeded: the failure was jit-specific
                # (trace-hostile fn) — blacklist. A user error would have
                # raised again just above, leaving the cache untouched.
                _EAGER_FWD_CACHE[_jit_key] = _FAILED
                return _wrap_outputs(outs, rec_nodes=node)
        outs, node = autograd._record(fn, args, datas)
        return _wrap_outputs(outs, rec_nodes=node)
    if _jit_key is not None:
        try:
            return _wrap_outputs(_fwd_jit(_jit_key, fn)(*datas))
        except Exception:
            out = _wrap_outputs(fn(*datas))  # user errors re-raise here
            _EAGER_FWD_CACHE[_jit_key] = _FAILED  # jit-specific failure
            return out
    return _wrap_outputs(fn(*datas))


# ------------------------------------------------- per-op jit cache (eager)
# The reference engineered its imperative hot loop around engine-push cost
# (SURVEY section 3.1); ours is per-op dispatch overhead: an eager op body
# of K jnp calls costs K XLA executions plus, under autograd.record, a
# fresh Python linearization through jax.vjp EVERY call (~ms of host work
# per op — profiled as THE eager bottleneck). The cure is one cached pair
# of jitted callables per (op, params) key:
#   fwd(key):  jit(fn)                      — primal, C++ cache fast path
#   bwd(key):  jit(lambda xs, ct: vjp(fn, *xs)[1](ct))
#              — recomputes the (tiny, dispatch-bound) forward inside the
#                backward instead of keeping per-call residual closures;
#                host cost collapses to a cached pjit call
# Keyed on hashable params only; ops whose bodies consume global RNG or
# produce data-dependent shapes are denied (a failed trace blacklists the
# key and falls back to the un-jitted path). MXTPU_EAGER_JIT=0 disables.
_EAGER_FWD_CACHE: dict = {}
_EAGER_BWD_CACHE: dict = {}
_EAGER_JIT_DENY = {
    "Dropout",   # draws from mx.random inside the body: jit would freeze
    "shuffle",   # the key as a compile-time constant
    "RNN",       # dropout path inside the scan body
    "Custom",    # python-callback custom ops manage their own tape/state
    "unique",    # data-dependent output shape
    "_contrib_boolean_mask",  # data-dependent output shape (host mask)
    # registry random samplers: key drawn in the body, same freeze hazard
    "_random_uniform", "_random_normal", "_random_gamma",
    "_random_exponential", "_random_poisson", "_random_randint",
    "sample_uniform", "sample_normal", "sample_gamma",
    "sample_exponential", "sample_poisson", "sample_multinomial",
}
_FAILED = object()

# ops whose BODIES read env vars at trace time: the var's current value
# must be part of the cache key, or flipping it after the first call is
# silently ignored (the trace froze the old branch — found when a
# long-context example measured flash == dense EXACTLY because both hit
# one cached executable)
_ENV_KEYED_OPS = {
    # (MXTPU_FLASH_BWD is NOT here: it binds at import; the runtime
    # switch is set_flash_backward(), which clears jax caches itself)
    "_contrib_flash_attention": ("MXTPU_ATTN_DENSE_MAX",),
    "linear_cross_entropy": ("MXTPU_CE_DENSE_MAX_BYTES",),
}


def _env_fingerprint(op_name):
    import os

    keys = _ENV_KEYED_OPS.get(op_name)
    if not keys:
        return ()
    return tuple(os.environ.get(k) for k in keys)


def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


def _jit_enabled() -> bool:
    import os

    return os.environ.get("MXTPU_EAGER_JIT", "1") != "0" \
        and engine().is_async()


# ----------------------------------------------- forward bulking (queue)
# The reference bulked contiguous eager op pushes into engine segments
# (``MXNET_GLUON_EXEC_BULK_SIZE``, ``src/imperative/imperative_utils.h``
# [unverified]); the TPU analogue: queue eligible op calls as _Pending
# NDArrays (shape/dtype known from a cached abstract eval) and flush the
# run as ONE jitted segment — one executable launch instead of one per
# op, which is the whole cost on a dispatch-latency-bound backend. Any
# value read (.data/.asnumpy/non-bulkable op) flushes, so laziness is
# invisible: the worst case is a segment of length 1.


def _bulk_size() -> int:
    from .base import env_int

    return env_int("MXNET_GLUON_EXEC_BULK_SIZE", 15)


_AVAL_CACHE: dict = {}  # (op key, input aval key) -> (out structs, multi)
_SEG_CACHE: dict = {}   # segment structural key -> jitted runner
_SEG_CAP = 512


class _BulkEntry:
    __slots__ = ("key", "fn", "datas", "chunks", "pendings", "node")

    def __init__(self, key, fn, datas, chunks, pendings, node):
        self.key = key
        self.fn = fn
        self.datas = datas      # captured operands (values / _Pending)
        self.chunks = chunks    # output _Chunk cells to write back
        self.pendings = pendings
        self.node = node        # deferred tape node (or None)


def _resolve(d):
    if type(d) is _Pending:
        return d.value
    return d


def _resolve_strict(d):
    """Resolve an operand, re-raising the producing op's failure for a
    dead pending instead of handing None downstream."""
    if type(d) is _Pending:
        if d.value is None:
            raise d.error or MXNetError(
                "bulk-queued operand was never produced (upstream op "
                "failed)")
        return d.value
    return d


import threading as _tls_threading

_FLUSH_TLS = _tls_threading.local()


def _flushing_queues() -> set:
    """ids of the _BulkQueues THIS thread is currently flushing (the
    re-entrance guard for mutual cross-queue dependencies)."""
    s = getattr(_FLUSH_TLS, "s", None)
    if s is None:
        s = _FLUSH_TLS.s = set()
    return s


def _entry_done(e) -> bool:
    """True when every output of the entry already carries a value or an
    error (resolved entry-by-entry during a re-entrant flush)."""
    return all(p.value is not None or p.error is not None
               for p in e.pendings)


def _lazy_data(a):
    """Operand capture WITHOUT forcing the queue: a live _Pending stays a
    slot reference; everything else is its concrete value."""
    if isinstance(a, NDArray):
        if a._view is None:
            d = a._chunk.data
            if type(d) is _Pending and d.value is not None:
                return d.value
            return d
        return a.data  # views force (rare on the hot path)
    return a


class _BulkQueue:
    def __init__(self):
        self.entries = []
        # queues are thread-local, but the NDArrays holding their
        # _Pending outputs are shareable: a foreign thread's flush must
        # wait out an in-flight flush, not observe its half-done state
        import threading

        self._lock = threading.RLock()

    def enqueue(self, key, fn, datas, out_structs, multi, node):
        pendings = [
            _Pending(self, s.shape, s.dtype,
                     getattr(s, "weak_type", False))
            for s in out_structs
        ]
        outs = [NDArray(p) for p in pendings]
        chunks = [o._chunk for o in outs]
        self.entries.append(
            _BulkEntry(key, fn, tuple(datas), chunks, pendings, node))
        if len(self.entries) >= _bulk_size():
            self.flush()
        return outs, multi

    def flush(self):
        # re-entrance guard: two queues holding mutually
        # dependent pendings (A reads B's, B reads A's) would otherwise
        # recurse A.flush -> B.flush -> A.flush ... to RecursionError —
        # the per-queue RLock is re-entrant, so nothing breaks the cycle.
        # The guard is PER THREAD (a set of queues this thread is already
        # flushing): a concurrent foreign-thread flush must still block
        # on the lock, not skip.
        flushing = _flushing_queues()
        if id(self) in flushing:
            return
        flushing.add(id(self))
        try:
            # resolve cross-thread dependencies BEFORE taking our own
            # lock: flushing a foreign queue while holding ours could
            # ABBA-deadlock two threads exchanging NDArrays. Our entries
            # list is only ever appended by this thread, so scanning it
            # lock-free is safe.
            for e in self.entries:
                for d in e.datas:
                    if type(d) is _Pending and d.value is None \
                            and d.error is None and d.queue is not self:
                        d.queue.flush()
                        if d.value is None and d.error is None:
                            # the producing queue's flush was re-entrant
                            # (mutual cross-queue dependency): resolve
                            # just the producing entry, following the
                            # dataflow DAG entry-by-entry — data deps
                            # cannot cycle, so this terminates
                            d.queue._resolve_entry_of(d)
            with self._lock:
                if _tel._ENABLED and self.entries:
                    with _tel.span("imperative.bulk_flush",
                                   {"ops": len(self.entries)}):
                        self._flush_locked()
                else:
                    self._flush_locked()
        finally:
            flushing.discard(id(self))

    def _resolve_entry_of(self, p):
        """Execute ONLY the entry producing pending ``p`` (plus, by
        recursion, its unresolved operands). Used when this queue's
        whole-queue flush is already on the caller's stack; the executed
        entries stay in ``entries`` and are skipped by ``_flush_locked``
        once their pendings carry values."""
        for e in self.entries:
            if any(x is p for x in e.pendings):
                if not _entry_done(e):
                    self._run_entry(e)
                return

    def _run_entry(self, e):
        """Eagerly execute one queued entry through the per-op jit cache
        (the ``_flush_fallback`` recipe for a single entry)."""
        args = []
        for d in e.datas:
            if type(d) is _Pending and d.value is None and d.error is None:
                if d.queue is self:
                    self._resolve_entry_of(d)
                else:
                    d.queue.flush()
                    if d.value is None and d.error is None:
                        d.queue._resolve_entry_of(d)
            args.append(_resolve_strict(d))
        try:
            try:
                outs = _fwd_jit(e.key, e.fn)(*args)
            except Exception:
                outs = e.fn(*args)
                _EAGER_FWD_CACHE[e.key] = _FAILED
        except Exception as exc:  # noqa: BLE001 - recorded per pending
            for p in e.pendings:
                p.error = exc
            raise
        outs_t = outs if isinstance(outs, (tuple, list)) else (outs,)
        for chunk, p, v in zip(e.chunks, e.pendings, outs_t):
            p.value = v
            if chunk.data is p:
                chunk.data = v
                chunk.version += 1
        if e.node is not None:
            e.node.xs = tuple(args)

    def _flush_locked(self):
        entries, self.entries = self.entries, []
        # entries already executed individually by _resolve_entry_of
        # (re-entrant cross-queue resolution) have their values written
        # back; only the rest form the fused segment
        entries = [e for e in entries if not _entry_done(e)]
        if not entries:
            return
        slot_of = {}
        for pos, e in enumerate(entries):
            for oi, p in enumerate(e.pendings):
                slot_of[id(p)] = (pos, oi)
        ext = []
        parts = []
        wirings = []
        for e in entries:
            wiring = []
            for d in e.datas:
                if type(d) is _Pending and d.value is None:
                    tgt = slot_of.get(id(d))
                    if tgt is None:
                        # foreign-queue pending (pre-resolved in flush();
                        # raced or failed cases surface the op's error)
                        v = _resolve_strict(d)
                        wiring.append(("ext", len(ext),
                                       (tuple(v.shape), str(v.dtype))))
                        ext.append(v)
                    else:
                        wiring.append(("slot",) + tgt)
                else:
                    v = _resolve(d)
                    if hasattr(v, "shape") and hasattr(v, "dtype"):
                        wiring.append(("ext", len(ext),
                                       (tuple(v.shape), str(v.dtype))))
                    else:
                        wiring.append(("ext", len(ext),
                                       ("py", type(v).__name__)))
                    ext.append(v)
            wirings.append(wiring)
            parts.append((e.key, tuple(wiring), len(e.pendings)))
        seg_key = tuple(parts)
        runner = _SEG_CACHE.get(seg_key)
        if runner is None:
            fns = [e.fn for e in entries]
            multis = [len(e.pendings) for e in entries]
            wir = [tuple(w) for w in wirings]

            def run(ext_ops):
                vals = []
                for i, fn in enumerate(fns):
                    args = []
                    for w in wir[i]:
                        if w[0] == "ext":
                            args.append(ext_ops[w[1]])
                        else:
                            args.append(vals[w[1]][w[2]])
                    o = fn(*args)
                    vals.append(tuple(o) if isinstance(o, (tuple, list))
                                else (o,))
                flat = []
                for v in vals:
                    flat.extend(v)
                return tuple(flat)

            import jax

            if len(_SEG_CACHE) >= _SEG_CAP:
                _SEG_CACHE.pop(next(iter(_SEG_CACHE)))
            runner = _SEG_CACHE[seg_key] = jax.jit(run)
        if runner is _FAILED:
            self._flush_fallback(entries)
            return
        try:
            results = runner(tuple(ext))
        except Exception:
            _SEG_CACHE[seg_key] = _FAILED
            self._flush_fallback(entries)
            return
        k = 0
        for e in entries:
            for chunk, p in zip(e.chunks, e.pendings):
                p.value = results[k]
                if chunk.data is p:
                    chunk.data = results[k]
                    chunk.version += 1
                k += 1
            if e.node is not None:
                e.node.xs = tuple(_resolve(d) for d in e.datas)

    def _flush_fallback(self, entries):
        """Per-entry execution through the per-op jit cache — correctness
        backstop when the fused segment refuses to trace. A failing
        entry must not poison its siblings: every entry still executes
        (or records its error on its pendings), and the FIRST failure
        re-raises after the sweep."""
        first_err = None
        for e in entries:
            try:
                datas = [_resolve_strict(d) for d in e.datas]
                try:
                    outs = _fwd_jit(e.key, e.fn)(*datas)
                except Exception:
                    outs = e.fn(*datas)
                    _EAGER_FWD_CACHE[e.key] = _FAILED
            except Exception as exc:  # noqa: BLE001 - recorded per pending
                for p in e.pendings:
                    p.error = exc
                if first_err is None:
                    first_err = exc
                continue
            outs_t = outs if isinstance(outs, (tuple, list)) else (outs,)
            for chunk, p, v in zip(e.chunks, e.pendings, outs_t):
                p.value = v
                if chunk.data is p:
                    chunk.data = v
                    chunk.version += 1
            if e.node is not None:
                e.node.xs = tuple(datas)
        if first_err is not None:
            raise first_err


import threading as _threading  # noqa: E402

_QUEUE_TLS = _threading.local()


def _queue() -> _BulkQueue:
    q = getattr(_QUEUE_TLS, "q", None)
    if q is None:
        q = _QUEUE_TLS.q = _BulkQueue()
    return q


def flush_bulk():
    """Flush any queued eager ops (public sync seam; waitall calls it)."""
    _queue().flush()


def _bulk_fwd_enabled() -> bool:
    from .base import env_bool

    return _bulk_size() > 0 and env_bool("MXTPU_BULK_FWD", True)


def _aval_key(d):
    # np.dtype objects hash by value — no stringification on the hot
    # path; weak_type is part of the promotion semantics so it must be
    # part of the key (a weak f32 scalar times bf16 gives bf16)
    if type(d) is _Pending:
        return (d.shape, d.dtype, d.weak_type)
    if hasattr(d, "shape") and hasattr(d, "dtype"):
        return (tuple(d.shape), d.dtype, getattr(d, "weak_type", False))
    return ("py", type(d))


def _try_enqueue(key, fn, args, datas, record):
    """Queue this op call; returns (outs, node) of _Pending NDArrays, or
    None when the op must execute now (unknown aval, scalar-output probes
    are fine — only trace failures disqualify)."""
    from . import autograd

    akey = (key, tuple(_aval_key(d) for d in datas))
    hit = _AVAL_CACHE.get(akey)
    if hit is _FAILED:
        return None
    if hit is None:
        import jax

        try:
            spec = [
                jax.ShapeDtypeStruct(
                    d.shape, _np.dtype(d.dtype),
                    weak_type=getattr(d, "weak_type", False))
                if (type(d) is _Pending
                    or (hasattr(d, "shape") and hasattr(d, "dtype")))
                else d
                for d in datas
            ]
            out = jax.eval_shape(fn, *spec)
        except Exception:
            _AVAL_CACHE[akey] = _FAILED
            return None
        multi = isinstance(out, (tuple, list))
        structs = tuple(out) if multi else (out,)
        if len(_AVAL_CACHE) >= _EAGER_CACHE_CAP:
            _AVAL_CACHE.pop(next(iter(_AVAL_CACHE)))
        hit = _AVAL_CACHE[akey] = (structs, multi)
    structs, multi = hit
    node = None
    if record:
        node = autograd._record_deferred(
            _bwd_jit(key, fn), fn, args,
            [(s.shape, _np.dtype(s.dtype)) for s in structs], multi,
            bulk_key=key)
    outs, multi = _queue().enqueue(key, fn, datas, structs, multi, node)
    return outs, multi, node


def _op_jit_key(op, params):
    """Cache key for a registered-op dispatch; None = do not jit."""
    if not _jit_enabled() or op.name in _EAGER_JIT_DENY \
            or getattr(op, "self_recording", False):
        return None
    for v in params.values():
        if isinstance(v, NDArray) or hasattr(v, "shape"):
            # array-valued params would be baked in as constants (and
            # NDArray rebinding would silently stale them) — stay eager
            return None
    try:
        key = ("op", op.name, _freeze(tuple(sorted(params.items()))),
               _env_fingerprint(op.name))
        hash(key)
    except TypeError:
        return None
    return key


def _holds_ndarray(v):
    """True if v is (or transitively contains) an NDArray. NDArray hashes
    by identity, so it would survive _freeze+hash and be baked into the
    executable while a later _rebind() of the same object silently went
    stale. jnp/np arrays are unhashable and already rejected by hash();
    np.dtype/np.generic hash by value and are safe to bake."""
    if isinstance(v, NDArray):
        return True
    if isinstance(v, (list, tuple)):
        return any(_holds_ndarray(x) for x in v)
    if isinstance(v, dict):
        return any(_holds_ndarray(x) for x in v.values())
    return False


def _fn_jit_key(fn):
    """Cache key for a bare function/lambda dispatch (NDArray method
    lambdas): the code object identity + closure values. The code object
    itself is part of the key (kept alive by the cache), so id reuse
    after GC cannot alias two different functions."""
    if not _jit_enabled():
        return None
    if isinstance(fn, functools.partial):
        inner = _fn_jit_key(fn.func)
        if inner is None or _holds_ndarray(fn.args) \
                or _holds_ndarray(fn.keywords):
            return None
        try:
            key = ("partial", inner, _freeze(tuple(sorted(fn.keywords.items()))),
                   _freeze(fn.args))
            hash(key)
        except TypeError:
            return None
        return key
    code = getattr(fn, "__code__", None)
    if code is None:
        # jnp ufuncs (NDArray arithmetic dispatches them directly) have
        # no __code__ but are pure stateless globals: key by the object
        # (kept alive by the cache, so id reuse cannot alias)
        import jax.numpy as jnp

        if isinstance(fn, jnp.ufunc):
            return ("ufunc", fn)
        return None
    cells = ()
    if fn.__closure__:
        try:
            cells = tuple(c.cell_contents for c in fn.__closure__)
        except ValueError:
            return None
        if _holds_ndarray(cells):
            return None
        try:
            cells = _freeze(cells)
            hash(cells)
        except (TypeError, ValueError):
            return None
    try:
        key = ("code", code, cells)
        hash(key)
    except TypeError:
        return None
    return key


_EAGER_CACHE_CAP = 2048  # keys; value-varying closures (loop-dependent
# slice bounds, schedules passed as op params) would otherwise mint
# wrappers + compiled executables without bound. FIFO eviction: dropping
# a wrapper frees its executables; a re-hit just re-jits.


def _cache_put(cache, key, value):
    if len(cache) >= _EAGER_CACHE_CAP:
        cache.pop(next(iter(cache)))
    cache[key] = value
    return value


def _fwd_jit(key, fn):
    j = _EAGER_FWD_CACHE.get(key)
    if j is None:
        import jax

        j = _cache_put(_EAGER_FWD_CACHE, key, jax.jit(fn))
    return j


def _bwd_jit(key, fn):
    j = _EAGER_BWD_CACHE.get(key)
    if j is None:
        import jax

        def bwd(xs, ct):
            _, vjp_fn = jax.vjp(fn, *xs)
            return vjp_fn(ct)

        j = _cache_put(_EAGER_BWD_CACHE, key, jax.jit(bwd))
    return j


def invoke(op, *args, out=None, **params):
    """Dispatch a registered operator (reference: ``MXImperativeInvokeEx``)."""
    if not isinstance(op, Operator):
        op = get_op(op)
    fn = functools.partial(op.fn, **params) if params else op.fn
    key = _op_jit_key(op, params)
    return _invoke_with(op, fn, key, args, out)


def _invoke_with(op, fn, key, args, out):
    if op.mutates_input is not None:
        # fused in-place update ops (optimizers): run unrecorded, rebind input
        target = args[op.mutates_input]
        datas = [a.data if isinstance(a, NDArray) else a for a in args]
        call = fn
        if key is not None and _EAGER_FWD_CACHE.get(key) is not _FAILED:
            call = _fwd_jit(key, fn)
        try:
            outs = call(*datas)
        except Exception:
            if call is fn:
                raise
            outs = fn(*datas)  # user errors re-raise here, no blacklist
            _EAGER_FWD_CACHE[key] = _FAILED  # jit-specific failure
        outs_t = outs if isinstance(outs, (tuple, list)) else (outs,)
        if isinstance(target, NDArray):
            target._rebind(outs_t[0])
            rest = [NDArray(o) for o in outs_t[1:]]
            return target if not rest else (target, *rest)
        return _wrap_outputs(outs)
    if getattr(op, "self_recording", False):
        # the op's fn builds its own tape entry (python/C++ custom ops
        # whose host bodies cannot consume jax tracers): hand it the
        # ORIGINAL NDArrays so its Function links to the caller's graph
        result = _wrap_outputs(fn(*args))
    else:
        result = invoke_fn(fn, *args, _jit_key=key)
    if out is not None:
        _bind_out(out, result)
        return out
    return result


def _bind_out(out, result):
    if isinstance(out, NDArray) and isinstance(result, NDArray):
        out._rebind(result.data)
        out._ag = result._ag  # keep the tape connected through out=
    elif isinstance(out, (tuple, list)) and isinstance(result, (tuple, list)):
        for o, r in zip(out, result):
            _bind_out(o, r)
    else:
        raise MXNetError("out= structure does not match op outputs")

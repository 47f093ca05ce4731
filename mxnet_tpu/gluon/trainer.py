"""Trainer: binds Parameters to an optimizer + KVStore (reference:
``python/mxnet/gluon/trainer.py`` [unverified]).

Reference flow (SURVEY.md §3.3): ``step()`` → allreduce grads via KVStore
push/pull → fused optimizer update per param. Here the single-process path
updates each param through a jitted fused-update op; multi-host grads are
psum'd through the dist KVStore facade; GSPMD data-parallel inside a jitted
step needs no Trainer-level sync at all (the collective is compiled in).
"""

from __future__ import annotations

import functools
import logging
import time as _time
from typing import Optional

import numpy as _np
import jax.numpy as jnp

from ..base import MXNetError
from .. import optimizer as opt
from .. import telemetry as _tel
from ..kvstore import KVStore as _KV
from ..ndarray.ndarray import NDArray
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]


def _fused_jit_enabled() -> bool:
    import os

    return os.environ.get("MXTPU_EAGER_JIT", "1") != "0"


@functools.lru_cache(maxsize=64)
def _fused_sgd_fn(n: int, momentum: float, clip: float):
    import jax

    # the per-tensor math is the op library's (_apply_wd_rescale is the
    # single source of rescale/clip/wd ordering — shared with
    # sgd_update/multi_sgd_update so the three paths cannot diverge)
    from ..ops.optimizer_op import _apply_wd_rescale

    def apply(ws, gs, ms, lrs, wds, rescale):
        new_w, new_m = [], []
        for i in range(n):
            g = _apply_wd_rescale(ws[i], gs[i], wds[i], rescale,
                                  clip if clip >= 0 else None)
            if momentum:
                m = momentum * ms[i] - lrs[i] * g
                new_m.append(m)
                new_w.append(ws[i] + m)
            else:
                new_w.append(ws[i] - lrs[i] * g)
        return tuple(new_w), tuple(new_m) if momentum else None

    return jax.jit(apply)


@functools.lru_cache(maxsize=64)
def _fused_adam_fn(n: int, beta1: float, beta2: float, eps: float,
                   clip: float, decoupled_wd: bool, bias_corr: bool,
                   low_dtypes: tuple = ()):
    import jax

    # per-tensor math mirrors ops/optimizer_op.adam_update (coupled wd via
    # _apply_wd_rescale ordering) and adamw_update (decoupled, wd outside
    # the moments); bias correction folds into lr IN-GRAPH from the ts
    # vector — the same f32 formulation TrainStep compiles, so the three
    # Adam paths agree to f32 resolution.
    # low_dtypes: per-tensor low-precision weight dtype name ('' = plain
    # f32 weight). A named entry is the MULTI-PRECISION case: ws[i] is the
    # f32 MASTER, gs[i] arrives in the low dtype (upcast in-graph), and a
    # fresh low-precision weight is returned alongside the master — the
    # reference's mp_*_update contract, one fused launch for all params.
    from ..ops.optimizer_op import _apply_wd_rescale

    low_dtypes = low_dtypes or ("",) * n

    def apply(ws, gs, ms, vs, lrs, wds, ts, rescale):
        new_w, new_m, new_v, new_low = [], [], [], []
        for i in range(n):
            g32 = gs[i].astype(jnp.float32)
            if decoupled_wd:
                g = g32 * rescale
                if clip >= 0:
                    g = jnp.clip(g, -clip, clip)
            else:
                g = _apply_wd_rescale(ws[i], g32, wds[i], rescale,
                                      clip if clip >= 0 else None)
            lr = lrs[i]
            if bias_corr:
                lr = lr * jnp.sqrt(1.0 - beta2 ** ts[i]) / \
                    (1.0 - beta1 ** ts[i])
            m = beta1 * ms[i] + (1.0 - beta1) * g
            v = beta2 * vs[i] + (1.0 - beta2) * jnp.square(g)
            upd = m / (jnp.sqrt(v) + eps)
            if decoupled_wd:
                upd = upd + wds[i] * ws[i]
            w1 = ws[i] - lr * upd
            new_w.append(w1)
            new_m.append(m)
            new_v.append(v)
            new_low.append(w1.astype(jnp.dtype(low_dtypes[i]))
                           if low_dtypes[i] else None)
        return tuple(new_w), tuple(new_m), tuple(new_v), tuple(new_low)

    return jax.jit(apply)


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError(
                "first argument must be a list or dict of Parameters, "
                f"got {type(params)}"
            )
        self._params = []
        for param in params:
            if not isinstance(param, Parameter):
                raise MXNetError(
                    "first argument must be a list or dict of Parameters, "
                    f"got list of {type(param)}"
                )
            if param.grad_req != "null":
                self._params.append(param)
        # name -> index in the FILTERED list (the index space used for
        # optimizer state and kvstore keys)
        self._param2idx = {p.name: i for i, p in enumerate(self._params)}
        self._compression_params = compression_params
        self._contains_sparse_weight = False
        optimizer_params = optimizer_params if optimizer_params else {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_params = {
            "kvstore": kvstore,
            "update_on_kvstore": update_on_kvstore,
        }
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._states_to_load = None
        self._grad_keys_inited = False

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, (
                "optimizer_params must be None if optimizer is an Optimizer "
                "instance"
            )
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(
                optimizer, param_dict=param_dict, **optimizer_params
            )
        self._updaters = [opt.get_updater(self._optimizer)]

    def _init_kvstore(self):
        config = self._kvstore_params
        kvstore = config["kvstore"]
        update_on_kvstore = config["update_on_kvstore"]
        if kvstore:
            kv = kvstore if isinstance(kvstore, _KV) else None
            if kv is None:
                from .. import kvstore as kvstore_mod

                kv = kvstore_mod.create(kvstore)
            self._kvstore = kv
            if self._compression_params:
                kv.set_gradient_compression(self._compression_params)
            if update_on_kvstore is None:
                update_on_kvstore = kv.num_workers > 1
            if update_on_kvstore:
                kv.set_optimizer(self._optimizer)
            for i, param in enumerate(self._params):
                kv.init(i, param.data())
        else:
            self._kvstore = None
            self._update_on_kvstore = False
        self._update_on_kvstore = bool(update_on_kvstore) if kvstore else False
        self._kv_initialized = True
        if self._states_to_load is not None:
            self.load_states(self._states_to_load)
            self._states_to_load = None

    @property
    def learning_rate(self):
        return self._optimizer.lr_scheduler(self._optimizer.num_update) \
            if self._optimizer.lr_scheduler is not None else self._optimizer.lr

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # ---------------------------------------------------------------- steps
    def step(self, batch_size, ignore_stale_grad=False):
        """Rescale by 1/batch_size, sync grads, apply optimizer update.

        Disabled-telemetry overhead is the single ``_tel._ENABLED`` flag
        check — no span or metric objects exist on that path."""
        if not _tel._ENABLED:
            if not self._kv_initialized:
                self._init_kvstore()
            self._optimizer.rescale_grad = self._scale / batch_size
            self._allreduce_grads()
            self._update(ignore_stale_grad)
            return
        t0 = _time.perf_counter()
        with _tel.span("trainer.step", {"batch_size": int(batch_size)}):
            if not self._kv_initialized:
                self._init_kvstore()
            self._optimizer.rescale_grad = self._scale / batch_size
            with _tel.span("trainer.allreduce_grads"):
                self._allreduce_grads()
            with _tel.span("trainer.update"):
                self._update(ignore_stale_grad)
        _tel.record_step(int(batch_size), _time.perf_counter() - t0)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise MXNetError(
                "allreduce_grads() when parameters are updated on kvstore "
                "is not supported"
            )
        if _tel._ENABLED:
            with _tel.span("trainer.allreduce_grads"):
                self._allreduce_grads()
        else:
            self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None or self._kvstore.num_workers == 1:
            return  # grads already global: single replica or in-program psum
        from ..parallel import sharding as _shard

        if _shard.mesh_spans_processes():
            # the process-global mesh covers every worker: gradient sync
            # is IN-GRAPH (GSPMD psum over the mesh) — the host-side
            # push/pull loop would double-sum on top of it. Count the
            # skip so the telemetry shows which sync path is live.
            if not getattr(self, "_mesh_sync_noted", False):
                self._mesh_sync_noted = True
                logging.getLogger(__name__).info(
                    "global mesh spans all %d processes: host KVStore "
                    "allreduce skipped (gradient sync is in-graph)",
                    self._kvstore.num_workers)
            if _tel._ENABLED:
                _tel.registry().counter(
                    "shard/host_allreduce_skipped").inc()
            return
        if self._update_on_kvstore:
            # the push inside _update() both all-reduces and applies the
            # server-side optimizer; pre-reducing here would double-sum and
            # run the updater against the gradient buffers
            return
        if not self._grad_keys_inited:
            # register gradient keys ONCE — init is idempotent but still
            # cost a span + dict probe per param per step when issued
            # unconditionally from this hot loop
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    self._kvstore.init(f"g{i}", param.grad())
            self._grad_keys_inited = True
        for i, param in enumerate(self._params):
            if param.grad_req != "null":
                grad = param.grad()
                self._kvstore.push(f"g{i}", grad)
                self._kvstore.pull(f"g{i}", grad)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise MXNetError(
                "update() when parameters are updated on kvstore is not "
                "supported; call step() instead"
            )
        self._optimizer.rescale_grad = self._scale / batch_size
        if _tel._ENABLED:
            with _tel.span("trainer.update"):
                self._update(ignore_stale_grad)
        else:
            self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        updater = self._updaters[0]
        if self._update_on_kvstore:
            for i, param in enumerate(self._params):
                self._kvstore.push(i, param.grad())
                self._kvstore.pull(i, param.data())
            return
        if self._fused_sgd_update(updater):
            return
        if self._fused_adam_update(updater):
            return
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            updater(i, param.grad(), param.data())

    def _fused_sgd_update(self, updater) -> bool:
        """Multi-tensor apply (reference ``multi_sgd_(mom_)update``,
        ``src/operator/optimizer_op.cc`` [unverified]): ONE jitted call
        updates every parameter — the whole optimizer step is a single
        dispatch instead of one per param, the same launch-amortization
        the reference's multi-tensor CUDA kernels bought. lr/wd arrive as
        device vectors so lr-schedule changes never retrigger a compile.

        Engages only for the plain dense f32 SGD(+momentum) case with
        the exact SGD class; anything else falls back to per-param
        updates."""
        opt_ = self._optimizer
        if type(opt_) is not opt.SGD or not _fused_jit_enabled():
            return False
        idxs, ws, gs, ms = [], [], [], []
        from ..ndarray.sparse import RowSparseNDArray

        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            w, g = param.data(), param.grad()
            if isinstance(g, RowSparseNDArray) or w.dtype != _np.float32:
                return False
            if i not in updater.states:
                updater.states[i] = opt_.create_state_multi_precision(i, w)
                updater.states_synced[i] = True
            st = updater.states[i]
            if st is not None and not isinstance(st, NDArray):
                return False  # multi-precision tuple state: fallback
            if (st is None) != (opt_.momentum == 0.0):
                return False
            idxs.append(i)
            ws.append(w)
            gs.append(g)
            ms.append(st)
        if not idxs:
            return False
        for i in idxs:
            opt_._update_count(i)
        # lr/wd/rescale are usually step-invariant: reuse their device
        # buffers (each jnp.asarray here is otherwise a host->device
        # transfer and an eager launch per step)
        host = ([opt_._get_lr(i) for i in idxs],
                [opt_._get_wd(i) for i in idxs], opt_.rescale_grad)
        memo = getattr(self, "_hyper_memo", None)
        if memo is None or memo[0] != host:
            self._hyper_memo = memo = (
                host, jnp.asarray(host[0], jnp.float32),
                jnp.asarray(host[1], jnp.float32), jnp.float32(host[2]))
        _, lrs, wds, rescale = memo
        clip = opt_.clip_gradient if opt_.clip_gradient is not None else -1.0
        fn = _fused_sgd_fn(len(idxs), float(opt_.momentum), float(clip))
        if opt_.momentum:
            new_w, new_m = fn(
                tuple(w.data for w in ws), tuple(g.data for g in gs),
                tuple(m.data for m in ms), lrs, wds, rescale)
            for w, m, nw, nm in zip(ws, ms, new_w, new_m):
                w._rebind(nw)
                m._rebind(nm)
        else:
            new_w, _ = fn(
                tuple(w.data for w in ws), tuple(g.data for g in gs),
                None, lrs, wds, rescale)
            for w, nw in zip(ws, new_w):
                w._rebind(nw)
        return True

    def _fused_adam_update(self, updater) -> bool:
        """Multi-tensor Adam/AdamW apply, ``_fused_sgd_update``'s shape for
        the adaptive optimizers: ONE jitted call updates every dense f32
        parameter and both moment states — a single dispatch per step
        instead of one per param. lr/wd ride memoized device vectors; the
        per-param step counts (``ts``, for bias correction) change every
        step and arrive as one small f32 vector.

        Engages for the exact Adam/AdamW classes over dense params with
        plain f32 ``(mean, var)`` states AND the multi-precision layout
        (``((mean, var), fp32 master)`` over a low-precision weight,
        from ``multi_precision=True``): the update runs on the f32
        master with the gradient upcast in-graph and the low-precision
        weight refreshed from the new master inside the SAME fused
        launch — the reference's ``mp_adamw_update`` contract. Sparse
        grads or any other state layout fall back to per-param
        updates."""
        opt_ = self._optimizer
        if type(opt_) not in (opt.Adam, opt.AdamW) or not _fused_jit_enabled():
            return False
        from ..ndarray.sparse import RowSparseNDArray

        idxs, ws, gs, ms, vs = [], [], [], [], []
        low_ws, low_dts = [], []
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            w, g = param.data(), param.grad()
            if isinstance(g, RowSparseNDArray):
                return False
            if i not in updater.states:
                updater.states[i] = opt_.create_state_multi_precision(i, w)
                updater.states_synced[i] = True
            st = updater.states[i]
            if (isinstance(st, tuple) and len(st) == 2
                    and isinstance(st[0], tuple)
                    and isinstance(st[1], NDArray)):
                # multi-precision: ((mean, var) on the master, master)
                inner, master = st
                if not (len(inner) == 2
                        and all(isinstance(s, NDArray) for s in inner)):
                    return False
                ws.append(master)
                low_ws.append(w)
                low_dts.append(w.data.dtype.name)
                ms.append(inner[0])
                vs.append(inner[1])
            elif (isinstance(st, tuple) and len(st) == 2
                    and all(isinstance(s, NDArray) for s in st)):
                if w.dtype != _np.float32:
                    return False  # low-precision w/o master: per-param path
                ws.append(w)
                low_ws.append(None)
                low_dts.append("")
                ms.append(st[0])
                vs.append(st[1])
            else:
                return False
            idxs.append(i)
            gs.append(g)
        if not idxs:
            return False
        for i in idxs:
            opt_._update_count(i)
        ts = tuple(float(opt_._index_update_count[i]) for i in idxs)
        host = ([opt_._get_lr(i) for i in idxs],
                [opt_._get_wd(i) for i in idxs], opt_.rescale_grad)
        memo = getattr(self, "_adam_hyper_memo", None)
        if memo is None or memo[0] != host:
            self._adam_hyper_memo = memo = (
                host, jnp.asarray(host[0], jnp.float32),
                jnp.asarray(host[1], jnp.float32), jnp.float32(host[2]))
        _, lrs, wds, rescale = memo
        clip = opt_.clip_gradient if opt_.clip_gradient is not None else -1.0
        decoupled = type(opt_) is opt.AdamW
        bias_corr = bool(opt_.correct_bias) if decoupled else True
        fn = _fused_adam_fn(len(idxs), float(opt_.beta1), float(opt_.beta2),
                            float(opt_.epsilon), float(clip), decoupled,
                            bias_corr, tuple(low_dts))
        new_w, new_m, new_v, new_low = fn(
            tuple(w.data for w in ws), tuple(g.data for g in gs),
            tuple(m.data for m in ms), tuple(v.data for v in vs),
            lrs, wds, jnp.asarray(ts, jnp.float32), rescale)
        for k, (w, m, v) in enumerate(zip(ws, ms, vs)):
            w._rebind(new_w[k])
            m._rebind(new_m[k])
            v._rebind(new_v[k])
            if low_ws[k] is not None:
                low_ws[k]._rebind(new_low[k])
        return True

    # ---------------------------------------------------------------- state
    def save_states(self, fname):
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        if not self._kv_initialized:
            self._states_to_load = fname
            return
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore.updater.optimizer
        else:
            with open(fname, "rb") as f:
                states = f.read()
            self._updaters[0].set_states(states)
            self._optimizer = self._updaters[0].optimizer
        self._optimizer.param_dict = {
            i: param for i, param in enumerate(self._params)
        }

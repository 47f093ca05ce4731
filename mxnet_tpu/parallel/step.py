"""Whole-model jitted training step with GSPMD sharding.

This is the TPU-native replacement for the reference's training hot path
(SURVEY.md §3.3: per-op engine pushes + KVStore push/pull + per-param fused
optimizer kernels). Here ONE XLA executable contains forward, backward,
gradient all-reduce (psum inserted by GSPMD over the mesh's ``data`` axis)
and the optimizer update, with parameter/optimizer buffers donated — the
compiled analogue of CachedOp + kvstore + multi-tensor update in a single
program, with comm/compute overlap handled by XLA's latency-hiding
scheduler.

Tensor parallelism comes free by rule: ``param_rules`` maps parameter-name
regexes to PartitionSpecs; annotated weights shard over the ``model`` axis
and GSPMD inserts the matching collectives.
"""

from __future__ import annotations

import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .. import compile_cache as _cc
from .. import random as _random
from .. import telemetry as _tel
from .. import optimizer as _opt
from ..ops import optimizer_op as _fused
from . import sharding as _sharding

__all__ = ["TrainStep", "DeviceBatch", "plan_batch", "hbm_budget_bytes"]


def hbm_budget_bytes(limit_bytes=None) -> Optional[int]:
    """The HBM planning budget: the device limit shaved by
    ``MXTPU_HBM_HEADROOM`` — a value <= 1 is the usable FRACTION of HBM
    (default 0.9), a value > 1 is an absolute byte count reserved.
    ``limit_bytes`` overrides the detected limit
    (``telemetry.hbm_limit_bytes``: device ``bytes_limit``, else
    ``MXTPU_HBM_BYTES``). None when no limit is known."""
    import os

    if limit_bytes is None:
        limit_bytes = _tel.hbm_limit_bytes()
    if limit_bytes is None:
        return None
    head = float(os.environ.get("MXTPU_HBM_HEADROOM", "0.9"))
    if head <= 1.0:
        return int(limit_bytes * head)
    return int(limit_bytes - head)


def plan_batch(step, signature_fn, budget_bytes, start=1, max_batch=65536,
               per_shard=None):
    """Largest global batch whose compiled step fits ``budget_bytes``.

    ``signature_fn(batch_size)`` returns the warmup-style signature
    (per-array ``(shape, dtype)`` specs for ``(input0, ..., label)``)
    describing one global batch of that size. Cost model is
    ``step.memory_analysis(sig)['peak_bytes_estimate']`` — abstract
    lowering only, nothing is materialized. Geometric probe up from
    ``start`` then bisection, so ~2*log2(answer) compiles (persistent
    compilation cache hits on re-runs). Returns ``(batch, peak_bytes)``;
    ``(0, None)`` when even ``start`` does not fit.

    ``per_shard`` — bisect against the PER-DEVICE peak
    (``peak_bytes_per_shard``): the budget is one device's HBM, and a
    mesh splits the working set across ``mesh.size`` devices. Default
    auto: per-shard whenever the step runs on a multi-device mesh
    (``hbm_budget_bytes`` is per-device by construction — it reads the
    min device ``bytes_limit``)."""
    if per_shard is None:
        m = getattr(step, "_mesh", None)
        per_shard = m is not None and int(m.size) > 1
    key = "peak_bytes_per_shard" if per_shard else "peak_bytes_estimate"
    memo = {}

    def peak(bs):
        if bs not in memo:
            ma = step.memory_analysis(signature_fn(bs))
            memo[bs] = ma.get(key, ma["peak_bytes_estimate"])
        return memo[bs]

    if peak(start) > budget_bytes:
        return 0, None
    lo, hi, b = start, None, start
    while hi is None and b < max_batch:
        b = min(b * 2, max_batch)
        if peak(b) <= budget_bytes:
            lo = b
        else:
            hi = b
    if hi is not None:
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if peak(mid) <= budget_bytes:
                lo = mid
            else:
                hi = mid
    return lo, peak(lo)


class DeviceBatch:
    """A batch already staged — leading step/accum axes split, per-input
    shardings applied, buffers device-resident — for ONE specific
    ``TrainStep``. Produced by ``TrainStep.device_put_batch`` (the
    ``prefetch_to_device`` worker's placement hook); ``TrainStep.__call__``
    detects it and skips the host-side staging entirely."""

    __slots__ = ("batch", "label", "owner")

    def __init__(self, batch, label, owner):
        self.batch = tuple(batch)
        self.label = label
        self.owner = owner


def _pure_update_factory(optimizer):
    """Map an Optimizer instance to (state_init, pure_update).

    pure_update(w, g, states, lr, wd, t) -> (new_w, new_states); hypers are
    closed over statically, lr/wd/t are dynamic scalars (no retrace when the
    schedule moves).
    """
    clip = optimizer.clip_gradient if optimizer.clip_gradient is not None else -1.0

    if isinstance(optimizer, _opt.SGD):
        mom = optimizer.momentum

        def init(w):
            return (jnp.zeros_like(w),) if mom else ()

        def update(w, g, states, lr, wd, t, rescale):
            if mom:
                new_w, new_m = _fused.sgd_mom_update(
                    w, g, states[0], lr=lr, momentum=mom, wd=wd,
                    rescale_grad=rescale, clip_gradient=clip,
                )
                return new_w, (new_m,)
            return (
                _fused.sgd_update(w, g, lr=lr, wd=wd, rescale_grad=rescale,
                                  clip_gradient=clip),
                (),
            )

        return init, update

    if isinstance(optimizer, _opt.LAMB):
        b1, b2, eps = optimizer.beta1, optimizer.beta2, optimizer.epsilon
        lower = optimizer.lower_bound if optimizer.lower_bound is not None else -1.0
        upper = optimizer.upper_bound if optimizer.upper_bound is not None else -1.0
        bias_corr = optimizer.bias_correction

        def init(w):
            return (jnp.zeros_like(w), jnp.zeros_like(w))

        def update(w, g, states, lr, wd, t, rescale):
            gup, m, v = _fused.lamb_update_phase1(
                w, g, states[0], states[1], beta1=b1, beta2=b2, epsilon=eps,
                t=t.astype(jnp.float32), bias_correction=bias_corr, wd=wd,
                rescale_grad=rescale, clip_gradient=clip,
            )
            r1 = jnp.linalg.norm(w)
            r2 = jnp.linalg.norm(gup)
            new_w = _fused.lamb_update_phase2(
                w, gup, r1, r2, lr=lr, lower_bound=lower, upper_bound=upper
            )
            return new_w, (m, v)

        return init, update

    if isinstance(optimizer, _opt.AdamW):
        b1, b2, eps = optimizer.beta1, optimizer.beta2, optimizer.epsilon
        correct = optimizer.correct_bias

        def init(w):
            return (jnp.zeros_like(w), jnp.zeros_like(w))

        def update(w, g, states, lr, wd, t, rescale):
            if correct:
                tf = t.astype(jnp.float32)
                lr = lr * jnp.sqrt(1.0 - b2 ** tf) / (1.0 - b1 ** tf)
            new_w, m, v = _fused.adamw_update(
                w, g, states[0], states[1], lr=lr, beta1=b1, beta2=b2,
                epsilon=eps, wd=wd, rescale_grad=rescale, clip_gradient=clip,
            )
            return new_w, (m, v)

        return init, update

    if isinstance(optimizer, _opt.Adam):
        b1, b2, eps = optimizer.beta1, optimizer.beta2, optimizer.epsilon

        def init(w):
            return (jnp.zeros_like(w), jnp.zeros_like(w))

        def update(w, g, states, lr, wd, t, rescale):
            tf = t.astype(jnp.float32)
            lr = lr * jnp.sqrt(1.0 - b2 ** tf) / (1.0 - b1 ** tf)
            new_w, m, v = _fused.adam_update(
                w, g, states[0], states[1], lr=lr, beta1=b1, beta2=b2,
                epsilon=eps, wd=wd, rescale_grad=rescale, clip_gradient=clip,
            )
            return new_w, (m, v)

        return init, update

    raise MXNetError(
        f"TrainStep has no fused pure update for {type(optimizer).__name__}; "
        "use Trainer.step (per-param path) or add a mapping"
    )


class TrainStep:
    """Compile net+loss+optimizer into one sharded XLA training step.

    Parameters
    ----------
    net : initialized Gluon Block
    loss_fn : gluon Loss block (applied as ``loss_fn(net(*data), label)``)
    optimizer : Optimizer instance (SGD/Adam/AdamW/LAMB fused)
    mesh : jax Mesh, or None — adopts the process-global mesh
        (``sharding.global_mesh()`` / ``MXTPU_MESH``); single device when
        neither is configured
    sharding : ``sharding.ShardingRules``, preset string ('replicated',
        'fsdp', 'fsdp:<axis>') or None (the ``MXTPU_SHARDING`` process
        default). Maps params + optimizer state + batch inputs to
        ``NamedSharding`` declaratively; 'fsdp' shards parameters AND
        moments over the data axis so a model larger than one chip's
        HBM trains (GSPMD inserts the gather/reduce-scatter collectives)
    data_spec : PartitionSpec for every batch input (default: the rules'
        batch spec, else shard axis 0 over 'data' when the mesh has one)
    param_rules : [(regex, PartitionSpec)] tensor-parallel placement
        rules; checked BEFORE the ``sharding`` rules, so explicit TP
        placements compose with an FSDP default
    grad_accum : microbatch accumulation steps (lax.scan over microbatches)

    Sequence/context parallelism: give the mesh a ``seq`` axis, shard batch
    inputs over it via ``data_spec`` (e.g. ``P('data', 'seq')`` for (B, S)
    token ids), and build the model's attention with ``ring_axis='seq'``
    (``MultiHeadAttention``) — the step's trace runs under this mesh's
    scope, so ring attention resolves the axis automatically and GSPMD
    composes the ring ppermutes with the data-parallel psum.
    """

    def __init__(self, net, loss_fn, optimizer, mesh: Optional[Mesh] = None,
                 data_spec: Optional[PartitionSpec] = None,
                 param_rules: Sequence[Tuple[str, PartitionSpec]] = (),
                 donate: bool = True, grad_accum: int = 1,
                 compute_dtype=None, state_dtype=None, steps_per_call: int = 1,
                 remat: Optional[str] = None, amp: Optional[str] = None,
                 loss_scaler=None, sharding=None):
        from .. import amp as _amp_mod
        from .. import remat as _remat_mod

        self._net = net
        self._loss = loss_fn
        self._optimizer = optimizer
        # sharding spine: explicit mesh/rules win; otherwise the
        # process-global mesh (MXTPU_MESH) and rules (MXTPU_SHARDING)
        rules = _sharding.ShardingRules.resolve(sharding)
        if mesh is None:
            mesh = _sharding.global_mesh()
        self._sharding_rules = rules
        self._mesh = mesh
        self._accum = int(grad_accum)
        # steps_per_call > 1: run that many full optimizer steps per
        # dispatch via a device-side lax.scan; batch inputs then carry a
        # leading (steps_per_call,) axis of distinct microbatches. Trades
        # per-step host control (lr schedule moves only between calls) for
        # dispatch latency — the standard JAX input-dispatch amortization.
        self._steps_per_call = int(steps_per_call)
        # AMP: cast float params/inputs to the compute dtype INSIDE the
        # jitted step. The step differentiates W.R.T. THE CAST COPIES, so
        # gradients carry the compute dtype — the reference's
        # multi-precision scheme exactly (low-precision weights+grads, f32
        # masters inside the optimizer, ``mp_sgd_update`` family in
        # ``src/operator/optimizer_op.cc`` [unverified]) — and the
        # optimizer casts back up. On bandwidth-bound chips halving
        # gradient bytes is a first-order win. Two spellings:
        #   compute_dtype=...  (legacy) casts EVERY float param;
        #   amp='bfloat16'|'float16' consults amp.lists — norm-family
        #   params stay fp32 (the cast-insertion pass at parameter
        #   granularity), losses/reductions stay fp32, and float16 runs
        #   the dynamic LossScaler inside the graph (scaled loss,
        #   all-finite grad check, lax.cond-skipped update, in-graph
        #   scale schedule — overflow steps cost no host sync).
        if amp is None and compute_dtype is None:
            amp = _amp_mod.default_amp()  # amp.init() global / MXTPU_AMP
        if amp is not None:
            if compute_dtype is not None:
                raise MXNetError(
                    "pass either amp= or compute_dtype=, not both")
            amp = str(amp)
            if amp not in ("bfloat16", "float16"):
                raise MXNetError("amp must be 'bfloat16' or 'float16'")
            self._amp = amp
            self._compute_dtype = jnp.dtype(amp)
            self._amp_fp32 = _amp_mod.fp32_param_names(net)
            if loss_scaler is None and amp == "float16":
                loss_scaler = _amp_mod.LossScaler()
        else:
            self._amp = None
            self._compute_dtype = (
                jnp.dtype(compute_dtype) if compute_dtype is not None
                else None
            )
            self._amp_fp32 = frozenset()
            loss_scaler = None  # scaling is the amp='float16' contract
        self._scaler = loss_scaler
        self._scaler_dev = None  # (scale f32, clean-streak i32, skips i32)
        # optionally store optimizer moments (m, v) in a narrow dtype; the
        # update computes in f32 and casts state back down (bf16 shares
        # f32's exponent range, so EMA magnitudes survive; mantissa noise
        # is the accepted trade — like the 8-bit-optimizer line of work)
        self._state_dtype = (
            jnp.dtype(state_dtype) if state_dtype is not None else None
        )
        # rematerialization (jax.checkpoint over the traced forward):
        # trades recompute FLOPs for residual HBM traffic — the standard
        # lever when the step is memory-bound. Policy menu + per-layer
        # grain (hybridize(remat=...)): mxnet_tpu.remat.
        if remat is None:
            remat = _remat_mod.default_policy()  # MXTPU_REMAT
        _remat_mod.resolve_policy(remat)  # validate eagerly
        self._remat = remat
        self._params = list(net.collect_params().items())
        for name, p in self._params:
            if p._data is None:
                raise MXNetError(
                    f"parameter {name} not initialized; run one forward (or "
                    "initialize with known shapes) before building TrainStep"
                )
        self._train_names = [n for n, p in self._params
                             if p.grad_req != "null"]
        self._train_set = frozenset(self._train_names)
        self._init_state, self._pure_update = _pure_update_factory(optimizer)
        self._t = 0

        # placement -------------------------------------------------------
        if mesh is not None:
            axis_names = mesh.axis_names
            if data_spec is None:
                data_spec = rules.batch_partition_spec(mesh) \
                    if rules is not None else (
                        PartitionSpec("data") if "data" in axis_names
                        else PartitionSpec())
            # data_spec may be ONE spec for every input, or a sequence of
            # per-input specs covering (*batch, label) — ragged inputs like
            # a (B,) valid_length can't share the (B, S) spec
            if isinstance(data_spec, (tuple, list)) and not isinstance(
                data_spec, PartitionSpec
            ):
                self._data_sharding = [
                    NamedSharding(mesh, s) for s in data_spec
                ]
            else:
                self._data_sharding = NamedSharding(mesh, data_spec)
            # explicit param_rules first (TP placements), then the
            # declarative rules' policy (FSDP/replicated), so both compose
            legacy = [(re.compile(pat), spec) for pat, spec in param_rules]
            shapes = {n: tuple(p._data.data.shape) for n, p in self._params}

            def param_spec(name):
                for pat, spec in legacy:
                    if pat.search(name):
                        return spec
                if rules is not None:
                    return rules.param_spec(
                        name, shapes.get(name, ()), mesh)
                return PartitionSpec()

            def param_sharding(name):
                return NamedSharding(mesh, param_spec(name))

            self._param_spec = param_spec
            self._param_sharding = param_sharding
        else:
            self._data_sharding = None
            self._param_spec = None
            self._param_sharding = None

        # device state ----------------------------------------------------
        # non-aliasing placement: this state is DONATED every step, so it
        # must never share buffers with the net's live Parameters
        vals: Dict[str, jax.Array] = {}
        for name, p in self._params:
            v = p._data.data
            if self._param_sharding is not None:
                v = _sharding.device_put_donatable(
                    v, self._param_sharding(name))
            vals[name] = v
        self._values = vals  # setter partitions into train/frozen dicts
        def _mk_state(v):
            st = self._init_state(v)
            if self._state_dtype is not None:
                st = tuple(s.astype(self._state_dtype) for s in st)
            return st

        self._opt_state = {
            n: _mk_state(vals[n]) for n in self._train_names
        }
        if self._param_sharding is not None:
            # moments follow their param's placement (the ZeRO contract:
            # FSDP shards optimizer state alongside the weights)
            self._opt_state = {
                n: tuple(
                    _sharding.device_put_donatable(
                        s, self._param_sharding(n)) for s in st
                )
                for n, st in self._opt_state.items()
            }

        # host-dispatch slimming: everything __call__ used to recompute
        # per call is hoisted here — the leading device-loop split axes,
        # the lead-adjusted per-input shardings, and the scalar memos
        lead = (self._steps_per_call,) if self._steps_per_call > 1 else ()
        if self._accum > 1:
            lead = lead + (self._accum,)
        self._lead = lead
        n_split = 1
        for d in lead:
            n_split *= d
        self._split_n = n_split
        if self._data_sharding is None:
            self._feed_shardings = None
        else:
            nlead = len(lead)

            def _with_lead(s):
                if not nlead:
                    return s
                # leading step/accum axes are device-side loop axes, not
                # data axes — shard the per-microbatch axis after them
                return NamedSharding(
                    mesh, PartitionSpec(*([None] * nlead), *s.spec))

            if isinstance(self._data_sharding, list):
                self._feed_shardings = [
                    _with_lead(s) for s in self._data_sharding]
            else:
                self._feed_shardings = _with_lead(self._data_sharding)
        self._split_memo: Dict[int, tuple] = {}
        self._key_dev = None
        self._t_dev = None
        self._lr_host = None
        self._rescale_host = None
        self._last_avals = None
        # every distinct (batch, label) aval signature is one compiled
        # step program; the guard is the exact compile counter and the
        # post-warmup shape-churn alarm (compile_cache.RecompileGuard)
        self.compile_guard = _cc.RecompileGuard(
            f"TrainStep({type(net).__name__})")

        # surface the memory/precision config in telemetry reports and
        # bench rows (amp_dtype / remat_policy columns)
        _tel.set_info(
            amp_dtype=(self._amp or (self._compute_dtype.name
                                     if self._compute_dtype else None)),
            remat_policy=self._remat)
        # shard/ metric family: mesh shape, global vs per-shard param
        # bytes, collective-traffic estimate (report()/bench rows)
        if mesh is not None:
            _sharding.publish_shard_metrics(
                self._values, mesh, rules, trainable=self._train_names)

        self._step_fn = self._build(donate)

    # device values stay pre-partitioned (train vs frozen) so the hot
    # dispatch never rebuilds dicts; cold paths (checkpoint/sync/interop)
    # read this merged view and assign through the setter
    @property
    def _values(self):
        merged = dict(self._frozen_vals)
        merged.update(self._train_vals)
        return merged

    @_values.setter
    def _values(self, vals):
        ts = self._train_set
        self._train_vals = {n: v for n, v in vals.items() if n in ts}
        self._frozen_vals = {n: v for n, v in vals.items() if n not in ts}

    # ---------------------------------------------------------------- build
    def _build(self, donate):
        from ..gluon.block import _aux_scope, _trace_scope
        from ..gluon.parameter import param_override
        from .. import autograd

        net, loss_block = self._net, self._loss
        params = self._params
        train_names = set(self._train_names)
        name2param = {n: p for n, p in params}
        pure_update = self._pure_update
        accum = self._accum
        # static per-param hyper multipliers
        lr_mult = {n: name2param[n].lr_mult for n in train_names}
        wd_mult = {n: name2param[n].wd_mult for n in train_names}
        base_wd = float(self._optimizer.wd)

        name2param_inv = {id(p): n for n, p in params}
        cdt = self._compute_dtype
        fp32_pinned = self._amp_fp32

        def _cast(v):
            if cdt is not None and jnp.issubdtype(v.dtype, jnp.floating):
                return v.astype(cdt)
            return v

        def _cast_param(n, v):
            # amp.lists pass at parameter granularity: norm-family params
            # keep their fp32 masters as the compute value
            if n in fp32_pinned:
                return v
            return _cast(v)

        mesh = self._mesh
        from . import mesh_scope as _mesh_scope
        import contextlib as _ctx

        def forward_loss(cast_vals, frozen_vals, batch, label, key):
            # cast_vals are already in compute dtype — they are the
            # differentiated leaves, so gradients carry that dtype too
            mapping = {}
            for n, p in params:
                v = cast_vals[n] if n in cast_vals \
                    else _cast_param(n, frozen_vals[n])
                mapping[p] = NDArray(v)
            sink = {}
            # activate the mesh during tracing so mesh-aware layers (ring
            # attention) can resolve their axis from current_mesh()
            mscope = _mesh_scope(mesh) if mesh is not None else _ctx.nullcontext()
            with mscope, param_override(mapping), _random.key_supply(key), \
                    _aux_scope(sink), _trace_scope(), \
                    autograd._scope(False, True):
                out = net(*[NDArray(_cast(b)) for b in batch])
                outs = out if isinstance(out, tuple) else (out,)
                L = loss_block(*outs, NDArray(label))
                Lm = L.data.astype(jnp.float32).mean()
            aux = {name2param_inv[id(p)]: v for p, v in sink.items()}
            return Lm, aux

        if self._remat is not None:
            from .. import remat as _remat_mod

            forward_loss = jax.checkpoint(
                forward_loss,
                policy=_remat_mod.resolve_policy(self._remat),
                static_argnums=())

        scaler = self._scaler
        scaled = scaler is not None
        if scaled:
            window = jnp.int32(scaler.scale_window)
            factor = jnp.float32(scaler.scale_factor)

        def apply_updates(train_vals, opt_state, grads, lr, t, rescale):
            new_vals = {}
            new_opt = {}
            for n in sorted(train_vals):
                w, g = train_vals[n], grads[n]
                st = opt_state[n]
                # narrow-state option: lift moments to f32 for the update
                # math; XLA fuses the converts into the update kernel so
                # only the narrow bytes move through HBM
                st_f = tuple(s.astype(w.dtype) for s in st)
                nw, ns = pure_update(
                    w, g.astype(w.dtype), st_f, lr * lr_mult[n],
                    base_wd * wd_mult[n], t, rescale,
                )
                new_vals[n] = nw.astype(w.dtype)
                new_opt[n] = tuple(
                    s_new.astype(s_old.dtype)
                    for s_new, s_old in zip(ns, st)
                )
            return new_vals, new_opt

        # rescale_grad is a dynamic operand: AMP dynamic loss scaling and
        # batch-size changes fold into it per step and must not retrace.
        # key and t are DEVICE-carried state (returned updated, donated):
        # advancing them on host would cost a host->device transfer plus an
        # eager dispatch per step.
        # scaler_state (float16 AMP only) rides the same way: (loss scale,
        # clean-step streak, skipped-step count), adjusted in-graph.
        def step_core(train_vals, frozen_vals, opt_state, batch, label, key,
                      lr, t, rescale, scaler_state):
            key, sub = jax.random.split(key)
            # batch: tuple of arrays; with accum > 1 each has a leading
            # microbatch dim of size `accum` scanned by lax.scan
            cast_vals = {n: _cast_param(n, v) for n, v in train_vals.items()}
            scale = scaler_state[0] if scaled else None

            def fwd(cv, fv, b, l, k):
                L, aux = forward_loss(cv, fv, b, l, k)
                # scaled loss => scaled (finite-checkable) gradients; the
                # unscale folds into rescale_grad below, never a host trip
                return (L * scale, aux) if scaled else (L, aux)

            if accum == 1:
                (L, aux), grads = jax.value_and_grad(
                    fwd, has_aux=True
                )(cast_vals, frozen_vals, batch, label, sub)
            else:
                def micro(carry, inp):
                    g_acc, k = carry
                    k, sk = jax.random.split(k)
                    mb, ml = inp
                    (Lm, aux_m), g = jax.value_and_grad(
                        fwd, has_aux=True
                    )(cast_vals, frozen_vals, mb, ml, sk)
                    # accumulate in f32 regardless of grad dtype
                    g_acc = jax.tree.map(
                        lambda a, b: a + b.astype(a.dtype), g_acc, g
                    )
                    return (g_acc, k), (Lm, aux_m)

                g0 = jax.tree.map(
                    lambda v: jnp.zeros(v.shape, jnp.float32), train_vals
                )
                (grads, _), (Ls, auxs) = jax.lax.scan(
                    micro, (g0, sub), (batch, label)
                )
                grads = jax.tree.map(lambda g: g / accum, grads)
                L = Ls.mean()
                aux = jax.tree.map(lambda a: a[-1], auxs)

            if not scaled:
                t1 = t + 1
                new_vals, new_opt = apply_updates(
                    train_vals, opt_state, grads, lr, t1, rescale)
                return L, new_vals, new_opt, key, t1, aux, None

            # in-graph overflow handling: the all-finite check gates a
            # lax.cond'd update — a skipped step leaves params, moments,
            # aux states and the bias-correction clock t untouched — and
            # the grow/halve schedule advances on device. No host sync
            # anywhere on this path (mxlint's amp-purity pass lints it).
            L = L / scale
            finite = jnp.bool_(True)
            for g in jax.tree.leaves(grads):
                finite = jnp.logical_and(finite, jnp.isfinite(g).all())
            t1 = t + finite.astype(t.dtype)

            def _apply(_):
                return apply_updates(train_vals, opt_state, grads, lr, t1,
                                     rescale / scale)

            def _skip(_):
                return (dict(train_vals),
                        {n: tuple(st) for n, st in opt_state.items()})

            new_vals, new_opt = jax.lax.cond(finite, _apply, _skip, None)
            aux = {
                n: jnp.where(finite, v,
                             train_vals[n] if n in train_vals
                             else frozen_vals[n])
                for n, v in aux.items()
            }
            # the LossScaler schedule, in-graph: halve (floor 1.0) on
            # overflow, double after scale_window consecutive clean steps
            good = jnp.where(finite, scaler_state[1] + 1, jnp.int32(0))
            new_scale = jnp.where(
                finite, scale, jnp.maximum(scale / factor, jnp.float32(1.0)))
            grow = good >= window
            new_scale = jnp.where(grow, new_scale * factor, new_scale)
            good = jnp.where(grow, jnp.int32(0), good)
            skips = scaler_state[2] + \
                jnp.logical_not(finite).astype(jnp.int32)
            return L, new_vals, new_opt, key, t1, aux, \
                (new_scale, good, skips)

        nsteps = self._steps_per_call
        if nsteps > 1:
            # device-side training loop: scan `nsteps` FULL optimizer steps
            # (distinct microbatches stacked on a leading axis) inside one
            # executable — one dispatch amortizes host latency over
            # nsteps steps; the scan body is the single-step program, so
            # compile time and numerics are unchanged
            if scaled:
                def multi(train_vals, frozen_vals, opt_state, batch, label,
                          key, lr, t, rescale, scaler_state):
                    def one(carry, inp):
                        tv, os_, k, tt, ss = carry
                        mb, ml = inp
                        L, nv, no, nk, nt, aux, nss = step_core(
                            tv, frozen_vals, os_, mb, ml, k, lr, tt,
                            rescale, ss
                        )
                        return (nv, no, nk, nt, nss), (L, aux)

                    (tv, os_, k, tt, ss), (Ls, auxs) = jax.lax.scan(
                        one, (train_vals, opt_state, key, t, scaler_state),
                        (batch, label)
                    )
                    aux = jax.tree.map(lambda a: a[-1], auxs)
                    return Ls.mean(), tv, os_, k, tt, aux, ss

                donate_args = (0, 2, 5, 7, 9) if donate else ()
                return jax.jit(multi, donate_argnums=donate_args)

            def multi(train_vals, frozen_vals, opt_state, batch, label, key,
                      lr, t, rescale):
                def one(carry, inp):
                    tv, os_, k, tt = carry
                    mb, ml = inp
                    L, nv, no, nk, nt, aux, _ = step_core(
                        tv, frozen_vals, os_, mb, ml, k, lr, tt, rescale,
                        None
                    )
                    return (nv, no, nk, nt), (L, aux)

                (tv, os_, k, tt), (Ls, auxs) = jax.lax.scan(
                    one, (train_vals, opt_state, key, t), (batch, label)
                )
                aux = jax.tree.map(lambda a: a[-1], auxs)
                return Ls.mean(), tv, os_, k, tt, aux

            donate_args = (0, 2, 5, 7) if donate else ()
            return jax.jit(multi, donate_argnums=donate_args)

        if scaled:
            def step(train_vals, frozen_vals, opt_state, batch, label, key,
                     lr, t, rescale, scaler_state):
                return step_core(train_vals, frozen_vals, opt_state, batch,
                                 label, key, lr, t, rescale, scaler_state)

            donate_args = (0, 2, 5, 7, 9) if donate else ()
            return jax.jit(step, donate_argnums=donate_args)

        def step(train_vals, frozen_vals, opt_state, batch, label, key,
                 lr, t, rescale):
            L, nv, no, k, t1, aux, _ = step_core(
                train_vals, frozen_vals, opt_state, batch, label, key, lr,
                t, rescale, None)
            return L, nv, no, k, t1, aux

        donate_args = (0, 2, 5, 7) if donate else ()
        return jax.jit(step, donate_argnums=donate_args)

    # ----------------------------------------------------------------- call
    def __call__(self, *batch_and_label):
        """Run one step. Last argument is the label; returns loss NDArray.

        Accepts either raw host arrays (staged synchronously: convert,
        split, device_put) or ONE pre-placed ``DeviceBatch`` from
        ``device_put_batch`` / ``prefetch_to_device`` — the fast path that
        skips the host-side staging entirely."""
        from ..imperative import flush_bulk

        flush_bulk()  # donated operands may be captured in the eager queue
        host = [0.0]
        if len(batch_and_label) == 1 and \
                isinstance(batch_and_label[0], DeviceBatch):
            db = batch_and_label[0]
            if db.owner is not self:
                raise MXNetError(
                    "DeviceBatch was staged by a different TrainStep; its "
                    "split axes/shardings may not match — feed it to the "
                    "step whose device_put_batch produced it")
            batch, label = db.batch, db.label
        else:
            with _tel.phase("train.stage", host, 0):
                batch, label = self._stage(batch_and_label)
        with _tel.phase("train.dispatch", host, 0):
            loss = self._dispatch(batch, label)
        # what the host spends on one step (staging, device_put,
        # enqueue): always on, like the batcher's infer/* histograms
        _tel.registry().histogram("trainstep/host_ms").observe(
            host[0] * 1e3)
        return loss

    # -------------------------------------------------------------- feeding
    def feed_spec(self) -> dict:
        """The host->device feed contract a feeder must apply to enter the
        pre-placed fast path: leading device-loop split axes (shapes), the
        total leading split factor, and the per-input placement.
        ``prefetch_to_device(loader, feed=step)`` applies it through
        ``device_put_batch`` on its worker thread."""
        return {
            "steps_per_call": self._steps_per_call,
            "grad_accum": self._accum,
            "lead": self._lead,
            "split": self._split_n,
            "mesh": self._mesh,
            "data_sharding": self._data_sharding,
            # declarative rules in force (None = legacy/replicated) — the
            # feeder stages batches onto their SHARDED placements, so the
            # device transfer lands each row on its owning shard directly
            "sharding": (self._sharding_rules.describe()
                         if self._sharding_rules is not None else None),
        }

    def device_put_batch(self, batch_and_label) -> DeviceBatch:
        """Stage one flat ``(input0, ..., label)`` batch exactly as
        ``__call__`` would — convert, split the leading step/accum axes,
        device_put with per-input shardings — and wrap it for the fast
        path. Safe to call from a feeder thread concurrently with the
        training loop (the prefetcher does)."""
        batch, label = self._stage(tuple(batch_and_label))
        return DeviceBatch(batch, label, self)

    # -------------------------------------------------------------- warmup
    def warmup(self, signatures):
        """AOT-compile one step program per batch signature, moving every
        compile out of the steady-state loop.

        ``signatures`` is an iterable; each entry describes ONE global
        (unsplit, exactly as ``__call__`` receives it) batch as a
        sequence of per-array specs for ``(input0, ..., label)`` — an
        array, a ``jax.ShapeDtypeStruct``, or a ``(shape, dtype)`` pair::

            step.warmup([(( (bs, key), "int32"), ((bs, key), "int32"))
                         for bs, key in sampler.signatures()])

        Each signature is driven through the REAL jitted step once —
        ``jit(...).lower(...).compile()`` would compile the same program
        but never populates the jit dispatch cache, so the first real
        call would compile again. Donated operands get throwaway
        zero-state copies (transient extra memory of one parameter+
        optimizer state set); the training state, RNG schedule of the
        real steps, and step counter are untouched.

        Afterwards the guard is marked steady: any NEW shape in the
        training loop counts as ``compile/steady_state_recompiles`` and
        warns or raises per ``MXTPU_RECOMPILE_LIMIT``. Returns the
        number of freshly compiled programs."""
        import numpy as _host_np

        reg = _tel.registry()
        compiled = 0
        for entry in signatures:
            specs = [_cc.normalize_spec(s) for s in entry]
            host = [_host_np.zeros(shape, dtype) for shape, dtype in specs]
            batch, label = self._stage(tuple(host))
            sig = tuple((a.shape, a.dtype.name) for a in batch) + (
                (label.shape, label.dtype.name),)
            if not self.compile_guard.observe(
                    sig, lambda: _cc.aval_summary(tuple(batch) + (label,))):
                continue  # already compiled (duplicate signature)
            compiled += 1
            reg.counter("compile/warmup_compiles").inc()
            with (_tel.span("trainstep.warmup", {"signature": str(sig)})
                  if _tel._ENABLED else _tel.NULL_SPAN):
                out = self._step_fn(*self._dummy_args(batch, label))
            jax.block_until_ready(out[0])  # compile + run fully retired
        self.compile_guard.mark_steady()
        return compiled

    def _dummy_args(self, batch, label):
        """Operands for a warmup dispatch: donated slots (train values,
        optimizer state, key, t) get throwaway zero copies with the real
        placement; non-donated slots reuse the live buffers."""
        def _zeros_like(v):
            z = jnp.zeros(v.shape, v.dtype)
            sh = getattr(v, "sharding", None)
            if self._mesh is not None and sh is not None:
                z = jax.device_put(z, sh)
            return z

        dummy_train = {n: _zeros_like(v)
                       for n, v in self._train_vals.items()}
        dummy_opt = {n: tuple(_zeros_like(s) for s in st)
                     for n, st in self._opt_state.items()}
        args = (dummy_train, self._frozen_vals, dummy_opt, batch, label,
                _random.next_key(), jnp.float32(self._current_lr()),
                jnp.int32(0),
                jnp.float32(self._optimizer.rescale_grad))
        if self._scaler is not None:
            # throwaway scaler state: warmup must not advance the real one
            args = args + (self._scaler_fresh(),)
        return args

    def _scaler_fresh(self):
        """Fresh device-resident (scale, clean-streak, skip-count) state
        seeded from the host LossScaler config."""
        s = (jnp.float32(self._scaler.loss_scale), jnp.int32(0),
             jnp.int32(0))
        if self._mesh is not None:
            repl = NamedSharding(self._mesh, PartitionSpec())
            s = tuple(jax.device_put(x, repl) for x in s)
        return s

    def cache_info(self) -> dict:
        """Signature cache summary: programs held, per-signature aval
        rendering, use counts, recency (``compile_cache.RecompileGuard``
        accounting)."""
        return self.compile_guard.info()

    def _stage(self, batch_and_label):
        """Host-side staging (the slow preamble the fast path skips)."""
        *batch, label = batch_and_label
        batch = [b.data if isinstance(b, NDArray) else jnp.asarray(b)
                 for b in batch]
        label = label.data if isinstance(label, NDArray) else jnp.asarray(label)
        n = self._split_n
        if n > 1:
            # split the flat global batch into the leading axes consumed by
            # the device-side loops: (nsteps, accum, microbatch, ...).
            # jax arrays are immutable, so memoize by input identity — a
            # training loop feeding the same buffers (benchmarks, epochs
            # over a device-resident set) pays the eager reshape dispatch
            # once instead of once per call
            lead = self._lead
            memo = self._split_memo

            def _split(a, pos):
                hit = memo.get(pos)
                if hit is not None and hit[0] is a:
                    return hit[1]
                out = a.reshape(lead + (a.shape[0] // n,) + a.shape[1:])
                memo[pos] = (a, out)
                return out

            batch = [_split(b, i) for i, b in enumerate(batch)]
            label = _split(label, -1)
        sh = self._feed_shardings
        if sh is not None:
            if isinstance(sh, list):
                if len(sh) != len(batch) + 1:
                    raise MXNetError(
                        f"data_spec sequence has {len(sh)} specs but "
                        f"the step takes {len(batch)} inputs + 1 label"
                    )
                per_input = sh
            else:
                per_input = [sh] * (len(batch) + 1)
            batch = [jax.device_put(b, s)
                     for b, s in zip(batch, per_input[:-1])]
            label = jax.device_put(label, per_input[-1])
        return tuple(batch), label

    def _dispatch(self, batch, label):
        """Dispatch one pre-staged step. The pre-placed feed enters here
        directly, so this body must stay free of host conversion, dict
        rebuilds, and anything that blocks on the device —
        ``tools/mxlint.py``'s ``no-sync`` pass lints it (and
        ``__call__``)."""
        nsteps = self._steps_per_call
        sig = tuple((a.shape, a.dtype.name) for a in batch) + (
            (label.shape, label.dtype.name),)
        self.compile_guard.observe(
            sig, lambda: _cc.aval_summary(tuple(batch) + (label,)))
        self._t += nsteps
        lr = self._current_lr()
        # key and t live on device, advanced inside the jitted step — the
        # seed is drawn from mx.random state once, on the first step
        if self._key_dev is None:
            self._key_dev = _random.next_key()
            self._t_dev = jnp.int32(self._t - nsteps)
        # scalar operands cost a host->device transfer each; lr/rescale are
        # usually step-invariant, so reuse their device buffers
        rescale = self._optimizer.rescale_grad
        if self._lr_host != lr:
            self._lr_host, self._lr_dev = lr, jnp.float32(lr)
        if self._rescale_host != rescale:
            self._rescale_host = rescale
            self._rescale_dev = jnp.float32(rescale)
        args = (self._train_vals, self._frozen_vals, self._opt_state, batch,
                label, self._key_dev, self._lr_dev, self._t_dev,
                self._rescale_dev)
        if self._scaler is not None:
            if self._scaler_dev is None:
                self._scaler_dev = self._scaler_fresh()
            args = args + (self._scaler_dev,)
        if self._last_avals is None:
            # stash operand avals ONCE so cost_analysis() can re-lower the
            # exact program later (donated buffers are consumed, so keep
            # shapes only; shapes cannot change without recompiling
            # _step_fn anyway)
            self._last_avals = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
            # the same with each operand's real sharding, for
            # compiled_text(); uncommitted operands (host scalars, the
            # key) follow the committed ones and carry none of their own
            self._last_sharded_avals = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=a.sharding if a.committed else None), args)
        if self._scaler is not None:
            (L, new_vals, self._opt_state, self._key_dev, self._t_dev, aux,
             self._scaler_dev) = self._step_fn(*args)
        else:
            L, new_vals, self._opt_state, self._key_dev, self._t_dev, aux = \
                self._step_fn(*args)
        self._train_vals = new_vals
        for n, v in aux.items():
            if n in self._train_set:
                self._train_vals[n] = v
            else:
                self._frozen_vals[n] = v
        return NDArray(L)

    def cost_analysis(self):
        """XLA ``cost_analysis`` of the exact compiled step program
        (flops, bytes accessed) — the honest-MFU/roofline denominator.
        Requires at least one prior call; re-lowers from the stashed
        operand avals (compilation-cache hit when nothing changed)."""
        avals = getattr(self, "_last_avals", None)
        if avals is None:
            raise MXNetError("call the step once before cost_analysis()")
        c = self._step_fn.lower(*avals).compile().cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        return c

    def compiled_text(self) -> str:
        """Optimized HLO of the step program last dispatched, lowered
        with its operands' real shardings: a Pallas kernel the chip's
        compiler took shows as ``tpu_custom_call`` (an interpreted one
        does not), and a program sharded over a mesh shows its
        collectives. Requires one prior call."""
        if self._last_avals is None:
            raise MXNetError("call the step once before compiled_text()")
        return self._step_fn.lower(
            *self._last_sharded_avals).compile().as_text()

    def _current_lr(self):
        opt = self._optimizer
        if opt.lr_scheduler is not None:
            return opt.lr_scheduler(self._t)
        return opt.lr

    # ------------------------------------------------------------- sync out
    def sync_params(self):
        """Write device values back into the net's Parameters (for eval /
        checkpointing through the normal Gluon APIs)."""
        vals = self._values  # one merged snapshot, not one per param
        for n, p in self._params:
            p._data._rebind(vals[n])

    @property
    def loss_scale(self):
        """Current dynamic loss scale (1.0 without float16 AMP). Reads
        device state — cold path only, never call per step."""
        if self._scaler is None:
            return 1.0
        if self._scaler_dev is None:
            return float(self._scaler.loss_scale)
        return float(self._scaler_dev[0])

    def scaler_stats(self) -> dict:
        """Device-carried scaler accounting (host sync; cold path):
        current scale, consecutive clean steps, total skipped steps."""
        if self._scaler is None:
            return {"loss_scale": 1.0, "clean_streak": 0,
                    "skipped_steps": 0}
        if self._scaler_dev is None:
            return {"loss_scale": float(self._scaler.loss_scale),
                    "clean_streak": 0, "skipped_steps": 0}
        s, good, skips = self._scaler_dev
        return {"loss_scale": float(s), "clean_streak": int(good),
                "skipped_steps": int(skips)}

    # ------------------------------------------------------- memory planning
    def memory_analysis(self, signature=None) -> dict:
        """XLA ``memory_analysis`` of the exact compiled step executable —
        the HBM planning numbers: argument/output/temp/alias bytes plus a
        peak estimate (``argument + output + temp - alias``; donated
        buffers appear in ``alias_bytes`` and are not double-counted).

        With no argument, analyzes the signature of the last dispatch.
        Pass one warmup-style signature (per-array specs for ``(input0,
        ..., label)``, global unsplit shapes — see ``warmup``) to cost a
        HYPOTHETICAL batch without running or materializing it;
        ``plan_batch``/``tools/hbm_plan.py`` walk bucket menus this way.
        Re-lowering an already-built program is a compilation-cache hit.
        """
        if signature is None:
            avals = getattr(self, "_last_avals", None)
            if avals is None:
                raise MXNetError(
                    "call the step once (or pass a signature) before "
                    "memory_analysis()")
        else:
            avals = self._signature_avals(signature)
        compiled = self._step_fn.lower(*avals).compile()
        ma = compiled.memory_analysis()
        if ma is None:
            raise MXNetError(
                "this backend exposes no compiled memory analysis")
        out = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
        out["peak_bytes_estimate"] = (
            out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
            - out["alias_bytes"])
        if self._mesh is not None:
            # XLA's analysis reports LOGICAL (global) sizes on this path;
            # the mesh splits arguments/temps across its devices, so one
            # device's working set is ~peak/mesh.size — the figure
            # plan_batch bisects against the per-device HBM budget
            n = int(self._mesh.size)
            out["mesh_devices"] = n
            out["peak_bytes_per_shard"] = out["peak_bytes_estimate"] // n
        limit = _tel.hbm_limit_bytes()
        out["hbm_limit_bytes"] = limit
        peak = out.get("peak_bytes_per_shard",
                       out["peak_bytes_estimate"])
        out["hbm_headroom_bytes"] = (
            limit - peak if limit is not None else None)
        return out

    def _signature_avals(self, signature):
        """Abstract operand avals for ONE global batch signature: the
        batch/label specs get the leading step/accum split axes exactly
        as ``_stage`` would apply them; every other operand's aval comes
        from the live state."""
        specs = [_cc.normalize_spec(s) for s in signature]
        n, lead = self._split_n, self._lead

        def _split_aval(shape, dtype):
            if n > 1:
                if shape[0] % n:
                    raise MXNetError(
                        f"signature batch dim {shape[0]} must divide the "
                        f"leading split factor {n} "
                        "(steps_per_call * grad_accum)")
                shape = lead + (shape[0] // n,) + tuple(shape[1:])
            return jax.ShapeDtypeStruct(tuple(shape), dtype)

        arrs = [_split_aval(sh, dt) for sh, dt in specs]
        batch, label = tuple(arrs[:-1]), arrs[-1]

        def aval(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        if getattr(self, "_key_dev", None) is not None:
            key_aval = aval(self._key_dev)
        else:
            # shape/dtype of the key the first dispatch will draw, without
            # advancing any RNG state (impl set by MXNET_TPU_PRNG)
            key_aval = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        scalar_f = jax.ShapeDtypeStruct((), jnp.float32)
        scalar_i = jax.ShapeDtypeStruct((), jnp.int32)
        args = (
            jax.tree.map(aval, self._train_vals),
            jax.tree.map(aval, self._frozen_vals),
            jax.tree.map(aval, self._opt_state),
            batch, label, key_aval, scalar_f, scalar_i, scalar_f,
        )
        if self._scaler is not None:
            args = args + ((scalar_f, scalar_i, scalar_i),)
        return args

    # ------------------------------------------------------------ state dict
    def _struct_names(self):
        """global param name -> structural name ("0.weight"): stable
        across processes, unlike the auto-incrementing global prefix
        (hybridsequential0_...), mirroring ``Block.save_parameters``."""
        cached = getattr(self, "_struct_cache", None)
        if cached is not None:
            return cached
        byid = {id(p): n for n, p in self._params}
        out = {}
        for sname, p in self._net._collect_params_with_prefix().items():
            g = byid.get(id(p))
            if g is not None and g not in out:
                out[g] = sname
        for n, p in self._params:  # safety: anything structurally hidden
            out.setdefault(n, n)
        self._struct_cache = out
        return out

    def state_dict(self) -> dict:
        """Full resumable state: parameter values, optimizer moments, the
        device-carried PRNG key and step counter — keyed by STRUCTURAL
        parameter names so a fresh process (different global prefixes)
        restores cleanly. The reference's equivalent contract is
        Trainer.save_states + net params (``python/mxnet/gluon/trainer.py``
        [unverified]); here ONE dict covers the whole fused step so a
        killed run loses nothing."""
        s = self._struct_names()
        # snapshot with fresh buffers (sharding preserved): the live ones
        # are donated to XLA by the next __call__, which would leave the
        # returned dict holding deleted arrays
        cp = jnp.copy
        sd = {
            "values": {s[n]: cp(v) for n, v in self._values.items()},
            "opt_state": {s[n]: tuple(cp(x) for x in st)
                          for n, st in self._opt_state.items()},
            "t_host": self._t,
        }
        if getattr(self, "_key_dev", None) is not None:
            sd["key"] = cp(self._key_dev)
            sd["t_dev"] = cp(self._t_dev)
        if getattr(self, "_scaler_dev", None) is not None:
            sd["scaler"] = tuple(cp(x) for x in self._scaler_dev)
        return sd

    def load_state_dict(self, sd: dict):
        """Restore ``state_dict()`` output, re-placing every array onto
        THIS step's mesh/shardings (resharding from a different layout is
        fine — device_put moves arbitrary source placements)."""
        def _place(name, v):
            if self._param_sharding is not None:
                return _sharding.device_put_donatable(
                    v, self._param_sharding(name))
            return jnp.asarray(v)

        s = self._struct_names()
        gname = {v: k for k, v in s.items()}
        vals = sd["values"]
        missing = [n for n, _ in self._params if s[n] not in vals]
        if missing:
            raise MXNetError(
                f"state_dict missing parameters: {missing[:5]}")
        self._values = {gname[sn]: _place(gname[sn], v)
                        for sn, v in vals.items() if sn in gname}
        self._opt_state = {
            gname[sn]: tuple(_place(gname[sn], x) for x in st)
            for sn, st in sd["opt_state"].items() if sn in gname
        }
        self._t = int(sd["t_host"])
        if "key" in sd:
            repl = (NamedSharding(self._mesh, PartitionSpec())
                    if self._mesh is not None else None)

            def _repl(v):
                v = jnp.asarray(v)
                return _sharding.device_put_donatable(v, repl) \
                    if repl is not None else v

            self._key_dev = _repl(sd["key"])
            self._t_dev = _repl(sd["t_dev"])
        else:
            self._key_dev = None
            self._t_dev = None
        if self._scaler is not None and "scaler" in sd:
            repl2 = (NamedSharding(self._mesh, PartitionSpec())
                     if self._mesh is not None else None)
            self._scaler_dev = tuple(
                jax.device_put(jnp.asarray(x), repl2) if repl2 is not None
                else jnp.asarray(x) for x in sd["scaler"])
        # derived scalar memos are stale now
        self._lr_host = None
        self._rescale_host = None

    # ------------------------------------------------------- sharded on-disk
    def _flat_state(self):
        s = self._struct_names()
        flat = {"meta/t_dev": getattr(self, "_t_dev", None),
                "meta/key": getattr(self, "_key_dev", None)}
        flat = {k: v for k, v in flat.items() if v is not None}
        if getattr(self, "_scaler_dev", None) is not None:
            for i, x in enumerate(self._scaler_dev):
                flat[f"meta/scaler{i}"] = x
        for n, v in self._values.items():
            flat[f"values/{s[n]}"] = v
        for n, st in self._opt_state.items():
            for i, x in enumerate(st):
                flat[f"opt/{i}/{s[n]}"] = x
        return flat

    def save_checkpoint(self, directory, step=None):
        """Write a sharded, committed checkpoint of the full step state.

        Every process writes only its addressable shards (no gather — a
        TP-sharded weight is never materialized whole anywhere); call
        from ALL processes. Layout/protocol: ``checkpoint_sharded``."""
        from .. import checkpoint_sharded as cs

        sub = directory if step is None else \
            f"{directory}/step_{int(step)}"
        s = self._struct_names()
        return cs.save_sharded(
            sub, self._flat_state(),
            extra={"t_host": self._t,
                   "train_names": [s[n] for n in self._train_names]})

    def load_checkpoint(self, directory, step=None):
        """Restore ``save_checkpoint`` output onto THIS step's mesh.

        The saved mesh/process layout may differ: each process assembles
        exactly the shards the current placement makes addressable."""
        from .. import checkpoint_sharded as cs
        import json as _json
        import os as _os

        sub = directory if step is None else \
            f"{directory}/step_{int(step)}"
        with open(_os.path.join(sub, "ckpt_meta.json")) as f:
            meta = _json.load(f)

        gname = {v: k for k, v in self._struct_names().items()}

        def sharding_for(flat_name):
            if flat_name.startswith(("values/", "opt/")):
                pname = flat_name.split("/", 1)[1]
                if flat_name.startswith("opt/"):
                    pname = pname.split("/", 1)[1]
                if self._param_sharding is not None:
                    return self._param_sharding(gname.get(pname, pname))
                return None
            if self._mesh is not None:
                return NamedSharding(self._mesh, PartitionSpec())
            return None

        flat = cs.load_sharded(sub, sharding_for)
        sd = {"values": {}, "opt_state": {},
              "t_host": meta["extra"]["t_host"]}
        nstates = {}
        scaler_parts = {}
        for k, v in flat.items():
            if k.startswith("values/"):
                sd["values"][k[7:]] = v
            elif k.startswith("opt/"):
                i, pname = k[4:].split("/", 1)
                nstates.setdefault(pname, {})[int(i)] = v
            elif k == "meta/key":
                sd["key"] = v
            elif k == "meta/t_dev":
                sd["t_dev"] = v
            elif k.startswith("meta/scaler"):
                scaler_parts[int(k[len("meta/scaler"):])] = v
        if scaler_parts:
            sd["scaler"] = tuple(scaler_parts[i]
                                 for i in sorted(scaler_parts))
        sd["opt_state"] = {
            n: tuple(st[i] for i in sorted(st))
            for n, st in nstates.items()
        }
        for n in meta["extra"]["train_names"]:
            sd["opt_state"].setdefault(n, ())
        if "key" not in sd and "t_dev" in sd:
            del sd["t_dev"]
        self.load_state_dict(sd)
        return meta.get("extra", {})

    # --------------------------------------------------------- Trainer interop
    def export_trainer_states(self, trainer):
        """Hand this step's optimizer moments to a Gluon ``Trainer`` over
        the SAME parameters, so training can continue on the eager
        per-param path (reference Trainer.save_states contract). Call
        ``sync_params()`` separately for the weights."""
        name_of = {id(p): n for n, p in self._params}
        if not trainer._kv_initialized:
            trainer._init_kvstore()
        updater = trainer._updaters[0]
        opt = updater.optimizer
        vals = self._values  # one merged snapshot, not one per param
        for i, p in enumerate(trainer._params):
            n = name_of.get(id(p))
            if n is None or n not in self._opt_state:
                continue
            if getattr(opt, "multi_precision", False) and \
                    vals[n].dtype == jnp.float16:
                # Trainer's multi-precision state is (inner_state,
                # fp32_master) — a flat moment tuple here would be
                # unpacked as (state, master) and DESTROY the weight.
                # TrainStep's AMP scheme (compute_dtype) keeps f32
                # masters itself, so this handoff has no meaning.
                raise MXNetError(
                    "export_trainer_states: multi_precision Trainer over "
                    "fp16 params is not interoperable with TrainStep "
                    "state; use a non-multi_precision optimizer or "
                    "TrainStep(compute_dtype=...) AMP")
            st = tuple(NDArray(s.astype(vals[n].dtype))
                       for s in self._opt_state[n])
            if len(st) == 0:
                updater.states[i] = None
            elif len(st) == 1:
                updater.states[i] = st[0]
            else:
                updater.states[i] = st
            updater.states_synced[i] = True
            opt._index_update_count[i] = self._t
        opt.num_update = max(opt.num_update, self._t)

    def import_trainer_states(self, trainer):
        """Adopt moments from a ``Trainer`` that trained the SAME
        parameters (the reverse direction: eager warmup, then switch to
        the fused sharded step)."""
        name_of = {id(p): n for n, p in self._params}
        updater = trainer._updaters[0]
        for i, p in enumerate(trainer._params):
            n = name_of.get(id(p))
            if n is None or n not in self._opt_state:
                continue
            st = updater.states.get(i)
            if st is None:
                continue
            st = st if isinstance(st, tuple) else (st,)
            if any(isinstance(x, (tuple, list)) for x in st):
                # (inner_state, fp32_master) — multi_precision layout
                raise MXNetError(
                    "import_trainer_states: multi_precision Trainer "
                    "states ((state, master) pairs) are not supported; "
                    "TrainStep keeps its own f32 masters via "
                    "compute_dtype AMP")
            want = len(self._opt_state[n])
            if len(st) != want:
                raise MXNetError(
                    f"optimizer state arity mismatch for {n}: trainer has "
                    f"{len(st)}, step expects {want} (same optimizer?)")
            placed = []
            for s_new, s_old in zip(st, self._opt_state[n]):
                v = s_new.data if isinstance(s_new, NDArray) else \
                    jnp.asarray(s_new)
                v = v.astype(s_old.dtype)
                if self._param_sharding is not None:
                    v = _sharding.device_put_donatable(
                        v, self._param_sharding(n))
                placed.append(v)
            self._opt_state[n] = tuple(placed)
        t = int(trainer._optimizer.num_update)
        if t:
            self._t = t
            if getattr(self, "_t_dev", None) is not None:
                self._t_dev = jnp.asarray(self._t_dev * 0 + t)

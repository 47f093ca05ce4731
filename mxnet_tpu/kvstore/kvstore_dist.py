"""Distributed KVStore over the jax coordination service (reference:
``src/kvstore/kvstore_dist.h`` + ``3rdparty/ps-lite`` [unverified]).

Architecture swap (SURVEY.md §5): the reference ran a ZMQ parameter server
(scheduler + S servers + W workers, server-side optimizer). Here the only
hand-written distributed piece is rendezvous: `jax.distributed.initialize`
(coordinator = ps-lite scheduler analogue) forms one global device mesh, and
gradient sync is an XLA `psum` over the mesh's 'data' axis — compiled into
the step, riding ICI/DCN. Push/pull therefore degenerate to the local path
plus a cross-process all-reduce for eager (non-jitted) callers.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .. import telemetry as _tel
from .kvstore import KVStore, KVStoreBase

__all__ = ["KVStoreDist"]


@KVStoreBase.register
class KVStoreDist(KVStore):
    """Multi-host data-parallel store."""

    def __init__(self, kv_type="dist_sync"):
        super().__init__(kv_type)
        self._rank = 0
        self._num_workers = 1
        self._initialized_dist = False
        # dist_async: bounded-staleness mode.
        # The reference's async let each worker hit the parameter server
        # without waiting; with collectives as the only transport, the
        # TPU-native analogue is LOCAL apply (push returns without any
        # cross-host wait) plus a parameter-averaging collective every
        # `staleness_bound` pushes per key — local SGD / periodic
        # averaging, which bounds divergence exactly the way the
        # reference's staleness bound did. Requires updater-on-store
        # (like the reference's server-side updater) and the SPMD
        # contract that workers push each key at the same cadence (the
        # reconcile is a collective; mismatched cadence hangs like any
        # mismatched collective).
        self._async = kv_type == "dist_async"
        self._push_counts: dict = {}
        self._warned_compress = False
        from ..base import env_int

        self._staleness_bound = max(1, env_int(
            "MXTPU_ASYNC_STALENESS_BOUND", 8))
        self._maybe_init_dist()

    def _maybe_init_dist(self):
        """Join the coordinator if launch env vars are present (set by
        ``tools/launch.py``; reference used DMLC_PS_ROOT_URI/DMLC_ROLE)."""
        coord = os.environ.get("MXNET_TPU_COORDINATOR")
        nproc = os.environ.get("MXNET_TPU_NUM_PROCS")
        pid = os.environ.get("MXNET_TPU_PROC_ID")
        if coord and nproc and pid and not self._initialized_dist:
            from ..parallel import init_process_group

            init_process_group(
                coordinator_address=coord,
                num_processes=int(nproc),
                process_id=int(pid),
            )
            self._initialized_dist = True
        self._rank = jax.process_index()
        self._num_workers = jax.process_count()

    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._num_workers

    def _warn_if_mesh_owns_sync(self):
        """One-time redundancy alarm: when the process-global mesh spans
        every worker, gradient sync already happens IN-GRAPH (GSPMD psum
        inside the jitted step) — an eager host push on top of it
        double-sums. ``Trainer._allreduce_grads`` skips automatically;
        direct kvstore users get this warning once."""
        if getattr(self, "_warned_mesh_sync", False):
            return
        from ..parallel import sharding as _shard

        if _shard.mesh_spans_processes():
            self._warned_mesh_sync = True
            import warnings

            warnings.warn(
                "KVStore.push with a process-global mesh spanning all "
                "workers: gradient sync is in-graph (mesh psum); the "
                "host allreduce is redundant and double-sums if the "
                "grads were already synced. Build the step on the mesh "
                "and drop the push/pull loop.", RuntimeWarning,
                stacklevel=3)

    def _push_impl(self, key, value, priority=0):
        self._warn_if_mesh_owns_sync()
        keys = _l(key)
        for k, vals in zip(keys, self._grouped(keys, value)):
            k = str(k)
            if k not in self._data:
                raise MXNetError(f"key {k} not initialized in kvstore")
            datas = [v.data for v in vals]
            if self._async and self._updater is not None \
                    and self._num_workers > 1:
                self._push_async(k, datas)
                continue
            # reference worker order (``kvstore_dist.h`` [unverified]):
            # aggregate the local device replicas FIRST, then compress
            # once per worker, then ship — so the wire carries one
            # compressed gradient per worker
            agg = datas[0]
            for v in datas[1:]:
                agg = agg + v
            if self._compression is not None and self._num_workers > 1:
                agg = self._cross_host_sum_compressed(k, agg)
            else:
                if self._compression is not None:
                    agg = self._compression.compress((k, "w"), agg)
                agg = self._cross_host_sum(agg)
            if self._updater is not None:
                self._updater(int(k) if k.isdigit() else k, NDArray(agg),
                              self._data[k])
            else:
                self._data[k]._rebind(agg)

    def _push_async(self, k, datas):
        """Bounded-staleness push: apply the LOCAL gradient immediately
        (no cross-host wait — the worker runs ahead on its own replica,
        reads are allowed to be stale), then every ``staleness_bound``
        pushes reconcile the replicas with one parameter-averaging
        collective. Ref: dist_async server-side updater + staleness
        bound (``src/kvstore/kvstore_dist_server.h`` [unverified])."""
        agg = datas[0]
        for v in datas[1:]:
            agg = agg + v
        if self._compression is not None and not self._warned_compress:
            # the local apply transmits nothing, so quantizing it would
            # add error while saving zero wire bytes; the reconcile ships
            # full weights (averaging quantized weights is not the
            # gradient-compression contract). Signal instead of silently
            # degrading.
            self._warned_compress = True
            import warnings

            warnings.warn(
                "gradient compression has no wire transfer to compress "
                "under dist_async local-apply; ignored (the periodic "
                "reconcile ships full-precision parameters)",
                RuntimeWarning, stacklevel=3)
        self._updater(int(k) if k.isdigit() else k, NDArray(agg),
                      self._data[k])
        c = self._push_counts.get(k, 0) + 1
        self._push_counts[k] = c
        if c % self._staleness_bound == 0:
            w = self._data[k].data
            avg = self._cross_host_sum(w) / self._num_workers
            self._data[k]._rebind(avg)

    def _cross_host_sum_compressed(self, k, agg):
        """Real wire-byte 2-bit transfer: quantize + error-feedback on the
        worker-local aggregate, all-gather the PACKED uint8 codes (16x
        fewer wire bytes than f32), dequantize + sum after transfer
        (reference: server-side dequantize in ``DataHandleEx``)."""
        from jax.experimental import multihost_utils

        from .compression import pack_2bit, quantize_2bit, unpack_2bit

        comp = self._compression
        rkey = (k, "w")
        r = comp._residuals.get(rkey)
        if r is None or r.shape != agg.shape:
            r = jnp.zeros_like(agg)
        q, new_r = quantize_2bit(agg + r.astype(agg.dtype), comp.threshold)
        comp._residuals[rkey] = new_r
        packed, n = pack_2bit(q, comp.threshold)
        gathered = multihost_utils.process_allgather(packed)  # (W, bytes)
        # bookkeeping for tests/telemetry: logical wire bytes this push
        self.last_push_wire_bytes = int(gathered.shape[-1])
        if _tel._ENABLED:
            _tel.registry().counter("kvstore/allreduce_wire_bytes").inc(
                self.last_push_wire_bytes)
        total = None
        for w in range(gathered.shape[0]):
            dq = unpack_2bit(gathered[w], n, comp.threshold, agg.dtype)
            total = dq if total is None else total + dq
        return total.reshape(agg.shape)

    def _cross_host_sum(self, arr):
        if self._num_workers == 1:
            return arr
        # eager cross-process psum over all global devices: each process
        # contributes its replica; result is identical on every host
        from ..parallel import all_reduce_eager

        if not _tel._ENABLED:
            return all_reduce_eager(arr)
        import time as _time

        t0 = _time.perf_counter()
        with _tel.span("kvstore.allreduce",
                       {"bytes": int(getattr(arr, "nbytes", 0) or 0)}):
            out = all_reduce_eager(arr)
        reg = _tel.registry()
        reg.histogram("kvstore/allreduce_time_s").observe(
            _time.perf_counter() - t0)
        reg.counter("kvstore/allreduce_bytes").inc(
            int(getattr(arr, "nbytes", 0) or 0))
        return out

    def barrier(self):
        super().barrier()
        if self._num_workers > 1:
            # dummy collective as a barrier
            self._cross_host_sum(jnp.zeros(()))


def _l(x):
    return x if isinstance(x, (list, tuple)) else [x]

"""Parallelism: device mesh, shardings, collectives (no reference analogue —
this replaces ``src/kvstore/comm*.h``, NCCL and ps-lite with mesh + GSPMD,
SURVEY.md §2.3).

Axes convention (the "How to Scale Your Model" recipe):
  data  — data parallel (batch sharded; grad psum over ICI)
  model — tensor parallel (weight matrices sharded)
  seq   — sequence/context parallel (ring attention neighbors)
  pipe  — pipeline stages

Use ``make_mesh`` to build a mesh over all visible devices, ``with_sharding``
to annotate arrays, and ``data_parallel_step``/``train_step`` builders in
``mxnet_tpu.parallel.step`` for whole-model jitted training steps.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as _np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError
from ..ndarray.ndarray import NDArray

__all__ = [
    "Mesh",
    "PartitionSpec",
    "NamedSharding",
    "make_mesh",
    "current_mesh",
    "set_mesh",
    "mesh_scope",
    "shard",
    "replicate",
    "with_sharding_constraint",
    "all_reduce_eager",
    "init_process_group",
    "local_mesh_axes",
]

_STATE = threading.local()


def make_mesh(axes: Optional[Dict[str, int]] = None, devices=None) -> Mesh:
    """Build a named mesh. ``axes`` maps axis name -> size; total must cover
    the device count (one axis 'data' over all devices by default)."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axes is None:
        axes = {"data": n}
    sizes = list(axes.values())
    total = int(_np.prod(sizes))
    if total != n:
        raise MXNetError(
            f"mesh axes {axes} cover {total} devices but {n} are visible"
        )
    dev_array = _np.array(devices).reshape(sizes)
    return Mesh(dev_array, tuple(axes.keys()))


def set_mesh(mesh: Optional[Mesh]):
    _STATE.mesh = mesh


def current_mesh() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


@contextlib.contextmanager
def mesh_scope(mesh: Mesh):
    prev = current_mesh()
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


def local_mesh_axes() -> Sequence[str]:
    mesh = current_mesh()
    return mesh.axis_names if mesh is not None else ()


def _unwrap(x):
    return x.data if isinstance(x, NDArray) else x


def shard(array, spec: PartitionSpec, mesh: Optional[Mesh] = None):
    """Place an array on the mesh with the given PartitionSpec."""
    mesh = mesh or current_mesh()
    if mesh is None:
        raise MXNetError("no active mesh: call set_mesh/make_mesh first")
    data = jax.device_put(_unwrap(array), NamedSharding(mesh, spec))
    return NDArray(data) if isinstance(array, NDArray) else data


def replicate(array, mesh: Optional[Mesh] = None):
    return shard(array, PartitionSpec(), mesh)


def with_sharding_constraint(x, spec: PartitionSpec):
    """In-jit sharding annotation (GSPMD hint); passthrough outside jit or
    without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    data = _unwrap(x)
    out = jax.lax.with_sharding_constraint(data, NamedSharding(mesh, spec))
    return NDArray(out) if isinstance(x, NDArray) else out


def all_reduce_eager(arr):
    """Cross-process sum of a replicated array (eager path used by the
    dist KVStore facade; the jitted train step uses in-program psum)."""
    arr = _unwrap(arr)
    if jax.process_count() == 1:
        return arr
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(arr)
    return jnp.sum(gathered, axis=0)


def init_process_group(coordinator_address: str, num_processes: int,
                       process_id: int, local_device_ids=None):
    """Join the cluster coordinator (reference analogue: ps-lite scheduler
    rendezvous in ``ps::Postoffice::Start`` [unverified]).

    The XLA CPU client only forms a multi-node cluster when a cross-process
    collectives implementation is selected (localhost multi-process testing,
    the reference's nightly dist tests), so pick gloo before the backend is
    instantiated — harmless for TPU runs, where the TPU client syncs through
    the coordination service itself."""
    if jax.distributed.is_initialized():
        return  # idempotent: a second KVStore/TrainStep must not re-join
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )


from . import sharding  # noqa: E402  (SPMD sharding spine)
from .sharding import (  # noqa: E402
    ShardingRules, global_mesh, set_global_mesh, make_global_mesh,
)
from .step import (  # noqa: E402  (public API; needs defs above)
    TrainStep, DeviceBatch, plan_batch, hbm_budget_bytes,
)
from .infer import InferStep  # noqa: E402  (inference twin of TrainStep)

__all__ += ["TrainStep", "DeviceBatch", "plan_batch", "hbm_budget_bytes",
            "InferStep", "sharding", "ShardingRules", "global_mesh",
            "set_global_mesh", "make_global_mesh"]

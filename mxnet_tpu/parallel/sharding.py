"""SPMD sharding spine: process-global device Mesh + declarative rules.

This module owns the answers to "which devices?" and "how is every array
placed?" for the whole execution layer — the GSPMD-native replacement for
the reference's host-side data parallelism (KVStore push/pull per step,
``src/kvstore/comm*.h``):

- **Process-global Mesh.** ``global_mesh()`` is the mesh every
  ``TrainStep``/``InferStep`` built without an explicit ``mesh=`` picks
  up. Configure it programmatically (``set_global_mesh``) or from the
  environment: ``MXTPU_MESH=data=4`` / ``2x2`` / ``auto``. CPU rigs
  simulate any mesh via
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the test
  suite's 8-device virtual mesh).

- **Declarative ShardingRules.** One object maps every pytree the jitted
  steps carry — parameters, optimizer state, batch inputs — to
  ``NamedSharding``/``PartitionSpec``: replicated params (classic data
  parallel), FSDP/ZeRO-style parameter+optimizer sharding (each param's
  largest divisible axis sharded over ``fsdp_axis``, so a model larger
  than one chip's HBM trains and serves), and explicit name-pattern
  rules for tensor-parallel placements. Presets resolve from strings
  (``'fsdp'``, ``'replicated'``, ``'fsdp:model'``) or from the
  ``MXTPU_SHARDING`` env var.

- **Placement + accounting helpers.** ``place_params`` puts a value tree
  on the mesh under the rules; ``shard_summary`` reports total vs
  per-shard parameter bytes and an allreduce/allgather traffic estimate,
  publishing the ``shard/`` telemetry family
  (``mx.telemetry.report()`` / ``tools/telemetry_report.py``).

Silent-fallback honesty: ``param_explain`` returns WHY a param got its
spec (matched rule, fsdp, or a replication fallback with the reason);
``tools/mxlint.py`` (pass ``sharding-placement``) lints that every param
entering the jitted step carries its declared sharding and that no rule
silently degraded to full replication.

Env knobs: ``MXTPU_MESH`` (mesh axes), ``MXTPU_SHARDING`` (rules
preset), ``MXTPU_FSDP_MIN_SIZE`` (elements below which a param stays
replicated, default 1024).
"""

from __future__ import annotations

import os
import re
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as _np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError
from .. import telemetry as _tel

__all__ = [
    "ShardingRules",
    "device_put_donatable",
    "parse_mesh_spec",
    "make_global_mesh",
    "global_mesh",
    "set_global_mesh",
    "reset_global_mesh",
    "mesh_shape_str",
    "mesh_spans_processes",
    "default_rules",
    "place_params",
    "shard_summary",
    "publish_shard_metrics",
]

DEFAULT_FSDP_MIN_SIZE = 1024


def _fsdp_min_size_default() -> int:
    v = os.environ.get("MXTPU_FSDP_MIN_SIZE", "").strip()
    try:
        return int(v) if v else DEFAULT_FSDP_MIN_SIZE
    except ValueError:
        return DEFAULT_FSDP_MIN_SIZE


# ------------------------------------------------------------- global mesh
def parse_mesh_spec(spec: Optional[str]) -> Optional[Dict[str, int]]:
    """Parse a ``MXTPU_MESH``-style mesh spec into ``{axis: size}``.

    Accepted forms: ``"data=4"`` / ``"data=2,model=2"`` (explicit axes),
    ``"4"`` (one ``data`` axis), ``"2x2"`` (``data`` x ``model``),
    ``"auto"``/``"data"`` (one ``data`` axis over ALL visible devices,
    size resolved at mesh build). ``None``/``""``/``"0"``/``"off"`` ->
    None (no mesh)."""
    if spec is None:
        return None
    s = str(spec).strip().lower()
    if s in ("", "0", "off", "none", "false"):
        return None
    if s in ("auto", "data"):
        return {"data": -1}
    if "=" in s:
        axes: Dict[str, int] = {}
        for part in s.split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise MXNetError(f"bad mesh spec segment {part!r} in {spec!r}")
            name, _, size = part.partition("=")
            axes[name.strip()] = int(size)
        if not axes:
            raise MXNetError(f"empty mesh spec {spec!r}")
        return axes
    if "x" in s:
        d, _, m = s.partition("x")
        return {"data": int(d), "model": int(m)}
    return {"data": int(s)}


def make_global_mesh(axes: Union[None, str, Dict[str, int]] = None,
                     devices=None) -> Mesh:
    """Build a mesh from a spec, using the FIRST ``prod(sizes)`` visible
    devices — so a 4-device mesh is constructible on the 8-device test
    rig (the "forced 4-device CPU mesh" of the sharding tests). An axis
    size of ``-1`` absorbs all remaining devices."""
    if isinstance(axes, str) or axes is None:
        axes = parse_mesh_spec(axes if axes is not None
                               else os.environ.get("MXTPU_MESH"))
    if axes is None:
        axes = {"data": -1}
    if devices is None:
        devices = jax.devices()
    sizes = dict(axes)
    fill = [k for k, v in sizes.items() if v == -1]
    if len(fill) > 1:
        raise MXNetError(f"at most one mesh axis may be -1, got {axes}")
    fixed = 1
    for k, v in sizes.items():
        if v != -1:
            if v < 1:
                raise MXNetError(f"mesh axis {k} must be >= 1, got {v}")
            fixed *= v
    if fill:
        if len(devices) % fixed:
            raise MXNetError(
                f"mesh axes {axes}: {len(devices)} devices not divisible "
                f"by the fixed axes product {fixed}")
        sizes[fill[0]] = len(devices) // fixed
    total = 1
    for v in sizes.values():
        total *= v
    if total > len(devices):
        raise MXNetError(
            f"mesh axes {sizes} need {total} devices but only "
            f"{len(devices)} are visible (CPU rigs: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={total})")
    dev_array = _np.array(devices[:total]).reshape(list(sizes.values()))
    return Mesh(dev_array, tuple(sizes.keys()))


_GLOBAL_LOCK = threading.Lock()
_GLOBAL = {"mesh": None, "explicit": False, "env_checked": False}


def set_global_mesh(mesh: Optional[Mesh]):
    """Pin the process-global mesh every step built without ``mesh=``
    adopts. ``None`` pins "no mesh" (overriding ``MXTPU_MESH``)."""
    with _GLOBAL_LOCK:
        _GLOBAL["mesh"] = mesh
        _GLOBAL["explicit"] = True
    if mesh is not None:
        _tel.set_info(mesh_shape=mesh_shape_str(mesh))


def reset_global_mesh():
    """Forget any pinned/env-derived global mesh (tests; re-reads
    ``MXTPU_MESH`` on the next ``global_mesh()`` call)."""
    with _GLOBAL_LOCK:
        _GLOBAL["mesh"] = None
        _GLOBAL["explicit"] = False
        _GLOBAL["env_checked"] = False


def global_mesh() -> Optional[Mesh]:
    """The process-global mesh: the one ``set_global_mesh`` pinned, else
    one built from ``MXTPU_MESH`` on first call, else None."""
    with _GLOBAL_LOCK:
        if _GLOBAL["explicit"]:
            return _GLOBAL["mesh"]
        if not _GLOBAL["env_checked"]:
            _GLOBAL["env_checked"] = True
            axes = parse_mesh_spec(os.environ.get("MXTPU_MESH"))
            if axes is not None:
                _GLOBAL["mesh"] = make_global_mesh(axes)
        return _GLOBAL["mesh"]


def mesh_shape_str(mesh: Optional[Mesh]) -> Optional[str]:
    """``"data=4,model=2"`` rendering for telemetry/bench rows."""
    if mesh is None:
        return None
    return ",".join(f"{k}={v}" for k, v in mesh.shape.items())


def mesh_spans_processes(mesh: Optional[Mesh] = None) -> bool:
    """True when the (given or global) mesh covers every process in a
    multi-process run — in-graph collectives then OWN cross-process
    gradient sync, and the host-side KVStore allreduce loop is redundant
    (``Trainer._allreduce_grads`` skips it)."""
    if mesh is None:
        mesh = global_mesh()
    if mesh is None:
        return False
    nproc = jax.process_count()
    if nproc <= 1:
        return False
    try:
        procs = {d.process_index for d in mesh.devices.flat}
    except Exception:  # noqa: BLE001 - exotic device objects
        return False
    return len(procs) >= nproc


# ----------------------------------------------------------- sharding rules
def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def fsdp_partition_spec(shape, axis: str, axis_size: int) -> PartitionSpec:
    """FSDP placement for one param: shard the LARGEST dim divisible by
    ``axis_size`` over ``axis`` (ties -> first). ``P()`` when no dim
    divides — the caller decides whether that fallback is acceptable."""
    best, best_dim = -1, None
    for i, d in enumerate(shape):
        d = int(d)
        if d >= axis_size and d % axis_size == 0 and d > best:
            best, best_dim = d, i
    if best_dim is None:
        return PartitionSpec()
    parts = [None] * len(shape)
    parts[best_dim] = axis
    # drop trailing Nones: jax canonicalizes them away in out_shardings,
    # so the declared spec must match the canonical form bit-for-bit
    return PartitionSpec(*parts[:best_dim + 1])


class ShardingRules:
    """Declarative placement registry for the jitted steps' pytrees.

    Parameters
    ----------
    batch_spec : PartitionSpec or None — placement for every batch
        input/label (None: ``P('data')`` when the mesh has a data axis,
        else replicated). Per-input sequences stay on the step's
        ``data_spec=`` argument.
    rules : [(regex, PartitionSpec)] — explicit name-pattern placements
        (tensor parallel etc.); first match wins, checked before the
        default policy.
    params : 'replicate' | 'fsdp' — default policy for params that match
        no rule. ``'fsdp'`` shards each param's largest divisible axis
        over ``fsdp_axis`` (optimizer moments follow their param — the
        ZeRO contract).
    fsdp_axis : mesh axis FSDP shards over (default ``'data'``).
    fsdp_min_size : params with fewer elements stay replicated (env
        default ``MXTPU_FSDP_MIN_SIZE``, 1024) — sharding tiny biases
        buys nothing and costs collectives.
    """

    def __init__(self, batch_spec: Optional[PartitionSpec] = None,
                 rules: Sequence[Tuple[str, PartitionSpec]] = (),
                 params: str = "replicate", fsdp_axis: str = "data",
                 fsdp_min_size: Optional[int] = None):
        if params not in ("replicate", "fsdp"):
            raise MXNetError(
                f"params policy must be 'replicate' or 'fsdp', got "
                f"{params!r}")
        self.batch_spec = batch_spec
        self.rules = [(pat, spec) for pat, spec in rules]
        self._compiled = [(re.compile(pat), spec) for pat, spec in rules]
        self.params = params
        self.fsdp_axis = fsdp_axis
        self.fsdp_min_size = (int(fsdp_min_size) if fsdp_min_size is not None
                              else _fsdp_min_size_default())

    # ------------------------------------------------------------ presets
    @classmethod
    def replicated(cls, **kw) -> "ShardingRules":
        """Params/optimizer state replicated, batch over ``data`` —
        classic in-graph data parallelism (grad psum by GSPMD)."""
        return cls(params="replicate", **kw)

    # batch-sharded + replicated params IS data parallelism; alias
    data_parallel = replicated

    @classmethod
    def fsdp(cls, axis: str = "data", min_size: Optional[int] = None,
             **kw) -> "ShardingRules":
        """ZeRO/FSDP: params + optimizer moments sharded over ``axis``,
        batch over ``data`` — a model larger than one chip's HBM trains
        and serves; GSPMD inserts the gather/reduce-scatter collectives."""
        return cls(params="fsdp", fsdp_axis=axis, fsdp_min_size=min_size,
                   **kw)

    @classmethod
    def from_string(cls, preset: str) -> "ShardingRules":
        s = str(preset).strip().lower()
        if s in ("replicated", "replicate", "dp", "data_parallel"):
            return cls.replicated()
        if s == "fsdp":
            return cls.fsdp()
        if s.startswith("fsdp:"):
            return cls.fsdp(axis=s.split(":", 1)[1])
        raise MXNetError(
            f"unknown sharding preset {preset!r}; use 'replicated', "
            "'fsdp', or 'fsdp:<axis>' (or pass a ShardingRules)")

    @classmethod
    def resolve(cls, obj) -> Optional["ShardingRules"]:
        """``sharding=`` argument coercion: None -> the ``MXTPU_SHARDING``
        env default (None when unset), str -> preset, rules -> itself."""
        if obj is None:
            return default_rules()
        if isinstance(obj, cls):
            return obj
        if isinstance(obj, str):
            return cls.from_string(obj)
        raise MXNetError(
            f"sharding must be a ShardingRules, preset string or None, "
            f"got {type(obj).__name__}")

    # ---------------------------------------------------------- resolution
    def param_explain(self, name: str, shape, mesh: Optional[Mesh]
                      ) -> Tuple[PartitionSpec, str]:
        """(spec, reason) for one param — the reason string is the lint's
        evidence trail: ``rule:<pattern>``, ``fsdp``, or a
        ``replicated:*`` fallback explaining why."""
        for pat, spec in self._compiled:
            if pat.search(name):
                return spec, f"rule:{pat.pattern}"
        if self.params == "fsdp":
            if mesh is None or self.fsdp_axis not in mesh.shape:
                return PartitionSpec(), "replicated:no_fsdp_axis"
            n = int(mesh.shape[self.fsdp_axis])
            if n <= 1:
                return PartitionSpec(), "replicated:axis_size_1"
            if _size(shape) < self.fsdp_min_size:
                return PartitionSpec(), "replicated:small"
            spec = fsdp_partition_spec(shape, self.fsdp_axis, n)
            if spec == PartitionSpec():
                return spec, "replicated:indivisible"
            return spec, "fsdp"
        return PartitionSpec(), "replicated:default"

    def param_spec(self, name: str, shape,
                   mesh: Optional[Mesh]) -> PartitionSpec:
        return self.param_explain(name, shape, mesh)[0]

    def param_sharding(self, mesh: Mesh, name: str, shape) -> NamedSharding:
        return NamedSharding(mesh, self.param_spec(name, shape, mesh))

    def batch_partition_spec(self, mesh: Mesh) -> PartitionSpec:
        if self.batch_spec is not None:
            return self.batch_spec
        return PartitionSpec("data") if "data" in mesh.axis_names \
            else PartitionSpec()

    # ----------------------------------------------------------- reporting
    def label(self) -> str:
        base = f"fsdp({self.fsdp_axis})" if self.params == "fsdp" \
            else "replicated"
        return f"{base}+{len(self.rules)}rules" if self.rules else base

    def describe(self) -> dict:
        return {
            "params": self.params,
            "fsdp_axis": self.fsdp_axis,
            "fsdp_min_size": self.fsdp_min_size,
            "rules": [pat for pat, _ in self.rules],
            "batch_spec": (None if self.batch_spec is None
                           else str(self.batch_spec)),
        }


_ENV_RULES = {"checked": False, "rules": None}


def default_rules() -> Optional[ShardingRules]:
    """The ``MXTPU_SHARDING`` process default (None when unset/off)."""
    if not _ENV_RULES["checked"]:
        _ENV_RULES["checked"] = True
        s = os.environ.get("MXTPU_SHARDING", "").strip().lower()
        if s and s not in ("0", "off", "none", "false"):
            _ENV_RULES["rules"] = ShardingRules.from_string(s)
    return _ENV_RULES["rules"]


def reset_default_rules():
    """Forget the cached env-derived rules (tests)."""
    _ENV_RULES["checked"] = False
    _ENV_RULES["rules"] = None


# ------------------------------------------------------ placement helpers
def device_put_donatable(x, sharding):
    """``device_put`` that never aliases the source's buffers.

    Plain ``device_put`` may reuse an already-in-place per-device buffer
    of the SOURCE array inside the result (e.g. the device-0 replica
    when replicating a single-device param over a mesh). Donating such a
    result to a jitted step then invalidates the source too — the net's
    live Parameter dies on the first training step (measured on the CPU
    backend; ``may_alias=False`` is NOT honored on this path in the
    pinned jax). Placement of any state that will be DONATED goes
    through here: jax-array sources get an explicit post-placement copy
    (fresh buffers, sharding preserved; build-time cost only)."""
    placed = jax.device_put(x, sharding)
    if isinstance(x, jax.Array):
        import jax.numpy as jnp

        placed = jnp.copy(placed)
    return placed


def place_params(values: Dict[str, jax.Array], mesh: Mesh,
                 rules: ShardingRules) -> Dict[str, jax.Array]:
    """device_put a name->array tree under the rules' param placements."""
    return {
        n: jax.device_put(
            v, rules.param_sharding(mesh, n, _np.shape(v)))
        for n, v in values.items()
    }


def _shard_bytes(v) -> int:
    """Bytes ONE device holds for this array (its shard, or the full
    array when replicated/single-device)."""
    itemsize = _np.dtype(v.dtype).itemsize
    sh = getattr(v, "sharding", None)
    if sh is None:
        return _size(v.shape) * itemsize
    try:
        return _size(sh.shard_shape(v.shape)) * itemsize
    except Exception:  # noqa: BLE001 - sharding types without shard_shape
        return _size(v.shape) * itemsize


def shard_summary(values: Dict[str, jax.Array], mesh: Optional[Mesh],
                  trainable: Optional[Sequence[str]] = None) -> dict:
    """Parameter placement accounting: global vs per-shard bytes, how
    many params are actually partitioned, and a per-step collective
    traffic estimate (ring-allreduce ``2(n-1)/n * grad bytes`` for
    replicated params; ``3(n-1)/n * param bytes`` — allgather fwd+bwd +
    reduce-scatter — for sharded params)."""
    total = 0
    per_shard = 0
    sharded = 0
    replicated = 0
    train = set(trainable) if trainable is not None else None
    coll = 0.0
    n = int(mesh.size) if mesh is not None else 1
    for name, v in values.items():
        b = _size(v.shape) * _np.dtype(v.dtype).itemsize
        sb = _shard_bytes(v)
        total += b
        per_shard += sb
        partitioned = sb < b
        if partitioned:
            sharded += 1
        else:
            replicated += 1
        if train is None or name in train:
            if n > 1:
                coll += (3.0 if partitioned else 2.0) * b * (n - 1) / n
    return {
        "mesh_shape": mesh_shape_str(mesh),
        "mesh_devices": n,
        "param_bytes_total": int(total),
        "param_bytes_per_shard": int(per_shard),
        "params_sharded": sharded,
        "params_replicated": replicated,
        "collective_bytes_per_step_est": int(coll),
    }


def publish_shard_metrics(values: Dict[str, jax.Array],
                          mesh: Optional[Mesh],
                          rules: Optional[ShardingRules] = None,
                          trainable: Optional[Sequence[str]] = None) -> dict:
    """Compute ``shard_summary`` and publish it as the ``shard/`` metric
    family + ``mesh_shape``/``sharding`` run info (surfaced by
    ``mx.telemetry.report()`` and ``tools/telemetry_report.py``)."""
    s = shard_summary(values, mesh, trainable)
    reg = _tel.registry()
    reg.gauge("shard/mesh_devices").set(s["mesh_devices"])
    reg.gauge("shard/param_bytes_total").set(s["param_bytes_total"])
    reg.gauge("shard/param_bytes_per_shard").set(s["param_bytes_per_shard"])
    reg.gauge("shard/params_sharded").set(s["params_sharded"])
    reg.gauge("shard/params_replicated").set(s["params_replicated"])
    reg.gauge("shard/collective_bytes_per_step_est").set(
        s["collective_bytes_per_step_est"])
    _tel.set_info(mesh_shape=s["mesh_shape"],
                  sharding=rules.label() if rules is not None else None)
    return s

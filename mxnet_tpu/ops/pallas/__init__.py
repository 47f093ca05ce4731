"""Pallas TPU kernels — the native-kernel budget of this framework
(SURVEY.md §7: attention fwd/bwd, layer_norm, softmax, fused optimizers go
to hand kernels where the reference had CUDA).

Kernels fall back to interpreter mode off-TPU so the one test suite runs on
the virtual CPU mesh unchanged (reference trick: one suite, many contexts).
"""

import os as _os

import jax as _jax


def _use_interpret() -> bool:
    """``MXTPU_FLASH_INTERPRET``: force (``1``) or forbid (``0``) Pallas
    interpret mode for every kernel of this package; default ``auto``
    interprets off-TPU (CPU testing)."""
    v = _os.environ.get("MXTPU_FLASH_INTERPRET", "").strip().lower()
    if v in ("1", "true", "force", "on"):
        return True
    if v in ("0", "false", "off"):
        return False
    return _jax.default_backend() != "tpu"


def _partitionable() -> bool:
    """May an op route to a kernel where it is being traced?

    GSPMD cannot partition a Mosaic kernel: a compiled kernel traced under
    a multi-device mesh is refused at lowering ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"). So
    under such a mesh scope the ops with an exact jnp form (LayerNorm, the
    paged attention gather path) take that form, which XLA partitions.
    Interpret mode lowers to plain HLO and is always partitionable; code
    that wraps a kernel in ``shard_map`` itself (ring attention) calls the
    kernel directly and never asks."""
    if _use_interpret():
        return True
    from ...parallel import current_mesh

    mesh = current_mesh()
    return mesh is None or mesh.size == 1


from .flash_attention import flash_attention  # noqa: E402,F401

__all__ = ["flash_attention"]

"""Ring attention: sequence/context parallelism over an ICI mesh axis.

Beyond-reference capability (SURVEY.md §5: the reference's long-context
ceiling was the O(L²) interleaved attention of
``src/operator/contrib/transformer.cc`` [unverified] plus bucketing) — here
the sequence dimension is sharded over a mesh axis and K/V blocks rotate
around the ring via ``ppermute`` while each device's flash kernel consumes
them blockwise. Per-device memory is O(S/n); the full sequence never
materializes on any chip.

Design (Liu et al. 2023 "Ring Attention with Blockwise Transformers"; the
public-domain recipe, reimplemented here on this repo's own flash kernel):

forward   n-1 neighbor ppermutes; each step runs the local Pallas flash
          kernel on (q_local, k_visiting, v_visiting) and merges the chunk
          partial into a running (out, lse) with the standard online-softmax
          combine. Causal masking degenerates to a static per-step choice:
          step 0 processes the diagonal chunk (local causal kernel); step
          s>0 processes chunk (i-s) mod n, which is fully visible iff
          i >= s — an all-or-nothing inclusion folded into the lse merge.
backward  one custom_vjp around the whole ring: recompute per visiting
          chunk with the saved GLOBAL lse (the same blockwise-recompute
          scheme as the single-chip flash backward), accumulating dk/dv on
          carriers that travel the ring with their chunks and arrive home
          after n rotations; dq stays local.

Use ``ring_flash_attention(q, k, v, mesh, axis)`` from regular code (wraps
``shard_map``; composes inside jit/TrainStep), or
``ring_flash_attention_shard`` directly inside an existing ``shard_map``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from jax import shard_map

from ..ops.pallas.flash_attention import _flash_fwd

__all__ = ["ring_flash_attention", "ring_flash_attention_shard"]

_NEG_INF = -1e30


def _merge(acc_out, acc_lse, out_s, lse_s):
    """Online-softmax combine of two normalized partials."""
    m = jnp.maximum(acc_lse, lse_s)
    # guard fully-excluded rows (both -inf): keep weights finite
    m_safe = jnp.maximum(m, _NEG_INF / 2)
    w_acc = jnp.exp(acc_lse - m_safe)[..., None]
    w_s = jnp.exp(lse_s - m_safe)[..., None]
    # floor must be a NORMAL f32: 1e-38 is subnormal and flushes to zero
    # on FTZ backends, turning fully-masked rows into 0/0 = NaN
    new_out = (acc_out * w_acc + out_s * w_s) / jnp.maximum(
        w_acc + w_s, 1e-30
    )
    new_lse = m_safe + jnp.log(jnp.maximum(w_acc + w_s, 1e-30))[..., 0]
    return new_out, new_lse


def _ring_perm(axis_name, n):
    return [(j, (j + 1) % n) for j in range(n)]


def _local_vl(vl, j, s_local):
    """Per-visiting-chunk key budget: chunk j holds GLOBAL key positions
    [j*s_local, (j+1)*s_local), so a row with global valid_length ``vl``
    keeps ``clip(vl - j*s_local, 0, s_local)`` keys of it (the flash
    kernel's local valid_length semantics)."""
    if vl is None:
        return None
    return jnp.clip(vl.astype(jnp.int32) - j * s_local, 0, s_local)


def _ring_fwd(q, k, v, vl, axis_name, causal, sm_scale):
    """Inside shard_map: q/k/v are LOCAL chunks (B, H, S_local, D);
    ``vl`` (B,) is the GLOBAL per-row valid key length (or None)."""
    n = jax.lax.psum(1, axis_name)  # static axis size
    i = jax.lax.axis_index(axis_name)
    perm = _ring_perm(axis_name, n)
    s_local = k.shape[2]

    out0, lse0 = _flash_fwd(q, k, v, _local_vl(vl, i, s_local), causal,
                            sm_scale, 128, 128)
    acc_out = out0.astype(jnp.float32)
    acc_lse = lse0
    k_cur, v_cur = k, v
    for s in range(1, n):
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        j = (i - s) % n  # home index of the chunk visiting at step s
        out_s, lse_s = _flash_fwd(q, k_cur, v_cur, _local_vl(vl, j, s_local),
                                  False, sm_scale, 128, 128)
        if causal:
            include = i >= s  # visiting chunk j=(i-s)%n is fully past iff so
            lse_s = jnp.where(include, lse_s, _NEG_INF)
        # a fully-masked visiting chunk (vl <= j*s_local) contributes
        # nothing: its kernel rows come back with lse == -inf already, so
        # the merge drops them without extra handling
        acc_out, acc_lse = _merge(acc_out, acc_lse, out_s.astype(jnp.float32),
                                  lse_s)
    return acc_out.astype(q.dtype), acc_lse


def _ring_bwd_math(q, k_cur, v_cur, g, out, lse, sm_scale, local_causal,
                   include, vl_local=None):
    """Gradient contributions of one visiting chunk: the single-chip
    blockwise-recompute backward with the GLOBAL lse — O(S_local·block)
    memory, never the full S_local² score matrix."""
    from ..ops.pallas.flash_attention import _flash_bwd_impl

    B = q.shape[0]
    if vl_local is None:
        vl_local = jnp.full((B,), k_cur.shape[2], jnp.int32)
    dq_b, dk_b, dv_b = _flash_bwd_impl(
        q, k_cur, v_cur, vl_local, out, lse, g, local_causal, sm_scale, 128
    )
    if include is not None:  # all-or-nothing chunk inclusion (causal ring)
        dq_b = dq_b * include
        dk_b = dk_b * include
        dv_b = dv_b * include
    return dq_b, dk_b, dv_b


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_flash_attention_shard(q, k, v, axis_name, causal=False,
                               sm_scale=None, valid_length=None):
    """Ring attention over ``axis_name``; call INSIDE shard_map with the
    sequence dimension sharded over that axis. Shapes (B, H, S_local, D);
    ``valid_length`` (B,) GLOBAL key budget per row, or None (placed last
    so positional (q, k, v, axis_name, ...) callers keep working)."""
    out, _ = _ring_fwd(q, k, v, valid_length, axis_name, causal,
                       _scale(sm_scale, q))
    return out


def _scale(sm_scale, q):
    return float(sm_scale) if sm_scale is not None else 1.0 / math.sqrt(
        q.shape[-1]
    )


def _ring_fwd_rule(q, k, v, axis_name, causal, sm_scale, valid_length):
    out, lse = _ring_fwd(q, k, v, valid_length, axis_name, causal,
                         _scale(sm_scale, q))
    return out, (q, k, v, valid_length, out, lse)


def _ring_bwd_rule(axis_name, causal, sm_scale, res, g):
    q, k, v, vl, out, lse = res
    scale = _scale(sm_scale, q)
    n = jax.lax.psum(1, axis_name)
    i = jax.lax.axis_index(axis_name)
    perm = _ring_perm(axis_name, n)
    s_local = k.shape[2]

    # step 0: diagonal chunk (local causal when causal)
    dq0, dk0, dv0 = _ring_bwd_math(
        q, k, v, g, out, lse, scale, local_causal=causal, include=None,
        vl_local=_local_vl(vl, i, s_local),
    )
    dq = dq0.astype(jnp.float32)
    dk_cur = dk0.astype(jnp.float32)
    dv_cur = dv0.astype(jnp.float32)
    k_cur, v_cur = k, v
    for s in range(1, n):
        # rotate chunks AND their grad accumulators together; after the
        # loop's n-1 rotations plus one final rotation they arrive home
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
        j = (i - s) % n
        include = (i >= s).astype(jnp.float32) if causal else None
        dq_b, dk_b, dv_b = _ring_bwd_math(
            q, k_cur, v_cur, g, out, lse, scale, local_causal=False,
            include=include, vl_local=_local_vl(vl, j, s_local),
        )
        dq = dq + dq_b.astype(jnp.float32)
        dk_cur = dk_cur + dk_b.astype(jnp.float32)
        dv_cur = dv_cur + dv_b.astype(jnp.float32)
    # one more rotation brings accumulators back to their home device
    dk = jax.lax.ppermute(dk_cur, axis_name, perm)
    dv = jax.lax.ppermute(dv_cur, axis_name, perm)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None)


ring_flash_attention_shard.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def _seq_parallel_call(shard_fn, q, k, v, mesh, axis, causal, sm_scale,
                       batch_axis, precheck=None, valid_length=None):
    """Shared wrapper for sequence-parallel attention variants: NDArray
    unwrap/rewrap, batch-axis resolution (shard B over ``batch_axis`` when
    the mesh has it — replicating B over 'data' would silently double
    attention FLOPs per device), and the shard_map plumbing. Composes
    under jit — GSPMD sees an opaque manually-sharded region.

    ``valid_length`` (B,) is the GLOBAL per-row key budget; each variant
    translates it to its own local masking (ring: per-visiting-chunk
    offsets; ulysses: pass-through after the all_to_all)."""
    from ..ndarray.ndarray import NDArray

    unwrap = lambda x: x.data if isinstance(x, NDArray) else x  # noqa: E731
    wrapped = isinstance(q, NDArray)
    q, k, v = unwrap(q), unwrap(k), unwrap(v)
    vl = unwrap(valid_length) if valid_length is not None else None
    if precheck is not None:
        precheck(q)
    b_ax = batch_axis if (batch_axis in mesh.axis_names
                          and batch_axis != axis) else None
    spec = PartitionSpec(b_ax, None, axis, None)
    in_specs = (spec, spec, spec)
    args = (q, k, v)
    if vl is not None:
        in_specs = in_specs + (PartitionSpec(b_ax),)
        args = args + (vl,)

        def inner(q, k, v, vl_):
            return shard_fn(q, k, v, axis_name=axis, causal=causal,
                            sm_scale=sm_scale, valid_length=vl_)
    else:
        inner = functools.partial(shard_fn, axis_name=axis, causal=causal,
                                  sm_scale=sm_scale, valid_length=None)
    fn = shard_map(
        inner,
        mesh=mesh, in_specs=in_specs, out_specs=spec,
        check_vma=False,  # pallas_call out_shapes carry no vma info
    )
    out = fn(*args)
    return NDArray(out) if wrapped else out


def ring_flash_attention(q, k, v, mesh: Mesh, axis: str = "seq",
                         causal=False, sm_scale=None, batch_axis="data",
                         valid_length=None):
    """Sequence-parallel attention over ``mesh`` axis ``axis``.

    q/k/v (B, H, S, D) with S divisible by the axis size; K/V chunks
    rotate around the ring via ppermute (see module docstring).
    ``valid_length`` (B,) int: GLOBAL count of non-padding key positions
    per row (ragged batches). See also ``parallel.ulysses`` for the
    all-to-all variant."""
    return _seq_parallel_call(ring_flash_attention_shard, q, k, v, mesh,
                              axis, causal, sm_scale, batch_axis,
                              valid_length=valid_length)

"""The gated delta rule's one-token update (``ops/delta_rule.py``) as a
Pallas kernel: the place where the ``jax.numpy`` form moves a head's matrix
state through HBM more often than the mathematics asks.

``gated_delta_step`` (``%gated_delta_step``): one token a row. A grid step
takes a row's heads: each head's state is read ONCE, decayed, read back
with the key, corrected by the rank-one write and read out with the query
on the copy in VMEM, and written ONCE (aliased onto the state it came from).
The ``jax.numpy`` form reads the state for ``S^T k``, again for the update
and again for ``S^T q``: 0.245 ms a call at 16 rows of 30 heads of 96 x 192
float32 on a v5e against this kernel's 0.159 (PERF.md section 6, PR 48). A
row that is not active is given decay 1 and write strength 0, which leaves
its state bit for bit. Everything is float32.

The window has no kernel: a walk over the blocks with the state resident in
VMEM (grid rows x heads x blocks, four small products a step) took 0.609 ms
a call at a chunk of 1,024 where ``delta_rule_chunk``'s scan, which takes
all 30 heads a block in batched products, takes 0.391, and went (PERF.md
section 6, PR 48).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _use_interpret

__all__ = ["gated_delta_step"]

_VMEM_LIMIT = 64 * 1024 * 1024
F32 = jnp.float32


# ---------------------------------------------------------------- the step
def _step_kernel(s_ref, kt_ref, qt_ref, v_ref, a_ref, b_ref, o_ref, s1_ref,
                 *, heads):
    """One row: ``s (H, d_k, d_v)``, ``kt, qt (d_k, H)`` (a head's key a
    COLUMN, as the state's rows are weighed), ``v (H, d_v)``, ``a, b (H,
    d_v)`` the decay and the write strength, the same along a row (Mosaic
    broadcasts along sublanes or lanes, not one number along both)."""
    for h in range(heads):
        k = kt_ref[:, h:h + 1]
        s = s_ref[h].astype(F32) * a_ref[h:h + 1, :]
        u = b_ref[h:h + 1, :] * (v_ref[h:h + 1, :]
                                 - jnp.sum(s * k, axis=0, keepdims=True))
        s = s + k * u
        o_ref[h:h + 1, :] = jnp.sum(s * qt_ref[:, h:h + 1], axis=0,
                                    keepdims=True)
        s1_ref[h] = s.astype(s1_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(state, kt, qt, v, decay, beta, interpret):
    B, H, dk, dv = state.shape

    def row(*last):
        return pl.BlockSpec((None,) + last,
                            lambda b: (b,) + (0,) * len(last))

    return pl.pallas_call(
        functools.partial(_step_kernel, heads=H),
        grid=(B,),
        in_specs=[row(H, dk, dv), row(dk, H), row(dk, H), row(H, dv),
                  row(H, dv), row(H, dv)],
        out_specs=[row(H, dv), row(H, dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="gated_delta_step")(
            state, kt, qt, v, decay, beta)


def gated_delta_step(state, q, k, v, g, beta, active):
    """``delta_rule.delta_rule_step`` with a row's state read once and
    written once: the same arguments, the same ``(o (B, H, d_v) float32,
    new_state)``."""
    live = active[:, None]
    wide = v.shape

    def along(x):
        return jnp.broadcast_to(x[..., None], wide)

    return _step_call(
        state, jnp.swapaxes(k.astype(F32), 1, 2),
        jnp.swapaxes(q.astype(F32), 1, 2), v.astype(F32),
        along(jnp.where(live, jnp.exp(g.astype(F32)), 1.0)),
        along(jnp.where(live, beta.astype(F32), 0.0)),
        interpret=_use_interpret())

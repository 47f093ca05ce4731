"""Expert layer: tokens grouped by expert and ONE grouped SwiGLU product
over the experts that have tokens.

A mixture-of-experts layer sends each token to ``k`` of ``E`` experts. No
token is dropped, whatever the load: the (token, expert) pairs are sorted
by expert, each expert's run of rows is padded to a whole number of row
tiles, and the kernel walks the tiles. A tile belongs to exactly one
expert; the tile-to-expert map rides the grid as a scalar-prefetch operand
and picks the weight blocks, so an expert's weights are read once for its
run of tiles and an expert with no tokens is never read. Tiles past the
last used one keep the previous block indices (no new DMA) and skip the
product. In decode 16 rows touch about 82 of 128 experts a layer and only
those 82 are read; a dense product over all experts with masking reads all
128.

The router scores by a softmax over all experts (Keye) or by a sigmoid
with a selection bias that does not weigh and a scale (JoyAI; ``route``).
A layer may hold a RUN of the router's experts, one chip's share of an
expert-parallel layer (``moe_experts(held=)``): the router still ranks all
its outputs, the pairs that fall on experts held elsewhere sort into a sink
past the held ones, and the sink's tiles are left out of the product.

ROUTING and DISPATCH are apart (PR 40). ``route`` is the routing of a
layer whose router is ONE matrix; ``dispatch_experts`` is everything after
it (grouping, product, combine) for ``(experts (T, k), weights (T, k))``
however they were made; ``moe_experts`` is ``route`` then
``dispatch_experts``, to the letter what it was before the split (Keye's
and JoyAI's programs trace to the jaxprs they traced to). A net whose
router is its own calls the dispatch alone: ZAYA routes by a small MLP
whose input runs from layer to layer, to ONE expert a token, weighed by its
probability as it stands (``route`` renormalises the chosen weights, which
at one expert a token would make every weight 1.0). Its experts have the
model's own width (2048 x 2048 x 3, 16 of them): ``f_block`` gives that
width 512 columns a grid step, three weight blocks of 2 MB two buffers
deep, 12.6 MB of the 48 MB of VMEM the kernel asks for where Keye's 768
takes 384 and 9.4; a decode step's 64 pairs on 16 experts walk tiles of 16
rows a quarter full, each expert's 25 MB read once whatever its tile
holds.

``grouped_swiglu`` is the product alone, a Pallas kernel on the TPU
(``MXTPU_FLASH_INTERPRET`` as for every kernel of this package) with
``grouped_swiglu_reference``, its jnp form, where a compiled kernel cannot
be partitioned (``_partitionable``) and as the tolerance tests' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _partitionable, _use_interpret

__all__ = ["moe_experts", "dispatch_experts", "route", "group_by_expert",
           "grouped_swiglu",
           "grouped_swiglu_reference", "row_tile", "f_block"]


def row_tile(pairs: int, experts: int) -> int:
    """Rows a tile: 128 where an expert's share of the pairs fills it
    (prefill), else 16 (decode: the bf16 sublane tile)."""
    return 128 if pairs >= 64 * experts else 16


def f_block(width: int) -> int:
    """Columns of the expert's hidden width a grid step: the largest
    multiple of 128 that divides it and is at most 512 (768 -> 384), so
    that three weight blocks, double-buffered, fit the scoped VMEM."""
    for fb in (512, 384, 256, 128):
        if width % fb == 0:
            return fb
    return width


def route(u, router, k, scoring="softmax", bias=None, scale=1.0):
    """``(experts (T, k) int32, weights (T, k) float32)`` of the ``k``
    experts a token, over all of the router's outputs in float32.

    ``scoring="softmax"``: the ``k`` largest softmax probabilities,
    renormalised. ``scoring="sigmoid"``: ``s = sigmoid(logits)``; the ``k``
    largest of ``s + bias`` are chosen (``bias (E,)`` selects and does not
    weigh), and they weigh ``scale x s / sum of the chosen s``."""
    logits = jnp.dot(u, router, preferred_element_type=jnp.float32)
    if scoring == "softmax":
        top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    elif scoring == "sigmoid":
        score = jax.nn.sigmoid(logits)
        pick = score if bias is None else score + bias.astype(jnp.float32)
        idx = jax.lax.top_k(pick, k)[1]
        top = jnp.take_along_axis(score, idx, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}; "
                         "use softmax/sigmoid")
    weights = top / jnp.sum(top, -1, keepdims=True)
    return idx.astype(jnp.int32), weights if scale == 1.0 \
        else weights * scale


def group_by_expert(experts, num_experts, tile):
    """Rows of the padded, expert-sorted layout for ``(T, k)`` expert ids.

    Returns ``(dest (T, k), src_token (M,), tile_expert (M // tile,),
    n_tiles (1,), counts (E,))``: pair ``(t, j)`` sits at row ``dest[t,
    j]``; row ``m`` holds token ``src_token[m]`` (0 for padding rows, whose
    result nobody reads); tile ``i`` belongs to ``tile_expert[i]`` (tiles
    past ``n_tiles`` repeat the last used expert)."""
    T, k = experts.shape
    n = T * k
    flat = experts.reshape(n)
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    padded = (counts + tile - 1) // tile * tile
    start = jnp.cumsum(counts) - counts          # in the sorted order
    pstart = jnp.cumsum(padded) - padded         # in the padded layout
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    dest_sorted = pstart[sorted_e] + jnp.arange(n, dtype=jnp.int32) \
        - start[sorted_e]
    M = (n + num_experts * (tile - 1) + tile - 1) // tile * tile
    dest = jnp.zeros((n,), jnp.int32).at[order].set(dest_sorted)
    src_token = jnp.zeros((M,), jnp.int32).at[dest_sorted].set(
        (order // k).astype(jnp.int32))
    ends = jnp.cumsum(padded)
    n_tiles = (ends[-1] // tile).astype(jnp.int32)
    tile_at = jnp.minimum(jnp.arange(M // tile, dtype=jnp.int32),
                          jnp.maximum(n_tiles - 1, 0)) * tile
    tile_expert = jnp.searchsorted(ends, tile_at, side="right") \
        .astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, num_experts - 1)
    return dest.reshape(T, k), src_token, tile_expert, \
        n_tiles.reshape(1), counts


def _dot(a, b):
    # a process-wide "highest" matmul precision (the float32 parity tests
    # set it) is not one Mosaic takes for bfloat16 operands
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.DEFAULT
                   if a.dtype == jnp.bfloat16 else None)


def _swiglu_kernel(te_ref, nt_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
                   acc_ref):
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < nt_ref[0])
    def _product():
        x = x_ref[...]
        g = _dot(x, wg_ref[0])
        u = _dot(x, wu_ref[0])
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        acc_ref[...] += _dot(h, wd_ref[0])

    @pl.when(f == pl.num_programs(1) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile",))
def _moe_grouped_swiglu_impl(x, tile_expert, n_tiles, w_gate, w_up, w_down,
                             tile):
    M, H = x.shape
    F = w_gate.shape[2]
    fb = f_block(F)
    nf = F // fb

    def col(i, f, te, nt):          # an unused tile re-reads nothing
        return jnp.where(i < nt[0], f, nf - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // tile, nf),
        in_specs=[
            pl.BlockSpec((tile, H), lambda i, f, te, nt: (i, 0)),
            pl.BlockSpec((1, H, fb),
                         lambda i, f, te, nt: (te[i], 0, col(i, f, te, nt))),
            pl.BlockSpec((1, H, fb),
                         lambda i, f, te, nt: (te[i], 0, col(i, f, te, nt))),
            pl.BlockSpec((1, fb, H),
                         lambda i, f, te, nt: (te[i], col(i, f, te, nt), 0)),
        ],
        out_specs=pl.BlockSpec((tile, H), lambda i, f, te, nt: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tile, H), jnp.float32)],
    )
    return pl.pallas_call(
        _swiglu_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, H), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=_use_interpret(),
        name="moe_grouped_swiglu",
    )(tile_expert, n_tiles, x, w_gate, w_up, w_down)


def grouped_swiglu_reference(x, tile_expert, n_tiles, w_gate, w_up, w_down,
                             tile):
    """jnp form of ``grouped_swiglu``: each tile against its expert's
    gathered weights (tiny sizes, and under a multi-device mesh)."""
    M, H = x.shape
    xt = x.reshape(M // tile, tile, H)
    g = jnp.einsum("nth,nhf->ntf", xt, w_gate[tile_expert],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("nth,nhf->ntf", xt, w_up[tile_expert],
                   preferred_element_type=jnp.float32)
    h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    y = jnp.einsum("ntf,nfh->nth", h, w_down[tile_expert],
                   preferred_element_type=jnp.float32)
    used = jnp.arange(M // tile)[:, None, None] < n_tiles[0]
    return jnp.where(used, y, 0.0).astype(x.dtype).reshape(M, H)


def grouped_swiglu(x, tile_expert, n_tiles, w_gate, w_up, w_down, tile):
    """``w_down[e] (silu(x w_gate[e]) * (x w_up[e]))`` for every row tile
    of ``x (M, H)`` against the expert ``e = tile_expert[i]`` of its tile;
    weights ``(E, H, F)``, ``(E, H, F)``, ``(E, F, H)``. Rows of tiles past
    ``n_tiles`` come back zero."""
    if not _partitionable():
        return grouped_swiglu_reference(x, tile_expert, n_tiles, w_gate,
                                        w_up, w_down, tile)
    return _moe_grouped_swiglu_impl(x, tile_expert, n_tiles, w_gate, w_up,
                                    w_down, tile=tile)


def dispatch_experts(u, experts, weights, w_gate, w_up, w_down, valid=None,
                     held=None, num_experts=None):
    """The expert layer AFTER its routing: tokens ``u (T, H)``, each with
    ``k`` chosen ``experts (T, k)`` int32 and the ``weights (T, k)``
    float32 they are combined by, are grouped by expert, taken through the
    grouped product and summed: ``sum_j weights[t, j] w_down[e] (silu(u
    w_gate[e]) * (u w_up[e]))``, ``e = experts[t, j]``. Whoever routes
    decides what a weight is: ``moe_experts`` hands over ``route``'s
    (renormalised over the chosen), a net with a router of its own its own
    (ZAYA: one expert a token, weighed by its probability as it stands).
    ``valid (T,)`` marks padding tokens, which are computed and not
    counted.

    ``held = (first, n)`` says WHICH experts the weights hold: ``w_gate``,
    ``w_up``, ``w_down`` are experts ``first .. first + n - 1`` of the
    ``num_experts`` the router ranks (one chip's share of an
    expert-parallel layer; without ``held`` the weights hold them all). A
    pair that falls on an expert held elsewhere is neither computed nor
    added, and the result is this share's part of the layer's sum.

    Returns ``(out (T, H), counts)``: tokens routed to each expert,
    ``(num_experts,)`` int32."""
    T, H = u.shape
    k = experts.shape[1]
    E = w_gate.shape[0] if num_experts is None else num_experts
    local, n = experts, E
    if held is not None:
        first, n = held
        inside = jnp.logical_and(experts >= first, experts < first + n)
        weights = jnp.where(inside, weights, 0.0)
        local = jnp.where(inside, experts - first, n)   # a sink, sorted last
    tile = row_tile(T * k, n)
    dest, src_token, tile_expert, n_tiles, grouped = group_by_expert(
        local, n + (held is not None), tile)
    if held is not None:
        # the sink's tiles lie last: they are left out of the product
        n_tiles = n_tiles - (grouped[n] + tile - 1) // tile
        tile_expert = jnp.minimum(tile_expert, jnp.minimum(
            tile_expert[jnp.maximum(n_tiles[0] - 1, 0)], n - 1))
    y = grouped_swiglu(u[src_token], tile_expert, n_tiles, w_gate, w_up,
                       w_down, tile)
    picked = y[dest.reshape(T * k)].reshape(T, k, H).astype(jnp.float32)
    out = jnp.einsum("tkh,tk->th", picked, weights).astype(u.dtype)
    if valid is None and held is None:
        return out, grouped
    counts = jnp.zeros((E,), jnp.int32).at[experts.reshape(T * k)].add(
        1 if valid is None else jnp.repeat(valid.astype(jnp.int32), k))
    return out, counts


def moe_experts(u, router, w_gate, w_up, w_down, k, valid=None,
                scoring="softmax", bias=None, scale=1.0, held=None):
    """The expert layer on tokens ``u (T, H)`` with ONE matrix for a
    router: ``route`` (softmax probabilities renormalised, or sigmoid
    scores chosen with ``bias`` and scaled by ``scale``), then
    ``dispatch_experts`` over all of the router's outputs. ``valid`` and
    ``held`` are the dispatch's.

    Returns ``(out (T, H), counts)``: tokens routed to each expert, ``(E,)``
    int32, over the router's whole width."""
    experts, weights = route(u, router, k, scoring, bias, scale)
    return dispatch_experts(u, experts, weights, w_gate, w_up, w_down,
                            valid, held, router.shape[1])

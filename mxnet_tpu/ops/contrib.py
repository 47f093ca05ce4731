"""Contrib ops: transformer attention, detection ops, resize/pooling extras.

TPU-native analogue of ``src/operator/contrib/`` [unverified]:
- ``transformer.cc``: the interleaved multi-head attention matmuls used by
  GluonNLP BERT (``_contrib_interleaved_matmul_selfatt_qk`` etc.) and
  ``div_sqrt_dim``. Here they are thin einsum compositions — under
  ``hybridize()`` XLA fuses them; the flash-attention Pallas kernel in
  ``ops.pallas`` is the fast path that subsumes the qk/valatt pair.
- ``bounding_box.cc``: ``box_nms``, ``box_iou``, ``box_encode/decode``.
- ``roi_align.cc``, ``adaptive_avg_pooling.cc``, ``bilinear_resize.cc``.

Shapes/conventions follow the reference ops so GluonNLP/GluonCV-style model
code ports unchanged.
"""

from __future__ import annotations

import math
import os as _os

import numpy as _np
import jax
import jax.numpy as jnp

from .registry import register

_NEG = -1e18


# ----------------------------------------------------- transformer (BERT ops)
@register("_contrib_div_sqrt_dim", aliases=["div_sqrt_dim"])
def div_sqrt_dim(data, **kw):
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], data.dtype))


@register("_contrib_interleaved_matmul_selfatt_qk", aliases=["interleaved_matmul_selfatt_qk"])
def interleaved_matmul_selfatt_qk(queries_keys_values, heads=1, **kw):
    """Input (L, B, H*3*C) with per-head interleaved q,k,v; output (B*H, L, L)."""
    L, B, P = queries_keys_values.shape
    C = P // (3 * heads)
    x = queries_keys_values.reshape(L, B, heads, 3, C)
    q = x[:, :, :, 0, :]  # (L, B, H, C)
    k = x[:, :, :, 1, :]
    scores = jnp.einsum("lbhc,mbhc->bhlm", q, k)
    return scores.reshape(B * heads, L, L)


@register("_contrib_interleaved_matmul_selfatt_valatt", aliases=["interleaved_matmul_selfatt_valatt"])
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads=1, **kw):
    """attention (B*H, L, L) x values from (L, B, H*3*C) -> (L, B, H*C)."""
    L, B, P = queries_keys_values.shape
    C = P // (3 * heads)
    v = queries_keys_values.reshape(L, B, heads, 3, C)[:, :, :, 2, :]
    att = attention.reshape(B, heads, L, L)
    out = jnp.einsum("bhlm,mbhc->lbhc", att, v)
    return out.reshape(L, B, heads * C)


@register("_contrib_interleaved_matmul_encdec_qk", aliases=["interleaved_matmul_encdec_qk"])
def interleaved_matmul_encdec_qk(queries, keys_values, heads=1, **kw):
    Lq, B, P = queries.shape
    C = P // heads
    Lk = keys_values.shape[0]
    q = queries.reshape(Lq, B, heads, C)
    k = keys_values.reshape(Lk, B, heads, 2, C)[:, :, :, 0, :]
    return jnp.einsum("lbhc,mbhc->bhlm", q, k).reshape(B * heads, Lq, Lk)


@register("_contrib_interleaved_matmul_encdec_valatt", aliases=["interleaved_matmul_encdec_valatt"])
def interleaved_matmul_encdec_valatt(keys_values, attention, heads=1, **kw):
    Lk, B, P = keys_values.shape
    C = P // (2 * heads)
    v = keys_values.reshape(Lk, B, heads, 2, C)[:, :, :, 1, :]
    Lq = attention.shape[1]
    att = attention.reshape(B, heads, Lq, Lk)
    out = jnp.einsum("bhlm,mbhc->lbhc", att, v)
    return out.reshape(Lq, B, heads * C)


@register("_contrib_arange_like", aliases=["arange_like"], differentiable=False)
def arange_like(data, start=0.0, step=1.0, repeat=1, axis=None, **kw):
    if axis is None:
        n = data.size
        return (jnp.arange(n) * step + start).reshape(data.shape).astype(data.dtype)
    n = data.shape[axis]
    return (jnp.arange(n) * step + start).astype(data.dtype)


# --------------------------------------------------------------- bounding box
def _corner(boxes, fmt):
    if fmt == "corner":
        return boxes
    x, y, w, h = jnp.split(boxes, 4, axis=-1)
    return jnp.concatenate([x - w / 2, y - h / 2, x + w / 2, y + h / 2], axis=-1)


@register("_contrib_box_iou", aliases=["box_iou"], differentiable=False)
def box_iou(lhs, rhs, format="corner", **kw):
    """IoU matrix: lhs (..., N, 4), rhs (..., M, 4) -> (..., N, M)."""
    a = _corner(lhs, format)[..., :, None, :]
    b = _corner(rhs, format)[..., None, :, :]
    xx1 = jnp.maximum(a[..., 0], b[..., 0])
    yy1 = jnp.maximum(a[..., 1], b[..., 1])
    xx2 = jnp.minimum(a[..., 2], b[..., 2])
    yy2 = jnp.minimum(a[..., 3], b[..., 3])
    inter = jnp.clip(xx2 - xx1, 0) * jnp.clip(yy2 - yy1, 0)
    area_a = (a[..., 2] - a[..., 0]) * (a[..., 3] - a[..., 1])
    area_b = (b[..., 2] - b[..., 0]) * (b[..., 3] - b[..., 1])
    return inter / jnp.maximum(area_a + area_b - inter, 1e-12)


@register("_contrib_box_nms", aliases=["box_nms"], differentiable=False)
def box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1, coord_start=2,
            score_index=1, id_index=-1, background_id=-1, force_suppress=False,
            in_format="corner", out_format="corner", **kw):
    """Mask-based NMS (reference: ``bounding_box.cc`` box_nms [unverified]).

    data (..., N, K) with score at score_index and box at coord_start:+4.
    Suppressed entries have score set to -1, matching the reference.
    O(N^2) IoU matrix + sequential suppression via lax.scan — static shapes
    keep XLA happy (no dynamic compaction on device).
    """
    batch_shape = data.shape[:-2]
    N, K = data.shape[-2:]
    flat = data.reshape((-1, N, K))

    def one(batch):
        scores = batch[:, score_index]
        boxes = _corner(batch[:, coord_start:coord_start + 4], in_format)
        valid = scores > valid_thresh
        if background_id >= 0 and id_index >= 0:
            valid = valid & (batch[:, id_index] != background_id)
        order = jnp.argsort(-jnp.where(valid, scores, -jnp.inf))
        # entries beyond topk can neither survive NOR suppress (a suppressor
        # must itself be kept, and keep0 is False past topk), so restricting
        # the IoU matrix and the suppression scan to the top-M sorted entries
        # is exact — O(topk^2) instead of O(N^2), O(topk) scan steps
        M = min(N, topk) if topk > 0 else N
        order_m = order[:M]
        sboxes = boxes[order_m]
        svalid = valid[order_m]
        iou = box_iou(sboxes, sboxes)
        if not force_suppress and id_index >= 0:
            ids = batch[:, id_index][order_m]
            same = ids[:, None] == ids[None, :]
            iou = jnp.where(same, iou, 0.0)

        def step(keep, i):
            sup = (iou[i] > overlap_thresh) & (jnp.arange(M) > i) & keep[i]
            keep = keep & ~sup
            return keep, 0

        keep0 = svalid
        # unrolled x10: batches of sequential (M,)-vector steps fuse into
        # straight-line kernels, cutting the device-loop per-iteration
        # overhead ~10x without the compile blowup a FULL unroll causes
        # on big batches (the suppression order stays exactly greedy)
        keep, _ = jax.lax.scan(step, keep0, jnp.arange(M), unroll=10)
        # scatter back to original positions (beyond-topk stays suppressed)
        keep_orig = jnp.zeros((N,), bool).at[order_m].set(keep)
        out = batch.at[:, score_index].set(
            jnp.where(keep_orig, batch[:, score_index], -1.0)
        )
        return out

    out = jax.vmap(one)(flat)
    return out.reshape(batch_shape + (N, K))


@register("_contrib_box_encode", aliases=["box_encode"], differentiable=False)
def box_encode(samples, matches, anchors, refs, means=(0., 0., 0., 0.),
               stds=(0.1, 0.1, 0.2, 0.2), **kw):
    """SSD-style target encode (reference: bounding_box.cc [unverified]).

    samples (B, N) in {-1, 0, 1}; matches (B, N) indices into refs;
    anchors (B, N, 4), refs (B, M, 4) corner format.
    Returns (targets (B, N, 4), masks (B, N, 4)).
    """
    m = matches.astype(jnp.int32)
    ref = jnp.take_along_axis(refs, m[..., None], axis=1)
    ax1, ay1, ax2, ay2 = jnp.split(anchors, 4, axis=-1)
    gx1, gy1, gx2, gy2 = jnp.split(ref, 4, axis=-1)
    aw, ah = ax2 - ax1, ay2 - ay1
    acx, acy = ax1 + aw / 2, ay1 + ah / 2
    gw, gh = gx2 - gx1, gy2 - gy1
    gcx, gcy = gx1 + gw / 2, gy1 + gh / 2
    t0 = ((gcx - acx) / jnp.maximum(aw, 1e-12) - means[0]) / stds[0]
    t1 = ((gcy - acy) / jnp.maximum(ah, 1e-12) - means[1]) / stds[1]
    t2 = (jnp.log(jnp.maximum(gw, 1e-12) / jnp.maximum(aw, 1e-12)) - means[2]) / stds[2]
    t3 = (jnp.log(jnp.maximum(gh, 1e-12) / jnp.maximum(ah, 1e-12)) - means[3]) / stds[3]
    targets = jnp.concatenate([t0, t1, t2, t3], axis=-1)
    mask = (samples > 0.5)[..., None].astype(targets.dtype) * jnp.ones_like(targets)
    return targets * mask, mask


@register("_contrib_box_decode", aliases=["box_decode"], differentiable=False)
def box_decode(data, anchors, std0=0.1, std1=0.1, std2=0.2, std3=0.2,
               clip=-1.0, format="corner", **kw):
    a = _corner(anchors, format)
    ax1, ay1, ax2, ay2 = jnp.split(a, 4, axis=-1)
    aw, ah = ax2 - ax1, ay2 - ay1
    acx, acy = ax1 + aw / 2, ay1 + ah / 2
    d0, d1, d2, d3 = jnp.split(data, 4, axis=-1)
    cx = d0 * std0 * aw + acx
    cy = d1 * std1 * ah + acy
    dw, dh = d2 * std2, d3 * std3
    if clip > 0:
        dw, dh = jnp.minimum(dw, clip), jnp.minimum(dh, clip)
    w, h = jnp.exp(dw) * aw / 2, jnp.exp(dh) * ah / 2
    return jnp.concatenate([cx - w, cy - h, cx + w, cy + h], axis=-1)


# ------------------------------------------------------------------ ROIAlign
def _roi_sample(data, rois, pooled_size, spatial_scale, sample_ratio, aligned,
                reduce_fn):
    """Shared bilinear ROI sampler: sample sr×sr points per output bin, then
    reduce with ``reduce_fn`` (mean → ROIAlign, max → legacy ROIPooling).

    rois (R, 5) rows [batch_idx, x1, y1, x2, y2] — reference layout — or
    (B, K, 4|5) per-image rois (batched fast path: with flat rois every
    ROI dynamically gathers its whole (C, H, W) image, which at detection
    sizes moves GBs through HBM; the batched form maps over images so no
    cross-image gather exists)."""
    ph, pw = pooled_size if isinstance(pooled_size, (tuple, list)) else (pooled_size,) * 2
    sr = sample_ratio if sample_ratio > 0 else 2
    offset = 0.5 if aligned else 0.0

    H, W = data.shape[2], data.shape[3]

    def _weights(roi):
        """roi (4,) [x1,y1,x2,y2] -> bilinear weight mats (s,H), (t,W).

        Separable bilinear interpolation as two matmuls (MXU path; a
        per-point gather formulation is scatter-bound on TPU): weight of
        pixel h for sample y is the bilinear hat max(0, 1-|y-h|), which is
        exactly map_coordinates(order=1, mode="constant", cval=0)."""
        x1, y1, x2, y2 = (roi[0] * spatial_scale - offset,
                          roi[1] * spatial_scale - offset,
                          roi[2] * spatial_scale - offset,
                          roi[3] * spatial_scale - offset)
        rw = jnp.maximum(x2 - x1, 1.0 if not aligned else 1e-6)
        rh = jnp.maximum(y2 - y1, 1.0 if not aligned else 1e-6)
        ys = y1 + (jnp.arange(ph * sr) + 0.5) * rh / (ph * sr)
        xs = x1 + (jnp.arange(pw * sr) + 0.5) * rw / (pw * sr)
        wy = jnp.maximum(0.0, 1.0 - jnp.abs(ys[:, None] - jnp.arange(H)[None, :]))
        wx = jnp.maximum(0.0, 1.0 - jnp.abs(xs[:, None] - jnp.arange(W)[None, :]))
        return wy, wx

    if rois.ndim == 3:
        # batched fast path: (B, K, 4|5) rois belong to data[b] by position
        coords = rois[..., -4:]

        def one_img(img, r):  # img (C, H, W), r (K, 4)
            wy, wx = jax.vmap(_weights)(r)  # (K, s, H), (K, t, W)
            t1 = jnp.einsum("ksh,chw->kcsw", wy, img)
            sampled = jnp.einsum("kcsw,ktw->kcst", t1, wx)
            sampled = sampled.reshape(r.shape[0], img.shape[0], ph, sr, pw, sr)
            return reduce_fn(sampled, (3, 5))

        return jax.vmap(one_img)(data, coords)  # (B, K, C, ph, pw)

    def one_roi(roi):
        bidx = roi[0].astype(jnp.int32)
        img = data[bidx]  # (C, H, W)
        wy, wx = _weights(roi[1:5])
        t1 = jnp.einsum("sh,chw->csw", wy, img)
        sampled = jnp.einsum("csw,tw->cst", t1, wx)
        sampled = sampled.reshape(img.shape[0], ph, sr, pw, sr)
        return reduce_fn(sampled, (2, 4))

    return jax.vmap(one_roi)(rois)


@register("_contrib_ROIAlign", aliases=["ROIAlign", "roi_align"])
def roi_align(data, rois, pooled_size=(7, 7), spatial_scale=1.0, sample_ratio=-1,
              position_sensitive=False, aligned=False, **kw):
    """Bilinear ROI pooling (reference: ``roi_align.cc`` [unverified]).

    data (N, C, H, W); rois (R, 5) rows [batch_idx, x1, y1, x2, y2]
    -> (R, C, ph, pw), or the batched fast path rois (B, K, 4|5)
    -> (B, K, C, ph, pw) where rois[b] belong to data[b] (no cross-image
    gather — use this from detection heads).
    Average of sampled bilinear points per bin, matching the reference.
    """
    return _roi_sample(data, rois, pooled_size, spatial_scale, sample_ratio,
                       aligned, jnp.mean)


@register("ROIPooling", aliases=["roi_pooling"])
def roi_pooling(data, rois, pooled_size=(7, 7), spatial_scale=1.0, **kw):
    """Exact quantized max ROI pooling (legacy op, ``roi_pooling.cc``
    [unverified]): integer bin boundaries, max over cells — computed with
    static-shape range masks so XLA sees no dynamic gathers."""
    ph, pw = pooled_size if isinstance(pooled_size, (tuple, list)) else (pooled_size,) * 2
    N, C, H, W = data.shape
    rows = jnp.arange(H)
    cols = jnp.arange(W)
    obins_h = jnp.arange(ph)
    obins_w = jnp.arange(pw)

    def one_roi(roi):
        bidx = roi[0].astype(jnp.int32)
        img = data[bidx]  # (C, H, W)
        y1 = jnp.round(roi[2] * spatial_scale).astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * spatial_scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * spatial_scale).astype(jnp.int32)
        hlen = jnp.maximum(y2 - y1 + 1, 1)
        wlen = jnp.maximum(x2 - x1 + 1, 1)
        sh = y1 + (obins_h * hlen) // ph
        eh = y1 + -((-(obins_h + 1) * hlen) // ph)  # ceil division
        sw = x1 + (obins_w * wlen) // pw
        ew = x1 + -((-(obins_w + 1) * wlen) // pw)
        mask_r = (rows[None, :] >= sh[:, None]) & (rows[None, :] < eh[:, None])  # (ph, H)
        mask_c = (cols[None, :] >= sw[:, None]) & (cols[None, :] < ew[:, None])  # (pw, W)
        mask = mask_r[:, None, :, None] & mask_c[None, :, None, :]  # (ph, pw, H, W)
        big = jnp.where(mask[None], img[:, None, None, :, :], -jnp.inf)
        out = big.max(axis=(3, 4))  # (C, ph, pw)
        return jnp.where(jnp.isfinite(out), out, 0.0)

    return jax.vmap(one_roi)(rois)


@register("_contrib_PSROIPooling", aliases=["PSROIPooling", "psroipooling"])
def psroi_pooling(data, rois, spatial_scale=1.0, output_dim=None,
                  pooled_size=7, group_size=0, **kw):
    """Position-sensitive ROI pooling (R-FCN; reference:
    ``src/operator/contrib/psroi_pooling.cc`` [unverified]).

    data (B, C, H, W) with C = output_dim * group_size**2; rois (R, 5)
    rows [batch_idx, x1, y1, x2, y2] -> (R, output_dim, ps, ps). Output
    bin (i, j) of class channel k AVERAGES its own channel slice
    c = (k * gs + gy) * gs + gx over the bin's pixels (reference hard
    integer bins: floor/ceil bounds, empty bin -> 0).

    TPU-first formulation: per-bin membership is a pair of static-shape
    range masks (like ROIPooling above) so the whole op is masked
    reductions + one static gather — no dynamic shapes, fully
    differentiable w.r.t. data."""
    ps = int(pooled_size)
    gs = int(group_size) or ps
    B, C, H, W = data.shape
    K = int(output_dim) if output_dim else C // (gs * gs)
    if C != K * gs * gs:
        raise ValueError(
            f"PSROIPooling: C={C} must equal output_dim*group_size^2 "
            f"= {K}*{gs}^2")
    rows = jnp.arange(H)
    cols = jnp.arange(W)
    bins = jnp.arange(ps)
    # channel index per (k, i, j): position-sensitive slice selection
    gy = (jnp.arange(ps) * gs) // ps
    cidx = ((jnp.arange(K)[:, None, None] * gs + gy[None, :, None]) * gs
            + gy[None, None, :])  # (K, ps, ps)

    def one_roi(roi):
        bidx = roi[0].astype(jnp.int32)
        img = data[bidx]  # (C, H, W)
        x1 = jnp.round(roi[1]) * spatial_scale
        y1 = jnp.round(roi[2]) * spatial_scale
        x2 = jnp.round(roi[3] + 1.0) * spatial_scale
        y2 = jnp.round(roi[4] + 1.0) * spatial_scale
        bh = jnp.maximum(y2 - y1, 0.1) / ps
        bw = jnp.maximum(x2 - x1, 0.1) / ps
        sh = jnp.clip(jnp.floor(y1 + bins * bh), 0, H).astype(jnp.int32)
        eh = jnp.clip(jnp.ceil(y1 + (bins + 1) * bh), 0, H).astype(jnp.int32)
        sw = jnp.clip(jnp.floor(x1 + bins * bw), 0, W).astype(jnp.int32)
        ew = jnp.clip(jnp.ceil(x1 + (bins + 1) * bw), 0, W).astype(jnp.int32)
        mask_r = (rows[None, :] >= sh[:, None]) & \
            (rows[None, :] < eh[:, None])   # (ps, H)
        mask_c = (cols[None, :] >= sw[:, None]) & \
            (cols[None, :] < ew[:, None])   # (ps, W)
        # per-bin sums as two masked matmuls (MXU path)
        t = jnp.einsum("ih,chw->ciw", mask_r.astype(img.dtype), img)
        sums = jnp.einsum("ciw,jw->cij", t, mask_c.astype(img.dtype))
        cnt = (eh - sh)[:, None] * (ew - sw)[None, :]  # (ps, ps)
        avg = sums / jnp.maximum(cnt, 1)[None]
        avg = jnp.where((cnt > 0)[None], avg, 0.0)     # empty bin -> 0
        ii = jnp.arange(ps)[:, None]
        jj = jnp.arange(ps)[None, :]
        return avg[cidx, ii[None], jj[None]]           # (K, ps, ps)

    return jax.vmap(one_roi)(rois)


# ----------------------------------------------------------- pooling/resize
def _adaptive_matrix(in_size: int, out_size: int):
    w = _np.zeros((out_size, in_size), dtype=_np.float32)
    for o in range(out_size):
        s = (o * in_size) // out_size
        e = -((-(o + 1) * in_size) // out_size)  # ceil
        w[o, s:e] = 1.0 / (e - s)
    return jnp.asarray(w)


@register("_contrib_AdaptiveAvgPooling2D", aliases=["AdaptiveAvgPooling2D"])
def adaptive_avg_pooling(data, output_size=1, **kw):
    oh, ow = (output_size, output_size) if isinstance(output_size, int) else tuple(output_size)
    wh = _adaptive_matrix(data.shape[2], oh)
    ww = _adaptive_matrix(data.shape[3], ow)
    return jnp.einsum("nchw,oh,pw->ncop", data, wh, ww)


@register("_contrib_BilinearResize2D", aliases=["BilinearResize2D"])
def bilinear_resize(data, height=None, width=None, scale_height=None,
                    scale_width=None, mode="size", align_corners=True, **kw):
    n, c, h, w = data.shape
    oh = int(height) if height else int(h * scale_height)
    ow = int(width) if width else int(w * scale_width)
    if align_corners and oh > 1 and ow > 1:
        ys = jnp.linspace(0, h - 1, oh)
        xs = jnp.linspace(0, w - 1, ow)
        yy, xx = jnp.meshgrid(ys, xs, indexing="ij")
        coords = jnp.stack([yy.ravel(), xx.ravel()])

        def per_chan(ch):
            return jax.scipy.ndimage.map_coordinates(ch, coords, order=1).reshape(oh, ow)

        flat = data.reshape(n * c, h, w)
        return jax.vmap(per_chan)(flat).reshape(n, c, oh, ow)
    return jax.image.resize(data, (n, c, oh, ow), method="bilinear")


@register("_contrib_count_sketch", aliases=["count_sketch"],
          differentiable=False)
def count_sketch(data, h, s, out_dim=None, **kw):  # rarely used; minimal
    idx = h.astype(jnp.int32)
    signed = data * s
    out = jnp.zeros(data.shape[:-1] + (int(out_dim),), data.dtype)
    return out.at[..., idx].add(signed)


# ------------------------------------------------------- fused attention
# Below this key length the exact dense path beats the flash kernel on TPU:
# the whole (B,H,Sq,Sk) score tile fits comfortably in HBM/VMEM and XLA
# fuses qk->softmax->pv better than the kernel's block machinery amortizes
# (measured on v5e-lite, BERT b64 s128: dense 50.6 ms/step vs flash 57.3).
def _dense_max_seq() -> int:
    # read per call (advisor round-3): setting the var after import must
    # take effect; jit caching keys on the resulting branch anyway
    return int(_os.environ.get("MXTPU_ATTN_DENSE_MAX", "256"))


def _masked_softmax_probs(s, valid_length, causal, q_offset=None):
    """Shared mask+softmax semantics for both dense layouts: scores s
    are ALWAYS (B, H, Sq, Sk); keys past valid_length and acausal
    positions drop out; fully-masked rows (valid_length == 0) zero
    instead of NaN, like the flash kernel.

    ``q_offset`` shifts the query positions for the causal mask: query
    row i sits at absolute position ``q_offset + i``, so a single-token
    query attending over a KV cache of ``q_offset`` earlier entries gets
    the correct non-square mask (the incremental-decode contract) instead
    of the historical ``(L, L)`` square assumption. Scalar or per-row
    (B,), traced values welcome."""
    if valid_length is not None:
        mask = jnp.arange(s.shape[3])[None, None, None, :] < \
            valid_length.astype(jnp.int32)[:, None, None, None]
        s = jnp.where(mask, s, -jnp.inf)
    if causal:
        qi = jnp.arange(s.shape[2])[None, None, :, None]
        ki = jnp.arange(s.shape[3])[None, None, None, :]
        if q_offset is not None:
            off = jnp.asarray(q_offset, jnp.int32)
            # scalar offset broadcasts whole-batch; (B,) is per-row
            qi = qi + off.reshape((-1, 1, 1, 1))
        s = jnp.where(qi >= ki, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if valid_length is not None:
        p = jnp.where(jnp.isfinite(s).any(-1, keepdims=True), p, 0.0)
    return p


def _dense_attention(q, k, v, valid_length, causal, sm_scale,
                     q_offset=None):
    """Exact softmax attention over (B, H, S, D); f32 mask/softmax, grad
    via XLA autodiff. The score dot runs in the OPERAND dtype and
    upcasts after (identical for f32 inputs; the MXU accumulates bf16
    dots in f32 internally anyway): routing the upcast through astype
    makes the backward cast ds down BEFORE the dq/dk matmuls, so under
    AMP every dot stays low-precision — a `preferred_element_type=f32`
    score dot would leak an f32 cotangent into bf16 matmuls
    (the amp-purity pass of tools/mxlint.py flags exactly that)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    p = _masked_softmax_probs(s, valid_length, causal, q_offset)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


def _dense_attention_bshd(q, k, v, valid_length, causal, sm_scale,
                          q_offset=None):
    """Exact softmax attention over (B, S, H, D) operands: the einsums
    carry the head batch dim in place, so the model never writes a head
    transpose. Not a speed claim (the per-layer QKV copies in a BERT
    trace are XLA's backward-residual layout choice, not the
    transposes); kept as the default for the simpler graphs."""
    # score dot in operand dtype, f32 after (see _dense_attention: keeps
    # the backward's dq/dk matmuls low-precision under AMP)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    p = _masked_softmax_probs(s, valid_length, causal, q_offset)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


@register("_contrib_flash_attention", aliases=["flash_attention"])
def _flash_attention_op(query, key, value, valid_length=None, causal=False,
                        sm_scale=None, block_q=128, block_k=128,
                        layout="BHSD", q_offset=None, **kw):
    """Fused O(S)-memory attention (beyond-reference: replaces the O(L^2)
    interleaved ops of src/operator/contrib/transformer.cc [unverified] as
    the long-context path). ``layout``: "BHSD" (default) takes
    (B, H, S, D) operands; "BSHD" takes (B, S, H, D) — transpose-free
    for layers whose projections emit sequence-major tensors.
    ``valid_length`` (B,) masks padding keys (reference softmax
    ``use_length`` semantics).

    Short sequences (Sk <= MXTPU_ATTN_DENSE_MAX, default 256; read per
    call) take an exact dense path — at these sizes the score tile is
    small and XLA's fusion beats the flash kernel's block overhead; long
    sequences take the O(S)-memory Pallas flash kernel. Both are
    numerically exact softmax attention. NOTE the dense path materializes
    the O(Sq*Sk) score tensor: callers choosing this op specifically for
    O(S) memory at short S should set MXTPU_ATTN_DENSE_MAX=0.

    ``q_offset`` (scalar or (B,), traced ok) shifts causal query
    positions: query row i is at absolute position ``q_offset + i``.
    This is the incremental-decode mask — a ``query_len=1`` query over a
    KV cache of ``q_offset`` earlier entries. Offset and single-token
    queries always run the dense path: a (B, H, 1, Sk) score row IS
    O(Sk) memory, so the flash kernel's block machinery (which bakes in
    square (L, L) position math) buys nothing there."""
    from .pallas import flash_attention as _fa

    # keyword args bypass invoke()'s NDArray unwrapping — accept both
    # styles; NOT getattr(..., "data"): numpy arrays expose a memoryview
    if hasattr(valid_length, "asnumpy"):
        valid_length = valid_length.data
    if hasattr(q_offset, "asnumpy"):
        q_offset = q_offset.data
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(query.shape[-1])
    if layout == "BSHD":
        # transpose-free short-seq path; the Pallas kernel wants BHSD,
        # so long sequences pay the transpose only when they must
        if q_offset is not None or query.shape[1] == 1 or \
                max(query.shape[1], key.shape[1]) <= _dense_max_seq():
            return _dense_attention_bshd(query, key, value, valid_length,
                                         bool(causal), float(sm_scale),
                                         q_offset)
        tq, tk, tv = (x.transpose(0, 2, 1, 3)
                      for x in (query, key, value))
        out = _fa(tq, tk, tv, valid_length, bool(causal), sm_scale,
                  int(block_q), int(block_k))
        return out.transpose(0, 2, 1, 3)
    if q_offset is not None or query.shape[2] == 1 or \
            max(query.shape[2], key.shape[2]) <= _dense_max_seq():
        return _dense_attention(query, key, value, valid_length,
                                bool(causal), float(sm_scale), q_offset)
    return _fa(query, key, value, valid_length, bool(causal), sm_scale,
               int(block_q), int(block_k))


# ------------------------------------------------------------------ multibox
# SSD op trio (reference: ``src/operator/contrib/multibox_prior.cc``,
# ``multibox_target.cc``, ``multibox_detection.cc`` [unverified]). All pure
# jax: anchor generation is iota math, target assignment is an argmax
# bipartite match + optional hard negative mining, detection reuses
# box_decode + box_nms — each jit/vmap friendly.

@register("_contrib_MultiBoxPrior", aliases=["MultiBoxPrior"],
          differentiable=False)
def multibox_prior(data, sizes=(1.0,), ratios=(1.0,), clip=False,
                   steps=(-1.0, -1.0), offsets=(0.5, 0.5), **kw):
    """Anchor boxes for one feature map. data (B, C, H, W) ->
    (1, H*W*(len(sizes)+len(ratios)-1), 4) corner boxes, normalized.

    Reference conventions: ``steps``/``offsets`` are (y, x); anchor k at
    each pixel uses (size_k, ratio_0) for k < len(sizes), else
    (size_0, ratio_{k-len(sizes)+1}); widths carry the H/W aspect factor
    so a size-s ratio-1 anchor is square in image pixels."""
    H, W = data.shape[2], data.shape[3]
    sizes = tuple(float(s) for s in sizes)
    ratios = tuple(float(r) for r in ratios)
    step_y = steps[0] if steps[0] > 0 else 1.0 / H
    step_x = steps[1] if steps[1] > 0 else 1.0 / W
    cy = (jnp.arange(H, dtype=jnp.float32) + offsets[0]) * step_y
    cx = (jnp.arange(W, dtype=jnp.float32) + offsets[1]) * step_x
    cxg, cyg = jnp.meshgrid(cx, cy)  # (H, W)

    aspect = H / W  # size-s ratio-1 anchors stay square in pixel space
    ws, hs = [], []
    for k in range(len(sizes)):
        s, r = sizes[k], ratios[0]
        ws.append(s * aspect * math.sqrt(r))
        hs.append(s / math.sqrt(r))
    for j in range(1, len(ratios)):
        s, r = sizes[0], ratios[j]
        ws.append(s * aspect * math.sqrt(r))
        hs.append(s / math.sqrt(r))
    ws = jnp.asarray(ws, jnp.float32)  # (A,)
    hs = jnp.asarray(hs, jnp.float32)

    cxg = cxg[..., None]  # (H, W, 1)
    cyg = cyg[..., None]
    boxes = jnp.stack(
        [
            cxg - ws / 2, cyg - hs / 2, cxg + ws / 2, cyg + hs / 2,
        ],
        axis=-1,
    )  # (H, W, A, 4)
    out = boxes.reshape(1, -1, 4)
    if clip:
        out = jnp.clip(out, 0.0, 1.0)
    return out


_VARIANCES = (0.1, 0.1, 0.2, 0.2)


@register("_contrib_MultiBoxTarget", aliases=["MultiBoxTarget"],
          num_outputs=3, differentiable=False)
def multibox_target(anchors, labels, cls_preds, overlap_threshold=0.5,
                    ignore_label=-1.0, negative_mining_ratio=-1.0,
                    variances=_VARIANCES, **kw):
    """Training targets. anchors (1, N, 4) corner; labels (B, M, 5)
    [cls, xmin, ymin, xmax, ymax] padded with cls=-1; cls_preds
    (B, num_cls+1, N).

    -> (box_target (B, N*4), box_mask (B, N*4), cls_target (B, N) with
    0 = background, c+1 = object class c). Reference semantics: each
    ground truth claims its best anchor; other anchors match their best
    gt when IoU >= overlap_threshold. With ``negative_mining_ratio > 0``
    only the hardest ratio*num_pos negatives stay background; the rest
    get ``ignore_label`` (reference hard negative mining — ties at the
    confidence cutoff may keep a few extra negatives)."""
    anchors = anchors.reshape(-1, 4)
    N = anchors.shape[0]

    def per_image(lab, cp):
        cls = lab[:, 0]
        valid = cls >= 0  # (M,)
        M = lab.shape[0]
        gt = lab[:, 1:5]
        iou = box_iou(anchors[None], gt[None])[0]  # (N, M)
        iou = jnp.where(valid[None, :], iou, -1.0)
        best_gt = jnp.argmax(iou, axis=1)  # (N,)
        best_iou = jnp.max(iou, axis=1)
        matched = jnp.logical_and(best_iou >= overlap_threshold,
                                  best_iou > 0)
        # greedy bipartite matching (reference dmlc matcher): M rounds of
        # global-argmax over still-available (anchor, gt) pairs, so two
        # gts sharing a best anchor each claim a distinct one
        def bipartite_round(carry, _):
            gt_of, avail_a, avail_g = carry
            masked = jnp.where(
                jnp.logical_and(avail_a[:, None], avail_g[None, :]),
                iou, -1.0,
            )
            flat = jnp.argmax(masked)
            i, j = flat // M, flat % M
            ok = masked.reshape(-1)[flat] > 1e-12
            gt_of = jnp.where(
                ok, gt_of.at[i].set(j.astype(jnp.int32)), gt_of
            )
            avail_a = jnp.where(ok, avail_a.at[i].set(False), avail_a)
            avail_g = jnp.where(ok, avail_g.at[j].set(False), avail_g)
            return (gt_of, avail_a, avail_g), 0

        (gt_of_forced, _, _), _ = jax.lax.scan(
            bipartite_round,
            (jnp.full((N,), -1, jnp.int32), jnp.ones((N,), bool), valid),
            None, length=M,
        )
        forced = gt_of_forced >= 0
        assign = jnp.where(forced, jnp.maximum(gt_of_forced, 0), best_gt)
        pos = jnp.logical_or(matched, forced)

        # encode via the shared box_encode kernel (batch of 1)
        targets, mask = box_encode(
            pos[None].astype(jnp.float32), assign[None], anchors[None],
            gt[None], stds=tuple(variances),
        )
        bt = targets[0].reshape(-1)
        bm = mask[0].reshape(-1)
        ct = jnp.where(pos, cls[assign].astype(jnp.int32) + 1, 0)
        ct = ct.astype(jnp.float32)
        if negative_mining_ratio > 0:
            probs = jax.nn.softmax(cp, axis=0)  # (num_cls+1, N)
            neg_conf = jnp.where(pos, -jnp.inf, 1.0 - probs[0])
            k = (negative_mining_ratio * jnp.sum(pos)).astype(jnp.int32)
            k = jnp.clip(k, 0, N - 1)
            thresh = jnp.sort(neg_conf)[::-1][jnp.maximum(k - 1, 0)]
            keep_neg = jnp.logical_and(
                jnp.logical_and(~pos, neg_conf >= thresh), k > 0
            )
            ct = jnp.where(jnp.logical_or(pos, keep_neg), ct,
                           jnp.float32(ignore_label))
        return bt, bm, ct

    bt, bm, ct = jax.vmap(per_image)(labels, cls_preds)
    return bt, bm, ct


@register("_contrib_MultiBoxDetection", aliases=["MultiBoxDetection"],
          differentiable=False)
def multibox_detection(cls_probs, loc_preds, anchors, clip=True,
                       threshold=0.01, nms_threshold=0.5, force_suppress=False,
                       nms_topk=-1, variances=_VARIANCES, **kw):
    """Decode + NMS. cls_probs (B, num_cls+1, N) softmaxed (class 0 =
    background); loc_preds (B, N*4); anchors (1, N, 4) ->
    (B, N, 6) rows [cls_id, score, xmin, ymin, xmax, ymax], suppressed
    rows get cls_id -1 (reference output convention)."""
    anchors = anchors.reshape(-1, 4)
    N = anchors.shape[0]
    v = tuple(variances)

    def per_image(probs, locs):
        # best foreground class per anchor
        fg = probs[1:]  # (num_cls, N)
        cls_id = jnp.argmax(fg, axis=0).astype(jnp.float32)
        score = jnp.max(fg, axis=0)
        keep = score > threshold
        cls_id = jnp.where(keep, cls_id, -1.0)
        boxes = box_decode(
            locs.reshape(1, N, 4), anchors[None], std0=v[0], std1=v[1],
            std2=v[2], std3=v[3], clip=10.0,
        )[0]
        if clip:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        det = jnp.concatenate(
            [cls_id[:, None], score[:, None], boxes], axis=-1
        )  # (N, 6)
        out = box_nms(det[None], overlap_thresh=nms_threshold,
                      valid_thresh=threshold, topk=nms_topk, coord_start=2,
                      score_index=1, id_index=0,
                      force_suppress=force_suppress)[0]
        # box_nms flags suppression by score=-1; the reference's detection
        # output convention is cls_id=-1 for invalid rows
        return out.at[:, 0].set(jnp.where(out[:, 1] < 0, -1.0, out[:, 0]))

    return jax.vmap(per_image)(cls_probs, loc_preds)


# -------------------------------------------------------------- faster-rcnn
def _rpn_anchors(H, W, feature_stride, scales, ratios):
    """Pixel-space base anchors at every feature position.

    Reference ``src/operator/contrib/proposal.cc`` GenerateAnchors
    [unverified]: a base box of side ``feature_stride`` centered on each
    position, reshaped per (ratio, scale) keeping area (ratio) / scaling
    sides (scale). Returns (H*W*A, 4) corner boxes, A = len(ratios)*len(scales).
    """
    base = float(feature_stride)
    cx = (jnp.arange(W, dtype=jnp.float32) + 0.5) * base
    cy = (jnp.arange(H, dtype=jnp.float32) + 0.5) * base
    ws, hs = [], []
    for r in ratios:
        for s in scales:
            w = base * float(s) / math.sqrt(float(r))
            h = base * float(s) * math.sqrt(float(r))
            ws.append(w)
            hs.append(h)
    ws = jnp.asarray(ws, jnp.float32)  # (A,)
    hs = jnp.asarray(hs, jnp.float32)
    cxg, cyg = jnp.meshgrid(cx, cy)  # (H, W)
    cxg = cxg[:, :, None]
    cyg = cyg[:, :, None]
    boxes = jnp.stack([
        cxg - ws / 2, cyg - hs / 2, cxg + ws / 2, cyg + hs / 2,
    ], axis=-1)  # (H, W, A, 4)
    return boxes.reshape(-1, 4)


def _rcnn_decode(anchors, deltas, clip_hw=None):
    """Standard R-CNN box decoding (no stds): anchors/deltas (..., 4)."""
    ax1, ay1, ax2, ay2 = jnp.split(anchors, 4, axis=-1)
    dx, dy, dw, dh = jnp.split(deltas, 4, axis=-1)
    aw, ah = ax2 - ax1, ay2 - ay1
    acx, acy = ax1 + aw / 2, ay1 + ah / 2
    cx = acx + dx * aw
    cy = acy + dy * ah
    w = aw * jnp.exp(jnp.clip(dw, -10.0, 10.0))
    h = ah * jnp.exp(jnp.clip(dh, -10.0, 10.0))
    out = jnp.concatenate([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2],
                          axis=-1)
    if clip_hw is not None:
        hlim, wlim = clip_hw
        out = jnp.stack([
            jnp.clip(out[..., 0], 0, wlim - 1.0),
            jnp.clip(out[..., 1], 0, hlim - 1.0),
            jnp.clip(out[..., 2], 0, wlim - 1.0),
            jnp.clip(out[..., 3], 0, hlim - 1.0),
        ], axis=-1)
    return out


@register("_contrib_Proposal", aliases=["Proposal"], num_outputs=None,
          differentiable=False)
def proposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
             rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
             scales=(4, 8, 16, 32), ratios=(0.5, 1, 2),
             feature_stride=16, output_score=False, layout="batched",
             **kw):
    """RPN proposal generation (reference ``proposal.cc`` [unverified]).

    cls_prob (B, 2A, H, W) — [:, :A] background, [:, A:] foreground
    scores; bbox_pred (B, 4A, H, W); im_info (B, 3) rows [h, w, scale].

    TPU-first deviations from the reference, both static-shape driven:
    rois come back BATCHED as (B, rpn_post_nms_top_n, 5) rows
    [batch_idx, x1, y1, x2, y2] (the flat (B*N, 5) reference layout is a
    reshape away; the batched form feeds the batched ROIAlign directly),
    and slots past the survivor count hold the highest-scoring suppressed
    boxes (score -1 in the score output) rather than shrinking.
    """
    B = cls_prob.shape[0]
    H, W = cls_prob.shape[2], cls_prob.shape[3]
    A = cls_prob.shape[1] // 2
    if A != len(scales) * len(ratios):
        raise ValueError(
            f"cls_prob carries {A} anchors/position but scales x ratios "
            f"defines {len(scales) * len(ratios)}"
        )
    anchors = _rpn_anchors(H, W, feature_stride, scales, ratios)  # (HWA, 4)
    N = anchors.shape[0]

    # (B, A, H, W) -> (B, H, W, A) -> (B, HWA): match the anchor layout
    fg = jnp.transpose(cls_prob[:, A:], (0, 2, 3, 1)).reshape(B, N)
    deltas = bbox_pred.reshape(B, A, 4, H, W)
    deltas = jnp.transpose(deltas, (0, 3, 4, 1, 2)).reshape(B, N, 4)

    def one(fg_b, deltas_b, info):
        boxes = _rcnn_decode(anchors, deltas_b, clip_hw=(info[0], info[1]))
        ws = boxes[:, 2] - boxes[:, 0] + 1.0
        hs = boxes[:, 3] - boxes[:, 1] + 1.0
        min_sz = rpn_min_size * info[2]
        score = jnp.where((ws >= min_sz) & (hs >= min_sz), fg_b, -jnp.inf)
        k1 = min(int(rpn_pre_nms_top_n), N)
        top_scores, top_idx = jax.lax.top_k(score, k1)
        top_boxes = boxes[top_idx]
        dets = jnp.concatenate([
            jnp.zeros((k1, 1)), top_scores[:, None], top_boxes,
        ], axis=-1)
        kept = box_nms(dets, overlap_thresh=threshold,
                       topk=int(rpn_post_nms_top_n), coord_start=2,
                       score_index=1, id_index=0)
        ord_scores, ord_idx = jax.lax.top_k(kept[:, 1],
                                            int(rpn_post_nms_top_n))
        rois = kept[ord_idx, 2:6]
        return rois, ord_scores

    rois, scores = jax.vmap(one)(fg, deltas, im_info)
    bidx = jnp.broadcast_to(
        jnp.arange(B, dtype=rois.dtype)[:, None, None],
        (B, rois.shape[1], 1),
    )
    rois = jnp.concatenate([bidx, rois], axis=-1)
    if layout == "flat":
        # reference proposal.cc emitted flat (B*N, 5) rows — one reshape
        # away from the batched form (advisor round 3: ported consumers
        # index this layout)
        rois = rois.reshape(-1, 5)
        if output_score:
            return rois, scores.reshape(-1, 1)
        return rois
    if output_score:
        return rois, scores[..., None]
    return rois


@register("_contrib_rcnn_target_sampler", aliases=["rcnn_target_sampler"],
          num_outputs=4, differentiable=False)
def rcnn_target_sampler(rois, gt_boxes, num_sample=128, pos_ratio=0.25,
                        pos_iou_thresh=0.5, bg_iou_low=0.0,
                        box_stds=(0.1, 0.1, 0.2, 0.2), **kw):
    """Second-stage target sampling + encoding (reference: the rcnn
    ``proposal_target`` operator / GluonCV RCNNTargetSampler+Generator
    [unverified]) with static shapes.

    rois (B, R, 4|5) proposals (batch-idx column ignored if present);
    gt_boxes (B, M, 5) rows [cls, x1, y1, x2, y2], cls < 0 = padding.

    Returns (sampled_rois (B, S, 4), cls_targets (B, S) int32 with
    0 = background and gt cls k -> k+1, box_targets (B, S, 4),
    box_masks (B, S, 4)); S = num_sample. Selection is deterministic
    top-by-IoU (foregrounds first, capped at pos_ratio*S, then the
    highest-IoU backgrounds) — the reference sampled randomly; determinism
    is the jit-friendly choice and tests/training treat it as the
    hardest-example variant.
    """
    rois = rois[..., -4:]
    S = int(num_sample)
    num_fg = int(round(S * float(pos_ratio)))

    def one(rois_b, gt_b):
        gt_cls = gt_b[:, 0]
        gt_box = gt_b[:, 1:5]
        valid_gt = gt_cls >= 0
        iou = box_iou(rois_b, gt_box)  # (R, M)
        iou = jnp.where(valid_gt[None, :], iou, 0.0)
        best_gt = jnp.argmax(iou, axis=1)
        best_iou = jnp.max(iou, axis=1)
        is_fg = best_iou >= pos_iou_thresh
        fg_key = jnp.where(is_fg, best_iou, -jnp.inf)
        _, fg_idx = jax.lax.top_k(fg_key, num_fg)
        bg_key = jnp.where(~is_fg & (best_iou >= bg_iou_low), best_iou,
                           -jnp.inf)
        _, bg_idx = jax.lax.top_k(bg_key, S - num_fg)
        sel = jnp.concatenate([fg_idx, bg_idx])
        sel_rois = rois_b[sel]
        sel_iou = best_iou[sel]
        sel_fg = is_fg[sel]
        # fg slots past the actual fg count carry non-fg rois; their
        # sel_fg is False so they fall through to background cleanly
        sel_gt = best_gt[sel]
        cls_t = jnp.where(sel_fg, gt_cls[sel_gt].astype(jnp.int32) + 1, 0)
        matched = gt_box[sel_gt]
        # center-form encoding with stds (the reference's bbox_transform)
        ax1, ay1, ax2, ay2 = jnp.split(sel_rois, 4, axis=-1)
        gx1, gy1, gx2, gy2 = jnp.split(matched, 4, axis=-1)
        aw = jnp.maximum(ax2 - ax1, 1e-6)
        ah = jnp.maximum(ay2 - ay1, 1e-6)
        gw = jnp.maximum(gx2 - gx1, 1e-6)
        gh = jnp.maximum(gy2 - gy1, 1e-6)
        t = jnp.concatenate([
            ((gx1 + gw / 2) - (ax1 + aw / 2)) / aw / box_stds[0],
            ((gy1 + gh / 2) - (ay1 + ah / 2)) / ah / box_stds[1],
            jnp.log(gw / aw) / box_stds[2],
            jnp.log(gh / ah) / box_stds[3],
        ], axis=-1)
        mask = sel_fg[:, None].astype(t.dtype) * jnp.ones_like(t)
        return sel_rois, cls_t, t * mask, mask

    return jax.vmap(one)(rois, gt_boxes)


# ------------------------------------------------------ deformable conv
def _deform_columns(data, offset, kernel, stride, dilate, pad,
                    num_deformable_group=1, num_group=1):
    """Deformed im2col: ONE vectorized bilinear gather (map_coordinates
    order=1, zeros outside) -> (B, C, kh*kw, Ho, Wo). Shared by
    DeformableConvolution v1 and the modulated v2."""
    from jax.scipy.ndimage import map_coordinates

    if num_group != 1:
        raise NotImplementedError(
            "grouped deformable convolution not supported yet"
        )
    kh, kw = (kernel, kernel) if isinstance(kernel, int) else tuple(kernel)
    sh, sw = (stride, stride) if isinstance(stride, int) else tuple(stride)
    dh, dw = (dilate, dilate) if isinstance(dilate, int) else tuple(dilate)
    ph, pw = (pad, pad) if isinstance(pad, int) else tuple(pad)
    B, C, H, W = data.shape
    G = int(num_deformable_group)
    Ho = (H + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    Wo = (W + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    K = kh * kw

    # base sampling grid per output position and tap (Ho, Wo) + (K,)
    oy = jnp.arange(Ho) * sh - ph
    ox = jnp.arange(Wo) * sw - pw
    ty = jnp.arange(kh) * dh
    tx = jnp.arange(kw) * dw
    base_y = oy[None, :, None] + ty.repeat(kw)[:, None, None]  # (K, Ho, 1)
    base_x = jnp.tile(tx, kh)[:, None, None] + ox[None, None, :]  # (K,1,Wo)

    if offset.shape[2] != Ho or offset.shape[3] != Wo:
        raise ValueError(
            f"offset spatial shape {offset.shape[2:]} must equal the "
            f"OUTPUT spatial shape ({Ho}, {Wo}) (reference contract); "
            "with stride > 1 an input-resolution offset map would be "
            "silently misaligned"
        )
    off = offset.reshape(B, G, K, 2, Ho, Wo)
    sy = base_y[None, None] + off[:, :, :, 0]   # (B, G, K, Ho, Wo)
    sx = base_x[None, None] + off[:, :, :, 1]

    cg = C // G  # channels per deformable group

    def sample_one(img2d, yy, xx):
        # img2d (H, W); yy/xx (K, Ho, Wo) -> (K, Ho, Wo)
        return map_coordinates(img2d, [yy, xx], order=1, mode="constant",
                               cval=0.0)

    # vmap over channels within a group, groups, batch
    sample_c = jax.vmap(sample_one, in_axes=(0, None, None))     # C_g imgs
    sample_g = jax.vmap(sample_c, in_axes=(0, 0, 0))             # groups
    sample_b = jax.vmap(sample_g, in_axes=(0, 0, 0))             # batch
    dg = data.reshape(B, G, cg, H, W)
    cols = sample_b(dg, sy, sx)          # (B, G, cg, K, Ho, Wo)
    return cols.reshape(B, C, K, Ho, Wo)


@register("_contrib_DeformableConvolution",
          aliases=["DeformableConvolution", "deformable_convolution"])
def deformable_convolution(data, offset, weight, bias=None, kernel=(3, 3),
                           stride=(1, 1), dilate=(1, 1), pad=(0, 0),
                           num_filter=None, num_deformable_group=1,
                           num_group=1, no_bias=False, **kw):
    """Deformable convolution v1 (reference:
    ``src/operator/contrib/deformable_convolution.cc`` [unverified]).

    data (B, C, H, W); offset (B, 2*G*kh*kw, H', W') with per-position
    (dy, dx) for every kernel tap, G = num_deformable_group (channel
    groups sharing an offset field); weight (O, C/num_group, kh, kw).

    TPU-first formulation: the deformed sampling is ONE vectorized
    bilinear gather (jax.scipy map_coordinates order=1, zero padding
    outside — the reference's im2col-with-offsets), producing the
    (B, C, kh*kw, H', W') column tensor, and the conv collapses to a
    single einsum on the MXU. Fully differentiable w.r.t. data, offset,
    and weight through XLA autodiff — the reference hand-wrote those
    three backward kernels.
    """
    B, C, H, W = data.shape
    cols = _deform_columns(data, offset, kernel, stride, dilate, pad,
                           num_deformable_group=num_deformable_group,
                           num_group=num_group)
    wflat = weight.reshape(weight.shape[0], C, cols.shape[2])
    out = jnp.einsum("bckhw,ock->bohw", cols, wflat)
    if bias is not None and not no_bias:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


@register("_contrib_ModulatedDeformableConvolution",
          aliases=["ModulatedDeformableConvolution",
                   "modulated_deformable_convolution"])
def modulated_deformable_convolution(data, offset, mask, weight, bias=None,
                                     kernel=(3, 3), stride=(1, 1),
                                     dilate=(1, 1), pad=(0, 0),
                                     num_filter=None,
                                     num_deformable_group=1, num_group=1,
                                     no_bias=False, **kw):
    """Deformable convolution v2 (reference:
    ``src/operator/contrib/modulated_deformable_convolution.cc``
    [unverified]): v1 plus a learned per-tap modulation scalar —
    ``mask`` (B, G*kh*kw, H', W'), already sigmoid-activated by the
    caller per the reference contract — multiplying each sampled column.

    Same TPU-first formulation as v1: one vectorized bilinear gather
    builds the column tensor, the modulation is a broadcast multiply
    XLA fuses into it, and the conv is a single MXU einsum; all three
    hand-written reference backward kernels come from autodiff."""
    kh, kw = (kernel, kernel) if isinstance(kernel, int) else tuple(kernel)
    B, C, H, W = data.shape
    G = int(num_deformable_group)
    K = kh * kw
    cols = _deform_columns(data, offset, kernel, stride, dilate, pad,
                           num_deformable_group=G, num_group=num_group)
    Ho, Wo = cols.shape[-2:]
    if mask.shape != (B, G * K, Ho, Wo):
        raise ValueError(
            f"mask shape {mask.shape} must be (B, G*kh*kw, Ho, Wo) = "
            f"({B}, {G * K}, {Ho}, {Wo})")
    m = mask.reshape(B, G, 1, K, Ho, Wo)
    cols = (cols.reshape(B, G, C // G, K, Ho, Wo) * m).reshape(
        B, C, K, Ho, Wo)
    wflat = weight.reshape(weight.shape[0], C, K)
    out = jnp.einsum("bckhw,ock->bohw", cols, wflat)
    if bias is not None and not no_bias:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


# ------------------------------------------------------- round-5 contrib tail
@register("_contrib_quadratic", aliases=["quadratic"])
def quadratic(data, a=0.0, b=0.0, c=0.0, **kw):
    """a*x^2 + b*x + c (the reference's tutorial contrib op,
    ``src/operator/contrib/quadratic_op.cc`` [unverified])."""
    return a * jnp.square(data) + b * data + c


@register("_contrib_allclose", aliases=["allclose"], differentiable=False)
def allclose_op(a, b, rtol=1e-5, atol=1e-8, equal_nan=False, **kw):
    """1.0 iff allclose (reference ``_contrib_allclose``)."""
    return jnp.allclose(a, b, rtol=rtol, atol=atol,
                        equal_nan=equal_nan).astype(jnp.float32).reshape(1)


@register("_contrib_index_copy", aliases=["index_copy"])
def index_copy(old, index, new, **kw):
    """Copy rows of ``new`` into ``old`` at ``index`` (reference
    ``src/operator/contrib/index_copy.cc`` [unverified]); functional
    result, differentiable through both data inputs."""
    return old.at[index.astype(jnp.int32)].set(new)


@register("_contrib_index_array", aliases=["index_array"],
          differentiable=False)
def index_array(data, axes=None, **kw):
    """Per-element N-d indices (reference ``index_array``): output
    data.shape + (len(axes),)."""
    nd_ = data.ndim
    ax = tuple(axes) if axes is not None else tuple(range(nd_))
    grids = jnp.meshgrid(*[jnp.arange(s) for s in data.shape],
                         indexing="ij")
    return jnp.stack([grids[a] for a in ax], axis=-1).astype(jnp.int32)


def _grad_mult_fwd(data, scalar):
    return data, scalar


def _grad_mult_bwd(res, ct):
    return ct * res, None


@jax.custom_vjp
def _grad_mult(data, scalar):
    return data


_grad_mult.defvjp(lambda d, s: (d, s), lambda s, ct: (ct * s, None))


@register("_contrib_gradientmultiplier", aliases=["gradientmultiplier"])
def gradientmultiplier(data, scalar=1.0, **kw):
    """Identity forward, gradient scaled by ``scalar`` (reference
    ``src/operator/contrib/gradient_multiplier_op.cc`` [unverified] —
    the GRL building block with negative scalar)."""
    return _grad_mult(data, jnp.asarray(scalar, data.dtype))


@jax.custom_vjp
def _rounded_ste(data):
    return jnp.round(data)


_rounded_ste.defvjp(lambda d: (jnp.round(d), None), lambda _, ct: (ct,))


@register("_contrib_round_ste", aliases=["round_ste", "rounded_ste",
                                         "_contrib_rounded_ste"])
def round_ste(data, **kw):
    """Straight-through round (reference ``_contrib_round_ste``,
    quantization-aware training)."""
    return _rounded_ste(data)


@jax.custom_vjp
def _sign_ste(data):
    return jnp.sign(data)


_sign_ste.defvjp(lambda d: (jnp.sign(d), None), lambda _, ct: (ct,))


@register("_contrib_sign_ste", aliases=["sign_ste"])
def sign_ste(data, **kw):
    return _sign_ste(data)


@register("_contrib_boolean_mask", aliases=["boolean_mask"],
          differentiable=False)
def boolean_mask(data, index, axis=0, **kw):
    """Select rows where index != 0 (reference
    ``src/operator/contrib/boolean_mask.cc`` [unverified]).

    Data-dependent OUTPUT SHAPE: like ``unique``, this op cannot live
    under jit/bulking (it is deny-listed) — it materializes the mask on
    host and returns the packed selection, matching the reference's
    dynamic-shape contract."""
    import numpy as _onp

    m = _onp.asarray(index) != 0
    return jnp.take(data, jnp.asarray(_onp.nonzero(m)[0]), axis=axis)


@register("_contrib_edge_id", aliases=["edge_id"], differentiable=False)
def edge_id(data, u, v, **kw):
    """Edge ids for (u, v) pairs in a dense adjacency-style matrix
    (reference DGL helper ``src/operator/contrib/dgl_graph.cc``
    [unverified]): returns data[u[i], v[i]] per pair, -1 where the
    entry is zero (no edge)."""
    uu = u.astype(jnp.int32)
    vv = v.astype(jnp.int32)
    vals = data[uu, vv]
    return jnp.where(vals != 0, vals, -1.0).astype(data.dtype)

"""BASELINE config 5: SSD/Faster-RCNN detection head — the custom CV ops
(box_decode -> box_nms -> ROIAlign over kept boxes), jitted end-to-end.

The backbone is config 2's job; this isolates the contrib detection ops
the reference implemented as CUDA kernels (``bounding_box.cc``,
``roi_align.cc`` [unverified])."""

from __future__ import annotations

import functools

import numpy as np

from .common import run_bench

BATCH = 32
NUM_ANCHORS = 4096
NUM_ROIS = 100
# no reference number exists (BASELINE.json published={}); the target is
# a round number kept so that regressions show.
CEILING = 3.9e3


def main():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import contrib as C

    rng = np.random.RandomState(0)
    # synthetic head inputs: per-anchor box deltas, scores, FPN feature map
    deltas = jnp.asarray(rng.randn(BATCH, NUM_ANCHORS, 4).astype(np.float32))
    cx = rng.rand(BATCH, NUM_ANCHORS, 2).astype(np.float32)
    wh = (rng.rand(BATCH, NUM_ANCHORS, 2) * 0.2 + 0.05).astype(np.float32)
    anchors = jnp.asarray(
        np.concatenate([cx - wh / 2, cx + wh / 2], -1)
    )
    scores = jnp.asarray(rng.rand(BATCH, NUM_ANCHORS, 1).astype(np.float32))
    feats = jnp.asarray(rng.randn(BATCH, 256, 64, 64).astype(np.float32))

    def head(deltas, anchors, scores, feats):
        boxes = C.box_decode(deltas, anchors, format="corner")
        dets = jnp.concatenate([jnp.zeros_like(scores), scores, boxes], -1)
        kept = C.box_nms(dets, overlap_thresh=0.5, topk=NUM_ROIS,
                         coord_start=2, score_index=1, id_index=0)
        # box_nms is position-preserving (suppressed scores -> -1 in place),
        # so gather the actual survivors by top-k on the output scores
        _, idx = jax.lax.top_k(kept[:, :, 1], NUM_ROIS)
        survivors = jnp.take_along_axis(kept, idx[:, :, None], axis=1)
        # survivor rois per image -> batched ROIAlign (B, K, 4): rois stay
        # grouped by image, so no per-ROI whole-image gather (the flat
        # (R, 5) form moved ~4 MB of feature map per ROI through HBM)
        rois_xy = survivors[:, :, 2:6] * 64.0
        pooled = C.roi_align(feats, rois_xy, pooled_size=(7, 7),
                             spatial_scale=1.0, sample_ratio=2)
        return kept, pooled

    CALLS_PER_DISPATCH = 64

    @jax.jit
    def head_n(deltas, anchors, scores, feats):
        # CALLS_PER_DISPATCH full head evaluations per dispatch
        # (device-side scan, the same dispatch amortization the
        # training configs use); scores are perturbed per iteration so
        # XLA cannot hoist the loop body
        def body(acc, i):
            kept, pooled = head(deltas, anchors,
                                scores + i * 1e-6, feats)
            return acc + jnp.sum(pooled[:1]) + jnp.sum(kept[:1, :1]), None

        acc, _ = jax.lax.scan(
            body, jnp.float32(0.0),
            jnp.arange(CALLS_PER_DISPATCH, dtype=jnp.float32))
        return acc

    run_bench(
        "ssd_head_box_decode_nms_roialign_images_per_sec", "images/sec",
        CEILING, functools.partial(head_n, deltas, anchors, scores, feats),
        # sync via the scalar the scan already reduced: a single 4-byte
        # fetch (pulling a tensor slice would time the transfer instead)
        float, BATCH * CALLS_PER_DISPATCH,
        warmup=3, steps=8,
    )


if __name__ == "__main__":
    main()

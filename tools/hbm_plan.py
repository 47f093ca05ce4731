#!/usr/bin/env python
"""Memory-guided batch planning: the largest per-bucket batch that fits
HBM under a target headroom.

Walks a ``FixedBucketSampler``-style bucket menu (PR 3's
``signatures()`` shape contract) and, for each bucket key, searches the
largest global batch whose compiled ``TrainStep`` executable fits the
planning budget — ``TrainStep.memory_analysis`` over abstract avals, so
nothing is materialized and no step runs. The budget is the device HBM
limit (or ``--hbm-bytes`` / ``MXTPU_HBM_BYTES`` on rigs without memory
stats) shaved by ``MXTPU_HBM_HEADROOM``.

The demo model is a small ``TransformerModel`` (size it with
``--units``/``--layers``/``--vocab``); ``--amp``/``--remat`` show how
mixed precision and rematerialization move the fitting batch. What a
fitting batch is worth in throughput is measured by a cell
(``perf/run.py``), not here.

Example (CPU rig, synthetic 2 GB budget)::

    MXTPU_HBM_BYTES=2e9 python tools/hbm_plan.py --amp bfloat16 \
        --remat dots_saveable

Prints one JSON row per bucket plus a summary row.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root


def build_step(args, amp=None, remat=None, mesh=None, sharding=None):
    import numpy as np  # noqa: F401

    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer as opt
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import TrainStep

    net = TransformerModel(
        src_vocab=args.vocab, tgt_vocab=args.vocab, units=args.units,
        hidden_size=args.units * 2, num_layers=args.layers,
        num_heads=max(2, args.units // 32), max_length=args.max_len + 8,
        dropout=0.0)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))

    class MaskedCE:
        def __call__(self, logits, label):
            x = logits.data.astype(jnp.float32)
            y = label.data
            mask = y >= 0
            safe = jnp.where(mask, y, 0).astype(jnp.int32)
            logp = jax.nn.log_softmax(x, axis=-1)
            nll = -jnp.take_along_axis(logp, safe[..., None],
                                       axis=-1)[..., 0]
            row = jnp.where(mask, nll, 0.0).sum(axis=-1)
            return NDArray(row.sum() / mask.sum())

    return TrainStep(net, MaskedCE(), opt.AdamW(learning_rate=1e-4),
                     amp=amp, remat=remat, mesh=mesh, sharding=sharding)


def plan(step, bucket_keys, budget, start=1, max_batch=65536):
    """One row per bucket key: the largest batch whose compiled step
    fits ``budget`` bytes."""
    from mxnet_tpu.parallel import plan_batch

    rows = []
    for key in bucket_keys:
        def sig(bs, _key=key):
            return ((((bs, _key), "int32"),) * 2 + (((bs, _key), "int32"),))

        batch, peak = plan_batch(step, sig, budget, start=start,
                                 max_batch=max_batch)
        row = {"bucket_key": int(key), "max_batch": int(batch),
               "peak_bytes": int(peak) if peak is not None else None,
               "budget_bytes": int(budget)}
        mesh = getattr(step, "_mesh", None)
        if mesh is not None:
            # the budget is ONE device's HBM; with a mesh, plan_batch
            # bisected the PER-SHARD peak against it (the mesh splits
            # the working set mesh.size ways)
            row["mesh_devices"] = int(mesh.size)
            row["per_shard"] = int(mesh.size) > 1
        rows.append(row)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--buckets", type=int, nargs="*",
                    default=[16, 32, 48, 64],
                    help="bucket keys (sequence lengths) to plan for")
    ap.add_argument("--hbm-bytes", type=float, default=None,
                    help="HBM limit override (else device stats / "
                         "MXTPU_HBM_BYTES)")
    ap.add_argument("--amp", default=None,
                    help="bfloat16|float16 mixed precision")
    ap.add_argument("--remat", default=None,
                    help="remat policy (mxnet_tpu.remat.POLICIES)")
    ap.add_argument("--mesh", default=None,
                    help="device mesh spec ('4', '2x2', 'data=2,model=2',"
                         " 'auto'); the plan then bisects the PER-SHARD "
                         "peak against the per-device budget")
    ap.add_argument("--sharding", default=None,
                    help="sharding rules preset for --mesh: 'replicated' "
                         "(data parallel) or 'fsdp' (params+moments "
                         "sharded; default when --mesh is set)")
    ap.add_argument("--units", type=int, default=32)
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--vocab", type=int, default=1000)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--start", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=4096)
    args = ap.parse_args(argv)
    args.max_len = max(args.max_len, max(args.buckets))

    from mxnet_tpu.parallel import hbm_budget_bytes

    budget = hbm_budget_bytes(
        int(args.hbm_bytes) if args.hbm_bytes else None)
    if budget is None:
        print("no HBM limit known: pass --hbm-bytes or set "
              "MXTPU_HBM_BYTES (no device memory stats on this backend)",
              file=sys.stderr)
        return 2

    mesh = None
    sharding = args.sharding
    if args.mesh:
        from mxnet_tpu.parallel import sharding as _shard

        mesh = _shard.make_global_mesh(args.mesh)
        if sharding is None:
            sharding = "fsdp"
    step = build_step(args, amp=args.amp, remat=args.remat, mesh=mesh,
                      sharding=sharding)
    rows = plan(step, args.buckets, budget, start=args.start,
                max_batch=args.max_batch)
    mesh_str = None
    if mesh is not None:
        from mxnet_tpu.parallel import sharding as _shard

        mesh_str = _shard.mesh_shape_str(mesh)
    for r in rows:
        r.update({"amp": args.amp, "remat": args.remat,
                  "mesh": mesh_str, "sharding": sharding})
        print(json.dumps(r))
    fitting = [r for r in rows if r["max_batch"] > 0]
    print(json.dumps({
        "metric": "hbm_plan_max_batch",
        "value": max((r["max_batch"] for r in fitting), default=0),
        "unit": "samples",
        "budget_bytes": int(budget),
        "amp": args.amp, "remat": args.remat,
        "mesh": mesh_str, "sharding": sharding,
        "buckets_fitting": len(fitting), "buckets_total": len(rows),
    }))
    return 0 if fitting else 1


if __name__ == "__main__":
    sys.exit(main())

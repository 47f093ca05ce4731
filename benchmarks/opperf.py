"""Operator micro-benchmark harness (reference: ``benchmark/opperf/``
[unverified]).

Times registered operators one by one — eager dispatch and jit-compiled —
and prints per-op rows plus a JSON summary. The op set covers the
reference harness's categories (unary/binary math, reductions, NN core,
contrib detection ops); ``--ops`` selects a subset.

    python -m benchmarks.opperf --runs 50
    python -m benchmarks.opperf --ops dot relu softmax
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _inputs(shapes, dtype=np.float32, seed=0, int_slots=()):
    rng = np.random.RandomState(seed)
    import jax.numpy as jnp

    out = []
    for i, s in enumerate(shapes):
        if i in int_slots:
            out.append(jnp.asarray(rng.randint(0, 64, s), jnp.int32))
        else:
            out.append(jnp.asarray(rng.rand(*s).astype(dtype) + 0.1))
    return out


# op name -> (input shapes, static params)
DEFAULT_SPECS = {
    # unary / binary tensor math
    "relu": ([(256, 256)], {}),
    "sigmoid": ([(256, 256)], {}),
    "exp": ([(256, 256)], {}),
    "log": ([(256, 256)], {}),
    "sqrt": ([(256, 256)], {}),
    "broadcast_add": ([(256, 256), (1, 256)], {}),
    "broadcast_mul": ([(256, 256), (1, 256)], {}),
    "elemwise_add": ([(256, 256), (256, 256)], {}),
    # reductions / linalg
    "sum": ([(256, 256)], {}),
    "mean": ([(256, 256)], {}),
    "max": ([(256, 256)], {}),
    "dot": ([(256, 256), (256, 256)], {}),
    "batch_dot": ([(16, 64, 64), (16, 64, 64)], {}),
    # shape ops
    "transpose": ([(256, 256)], {}),
    "Reshape": ([(256, 256)], {"shape": (64, 1024)}),
    "Concat": ([(64, 128), (64, 128)], {"dim": 1}),
    # NN core
    "softmax": ([(128, 1000)], {}),
    "log_softmax": ([(128, 1000)], {}),
    "FullyConnected": ([(64, 512), (256, 512), (256,)],
                       {"num_hidden": 256}),
    "Convolution": ([(8, 16, 32, 32), (32, 16, 3, 3), (32,)],
                    {"kernel": (3, 3), "num_filter": 32, "pad": (1, 1)}),
    "Pooling": ([(8, 16, 32, 32)],
                {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"}),
    "BatchNorm": ([(32, 64, 16, 16), (64,), (64,), (64,), (64,)], {}),
    "LayerNorm": ([(64, 512), (512,), (512,)], {}),
    "Dropout": ([(256, 256)], {"p": 0.5}),
    "Activation": ([(256, 256)], {"act_type": "tanh"}),
    # round-4 families: linalg, spatial, multi-tensor, loss heads
    "linalg_gemm2": ([(16, 64, 64), (16, 64, 64)], {}),
    "linalg_potrf": ([(16, 64, 64)], {"__spd__": True}),
    "linalg_trsm": ([(16, 64, 64), (16, 64, 64)], {"__spd__": True}),
    "linalg_syrk": ([(16, 64, 64)], {}),
    "BilinearSampler": ([(8, 16, 32, 32), (8, 2, 32, 32)], {}),
    "GridGenerator": ([(8, 6)], {"transform_type": "affine",
                                 "target_shape": (32, 32)}),
    "SpatialTransformer": ([(8, 16, 32, 32), (8, 6)],
                           {"target_shape": (32, 32)}),
    "Correlation": ([(4, 16, 24, 24), (4, 16, 24, 24)],
                    {"max_displacement": 2, "pad_size": 2}),
    "im2col": ([(8, 16, 32, 32)], {"kernel": (3, 3), "pad": (1, 1)}),
    "multi_sum_sq": ([(256, 256), (256, 256), (256, 256)],
                     {"num_arrays": 3}),
    "multi_sgd_update": ([(256, 256), (256, 256), (128, 128), (128, 128)],
                         {"lrs": (0.1, 0.1), "num_weights": 2}),
    "LinearRegressionOutput": ([(256, 256), (256, 256)], {}),
    "SVMOutput": ([(256, 64), (256,)], {}),
    "cumsum": ([(256, 256)], {"axis": 1}),
    "add_n": ([(256, 256), (256, 256), (256, 256)], {}),
    "swapaxes": ([(64, 32, 16)], {"dim1": 0, "dim2": 2}),
    "reshape_like": ([(256, 256), (64, 1024)], {}),
    # contrib detection ops
    "_contrib_box_iou": ([(1, 64, 4), (1, 64, 4)], {}),
    "_contrib_box_nms": ([(1, 128, 6)], {}),
    "_contrib_ROIAlign": ([(1, 32, 32, 32), (8, 5)],
                          {"pooled_size": (7, 7), "spatial_scale": 1.0}),
    # trig / rounding / power unary family
    "sin": ([(256, 256)], {}),
    "cos": ([(256, 256)], {}),
    "tanh": ([(256, 256)], {}),
    "erf": ([(256, 256)], {}),
    "abs": ([(256, 256)], {}),
    "floor": ([(256, 256)], {}),
    "round": ([(256, 256)], {}),
    "square": ([(256, 256)], {}),
    "rsqrt": ([(256, 256)], {}),
    "reciprocal": ([(256, 256)], {}),
    # binary / comparison broadcasting
    "broadcast_sub": ([(256, 256), (1, 256)], {}),
    "broadcast_div": ([(256, 256), (1, 256)], {}),
    "broadcast_maximum": ([(256, 256), (1, 256)], {}),
    "broadcast_power": ([(256, 256), (1, 256)], {}),
    "broadcast_greater": ([(256, 256), (1, 256)], {}),
    "broadcast_equal": ([(256, 256), (256, 256)], {}),
    # reductions with axes / norms
    "prod": ([(256, 256)], {"axis": 1}),
    "min": ([(256, 256)], {"axis": 0}),
    "argmax": ([(256, 256)], {"axis": 1}),
    "argmin": ([(256, 256)], {"axis": 1}),
    "norm": ([(256, 256)], {}),
    "L2Normalization": ([(64, 512)], {}),
    # sorting / indexing / gather-scatter
    "sort": ([(64, 1024)], {}),
    "argsort": ([(64, 1024)], {}),
    "topk": ([(64, 1024)], {"k": 16}),
    "take": ([(1024, 64), (256,)], {}),
    "one_hot": ([(4096,)], {"depth": 128}),
    "where": ([(256, 256), (256, 256), (256, 256)], {}),
    "clip": ([(256, 256)], {"a_min": 0.2, "a_max": 0.8}),
    "tile": ([(64, 64)], {"reps": (2, 4)}),
    "repeat": ([(64, 64)], {"repeats": 4, "axis": 1}),
    "expand_dims": ([(256, 256)], {"axis": 1}),
    "slice": ([(256, 256)], {"begin": (32, 32), "end": (224, 224)}),
    "flip": ([(256, 256)], {"axis": 1}),
    # NN extras
    "Embedding": ([(64, 32), (8192, 128)],
                  {"input_dim": 8192, "output_dim": 128}),
    "SoftmaxOutput": ([(128, 1000), (128,)], {}),
    "LeakyReLU": ([(256, 256)], {"act_type": "leaky"}),
    "Deconvolution": ([(8, 16, 16, 16), (16, 8, 2, 2)],
                      {"kernel": (2, 2), "stride": (2, 2), "num_filter": 8,
                       "num_group": 1}),
    "_contrib_DeformableConvolution": (
        [(2, 8, 16, 16), (2, 18, 16, 16), (8, 8, 3, 3)],
        {"kernel": (3, 3), "pad": (1, 1), "num_filter": 8, "no_bias": True}),
    "_contrib_ModulatedDeformableConvolution": (
        [(2, 8, 16, 16), (2, 18, 16, 16), (2, 9, 16, 16), (8, 8, 3, 3)],
        {"kernel": (3, 3), "pad": (1, 1), "num_filter": 8, "no_bias": True}),
    "_contrib_PSROIPooling": ([(1, 196, 32, 32), (8, 5)],
                              {"output_dim": 4, "pooled_size": 7,
                               "spatial_scale": 1.0}),
    "linalg_gesvd": ([(4, 64, 64)], {}),
    "sample_multinomial": ([(64, 128)], {"shape": (16,)}),
    "_contrib_flash_attention": ([(2, 4, 512, 64)] * 3, {}),
    "_contrib_AdaptiveAvgPooling2D": ([(8, 16, 32, 32)],
                                      {"output_size": 7}),
    "linear_cross_entropy": ([(512, 128), (8192, 128), (512,)], {}),
    # fused optimizer updates
    "sgd_update": ([(1024, 1024), (1024, 1024)], {"lr": 0.1}),
    "adam_update": ([(1024, 1024)] * 4, {"lr": 0.1}),
}

# ops whose extra inputs must be integer (index) arrays
_INT_INPUT = {"take": [1], "Embedding": [0], "SoftmaxOutput": [1],
              "linear_cross_entropy": [2]}


def bench_op(name, shapes, params, warmup=2, runs=20, dtype=np.float32,
             device=False):
    import jax

    from mxnet_tpu.ops import registry

    op = registry.maybe_get(name)
    if op is None:
        return None
    params = dict(params)
    spd = params.pop("__spd__", False)
    # linear_cross_entropy takes labels as arg 2 with small vocab index
    args = _inputs(shapes, dtype=dtype,
                   int_slots=_INT_INPUT.get(name, ()))
    if spd:
        # factorization/solve ops need a well-conditioned SPD (or its
        # Cholesky-factor) leading operand
        import jax.numpy as jnp

        a = args[0]
        n = a.shape[-1]
        args[0] = jnp.matmul(a, jnp.swapaxes(a, -1, -2)) \
            + n * jnp.eye(n, dtype=a.dtype)
    import functools

    fn = functools.partial(op.fn, **params) if params else op.fn

    def _sync(o):
        leaves = jax.tree.leaves(o)
        np.asarray(jax.device_get(leaves[0]).reshape(-1)[:1])

    # eager
    try:
        for _ in range(warmup):
            out = fn(*args)
        _sync(out)
        t0 = time.perf_counter()
        for _ in range(runs):
            out = fn(*args)
        _sync(out)
        eager_us = (time.perf_counter() - t0) / runs * 1e6
    except Exception as e:  # noqa: BLE001
        return {"op": name, "error": f"{type(e).__name__}: {e}"[:120]}
    # jitted
    jfn = jax.jit(fn)
    try:
        for _ in range(warmup):
            out = jfn(*args)
        _sync(out)
        t0 = time.perf_counter()
        for _ in range(runs):
            out = jfn(*args)
        _sync(out)
        jit_us = (time.perf_counter() - t0) / runs * 1e6
    except Exception as e:  # noqa: BLE001
        jit_us = None
    dev_us = None
    if device and jit_us is not None:
        from .common import device_us

        try:
            dev_us = device_us(jfn, args)
        except Exception:  # noqa: BLE001 - profiler unavailable (CPU rigs)
            dev_us = None
    return {"op": name, "dtype": np.dtype(dtype).name,
            "eager_us": round(eager_us, 1),
            "jit_us": round(jit_us, 1) if jit_us is not None else None,
            "device_us": round(dev_us, 1) if dev_us is not None else None}


def run(ops=None, warmup=2, runs=20, dtypes=("float32",), device=False):
    specs = DEFAULT_SPECS if not ops else {
        k: v for k, v in DEFAULT_SPECS.items()
        if k in ops or k.removeprefix("_contrib_") in ops
    }
    import jax.numpy as jnp

    rows = []
    for name, (shapes, params) in specs.items():
        for dt in dtypes:
            dtype = jnp.bfloat16 if dt == "bfloat16" else np.dtype(dt)
            row = bench_op(name, shapes, params, warmup, runs, dtype=dtype,
                           device=device)
            if row is None:
                continue
            rows.append(row)
            if "error" in row:
                print(f"{name:28s} [{dt:8s}] ERROR {row['error']}")
            else:
                j = f"{row['jit_us']:10.1f}"                     if row["jit_us"] is not None else "       n/a"
                dv = row.get("device_us")
                dv = f"   device {dv:9.1f} us" if dv is not None else ""
                print(f"{name:28s} [{dt:8s}] eager "
                      f"{row['eager_us']:10.1f} us   jit {j} us{dv}")
    return rows


def write_markdown(rows, path):
    """Markdown report (the reference harness wrote one per category)."""
    lines = ["# opperf report", "",
             "| op | dtype | eager (us) | jit (us) | device (us) |",
             "|---|---|---|---|---|"]
    for r in rows:
        if "error" in r:
            lines.append(f"| {r['op']} | — | ERROR | {r['error']} | — |")
        else:
            j = r["jit_us"] if r["jit_us"] is not None else "n/a"
            d = r.get("device_us")
            d = d if d is not None else "n/a"
            lines.append(
                f"| {r['op']} | {r.get('dtype', 'float32')} | "
                f"{r['eager_us']} | {j} | {d} |"
            )
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", nargs="*", default=None)
    ap.add_argument("--runs", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--dtypes", nargs="*", default=["float32"],
                    help="e.g. --dtypes float32 bfloat16")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON line with all rows")
    ap.add_argument("--md", default=None,
                    help="write a markdown report to this path")
    ap.add_argument("--device", action="store_true",
                    help="add a profiler-counted DEVICE time column (wall "
                         "columns of small ops sit at the dispatch floor)")
    args = ap.parse_args()
    rows = run(args.ops, args.warmup, args.runs, tuple(args.dtypes),
               device=args.device)
    if args.json:
        print(json.dumps({"opperf": rows}))
    if args.md:
        write_markdown(rows, args.md)


if __name__ == "__main__":
    main()

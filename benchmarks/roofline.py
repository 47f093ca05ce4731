"""Roofline accounting for a BASELINE config's compiled training step.

XLA's own ``cost_analysis`` (flops + bytes accessed) on the exact
compiled step vs the attached chip's published peaks, side by side with
the traced device time and the top individual device ops.

    python -m benchmarks.roofline --config resnet50 [--layout NHWC]

The peaks come from ``benchmarks.common.DEVICE_PEAKS``, keyed by the
``device_kind`` the device reports; a kind that is not in the table (the
CPU included) is an error, not a default.
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import shutil
import tempfile


def top_ops(trace_dir, steps, k=25):
    path = glob.glob(f"{trace_dir}/plugins/profile/*/*.trace.json.gz")[0]
    with gzip.open(path) as f:
        tr = json.load(f)
    agg = collections.Counter()
    tot = 0.0
    for e in tr["traceEvents"]:
        if e.get("ph") == "X" and e.get("pid") == 3 and e.get("tid") == 3:
            tot += e.get("dur", 0)
            agg[e["name"]] += e.get("dur", 0)
    print(f"device busy per step: {tot / steps / 1e3:.2f} ms; "
          f"top {k} individual ops:")
    for name, d in agg.most_common(k):
        print(f"{d / steps / 1e3:8.3f} ms  {name}")
    return tot / steps / 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="resnet50")
    ap.add_argument("--layout", default="NCHW")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--skip-trace", action="store_true")
    ap.add_argument("--remat", default=None, choices=(None, "full", "dots"))
    args = ap.parse_args()

    from . import trace_config as tc
    from .common import device_peaks
    from .trace_bert import capture

    peaks = device_peaks()

    if args.config == "resnet50":
        step, x, y, items = tc.build_resnet50(args.batch or 64, args.layout)
    elif args.config == "transformer":
        step, x, y, items = tc.build_transformer(args.batch or 64,
                                                 remat=args.remat)
    else:
        raise SystemExit(f"unsupported config {args.config}")

    xs = x if isinstance(x, tuple) else (x,)
    float(step(*xs, y).asscalar())  # compile + stash avals
    spc = getattr(step, "_steps_per_call", 1)
    c = step.cost_analysis()
    flops = c.get("flops", 0.0) / spc
    bytes_ = c.get("bytes accessed", 0.0) / spc
    t_f = flops / peaks["flops_bf16"] * 1e3
    t_b = bytes_ / peaks["hbm_bytes_per_s"] * 1e3
    print(f"XLA cost_analysis (per optimizer step, steps_per_call={spc}): "
          f"{flops / 1e12:.3f} TFLOP, {bytes_ / 1e9:.3f} GB accessed")
    print(f"roofline floors: compute {t_f:.2f} ms, memory {t_b:.2f} ms "
          f"-> {max(t_f, t_b):.2f} ms")
    if args.skip_trace:
        return
    trace_dir = tempfile.mkdtemp(prefix="roofline_")
    capture(lambda a, b: step(*xs, y), x, y, trace_dir, args.steps)
    ms = top_ops(trace_dir, args.steps, args.top) / spc
    floor = max(t_f, t_b)
    print(f"per-step device busy: {ms:.2f} ms; measured/floor = "
          f"{ms / floor:.2f}x; device-bound items/s: {items / ms * 1e3:.0f}")
    shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    main()

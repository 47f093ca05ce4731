"""End-to-end input pipeline bench: ConvNet
training FED by the multiprocessing DataLoader from host memory —
augment -> batchify -> device feed -> TrainStep — the steady-state
images/sec a real user gets, input included.

Three rates from the SAME session so the input-pipeline overhead and the
async-feed win are explicit:

- ``device_resident``: the step re-fed one pre-placed DeviceBatch (the
  synthetic ceiling every BASELINE number is quoted against);
- ``fed_raw``: DataLoader -> synchronous ``TrainStep.__call__`` staging
  (reshape/split + device_put on the critical path);
- ``fed_prefetched`` (``--prefetch N``): DataLoader ->
  ``prefetch_to_device(..., feed=step)`` -> the pre-placed fast path,
  with the achieved overlap computed from the ``input/wait_ms``
  telemetry histogram the prefetcher feeds.

    python -m benchmarks.bench_e2e_input [--prefetch 2] [--batch 64]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (default: 64, or 8 on CPU)")
    ap.add_argument("--steps", type=int, default=None,
                    help="steps per measured phase (default: 40, 6 on CPU)")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--prefetch", type=int, default=0,
                    help="staged device batches for the async feed phase "
                         "(0 = raw fed loop only)")
    ap.add_argument("--model", default=None,
                    help="model_zoo name (default: resnet50_v1, or "
                         "resnet18_v1 on CPU)")
    args = ap.parse_args()

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, optimizer as opt
    from mxnet_tpu.gluon import data as gdata
    from mxnet_tpu.gluon.data.prefetch import prefetch_to_device
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    from mxnet_tpu.parallel import TrainStep

    on_cpu = jax.default_backend() == "cpu"
    B = args.batch or (8 if on_cpu else 64)
    steps = args.steps or (6 if on_cpu else 40)
    model = args.model or ("resnet18_v1" if on_cpu else "resnet50_v1")

    class SyntheticImageNet(gdata.Dataset):
        """uint8 image pool with the standard train-time augment chain
        (random crop + flip + normalize) done in numpy per sample —
        the shape of a decoded-JPEG pipeline without the codec."""

        def __init__(self, n=512):
            rng = np.random.RandomState(0)
            self._pool = rng.randint(0, 255, (64, 256, 256, 3), np.uint8)
            self._n = n

        def __len__(self):
            return self._n

        def __getitem__(self, i):
            rng = np.random.RandomState(i)
            img = self._pool[i % len(self._pool)]
            y0, x0 = rng.randint(0, 32, 2)
            crop = img[y0:y0 + 224, x0:x0 + 224]
            if rng.rand() < 0.5:
                crop = crop[:, ::-1]
            out = crop.astype(np.float32) / 255.0
            out = (out - 0.45) / 0.225
            return out.transpose(2, 0, 1).copy(), np.float32(i % 1000)

    # fork workers BEFORE the first device computation (see DataLoader
    # docstring: post-runtime forks inherit locked mutexes)
    loader = gdata.DataLoader(
        SyntheticImageNet(n=B * (steps + 4)), batch_size=B,
        num_workers=args.workers, pin_memory=True, last_batch="discard")
    it = iter(loader)
    first = next(it)  # workers up before the net compiles

    net = get_model(model)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 3, 224, 224)))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = TrainStep(net, lambda o, l: loss_fn(o, l),
                     opt.SGD(learning_rate=0.1, momentum=0.9),
                     compute_dtype="bfloat16", state_dtype="bfloat16")
    # compile + warm
    loss = step(first[0], first[1])
    float(loss.asscalar())

    def timed_loop(feed):
        """Run `steps` steps from `feed` (callable -> loss); returns rate."""
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = feed()
        float(loss.asscalar())
        return B * steps / (time.perf_counter() - t0)

    # device-resident ceiling: ONE pre-placed batch re-fed through the
    # fast path (batch operands are not donated, so this is legal)
    db = step.device_put_batch((first[0], first[1]))
    for _ in range(3):
        loss = step(db)
    float(loss.asscalar())
    dev_rate = timed_loop(lambda: step(db))

    # the raw real loop: DataLoader -> synchronous staging in __call__
    raw_iter = iter(loader)
    raw_rate = timed_loop(lambda: step(*next(raw_iter)))
    if hasattr(raw_iter, "close"):
        raw_iter.close()

    wait_hist = mx.telemetry.registry().histogram("input/wait_ms")
    pf_rate = None
    overlap_achieved = None
    wait_summary = None
    if args.prefetch > 0:
        wait_before = wait_hist.sum
        pf = prefetch_to_device(iter(loader), size=args.prefetch, feed=step)
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = step(next(pf))
        float(loss.asscalar())
        elapsed = time.perf_counter() - t0
        pf.close()
        pf_rate = B * steps / elapsed
        # achieved overlap: fraction of the fed wall time NOT spent
        # blocked waiting for a staged batch (from the new telemetry)
        wait_s = (wait_hist.sum - wait_before) / 1e3
        overlap_achieved = max(0.0, 1.0 - wait_s / elapsed)
        wait_summary = wait_hist.summary()

    fed_rate = pf_rate if pf_rate is not None else raw_rate
    report = mx.telemetry.report()
    print(json.dumps({
        "metric": f"{model.split('_')[0]}_e2e_input_images_per_sec",
        "value": round(fed_rate, 1), "unit": "images/sec",
        "model": model,
        "device_resident_images_per_sec": round(dev_rate, 1),
        "fed_images_per_sec_raw": round(raw_rate, 1),
        "fed_images_per_sec_prefetched":
            round(pf_rate, 1) if pf_rate is not None else None,
        "input_overlap_fraction":
            round(fed_rate / dev_rate, 3) if dev_rate else 0.0,
        "input_overlap_achieved":
            round(overlap_achieved, 3) if overlap_achieved is not None
            else None,
        "input_wait_ms_p50": report["input_wait_ms_p50"],
        "input_wait_ms_p95": report["input_wait_ms_p95"],
        "input_wait_ms_mean":
            round(wait_summary["mean"], 3) if wait_summary else None,
        "prefetch": args.prefetch, "workers": args.workers, "batch": B,
        "steps": steps,
    }))


if __name__ == "__main__":
    main()

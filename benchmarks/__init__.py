"""Driver-format benchmarks for the BASELINE.json configs.

Run from the repo root as modules (so ``mxnet_tpu`` is the checkout's):

    python -m benchmarks.bench_lenet        # config 1
    python -m benchmarks.bench_resnet50     # config 2
    python bench.py                         # config 3 (driver metric)
    python -m benchmarks.bench_ssd          # config 5
    python -m benchmarks.run_all            # all of them

Config 4 (the Transformer) is measured by the cells of ``perf/run.py``
(``BENCHMARK.json``), on the chip.

Each prints ONE JSON line {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count", ...}. ``vs_baseline`` divides
by a TARGET derived in BASELINE.md (v4-era 45%-MFU arithmetic), not by
anything the attached chip can do. No accelerator is an error
(``common.require_accelerator``), and a failing run exits nonzero.
"""

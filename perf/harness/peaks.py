"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s. A device that is not in the table is an error,
never a default. (Copied from ``benchmarks/common.DEVICE_PEAKS``: later
PRs may change ``benchmarks/``, not the yardstick.)
"""

DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    if device_kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"no published peaks for device kind {device_kind!r}: add it to "
            "perf/harness/peaks.py with its source")
    return DEVICE_PEAKS[device_kind]

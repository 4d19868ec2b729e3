"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

Copied from ``bench.PEAK_FLOPS`` at commit 669e046 and extended with the
memory bandwidth and size. Source: Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip. A kind that is not
here is an error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str, key: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; add it to "
            "benchmark/benchlib/peaks.py with its source")
    return PEAKS[device_kind][key]

"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not here is an error, not a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(kind: str, platform: str = "tpu") -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {kind!r} "
                       f"(platform {platform}); add it to bench/peaks.py")
    return PEAKS[kind]

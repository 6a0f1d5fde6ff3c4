"""Published peaks of the chips the benchmark knows, keyed by jax's
``device_kind``. A device that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip gives 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at 819 GB/s.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on record for device kind {device_kind!r}: add it to "
            "benchmark/peaks.py with its source")
    return PEAKS[device_kind]

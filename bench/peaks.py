"""Published peaks of each chip, keyed by JAX's ``device_kind``.

A kind missing from the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip,
    # 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]

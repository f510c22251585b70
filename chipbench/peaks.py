"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  An unknown kind is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises for a kind that
    the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  An unknown kind is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s in
bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

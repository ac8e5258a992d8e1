"""The chip's published peaks, keyed by the exact ``device_kind`` JAX reports.

A copy of the program's table (``profiling/devices.py``): the yardstick may
not move with the program. A device that is not in the table is an error,
not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "name": "v5e",
        "hbm_bytes": 16_000_000_000,
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "source": 'Google Cloud documentation, "TPU v5e": 16 GB HBM2e, 819 GB/s, '
                  "197 TFLOP/s bf16, 393 TOP/s int8 per chip",
    },
}


def for_kind(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(f"unknown device_kind {device_kind!r}: no row in bench/peaks.py "
                         f"(known: {sorted(PEAKS)}); add the row with its source")
    return PEAKS[device_kind]

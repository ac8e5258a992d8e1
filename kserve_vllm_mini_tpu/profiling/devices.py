"""Device facts: one table keyed by the exact ``device_kind`` JAX reports.

Everything the program assumes about the chip it runs on — HBM capacity,
the roofline peaks, which price-sheet row and which TDP apply — is read
from here, by the string ``jax.devices()[0].device_kind`` returns, matched
exactly. A TPU kind that is not in the table is an error: a price, a power
figure or a capacity assumed for a device the program does not recognise
would be served as if it were known.

Labels an operator types (``--accelerator v5e-8``, a GKE node label) are a
different input and keep their fuzzy match and its ``default`` row
(costs/pricing.py ``chip_price``, analysis/telemetry.py
``tdp_for_accelerator``).

JAX-free on purpose: callers hand in the ``device_kind`` string.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class DeviceFacts:
    name: str                 # generation label: the tpu-cost.yaml price key
                              # and the TPU_TDP_WATTS key
    hbm_bytes: int            # per chip
    hbm_bytes_per_s: float    # peak HBM bandwidth, per chip
    bf16_flops_per_s: float   # peak dense bf16, per chip
    source: str


TPU_DEVICES: dict[str, DeviceFacts] = {
    # device_kind observed on the chip (chip_smoke.py, PR 21)
    "TPU v5 lite": DeviceFacts(
        name="v5e",
        hbm_bytes=16_000_000_000,
        hbm_bytes_per_s=819e9,
        bf16_flops_per_s=197e12,
        source='Google Cloud documentation, "TPU v5e": 16 GB HBM2e, '
               "819 GB/s, 197 TFLOP/s bf16 per chip",
    ),
}


def device_facts(device_kind: str) -> DeviceFacts:
    """Facts for the exact ``device_kind`` of a TPU; raises on a kind the
    table does not hold (add the row, with its source, to run there)."""
    facts = TPU_DEVICES.get(device_kind)
    if facts is None:
        raise ValueError(
            f"unknown TPU device_kind {device_kind!r}: no row in "
            f"profiling/devices.py TPU_DEVICES (known: "
            f"{sorted(TPU_DEVICES)}). Capacity, peaks, price and TDP are "
            "never assumed for an unrecognised chip — add its row with a "
            "source"
        )
    return facts

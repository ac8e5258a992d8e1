"""TPU pricing sheet loading + accelerator price matching.

tpu-cost.yaml replaces the reference's GPU cost.yaml; chip-hour prices are
keyed by accelerator-label fragments and matched fuzzily the way the
reference picks GPU prices from node labels
(/root/reference/cost_estimator.py:201-213).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

import yaml

DEFAULT_SHEET = Path(__file__).resolve().parents[2] / "tpu-cost.yaml"


@dataclass
class Pricing:
    tpu_chip_hourly: dict[str, float] = field(default_factory=dict)
    cpu_core_hourly: float = 0.031
    memory_gib_hourly: float = 0.0042
    overhead_factor: float = 0.15
    region_multipliers: dict[str, float] = field(default_factory=dict)
    grid_usd_per_kwh: float = 0.12

    def chip_price(self, accelerator: Optional[str]) -> tuple[float, str]:
        """Fuzzy match an accelerator label (e.g. 'tpu-v5-lite-podslice',
        'v5e-8') to a chip-hour price; falls back to 'default'."""
        if accelerator:
            label = accelerator.lower().replace("-", "").replace("_", "")
            for key, price in self.tpu_chip_hourly.items():
                if key == "default":
                    continue
                if key.lower().replace("-", "") in label:
                    return price, key
        return self.tpu_chip_hourly.get("default", 1.50), "default"

    def region_multiplier(self, region: Optional[str]) -> float:
        if region and region in self.region_multipliers:
            return self.region_multipliers[region]
        return 1.0


# top-level sections a pricing sheet may carry; anything else is almost
# certainly a typo ("tpu_chip_hourli") that would silently price every
# run at the fallback default — fail LOUD at the load
_KNOWN_TOP_KEYS = ("tpu_chip_hourly", "host", "calculation", "energy")


def _sheet_num(sheet: Path, where: str, v: Any) -> float:
    """A price that isn't a number must stop the load — ``float("1,20")``
    raising deep inside an analyzer stage points at nothing."""
    if isinstance(v, bool) or not isinstance(v, (int, float, str)):
        raise SystemExit(
            f"{sheet}: {where} = {v!r} is not a number"
        )
    try:
        return float(v)
    except ValueError:
        raise SystemExit(
            f"{sheet}: {where} = {v!r} is not a number"
        ) from None


def load_pricing(path: str | Path | None = None) -> Pricing:
    """Load + validate a pricing sheet. Validation is LOUD (SystemExit
    naming the sheet, the key, and the fix): a garbled sheet silently
    falling back to defaults would price every run wrong under the
    operator's own label (docs/ECONOMICS.md "Pricing provenance")."""
    p = Path(path) if path else DEFAULT_SHEET
    with p.open() as f:
        raw = yaml.safe_load(f) or {}
    if not isinstance(raw, dict):
        raise SystemExit(
            f"{p}: pricing sheet must be a mapping, got "
            f"{type(raw).__name__}"
        )
    unknown = sorted(set(raw) - set(_KNOWN_TOP_KEYS))
    if unknown:
        raise SystemExit(
            f"{p}: unknown top-level key(s) {', '.join(map(repr, unknown))}; "
            f"known: {', '.join(_KNOWN_TOP_KEYS)}"
        )
    chip = raw.get("tpu_chip_hourly") or {}
    if chip and "default" not in chip:
        raise SystemExit(
            f"{p}: tpu_chip_hourly has no 'default' entry — unmatched "
            "accelerators would be priced by a hardcoded fallback "
            "instead of the sheet; add `default: <usd/chip-hr>`"
        )
    host = raw.get("host") or {}
    calc = raw.get("calculation") or {}
    energy = raw.get("energy") or {}
    return Pricing(
        tpu_chip_hourly={
            k: _sheet_num(p, f"tpu_chip_hourly.{k}", v)
            for k, v in chip.items()
        },
        cpu_core_hourly=_sheet_num(
            p, "host.cpu_core_hourly", host.get("cpu_core_hourly", 0.031)),
        memory_gib_hourly=_sheet_num(
            p, "host.memory_gib_hourly", host.get("memory_gib_hourly", 0.0042)),
        overhead_factor=_sheet_num(
            p, "calculation.overhead_factor", calc.get("overhead_factor", 0.15)),
        region_multipliers={
            k: _sheet_num(p, f"calculation.region_multipliers.{k}", v)
            for k, v in (calc.get("region_multipliers") or {}).items()
        },
        grid_usd_per_kwh=_sheet_num(
            p, "energy.grid_usd_per_kwh", energy.get("grid_usd_per_kwh", 0.12)),
    )

"""Loads what ``BENCHMARK.json`` names: a cell, its configuration, its traffic.

Everything that belongs to one configuration, one traffic mix or one cell is a
data file found by its name, so a later PR adds files and entries and edits
nothing that is there.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# what a configuration's file holds beside the model's own keys
_GROUPS = ("source", "reduced", "assumed", "deployment", "engine", "rehearse")


@functools.lru_cache(maxsize=None)
def load_module(directory: str, name: str):
    """``bench/<directory>/<name>.py``: a reader, a weight kind, a reference, a
    system or a traffic kind, found by the name a data file gives."""

    path = BENCH / directory / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no bench/{directory}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str, rehearse: bool = False) -> dict:
    """One cell, resolved: its ``BENCHMARK.json`` entry, its configuration
    (``model`` sizes and ``engine`` settings), its traffic mix, the cell's own
    parameters (``bench/workloads/<cell>.json``) and the metrics it reports."""
    bm = benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    entry = cells[name]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[entry["config"]]
    raw = json.loads((ROOT / cfg_entry["file"]).read_text())
    engine = dict(raw["engine"])
    if rehearse:
        tiny = dict(raw["rehearse"])
        engine.update(tiny.pop("engine", {}))
        raw = {**raw, **tiny}
    model = {k: v for k, v in raw.items() if k not in _GROUPS}
    if raw.get("use_sliding_window") is False:
        model["sliding_window"] = None
    model["quantization"] = engine["quantization"]   # how the decoder matmuls are stored
    traffic = json.loads((BENCH / "traffic" / f"{entry['traffic']}.json").read_text())
    params = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    if rehearse:
        traffic = {**traffic, **traffic.get("rehearse", {})}
        params = {**params, **params.get("rehearse", {})}

    def mine(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "name": name, "entry": entry, "chips": entry["chips"],
        "config": {"name": entry["config"], "model": model, "engine": engine},
        "traffic": traffic, "params": params,
        "run_seconds": bm["run_seconds"],
        "end_to_end": [x for x in bm["end_to_end"] if mine(x)],
        "per_layer": [x for x in bm["per_layer"] if mine(x)],
    }

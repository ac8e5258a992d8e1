"""The reader of the decode kernel's block counters (PR 27): on counter deltas
made by hand, and on what a program without the counters (or one whose decode
steps take the eager read) exports, where it must read nothing. No JAX."""

import json
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {"decode_kv_read_share": ["mistral-7b.chat", "qwen2-7b.chat"],
         "decode_kv_read_share.sat": ["mistral-7b.decode-sat"]}


def ctx(counters: dict):
    return run.Context(cell=None, records=[], window_s=45.0, run_end_s=47.0, setup_s=25.0,
                       counters=counters, memory={}, trace=None, device_kind="TPU v5 lite")


# 2,000 decode steps of 16 slots x 8 blocks a stripe, 1.9 blocks a slot fetched
WINDOW = {"kvmini_tpu_decode_steps_total": 2_000.0,
          "kvmini_tpu_decode_kv_blocks_total": 2_000.0 * 16 * 8,
          "kvmini_tpu_decode_kv_blocks_fetched_total": 2_000.0 * 16 * 1.9}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_reader_on_hand_made_counters(name):
    assert run.read_metric(name, ctx(WINDOW)) == pytest.approx(100.0 * 1.9 / 8)


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("counters", [
    {},                                                       # no program counters at all
    {"kvmini_tpu_decode_steps_total": 2_000.0},               # the parent: no such counters
    dict(WINDOW, kvmini_tpu_decode_kv_blocks_total=0.0,       # the eager read: both stay 0
         kvmini_tpu_decode_kv_blocks_fetched_total=0.0),
], ids=["empty", "parent", "eager"])
def test_reader_finds_nothing_without_the_kernel(name, counters):
    assert run.read_metric(name, ctx(counters)) is None
    spec = [m for m in BM["per_layer"] if m["name"] == name]
    assert run.collect(spec, ctx(counters)) == {}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_entry_is_a_program_counter_of_its_cells(name):
    entry = {m["name"]: m for m in BM["per_layer"]}[name]
    assert entry["source"] == "program_counter" and entry["better"] == "lower"
    # the cells it was accepted with, and whichever later cells its reader reads a number in
    assert entry["unit"] == "%" and set(CELLS[name]) <= set(entry["workloads"])
    assert entry["moves"] == ("out_tokens_per_s" if name.endswith(".sat") else "itl_p50_ms")
    layers = {m["layer"] for m in BM["per_layer"] if m["name"] == "flash_prefill_roofline"}
    assert entry["layer"] in layers
    # new entries stand at the end of the list, after everything PR 26 accepted
    names = [m["name"] for m in BM["per_layer"]]
    assert names.index(name) > names.index("sched_stall_ms.sat")

"""The reader of the sampler's filter-step counter (PR 29): on counter deltas
made by hand, and on what a program without the counter exports, where it must
read nothing. Membership only: a later PR appends cells and metrics freely.
No JAX."""

import json
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {"sampler_filter_share": ["mistral-7b.chat", "qwen2-7b.chat", "olmo-hybrid-7b.chat"],
         "sampler_filter_share.sat": ["mistral-7b.decode-sat", "qwen2-7b.decode-sat"]}
STEPS, FILTERED = "kvmini_tpu_decode_steps_total", "kvmini_tpu_sampler_filter_steps_total"


def ctx(counters: dict):
    return run.Context(cell=None, records=[], window_s=45.0, run_end_s=47.0, setup_s=25.0,
                       counters=counters, memory={}, trace=None, device_kind="TPU v5 lite")


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("filtered, share", [(0.0, 0.0), (500.0, 25.0), (2_000.0, 100.0)])
def test_reader_on_hand_made_counters(name, filtered, share):
    """Greedy traffic reads 0, not nothing: the line of every cell carries it."""
    value = run.read_metric(name, ctx({STEPS: 2_000.0, FILTERED: filtered}))
    assert value == pytest.approx(share) and value is not None
    spec = [m for m in BM["per_layer"] if m["name"] == name]
    assert run.collect(spec, ctx({STEPS: 2_000.0, FILTERED: filtered})) == {
        name: {"value": pytest.approx(share), "unit": "%"}}


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("counters", [
    {},                            # no program counters at all
    {STEPS: 2_000.0},              # the parent: no such counter
    {STEPS: 0.0, FILTERED: 0.0},   # no decode step retired in the window
], ids=["empty", "parent", "no_steps"])
def test_reader_finds_nothing_without_the_counter(name, counters):
    assert run.read_metric(name, ctx(counters)) is None
    spec = [m for m in BM["per_layer"] if m["name"] == name]
    assert run.collect(spec, ctx(counters)) == {}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_entry_is_a_program_counter_of_the_executor_in_its_cells(name):
    entry = {m["name"]: m for m in BM["per_layer"]}[name]
    assert entry["source"] == "program_counter" and entry["better"] == "lower"
    assert entry["unit"] == "%" and set(CELLS[name]) <= set(entry["workloads"])
    moves = "out_tokens_per_s" if name.endswith(".sat") else "itl_p50_ms"
    assert entry["moves"] == moves
    # every cell that lists it reports the end-to-end metric it moves
    judged = {m["name"]: m for m in BM["end_to_end"]}[moves]["workloads"]
    assert set(entry["workloads"]) <= set(judged)
    assert entry["layer"] in {m["layer"] for m in BM["per_layer"] if m["name"] == "decode_step_ms"}
    # new entries stand after everything PR 28 accepted
    names = [m["name"] for m in BM["per_layer"]]
    assert names.index(name) > names.index("linear_state_held_share")

"""``moe_decode_visited_share``: the decode dispatch's visits over what the
expert layers hold, for each of the three stacks of routed experts, and
nothing where there is no counter (the parent's program) or no such layer."""

import pytest

from bench import config, run

CELLS = {   # cell -> held experts x expert layers
    "lfm2-8b-a1b.chat": 32 * 22,
    "smallthinker-21b-pp4.doc-chat": 64 * 12,
    "nemotron-3-super-ep4.decode-sat": 128 * 10,
}


def ctx(counters, cell):
    return run.Context(cell=None, records=[], window_s=45.0, run_end_s=47.0, setup_s=30.0,
                       counters=counters, memory={"model": config.load_cell(cell)["config"]["model"]},
                       trace=None, device_kind="TPU v5 lite")


@pytest.mark.parametrize("name", ["moe_decode_visited_share", "moe_decode_visited_share.sat"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_share_is_the_visits_over_what_the_layers_hold(cell, name):
    steps = 3000.0
    every = {"kvmini_tpu_decode_steps_total": steps,
             "kvmini_tpu_moe_expert_visits_total": steps * CELLS[cell]}
    assert run.read_metric(name, ctx(every, cell)) == pytest.approx(100.0)
    some = {**every, "kvmini_tpu_moe_expert_visits_total": steps * CELLS[cell] * 0.149}
    assert run.read_metric(name, ctx(some, cell)) == pytest.approx(14.9)


@pytest.mark.parametrize("cell", sorted(CELLS) + ["mistral-7b.chat", "olmo-hybrid-7b.chat",
                                                  "falcon-h1-34b.decode-sat"])
def test_nothing_without_the_counter_or_without_such_layers(cell):
    no_counter = {"kvmini_tpu_decode_steps_total": 3000.0,
                  "kvmini_tpu_moe_expert_touches_total": 3000.0 * 40}
    assert run.read_metric("moe_decode_visited_share", ctx(no_counter, cell)) is None
    assert run.read_metric("moe_decode_visited_share", ctx({}, cell)) is None
    if cell not in CELLS:
        every = {**no_counter, "kvmini_tpu_moe_expert_visits_total": 3000.0 * 40}
        assert run.read_metric("moe_decode_visited_share", ctx(every, cell)) is None


def test_the_benchmark_lists_it_for_the_expert_cells_alone():
    by_name = {m["name"]: m for m in config.benchmark()["per_layer"]}
    chat, sat = by_name["moe_decode_visited_share"], by_name["moe_decode_visited_share.sat"]
    assert chat["moves"] == "itl_p50_ms" and sat["moves"] == "out_tokens_per_s"
    assert sorted(chat["workloads"] + sat["workloads"]) == sorted(CELLS)

"""The reader of ``moe_grouped_prefill_share`` (PR 45): a counter pair in, a
share out; nothing against a program without the counter (the parent of
PR 45) or a window without a prefill; the scrape of the mock endpoint's
`/metrics`; and its entry, at the end of the benchmark's list, on the two
chat cells whose models route experts. No JAX."""

import asyncio
import json
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "moe_grouped_prefill_share"
CELLS = ["lfm2-8b-a1b.chat", "smallthinker-21b-pp4.doc-chat"]
GROUPED = "kvmini_tpu_prefill_grouped_bucket_tokens_total"
BUCKETS = "kvmini_tpu_prefill_bucket_tokens_total"


def ctx(counters: dict):
    return run.Context(cell=None, records=[], window_s=45.0, run_end_s=47.0, setup_s=25.0,
                       counters=counters, memory={}, trace=None, device_kind="TPU v5 lite")


@pytest.mark.parametrize("grouped,buckets,want", [
    (211_968.0, 211_968.0, 100.0),      # doc-chat: every piece is 1024 tokens or more
    (30_720.0, 81_920.0, 37.5),         # chat: the 512 and 1024 buckets' share
    (0.0, 50_000.0, 0.0),               # w8a8, a mesh: the dense dispatch everywhere
])
def test_a_counter_pair_in_a_share_out(grouped, buckets, want):
    window = {GROUPED: grouped, BUCKETS: buckets, "kvmini_tpu_prefill_tokens_total": 0.7 * buckets}
    assert run.read_metric(NAME, ctx(window)) == pytest.approx(want)


def test_nothing_against_a_program_without_the_counter():
    """The parent counts the buckets and not the grouped ones: a share of 0
    there would be a reading of something it cannot do."""
    assert run.read_metric(NAME, ctx({BUCKETS: 50_000.0})) is None
    assert run.read_metric(NAME, ctx({})) is None


def test_nothing_in_a_window_without_a_prefill():
    assert run.read_metric(NAME, ctx({GROUPED: 0.0, BUCKETS: 0.0})) is None
    assert run.read_metric(NAME, ctx({GROUPED: 0.0})) is None


def test_collect_leaves_out_what_reads_nothing():
    specs = [m for m in BM["per_layer"] if m["name"] == NAME]
    assert run.collect(specs, ctx({BUCKETS: 9.0})) == {}
    assert run.collect(specs, ctx({GROUPED: 3.0, BUCKETS: 4.0})) == {
        NAME: {"value": pytest.approx(75.0), "unit": "%"}}


def test_entry_is_a_program_counter_of_the_two_expert_chat_cells():
    entry = {m["name"]: m for m in BM["per_layer"]}[NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "executor (models/llama.py steps)", "moves": "ttft_p50_ms",
                     "workloads": CELLS}
    judged = {m["name"]: m for m in BM["end_to_end"]}["ttft_p50_ms"]["workloads"]
    assert set(CELLS) <= set(judged)
    # at the end of the list, after everything PR 44's benchmark had
    names = [m["name"] for m in BM["per_layer"]]
    assert names.index(NAME) > names.index("window_flash_prefill_roofline")
    assert (ROOT / "bench" / "readers" / f"{NAME}.py").is_file()


def test_the_reader_reads_a_scrape_of_the_mock_endpoint():
    from tests.mock_server import MockServer

    async def scraped():
        async with MockServer() as srv:
            return await asyncio.to_thread(run.scrape, srv.url)

    after = asyncio.run(scraped())
    assert after[GROUPED] == 1536.0 and after[BUCKETS] == 2048.0
    assert run.read_metric(NAME, ctx(after)) == pytest.approx(75.0)

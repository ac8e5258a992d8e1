"""The reader of ``admit_inflight_share`` (PR 39): a counter pair in, a share
out; nothing against a program without the counter (the parent of PR 39) or a
window in which no admission found a sweep in flight; the scrape of the mock
endpoint's `/metrics`; and its two entries, at the end of the benchmark's
list, on exactly the cells that report what they move. No JAX."""

import asyncio
import json
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CHAT = ["mistral-7b.chat", "qwen2-7b.chat", "olmo-hybrid-7b.chat"]
SAT = ["mistral-7b.decode-sat", "qwen2-7b.decode-sat", "nemotron-3-super-ep4.decode-sat",
       "olmo-hybrid-7b.decode-sat", "falcon-h1-34b.decode-sat"]
NEW = {"admit_inflight_share": (CHAT, "ttft_p50_ms"),
       "admit_inflight_share.sat": (SAT, "out_tokens_per_s")}
BEHIND = "kvmini_tpu_admissions_inflight_total"
DRAINED = 'kvmini_tpu_pipeline_fallback_total{reason="active_set"}'


def ctx(counters: dict):
    return run.Context(cell=None, records=[], window_s=45.0, run_end_s=47.0, setup_s=25.0,
                       counters=counters, memory={}, trace=None, device_kind="TPU v5 lite")


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("behind,drained,want", [
    (97.0, 3.0, 97.0),          # a saturated window: three admissions still drained
    (111.0, 0.0, 100.0),
    (0.0, 4.0, 0.0),            # the path never engaged (a paged engine, a chunked prefill)
    (150.0, 50.0, 75.0),
])
def test_a_counter_pair_in_a_share_out(name, behind, drained, want):
    window = {BEHIND: behind, DRAINED: drained,
              'kvmini_tpu_pipeline_fallback_total{reason="headroom"}': 7.0,
              'kvmini_tpu_admissions_drained_total{reason="sweep"}': drained}
    assert run.read_metric(name, ctx(window)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_against_a_program_without_the_counter(name):
    """The parent exports the fallback and not the admissions behind a sweep:
    a share of 0 there would be a reading of something it cannot do."""
    assert run.read_metric(name, ctx({DRAINED: 101.0})) is None
    assert run.read_metric(name, ctx({})) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_where_no_admission_found_a_sweep_in_flight(name):
    assert run.read_metric(name, ctx({BEHIND: 0.0, DRAINED: 0.0})) is None
    assert run.read_metric(name, ctx({BEHIND: 0.0})) is None
    assert run.read_metric(name, ctx({BEHIND: 2.0})) == 100.0       # a program without the fallback's series


def test_collect_leaves_out_what_reads_nothing():
    specs = [m for m in BM["per_layer"] if m["name"] in NEW]
    assert run.collect(specs, ctx({DRAINED: 9.0})) == {}
    full = run.collect(specs, ctx({BEHIND: 9.0, DRAINED: 1.0}))
    assert full == {n: {"value": pytest.approx(90.0), "unit": "%"} for n in NEW}


@pytest.mark.parametrize("name", sorted(NEW))
def test_entry_is_a_program_counter_of_exactly_its_cells(name):
    cells, moves = NEW[name]
    entry = {m["name"]: m for m in BM["per_layer"]}[name]
    assert entry == {"name": name, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "scheduler (runtime/engine.py loop)", "moves": moves,
                     "workloads": entry["workloads"]}
    assert entry["workloads"][:len(cells)] == cells
    judged = {m["name"]: m for m in BM["end_to_end"]}[moves]["workloads"]
    assert set(entry["workloads"]) == set(judged)
    # at the end of the list, after everything PR 38's benchmark had
    names = [m["name"] for m in BM["per_layer"]]
    assert names.index(name) > names.index("admit_gap_host_ms.sat")
    assert run.reader_name(name) == "admit_inflight_share"
    assert (ROOT / "bench" / "readers" / "admit_inflight_share.py").is_file()


def test_the_reader_reads_a_scrape_of_the_mock_endpoint():
    from tests.mock_server import MockServer

    async def scraped():
        async with MockServer() as srv:
            return await asyncio.to_thread(run.scrape, srv.url)

    after = asyncio.run(scraped())
    assert after[BEHIND] == 3.0 and after[DRAINED] == 1.0
    assert run.read_metric("admit_inflight_share", ctx(after)) == pytest.approx(75.0)
    assert run.read_metric("admit_inflight_share.sat", ctx(after)) == pytest.approx(75.0)

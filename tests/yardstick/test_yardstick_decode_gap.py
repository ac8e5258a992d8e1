"""The readers of the scheduler ledger's decode-gap counters (PR 38): each on
counter deltas made by hand, on what a program without them exports (where they
must read nothing rather than fail), and on a scrape of the mock endpoint's
`/metrics`. No JAX."""

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
SCHEDULER, EXECUTOR = "scheduler (runtime/engine.py loop)", "executor (models/llama.py steps)"
# name -> (cells, layer, the end-to-end metric it should move)
NEW = {
    "decode_gap_share": (CHAT, SCHEDULER, "itl_p50_ms"),
    "decode_gap_share.sat": (SAT, SCHEDULER, "out_tokens_per_s"),
    "admit_gap_ms": (CHAT, SCHEDULER, "ttft_p50_ms"),
    "admit_gap_ms.sat": (SAT, SCHEDULER, "out_tokens_per_s"),
    "admit_gap_prefill_ms": (CHAT, EXECUTOR, "ttft_p50_ms"),
    "admit_gap_prefill_ms.sat": (SAT, EXECUTOR, "out_tokens_per_s"),
    "admit_gap_host_ms": (CHAT, SCHEDULER, "ttft_p50_ms"),
    "admit_gap_host_ms.sat": (SAT, SCHEDULER, "out_tokens_per_s"),
}
PHASES = ("loop", "idle", "admit", "prefill", "first_token", "decode_prepare",
          "decode_dispatch", "decode_retire", "emit", "spec_sweep", "masked_sweep", "handoff")


def by_label(series: str, label: str, names, **values) -> dict:
    return {f'kvmini_tpu_sched_{series}_total{{{label}="{n}"}}': values.get(n, 0.0) for n in names}


def ctx(counters: dict, window_s: float = 45.0):
    return run.Context(cell=None, records=[], window_s=window_s, run_end_s=window_s + 2.0,
                       setup_s=25.0, counters=counters, memory={}, trace=None,
                       device_kind="TPU v5 lite")


# the deltas of a saturated 45 s window as the ledger would count them: 72
# admissions in 70 gaps (twice two callers were admitted together), 40 ms an
# admission of which 22 the prefill; three synchronous sweeps beside them
WINDOW = {
    "kvmini_tpu_decode_steps_total": 3_100.0,
    "kvmini_tpu_bubble_seconds_total": 2.889,
    **by_label("gaps", "cause", ("admission", "prefill_piece", "sweep"), admission=70.0, sweep=3.0),
    **by_label("gap_seconds", "cause", ("admission", "prefill_piece", "sweep"),
               admission=2.88, sweep=0.009),
    "kvmini_tpu_sched_gap_slot_seconds_total": 43.3,
    **by_label("admit_gap_seconds", "phase", PHASES, prefill=1.584, first_token=0.72,
               decode_prepare=0.288, emit=0.144, loop=0.072, admit=0.036, decode_dispatch=0.036),
    "kvmini_tpu_sched_admit_gap_admissions_total": 72.0,
    **by_label("seconds", "phase", PHASES, prefill=1.6, first_token=0.73, decode_retire=38.0),
    **by_label("spans", "phase", PHASES, first_token=73.0, admit=73.0),
}

# what the parent of PR 38 exports: the ledger's four families and the older
# stopwatch under the bubble's name, no gap counters
PARENT = {k: v for k, v in WINDOW.items() if "_gap" not in k}


@pytest.mark.parametrize("name,want", [
    ("decode_gap_share", 100.0 * 2.889 / 45.0),       # every cause
    ("decode_gap_share.sat", 6.42),
    ("admit_gap_ms", 40.0),                           # 2.88 s over 72 first tokens
    ("admit_gap_ms.sat", 40.0),
    ("admit_gap_prefill_ms", 22.0),
    ("admit_gap_prefill_ms.sat", 22.0),
    ("admit_gap_host_ms", 18.0),                      # every phase but prefill
    ("admit_gap_host_ms.sat", 18.0),
])
def test_reader_on_hand_made_counters(name, want):
    assert run.read_metric(name, ctx(WINDOW)) == pytest.approx(want)


def test_the_two_parts_add_up_to_the_admission_gap():
    got = {n: run.read_metric(n, ctx(WINDOW)) for n in NEW}
    for suffix in ("", ".sat"):
        assert got["admit_gap_prefill_ms" + suffix] + got["admit_gap_host_ms" + suffix] == (
            pytest.approx(got["admit_gap_ms" + suffix], rel=1e-12))


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_finds_nothing_in_a_program_without_the_gap_counters(name):
    assert run.read_metric(name, ctx(PARENT)) is None
    assert run.read_metric(name, ctx({})) is None


@pytest.mark.parametrize("name", sorted(n for n in NEW if n.startswith("admit_gap")))
def test_a_window_without_an_admission_in_a_gap_reads_nothing(name):
    quiet = {k: 0.0 for k in WINDOW}
    assert run.read_metric(name, ctx(quiet)) is None
    # sweeps' gaps alone: still no admission to divide by
    sweeps = dict(quiet, **{'kvmini_tpu_sched_gap_seconds_total{cause="sweep"}': 0.5})
    assert run.read_metric(name, ctx(sweeps)) is None
    assert run.read_metric("decode_gap_share", ctx(sweeps)) == pytest.approx(100.0 * 0.5 / 45.0)


def test_no_gap_is_a_reading_of_zero():
    quiet = {k: 0.0 for k in WINDOW}
    assert run.read_metric("decode_gap_share", ctx(quiet)) == 0.0
    assert run.read_metric("decode_gap_share", ctx(WINDOW, window_s=0.0)) is None


def test_collect_leaves_out_what_reads_nothing():
    specs = [m for m in BM["per_layer"] if m["name"] in NEW]
    assert run.collect(specs, ctx(PARENT)) == {}
    full = run.collect(specs, ctx(WINDOW))
    assert full.keys() == set(NEW)
    assert full["decode_gap_share.sat"] == {"value": pytest.approx(6.42), "unit": "%"}
    assert full["admit_gap_host_ms"] == {"value": pytest.approx(18.0), "unit": "ms"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_entry_is_a_program_counter_of_exactly_its_cells(name):
    cells, layer, moves = NEW[name]
    entry = {m["name"]: m for m in BM["per_layer"]}[name]
    assert entry == {"name": name, "unit": "%" if name.startswith("decode_gap_share") else "ms",
                     "better": "lower", "source": "program_counter", "layer": layer,
                     "moves": moves, "workloads": entry["workloads"]}
    # the accepted chat or saturated cells, all of them, and whichever later cells report
    # the end-to-end metric it moves
    assert entry["workloads"][:len(cells)] == cells
    judged = {m["name"]: m for m in BM["end_to_end"]}[moves]["workloads"]
    assert set(entry["workloads"]) == set(judged)
    # at the end of the list, after everything PR 37's benchmark had
    names = [m["name"] for m in BM["per_layer"]]
    assert names.index(name) > names.index("pmix_mixer_step_share")
    assert (ROOT / "bench" / "readers" / f"{run.reader_name(name)}.py").is_file()


def test_the_readers_read_a_scrape_of_the_mock_endpoint():
    """The scrape path end to end, without the engine: tests/mock_server.py exports the gap
    series as runtime/server.py does, `run.scrape` parses the labelled lines, and the
    readers read what it returns."""
    from tests.mock_server import MockServer

    async def scraped():
        async with MockServer() as srv:
            return await asyncio.to_thread(run.scrape, srv.url)

    after = asyncio.run(scraped())
    causes = ("admission", "prefill_piece", "sweep")
    assert after["kvmini_tpu_bubble_seconds_total"] == pytest.approx(
        sum(after[f'kvmini_tpu_sched_gap_seconds_total{{cause="{c}"}}'] for c in causes))
    assert sum(after[f'kvmini_tpu_sched_admit_gap_seconds_total{{phase="{p}"}}'] for p in PHASES) == (
        pytest.approx(after['kvmini_tpu_sched_gap_seconds_total{cause="admission"}']))
    window = ctx(after, window_s=0.5)          # the deltas since a start at zero
    assert run.read_metric("decode_gap_share", window) == pytest.approx(2.0)
    assert run.read_metric("admit_gap_ms.sat", window) == pytest.approx(4.0)
    assert run.read_metric("admit_gap_prefill_ms", window) == pytest.approx(2.0)
    assert run.read_metric("admit_gap_host_ms", window) == pytest.approx(2.0)

"""The readers of the scheduler ledger's counters (PR 26): each on counter
deltas made by hand, and on what a program without the ledger exports, where
they must read nothing rather than fail. No JAX."""

import json
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CHAT, SAT = ["mistral-7b.chat", "qwen2-7b.chat"], ["mistral-7b.decode-sat"]
NEW = {
    "queue_wait_mean_ms": CHAT, "prefill_service_mean_ms": CHAT, "prefill_pad_share": CHAT,
    "admit_overhead_ms": CHAT, "admit_overhead_ms.sat": SAT,
    "host_exposed_share": CHAT, "host_exposed_share.sat": SAT,
    "sched_stall_ms.rec": CHAT, "sched_stall_ms.sat": SAT,
}
PHASES = ("loop", "idle", "admit", "prefill", "first_token", "decode_prepare",
          "decode_dispatch", "decode_retire", "emit", "spec_sweep", "masked_sweep", "handoff")


def sched(metric: str, **by_phase) -> dict:
    return {f'kvmini_tpu_sched_{metric}_total{{phase="{p}"}}': by_phase.get(p, 0.0)
            for p in PHASES}


def ctx(counters: dict, window_s: float = 45.0):
    return run.Context(cell=None, records=[], window_s=window_s, run_end_s=window_s + 2.0,
                       setup_s=25.0, counters=counters, memory={}, trace=None,
                       device_kind="TPU v5 lite")


# the deltas of a 45 s chat window as the ledger would count them: 198
# admissions, ~2,200 decode steps
WINDOW = {
    "kvmini_tpu_prefills_total": 198.0,
    "kvmini_tpu_prefill_tokens_total": 52_000.0,
    "kvmini_tpu_prefill_bucket_tokens_total": 80_000.0,
    "kvmini_tpu_decode_steps_total": 2_200.0,
    'kvmini_tpu_phase_seconds_sum{phase="queue"}': 1.98,
    'kvmini_tpu_phase_seconds_count{phase="queue"}': 198.0,
    'kvmini_tpu_phase_seconds_sum{phase="prefill"}': 11.88,
    'kvmini_tpu_phase_seconds_count{phase="prefill"}': 198.0,
    **sched("seconds", loop=0.4, idle=3.0, admit=0.2, prefill=9.0, first_token=1.78,
            decode_prepare=0.9, decode_dispatch=1.1, decode_retire=27.0, emit=1.6),
    **sched("exposed_seconds", loop=0.09, admit=0.2, prefill=9.0, first_token=1.78,
            decode_prepare=0.18, decode_dispatch=0.36, decode_retire=2.0, emit=0.09),
    **sched("slow_seconds", prefill=6.5, idle=0.0, decode_retire=0.101, emit=0.13),
    **sched("spans", loop=2_500.0, admit=200.0, first_token=198.0),
}

# what the parent of PR 26 exports: the phase histograms and the plain
# counters, no ledger and no bucket count
PARENT = {k: v for k, v in WINDOW.items()
          if "_sched_" not in k and "bucket_tokens" not in k}


@pytest.mark.parametrize("name,want", [
    ("queue_wait_mean_ms", 10.0),
    ("prefill_service_mean_ms", 60.0),
    ("admit_overhead_ms", 10.0),                      # (0.2 + 1.78) s over 198 first tokens
    ("admit_overhead_ms.sat", 10.0),
    ("host_exposed_share", 100.0 * 2.7 / 45.0),       # the six host phases; waits left out
    ("host_exposed_share.sat", 6.0),
    ("prefill_pad_share", 35.0),
    ("sched_stall_ms.rec", 231.0),                    # retire 101 + emit 130; prefill is work
    ("sched_stall_ms.sat", 231.0),
])
def test_reader_on_hand_made_counters(name, want):
    assert run.read_metric(name, ctx(WINDOW)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["admit_overhead_ms", "admit_overhead_ms.sat",
                                  "host_exposed_share", "host_exposed_share.sat",
                                  "prefill_pad_share", "sched_stall_ms.rec", "sched_stall_ms.sat"])
def test_reader_finds_nothing_in_a_program_without_the_ledger(name):
    assert run.read_metric(name, ctx(PARENT)) is None
    assert run.read_metric(name, ctx({})) is None


@pytest.mark.parametrize("name", ["queue_wait_mean_ms", "prefill_service_mean_ms"])
def test_phase_means_read_the_histograms_the_parent_has_too(name):
    assert run.read_metric(name, ctx(PARENT)) == run.read_metric(name, ctx(WINDOW))
    assert run.read_metric(name, ctx({})) is None
    quiet = {k: 0.0 for k in WINDOW}                  # a window that admitted nothing
    assert run.read_metric(name, ctx(quiet)) is None


def test_counters_that_count_nothing_read_nothing_but_no_stall_reads_zero():
    quiet = {k: 0.0 for k in WINDOW}
    assert run.read_metric("admit_overhead_ms", ctx(quiet)) is None
    assert run.read_metric("prefill_pad_share", ctx(quiet)) is None
    # an exposed share and a stall time of zero are readings, not gaps
    assert run.read_metric("host_exposed_share", ctx(quiet)) == 0.0
    assert run.read_metric("sched_stall_ms.rec", ctx(quiet)) == 0.0


def test_admit_overhead_is_per_entry_of_first_token():
    """The divisor is the ledger's own count of ``first_token`` entries, not
    the program's older prefill counter: the two phases' seconds and their
    entries come from one book."""
    twice = dict(WINDOW, **{'kvmini_tpu_sched_spans_total{phase="first_token"}': 396.0})
    assert run.read_metric("admit_overhead_ms", ctx(twice)) == pytest.approx(5.0)
    no_spans = {k: v for k, v in WINDOW.items() if "_sched_spans_" not in k}
    assert run.read_metric("admit_overhead_ms", ctx(no_spans)) is None


def test_collect_leaves_out_what_reads_nothing():
    specs = [m for m in BM["per_layer"] if m["name"] in NEW]
    assert run.collect(specs, ctx(PARENT)).keys() == {"queue_wait_mean_ms",
                                                      "prefill_service_mean_ms"}
    full = run.collect(specs, ctx(WINDOW))
    assert full.keys() == set(NEW)
    assert full["host_exposed_share"] == {"value": pytest.approx(6.0), "unit": "%"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_entry_is_a_program_counter_of_its_cells(name):
    entry = {m["name"]: m for m in BM["per_layer"]}[name]
    assert entry["source"] == "program_counter" and entry["better"] == "lower"
    # the cells it was accepted with, and whichever later cells its reader reads a number in
    assert set(NEW[name]) <= set(entry["workloads"])
    moves = "out_tokens_per_s" if name.endswith(".sat") else (
        "itl_p50_ms" if name == "host_exposed_share" else "ttft_p50_ms")
    assert entry["moves"] == moves
    # new entries stand at the end of the list, after everything PR 25 accepted
    names = [m["name"] for m in BM["per_layer"]]
    assert names.index(name) > names.index("device_idle_share.sat")


# -- the recorded trace with the ledger's spans --------------------------------

HEAD = ROOT / "bench" / "testdata" / "trace_head_pr26.json.gz"
CORE = ("loop", "admit", "prefill", "first_token", "decode_prepare", "decode_dispatch",
        "decode_retire", "emit")


def test_recorded_trace_holds_the_new_spans_and_still_reduces():
    """A quarter second of a traced mistral-7b.chat run on the v5e (PR 26) around
    one admission: what the committed reduction makes of it, and what it holds
    for the benchmark PR that lists the new names in ``HOST_SPANS``."""
    from bench import trace_reduce

    events = trace_reduce.load_events(HEAD)
    r = trace_reduce.reduce(events)
    assert 0.0 < r["busy_s"] <= r["window_s"] <= 0.3
    assert {"jit_decode", "jit_prefill", "jit_first"} <= set(r["programs"])
    assert {name for name, _ in r["breakdown"]["idle_gaps"]} <= {
        "kvmini.decode_dispatch", "kvmini.decode_retire", "other-host"}
    host = [e for e in events if e["plane"].startswith("/host:")]
    assert {e["name"] for e in host} == {f"kvmini.{p}" for p in CORE}
    prefill = [e for e in host if e["name"] == "kvmini.prefill"]
    assert prefill and all(
        {"request_id", "prompt_tokens", "bucket", "offset"} <= set(e["args"]) for e in prefill)
    assert all(e["args"]["prompt_tokens"] <= e["args"]["bucket"] for e in prefill)
    # the flash-prefill kernel under the name ops/flash_attention.py gives it
    assert any("kvmini_flash_prefill" in e["name"] and " custom-call(" in e["name"]
               for e in events if e["line"] == trace_reduce.OPS_LINE)


def test_recorded_trace_names_gaps_once_the_names_are_listed(monkeypatch):
    from bench import trace_reduce

    monkeypatch.setattr(trace_reduce, "HOST_SPANS",
                        tuple(f"kvmini.{p}" for p in CORE if p not in ("loop", "admit")))
    gaps = trace_reduce.reduce(trace_reduce.load_events(HEAD))["breakdown"]["idle_gaps"]
    assert {"kvmini.first_token", "kvmini.prefill"} & {name for name, _ in gaps}
    assert sum(name == "other-host" for name, _ in gaps) <= 1

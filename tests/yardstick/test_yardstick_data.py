"""The benchmark's data: BENCHMARK.json against the contract's forms, the
traffic generator, the arrivals and the metric arithmetic. No JAX."""

import json
import random
import re
from pathlib import Path

import pytest

from bench import arrivals, config, metrics, run, traffic

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BM["end_to_end"] + BM["per_layer"]
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BM) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51 and isinstance(BM["run_seconds"], int)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert sum(w["chips"] == 4 for w in BM["workloads"]) <= max(len(CELLS) // 4, 1)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in BM["end_to_end"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    e2e = metric in BM["end_to_end"]
    allowed = ({"name", "unit", "better", "bound", "source", "workloads"} if e2e else
               {"name", "unit", "better", "source", "layer", "moves", "workloads"})
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["source"] in ("device_trace", "program_span", "program_counter",
                                    "host_clock")
        moved = {m["name"]: m for m in BM["end_to_end"]}[metric["moves"]]
        # every cell that reports the layer metric reports the metric it moves
        assert set(metric["workloads"]) <= set(moved.get("workloads", CELLS))
        assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200
    assert set(metric.get("workloads", [])) <= set(CELLS)
    # its own reader, or for a name with a suffix the reader of its stem
    reader = run.reader_name(metric["name"])
    assert reader in (metric["name"], metric["name"].rsplit(".", 1)[0])
    assert callable(config.load_module("readers", reader).read)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_and_reports(cell):
    loaded = config.load_cell(cell)
    entry = loaded["entry"]
    assert NAME.match(cell) and NAME.match(entry["config"]) and NAME.match(entry["traffic"])
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    names = [m["name"] for m in loaded["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2 and loaded["per_layer"]
    model = loaded["config"]["model"]
    assert model["head_dim"] * model["num_attention_heads"] == model["hidden_size"]
    longest = (loaded["traffic"]["prompt_tokens"]["max"]
               + loaded["traffic"]["output_tokens"]["max"])
    assert longest < loaded["config"]["engine"]["max_seq_len"]


@pytest.mark.parametrize("cfg", BM["configs"], ids=lambda c: c["name"])
def test_config_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and cfg["source"].startswith("https://")
    assert cfg["file"].startswith("bench/") and (ROOT / cfg["file"]).is_file()
    raw = json.loads((ROOT / cfg["file"]).read_text())
    assert raw["source"] == cfg["source"] and set(cfg["reduced"]) == set(raw["reduced"])
    for key in cfg["reduced"]:
        assert not re.search(r"(_dim|_rank|hidden|intermediate|head|experts_per)", key)
    assert any(w["config"] == cfg["name"] for w in BM["workloads"])


def test_paths_and_command():
    assert BM["command"] == ["python3", "bench/run.py"]
    for p in BM["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    for path in (ROOT / "bench").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", str(path.relative_to(ROOT))), path


@pytest.mark.parametrize("pattern", arrivals.PATTERNS)
def test_arrivals_one_fixed_period_rotated(pattern):
    gap = arrivals.cycle(pattern, 200, 45.0, random.Random(1))
    assert gap == arrivals.cycle(pattern, 200, 45.0, random.Random(1))
    assert len(gap) == 200 and sum(gap) == pytest.approx(45.0) and min(gap) > 0
    if pattern == "bursty":
        assert max(gap) > 10 * sorted(gap)[100]      # idle time after each burst
    sched = arrivals.rotated(gap, start=150, phase=0.25, before=20)
    lead, window = sched[:20], sched[20:]
    assert [i for i, _ in window] == [(150 + k) % 200 for k in range(200)]
    assert [i for i, _ in lead] == list(range(130, 150))
    times = [t for _, t in sched]
    assert all(y > x for x, y in zip(times, times[1:]))
    assert window[0][1] == pytest.approx(gap[150] * 0.25) and 0 <= times[20] and times[-1] < 45.0
    assert lead[-1][1] < 0 and window[1][1] - window[0][1] == pytest.approx(gap[151])
    assert lead[-1][1] == pytest.approx(window[0][1] - gap[150])


@pytest.mark.parametrize("mix", ["chat", "decode-sat"])
def test_traffic_is_the_same_work_in_another_order(mix):
    tr = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    params = {"rate_rps": 4.4, "clients": 32}
    big = 2**31 + 12345    # the driver's seeds pass 32 signed bits
    p1 = traffic.plan(tr, params, big, 45.0, overhead=17)
    p2 = traffic.plan(tr, params, 7, 45.0, overhead=17)
    assert p1 == traffic.plan(tr, params, big, 45.0, overhead=17)
    # a closed loop cuts each client's first answer to a drawn share: those differ by seed
    sizes = lambda p: sorted((r["prompt_tokens"], 0 if r["id"].endswith(".0") and mix != "chat"  # noqa: E731
                              else r["max_tokens"]) for r in p["requests"])
    full = sorted(traffic.length_pairs(tr, len(p1["requests"]), random.Random(0)))
    if mix != "chat":
        assert sorted(r["prompt_tokens"] for r in p1["requests"]) == sorted(
            r["prompt_tokens"] for r in p2["requests"])
    if mix == "chat":
        win = lambda p: [(r["id"], r["prompt_tokens"], r["max_tokens"])  # noqa: E731
                         for r in p["requests"] if r["phase"] == "window"]
        assert sorted(win(p1)) == sorted(win(p2)) and win(p1) != win(p2)
        k = win(p2).index(win(p1)[0])               # the same period, opened elsewhere
        assert win(p1) == win(p2)[k:] + win(p2)[:k]
    else:
        assert sizes(p1) != sizes(p2) and len(p1["requests"]) == 32 * tr["requests_per_client"]
        assert all((r["prompt_tokens"], r["max_tokens"]) in full
                   for r in p1["requests"] if not r["id"].endswith(".0"))
    assert [r["content"] for r in p1["requests"]] != [r["content"] for r in p2["requests"]]
    for r in p1["requests"]:
        assert len(r["content"].encode()) == r["prompt_tokens"] - 17
        assert tr["prompt_tokens"]["min"] <= r["prompt_tokens"] <= tr["prompt_tokens"]["max"]
    if mix == "chat":
        due = [r["due_s"] for r in p1["requests"] if r["phase"] == "window"]
        assert len(due) == round(4.4 * 45.0) and 0 <= min(due) and max(due) < 45.0
        assert all(-tr["lead_in_s"] <= r["due_s"] < 0 for r in p1["requests"]
                   if r["phase"] == "lead")


def _rec(due, sent, tokens, done=None, status=200, error=None, prompt=100):
    return {"id": "r", "phase": "window", "due_s": due, "sent_s": sent, "token_s": tokens,
            "done_s": done, "status": status, "error": error, "prompt_tokens": prompt,
            "max_tokens": len(tokens), "finish_reason": "length" if done else None,
            "usage": None, "server_ttft_ms": 5.0, "text": ""}


def test_percentile_matches_hand_values():
    assert metrics.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    assert metrics.percentile([10.0], 95) == 10.0
    assert metrics.percentile(list(map(float, range(1, 101))), 95) == pytest.approx(95.05)
    with pytest.raises(ValueError):
        metrics.percentile([], 50)


def test_ttft_counts_from_due_time_and_a_failure_is_the_worst():
    recs = [
        _rec(1.0, 1.5, [2.0, 2.1, 2.3], done=2.3),          # sent late: 1000 ms from due
        _rec(2.0, 2.0, [2.2], done=2.2),                    # 200 ms
        _rec(3.0, 3.0, [], status=429, error="shed"),       # refused: the worst
        _rec(4.0, 4.0, []),                                 # never answered: waited to the end
        _rec(-1.0, -1.0, [0.5, 0.6], done=0.6),             # lead-in: not due in the window
        _rec(10.5, 10.5, [10.6]),                           # due after the window
    ]
    tt = metrics.ttfts_ms(recs, window_s=10.0, run_end_s=12.0)
    assert sorted(tt) == pytest.approx([200.0, 1000.0, 8000.0, 9000.0])
    assert metrics.lateness_ms(recs, 10.0) == pytest.approx([500.0, 0.0, 0.0, 0.0])
    assert [metrics.failed(r) for r in recs[:4]] == [False, False, True, False]
    # gaps: every gap whose later token arrived inside the window, lead-in requests too
    assert sorted(metrics.token_gaps_ms(recs, 10.0)) == pytest.approx([100.0, 100.0, 200.0])
    assert metrics.tokens_in_window(recs, 10.0) == 6
    assert metrics.resident_tokens(recs, 2.2) == (100 + 2) + (100 + 1)

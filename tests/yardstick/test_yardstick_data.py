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
    # what any decoder's attention needs; the heads' width together need not be the hidden size
    for key in ("head_dim", "num_attention_heads", "num_key_value_heads", "hidden_size"):
        assert isinstance(model[key], int) and not isinstance(model[key], bool) and model[key] > 0
    assert model["num_attention_heads"] % model["num_key_value_heads"] == 0
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
        assert NAME.match(key) and COUNT.search(key), key
    assert states_the_depth_it_was_cut_from(raw)
    assert any(w["config"] == cfg["name"] for w in BM["workloads"])


# ``reduced`` may name only what is COUNTED and held here: layers (a depth, a layer pattern),
# heads, routed experts, vocabulary rows, positions. One pattern admits those and every other
# key is refused, so a width can never be listed: a hidden, intermediate, latent, state or
# projection size, a key that ends in ``_dim`` or ``_rank``, a head size, a window, an
# expansion factor, the number of experts a token takes (shared experts among them: every
# token runs each), nor anything that is no size at all.
COUNT = re.compile(r"^num_hidden_layers$|^n_layers?$|pattern$|^layer_types$|^layers_block_type$"
                   r"|^(\w+_)?(n|num)_(?!shared_)(\w+_)?(heads|experts)$"
                   r"|^vocab_size$|^max_position_embeddings$")
REFUSED = ["hidden_size", "head_dim", "intermediate_size", "moe_intermediate_size",
           "moe_shared_expert_intermediate_size", "shared_expert_intermediate_size",
           "mamba_d_state", "mamba_d_ssm", "mamba_head_dim", "mamba_expand", "expand",
           "ssm_state_size", "moe_latent_size", "sliding_window", "window_size",
           "num_experts_per_tok", "moe_num_experts_per_tok", "kv_lora_rank", "q_lora_rank",
           "qk_rope_head_dim", "v_head_dim", "linear_key_head_dim", "linear_value_head_dim",
           "linear_conv_kernel_dim", "d_head", "d_state", "d_ssm", "d_model", "d_ff",
           "attention_head_size",
           # what the parent's rule refused and a list of widths alone would let through
           "ffn_hidden_size", "headdim", "d_inner", "kv_channels", "n_embd", "n_inner",
           "mamba_d_conv", "n_shared_experts", "num_shared_experts",
           # no size at all
           "rope_theta", "rms_norm_eps", "torch_dtype", "tie_word_embeddings", "hidden_act"]
ADMITTED = ["num_hidden_layers", "n_layer", "hybrid_override_pattern", "layer_types",
            "layers_block_type", "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_num_heads", "linear_num_key_heads", "linear_num_value_heads",
            "n_routed_experts", "num_local_experts", "num_experts", "vocab_size",
            "max_position_embeddings"]


def states_the_depth_it_was_cut_from(raw: dict) -> bool:
    """A file that lists ``num_hidden_layers`` under ``reduced`` carries the
    published depth beside its own, under ``published``."""
    if "num_hidden_layers" not in raw["reduced"]:
        return True
    published = raw.get("published", {}).get("num_hidden_layers")
    return isinstance(published, int) and published >= raw["num_hidden_layers"] >= 1


def test_a_depth_cut_states_the_depth_it_was_cut_from():
    cut = {"num_hidden_layers": 12, "reduced": {"num_hidden_layers": "12 of the published 72"}}
    assert not states_the_depth_it_was_cut_from(cut)
    assert states_the_depth_it_was_cut_from({**cut, "published": {"num_hidden_layers": 72}})
    assert not states_the_depth_it_was_cut_from({**cut, "published": {"num_hidden_layers": 8}})
    assert states_the_depth_it_was_cut_from({"num_hidden_layers": 32, "reduced": {}})


@pytest.mark.parametrize("key,admitted",
                         [(k, False) for k in REFUSED] + [(k, True) for k in ADMITTED])
def test_reduced_refuses_a_width_and_admits_a_count(key, admitted):
    assert bool(COUNT.search(key)) is admitted


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

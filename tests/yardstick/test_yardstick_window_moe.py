"""The window / global stack with routed ReLU-gated experts in the yardstick
(PR 44): its entries in BENCHMARK.json (membership only: a later PR appends what
it likes), its file and byte table, the new traffic mix, the new cell's
rehearsal, the served path against the plain reference with the lower-precision
control and altered tokens, and the seven readers on a recorded trace and
against programs that have no such layers or counters."""

import json
from pathlib import Path

import pytest

from bench import check, config, conv_moe_roofline, run, trace_reduce, traffic, window_moe_roofline
from tests.yardstick.test_yardstick_hybrid import serve

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
HEAD = ROOT / "bench" / "testdata" / "trace_head_pr44.json.gz"
CONFIG = "smallthinker-21b-pp4-int8"
CELL = "smallthinker-21b-pp4.doc-chat"
TRACE_READERS = ("mixed_kv_decode_ms", "reglu_moe_decode_ms", "reglu_moe_expert_hbm_share",
                 "reglu_moe_prefill_share", "reglu_moe_prefill_flop_share",
                 "window_flash_prefill_roofline")
COUNTER_READERS = ("reglu_moe_expert_touch_share",)
OTHERS = ("mistral-7b.chat", "olmo-hybrid-7b.chat", "nemotron-3-super-ep4.decode-sat",
          "falcon-h1-34b.decode-sat", "lfm2-8b-a1b.chat")
system = config.load_module("systems", "engine")


def tiny():
    return config.load_cell(CELL, rehearse=True)["config"]


def full_model():
    return config.load_cell(CELL)["config"]["model"]


def entry(name):
    return {m["name"]: m for m in BM["end_to_end"] + BM["per_layer"]}[name]


def test_benchmark_entries_of_this_pr():
    cell = {w["name"]: w for w in BM["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "doc-chat", 1)
    cfg = {c["name"]: c for c in BM["configs"]}[CONFIG]
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["source"] == ("https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
                             "blob/main/config.json")
    # one line of at most 200 printable characters each (a 208-character `why` was refused)
    for line in (cfg["why"], cfg["source"], cell["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable() and line.isascii(), line
    # the chat cells' metrics, every one whose reader reads a number here
    for name in ("ttft_p50_ms", "itl_p50_ms", "gen_late_p95_ms", "http_overhead_p50_ms",
                 "prefill_stall_share", "ttft_p95_ms.rec", "itl_p95_ms.rec", "queue_wait_mean_ms",
                 "prefill_service_mean_ms", "prefill_pad_share", "admit_overhead_ms",
                 "host_exposed_share", "sched_stall_ms.rec", "sampler_filter_share",
                 "decode_gap_share", "admit_gap_ms", "admit_gap_prefill_ms", "admit_gap_host_ms",
                 "admit_inflight_share", "decode_step_ms", "device_idle_share",
                 "decode_kv_read_share"):
        assert CELL in entry(name)["workloads"], name
    # the accepted flash reader counts every call's operations under the causal mask: not here
    assert CELL not in entry("flash_prefill_roofline")["workloads"]
    for name in TRACE_READERS + COUNTER_READERS:
        assert entry(name)["workloads"] == [CELL], name
    moved = {name: entry(name)["moves"] for name in TRACE_READERS + COUNTER_READERS}
    assert moved == {"mixed_kv_decode_ms": "itl_p50_ms", "reglu_moe_decode_ms": "itl_p50_ms",
                     "reglu_moe_expert_hbm_share": "itl_p50_ms",
                     "reglu_moe_expert_touch_share": "itl_p50_ms",
                     "reglu_moe_prefill_share": "ttft_p50_ms",
                     "reglu_moe_prefill_flop_share": "ttft_p50_ms",
                     "window_flash_prefill_roofline": "ttft_p50_ms"}
    assert all(entry(n)["unit"] == "%" for n in TRACE_READERS if n.endswith(("share", "roofline")))
    params = json.loads((ROOT / "bench" / "workloads" / f"{CELL}.json").read_text())
    assert params["rate_rps"] == pytest.approx(params["rate_share_of_knee"] * params["knee_rps"])
    assert params["rate_share_of_knee"] in (0.8, 0.6)
    rows = params["sweep"]["rows"]
    assert len(rows) >= 4 and any(r["rate_rps"] == params["knee_rps"] for r in rows)
    assert f"{params['rate_rps']:g} req/s" in cell["why"]
    # at least 40 requests are due in a window at the cell's rate
    assert round(params["rate_rps"] * BM["run_seconds"]) >= 40


def test_configuration_file_holds_the_sources_keys_the_cut_and_the_byte_table():
    raw = json.loads((ROOT / "bench" / "configs" / f"{CONFIG}.json").read_text())
    catalog = {"head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
               "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
               "moe_num_primary_experts": 64, "moe_primary_router_apply_softmax": True,
               "norm_topk_prob": True, "num_attention_heads": 28, "num_key_value_heads": 4,
               "rms_norm_eps": 1e-06, "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
               "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
               "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936}
    assert {k: raw[k] for k in catalog} == catalog        # every width and every count as published
    assert list(raw["reduced"]) == ["num_hidden_layers"]
    assert (raw["num_hidden_layers"], raw["published"]["num_hidden_layers"]) == (12, 52)
    # what only the program names repeats the file's keys under ModelConfig's field names
    extra = raw["model_extra"]
    assert extra == {"sliding_window_layout": raw["sliding_window_layout"],
                     "rope_layout": raw["rope_layout"],
                     "sliding_window_size": raw["sliding_window_size"],
                     "n_routed_experts": raw["moe_num_primary_experts"],
                     "num_experts_per_tok": raw["moe_num_active_primary_experts"],
                     "moe_intermediate_size": raw["moe_ffn_hidden_size"],
                     "norm_topk_prob": raw["norm_topk_prob"]}
    assert raw["engine"] == {"quantization": "int8", "kv_cache_dtype": None, "kv_layout": "dense",
                             "max_slots": 16, "max_seq_len": 16384}
    assumed = " ".join(raw["assumed"])
    assert all(word in assumed for word in ("BEFORE the attention norm", "ReLU on the gate alone",
                                            "intermediate_size 0", "secondary experts",
                                            "expert_down_std", "R = sliding_window_size"))
    # the deployment's byte table: two int8 stacks, a bf16 embedding and head, two K/V leaves
    m = full_model()
    weights = config.load_module("references", "window_moe_decoder").weights
    assert weights.layer_nbytes(m, "attn") == 20_971_520 + 4 * (3584 + 512 + 512 + 2560)
    assert weights.layer_nbytes(m, "experts") == 64 * (5_898_240 + 16_384)
    assert 12 * 398_458_880 == 4_781_506_560 and 2 * 151_936 * 2560 * 2 == 1_555_824_640
    assert 3 * 16 * 4 * 16_384 * 128 * 2 * 2 == 1_610_612_736      # the global layers' stripes
    assert 9 * 16 * 4 * 4096 * 128 * 2 * 2 == 1_207_959_552         # the window layers' ring
    held = 4_781_506_560 + 12 * 1_077_248 + 12 * 655_360 + 1_555_824_640 + 1_610_612_736 + 1_207_959_552
    assert 0.54 < held / 16_909_336_064 < 0.55
    assert window_moe_roofline.expert_bytes(m) == 3 * 2560 * 768 + 4 * (2 * 768 + 2560) == 5_914_624
    assert window_moe_roofline.expert_flops_per_token(m) == 6 * 3 * 2 * 2560 * 768
    cfg = system.model_config(m)
    assert cfg.is_window_stack and (cfg.n_window_layers, cfg.n_global_layers) == (9, 3)
    assert (cfg.n_expert_layers, cfg.experts_held, cfg.head_dim) == (12, 64, 128)
    assert cfg.ring_len(16_384) == 4096 and not cfg.tie_embeddings


def test_the_traffic_sends_long_prompts_past_the_window_on_one_fixed_schedule():
    tr = json.loads((ROOT / "bench" / "traffic" / "doc-chat.json").read_text())
    assert tr["kind"] == "independent" and tr["loop"] == "open" and tr["arrivals"] == "exponential"
    assert tr["temperature"] == 0.0 and tr["limits"]["ttft_ms"] == 2000.0
    assert tr["limits"]["itl_mean_ms"] == 50.0
    prompts = traffic.quantiles(tr["prompt_tokens"], 200)
    assert min(prompts) == 1024 and max(prompts) == 8192
    assert 0.25 < sum(p > 4096 for p in prompts) / 200 < 0.31          # about 28% past the window
    assert 2900 < sorted(prompts)[100] < 3250
    assert tr["warm_prompt_tokens"] == [1024, 2048, 4096, 8192]        # every bucket it will use
    assert tr["check"]["requests"] == 4 and tr["check"]["pad_sequence_to"] == 8704 >= 8192 + 384
    params = {"rate_rps": 1.0}
    big = 2**31 + 12345
    p1, p2 = (traffic.plan(tr, params, seed, 45.0, overhead=17) for seed in (big, 7))
    win = lambda p: [(r["id"], r["prompt_tokens"], r["max_tokens"])  # noqa: E731
                     for r in p["requests"] if r["phase"] == "window"]
    assert sorted(win(p1)) == sorted(win(p2)) and win(p1) != win(p2) and len(win(p1)) == 45
    assert all(len(r["content"].encode()) == r["prompt_tokens"] - 17 for r in p1["requests"])


def rehearse_args(seed, trace):
    return run.parse_args(["--workload", CELL, "--seed", str(seed), "--seconds", "5",
                           "--trace", str(trace), "--rehearse"])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_rehearses(trace):
    """The harness end to end at the tiny sizes (window 16, prompts of 36-120:
    past two windows); ``correct`` by the full-size limits is not asked of the
    tiny model (the next test holds it to its own)."""
    got = run.run_cell(rehearse_args(2**31 + 441 + trace, trace))
    assert got["failed"] == 0 and got["attempted"] >= 8
    if not trace:
        assert {"ttft_p50_ms", "itl_p50_ms", "setup_s"} <= set(got["metrics"])
    else:
        # no device trace on the CPU: the counter's reader reads, the trace's six do not;
        # the eager reads book no K/V blocks
        assert 0.0 < got["metrics"]["reglu_moe_expert_touch_share"]["value"] <= 100.0
        assert not set(TRACE_READERS) & set(got["metrics"])
        assert "decode_kv_read_share" not in got["metrics"]
        # (admit_inflight_share reads nothing in a window where no admission found a sweep in
        # flight, which a loaded machine's 5 s can be: tests/test_window_moe_engine.py holds it)
        assert {"prefill_pad_share", "decode_gap_share"} <= set(got["metrics"])


# 650 served positions at the tiny widths (two periods of a global layer and three window-16
# layers, 8 experts under each, 2 a token; weights at std 0.08 and the experts' W_down at 0.4,
# the configuration's factor of five: at 0.08 every answer repeats one token, as at the
# published widths). Readings of gap_mean (CPU, seeds 5, 6, 16): sound 0.0550, 0.0691, 0.0575
# (493-519 distinct tokens of 650); w8a8 0.1241, 0.1559, 0.1271. Seed 5: the limit sits 1.55
# times over the sound run and 1.46 times under the control. A routing choice is discrete and a
# swapped expert is half of a layer's routed output here, so the sound runs read ten times what
# a dense stack of these widths does, as the other expert stacks' tests say of theirs. gap_max
# (2.9-3.6 either way: one position where the best of 512 logits went to another token) says
# nothing at this size and is held against tokens that are not the model's.
CONTROL_LIMITS = {"gap_mean": 0.085, "gap_max": 8.0}
CONTROL_ASKS = [("a" * 20, 180), ("hello yardstick " * 6, 120), ("zulu9 " * 3, 200),
                ("kilo lima 7 " * 5, 150)]
PADS = {"pad_sequence_to": 256, "pad_rows_to": 64}


def test_served_path_agrees_the_control_does_not_and_altered_tokens_do_not():
    """Prefill and decoding through the cache (a ring of 16 that every answer
    wraps ten times over), served over HTTP, against the reference's full
    forward pass; the program's own w8a8 path in its place; then served tokens
    that are not the model's."""
    numbers = {}
    for mode in ("dequant", "w8a8"):
        cfg = tiny()
        cfg["engine"]["quant_mode"] = mode
        seqs, first = serve(cfg, 5, CONTROL_ASKS)
        numbers[mode], positions = check.served_numbers(5, cfg["model"], seqs, first, PADS)
        assert positions == sum(n for _, n in CONTROL_ASKS)
        if mode == "dequant":
            sound = (seqs, first)
    assert check.within(numbers["dequant"], CONTROL_LIMITS)[0], numbers
    ok, lines = check.within(numbers["w8a8"], CONTROL_LIMITS)
    assert not ok and any("gap_mean" in line and "OVER" in line for line in lines), numbers
    seqs, first = sound
    assert len({t for s, f in zip(seqs, first) for t in s[f:]}) > 20     # no one token repeated
    vocab = tiny()["model"]["vocab_size"]
    wrong = [s[:f] + [(t + 1) % vocab for t in s[f:]] for s, f in zip(seqs, first)]
    broken, _ = check.served_numbers(5, tiny()["model"], wrong, first, PADS)
    assert not check.within(broken, CONTROL_LIMITS)[0]
    assert not check.within(broken, check.limits_for(CONFIG))[0]


# -- the readers ---------------------------------------------------------------

def ctx(counters, trace=None, model=None):
    return run.Context(cell=None, records=[], window_s=45.0, run_end_s=47.0, setup_s=30.0,
                       counters=counters,
                       memory={"model": model or full_model(), "engine": {"max_slots": 16}},
                       trace=trace, device_kind="TPU v5 lite")


COUNTERS = {   # a window: 3,000 decode steps, ~5 of 16 slots live, 12 expert layers
    "kvmini_tpu_decode_steps_total": 3000.0,
    "kvmini_tpu_decode_tokens_total": 3000.0 * 5.0,
    "kvmini_tpu_moe_assignments_total": 3000.0 * 5.0 * 6 * 12,
    "kvmini_tpu_moe_local_assignments_total": 3000.0 * 5.0 * 6 * 12,
    "kvmini_tpu_moe_expert_touches_total": 3000.0 * 24.0 * 12,
    "kvmini_tpu_moe_expert_load_max_total": 3000.0 * 3.0 * 12,
    "kvmini_tpu_prefill_chunks_total": 45.0,
    "kvmini_tpu_prefill_tokens_total": 45.0 * 3400.0,
    "kvmini_tpu_prefill_bucket_tokens_total": 45.0 * 4700.0,
}


def test_counter_reader_and_nothing_without_the_counters_or_the_layers():
    touch = config.load_module("readers", "reglu_moe_expert_touch_share")
    assert touch.read(ctx(COUNTERS)) == pytest.approx(100.0 * 24.0 / 64.0)
    assert touch.read(ctx({})) is None                                   # the parent's program
    assert touch.read(ctx({"kvmini_tpu_decode_steps_total": 3000.0})) is None
    for cell in OTHERS:
        other = config.load_cell(cell)["config"]["model"]
        assert touch.read(ctx(COUNTERS, model=other)) is None, cell
        assert window_moe_roofline.sizes(other) is None


def test_shape_rules_name_the_layers_operations_and_nothing_else():
    m = full_model()
    experts = ("jit_decode/%fusion.412 = bf16[64,16,768] fusion(bf16[16,2560] %x, s8[12,64,2560,768] %p, "
               "f32[12,64,768]")
    down = "jit_decode/%fusion.413 = f32[16,2560] fusion(bf16[64,16,768] %g, s8[12,64,768,2560] %p.1, f32[16,64]"
    router = "jit_decode/%fusion.77 = f32[16,64] fusion(bf16[16,2560] %h, f32[12,2560,64] %get-tuple-element.9"
    choice = "jit_decode/%sort.5 = (f32[16,64], s32[16,64]) sort(f32[16,64] %s, s32[16,64] %iota"
    chosen = "jit_decode/%fusion.80 = f32[16,6] fusion(f32[16,64] %scores, s32[16,6] %top"
    ring = ("jit_decode/%kvmini_dense_decode.9 = bf16[16,4,7,128] custom-call(s32[1] %l, s32[16] %q, "
            "bf16[16,4,7,128] %qg, bf16[9,16,4,4096,128]")
    stripe = ("jit_decode/%kvmini_dense_decode.1 = bf16[16,4,7,128] custom-call(s32[1] %l, s32[16] %q, "
              "bf16[16,4,7,128] %qg, bf16[3,16,4,16384,128]")
    write = "jit_decode/%fusion.14 = bf16[9,16,4,4096,128] fusion(bf16[9,16,4,4096,128] %cache, bf16[16,4,1,128] %new"
    wq = "jit_decode/%fusion.20 = bf16[16,3584] fusion(s8[12,2560,3584] %get-tuple-element.5, bf16[16,2560] %h"
    head = "jit_decode/%convert_subtract_fusion = f32[16,151936] fusion(bf16[151936,2560] %lm_head, bf16[16,2560] %x"
    flash = ("jit_prefill/%kvmini_flash_prefill.20 = bf16[1,28,8192,128] custom-call(bf16[1,28,8192,128] %q, "
             "bf16[1,4,8192,128] %k")
    loop = "jit_decode/%while.13 = (s32[], bf16[16,1,2560], s8[12,64,2560,768], bf16[9,16,4,4096,128]"
    ops = (experts, down, router, choice, chosen, ring, stripe, write, wq, head, flash, loop)
    assert [window_moe_roofline.reads_expert_stack(n, m) for n in ops] == [True, True] + [False] * 10
    assert [window_moe_roofline.in_expert_layer(n, m) for n in ops] == [True] * 5 + [False] * 7
    assert [window_moe_roofline.reads_kv(n, m, 16) for n in ops] == (
        [False] * 5 + [True, True] + [False] * 5)
    trace = {"ops": {flash: {"seconds": 0.0157, "calls": 1}}}
    assert window_moe_roofline.flash_calls(trace, m) == {8192: (0.0157, 1)}


def test_the_flash_calls_operations_are_counted_inside_their_own_masks():
    m = full_model()
    pk = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    pairs = window_moe_roofline.visible_pairs
    assert pairs(4, None) == 10 and pairs(4, 2) == 3 + 2 * 2 == 7 and pairs(4, 9) == 10
    brute = sum(1 for i in range(300) for j in range(300) if j <= i and i - j < 70)
    assert pairs(300, 70) == brute
    assert pairs(8192, 4096) / pairs(8192, None) == pytest.approx(0.75, abs=0.001)
    # a piece that fits the window costs a window layer what it costs a global one
    for t in (1024, 4096):
        assert window_moe_roofline.flash_min_s(m, t, 4096, pk) == window_moe_roofline.flash_min_s(
            m, t, None, pk)
    causal = window_moe_roofline.flash_min_s(m, 8192, None, pk)
    assert causal == pytest.approx(4 * 28 * pairs(8192, None) * 128 / 197e12)     # compute-bound
    assert window_moe_roofline.flash_min_s(m, 8192, 4096, pk) == pytest.approx(0.75 * causal, rel=1e-3)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_read_nothing_without_a_trace_or_without_such_layers(name):
    reader = config.load_module("readers", name)
    assert reader.read(ctx(COUNTERS)) is None
    trace = trace_reduce.reduce(trace_reduce.load_events(HEAD))
    for cell in OTHERS:
        other = config.load_cell(cell)["config"]["model"]
        assert reader.read(ctx(COUNTERS, trace, model=other)) is None
    # and on the other stacks' own recorded traces, their own models
    for head, cell in (("trace_head.json.gz", "mistral-7b.chat"),
                       ("trace_head_pr40.json.gz", "lfm2-8b-a1b.chat")):
        theirs = trace_reduce.reduce(trace_reduce.load_events(ROOT / "bench" / "testdata" / head))
        other = config.load_cell(cell)["config"]["model"]
        assert reader.read(ctx(COUNTERS, theirs, model=other)) is None


def test_the_readers_on_the_recorded_trace():
    """0.43 s of smallthinker-21b-pp4.doc-chat on the v5e (PR 44): a decode
    step, a 2835-token prompt's ``prefill[4096]``, its first token, another
    step."""
    m = full_model()
    events = trace_reduce.load_events(HEAD)
    trace = trace_reduce.reduce(events)
    steps, prefills = trace["programs"]["jit_decode"], trace["programs"]["jit_prefill"]
    assert len(steps) == 2 and len(prefills) == 1 and 0.30 < prefills[0] < 0.35
    step_ms = 1000.0 * sum(steps) / len(steps)
    c = ctx(COUNTERS, trace)
    got = {name: config.load_module("readers", name).read(c) for name in TRACE_READERS}
    # twelve Mosaic calls a step over the two leaves, a tenth of it; the experts two thirds
    kv = [n for n in trace["ops"] if n.startswith("jit_decode/")
          and window_moe_roofline.reads_kv(n, m, 16)]
    assert len(kv) == 12 and all("kvmini_dense_decode" in n for n in kv)
    assert 0.05 * step_ms < got["mixed_kv_decode_ms"] < 0.2 * step_ms
    assert 0.6 * step_ms < got["reglu_moe_decode_ms"] < 0.8 * step_ms
    # the slice's own two retired steps touched 130 and 188 experts over the twelve layers (13 a
    # layer of 64; the window's counters say 24: not read), with 360 assignments' activations
    assert trace["spans"]["kvmini.emit"] == {"count": 2, "sums": {"moe_touched": 318, "moe_steps": 2}}
    stack_ms = 1000.0 * window_moe_roofline.seconds_and_runs(
        trace, m, "jit_decode", window_moe_roofline.reads_expert_stack)[0] / len(steps)
    need_ms = (159.0 * 5_914_624 + 5.0 * 6 * 12 * 10_240) / 819e9 * 1000.0
    assert got["reglu_moe_expert_hbm_share"] == pytest.approx(100.0 * need_ms / stack_ms)
    assert 12.0 < got["reglu_moe_expert_hbm_share"] < 25.0 and stack_ms <= got["reglu_moe_decode_ms"]
    assert got["reglu_moe_expert_hbm_share"] == conv_moe_roofline.expert_hbm_share(
        c, window_moe_roofline)
    # every expert touched in the slice: under 100 still (PR 44's program multiplies them all)
    every = {**trace, "spans": {"kvmini.emit": {"count": 2, "sums": {
        "moe_steps": 2, "moe_touched": 2 * 64 * 12}}}}
    full = config.load_module("readers", "reglu_moe_expert_hbm_share").read(ctx(COUNTERS, every))
    assert 80.0 < full < 100.0
    assert run.collect([{"name": "reglu_moe_expert_hbm_share", "unit": "%"}], ctx(COUNTERS, every))
    # the one prefill: the expert layers are four fifths of it; the slice ran ONE piece of 4096
    # positions (twelve flash calls of that length), 72.3% of a bucket real by the window's
    # counters: the chosen experts' arithmetic is a twentieth of the peak over the stacks' time
    assert window_moe_roofline.flash_calls(trace, m).keys() == {4096}
    assert window_moe_roofline.flash_calls(trace, m)[4096][1] == 12
    assert 75.0 < got["reglu_moe_prefill_share"] < 88.0
    stack_s = window_moe_roofline.seconds_and_runs(
        trace, m, "jit_prefill", window_moe_roofline.reads_expert_stack)[0]
    flops = 4096 * (3400.0 / 4700.0) * 12 * 6 * 3 * 2 * 2560 * 768
    assert got["reglu_moe_prefill_flop_share"] == pytest.approx(100.0 * flops / 197e12 / stack_s)
    assert 3.0 < got["reglu_moe_prefill_flop_share"] < 9.4          # 6 of 64 is the ceiling
    # the twelve flash calls at 4096 positions (a window layer's mask is the causal one there)
    took = window_moe_roofline.flash_calls(trace, m)[4096][0]
    least = 12 * 4 * 28 * (4096 * 4097 // 2) * 128 / 197e12
    assert got["window_flash_prefill_roofline"] == pytest.approx(100.0 * least / took)
    assert 20.0 < got["window_flash_prefill_roofline"] < 60.0
    # the rules name leaf operations only; an operation that reads a stack is the layer's
    stacks = [n for n in trace["ops"] if window_moe_roofline.reads_expert_stack(n, m)]
    assert stacks and all(window_moe_roofline.in_expert_layer(n, m) and "%while" not in n
                          for n in stacks)
    assert sum("s8[12,64,768,2560]" in n for n in stacks if n.startswith("jit_decode/")) == 12
    assert not any(window_moe_roofline.in_expert_layer(n, m) for n in kv)
    # the accepted flash reader would count these calls too, every one under the causal mask
    assert config.load_module("readers", "flash_prefill_roofline").read(c) is not None
    # a piece's span names its layers by kind and whether it wrapped the ring; the span that
    # retires a sweep carries that sweep's touched experts
    spans = [e for e in events if e["plane"].startswith("/host")]
    piece = next(e["args"] for e in spans if e["name"] == "kvmini.prefill" and "bucket" in e.get("args", {}))
    assert (piece["window_layers"], piece["global_layers"], piece["ring_wrapped"]) == (9, 3, 0)
    assert piece["moe_assignments"] == piece["prompt_tokens"] * 6 * 12 and piece["bucket"] == 4096
    emits = [e["args"] for e in spans if e["name"] == "kvmini.emit" and "args" in e]
    assert emits and all(a["moe_steps"] == 1 and 12 <= a["moe_touched"] <= 12 * 64 for a in emits)

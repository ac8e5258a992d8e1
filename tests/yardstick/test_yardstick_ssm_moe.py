"""The state-space / latent-expert configuration in the yardstick (PR 32): its
entries in BENCHMARK.json (membership only: a later PR appends what it likes),
its file and byte table, the two new cells' rehearsal, the served path against
the plain reference with the lower-precision control and altered tokens, and
the six readers on a recorded trace and against programs that have no such
blocks or counters."""

import json
from pathlib import Path

import pytest

from bench import check, config, conv_moe_roofline, run, ssm_moe_roofline, trace_reduce
from tests.yardstick.test_yardstick_hybrid import serve

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
HEAD = ROOT / "bench" / "testdata" / "trace_head_pr32.json.gz"
CONFIG = "nemotron-3-super-ep4-int8"
CELL = "nemotron-3-super-ep4.decode-sat"
OLMO = "olmo-hybrid-7b.decode-sat"
TRACE_READERS = ("moe_decode_ms", "moe_expert_hbm_share", "ssm_decode_ms", "ssm_state_hbm_share")
COUNTER_READERS = ("moe_expert_touch_share", "moe_load_max_over_mean")
system = config.load_module("systems", "engine")


def tiny():
    return config.load_cell(CELL, rehearse=True)["config"]


def full_model():
    return config.load_cell(CELL)["config"]["model"]


def cells_of(name):
    return {m["name"]: m for m in BM["end_to_end"] + BM["per_layer"]}[name].get("workloads", [])


def test_benchmark_entries_of_this_pr():
    cells = {w["name"]: w for w in BM["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"], cells[CELL]["chips"]) == (
        CONFIG, "decode-sat", 1)
    assert (cells[OLMO]["config"], cells[OLMO]["traffic"], cells[OLMO]["chips"]) == (
        "olmo-hybrid-7b-int8", "decode-sat", 1)
    entry = {c["name"]: c for c in BM["configs"]}[CONFIG]
    assert set(entry["reduced"]) == {"hybrid_override_pattern", "n_routed_experts", "vocab_size",
                                     "max_position_embeddings"}
    for name in ("out_tokens_per_s", "batch_occupancy", "ttft_p95_ms.sat", "kv_in_use_share",
                 "hbm_peak_share", "decode_step_ms.sat", "device_idle_share.sat",
                 "sampler_filter_share.sat"):
        assert CELL in cells_of(name) and OLMO in cells_of(name), name
    for name in TRACE_READERS + COUNTER_READERS:
        assert CELL in cells_of(name) and OLMO not in cells_of(name), name
    for name in ("linear_decode_ms.sat", "linear_state_hbm_share.sat"):
        assert OLMO in cells_of(name) and CELL not in cells_of(name), name
    # a dense decoder's bytes, and the lists two other test files pin: not ours
    assert not {CELL, OLMO} & set(cells_of("decode_hbm_share"))
    moved = {m["name"]: m["moves"] for m in BM["per_layer"]}
    assert all(moved[n] == "out_tokens_per_s" for n in TRACE_READERS + COUNTER_READERS
               + ("linear_decode_ms.sat", "linear_state_hbm_share.sat"))
    for cell, clients, slots in ((CELL, 64, 32), (OLMO, 32, 16)):
        params = json.loads((ROOT / "bench" / "workloads" / f"{cell}.json").read_text())
        assert params["clients"] == clients == 2 * config.load_cell(cell)["config"]["engine"]["max_slots"]
        assert slots == clients // 2


def test_configuration_file_holds_the_sources_keys_the_cut_and_the_byte_table():
    raw = json.loads((ROOT / "bench" / "configs" / f"{CONFIG}.json").read_text())
    catalog = {"attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
               "head_dim": 128, "hidden_size": 4096, "intermediate_size": 2688,
               "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu",
               "mamba_num_heads": 128, "mamba_proj_bias": False, "mlp_bias": False,
               "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 2688,
               "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
               "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8, "n_shared_experts": 1,
               "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
               "num_experts_per_tok": 22, "num_hidden_layers": 88, "num_key_value_heads": 2,
               "num_nextn_predict_layers": 1, "partial_rotary_factor": 1, "rope_theta": 10000,
               "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
               "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
               "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True}
    assert {k: raw[k] for k in catalog} == catalog          # every width as published
    assert list(raw["reduced"]) == ["hybrid_override_pattern", "n_routed_experts", "vocab_size",
                                    "max_position_embeddings"]
    published = raw["published"]
    assert raw["hybrid_override_pattern"] == published["hybrid_override_pattern"][:22]
    assert len(published["hybrid_override_pattern"]) == published["num_hidden_layers"] == 88
    assert {c: published["hybrid_override_pattern"].count(c) for c in "M*E"} == published["blocks"]
    assert {c: raw["hybrid_override_pattern"].count(c) for c in "M*E"} == {"M": 10, "*": 2, "E": 10}
    assert (raw["n_routed_experts"], published["n_routed_experts"]) == (128, 512)
    assert (raw["vocab_size"], published["vocab_size"]) == (32768, 131072)
    assert raw["max_position_embeddings"] == 2048
    assert raw["share"] == {"chips": 4, "rank": 0, "router_width": 512, "expert_offset": 0,
                            "vocab_offset": 0}
    # what only the program names repeats the file's keys; the router's width and the share beside
    extra = raw["model_extra"]
    assert (extra["n_routed_experts"], extra["n_experts_held"], extra["expert_offset"]) == (512, 128, 0)
    assert all(raw[k] == v for k, v in extra.items()
               if k not in ("n_routed_experts", "n_experts_held", "expert_offset"))
    assert raw["engine"] == {"quantization": "int8", "kv_cache_dtype": None, "kv_layout": "dense",
                             "max_slots": 32, "max_seq_len": 2048}
    assert any("rotary" in a for a in raw["assumed"]) and any("multi-token" in a for a in raw["assumed"])
    m = full_model()
    weights = config.load_module("references", "ssm_moe_decoder").weights
    per = {ch: weights.block_nbytes(m, ch) for ch in "M*E"}
    assert abs(per["M"] - 109.6e6) < 0.3e6 and abs(per["*"] - 35.7e6) < 0.1e6
    assert abs(per["E"] - (52.4e6 + 128 * 5.505e6)) < 2e6      # 54.5M less the float32 router
    decoder = sum(per[ch] for ch in m["hybrid_override_pattern"])
    assert abs(decoder - 8.74e9) < 0.03e9
    assert ssm_moe_roofline.state_bytes_per_slot(m) == 10 * 128 * 64 * 128 * 4 == 41_943_040
    assert ssm_moe_roofline.expert_bytes(m) == 2 * 1024 * 2688 + 4 * (2688 + 1024)
    assert 32 * ssm_moe_roofline.state_bytes_per_slot(m) == 1_342_177_280
    cfg = system.model_config(m)
    assert cfg.n_layers == 22 and cfg.rotary_dim == 0 and cfg.experts_held == 128


def rehearse_args(cell, seed, trace):
    return run.parse_args(["--workload", cell, "--seed", str(seed), "--seconds", "4",
                           "--trace", str(trace), "--rehearse"])


@pytest.mark.parametrize("cell,trace", [(CELL, 0), (CELL, 1), (OLMO, 0), (OLMO, 1)])
def test_the_new_cells_rehearse(cell, trace):
    """The harness end to end at the tiny sizes. ``correct`` is asked of the
    hybrid alone: at 4 of 32 experts a token, one held, a router score that
    bfloat16 rounds the other way swaps a token's whole routed part (gap_max
    1-2.6 over 650 positions), and the full-size limits are not the tiny
    model's (the next test holds it to its own)."""
    got = run.run_cell(rehearse_args(cell, 2**31 + 321 + trace, trace))
    assert got["failed"] == 0 and got["attempted"] >= 8
    if cell == OLMO:
        assert got["correct"] is True
    if not trace:
        assert {"out_tokens_per_s", "setup_s"} <= set(got["metrics"])
    elif cell == CELL:
        # no device trace on the CPU: the counters' readers read, the trace's four do not
        assert 0.0 < got["metrics"]["moe_expert_touch_share"]["value"] <= 100.0
        assert 1.0 <= got["metrics"]["moe_load_max_over_mean"]["value"] <= 8.0
        assert not set(TRACE_READERS) & set(got["metrics"])
        assert {"batch_occupancy", "kv_in_use_share", "ttft_p95_ms.sat"} <= set(got["metrics"])
    else:
        assert {"batch_occupancy", "kv_in_use_share"} <= set(got["metrics"])
        assert "linear_decode_ms.sat" not in got["metrics"]


# 650 served positions at the tiny widths (MEM*EME, 8 experts held of 32, 4 a token).
# Readings of gap_mean (CPU, seeds 5, 6, 7, 15, 16): sound 0.0091-0.0159, w8a8 0.032-0.050.
# Above the hybrid's 0.004-0.006 at the same widths: a router score that bfloat16 rounds
# the other way swaps one of a token's experts, and with one held expert a token that is
# its whole routed part. The limit sits 1.4 times over the sound runs' largest and 1.45
# times under the control's smallest; seed 6 reads 0.0091 against 0.049. gap_max (1.0-2.6
# either way) says nothing at this size and is held against tokens that are not the model's.
CONTROL_ASKS = [("a" * 20, 180), ("hello yardstick " * 6, 120), ("zulu9 " * 3, 200),
                ("kilo lima 7 " * 5, 150)]
CONTROL_LIMITS = {"gap_mean": 0.022, "gap_max": 8.0}
PADS = {"pad_sequence_to": 256, "pad_rows_to": 64}


def test_served_path_agrees_the_control_does_not_and_altered_tokens_do_not():
    """Prefill and decoding through the cache, served over HTTP, against the
    reference's full forward pass; the program's own w8a8 path in its place;
    then served tokens that are not the model's."""
    numbers = {}
    for mode in ("dequant", "w8a8"):
        cfg = tiny()
        cfg["engine"]["quant_mode"] = mode
        seqs, first = serve(cfg, 6, CONTROL_ASKS)
        numbers[mode], positions = check.served_numbers(6, cfg["model"], seqs, first, PADS)
        assert positions == sum(n for _, n in CONTROL_ASKS)
        if mode == "dequant":
            sound = (seqs, first)
    assert check.within(numbers["dequant"], CONTROL_LIMITS)[0], numbers
    ok, lines = check.within(numbers["w8a8"], CONTROL_LIMITS)
    assert not ok and any("gap_mean" in line and "OVER" in line for line in lines), numbers
    seqs, first = sound
    vocab = tiny()["model"]["vocab_size"]
    wrong = [s[:f] + [(t + 1) % vocab for t in s[f:]] for s, f in zip(seqs, first)]
    broken, _ = check.served_numbers(6, tiny()["model"], wrong, first, PADS)
    assert not check.within(broken, CONTROL_LIMITS)[0]
    assert not check.within(broken, check.limits_for(CONFIG))[0]


# -- the readers ---------------------------------------------------------------

def ctx(counters, trace=None, model=None):
    return run.Context(cell=None, records=[], window_s=45.0, run_end_s=47.0, setup_s=30.0,
                       counters=counters, memory={"model": model or full_model()}, trace=trace,
                       device_kind="TPU v5 lite")


COUNTERS = {   # a saturated window: 2,000 decode steps of 32 slots, all live, 10 expert layers
    "kvmini_tpu_decode_steps_total": 2000.0,
    "kvmini_tpu_linear_state_slot_steps_total": 64000.0,
    "kvmini_tpu_linear_state_held_slot_steps_total": 640.0,
    "kvmini_tpu_moe_assignments_total": 2000.0 * 31.68 * 22 * 10,
    "kvmini_tpu_moe_local_assignments_total": 2000.0 * 174.0 * 10,
    "kvmini_tpu_moe_expert_touches_total": 2000.0 * 95.0 * 10,
    "kvmini_tpu_moe_expert_load_max_total": 2000.0 * 5.2 * 10,
}


def test_counter_readers_and_nothing_without_the_counters_or_the_blocks():
    touch = config.load_module("readers", "moe_expert_touch_share")
    skew = config.load_module("readers", "moe_load_max_over_mean")
    assert touch.read(ctx(COUNTERS)) == pytest.approx(100.0 * 95.0 / 128.0)
    assert skew.read(ctx(COUNTERS)) == pytest.approx(5.2 / (174.0 / 128.0))
    dense = config.load_cell("mistral-7b.chat")["config"]["model"]
    hybrid = config.load_cell("olmo-hybrid-7b.chat")["config"]["model"]
    for reader in (touch, skew):
        assert reader.read(ctx({})) is None                          # the parent's program
        assert reader.read(ctx({"kvmini_tpu_decode_steps_total": 2000.0})) is None
        assert reader.read(ctx(COUNTERS, model=dense)) is None
        assert reader.read(ctx(COUNTERS, model=hybrid)) is None


def test_shape_rules_name_the_blocks_operations_and_nothing_else():
    m = full_model()
    state = ("jit_decode/%select_dynamic-update-slice_fusion.3 = f32[10,32,128,64,128] "
             "fusion(f32[10,32,128,64,128] %cache")
    experts = ("jit_decode/%fusion.412 = bf16[32,1024] fusion(f32[32,128] %x, s8[10,128,2688,1024] %p, "
               "s8[10,128,1024,2688]")
    router = "jit_decode/%fusion.77 = f32[32,512] fusion(bf16[32,4096] %h, f32[10,4096,512] %get-tuple-element.9"
    latent = "jit_decode/%fusion.81 = bf16[32,1024] fusion(s8[10,4096,1024] %get-tuple-element.3, s32[] %c"
    shared = "jit_decode/%fusion.90 = bf16[32,5376] fusion(s8[10,4096,5376] %get-tuple-element.5, bf16[32,4096]"
    in_proj = "jit_decode/%fusion.12 = bf16[32,18560] fusion(s8[10,4096,18560] %get-tuple-element.1, bf16[32,4096] %x"
    out_proj = "jit_decode/%fusion.13 = bf16[32,4096] fusion(s8[10,8192,4096] %get-tuple-element.2, bf16[32,8192] %y"
    attn = "jit_decode/%kvmini_dense_decode.2 = bf16[32,2,16,128] custom-call(bf16[32,2,16,128] %q, bf16[2,32,2,2048,128]"
    head = "jit_decode/%convert_subtract_fusion = f32[32,32768] fusion(bf16[32768,4096] %lm_head, bf16[32,4096] %x"
    loop = ("jit_decode/%while.13 = (s32[], bf16[32,1,4096], f32[10,32,128,64,128], s8[10,128,1024,2688], "
            "bf16[10,32,3,10240]")
    ops = (state, experts, router, latent, shared, in_proj, out_proj, attn, head, loop)
    assert [ssm_moe_roofline.touches_state(n, m) for n in ops] == [True] + [False] * 9
    assert [ssm_moe_roofline.reads_expert_stack(n, m) for n in ops] == [False, True] + [False] * 8
    assert [ssm_moe_roofline.in_expert_block(n, m) for n in ops] == [
        False, True, True, True, True, False, False, False, False, False]
    dense = config.load_cell("mistral-7b.chat")["config"]["model"]
    assert ssm_moe_roofline.sizes(dense) is None


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_read_nothing_without_a_trace_or_without_such_blocks(name):
    reader = config.load_module("readers", name)
    assert reader.read(ctx(COUNTERS)) is None
    trace = trace_reduce.reduce(trace_reduce.load_events(HEAD))
    for cell in ("mistral-7b.chat", "olmo-hybrid-7b.chat"):
        other = config.load_cell(cell)["config"]["model"]
        assert reader.read(ctx(COUNTERS, trace, model=other)) is None
    old = trace_reduce.reduce(trace_reduce.load_events(HEAD.with_name("trace_head_pr28.json.gz")))
    assert reader.read(ctx(COUNTERS, old)) is None       # the hybrid's trace: no such shapes


def test_the_readers_on_the_recorded_trace():
    """0.12 s of nemotron-3-super-ep4.decode-sat on the v5e, 32 slots live (PR 32)."""
    m = full_model()
    trace = trace_reduce.reduce(trace_reduce.load_events(HEAD))
    steps = trace["programs"]["jit_decode"]
    assert len(steps) >= 3
    step_ms = 1000.0 * sorted(steps)[len(steps) // 2]
    c = ctx(COUNTERS, trace)
    got = {name: config.load_module("readers", name).read(c) for name in TRACE_READERS}
    assert 0.25 * step_ms < got["moe_decode_ms"] < 0.8 * step_ms
    assert 0.05 * step_ms < got["ssm_decode_ms"] < 0.5 * step_ms
    assert got["moe_decode_ms"] + got["ssm_decode_ms"] < step_ms
    # 31.68 live slots' state, read and written once, over what the state's operations took
    need_ms = 2 * 31.68 * 41_943_040 / 819e9 * 1000.0
    assert got["ssm_state_hbm_share"] == pytest.approx(100.0 * need_ms / got["ssm_decode_ms"])
    assert 20.0 < got["ssm_state_hbm_share"] < 100.0
    # PR 32's head carries no counts on its spans: nothing to divide, so nothing is read
    assert got["moe_expert_hbm_share"] is None and "kvmini.emit" in trace["spans"]
    # the slice's retired steps touched 95 experts a layer, ten layers; 1,740 landed
    # assignments' activations a step are the window's
    n = len(steps)
    counted = {**trace, "spans": {"kvmini.emit": {"count": n, "sums": {
        "moe_steps": n, "moe_touched": 950 * n}}}}
    stack_ms = 1000.0 * ssm_moe_roofline.decode_seconds_and_steps(
        trace, m, ssm_moe_roofline.reads_expert_stack)[0] / n
    need_ms = (950 * ssm_moe_roofline.expert_bytes(m) + 1740 * 4096) / 819e9 * 1000.0
    share = config.load_module("readers", "moe_expert_hbm_share").read(ctx(COUNTERS, counted))
    assert share == pytest.approx(100.0 * need_ms / stack_ms)
    assert share == conv_moe_roofline.expert_hbm_share(ctx(COUNTERS, counted), ssm_moe_roofline)
    assert 30.0 < share < 100.0 and stack_ms <= got["moe_decode_ms"]
    # every held expert touched: still under 100, because that program multiplied them all
    every = {**trace, "spans": {"kvmini.emit": {"count": n, "sums": {
        "moe_steps": n, "moe_touched": 128 * 10 * n}}}}
    assert run.collect([{"name": "moe_expert_hbm_share", "unit": "%"}], ctx(COUNTERS, every))
    assert run.collect([{"name": "ssm_state_hbm_share", "unit": "%"}], ctx(
        {**COUNTERS, "kvmini_tpu_linear_state_held_slot_steps_total": 0.0}, trace))
    # the rules name leaf operations only, and the state's are exactly its own
    named = [n for n in trace["ops"] if n.startswith("jit_decode/")
             and ssm_moe_roofline.touches_state(n, m)]
    assert named and all("128,64,128]" in n and "%while" not in n for n in named)
    stacks = [n for n in trace["ops"] if ssm_moe_roofline.reads_expert_stack(n, m)]
    assert stacks and all(ssm_moe_roofline.in_expert_block(n, m) for n in stacks)
